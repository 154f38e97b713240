"""The main-path kernels compile for a TPU v5e, at the deployments' widths.

No chip is needed: the TPU compiler is installed, and it compiles for a
``v5e:2x2`` topology that is described, not attached.  Each test lowers
one kernel with ``interpret=False`` onto one described chip and asserts
the compiled program holds a Mosaic kernel (``tpu_custom_call``) - what
interpret mode off-TPU cannot show: Mosaic refuses layouts, 1-D values
and primitives the interpreter runs happily.

Widths are those of `repro.configs.paper_dynaps.scaled_config` (16 cores
x 256 neurons x 512 CAM entries, HAT arbiter) and its SNN (4096 neurons).
The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, and every test worker
imports this file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.interface import InterfaceConfig, pipeline
from repro.kernels.cam_search import ops as cam_ops
from repro.kernels.hat_encode import ops as hat_ops
from repro.kernels.lif_step import ops as lif_ops
from repro.kernels.sparse_tick import ops as sparse_ops

CORES, NEURONS, ENTRIES = 16, 256, 512


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache, so keep these compiles out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def test_cam_match_counts_compiles(one_chip):
    total = CORES * NEURONS
    words = cam_ops.pack_bits(jnp.zeros((1, 12), jnp.int32)).shape[1]

    def match(q, src, spikes):
        return cam_ops.cam_match_counts(q, src, spikes, impl="pallas",
                                        interpret=False)

    text = _compiled_text(match, one_chip,
                          ((CORES * ENTRIES, words), jnp.int32),
                          ((total, words), jnp.int32),
                          ((total,), jnp.bool_))
    assert "tpu_custom_call" in text


def test_hat_encode_stream_compiles(one_chip):
    def encode(spikes):
        return hat_ops.encode_stream(spikes, row=256, impl="pallas",
                                     interpret=False)

    text = _compiled_text(encode, one_chip, ((NEURONS,), jnp.bool_))
    assert "tpu_custom_call" in text


def test_lif_step_compiles(one_chip):
    def step(v, current):
        return lif_ops.lif_step(v, current, decay=0.9, threshold=1.0,
                                impl="pallas", interpret=False)

    shape = ((8, CORES * NEURONS), jnp.float32)
    assert "tpu_custom_call" in _compiled_text(step, one_chip, shape, shape)


@pytest.mark.parametrize("lanes", [None, 8])
def test_sparse_tick_compiles(one_chip, lanes):
    # the HAT policies at the scaled deployment; lanes=8 is the vmapped
    # form `run_batched` and the serving engine compile
    cfg = InterfaceConfig(cores=CORES, neurons_per_core=NEURONS,
                          cam_entries_per_core=ENTRIES, scheme="hier_tree")
    latency_fn, encode_fn, _, capacity = pipeline.resolve_sparse_plan(cfg)

    def tick(buf, counts):
        return sparse_ops.sparse_tick(buf, counts, latency_fn=latency_fn,
                                      encode_fn=encode_fn, impl="pallas",
                                      interpret=False)

    lead = () if lanes is None else (lanes,)
    if lanes is not None:
        tick = jax.vmap(tick)
    text = _compiled_text(tick, one_chip,
                          (lead + (CORES, capacity + 1), jnp.int32),
                          (lead + (CORES,), jnp.int32))
    assert "tpu_custom_call" in text
