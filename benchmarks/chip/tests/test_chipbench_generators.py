"""The benchmark's copies of the program's generators are faithful.

Each copy under ``generators/`` and the benchmark's wiring have to
reproduce the program's own (`repro.traffic`, `repro.interface.
random_connectivity`) bit for bit on the same key.
"""

import jax
import numpy as np
import pytest

from chip import data, generators
from repro import traffic
from repro.interface import InterfaceConfig, random_connectivity

CASES = [
    ("sparse_poisson", {}),
    ("sparse_poisson", {"rate": 0.02}),
    ("synchronized_burst", {}),
    ("synchronized_burst", {"period": 4, "duty": 1, "burst_rate": 0.9,
                            "background": 0.005}),
    ("synchronized_burst", {"period": 3, "duty": 2}),
    ("synchronized_burst", {"period": 8, "duty": 3, "burst_rate": 0.5}),
    ("sparse_poisson", {"rate": 0.2}),
]


@pytest.mark.parametrize("name,params", CASES)
@pytest.mark.parametrize("shape", [(24, 4, 256), (9, 3, 64)])
def test_copy_reproduces_the_program_generator(name, params, shape):
    key = data.seed_key(2**33 + 17, 1)
    ticks, cores, n = shape
    ours = generators.load(name)(key, ticks, cores, n, **params)
    theirs = traffic.generate(name, key, ticks, (cores, n), **params)
    assert ours.dtype == theirs.dtype == np.bool_
    np.testing.assert_array_equal(np.asarray(ours), np.asarray(theirs))


def test_batched_rasters_are_the_generator_per_key():
    fab = {"cores": 2, "neurons_per_core": 64}
    keys = jax.random.split(data.seed_key(5, 1), 3)
    batch = data.raster_fn("sparse_poisson", {"rate": 0.1}, 8, fab)(keys)
    for i, key in enumerate(keys):
        np.testing.assert_array_equal(
            np.asarray(batch[i]),
            np.asarray(traffic.generate("sparse_poisson", key, 8, (2, 64),
                                        rate=0.1)))


@pytest.mark.parametrize("chips,cores", [(1, 4), (2, 4)])
def test_wiring_reproduces_random_connectivity(chips, cores):
    config = {"fabric": {"chips": chips, "cores": cores,
                         "neurons_per_core": 64, "cam_entries_per_core": 64},
              "assumed": {"fan_in": 0.9}}
    key = data.seed_key(3, 0)
    (tags, valid, weights, targets), host = data.connectivity(key, config)
    cfg = InterfaceConfig(chips=chips, cores=cores, neurons_per_core=64,
                          cam_entries_per_core=64)
    theirs = random_connectivity(key, cfg, fan_in=0.9)
    for ours, ref in zip((tags, valid, targets),
                         (theirs.tags, theirs.valid, theirs.targets)):
        np.testing.assert_array_equal(np.asarray(ours), np.asarray(ref))
    # one jitted call fuses N(0, 1) * 0.5 + 1.0, which may round the last
    # bit differently from the eager ops
    np.testing.assert_allclose(np.asarray(weights),
                               np.asarray(theirs.weights), rtol=0, atol=2.5e-7)
    bits = data.tag_bits(config["fabric"])
    decoded = np.asarray(tags) @ (1 << np.arange(bits - 1, -1, -1))
    np.testing.assert_array_equal(decoded, host["src"])


def test_seeds_past_32_bits_give_distinct_keys():
    keys = {tuple(np.asarray(jax.random.key_data(data.seed_key(s, 0)))
                  if hasattr(jax.random, "key_data") else
                  np.asarray(data.seed_key(s, 0)))
            for s in (1, 2**32 + 1, 2**33 + 1, 2**31 + 5)}
    assert len(keys) == 4
    with pytest.raises(ValueError):
        data.seed_key(-1, 0)
