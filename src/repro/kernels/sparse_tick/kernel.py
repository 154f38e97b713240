"""Pallas kernel for the sparse event tick's arbiter policies.

One `pallas_call` reads the compacted event buffer and computes both
per-core quantities the arbiter owes the tick: the scheme's
``tick_latency`` and its AER encode energy.  The policies are passed in
as traceable callables (`ArbiterScheme.sparse_tick_latency` /
``sparse_encode_energy`` factories, resolved per session), so new
arbiter schemes reach the kernel through the registry without editing
it.

TPU lowering: every operand and every value in the body is 2-D - the
buffer (cores, capacity + 1), the counts and both outputs (cores, 1) -
because Mosaic refuses 1-D vectors.  The grid runs over blocks of
`BLOCK_CORES` cores (the policies are row-independent), so the working
set per program stays a few vregs whatever the core count.  The CAM
gather and the weighted scatter-add stay in XLA
(`repro.interface.pipeline.route_currents`): the TPU lowering has no
vector gather over a whole frame and no scatter-add, and the XLA
expression is the one every other path uses, which keeps the currents
bit-identical by construction.

Off TPU the kernel runs in interpret mode (`repro.kernels.cam_search`
precedent): the body traces to the same jnp ops as
`repro.kernels.sparse_tick.ref`, so CPU hosts exercise the kernel path
bit-for-bit.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# Cores per grid program: one sublane tile of int32.
BLOCK_CORES = 8


def _policy_kernel(latency_fn, encode_fn):
    """Bind the resolved arbiter policies into the kernel body."""

    def kernel(buf_ref, counts_ref, lat_ref, enc_ref):
        buf = buf_ref[...]
        counts = counts_ref[...]
        lat_ref[...] = latency_fn(buf, counts)
        enc_ref[...] = encode_fn(buf, counts)

    return kernel


def sparse_tick_pallas(buf, counts, *, latency_fn, encode_fn,
                       interpret: bool = False):
    """Run the sparse arbiter policies as one `pallas_call`.

    Same contract as `repro.kernels.sparse_tick.ref.sparse_tick_ref`;
    ``interpret=True`` executes the kernel body as plain XLA ops off-TPU.
    """
    cores, width = buf.shape
    rows = BLOCK_CORES if cores % BLOCK_CORES == 0 else cores

    def block(cols):
        return pl.BlockSpec((rows, cols), lambda i: (i, 0))

    col = jax.ShapeDtypeStruct((cores, 1), jnp.float32)
    lat, enc = pl.pallas_call(
        _policy_kernel(latency_fn, encode_fn),
        grid=(cores // rows,),
        in_specs=[block(width), block(1)],
        out_specs=[block(1), block(1)],
        out_shape=[col, col],
        interpret=interpret,
    )(buf, counts[:, None])
    return lat[:, 0], enc[:, 0]
