"""Pallas TPU kernel: blocked segment-sum (GNN message aggregation).

The GNN SpMM regime (kernel taxonomy §GNN): messages laid out per edge,
reduced by destination vertex.  GPUs do this with atomics-based
scatter-add; the TPU has no HBM atomics, so the TPU-idiomatic realization
is a **one-hot matmul on the MXU**: an (N, block_e) one-hot of the segment
ids left-multiplied into the (block_e, block_d) message tile yields the
(N, block_d) partial sums.  The grid walks edge blocks in the minormost
dimension; because the TPU grid executes *sequentially*, the output tile
(N, block_d) can be revisited and accumulated in VMEM across edge blocks —
a reduction pattern with zero inter-step collectives.

The ids travel lane-major as a ``(1, E)`` row blocked ``(1, block_e)``:
a rank-1 int32 block must match XLA's 1-D tiling (``T(1024)``), which a
power-of-two ``block_e`` below 1024 does not, while a ``(1, block_e)``
block only needs ``block_e % 128 == 0`` (ops.py picks at least 128).

FLOP overhead vs. a scatter: factor N/1 per message, but they are MXU FLOPs
at ~100x the VPU scatter throughput and the edge tile is read exactly once
from HBM — for N up to a few thousand (molecule/minibatch regimes) the
one-hot matmul wins.  ops.py falls back to ``jax.ops.segment_sum`` (XLA's
sorted-scatter) above ``max_kernel_segments``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _segsum_kernel(ids_ref, msg_ref, out_ref, *, num_segments: int):
    e_idx = pl.program_id(1)

    @pl.when(e_idx == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    ids = ids_ref[...]  # int32[1, block_e]
    msgs = msg_ref[...]  # f32[block_e, block_d]
    seg = jax.lax.broadcasted_iota(jnp.int32, (num_segments, ids.shape[1]), 0)
    onehot = (seg == ids).astype(msgs.dtype)  # [N, block_e]
    # (N, block_e) @ (block_e, block_d) on the MXU, f32 accumulation
    out_ref[...] += jnp.dot(onehot, msgs, preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("num_segments", "block_e",
                                             "block_d", "interpret"))
def segment_sum_blocked(messages: jnp.ndarray, segment_ids: jnp.ndarray,
                        *, num_segments: int, block_e: int, block_d: int,
                        interpret: bool) -> jnp.ndarray:
    """messages: f32[E, D], segment_ids: int32[1, E] (E % block_e == 0,
    block_e % 128 == 0, D % block_d == 0, padded by ops.py with
    segment_ids == -1 on padding); returns f32[N, D]."""
    E, D = messages.shape
    assert segment_ids.shape == (1, E), (segment_ids.shape, E)
    assert E % block_e == 0 and block_e % 128 == 0 and D % block_d == 0, \
        (E, D, block_e, block_d)
    grid = (D // block_d, E // block_e)  # edge blocks minormost => sequential accum
    return pl.pallas_call(
        functools.partial(_segsum_kernel, num_segments=num_segments),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_e), lambda d, e: (0, e)),
            pl.BlockSpec((block_e, block_d), lambda d, e: (e, d)),
        ],
        out_specs=pl.BlockSpec((num_segments, block_d), lambda d, e: (0, d)),
        out_shape=jax.ShapeDtypeStruct((num_segments, D), jnp.float32),
        interpret=interpret,
    )(segment_ids, messages)
