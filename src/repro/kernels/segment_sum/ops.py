"""jit'd public wrapper for the blocked segment-sum kernel."""

from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.segment_sum.kernel import segment_sum_blocked
from repro.kernels.segment_sum.ref import segment_sum_ref
from repro.kernels.utils import interpret_default, pad_to_multiple

# Above this the (N, block_d) output tile no longer fits VMEM comfortably;
# fall back to XLA's sorted scatter (jax.ops.segment_sum).
MAX_KERNEL_SEGMENTS = 8192

#: the lane width: the ids block is (1, block_e), so block_e is a multiple
ID_LANES = 128


def edge_block(n_edges: int, block_e: int = 512) -> int:
    """Edge-block size for ``n_edges`` messages: the next power of two of
    ``n_edges``, clamped to [128, ``block_e``] (the kernel's ids block is
    lane-major, so every size is a multiple of 128)."""
    if block_e % ID_LANES:
        raise ValueError(f"block_e must be a multiple of {ID_LANES}, "
                         f"got {block_e}")
    return min(block_e, max(ID_LANES, 1 << max(0, n_edges - 1).bit_length()))


def segment_sum(messages: jnp.ndarray, segment_ids: jnp.ndarray,
                num_segments: int, *, block_e: int = 512, block_d: int = 128,
                interpret: bool | None = None,
                use_kernel: bool = True) -> jnp.ndarray:
    """Segment-sum messages[E, D] by segment_ids[E] into [num_segments, D].

    Padding edges may carry ``segment_ids == -1`` (dropped).  Kernel path is
    used for ``num_segments <= MAX_KERNEL_SEGMENTS``; otherwise XLA scatter.
    """
    if messages.ndim != 2 or segment_ids.ndim != 1:
        raise ValueError("messages must be [E, D], segment_ids [E]")
    if messages.shape[0] != segment_ids.shape[0]:
        raise ValueError("E mismatch between messages and segment_ids")
    if not use_kernel or num_segments > MAX_KERNEL_SEGMENTS:
        return segment_sum_ref(messages, segment_ids, num_segments)
    if interpret is None:
        interpret = interpret_default()
    E, D = messages.shape
    block_e = edge_block(E, block_e)
    block_d = min(block_d, max(128, D))
    msgs = pad_to_multiple(messages.astype(jnp.float32), block_e, axis=0)
    msgs = pad_to_multiple(msgs, block_d, axis=1)
    ids = pad_to_multiple(segment_ids.astype(jnp.int32), block_e, axis=0,
                          value=-1).reshape(1, -1)
    out = segment_sum_blocked(
        msgs, ids, num_segments=num_segments, block_e=block_e,
        block_d=min(block_d, msgs.shape[1]), interpret=interpret,
    )
    return out[:, :D]
