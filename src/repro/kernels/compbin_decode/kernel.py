"""Pallas TPU kernel: CompBin vertex-ID decode (paper §IV, eq. (1)).

TPU rethink of the paper's CPU decoder (DESIGN.md §2): instead of decoding
on the host and shipping 4-byte IDs over PCIe/DMA, the *packed* b-byte
stream is DMA'd into HBM and unpacked to int32 in VMEM right before the
consuming gather — the (4-b)/4 bandwidth saving applies to every level of
the memory hierarchy, not just storage.

Tiling: the flat packed stream is viewed as ``(n, b)`` uint8 and blocked
``(block_n, b)`` into VMEM; each grid step widens the bytes on the 8x128
VPU lanes and reduces with ``b-1`` shift-adds — eq. (1) verbatim.  The
trailing (lane) dimension of the *output* tile is kept at a multiple of 128
by emitting ``(block_rows, 128)`` tiles; ops.py reshapes the flat stream
accordingly so the kernel sees hardware-aligned shapes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _decode_kernel(packed_ref, out_ref, *, b: int):
    """packed_ref: uint8[rows, 128*b]  ->  out_ref: int32[rows, 128].

    Bytes are laid out little-endian per ID along the lane axis:
    packed[r, 128*i + l] is byte i of the ID in (r, l) — a *planar* layout
    chosen so each byte plane is a contiguous, lane-aligned (rows, 128)
    tile (an interleaved layout would need 8-bit lane shuffles, which the
    VPU does not do natively; ops.py performs the one-time transpose when
    staging the stream to the device).
    """
    acc = jnp.zeros(out_ref.shape, jnp.int32)
    for i in range(b):  # eq. (1): a few shifts and adds
        plane = packed_ref[:, 128 * i : 128 * (i + 1)].astype(jnp.int32)
        acc = acc | (plane << (8 * i))
    out_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("b", "block_rows", "interpret"))
def compbin_decode_planar(planar: jnp.ndarray, *, b: int, block_rows: int = 256,
                          interpret: bool) -> jnp.ndarray:
    """Decode a planar-packed stream uint8[rows, 128*b] -> int32[rows, 128].

    ``rows`` must be a multiple of ``block_rows`` (ops.py pads).
    """
    rows = planar.shape[0]
    assert rows % block_rows == 0, (rows, block_rows)
    grid = (rows // block_rows,)
    return pl.pallas_call(
        functools.partial(_decode_kernel, b=b),
        grid=grid,
        in_specs=[pl.BlockSpec((block_rows, 128 * b), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block_rows, 128), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, 128), jnp.int32),
        interpret=interpret,
    )(planar)
