"""jit'd public wrapper for the CompBin decode kernel."""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.kernels.compbin_decode.kernel import compbin_decode_planar
from repro.kernels.compbin_decode.ref import compbin_decode_ref
from repro.kernels.utils import ceil_div, interpret_default

# Streaming granularity: partitions are padded (host-side, before the H2D
# copy) to a multiple of this many IDs so the decode jit-cache holds a few
# bucket shapes instead of one trace per partition, and every transfer in a
# double-buffered stream has one of a few fixed sizes.
STREAM_GRANULE_IDS = 1 << 15


def stream_bucket_ids(n: int, granule: int = STREAM_GRANULE_IDS) -> int:
    """Bucketed ID count for a partition of ``n`` IDs.

    Rounds up keeping 4 significant bits (quantum 2^(bits-4)), floored at
    1024: at most ~6% padding for any partition, O(16 log n) distinct
    shapes total so the decode jit-cache stays small.  ``granule`` caps the
    quantum so very large partitions stay aligned to a fixed multiple
    (uniform transfer sizes for the double buffers)."""
    if n <= 1024:
        return 1024
    q = min(1 << max(10, n.bit_length() - 4), granule)
    return ceil_div(n, q) * q


def pad_packed_for_stream(raw: np.ndarray, b: int, *,
                          granule: int = STREAM_GRANULE_IDS
                          ) -> tuple[np.ndarray, int]:
    """Zero-pad a packed uint8 stream up to a :func:`stream_bucket_ids`
    bucket.

    Returns (padded bytes, n_valid_ids).  The caller decodes the whole
    bucket on device and slices ``[:n_valid_ids]`` — padding decodes to
    vertex 0 and is dropped before anything consumes it.
    """
    raw = np.ascontiguousarray(raw, dtype=np.uint8)
    if raw.size % b:
        raise ValueError(f"packed length {raw.size} not a multiple of b={b}")
    n = raw.size // b
    n_pad = stream_bucket_ids(n, granule)
    if n_pad != n:
        raw = np.pad(raw, (0, (n_pad - n) * b))
    return raw, n


@functools.partial(jax.jit, static_argnames=("b", "n", "block_rows", "interpret"))
def _decode_impl(packed: jnp.ndarray, b: int, n: int, block_rows: int,
                 interpret: bool) -> jnp.ndarray:
    # Stage to the planar layout the kernel wants: (rows, 128*b) where
    # plane i holds byte i of each ID.  rows = padded_n / 128.
    lanes = 128
    rows = ceil_div(n, lanes)
    rows_p = ceil_div(rows, block_rows) * block_rows
    n_pad = rows_p * lanes
    flat = packed.reshape(-1)
    flat = jnp.pad(flat, (0, n_pad * b - flat.shape[0]))
    # (n_pad, b) -> (rows, lanes, b) -> (rows, b, lanes) -> (rows, b*lanes)
    planar = (
        flat.reshape(rows_p, lanes, b).transpose(0, 2, 1).reshape(rows_p, b * lanes)
    )
    out = compbin_decode_planar(planar, b=b, block_rows=block_rows,
                                interpret=interpret)
    return out.reshape(-1)[:n]


@functools.partial(jax.jit, static_argnames=("b", "block_rows", "interpret",
                                             "mesh", "axis"))
def _decode_sharded(packed: jnp.ndarray, b: int, block_rows: int,
                    interpret: bool, mesh: Mesh, axis) -> jnp.ndarray:
    """:func:`_decode_impl` on every device of ``axis`` over its own
    contiguous slice of the stream.  Mosaic kernels cannot be partitioned
    automatically, so the split is explicit: each slice holds whole IDs
    and decodes to the matching slice of the output."""
    def local(part):
        return _decode_impl(part, b, part.size // b, block_rows, interpret)

    return jax.shard_map(local, mesh=mesh, in_specs=P(axis),
                         out_specs=P(axis), check_vma=False)(packed)


def _split_axis(packed) -> tuple[Mesh, object, int] | None:
    """(mesh, axis, devices) when ``packed`` is a concrete array split
    over more than one device along its first dimension, else None."""
    sharding = getattr(packed, "sharding", None)   # tracers carry none
    if not isinstance(sharding, NamedSharding) or not sharding.spec:
        return None
    axis = sharding.spec[0]
    if axis is None:
        return None
    names = axis if isinstance(axis, tuple) else (axis,)
    d = math.prod(sharding.mesh.shape[a] for a in names)
    return (sharding.mesh, axis, d) if d > 1 else None


def decode_packed_stream(raw: np.ndarray, b: int, *,
                         block_rows: int = 256,
                         interpret: bool | None = None,
                         use_kernel: bool = True
                         ) -> tuple[np.ndarray, int]:
    """One-transfer device decode of a host-side packed byte stream.

    The serving path's building block: ``raw`` (uint8, ``n*b`` bytes —
    e.g. a micro-batch's merged packed-byte runs concatenated) is padded
    to a :func:`stream_bucket_ids` bucket, shipped with ONE explicit
    ``jax.device_put``, decoded by the Pallas kernel, and returned as
    int64 IDs on host, bit-identical to
    :func:`repro.core.compbin.decode_ids`.  Returns ``(ids, bytes_h2d)``
    where ``bytes_h2d`` is the padded transfer size (what actually
    crossed the link), so callers can account H2D traffic exactly.
    """
    raw = np.ascontiguousarray(raw, dtype=np.uint8).reshape(-1)
    if raw.size == 0:
        return np.zeros(0, dtype=np.int64), 0
    padded, n = pad_packed_for_stream(raw, b)
    dev = jax.device_put(padded)                # the batch's single H2D
    out = compbin_decode(dev, b, block_rows=block_rows,
                         interpret=interpret, use_kernel=use_kernel)
    return np.asarray(out[:n]).astype(np.int64), padded.size


def compbin_decode(packed: jnp.ndarray, b: int, *, block_rows: int = 256,
                   interpret: bool | None = None,
                   use_kernel: bool = True) -> jnp.ndarray:
    """Decode CompBin-packed vertex IDs on device.

    packed: uint8[n*b] (or any shape with n*b elements, little-endian bytes
    per ID in memory order).  Returns int32[n].

    b in [5,8] (graphs with |V| >= 2^32) is accepted for IDs that still fit
    int32 — the int32-lane ceiling every on-device consumer has anyway;
    the zero high bytes are stripped before the kernel so only 4 byte
    planes cross into VMEM.  IDs >= 2^31 must take the host decode path
    (core.policy.choose_stream_decode routes them there).

    A stream split over several devices along a mesh axis (the streaming
    loader's ``P("data")`` placement) decodes on each device over its own
    slice, and the output is split the same way.
    """
    if not 1 <= b <= 8:
        raise ValueError(f"b must be in [1,8] for device decode, got {b}")
    n = packed.size // b
    split = _split_axis(packed)
    if split is not None and n % split[2]:
        raise ValueError(f"{n} IDs do not split evenly over {split[2]} "
                         f"devices of mesh axis {split[1]!r}")
    if b > 4:
        packed = jnp.asarray(packed).reshape(n, b)
        try:
            has_high = bool((packed[:, 4:] != 0).any())
        except jax.errors.ConcretizationTypeError as e:
            raise ValueError(
                "b>4 device decode validates the high ID bytes and so needs "
                "a concrete (non-traced) input; decode b<=4 inside jit") from e
        if has_high:
            raise ValueError(
                f"b={b} packed stream holds IDs >= 2^32; they cannot decode "
                "to int32 lanes — use the host decode path "
                "(core.policy.choose_stream_decode routes this)")
        packed = packed[:, :4]
        b = 4
    if not use_kernel:
        return compbin_decode_ref(packed.reshape(-1), b)
    if interpret is None:
        interpret = interpret_default()
    if split is not None:
        mesh, axis, _ = split
        return _decode_sharded(packed.reshape(-1), b, block_rows, interpret,
                               mesh, axis)
    return _decode_impl(packed.reshape(-1), b, n, block_rows, interpret)


#: codec name -> device stream decoder ``(raw_u8, b) -> (int64 ids,
#: bytes_h2d)``.  This is the op-surface registry the query engine and
#: streaming loader resolve through (repro.core.codec declares WHICH
#: codecs are direct; this maps each to its device decode).  LogCSR
#: byte-packs neighbors exactly like CompBin, so one kernel serves both;
#: a codec with a different packed layout registers its own entry.
PACKED_STREAM_DECODERS = {
    "compbin": decode_packed_stream,
    "logcsr": decode_packed_stream,
}


def packed_stream_decoder(codec_name: str):
    """The device stream decoder registered for ``codec_name``."""
    try:
        return PACKED_STREAM_DECODERS[codec_name]
    except KeyError:
        raise ValueError(
            f"no device stream decoder registered for codec "
            f"{codec_name!r}; registered: "
            f"{', '.join(sorted(PACKED_STREAM_DECODERS))}") from None
