"""Shared helpers for the Pallas TPU kernels."""

from __future__ import annotations

import jax.numpy as jnp


def ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def pad_to_multiple(x, multiple: int, axis: int, value=0):
    """Pad ``x`` along ``axis`` up to the next multiple of ``multiple``."""
    n = x.shape[axis]
    target = ceil_div(n, multiple) * multiple
    if target == n:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, target - n)
    return jnp.pad(x, pads, constant_values=value)


def interpret_default() -> bool:
    """Whether Pallas kernels run in interpret mode on this backend.

    ``tpu`` compiles every kernel with Mosaic (False).  ``cpu`` has no
    Mosaic compiler, so kernels run their bodies in interpret mode there
    (True) — the tests and CPU rehearsals.  Any other backend has no
    path for these kernels and raises rather than silently interpreting.
    """
    import jax

    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(f"Pallas kernels compile for 'tpu' and interpret on "
                       f"'cpu'; backend {backend!r} has neither path")
