"""Pallas TPU kernel: blocked causal GQA attention (FlashAttention regime).

IO-aware attention [FlashAttention, arXiv:2205.14135] adapted to the TPU
memory hierarchy: Q/K/V tiles are staged HBM->VMEM by BlockSpecs, scores
(block_q, block_k) live only in VMEM/VREGs, and the online-softmax running
state (m, l, acc) sits in VMEM scratch that persists across the
sequentially-executed KV grid dimension.  MXU dims: block_q/block_k are
multiples of 128 and Dh is 64/128 on all assigned archs.

GQA is folded into the index maps: query head h reads KV head
``h // (Hq // Hkv)`` — no repeat/materialization of K/V.

Grid: (B * Hq, Sq / block_q, Skv / block_k), KV minormost (sequential
accumulation).  Causal masking compares global q/k positions with the
decode convention (last query row attends to the whole KV prefix), so the
same kernel serves training (Sq == Skv) and chunked prefill (Sq < Skv).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, causal: bool, block_q: int, block_k: int,
                  offset: int, kv_len: int):
    """``offset`` = unpadded (Skv - Sq): query row i attends to KV positions
    <= i + offset (decode convention).  ``kv_len`` = unpadded Skv, masking
    the KV padding columns for every query."""
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)  # [block_q, Dh]
    k = k_ref[0].astype(jnp.float32)  # [block_k, Dh]
    v = v_ref[0].astype(jnp.float32)  # [block_k, Dh]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    kpos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    mask = kpos < kv_len
    if causal:
        qpos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0) + offset
        mask &= kpos <= qpos
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                      # [block_q, 1]
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)                   # [block_q, block_k]
    alpha = jnp.exp(m_prev - m_new)          # rescale of the old state
    l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        # rows with an empty receptive field (fully masked) produce l == 0
        l = l_ref[...]
        o_ref[0] = jnp.where(l > 0, acc_ref[...] / l, 0.0).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "scale", "block_q", "block_k", "offset", "kv_len", "interpret"))
def flash_attention_blocked(q, k, v, *, causal: bool, scale: float,
                            block_q: int, block_k: int, offset: int,
                            kv_len: int, interpret: bool):
    """q: [BHq, Sq, Dh]; k, v: [BHkv, Skv, Dh] with BHq = B*Hq flattened and
    the GQA group size inferred as BHq // BHkv.  Shapes pre-padded;
    ``offset``/``kv_len`` carry the unpadded alignment (see _flash_kernel)."""
    BHq, Sq, Dh = q.shape
    BHkv, Skv, _ = k.shape
    assert BHq % BHkv == 0
    group = BHq // BHkv
    assert Sq % block_q == 0 and Skv % block_k == 0
    grid = (BHq, Sq // block_q, Skv // block_k)
    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, offset=offset, kv_len=kv_len)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, Dh), lambda h, qi, ki: (h, qi, 0)),
            pl.BlockSpec((1, block_k, Dh), lambda h, qi, ki: (h // group, ki, 0)),
            pl.BlockSpec((1, block_k, Dh), lambda h, qi, ki: (h // group, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, Dh), lambda h, qi, ki: (h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),    # m: running max
            pltpu.VMEM((block_q, 1), jnp.float32),    # l: running denom
            pltpu.VMEM((block_q, Dh), jnp.float32),   # acc: running numerator
        ],
        interpret=interpret,
    )(q, k, v)
