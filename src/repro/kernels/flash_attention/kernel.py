"""Flash attention Pallas TPU kernel (causal / sliding-window / GQA).

Tiling: grid = (batch × q_heads, Sq/block_q, Sk/block_k); the KV axis is the
innermost (sequential on TPU) grid dimension, so the f32 accumulator and the
online-softmax (m, l) statistics live in VMEM scratch across KV steps.
GQA is handled in the BlockSpec index maps — the KV block for q-head h is
loaded from kv-head h // (H/Hkv); KV tensors are never repeated in HBM.

MXU alignment: block_q/block_k default to 128; head_dim is padded to a
multiple of 128 by the ops wrapper.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float("-inf")


def _fa_kernel(
    q_ref,  # (1, block_q, d)
    k_ref,  # (1, block_k, d)
    v_ref,  # (1, block_k, d)
    o_ref,  # (1, block_q, d)
    acc_ref,  # VMEM (block_q, d) f32
    m_ref,  # VMEM (block_q,) f32
    l_ref,  # VMEM (block_q,) f32
    *,
    causal: bool,
    window: Optional[int],
    q_offset: int,
    block_q: int,
    block_k: int,
    sk_valid: int,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32)
    k = k_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (block_q, block_k)

    q_pos = q_offset + qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    mask = k_pos < sk_valid
    if causal:
        mask &= k_pos <= q_pos
    if window is not None:
        mask &= k_pos > (q_pos - window)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    safe_m = jnp.where(m_new == NEG_INF, 0.0, m_new)
    p = jnp.exp(s - safe_m[:, None])
    corr = jnp.exp(m_prev - safe_m)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=-1)
    # the TPU's matmul accumulates bf16 operands in f32 only when asked to
    acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot(
        p.astype(v_ref.dtype), v_ref[0], preferred_element_type=jnp.float32
    )
    m_ref[...] = m_new

    @pl.when(ki == pl.num_programs(2) - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...][:, None], 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "q_offset", "block_q", "block_k", "num_kv_heads", "interpret", "sk_valid"),
)
def flash_attention_bhsd(
    q,  # (B*H, Sq, D)
    k,  # (B*Hkv, Sk, D)
    v,  # (B*Hkv, Sk, D)
    *,
    num_kv_heads: int,
    causal: bool,
    window: Optional[int],
    q_offset: int,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
    sk_valid: Optional[int] = None,
):
    bh, sq, d = q.shape
    bkv, sk, _ = k.shape
    sk_valid = sk if sk_valid is None else sk_valid
    h_per_b = bh // (bkv // num_kv_heads)  # q heads per batch
    group = h_per_b // num_kv_heads
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    grid = (bh, pl.cdiv(sq, block_q), pl.cdiv(sk, block_k))

    def q_index(bhi, qi, ki):
        return (bhi, qi, 0)

    def kv_index(bhi, qi, ki):
        b = bhi // h_per_b
        h = bhi % h_per_b
        return (b * num_kv_heads + h // group, ki, 0)

    kern = functools.partial(
        _fa_kernel,
        causal=causal,
        window=window,
        q_offset=q_offset,
        block_q=block_q,
        block_k=block_k,
        sk_valid=sk_valid,
    )
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), q_index),
            pl.BlockSpec((1, block_k, d), kv_index),
            pl.BlockSpec((1, block_k, d), kv_index),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), q_index),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
