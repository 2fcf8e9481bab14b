"""Public flash-attention wrapper: layout handling + GQA + custom VJP.

Forward runs the Pallas kernel; the backward pass recomputes attention with
the chunked-jnp algorithm (flash-style recompute — no S×S residuals), which
is the standard memory-saving backward on TPU.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import flags
from repro.kernels.flash_attention import kernel as _k
from repro.kernels.flash_attention import ref as _ref


def _pad_head_dim(x, mult: int = 128):
    d = x.shape[-1]
    pad = (-d) % mult
    if pad:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, 0), (0, pad)))
    return x, d


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention(q, k, v, causal: bool = True, window: Optional[int] = None, q_offset: int = 0):
    """q: (B,Sq,H,D), k/v: (B,Sk,Hkv,D) -> (B,Sq,H,D). Pre-scaled q expected."""
    return _forward(q, k, v, causal, window, q_offset)


def _forward(q, k, v, causal, window, q_offset, block: int = 128):
    b, sq, h, d0 = q.shape
    _, sk, hkv, _ = k.shape
    qp, d = _pad_head_dim(q)
    kp, _ = _pad_head_dim(k)
    vp, _ = _pad_head_dim(v)
    # zero-pad the sequence dims to block multiples: Pallas out-of-bounds
    # block reads are undefined, and even fully-masked scores can't protect
    # against NaN garbage in V (0·NaN = NaN)
    pq = (-sq) % min(block, sq) if sq > 1 else 0
    pk = (-sk) % min(block, sk)
    if pq:
        qp = jnp.pad(qp, ((0, 0), (0, pq), (0, 0), (0, 0)))
    if pk:
        kp = jnp.pad(kp, ((0, 0), (0, pk), (0, 0), (0, 0)))
        vp = jnp.pad(vp, ((0, 0), (0, pk), (0, 0), (0, 0)))
    dpad = qp.shape[-1]
    sqp, skp = qp.shape[1], kp.shape[1]
    qf = jnp.transpose(qp, (0, 2, 1, 3)).reshape(b * h, sqp, dpad)
    kf = jnp.transpose(kp, (0, 2, 1, 3)).reshape(b * hkv, skp, dpad)
    vf = jnp.transpose(vp, (0, 2, 1, 3)).reshape(b * hkv, skp, dpad)
    out = _k.flash_attention_bhsd(
        qf,
        kf,
        vf,
        num_kv_heads=hkv,
        causal=causal,
        window=window,
        q_offset=q_offset,
        sk_valid=sk,
        block_q=block,
        block_k=block,
        interpret=flags.interpret_mode(),
    )
    out = out.reshape(b, h, sqp, dpad)[:, :, :sq, :d0]
    return jnp.transpose(out, (0, 2, 1, 3))


def _fwd(q, k, v, causal, window, q_offset):
    return _forward(q, k, v, causal, window, q_offset), (q, k, v)


def _bwd(causal, window, q_offset, res, g):
    q, k, v = res

    def f(q, k, v):
        return _ref.chunked_mha(q, k, v, causal=causal, window=window, q_offset=q_offset)

    with jax.named_scope("attention_bwd"):  # the name a device trace finds it by
        _, vjp = jax.vjp(f, q, k, v)
        return vjp(g)


flash_attention.defvjp(_fwd, _bwd)

reference = _ref.mha_reference
chunked = _ref.chunked_mha
