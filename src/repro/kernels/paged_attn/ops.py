"""Dispatch layer for paged-KV attention: Pallas kernels on TPU (or when
forced), jnp reference bodies otherwise — same contract as
kernels/flash_attention/ops.py.

The kernels cover the GQA decode hot path (one token per slot). Chunked
prefill (T > 1) and the MLA latent path stay on the jnp reference on every
backend — MLA's absorbed decode is einsum-shaped (no softmax-over-pages
structure to tile), matching the dense MLA decode which is also jnp-only.
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from repro.kernels import flags
from repro.kernels.paged_attn import kernel as pk
from repro.kernels.paged_attn import ref

paged_gather = ref.paged_gather
append_targets = ref.append_targets
paged_attend_mla = ref.paged_attend_mla


def _pad_to(x, axis: int, mult: int):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _kernel_ok(pool) -> bool:
    """Whether a GQA decode step runs the Pallas kernels. Their page block
    is a (page_size, head_dim) tile, so a page size the TPU cannot tile is
    an error on that path, never a silent fallback to the reference."""
    if not flags.use_pallas():
        return False
    page = ref.page_size_of(pool)
    if page % 8:
        raise ValueError(f"the Pallas paged-attention kernels need page_size % 8 == 0, got {page}")
    return True


def paged_append(pool, new, page_tables, lengths):
    """(P, KV, page, D) / (P, page, r) pool ← (S, T, ...) new tokens. T == 1
    on the GQA pool shape routes to the Pallas scatter kernel."""
    if new.ndim == 4 and new.shape[1] == 1 and _kernel_ok(pool):
        d = pool.shape[-1]
        dp = d + ((-d) % 128)
        out = pk.paged_append_decode(
            _pad_to(pool, 3, 128),
            _pad_to(new[:, 0].astype(pool.dtype), 2, 128),
            page_tables,
            lengths,
            interpret=flags.interpret_mode(),
        )
        return out[..., :d] if dp != d else out
    return ref.paged_append(pool, new, page_tables, lengths)


def paged_attend_gqa(q, pool_k, pool_v, page_tables, lengths, *, window: Optional[int] = None):
    """(S, T, H, D) pre-scaled q against the pool. T == 1 routes to the
    Pallas online-softmax kernel with page-table-driven index maps."""
    if q.shape[1] == 1 and _kernel_ok(pool_k):
        s_, _, h, d = q.shape
        kv = pool_k.shape[1]
        g = h // kv
        qk = q.reshape(s_, kv, g, d)
        gp = g + ((-g) % 8)
        qk = _pad_to(_pad_to(qk, 3, 128), 2, 8)
        out = pk.paged_attend_decode(
            qk,
            _pad_to(pool_k, 3, 128),
            _pad_to(pool_v, 3, 128),
            page_tables,
            lengths,
            window=window,
            interpret=flags.interpret_mode(),
        )
        return out[:, :, :g, :d].reshape(s_, 1, h, d)
    return ref.paged_attend_gqa(q, pool_k, pool_v, page_tables, lengths, window=window)
