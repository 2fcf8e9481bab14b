"""Paged-KV attention — jnp reference bodies (DESIGN.md §10).

Layout: a *pool* holds fixed-size pages shared by every request slot. A
GQA pool is kv-head-major, ``(num_pages, kv_heads, page_size, head_dim)``,
so one page of one kv head is a ``(page_size, head_dim)`` tile (the block
the TPU decode kernel reads); an MLA pool is ``(num_pages, page_size,
rank)``. The GQA pool is the one with four dims. A per-slot *page table*
``(slots, max_pages)`` maps logical page j of a slot to a physical page, and
``lengths (slots,)`` counts the tokens already resident, which is also the
absolute position of the first token appended this call. Physical page 0 is
the engine's trash page: idle slots carry an all-zero table row and length 0,
so their (discarded) appends land there and never touch live pages.

Bitwise contract, pinned by tests/test_paged_attn.py: gathering a slot's
pages in logical order reproduces a dense ``(B, L, ...)`` cache in position
order, and the attends below mirror the dense decode oracles op-for-op —
same einsum strings, same f32 softmax, same mask *values* (masks broadcast
from different shapes, which ``where`` evaluates elementwise) — so paged
decode is bitwise-equal to ``_decode_attend`` / the absorbed MLA decode in
f32 whenever ``max_pages * page_size`` equals the dense cache length.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp


def paged_gather(pool: jnp.ndarray, page_tables: jnp.ndarray) -> jnp.ndarray:
    """(P, page, ...) × (S, maxp) → (S, maxp·page, ...): a slot's cache in
    position order."""
    g = pool[page_tables]  # (S, maxp, page, ...)
    return g.reshape(g.shape[0], g.shape[1] * g.shape[2], *pool.shape[2:])


def paged_gather_kv(pool: jnp.ndarray, page_tables: jnp.ndarray) -> jnp.ndarray:
    """(P, KV, page, D) × (S, maxp) → (S, maxp·page, KV, D): a slot's GQA
    cache in position order, in the dense cache's layout."""
    return paged_gather(jnp.swapaxes(pool, 1, 2), page_tables)


def page_size_of(pool: jnp.ndarray) -> int:
    """Tokens per page of a GQA (4-D, kv-head-major) or MLA (3-D) pool."""
    return pool.shape[2] if pool.ndim == 4 else pool.shape[1]


def append_targets(
    page_tables: jnp.ndarray, lengths: jnp.ndarray, t: int, page_size: int
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Physical (page_ids, offsets), each (S, t), for the next ``t`` tokens
    of every slot. Positions past the table's last page clamp to it — such
    tokens are prefill-chunk tail padding, written then either overwritten
    (at their real position, before any query can attend that far) or masked
    by ``lengths``."""
    pos = lengths[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]  # (S, t)
    maxp = page_tables.shape[1]
    page_idx = jnp.minimum(pos // page_size, maxp - 1)
    page_ids = jnp.take_along_axis(page_tables, page_idx, axis=1)
    return page_ids, pos % page_size


def paged_append(
    pool: jnp.ndarray,  # (P, KV, page, D) or (P, page, r)
    new: jnp.ndarray,  # (S, T, KV, D) or (S, T, r)
    page_tables: jnp.ndarray,  # (S, maxp) int32
    lengths: jnp.ndarray,  # (S,) int32 — tokens resident before this append
) -> jnp.ndarray:
    """Scatter T new tokens per slot into their pages; O(tokens) writes, no
    cache growth or copy (the dense path's `_grow_all` pad-chain is exactly
    what this replaces)."""
    page_ids, offsets = append_targets(page_tables, lengths, new.shape[1], page_size_of(pool))
    if pool.ndim == 4:  # the (S, T) index pair lands in front of (KV, D)
        return pool.at[page_ids, :, offsets].set(new.astype(pool.dtype))
    return pool.at[page_ids, offsets].set(new.astype(pool.dtype))


def _causal_valid(lengths, t: int, l: int, window: Optional[int]):
    """(S, t, l) bool: key position visible to query position."""
    k_pos = jnp.arange(l, dtype=jnp.int32)
    q_pos = lengths[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]  # (S, t)
    valid = k_pos[None, None, :] <= q_pos[:, :, None]
    if window is not None:
        valid &= k_pos[None, None, :] > (q_pos[:, :, None] - window)
    return valid


def paged_attend_gqa(
    q: jnp.ndarray,  # (S, T, H, D), pre-scaled
    pool_k: jnp.ndarray,  # (P, KV, page, D)
    pool_v: jnp.ndarray,
    page_tables: jnp.ndarray,  # (S, maxp)
    lengths: jnp.ndarray,  # (S,) — position of q[:, 0]
    *,
    window: Optional[int] = None,
) -> jnp.ndarray:
    """Grouped-query attention against the (already appended) pool. Mirrors
    `_decode_attend` op-for-op; T > 1 adds in-chunk causality for chunked
    prefill."""
    b, t, h, d = q.shape
    k = paged_gather_kv(pool_k, page_tables)  # (S, L, KV, D)
    v = paged_gather_kv(pool_v, page_tables)
    kvh = k.shape[2]
    g = h // kvh
    qg = q.reshape(b, t, kvh, g, d)
    scores = jnp.einsum("bqhgd,blhd->bhgql", qg.astype(jnp.float32), k.astype(jnp.float32))
    valid = _causal_valid(lengths, t, k.shape[1], window)  # (S, T, L)
    scores = jnp.where(valid[:, None, None, :, :], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgql,blhd->bqhgd", p, v.astype(jnp.float32))
    return out.reshape(b, t, h, d)


def paged_attend_mla(
    q_lat: jnp.ndarray,  # (S, T, H, r) — W_uk-absorbed no-pe query
    q_rope: jnp.ndarray,  # (S, T, H, dr)
    pool_ckv: jnp.ndarray,  # (P, page, r)
    pool_krope: jnp.ndarray,  # (P, page, dr)
    page_tables: jnp.ndarray,
    lengths: jnp.ndarray,
    *,
    scale,
) -> jnp.ndarray:
    """Absorbed MLA decode over the paged latent cache. Returns the latent
    output (S, T, H, r) in f32; the caller applies W_uv (param-side)."""
    ckv = paged_gather(pool_ckv, page_tables)  # (S, L, r)
    kr = paged_gather(pool_krope, page_tables)  # (S, L, dr)
    s_nope = jnp.einsum("bshr,blr->bhsl", q_lat.astype(jnp.float32), ckv.astype(jnp.float32))
    s_rope = jnp.einsum("bshk,blk->bhsl", q_rope.astype(jnp.float32), kr.astype(jnp.float32))
    scores = (s_nope + s_rope) * scale
    valid = _causal_valid(lengths, q_lat.shape[1], ckv.shape[1], None)  # (S, T, L)
    scores = jnp.where(valid[:, None, :, :], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhsl,blr->bshr", p, ckv.astype(jnp.float32))
