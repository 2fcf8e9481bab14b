"""Paged-KV Pallas TPU kernels: page-table-indirect decode attention (gather)
and token append (scatter).

Both kernels take the page table / lengths as *scalar-prefetch* operands
(``pltpu.PrefetchScalarGridSpec``): the values are resident before the body
runs, so the BlockSpec index maps themselves chase the page table — the KV
block for grid step (s, kv, j) is DMA'd straight from physical page
``page_table[s, j]``, and the pool is never gathered or repeated in HBM.

Attention follows the flash_attention kernel structure: the page axis is the
innermost (sequential) grid dim, with the f32 accumulator and online-softmax
(m, l) statistics in VMEM scratch across pages. The append kernel rewrites
the one page each slot's new token lands in (all kv heads, the token's row
replaced) via an index-mapped output block, with the pool aliased
input→output so unvisited pages pass through.

Layout: GQA pools are kv-head-major, ``(P, KV, page, D)``, so the block of
one page of one kv head is ``(1, 1, page, D)`` — its last two dims are
(page, D), which the TPU tiles when page is a multiple of 8 and D of 128.
The ops wrapper pads head_dim to a multiple of 128 and the GQA group dim to
a multiple of 8, and refuses a page size that is not a multiple of 8.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float("-inf")


# ---------------------------------------------------------------------------
# decode attention (gather/read)
# ---------------------------------------------------------------------------


def _attend_kernel(
    pt_ref,  # scalar (S, maxp) int32
    len_ref,  # scalar (S,) int32
    q_ref,  # (1, 1, G, D)
    k_ref,  # (1, 1, page, D) — physical page pt[s, j] of kv head kv
    v_ref,  # (1, 1, page, D)
    o_ref,  # (1, 1, G, D)
    acc_ref,  # VMEM (G, D) f32
    m_ref,  # VMEM (G,) f32
    l_ref,  # VMEM (G,) f32
    *,
    page: int,
    window: Optional[int],
):
    s = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32)  # (G, D)
    k = k_ref[0, 0].astype(jnp.float32)  # (page, D)
    sc = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (G, page)

    q_pos = len_ref[s]  # position of the (already appended) new token
    k_pos = j * page + jax.lax.broadcasted_iota(jnp.int32, (1, page), 1)
    mask = k_pos <= q_pos
    if window is not None:
        mask &= k_pos > (q_pos - window)
    sc = jnp.where(mask, sc, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, sc.max(axis=-1))
    safe_m = jnp.where(m_new == NEG_INF, 0.0, m_new)
    p = jnp.exp(sc - safe_m[:, None])
    corr = jnp.exp(m_prev - safe_m)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=-1)
    acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot(
        p, v_ref[0, 0].astype(jnp.float32), preferred_element_type=jnp.float32
    )
    m_ref[...] = m_new

    @pl.when(j == pl.num_programs(2) - 1)
    def _finalize():
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[...][:, None], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def paged_attend_decode(
    q,  # (S, KV, G, D) — one new token per slot, grouped by kv head
    pool_k,  # (P, KV, page, D)
    pool_v,
    page_tables,  # (S, maxp) int32
    lengths,  # (S,) int32
    *,
    window: Optional[int],
    interpret: bool = False,
):
    s_, kv, g, d = q.shape
    _, _, page, _ = pool_k.shape
    maxp = page_tables.shape[1]
    grid = (s_, kv, maxp)

    def q_index(s, kvi, j, pt, ln):
        return (s, kvi, 0, 0)

    def kv_index(s, kvi, j, pt, ln):
        return (pt[s, j], kvi, 0, 0)

    kern = functools.partial(_attend_kernel, page=page, window=window)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, g, d), q_index),
            pl.BlockSpec((1, 1, page, d), kv_index),
            pl.BlockSpec((1, 1, page, d), kv_index),
        ],
        out_specs=pl.BlockSpec((1, 1, g, d), q_index),
        scratch_shapes=[
            pltpu.VMEM((g, d), jnp.float32),
            pltpu.VMEM((g,), jnp.float32),
            pltpu.VMEM((g,), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s_, kv, g, d), q.dtype),
        interpret=interpret,
    )(page_tables, lengths, q, pool_k, pool_v)


# ---------------------------------------------------------------------------
# token append (scatter/write)
# ---------------------------------------------------------------------------


def _append_kernel(pt_ref, len_ref, pool_ref, new_ref, o_ref, *, page: int):
    del pt_ref  # the page is chosen in the BlockSpec maps
    s = pl.program_id(0)
    _, kv, _, d = pool_ref.shape
    row = jax.lax.broadcasted_iota(jnp.int32, (kv, page, d), 1)
    new = jnp.broadcast_to(new_ref[0][:, None, :], (kv, page, d))
    o_ref[0] = jnp.where(row == len_ref[s] % page, new, pool_ref[0])


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_append_decode(
    pool,  # (P, KV, page, D)
    new,  # (S, KV, D) — one token per slot
    page_tables,  # (S, maxp) int32
    lengths,  # (S,) int32 — write position of slot s
    *,
    interpret: bool = False,
):
    s_, kv, d = new.shape
    _, _, page, _ = pool.shape
    maxp = page_tables.shape[1]

    def pool_index(s, pt, ln):
        p = jnp.minimum(ln[s] // page, maxp - 1)
        return (pt[s, p], 0, 0, 0)

    def new_index(s, pt, ln):
        return (s, 0, 0)

    # one token's row is a single sublane of a (page, D) tile, which the TPU
    # cannot address as a block of its own: the kernel rewrites the token's
    # whole page (all kv heads), replacing the one row
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(s_,),
        in_specs=[
            pl.BlockSpec((1, kv, page, d), pool_index),
            pl.BlockSpec((1, kv, d), new_index),
        ],
        out_specs=pl.BlockSpec((1, kv, page, d), pool_index),
    )
    return pl.pallas_call(
        functools.partial(_append_kernel, page=page),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        # alias pool → output: pages not visited by any grid step pass through
        input_output_aliases={2: 0},
        interpret=interpret,
    )(page_tables, lengths, pool, new)
