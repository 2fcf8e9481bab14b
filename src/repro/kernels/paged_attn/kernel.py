"""Paged-KV Pallas TPU kernels: page-table-indirect decode attention (gather)
and token append (scatter).

Both kernels take the page table / lengths as *scalar-prefetch* operands
(``pltpu.PrefetchScalarGridSpec``): the values are resident in scalar
memory before the body runs.

Attention runs the grid ``(slots, blocks)``: a block is ``pages_per_block``
consecutive logical pages of one slot (at most ``BLOCK_ROWS`` tokens), and
one grid step attends the slot's new token, every kv head of it, to one
block. The pools stay in HBM (``memory_space=pl.ANY``). A step copies only
the pages the slot's new token can see — from its window's first page (page
0 without a window) through the page holding its own position — one
``(KV, page, D)`` slab per page, into a double-buffered VMEM scratch, and
starts the copies of the next live block (this slot's next, else the next
slot's first) before it computes. A block with no such page costs one empty
grid step: no copy, no compute. An idle slot (length 0, the trash row) does
one block of one page. Inside a block the mask drops the tokens past the
length or before the window, and the rows of pages left uncopied (the
buffers are zeroed once, so such rows hold finite K/V); the f32
accumulator and online-softmax (m, l) statistics of each kv head stay in
VMEM scratch across a slot's blocks, as in the flash_attention kernel.

The append kernel rewrites the one page each slot's new token lands in (all
kv heads, the token's row replaced) via an index-mapped output block, with
the pool aliased input→output so unvisited pages pass through.

Layout: GQA pools are kv-head-major, ``(P, KV, page, D)``: one page of one
kv head is a ``(page, D)`` tile, which the TPU tiles when page is a multiple
of 8 and D of 128. The ops wrapper pads head_dim to a multiple of 128 and
the GQA group dim to a multiple of 8, and refuses a page size that is not a
multiple of 8.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float("-inf")
BLOCK_ROWS = 512  # tokens of one attention block, at most


# ---------------------------------------------------------------------------
# decode attention (gather/read)
# ---------------------------------------------------------------------------


def pages_per_block(page: int, max_pages: int) -> int:
    """The most pages whose tokens fit ``BLOCK_ROWS``; at least 1, at most
    the table's width. At 8 kv heads of 128 in bf16, a block's K and V,
    double-buffered, take 4 MiB of VMEM."""
    return max(1, min(max_pages, BLOCK_ROWS // page))


def _live_pages(length, page: int, max_pages: int, window: Optional[int]):
    """First and last logical page that a slot's new token, at position
    ``length``, attends: its window's first (0 without one) through its own."""
    last = jnp.minimum(length // page, max_pages - 1)
    if window is None:
        return jnp.int32(0), last
    return jnp.minimum(jnp.maximum(length - window + 1, 0) // page, last), last


def _attend_kernel(
    pt_ref,  # scalar (S * maxp,) int32, the page table row-major
    len_ref,  # scalar (S,) int32
    q_ref,  # (1, KV, G, D)
    k_hbm,  # (P, KV, page, D), in HBM
    v_hbm,
    o_ref,  # (1, KV, G, D)
    k_buf,  # VMEM (2, ppb, KV, page, D): two blocks' pages
    v_buf,
    sems,  # DMA (2,): one per buffer
    buf_ref,  # SMEM (1,) int32: the buffer this step's block lands in
    acc_ref,  # VMEM (KV, G, D) f32
    m_ref,  # VMEM (KV, G, 1) f32
    l_ref,  # VMEM (KV, G, 1) f32
    *,
    page: int,
    ppb: int,
    max_pages: int,
    window: Optional[int],
):
    s, b = pl.program_id(0), pl.program_id(1)
    slots, blocks = pl.num_programs(0), pl.num_programs(1)
    _, _, kv, _, d = k_buf.shape
    rows = ppb * page

    def copies(si, bi, buf, start: bool):
        """Start (or wait for) the copies of slot si's block bi into buffer
        buf: one K and one V copy per live page of the block."""
        lo, hi = _live_pages(len_ref[si], page, max_pages, window)

        def one(j, carry):
            pid = pt_ref[si * max_pages + j]
            for src, dst in ((k_hbm, k_buf), (v_hbm, v_buf)):
                cp = pltpu.make_async_copy(src.at[pid], dst.at[buf, j - bi * ppb], sems.at[buf])
                cp.start() if start else cp.wait()
            return carry

        jax.lax.fori_loop(jnp.maximum(lo, bi * ppb), jnp.minimum(hi, bi * ppb + ppb - 1) + 1, one, 0)

    lo, hi = _live_pages(len_ref[s], page, max_pages, window)
    b_lo, b_hi = lo // ppb, hi // ppb

    @pl.when((s == 0) & (b == 0))
    def _clear():
        # rows a block leaves uncopied keep what the buffer last held: real
        # K/V of an earlier block, or these zeros, never memory no copy wrote
        k_buf[...] = jnp.zeros_like(k_buf)
        v_buf[...] = jnp.zeros_like(v_buf)

    @pl.when(b == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when((b >= b_lo) & (b <= b_hi))
    def _block():
        # every slot has a live block, so the next one is this slot's next
        # block, else the next slot's first; the grid's first live block
        # starts its own copies
        first_step = (s == 0) & (b == b_lo)
        buf = jnp.where(first_step, 0, buf_ref[0])

        @pl.when(first_step)
        def _():
            copies(s, b, buf, start=True)

        more = b < b_hi

        @pl.when(more | (s + 1 < slots))
        def _():
            ns = jnp.where(more, s, s + 1)
            nlo, _ = _live_pages(len_ref[jnp.minimum(s + 1, slots - 1)], page, max_pages, window)
            copies(ns, jnp.where(more, b + 1, nlo // ppb), 1 - buf, start=True)

        buf_ref[0] = 1 - buf
        copies(s, b, buf, start=False)

        q_pos = len_ref[s]  # position of the (already appended) new token
        k_pos = b * rows + jax.lax.broadcasted_iota(jnp.int32, (1, rows), 1)
        mask = k_pos <= q_pos
        if window is not None:
            mask &= k_pos > (q_pos - window)
        for h in range(kv):
            q = q_ref[0, h].astype(jnp.float32)  # (G, D)
            k = k_buf[buf, :, h].astype(jnp.float32).reshape(rows, d)
            sc = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (G, rows)
            sc = jnp.where(mask, sc, NEG_INF)

            m_prev = m_ref[h]
            m_new = jnp.maximum(m_prev, sc.max(axis=-1, keepdims=True))
            safe_m = jnp.where(m_new == NEG_INF, 0.0, m_new)
            p = jnp.exp(sc - safe_m)
            corr = jnp.exp(m_prev - safe_m)
            l_ref[h] = l_ref[h] * corr + p.sum(axis=-1, keepdims=True)
            v = v_buf[buf, :, h].astype(jnp.float32).reshape(rows, d)
            acc_ref[h] = acc_ref[h] * corr + jax.lax.dot(p, v, preferred_element_type=jnp.float32)
            m_ref[h] = m_new

    @pl.when(b == blocks - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


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
    ppb = pages_per_block(page, maxp)

    def q_index(s, b, pt, ln):
        return (s, 0, 0, 0)

    kern = functools.partial(_attend_kernel, page=page, ppb=ppb, max_pages=maxp, window=window)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(s_, pl.cdiv(maxp, ppb)),
        in_specs=[
            pl.BlockSpec((1, kv, g, d), q_index),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, kv, g, d), q_index),
        scratch_shapes=[
            pltpu.VMEM((2, ppb, kv, page, d), pool_k.dtype),
            pltpu.VMEM((2, ppb, kv, page, d), pool_v.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((kv, g, d), jnp.float32),
            pltpu.VMEM((kv, g, 1), jnp.float32),
            pltpu.VMEM((kv, g, 1), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s_, kv, g, d), q.dtype),
        # a step prefetches the next step's pages: the grid runs in order
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(page_tables.reshape(-1), lengths, q, pool_k, pool_v)


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
