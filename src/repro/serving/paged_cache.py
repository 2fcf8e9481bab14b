"""Paged KV-cache pools and the physical page allocator (DESIGN.md §10).

One pool per attention segment, with a leading layer axis so the existing
``lax.scan`` cache plumbing in ``apply_model`` slices a per-layer pool
exactly like a per-layer dense cache:

* GQA:  ``pool_k`` / ``pool_v``       — (n, num_pages, kv_heads, page_size, head_dim)
* MLA:  ``pool_ckv`` / ``pool_krope`` — (n, num_pages, page_size, rank)

The page table and lengths are *not* part of the cache pytree: they are
host-owned scheduler state (``serving/scheduler.py``) passed per step as a
:class:`PagedState`, shared by every layer. Physical page 0 is reserved as
the trash page — idle batch rows carry a zero table row + length 0 so their
discarded appends land there (see kernels/paged_attn/ref.py).
"""
from __future__ import annotations

import heapq
import math
from typing import Any, Dict, List, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.config.base import ModelConfig

TRASH_PAGE = 0


class PagedState(NamedTuple):
    """Per-step paged-attention operands (device-ready)."""

    page_tables: Any  # (S, max_pages) int32 — physical page per logical page
    lengths: Any  # (S,) int32 — tokens resident before this step's append


def paged_supported(cfg: ModelConfig) -> bool:
    """Paged serving covers pure attention-family text archs (GQA/MLA, full
    or sliding-window, MoE included). Recurrent/hybrid archs (mamba2, rwkv6,
    zamba2) keep the dense engine — their decode state is O(1) in sequence
    length, so there is nothing to page — as do modality frontends and
    mrope's multi-axis positions."""
    from repro.models.transformer import segments

    if cfg is None:  # guard-only engines (validation tests) stay dense
        return False
    if cfg.frontend is not None or cfg.attention is None:
        return False
    if cfg.attention.rope == "mrope":
        return False
    return all(kind in ("attn", "moe") for kind, _ in segments(cfg))


def pages_for(tokens: int, page_size: int) -> int:
    return max(1, math.ceil(tokens / page_size))


def init_paged_pools(
    cfg: ModelConfig, num_pages: int, page_size: int, dtype=None
) -> Dict[str, Any]:
    """Zero-initialized per-segment pools, mirroring ``init_caches``'s
    ``seg{i}`` keying (stacked over each run's layers)."""
    from repro.models.transformer import segments

    if not paged_supported(cfg):
        raise ValueError("paged pools require an attention-only text arch (see paged_supported)")
    dtype = dtype or cfg.param_dtype
    a = cfg.attention
    pools: Dict[str, Any] = {}
    for si, (kind, n) in enumerate(segments(cfg)):
        if a.kind == "mla":
            pools[f"seg{si}"] = dict(
                pool_ckv=jnp.zeros((n, num_pages, page_size, a.kv_lora_rank), dtype),
                pool_krope=jnp.zeros((n, num_pages, page_size, a.qk_rope_head_dim), dtype),
            )
        else:
            pools[f"seg{si}"] = dict(
                pool_k=jnp.zeros((n, num_pages, a.num_kv_heads, page_size, a.head_dim), dtype),
                pool_v=jnp.zeros((n, num_pages, a.num_kv_heads, page_size, a.head_dim), dtype),
            )
    return pools


def pool_bytes(cfg: ModelConfig, num_pages: int, page_size: int, dtype=None) -> int:
    pools = jax.eval_shape(lambda: init_paged_pools(cfg, num_pages, page_size, dtype))
    return sum(leaf.size * leaf.dtype.itemsize for leaf in jax.tree.leaves(pools))


class PageAllocator:
    """Deterministic physical-page allocator. Page 0 (trash) is never handed
    out; free pages are issued lowest-id-first so a replayed arrival trace
    reproduces the exact page assignment."""

    def __init__(self, num_pages: int):
        if num_pages < 2:
            raise ValueError(f"need >= 2 pages (one is the trash page), got {num_pages}")
        self.num_pages = num_pages
        self._free: List[int] = list(range(1, num_pages))
        heapq.heapify(self._free)

    @property
    def capacity(self) -> int:
        return self.num_pages - 1

    @property
    def available(self) -> int:
        return len(self._free)

    def alloc(self, n: int) -> Optional[List[int]]:
        """n pages (ascending ids), or None — never a partial grant."""
        if n > len(self._free):
            return None
        return [heapq.heappop(self._free) for _ in range(n)]

    def free(self, pages) -> None:
        for p in pages:
            if not (0 < p < self.num_pages):
                raise ValueError(f"freeing invalid page {p}")
            if p in self._free:
                raise ValueError(f"double free of page {p}")
            heapq.heappush(self._free, p)
