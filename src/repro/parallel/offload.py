"""Host offload of packed planes with double-buffered chunk streaming.

The paper hides the boundary collective behind the tau local steps; the
same window hides host<->device traffic.  Optimizer-state buckets
(``PackedSGDState``/``PackedAdamState``) and anchor/inflight buckets are
*host-resident between boundaries* as a :class:`HostPlane` — each flat
dtype bucket split into fixed-size chunks stacked along a leading axis —
and streamed back chunk-by-chunk exactly where they are consumed:

* opt state: per local step through :func:`streamed_update`, a
  ``lax.scan`` over chunks whose carry holds ONE staged device chunk per
  state plane while the body prefetches the next — the two in-flight
  device-side staging buffers.  The fused ``kernels/opt_step`` math runs
  per chunk (the ops accept any ``(..., n)`` buffer), so the update is
  bitwise-identical to the plane-resident path: chunking is a pad +
  reshape whose zero tail every optimizer maps to zero and the unchunk
  drops.
* anchor/inflight/vars: whole-plane :func:`restore_plane` before the
  window (the H2D copies have no data dependency on the local scan, so
  the scheduler overlaps them with the tau steps — the prefetch), and
  :func:`offload_plane` after the boundary consumes them (the D2H).

Chunk shapes are compile-time: :class:`OffloadPlan` is a static hashable
table derived from :class:`~repro.parallel.packing.Layout` bucket sizes,
lead-agnostic so one plan serves the worker-stacked ``(m, n)`` opt
buckets, the flat ``(n,)`` anchor, and f32 ``with_dtype`` shadows.

Memory-space placement: on backends that expose a ``pinned_host``
memory (TPU, and the CPU backend) every chunk hand-off is annotated with
``jax.device_put(..., jax.memory.Space.Host / .Device)`` inside jit; on a
backend without one the stream is structural-only and the annotations
are skipped.  The program shape — and therefore the parity and staging
guarantees — is identical either way.  See DESIGN.md §9.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.parallel.packing import LANE, Layout, Packed, _round_up

HOST_KIND = "pinned_host"
DEFAULT_CHUNK_MB = 64.0


@functools.lru_cache(maxsize=None)
def host_memory_kind() -> Optional[str]:
    """``"pinned_host"`` when the default backend has a distinct host
    memory space, else ``None`` (the stream is structural and placement
    annotations are skipped)."""
    kinds = {m.kind for m in jax.devices()[0].addressable_memories()}
    return HOST_KIND if HOST_KIND in kinds else None


def _to_host(x):
    return jax.device_put(x, jax.memory.Space.Host) if host_memory_kind() else x


def _to_device(x):
    return jax.device_put(x, jax.memory.Space.Device) if host_memory_kind() else x


# ---------------------------------------------------------------------------
# Static chunk table


@dataclasses.dataclass(frozen=True)
class OffloadPlan:
    """Per-bucket chunk grid, aligned with ``Layout.bucket_sizes``.

    ``chunk_elems[b]`` is a LANE multiple (so chunk slices hit the
    pad-free fast path of the opt kernels) and
    ``num_chunks[b] * chunk_elems[b] >= bucket_sizes[b]`` — the chunked
    form is zero-padded up to the grid and the tail is dropped on
    unchunk.  Hashable so it can ride in pytree aux data (scan carries,
    jit static args).
    """

    chunk_elems: Tuple[int, ...]
    num_chunks: Tuple[int, ...]

    @classmethod
    def for_layout(cls, layout: Layout, chunk_mb: float = DEFAULT_CHUNK_MB) -> "OffloadPlan":
        chunk_elems = []
        num_chunks = []
        for n, dt in zip(layout.bucket_sizes, layout.bucket_dtypes):
            itemsize = jnp.dtype(dt).itemsize
            c = int(chunk_mb * (1 << 20)) // itemsize
            c = max(LANE, (c // LANE) * LANE)
            c = min(c, _round_up(max(n, 1)))
            chunk_elems.append(c)
            num_chunks.append(-(-max(n, 1) // c))
        return cls(tuple(chunk_elems), tuple(num_chunks))

    def grid(self, bucket: int) -> Tuple[int, int]:
        """(num_chunks, chunk_elems) for one bucket."""
        return self.num_chunks[bucket], self.chunk_elems[bucket]


def chunk_buffer(buf: jax.Array, num_chunks: int, chunk_elems: int) -> jax.Array:
    """``lead + (n,)`` -> ``(num_chunks,) + lead + (chunk_elems,)``:
    zero-pad the flat axis to the chunk grid, split, move the chunk axis
    to the front.  Exact inverse of :func:`unchunk_buffer`."""
    lead, n = buf.shape[:-1], buf.shape[-1]
    padded = num_chunks * chunk_elems
    if padded != n:
        buf = jnp.pad(buf, [(0, 0)] * len(lead) + [(0, padded - n)])
    buf = buf.reshape(lead + (num_chunks, chunk_elems))
    return jnp.moveaxis(buf, -2, 0)


def unchunk_buffer(chunks: jax.Array, n: int) -> jax.Array:
    """Inverse of :func:`chunk_buffer`: drop the pad, restore the flat axis."""
    num_chunks, chunk_elems = chunks.shape[0], chunks.shape[-1]
    lead = chunks.shape[1:-1]
    buf = jnp.moveaxis(chunks, 0, -2).reshape(lead + (num_chunks * chunk_elems,))
    return buf[..., :n]


# ---------------------------------------------------------------------------
# HostPlane: the between-boundaries form of a Packed plane


@jax.tree_util.register_pytree_node_class
class HostPlane:
    """Chunked, host-resident form of a :class:`Packed` plane.

    Flattens to one chunk stack per bucket (same arity as ``Packed``)
    with ``(layout, plan)`` as static aux, so it slots into scan
    carries, eval_shape specs, and checkpointable pytrees wherever the
    resident plane did.
    """

    __slots__ = ("chunks", "layout", "plan")

    def __init__(self, chunks: Sequence[jax.Array], layout: Layout, plan: OffloadPlan):
        self.chunks = tuple(chunks)
        self.layout = layout
        self.plan = plan

    def tree_flatten(self):
        return self.chunks, (self.layout, self.plan)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(tuple(children), *aux)

    @property
    def nbytes(self) -> int:
        """Total chunked (padded) bytes — the host residency cost."""
        total = 0
        for ch in self.chunks:
            size = 1
            for d in ch.shape:
                size *= d
            total += size * ch.dtype.itemsize
        return total

    @property
    def lead_shape(self) -> Tuple[int, ...]:
        return tuple(self.chunks[0].shape[1:-1]) if self.chunks else ()

    def __repr__(self):  # pragma: no cover - debug aid
        grids = list(zip(self.plan.num_chunks, self.plan.chunk_elems))
        return f"HostPlane(lead={self.lead_shape}, grids={grids})"


def offload_plane(px: Packed, plan: OffloadPlan) -> HostPlane:
    """Chunk a resident plane and hand it to host memory (the D2H leg)."""
    chunks = tuple(
        _to_host(chunk_buffer(buf, plan.num_chunks[b], plan.chunk_elems[b]))
        for b, buf in enumerate(px.buffers)
    )
    return HostPlane(chunks, px.layout, plan)


def restore_plane(hp: HostPlane) -> Packed:
    """Bring a host plane back device-resident (the H2D leg)."""
    buffers = tuple(
        unchunk_buffer(_to_device(ch), hp.layout.bucket_sizes[b])
        for b, ch in enumerate(hp.chunks)
    )
    return Packed(buffers, hp.layout)


def _is_packed(x) -> bool:
    return isinstance(x, Packed)


def _is_host(x) -> bool:
    return isinstance(x, HostPlane)


def is_offloaded(tree) -> bool:
    """True when any leaf plane in ``tree`` is a :class:`HostPlane`."""
    found = False

    def visit(x):
        nonlocal found
        found = found or isinstance(x, HostPlane)
        return x

    jax.tree_util.tree_map(visit, tree, is_leaf=_is_host)
    return found


def tree_offload(tree, plan: OffloadPlan):
    """Offload every ``Packed`` plane in a state pytree (vars/inflight/
    opt state); non-plane leaves (scalars, masks) pass through."""
    return jax.tree_util.tree_map(
        lambda x: offload_plane(x, plan) if isinstance(x, Packed) else x,
        tree,
        is_leaf=_is_packed,
    )


def tree_restore(tree):
    """Restore every :class:`HostPlane` in a state pytree to a resident
    ``Packed`` plane; other leaves pass through."""
    return jax.tree_util.tree_map(
        lambda x: restore_plane(x) if isinstance(x, HostPlane) else x,
        tree,
        is_leaf=_is_host,
    )


def tree_pin_host(tree):
    """Annotate every :class:`HostPlane` chunk in ``tree`` as host memory."""
    return jax.tree_util.tree_map(
        lambda x: HostPlane(tuple(_to_host(c) for c in x.chunks), x.layout, x.plan) if isinstance(x, HostPlane) else x,
        tree,
        is_leaf=_is_host,
    )


def plan_of(tree) -> Optional[OffloadPlan]:
    """The :class:`OffloadPlan` carried by the first HostPlane in ``tree``."""
    plan = None

    def visit(x):
        nonlocal plan
        if plan is None and isinstance(x, HostPlane):
            plan = x.plan
        return x

    jax.tree_util.tree_map(visit, tree, is_leaf=_is_host)
    return plan


def host_nbytes(tree) -> int:
    """Total host-resident bytes across every HostPlane in ``tree``."""
    total = 0

    def visit(x):
        nonlocal total
        if isinstance(x, HostPlane):
            total += x.nbytes
        return x

    jax.tree_util.tree_map(visit, tree, is_leaf=_is_host)
    return total


# ---------------------------------------------------------------------------
# Double-buffered streamed optimizer update


def streamed_update(
    apply_chunk: Callable,
    state: Tuple[HostPlane, ...],
    px: Packed,
    pg: Packed,
) -> Tuple[Packed, Tuple[HostPlane, ...]]:
    """Run ``apply_chunk(x_c, g_c, *state_c) -> (x_c', *state_c')`` over
    the plane, streaming the host-resident state planes through two
    device staging buffers per bucket.

    Per bucket a ``lax.scan`` walks the chunk grid: the carry holds the
    *staged* device copy of chunk ``i`` of each state plane, the body
    prefetches chunk ``i+1`` (clamped at the last chunk so the epilogue
    fetch is a no-op re-fetch, keeping the carry shape fixed) while the
    fused opt kernel updates chunk ``i``, then sends the updated state
    chunk back to host.  staged + prefetch = the two in-flight staging
    buffers; the params/grad chunks are device-resident throughout.
    """
    if not state:
        raise ValueError("streamed_update needs at least one host state plane")
    plan = state[0].plan
    new_x = []
    new_state_chunks = [[] for _ in state]
    for b, (x_buf, g_buf) in enumerate(zip(px.buffers, pg.buffers)):
        num_chunks, chunk_elems = plan.grid(b)
        n = px.layout.bucket_sizes[b]
        xh = chunk_buffer(x_buf, num_chunks, chunk_elems)
        gh = chunk_buffer(g_buf, num_chunks, chunk_elems)
        stacks = tuple(hp.chunks[b] for hp in state)

        def fetch(i, stacks=stacks, num_chunks=num_chunks):
            j = jnp.minimum(i, num_chunks - 1)
            return tuple(
                _to_device(jax.lax.dynamic_index_in_dim(s, j, axis=0, keepdims=False))
                for s in stacks
            )

        def body(staged, xs, fetch=fetch):
            i, x_c, g_c = xs
            nxt = fetch(i + 1)  # prefetch: in flight while chunk i computes
            outs = apply_chunk(x_c, g_c, *staged)
            return nxt, (outs[0],) + tuple(_to_host(s) for s in outs[1:])

        idx = jnp.arange(num_chunks, dtype=jnp.int32)
        _, ys = jax.lax.scan(body, fetch(0), (idx, xh, gh))
        new_x.append(unchunk_buffer(ys[0], n))
        for k in range(len(state)):
            new_state_chunks[k].append(ys[1 + k])
    px_new = Packed(tuple(new_x), px.layout)
    state_new = tuple(
        HostPlane(tuple(new_state_chunks[k]), hp.layout, hp.plan)
        for k, hp in enumerate(state)
    )
    return px_new, state_new


# ---------------------------------------------------------------------------
# Stream accounting (shared by dryrun / costprobe / runtime model)


def stream_roundtrip_bytes(state_tree) -> int:
    """Bytes for ONE H2D + D2H round trip of every host plane in
    ``state_tree``.  Opt-state planes make ``tau`` trips per round (one
    per local step), anchor/inflight/vars one; callers apply the
    multiplier."""
    return 2 * host_nbytes(state_tree)


def staging_bytes(plan: OffloadPlan, layout: Layout, state_planes: int) -> int:
    """Device bytes pinned by the double buffer: 2 staging chunks per
    state plane per bucket (the scan carry + the in-body prefetch)."""
    total = 0
    for b, dt in enumerate(layout.bucket_dtypes):
        total += 2 * state_planes * plan.chunk_elems[b] * jnp.dtype(dt).itemsize
    return total
