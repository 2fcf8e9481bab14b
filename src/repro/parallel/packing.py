"""Packed parameter plane: one flat buffer per dtype, static layout table.

The round boundary (paper eqs. 4–5) is a pure memory-bound sweep over the
parameters, yet a pytree-shaped boundary pays one op *per leaf*: per-leaf
means, per-leaf sharding constraints, and a separate padded kernel launch
per leaf in the pullback. This module collapses the plane the boundary
sweeps over into one (or a few, dtype-bucketed) contiguous 128-lane-aligned
flat buffers with a *static* layout table, so the whole boundary becomes one
collective plus one kernel launch regardless of how many tensors the model
has.

Local-step dispatch model (plane-resident training)
---------------------------------------------------
The plane is the *canonical* training representation end-to-end:
``TrainState.x`` stores the worker-stacked :class:`Packed` plane across
rounds, the round engine's scan carries it, and the loss is differentiated
**with the plane buffers as the primal argument** — the model reads
parameters through a :class:`ParamView` (lazy ``view_leaf`` windows), so
gradients arrive as one flat cotangent buffer per dtype bucket with no
``pack(grads)`` scatter chain anywhere in the step. Per local step the work
is, for a model with L leaves and B dtype buckets (B is 1–2 in practice, L
is hundreds):

    =====================  ==============  ===========================
    per local step          per-leaf path   packed path
    =====================  ==============  ===========================
    optimizer update        ~5·L ops        B fused kernel launches
    sync-SGD all-reduce     L means         B means
    PowerSGD elementwise    ~3·L ops        B sweeps (+ inherently
                                            per-leaf factor math and
                                            uncompressed-leaf means)
    DaSGD mid-round rebase  L lerps         B sweeps
    layout ops              0               window reads (slices fused
                                            into leaf consumers) +
                                            their pad transposes on the
                                            backward; zero DUS
    =====================  ==============  ===========================

Optimizer state (SGD momentum, AdamW f32 moments) lives as flat buffers in
``TrainState.opt`` between boundaries — ``pack``/``unpack`` never touch it
mid-round — and round boundaries consume and return the plane itself (no
pack/unpack seam at round granularity either). The fused update kernels are
in ``repro.kernels.opt_step``; the per-leaf optimizer remains the bit-exact
oracle (``AlgoConfig.packed`` off), pinned by tests/test_packed_optim.py.

Layout rules
------------
* Leaves are bucketed by dtype (buckets ordered by dtype name) — mixing
  dtypes in one buffer would force upcasts; bucketing keeps every boundary
  op at its native width.
* Within a bucket, leaves keep their ``jax.tree`` flatten order. Each leaf
  occupies ``stride = ceil(size / 128) * 128`` elements starting at a
  128-aligned ``offset``; the tail padding is written as zeros by ``pack``
  and never read back by ``unpack``. Every leaf therefore starts on a TPU
  lane boundary and a buffer slice is directly kernel-feedable.
* The table (:class:`Layout`) is built from shapes/dtypes only — it works
  identically on concrete arrays and ``ShapeDtypeStruct`` stand-ins, is
  hashable, and rides as pytree aux data, so a :class:`Packed` value can be
  a ``jit``/``scan``/``eval_shape`` carry.

``pack``/``unpack`` are pure layout changes (XLA fuses the pads into one
concatenate per bucket); all boundary *math* then runs on the buffers.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

LANE = 128  # TPU lane width: every leaf segment is padded to this boundary


def _round_up(n: int, mult: int = LANE) -> int:
    return ((n + mult - 1) // mult) * mult


def _prod(shape) -> int:
    out = 1
    for s in shape:
        out *= int(s)
    return out


@dataclasses.dataclass(frozen=True)
class LeafSlot:
    """Static placement of one pytree leaf inside its dtype bucket."""

    index: int  # position in jax.tree flatten order (across all buckets)
    bucket: int  # which dtype bucket the leaf lives in
    shape: Tuple[int, ...]  # leaf shape (without any stacked lead dims)
    dtype: str  # canonical dtype name
    offset: int  # element offset of the leaf inside its bucket buffer
    size: int  # number of real elements
    stride: int  # padded extent (size rounded up to the lane boundary)


@dataclasses.dataclass(frozen=True)
class Layout:
    """Static layout table: where every leaf of a pytree lives in the packed
    plane. Hashable (usable as jit-static / pytree aux data)."""

    treedef: Any  # jax PyTreeDef of the packed tree
    slots: Tuple[LeafSlot, ...]  # one per leaf, in flatten order
    bucket_dtypes: Tuple[str, ...]  # dtype name per bucket (sorted)
    bucket_sizes: Tuple[int, ...]  # padded total elements per bucket

    @property
    def num_buckets(self) -> int:
        return len(self.bucket_dtypes)

    @property
    def num_leaves(self) -> int:
        return len(self.slots)

    def total_elements(self) -> int:
        return sum(self.bucket_sizes)

    def with_dtype(self, dtype) -> "Layout":
        """Same bucketing/offsets, every slot retagged to ``dtype`` — used
        for f32 shadows (error feedback) that must stay element-aligned with
        the param-dtype plane."""
        name = jnp.dtype(dtype).name
        slots = tuple(dataclasses.replace(s, dtype=name) for s in self.slots)
        return Layout(
            treedef=self.treedef,
            slots=slots,
            bucket_dtypes=tuple(name for _ in self.bucket_dtypes),
            bucket_sizes=self.bucket_sizes,
        )


@jax.tree_util.register_pytree_node_class
class Packed:
    """A pytree flattened into per-dtype flat buffers + its static layout.

    ``buffers[b]`` has shape ``lead + (layout.bucket_sizes[b],)`` where
    ``lead`` is any stacked prefix (e.g. the worker axis m). Registered as a
    pytree whose children are the buffers and whose aux data is the layout,
    so it carries through jit/scan/vmap/eval_shape unchanged.
    """

    __slots__ = ("buffers", "layout")

    def __init__(self, buffers: Tuple[Any, ...], layout: Layout):
        self.buffers = tuple(buffers)
        self.layout = layout

    def tree_flatten(self):
        return self.buffers, self.layout

    @classmethod
    def tree_unflatten(cls, layout, buffers):
        return cls(tuple(buffers), layout)

    @property
    def lead_shape(self) -> Tuple[int, ...]:
        return tuple(self.buffers[0].shape[:-1]) if self.buffers else ()

    @property
    def nbytes(self) -> int:
        """Total plane bytes (padding lanes and any stacked lead dims
        included). Shape/dtype arithmetic only, so it works on concrete
        arrays and ``ShapeDtypeStruct`` stand-ins alike — the dry-run
        records it for AOT specs."""
        return sum(
            _prod(b.shape) * jnp.dtype(d).itemsize
            for b, d in zip(self.buffers, self.layout.bucket_dtypes)
        )

    def __repr__(self):
        shapes = ", ".join(f"{b.shape}:{self.layout.bucket_dtypes[i]}" for i, b in enumerate(self.buffers))
        return f"Packed([{shapes}], {self.layout.num_leaves} leaves)"


def layout_of(tree, lead: int = 0) -> Layout:
    """Build the static layout table for ``tree``. ``lead`` leading dims of
    every leaf (e.g. the stacked worker axis) are excluded from the layout —
    they become the buffers' lead shape at ``pack`` time."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    shapes = [tuple(int(s) for s in l.shape[lead:]) for l in leaves]
    dtypes = [jnp.dtype(l.dtype).name for l in leaves]
    bucket_dtypes = tuple(sorted(set(dtypes)))
    bucket_index = {d: i for i, d in enumerate(bucket_dtypes)}
    offsets = [0] * len(bucket_dtypes)
    slots = []
    for i, (shape, dname) in enumerate(zip(shapes, dtypes)):
        b = bucket_index[dname]
        size = _prod(shape)
        stride = _round_up(max(size, 1))
        slots.append(
            LeafSlot(index=i, bucket=b, shape=shape, dtype=dname, offset=offsets[b], size=size, stride=stride)
        )
        offsets[b] += stride
    return Layout(
        treedef=treedef,
        slots=tuple(slots),
        bucket_dtypes=bucket_dtypes,
        bucket_sizes=tuple(offsets),
    )


def pack(tree, layout: Optional[Layout] = None, lead: int = 0) -> Packed:
    """Flatten ``tree`` into the packed plane (one buffer per dtype bucket).

    The first ``lead`` dims of every leaf are carried through as the
    buffers' lead shape (all leaves must agree on them). Padding lanes are
    zero-filled.

    The plane is built by static-offset ``dynamic_update_slice`` into a
    zeros buffer rather than ``jnp.concatenate``: XLA fuses the chain into
    one write either way, padding comes for free — and, load-bearing on
    jax 0.4.x meshes, the SPMD partitioner miscompiles partially-sharded
    values downstream of a flat concatenate (partial sums across replicated
    mesh axes are double-counted) while the update-slice chain partitions
    correctly. Pinned by the packed mesh golden test in
    tests/test_dryrun_small.py.
    """
    if layout is None:
        layout = layout_of(tree, lead=lead)
    leaves = jax.tree_util.tree_leaves(tree)
    lead_shape = tuple(leaves[0].shape[:lead]) if (leaves and lead) else ()
    # offsets are dynamic_update_slice start indices: int32 unless the plane
    # outgrows it (>2^31 elements in one dtype bucket — int64 needs x64 mode)
    int32_max = jnp.iinfo(jnp.int32).max
    if max(layout.bucket_sizes, default=0) > int32_max and not jax.config.jax_enable_x64:
        raise ValueError(
            f"packed plane bucket of {max(layout.bucket_sizes)} elements exceeds the "
            "int32 index range; enable jax_enable_x64 or run with packed=False"
        )
    idx_dtype = jnp.int64 if max(layout.bucket_sizes, default=0) > int32_max else jnp.int32
    zero_idx = (jnp.zeros((), idx_dtype),) * len(lead_shape)
    buffers = [
        jnp.zeros(lead_shape + (n,), jnp.dtype(d))
        for n, d in zip(layout.bucket_sizes, layout.bucket_dtypes)
    ]
    for slot, leaf in zip(layout.slots, leaves):
        flat = jnp.reshape(leaf, lead_shape + (slot.size,))
        buffers[slot.bucket] = jax.lax.dynamic_update_slice(
            buffers[slot.bucket], flat, zero_idx + (jnp.asarray(slot.offset, idx_dtype),)
        )
    return Packed(tuple(buffers), layout)


def unpack(packed: Packed):
    """Inverse of :func:`pack`: rebuild the pytree (padding lanes dropped)."""
    layout = packed.layout
    lead_shape = packed.lead_shape
    axis = len(lead_shape)
    leaves = []
    for slot in layout.slots:
        buf = packed.buffers[slot.bucket]
        seg = jax.lax.slice_in_dim(buf, slot.offset, slot.offset + slot.size, axis=axis)
        leaves.append(jnp.reshape(seg, lead_shape + slot.shape))
    return jax.tree_util.tree_unflatten(layout.treedef, leaves)


def view_leaf(packed: Packed, index: int):
    """Cheap view of one leaf (by flatten-order index) without a full unpack."""
    slot = packed.layout.slots[index]
    buf = packed.buffers[slot.bucket]
    axis = len(packed.lead_shape)
    seg = jax.lax.slice_in_dim(buf, slot.offset, slot.offset + slot.size, axis=axis)
    return jnp.reshape(seg, packed.lead_shape + slot.shape)


def packed_like(packed: Packed, fill=0.0, dtype=None) -> Packed:
    """A packed plane with the same layout, filled with ``fill`` (optionally
    retagged to ``dtype`` — see :meth:`Layout.with_dtype`)."""
    layout = packed.layout if dtype is None else packed.layout.with_dtype(dtype)
    lead = packed.lead_shape
    buffers = tuple(
        jnp.full(lead + (n,), fill, jnp.dtype(d))
        for n, d in zip(layout.bucket_sizes, layout.bucket_dtypes)
    )
    return Packed(buffers, layout)


def buffer_map(fn, *packeds: Packed, layout: Optional[Layout] = None) -> Packed:
    """Apply ``fn`` buffer-wise across packed planes (all must share bucket
    structure element-for-element — e.g. a plane and its f32 shadow). The
    result takes ``layout`` (default: the first plane's)."""
    first = packeds[0]
    out = tuple(fn(*bufs) for bufs in zip(*(p.buffers for p in packeds)))
    return Packed(out, layout or first.layout)


def leaf_segments(layout: Layout, bucket: int) -> Tuple[LeafSlot, ...]:
    """The slots living in ``bucket``, in offset order — the per-leaf walk
    for the rare boundary ops that are inherently per-leaf (top-k quantile
    thresholds), while the sweeps stay packed."""
    return tuple(s for s in layout.slots if s.bucket == bucket)


def read_windows(packed: Packed, indices: Tuple[int, ...]):
    """Materialize the leaves at ``indices`` (static slot indices) as views
    of the plane — the differentiable read :class:`ParamView` routes every
    access through.

    Forward: plain :func:`view_leaf` slices (XLA fuses them into the leaf
    consumers). Backward (the custom part): the leaf cotangents are
    scattered straight onto zeroed plane buffers with the same
    static-offset ``dynamic_update_slice`` chain :func:`pack` uses — the
    transpose of a window read *is* a pack, emitted once here by the
    packing layer instead of as a separate post-grad step in the engine.
    The custom rule is load-bearing twice over: JAX's default transpose of
    N slices is N full-plane ``pad`` + ``add`` ops, O(leaves · plane) work
    measured 7–50× slower than this chain at production leaf counts; and
    the natural O(plane) alternative (one zero-gap ``concatenate`` per
    bucket) both lowers poorly on CPU XLA (per-operand overhead, measured
    ~20× slower than the DUS chain) and walks into the jax-0.4.x SPMD
    partially-sharded-concat miscompile the pack docstring pins. Leaf
    cotangent *values* are placed verbatim with zero padding, so the
    gradient plane is bitwise identical to packing the gradient pytree.
    """
    return _read_windows(tuple(indices), packed.layout, packed.lead_shape, packed.buffers)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _read_windows(indices, layout: Layout, lead_shape, buffers):
    packed = Packed(buffers, layout)
    return tuple(view_leaf(packed, i) for i in indices)


def _read_windows_fwd(indices, layout, lead_shape, buffers):
    return _read_windows(indices, layout, lead_shape, buffers), None


def _read_windows_bwd(indices, layout, lead_shape, _res, cts):
    lead_shape = tuple(lead_shape)
    # mirror pack()'s index-dtype choice: int32 until a bucket outgrows it
    int32_max = jnp.iinfo(jnp.int32).max
    idx_dtype = jnp.int64 if max(layout.bucket_sizes, default=0) > int32_max else jnp.int32
    zero_idx = (jnp.zeros((), idx_dtype),) * len(lead_shape)
    bufs = [
        jnp.zeros(lead_shape + (n,), jnp.dtype(d))
        for n, d in zip(layout.bucket_sizes, layout.bucket_dtypes)
    ]
    # materialize the leaf cotangents before placing them: fused into the
    # plane writes, the embedding gather's transpose (a scatter-add) becomes
    # a scatter into the whole plane, which the TPU compiler takes minutes
    # over (a 1-layer h2o-danube round compiled for v5e: 371 s, 12 s with
    # the barrier)
    cts = jax.lax.optimization_barrier(tuple(cts))
    for i, ct in zip(indices, cts):
        slot = layout.slots[i]
        flat = jnp.reshape(ct, lead_shape + (slot.size,)).astype(bufs[slot.bucket].dtype)
        bufs[slot.bucket] = jax.lax.dynamic_update_slice(
            bufs[slot.bucket], flat, zero_idx + (jnp.asarray(slot.offset, idx_dtype),)
        )
    return (tuple(bufs),)


_read_windows.defvjp(_read_windows_fwd, _read_windows_bwd)


@jax.tree_util.register_pytree_node_class
class ParamView:
    """Lazy, dict-like, path-keyed view of a :class:`Packed` plane.

    Model code consumes parameters through ``view[key]`` / ``view.get`` /
    ``key in view`` (nested or ``"a/b/c"`` slash paths) exactly as it would
    a nested param dict, without ever importing :class:`Layout`: a leaf
    access materializes one :func:`view_leaf` window (a static slice XLA
    fuses into the consumer), a subtree access returns a nested view.
    Because the windows are slices of the plane buffers, differentiating a
    loss written against the view **with the plane as the primal** yields
    flat per-bucket cotangents directly — the gradient never exists as a
    pytree, so there is no per-leaf ``pack(grads)`` scatter chain.

    Registered as a pytree (flattening materializes the subtree's windows
    in layout order), so a view works as ``lax.scan`` xs: a stacked-layer
    subtree (leaves with a leading layer dim, the transformer's
    scan-over-blocks body) flattens to its ``(n, ...)`` windows, the scan
    slices them per iteration and rebuilds a *concrete* view (backed by the
    sliced arrays rather than the plane) with identical access semantics.
    """

    __slots__ = ("_packed", "_node", "_path")

    def __init__(self, packed: Optional[Packed] = None, _node=None, _path: str = ""):
        if _node is None:
            if packed is None:
                raise ValueError("ParamView needs a Packed plane (or an explicit node)")
            # lazy mode: the node tree holds leaf *indices* into the layout
            _node = jax.tree_util.tree_unflatten(
                packed.layout.treedef, list(range(packed.layout.num_leaves))
            )
        self._packed = packed
        self._node = _node
        self._path = _path

    # -- dict protocol ------------------------------------------------------
    def _leaf(self, node):
        if self._packed is not None:  # lazy: node is a slot index
            return read_windows(self._packed, (node,))[0]
        return node  # concrete: node is the materialized array

    def __getitem__(self, key):
        node, path = self._node, self._path
        for part in str(key).split("/"):
            if not (isinstance(node, dict) and part in node):
                raise KeyError(f"{path + '/' + part if path else part}")
            node = node[part]
            path = f"{path}/{part}" if path else part
        if isinstance(node, dict):
            return ParamView(self._packed, _node=node, _path=path)
        return self._leaf(node)

    def get(self, key, default=None):
        try:
            return self[key]
        except KeyError:
            return default

    def __contains__(self, key) -> bool:
        node = self._node
        for part in str(key).split("/"):
            if not (isinstance(node, dict) and part in node):
                return False
            node = node[part]
        return True

    def keys(self):
        return self._node.keys()

    def __iter__(self):
        return iter(self._node)

    def __len__(self) -> int:
        return len(self._node)

    def items(self):
        return ((k, self[k]) for k in self._node)

    def __repr__(self):
        mode = "plane" if self._packed is not None else "concrete"
        return f"ParamView({mode}, path={self._path or '/'!r}, keys={sorted(self._node)})"

    def materialize(self) -> "ParamView":
        """Concrete view of this subtree: every window read through ONE
        :func:`read_windows` site. The round engine materializes the
        worker-stacked view *before* vmapping the per-worker loss so the
        read's DUS-chain transpose sees the worker axis as a plain lead
        dim — under vmap the DUS batching rule degrades to select/iota
        masked writes (measured ~2× slower end-to-end).

        Differentiated code that touches many leaves should go through this
        (or through any whole-subtree flatten, e.g. ``lax.scan`` xs): each
        *lazy* leaf access is its own ``read_windows`` site, and every site
        contributes a full-plane cotangent that JAX then sums — fine for
        the handful of top-level reads a model makes (embeddings, norms,
        head), O(accesses · plane) if a training loss reads hundreds of
        leaves one by one."""
        leaves, aux = self.tree_flatten()
        return ParamView.tree_unflatten(aux, leaves)

    # -- pytree protocol (scan xs / tree.map / checkpointing) ---------------
    def tree_flatten(self):
        nodes, subdef = jax.tree_util.tree_flatten(self._node)
        if self._packed is not None:
            # one read_windows site for the whole subtree: its backward
            # assembles the bucket cotangents in a single pass
            return list(read_windows(self._packed, tuple(nodes))), (subdef, self._path)
        return nodes, (subdef, self._path)

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        subdef, path = aux
        return cls(None, _node=jax.tree_util.tree_unflatten(subdef, list(leaves)), _path=path)
