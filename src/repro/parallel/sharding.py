"""Sharding rules: logical parameter/activation axes → mesh axes.

The production mesh (launch/mesh.py) has physical axes ("pod","data","model")
/ ("data","model") per the assignment. Architectures differ in how much
within-worker sharding they need, so each ``ParallelPlan`` derives a
*logical* mesh over the same devices with axes:

    worker  — Local-SGD worker groups (the paper's m); slowest axes, so on
              the multi-pod mesh the worker boundary is the pod boundary and
              anchor traffic rides the slow inter-pod links (the exact
              communication the paper hides).
    fsdp    — parameter/optimizer sharding within a worker (ZeRO-3 style).
    tensor  — tensor parallelism within a worker.

Model code never names mesh axes directly: parameters carry *logical* axis
names ("embed", "ff", "heads", ...) and activations are constrained through
:func:`constrain`. Both are resolved through the rule table below, and both
become no-ops when no mesh context is active (pure-CPU unit tests).
"""
from __future__ import annotations

import contextlib
import threading
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from repro.config.base import ParallelPlan

# Logical axis -> logical mesh axes. ``None`` = replicated.
LOGICAL_RULES = {
    # parameter axes
    "worker": ("worker",),
    "embed": ("fsdp",),
    "embed_no_shard": (),
    "ff": ("tensor",),
    "heads": ("tensor",),
    "kv_heads": ("tensor",),
    "head_dim": (),
    "vocab": ("tensor",),
    "experts": ("fsdp",),
    "expert_ff": ("tensor",),
    "state": (),
    "conv": (),
    "lora": (),
    None: (),
    # activation axes
    "batch": ("fsdp",),
    "stacked_batch": ("worker", "fsdp"),  # serving: no worker axis, batch over all data axes
    "seq": (),
    "act_embed": (),
    "act_heads": ("tensor",),
    "act_kv_heads": ("tensor",),
    "act_ff": ("tensor",),
    "act_vocab": ("tensor",),
    "act_experts": ("fsdp",),
    "act_expert_ff": ("tensor",),
    "act_tokens": ("fsdp",),  # flattened (B·S) token dim in MoE dispatch
    # anchor model: identical across workers => additionally sharded over
    # the worker axis (ZeRO-3-style; see DESIGN.md §2).
    "anchor_embed": ("worker", "fsdp"),
    "anchor_experts": ("worker", "fsdp"),
    # packed parameter plane (repro.parallel.packing): flat 128-lane-aligned
    # buffers carry one logical axis instead of per-leaf axes. The per-worker
    # plane shards over fsdp; the anchor plane is identical across workers so
    # it shards over EVERY mesh axis (ZeRO-3 taken to its limit — each device
    # owns a disjoint 128-multiple slice of the plane, minimal memory and a
    # pure reduce-scatter boundary). Full sharding is also load-bearing on
    # jax 0.4.x: the SPMD partitioner miscompiles *partially* sharded
    # constraints downstream of the plane's concatenate (values multiply by
    # the product of the replicated axes — pinned by the packed mesh golden
    # test in tests/test_dryrun_small.py); fully-sharded and replicated
    # layouts have no replica bookkeeping to get wrong.
    "flat_param": ("fsdp",),
    "anchor_flat": ("worker", "fsdp", "tensor"),
}


class _Ctx(threading.local):
    def __init__(self):
        self.mesh: Optional[Mesh] = None
        self.rules: dict = LOGICAL_RULES


_CTX = _Ctx()


def make_auto_mesh(shape: Sequence[int], axes: Sequence[str]) -> Mesh:
    """``jax.make_mesh`` with every axis auto-sharded."""
    return jax.make_mesh(tuple(shape), tuple(axes), axis_types=(AxisType.Auto,) * len(axes))


def logical_mesh(production_mesh: Mesh, plan: ParallelPlan) -> Mesh:
    """Reshape the production mesh's devices into (worker, fsdp, tensor).

    The device order is preserved, so the worker axis occupies the slowest
    physical axes (pod, then data) — anchor collectives cross the slowest
    links, tensor-parallel collectives stay on the fastest.
    """
    devices = production_mesh.devices.reshape(-1)
    n = devices.size
    assert plan.num_devices == n, (plan, n)
    arr = devices.reshape(plan.workers, plan.fsdp, plan.tensor)
    return Mesh(arr, ("worker", "fsdp", "tensor"), axis_types=(AxisType.Auto,) * 3)


def spec_for(axes: Sequence[Optional[str]], rules: Optional[dict] = None) -> P:
    rules = rules or _CTX.rules
    parts = []
    for ax in axes:
        mapped = rules[ax]
        if len(mapped) == 0:
            parts.append(None)
        elif len(mapped) == 1:
            parts.append(mapped[0])
        else:
            parts.append(tuple(mapped))
    return P(*parts)


@contextlib.contextmanager
def mesh_context(mesh: Optional[Mesh], rules: Optional[dict] = None):
    prev_mesh, prev_rules = _CTX.mesh, _CTX.rules
    _CTX.mesh = mesh
    _CTX.rules = rules or LOGICAL_RULES
    try:
        yield
    finally:
        _CTX.mesh, _CTX.rules = prev_mesh, prev_rules


def current_mesh() -> Optional[Mesh]:
    return _CTX.mesh


def fit_spec(spec: P, shape: Sequence[int], mesh: Mesh) -> P:
    """Drop sharding on dims the mesh axes don't divide (replicate instead).

    Safety net for awkward dims (e.g. 28 attention heads on tp=16): jit
    argument shardings require divisibility, so non-dividing assignments are
    demoted to replication rather than failing the lowering."""
    parts = list(spec) + [None] * (len(shape) - len(spec))
    out = []
    for dim, part in zip(shape, parts):
        if part is None:
            out.append(None)
            continue
        axes = part if isinstance(part, tuple) else (part,)
        prod = 1
        for a in axes:
            prod *= mesh.shape[a]
        out.append(part if dim % prod == 0 else None)
    return P(*out)


def constrain(x, axes: Sequence[Optional[str]]):
    """with_sharding_constraint through the logical rule table (no-op off-mesh)."""
    mesh = _CTX.mesh
    if mesh is None:
        return x
    spec = fit_spec(spec_for(axes), x.shape, mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def sharding_for(axes: Sequence[Optional[str]], mesh: Optional[Mesh] = None) -> Optional[NamedSharding]:
    mesh = mesh or _CTX.mesh
    if mesh is None:
        return None
    return NamedSharding(mesh, spec_for(axes))


def tree_shardings(mesh: Mesh, axes_tree, prefix: Tuple[Optional[str], ...] = (), rules: Optional[dict] = None):
    """Map a tree of logical-axes tuples to NamedShardings (optionally
    prepending ``prefix`` axes, e.g. ("worker",) for stacked states)."""

    def one(axes):
        return NamedSharding(mesh, spec_for(tuple(prefix) + tuple(axes), rules))

    return jax.tree.map(one, axes_tree, is_leaf=lambda t: isinstance(t, tuple))


def anchor_axes(axes_tree):
    """Axes for the anchor model: same as params but the fsdp-sharded dim is
    additionally sharded over the worker axis (identical across workers)."""

    def one(axes):
        out = []
        for ax in axes:
            if ax == "embed":
                out.append("anchor_embed")
            elif ax == "experts":
                out.append("anchor_experts")
            else:
                out.append(ax)
        return tuple(out)

    return jax.tree.map(one, axes_tree, is_leaf=lambda t: isinstance(t, tuple))
