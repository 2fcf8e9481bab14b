"""Dry-run specifications: ShapeDtypeStruct stand-ins + NamedShardings for
every (architecture × input shape), with zero device allocation.

Train shapes lower the Overlap-Local-SGD round program (τ local steps +
pullback + anchor sync); decode shapes lower ``serve_step`` (one token vs a
seq_len cache); prefill lowers the full-sequence cache-building forward.

Sharding regimes:
* training — worker-stacked state; params P(worker, …param axes…)
* serving  — single model; request batch sharded over (worker×fsdp) i.e.
  data-parallel serving replicas when fsdp=1, one big sharded model when
  fsdp>1. long_500k (batch=1) shards the KV/window cache's *sequence* dim
  over those axes instead.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.config.base import AlgoConfig, ArchConfig, InputShape, ModelConfig, OptimizerConfig, ParallelPlan
from repro.core.strategy import AlgoVars, CommStrategy, PACKED_STACKED_AXES, _stacked_axes
from repro.models import transformer as T
from repro.optim import optimizers as opt_mod
from repro.parallel import offload as off
from repro.parallel import packing as pk
from repro.parallel import sharding as sh
from repro.training.train_state import TrainState

# rule tables ---------------------------------------------------------------

TRAIN_RULES = dict(sh.LOGICAL_RULES)

SERVE_RULES = dict(sh.LOGICAL_RULES)
SERVE_RULES["batch"] = ("worker", "fsdp")
SERVE_RULES["cache_seq"] = ()

LONG_RULES = dict(sh.LOGICAL_RULES)
LONG_RULES["batch"] = ()
LONG_RULES["cache_seq"] = ("worker", "fsdp")


def rules_for(shape: InputShape) -> dict:
    if shape.mode == "train":
        return TRAIN_RULES
    if shape.name == "long_500k":
        return LONG_RULES
    return SERVE_RULES


def optimized_rules(shape: InputShape) -> dict:
    """Beyond-paper §Perf variant (see EXPERIMENTS.md §Perf).

    Decode: weight-stationary pure-TP — model dims sharded over the full
    (fsdp × tensor) sub-mesh, embed replicated, KV-cache sequence sharded
    (flash-decoding). Eliminates the per-token ZeRO weight all-gathers that
    dominate the baseline's collective term (measured 29× collective-bytes
    reduction on mistral-large decode_32k).
    """
    base = rules_for(shape)
    if shape.mode != "decode":
        return base
    out = dict(base)
    out.update(
        {
            "batch": ("worker",) if shape.global_batch > 1 else (),
            "embed": (),
            "anchor_embed": (),
            "ff": ("fsdp", "tensor"),
            "act_ff": ("fsdp", "tensor"),
            "heads": ("fsdp", "tensor"),
            "kv_heads": ("fsdp", "tensor"),
            "act_heads": ("fsdp", "tensor"),
            "vocab": ("fsdp", "tensor"),
            "act_vocab": ("fsdp", "tensor"),
            "cache_seq": ("fsdp", "tensor"),
            "act_tokens": (),
        }
    )
    return out


# production training strategy ----------------------------------------------


def default_train_strategy(plan: ParallelPlan) -> str:
    """The production default: the paper's algorithm — except at w=1
    (arctic/deepseek single-pod), where Overlap-Local-SGD degenerates (no
    second replica to average with) and the honest program is the round
    WITHOUT anchor state. See DESIGN.md §Arch-applicability."""
    return "overlap_local_sgd" if plan.workers > 1 else "local_sgd"


def train_algo_config(
    plan: ParallelPlan,
    strategy: Optional[str] = None,
    tau: int = 2,
    topology: Optional[str] = None,
    offload: bool = False,
    offload_chunk_mb: Optional[float] = None,
) -> AlgoConfig:
    """The AlgoConfig the production lowering trains with (dry-run and cost
    probes resolve it through ``repro.api.resolve_strategy``, the exact
    chain ``Experiment`` uses). ``topology`` selects the gossip mixing-matrix
    family for ``gossip_pushsum`` (fixed-topology registry names like
    ``gossip_ring`` override it); other strategies ignore it. ``offload``
    turns on the host-offloaded state plane (DESIGN.md §9)."""
    return AlgoConfig(
        name=strategy or default_train_strategy(plan),
        tau=tau,
        alpha=0.6,
        anchor_beta=0.7,
        topology=topology or "full",
        offload=offload,
        offload_chunk_mb=float(offload_chunk_mb if offload_chunk_mb is not None else off.DEFAULT_CHUNK_MB),
    )


# model variant -------------------------------------------------------------


def model_for(arch: ArchConfig, shape: InputShape) -> Tuple[ModelConfig, str]:
    """Returns (model config, variant label). long_500k on full-attention
    archs runs the labelled sliding-window variant (DESIGN.md policy)."""
    cfg = arch.model
    if shape.name == "long_500k" and arch.long_context_policy == "swa_variant":
        if cfg.attention is not None and cfg.attention.sliding_window is None:
            att = dataclasses.replace(cfg.attention, sliding_window=arch.swa_variant_window)
            return dataclasses.replace(cfg, attention=att), "swa"
    return cfg, "faithful"


# input specs ---------------------------------------------------------------


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(tuple(int(s) for s in shape), dtype)


def train_batch_specs(cfg: ModelConfig, shape: InputShape, plan: ParallelPlan, tau: int):
    m = plan.workers
    assert shape.global_batch % m == 0, (shape.global_batch, m)
    b = shape.global_batch // m
    s = shape.seq_len
    fe = cfg.frontend
    if fe is not None and fe.kind == "audio":
        toks = _sds((tau, m, b, fe.num_codebooks, s), jnp.int32)
        return dict(tokens=toks, targets=toks)
    if fe is not None and fe.kind == "vision":
        s_img = fe.tokens_per_item
        s_text = s - s_img
        return dict(
            tokens=_sds((tau, m, b, s_text), jnp.int32),
            image_embeds=_sds((tau, m, b, s_img, fe.embed_dim), jnp.bfloat16),
            targets=_sds((tau, m, b, s_text), jnp.int32),
        )
    toks = _sds((tau, m, b, s), jnp.int32)
    return dict(tokens=toks, targets=toks)


def batch_shardings(batch_specs, mesh: Mesh, rules: dict):
    def one(s):
        # (tau, m, b, ...) -> P(None, worker, fsdp, ...)
        extra = (None,) * (len(s.shape) - 3)
        return NamedSharding(mesh, sh.fit_spec(P(None, "worker", "fsdp", *extra), s.shape, mesh))

    return jax.tree.map(one, batch_specs)


# train state specs ---------------------------------------------------------


def _axes_tree_shardings(ax_tree, sds_tree, mesh: Mesh, rules: dict):
    """Map a logical-axes tree (leaves = axes tuples, mirroring ``sds_tree``)
    to NamedShardings. A ``None`` node — the whole tree or any subtree —
    replicates the corresponding specs. An axes tuple facing a *subtree* of
    specs (e.g. the packed plane's ``("anchor_flat",)`` facing a ``Packed``
    of flat buffers) applies to every leaf of that subtree."""
    replicate = lambda sub: jax.tree.map(lambda s: NamedSharding(mesh, P()), sub)
    if ax_tree is None:
        return replicate(sds_tree)

    def one(ax, sub):
        if ax is None:
            return replicate(sub)
        return jax.tree.map(
            lambda s: NamedSharding(mesh, sh.fit_spec(sh.spec_for(ax, rules), s.shape, mesh)), sub
        )

    is_leaf = lambda t: t is None or (
        isinstance(t, tuple) and all(isinstance(e, (str, type(None))) for e in t)
    )
    return jax.tree.map(one, ax_tree, sds_tree, is_leaf=is_leaf)


def opt_state_specs(optimizer, strategy_packed: bool, x_sds, x_sh, mesh: Mesh, rules: dict):
    """Abstract optimizer state + shardings, mirroring the layout
    ``make_train_state`` actually builds.

    Packed (packed strategy + packed-capable optimizer): the state is flat
    worker-stacked buffers — one spec per dtype bucket under the
    ``("worker", "flat_param")`` rule (worker axis stacked, plane sharded
    over fsdp within a worker) instead of one per leaf; AdamW's f32 moment
    buckets follow the same rule and its single scalar count replicates.
    Per-leaf: momentum/moments mirror the stacked-parameter shardings
    leaf-for-leaf; the per-worker (m,) Adam count replicates.
    """
    packed = strategy_packed and opt_mod.packed_capable(optimizer)
    if packed:
        # plane-resident x_sds is already the packed plane; a per-leaf x_sds
        # (packed strategy with per-leaf x specs) packs abstractly here
        opt_sds = jax.eval_shape(
            lambda xs: optimizer.init_packed(xs if isinstance(xs, pk.Packed) else pk.pack(xs, lead=1)),
            x_sds,
        )

        def one(s):
            if len(s.shape) == 0:  # the shared scalar count
                return NamedSharding(mesh, P())
            return NamedSharding(mesh, sh.fit_spec(sh.spec_for(PACKED_STACKED_AXES, rules), s.shape, mesh))

        return opt_sds, jax.tree.map(one, opt_sds)
    opt_sds = jax.eval_shape(lambda xs: jax.vmap(optimizer.init)(xs), x_sds)
    if isinstance(opt_sds, opt_mod.AdamState):
        opt_sh = opt_mod.AdamState(mu=x_sh, nu=x_sh, count=NamedSharding(mesh, P()))
    else:
        opt_sh = opt_mod.SGDState(momentum=x_sh)
    return opt_sds, opt_sh


def strategy_state_specs(cfg: ModelConfig, plan: ParallelPlan, strategy: CommStrategy, mesh: Mesh, rules: dict, packed_x: Optional[bool] = None):
    """Abstract ``(x, vars, inflight)`` + shardings for one ``boundary_round``
    of a two-phase :class:`CommStrategy` — the boundary slice of
    :func:`train_state_specs`, shared with the cost probes
    (``launch/costprobe.py``) so the boundary they time is exactly the one
    the production round program runs.

    ``packed_x=None`` follows the strategy's ``packed`` flag (plane-resident
    x: one ``("worker", "flat_param")`` spec per dtype bucket); pass ``False``
    to keep per-leaf x specs (e.g. under a non-packed-capable optimizer).
    """
    params_sds, axes = T.init_model(cfg, jax.random.PRNGKey(0), abstract=True)
    m = plan.workers
    x_sds = jax.tree.map(lambda s: _sds((m,) + tuple(s.shape), s.dtype), params_sds)
    x_sh = _axes_tree_shardings(_stacked_axes(axes), x_sds, mesh, rules)
    if packed_x is None:
        packed_x = bool(getattr(strategy, "packed", False))
    if packed_x:
        # plane-resident state: x is the worker-stacked Packed plane — one
        # ("worker", "flat_param") spec per dtype bucket instead of one per
        # leaf, mirroring make_train_state
        x_sds = jax.eval_shape(lambda xs: pk.pack(xs, lead=1), x_sds)
        x_sh = jax.tree.map(
            lambda s: NamedSharding(mesh, sh.fit_spec(sh.spec_for(PACKED_STACKED_AXES, rules), s.shape, mesh)),
            x_sds,
        )
    vars_sds = jax.eval_shape(lambda xs: strategy.init_vars(xs, None), x_sds)
    inflight_sds = jax.eval_shape(lambda xs, vs: strategy.init_inflight(xs, vs, None), x_sds, vars_sds)
    vars_axes, inflight_axes = strategy.state_axes(axes)
    vars_sh = _axes_tree_shardings(vars_axes, vars_sds, mesh, rules)
    inflight_sh = _axes_tree_shardings(inflight_axes, inflight_sds, mesh, rules)
    return (x_sds, x_sh), (vars_sds, vars_sh), (inflight_sds, inflight_sh), axes


def _offload_state_shardings(host_sds, dev_sds, dev_sh, mesh: Mesh):
    """Shardings for a host-offloaded state slot: chunked HostPlane leaves
    (one extra leading chunk axis vs their device form) keep the device
    plane's spec per chunk with the chunk axis replicated, placed in the
    backend's host memory space when it has one (``pinned_host`` on TPU —
    advisory on single-memory backends, where the spec alone is emitted).
    Untouched leaves (scalars, masks) keep their device shardings."""
    hk = off.host_memory_kind()
    kw = {"memory_kind": hk} if hk else {}
    h_leaves, tdef = jax.tree_util.tree_flatten(host_sds)
    d_leaves = jax.tree_util.tree_leaves(dev_sds)
    s_leaves = jax.tree_util.tree_leaves(dev_sh)
    out = []
    for h, d, s in zip(h_leaves, d_leaves, s_leaves):
        if len(h.shape) == len(d.shape) + 1:  # chunked: (C,) + lead + (c,)
            spec = sh.fit_spec(P(None, *tuple(s.spec)), h.shape, mesh)
            out.append(NamedSharding(mesh, spec, **kw))
        else:
            out.append(s)
    return jax.tree_util.tree_unflatten(tdef, out)


def _offload_slot(slot_sds, slot_sh, plan: off.OffloadPlan, mesh: Mesh):
    """(sds, shardings) of one state slot in its host-offloaded form."""
    if slot_sds is None:
        return None, None
    host_sds = jax.eval_shape(lambda t: off.tree_offload(t, plan), slot_sds)
    return host_sds, _offload_state_shardings(host_sds, slot_sds, slot_sh, mesh)


def membership_specs(plan: ParallelPlan, mesh: Mesh):
    """Abstract :class:`repro.fault.membership.Membership` + shardings: two
    (m,) f32 vectors, replicated — every device needs the full mask for the
    masked boundary's where/weighted-sum, and at a few bytes per worker the
    vectors are far below any useful shard granularity."""
    from repro.fault.membership import Membership

    m_sds = Membership(mask=_sds((plan.workers,), jnp.float32), weights=_sds((plan.workers,), jnp.float32))
    rep = NamedSharding(mesh, P())
    return m_sds, Membership(mask=rep, weights=rep)


def train_state_specs(cfg: ModelConfig, plan: ParallelPlan, algo, optimizer, mesh: Mesh, rules: dict, with_membership: bool = False):
    """Abstract TrainState + shardings for ``algo`` — a two-phase
    ``CommStrategy`` (whose ``state_axes`` hook supplies the vars/inflight
    layouts, including the carried anchor collective) or, for the oracle
    tests only, a legacy deprecated ``Algorithm``.

    ``with_membership`` adds the degraded-boundary membership slot
    (DESIGN.md §7) to the state specs — the fault-injection dry-run lowers
    the masked round program; the default keeps the baseline fully-live
    state (``membership=None``), whose program is pinned by the budgets."""
    strategy_packed = isinstance(algo, CommStrategy) and getattr(algo, "packed", False)
    if isinstance(algo, CommStrategy):
        plane_resident = strategy_packed and opt_mod.packed_capable(optimizer)
        (x_sds, x_sh), (vars_sds, vars_sh), (inflight_sds, inflight_sh), axes = strategy_state_specs(
            cfg, plan, algo, mesh, rules, packed_x=plane_resident
        )
        opt_sds, opt_sh = opt_state_specs(optimizer, strategy_packed, x_sds, x_sh, mesh, rules)
        if (
            plane_resident
            and bool(getattr(algo.cfg, "offload", False))
            and opt_mod.offload_capable(optimizer)
        ):
            # AlgoConfig.offload: between boundaries the opt state and
            # anchor/inflight buckets are chunked HostPlanes — mirror
            # make_train_state so the lowered round program's input state
            # is the host-resident form (DESIGN.md §9)
            chunk_mb = float(getattr(algo.cfg, "offload_chunk_mb", off.DEFAULT_CHUNK_MB))
            oplan = off.OffloadPlan.for_layout(x_sds.layout, chunk_mb)
            opt_sds, opt_sh = _offload_slot(opt_sds, opt_sh, oplan, mesh)
            vars_sds, vars_sh = _offload_slot(vars_sds, vars_sh, oplan, mesh)
            inflight_sds, inflight_sh = _offload_slot(inflight_sds, inflight_sh, oplan, mesh)
    else:
        params_sds, axes = T.init_model(cfg, jax.random.PRNGKey(0), abstract=True)
        m = plan.workers
        x_sds = jax.tree.map(lambda s: _sds((m,) + tuple(s.shape), s.dtype), params_sds)
        x_sh = _axes_tree_shardings(_stacked_axes(axes), x_sds, mesh, rules)
        opt_sds, opt_sh = opt_state_specs(optimizer, False, x_sds, x_sh, mesh, rules)
        z_sds = v_sds = None
        if algo.needs_anchor:
            z_sds = params_sds
            if getattr(algo.cfg, "anchor_beta", 0) > 0 and algo.name == "overlap_local_sgd":
                v_sds = params_sds
        extra = None
        if algo.name == "cocod":
            extra = x_sds
        vars_sds = AlgoVars(z=z_sds, v=v_sds, extra=extra)
        inflight_sds = None
        anchor_ax = sh.anchor_axes(axes)
        z_sh = _axes_tree_shardings(anchor_ax, params_sds, mesh, rules)
        vars_sh = AlgoVars(
            z=z_sh if z_sds is not None else None,
            v=z_sh if v_sds is not None else None,
            extra=x_sh if extra is not None else None,
        )
        inflight_sh = None

    mem_sds = mem_sh = None
    if with_membership:
        mem_sds, mem_sh = membership_specs(plan, mesh)
    state_sds = TrainState(
        x=x_sds, opt=opt_sds, vars=vars_sds, step=_sds((), jnp.int32), inflight=inflight_sds, membership=mem_sds
    )
    state_sh = TrainState(
        x=x_sh, opt=opt_sh, vars=vars_sh, step=NamedSharding(mesh, P()), inflight=inflight_sh, membership=mem_sh
    )
    return state_sds, state_sh, axes


# serving specs -------------------------------------------------------------


def serve_param_specs(cfg: ModelConfig, mesh: Mesh, rules: dict):
    params_sds, axes = T.init_model(cfg, jax.random.PRNGKey(0), abstract=True)
    return params_sds, _axes_tree_shardings(axes, params_sds, mesh, rules), axes


def prefill_input_specs(cfg: ModelConfig, shape: InputShape):
    b, s = shape.global_batch, shape.seq_len
    fe = cfg.frontend
    if fe is not None and fe.kind == "audio":
        return dict(tokens=_sds((b, fe.num_codebooks, s), jnp.int32))
    if fe is not None and fe.kind == "vision":
        s_img = fe.tokens_per_item
        return dict(
            tokens=_sds((b, s - s_img), jnp.int32),
            image_embeds=_sds((b, s_img, fe.embed_dim), jnp.bfloat16),
        )
    return dict(tokens=_sds((b, s), jnp.int32))


def prefill_input_shardings(specs, mesh: Mesh, rules: dict):
    batch_axes = rules["batch"]
    bspec = tuple(batch_axes) if batch_axes else None

    def one(s):
        return NamedSharding(mesh, sh.fit_spec(P(bspec, *(None,) * (len(s.shape) - 1)), s.shape, mesh))

    return jax.tree.map(one, specs)


def decode_cache_specs(cfg: ModelConfig, shape: InputShape, mesh: Mesh, rules: dict):
    """Abstract caches (warm, length seq_len) + shardings per segment kind."""
    caches = jax.eval_shape(
        lambda: T.init_caches(cfg, shape.global_batch, shape.seq_len, dtype=cfg.param_dtype)
    )
    return cache_tree_shardings(caches, mesh, rules)


def cache_tree_shardings(caches, mesh: Mesh, rules: dict):
    batch_axes = rules["batch"]
    seq_axes = rules["cache_seq"]
    b_ax = tuple(batch_axes) if batch_axes else None
    s_ax = tuple(seq_axes) if seq_axes else None
    t_ax = "tensor"

    def spec_for_leaf(path_keys, s):
        name = path_keys[-1]
        nd = len(s.shape)
        if name in ("k", "v"):  # (n?, B, L, kvh, hd)
            lead = (None,) * (nd - 4)
            return P(*lead, b_ax, s_ax, t_ax, None)
        if name in ("ckv", "krope"):  # (n?, B, L, r)
            lead = (None,) * (nd - 3)
            return P(*lead, b_ax, s_ax, None)
        if name in ("pool_k", "pool_v"):  # (n?, P, kvh, page, hd) — shared pool:
            lead = (None,) * (nd - 4)  # pages data-sharded, heads tensor-sharded
            return P(*lead, b_ax, t_ax, None, None)
        if name in ("pool_ckv", "pool_krope"):  # (n?, P, page, r)
            lead = (None,) * (nd - 3)
            return P(*lead, b_ax, None, None)
        if name == "ssd_state":  # (n?, B, H, P, N)
            lead = (None,) * (nd - 4)
            return P(*lead, b_ax, t_ax, None, None)
        if name == "wkv_state":  # (n?, B, H, N, P)
            lead = (None,) * (nd - 4)
            return P(*lead, b_ax, t_ax, None, None)
        if name == "conv_state":  # (n?, B, w, conv_dim)
            lead = (None,) * (nd - 3)
            return P(*lead, b_ax, None, t_ax)
        if name in ("tm_last", "cm_last"):  # (n?, B, d)
            lead = (None,) * (nd - 2)
            return P(*lead, b_ax, None)
        return P(*(None,) * nd)  # positions, pos

    flat, treedef = jax.tree_util.tree_flatten_with_path(caches)
    shardings = []
    for path, leaf in flat:
        keys = [getattr(p, "key", getattr(p, "idx", None)) for p in path]
        spec = sh.fit_spec(spec_for_leaf([str(k) for k in keys], leaf), leaf.shape, mesh)
        shardings.append(NamedSharding(mesh, spec))
    _, tdef = jax.tree_util.tree_flatten(caches)
    return caches, jax.tree_util.tree_unflatten(tdef, shardings)


def paged_decode_specs(cfg: ModelConfig, shape: InputShape, mesh: Mesh, rules: dict, page_size: int = 128):
    """Abstract paged pools + page-table/length operands for the paged decode
    lowering (DESIGN.md §10). Pool sized for full residency of every slot
    (one trash page extra); pages spread over the data axes, heads over
    tensor. Page tables and lengths are tiny int32 host-produced operands —
    replicated."""
    from repro.serving.paged_cache import init_paged_pools, pages_for

    maxp = pages_for(shape.seq_len, page_size)
    num_pages = shape.global_batch * maxp + 1
    pools_sds = jax.eval_shape(
        lambda: init_paged_pools(cfg, num_pages, page_size, cfg.param_dtype)
    )
    pools_sds, pools_sh = cache_tree_shardings(pools_sds, mesh, rules)
    pt_sds = _sds((shape.global_batch, maxp), jnp.int32)
    len_sds = _sds((shape.global_batch,), jnp.int32)
    rep = NamedSharding(mesh, P())
    info = dict(
        page_size=page_size,
        num_pages=num_pages,
        max_pages_per_slot=maxp,
        pool_bytes=int(sum(l.size * l.dtype.itemsize for l in jax.tree.leaves(pools_sds))),
    )
    return pools_sds, pools_sh, pt_sds, len_sds, rep, info


def decode_token_specs(cfg: ModelConfig, shape: InputShape, mesh: Mesh, rules: dict):
    b = shape.global_batch
    fe = cfg.frontend
    batch_axes = rules["batch"]
    b_ax = tuple(batch_axes) if batch_axes else None
    if fe is not None and fe.kind == "audio":
        toks = _sds((b, fe.num_codebooks, 1), jnp.int32)
        shd = NamedSharding(mesh, sh.fit_spec(P(b_ax, None, None), toks.shape, mesh))
    else:
        toks = _sds((b, 1), jnp.int32)
        shd = NamedSharding(mesh, sh.fit_spec(P(b_ax, None), toks.shape, mesh))
    return toks, shd
