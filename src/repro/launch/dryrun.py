import os

# The dry-run meshes are host (CPU) placeholder devices: pin the CPU platform
# so the tool never reaches for an attached accelerator.
os.environ["JAX_PLATFORMS"] = "cpu"
# Append rather than overwrite: the 8-device test subprocesses (and any
# caller that already pinned a host-device count) keep their value, a
# pre-existing unrelated XLA_FLAGS (e.g. --xla_dump_to) is preserved, and
# the production CLI path still gets the 512 placeholder devices.
if "--xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=512"
    ).strip()

"""Multi-pod dry-run: lower + compile every (architecture × input shape) on
the production meshes, print memory/cost analysis, and dump roofline terms.

This is the proof that the distribution config is coherent without real
hardware: a sharding mismatch, compile-time OOM, or unsupported collective
fails here. The 512 placeholder host devices exist ONLY in this process
(the XLA flag above must precede every other import).

Train shapes lower the NATIVE round program — the strategy is resolved
through ``repro.api.resolve_strategy`` (the exact chain ``Experiment``
uses), so the program being cost-modelled is the plane-resident program
training runs: ``TrainState.x`` is the worker-stacked ``Packed`` parameter
plane, optimizer state lives in flat dtype buckets, and the strategy's
launched-but-unconsumed collective rides in the ``inflight`` slot through
``boundary_round``. Any registered strategy lowers (``--strategy``:
overlap/local/sync-SGD, DaSGD ``delayed_avg``, LOSCAR ``sparse_anchor``,
…); the default follows ``specs.default_train_strategy`` (w=1 degenerates
to local_sgd — DESIGN.md §Arch-applicability).

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-7b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--out experiments/dryrun]
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-7b --shape train_4k --strategy delayed_avg
"""

import argparse  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.api import resolve_strategy  # noqa: E402
from repro.config import INPUT_SHAPES, get_arch, list_archs  # noqa: E402
from repro.core.strategy import STRATEGIES  # noqa: E402
from repro.launch import roofline as rl  # noqa: E402
from repro.launch import specs  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.models import transformer as T  # noqa: E402
from repro.optim import schedules, sgd  # noqa: E402
from repro.parallel import logical_mesh, mesh_context  # noqa: E402
from repro.parallel import offload as off  # noqa: E402
from repro.parallel.packing import Packed  # noqa: E402
from repro.serving.engine import decode_step, paged_step  # noqa: E402
from repro.serving.paged_cache import paged_supported  # noqa: E402
from repro.training.train_loop import make_round_step  # noqa: E402


def _is_plane(t) -> bool:
    return isinstance(t, (Packed, off.HostPlane))


def _slot_bytes(tree) -> tuple:
    """(device_bytes, host_bytes) of one state slot: HostPlane chunks are
    host-resident between boundaries, everything else (Packed buffers,
    raw arrays/scalars) is device-resident."""
    dev = host = 0
    for leaf in jax.tree.leaves(tree, is_leaf=_is_plane):
        if isinstance(leaf, off.HostPlane):
            host += leaf.nbytes
        elif isinstance(leaf, Packed):
            dev += leaf.nbytes
        else:
            dev += int(np.prod(leaf.shape) * leaf.dtype.itemsize)
    return int(dev), int(host)


def plane_meta(state_sds) -> dict:
    """Machine-readable description of the packed-plane state in the AOT
    specs — recorded so dry-run JSONs are comparable with the
    ``boundary/*`` / ``localstep/*`` rows of BENCH_kernels.json (which time
    the same planes standalone)."""
    x = state_sds.x
    if not isinstance(x, Packed):
        return dict(plane_resident=False)
    opt_leaves = [s for s in jax.tree.leaves(state_sds.opt) if len(s.shape) > 0]
    inflight_bytes = sum(
        p.nbytes for p in jax.tree.leaves(
            state_sds.inflight, is_leaf=_is_plane
        ) if _is_plane(p)
    )
    # true residency split across the whole plane state (x + opt + vars +
    # inflight): offloaded runs report their HostPlane bytes as host-resident
    # (logical totals; the per-device split lives in the offload block)
    dev = host = 0
    for slot in (state_sds.x, state_sds.opt, state_sds.vars, state_sds.inflight):
        d, h = _slot_bytes(slot)
        dev += d
        host += h
    return dict(
        plane_resident=True,
        num_leaves=x.layout.num_leaves,
        buckets=[
            dict(dtype=d, elements=int(n))
            for d, n in zip(x.layout.bucket_dtypes, x.layout.bucket_sizes)
        ],
        x_buffer_bytes=int(x.nbytes),
        opt_buffer_bytes=int(sum(np.prod(s.shape) * s.dtype.itemsize for s in opt_leaves)),
        inflight_buffer_bytes=int(inflight_bytes),
        device_bytes=dev,
        host_bytes=host,
    )


def _host_bytes_per_device(slot_sds, slot_sh) -> int:
    """Per-device host-resident bytes of one offloaded state slot, from the
    AOT shardings (`shard_shape` of every HostPlane chunk)."""
    if slot_sds is None:
        return 0
    h_sds = [t for t in jax.tree.leaves(slot_sds, is_leaf=_is_plane) if isinstance(t, off.HostPlane)]
    h_sh = [t for t in jax.tree.leaves(slot_sh, is_leaf=_is_plane) if isinstance(t, off.HostPlane)]
    total = 0
    for hp, hs in zip(h_sds, h_sh):
        for chunk, sharding in zip(hp.chunks, hs.chunks):
            total += int(np.prod(sharding.shard_shape(chunk.shape)) * chunk.dtype.itemsize)
    return total


def _staging_bytes_per_device(slot_sds, slot_sh) -> int:
    """Per-device double-buffer staging footprint: two in-flight device
    chunks (applied + prefetched) per state plane per bucket — the bound the
    jaxpr regression in tests/test_offload.py pins."""
    h_sds = [t for t in jax.tree.leaves(slot_sds, is_leaf=_is_plane) if isinstance(t, off.HostPlane)]
    h_sh = [t for t in jax.tree.leaves(slot_sh, is_leaf=_is_plane) if isinstance(t, off.HostPlane)]
    total = 0
    for hp, hs in zip(h_sds, h_sh):
        for chunk, sharding in zip(hp.chunks, hs.chunks):
            ss = sharding.shard_shape(chunk.shape)
            total += 2 * int(np.prod(ss[1:]) * chunk.dtype.itemsize)
    return total


def _offload_meta(state_sds, state_sh, tau: int) -> dict:
    """Static offload-plan block for the dry-run JSON: what lives on the
    host between boundaries (per device), the chunk grid the stream walks,
    and the bytes it must move per round. Bandwidth/overlap terms are
    attached later by run_pair (measured, not static)."""
    plan = off.plan_of(state_sds.opt)
    if plan is None:
        return dict(enabled=False, reason="optimizer/plane not offload-capable")
    layout = state_sds.x.layout
    per_slot = dict(
        opt=_host_bytes_per_device(state_sds.opt, state_sh.opt),
        vars=_host_bytes_per_device(state_sds.vars, state_sh.vars),
        inflight=_host_bytes_per_device(state_sds.inflight, state_sh.inflight),
    )
    # opt state round-trips (H2D + D2H) once per local step inside the
    # τ-scan; anchor-shaped slots round-trip once per round at the boundary
    stream_pd = tau * 2 * per_slot["opt"] + 2 * (per_slot["vars"] + per_slot["inflight"])
    return dict(
        enabled=True,
        memory_kind=off.host_memory_kind() or "unpinned_host",
        buckets=[
            dict(dtype=d, elements=int(n), chunk_elems=int(c), num_chunks=int(k))
            for d, n, c, k in zip(
                layout.bucket_dtypes, layout.bucket_sizes, plan.chunk_elems, plan.num_chunks
            )
        ],
        host_bytes_per_device=int(sum(per_slot.values())),
        host_bytes_per_device_by_slot=per_slot,
        staging_bytes_per_device=_staging_bytes_per_device(state_sds.opt, state_sh.opt),
        stream_bytes_per_round_per_device=int(stream_pd),
    )


def _maybe_enable_x64(cfg) -> None:
    """>100B-param archs overflow the packed plane's int32 index range
    (>2^31 elements in one dtype bucket); pack() then requires int64
    indices. Flipped process-wide — the dry-run CLI owns its process."""
    if jax.config.jax_enable_x64:
        return
    sds, _ = T.init_model(cfg, jax.random.PRNGKey(0), abstract=True)
    per = {}
    for s in jax.tree.leaves(sds):
        per[str(s.dtype)] = per.get(str(s.dtype), 0) + int(np.prod(s.shape))
    if max(per.values(), default=0) > np.iinfo(np.int32).max:
        jax.config.update("jax_enable_x64", True)
        print("   (jax_enable_x64: a packed bucket exceeds the int32 index range)")


def lower_pair(arch_name: str, shape_name: str, multi_pod: bool = False, tau: int = 2, opt: bool = False, strategy: str = None, faults: str = None, topology: str = None, offload: bool = False):
    """Returns (lowered, meta) for one (arch × shape × mesh).

    ``faults`` (a :meth:`repro.fault.plan.FaultPlan.parse` spec) lowers the
    *membership-carrying* round program for train shapes: ``TrainState``
    gains the replicated live-mask/weights vectors and the boundary traces
    its masked form (DESIGN.md §7). Without it the baseline fully-live
    program — the one pinned by the collective budgets — is lowered."""
    arch = get_arch(arch_name)
    shape = INPUT_SHAPES[shape_name]
    if not arch.supports(shape):
        raise ValueError(f"{arch_name} skips {shape_name} (policy {arch.long_context_policy})")
    prod_mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = prod_mesh.devices.size
    plan = arch.plan_for(shape.name, n_dev)
    lmesh = logical_mesh(prod_mesh, plan)
    rules = specs.optimized_rules(shape) if opt else specs.rules_for(shape)
    cfg, variant = specs.model_for(arch, shape)
    if opt:
        variant = variant + "+opt"

    meta = dict(
        arch=arch_name,
        shape=shape_name,
        mesh="2x16x16" if multi_pod else "16x16",
        n_devices=n_dev,
        plan=dict(workers=plan.workers, fsdp=plan.fsdp, tensor=plan.tensor),
        variant=variant,
    )
    if faults is not None:
        meta["faults"] = faults

    with mesh_context(lmesh, rules):
        if shape.mode == "train":
            # native two-phase lowering: the same AlgoConfig → make_strategy
            # resolution Experiment.build() runs (w=1 degenerates to
            # local_sgd — see DESIGN.md §Arch-applicability)
            _maybe_enable_x64(cfg)
            strat = resolve_strategy(
                specs.train_algo_config(plan, strategy, tau, topology=topology, offload=offload)
            )
            tau = strat.tau  # sync-style strategies pin τ = 1
            meta["strategy"] = strat.name
            meta["tau"] = tau
            if getattr(strat, "topo_name", None) is not None:
                meta["topology"] = strat.topo_name
            optimizer = sgd(momentum=0.9, nesterov=True, weight_decay=1e-4)
            sched = schedules.constant(0.1)
            state_sds, state_sh, axes = specs.train_state_specs(
                cfg, plan, strat, optimizer, lmesh, rules, with_membership=faults is not None
            )
            meta["plane"] = plane_meta(state_sds)
            if offload:
                meta["offload"] = _offload_meta(state_sds, state_sh, tau)
            batch_sds = specs.train_batch_specs(cfg, shape, plan, tau)
            batch_sh = specs.batch_shardings(batch_sds, lmesh, rules)

            def loss_fn(p, b):
                return T.lm_loss(cfg, p, b, remat=True)

            round_step = make_round_step(
                loss_fn, optimizer, strat, sched, axes, microbatch=arch.train_microbatch
            )
            lowered = jax.jit(
                round_step, in_shardings=(state_sh, batch_sh), donate_argnums=(0,)
            ).lower(state_sds, batch_sds)
            meta["tokens_per_program"] = tau * shape.global_batch * shape.seq_len
            meta["mode"] = "train"
        elif shape.mode == "prefill":
            params_sds, params_sh, _ = specs.serve_param_specs(cfg, lmesh, rules)
            in_sds = specs.prefill_input_specs(cfg, shape)
            in_sh = specs.prefill_input_shardings(in_sds, lmesh, rules)

            def prefill_fn(p, inputs):
                logits, aux = T.apply_model(cfg, p, inputs, mode="prefill")
                return logits, aux["caches"]

            lowered = jax.jit(prefill_fn, in_shardings=(params_sh, in_sh)).lower(params_sds, in_sds)
            meta["tokens_per_program"] = shape.global_batch * shape.seq_len
            meta["mode"] = "prefill"
        else:  # decode
            params_sds, params_sh, _ = specs.serve_param_specs(cfg, lmesh, rules)
            tok_sds, tok_sh = specs.decode_token_specs(cfg, shape, lmesh, rules)
            # paged decode for attention-family text archs — except long_500k,
            # whose rules shard the cache sequence axis: a page-table gather
            # over a sequence-sharded pool would all-gather the pool, so the
            # long-context shape keeps the dense sequence-sharded cache
            use_paged = paged_supported(cfg) and shape.name != "long_500k"
            if use_paged:
                pools_sds, pools_sh, pt_sds, len_sds, rep, info = specs.paged_decode_specs(
                    cfg, shape, lmesh, rules
                )

                def paged_fn(p, toks, pools, pt, lens):
                    return paged_step(cfg, p, toks, pools, pt, lens)

                lowered = jax.jit(
                    paged_fn,
                    in_shardings=(params_sh, tok_sh, pools_sh, rep, rep),
                ).lower(params_sds, tok_sds, pools_sds, pt_sds, len_sds)
                meta["serving"] = dict(engine="paged", **info)
            else:
                cache_sds, cache_sh = specs.decode_cache_specs(cfg, shape, lmesh, rules)
                pos_sds = jax.ShapeDtypeStruct((), np.int32)

                def serve_fn(p, toks, caches, pos):
                    return decode_step(cfg, p, toks, caches, pos)

                lowered = jax.jit(
                    serve_fn,
                    in_shardings=(params_sh, tok_sh, cache_sh, None),
                ).lower(params_sds, tok_sds, cache_sds, pos_sds)
                meta["serving"] = dict(engine="dense")
            meta["tokens_per_program"] = shape.global_batch
            meta["mode"] = "decode"
    return lowered, meta, cfg


def active_params(cfg) -> int:
    """Parameters touched per token (MoE expert weights scaled by top_k/E)."""
    sds, _ = T.init_model(cfg, jax.random.PRNGKey(0), abstract=True)
    segs = T.segments(cfg)
    total = 0
    for key, sub in sds.items():
        frac = 1.0
        if key.startswith("seg"):
            si = int(key[3:])
            kind = segs[si][0]
            if kind == "moe" and cfg.moe is not None:
                # scale only the routed-expert weights
                moe_total = 0
                routed = 0
                for path, leaf in jax.tree_util.tree_flatten_with_path(sub)[0]:
                    n = int(np.prod(leaf.shape))
                    moe_total += n
                    keys = [str(getattr(p, "key", "")) for p in path]
                    if "ffn" in keys and any(k in ("wi_gate", "wi_up", "wo") for k in keys) and "shared" not in keys and "dense_residual" not in keys:
                        routed += n
                total += (moe_total - routed) + int(routed * cfg.moe.top_k / cfg.moe.num_experts)
                continue
        total += int(sum(np.prod(l.shape) for l in jax.tree.leaves(sub)) * frac)
    return total


def _memory_block(mem, meta: dict, hbm_gb: float) -> dict:
    """Per-device memory accounting against a configurable HBM budget.

    ``fits_hbm`` answers the question the offload plane controls: does the
    *device-resident steady-state* — program arguments (params/opt/anchor
    planes, batch, membership) minus the host-offloaded bytes, plus the
    double-buffer staging chunks — fit the budget.  All sizes from
    ``memory_analysis`` are per device (the compiler reports one shard's
    footprint).  Two deliberate exclusions/conventions:

    * ``temp_bytes`` (activation workspace) is reported raw but NOT counted
      against the budget: the host-backend lowering performs no remat (and
      logs involuntary full-remat broadcasts), so its temp accounting is
      orders of magnitude above what the rematerialized accelerator
      program holds live — see the host-mesh remat caveat in
      EXPERIMENTS.md.  Activation residency is governed by remat policy
      and microbatch size, orthogonal to state residency.
    * the budget is binary-sized: an "80GB" HBM part holds 80 GiB
      (85.9e9 bytes), so ``--hbm-gb 80`` means ``80 * 2**30`` bytes.

    ``fits_hbm_16g`` keeps the old arg+temp-vs-16e9 semantics for one
    release for older budget-diff tooling — ``fits_hbm`` +
    ``hbm_budget_gb`` is the keyed field."""
    peak = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    ob = meta.get("offload", {})
    host_pd = ob.get("host_bytes_per_device", 0)
    staging_pd = ob.get("staging_bytes_per_device", 0)
    resident = mem.argument_size_in_bytes - host_pd + staging_pd
    budget = hbm_gb * 2**30
    return dict(
        argument_bytes=mem.argument_size_in_bytes,
        output_bytes=mem.output_size_in_bytes,
        temp_bytes=mem.temp_size_in_bytes,
        alias_bytes=mem.alias_size_in_bytes,
        peak_per_device=peak,
        host_offloaded_bytes_per_device=int(host_pd),
        device_resident_bytes_per_device=int(resident),
        hbm_budget_gb=float(hbm_gb),
        hbm_budget_bytes=int(budget),
        fits_hbm=bool(resident <= budget),
        fits_hbm_16g=bool(peak <= 16e9),  # deprecated: use fits_hbm + hbm_budget_gb
    )


def run_pair(
    arch_name: str,
    shape_name: str,
    multi_pod: bool = False,
    out_dir: str = None,
    verbose: bool = True,
    with_probes: bool = True,
    opt: bool = False,
    strategy: str = None,
    faults: str = None,
    topology: str = None,
    offload: bool = False,
    hbm_gb: float = 16.0,
):
    t0 = time.time()
    lowered, meta, cfg = lower_pair(
        arch_name, shape_name, multi_pod, opt=opt, strategy=strategy, faults=faults,
        topology=topology, offload=offload,
    )
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    hlo = compiled.as_text()
    roof_sched = rl.analyze(compiled, hlo)

    n_params_sds, _ = T.init_model(cfg, jax.random.PRNGKey(0), abstract=True)
    n_params = int(sum(np.prod(s.shape) for s in jax.tree.leaves(n_params_sds)))

    # scan-corrected per-device cost via component probes (see costprobe.py)
    composed = None
    roof = roof_sched
    if with_probes:
        from repro.launch import costprobe
        from repro.parallel import logical_mesh as _lm

        arch = get_arch(arch_name)
        shape = INPUT_SHAPES[shape_name]
        prod_mesh = make_production_mesh(multi_pod=multi_pod)
        plan = arch.plan_for(shape.name, prod_mesh.devices.size)
        lmesh = _lm(prod_mesh, plan)
        rules = specs.optimized_rules(shape) if opt else specs.rules_for(shape)
        t0 = time.time()
        composed = costprobe.composed_cost(
            arch, shape, lmesh, plan, rules, strategy=meta.get("strategy"),
            offload_stream_bytes=meta.get("offload", {}).get("stream_bytes_per_round_per_device"),
        )
        composed["probe_s"] = round(time.time() - t0, 1)
        roof = rl.Roofline(
            flops=composed["flops"],
            bytes_accessed=composed["bytes"],
            collective_bytes=composed["coll"],
            collectives=roof_sched.collectives,
        )

    # measured host-link bandwidth + overlap schedule for the offload plane:
    # is the stream hidden inside the τ-step window? (DESIGN.md §9)
    if meta.get("offload", {}).get("enabled"):
        from repro.core.runtime_model import offload_schedule
        from repro.launch.costprobe import measure_host_bandwidth

        bw = measure_host_bandwidth()
        t_step = max(roof.compute_s, roof.memory_s) / max(meta["tau"], 1)
        meta["offload"]["bandwidth"] = bw
        meta["offload"]["schedule"] = offload_schedule(
            meta["offload"]["stream_bytes_per_round_per_device"],
            min(bw["d2h_gbps"], bw["h2d_gbps"]),
            meta["tau"],
            t_step,
        )

    n_active = active_params(cfg)
    mode = meta["mode"]
    mflops = rl.model_flops(n_active, meta["tokens_per_program"], "train" if mode == "train" else "serve")
    n_dev = meta["n_devices"]  # the mesh actually built, not a re-derived constant
    mflops_per_dev = mflops / n_dev

    # the boundary's own collective schedule (per-kind count/bytes from the
    # boundary probe) — directly comparable to BENCH_kernels.json boundary rows
    boundary_collectives = None
    if composed is not None and "boundary" in composed.get("parts", {}):
        boundary_collectives = composed["parts"]["boundary"].get("collectives")

    # adaptive-τ schedule cost model (train mode): the composed cost is
    # linear in τ, so the dry-run prices the whole τ *schedule* a controller
    # would realize — per-τ program costs + simulated trajectory against
    # the runtime model (repro.control.schedule, DESIGN.md §6)
    tau_schedule = None
    degraded_rounds = None
    if meta["mode"] == "train":
        from repro.control import TauController, schedule_block

        # deterministic fault schedule (DESIGN.md §7): the membership-masked
        # program was lowered above; here the plan's resolved schedule is
        # recorded and threaded into the controller trajectory so the JSON
        # proves adaptive-τ and fault handling compose (fault_hold rounds)
        fault_plan = None
        if faults is not None:
            from repro.fault import FaultPlan

            fault_plan = FaultPlan.parse(faults, m=meta["plan"]["workers"])
            degraded_rounds = fault_plan.degraded_rounds(50)

        ctrl = TauController(tau=meta["tau"], tau_min=1, tau_max=32)
        tau_schedule = schedule_block(
            meta["strategy"], ctrl, rounds=50, composed=composed, fault_plan=fault_plan
        )

    result = dict(
        meta,
        ok=True,
        lower_s=round(t_lower, 1),
        compile_s=round(t_compile, 1),
        boundary_collectives=boundary_collectives,
        n_params=n_params,
        n_active_params=n_active,
        model_flops_per_device=mflops_per_dev,
        useful_flops_ratio=(mflops_per_dev / roof.flops) if roof.flops else None,
        memory=_memory_block(mem, meta, hbm_gb),
        roofline=roof.as_dict(),
        schedule_view=roof_sched.as_dict(),
        composed=composed,
        tau_schedule=tau_schedule,
        degraded_rounds=degraded_rounds,
    )
    if verbose:
        strat_note = f", strategy {meta['strategy']}" if "strategy" in meta else ""
        print(f"== {meta['arch']} × {meta['shape']} × {meta['mesh']} (plan {meta['plan']}, {meta['variant']}{strat_note})")
        print(f"   memory_analysis: {mem}")
        print(
            f"   cost/device: flops={roof.flops:.3e} bytes={roof.bytes_accessed:.3e} "
            f"collective_bytes={roof.collective_bytes:.3e} (scan-corrected={composed is not None})"
        )
        print(
            f"   roofline: compute={roof.compute_s*1e3:.2f}ms memory={roof.memory_s*1e3:.2f}ms "
            f"collective={roof.collective_s*1e3:.2f}ms -> dominant: {roof.dominant}"
        )
        ratio = result["useful_flops_ratio"]
        print(f"   MODEL_FLOPS/HLO_FLOPS = {ratio:.3f}" if ratio else "   MODEL_FLOPS ratio n/a")
        if tau_schedule is not None:
            taus = [t["tau"] for t in tau_schedule["per_tau"]]
            print(
                f"   tau schedule: {tau_schedule['rounds']} rounds over taus {taus} "
                f"({tau_schedule['compiled_programs']} programs), "
                f"scheduled {tau_schedule['total_time_s']:.1f}s vs fixed-tau {tau_schedule['fixed_tau_time_s']:.1f}s"
            )
        if degraded_rounds is not None:
            n_holds = sum(1 for tr in tau_schedule["trajectory"] if tr["decision"] == "fault_hold")
            print(
                f"   faults: {degraded_rounds['degraded']}/{degraded_rounds['rounds']} degraded rounds, "
                f"{n_holds} fault_hold tau decisions"
            )
        if meta.get("offload", {}).get("enabled"):
            ob = meta["offload"]
            sched_blk = ob["schedule"]
            print(
                f"   offload: host/device {ob['host_bytes_per_device']/1e9:.2f}GB off, "
                f"stream {sched_blk['stream_s']*1e3:.2f}ms vs window {sched_blk['window_s']*1e3:.2f}ms "
                f"-> exposed {sched_blk['exposed_s']*1e3:.2f}ms (breakeven tau {sched_blk['breakeven_tau']})"
            )
            mb = result["memory"]
            print(
                f"   hbm: resident {mb['device_resident_bytes_per_device']/1e9:.2f}GB of "
                f"{mb['hbm_budget_gb']:.0f}GiB budget -> fits_hbm={mb['fits_hbm']} "
                f"(temp {mb['temp_bytes']/1e9:.0f}GB excluded: host lowering has no remat)"
            )
        print(f"   collective schedule: {roof_sched.collectives}")
        print(f"   lower {t_lower:.1f}s compile {t_compile:.1f}s probes {composed['probe_s'] if composed else 0}s")
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        tag = f"{meta['arch']}_{meta['shape']}_{meta['mesh'].replace('x','-')}"
        if opt:
            tag += "_opt"
        if strategy is not None and "strategy" in meta:
            # only train shapes resolve a strategy; serve pairs under
            # --all --strategy keep their untagged filenames
            tag += f"_{meta['strategy']}"
        if faults is not None and "strategy" in meta:
            # the membership-carrying lowering is a different program; keep
            # the baseline JSONs (and their budget comparisons) untouched
            tag += "_faults"
        if offload and "strategy" in meta:
            tag += "_offload"
        with open(os.path.join(out_dir, tag + ".json"), "w") as f:
            json.dump(result, f, indent=2, default=str)
    return result


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", type=str, default=None)
    ap.add_argument("--shape", type=str, default=None, choices=list(INPUT_SHAPES) + [None])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--opt", action="store_true", help="lower the beyond-paper optimized sharding variant (EXPERIMENTS.md §Perf)")
    ap.add_argument(
        "--strategy",
        type=str,
        default=None,
        choices=sorted(STRATEGIES),
        help="two-phase CommStrategy for train shapes (default: specs.default_train_strategy — "
        "overlap_local_sgd, degenerating to local_sgd at w=1)",
    )
    ap.add_argument(
        "--topology",
        type=str,
        default=None,
        help="gossip mixing-matrix family for --strategy gossip_pushsum (full|ring|exp); "
        "the fixed-topology strategy names (gossip_ring, gossip_exp, ...) override it",
    )
    ap.add_argument(
        "--faults",
        type=str,
        default=None,
        help="fault-plan spec for train shapes (repro.fault.FaultPlan.parse grammar, e.g. "
        "'crash:1@2-5,slow:2x4'): lowers the membership-masked round program and records "
        "the degraded_rounds schedule + fault_hold tau decisions (DESIGN.md §7)",
    )
    ap.add_argument(
        "--offload",
        action="store_true",
        help="lower the host-offloaded round program (AlgoConfig.offload): opt state and "
        "anchor-shaped slots live host-side between boundaries and stream through the "
        "τ-step window (DESIGN.md §9); JSON gains the offload schedule block",
    )
    ap.add_argument(
        "--hbm-gb",
        type=float,
        default=16.0,
        help="per-device HBM budget for the memory block's fits_hbm field "
        "(binary-sized, as HBM parts are: 80 means 80 GiB)",
    )
    ap.add_argument("--no-probes", action="store_true", help="skip the scan-corrected component probes (faster smoke)")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", type=str, default="experiments/dryrun")
    args = ap.parse_args()

    pairs = []
    archs = list_archs() if (args.all or args.arch is None) else [args.arch]
    shapes = list(INPUT_SHAPES) if (args.all or args.shape is None) else [args.shape]
    for a in archs:
        for s in shapes:
            pairs.append((a, s))

    failures = []
    for a, s in pairs:
        try:
            run_pair(
                a,
                s,
                multi_pod=args.multi_pod,
                out_dir=args.out,
                opt=args.opt,
                strategy=args.strategy,
                faults=args.faults,
                topology=args.topology,
                with_probes=not args.no_probes,
                offload=args.offload,
                hbm_gb=args.hbm_gb,
            )
        except Exception as e:  # noqa: BLE001
            failures.append((a, s, repr(e)))
            print(f"!! FAIL {a} × {s}: {e}")
            traceback.print_exc()
    print(f"\n{len(pairs) - len(failures)}/{len(pairs)} pairs OK on "
          f"{'2x16x16' if args.multi_pod else '16x16'}")
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
