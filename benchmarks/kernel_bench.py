"""Kernel microbenchmarks.

On this CPU-only container the Pallas kernels execute in interpret mode
(orders of magnitude slower than compiled; correctness only), so the timed
numbers are the jnp reference paths under jit — the same code the dry-run
lowers — plus derived arithmetic throughput. The Pallas variants are timed
once in interpret mode purely to prove the harness runs them end-to-end.

The ``boundary/*`` rows time one full Overlap-Local-SGD round boundary
(eqs. 4–5 + anchor momentum) over a many-leaf synthetic parameter tree, on
the packed flat-plane path vs the per-leaf reference path — the perf claim
of the packed parameter plane (ISSUE 2), persisted into BENCH_kernels.json
by benchmarks/run.py.

The ``localstep/*`` rows time one local optimizer step the same two ways:
per-leaf (vmapped tree optimizer, O(leaves) ops) vs packed (plane-resident
training — flat bucket cotangents into one fused ``kernels/opt_step``
update per dtype bucket). The ``fwdstep``/``gradflow`` rows time the AD
chain itself: forward/grad with the plane as the primal (ParamView window
reads, flat cotangents) vs the retired per-step pack/unpack chain (unpack →
tree grad → DUS-scatter the gradient pytree back onto the plane).

The ``boundary/<arch>/*`` rows time the round boundary per architecture on
the 8-device dry-run (host) smoke mesh via a subprocess (the device-count
XLA flag must be set before jax initializes) — sharded lowering included,
so the per-arch packed-vs-per-leaf trajectory tracks what the dry-run
actually compiles. ``REPRO_BENCH_QUICK=1`` shrinks shapes/iters/arch count
for the CI smoke step.
"""
from __future__ import annotations

import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import QUICK, csv_row
from repro.config import AlgoConfig
from repro.control import consensus_drift
from repro.core import make_strategy
from repro.kernels.consensus_probe import ops as probe_ops
from repro.kernels.anchor_mix import ref as am_ref
from repro.kernels.flash_attention import ref as fa_ref
from repro.kernels.rmsnorm import ref as rms_ref
from repro.kernels.rwkv6_wkv import ref as wkv_ref
from repro.kernels.ssd_scan import ref as ssd_ref
from repro.optim import adamw, sgd
from repro.parallel import offload as off
from repro.parallel.packing import ParamView, pack, unpack


def _time(fn, *args, iters=5):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6  # us


def _synthetic_tree(rng, n_layers: int, width: int):
    """A transformer-ish parameter tree: n_layers × {matrix, vector, norm}
    (+ embedding) — ≥ 50 leaves of mixed, mostly lane-ragged shapes."""
    p = {"embed": jnp.asarray(rng.normal(size=(width * 4, width)), np.float32)}
    for i in range(n_layers):
        p[f"layer{i}"] = {
            "w": jnp.asarray(rng.normal(size=(width, width)), np.float32),
            "b": jnp.asarray(rng.normal(size=(width,)), np.float32),
            "scale": jnp.asarray(rng.normal(size=(width + 1,)), np.float32),
        }
    return p


def boundary_rows(quick: bool = False, m: int = 4, n_layers: int = 80, width: int = 48):
    """Packed plane vs per-leaf reference for one full round boundary, on a
    production-depth tree (80 layers → 241 leaves): the regime the packed
    plane targets, where per-leaf dispatch dominates the memory sweeps."""
    if quick:
        n_layers, width = 40, 32  # same dispatch-bound regime, 121 leaves, ~4× less data
    rng = np.random.default_rng(0)
    params = _synthetic_tree(rng, n_layers, width)
    n_leaves = len(jax.tree.leaves(params))
    n_elems = sum(l.size for l in jax.tree.leaves(params))
    x = jax.tree.map(lambda t: jnp.tile(t[None], (m,) + (1,) * t.ndim), params)
    x = jax.tree.map(
        lambda t: t + 0.01 * jnp.arange(m, dtype=np.float32).reshape((m,) + (1,) * (t.ndim - 1)), x
    )
    # useful bytes per boundary (f32, fused-pass model: read x+z+v, write
    # x+z+v) — the SAME basis for both rows, so effective_gbps is directly
    # comparable across modes (higher = better); the per-leaf path actually
    # moves more than this (it re-reads x between sweeps)
    nbytes = (2 * m * n_elems + 4 * n_elems) * 4

    # worker-stacked plane bytes of the timed x — the same quantity the
    # dry-run records as plane.x_buffer_bytes, keying these rows against
    # dry-run JSONs (EXPERIMENTS.md §Perf)
    from repro.parallel.packing import pack

    plane_bytes = jax.eval_shape(lambda t: pack(t, lead=1), x).nbytes

    rows = []
    us_by_mode = {}
    for packed in (True, False):
        cfg = AlgoConfig(name="overlap_local_sgd", tau=2, alpha=0.6, anchor_beta=0.7, packed=packed)
        strat = make_strategy(cfg)
        vars_ = strat.init_vars(x, None)
        inflight = strat.init_inflight(x, vars_, None)
        fn = jax.jit(lambda xx, vv, ff: strat.boundary_round(xx, vv, ff, None))
        us = _time(fn, x, vars_, inflight, iters=3 if quick else 20)
        us_by_mode[packed] = us
        mode = "packed" if packed else "perleaf"
        rows.append(
            (
                f"boundary/overlap_momentum_{mode}_{n_leaves}leaf",
                us,
                f"effective_gbps={nbytes/us/1e3:.1f} leaves={n_leaves} elems={n_elems} m={m} "
                f"strategy={strat.name} plane_bytes={plane_bytes}",
            )
        )
    rows.append(
        (
            f"boundary/packed_speedup_{n_leaves}leaf",
            us_by_mode[True],
            f"speedup_x={us_by_mode[False]/us_by_mode[True]:.2f} baseline_us={us_by_mode[False]:.1f}",
        )
    )
    return rows


def local_step_rows(quick: bool = False, m: int = 4, n_layers: int = 80, width: int = 48):
    """Packed vs per-leaf local optimizer step at the production-depth
    241-leaf config.

    Both modes run the full per-step chain the round engine executes after
    the backward pass — per-leaf: vmapped tree step; packed (plane-resident
    training): gradients already live as flat bucket cotangents, so the
    chain is just one fused update per dtype bucket — plus an identical
    elementwise gradient oracle standing in for the backward output (the
    fwdstep/gradflow rows time the AD chain itself)."""
    if quick:
        n_layers, width = 40, 32
    rng = np.random.default_rng(0)
    params = _synthetic_tree(rng, n_layers, width)
    n_leaves = len(jax.tree.leaves(params))
    n_elems = sum(l.size for l in jax.tree.leaves(params))
    x = jax.tree.map(lambda t: jnp.tile(t[None], (m,) + (1,) * t.ndim), params)
    lr = jnp.float32(0.05)
    iters = 5 if quick else 30

    # useful f32 bytes per fused step (same basis both modes): sgd reads
    # x,g,mom and writes x,mom; adamw reads x,g,mu,nu and writes x,mu,nu
    opts = {
        "sgd": (sgd(momentum=0.9, nesterov=True, weight_decay=1e-4), 5),
        "adamw": (adamw(weight_decay=1e-4), 7),
    }
    rows = []
    for opt_name, (opt, passes) in opts.items():
        nbytes = passes * m * n_elems * 4

        def f_leaf(o, xx):
            gg = jax.tree.map(lambda t: t * 0.01, xx)
            return jax.vmap(lambda oi, xi, gi: opt.step(oi, xi, gi, lr))(o, xx, gg)

        def f_packed(o, pxx):
            # plane-resident: the backward hands over flat bucket cotangents
            # directly — no pack/unpack in the step chain
            gg = jax.tree.map(lambda b: b * 0.01, pxx)
            return opt.step_packed(o, pxx, gg, lr)

        px = pack(x, lead=1)
        us_by_mode = {}
        for mode, fn, args in (
            ("packed", jax.jit(f_packed), (opt.init_packed(px), px)),
            ("perleaf", jax.jit(f_leaf), (jax.vmap(opt.init)(x), x)),
        ):
            us = _time(fn, *args, iters=iters)
            us_by_mode[mode] = us
            rows.append(
                (
                    f"localstep/{opt_name}_{mode}_{n_leaves}leaf",
                    us,
                    f"effective_gbps={nbytes/us/1e3:.1f} leaves={n_leaves} elems={n_elems} m={m}",
                )
            )
        rows.append(
            (
                f"localstep/{opt_name}_packed_speedup_{n_leaves}leaf",
                us_by_mode["packed"],
                f"speedup_x={us_by_mode['perleaf']/us_by_mode['packed']:.2f} baseline_us={us_by_mode['perleaf']:.1f}",
            )
        )
    return rows


def plane_rows(quick: bool = False, m: int = 4, n_layers: int = 80, width: int = 48):
    """``fwdstep``/``gradflow`` rows (plane-resident training): forward pass
    and gradient computation with the packed plane as the primal — params
    read through ParamView windows, cotangents arriving as flat per-bucket
    buffers — vs the per-step pack/unpack chain (unpack the plane, grad the
    pytree, DUS-scatter the gradient tree back onto the plane) that the
    round engine ran before the plane went end-to-end. Same 241-leaf
    synthetic tree as the boundary/localstep rows; the loss touches every
    leaf elementwise so both directions sweep the whole plane."""
    if quick:
        n_layers, width = 40, 32
    rng = np.random.default_rng(0)
    params = _synthetic_tree(rng, n_layers, width)
    n_leaves = len(jax.tree.leaves(params))
    n_elems = sum(l.size for l in jax.tree.leaves(params))
    x = jax.tree.map(lambda t: jnp.tile(t[None], (m,) + (1,) * t.ndim), params)
    px = pack(x, lead=1)
    iters = 5 if quick else 30

    def tree_loss(p):  # touches every leaf; stands in for the model forward
        return sum(0.5 * jnp.sum(jnp.square(l)) for l in jax.tree.leaves(p))

    def plane_loss(pxx):  # the engine's formulation: stacked view, vmapped loss
        view = ParamView(pxx).materialize()
        return jnp.sum(jax.vmap(tree_loss)(view))

    fwd = {
        "plane": jax.jit(plane_loss),
        "packunpack": jax.jit(lambda pxx: jnp.sum(jax.vmap(tree_loss)(unpack(pxx)))),
    }
    grad = {
        "plane": jax.jit(jax.grad(plane_loss)),
        "packunpack": jax.jit(
            lambda pxx: pack(jax.vmap(jax.grad(tree_loss))(unpack(pxx)), layout=pxx.layout, lead=1)
        ),
    }
    rows = []
    for group, fns, nbytes in (
        ("fwdstep", fwd, m * n_elems * 4),  # read the plane once
        ("gradflow", grad, 2 * m * n_elems * 4),  # read plane, write cotangent plane
    ):
        us_by_mode = {}
        for mode, fn in fns.items():
            us = _time(fn, px, iters=iters)
            us_by_mode[mode] = us
            rows.append(
                (
                    f"{group}/{mode}_{n_leaves}leaf",
                    us,
                    f"effective_gbps={nbytes/us/1e3:.1f} leaves={n_leaves} elems={n_elems} m={m}",
                )
            )
        rows.append(
            (
                f"{group}/plane_speedup_{n_leaves}leaf",
                us_by_mode["plane"],
                f"speedup_x={us_by_mode['packunpack']/us_by_mode['plane']:.2f} "
                f"baseline_us={us_by_mode['packunpack']:.1f}",
            )
        )
    return rows


def consensus_probe_rows(quick: bool = False, m: int = 4, n_layers: int = 80, width: int = 48):
    """Adaptive-τ consensus probe (DESIGN.md §6) on the production-depth
    synthetic tree.

    ``consensusprobe/packed_*``: the plane probe (one sweep over the flat
    bucket buffers) vs the unfused per-leaf two-pass reduction
    (``repro.control.consensus_drift``: mean + squared-deviation reductions
    per leaf, O(leaves) dispatch) — the controller's measurement cost when
    the strategy has no boundary kernel to fuse into.

    ``consensusprobe/boundary_*``: one full Overlap-Local-SGD round boundary
    with and without ``probe=True`` — the fused-probe overhead on the path
    adaptive fits actually run (the partial sums ride the pullback kernels,
    so the expected overhead is the extra write of a (2, 128) buffer)."""
    if quick:
        n_layers, width = 40, 32
    rng = np.random.default_rng(0)
    params = _synthetic_tree(rng, n_layers, width)
    n_leaves = len(jax.tree.leaves(params))
    n_elems = sum(l.size for l in jax.tree.leaves(params))
    x = jax.tree.map(lambda t: jnp.tile(t[None], (m,) + (1,) * t.ndim), params)
    x = jax.tree.map(
        lambda t: t + 0.01 * jnp.arange(m, dtype=np.float32).reshape((m,) + (1,) * (t.ndim - 1)), x
    )
    px = pack(x, lead=1)
    iters = 5 if quick else 30
    nbytes = m * n_elems * 4  # one f32 sweep of the stacked plane

    rows = []
    us_probe = _time(jax.jit(probe_ops.packed_probe), px, iters=iters)
    us_leaf = _time(jax.jit(consensus_drift), x, iters=iters)
    rows.append(
        (
            f"consensusprobe/packed_probe_{n_leaves}leaf",
            us_probe,
            f"gbps={nbytes/us_probe/1e3:.1f} leaves={n_leaves} elems={n_elems} m={m}",
        )
    )
    rows.append(
        (
            f"consensusprobe/perleaf_twopass_{n_leaves}leaf",
            us_leaf,
            f"gbps={nbytes/us_leaf/1e3:.1f} leaves={n_leaves} elems={n_elems} m={m}",
        )
    )
    rows.append(
        (
            f"consensusprobe/packed_speedup_{n_leaves}leaf",
            us_probe,
            f"speedup_x={us_leaf/us_probe:.2f} baseline_us={us_leaf:.1f}",
        )
    )

    cfg = AlgoConfig(name="overlap_local_sgd", tau=2, alpha=0.6, anchor_beta=0.7, packed=True)
    strat = make_strategy(cfg)
    vars_ = strat.init_vars(px, None)
    inflight = strat.init_inflight(px, vars_, None)
    us_by_probe = {}
    for probe in (False, True):
        fn = jax.jit(lambda xx, vv, ff: strat.boundary_round(xx, vv, ff, None, probe=probe))
        us_by_probe[probe] = _time(fn, px, vars_, inflight, iters=iters)
    rows.append(
        (
            f"consensusprobe/boundary_plain_{n_leaves}leaf",
            us_by_probe[False],
            f"leaves={n_leaves} elems={n_elems} m={m}",
        )
    )
    rows.append(
        (
            f"consensusprobe/boundary_probed_{n_leaves}leaf",
            us_by_probe[True],
            f"overhead_pct={100*(us_by_probe[True]/us_by_probe[False]-1):.1f} "
            f"baseline_us={us_by_probe[False]:.1f}",
        )
    )
    return rows


def offload_rows(quick: bool = False, m: int = 4, n_layers: int = 80, width: int = 48):
    """Host-offload plane rows (DESIGN.md §9) at the production-depth
    241-leaf config.

    ``offload/stream_*``: the chunked D2H/H2D stream of the packed opt
    state — ``tree_offload`` (chunk + host placement) / ``tree_restore`` —
    plus the raw host-link copy rate (``costprobe.measure_host_bandwidth``),
    the bandwidth the dry-run's offload schedule block is priced with. This
    CPU container has no separate host memory space, so the stream rows
    time the chunking sweeps themselves; the copy rows time the runtime's
    actual copy path.

    ``offload/localstep_*``: one local optimizer step with host-resident
    state (``step_streamed``: double-buffered chunk scan — prefetch chunk
    i+1 while applying i) vs the plane-resident fused step. The ratio is
    the per-step cost the τ window must amortize for the offload plane to
    be free (the dry-run's ``breakeven_tau``)."""
    from repro.launch.costprobe import measure_host_bandwidth

    if quick:
        n_layers, width = 40, 32
    rng = np.random.default_rng(0)
    params = _synthetic_tree(rng, n_layers, width)
    n_leaves = len(jax.tree.leaves(params))
    x = jax.tree.map(lambda t: jnp.tile(t[None], (m,) + (1,) * t.ndim), params)
    px = pack(x, lead=1)
    # 64 KiB chunks: the synthetic bucket is ~0.8 MB, so the stream walks a
    # real multi-chunk grid (~13 chunks) like the production plane does
    chunk_mb = 1 / 16
    plan = off.OffloadPlan.for_layout(px.layout, chunk_mb=chunk_mb)
    pg = jax.tree.map(lambda b: b * 0.01, px)
    lr = jnp.float32(0.05)
    iters = 3 if quick else 20
    n_chunks = int(sum(plan.num_chunks))

    rows = []
    bw = measure_host_bandwidth(nbytes=(8 << 20) if quick else (64 << 20))
    for d, g in (("h2d", bw["h2d_gbps"]), ("d2h", bw["d2h_gbps"])):
        rows.append(
            (
                f"offload/hostlink_copy_{d}",
                bw["probe_bytes"] / (g * 1e3),
                f"gbps={g:.2f} bytes={bw['probe_bytes']}",
            )
        )

    opt0 = sgd(momentum=0.9, nesterov=True, weight_decay=1e-4)
    st = opt0.init_packed(px)
    st_host = off.tree_offload(st, plan)
    sbytes = off.host_nbytes(st_host)
    us_d2h = _time(jax.jit(lambda s: off.tree_offload(s, plan)), st, iters=iters)
    us_h2d = _time(jax.jit(off.tree_restore), st_host, iters=iters)
    for d, us in (("d2h", us_d2h), ("h2d", us_h2d)):
        rows.append(
            (
                f"offload/stream_{d}_{n_leaves}leaf",
                us,
                f"gbps={sbytes/us/1e3:.1f} chunks={n_chunks} chunk_mb={chunk_mb} bytes={sbytes} m={m}",
            )
        )

    for opt_name, opt in (("sgd", opt0), ("adamw", adamw(weight_decay=1e-4))):
        st = opt.init_packed(px)
        st_h = off.tree_offload(st, plan)
        us_res = _time(jax.jit(lambda o, xx: opt.step_packed(o, xx, pg, lr)), st, px, iters=iters)
        us_str = _time(jax.jit(lambda o, xx: opt.step_streamed(o, xx, pg, lr)), st_h, px, iters=iters)
        rows.append(
            (
                f"offload/localstep_{opt_name}_resident_{n_leaves}leaf",
                us_res,
                f"leaves={n_leaves} m={m}",
            )
        )
        rows.append(
            (
                f"offload/localstep_{opt_name}_offloaded_{n_leaves}leaf",
                us_str,
                f"overhead_x={us_str/us_res:.2f} baseline_us={us_res:.1f} chunks={n_chunks} m={m}",
            )
        )
    return rows


_ARCH_BOUNDARY_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import time
import jax, jax.numpy as jnp
from repro.config import AlgoConfig, get_arch, InputShape
from repro.core import make_strategy
from repro.launch import specs
from repro.launch.mesh import make_smoke_mesh
from repro.models import transformer as T
from repro.parallel import mesh_context

arch, iters = "{arch}", {iters}
mesh = make_smoke_mesh()
cfg = get_arch(arch).model.reduced()
shape = InputShape("small_train", seq_len=32, global_batch=8, mode="train")
with mesh_context(mesh, specs.rules_for(shape)):
    params, axes = T.init_model(cfg, jax.random.PRNGKey(0))
    x = jax.tree.map(lambda t: jnp.tile(t[None], (2,) + (1,) * t.ndim), params)
    n_leaves = len(jax.tree.leaves(params))
    us_by_mode = {{}}
    for packed in (True, False):
        acfg = AlgoConfig(name="overlap_local_sgd", tau=2, alpha=0.6, anchor_beta=0.7, packed=packed)
        strat = make_strategy(acfg)
        vars_ = strat.init_vars(x, axes)
        inflight = strat.init_inflight(x, vars_, axes)
        fn = jax.jit(lambda xx, vv, ff: strat.boundary_round(xx, vv, ff, axes))
        out = fn(x, vars_, inflight)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(x, vars_, inflight)
        jax.block_until_ready(out)
        us = (time.perf_counter() - t0) / iters * 1e6
        mode = "packed" if packed else "perleaf"
        us_by_mode[packed] = us
        print(f"ROW boundary/{arch}/overlap_momentum_" + mode + f",{{us:.1f}},leaves={{n_leaves}} mesh=2x2x2 note=host_sim")
    # NOTE: on the host-simulated mesh collectives run on CPU threads and the
    # fully-sharded anchor plane pays resharding a real interconnect hides, so
    # packed can lose here; the row tracks the dry-run-mesh trajectory (e.g.
    # for the jax>=0.5 partial-sharding re-evaluation), not TPU-relative perf.
    print(f"ROW boundary/{arch}/packed_speedup,{{us_by_mode[True]:.1f}},"
          f"speedup_x={{us_by_mode[False]/us_by_mode[True]:.2f}} baseline_us={{us_by_mode[False]:.1f}} note=host_sim")
"""


def arch_boundary_rows(quick: bool = False):
    """Per-arch round-boundary timings on the 8-device dry-run (host) smoke
    mesh — ROADMAP item. Subprocess per arch: the device-count flag must be
    set before jax initializes, and the bench process must stay
    single-device for the other rows."""
    archs = ["h2o-danube-1.8b"] if quick else ["h2o-danube-1.8b", "qwen2-7b"]
    iters = 3 if quick else 10
    src = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
    env = dict(os.environ)
    env["PYTHONPATH"] = src + (os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    env.pop("XLA_FLAGS", None)
    # the host mesh is CPU devices: a child must never reach for an
    # accelerator the parent process may already hold
    env["JAX_PLATFORMS"] = "cpu"
    rows = []
    for arch in archs:
        script = _ARCH_BOUNDARY_SCRIPT.format(arch=arch, iters=iters)
        proc = subprocess.run(
            [sys.executable, "-c", script], env=env, capture_output=True, text=True, timeout=900
        )
        if proc.returncode != 0:
            raise RuntimeError(f"boundary/{arch} child failed (rc={proc.returncode}):\n{proc.stderr[-2000:]}")
        for line in proc.stdout.splitlines():
            if line.startswith("ROW "):
                name, us, derived = line[4:].split(",", 2)
                rows.append((name, float(us), derived))
    return rows


def run(quick: bool = False):
    quick = quick or QUICK
    rng = np.random.default_rng(0)
    rows = []

    b, s, h, d = 2, 512, 8, 64
    q = jnp.asarray(rng.normal(size=(b, s, h, d)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(b, s, 2, d)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(b, s, 2, d)).astype(np.float32))
    f = jax.jit(lambda q, k, v: fa_ref.chunked_mha(q, k, v, block_q=128, block_k=128))
    us = _time(f, q, k, v)
    flops = 4 * b * h * s * s * d
    rows.append(("kernel/flash_attention_chunked_512", us, f"gflops={flops/us/1e3:.1f}"))

    x = jnp.asarray(rng.normal(size=(4096, 2048)).astype(np.float32))
    sc = jnp.asarray(rng.normal(size=(2048,)).astype(np.float32))
    us = _time(jax.jit(rms_ref.rmsnorm), x, sc)
    rows.append(("kernel/rmsnorm_4096x2048", us, f"gbps={(x.size*2*4)/us/1e3:.1f}"))

    xs = jnp.asarray(rng.normal(size=(2, 256, 8, 32)).astype(np.float32))
    dt = jnp.asarray(np.abs(rng.normal(size=(2, 256, 8))).astype(np.float32))
    A = jnp.asarray(-np.abs(rng.normal(size=(8,))).astype(np.float32))
    B = jnp.asarray(rng.normal(size=(2, 256, 1, 16)).astype(np.float32))
    C = jnp.asarray(rng.normal(size=(2, 256, 1, 16)).astype(np.float32))
    Dp = jnp.asarray(rng.normal(size=(8,)).astype(np.float32))
    f = jax.jit(lambda *a: ssd_ref.ssd_chunked(*a, chunk=64)[0])
    us = _time(f, xs, dt, A, B, C, Dp)
    rows.append(("kernel/ssd_scan_256", us, "chunk=64"))

    r = jnp.asarray(rng.normal(size=(2, 256, 4, 32)).astype(np.float32))
    kk = jnp.asarray(rng.normal(size=(2, 256, 4, 32)).astype(np.float32))
    vv = jnp.asarray(rng.normal(size=(2, 256, 4, 32)).astype(np.float32))
    w = jnp.asarray(0.3 + 0.69 * rng.random((2, 256, 4, 32)).astype(np.float32))
    u = jnp.asarray(rng.normal(size=(4, 32)).astype(np.float32))
    f = jax.jit(lambda *a: wkv_ref.wkv_chunked(*a, chunk=32)[0])
    us = _time(f, r, kk, vv, w, u)
    rows.append(("kernel/rwkv6_wkv_256", us, "chunk=32"))

    n_mix = 1 << (17 if quick else 20)
    xa = jnp.asarray(rng.normal(size=(n_mix,)).astype(np.float32))
    za = jnp.asarray(rng.normal(size=(n_mix,)).astype(np.float32))
    f = jax.jit(lambda x, z: am_ref.anchor_mix(x, z, 0.6))
    us = _time(f, xa, za)
    label = "1M" if n_mix == 1 << 20 else f"{n_mix >> 10}K"
    rows.append((f"kernel/anchor_mix_{label}", us, f"gbps={(3*xa.size*4)/us/1e3:.1f}"))

    rows.extend(boundary_rows(quick))
    rows.extend(local_step_rows(quick))
    rows.extend(plane_rows(quick))
    rows.extend(consensus_probe_rows(quick))
    rows.extend(offload_rows(quick))
    rows.extend(arch_boundary_rows(quick))
    return rows


def main(emit):
    for name, us, derived in run():
        emit(csv_row(name, us, derived))
