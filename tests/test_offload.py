"""Host-offloaded optimizer & anchor planes (DESIGN.md §9): golden parity +
budget regressions.

With ``AlgoConfig.offload`` the flat opt-state buckets and the anchor-shaped
slots (strategy vars, inflight collective) live host-side between round
boundaries as chunked :class:`repro.parallel.offload.HostPlane` trees, and
the engine streams them through the τ-step window — opt state chunk-by-chunk
inside the local-step scan (double-buffered: prefetch chunk i+1 while
applying chunk i), anchor slots once per round at the boundary. This suite
pins the contract three ways:

1. unit: the chunk grid round-trips bitwise for lane-ragged buckets, and
   ``tree_offload``/``tree_restore`` are exact inverses;
2. differential: offloaded training reproduces plane-resident training
   across {sgd, adamw} × {f32, bf16} × the pullback-family strategies —
   sgd bit-exact through full rounds, adamw bit-exact per streamed step
   with an amplification-aware few-ulp bound over full rounds (see
   ``_assert_tree``);
3. budget: the offloaded round program adds ZERO collectives to the
   local-step scan body, and each per-bucket chunk scan keeps at most
   ``n_state_planes`` staged chunks in its carry with exactly one prefetch
   ``dynamic_slice`` per plane in the body — ≤2 device staging buffers per
   state plane per dtype bucket, the double-buffer bound the dry-run's
   ``offload.staging_bytes_per_device`` reports.

The CPU backend reports a ``pinned_host`` memory, as a TPU does, so the
chunk hand-offs are annotated here too (``jax.memory.Space.Host`` /
``.Device``); XLA:CPU keeps the arrays in one memory, so the placement itself
shows only on a chip or in a TPU compile. The chunk grid, scan structure,
and numerics are exactly what a TPU run executes.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import AlgoConfig
from repro.core import make_strategy
from repro.optim import adamw, schedules, sgd
from repro.optim.optimizers import offload_capable
from repro.parallel import offload as off
from repro.parallel.packing import LANE, Packed, pack
from repro.training import make_round_step, make_train_state

M = 4
# 512-byte chunks → 128-element (one-lane) chunks, so the few-hundred-element
# test buckets walk a real multi-chunk grid
_CHUNK_MB = 1 / 2048

from conftest import unpack_view as _unp  # packed-state pytree view


def _params(rng, bf16: bool):
    """Mixed-shape tree sized so every dtype bucket spans several chunks at
    ``_CHUNK_MB`` (bf16 adds a second bucket, like the golden suite)."""
    mat = jnp.bfloat16 if bf16 else jnp.float32
    return {
        "w0": jnp.asarray(rng.normal(size=(9, 33)), mat),
        "w1": jnp.asarray(rng.normal(size=(7, 41)), mat),
        "vec": jnp.asarray(rng.normal(size=(143,)), jnp.float32),
        "scalar": jnp.float32(rng.normal()),
        "b0": jnp.asarray(rng.normal(size=(37,)), mat),
    }


def _loss(params, batch):
    A, b = batch
    flat = jnp.concatenate([jnp.ravel(l).astype(jnp.float32) for l in jax.tree.leaves(params)])
    r = A @ flat - b
    loss = 0.5 * jnp.sum(r * r)
    return loss, dict(loss=loss)


def _run_pair(cfg: AlgoConfig, optimizer, params, rounds=2, lr=0.03, seed=1):
    """Run the offloaded and plane-resident configurations on identical
    batches; return the two final TrainStates (offloaded first)."""
    n_flat = sum(l.size for l in jax.tree.leaves(params))
    states, steps, strats = [], [], []
    for c in (dataclasses.replace(cfg, offload=True, offload_chunk_mb=_CHUNK_MB), cfg):
        strat = make_strategy(c)
        strats.append(strat)
        states.append(make_train_state(params, M, optimizer, strat, None))
        steps.append(jax.jit(make_round_step(_loss, optimizer, strat, schedules.constant(lr), None)))
    rng = np.random.default_rng(seed)
    for _ in range(rounds):
        A = jnp.asarray(rng.normal(size=(strats[0].tau, M, 4, n_flat)), jnp.float32)
        b = jnp.asarray(rng.normal(size=(strats[0].tau, M, 4)), jnp.float32)
        states = [step(s, (A, b))[0] for step, s in zip(steps, states)]
    return states


STRATEGY_VARIANTS = [
    ("overlap_local_sgd", dict(anchor_beta=0.7)),
    ("local_sgd", {}),
    ("delayed_avg", dict(delay_steps=2)),  # mid-round consume (delay < tau)
    ("delayed_avg", dict(delay_steps=3)),  # boundary consume (delay = tau)
]

OPTIMIZERS = {
    "sgd": lambda: sgd(momentum=0.9, nesterov=True, weight_decay=1e-4),
    "adamw": lambda: adamw(b1=0.9, b2=0.95, eps=1e-8, weight_decay=1e-4),
}


def _assert_tree(tp, tr, opt_name, msg):
    """sgd: bitwise, full rounds included. adamw: the streamed step itself
    is bit-identical (test_streamed_step_matches_packed_bitwise), but inside
    the whole-round program XLA fuses the division/sqrt chain differently
    around the chunk scan, seeding ~1-ulp update differences that the test
    loss's gradient amplifies over τ·rounds steps (measured worst ≈ 4e-5
    relative after 2 rounds; a real bug is orders of magnitude beyond)."""
    for a, b in zip(jax.tree.leaves(tp), jax.tree.leaves(tr)):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        if opt_name == "sgd":
            np.testing.assert_array_equal(a, b, err_msg=msg)
        else:
            np.testing.assert_allclose(a, b, rtol=2e-4, atol=1e-6, err_msg=msg)


# ---------------------------------------------------------------------------
# unit: chunk grid + host-plane round trips
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n,c", [(1, 128), (128, 128), (129, 128), (765, 128), (765, 256), (300, 512)])
def test_chunk_roundtrip_exact(rng, n, c):
    for lead in ((), (M,)):
        x = jnp.asarray(rng.normal(size=lead + (n,)), jnp.float32)
        k = -(-n // c)
        ch = off.chunk_buffer(x, k, c)
        assert ch.shape == (k,) + lead + (c,)
        back = off.unchunk_buffer(ch, n)
        np.testing.assert_array_equal(np.asarray(back), np.asarray(x))


def test_offload_plan_grid(rng):
    px = pack(jax.tree.map(lambda t: jnp.tile(t[None], (M,) + (1,) * t.ndim), _params(rng, True)), lead=1)
    plan = off.OffloadPlan.for_layout(px.layout, _CHUNK_MB)
    for n, c, k in zip(px.layout.bucket_sizes, plan.chunk_elems, plan.num_chunks):
        assert c % LANE == 0
        assert k == -(-int(n) // c)
        assert k > 1  # the test buckets must actually exercise the stream
    # default chunk size swallows these tiny buckets whole
    plan1 = off.OffloadPlan.for_layout(px.layout, off.DEFAULT_CHUNK_MB)
    assert all(k == 1 for k in plan1.num_chunks)


def test_tree_offload_restore_roundtrip(rng):
    px = pack(jax.tree.map(lambda t: jnp.tile(t[None], (M,) + (1,) * t.ndim), _params(rng, True)), lead=1)
    st = adamw().init_packed(px)
    plan = off.OffloadPlan.for_layout(px.layout, _CHUNK_MB)
    host = off.tree_offload(st, plan)
    assert off.is_offloaded(host) and not off.is_offloaded(st)
    assert off.plan_of(host) == plan and off.plan_of(st) is None
    assert off.host_nbytes(host) > 0
    # the scalar count passes through untouched; the moment planes chunk
    assert host.count.shape == ()
    back = off.tree_restore(host)
    for a, b in zip(jax.tree.leaves(st), jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))


def test_offload_annotates_host_memory(rng):
    """Offload is not a silent no-op: the backend reports a pinned host
    memory, and the offloaded chunks leave the program placed in it."""
    assert off.host_memory_kind() == "pinned_host"
    px = pack(_params(rng, False))
    plan = off.OffloadPlan.for_layout(px.layout, _CHUNK_MB)
    down = jax.jit(lambda p: off.offload_plane(p, plan)).lower(px).as_text()
    assert down.count('memory_kind = "pinned_host"') == len(px.buffers)


# ---------------------------------------------------------------------------
# differential: streamed step and full offloaded rounds vs plane-resident
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("bf16", [False, True], ids=["f32", "bf16"])
@pytest.mark.parametrize("opt_name", sorted(OPTIMIZERS))
def test_streamed_step_matches_packed_bitwise(rng, opt_name, bf16):
    """One streamed local step (double-buffered chunk scan) is bit-identical
    to the fused plane-resident step, for every plane including the f32
    moment shadows — compared jit-to-jit so XLA fuses both the same way."""
    opt = OPTIMIZERS[opt_name]()
    px = pack(jax.tree.map(lambda t: jnp.tile(t[None], (M,) + (1,) * t.ndim), _params(rng, bf16)), lead=1)
    pg = jax.tree.map(lambda b: b * 0.01 + 0.003, px)
    lr = jnp.float32(0.05)
    plan = off.OffloadPlan.for_layout(px.layout, _CHUNK_MB)
    st = opt.init_packed(px)

    st_ref, px_ref = jax.jit(lambda o, x, g: opt.step_packed(o, x, g, lr))(st, px, pg)
    host = off.tree_offload(st, plan)
    host_new, px_new = jax.jit(lambda o, x, g: opt.step_streamed(o, x, g, lr))(host, px, pg)
    assert off.is_offloaded(host_new)

    for a, b in zip(jax.tree.leaves(_unp(px_new)), jax.tree.leaves(_unp(px_ref))):
        np.testing.assert_array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))
    for a, b in zip(jax.tree.leaves(_unp(off.tree_restore(host_new))), jax.tree.leaves(_unp(st_ref))):
        np.testing.assert_array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))


@pytest.mark.parametrize("bf16", [False, True], ids=["f32", "bf16"])
@pytest.mark.parametrize("opt_name", sorted(OPTIMIZERS))
@pytest.mark.parametrize("name,kw", STRATEGY_VARIANTS, ids=[f"{n}-{v}" for n, v in STRATEGY_VARIANTS])
def test_offloaded_round_matches_resident(name, kw, opt_name, bf16, rng):
    """ISSUE golden suite: full offloaded rounds — streamed opt state in the
    τ-scan, anchor/inflight restored and re-offloaded at the boundary —
    reproduce plane-resident training exactly: params, opt state, strategy
    vars, and the carried inflight collective."""
    cfg = AlgoConfig(name=name, tau=3, alpha=0.6, packed=True, **kw)
    optimizer = OPTIMIZERS[opt_name]()
    s_o, s_r = _run_pair(cfg, optimizer, _params(rng, bf16))

    # the offloaded run keeps x device-resident (it rides the scan carry)
    # and the opt/vars/inflight slots host-resident between rounds
    assert isinstance(s_o.x, Packed)
    assert off.is_offloaded(s_o.opt)
    assert not off.is_offloaded(s_r.opt)

    _assert_tree(_unp(s_o.x), _unp(s_r.x), opt_name, f"{name}.x")
    _assert_tree(
        _unp(off.tree_restore(s_o.opt)), _unp(s_r.opt), opt_name, f"{name}.opt"
    )
    pv, rv = _unp(off.tree_restore(s_o.inflight)), _unp(s_r.inflight)
    if pv is None or rv is None:
        assert (pv is None) == (rv is None)
    else:
        _assert_tree(pv, rv, opt_name, f"{name}.inflight")
    for f in ("z", "v", "extra"):
        pv = _unp(off.tree_restore(getattr(s_o.vars, f)))
        rv = _unp(getattr(s_r.vars, f))
        if pv is None or rv is None:
            assert (pv is None) == (rv is None)
            continue
        _assert_tree(pv, rv, opt_name, f"{name}.vars.{f}")


# ---------------------------------------------------------------------------
# budget: zero extra collectives, ≤2 staging buffers per plane per bucket
# ---------------------------------------------------------------------------

COLLECTIVES = ["psum", "all_reduce", "all_gather", "reduce_scatter", "ppermute", "all_to_all"]


def _count_primitives(jaxpr, names):
    counts = dict.fromkeys(names, 0)
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in counts:
            counts[eqn.primitive.name] += 1
        if eqn.primitive.name == "pallas_call":
            continue
        for v in eqn.params.values():
            sub = None
            if isinstance(v, jax.extend.core.ClosedJaxpr):
                sub = v.jaxpr
            elif hasattr(v, "eqns"):
                sub = v
            if sub is not None:
                for k, c in _count_primitives(sub, names).items():
                    counts[k] += c
    return counts


def _scan_eqns(jaxpr):
    """All scan equations at any depth (excluding pallas bodies)."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            continue
        if eqn.primitive.name == "scan":
            out.append(eqn)
        for v in eqn.params.values():
            sub = None
            if isinstance(v, jax.extend.core.ClosedJaxpr):
                sub = v.jaxpr
            elif hasattr(v, "eqns"):
                sub = v
            if sub is not None:
                out.extend(_scan_eqns(sub))
    return out


def _round_jaxpr(params, opt_name="sgd", tau=3, offload=False):
    cfg = AlgoConfig(
        name="overlap_local_sgd", tau=tau, alpha=0.6, anchor_beta=0.7,
        packed=True, offload=offload, offload_chunk_mb=_CHUNK_MB,
    )
    strat = make_strategy(cfg)
    optimizer = OPTIMIZERS[opt_name]()
    state = make_train_state(params, M, optimizer, strat, None)
    step = make_round_step(_loss, optimizer, strat, schedules.constant(0.03), None)
    n_flat = sum(l.size for l in jax.tree.leaves(params))
    A = jnp.zeros((tau, M, 4, n_flat), jnp.float32)
    b = jnp.zeros((tau, M, 4), jnp.float32)
    return jax.make_jaxpr(step)(state, (A, b))


@pytest.mark.parametrize("opt_name", sorted(OPTIMIZERS))
def test_offload_adds_zero_collectives(rng, opt_name):
    """ISSUE acceptance: streaming the opt state through the window must not
    change the communication schedule — the offloaded round program has
    exactly the plane-resident program's collective count (and its local-step
    scan bodies contain none at all)."""
    params = _params(rng, bf16=True)
    j_res = _round_jaxpr(params, opt_name, offload=False)
    j_off = _round_jaxpr(params, opt_name, offload=True)
    c_res = _count_primitives(j_res.jaxpr, COLLECTIVES)
    c_off = _count_primitives(j_off.jaxpr, COLLECTIVES)
    assert c_off == c_res, (c_off, c_res)
    for eqn in _scan_eqns(j_off.jaxpr):
        body = eqn.params["jaxpr"].jaxpr
        assert sum(_count_primitives(body, COLLECTIVES).values()) == 0


@pytest.mark.parametrize("opt_name,n_planes", [("sgd", 1), ("adamw", 2)])
def test_double_buffer_staging_bound(rng, opt_name, n_planes):
    """ISSUE acceptance: the per-bucket chunk scan carries exactly the
    staged state chunks (``n_planes`` arrays) and its body issues exactly
    one prefetch ``dynamic_slice`` per plane — so at most 2 device staging
    buffers (applied + prefetched) per state plane per dtype bucket are ever
    live, the ``staging_bytes_per_device`` bound in dry-run JSONs."""
    params = _params(rng, bf16=True)
    px = pack(jax.tree.map(lambda t: jnp.tile(t[None], (M,) + (1,) * t.ndim), params), lead=1)
    n_buckets = len(px.layout.bucket_sizes)

    j_off = _round_jaxpr(params, opt_name, offload=True)
    scans = _scan_eqns(j_off.jaxpr)
    # the τ-step scan is the one whose body hosts the chunk scans
    tau_scans = [e for e in scans if _scan_eqns(e.params["jaxpr"].jaxpr)]
    assert len(tau_scans) == 1, [e.params["length"] for e in scans]
    chunk_scans = _scan_eqns(tau_scans[0].params["jaxpr"].jaxpr)
    assert len(chunk_scans) == n_buckets, (len(chunk_scans), n_buckets)
    for eqn in chunk_scans:
        assert eqn.params["num_carry"] == n_planes
        body = eqn.params["jaxpr"].jaxpr
        ds = _count_primitives(body, ["dynamic_slice"])["dynamic_slice"]
        assert ds == n_planes, (ds, n_planes)

    # the resident program has no chunk scans to begin with
    j_res = _round_jaxpr(params, opt_name, offload=False)
    res_scans = _scan_eqns(j_res.jaxpr)
    assert not any(_scan_eqns(e.params["jaxpr"].jaxpr) for e in res_scans)


# ---------------------------------------------------------------------------
# engine contract: construction, adoption, capability gate
# ---------------------------------------------------------------------------


def test_train_state_constructed_offloaded(rng):
    cfg = AlgoConfig(
        name="overlap_local_sgd", tau=2, alpha=0.6, anchor_beta=0.7,
        packed=True, offload=True, offload_chunk_mb=_CHUNK_MB,
    )
    strat = make_strategy(cfg)
    opt = OPTIMIZERS["sgd"]()
    assert offload_capable(opt)
    s = make_train_state(_params(rng, True), M, opt, strat, None)
    assert isinstance(s.x, Packed)
    assert off.is_offloaded(s.opt) and off.is_offloaded(s.vars) and off.is_offloaded(s.inflight)
    plan = off.plan_of(s.opt)
    assert plan is not None and all(k > 1 for k in plan.num_chunks)


def test_offload_requires_streamed_optimizer(rng):
    """The engine refuses offload with an optimizer that has no streamed
    step — silently falling back to a resident step would leave the state
    device-side and blow the HBM budget the flag was set for."""
    base = OPTIMIZERS["sgd"]()
    crippled = dataclasses.replace(base, step_streamed=None)
    assert not offload_capable(crippled)
    cfg = AlgoConfig(
        name="overlap_local_sgd", tau=2, alpha=0.6, packed=True,
        offload=True, offload_chunk_mb=_CHUNK_MB,
    )
    strat = make_strategy(cfg)
    with pytest.raises(ValueError, match="offload"):
        make_round_step(_loss, crippled, strat, schedules.constant(0.03), None)


def test_offloaded_fault_resync_matches_resident():
    """Elastic membership composes with offload (DESIGN.md §9): a rejoining
    worker re-syncs from the anchor even though the anchor-shaped slots are
    host-resident between rounds — `_anchor_of` restores a read-only view.
    The whole faulted run stays bitwise-equal to the plane-resident one
    (SGD path). Regression: resync used to crash on a HostPlane inflight."""
    from repro.api import ClassificationSpec, Experiment
    from repro.fault.plan import FaultPlan

    def run(offload):
        exp = Experiment(
            task=ClassificationSpec(n=2000, holdout=500),
            strategy=AlgoConfig(
                name="overlap_local_sgd", tau=4, alpha=0.5, anchor_beta=0.7,
                offload=offload, offload_chunk_mb=_CHUNK_MB,
            ),
        )
        return exp.fit(rounds=6, faults=FaultPlan.parse("crash:1@2-5,slow:2x4", m=4, seed=7))

    r_off, r_res = run(True), run(False)
    assert [float(a) for a in r_off.losses] == [float(b) for b in r_res.losses]
    assert r_off.losses[-1] < r_off.losses[0]
    resyncs = [r for r in r_off.fault_log if r.get("resynced")]
    assert any(1 in r["resynced"] for r in resyncs), r_off.fault_log
