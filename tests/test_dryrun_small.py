"""Sharding integration tests on an 8-device host mesh (subprocess: the
device-count XLA flag must be set before jax initializes, and only the
dry-run may see multiple devices)."""
import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

# per-test wall-clock budget for the subprocess lowering/execution tests: a
# hung XLA compile (or a deadlocked host collective) fails the one test with
# a readable message instead of stalling the whole suite at the runner's
# global timeout. Override for slow machines via REPRO_SUBPROC_TIMEOUT.
_TIMEOUT = int(os.environ.get("REPRO_SUBPROC_TIMEOUT", "300"))


def _run_subprocess(script: str, label: str) -> subprocess.CompletedProcess:
    env = dict(os.environ, PYTHONPATH=SRC)
    env.pop("XLA_FLAGS", None)  # the script pins its own device count
    try:
        return subprocess.run(
            [sys.executable, "-c", script],
            env=env,
            capture_output=True,
            text=True,
            timeout=_TIMEOUT,
        )
    except subprocess.TimeoutExpired as e:
        out = (e.stdout or b"")
        out = out.decode() if isinstance(out, bytes) else out
        pytest.fail(
            f"{label}: subprocess exceeded {_TIMEOUT}s "
            f"(REPRO_SUBPROC_TIMEOUT to raise); partial stdout:\n{out[-2000:]}"
        )

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.config import AlgoConfig, get_arch, InputShape, ParallelPlan
from repro.core import make_algorithm
from repro.launch import specs, roofline as rl
from repro.launch.mesh import make_smoke_mesh
from repro.models import transformer as T
from repro.optim import schedules, sgd
from repro.parallel import mesh_context
from repro.training.train_loop import make_round_step

mesh = make_smoke_mesh()
arch = get_arch("{arch}")
cfg = arch.model.reduced()
plan = ParallelPlan(workers=2, fsdp=2, tensor=2)
shape = InputShape("small_train", seq_len=32, global_batch=8, mode="train")
rules = specs.rules_for(shape)
algo = make_algorithm(AlgoConfig(name="overlap_local_sgd", tau=2, alpha=0.6, anchor_beta=0.7))
opt = sgd()

with mesh_context(mesh, rules):
    state_sds, state_sh, axes = specs.train_state_specs(cfg, plan, algo, opt, mesh, rules)
    batch_sds = specs.train_batch_specs(cfg, shape, plan, tau=2)
    batch_sh = specs.batch_shardings(batch_sds, mesh, rules)
    loss_fn = lambda p, b: T.lm_loss(cfg, p, b, remat=True)
    step = make_round_step(loss_fn, opt, algo, schedules.constant(0.1), axes)
    lowered = jax.jit(step, in_shardings=(state_sh, batch_sh)).lower(state_sds, batch_sds)
    compiled = lowered.compile()
    stats = rl.collective_stats(compiled.as_text())
    assert any(k in stats for k in ("all-reduce", "all-gather", "reduce-scatter")), stats
    print("COLLECTIVES", sorted(stats))
    print("OK {arch}")
"""


@pytest.mark.parametrize("arch", ["h2o-danube-1.8b", "deepseek-v3-671b", "rwkv6-7b", "zamba2-1.2b"])
def test_reduced_arch_lowers_on_8_device_mesh(arch):
    proc = _run_subprocess(SCRIPT.replace("{arch}", arch), f"lower {arch}")
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert f"OK {arch}" in proc.stdout


RUN_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.config import AlgoConfig, get_arch
from repro.core import make_algorithm
from repro.launch.mesh import make_smoke_mesh
from repro.models import transformer as T
from repro.optim import schedules, sgd
from repro.parallel import mesh_context
from repro.training import make_round_step, make_train_state
from repro.launch import specs

mesh = make_smoke_mesh()
cfg = get_arch("h2o-danube-1.8b").model.reduced()
rng = np.random.default_rng(0)
with mesh_context(mesh, specs.TRAIN_RULES):
    params, axes = T.init_model(cfg, jax.random.PRNGKey(0))
    algo = make_algorithm(AlgoConfig(name="overlap_local_sgd", tau=2, alpha=0.6, anchor_beta=0.7))
    opt = sgd()
    state = make_train_state(params, 2, opt, algo, axes)
    step = jax.jit(make_round_step(lambda p, b: T.lm_loss(cfg, p, b), opt, algo, schedules.constant(1e-2), axes))
    batch = dict(
        tokens=jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 2, 4, 32)), jnp.int32),
        targets=jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 2, 4, 32)), jnp.int32),
    )
    state, ms = step(state, batch)
    loss = np.asarray(ms["loss"])
    assert np.isfinite(loss).all()
    # executed on 8 real (host) devices — numerics must match 1-device run
    print("LOSS", float(loss.mean()))
print("RUN OK")
"""


def test_sharded_execution_runs_on_8_devices():
    proc = _run_subprocess(RUN_SCRIPT, "sharded execution")
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "RUN OK" in proc.stdout


PACKED_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from repro.config import AlgoConfig, get_arch, InputShape, ParallelPlan
from repro.core import make_strategy
from repro.launch import specs, roofline as rl
from repro.launch.mesh import make_smoke_mesh
from repro.models import transformer as T
from repro.optim import schedules, sgd
from repro.parallel import mesh_context
from repro.parallel.packing import Packed, unpack
from repro.training import make_round_step, make_train_state

mesh = make_smoke_mesh()
cfg = get_arch("h2o-danube-1.8b").model.reduced()
plan = ParallelPlan(workers=2, fsdp=2, tensor=2)
shape = InputShape("small_train", seq_len=32, global_batch=8, mode="train")
rules = specs.rules_for(shape)
acfg = AlgoConfig(name="overlap_local_sgd", tau=2, alpha=0.6, anchor_beta=0.7, packed=True)
opt = sgd()

# 1) AOT specs: the packed inflight/vars slots get flat-plane shardings
with mesh_context(mesh, rules):
    strat = make_strategy(acfg)
    state_sds, state_sh, axes = specs.train_state_specs(cfg, plan, strat, opt, mesh, rules)
    assert isinstance(state_sds.inflight, Packed) and isinstance(state_sh.inflight, Packed)
    # plane-resident state: x itself is the worker-stacked plane in the AOT specs
    assert isinstance(state_sds.x, Packed) and isinstance(state_sh.x, Packed)
    batch_sds = specs.train_batch_specs(cfg, shape, plan, tau=2)
    batch_sh = specs.batch_shardings(batch_sds, mesh, rules)
    loss_fn = lambda p, b: T.lm_loss(cfg, p, b, remat=True)
    step = make_round_step(loss_fn, opt, strat, schedules.constant(0.1), axes)
    compiled = jax.jit(step, in_shardings=(state_sh, batch_sh)).lower(state_sds, batch_sds).compile()
    stats = rl.collective_stats(compiled.as_text())
    assert any(k in stats for k in ("all-reduce", "all-gather", "reduce-scatter")), stats

# 2) execution on 8 host devices: packed round == per-leaf round (1-ULP
# tolerance: the two programs shard differently, so XLA may reassociate
# f32 reductions inside the *local steps*; the boundary math itself is
# pinned bitwise by the no-mesh golden tests)
rng = np.random.default_rng(0)
batch = dict(
    tokens=jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 2, 4, 32)), jnp.int32),
    targets=jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 2, 4, 32)), jnp.int32),
)
finals = []
with mesh_context(mesh, rules):
    params, axes = T.init_model(cfg, jax.random.PRNGKey(0))
    for c in (acfg, dataclasses.replace(acfg, packed=False)):
        strat = make_strategy(c)
        state = make_train_state(params, 2, opt, strat, axes)
        step = jax.jit(make_round_step(lambda p, b: T.lm_loss(cfg, p, b), opt, strat, schedules.constant(1e-2), axes))
        state, ms = step(state, batch)
        assert np.isfinite(np.asarray(ms["loss"])).all()
        finals.append(state)
assert isinstance(finals[0].x, Packed) and not isinstance(finals[1].x, Packed)
for a, b in zip(jax.tree.leaves(unpack(finals[0].x)), jax.tree.leaves(finals[1].x)):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-7, atol=2e-7)
for a, b in zip(jax.tree.leaves(unpack(finals[0].inflight)), jax.tree.leaves(finals[1].inflight)):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-7, atol=2e-7)
print("PACKED MESH OK")
"""


OPT_PLANE_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from repro.config import AlgoConfig, get_arch, InputShape, ParallelPlan
from repro.core import make_strategy
from repro.launch import specs, roofline as rl
from repro.launch.mesh import make_smoke_mesh
from repro.models import transformer as T
from repro.optim import schedules, sgd, PackedSGDState
from repro.parallel import mesh_context
from repro.parallel.packing import Packed, unpack
from repro.training import make_round_step, make_train_state

mesh = make_smoke_mesh()
cfg = get_arch("h2o-danube-1.8b").model.reduced()
plan = ParallelPlan(workers=2, fsdp=2, tensor=2)
shape = InputShape("small_train", seq_len=32, global_batch=8, mode="train")
rules = specs.rules_for(shape)
acfg = AlgoConfig(name="overlap_local_sgd", tau=2, alpha=0.6, anchor_beta=0.7, packed=True)
opt = sgd(momentum=0.9, nesterov=True, weight_decay=1e-4)

# 1) AOT specs: the flat optimizer-state buffers get worker-stacked
# flat-plane shardings ((worker, fsdp) — the jax-0.4.x partially-sharded
# regime the DUS-built plane exists for)
with mesh_context(mesh, rules):
    strat = make_strategy(acfg)
    state_sds, state_sh, axes = specs.train_state_specs(cfg, plan, strat, opt, mesh, rules)
    assert isinstance(state_sds.opt, PackedSGDState), type(state_sds.opt)
    assert isinstance(state_sh.opt.momentum, Packed)
    assert isinstance(state_sds.x, Packed) and isinstance(state_sh.x, Packed)
    x_specs = {sh.spec for sh in jax.tree.leaves(state_sh.x)}
    assert any("worker" in str(sp) and "fsdp" in str(sp) for sp in x_specs), x_specs
    sh_specs = {s.spec for s in jax.tree.leaves(state_sh.opt)}
    assert any("worker" in str(sp) and "fsdp" in str(sp) for sp in sh_specs), sh_specs
    batch_sds = specs.train_batch_specs(cfg, shape, plan, tau=2)
    batch_sh = specs.batch_shardings(batch_sds, mesh, rules)
    loss_fn = lambda p, b: T.lm_loss(cfg, p, b, remat=True)
    step = make_round_step(loss_fn, opt, strat, schedules.constant(0.1), axes)
    compiled = jax.jit(step, in_shardings=(state_sh, batch_sh)).lower(state_sds, batch_sds).compile()
    stats = rl.collective_stats(compiled.as_text())
    assert any(k in stats for k in ("all-reduce", "all-gather", "reduce-scatter")), stats

# 2) executed parity on 8 host devices: a full round with the packed local
# step (flat momentum carried in the scan) matches the per-leaf oracle.
# Tolerance is a few ULPs — the two programs shard/fuse differently through
# the ENTIRE local step now, so XLA may reassociate f32 math per step; the
# update math itself is pinned bitwise by the no-mesh suite.
rng = np.random.default_rng(0)
batch = dict(
    tokens=jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 2, 4, 32)), jnp.int32),
    targets=jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 2, 4, 32)), jnp.int32),
)
finals = []
with mesh_context(mesh, rules):
    params, axes = T.init_model(cfg, jax.random.PRNGKey(0))
    for c in (acfg, dataclasses.replace(acfg, packed=False)):
        strat = make_strategy(c)
        state = make_train_state(params, 2, opt, strat, axes)
        step = jax.jit(make_round_step(lambda p, b: T.lm_loss(cfg, p, b), opt, strat, schedules.constant(1e-2), axes))
        state, ms = step(state, batch)
        assert np.isfinite(np.asarray(ms["loss"])).all()
        finals.append(state)
assert isinstance(finals[0].opt, PackedSGDState) and not isinstance(finals[1].opt, PackedSGDState)
assert isinstance(finals[0].x, Packed) and not isinstance(finals[1].x, Packed)
for a, b in zip(jax.tree.leaves(unpack(finals[0].x)), jax.tree.leaves(finals[1].x)):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=5e-7)
for a, b in zip(jax.tree.leaves(unpack(finals[0].opt.momentum)), jax.tree.leaves(finals[1].opt.momentum)):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=5e-7)
print("OPT PLANE MESH OK")
"""


def test_packed_opt_state_lowers_and_matches_on_8_devices():
    """Satellite (ISSUE 3): flat optimizer-state buckets get the
    (worker, fsdp) flat-plane shardings in the AOT specs, the round program
    compiles on the 8-device host mesh, and an executed round matches the
    per-leaf oracle — pinning the jax-0.4.x partially-sharded-concat
    workaround (DUS-built planes) for the optimizer buckets."""
    proc = _run_subprocess(OPT_PLANE_SCRIPT, "packed opt plane")
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "OPT PLANE MESH OK" in proc.stdout


NATIVE_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
import jax, jax.numpy as jnp, numpy as np
from repro.api import resolve_strategy
from repro.config import AlgoConfig, get_arch, InputShape, ParallelPlan
from repro.core.strategy import CommStrategy, LegacyStrategy
from repro.launch import specs, roofline as rl
from repro.launch.mesh import make_smoke_mesh
from repro.models import transformer as T
from repro.optim import schedules, sgd, PackedSGDState
from repro.parallel import mesh_context
from repro.parallel.packing import Packed
from repro.training.train_loop import make_round_step

# the production lowering path must never touch the deprecated shim: after
# importing the dry-run module, repro.core.algorithms is not even loaded,
# and its source has no make_algorithm reference left
import repro.launch.dryrun as dryrun
assert "repro.core.algorithms" not in sys.modules, "dryrun import pulled the deprecated shim"
src = open(dryrun.__file__).read()
assert "make_algorithm" not in src, "dryrun.py still references the legacy make_algorithm path"

mesh = make_smoke_mesh()
cfg = get_arch("h2o-danube-1.8b").model.reduced()
plan = ParallelPlan(workers=2, fsdp=2, tensor=2)
shape = InputShape("small_train", seq_len=32, global_batch=8, mode="train")
rules = specs.rules_for(shape)
opt = sgd(momentum=0.9, nesterov=True, weight_decay=1e-4)

# per-strategy native coverage: the paper's algorithm, both blocking
# baselines, DaSGD delayed averaging, and LOSCAR sparse anchor
for name in ("overlap_local_sgd", "local_sgd", "sync_sgd", "delayed_avg", "sparse_anchor"):
    strat = resolve_strategy(specs.train_algo_config(plan, name))
    assert isinstance(strat, CommStrategy) and not isinstance(strat, LegacyStrategy), name
    assert strat.packed, name
    tau = strat.tau
    with mesh_context(mesh, rules):
        state_sds, state_sh, axes = specs.train_state_specs(cfg, plan, strat, opt, mesh, rules)
        # strategy-native, plane-resident round program: x IS the packed
        # plane and the optimizer state is flat buckets, in specs and shardings
        assert isinstance(state_sds.x, Packed) and isinstance(state_sh.x, Packed), name
        assert isinstance(state_sds.opt, PackedSGDState), (name, type(state_sds.opt))
        batch_sds = specs.train_batch_specs(cfg, shape, plan, tau)
        batch_sh = specs.batch_shardings(batch_sds, mesh, rules)
        step = make_round_step(lambda p, b: T.lm_loss(cfg, p, b, remat=True), opt, strat,
                               schedules.constant(0.1), axes)
        compiled = jax.jit(step, in_shardings=(state_sh, batch_sh)).lower(state_sds, batch_sds).compile()
        stats = rl.collective_stats(compiled.as_text())
        assert any(k in stats for k in ("all-reduce", "all-gather", "reduce-scatter")), (name, stats)
    print("NATIVE OK", name)
assert "repro.core.algorithms" not in sys.modules, "native lowering pulled the deprecated shim"
print("NATIVE DRYRUN OK")
"""


def test_native_strategy_dryrun_on_8_devices():
    """Tentpole (ISSUE 5): the dry-run's train lowering is strategy-native —
    resolved through repro.api.resolve_strategy, plane-resident x + flat
    opt-state specs, per-strategy coverage (overlap/local/sync/DaSGD/LOSCAR)
    — and never imports the deprecated make_algorithm shim."""
    proc = _run_subprocess(NATIVE_SCRIPT, "native strategy dryrun")
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "NATIVE DRYRUN OK" in proc.stdout
    for name in ("overlap_local_sgd", "local_sgd", "sync_sgd", "delayed_avg", "sparse_anchor"):
        assert f"NATIVE OK {name}" in proc.stdout


def test_legacy_shim_import_and_call_warn():
    """The deprecated oracle shim is still reachable for the golden tests,
    but both pulling it out of repro.core and calling make_algorithm emit
    DeprecationWarning."""
    import warnings

    import repro.core

    from repro.config import AlgoConfig

    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        make_algorithm = repro.core.make_algorithm
        assert any(issubclass(x.category, DeprecationWarning) for x in w), w
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        algo = make_algorithm(AlgoConfig(name="local_sgd"))
        assert any(issubclass(x.category, DeprecationWarning) for x in w), w
    assert algo.name == "local_sgd"


def test_packed_boundary_lowers_and_matches_on_8_devices():
    """Packed-plane boundary on a real (host) mesh: the AOT specs give the
    flat inflight/vars buffers anchor-plane shardings, the program lowers
    and compiles, and one executed round is bitwise-identical to the
    per-leaf oracle under the same sharding."""
    proc = _run_subprocess(PACKED_SCRIPT, "packed boundary")
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "PACKED MESH OK" in proc.stdout


GOSSIP_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.api import resolve_strategy
from repro.config import get_arch, InputShape, ParallelPlan
from repro.core.strategy import GossipInflight
from repro.launch import specs, roofline as rl
from repro.launch.mesh import make_smoke_mesh
from repro.models import transformer as T
from repro.optim import schedules, sgd
from repro.parallel import mesh_context
from repro.parallel.packing import Packed
from repro.training import make_round_step, make_train_state

mesh = make_smoke_mesh()
cfg = get_arch("h2o-danube-1.8b").model.reduced()
plan = ParallelPlan(workers=2, fsdp=2, tensor=2)
shape = InputShape("small_train", seq_len=32, global_batch=8, mode="train")
rules = specs.rules_for(shape)
opt = sgd()

# 1) the gossip family lowers through the same strategy-native dry-run path:
# degenerate full topology reuses the anchor-shaped inflight; sparse
# topologies carry the two-slot push-sum inflight (mix plane + (m,) weights)
for name, sparse in (("gossip_full", False), ("gossip_exp", True)):
    strat = resolve_strategy(specs.train_algo_config(plan, name))
    assert strat.packed and getattr(strat, "topo_name", None) is not None, name
    with mesh_context(mesh, rules):
        state_sds, state_sh, axes = specs.train_state_specs(cfg, plan, strat, opt, mesh, rules)
        assert isinstance(state_sds.x, Packed) and isinstance(state_sh.x, Packed), name
        if sparse:
            assert isinstance(state_sds.inflight, GossipInflight), (name, type(state_sds.inflight))
            assert isinstance(state_sds.inflight.mix, Packed), name
            assert state_sds.inflight.w.shape == (2,), name
        batch_sds = specs.train_batch_specs(cfg, shape, plan, strat.tau)
        batch_sh = specs.batch_shardings(batch_sds, mesh, rules)
        step = make_round_step(lambda p, b: T.lm_loss(cfg, p, b, remat=True), opt, strat,
                               schedules.constant(0.1), axes)
        compiled = jax.jit(step, in_shardings=(state_sh, batch_sh)).lower(state_sds, batch_sds).compile()
        stats = rl.collective_stats(compiled.as_text())
        assert any(k in stats for k in ("all-reduce", "all-gather", "reduce-scatter")), (name, stats)
    print("GOSSIP LOWER OK", name)

# 2) an executed push-sum round on the 8 host devices: finite loss and the
# push weights stay a probability mass (sum == m, fully live)
rng = np.random.default_rng(0)
batch = dict(
    tokens=jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 2, 4, 32)), jnp.int32),
    targets=jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 2, 4, 32)), jnp.int32),
)
with mesh_context(mesh, rules):
    params, axes = T.init_model(cfg, jax.random.PRNGKey(0))
    strat = resolve_strategy(specs.train_algo_config(plan, "gossip_exp"))
    state = make_train_state(params, 2, opt, strat, axes)
    step = jax.jit(make_round_step(lambda p, b: T.lm_loss(cfg, p, b), opt, strat,
                                   schedules.constant(1e-2), axes))
    for _ in range(2):
        state, ms = step(state, batch)
        assert np.isfinite(np.asarray(ms["loss"])).all()
    np.testing.assert_allclose(float(np.asarray(state.inflight.w).sum()), 2.0, rtol=1e-5)
print("GOSSIP MESH OK")
"""


def test_gossip_strategies_lower_and_run_on_8_devices():
    """Tentpole (ISSUE 8): the push-sum/gossip family lowers through the
    strategy-native dry-run path on the 8-device host mesh — degenerate full
    topology plus a sparse one-peer-exponential — and an executed push-sum
    round keeps the loss finite with conserved push mass."""
    proc = _run_subprocess(GOSSIP_SCRIPT, "gossip strategies")
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "GOSSIP MESH OK" in proc.stdout
    for name in ("gossip_full", "gossip_exp"):
        assert f"GOSSIP LOWER OK {name}" in proc.stdout


MEMBERSHIP_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.api import resolve_strategy
from repro.config import get_arch, InputShape, ParallelPlan
from repro.fault.membership import from_mask
from repro.launch import specs, roofline as rl
from repro.launch.mesh import make_smoke_mesh
from repro.models import transformer as T
from repro.optim import schedules, sgd
from repro.parallel import mesh_context
from repro.training import make_round_step, make_train_state

mesh = make_smoke_mesh()
cfg = get_arch("h2o-danube-1.8b").model.reduced()
plan = ParallelPlan(workers=2, fsdp=2, tensor=2)
shape = InputShape("small_train", seq_len=32, global_batch=8, mode="train")
rules = specs.rules_for(shape)
opt = sgd()
strat = resolve_strategy(specs.train_algo_config(plan, "overlap_local_sgd"))

# 1) the membership-carrying AOT specs lower + compile (the fault dry-run
# path: replicated (m,) mask/weights threaded into the masked boundary)
with mesh_context(mesh, rules):
    state_sds, state_sh, axes = specs.train_state_specs(
        cfg, plan, strat, opt, mesh, rules, with_membership=True
    )
    assert state_sds.membership is not None and state_sh.membership is not None
    batch_sds = specs.train_batch_specs(cfg, shape, plan, strat.tau)
    batch_sh = specs.batch_shardings(batch_sds, mesh, rules)
    step = make_round_step(lambda p, b: T.lm_loss(cfg, p, b, remat=True), opt, strat,
                           schedules.constant(0.1), axes)
    compiled = jax.jit(step, in_shardings=(state_sh, batch_sh)).lower(state_sds, batch_sds).compile()
    stats = rl.collective_stats(compiled.as_text())
    assert any(k in stats for k in ("all-reduce", "all-gather", "reduce-scatter")), stats

# 2) a degraded round executes on the 8 host devices: worker 1 masked out
rng = np.random.default_rng(0)
batch = dict(
    tokens=jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 2, 4, 32)), jnp.int32),
    targets=jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 2, 4, 32)), jnp.int32),
)
with mesh_context(mesh, rules):
    params, axes = T.init_model(cfg, jax.random.PRNGKey(0))
    state = make_train_state(params, 2, opt, strat, axes)
    state = state._replace(membership=from_mask(np.array([1.0, 0.0], np.float32)))
    step = jax.jit(make_round_step(lambda p, b: T.lm_loss(cfg, p, b), opt, strat,
                                   schedules.constant(1e-2), axes))
    state, ms = step(state, batch)
    assert np.isfinite(np.asarray(ms["loss"])).all()
print("MEMBERSHIP MESH OK")
"""


def test_membership_boundary_lowers_and_runs_on_8_devices():
    """Tentpole (ISSUE 7): the membership-carrying train state lowers and
    compiles on the 8-device host mesh (the fault dry-run's masked round
    program), and a degraded round executes with a masked-out worker."""
    proc = _run_subprocess(MEMBERSHIP_SCRIPT, "membership boundary")
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "MEMBERSHIP MESH OK" in proc.stdout


OFFLOAD_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
from repro.config import AlgoConfig, get_arch, InputShape, ParallelPlan
from repro.core import make_strategy
from repro.launch import specs
from repro.launch.mesh import make_smoke_mesh
from repro.models import transformer as T
from repro.optim import schedules, sgd
from repro.parallel import mesh_context
from repro.training import make_round_step

mesh = make_smoke_mesh()
cfg = get_arch("h2o-danube-1.8b").model.reduced()
plan = ParallelPlan(workers=2, fsdp=2, tensor=2)
shape = InputShape("small_train", seq_len=32, global_batch=8, mode="train")
rules = specs.rules_for(shape)
strat = make_strategy(AlgoConfig(name="overlap_local_sgd", tau=2, alpha=0.6, anchor_beta=0.7, offload=True))
opt = sgd(momentum=0.9, nesterov=True)
with mesh_context(mesh, rules):
    state_sds, state_sh, axes = specs.train_state_specs(cfg, plan, strat, opt, mesh, rules)
    batch_sds = specs.train_batch_specs(cfg, shape, plan, tau=2)
    batch_sh = specs.batch_shardings(batch_sds, mesh, rules)
    step = make_round_step(lambda p, b: T.lm_loss(cfg, p, b), opt, strat, schedules.constant(0.1), axes)
    text = jax.jit(step, in_shardings=(state_sh, batch_sh)).lower(state_sds, batch_sds).as_text()
    assert 'memory_kind = "pinned_host"' in text
print("OFFLOAD LOWER OK")
"""


def test_offloaded_round_lowers_on_8_devices():
    """The dry-run's abstract (shape-only) state carries no memory space:
    the offloaded round program must still lower, with its opt-state
    chunks placed in host memory."""
    proc = _run_subprocess(OFFLOAD_SCRIPT, "offloaded lowering")
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "OFFLOAD LOWER OK" in proc.stdout
