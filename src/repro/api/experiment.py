"""The ``Experiment`` facade: one config-driven entrypoint for training,
evaluation and serving.

Collapses the argparse drivers that used to re-implement the same wiring
(model init → strategy → optimizer → train state → jitted round step → batch
loop) into a single declarative object:

    from repro.api import Experiment

    exp = Experiment(arch="qwen2-7b", strategy="overlap_local_sgd",
                     workers=4, rounds=20)
    result = exp.fit()
    print(exp.evaluate())          # held-out loss of the consensus model
    engine = exp.serve(slots=4)    # batched generation on the fitted params

Two task families are supported:

* **LM** — ``arch`` names a registered architecture (reduced variant by
  default) or is a full ``ModelConfig``; data is the synthetic token stream.
* **classification** — ``task=ClassificationSpec(...)`` runs the paper's
  CIFAR-10 stand-in (MLP on synthetic classification), the substrate of the
  Table/Figure benchmarks.

``strategy`` accepts a name, an ``AlgoConfig``, a two-phase ``CommStrategy``
instance, or a legacy ``Algorithm`` (wrapped transparently) — including the
DaSGD-style ``delayed_avg`` and LOSCAR-style ``sparse_anchor`` strategies.

With the default packed strategies (and a packed-capable optimizer) the
fitted ``state.x`` is *plane-resident* — the worker-stacked flat
``Packed`` parameter plane rather than a pytree; ``consensus()`` /
``evaluate()`` / ``serve()`` read it through the pytree view transparently
(``repro.training.params_view``).
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import AlgoConfig, ModelConfig, OptimizerConfig, ParallelPlan, get_arch
from repro.control import RoundProgramCache, TauController
from repro.core.strategy import CommStrategy, resolve_strategy
from repro.data.loaders import (
    ClassificationSplits,
    classification_batch_fn,
    lm_batch_fn,
    make_classification_splits,
    round_batch,
)
from repro.models import transformer as T
from repro.models.classifier import accuracy, init_mlp, mlp_loss
from repro.optim import from_config as opt_from_config
from repro.optim import schedules
from repro.optim.optimizers import Optimizer
from repro.training import consensus_params, make_round_step, make_train_state
from repro.utils import trace


@dataclass
class ClassificationSpec:
    """The synthetic classification task (paper §4's CIFAR-10 stand-in)."""

    n: int = 30000
    dim: int = 64
    num_classes: int = 10
    noise: float = 3.0
    holdout: int = 4000
    noniid: bool = False
    skew: float = 0.64
    batch_per_worker: int = 32
    hidden: Tuple[int, ...] = (128, 64)
    seed: int = 0
    # pre-built splits (shared across experiments, e.g. a benchmark grid);
    # overrides the generation parameters above
    splits: Optional[ClassificationSplits] = None


@dataclass
class TokenStream:
    """Synthetic LM token-stream spec (bigram-structured, per-worker seeds)."""

    batch_per_worker: int = 2
    seq_len: int = 64
    seed: int = 0


@dataclass
class FitResult:
    losses: List[float]  # per-round mean loss
    state: Any  # final TrainState; the next fit() donates its buffers
    rounds: int
    steps: int  # local steps taken (rounds × τ)
    wall_s: float
    # adaptive-τ runs only: one controller telemetry record per round
    # (round/tau/drift/scale/drift_ratio/decision/next_tau — DESIGN.md §6)
    tau_schedule: Optional[List[dict]] = None
    # faulted runs only: the harness's membership records — one per round
    # where the fleet departed from fully-live (round/live/excluded/resynced/
    # reason — DESIGN.md §7)
    fault_log: Optional[List[dict]] = None

    @property
    def final_loss(self) -> float:
        return self.losses[-1] if self.losses else float("nan")


def _unshared(state):
    """The state with every leaf that repeats an earlier one copied: a
    donated argument may not hold one buffer twice (strategies start their
    in-flight slot as ``x`` itself)."""
    seen = set()

    def fresh(leaf):
        if id(leaf) in seen:
            return jnp.copy(leaf)
        seen.add(id(leaf))
        return leaf

    return jax.tree.map(fresh, state)


@dataclass
class Experiment:
    """Declarative training/serving experiment. See module docstring."""

    arch: Union[str, ModelConfig, None] = None
    task: Optional[ClassificationSpec] = None
    strategy: Union[str, AlgoConfig, CommStrategy, Any] = "overlap_local_sgd"
    optimizer: Union[str, OptimizerConfig, Optimizer] = field(default_factory=OptimizerConfig)
    data: Optional[TokenStream] = None
    parallel: Optional[ParallelPlan] = None  # reserved for mesh runs (see launch/dryrun.py)
    workers: int = 4
    rounds: int = 20
    schedule: Optional[Callable] = None  # lr schedule; default derives from optimizer config
    grad_clip: float = 0.0
    microbatch: Optional[int] = None
    full: bool = False  # use the full (not reduced) registered model config
    seed: int = 0

    def __post_init__(self):
        if self.arch is None and self.task is None:
            self.task = ClassificationSpec()
        if self.arch is not None and self.task is not None:
            raise ValueError("specify either arch= (LM) or task= (classification), not both")
        self._built = False
        self.state = None
        # rounds finished, local steps in them, and their target tokens (LM)
        self.counters = trace.Counters()

    # -- construction -------------------------------------------------------

    def _resolve_strategy(self) -> CommStrategy:
        return resolve_strategy(self.strategy)

    def _resolve_optimizer(self) -> Tuple[Optimizer, Callable]:
        o = self.optimizer
        if isinstance(o, str):
            o = OptimizerConfig(name=o)
        if isinstance(o, OptimizerConfig):
            sched = self.schedule or schedules.from_config(o)
            return opt_from_config(o), sched
        if self.schedule is None:
            raise ValueError(
                "a raw Optimizer instance carries no learning rate; pass schedule= "
                "(e.g. schedules.constant(lr)) or use an OptimizerConfig"
            )
        return o, self.schedule

    def build(self) -> "Experiment":
        """Resolve configs into params/state/step-fn/batch-fn (idempotent)."""
        if self._built:
            return self
        self.strategy_obj = self._resolve_strategy()
        self.opt_obj, self.schedule_fn = self._resolve_optimizer()
        key = jax.random.PRNGKey(self.seed)

        if self.task is not None:
            spec = self.task
            self.splits = spec.splits or make_classification_splits(
                self.workers,
                n=spec.n,
                dim=spec.dim,
                num_classes=spec.num_classes,
                noise=spec.noise,
                holdout=spec.holdout,
                noniid=spec.noniid,
                skew=spec.skew,
                seed=spec.seed,
            )
            if self.splits.num_workers != self.workers:
                raise ValueError(
                    f"task splits have {self.splits.num_workers} partitions but workers={self.workers}"
                )
            self.model_cfg = None
            self.params, self.axes = init_mlp(key, spec.dim, spec.num_classes, hidden=spec.hidden)
            self.loss_fn = mlp_loss
            self.next_batch = classification_batch_fn(self.splits, spec.batch_per_worker, seed=spec.seed)
        else:
            if isinstance(self.arch, ModelConfig):
                cfg = self.arch
            else:
                model = get_arch(self.arch).model
                cfg = model if self.full else model.reduced()
            self.model_cfg = cfg
            stream = self.data or TokenStream()
            self.params, self.axes = T.init_model(cfg, key)
            self.loss_fn = lambda p, b: T.lm_loss(cfg, p, b)
            self.next_batch = lm_batch_fn(
                cfg, self.workers, stream.batch_per_worker, stream.seq_len, seed=stream.seed
            )

        self.state = make_train_state(self.params, self.workers, self.opt_obj, self.strategy_obj, self.axes)
        # a round's input state is dead once its output exists: donating it
        # lets the output reuse its buffers, so one copy of the state is on
        # the device at a time
        self.step_fn = jax.jit(
            make_round_step(
                self.loss_fn,
                self.opt_obj,
                self.strategy_obj,
                self.schedule_fn,
                self.axes,
                grad_clip=self.grad_clip,
                microbatch=self.microbatch,
            ),
            donate_argnums=(0,),
        )
        self._built = True
        return self

    # -- introspection ------------------------------------------------------

    @property
    def tau(self) -> int:
        self.build()
        return self.strategy_obj.tau

    @property
    def num_params(self) -> int:
        self.build()
        return sum(int(x.size) for x in jax.tree.leaves(self.params))

    # -- training -----------------------------------------------------------

    def fit(
        self,
        rounds: Optional[int] = None,
        steps: Optional[int] = None,
        log: Optional[Callable[[int, float], None]] = None,
        adaptive_tau: Optional[TauController] = None,
        faults: Optional[Any] = None,
    ) -> FitResult:
        """Run the round loop. ``steps`` (local steps) is an alternative to
        ``rounds``: rounds = steps // τ. ``log(round_idx, mean_loss)`` is
        called once per round when given. Fitting continues from the current
        state, so repeated calls accumulate training. Each round donates its
        input state, so a ``FitResult.state`` or ``exp.state`` taken before
        a fit is dead after it; ``consensus()``, ``consensus_plane()`` and
        ``anchor_plane()`` hand out buffers of their own.

        ``adaptive_tau`` hands the round loop to a
        :class:`repro.control.TauController` (DESIGN.md §6): each round runs
        at the controller's current τ through a per-τ jitted program cache,
        with the fused consensus probe feeding the controller between
        rounds. The returned :class:`FitResult` carries the realized τ
        schedule; ``steps`` then counts the actual local steps taken.

        ``faults`` (a :class:`repro.fault.FaultPlan`) runs the loop under
        the deterministic fault harness (DESIGN.md §7): each round's
        membership mask is resolved before the round, rejoining workers are
        re-synced from the anchor, and degraded rounds run the
        membership-masked boundary. Composes with ``adaptive_tau`` — fault
        rounds become ``fault_hold`` controller decisions."""
        self.build()
        if faults is not None:
            return self._fit_faulted(faults, rounds or self.rounds, log, ctrl=adaptive_tau)
        if adaptive_tau is not None:
            return self._fit_adaptive(adaptive_tau, rounds or self.rounds, log)
        tau = self.strategy_obj.tau
        if rounds is None:
            rounds = (steps // tau) if steps is not None else self.rounds
        losses: List[float] = []
        t0 = time.time()
        state = self.state
        for r in range(rounds):
            with trace.span("fit.round", round=self.counters["rounds"], tau=tau):
                with trace.span("fit.batch"):
                    rb = round_batch(self.next_batch, tau)
                with trace.span("fit.dispatch"):
                    state, ms = self.step_fn(_unshared(state), rb)
                self.state = state  # the previous state was donated
                with trace.span("fit.loss_read"):
                    loss = float(np.asarray(ms["loss"]).mean())
                self._count_round(tau, rb)
            losses.append(loss)
            if log is not None:
                log(r, loss)
        return FitResult(
            losses=losses, state=state, rounds=rounds, steps=rounds * tau, wall_s=time.time() - t0
        )

    def _count_round(self, tau: int, rb) -> None:
        c = self.counters
        c["rounds"] += 1
        c["local_steps"] += tau
        if isinstance(rb, dict) and "targets" in rb:
            c["tokens"] += int(rb["targets"].size)

    def _ensure_tau_programs(self) -> None:
        if not hasattr(self, "tau_programs"):
            probed = make_round_step(
                self.loss_fn,
                self.opt_obj,
                self.strategy_obj,
                self.schedule_fn,
                self.axes,
                grad_clip=self.grad_clip,
                microbatch=self.microbatch,
                probe=True,
            )
            # one jit wrapper per τ: each distinct τ is a distinct XLA
            # program (different scan trip count / batch shape)
            self.tau_programs = RoundProgramCache(lambda tau: jax.jit(probed))

    def _fit_adaptive(self, ctrl: TauController, rounds: int, log) -> FitResult:
        """The adaptive-τ round loop: τ is a static shape parameter (the
        round batch's leading axis), so the controller swaps between the
        O(log τ_max) compiled programs held by ``self.tau_programs``; the
        probe-enabled round step surfaces ``consensus_drift``/``_scale``
        metrics that drive the controller's next decision."""
        self._ensure_tau_programs()
        losses: List[float] = []
        first = len(ctrl.history)
        total_steps = 0
        t0 = time.time()
        state = self.state
        for r in range(rounds):
            tau = ctrl.tau
            with trace.span("fit.round", round=self.counters["rounds"], tau=tau):
                step = self.tau_programs.program_for(tau)
                rb = round_batch(self.next_batch, tau)
                state, ms = step(state, rb)
                losses.append(float(np.asarray(ms["loss"]).mean()))
                self._count_round(tau, rb)
            ctrl.update(float(ms["consensus_drift"]), float(ms["consensus_scale"]))
            total_steps += tau
            if log is not None:
                log(r, losses[-1])
        self.state = state
        return FitResult(
            losses=losses,
            state=state,
            rounds=rounds,
            steps=total_steps,
            wall_s=time.time() - t0,
            tau_schedule=list(ctrl.history[first:]),
        )

    def _fit_faulted(self, plan, rounds: int, log, ctrl: Optional[TauController] = None) -> FitResult:
        """The fault-harness round loop (DESIGN.md §7). Each round:
        ``harness.before_round`` resolves the plan's membership (re-syncing
        rejoining workers' plane slices from the anchor) and stashes it in
        the state; the round program masks its boundary accordingly. A
        membership toggling between ``None`` (fully live) and a mask only
        retraces the jitted step once per structure — two programs total per
        τ. With ``ctrl``, fault rounds are fed into the controller as
        ``fault_hold`` decisions so a crash cannot masquerade as drift."""
        from repro.fault import FaultHarness, FaultPlan

        if not isinstance(plan, FaultPlan):
            raise TypeError(f"faults= expects a repro.fault.FaultPlan, got {type(plan).__name__}")
        if plan.m != self.workers:
            raise ValueError(f"fault plan is over m={plan.m} workers, experiment has workers={self.workers}")
        harness = FaultHarness(plan)
        if ctrl is not None:
            self._ensure_tau_programs()
        losses: List[float] = []
        first = len(ctrl.history) if ctrl is not None else 0
        total_steps = 0
        t0 = time.time()
        state = self.state
        for r in range(rounds):
            tau = ctrl.tau if ctrl is not None else self.strategy_obj.tau
            with trace.span("fit.round", round=self.counters["rounds"], tau=tau):
                state = harness.before_round(state, r)
                step = self.tau_programs.program_for(tau) if ctrl is not None else self.step_fn
                rb = round_batch(self.next_batch, tau)
                state, ms = step(_unshared(state), rb)
                self.state = state
                losses.append(float(np.asarray(ms["loss"]).mean()))
                self._count_round(tau, rb)
            if ctrl is not None:
                ctrl.update(
                    float(ms["consensus_drift"]),
                    float(ms["consensus_scale"]),
                    fault=harness.fault_reason(r),
                )
            total_steps += tau
            if log is not None:
                log(r, losses[-1])
        # leave the experiment fully live: a later fit() without faults=
        # must run the unmasked (budget-pinned) program
        self.state = state._replace(membership=None)
        return FitResult(
            losses=losses,
            state=self.state,
            rounds=rounds,
            steps=total_steps,
            wall_s=time.time() - t0,
            tau_schedule=list(ctrl.history[first:]) if ctrl is not None else None,
            fault_log=list(harness.records),
        )

    # -- evaluation ---------------------------------------------------------

    def consensus(self):
        """Float32 consensus (averaged) model — the paper's evaluation point."""
        if self.state is None:
            self.build()
        return jax.tree.map(lambda t: t.astype(jnp.float32), consensus_params(self.state))

    def consensus_plane(self):
        """The consensus model as a packed plane (lead ()): the worker mean
        of each plane bucket, cast back to the bucket dtype. Plane-resident
        experiments only — this is what the plane-resident serving engine
        consumes, and what ``swap_plane`` retargets a live engine at."""
        from repro.parallel.packing import Packed

        if self.state is None:
            self.build()
        x = self.state.x
        if not isinstance(x, Packed):
            raise ValueError("consensus_plane() requires a plane-resident (packed) experiment; use consensus()")
        bufs = tuple(jnp.mean(b.astype(jnp.float32), axis=0).astype(b.dtype) for b in x.buffers)
        return Packed(bufs, x.layout)

    def anchor_plane(self):
        """A copy of the anchor plane z (the strategy's slow consensus
        weights). A copy because the next ``fit()`` donates the round state,
        z included: an engine serving z itself would lose its buffers.
        Anchor-based packed strategies only."""
        from repro.parallel.packing import Packed

        if self.state is None:
            self.build()
        z = getattr(self.state.vars, "z", None) if self.state.vars is not None else None
        if not isinstance(z, Packed):
            raise ValueError("anchor_plane() requires a packed anchor strategy (state.vars.z is the plane)")
        return Packed(tuple(jnp.copy(b) for b in z.buffers), z.layout)

    def evaluate(self, eval_batches: int = 8) -> dict:
        """Evaluate the consensus model: classification → held-out accuracy;
        LM → mean loss on fresh held-out token batches."""
        self.build()
        p = self.consensus()
        if self.task is not None:
            acc = accuracy(p, jnp.asarray(self.splits.test.x), jnp.asarray(self.splits.test.y))
            return {"test_acc": float(acc)}
        cfg = self.model_cfg
        p = jax.tree.map(lambda t: t.astype(cfg.param_dtype), p)
        if not hasattr(self, "_eval_fn"):  # cache: one compile per experiment
            stream = self.data or TokenStream()
            self._eval_stream = lm_batch_fn(
                cfg, 1, stream.batch_per_worker, stream.seq_len, seed=stream.seed + 7919
            )
            self._eval_fn = jax.jit(lambda prm, b: self.loss_fn(prm, b)[0])
        losses = []
        for _ in range(eval_batches):
            batch = jax.tree.map(lambda t: t[0], self._eval_stream())  # drop the worker axis
            losses.append(float(self._eval_fn(p, batch)))
        return {"eval_loss": float(np.mean(losses))}

    # -- serving ------------------------------------------------------------

    def serve(self, slots: int = 4, max_len: int = 256, **engine_kw):
        """Batched generation engine over the fitted consensus params (LM
        experiments only). Plane-resident experiments are served through the
        plane directly (no unpack copy) — DESIGN.md §10 — so a later
        ``engine.swap_plane(exp.anchor_plane())`` hot-swaps a freshly
        averaged anchor into the running engine between decode steps."""
        from repro.parallel.packing import Packed
        from repro.serving import BatchedEngine

        self.build()
        if self.model_cfg is None:
            raise ValueError("serve() requires an LM experiment (arch=...), not a classification task")
        cfg = self.model_cfg
        if isinstance(self.state.x, Packed):
            p = self.consensus_plane()
        else:
            p = jax.tree.map(lambda t: t.astype(cfg.param_dtype), self.consensus())
        return BatchedEngine(cfg, p, slots=slots, max_len=max_len, **engine_kw)
