"""Round-based training engine for two-phase communication strategies.

One *round* = τ local steps (lax.scan) + the strategy's two boundary phases,
driven through the combined ``boundary_round`` hook:

    boundary_apply(x, vars, inflight)      consume the collective launched at
                                           the PREVIOUS boundary (eq. 4)
    boundary_launch(x, vars) -> inflight   start this round's collective
                                           (eq. 5), carried in TrainState

Packed strategies (``AlgoConfig.packed``, the default) override
``boundary_round`` to run both phases fused over the packed parameter plane
— anchor-shaped state and inflight slots are then flat
:class:`repro.parallel.packing.Packed` buffers rather than pytrees.

Plane-resident training (packed strategy + packed-capable optimizer): the
packed parameter plane is the *canonical* representation end-to-end.
``TrainState.x`` stores the worker-stacked plane across rounds, the τ-step
scan carries it, and the loss is differentiated **with the plane buffers as
the primal argument** — the model reads parameters through a
:class:`repro.parallel.packing.ParamView` (lazy ``view_leaf`` windows whose
slices XLA fuses into the leaf consumers), so gradients arrive as one flat
cotangent buffer per dtype bucket. The engine itself never touches a
parameter pytree: the per-microstep ``pack(grads)`` call is gone (the one
plane build per step is the window read's AD transpose, emitted by the
packing layer — see ``read_windows``), there is no per-round pack/unpack
seam (``boundary_round`` consumes and returns the plane), and the
gradient hook runs as ``transform_grads_packed`` (one collective per dtype
bucket for sync-SGD; PowerSGD's elementwise error feedback per-bucket, with
only its inherently per-leaf rank-r factor math left per-leaf), the
optimizer update is one fused ``kernels/opt_step`` launch per bucket
against flat optimizer-state buffers carried in ``TrainState.opt``, and
mid-round consumers (DaSGD) rebase the plane in place via
``local_post_update_packed``. The per-leaf path remains intact as the
bit-exact oracle (``packed=False``), pinned by tests/test_packed_optim.py.

Gradient clipping follows the same split: by default the plane-resident
step computes the global norm with the per-leaf summation order (window
reads off the plane — bitwise-identical to ``clip_by_global_norm``, keeping
the golden pin); ``AlgoConfig.packed_clip`` opts into per-bucket partial
square-sums feeding the one global scale (O(buckets) reductions, a
different f32 summation order, ≤ a few ulps apart).

Because launch and consume are distinct phases separated by τ local steps,
the anchor collective's consumer lies a full round downstream when several
rounds are scanned into one program (``rounds_per_call > 1``, the production
setting) — the latency-hiding scheduler overlaps it with local compute, the
JAX-native form of the paper's communication thread. Delayed-averaging
strategies consume mid-round instead via the per-step
``local_post_update(x, vars, inflight, k)`` hook, which receives the local
step index within the round.

Legacy single-hook ``Algorithm`` objects are accepted everywhere a strategy
is and run through :class:`repro.core.strategy.LegacyStrategy` (their whole
``boundary`` executes in the apply phase — seed semantics, bit for bit).

Batch layout: a *round batch* is a pytree whose array leaves are shaped
(τ, m, per_worker_batch, ...) — scanned over τ, vmapped over m.
"""
from __future__ import annotations

from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.strategy import as_strategy
from repro.optim.optimizers import (
    Optimizer,
    clip_by_global_norm,
    clip_packed_by_global_norm,
    offload_capable,
    packed_capable,
)
from repro.parallel import offload as off
from repro.parallel.packing import Packed, ParamView, pack
from repro.training.train_state import TrainState


def make_round_step(
    loss_fn: Callable,  # (params, batch) -> (loss, metrics)
    optimizer: Optimizer,
    strategy,  # CommStrategy or legacy Algorithm
    schedule: Callable,
    axes_tree: Any = None,
    grad_clip: float = 0.0,
    microbatch: Optional[int] = None,
    probe: bool = False,
):
    """``probe=True`` runs the boundary with the fused consensus probe
    (DESIGN.md §6) and adds scalar ``consensus_drift`` / ``consensus_scale``
    metrics — the adaptive-τ controller's inputs, measured on the round-end
    plane at zero extra kernel launches for pullback-family strategies."""
    strategy = as_strategy(strategy)
    # plane-resident local step: the scan carries the packed plane, the loss
    # is differentiated with the plane as the primal (params reach the model
    # through a ParamView), and grads flow as flat per-bucket cotangents
    # straight into the packed gradient hook + fused optimizer launch
    packed_step = strategy.packed and packed_capable(optimizer)
    packed_clip = packed_step and bool(getattr(strategy.cfg, "packed_clip", False))
    # host-offloaded state (AlgoConfig.offload): opt/anchor/inflight buckets
    # are HostPlanes between boundaries; the opt update streams them through
    # the double buffer each local step, anchor-shaped state round-trips
    # whole-plane at the window edges (DESIGN.md §9)
    offload_on = bool(getattr(strategy.cfg, "offload", False))
    if offload_on and not packed_step:
        raise ValueError("AlgoConfig.offload requires a packed strategy and a packed-capable optimizer")
    if offload_on and not offload_capable(optimizer):
        raise ValueError("AlgoConfig.offload requires an optimizer with a streamed step (step_streamed)")
    offload_chunk_mb = float(getattr(strategy.cfg, "offload_chunk_mb", off.DEFAULT_CHUNK_MB))
    if packed_step:
        # differentiate with the STACKED plane as the primal: materialize
        # the worker-stacked view once (a single read_windows site), vmap
        # the per-worker loss over it, and take the gradient of the summed
        # losses — each worker's loss cotangent seed is the same 1.0 the
        # vmapped per-worker grad uses, so the stacked cotangent plane is
        # the per-worker grads stacked, bitwise. Keeping the window read
        # (and its DUS-chain transpose) OUTSIDE the vmap matters: the DUS
        # batching rule lowers to select/iota masked writes.
        def _summed_loss(px, micro):
            view = ParamView(px).materialize()
            losses, metrics = jax.vmap(loss_fn)(view, micro)
            return jnp.sum(losses), metrics

        worker_grads = jax.grad(_summed_loss, has_aux=True)
    else:
        worker_grads = jax.vmap(jax.grad(loss_fn, has_aux=True))

    def stacked_grads(x, micro):
        """Per-worker grads, with optional gradient accumulation over
        microbatches (large per-worker batches on big-vocab/MoE archs).
        Metrics are averaged across microbatches. ``x`` is the per-mode
        primal — the stacked pytree, or the stacked plane."""
        leaves = jax.tree.leaves(micro)
        b = leaves[0].shape[1]
        if microbatch is None or b <= microbatch:
            return worker_grads(x, micro)
        k = b // microbatch
        split = jax.tree.map(
            lambda t: t.reshape((t.shape[0], k, microbatch) + t.shape[2:]).swapaxes(0, 1), micro
        )

        def acc(carry, mb):
            g_acc, m_acc = carry
            g, mets = worker_grads(x, mb)
            g_acc = jax.tree.map(lambda a, gg: a + gg.astype(a.dtype), g_acc, g)
            m_acc = jax.tree.map(lambda a, mm: a + mm.astype(jnp.float32), m_acc, mets)
            return (g_acc, m_acc), None

        g0 = jax.tree.map(lambda t: jnp.zeros(t.shape, jnp.float32), x)
        m_sds = jax.eval_shape(lambda mb: worker_grads(x, mb)[1], jax.tree.map(lambda t: t[0], split))
        m0 = jax.tree.map(lambda s: jnp.zeros(s.shape, jnp.float32), m_sds)
        (g_sum, m_sum), _ = jax.lax.scan(acc, (g0, m0), split)
        grads = jax.tree.map(lambda g, xx: (g / k).astype(xx.dtype), g_sum, x)
        metrics = jax.tree.map(lambda s, ref: (s / k).astype(ref.dtype), m_sum, m_sds)
        return grads, metrics

    def round_step(state: TrainState, round_batch) -> Tuple[TrainState, dict]:
        inflight = state.inflight

        def local_step(carry, scanned):
            micro, k_in_round = scanned
            x, opt, vars, step = carry  # x: the packed plane when plane-resident
            lr = schedule(step)
            grads, metrics = stacked_grads(x, micro)
            if grad_clip > 0.0:
                if packed_step:
                    grads = jax.vmap(
                        lambda g: clip_packed_by_global_norm(g, grad_clip, per_bucket=packed_clip)[0]
                    )(grads)
                else:
                    grads = jax.vmap(lambda g: clip_by_global_norm(g, grad_clip)[0])(grads)
            if packed_step:
                pg, vars = strategy.transform_grads_packed(grads, vars)
                if offload_on:
                    # streamed update: the host-resident state buckets walk
                    # through the two device staging chunks per bucket
                    opt, x = optimizer.step_streamed(opt, x, pg, lr)
                else:
                    opt, x = optimizer.step_packed(opt, x, pg, lr)
                x = strategy.local_post_update_packed(x, vars, inflight, k_in_round)
            else:
                grads, vars = strategy.transform_grads(grads, vars)
                opt, x = jax.vmap(lambda o, xi, gi: optimizer.step(o, xi, gi, lr))(opt, x, grads)
                x = strategy.local_post_update(x, vars, inflight, k_in_round)
            metrics = dict(metrics, lr=jnp.broadcast_to(lr, metrics["loss"].shape))
            return (x, opt, vars, step + 1), metrics

        tau = jax.tree.leaves(round_batch)[0].shape[0]
        x0 = state.x
        if packed_step and not isinstance(x0, Packed):
            # migration path for states built (or restored) per-leaf: the
            # first round adopts the plane; from then on x stays resident
            x0 = pack(x0, lead=1)
        opt0, vars0 = state.opt, state.vars
        plan = None
        if offload_on:
            plan = off.plan_of(opt0)
            if plan is None:
                # adoption: a resident state entering the offloaded engine
                plan = off.OffloadPlan.for_layout(x0.layout, offload_chunk_mb)
                opt0 = off.tree_offload(opt0, plan)
            else:
                # pin the carry's memory space to the host, as the streamed
                # update leaves it (a no-op for host-resident arrays; it
                # types abstract inputs, which carry no memory space)
                opt0 = off.tree_pin_host(opt0)
            # prefetch (H2D) of the anchor-shaped state: vars ride the scan
            # carry, so they restore up front; the inflight slot restores up
            # front only for mid-round consumers (DaSGD) — otherwise right
            # at the boundary, so its device live range starts at the copy.
            # Either way the copy has no data dependency on the local scan
            # and the latency-hiding scheduler overlaps it with the τ steps,
            # exactly like the collective it rides next to.
            vars0 = off.tree_restore(vars0)
            if strategy.consumes_inflight_midround:
                inflight = off.tree_restore(inflight)
        (x, opt, vars, step), metrics = jax.lax.scan(
            local_step,
            (x0, opt0, vars0, state.step),
            (round_batch, jnp.arange(tau)),
        )
        if offload_on:
            inflight = off.tree_restore(inflight)  # no-op when already device-resident
        # apply + launch in one hook: per-leaf strategies run the two phases
        # back to back; packed strategies fuse them over the flat parameter
        # plane (one collective + one kernel launch per boundary) and return
        # the plane itself — x never leaves the packed representation, so
        # there is no pack/unpack seam at round granularity.
        # the membership installed by the fault harness (None on clean
        # rounds) masks the boundary; it is carried through unchanged — the
        # harness owns installing/clearing it between rounds (DESIGN.md §7)
        membership = state.membership
        if probe:
            x, vars, inflight, stats = strategy.boundary_round(
                x, vars, inflight, axes_tree, probe=True, membership=membership
            )
            metrics = dict(metrics, consensus_drift=stats.drift, consensus_scale=stats.scale)
        else:
            x, vars, inflight = strategy.boundary_round(x, vars, inflight, axes_tree, membership=membership)
        if offload_on:
            # D2H: the boundary's outputs go back host-resident until the
            # next window needs them (opt state already streamed back
            # chunk-by-chunk inside the scan)
            vars = off.tree_offload(vars, plan)
            inflight = off.tree_offload(inflight, plan)
        new_state = TrainState(x=x, opt=opt, vars=vars, step=step, inflight=inflight, membership=membership)
        return new_state, metrics

    return round_step


def make_train_fn(
    loss_fn: Callable,
    optimizer: Optimizer,
    strategy,  # CommStrategy or legacy Algorithm
    schedule: Callable,
    axes_tree: Any = None,
    grad_clip: float = 0.0,
    rounds_per_call: int = 1,
    donate: bool = True,
    microbatch: Optional[int] = None,
):
    """jit'd multi-round step: (state, batches[(R, τ, m, b, ...)]) -> (state, metrics)."""
    round_step = make_round_step(loss_fn, optimizer, strategy, schedule, axes_tree, grad_clip, microbatch)
    strategy_obj = as_strategy(strategy)
    packed_step = strategy_obj.packed and packed_capable(optimizer)
    offload_on = packed_step and bool(getattr(strategy_obj.cfg, "offload", False))

    def many(state, batches):
        if packed_step and not isinstance(state.x, Packed):
            # migrate a per-leaf state BEFORE the rounds scan: round_step's
            # own coercion changes the TrainState structure, which a
            # multi-round lax.scan carry cannot absorb mid-body
            state = state._replace(x=pack(state.x, lead=1))
        if offload_on and not off.is_offloaded(state.opt):
            # same structural constraint for a resident state entering the
            # offloaded engine: adopt the host form before the rounds scan
            plan = off.OffloadPlan.for_layout(
                state.x.layout, float(getattr(strategy_obj.cfg, "offload_chunk_mb", off.DEFAULT_CHUNK_MB))
            )
            state = state._replace(
                opt=off.tree_offload(state.opt, plan),
                vars=off.tree_offload(state.vars, plan),
                inflight=off.tree_offload(state.inflight, plan),
            )
        if rounds_per_call == 1:
            rb = jax.tree.map(lambda t: t[0], batches)
            return round_step(state, rb)
        return jax.lax.scan(round_step, state, batches)

    return jax.jit(many, donate_argnums=(0,) if donate else ())


def stack_round_batches(per_step_batches) -> Any:
    """List (len τ) of per-step batches with leaves (m, b, ...) -> leaves (τ, m, b, ...)."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *per_step_batches)
