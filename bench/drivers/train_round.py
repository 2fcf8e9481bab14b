"""Training cells on one chip: Overlap-Local-SGD rounds through
``Experiment.fit(rounds=1)``, the library's own entry point.

Set-up builds the one Experiment the window drives, with the benchmark's
weights handed in where the program would make its own and the benchmark's
token rows as its feed. Its first rounds run through the window's own call
and feed, and the comparison reads them: each round's loss, the SGD
momentum and each worker's distance from the anchor after the first round,
and how far the parameters moved in the first rounds, each per leaf. The window then calls ``fit(rounds=1)`` until
its seconds are up. Once it has closed and the program's state is freed,
the plain reference runs the same rounds from the same seed.
"""
from __future__ import annotations

import contextlib
import gc

import jax
import jax.numpy as jnp
import numpy as np

from bench import harness, weights
from bench.harness import Check, log, now
from bench.reference import model as ref_model
from bench.reference import train as ref_train


@contextlib.contextmanager
def program_weights(params):
    """Hand ``params`` to ``Experiment.build`` in place of the weights the
    program would draw itself: its one model-init call returns them."""
    from repro.models import transformer as T

    made = T.init_model

    def given(cfg, key, abstract=False):
        if abstract:
            return made(cfg, key, abstract=True)
        return params, made(cfg, key, abstract=True)[1]

    T.init_model = given
    try:
        yield
    finally:
        T.init_model = made

def leaf_paths(layout):
    return [weights.path_str(p) for p, _ in jax.tree_util.tree_flatten_with_path(
        jax.tree_util.tree_unflatten(layout.treedef, [0] * layout.num_leaves))[0]]


def plane_leaf_norms(plane, base=None):
    """Per-leaf norms of a worker-stacked Packed plane, over every worker,
    keyed by leaf path; with ``base`` (a tree) the norms of plane - base."""
    layout = plane.layout
    paths = leaf_paths(layout)

    @jax.jit
    def norms(bufs, base_leaves):
        out = []
        for i, s in enumerate(layout.slots):
            seg = bufs[s.bucket][:, s.offset:s.offset + s.size].astype(jnp.float32)
            if base_leaves is not None:
                seg = seg - base_leaves[i].reshape(1, -1).astype(jnp.float32)
            out.append(jnp.sqrt(jnp.sum(seg * seg)))
        return jnp.stack(out)

    base_leaves = None if base is None else jax.tree.leaves(base)
    vals = np.asarray(norms(plane.buffers, base_leaves))
    return {p: float(v) for p, v in zip(paths, vals)}


def anchor_distances(x, anchor):
    """Per leaf and worker, the norm of that worker's distance from the
    anchor plane, keyed ``<leaf>@<worker>``."""
    layout = x.layout

    @jax.jit
    def norms(xb, zb):
        out = []
        for s in layout.slots:
            seg = slice(s.offset, s.offset + s.size)
            d = xb[s.bucket][:, seg].astype(jnp.float32) - zb[s.bucket][seg].astype(jnp.float32)
            out.append(jnp.sqrt(jnp.sum(d * d, axis=1)))
        return jnp.stack(out)

    vals = np.asarray(norms(x.buffers, anchor.buffers))
    return {f"{p}@{i}": float(v) for p, row in zip(leaf_paths(layout), vals) for i, v in enumerate(row)}


def build(ctx: harness.Context):
    """The Experiment the window drives, and its feed."""
    from repro.api import Experiment, TokenStream
    from repro.config import AlgoConfig, OptimizerConfig
    from repro.optim import schedules

    job, cfg = ctx.job, ctx.model
    m, b, seq = job["workers"], job["batch_per_worker"], job["seq_len"]
    opt, strat = job["optimizer"], job["strategy"]
    key = weights.seed_key(ctx.seed)
    shapes = harness.param_shapes(cfg)
    make = jax.jit(lambda k: weights.make_tree(k, shapes))
    params = make(key)
    exp = Experiment(
        arch=cfg,
        strategy=AlgoConfig(name=strat["name"], tau=job["tau"], alpha=strat["alpha"], anchor_beta=strat["anchor_beta"]),
        optimizer=OptimizerConfig(name=opt["name"], lr=opt["lr"], momentum=opt["momentum"], nesterov=opt["nesterov"],
                                  weight_decay=opt["weight_decay"]),
        schedule=schedules.constant(opt["lr"]),
        data=TokenStream(batch_per_worker=b, seq_len=seq),
        workers=m,
        rounds=1,
    )
    with program_weights(params):
        exp.build()
    del params
    blocks = weights.row_blocks(ctx.seed, m * b, seq, cfg.vocab_size)

    def feed():
        blk = next(blocks).reshape(m, b, seq + 1)
        return dict(tokens=jnp.asarray(blk[..., :-1]), targets=jnp.asarray(blk[..., 1:]))

    exp.next_batch = feed
    return exp, key, make


def first_rounds(exp, make, key, rounds: int):
    """Run the first ``rounds`` rounds through ``fit`` and read what the
    comparison needs; returns the readings and the seconds spent reading."""
    prog = {"losses": []}
    check_s = 0.0
    for r in range(rounds):
        prog["losses"].append(exp.fit(rounds=1).losses[0])
        t = now()
        if r == 0:
            prog["momentum"] = plane_leaf_norms(exp.state.opt.momentum)
            prog["anchor"] = anchor_distances(exp.state.x, exp.state.inflight)
        if r == rounds - 1:
            prog["change"] = plane_leaf_norms(exp.state.x, make(key))
        check_s += now() - t
    return prog, check_s


def run(ctx: harness.Context) -> dict:
    job = ctx.job
    m, tau, b, seq = job["workers"], job["tau"], job["batch_per_worker"], job["seq_len"]
    check_rounds = job["check_rounds"]
    exp, key, make = build(ctx)
    prog, check_s = first_rounds(exp, make, key, check_rounds)
    setup_s = now() - ctx.t_start - check_s

    tokens_per_round = m * tau * b * seq
    rounds, losses = 0, []
    t0 = now()
    traced = job.get("trace_rounds", 4) if ctx.tracer.on else 0
    with ctx.tracer.window():
        for _ in range(traced):
            with ctx.tracer.span("bench.fit"):
                losses += exp.fit(rounds=1).losses
            rounds += 1
    while now() - t0 < ctx.seconds:
        losses += exp.fit(rounds=1).losses
        rounds += 1
    t1 = now()
    window_s = t1 - t0
    ctx.tracer.counts.update(rounds=traced, tokens=traced * tokens_per_round, plane_elements=exp.state.x.buffers[0].size)
    device = harness.device_record(ctx.devices)
    nonfinite = int(sum(not np.isfinite(x) for x in losses))
    log(f"[train] {rounds} rounds in {window_s:.3f} s; setup {setup_s:.2f} s; program losses {prog['losses']}")

    exp.state = None
    exp.params = None
    del exp
    gc.collect()

    ref = reference(ctx, key, make, check_rounds)
    checks = compare(prog, ref, ctx.limits)
    metrics = {
        "train_tok_s_per_chip": {"value": rounds * tokens_per_round / window_s / len(ctx.devices), "unit": "tokens/s"},
        "setup_s": {"value": setup_s, "unit": "s"},
    }
    return dict(attempted=rounds, failed=nonfinite, metrics=metrics, device=device, checks=checks)


def reference(ctx, key, make, rounds, mm=ref_model.matmul, fault=None) -> dict:
    """The plain reference over the same weights and rows, on the device."""
    job, cfg = ctx.job, ctx.model
    m, b, seq = job["workers"], job["batch_per_worker"], job["seq_len"]
    blocks = weights.row_blocks(ctx.seed, m * b, seq, cfg.vocab_size)
    rows = [next(blocks).reshape(m, b, seq + 1) for _ in range(rounds * job["tau"])]
    with jax.default_matmul_precision("highest"):
        return ref_train.run_rounds(make(key), rows, ref_model.Cfg.from_file(ctx.cell.config), job,
                                    rounds, mm=mm, fault=fault)


def compare(prog: dict, ref: dict, limits: dict) -> list:
    """The numbers compared, each beside its limit."""
    loss_gap = max(abs(p - r) / abs(r) for p, r in zip(prog["losses"], ref["losses"]))
    grad_gap, grad_leaf = harness.worst_leaf_gap(prog["momentum"], ref["momentum"])
    moving = harness.moving_leaves(ref["grad"])
    change_gap, change_leaf = harness.worst_leaf_gap(prog["change"], ref["change"], moving)
    anchor_gap, anchor_leaf = harness.worst_leaf_gap(prog["anchor"], ref["anchor"],
                                                     [k for k in ref["anchor"] if k.rsplit("@", 1)[0] in moving])
    log(f"[check] reference losses {ref['losses']}; worst momentum leaf {grad_leaf}, worst change leaf {change_leaf}, "
        f"worst anchor distance {anchor_leaf}")
    return [
        Check("loss_gap", loss_gap, limits.get("loss_gap", float("nan"))),
        Check("grad_gap", grad_gap, limits.get("grad_gap", float("nan"))),
        Check("change_gap", change_gap, limits.get("change_gap", float("nan"))),
        Check("anchor_gap", anchor_gap, limits.get("anchor_gap", float("nan"))),
    ]
