#!/usr/bin/env python3
"""Smoke test of the main path on a TPU chip: train, check, serve.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # four chips: the sharded worker round only

One chip, h2o-danube-1.8b at its published widths with the depth cut:

1. training: 3 rounds at 4 layers through ``Experiment.fit``; the round
   program must hold the opt_step, anchor_mix, flash attention and rmsnorm
   kernels, and every loss must be finite;
2. serving: 4 requests off the trained consensus plane through
   ``Experiment.serve``; each returns 16 in-vocab tokens, the decode program
   holds the paged attend and append kernels, and the first decode step's
   logits agree with the jnp path;
3. kernels vs jnp: one Overlap-Local-SGD round at 2 layers through the
   Pallas kernels and again through the pure-jnp path, from the same seeded
   state; the round losses, the consensus planes, the move of the x plane
   and the two momenta must agree (the jnp reference's full attention
   matrices do not fit 16 GB at 4 layers).

A failed check fails the script after the remaining phases have reported.

Four chips (``--four-chips``): one worker per chip on the sharded round
(``launch/specs`` shardings, ``mesh_context``, ``make_round_step`` under
``jit(in_shardings=...)``) against the same 4-worker round stacked on one
chip, at 1 layer and through the jnp bodies. Each chip must hold a quarter
of the worker-stacked plane, the round program must reduce across workers,
and the losses must agree.

The last line of stdout is ``{"ok": true, "device": {...}}`` on success.
With no TPU, or when any phase fails, the script exits non-zero without it.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import importlib.metadata
import json
import os
import re
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

ARCH = "h2o-danube-1.8b"
TAU, ALPHA, ANCHOR_BETA, LR = 2, 0.6, 0.7, 1e-2
SEQ = 2048
# kernel path vs pure-jnp path, both in bf16 (unit roundoff 2^-8 relative).
# The round loss is an f32 mean over 2·2·2048 token losses: independent
# bf16 rounding differences average out, so one bf16 step bounds it. The
# states the round writes are compared by what the round did to them: the
# consensus plane, the worker-stacked x plane and the SGD momentum, each as
# ||(ak − a0) − (aj − a0)|| / ||aj − a0||, so a skipped update shows as 1
# however small the update is beside the weights (a skipped pullback
# about 1.5). The two paths' gradients differ by a few bf16 steps (flash
# attention's online softmax vs one softmax), 1–2% of the momentum; the
# moves add the elements whose bf16 rounding the two paths take apart,
# which shrinks as the move spans more bf16 steps. 2^-3 leaves room above
# both. The check round runs at CHECK_LR, a hundred times the training
# rate: at 0.1 a v5e run moved only 21% of the plane's elements and the two
# x moves differed by 9.4%. The anchor momentum v, the mean's move after two
# roundings to the bf16 grid, is too small to compare within a round (0.60
# apart at 0.1 on the v5e); the plane kernels are compared on the same
# random inputs instead. There the SGD step is written in bf16 arithmetic,
# which XLA on a TPU may carry in f32 while the kernel rounds each op: on
# the v5e 41.6% of its x outputs differed, 9% by more than a bf16 step of
# the result. Each of its five roundings is at most 2^-8 of the terms it
# adds, so an output element may differ by about 5·2^-8 of the sum of their
# magnitudes (5.3 when the CPU carries the same formula in f32); 2^-5 holds
# it, and a skipped update, which moves an element by about its own size,
# does not fit.
# Logits come out of 4 bf16 layers whose attention the two paths round in a
# different order (16-token pages with online softmax vs one softmax); a few
# bf16 steps of the largest logit bound that, while a wrong mask, page or
# scale moves logits by O(1).
CHECK_LR = 1.0
LOSS_RTOL = 2.0**-8
STATE_RTOL = 2.0**-3
SAME_INPUT_TOL = 2.0**-5
LOGIT_RTOL = 2.0**-5

TRAIN_KERNELS = ("sgd_step_flat", "pullback_momentum_flat", "flash_attention_bhsd", "rmsnorm_2d")
DECODE_KERNELS = ("paged_attend_decode", "paged_append_decode")


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(msg, flush=True)


def peak_gb(dev) -> str:
    stats = dev.memory_stats() or {}
    return f"{stats.get('peak_bytes_in_use', float('nan')) / 1e9:.3f} GB"


def kernels_in(hlo_text: str) -> set:
    """Names of the Pallas kernels (their jitted wrappers) that survive as
    TPU custom calls in a compiled program."""
    names = set()
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            names.update(re.findall(r"jit\((\w+)\)\)*/pallas_call", line))
    return names


def model_cfg(layers: int):
    from repro.config import get_arch

    return dataclasses.replace(get_arch(ARCH).model, num_layers=layers)


def experiment(cfg, workers: int, rounds: int, lr: float = LR):
    from repro.api import Experiment, TokenStream
    from repro.config import AlgoConfig, OptimizerConfig
    from repro.optim import schedules

    return Experiment(
        arch=cfg,
        strategy=AlgoConfig(name="overlap_local_sgd", tau=TAU, alpha=ALPHA, anchor_beta=ANCHOR_BETA),
        optimizer=OptimizerConfig(name="sgd", lr=lr, momentum=0.9, nesterov=True),
        schedule=schedules.constant(lr),
        data=TokenStream(batch_per_worker=1, seq_len=SEQ),
        workers=workers,
        rounds=rounds,
        seed=0,
    )


def host(bufs) -> list:
    """A plane's buffers copied to the host: the comparisons below keep
    both runs' states, which do not fit the device beside a round."""
    return [np.asarray(b) for b in bufs]


def f32(a):
    return a.astype(np.float32).ravel()


def norm(a) -> float:
    """L2 norm over every buffer of a host plane, in f32."""
    return sum(float(np.dot(f32(x), f32(x))) for x in a) ** 0.5


def dist(a, b) -> float:
    """||a − b|| over every buffer of two host planes, in f32."""
    return norm([f32(x) - f32(y) for x, y in zip(a, b)])


def moved(a, b) -> float:
    """Share of the elements that differ between two host planes."""
    return sum(int(np.count_nonzero(x != y)) for x, y in zip(a, b)) / sum(x.size for x in a)


# -- one chip -----------------------------------------------------------------


def phase_kernels_vs_jnp(dev, layers: int = 2) -> None:
    """One round from the same seeded state, jnp path first, kernel path
    second; each experiment is freed before the next is built. Compared:
    the round loss, the consensus plane, how far the round moved the
    worker-stacked x plane, and the SGD momentum. Then the two plane
    kernels against their jnp references on the same inputs."""
    from repro.kernels import flags

    runs = {}
    x0 = None
    for path in ("jnp", "kernels"):
        exp = experiment(model_cfg(layers), workers=2, rounds=1, lr=CHECK_LR).build()
        start = host(exp.state.x.buffers)
        if x0 is None:
            x0 = start
        check(all(np.array_equal(a, b) for a, b in zip(start, x0)), "the two paths start from different states")
        del start
        # use_pallas() is read when the round program is traced, at its
        # first call inside fit()
        with flags.force_pallas(path == "kernels"):
            res = exp.fit(rounds=1)
        s = exp.state
        runs[path] = dict(
            loss=res.losses[0],
            plane=host(exp.consensus_plane().buffers),
            x=host(s.x.buffers),
            momentum=host(s.opt.momentum.buffers),
        )
        del exp, res, s
    j, k = runs["jnp"], runs["kernels"]
    p0 = [b[0] for b in x0]  # every worker starts at the same point
    dl = abs(k["loss"] - j["loss"]) / abs(j["loss"])
    move = dist(j["x"], x0)
    check(move > 0.0, "the round did not move the x plane")
    # each difference relative to how far the round moved that state:
    # ||(ak − a0) − (aj − a0)|| / ||aj − a0||. Skipping the SGD update gives
    # 1, skipping the pullback about 1.5
    diffs = dict(
        plane=dist(k["plane"], j["plane"]) / dist(j["plane"], p0),
        x=dist(k["x"], j["x"]) / move,
        momentum=dist(k["momentum"], j["momentum"]) / norm(j["momentum"]),
    )
    log(f"[compare] {layers} layers: loss kernels {k['loss']:.6f} jnp {j['loss']:.6f} rel diff {dl:.3e} (tol {LOSS_RTOL:.3e})")
    log(
        f"[compare] the round moved x by {move / norm(x0):.3e} of its norm; {moved(j['x'], x0):.4f} of its "
        f"elements changed (jnp path), {moved(k['x'], x0):.4f} (kernel path)"
    )
    for name, d in diffs.items():
        log(f"[compare] {name}: kernels vs jnp, relative to the round's move, {d:.3e} (tol {STATE_RTOL:.3e})")
    n = x0[0].shape[-1]
    del runs, j, k, x0, p0
    failures = plane_kernels_vs_jnp(n)
    log(f"[compare] peak memory {peak_gb(dev)}")
    check(not failures, "; ".join(failures))
    check(np.isfinite(dl), "non-finite loss in the kernel/jnp comparison")
    check(dl <= LOSS_RTOL, f"kernel and jnp round losses differ by {dl:.3e} > {LOSS_RTOL:.3e}")
    for name, d in diffs.items():
        check(d <= STATE_RTOL, f"kernel and jnp {name} differ by {d:.3e} of the round's move > {STATE_RTOL:.3e}")


def plane_kernels_vs_jnp(n: int) -> list:
    """One SGD step and one pullback with anchor momentum over a random
    worker-stacked bf16 plane of n columns, through the kernels and through
    jnp. Inputs of one scale, so every output element moves by a good
    share of itself: a skipped or wrong update cannot hide below bf16
    rounding. Each output element is held to SAME_INPUT_TOL of the sum of
    the magnitudes of the terms it is made of. Returns the failures."""
    from repro.kernels import flags
    from repro.kernels.anchor_mix import ops as anchor_ops
    from repro.kernels.opt_step import ops as opt_ops

    keys = jax.random.split(jax.random.PRNGKey(1), 5)
    plane = lambda i, shape: (0.02 * jax.random.normal(keys[i], shape)).astype(jnp.bfloat16)  # noqa: E731
    x, g, m = plane(0, (2, n)), plane(1, (2, n)), plane(2, (2, n))
    z, v = plane(3, (n,)), plane(4, (n,))
    X, G, M, Z, V = (np.abs(np.asarray(t, np.float32)) for t in (x, g, m, z, v))
    mu, lr, a, b = 0.9, CHECK_LR, ALPHA, ANCHOR_BETA
    m_scale = mu * M + G
    x_scale = (1 - a) * X + a * Z
    v_scale = b * V + x_scale.mean(axis=0) + Z
    cases = dict(
        # outputs (x_new, m_new)
        sgd_step=(
            lambda x, g, m: opt_ops.sgd_step(x, g, m, jnp.float32(lr), momentum=mu, nesterov=True, weight_decay=0.0),
            (x, g, m),
            (X + lr * (mu * m_scale + G), m_scale),
        ),
        # outputs (x_new, z_next, v_new)
        pullback_momentum=(
            lambda x, z, v: anchor_ops.pullback_mean_momentum(x, z, v, a, b),
            (x, z, v),
            (x_scale, Z + v_scale, v_scale),
        ),
    )
    del X, G, M, Z, V
    failures = []
    for name, (fn, args, scales) in cases.items():
        outs = {}
        for path in ("kernels", "jnp"):
            with flags.force_pallas(path == "kernels"):  # a fresh jit: traced under this flag
                outs[path] = host(jax.jit(lambda *t: fn(*t))(*args))
        for i, (ok, oj, scale) in enumerate(zip(outs["kernels"], outs["jnp"], scales)):
            err = np.abs(ok.astype(np.float32) - oj.astype(np.float32)) / scale
            far = int(np.count_nonzero(~(err <= SAME_INPUT_TOL)))  # NaN counts as far
            log(f"[compare] {name} output {i} on the same inputs: {np.count_nonzero(ok != oj) / ok.size:.3e} of "
                f"{ok.size} elements differ, at most {err.max() / 2.0**-8:.2f} bf16 roundings (2^-8) of their "
                f"terms; {far} beyond {SAME_INPUT_TOL / 2.0**-8:.0f}")
            if far:
                failures.append(f"{name} output {i}: {far} elements differ from jnp by more than {SAME_INPUT_TOL:.3e} of their terms")
    return failures


def phase_train(dev, layers: int = 4, rounds: int = 3):
    exp = experiment(model_cfg(layers), workers=2, rounds=rounds).build()
    cfg = exp.model_cfg
    a = cfg.attention
    log(
        f"[train] {cfg.name} x{layers} layers: d_model {cfg.d_model}, heads {a.num_heads}/{a.num_kv_heads}, "
        f"head_dim {a.head_dim}, d_ff {cfg.d_ff}, vocab {cfg.vocab_size}, window {a.sliding_window}, "
        f"{cfg.dtype}; {exp.num_params / 1e6:.1f}M params, 2 workers, tau {TAU}, seq {SEQ}"
    )
    batch = jax.ShapeDtypeStruct((TAU, exp.workers, 1, SEQ), jnp.int32)
    t0 = time.perf_counter()
    compiled = exp.step_fn.lower(exp.state, dict(tokens=batch, targets=batch)).compile()
    log(f"[train] round program compile {time.perf_counter() - t0:.1f} s")
    found = kernels_in(compiled.as_text())
    log(f"[train] kernels in the round program: {sorted(found)}")
    missing = sorted(set(TRAIN_KERNELS) - found)
    check(not missing, f"round program fell back to jnp for {missing}")
    del compiled

    marks = [time.perf_counter()]

    def on_round(r, loss):
        marks.append(time.perf_counter())
        log(f"[train] round {r} loss {loss:.6f} ({marks[-1] - marks[-2]:.2f} s)")

    res = exp.fit(log=on_round)
    check(len(res.losses) == rounds and np.isfinite(res.losses).all(), f"non-finite round losses {res.losses}")
    steady = np.diff(marks)[1:]
    log(f"[train] round time after the first: {np.mean(steady):.3f} s; peak memory {peak_gb(dev)}")
    return exp


def phase_serve(dev, exp, requests: int = 4, max_new: int = 16):
    from repro.kernels import flags
    from repro.serving import init_paged_pools, paged_step

    cfg = exp.model_cfg
    eng = exp.serve(slots=4, max_len=512)
    check(eng.paged, "engine did not take the paged path")
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32) for n in np.linspace(32, 128, requests, dtype=int)]
    for i, p in enumerate(prompts):
        eng.submit(f"req-{i}", p, max_new)
    t0 = time.perf_counter()
    out = eng.run()
    dt = time.perf_counter() - t0
    for i in range(requests):
        toks = out[f"req-{i}"]
        check(len(toks) == max_new, f"req-{i} returned {len(toks)} tokens, not {max_new}")
        check(((toks >= 0) & (toks < cfg.vocab_size)).all(), f"req-{i} has out-of-vocab tokens")
    log(f"[serve] {requests} requests x {max_new} tokens in {dt:.2f} s (compiles included): {[len(out[f'req-{i}']) for i in range(requests)]}")

    # the engine's decode program: one token for each of its slots
    plane = eng.plane
    pools = init_paged_pools(cfg, eng.num_pages, eng.page_size)
    tables = jnp.zeros((eng.slots, eng.max_pages), jnp.int32)
    toks1 = jnp.zeros((eng.slots, 1), jnp.int32)
    lens = jnp.zeros((eng.slots,), jnp.int32)
    hlo = jax.jit(functools.partial(paged_step, cfg)).lower(plane, toks1, pools, tables, lens).compile().as_text()
    found = kernels_in(hlo)
    log(f"[serve] kernels in the decode program: {sorted(found)}")
    missing = sorted(set(DECODE_KERNELS) - found)
    check(not missing, f"decode program fell back to jnp for {missing}")

    # first decode step of request 0: kernel path vs jnp path
    prompt = jnp.asarray(prompts[0])[None]
    table = jnp.arange(1, eng.max_pages + 1, dtype=jnp.int32)[None]
    nxt = None
    logits = {}
    for path in ("kernels", "jnp"):
        with flags.force_pallas(path == "kernels"):
            fn = jax.jit(functools.partial(paged_step, cfg))
            pools = init_paged_pools(cfg, eng.max_pages + 1, eng.page_size)
            pre, pools = fn(plane, prompt, pools, table, jnp.zeros((1,), jnp.int32))
            if nxt is None:
                nxt = jnp.argmax(pre[:, -1], axis=-1)[:, None].astype(jnp.int32)
            step, _ = fn(plane, nxt, pools, table, jnp.asarray([prompt.shape[1]], jnp.int32))
        logits[path] = np.asarray(step[0, 0], np.float32)
    lk, lj = logits["kernels"], logits["jnp"]
    check(np.isfinite(lk).all() and np.isfinite(lj).all(), "non-finite decode logits")
    d = float(np.abs(lk - lj).max() / np.abs(lj).max())
    log(f"[serve] first decode logits kernels vs jnp: max abs diff / max |logit| {d:.3e} (tol {LOGIT_RTOL:.3e})")
    check(d <= LOGIT_RTOL, f"decode logits differ by {d:.3e} > {LOGIT_RTOL:.3e}")
    log(f"[serve] peak memory {peak_gb(dev)}")


# -- four chips -----------------------------------------------------------------


def phase_four_chips(devices, layers: int = 1, rounds: int = 2):
    """The 4-worker round, one worker per chip, against the same round
    stacked on the first chip. One layer: four workers' 2-layer round does
    not fit one chip's 16 GB. Both run the jnp bodies: XLA's SPMD
    partitioner cannot split a Pallas (Mosaic) kernel across devices, and
    the kernels have no ``shard_map`` of their own yet. The one-chip phases
    check the kernels."""
    from repro.kernels import flags

    log("[4chip] kernels off (flags.force_pallas(False)) in both runs: Mosaic kernels are not partitioned")
    with flags.force_pallas(False):
        _four_chips(devices, layers, rounds)


def _four_chips(devices, layers: int, rounds: int):
    from repro.config import AlgoConfig, OptimizerConfig, ParallelPlan
    from repro.core import make_strategy
    from repro.data.loaders import lm_batch_fn, round_batch
    from repro.launch import specs
    from repro.launch.roofline import collective_stats
    from repro.models import transformer as T
    from repro.optim import from_config, schedules
    from repro.parallel import logical_mesh, mesh_context
    from repro.training import make_round_step, make_train_state
    from jax.sharding import NamedSharding, PartitionSpec as P

    m = 4
    cfg = model_cfg(layers)
    strat = make_strategy(AlgoConfig(name="overlap_local_sgd", tau=TAU, alpha=ALPHA, anchor_beta=ANCHOR_BETA))
    opt = from_config(OptimizerConfig(name="sgd", lr=LR, momentum=0.9, nesterov=True))
    loss_fn = lambda p, b: T.lm_loss(cfg, p, b)  # noqa: E731
    batches = round_batch(lm_batch_fn(cfg, m, 1, SEQ, seed=0), TAU), round_batch(lm_batch_fn(cfg, m, 1, SEQ, seed=1), TAU)

    # reference: all four workers stacked on the first chip
    params, axes = T.init_model(cfg, jax.random.PRNGKey(0))
    log(f"[4chip] {cfg.name} x{layers} layers, {sum(x.size for x in jax.tree.leaves(params)) / 1e6:.1f}M params, {m} workers")
    state = make_train_state(params, m, opt, strat, axes)
    step = jax.jit(make_round_step(loss_fn, opt, strat, schedules.constant(LR), axes), donate_argnums=(0,))
    stacked = []
    for r in range(rounds):
        state, ms = step(state, batches[r])
        stacked.append(float(np.asarray(ms["loss"]).mean()))
    del state, step
    log(f"[4chip] stacked on {devices[0]}: losses {stacked}; peak memory {peak_gb(devices[0])}")

    plan = ParallelPlan(workers=m, fsdp=1, tensor=1)
    mesh = logical_mesh(jax.make_mesh((m,), ("data",), devices=devices), plan)
    rules = specs.TRAIN_RULES
    with mesh_context(mesh, rules):
        _, state_sh, axes = specs.train_state_specs(cfg, plan, strat, opt, mesh, rules)
        state = jax.device_put(make_train_state(params, m, opt, strat, axes), state_sh)
        batch_sh = specs.batch_shardings(batches[0], mesh, rules)
        step = jax.jit(
            make_round_step(loss_fn, opt, strat, schedules.constant(LR), axes),
            in_shardings=(state_sh, batch_sh),
            out_shardings=(state_sh, NamedSharding(mesh, P())),
            donate_argnums=(0,),
        )
        plane = state.x.buffers[0]
        shards = plane.addressable_shards
        check(
            len({s.device for s in shards}) == m and all(s.data.shape[0] == plane.shape[0] // m for s in shards),
            f"x plane {plane.shape} is not split one worker per chip: {[(s.device, s.data.shape) for s in shards]}",
        )
        log(f"[4chip] x plane {plane.shape}: shards {[(s.device.id, tuple(s.data.shape)) for s in shards]}")
        t0 = time.perf_counter()
        hlo = step.lower(state, batches[0]).compile().as_text()
        log(f"[4chip] sharded round program compile {time.perf_counter() - t0:.1f} s")
        stats = collective_stats(hlo)
        log(f"[4chip] collectives: { {k: int(v['count']) for k, v in stats.items()} }")
        check("all-reduce" in stats or "reduce-scatter" in stats, f"no cross-worker reduction in the round program: {stats}")
        sharded = []
        for r in range(rounds):
            state, ms = step(state, jax.device_put(batches[r], batch_sh))
            sharded.append(float(np.asarray(ms["loss"]).mean()))
    log(f"[4chip] one worker per chip: losses {sharded}; peak memory per chip {[peak_gb(d) for d in devices]}")
    for r, (a, b) in enumerate(zip(sharded, stacked)):
        d = abs(a - b) / abs(b)
        log(f"[4chip] round {r}: sharded {a:.6f} stacked {b:.6f} rel diff {d:.3e} (tol {LOSS_RTOL:.3e})")
        check(np.isfinite(a) and d <= LOSS_RTOL, f"round {r} losses differ by {d:.3e}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true", help="run only the sharded 4-worker round on four chips")
    args = ap.parse_args()

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        log(f"chip_smoke: needs a TPU, JAX found {dev.platform} ({dev.device_kind})")
        return 1
    from repro.utils.compile_cache import enable_compile_cache

    log(f"[device] {dev.platform} {dev.device_kind} x{len(devices)}; jax {jax.__version__}, "
        f"jaxlib {importlib.metadata.version('jaxlib')}, libtpu {importlib.metadata.version('libtpu')}; "
        f"compile cache {enable_compile_cache()}")
    if args.four_chips and len(devices) < 4:
        log(f"chip_smoke: --four-chips needs 4 devices, found {len(devices)}")
        return 1
    t0 = time.perf_counter()
    failed = []

    def run(name, fn, *a):
        """Run one phase; a failed check is reported and fails the script
        at the end, so the phases after it still report."""
        try:
            return fn(*a)
        except SmokeFailure as e:
            log(f"chip_smoke FAILED in {name}: {e}")
            failed.append(name)

    if args.four_chips:
        run("four chips", phase_four_chips, devices[:4])
    else:
        exp = run("train", phase_train, dev)
        if exp is not None:
            run("serve", phase_serve, dev, exp)
        del exp  # the comparison needs the device to itself
        run("kernels vs jnp", phase_kernels_vs_jnp, dev)
    log(f"[done] {time.perf_counter() - t0:.1f} s")
    if failed:
        return 1
    print(json.dumps({"ok": True, "device": {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
