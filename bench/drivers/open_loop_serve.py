"""Serving cells: open-loop Poisson arrivals into ``BatchedEngine`` on a
plane-resident ``Packed`` plane, as ``Experiment.serve`` builds it.

Set-up draws the plane from the seed in one jitted call and warms the
engine's two programs (a prefill chunk and the joint decode) with one
request that needs both. The window then submits each request when it is
due, steps the engine whenever it has work, and stamps every token when the
host holds it. After the window no request is sent; the engine steps on to
finish those that arrived in it, up to ``drain_s``. Each request is timed
from when it was due, so a stalled engine shows in the latency of the
requests behind it, and the run reports how late the generator ran.

Once that is done and the engine is freed, the plain reference reads a
sample of the finished requests (with the longest in it) and gives the
widest gap by which a served token's logit lies below its best.
"""
from __future__ import annotations

import contextlib
import gc
import time

import jax
import numpy as np

from bench import arrivals, harness, weights
from bench.harness import Check, log, now, quantile
from bench.reference import model as ref_model
from bench.reference import serve as ref_serve



def make_plane(cfg, seed: int):
    """The served plane in the program's layout, drawn from the seed
    straight into its buffers: no second copy of the weights is ever held
    (the program's own ``pack`` indexes in int32, which a 12-layer plane of
    3.89e9 elements outgrows)."""
    from repro.parallel.packing import Packed, layout_of

    shapes = harness.param_shapes(cfg)
    layout = layout_of(shapes)
    return Packed(jax.jit(lambda k: weights.make_plane_buffers(k, shapes, layout))(weights.seed_key(seed)), layout)


def build(ctx: harness.Context):
    from repro.serving import BatchedEngine

    e = ctx.job["engine"]
    eng = BatchedEngine(ctx.model, make_plane(ctx.model, ctx.seed), slots=e["slots"], max_len=e["max_len"],
                        page_size=e["page_size"], chunk=e["chunk"])
    # one request that needs both programs: two prefill chunks, then decode
    eng.submit("warm", np.arange(e["chunk"] + 1, dtype=np.int32) % ctx.model.vocab_size, 2)
    eng.run()
    return eng


class Log:
    """What the host saw of each request: when each token arrived."""

    def __init__(self, reqs):
        self.req = {r.rid: r for r in reqs}
        self.times = {r.rid: [] for r in reqs}
        self.tokens = {r.rid: [] for r in reqs}
        self.prefilled = 0  # prompt tokens prefilled inside the window
        self.generated = 0  # tokens generated inside the window
        self.late = []  # how late each request was submitted, seconds
        self.trace = dict(decode_steps=0, decode_tokens=0, decode_kv_tokens=0)

    def done(self, rid) -> bool:
        return len(self.tokens[rid]) >= self.req[rid].max_new


def _snapshot(eng):
    out = {}
    for a in eng.sched.active:
        if a is not None:
            out[a.req.rid] = (a.prefill_done, len(a.generated), a.length)
    return out


def step(eng, lg: Log, t: float, in_window: bool, traced: bool) -> None:
    """One engine tick, and what it produced stamped at ``t`` after it."""
    before = _snapshot(eng)
    eng.step()
    stamp = t()
    decode = 0
    for a in eng.sched.active:
        if a is None or a.req.rid not in lg.times:
            continue
        rid = a.req.rid
        was_done, was_gen, was_len = before.get(rid, (False, 0, 0))
        new = len(a.generated) - was_gen
        if new > 0:
            lg.times[rid] += [stamp] * new
            lg.tokens[rid] = list(a.generated)
        prefilled = min(a.length, len(a.req.prompt)) - min(was_len, len(a.req.prompt)) if not was_done else 0
        in_decode = (was_done and new >= 1) or (not was_done and new == 2)
        if in_window:
            lg.prefilled += prefilled
            lg.generated += new
        if traced and in_decode:
            decode += 1
            lg.trace["decode_kv_tokens"] += a.length
    if traced and decode:
        lg.trace["decode_steps"] += 1
        lg.trace["decode_tokens"] += decode


def drive(eng, reqs, seconds: float, drain_s: float, tracer=None, trace_from: float = 0.0, trace_s: float = 0.0) -> Log:
    """Offer ``reqs`` open loop for ``seconds``, then finish them."""
    lg = Log(reqs)
    pending = list(reqs)
    t0 = now()
    t = lambda: now() - t0  # noqa: E731
    stack = contextlib.ExitStack()
    tracing = False
    while True:
        if tracer is not None and tracer.on:
            if not tracing and trace_from <= t() < trace_from + trace_s:
                stack.enter_context(tracer.window())
                tracing = True
            elif tracing and t() >= trace_from + trace_s:
                stack.close()
                tracer.counts.update(lg.trace)
                tracer, tracing = None, False
        while pending and pending[0].due_s <= t():
            r = pending.pop(0)
            lg.late.append(t() - r.due_s)
            eng.submit(r.rid, r.prompt, r.max_new)
        if t() >= seconds:
            break
        if eng.sched.busy:
            ctx = tracer.span("bench.step") if tracing else contextlib.nullcontext()
            with ctx:
                step(eng, lg, t, True, tracing)
        else:
            time.sleep(max(0.0, min(pending[0].due_s if pending else seconds, seconds) - t()))
    stack.close()
    end = seconds + drain_s
    while eng.sched.busy and not all(lg.done(r.rid) for r in reqs) and t() < end:
        step(eng, lg, t, False, False)
    lg.window_s = seconds
    return lg


def latencies(lg: Log, cap: float):
    """TTFT of every request (the cap, from its due time, where it never got
    a first token) and every gap between consecutive tokens, in seconds."""
    ttft, itl = [], []
    for rid, r in lg.req.items():
        ts = lg.times[rid]
        ttft.append((ts[0] if ts else cap) - r.due_s)
        itl += list(np.diff(ts))
    return ttft, itl


def run(ctx: harness.Context) -> dict:
    job, cfg = ctx.job, ctx.model
    reqs = arrivals.open_loop(job, ctx.seed, ctx.seconds, cfg.vocab_size)
    eng = build(ctx)
    setup_s = now() - ctx.t_start
    lg = drive(eng, reqs, ctx.seconds, job["drain_s"], ctx.tracer, ctx.seconds * job["trace_from"], job["trace_seconds"])
    device = harness.device_record(ctx.devices)
    finished = [r.rid for r in reqs if lg.done(r.rid)]
    ttft, itl = latencies(lg, ctx.seconds + job["drain_s"])
    ctx.tracer.counts.update(slots=job["engine"]["slots"])
    log(f"[serve] {len(reqs)} arrivals, {len(finished)} finished; late p50 {quantile(lg.late, 0.5) * 1e3:.2f} ms "
        f"max {max(lg.late) * 1e3:.2f} ms; {lg.prefilled} prompt and {lg.generated} generated tokens in the window; "
        f"ttft p50 {quantile(ttft, 0.5) * 1e3:.1f} ms, p95 {quantile(ttft, 0.95) * 1e3:.1f} ms; itl p50 {quantile(itl, 0.5) * 1e3:.2f} ms; setup {setup_s:.2f} s")
    del eng
    gc.collect()

    sample = check_sample(lg, finished, ctx.seed, job["check_requests"])
    gap = max((float(np.max(served_gaps(ctx, lg, rid))) for rid in sample), default=float("inf"))
    checks = [Check("logit_gap", gap, ctx.limits.get("logit_gap", float("nan")))]
    metrics = {
        "setup_s": {"value": setup_s, "unit": "s"},
        "serve_itl_p95_ms": {"value": quantile(itl, 0.95) * 1e3, "unit": "ms"},
    }
    return dict(attempted=len(reqs), failed=len(reqs) - len(finished), metrics=metrics, device=device, checks=checks)


def check_sample(lg: Log, finished, seed: int, k: int):
    """``k`` finished requests drawn from the seed, the longest among them."""
    if not finished:
        return []
    longest = max(finished, key=lambda rid: len(lg.req[rid].prompt) + len(lg.tokens[rid]))
    rest = [rid for rid in finished if rid != longest]
    pick = weights.seed_rng(seed, 3).permutation(len(rest))[: k - 1]
    return [longest] + [rest[i] for i in sorted(pick)]


def served_gaps(ctx, lg: Log, rid: str, control: bool = False) -> np.ndarray:
    r = lg.req[rid]
    served = np.asarray(lg.tokens[rid][: r.max_new], np.int32)
    return ref_serve.served_gaps(weights.seed_key(ctx.seed), ref_model.Cfg.from_file(ctx.cell.config),
                                 ctx.cell.config["dtype"], r.prompt, served, ctx.job["engine"]["max_len"], control)
