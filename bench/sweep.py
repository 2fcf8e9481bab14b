#!/usr/bin/env python3
"""Find a serving cell's knee: the highest Poisson rate at which the
backlog does not grow over a window.

    python bench/sweep.py --workload <name> --rates 2 4 8 ... [--seconds 20] [--seeds 1 2 3]

One engine serves the cell's mix at each rate in turn, for ``--seconds``
each, and drains before the next; for each seed in turn, the rates rise
until the queue grows. The knee is the highest rate at which it grew for
no seed. The queue at a time is the requests that have arrived and hold no
first token yet: below the knee a free slot takes each within a few ticks,
above it they wait in line. It grows where the second half of the window
ends with more of it than the first half did, by more than a tenth of the
arrivals of the second half (and by more than 2). The backlog (arrived and
not finished) is printed beside it; it also counts the requests being
served, so with few arrivals it follows how the seed's order bunches them.
The cells' mix
files hold a fixed rate taken from this sweep; no run searches for one.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import arrivals, harness  # noqa: E402
from bench.harness import log, quantile  # noqa: E402


def backlog(lg, at: float) -> int:
    arrived = sum(1 for r in lg.req.values() if r.due_s <= at)
    done = sum(1 for rid in lg.req if lg.done(rid) and lg.times[rid][-1] <= at)
    return arrived - done


def waiting(lg, at: float) -> int:
    return sum(1 for rid, r in lg.req.items() if r.due_s <= at and not (lg.times[rid] and lg.times[rid][0] <= at))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--drain", type=float, default=30.0)
    ap.add_argument("--seeds", type=int, nargs="+", default=[1])
    args = ap.parse_args(argv)

    cell = harness.find_cell(ROOT, args.workload)
    try:
        devs = harness.require_devices(cell.chips, harness.peaks(ROOT))
    except harness.NoDevice as e:
        log(f"sweep: {e}")
        return 2
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    ctx = harness.Context(cell=cell, model=harness.model_config(cell.config), seed=args.seeds[0], seconds=args.seconds,
                          tracer=harness.Tracer(False, ROOT), devices=devs, t_start=time.perf_counter())
    drv = cell.driver()
    eng = drv.build(ctx)
    grew = set()
    for seed in args.seeds:
        for rate in args.rates:
            reqs = arrivals.open_loop(cell.job, seed, args.seconds, ctx.model.vocab_size, rate=rate,
                                      prefix=f"{seed}-{rate}-")
            lg = drv.drive(eng, reqs, args.seconds, args.drain)
            half, end = waiting(lg, args.seconds / 2), waiting(lg, args.seconds)
            late_half = sum(1 for r in reqs if r.due_s > args.seconds / 2)
            grows = end - half > max(2, 0.1 * late_half)
            ttft, itl = drv.latencies(lg, args.seconds + args.drain)
            row = dict(seed=seed, rate=rate, arrivals=len(reqs), waiting_half=half, waiting_end=end, grows=grows,
                       backlog_half=backlog(lg, args.seconds / 2), backlog_end=backlog(lg, args.seconds),
                       finished=sum(lg.done(r.rid) for r in reqs), ttft_p95_ms=quantile(ttft, 0.95) * 1e3,
                       itl_p95_ms=quantile(itl, 0.95) * 1e3, tokens_per_s=(lg.prefilled + lg.generated) / args.seconds,
                       late_max_ms=max(lg.late) * 1e3)
            print(json.dumps(row), flush=True)
            while eng.sched.busy:  # what the drain cap left
                eng.step()
            if grows:
                grew.add(rate)
                break
    held = [r for r in args.rates if not any(g <= r for g in grew)]
    knee = max(held) if held else None
    print(json.dumps({"workload": cell.name, "knee_per_s": knee}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
