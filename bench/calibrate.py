#!/usr/bin/env python3
"""Read the numbers that set a cell's limits, all in one process.

    python bench/calibrate.py --workload <name> --seeds 1 2 ... [--control-seeds 1 2 3]
                              [--seconds <s>] --out <readings.jsonl>

For every seed: the program's readings of the numbers ``correct`` compares,
against the plain reference, as a run of the cell takes them. For every
control seed besides: the same numbers with the reference computed in
float8 put in the program's place (the control), and with each fault the
cell can have planted in that reference (training) or in the served tokens
(serving). The lower reading of a limit is the largest program reading;
the upper is the smallest control or fault reading. One JSON line per seed
and reading goes to ``--out``.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

T_START = time.perf_counter()
ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import numpy as np  # noqa: E402

from bench import harness  # noqa: E402
from bench.harness import log  # noqa: E402
from bench.reference import model as ref_model  # noqa: E402
from bench.reference import train as ref_train  # noqa: E402


def train_seed(ctx, drv, control: bool):
    rounds = ctx.job["check_rounds"]
    exp, key, make = drv.build(ctx)
    prog, _ = drv.first_rounds(exp, make, key, rounds)
    exp.state = exp.params = None
    del exp
    gc.collect()
    ref = drv.reference(ctx, key, make, rounds)
    rows = {"program": drv.compare(prog, ref, {})}
    if control:
        rows["control_fp8"] = drv.compare(drv.reference(ctx, key, make, rounds, mm=ref_model.fp8_matmul), ref, {})
        for fault in ref_train.FAULTS:
            rows[f"fault_{fault}"] = drv.compare(drv.reference(ctx, key, make, rounds, fault=fault), ref, {})
    return rows


def serve_seed(ctx, drv, control: bool):
    from bench import arrivals

    job = ctx.job
    reqs = arrivals.open_loop(job, ctx.seed, ctx.seconds, ctx.model.vocab_size)
    eng = drv.build(ctx)
    lg = drv.drive(eng, reqs, ctx.seconds, job["drain_s"])
    del eng
    gc.collect()
    finished = [r.rid for r in reqs if lg.done(r.rid)]
    sample = drv.check_sample(lg, finished, ctx.seed, job["check_requests"])
    gaps = {rid: drv.served_gaps(ctx, lg, rid) for rid in sample}
    served = sum(len(g) for g in gaps.values())
    rows = {"program": [harness.Check("logit_gap", max(float(np.max(g)) for g in gaps.values()), float("nan"))]}
    log(f"[calibrate] {len(finished)} of {len(reqs)} finished; {served} served tokens compared")
    if control:
        ctrl = [float(np.max(drv.served_gaps(ctx, lg, rid, control=True))) for rid in sample]
        rows["control_fp8"] = [harness.Check("logit_gap", max(ctrl), float("nan"))]
        # a token altered where it is produced: each sampled request's
        # middle token replaced by its successor in the vocabulary
        worst = 0.0
        for rid in sample:
            toks = lg.tokens[rid]
            i = len(toks) // 2
            toks[i] = (toks[i] + 1) % ctx.model.vocab_size
            worst = max(worst, float(np.max(drv.served_gaps(ctx, lg, rid))))
        rows["fault_token_altered"] = [harness.Check("logit_gap", worst, float("nan"))]
    return rows


def main(argv=None, root: Path = ROOT, devices=harness.require_devices) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--seconds", type=float, default=10.0, help="serving: the short window at the cell's load")
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    cell = harness.find_cell(root, args.workload)
    try:
        devs = devices(cell.chips, harness.peaks(root))
    except harness.NoDevice as e:
        log(f"calibrate: {e}")
        return 2
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    drv = cell.driver()
    seed_fn = train_seed if cell.job["driver"].startswith("train") else serve_seed
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    with open(args.out, "a") as f:
        for seed in dict.fromkeys(args.seeds + args.control_seeds):
            t = time.perf_counter()
            ctx = harness.Context(cell=cell, model=harness.model_config(cell.config), seed=seed, seconds=args.seconds,
                                  tracer=harness.Tracer(False, root), devices=devs, t_start=t)
            rows = seed_fn(ctx, drv, seed in args.control_seeds)
            for kind, checks in rows.items():
                line = {"workload": cell.name, "seed": seed, "reading": kind, **{c.name: c.value for c in checks}}
                f.write(json.dumps(line) + "\n")
                f.flush()
                print(json.dumps(line), flush=True)
            log(f"[calibrate] seed {seed}: {time.perf_counter() - t:.1f} s")
            gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
