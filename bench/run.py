#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic and its metrics come from
``BENCHMARK.json`` and the files under ``bench/`` that it names. The run
names its device and stops with a non-zero exit, printing no result, when
JAX finds no TPU, fewer chips than the cell asks for, or a chip with no
entry in ``bench/peaks.json``. It makes its weights and inputs from
``--seed``, warms every shape it will use (that is set-up), measures for
``--seconds``, compares what the timed path produced with the plain
reference, and prints one JSON line last on standard output. With
``--trace 0`` that line holds the cell's end-to-end metrics; with
``--trace 1`` its per-layer metrics, read from a profiler trace of part of
the window. The numbers compared, each beside its limit, close both the
line (``checks``) and standard error.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402
from bench.harness import log  # noqa: E402


def per_layer(cell: harness.Cell, rec: dict) -> dict:
    """Each per-layer metric that finds something to read in ``rec``."""
    out = {}
    for entry in cell.per_layer:
        value = cell.metric(entry["name"]).read(rec)
        if value is not None:
            out[entry["name"]] = {"value": value, "unit": entry["unit"]}
    return out


def main(argv=None, root: Path = ROOT, bench: dict = None, devices=harness.require_devices) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = harness.find_cell(root, args.workload, bench)
    table = harness.peaks(root)
    try:
        devs = devices(cell.chips, table)
    except harness.NoDevice as e:
        log(f"bench: {e}")
        return 2
    from repro.utils.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    d = devs[0]
    log(f"[device] {d.platform} {d.device_kind} x{len(devs)}; compile cache {cache}; cell {cell.name}, seed {args.seed}")
    tracer = harness.Tracer(bool(args.trace), root)
    ctx = harness.Context(
        cell=cell,
        model=harness.model_config(cell.config),
        seed=args.seed,
        seconds=args.seconds,
        tracer=tracer,
        devices=devs,
        t_start=T_START,
        limits=cell.job.get("limits", {}),
    )
    out = cell.driver().run(ctx)
    device = out["device"]
    breakdown = None
    if tracer.on:
        reduced = tracer.reduce([x.id for x in devs])
        metrics = {}
        if reduced is not None:  # a trace with the chip's operations in it
            rec = dict(trace=reduced, counts=tracer.counts, config=cell.config, job=cell.job,
                       peak=table[d.device_kind], chips=len(devs))
            metrics = per_layer(cell, rec)
            device = dict(device, busy_s=reduced["busy_s"], window_s=reduced["window_s"])
            breakdown = reduced["breakdown"]
    else:
        names = {e["name"] for e in cell.end_to_end}
        metrics = {k: v for k, v in out["metrics"].items() if k in names}
    checks = out["checks"]
    # an answer that never came (a request unfinished a drain past the
    # window, a round with no finite loss) is a wrong one
    correct = all(c.ok for c in checks) and out["failed"] == 0
    for c in checks:
        log(f"check {c.name} {c.value!r} limit {c.limit!r} {'ok' if c.ok else 'FAILED'}")
    print(harness.result_line(correct, out["attempted"], out["failed"], metrics, device, checks, breakdown), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
