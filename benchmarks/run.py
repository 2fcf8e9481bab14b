# One function per paper table/figure. Prints ``name,us_per_call,derived`` CSV.
# Kernel rows are additionally persisted machine-readably to BENCH_kernels.json
# (name -> {us, gbps?, derived}) so the packed-boundary perf trajectory is
# trackable across PRs. ``--only <module>`` runs a single benchmark module
# (the CI smoke step uses ``--only kernel_bench`` under REPRO_BENCH_QUICK=1).
from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# quick (CI-smoke) runs write to a separate, gitignored file so they never
# clobber the committed full-mode perf trajectory
BENCH_JSON = os.path.join(_ROOT, "BENCH_kernels.json")
BENCH_JSON_QUICK = os.path.join(_ROOT, "BENCH_kernels_quick.json")
SERVING_JSON = os.path.join(_ROOT, "BENCH_serving.json")
SERVING_JSON_QUICK = os.path.join(_ROOT, "BENCH_serving_quick.json")


def _derived_fields(derived: str) -> dict:
    """Parse k=v tokens out of the derived column (gbps/gflops/speedup...)."""
    out = {}
    for key, val in re.findall(r"(\w+)=([-\w.]+)", derived):
        try:
            out[key] = float(val)
        except ValueError:
            out[key] = val
    return out


def write_kernel_json(rows, path: str = None) -> str:
    quick = os.environ.get("REPRO_BENCH_QUICK", "0") == "1"
    if path is None:
        path = BENCH_JSON_QUICK if quick else BENCH_JSON
    payload = {
        name: dict(us=round(us, 1), **_derived_fields(derived)) for name, us, derived in rows
    }
    payload["_meta"] = dict(
        quick=quick,
        schema="name -> {us (per call), derived throughput fields}",
    )
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
    return path


def main() -> None:
    from benchmarks import fig1_error_runtime, fig4_comm_ratio, kernel_bench, roofline_table, serving_bench, table1_iid, table2_noniid, theorem1_rate

    mods = [kernel_bench, serving_bench, theorem1_rate, fig4_comm_ratio, roofline_table, table1_iid, table2_noniid, fig1_error_runtime]
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", type=str, default=None, help="run a single benchmark module by name")
    args = ap.parse_args()
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    if args.only:
        wanted = [m for m in mods if m.__name__.split(".")[-1] == args.only]
        if not wanted:
            raise SystemExit(f"unknown benchmark {args.only!r}; known: {[m.__name__.split('.')[-1] for m in mods]}")
        mods = wanted

    def emit(line: str) -> None:
        print(line, flush=True)

    emit("name,us_per_call,derived")
    t0 = time.time()
    failures = []
    for mod in mods:
        name = mod.__name__.split(".")[-1]
        t = time.time()
        try:
            if mod is kernel_bench:
                rows = kernel_bench.run()
                for r in rows:
                    emit(kernel_bench.csv_row(*r))
                json_path = write_kernel_json(rows)
                emit(f"bench/kernel_bench/json,{0:.0f},{json_path}")
            elif mod is serving_bench:
                rows = serving_bench.run()
                for r in rows:
                    emit(serving_bench.csv_row(*r))
                quick = os.environ.get("REPRO_BENCH_QUICK", "0") == "1"
                json_path = write_kernel_json(rows, SERVING_JSON_QUICK if quick else SERVING_JSON)
                emit(f"bench/serving_bench/json,{0:.0f},{json_path}")
            else:
                mod.main(emit)
            emit(f"bench/{name}/elapsed,{(time.time()-t)*1e6:.0f},ok")
        except Exception as e:  # noqa: BLE001
            failures.append(name)
            emit(f"bench/{name}/elapsed,{(time.time()-t)*1e6:.0f},FAILED:{type(e).__name__}:{e}")
    emit(f"bench/total_elapsed,{(time.time()-t0)*1e6:.0f},done")
    # --only is the CI-smoke contract: a failed module must fail the step.
    # Full sweeps keep the degrade-gracefully contract (FAILED rows, exit 0).
    if failures and args.only:
        raise SystemExit(f"benchmark modules failed: {failures}")


if __name__ == "__main__":
    main()
