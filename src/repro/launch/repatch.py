import os

# same contract as launch/dryrun.py: CPU placeholder devices only; keep a
# caller-pinned device count or unrelated XLA flags, default to 512 devices
os.environ["JAX_PLATFORMS"] = "cpu"
if "--xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=512"
    ).strip()

"""Recompute the probe-composed roofline numbers for existing dry-run JSONs
(used after parser/costing fixes — full-program memory/schedule fields are
kept, probe-derived costs are refreshed)."""

import argparse  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402

import jax  # noqa: E402

from repro.config import INPUT_SHAPES, get_arch  # noqa: E402
from repro.launch import costprobe, roofline as rl, specs  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.parallel import logical_mesh  # noqa: E402


def repatch(path: str) -> None:
    d = json.load(open(path))
    arch = get_arch(d["arch"])
    shape = INPUT_SHAPES[d["shape"]]
    multi_pod = d["mesh"] == "2x16x16"
    opt = d.get("variant", "").endswith("+opt")
    prod = make_production_mesh(multi_pod=multi_pod)
    plan = arch.plan_for(shape.name, prod.devices.size)
    lmesh = logical_mesh(prod, plan)
    rules = specs.optimized_rules(shape) if opt else specs.rules_for(shape)
    t0 = time.time()
    composed = costprobe.composed_cost(arch, shape, lmesh, plan, rules, strategy=d.get("strategy"))
    composed["probe_s"] = round(time.time() - t0, 1)
    roof = rl.Roofline(
        flops=composed["flops"],
        bytes_accessed=composed["bytes"],
        collective_bytes=composed["coll"],
        collectives=d["roofline"].get("collectives", {}),
    )
    d["composed"] = composed
    # keep the top-level mirror in sync with the refreshed boundary probe
    # (run_pair writes it the same way; None for serve shapes)
    d["boundary_collectives"] = composed.get("parts", {}).get("boundary", {}).get("collectives")
    d["roofline"] = roof.as_dict()
    if d.get("model_flops_per_device") and roof.flops:
        d["useful_flops_ratio"] = d["model_flops_per_device"] / roof.flops
    with open(path, "w") as f:
        json.dump(d, f, indent=2, default=str)
    print(f"repatched {path}: coll={roof.collective_s:.3f}s mem={roof.memory_s:.3f}s comp={roof.compute_s:.3f}s ({composed['probe_s']}s)", flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--glob", default="experiments/dryrun/*.json")
    args = ap.parse_args()
    for path in sorted(glob.glob(args.glob)):
        try:
            repatch(path)
        except Exception as e:  # noqa: BLE001
            print(f"FAIL {path}: {e}", flush=True)


if __name__ == "__main__":
    main()
