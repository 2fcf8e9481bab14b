"""What every cell shares: its files found by name, the device and its
peaks, the compile cache, the traced window, and the comparison's numbers.

A cell is one entry of ``BENCHMARK.json``'s ``workloads``. Its
configuration is ``bench/configs/<config>.json``, its traffic or job is
``bench/traffic/<traffic>.json`` (whose ``driver`` names
``bench/drivers/<driver>.py``), and each per-layer metric is
``bench/metrics/<metric>.py``. Adding a cell, a mix or a metric adds files;
nothing here names one.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import shutil
import statistics
import sys
import time
from pathlib import Path
from types import ModuleType
from typing import Any, Dict, List, Optional

ROOT = Path(__file__).resolve().parents[1]


class NoDevice(RuntimeError):
    """No accelerator, too few chips, or a chip with no entry in peaks.json."""


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path) -> ModuleType:
    """A benchmark file imported by path: metric and driver names may hold
    dots and dashes, which an import statement cannot spell."""
    if not path.is_file():
        raise FileNotFoundError(f"no such benchmark file: {path}")
    spec = importlib.util.spec_from_file_location(f"bench_file_{path.stem.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """One workload with everything its files say."""

    root: Path
    name: str
    chips: int
    config: dict  # the configuration file
    job: dict  # the traffic file
    end_to_end: List[dict]  # this cell's end-to-end metric entries
    per_layer: List[dict]  # this cell's per-layer metric entries

    def driver(self) -> ModuleType:
        return load_module(self.root / "bench" / "drivers" / f"{self.job['driver']}.py")

    def metric(self, name: str) -> ModuleType:
        return load_module(self.root / "bench" / "metrics" / f"{name}.py")


def _applies(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def find_cell(root: Path, name: str, bench: Optional[dict] = None) -> Cell:
    bench = bench if bench is not None else load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; the benchmark has {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(root / configs[w["config"]]["file"])
    job = load_json(root / "bench" / "traffic" / f"{w['traffic']}.json")
    return Cell(
        root=root,
        name=name,
        chips=int(w["chips"]),
        config=config,
        job=job,
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
    )


# the configuration file's keys, as the program's ModelConfig and its
# AttentionConfig name them
_MODEL_KEYS = ("num_layers", "d_model", "d_ff", "vocab_size", "norm_eps", "act", "tie_embeddings", "dtype")
_ATTN_KEYS = ("num_heads", "num_kv_heads", "head_dim", "sliding_window", "rope_theta", "qkv_bias")


def model_config(spec: dict):
    """The program's ModelConfig for a configuration file: the registered
    architecture's code path with every size the file gives."""
    from repro.config import get_arch

    base = get_arch(spec["arch"]).model
    attn = dataclasses.replace(base.attention, **{k: spec[k] for k in _ATTN_KEYS})
    return dataclasses.replace(base, attention=attn, **{k: spec[k] for k in _MODEL_KEYS})


def param_shapes(cfg):
    """The program's parameter tree for ``cfg``, as ShapeDtypeStructs."""
    import jax

    from repro.models import transformer as T

    return T.init_model(cfg, jax.random.PRNGKey(0), abstract=True)[0]


# -- the device ------------------------------------------------------------


def peaks(root: Path) -> dict:
    return load_json(root / "bench" / "peaks.json")["devices"]


def require_devices(chips: int, table: dict) -> list:
    """The first ``chips`` accelerators, or NoDevice. Never the CPU."""
    import jax

    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        raise NoDevice(f"needs a TPU, JAX found {dev.platform} ({dev.device_kind})")
    if len(devs) < chips:
        raise NoDevice(f"the cell asks for {chips} chips, JAX found {len(devs)}")
    if dev.device_kind not in table:
        raise NoDevice(f"no peaks for device kind {dev.device_kind!r} in bench/peaks.json")
    return devs[:chips]


def memory_peak(devs) -> int:
    """Peak bytes in use on the fullest chip since the process started."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devs)


def device_record(devs) -> dict:
    d = devs[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devs), "memory_peak_bytes": memory_peak(devs)}


# -- the traced window -----------------------------------------------------


class Tracer:
    """Host spans and one profiler window. Off, it does nothing at all."""

    def __init__(self, on: bool, root: Path):
        self.on = on
        self.dir = root / ".bench_trace"
        self.counts: Dict[str, float] = {}  # what the run did in the traced window

    def span(self, name: str):
        if not self.on:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    @contextlib.contextmanager
    def window(self):
        if not self.on:
            yield
            return
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        jax.profiler.start_trace(str(self.dir))
        try:
            with jax.profiler.TraceAnnotation("bench.window"):
                yield
        finally:
            jax.profiler.stop_trace()

    def reduce(self, device_ids) -> Optional[dict]:
        if not self.on:
            return None
        from bench import trace_reduce

        files = sorted(self.dir.glob("**/*.xplane.pb"))
        if not files:
            return None
        out = trace_reduce.reduce_file(files[-1], device_ids)
        shutil.rmtree(self.dir, ignore_errors=True)
        return out


# -- the comparison --------------------------------------------------------


@dataclasses.dataclass
class Check:
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value == self.value and self.value <= self.limit  # NaN fails


def worst_leaf_gap(prog: Dict[str, float], ref: Dict[str, float], leaves=None):
    """The largest gap between the program's and the reference's norm of a
    leaf, against the reference's norm of that leaf or of the median leaf,
    whichever is larger. Returns (gap, leaf)."""
    leaves = list(leaves if leaves is not None else ref)
    med = statistics.median(ref[k] for k in leaves)
    gaps = {k: abs(prog[k] - ref[k]) / max(ref[k], med) for k in leaves}
    worst = max(gaps, key=gaps.get)
    return gaps[worst], worst


def moving_leaves(grad: Dict[str, float]) -> List[str]:
    """Leaves whose first gradient in the reference is above a thousandth of
    the median leaf's: the others move under round-off alone."""
    med = statistics.median(grad.values())
    return sorted(k for k, v in grad.items() if v >= 1e-3 * med)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


@dataclasses.dataclass
class Context:
    """What a driver is handed."""

    cell: Cell
    model: Any  # the program's ModelConfig
    seed: int
    seconds: float
    tracer: Tracer
    devices: list
    t_start: float  # when the process started, on time.perf_counter's clock
    limits: Dict[str, float] = dataclasses.field(default_factory=dict)

    @property
    def job(self) -> dict:
        return self.cell.job


def now() -> float:
    return time.perf_counter()


def result_line(correct: bool, attempted: int, failed: int, metrics: dict, device: dict,
                checks: List[Check], breakdown: Optional[dict] = None) -> str:
    out: Dict[str, Any] = {"correct": bool(correct), "attempted": int(attempted), "failed": int(failed),
                           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {c.name: {"value": c.value, "limit": c.limit} for c in checks}
    return json.dumps(out)


def quantile(values: List[float], q: float) -> float:
    """The q-quantile by linear interpolation between order statistics."""
    xs = sorted(values)
    if len(xs) == 1:
        return xs[0]
    pos = q * (len(xs) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)

