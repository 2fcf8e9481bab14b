#!/usr/bin/env python3
"""The program's own spans and name scopes in a profiler trace, and the
per-layer readings they give.

    python bench/program_trace.py --workload <name> --seed <n> --seconds <s> [--record FILE]

runs a cell's driver as ``bench/run.py --trace 1`` does, with the profiler
on over the driver's traced window, and prints one JSON line: the reduction
below, the program's counts over the window (``BatchedEngine.counters`` or
``Experiment.counters``, as deltas), the programs compiled in the window,
and the readings. ``--record`` writes the trace, cut to a few rounds or
ticks, as a test file (``tests/bench/data``).

``extract`` reads what ``bench/trace_reduce.py``'s ``extract`` reads, and
also the program's host spans (``repro.*``, from ``repro.utils.trace``) and,
for each device operation, the name scopes it was traced under (``forward``,
``backward``, ``opt_step``, ``boundary``, ``attention_bwd``). ``reduce``
returns ``trace_reduce.reduce``'s output, whose idle gaps are then named by
the innermost span of either kind, with two keys more:

* ``spans``: for each span name, its count, its seconds, its self seconds
  (less the spans inside it) and the seconds the device sat idle in it;
* ``scopes``: device seconds per name scope. An operation counts in every
  scope around it, so ``backward`` holds ``attention_bwd``.

Every time is clipped to the traced window and averaged over the chips.
"""
from __future__ import annotations

import argparse
import bisect
import contextlib
import gzip
import json
import re
import shutil
import sys
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional

ROOT = Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402
from bench import trace_reduce as tr  # noqa: E402
from bench.harness import log  # noqa: E402
from repro.utils import trace  # noqa: E402

SCOPES = ("forward", "backward", "opt_step", "boundary", "attention_bwd")
_WRAPPED = re.compile(r"^\w+\((.*)\)$")


def scope_of(path: str) -> str:
    """The known scopes of an op_name path, outermost first, joined by "/".
    A transform's wrapper names the scope inside it ("jvp(forward)" is
    ``forward``), except a transpose: the backward pass of the forward is
    ``backward``, named where the backward is taken."""
    out = []
    for part in path.split("/"):
        if part.startswith("transpose("):
            continue
        while (m := _WRAPPED.match(part)) is not None:
            part = m.group(1)
        if part in SCOPES:
            out.append(part)
    return "/".join(out)


# -- the op_name of each instruction ---------------------------------------
#
# A device op's event carries its instruction's text and its times, not its
# op_name (a v5e trace's "XLA Ops" events hold device_offset_ps,
# device_duration_ps and "Time Scale Multiplier" alone). The trace keeps
# each program's compiled HLO (an HloProto, the stat "Hlo Proto" of the
# "/host:metadata" plane's event metadata, one per program), whose
# instructions carry the op_name. ``jax.profiler.ProfileData`` does not read
# event metadata, so these few fields are read off the file's bytes.


def _fields(buf):
    """(field number, value) of each field of a protobuf message: a memory
    view for a length-delimited field, an int for a varint; fixed-width
    fields are skipped."""
    buf, i = memoryview(buf), 0

    def varint():
        nonlocal i
        out = shift = 0
        while True:
            b = buf[i]
            i += 1
            out |= (b & 0x7F) << shift
            shift += 7
            if b < 0x80:
                return out

    while i < len(buf):
        key = varint()
        kind = key & 7
        if kind == 0:
            yield key >> 3, varint()
        elif kind == 2:
            n = varint()
            yield key >> 3, buf[i:i + n]
            i += n
        else:
            i += 8 if kind == 1 else 4


def _first(buf, number):
    return next((v for f, v in _fields(buf) if f == number), None)


def hlo_scopes(xspace: bytes) -> Dict[str, Dict[str, str]]:
    """For each program whose HLO the trace keeps (by its name without the
    id, "jit_round_step"), the scopes of each top-level instruction that
    has any, by the instruction's name."""
    out: Dict[str, Dict[str, str]] = {}
    for field, plane in _fields(xspace):  # XSpace.planes
        if field != 1 or bytes(_first(plane, 2) or b"") != b"/host:metadata":  # XPlane.name
            continue
        for f, entry in _fields(plane):
            if f != 4:  # XPlane.event_metadata: {id: XEventMetadata}
                continue
            meta = _first(entry, 2)
            name = bytes(_first(meta, 2)).decode().split("(")[0]
            for g, stat in _fields(meta):
                proto = _first(stat, 6) if g == 5 else None  # XEventMetadata.stats, XStat.bytes_value
                if proto is not None:
                    out.setdefault(name, {}).update(_module_scopes(_first(proto, 1)))  # HloProto.hlo_module
    return out


def _module_scopes(module) -> Dict[str, str]:
    """HloModuleProto.computations (3), each with its instructions (2), each
    with its name (1) and metadata (7), whose op_name is field 2."""
    out = {}
    for f, comp in _fields(module):
        if f != 3:
            continue
        for g, inst in _fields(comp):
            if g != 2:
                continue
            name = meta = None
            for h, v in _fields(inst):
                if h == 1:
                    name = bytes(v).decode()
                elif h == 7:
                    meta = v
            scope = scope_of(bytes(_first(meta, 2) or b"").decode()) if meta is not None else ""
            if name and scope:
                out[name] = scope
    return out


def extract(pd, xspace: bytes = b"") -> dict:
    """``trace_reduce.extract``'s lists, with the program's spans among the
    host spans and each device op's scopes in ``op_scopes`` (one per op),
    read from ``xspace``, the trace file's bytes."""
    out = tr.extract(pd)
    programs = hlo_scopes(xspace) if xspace else {}
    for plane in pd.planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                out["host"] += [[e.name, e.start_ns, e.start_ns + e.duration_ns]
                                for e in line.events if e.name.startswith("repro.")]
    for d in out["devices"].values():
        mods = sorted((s, e, lab.split("(")[0]) for lab, s, e in d["modules"])
        starts = [m[0] for m in mods]
        scopes = []
        for lab, s, e in d["ops"]:
            i = bisect.bisect_right(starts, s) - 1
            prog = mods[i][2] if i >= 0 and e <= mods[i][1] else ""
            scopes.append(programs.get(prog, {}).get(lab.lstrip("%"), ""))
        d["op_scopes"] = scopes
    out["host"].sort(key=lambda s: (s[1], -s[2]))
    return out


def _nest(spans: List[list]) -> List[Optional[int]]:
    """The index of each span's parent: the innermost span that holds it.
    ``spans`` is sorted by start, the longer first where two start
    together; spans of one thread nest or do not overlap."""
    parent: List[Optional[int]] = []
    stack: List[int] = []
    for i, (_, s, e) in enumerate(spans):
        while stack and spans[stack[-1]][2] < e:
            stack.pop()
        parent.append(stack[-1] if stack else None)
        stack.append(i)
    return parent


def _covered(lo: float, hi: float, busy: List[tuple], starts: List[float]) -> float:
    """Length of [lo, hi] that the sorted, disjoint ``busy`` covers."""
    out = 0.0
    for i in range(max(bisect.bisect_right(starts, lo) - 1, 0), len(busy)):
        s, e = busy[i]
        if s >= hi:
            break
        out += max(0.0, min(e, hi) - max(s, lo))
    return out


def reduce(trace: dict, device_ids=None) -> Optional[dict]:
    out = tr.reduce(trace, device_ids)
    if out is None:
        return None
    window = next(s for s in trace["host"] if s[0] == tr.WINDOW)
    lo, hi = window[1], window[2]
    chips = {k: v for k, v in trace["devices"].items() if device_ids is None or int(k) in set(device_ids)}
    n = len(chips)
    busy, scopes = [], defaultdict(float)
    for d in chips.values():
        ops = sorted(((lab, c, sc) for (lab, s, e), sc in zip(d["ops"], d.get("op_scopes", [""] * len(d["ops"])))
                      if (c := tr._clip((s, e), lo, hi))), key=lambda o: (o[1][0], -o[1][1]))
        busy.append(tr.union(c for _, c, _ in ops))
        for _, (s, e), sc in tr._leaves(ops):
            for name in filter(None, sc.split("/")):
                scopes[name] += (e - s) / n
    spans = sorted(([name, c[0], c[1]] for name, s, e in trace["host"]
                    if name != tr.WINDOW and (c := tr._clip((s, e), lo, hi))), key=lambda sp: (sp[1], -sp[2]))
    parent = _nest(spans)
    stats: Dict[str, Dict[str, float]] = {}
    child_s = defaultdict(float)
    for i, p in enumerate(parent):
        if p is not None:
            child_s[p] += spans[i][2] - spans[i][1]
    starts = [[iv[0] for iv in b] for b in busy]
    for i, (name, s, e) in enumerate(spans):
        st = stats.setdefault(name, {"count": 0, "seconds": 0.0, "self_s": 0.0, "idle_s": 0.0})
        st["count"] += 1
        st["seconds"] += (e - s) / 1e9
        st["self_s"] += (e - s - child_s[i]) / 1e9
        st["idle_s"] += sum((e - s) - _covered(s, e, b, st_) for b, st_ in zip(busy, starts)) / n / 1e9
    out["spans"] = stats
    out["scopes"] = {k: v / 1e9 for k, v in scopes.items()}
    return out


def extract_file(path) -> dict:
    from jax.profiler import ProfileData

    raw = Path(path).read_bytes()
    return extract(ProfileData.from_serialized_xspace(raw), raw)


def reduce_file(path, device_ids=None) -> Optional[dict]:
    return reduce(extract_file(path), device_ids)


# -- the readings ----------------------------------------------------------


def tick_idle_share(r: dict) -> Optional[float]:
    """Percent of the engine ticks' time in which the device sat idle."""
    t = (r or {}).get("spans", {}).get("repro.engine.tick")
    return 100.0 * t["idle_s"] / t["seconds"] if t and t["seconds"] > 0 else None


def readback_bytes_per_token(program: dict) -> Optional[float]:
    """Bytes the engine copied device -> host per token it generated."""
    n = program.get("generated_tokens", 0)
    return program.get("readback_bytes", 0) / n if n else None


def attention_bwd_roofline(r: dict, rounds: int, config: dict, job: dict, peak: dict) -> Optional[float]:
    """Percent of the attention backward's least time over its device
    seconds: 8 * context * heads * head_dim FLOPs per token and layer (the
    backward's share of ``train.mfu``'s count), at the bf16 peak."""
    from bench.harness import load_module

    s = (r or {}).get("scopes", {}).get("attention_bwd", 0.0)
    if not s or not rounds:
        return None
    mfu = load_module(ROOT / "bench" / "metrics" / "train.mfu.py")
    seq = job["seq_len"]
    tokens = rounds * job["tau"] * job["workers"] * job["batch_per_worker"] * seq
    flops = 8.0 * mfu.mean_context(seq, config["sliding_window"]) * config["num_heads"] * config["head_dim"]
    return 100.0 * tokens * config["num_layers"] * flops / peak["bf16_flops"] / s


# -- a traced run of a cell ------------------------------------------------


def trim(trace: dict, lo: float, hi: float) -> dict:
    """``trace`` cut to [lo, hi], which becomes its window."""
    host = [[tr.WINDOW, lo, hi]] + [s for s in trace["host"] if s[0] != tr.WINDOW and lo <= s[1] and s[2] <= hi]
    devices = {}
    for k, d in trace["devices"].items():
        keep = [i for i, (_, s, e) in enumerate(d["ops"]) if lo <= s and e <= hi]
        devices[k] = {"ops": [d["ops"][i] for i in keep],
                      "modules": [m for m in d["modules"] if lo <= m[1] and m[2] <= hi],
                      "op_scopes": [d["op_scopes"][i] for i in keep]}
    return {"host": host, "devices": devices}


def record_cut(trace: dict) -> tuple:
    """A short stretch of a traced run for a test file: the first round of
    a training window, or the ticks around the first prefill's last chunk."""
    host = trace["host"]
    rounds = [s for s in host if s[0] == "repro.fit.round"]
    if rounds:
        return rounds[0][1] - 2_000_000, rounds[0][2] + 2_000_000
    ticks = [s for s in host if s[0] == "repro.engine.tick"]
    read = next(s for s in host if s[0] == "repro.engine.prefill_readback")
    i = next(k for k, t in enumerate(ticks) if t[1] <= read[1] <= t[2])
    return ticks[max(i - 2, 0)][1], ticks[min(i + 2, len(ticks) - 1)][2]


class ProgramTracer(harness.Tracer):
    """The harness's tracer, on: it also takes the program's counts (the
    ``counters`` it is handed) and compiles over the window, and keeps what
    ``extract`` read."""

    def __init__(self, root: Path):
        super().__init__(True, root)
        self.counters = trace.Counters()
        self.program: dict = {}
        self.compiled: dict = {}
        self.ir: dict = {"host": [], "devices": {}}

    @contextlib.contextmanager
    def window(self):
        before, c0 = self.counters.copy(), trace.compiles()
        with super().window():
            yield
        self.program = dict(self.counters - before)
        self.compiled = {k: v - c0[k] for k, v in trace.compiles().items()}
        log(f"[trace] window compiled {self.compiled['compiles']} programs, "
            f"loaded {self.compiled['cache_loads']} from the cache")

    def reduce(self, device_ids) -> dict:
        files = sorted(self.dir.glob("**/*.xplane.pb"))
        if files:
            self.ir = extract_file(files[-1])
        shutil.rmtree(self.dir, ignore_errors=True)
        return reduce(self.ir, device_ids) or {}


def main(argv=None, root: Path = ROOT, devices=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--record", default=None, help="write the cut trace here (.json.gz)")
    args = ap.parse_args(argv)

    cell = harness.find_cell(root, args.workload)
    table = harness.peaks(root)
    try:
        devs = (devices or harness.require_devices)(cell.chips, table)
    except harness.NoDevice as e:
        log(f"program_trace: {e}")
        return 2
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    trace.compiles()  # starts the count before anything compiles
    tracer = ProgramTracer(root)
    drv = cell.driver()
    built = drv.build

    def build(ctx):  # the driver's own build; the tracer reads the program's counts
        out = built(ctx)
        tracer.counters = (out[0] if isinstance(out, tuple) else out).counters
        return out

    drv.build = build
    ctx = harness.Context(cell=cell, model=harness.model_config(cell.config), seed=args.seed, seconds=args.seconds,
                          tracer=tracer, devices=devs, t_start=harness.now(), limits=cell.job.get("limits", {}))
    out = drv.run(ctx)
    r = tracer.reduce([d.id for d in devs])
    peak = table[devs[0].device_kind] if devs[0].device_kind in table else {}
    readings = {
        "serve.tick_idle_share": tick_idle_share(r),
        "serve.readback_bytes_per_token": readback_bytes_per_token(tracer.program),
        "train.attention_bwd_roofline": attention_bwd_roofline(r, tracer.counts.get("rounds", 0), cell.config,
                                                               cell.job, peak),
    }
    rec = dict(trace=r or None, counts=tracer.counts, config=cell.config, job=cell.job, peak=peak, chips=len(devs))
    metrics = {m["name"]: cell.metric(m["name"]).read(rec) for m in cell.per_layer} if r else {}
    if args.record:
        with gzip.open(args.record, "wt") as f:
            json.dump(trim(tracer.ir, *record_cut(tracer.ir)), f)
    line = {"workload": cell.name, "seed": args.seed, "device": devs[0].device_kind,
            "correct": all(c.ok for c in out["checks"]) and out["failed"] == 0,
            "readings": {k: v for k, v in readings.items() if v is not None}, "metrics": metrics,
            "counts": tracer.counts, "program": tracer.program, "compiled": tracer.compiled,
            **{k: r[k] for k in ("spans", "scopes", "breakdown", "busy_s", "window_s") if k in r},
            "programs": sorted(r.get("modules", {}).items(), key=lambda kv: -kv[1])[:8]}
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
