"""From a profiler trace to the numbers the per-layer metrics read.

``extract`` turns the trace (``jax.profiler.ProfileData``, the ``.xplane.pb``
file the profiler writes) into plain lists: the benchmark's host spans
("bench.*"), and for each chip its device operations and the programs
(XLA modules) they ran in, as [label, start_ns, end_ns]. ``reduce`` works
on those lists alone, so a small recorded trace can check it on a machine
with no chip. Within the traced window ("bench.window"), per chip:

* busy: the union of the intervals in which an operation ran; the idle
  share is 1 - busy / window;
* time per operation (by HLO instruction name; a loop's op, which spans
  its body's ops, is left out) and per program;
* exposed collective time: the part of collective operations during which
  no other operation ran;
* the breakdown: the operations that took most time, and the longest idle
  gaps, each named by the innermost host span around it.

Every time is averaged over the chips traced.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Tuple

COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all", re.I)
WINDOW = "bench.window"
Interval = Tuple[float, float]


def _device_id(plane_name: str) -> Optional[int]:
    m = re.match(r"/device:TPU:(\d+)$", plane_name)
    return int(m.group(1)) if m else None


def _label(name: str) -> str:
    """An operation's HLO instruction name ("%fusion.12 = bf16[...] ..." ->
    "%fusion.12"): a Pallas kernel's custom call carries the kernel's name."""
    return name.split(" = ", 1)[0]


def extract(pd) -> dict:
    """Plain lists from a ``ProfileData``."""
    host: List[list] = []
    devices: Dict[int, dict] = {}
    for plane in pd.planes:
        dev = _device_id(plane.name)
        if dev is None:
            if plane.name.startswith("/host"):
                for line in plane.lines:
                    for e in line.events:
                        if e.name.startswith("bench."):
                            host.append([e.name, e.start_ns, e.start_ns + e.duration_ns])
            continue
        d = devices.setdefault(dev, {"ops": [], "modules": []})
        for line in plane.lines:
            key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(line.name)
            if key is None:
                continue
            for e in line.events:
                d[key].append([_label(e.name), e.start_ns, e.start_ns + e.duration_ns])
    return {"host": host, "devices": {str(k): v for k, v in devices.items()}}


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[list] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _length(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def _clip(iv: Interval, lo: float, hi: float) -> Optional[Interval]:
    s, e = max(iv[0], lo), min(iv[1], hi)
    return (s, e) if e > s else None


def _subtract(iv: Interval, cover: List[Interval]) -> float:
    """Length of ``iv`` not covered by the sorted, disjoint ``cover``."""
    left = iv[1] - iv[0]
    for s, e in cover:
        if e <= iv[0]:
            continue
        if s >= iv[1]:
            break
        left -= min(e, iv[1]) - max(s, iv[0])
    return left


def _span_at(spans: List[list], t: float) -> str:
    """The innermost host span holding time ``t``."""
    inside = [s for s in spans if s[1] <= t <= s[2] and s[0] != WINDOW]
    return min(inside, key=lambda s: s[2] - s[1])[0] if inside else "no bench span"


def reduce(trace: dict, device_ids=None) -> Optional[dict]:
    windows = [s for s in trace["host"] if s[0] == WINDOW]
    chips = {k: v for k, v in trace["devices"].items() if device_ids is None or int(k) in set(device_ids)}
    if not windows or not chips:
        return None
    lo, hi = windows[0][1], windows[0][2]
    n = len(chips)
    busy = exposed = 0.0
    ops: Dict[str, float] = defaultdict(float)
    modules: Dict[str, float] = defaultdict(float)
    gaps: List[Tuple[float, float]] = []
    for d in chips.values():
        clipped = [(lab, c) for lab, s, e in sorted(d["ops"], key=lambda o: (o[1], -o[2])) if (c := _clip((s, e), lo, hi))]
        busy_iv = union(c for _, c in clipped)
        busy += _length(busy_iv)
        for lab, (s, e) in _leaves(clipped):
            ops[lab] += (e - s) / n
        for lab, s, e in d["modules"]:
            c = _clip((s, e), lo, hi)
            if c:
                modules[lab] += (c[1] - c[0]) / n
        leaves = _leaves(clipped)
        others = union(c for lab, c in leaves if not COLLECTIVE.search(lab))
        exposed += sum(_subtract(c, others) for lab, c in leaves if COLLECTIVE.search(lab))
        edges = [lo] + [t for iv in busy_iv for t in iv] + [hi]
        gaps += [(e - s, (s + e) / 2) for s, e in zip(edges[::2], edges[1::2]) if e > s]
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:10]
    top_gaps = [(g, _span_at(trace["host"], t)) for g, t in sorted(gaps, key=lambda g: -g[0])[:10]]
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy / n / 1e9,
        "exposed_collective_s": exposed / n / 1e9,
        "ops": {k: v / 1e9 for k, v in ops.items()},
        "modules": {k: v / 1e9 for k, v in modules.items()},
        "module_ops": _module_ops(chips, lo, hi),
        "breakdown": {
            "device_ops": [[k, v / 1e9] for k, v in top_ops],
            "idle_gaps": [[name, g / n / 1e9] for g, name in top_gaps],
        },
    }


def _leaves(ops: List[tuple]) -> List[tuple]:
    """The operations that hold no other: a loop's op spans its body's ops,
    which are counted themselves. ``ops`` is sorted by start, the longer
    first where two start together."""
    return [o for o, nxt in zip(ops, ops[1:] + [None])
            if nxt is None or not (nxt[1][0] < o[1][1] and nxt[1][1] <= o[1][1])]


def _module_ops(chips: dict, lo: float, hi: float) -> Dict[str, List[str]]:
    """For each program, the labels of a few of the operations run inside
    it: enough to find the program that holds a given kernel."""
    out: Dict[str, set] = defaultdict(set)
    for d in chips.values():
        mods = sorted((s, e, lab) for lab, s, e in d["modules"] if _clip((s, e), lo, hi))
        starts = [m[0] for m in mods]
        for lab, s, e in d["ops"]:
            i = bisect.bisect_right(starts, s) - 1
            if i >= 0 and e <= mods[i][1]:
                out[mods[i][2]].add(lab)
    return {k: sorted(v) for k, v in out.items()}


def reduce_file(path, device_ids=None) -> Optional[dict]:
    from jax.profiler import ProfileData

    return reduce(extract(ProfileData.from_file(str(path))), device_ids)


def seconds_of(trace: dict, pattern: str) -> float:
    """Device seconds of the operations whose label holds ``pattern``."""
    return sum(v for k, v in trace["ops"].items() if pattern in k)


def program_seconds(trace: dict, pattern: str) -> float:
    """Device seconds of the programs that ran an operation whose label
    holds ``pattern``."""
    return sum(trace["modules"].get(m, 0.0) for m, labs in trace["module_ops"].items()
               if any(pattern in lab for lab in labs))
