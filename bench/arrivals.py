"""The one generator of serving traffic, read from a mix's parameters.

A mix fixes the shape of the work: a Poisson rate, and clipped lognormal
prompt and output lengths. The set of inter-arrival gaps and of (prompt,
output) lengths is drawn from the mix's own ``shape_seed``, so every run
seed gets the same sizes and the same number of arrivals in the window; the
run's seed only orders them and picks the tokens. Runs with different seeds
then differ by order alone, not by how much work they hold.
"""
from __future__ import annotations

import dataclasses
from typing import List

import numpy as np

from bench.weights import seed_rng


@dataclasses.dataclass(frozen=True)
class Request:
    rid: str
    due_s: float  # when it is sent, from the window's start
    prompt: np.ndarray  # int32 tokens
    max_new: int


def clipped_lognormal(rng: np.random.Generator, spec: dict, n: int) -> np.ndarray:
    """``n`` whole lengths with median ``spec['median']`` and log-sd
    ``spec['sigma']``, clipped to [``min``, ``max``]."""
    x = spec["median"] * np.exp(spec["sigma"] * rng.standard_normal(n))
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def poisson_gaps(rng: np.random.Generator, rate: float, seconds: float) -> np.ndarray:
    """Exponential gaps at ``rate`` per second whose sum stays inside
    ``seconds``."""
    times = []
    t = rng.exponential(1.0 / rate)
    while t < seconds:
        times.append(t)
        t += rng.exponential(1.0 / rate)
    return np.diff(np.asarray(times), prepend=0.0)


def open_loop(mix: dict, seed: int, seconds: float, vocab: int, rate: float = None, prefix: str = "r") -> List[Request]:
    """The requests due in a window of ``seconds``, in arrival order."""
    rate = mix["rate_per_s"] if rate is None else rate
    shape = np.random.default_rng(mix["shape_seed"])
    gaps = poisson_gaps(shape, rate, seconds)
    n = len(gaps)
    prompts = clipped_lognormal(shape, mix["prompt"], n)
    outputs = clipped_lognormal(shape, mix["output"], n)
    run = seed_rng(seed, 2)
    gaps = gaps[run.permutation(n)]
    order = run.permutation(n)
    due = np.cumsum(gaps)
    return [
        Request(f"{prefix}{i}", float(due[i]), run.integers(0, vocab, int(prompts[j]), dtype=np.int32), int(outputs[j]))
        for i, j in enumerate(order)
    ]
