"""The trace reduction: busy and idle time, time per kernel and per program,
exposed collective time and the breakdown, on hand-made lists, on a real
trace recorded here, and on a small trace recorded on a v5e chip."""
import bench_tiny  # noqa: F401  (puts the checkout on sys.path)

import gzip
import json
from pathlib import Path

import pytest

from bench import trace_reduce as tr

DATA = Path(__file__).resolve().parent / "data"
MS = 1_000_000  # ns


def hand_trace():
    """One chip, a 10 ms window: a program of 3 ops, a collective that is
    half hidden under compute, and two idle gaps under named host spans."""
    return {
        "host": [["bench.window", 0, 10 * MS], ["bench.fit", 0, 6 * MS], ["bench.batch", 6 * MS, 10 * MS]],
        "devices": {"0": {
            "ops": [["%while.2", 1 * MS, 3 * MS],  # a loop: it holds the next op
                    ["%fusion.1", 1 * MS, 3 * MS],
                    ["%all-reduce.7", 2 * MS, 4 * MS],
                    ["%sgd_step_flat.3", 4 * MS, 5 * MS],
                    ["%fusion.9", 11 * MS, 12 * MS]],  # outside the window
            "modules": [["jit_step(1)", 1 * MS, 5 * MS]],
        }},
    }


def test_busy_idle_and_exposed_collectives():
    r = tr.reduce(hand_trace())
    assert r["window_s"] == pytest.approx(0.010)
    assert r["busy_s"] == pytest.approx(0.004)  # [1, 5] ms: the union
    assert r["exposed_collective_s"] == pytest.approx(0.001)  # [3, 4] ms: nothing else ran
    assert tr.seconds_of(r, "sgd_step_flat") == pytest.approx(0.001)
    assert tr.seconds_of(r, "fusion.9") == 0.0 and tr.seconds_of(r, "while") == 0.0
    assert tr.program_seconds(r, "sgd_step_flat") == pytest.approx(0.004)
    assert tr.program_seconds(r, "no such kernel") == 0.0


def test_breakdown_names_idle_gaps_by_host_span():
    r = tr.reduce(hand_trace())
    ops = dict(r["breakdown"]["device_ops"])
    assert ops["%fusion.1"] == pytest.approx(0.002) and len(ops) == 3
    gaps = r["breakdown"]["idle_gaps"]
    assert gaps[0] == ["bench.batch", pytest.approx(0.005)]  # [5, 10] ms
    assert gaps[1] == ["bench.fit", pytest.approx(0.001)]  # [0, 1] ms


def test_no_window_or_no_chip_reads_nothing():
    t = hand_trace()
    assert tr.reduce(dict(t, host=t["host"][1:])) is None
    assert tr.reduce(t, device_ids=[3]) is None


def test_extract_reads_host_spans_of_a_profiler_trace(tmp_path):
    """A real xplane written here on the CPU: it has host planes and no
    chip, so only the benchmark's spans come out."""
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    f = jax.jit(lambda a: (a @ a).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("bench.window"):
            with jax.profiler.TraceAnnotation("bench.fit"):
                f(x).block_until_ready()
    pb = next(tmp_path.glob("**/*.xplane.pb"))
    ir = tr.extract(ProfileData.from_file(str(pb)))
    names = [s[0] for s in ir["host"]]
    assert names.count("bench.window") == 1 and names.count("bench.fit") == 1
    window = next(s for s in ir["host"] if s[0] == "bench.window")
    fit = next(s for s in ir["host"] if s[0] == "bench.fit")
    assert window[1] <= fit[1] < fit[2] <= window[2]
    assert ir["devices"] == {}


def recorded(name):
    """A trace recorded on a v5e chip (``extract`` output, trimmed to its
    first ``bench.window``): one Overlap-Local-SGD round of h2o-danube-1.8b
    at 4 layers, or serving ticks of the same model on the paged engine."""
    with gzip.open(DATA / f"{name}.json.gz", "rt") as f:
        return json.load(f)


def test_recorded_training_round():
    r = tr.reduce(recorded("train_round_v5e"), device_ids=[0])
    assert r["window_s"] == pytest.approx(0.724440593)
    assert r["busy_s"] == pytest.approx(0.669637031)
    assert tr.seconds_of(r, "flash_attention_bhsd") == pytest.approx(0.134605807)
    assert tr.seconds_of(r, "sgd_step_flat") == pytest.approx(0.070712575)
    assert tr.seconds_of(r, "pullback_momentum_flat") == pytest.approx(0.033986265)
    assert r["exposed_collective_s"] == 0.0  # two workers stacked on one chip
    assert r["breakdown"]["device_ops"][0][0] == "%flash_attention_bhsd.8"
    assert not any(k.startswith("%while") for k in r["ops"])  # loops hold their bodies
    assert sum(r["ops"].values()) <= r["busy_s"] + 1e-9
    assert all(name == "bench.fit" for name, _ in r["breakdown"]["idle_gaps"])


def test_recorded_serving_ticks():
    r = tr.reduce(recorded("serve_ticks_v5e"), device_ids=[0])
    assert r["busy_s"] == pytest.approx(0.451218893) and r["window_s"] == pytest.approx(0.54832148)
    assert tr.seconds_of(r, "paged_attend_decode") == pytest.approx(0.098349571)
    # the decode program: the one module that ran the paged decode kernel
    assert tr.program_seconds(r, "paged_attend_decode") == pytest.approx(0.307371445)
    assert len(r["breakdown"]["device_ops"]) == 10 and len(r["breakdown"]["idle_gaps"]) == 10
