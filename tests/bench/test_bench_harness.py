"""The harness finds a cell's files by name, refuses a CPU or an unknown
chip, and the traffic generator is fixed by its seed."""
from bench_tiny import tiny_root, no_compile_cache  # noqa: F401  (fixtures; the checkout on sys.path)

import importlib.util
import json
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]


def test_new_config_mix_and_metric_are_found_by_name(tiny_root):
    """A cell, a mix and a per-layer metric added as new files, with no
    harness file edited."""
    from bench import harness

    bench = json.loads((tiny_root / "BENCHMARK.json").read_text())
    (tiny_root / "bench" / "metrics" / "serve.prompt_tokens.py").write_text(
        "def read(rec):\n    return rec['counts'].get('prefill_tokens')\n")
    mix = json.loads((tiny_root / "bench" / "traffic" / "tiny-chat.json").read_text())
    mix["rate_per_s"] = 3.0
    (tiny_root / "bench" / "traffic" / "tiny-slow.json").write_text(json.dumps(mix))
    bench["workloads"].append({"name": "tiny.slow", "config": "tiny-qwen2", "traffic": "tiny-slow", "chips": 1, "why": "x"})
    bench["per_layer"].append({"name": "serve.prompt_tokens", "unit": "tokens", "better": "higher",
                               "source": "program_counter", "layer": "engine", "moves": "serve_itl_p95_ms",
                               "workloads": ["tiny.slow"]})
    cell = harness.find_cell(tiny_root, "tiny.slow", bench)
    assert cell.job["rate_per_s"] == 3.0 and cell.config["arch"] == "qwen2-7b"
    assert cell.driver().__name__.endswith("open_loop_serve")
    assert [m["name"] for m in cell.per_layer] == ["serve.prompt_tokens"]
    assert cell.metric("serve.prompt_tokens").read({"counts": {"prefill_tokens": 7}}) == 7
    run = load_run()
    assert run.per_layer(cell, {"counts": {}}) == {}  # a reader with nothing to read is left out
    cfg = harness.model_config(cell.config)
    assert (cfg.d_model, cfg.attention.num_heads, cfg.attention.qkv_bias, cfg.num_layers) == (64, 4, True, 2)


def test_benchmark_cells_name_their_files():
    from bench import harness

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = harness.find_cell(ROOT, w["name"])
        assert cell.driver() is not None
        for m in cell.per_layer:
            assert callable(cell.metric(m["name"]).read)
        cfg = harness.model_config(cell.config)
        assert cfg.num_layers == cell.config["reduced"]["num_layers"][1]


def load_run():
    spec = importlib.util.spec_from_file_location("bench_run", ROOT / "bench" / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_cpu_is_refused_and_prints_no_result(capsys):
    from bench import harness

    with pytest.raises(harness.NoDevice, match="needs a TPU"):
        harness.require_devices(1, harness.peaks(ROOT))
    rc = load_run().main(["--workload", "train-danube-s2k-t2", "--seed", "1", "--seconds", "1"])
    out = capsys.readouterr()
    assert rc != 0 and out.out == ""


def test_unknown_chip_and_too_few_chips_are_refused(monkeypatch):
    import jax

    from bench import harness

    chip = SimpleNamespace(platform="tpu", device_kind="TPU v9 imaginary", id=0)
    monkeypatch.setattr(jax, "devices", lambda: [chip])
    with pytest.raises(harness.NoDevice, match="no peaks"):
        harness.require_devices(1, harness.peaks(ROOT))
    known = SimpleNamespace(platform="tpu", device_kind="TPU v5 lite", id=0)
    monkeypatch.setattr(jax, "devices", lambda: [known])
    assert harness.require_devices(1, harness.peaks(ROOT)) == [known]
    with pytest.raises(harness.NoDevice, match="asks for 4"):
        harness.require_devices(4, harness.peaks(ROOT))


def chat_mix():
    return json.loads((ROOT / "bench" / "traffic" / "chat.json").read_text())


def test_arrivals_are_fixed_by_the_seed():
    from bench import arrivals

    mix = chat_mix()
    a = arrivals.open_loop(mix, 2**33 + 5, 30.0, 1000)
    b = arrivals.open_loop(mix, 2**33 + 5, 30.0, 1000)
    c = arrivals.open_loop(mix, 7, 30.0, 1000)
    assert [(r.due_s, r.max_new, r.prompt.tolist()) for r in a] == [(r.due_s, r.max_new, r.prompt.tolist()) for r in b]
    # another seed orders the same work differently
    assert len(a) == len(c) and a[-1].due_s == pytest.approx(c[-1].due_s)
    assert sorted((len(r.prompt), r.max_new) for r in a) == sorted((len(r.prompt), r.max_new) for r in c)
    assert [r.due_s for r in a] != [r.due_s for r in c]
    assert all(0 < r.due_s < 30.0 for r in a) and all(x.due_s <= y.due_s for x, y in zip(a, a[1:]))


def test_clipped_lognormal_keeps_its_median():
    from bench import arrivals

    rng = np.random.default_rng(0)
    for spec in (chat_mix()["prompt"], chat_mix()["output"]):
        x = arrivals.clipped_lognormal(rng, spec, 20001)
        assert x.min() >= spec["min"] and x.max() <= spec["max"]
        assert abs(np.median(x) - spec["median"]) <= 0.03 * spec["median"]


def test_open_loop_times_requests_from_their_due_time():
    """A stalled engine shows in the latency of the requests behind it, and
    the generator reports how late it ran."""
    from bench import arrivals, harness

    drv = harness.load_module(ROOT / "bench" / "drivers" / "open_loop_serve.py")

    class Stalling:
        """Serves one token per request per tick, and every tick takes 50 ms."""

        chunk = 4

        def __init__(self):
            self.sched = SimpleNamespace(active=[], busy=False)

        def submit(self, rid, prompt, max_new):
            req = SimpleNamespace(rid=rid, prompt=prompt, max_new=max_new)
            self.sched.active.append(SimpleNamespace(req=req, generated=[], prefill_done=True, length=len(prompt)))
            self.sched.busy = True

        def step(self):
            import time

            time.sleep(0.05)
            for a in self.sched.active:
                if len(a.generated) < a.req.max_new:
                    a.generated.append(1)
                    a.length += 1
            self.sched.busy = any(len(a.generated) < a.req.max_new for a in self.sched.active)

    reqs = [arrivals.Request(f"r{i}", 0.01 * i, np.zeros(3, np.int32), 2) for i in range(4)]
    lg = drv.drive(Stalling(), reqs, seconds=0.3, drain_s=2.0)
    ttft, itl = drv.latencies(lg, 2.3)
    assert all(lg.done(r.rid) for r in reqs)
    assert max(lg.late) >= 0.03  # requests due while the first tick ran went out late
    assert min(ttft) >= 0.05 and all(g >= 0.05 for g in itl)
