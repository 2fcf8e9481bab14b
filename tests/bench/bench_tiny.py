"""Tiny shapes of the cells' code paths, for runs driven on the CPU, and the
fixtures that drive them. Importing it puts the checkout on ``sys.path``, so
that the test files can import the benchmark (``bench``) and the program."""
import json
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [p for p in (str(ROOT), str(ROOT / "src")) if p not in sys.path]

# the two cells' code paths' code paths: sliding-window GQA without bias
# (danube) and full-attention GQA with QKV bias (qwen2)
TINY = {
    "tiny-danube": {"arch": "h2o-danube-1.8b", "num_layers": 2, "d_model": 64, "d_ff": 128, "vocab_size": 256,
                    "num_heads": 4, "num_kv_heads": 2, "head_dim": 16, "sliding_window": 24, "rope_theta": 10000.0,
                    "qkv_bias": False, "norm_eps": 1e-05, "act": "silu", "tie_embeddings": False, "dtype": "bfloat16"},
    "tiny-qwen2": {"arch": "qwen2-7b", "num_layers": 2, "d_model": 64, "d_ff": 128, "vocab_size": 512,
                   "num_heads": 4, "num_kv_heads": 2, "head_dim": 16, "sliding_window": None, "rope_theta": 1000000.0,
                   "qkv_bias": True, "norm_eps": 1e-06, "act": "silu", "tie_embeddings": False, "dtype": "bfloat16"},
}


def tiny_mixes():
    """The cells' own job and mix files, cut to tiny shapes."""
    train = json.loads((ROOT / "bench/traffic/train-s2k-t2.json").read_text())
    # limits of these shapes, set as the cells' are (six tenths of the way
    # from the lower to the upper reading, on a log scale) from 8 seeds on
    # the CPU: sound runs read loss 1.05e-3, grad 2.5e-3, change 0.039,
    # anchor 0.013 at most; the float8 control and the faults read loss
    # 3.2e-3, grad 0.35, change 0.45, anchor 0.39 at least
    train.update(seq_len=32, trace_rounds=2,
                 limits={"loss_gap": 2.0e-3, "grad_gap": 0.048, "change_gap": 0.17, "anchor_gap": 0.1})
    chat = json.loads((ROOT / "bench/traffic/chat.json").read_text())
    chat.update(rate_per_s=8.0, prompt={"median": 12, "sigma": 0.8, "min": 2, "max": 40},
                output={"median": 6, "sigma": 0.6, "min": 2, "max": 16},
                engine={"slots": 4, "max_len": 64, "page_size": 8, "chunk": 16},
                drain_s=30, trace_seconds=0.5, check_requests=3,
                limits={"logit_gap": 0.02})  # sound runs read 0; the control 0.06, an altered token 4
    return {"tiny-train": train, "tiny-chat": chat}


def make_tiny_root(tmp_path: Path) -> Path:
    """A checkout whose cells are tiny: new configuration, mix and benchmark
    files beside the benchmark's own drivers, metrics and peaks."""
    bench = tmp_path / "bench"
    (bench / "configs").mkdir(parents=True)
    (bench / "traffic").mkdir()
    for d in ("drivers", "metrics"):
        shutil.copytree(ROOT / "bench" / d, bench / d, ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "bench/peaks.json", bench / "peaks.json")
    for name, spec in TINY.items():
        (bench / "configs" / f"{name}.json").write_text(json.dumps(spec))
    for name, mix in tiny_mixes().items():
        (bench / "traffic" / f"{name}.json").write_text(json.dumps(mix))
    real = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {"tiny.train": ("tiny-danube", "tiny-train", "train-danube-s2k-t2"),
             "tiny.chat": ("tiny-qwen2", "tiny-chat", "serve-qwen2-chat")}

    def retarget(entry):
        if "workloads" in entry:
            entry = dict(entry, workloads=[t for t, (_, _, c) in cells.items() if c in entry["workloads"]])
        return entry

    out = dict(real)
    out["configs"] = [{"name": n, "source": "tiny", "file": f"bench/configs/{n}.json", "reduced": [], "why": "tiny"}
                      for n in TINY]
    out["workloads"] = [{"name": t, "config": c, "traffic": m, "chips": 1, "why": "tiny"}
                        for t, (c, m, _) in cells.items()]
    out["end_to_end"] = [retarget(e) for e in real["end_to_end"]]
    out["per_layer"] = [retarget(e) for e in real["per_layer"]]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(out))
    return tmp_path


@pytest.fixture
def tiny_root(tmp_path):
    return make_tiny_root(tmp_path)


@pytest.fixture
def cpu_devices():
    """Stands in for the harness's look for a chip, so that the rest of a
    run can be driven on the CPU."""
    import jax

    return lambda chips, table: jax.devices()[:chips]


@pytest.fixture(autouse=True)
def no_compile_cache(monkeypatch):
    """Runs driven here keep JAX's persistent cache off."""
    import repro.utils.compile_cache as cc

    monkeypatch.setattr(cc, "enable_compile_cache", lambda: "off")
