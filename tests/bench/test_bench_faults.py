"""A run of each cell kind driven end to end on the CPU at tiny shapes,
with the harness's look for a chip stood in for: a sound program comes out
``correct``, and each fault the cell can have, planted in the timed path,
comes out not correct."""
from bench_tiny import tiny_root, cpu_devices, no_compile_cache  # noqa: F401  (fixtures; the checkout on sys.path)

import importlib.util
import json
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]

SEED = 2**31 + 11  # seeds run past 32 bits


def run_module():
    spec = importlib.util.spec_from_file_location("bench_run", ROOT / "bench" / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def run(root, cell, devices, capsys, seconds=1.0, trace=0):
    rc = run_module().main(["--workload", cell, "--seed", str(SEED), "--seconds", str(seconds), "--trace", str(trace)],
                           root=root, devices=devices)
    assert rc == 0
    out = capsys.readouterr()
    line = json.loads(out.out.strip().splitlines()[-1])
    assert list(line)[-1] == "checks"
    assert out.err.strip().splitlines()[-1].startswith("check ")
    return line


def state_unchanged(monkeypatch):
    import repro.api.experiment as E

    made = E.make_round_step

    def broken(*a, **k):
        step = made(*a, **k)
        return lambda state, batch: (state, step(state, batch)[1])

    monkeypatch.setattr(E, "make_round_step", broken)


def half_batch(monkeypatch):
    import repro.models.transformer as T

    loss = T.lm_loss

    def broken(cfg, params, batch, **k):
        half = batch["tokens"].shape[-1] // 2
        return loss(cfg, params, {n: t[..., :half] for n, t in batch.items()}, **k)

    monkeypatch.setattr(T, "lm_loss", broken)


def no_worker_mean(monkeypatch):
    import jax.numpy as jnp
    from repro.kernels.anchor_mix import ops

    fused = ops.pullback_mean_momentum

    def broken(x, z, v, alpha, beta, **k):
        x_new = fused(x, z, v, alpha, beta, **k)[0]
        # the next anchor launched from worker 0 alone
        _, z_next, v_new = fused(jnp.broadcast_to(x[:1], x.shape), z, v, alpha, beta, **k)
        return x_new, z_next, v_new

    monkeypatch.setattr(ops, "pullback_mean_momentum", broken)


def test_train_cell_sound_run_is_correct(tiny_root, cpu_devices, capsys):
    line = run(tiny_root, "tiny.train", cpu_devices, capsys)
    assert line["correct"], line["checks"]
    assert set(line["metrics"]) == {"train_tok_s_per_chip", "setup_s"}
    assert line["attempted"] >= 1 and line["failed"] == 0


@pytest.mark.parametrize("fault", [state_unchanged, half_batch, no_worker_mean], ids=lambda f: f.__name__)
def test_train_cell_fault_is_not_correct(fault, tiny_root, cpu_devices, capsys, monkeypatch):
    fault(monkeypatch)
    line = run(tiny_root, "tiny.train", cpu_devices, capsys)
    assert not line["correct"], line["checks"]


def test_serve_cell_sound_run_is_correct(tiny_root, cpu_devices, capsys):
    line = run(tiny_root, "tiny.chat", cpu_devices, capsys, seconds=2.0)
    assert line["correct"], line["checks"]
    assert set(line["metrics"]) == {"serve_itl_p95_ms", "setup_s"}
    assert line["attempted"] > 4 and line["failed"] == 0


def test_serve_cell_altered_token_is_not_correct(tiny_root, cpu_devices, capsys, monkeypatch):
    from repro.serving import engine as E

    step = E.BatchedEngine.step

    def broken(self):
        out = step(self)
        for a in self.sched.active:  # a token altered where it is produced
            if a is not None and len(a.generated) == 2:
                a.generated[1] = (a.generated[1] + 1) % self.cfg.vocab_size
        return out

    monkeypatch.setattr(E.BatchedEngine, "step", broken)
    line = run(tiny_root, "tiny.chat", cpu_devices, capsys, seconds=2.0)
    assert not line["correct"], line["checks"]


@pytest.mark.parametrize("cell", ["tiny.train", "tiny.chat"])
def test_traced_run_reads_its_window(cell, tiny_root, cpu_devices, capsys):
    """A ``--trace 1`` run traces part of its window and still compares. On
    the CPU the trace holds no chip, so no per-layer metric finds anything
    to read and none is reported: no CPU number stands under a device
    metric's name."""
    line = run(tiny_root, cell, cpu_devices, capsys, seconds=2.0, trace=1)
    assert line["correct"], line["checks"]
    assert line["metrics"] == {} and "busy_s" not in line["device"]
    assert not (tiny_root / ".bench_trace").exists()
