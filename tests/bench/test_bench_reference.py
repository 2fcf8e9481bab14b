"""The plain float32 references against the program at tiny widths, both
in float32: the round, prefill and decode agree; a skipped update and an
altered token do not."""
from bench_tiny import TINY, tiny_root, cpu_devices, no_compile_cache  # noqa: F401  (fixtures; the checkout on sys.path)

import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest


ROOT = Path(__file__).resolve().parents[2]


def f32_cell(name):
    from bench import harness

    spec = dict(TINY[name], dtype="float32")
    return spec, harness.model_config(spec)


def test_reference_loss_is_the_program_loss():
    from bench import harness, weights
    from bench.reference import model as ref
    from repro.models import transformer as T

    spec, cfg = f32_cell("tiny-danube")
    params = weights.make_tree(weights.seed_key(3), harness.param_shapes(cfg))
    rows = next(weights.row_blocks(3, 2, 40, cfg.vocab_size))  # longer than the window of 24
    with jax.default_matmul_precision("highest"):
        want = float(T.lm_loss(cfg, params, dict(tokens=jnp.asarray(rows[:, :-1]), targets=jnp.asarray(rows[:, 1:])))[0])
        got = float(ref.loss(params, jnp.asarray(rows[:, :-1]), jnp.asarray(rows[:, 1:]), ref.Cfg.from_file(spec)))
    assert got == pytest.approx(want, rel=1e-5)


def round_readings(spec, seed, monkeypatch=None, skip_update=False):
    from bench import harness
    from bench.harness import Context, Tracer, now

    drv = harness.load_module(ROOT / "bench" / "drivers" / "train_round.py")
    job = json.loads((ROOT / "bench" / "traffic" / "train-s2k-t2.json").read_text())
    job.update(seq_len=32)
    cell = harness.Cell(root=ROOT, name="t", chips=1, config=spec, job=job, end_to_end=[], per_layer=[])
    ctx = Context(cell=cell, model=harness.model_config(spec), seed=seed, seconds=0, tracer=Tracer(False, ROOT),
                  devices=jax.devices()[:1], t_start=now())
    if skip_update:
        from repro.kernels.opt_step import ops

        monkeypatch.setattr(ops, "sgd_step", lambda x, g, m, lr, **k: (x, (k["momentum"] * m + g).astype(m.dtype)))
    exp, key, make = drv.build(ctx)
    with jax.default_matmul_precision("highest"):
        prog, _ = drv.first_rounds(exp, make, key, 3)
    return drv, prog, drv.reference(ctx, key, make, 3)


def test_reference_rounds_agree_with_the_program_in_float32():
    from bench import harness

    spec, _ = f32_cell("tiny-danube")
    drv, prog, ref = round_readings(spec, 2**32 + 1)
    checks = {c.name: c.value for c in drv.compare(prog, ref, {})}
    assert checks["loss_gap"] < 1e-5 and checks["grad_gap"] < 1e-4 and checks["change_gap"] < 1e-4, checks
    assert checks["anchor_gap"] < 1e-4, checks
    assert set(harness.moving_leaves(ref["grad"])) == set(ref["grad"])


def test_a_skipped_update_fails_the_round_comparison(monkeypatch):
    spec, _ = f32_cell("tiny-danube")
    drv, prog, ref = round_readings(spec, 2**32 + 1, monkeypatch, skip_update=True)
    checks = {c.name: c.value for c in drv.compare(prog, ref, {})}
    assert checks["change_gap"] > 0.5, checks


def test_served_tokens_are_the_reference_argmax():
    """Chunked prefill into the paged pool, paged decode, ParamView reads
    and the head, against one plain forward pass per request."""
    from bench import weights
    from bench.drivers import open_loop_serve as drv  # the serving cell's plane maker
    from bench.reference import model as ref
    from bench.reference import serve as ref_serve
    from repro.serving import BatchedEngine

    spec, cfg = f32_cell("tiny-qwen2")
    plane = drv.make_plane(cfg, 5)
    eng = BatchedEngine(cfg, plane, slots=3, max_len=64, page_size=8, chunk=16)
    rng = np.random.default_rng(0)
    prompts = {f"r{i}": rng.integers(0, cfg.vocab_size, n).astype(np.int32) for i, n in enumerate((5, 23, 40))}
    for rid, p in prompts.items():
        eng.submit(rid, p, 12)
    out = eng.run()
    key = weights.seed_key(5)
    for rid, p in prompts.items():
        gaps = ref_serve.served_gaps(key, ref.Cfg.from_file(spec), "float32", p, out[rid], 64)
        assert gaps.shape == (12,) and gaps.max() < 1e-4, (rid, gaps)
        bad = out[rid].copy()
        bad[6] = (bad[6] + 1) % cfg.vocab_size
        assert ref_serve.served_gaps(key, ref.Cfg.from_file(spec), "float32", p, bad, 64)[6] > 1e-3


def test_plane_is_the_packed_tree():
    from bench import harness, weights
    from bench.drivers import open_loop_serve as drv
    from repro.parallel.packing import pack

    _, cfg = f32_cell("tiny-qwen2")
    cfg = dataclasses.replace(cfg, dtype="bfloat16")
    plane = drv.make_plane(cfg, 2**40)
    want = pack(weights.make_tree(weights.seed_key(2**40), harness.param_shapes(cfg)))
    assert plane.layout == want.layout
    assert all(bool((a == b).all()) for a, b in zip(plane.buffers, want.buffers))


@pytest.mark.parametrize("cell", ["tiny.train", "tiny.chat"])
def test_float8_control_is_not_correct(cell, tiny_root, cpu_devices, tmp_path):
    """The control -- the reference computed in float8 put in the program's
    place -- fails one of the cell's numbers, and the sound program passes
    them, read as ``bench/calibrate.py`` reads them on the chip."""
    import importlib.util

    from bench import harness

    spec = importlib.util.spec_from_file_location("bench_calibrate", ROOT / "bench" / "calibrate.py")
    cal = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cal)
    out = tmp_path / "readings.jsonl"
    seed = str(2**31 + 3)
    cal.main(["--workload", cell, "--seeds", seed, "--control-seeds", seed, "--seconds", "2", "--out", str(out)],
             root=tiny_root, devices=cpu_devices)
    rows = {r["reading"]: r for r in map(json.loads, out.read_text().splitlines())}
    limits = harness.find_cell(tiny_root, cell).job["limits"]
    assert all(rows["program"][k] <= v for k, v in limits.items()), rows["program"]
    assert any(rows["control_fp8"][k] > v for k, v in limits.items()), rows["control_fp8"]
    # each fault the cell can have, a worker mean left out among them
    faults = [r for name, r in rows.items() if name.startswith("fault_")]
    assert faults and all(any(r[k] > v for k, v in limits.items()) for r in faults)
    if cell == "tiny.train":
        assert rows["fault_no_worker_mean"]["anchor_gap"] > limits["anchor_gap"], rows["fault_no_worker_mean"]
