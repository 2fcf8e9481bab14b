"""The program's spans and name scopes in a trace (``bench/program_trace.py``):
spans with their self and idle time, device time per scope, idle gaps named
by the innermost span of either kind, and the readings, on hand-made lists,
on traces of tiny runs taken here, and on traces recorded on a v5e chip. The
program's counts against the drivers' own, and the trace reduction's output
on the two older recorded traces, pinned."""
import bench_tiny
from bench_tiny import tiny_root, cpu_devices, no_compile_cache  # noqa: F401  (fixtures; the checkout on sys.path)

import gzip
import hashlib
import json
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import harness
from bench import program_trace as pt
from bench import trace_reduce as tr

DATA = Path(__file__).resolve().parent / "data"
MS = 1_000_000  # ns


def hand_trace():
    """One chip, a 10 ms window: a tick [1, 9] ms holding a schedule, a
    prefill and its readback; ops of three scopes, one loop."""
    return {
        "host": [["bench.window", 0, 10 * MS], ["bench.step", 1 * MS, 9 * MS],
                 ["repro.engine.tick", 1 * MS, 9 * MS], ["repro.engine.schedule", 1 * MS, 2 * MS],
                 ["repro.engine.prefill", 2 * MS, 3 * MS], ["repro.engine.prefill_readback", 5 * MS, 8 * MS]],
        "devices": {"0": {
            "ops": [["%while.1", 2 * MS, 4 * MS], ["%fusion.1", 2 * MS, 3 * MS], ["%fusion.2", 3 * MS, 4 * MS],
                    ["%attn.3", 4 * MS, 5 * MS], ["%sgd_step_flat.4", 8 * MS, 9 * MS]],
            "modules": [["jit_serve_prefill_chunk(1)", 2 * MS, 5 * MS]],
            "op_scopes": ["backward", "backward", "forward", "backward/attention_bwd", "opt_step"],
        }},
    }


def test_spans_self_and_idle_time():
    r = pt.reduce(hand_trace())
    s = r["spans"]
    tick = s["repro.engine.tick"]
    assert tick["count"] == 1 and tick["seconds"] == pytest.approx(0.008)
    assert tick["self_s"] == pytest.approx(0.008 - 0.001 - 0.001 - 0.003)
    # busy [2, 5] and [8, 9] ms: the tick is idle 1-2 and 5-8
    assert tick["idle_s"] == pytest.approx(0.004)
    assert s["repro.engine.prefill_readback"]["idle_s"] == pytest.approx(0.003)
    assert s["repro.engine.prefill"]["idle_s"] == 0.0
    # bench.step holds the tick, which covers it whole
    assert s["bench.step"]["self_s"] == pytest.approx(0.0)
    assert pt.tick_idle_share(r) == pytest.approx(50.0)


def test_scopes_count_leaf_ops_in_every_scope_around_them():
    r = pt.reduce(hand_trace())
    # the loop holds fusion.1 and fusion.2 and is left out, as in ``ops``
    assert r["scopes"] == pytest.approx({"backward": 0.002, "forward": 0.001, "attention_bwd": 0.001,
                                         "opt_step": 0.001})


def test_idle_gaps_take_the_innermost_span_of_either_kind():
    r = pt.reduce(hand_trace())
    gaps = r["breakdown"]["idle_gaps"]
    # idle [5, 8], [0, 2] and [9, 10] ms, named at their midpoints
    assert gaps == [["repro.engine.prefill_readback", pytest.approx(0.003)],
                    ["repro.engine.schedule", pytest.approx(0.002)], ["no bench span", pytest.approx(0.001)]]
    # the keys trace_reduce gives are kept as it gives them
    old = tr.reduce(hand_trace())
    assert {k: r[k] for k in old} == {**old, "breakdown": r["breakdown"]}
    assert r["ops"] == old["ops"] and r["busy_s"] == old["busy_s"]


@pytest.mark.parametrize("path,scope", [
    ("jit(round_step)/while/body/closed_call/jvp(forward)/vmap()/while/body/jit(silu)/mul", "forward"),
    ("jit(round_step)/while/body/closed_call/backward/transpose(jvp(forward))/vmap(jit(take_along_axis))",
     "backward"),
    ("jit(round_step)/while/body/closed_call/backward/transpose(jvp(forward))/vmap()/while/body/closed_call/"
     "attention_bwd/transpose(jvp())/while/body/exp", "backward/attention_bwd"),
    ("jit(round_step)/while/body/closed_call/opt_step/jit(sgd_step_flat)/while/body/sub", "opt_step"),
    ("jit(round_step)/boundary/jit(pullback_momentum_flat)/while/body/eq", "boundary"),
    ("jit(serve_decode)/dot_general", ""),
])
def test_scope_of_an_op_name(path, scope):
    assert pt.scope_of(path) == scope


def test_readings_read_nothing_where_the_parent_has_nothing():
    """The parent's program has no spans, scopes or counters: every reading
    is None and none raises."""
    assert pt.tick_idle_share({}) is None and pt.tick_idle_share(None) is None
    assert pt.readback_bytes_per_token({}) is None
    assert pt.attention_bwd_roofline({}, 4, {}, {}, {}) is None


def test_attention_bwd_roofline_counts_the_backwards_flops():
    c = json.loads((bench_tiny.ROOT / "bench/configs/h2o-danube-1.8b-l4.json").read_text())
    job = json.loads((bench_tiny.ROOT / "bench/traffic/train-s2k-t2.json").read_text())
    r = {"scopes": {"attention_bwd": 0.2}}
    # one round: 2 workers x tau 2 x 2048 tokens, 4 layers, 8 x 1024.5 x 32 x 80 FLOPs each
    least = 8192 * 4 * 8 * 1024.5 * 32 * 80 / 197e12
    assert pt.attention_bwd_roofline(r, 1, c, job, {"bf16_flops": 197e12}) == pytest.approx(100 * least / 0.2)


# -- traces of tiny runs, taken here ---------------------------------------


def _profile(tmp_path, fn):
    """The xplane file of a profiler trace around ``fn``."""
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("bench.window"):
            fn()
    return next(tmp_path.glob("**/*.xplane.pb"))


def _tiny(name):
    return harness.model_config(bench_tiny.TINY[name])


def _tiny_engine():
    from repro.models import transformer as T
    from repro.serving import BatchedEngine

    cfg = _tiny("tiny-qwen2")
    e = bench_tiny.tiny_mixes()["tiny-chat"]["engine"]
    params, _ = T.init_model(cfg, jax.random.PRNGKey(0))
    return BatchedEngine(cfg, params, slots=e["slots"], max_len=e["max_len"], page_size=e["page_size"],
                         chunk=e["chunk"]), cfg


def _parents(ir):
    spans = sorted((s for s in ir["host"] if s[0] != tr.WINDOW), key=lambda s: (s[1], -s[2]))
    return [(s[0], spans[p][0] if p is not None else None) for s, p in zip(spans, pt._nest(spans))]


def test_tiny_engine_run_spans_nest_in_the_tick(tmp_path):
    eng, cfg = _tiny_engine()
    rng = np.random.default_rng(0)
    for i, n in enumerate((30, 5, 17)):  # two chunks of 16, one, two
        eng.submit(f"r{i}", rng.integers(1, cfg.vocab_size, n).astype(np.int32), 3)
    eng.step()
    before = eng.counters.copy()
    ir = pt.extract_file(_profile(tmp_path, eng.run))
    d = eng.counters - before
    pairs = _parents(ir)
    names = [n for n, _ in pairs]
    assert names.count("repro.engine.tick") == d["ticks"]
    assert names.count("repro.engine.prefill") == d["prefill_chunks"]
    assert names.count("repro.engine.decode") == names.count("repro.engine.sample") == d["decode_steps"]
    assert names.count("repro.engine.prefill_readback") == 2  # r1 finished its prompt in the first tick
    for name, parent in pairs:
        assert parent == ("repro.engine.tick" if name != "repro.engine.tick" else None), name


def test_tiny_fit_spans_nest_in_the_round(tmp_path):
    from repro.api import Experiment, TokenStream

    exp = Experiment(arch=_tiny("tiny-danube"), strategy="overlap_local_sgd", workers=2, rounds=1,
                     data=TokenStream(batch_per_worker=1, seq_len=32))
    exp.fit(rounds=1)
    pb = _profile(tmp_path, lambda: exp.fit(rounds=2))
    pairs = _parents(pt.extract_file(pb))
    assert [n for n, _ in pairs].count("repro.fit.round") == 2
    assert sorted(pairs) == sorted([("repro.fit.round", None)] * 2 + [
        (f"repro.fit.{k}", "repro.fit.round") for k in ("batch", "dispatch", "loss_read")] * 2)
    # the trace keeps the round program's HLO, whose instructions carry their scopes
    scopes = set(pt.hlo_scopes(pb.read_bytes())["jit_round_step"].values())
    assert {"forward", "backward", "opt_step", "boundary"} <= {p for sc in scopes for p in sc.split("/")}


# -- the program's counts against the drivers' -----------------------------


def test_engine_counts_equal_the_serving_drivers():
    from bench import arrivals

    drv = harness.load_module(bench_tiny.ROOT / "bench/drivers/open_loop_serve.py")
    eng, cfg = _tiny_engine()
    reqs = arrivals.open_loop(bench_tiny.tiny_mixes()["tiny-chat"], 7_000_000_001, 2.0, cfg.vocab_size)
    lg = drv.Log(reqs)
    for r in reqs:
        eng.submit(r.rid, r.prompt, r.max_new)
    while eng.sched.busy:
        drv.step(eng, lg, lambda: 0.0, True, True)
    c = eng.counters
    assert lg.trace == {k: c[k] for k in ("decode_steps", "decode_tokens", "decode_kv_tokens")}
    assert lg.generated == c["generated_tokens"] == sum(r.max_new for r in reqs)
    assert lg.prefilled == c["prefill_tokens"]


@pytest.mark.parametrize("cell,keys", [("tiny.chat", ("decode_steps", "decode_tokens", "decode_kv_tokens")),
                                       ("tiny.train", ("rounds", "tokens"))])
def test_traced_run_of_a_cell_counts_as_its_driver(cell, keys, tiny_root, cpu_devices, capsys):
    """``bench/program_trace.py`` on a tiny cell: the driver's window
    compiles nothing, and the program's counts over it (``BatchedEngine`` or
    ``Experiment.counters``) equal the driver's own."""
    assert pt.main(["--workload", cell, "--seed", "8000000001", "--seconds", "2"], root=tiny_root,
                   devices=cpu_devices) == 0
    out = capsys.readouterr()
    line = json.loads(out.out.strip().splitlines()[-1])
    assert line["compiled"] == {"compiles": 0, "cache_loads": 0}
    assert "[trace] window compiled 0 programs" in out.err
    p = line["program"]
    assert {k: p[k] for k in keys} == {k: line["counts"][k] for k in keys}
    if cell == "tiny.chat":
        assert line["readings"]["serve.readback_bytes_per_token"] == p["readback_bytes"] / p["generated_tokens"]


# -- recorded on a v5e chip --------------------------------------------------


def recorded(name):
    with gzip.open(DATA / f"{name}.json.gz", "rt") as f:
        return json.load(f)


def _digest(value) -> str:
    return hashlib.sha256(json.dumps(value, sort_keys=True).encode()).hexdigest()[:16]


# each key of ``trace_reduce.reduce`` on the two older recorded traces, as
# the benchmark first read them: the spans and scopes leave them as they were
PINNED = {
    "train_round_v5e": {"window_s": "68e0244b5b495721", "busy_s": "fce7596aad4714b2",
                        "exposed_collective_s": "8aed642bf5118b9d", "ops": "243cfa1de684149b",
                        "modules": "aa89cad3e35fa509", "module_ops": "89dbeaa6896ac2ab",
                        "breakdown": "a2cb1b6a0928faa6"},
    "serve_ticks_v5e": {"window_s": "df4fd47479c71870", "busy_s": "bffff6ca02d5d9fe",
                        "exposed_collective_s": "8aed642bf5118b9d", "ops": "f7664b698f59f297",
                        "modules": "83136dfa7ebc431c", "module_ops": "53745042b5fed029",
                        "breakdown": "7286a7335ce00d94"},
}


@pytest.mark.parametrize("name", sorted(PINNED))
def test_older_recorded_traces_reduce_as_pinned(name):
    old = tr.reduce(recorded(name), device_ids=[0])
    assert {k: _digest(v) for k, v in old.items()} == PINNED[name]
    new = pt.reduce(recorded(name), device_ids=[0])
    assert {k: _digest(new[k]) for k in old} == PINNED[name]
    assert set(new) == set(old) | {"spans", "scopes"} and new["scopes"] == {}


def test_recorded_chat_ticks_name_the_readback():
    """Five ticks of the chat cell around a prompt's last prefill chunk
    (qwen2-7b at 8 layers, 16 slots; ``bench/program_trace.py --record``)."""
    r = pt.reduce(recorded("serve_ticks_spans_v5e"), device_ids=[0])
    s = r["spans"]
    assert s["repro.engine.tick"]["count"] == 5 and s["repro.engine.prefill_readback"]["count"] == 1
    assert s["repro.engine.prefill"]["count"] == 3 and s["repro.engine.decode"]["count"] == 3
    # the tick that ends a prompt's prefill copies its whole logits to the host
    # while the device waits: the window's longest idle gap is there
    assert r["breakdown"]["idle_gaps"][0] == ["repro.engine.prefill_readback", pytest.approx(0.111944749)]
    assert s["repro.engine.prefill_readback"]["idle_s"] == pytest.approx(0.106670191)
    assert pt.tick_idle_share(r) == pytest.approx(100 * 0.122943117 / 0.649576111)
    programs = {m.split("(")[0] for m in r["modules"]}
    assert {"jit_serve_decode", "jit_serve_prefill_chunk"} <= programs and "jit__unknown" not in programs
    assert r["scopes"] == {}  # the serving programs carry no training scope


def test_recorded_training_round_by_scope():
    """One round of the training cell (h2o-danube-1.8b at 4 layers, two
    workers stacked, tau 2; ``bench/program_trace.py --record``), its ops
    named by the scopes the trace's HLO gives them."""
    r = pt.reduce(recorded("train_round_spans_v5e"), device_ids=[0])
    sc = r["scopes"]
    assert sc == pytest.approx({"forward": 0.190936945, "backward": 0.291673034, "attention_bwd": 0.185080051,
                                "opt_step": 0.070573067, "boundary": 0.033999486})
    assert sc["forward"] + sc["backward"] + sc["opt_step"] + sc["boundary"] <= r["busy_s"]
    s = r["spans"]
    assert [s[f"repro.fit.{k}"]["count"] for k in ("round", "batch", "dispatch", "loss_read")] == [1, 1, 1, 1]
    # the host waits on the device in the loss read; the device waits for the batch
    assert s["repro.fit.loss_read"]["seconds"] > 0.95 * s["repro.fit.round"]["seconds"]
    assert s["repro.fit.batch"]["idle_s"] == pytest.approx(0.006390606)
    assert {g[0] for g in r["breakdown"]["idle_gaps"]} <= {"repro.fit.loss_read", "repro.fit.batch", "no bench span"}
    c = json.loads((bench_tiny.ROOT / "bench/configs/h2o-danube-1.8b-l4.json").read_text())
    job = json.loads((bench_tiny.ROOT / "bench/traffic/train-s2k-t2.json").read_text())
    assert pt.attention_bwd_roofline(r, 1, c, job, {"bf16_flops": 197e12}) == pytest.approx(
        100 * 8192 * 4 * 8 * 1024.5 * 32 * 80 / 197e12 / 0.185080051)
