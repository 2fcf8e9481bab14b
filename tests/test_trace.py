"""The program's tracing (``repro.utils.trace``): the engine tick's and the
fit round's spans nest in a profiler trace, the counters count the work
done, the compile count sees a new shape and nothing else, a span costs
next to nothing with the profiler off, and the round program's name scopes
leave its operations as they were."""
import contextlib
import dataclasses
import re
import timeit

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import get_arch
from repro.models import transformer as T
from repro.serving import BatchedEngine
from repro.utils import trace


def _host_spans(logdir):
    """(name, start_ns, end_ns) of every ``repro.*`` event the profiler wrote."""
    from jax.profiler import ProfileData

    pb = next(logdir.glob("**/*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(str(pb)).planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                out += [(e.name, e.start_ns, e.start_ns + e.duration_ns) for e in line.events
                        if e.name.startswith("repro.")]
    return sorted(out, key=lambda s: (s[1], -s[2]))


def _inside(child, parents):
    return any(p[1] <= child[1] and child[2] <= p[2] for p in parents)


@pytest.fixture(scope="module")
def engine_setup():
    cfg = dataclasses.replace(get_arch("qwen2-7b").model.reduced(), dtype="float32")
    params, _ = T.init_model(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _engine(cfg, params):
    return BatchedEngine(cfg, params, slots=2, max_len=48, page_size=8, chunk=8)


def test_engine_tick_spans_nest_and_counters_count(engine_setup, tmp_path):
    cfg, params = engine_setup
    eng = _engine(cfg, params)
    prompts = {"a": (11, 4), "b": (5, 3), "c": (17, 2)}  # two chunks of 8, one, three
    for rid, (n, max_new) in prompts.items():
        eng.submit(rid, np.arange(1, n + 1, dtype=np.int32), max_new)
    eng.step()  # compiles both programs outside the trace
    before = eng.counters.copy()
    with jax.profiler.trace(str(tmp_path)):
        eng.run()
    d = eng.counters - before
    by = {}
    for s in _host_spans(tmp_path):
        by.setdefault(s[0], []).append(s)
    ticks = by["repro.engine.tick"]
    assert len(ticks) == d["ticks"]
    for name in ("repro.engine.schedule", "repro.engine.prefill", "repro.engine.prefill_readback",
                 "repro.engine.decode", "repro.engine.sample"):
        assert by[name] and all(_inside(s, ticks) for s in by[name]), name
    assert not any(_inside(s, by["repro.engine.prefill"]) for s in by["repro.engine.prefill_readback"])
    assert all(any(_inside(s, [t]) for s in by["repro.engine.schedule"]) for t in ticks)
    assert len(by["repro.engine.prefill"]) == d["prefill_chunks"]
    assert len(by["repro.engine.decode"]) == len(by["repro.engine.sample"]) == d["decode_steps"]
    # what the counters count over the whole run, from the prompts alone
    c = eng.counters
    assert c["prefill_chunks"] == sum(-(-n // 8) for n, _ in prompts.values())
    assert c["prefill_tokens"] == sum(n for n, _ in prompts.values())
    assert c["generated_tokens"] == sum(m for _, m in prompts.values())
    assert c["decode_tokens"] == sum(m - 1 for _, m in prompts.values())
    assert c["decode_kv_tokens"] == sum(n + k for n, m in prompts.values() for k in range(1, m))
    # each prompt's last chunk copies its f32 logits whole; each decode its
    # two slots' int32 tokens
    assert c["readback_bytes"] == len(prompts) * 8 * cfg.vocab_size * 4 + c["decode_steps"] * 2 * 4
    assert not hasattr(eng, "steps")


def test_engine_programs_have_stable_names(engine_setup):
    cfg, params = engine_setup
    eng = _engine(cfg, params)
    table = np.zeros((1, eng.max_pages), np.int32)
    args = (params, jnp.zeros((1, 8), jnp.int32), eng.pools, table, jnp.zeros((1,), jnp.int32))
    assert eng._prefill_jit.lower(*args).as_text().startswith("module @jit_serve_prefill_chunk")
    dec = (params, jnp.zeros((2, 1), jnp.int32), eng.pools, np.zeros((2, eng.max_pages), np.int32),
           jnp.zeros((2,), jnp.int32))
    assert eng._decode_jit.lower(*dec).as_text().startswith("module @jit_serve_decode")


def test_fit_round_spans_nest_and_counters_count(tmp_path):
    from repro.api import Experiment, TokenStream

    exp = Experiment(arch="qwen2-7b", strategy="overlap_local_sgd", workers=2, rounds=1,
                     data=TokenStream(batch_per_worker=1, seq_len=16))
    exp.fit(rounds=1)
    with jax.profiler.trace(str(tmp_path)):
        exp.fit(rounds=2)
    spans = _host_spans(tmp_path)
    rounds = [s for s in spans if s[0] == "repro.fit.round"]
    assert len(rounds) == 2
    for name in ("repro.fit.batch", "repro.fit.dispatch", "repro.fit.loss_read"):
        inner = [s for s in spans if s[0] == name]
        assert len(inner) == 2 and all(_inside(s, rounds) for s in inner), name
    tau = exp.tau
    assert exp.counters == {"rounds": 3, "local_steps": 3 * tau, "tokens": 3 * tau * 2 * 1 * 16}


def test_compiles_counts_a_new_shape_and_nothing_else():
    f = jax.jit(lambda a: a * 2 + 1)
    trace.compiles()
    f(jnp.ones(3)).block_until_ready()
    before = trace.compiles()
    for _ in range(3):
        f(jnp.ones(3)).block_until_ready()
    assert (trace.compiles() - before)["compiles"] == 0
    f(jnp.ones(5)).block_until_ready()
    assert (trace.compiles() - before)["compiles"] >= 1


def test_span_with_the_profiler_off_costs_under_2us():
    def tick():
        with trace.span("engine.tick", tick=7) as t:
            t.set_metadata(prefilling=1, decoding=3)

    n = 5000
    best = min(timeit.repeat(tick, number=n, repeat=20)) / n
    assert best < 2e-6, f"{best * 1e6:.2f} us per span"


def _op_count(monkeypatch, scoped: bool) -> int:
    from repro.api import Experiment, TokenStream
    from repro.data.loaders import round_batch
    from repro.kernels.flags import force_pallas

    if not scoped:
        monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    cfg = dataclasses.replace(get_arch("h2o-danube-1.8b").model.reduced(), num_layers=1)
    with force_pallas(True):  # the attention backward is the flash kernel's VJP
        exp = Experiment(arch=cfg, strategy="overlap_local_sgd", workers=2, rounds=1,
                         data=TokenStream(batch_per_worker=1, seq_len=16)).build()
        text = exp.step_fn.lower(exp.state, round_batch(exp.next_batch, exp.tau)).compile().as_text()
    monkeypatch.undo()
    if scoped:
        names = set(re.findall(r'op_name="([^"]*)"', text))
        for scope in ("forward", "backward", "opt_step", "boundary", "attention_bwd"):
            assert any(f"/{scope}" in n or f"({scope})" in n for n in names), scope
    return len(re.findall(r"^\s*(?:ROOT )?%?[\w.\-]+ = ", text, re.M))


def test_name_scopes_leave_the_round_program_unchanged(monkeypatch):
    assert _op_count(monkeypatch, True) == _op_count(monkeypatch, False)
