"""Continuous-batching scheduler + paged engine behaviour (DESIGN.md §10).

Covers the scheduler contract (deterministic replay, FIFO admission,
evict-requeue under pool exhaustion, no page leaks), the engine-level
exactness guarantee (per-request outputs equal solo ``generate`` regardless
of co-batching — the regression pin for the old left-padded ``run()``), and
plane hot-swap under load (swap applies only at step boundaries; continuing
on the new plane is bitwise-equal to restarting the in-flight state on it).
"""
import copy
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import get_arch
from repro.models import transformer as T
from repro.serving import BatchedEngine, PageAllocator, Request, Scheduler, generate


def _cfg(arch_name="qwen2-7b", dtype="float32"):
    return dataclasses.replace(get_arch(arch_name).model.reduced(), dtype=dtype)


@pytest.fixture(scope="module")
def qwen_setup():
    cfg = _cfg()
    params, _ = T.init_model(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _trace(rng, n, vmax, lp=(3, 20), mn=(2, 9)):
    return [
        (f"r{i}", rng.integers(1, vmax, (int(rng.integers(*lp)),)).astype(np.int32), int(rng.integers(*mn)))
        for i in range(n)
    ]


# -- allocator ---------------------------------------------------------------


def test_allocator_never_issues_trash_page_and_detects_double_free():
    al = PageAllocator(5)
    assert al.capacity == 4
    got = al.alloc(4)
    assert 0 not in got and al.alloc(1) is None
    al.free([got[0]])
    with pytest.raises(ValueError, match="double free"):
        al.free([got[0]])
    with pytest.raises(ValueError, match="invalid page"):
        al.free([0])


def test_allocator_partial_requests_never_granted():
    al = PageAllocator(4)
    assert al.alloc(5) is None  # nothing handed out
    assert al.available == 3


# -- scheduler ---------------------------------------------------------------


def test_scheduler_rejects_request_that_can_never_fit():
    s = Scheduler(slots=2, num_pages=4, page_size=4, max_pages_per_slot=3)
    with pytest.raises(ValueError, match="needs"):
        s.submit(Request("big", np.ones(12, np.int32), 4))  # 15 tokens → 4 pages > capacity 3


def test_scheduler_admits_fifo_and_stops_at_first_nonfit():
    s = Scheduler(slots=3, num_pages=4, page_size=4, max_pages_per_slot=4)  # capacity 3
    s.submit(Request("a", np.ones(4, np.int32), 2))  # prefill 1 page + 1 headroom ≤ 3
    s.submit(Request("b", np.ones(10, np.int32), 2))  # prefill 3 pages + 1 > 3 — can't admit yet
    s.submit(Request("c", np.ones(2, np.int32), 2))  # would fit, but FIFO blocks behind b
    s.admit()
    assert [e for e in s.events if e[0] == "admit"] == [("admit", "a", 0)]
    assert [r.rid for r in s.queue] == ["b", "c"]


def test_scheduler_eviction_requeues_youngest_never_oldest():
    s = Scheduler(slots=2, num_pages=4, page_size=4, max_pages_per_slot=3)  # capacity 3
    s.submit(Request("old", np.ones(4, np.int32), 9))
    s.submit(Request("young", np.ones(4, np.int32), 9))
    s.admit()
    assert s.ensure_pages(0, 3) and s.ensure_pages(1, 3)  # 1 page each, 1 free
    assert s.ensure_pages(0, 11)  # old grows to 3 pages → pool exhausted...
    # ...but 'young' was evicted (not 'old'), and its request is back in front
    assert ("evict", "young", 1) in s.events
    assert [r.rid for r in s.queue] == ["young"]
    assert s.active[1] is None and s.active[0].req.rid == "old"
    # growing the survivor returns False only when it evicts itself — here it fit
    s.complete(0)
    assert s.alloc.available == s.alloc.capacity  # everything returned


def test_scheduler_replay_is_deterministic(qwen_setup, rng):
    cfg, params = qwen_setup

    def run_once():
        eng = BatchedEngine(cfg, params, slots=2, max_len=24, page_size=4, num_pages=9, chunk=8)
        trace = _trace(np.random.default_rng(42), 6, cfg.vocab_size, lp=(3, 14), mn=(2, 6))
        for rid, prompt, mn in trace[:4]:
            eng.submit(rid, prompt, mn)
        steps = 0
        while eng.sched.busy:
            eng.step()
            steps += 1
            if steps == 2:  # mid-run arrivals at a fixed step index
                for rid, prompt, mn in trace[4:]:
                    eng.submit(rid, prompt, mn)
        return eng.sched.events, {k: v.tolist() for k, v in eng.results.items()}, eng

    ev1, res1, _ = run_once()
    ev2, res2, eng = run_once()
    assert ev1 == ev2
    assert res1 == res2
    assert eng.sched.alloc.available == eng.sched.alloc.capacity  # no page leak


# -- engine exactness (the padded-batch regression pin) ----------------------


def test_cobatched_outputs_equal_solo_generate(qwen_setup, rng):
    """Ragged prompts and ragged max_new co-batched through the paged engine
    reproduce each request's solo ``generate`` exactly. The old engine
    left-padded prompts as attended tokens and decoded max(max_new) steps
    for everyone — either bug breaks this equality."""
    cfg, params = qwen_setup
    eng = BatchedEngine(cfg, params, slots=3, max_len=48, page_size=8, chunk=8)
    trace = _trace(rng, 6, cfg.vocab_size, lp=(3, 30), mn=(2, 8))
    for rid, prompt, mn in trace:
        eng.submit(rid, prompt, mn)
    res = eng.run()
    for rid, prompt, mn in trace:
        solo = np.asarray(generate(cfg, params, jnp.asarray(prompt)[None], mn)[0])
        np.testing.assert_array_equal(solo, res[rid], err_msg=rid)
        assert len(res[rid]) == mn  # per-request max_new, not max over the batch


def test_dense_fallback_is_exact_per_request(rng):
    """Recurrent archs (no pages to manage) fall back to solo decoding —
    also exact, also per-request max_new."""
    cfg = _cfg("rwkv6-7b")
    params, _ = T.init_model(cfg, jax.random.PRNGKey(0))
    eng = BatchedEngine(cfg, params, slots=2, max_len=32)
    assert not eng.paged
    trace = _trace(rng, 3, cfg.vocab_size, lp=(3, 10), mn=(2, 6))
    for rid, prompt, mn in trace:
        eng.submit(rid, prompt, mn)
    res = eng.run()
    for rid, prompt, mn in trace:
        solo = np.asarray(generate(cfg, params, jnp.asarray(prompt)[None], mn)[0])
        np.testing.assert_array_equal(solo, res[rid], err_msg=rid)


def test_eviction_under_exhaustion_completes_all_requests(qwen_setup, rng):
    """Pool too small for co-residency: requests must evict+requeue (never
    drop) and still produce exact outputs."""
    cfg, params = qwen_setup
    eng = BatchedEngine(cfg, params, slots=2, max_len=16, page_size=4, num_pages=6, chunk=8)
    trace = _trace(rng, 3, cfg.vocab_size, lp=(8, 9), mn=(8, 9))
    for rid, prompt, mn in trace:
        eng.submit(rid, prompt, mn)
    res = eng.run()
    assert any(e[0] == "evict" for e in eng.sched.events)
    assert sorted(res) == sorted(r for r, _, _ in trace)  # nothing dropped
    for rid, prompt, mn in trace:
        solo = np.asarray(generate(cfg, params, jnp.asarray(prompt)[None], mn)[0])
        np.testing.assert_array_equal(solo, res[rid], err_msg=rid)
    assert eng.sched.alloc.available == eng.sched.alloc.capacity


def test_stop_token_frees_slot_early(qwen_setup, rng):
    cfg, params = qwen_setup
    prompt = rng.integers(1, cfg.vocab_size, (6,)).astype(np.int32)
    free = generate(cfg, params, jnp.asarray(prompt)[None], 8)[0]
    stop = int(free[2])  # force an early stop at the 3rd generated token
    eng = BatchedEngine(cfg, params, slots=2, max_len=32, page_size=8)
    eng.submit("s", prompt, 8, stop=stop)
    res = eng.run()
    np.testing.assert_array_equal(np.asarray(free[:3]), res["s"])
    assert eng.sched.alloc.available == eng.sched.alloc.capacity


def test_submit_validations(qwen_setup):
    cfg, params = qwen_setup
    eng = BatchedEngine(cfg, params, slots=2, max_len=16, page_size=4)
    eng.submit("a", np.ones(4, np.int32), 2)
    with pytest.raises(ValueError, match="duplicate"):
        eng.submit("a", np.ones(4, np.int32), 2)
    with pytest.raises(ValueError, match="non-empty 1-D"):
        eng.submit("b", np.ones((2, 2), np.int32), 2)
    with pytest.raises(ValueError, match="max_new"):
        eng.submit("c", np.ones(4, np.int32), 0)
    with pytest.raises(ValueError, match="exceeds"):
        eng.submit("d", np.ones(14, np.int32), 8)


# -- plane hot-swap under load ----------------------------------------------


def _plane_pair():
    from repro.api import Experiment

    exp = Experiment(arch="qwen2-7b", strategy="overlap_local_sgd", workers=2, rounds=1)
    exp.fit()
    return exp, exp.consensus_plane(), exp.anchor_plane()


def test_swap_plane_applies_at_step_boundary_only(rng):
    """Tokens decoded before the swap boundary are identical to a no-swap
    run; the served plane object is unchanged until the next step() call."""
    exp, plane1, plane2 = _plane_pair()
    cfg = exp.model_cfg
    prompt = rng.integers(1, cfg.vocab_size, (6,)).astype(np.int32)

    def engine():
        e = BatchedEngine(cfg, plane1, slots=2, max_len=32, page_size=8)
        e.submit("x", prompt, 8)
        return e

    base = engine()
    base.run()
    swp = engine()
    for _ in range(4):  # chunked prefill + first decode steps on plane1
        swp.step()
    swp.swap_plane(plane2)
    assert swp.plane is plane1  # pending — never applied mid-stream
    pre_swap = list(next(a for a in swp.sched.active if a is not None).generated)
    np.testing.assert_array_equal(base.results["x"][: len(pre_swap)], pre_swap)
    res = swp.run()
    assert swp.plane is plane2  # zero-copy: the exact object is now served
    assert len(res["x"]) == 8


def test_swap_under_load_bitwise_equals_restart_on_new_plane(rng):
    """The acceptance pin: swap_plane on a live engine with in-flight
    requests must produce exactly the tokens a fresh engine on the new plane
    would produce when handed the same mid-flight state (pools, page tables,
    scheduler bookkeeping)."""
    exp, plane1, plane2 = _plane_pair()
    cfg = exp.model_cfg
    gen = np.random.default_rng(7)
    live = exp.serve(slots=2, max_len=32, page_size=8)
    for i in range(3):
        live.submit(f"r{i}", gen.integers(1, cfg.vocab_size, (5 + 3 * i,)).astype(np.int32), 6)
    for _ in range(5):
        live.step()
    # snapshot the in-flight state at the boundary, then swap
    control = exp.serve(slots=2, max_len=32, page_size=8)
    control.swap_plane(plane2)
    control.pools = live.pools  # device arrays are immutable — safe to share
    control.sched = copy.deepcopy(live.sched)
    control.results = {k: v.copy() for k, v in live.results.items()}
    live.swap_plane(plane2)
    res_live = live.run()
    res_ctrl = control.run()
    assert sorted(res_live) == sorted(res_ctrl)
    for rid in res_live:
        np.testing.assert_array_equal(res_live[rid], res_ctrl[rid], err_msg=rid)


def test_live_fit_anchor_plane_swap_is_zero_copy(rng):
    """Serving a training run's anchor: fit → serve → fit more → swap the
    fresh anchor in. anchor_plane() copies z out of the train state (the
    next round donates it); the swap into the engine makes no copy."""
    from repro.api import Experiment

    exp = Experiment(arch="qwen2-7b", strategy="overlap_local_sgd", workers=2, rounds=1)
    exp.fit()
    eng = exp.serve(slots=2, max_len=32, page_size=8)
    cfg = exp.model_cfg
    eng.submit("a", rng.integers(1, cfg.vocab_size, (6,)).astype(np.int32), 4)
    eng.step()
    exp.fit()  # the anchor advances under the live engine
    z = exp.anchor_plane()
    for a, b in zip(z.buffers, exp.state.vars.z.buffers):
        assert a is not b and a.unsafe_buffer_pointer() != b.unsafe_buffer_pointer()
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    eng.swap_plane(z)
    res = eng.run()
    assert len(res["a"]) == 4
    assert all(a is b for a, b in zip(eng.plane.buffers, z.buffers))  # no copy into the engine


def test_engine_serving_the_anchor_survives_a_later_fit(rng):
    """fit → swap the anchor into a live engine → fit again → decode. The
    round donates its input state; the engine's plane must not be among
    the donated buffers."""
    from repro.api import Experiment

    exp = Experiment(arch="qwen2-7b", strategy="overlap_local_sgd", workers=2, rounds=1)
    exp.fit()
    eng = exp.serve(slots=2, max_len=32, page_size=8)
    cfg = exp.model_cfg
    eng.submit("a", rng.integers(1, cfg.vocab_size, (6,)).astype(np.int32), 6)
    eng.step()
    eng.swap_plane(exp.anchor_plane())
    eng.step()  # the swap is applied here
    before = exp.state
    exp.fit()
    assert all(b.is_deleted() for b in before.x.buffers)  # the round did donate
    assert not any(b.is_deleted() for b in eng.plane.buffers)
    res = eng.run()
    assert len(res["a"]) == 6
