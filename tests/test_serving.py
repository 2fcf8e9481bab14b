"""Serving correctness: prefill+decode must reproduce the full forward."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import get_arch
from repro.models import transformer as T
from repro.serving import BatchedEngine, decode_step, generate, prefill
from repro.serving.engine import _grow_all

# one representative per cache family: full-attn, SWA ring, MLA latent,
# recurrent SSM, hybrid, MoE, audio
CONSISTENCY_ARCHS = ["qwen2-7b", "h2o-danube-1.8b", "deepseek-v3-671b", "rwkv6-7b", "zamba2-1.2b"]


@pytest.mark.parametrize("arch_name", CONSISTENCY_ARCHS)
def test_decode_matches_prefill(arch_name, rng):
    cfg = get_arch(arch_name).model.reduced()
    params, _ = T.init_model(cfg, jax.random.PRNGKey(0))
    b, s = 2, 12
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (b, s)), jnp.int32)
    full_logits, _ = prefill(cfg, params, dict(tokens=toks))
    _, caches = prefill(cfg, params, dict(tokens=toks[:, : s - 1]))
    caches = _grow_all(caches, cfg, s)
    dec_logits, _ = decode_step(cfg, params, toks[:, s - 1 :], caches, jnp.asarray(s - 1, jnp.int32))
    a, b_ = np.asarray(full_logits[:, -1]), np.asarray(dec_logits[:, -1])
    err = np.abs(a - b_).max() / (np.abs(a).max() + 1e-9)
    assert err < 2e-3, (arch_name, err)


def test_multi_step_generation_consistent_with_teacher_forcing(rng):
    """Greedy generation then teacher-forced forward on the generated tokens
    must reproduce the same argmax chain."""
    cfg = get_arch("qwen2-7b").model.reduced()
    params, _ = T.init_model(cfg, jax.random.PRNGKey(0))
    prompt = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, 6)), jnp.int32)
    gen = generate(cfg, params, prompt, max_new=5)
    seq = jnp.concatenate([prompt, jnp.asarray(gen)], axis=1)
    logits, _ = T.apply_model(cfg, params, dict(tokens=seq), mode="train")
    for t in range(5):
        pred = int(jnp.argmax(logits[0, 5 + t]))
        assert pred == int(gen[0, t]), t


def test_sliding_window_ring_buffer_generation(rng):
    """Generate past the window: ring buffer must stay consistent with a
    teacher-forced forward (danube, window shrunk to 8)."""
    import dataclasses

    cfg = get_arch("h2o-danube-1.8b").model.reduced()
    cfg = dataclasses.replace(cfg, attention=dataclasses.replace(cfg.attention, sliding_window=8))
    params, _ = T.init_model(cfg, jax.random.PRNGKey(0))
    prompt = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, 6)), jnp.int32)
    gen = generate(cfg, params, prompt, max_new=10)  # crosses the window
    seq = jnp.concatenate([prompt, jnp.asarray(gen)], axis=1)
    logits, _ = T.apply_model(cfg, params, dict(tokens=seq), mode="train")
    for t in range(10):
        pred = int(jnp.argmax(logits[0, 5 + t]))
        assert pred == int(gen[0, t]), t


def test_batched_engine_serves_queue(rng):
    cfg = get_arch("qwen2-7b").model.reduced()
    params, _ = T.init_model(cfg, jax.random.PRNGKey(0))
    eng = BatchedEngine(cfg, params, slots=2)
    for i in range(5):
        eng.submit(f"req{i}", rng.integers(0, cfg.vocab_size, (4 + i,)).astype(np.int32), max_new=4)
    results = eng.run()
    assert set(results) == {f"req{i}" for i in range(5)}
    assert all(len(v) == 4 for v in results.values())


def test_audio_decode_shapes(rng):
    cfg = get_arch("musicgen-large").model.reduced()
    params, _ = T.init_model(cfg, jax.random.PRNGKey(0))
    k = cfg.frontend.num_codebooks
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, k, 8)), jnp.int32)
    logits, caches = prefill(cfg, params, dict(tokens=toks))
    assert logits.shape == (2, k, 8, cfg.vocab_size)
    caches = _grow_all(caches, cfg, 9)
    step_logits, _ = decode_step(cfg, params, toks[..., -1:], caches, jnp.asarray(8, jnp.int32))
    assert step_logits.shape == (2, k, 1, cfg.vocab_size)


@pytest.mark.parametrize("window", [None, 6])
def test_batched_engine_counts_decode_kv_pages(window, rng):
    """``decode_kv_pages`` counts, over every joint decode and every slot in
    it, the pages that hold a position the new token attends: its window's
    (or the whole prefix's) through its own append position."""
    import dataclasses

    cfg = get_arch("qwen2-7b").model.reduced()
    cfg = dataclasses.replace(cfg, attention=dataclasses.replace(cfg.attention, sliding_window=window))
    params, _ = T.init_model(cfg, jax.random.PRNGKey(0))
    page = 4
    eng = BatchedEngine(cfg, params, slots=2, max_len=32, page_size=page, chunk=8)
    prompts = {"a": (3, 6), "b": (8, 5), "c": (13, 2)}  # (prompt, max_new): pages cross and end mid-page
    for rid, (n, m) in prompts.items():
        eng.submit(rid, rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32), max_new=m)
    eng.run()

    def pages(pos):
        lo = 0 if window is None else max(pos - window + 1, 0)
        return len({k // page for k in range(lo, pos + 1)})

    # the first token comes from the prefill; decode i appends at n + i
    want = sum(pages(n + i) for n, m in prompts.values() for i in range(m - 1))
    assert eng.counters["decode_kv_pages"] == want
    assert eng.counters["decode_tokens"] == sum(m - 1 for _, m in prompts.values())
