"""Differential paged-vs-dense harness (DESIGN.md §10).

Pins the paged-KV decode path bitwise against the dense-cache oracle: the
same model, same params, same token sequence decoded through (a) the dense
``decode_step`` over a manually built full-length cache and (b) ``paged_step``
over the page pool must produce *identical* f32 logits at every position —
across cache families (full GQA, sliding-window GQA, MLA latent, parallel
block), ragged batch lengths, block-boundary-straddling positions, and
sequence lengths that are not a multiple of the page size.

The dense oracle always uses a full-length cache (slot i = position i) even
for sliding-window archs: the window is enforced by masking, like the paged
path, so the softmax accumulates in the same position order — the ring
buffer's reordering would change summation order and break bitwise equality
while still being numerically correct.

Also here: interpret-mode Pallas-kernel parity with the jnp reference, and a
jaxpr budget asserting the decode path performs zero full-cache copies
(no `_grow_all`-style pad/concatenate growth).
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from repro.config import get_arch
from repro.kernels import flags
from repro.kernels.paged_attn import kernel as pa_kernel
from repro.kernels.paged_attn import ref as pa_ref
from repro.models import transformer as T
from repro.serving import paged_step
from repro.serving.engine import decode_step
from repro.serving.paged_cache import init_paged_pools, paged_supported

PAGED_ARCHS = ["qwen2-7b", "h2o-danube-1.8b", "deepseek-v3-671b", "command-r-35b"]


def _cfg(arch_name, dtype="float32", window=None):
    cfg = dataclasses.replace(get_arch(arch_name).model.reduced(), dtype=dtype)
    if window is not None:
        cfg = dataclasses.replace(cfg, attention=dataclasses.replace(cfg.attention, sliding_window=window))
    return cfg


def _dense_empty_caches(cfg, batch: int, length: int):
    """Empty full-length caches, slot i ↔ position i — the bitwise oracle."""
    a = cfg.attention
    caches = {}
    for si, (kind, n) in enumerate(T.segments(cfg)):
        if a.kind == "mla":
            one = dict(
                ckv=jnp.zeros((batch, length, a.kv_lora_rank), cfg.param_dtype),
                krope=jnp.zeros((batch, length, a.qk_rope_head_dim), cfg.param_dtype),
                pos=jnp.asarray(0, jnp.int32),
            )
        else:
            one = dict(
                k=jnp.zeros((batch, length, a.num_kv_heads, a.head_dim), cfg.param_dtype),
                v=jnp.zeros((batch, length, a.num_kv_heads, a.head_dim), cfg.param_dtype),
                positions=jnp.full((length,), -1, jnp.int32),
                pos=jnp.asarray(0, jnp.int32),
            )
        caches[f"seg{si}"] = jax.tree.map(lambda t: jnp.broadcast_to(t[None], (n,) + t.shape), one)
    return caches


def _paged_setup(cfg, batch: int, maxp: int, page_size: int):
    """Pools + a page table giving every slot ``maxp`` pre-assigned pages."""
    pools = init_paged_pools(cfg, batch * maxp + 1, page_size)
    pt = np.arange(1, batch * maxp + 1, dtype=np.int32).reshape(batch, maxp)
    return pools, jnp.asarray(pt)


def _teacher_forced(cfg, params, toks, page_size=4):
    """Decode ``toks`` token-by-token through both paths; returns the stacked
    (steps, B, V) logits of each."""
    b, seq = toks.shape
    maxp = -(-seq // page_size)
    length = maxp * page_size
    dcaches = _dense_empty_caches(cfg, b, length)
    pools, pt = _paged_setup(cfg, b, maxp, page_size)
    dense_fn = jax.jit(functools.partial(decode_step, cfg))
    paged_fn = jax.jit(functools.partial(paged_step, cfg))
    out_d, out_p = [], []
    for t in range(seq):
        tok = toks[:, t : t + 1]
        ld, dcaches = dense_fn(params, tok, dcaches, jnp.asarray(t, jnp.int32))
        lp, pools = paged_fn(params, tok, pools, pt, jnp.full((b,), t, jnp.int32))
        out_d.append(np.asarray(ld[:, 0]))
        out_p.append(np.asarray(lp[:, 0]))
    return np.stack(out_d), np.stack(out_p)


@pytest.mark.parametrize("arch_name", PAGED_ARCHS)
def test_paged_decode_bitwise_equals_dense_oracle(arch_name, rng):
    """f32: every logit at every position identical — page-size 4 with seq 13
    crosses three page boundaries and leaves the last page partial."""
    window = 8 if arch_name == "h2o-danube-1.8b" else None
    cfg = _cfg(arch_name, window=window)
    assert paged_supported(cfg)
    params, _ = T.init_model(cfg, jax.random.PRNGKey(0))
    toks = jnp.asarray(rng.integers(1, cfg.vocab_size, (2, 13)), jnp.int32)
    dense, paged = _teacher_forced(cfg, params, toks, page_size=4)
    assert dense.dtype == np.float32
    np.testing.assert_array_equal(dense, paged)


def test_paged_decode_bf16_storage_within_ulps(rng):
    """bf16 param/pool storage: both paths cast the same stored values to f32
    before the softmax, so they stay bitwise-equal there too."""
    cfg = _cfg("qwen2-7b", dtype="bfloat16")
    params, _ = T.init_model(cfg, jax.random.PRNGKey(0))
    toks = jnp.asarray(rng.integers(1, cfg.vocab_size, (1, 9)), jnp.int32)
    dense, paged = _teacher_forced(cfg, params, toks, page_size=4)
    # few-ulp budget at bf16 scale (eps = 2^-8), bitwise in practice
    tol = np.abs(dense).max() * 2.0**-8 * 2
    assert np.abs(dense - paged).max() <= tol


def test_chunked_prefill_matches_dense_token_by_token(rng):
    """A T>1 chunk through paged_step (in-chunk causal mask) matches T=1
    teacher-forced decode per position. Near-equality, not bitwise: XLA tiles
    the projection matmuls differently for (1,6,d) vs (1,1,d) operands, so
    the inputs to attention already differ in the last float32 ulps — the
    bitwise contract applies to the decode path, where shapes coincide.

    What must hold exactly: the pool left behind by the chunk and by
    token-by-token appends holds the same pages (same K/V bytes modulo that
    matmul jitter), checked via the follow-up decode below."""
    cfg = _cfg("qwen2-7b")
    params, _ = T.init_model(cfg, jax.random.PRNGKey(0))
    toks = jnp.asarray(rng.integers(1, cfg.vocab_size, (1, 6)), jnp.int32)
    page_size, maxp = 4, 2
    pools, pt = _paged_setup(cfg, 1, maxp, page_size)
    chunk_logits, pools = paged_step(cfg, params, toks, pools, pt, jnp.zeros((1,), jnp.int32))
    dense, _ = _teacher_forced(cfg, params, toks, page_size=page_size)
    np.testing.assert_allclose(np.asarray(chunk_logits)[0], dense[:, 0], atol=1e-5, rtol=1e-4)
    # decoding one more token from the chunk-filled pool agrees with the
    # dense continuation to the same tolerance
    nxt = jnp.asarray([[7]], jnp.int32)
    lp, _ = paged_step(cfg, params, nxt, pools, pt, jnp.asarray([6], jnp.int32))
    dcaches = _dense_empty_caches(cfg, 1, maxp * page_size)
    fn = jax.jit(functools.partial(decode_step, cfg))
    for t in range(6):
        _, dcaches = fn(params, toks[:, t : t + 1], dcaches, jnp.asarray(t, jnp.int32))
    ld, _ = fn(params, nxt, dcaches, jnp.asarray(6, jnp.int32))
    np.testing.assert_allclose(np.asarray(lp[0, 0]), np.asarray(ld[0, 0]), atol=1e-5, rtol=1e-4)


def test_ragged_joint_decode_bitwise_per_row(rng):
    """Three slots at different lengths (mid-page, boundary-adjacent,
    end-of-page) decoded in ONE joint paged step.

    Bitwise claim: a row's logits depend only on its own pages, length, and
    token — replacing every *other* row with an idle trash row (token 0,
    zero page table, length 0) leaves it bit-identical, which is exactly the
    continuous-batching invariant (co-batched neighbours can't perturb a
    request). Against the per-row dense oracle the comparison is
    tight-tolerance only, because a (3,1,d) and a (1,1,d) projection matmul
    tile differently in XLA — the bitwise oracle equality is pinned at
    matching batch shapes by the tests above."""
    cfg = _cfg("qwen2-7b")
    params, _ = T.init_model(cfg, jax.random.PRNGKey(0))
    page_size, maxp = 4, 4
    length = maxp * page_size
    lens = [3, 7, 12]
    b = len(lens)
    pools, pt = _paged_setup(cfg, b, maxp, page_size)
    prompts = [jnp.asarray(rng.integers(1, cfg.vocab_size, (1, L)), jnp.int32) for L in lens]
    fn = jax.jit(functools.partial(paged_step, cfg))
    for i, p in enumerate(prompts):  # fill each slot token-by-token
        for t in range(lens[i]):
            _, pools = fn(params, p[:, t : t + 1], pools, pt[i : i + 1], jnp.asarray([t], jnp.int32))
    nxt = jnp.asarray(rng.integers(1, cfg.vocab_size, (b, 1)), jnp.int32)
    joint, _ = fn(params, nxt, pools, pt, jnp.asarray(lens, jnp.int32))
    for i, (p, L) in enumerate(zip(prompts, lens)):
        # (a) bitwise: same step with every other row idled to the trash page
        solo_toks = np.zeros((b, 1), np.int32)
        solo_toks[i] = np.asarray(nxt[i])
        solo_pt = np.zeros_like(np.asarray(pt))
        solo_pt[i] = np.asarray(pt[i])
        solo_lens = np.zeros((b,), np.int32)
        solo_lens[i] = L
        solo, _ = fn(params, jnp.asarray(solo_toks), pools, jnp.asarray(solo_pt), jnp.asarray(solo_lens))
        np.testing.assert_array_equal(np.asarray(solo[i, 0]), np.asarray(joint[i, 0]), err_msg=f"row {i}")
        # (b) numeric anchor: per-row dense oracle at B=1
        dcaches = _dense_empty_caches(cfg, 1, length)
        dfn = jax.jit(functools.partial(decode_step, cfg))
        for t in range(L):
            _, dcaches = dfn(params, p[:, t : t + 1], dcaches, jnp.asarray(t, jnp.int32))
        ld, _ = dfn(params, nxt[i : i + 1], dcaches, jnp.asarray(L, jnp.int32))
        np.testing.assert_allclose(
            np.asarray(ld[0, 0]), np.asarray(joint[i, 0]), atol=1e-5, rtol=1e-4, err_msg=f"row {i}"
        )


# -- kernel parity (interpret mode) -----------------------------------------


def test_append_kernel_interpret_parity(rng):
    pool = jnp.asarray(rng.normal(size=(9, 2, 8, 16)), jnp.float32)  # (P, KV, page 8, D)
    new = jnp.asarray(rng.normal(size=(3, 2, 16)), jnp.float32)
    pt = jnp.asarray(rng.permutation(8)[:6].reshape(3, 2) + 1, jnp.int32)
    lens = jnp.asarray([0, 5, 13], jnp.int32)
    want = pa_ref.paged_append(pool, new[:, None], pt, lens)
    got = pa_kernel.paged_append_decode(
        jnp.pad(pool, ((0, 0), (0, 0), (0, 0), (0, 112))),
        jnp.pad(new, ((0, 0), (0, 0), (0, 112))),
        pt, lens, interpret=True,
    )[..., :16]
    np.testing.assert_array_equal(np.asarray(want), np.asarray(got))


def _dead_pages(pt, lens, page, window):
    """The pages the tables name that no slot's new token can see: past its
    length, or before its window."""
    named, live = set(), set()
    for row, n in zip(np.asarray(pt).tolist(), np.asarray(lens).tolist()):
        lo = 0 if window is None else max(n - window + 1, 0) // page
        named |= set(row)
        live |= set(row[lo : n // page + 1])
    return sorted(named - live)


@pytest.mark.parametrize("window,poisoned", [
    pytest.param(None, False, id="None"),
    pytest.param(10, False, id="10"),
    pytest.param(None, True, id="None-nan"),
    pytest.param(10, True, id="10-nan"),
])
def test_attend_kernel_interpret_parity(window, poisoned, rng):
    """The kernel against the f32 reference. Page 8 gives blocks of 64 pages
    (512 tokens); 150 pages are two whole blocks and a part. The lengths are
    an idle slot (0, the trash row), short ones, the last token of block 0
    (511), the first of block 1 (512), one past it (513) and one in the last
    block. Poisoned, every page a table names but its slot's new token
    cannot see is NaN in the kernel's pool (the reference reads a clean
    one): the kernel never reads a skipped page. The TPU interpreter runs
    the page copies and their semaphores, with scratch memory NaN until
    written."""
    s, kv, g, d, page, maxp = 7, 2, 4, 16, 8, 150
    assert pa_kernel.pages_per_block(page, maxp) == 64
    pool_k = jnp.asarray(rng.normal(size=(s * maxp + 1, kv, page, d)), jnp.float32)
    pool_v = jnp.asarray(rng.normal(size=pool_k.shape), jnp.float32)
    pt = jnp.arange(1, s * maxp + 1, dtype=jnp.int32).reshape(s, maxp).at[0].set(0)
    lens = jnp.asarray([0, 2, 11, 511, 512, 513, 1100], jnp.int32)
    q = jnp.asarray(rng.normal(size=(s, 1, kv * g, d)), jnp.float32)
    want = pa_ref.paged_attend_gqa(q, pool_k, pool_v, pt, lens, window=window)
    if poisoned:
        dead = jnp.asarray(_dead_pages(pt, lens, page, window))
        assert 0 not in dead.tolist() and dead.size > 500  # the idle slot's trash page is live
        pool_k, pool_v = pool_k.at[dead].set(jnp.nan), pool_v.at[dead].set(jnp.nan)
    pad = ((0, 0), (0, 0), (0, 0), (0, 112))
    qk = jnp.pad(q.reshape(s, kv, g, d), ((0, 0), (0, 0), (0, 4), (0, 112)))
    got = pa_kernel.paged_attend_decode(
        qk, jnp.pad(pool_k, pad), jnp.pad(pool_v, pad), pt, lens, window=window,
        interpret=pltpu.InterpretParams(detect_races=True),
    )[:, :, :g, :d].reshape(s, 1, kv * g, d)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(want), np.asarray(got), atol=2e-6, rtol=2e-6)


def test_ops_route_to_kernel_when_forced(rng):
    """force_pallas() exercises the dispatch layer end-to-end in interpret
    mode on CPU: results must agree with the reference within kernel tolerance."""
    from repro.kernels.paged_attn import ops

    pool = jnp.asarray(rng.normal(size=(7, 2, 8, 16)), jnp.float32)  # (P, KV, page, D)
    new = jnp.asarray(rng.normal(size=(2, 1, 2, 16)), jnp.float32)
    pt = jnp.asarray([[1, 2, 3], [4, 5, 6]], jnp.int32)
    lens = jnp.asarray([4, 17], jnp.int32)
    q = jnp.asarray(rng.normal(size=(2, 1, 8, 16)), jnp.float32)
    ref_pool = pa_ref.paged_append(pool, new, pt, lens)
    ref_out = pa_ref.paged_attend_gqa(q, ref_pool, ref_pool, pt, lens, window=None)
    with flags.force_pallas():
        assert flags.use_pallas() and flags.interpret_mode()
        k_pool = ops.paged_append(pool, new, pt, lens)
        k_out = ops.paged_attend_gqa(q, k_pool, k_pool, pt, lens, window=None)
    np.testing.assert_array_equal(np.asarray(ref_pool), np.asarray(k_pool))
    np.testing.assert_allclose(np.asarray(ref_out), np.asarray(k_out), atol=2e-6, rtol=2e-6)


# -- structural: no full-cache copies on the decode path ---------------------


def _walk_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            if hasattr(v, "jaxpr"):  # closed sub-jaxpr (scan/cond/jit bodies)
                yield from _walk_eqns(v.jaxpr)
            elif isinstance(v, (list, tuple)):
                for u in v:
                    if hasattr(u, "jaxpr"):
                        yield from _walk_eqns(u.jaxpr)


def test_paged_decode_jaxpr_has_no_cache_growth(rng):
    """The structural pin behind the perf claim: the paged decode program
    contains no pad/concatenate producing a cache-sized array — appends are
    O(tokens) scatters, unlike the `_grow_all` pad-chain it replaces."""
    cfg = _cfg("qwen2-7b")
    params, _ = T.init_model(cfg, jax.random.PRNGKey(0))
    pools, pt = _paged_setup(cfg, 2, 4, 8)
    toks = jnp.zeros((2, 1), jnp.int32)
    lens = jnp.zeros((2,), jnp.int32)
    pool_leaf_bytes = min(l.size * l.dtype.itemsize for l in jax.tree.leaves(pools))
    jaxpr = jax.make_jaxpr(functools.partial(paged_step, cfg))(params, toks, pools, pt, lens)
    grow = [
        e
        for e in _walk_eqns(jaxpr.jaxpr)
        if e.primitive.name in ("pad", "concatenate")
        and any(o.aval.size * o.aval.dtype.itemsize >= pool_leaf_bytes for o in e.outvars)
    ]
    assert not grow, f"cache-sized {[e.primitive.name for e in grow]} on the paged decode path"
    # and the appends are there: scatter into the pool
    assert any(e.primitive.name.startswith("scatter") for e in _walk_eqns(jaxpr.jaxpr))


# -- reference-op unit coverage ---------------------------------------------


def test_paged_gather_reconstructs_position_order(rng):
    pool = jnp.asarray(rng.normal(size=(5, 4, 3)), jnp.float32)
    pt = jnp.asarray([[2, 4], [1, 3]], jnp.int32)
    g = pa_ref.paged_gather(pool, pt)
    assert g.shape == (2, 8, 3)
    np.testing.assert_array_equal(np.asarray(g[0, :4]), np.asarray(pool[2]))
    np.testing.assert_array_equal(np.asarray(g[1, 4:]), np.asarray(pool[3]))


def test_paged_gather_kv_matches_dense_layout(rng):
    """A kv-head-major GQA pool gathers into the dense (S, L, KV, D) cache."""
    pool = jnp.asarray(rng.normal(size=(5, 2, 4, 3)), jnp.float32)  # (P, KV, page, D)
    pt = jnp.asarray([[2, 4], [1, 3]], jnp.int32)
    g = pa_ref.paged_gather_kv(pool, pt)
    assert g.shape == (2, 8, 2, 3)
    np.testing.assert_array_equal(np.asarray(g[0, :4]), np.asarray(pool[2]).transpose(1, 0, 2))
    np.testing.assert_array_equal(np.asarray(g[1, 4:]), np.asarray(pool[3]).transpose(1, 0, 2))


def test_pallas_path_refuses_untileable_page_size(rng):
    """The kernels' page block must tile on the TPU: page_size 4 is an error
    on the Pallas path (no silent jnp fallback), and fine on the reference."""
    from repro.kernels.paged_attn import ops

    pool = jnp.zeros((5, 2, 4, 16), jnp.float32)
    new = jnp.asarray(rng.normal(size=(2, 1, 2, 16)), jnp.float32)
    pt = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    lens = jnp.asarray([1, 5], jnp.int32)
    ops.paged_append(pool, new, pt, lens)
    with flags.force_pallas(), pytest.raises(ValueError, match="page_size % 8"):
        ops.paged_append(pool, new, pt, lens)


def test_append_targets_clamps_past_table_end():
    pt = jnp.asarray([[3, 7]], jnp.int32)
    page_ids, offsets = pa_ref.append_targets(pt, jnp.asarray([6], jnp.int32), 4, 4)
    # positions 6..9: page 1 (slots 2,3), then clamped to last page (slots 0,1)
    np.testing.assert_array_equal(np.asarray(page_ids[0]), [7, 7, 7, 7])
    np.testing.assert_array_equal(np.asarray(offsets[0]), [2, 3, 0, 1])
