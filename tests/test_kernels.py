"""Per-kernel correctness: Pallas (interpret mode) vs pure-jnp oracles,
swept over shapes and dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import flags
from repro.kernels.anchor_mix import ops as am_ops
from repro.kernels.anchor_mix import ref as am_ref
from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.flash_attention import ref as fa_ref
from repro.kernels.rmsnorm import ops as rms_ops
from repro.kernels.rmsnorm import ref as rms_ref
from repro.kernels.rwkv6_wkv import ops as wkv_ops
from repro.kernels.rwkv6_wkv import ref as wkv_ref
from repro.kernels.ssd_scan import ops as ssd_ops
from repro.kernels.ssd_scan import ref as ssd_ref


def tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(rtol=4e-4, atol=4e-4)


@pytest.mark.parametrize("rows,d", [(8, 64), (33, 128), (128, 300), (1, 2048)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_sweep(rng, rows, d, dtype):
    x = jnp.asarray(rng.normal(size=(rows, d)), dtype)
    s = jnp.asarray(rng.normal(size=(d,)), dtype)
    with flags.force_pallas():
        out = rms_ops.rmsnorm(x, s)
    ref = rms_ref.rmsnorm(x, s)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref, np.float32), **tol(dtype))


def test_rmsnorm_grads_match_reference(rng):
    """The kernel op carries its own VJP (a pallas_call has no reverse-mode
    rule), so training can differentiate through it — vmapped over workers
    as the round step does."""
    x = jnp.asarray(rng.normal(size=(2, 16, 128)), jnp.float32)
    s = jnp.asarray(rng.normal(size=(2, 128)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(16, 128)), jnp.float32)

    def loss(fn):
        return lambda xx, ss: jnp.sum(jax.vmap(fn)(xx, ss) * w)

    with flags.force_pallas():
        got = jax.grad(loss(rms_ops.rmsnorm), argnums=(0, 1))(x, s)
    want = jax.grad(loss(rms_ref.rmsnorm), argnums=(0, 1))(x, s)
    for g, r in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize(
    "b,sq,sk,h,hkv,d,causal,window",
    [
        (2, 64, 64, 4, 2, 32, True, None),
        (1, 130, 130, 4, 4, 64, True, None),  # non-multiple of block
        (2, 64, 64, 8, 2, 32, True, 16),  # sliding window
        (1, 64, 64, 2, 1, 32, False, None),  # bidirectional
        (2, 1, 96, 4, 2, 32, True, None),  # single query vs cache
    ],
)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(rng, b, sq, sk, h, hkv, d, causal, window, dtype):
    q = jnp.asarray(rng.normal(size=(b, sq, h, d)), dtype)
    k = jnp.asarray(rng.normal(size=(b, sk, hkv, d)), dtype)
    v = jnp.asarray(rng.normal(size=(b, sk, hkv, d)), dtype)
    q_off = sk - sq if sq < sk else 0
    out = fa_ops.flash_attention(q, k, v, causal, window, q_off)
    ref = fa_ref.mha_reference(q, k, v, causal=causal, window=window, q_offset=q_off)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref, np.float32), **tol(dtype))


@pytest.mark.parametrize("block_q,block_k", [(16, 16), (32, 64), (1024, 1024)])
def test_chunked_mha_blocks(rng, block_q, block_k):
    q = jnp.asarray(rng.normal(size=(2, 70, 4, 16)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, 70, 2, 16)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, 70, 2, 16)), jnp.float32)
    out = fa_ref.chunked_mha(q, k, v, block_q=block_q, block_k=block_k)
    ref = fa_ref.mha_reference(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=4e-4, atol=4e-4)


def test_flash_attention_grads_match_reference(rng):
    q = jnp.asarray(rng.normal(size=(1, 32, 4, 16)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 32, 2, 16)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 32, 2, 16)), jnp.float32)
    g1 = jax.grad(lambda *a: fa_ops.flash_attention(*a).sum(), argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(lambda *a: fa_ref.mha_reference(*a).sum(), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("b,s,h,p,g,n,chunk", [(2, 32, 4, 8, 2, 5, 8), (1, 37, 2, 16, 1, 8, 16), (2, 64, 4, 8, 4, 4, 64)])
def test_ssd_kernel_sweep(rng, b, s, h, p, g, n, chunk):
    x = jnp.asarray(rng.normal(size=(b, s, h, p)), jnp.float32)
    dt = jnp.asarray(np.abs(rng.normal(size=(b, s, h))) * 0.5, jnp.float32)
    A = jnp.asarray(-np.abs(rng.normal(size=(h,))), jnp.float32)
    B = jnp.asarray(rng.normal(size=(b, s, g, n)), jnp.float32)
    C = jnp.asarray(rng.normal(size=(b, s, g, n)), jnp.float32)
    D = jnp.asarray(rng.normal(size=(h,)), jnp.float32)
    y_ref, s_ref = ssd_ref.ssd_reference(x, dt, A, B, C, D)
    y_chunk, s_chunk = ssd_ref.ssd_chunked(x, dt, A, B, C, D, chunk=chunk)
    np.testing.assert_allclose(np.asarray(y_chunk), np.asarray(y_ref), rtol=5e-4, atol=5e-4)
    np.testing.assert_allclose(np.asarray(s_chunk), np.asarray(s_ref), rtol=5e-3, atol=5e-3)
    if s % chunk == 0:
        with flags.force_pallas():
            y_pal, s_pal = ssd_ops.ssd_scan(x, dt, A, B, C, D, chunk)
        np.testing.assert_allclose(np.asarray(y_pal), np.asarray(y_ref), rtol=5e-4, atol=5e-4)
        np.testing.assert_allclose(np.asarray(s_pal), np.asarray(s_ref), rtol=5e-3, atol=5e-3)


def test_ssd_decode_step_matches_reference(rng):
    b, s, h, p, g, n = 1, 9, 2, 4, 1, 3
    x = jnp.asarray(rng.normal(size=(b, s, h, p)), jnp.float32)
    dt = jnp.asarray(np.abs(rng.normal(size=(b, s, h))) * 0.5, jnp.float32)
    A = jnp.asarray(-np.abs(rng.normal(size=(h,))), jnp.float32)
    B = jnp.asarray(rng.normal(size=(b, s, g, n)), jnp.float32)
    C = jnp.asarray(rng.normal(size=(b, s, g, n)), jnp.float32)
    D = jnp.asarray(rng.normal(size=(h,)), jnp.float32)
    y_ref, _ = ssd_ref.ssd_reference(x, dt, A, B, C, D)
    state = jnp.zeros((b, h, p, n), jnp.float32)
    ys = []
    for t in range(s):
        y, state = ssd_ops.ssd_decode_step(state, x[:, t], dt[:, t], A, B[:, t], C[:, t], D)
        ys.append(y)
    np.testing.assert_allclose(np.stack(ys, 1), np.asarray(y_ref), rtol=5e-4, atol=5e-4)


@pytest.mark.parametrize("b,s,h,n,p,chunk", [(2, 24, 3, 8, 6, 8), (1, 45, 2, 16, 16, 16), (2, 32, 4, 8, 8, 32)])
def test_wkv_kernel_sweep(rng, b, s, h, n, p, chunk):
    r = jnp.asarray(rng.normal(size=(b, s, h, n)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, s, h, n)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, s, h, p)), jnp.float32)
    w = jnp.asarray(0.2 + 0.79 * rng.random(size=(b, s, h, n)), jnp.float32)
    u = jnp.asarray(rng.normal(size=(h, n)), jnp.float32)
    y_ref, s_ref = wkv_ref.wkv_reference(r, k, v, w, u)
    y_chunk, s_chunk = wkv_ref.wkv_chunked(r, k, v, w, u, chunk=chunk)
    np.testing.assert_allclose(np.asarray(y_chunk), np.asarray(y_ref), rtol=5e-4, atol=5e-4)
    np.testing.assert_allclose(np.asarray(s_chunk), np.asarray(s_ref), rtol=5e-3, atol=5e-3)
    if s % chunk == 0:
        with flags.force_pallas():
            y_pal, s_pal = wkv_ops.wkv(r, k, v, w, u, chunk)
        np.testing.assert_allclose(np.asarray(y_pal), np.asarray(y_ref), rtol=5e-4, atol=5e-4)


def test_wkv_decode_step_matches_reference(rng):
    b, s, h, n, p = 1, 7, 2, 4, 4
    r = jnp.asarray(rng.normal(size=(b, s, h, n)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, s, h, n)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, s, h, p)), jnp.float32)
    w = jnp.asarray(0.2 + 0.79 * rng.random(size=(b, s, h, n)), jnp.float32)
    u = jnp.asarray(rng.normal(size=(h, n)), jnp.float32)
    y_ref, _ = wkv_ref.wkv_reference(r, k, v, w, u)
    state = jnp.zeros((b, h, n, p), jnp.float32)
    ys = []
    for t in range(s):
        y, state = wkv_ops.wkv_decode_step(state, r[:, t], k[:, t], v[:, t], w[:, t], u)
        ys.append(y)
    np.testing.assert_allclose(np.stack(ys, 1), np.asarray(y_ref), rtol=5e-4, atol=5e-4)


@pytest.mark.parametrize("shape", [(8,), (13, 7), (3, 5, 9), (128, 128)])
@pytest.mark.parametrize("alpha", [0.0, 0.5, 0.6, 1.0])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_anchor_mix_sweep(rng, shape, alpha, dtype):
    x = jnp.asarray(rng.normal(size=shape), dtype)
    z = jnp.asarray(rng.normal(size=shape), dtype)
    with flags.force_pallas():
        out = am_ops.anchor_mix(x, z, alpha)
    ref = am_ref.anchor_mix(x, z, alpha)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref, np.float32), **tol(dtype))


def test_pullback_tree(rng):
    x = {"a": jnp.asarray(rng.normal(size=(4, 3)), jnp.float32), "b": jnp.asarray(rng.normal(size=(7,)), jnp.float32)}
    z = jax.tree.map(jnp.zeros_like, x)
    out = am_ops.pullback_tree(x, z, 0.25)
    for k in x:
        np.testing.assert_allclose(np.asarray(out[k]), 0.75 * np.asarray(x[k]), rtol=1e-6)


@pytest.mark.parametrize("m,n", [(2, 128), (4, 384), (3, 257), (8, 1)])
@pytest.mark.parametrize("mean_pre", [False, True])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_pullback_mean_fused_sweep(rng, m, n, mean_pre, dtype):
    """Fused pullback+mean kernel vs oracle, aligned and ragged planes."""
    from repro.kernels.anchor_mix import ops as ops_
    from repro.kernels.anchor_mix import ref as ref_

    x = jnp.asarray(rng.normal(size=(m, n)), dtype)
    z = jnp.asarray(rng.normal(size=(n,)), dtype)
    with flags.force_pallas():
        xn, mean = ops_.pullback_mean(x, z, 0.6, mean_pre=mean_pre)
    xn_r, mean_r = ref_.pullback_mean(x, z, 0.6, mean_pre=mean_pre)
    np.testing.assert_allclose(np.asarray(xn, np.float32), np.asarray(xn_r, np.float32), **tol(dtype))
    np.testing.assert_allclose(np.asarray(mean, np.float32), np.asarray(mean_r, np.float32), **tol(dtype))


@pytest.mark.parametrize("m,n", [(2, 256), (4, 130)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_pullback_mean_momentum_fused_sweep(rng, m, n, dtype):
    """Fused pullback+momentum kernel (eqs. 4,10,11 in one pass) vs oracle."""
    from repro.kernels.anchor_mix import ops as ops_
    from repro.kernels.anchor_mix import ref as ref_

    x = jnp.asarray(rng.normal(size=(m, n)), dtype)
    z = jnp.asarray(rng.normal(size=(n,)), dtype)
    v = jnp.asarray(rng.normal(size=(n,)), dtype)
    with flags.force_pallas():
        out = ops_.pullback_mean_momentum(x, z, v, 0.6, 0.7)
    ref_out = ref_.pullback_mean_momentum(x, z, v, 0.6, 0.7)
    for a, b in zip(out, ref_out):
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b, np.float32), **tol(dtype))


def test_anchor_mix_aligned_skips_pad(rng):
    """n % 128 == 0 must not pay the pad+slice round-trip: the traced
    program contains no pad primitive (and stays correct)."""
    x = jnp.asarray(rng.normal(size=(4, 128)), jnp.float32)
    z = jnp.asarray(rng.normal(size=(4, 128)), jnp.float32)
    with flags.force_pallas():
        jaxpr = jax.make_jaxpr(lambda a, b: am_ops.anchor_mix(a, b, 0.5))(x, z)
        out = am_ops.anchor_mix(x, z, 0.5)
    assert "pad" not in [e.primitive.name for e in jaxpr.jaxpr.eqns]
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(am_ref.anchor_mix(x, z, 0.5)), rtol=4e-4, atol=4e-4
    )
    # ragged sizes still pad (and still match the oracle)
    xr = jnp.asarray(rng.normal(size=(7, 13)), jnp.float32)
    zr = jnp.asarray(rng.normal(size=(7, 13)), jnp.float32)
    with flags.force_pallas():
        out_r = am_ops.anchor_mix(xr, zr, 0.5)
    np.testing.assert_allclose(
        np.asarray(out_r), np.asarray(am_ref.anchor_mix(xr, zr, 0.5)), rtol=4e-4, atol=4e-4
    )
