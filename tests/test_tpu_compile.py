"""Main-path Pallas kernels compiled for a described TPU v5e (no chip needed).

Interpret-mode tests cannot see what the TPU compiler refuses: block shapes
that do not tile, matmuls that do not accumulate in f32, VMEM overruns. Each
test here compiles one kernel with ``interpret=False`` at h2o-danube-1.8b
widths (d_model 2560, 32 q / 8 kv heads, head_dim 80 padded to 128, d_ff
6912, sequence 2048, sliding window 4096) against a ``v5e:2x2`` topology and
checks the kernel survives as a ``tpu_custom_call``.

The kernels are called through ``kernel.py`` directly: ``ops.py`` routes by
the process's default backend, which is the CPU here. The topology is
described inside a fixture, never at import time: only one process may load
the TPU library, and the test workers all import this file.
"""
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.config import AlgoConfig, OptimizerConfig, get_arch
from repro.core import make_strategy
from repro.kernels import flags
from repro.kernels.anchor_mix import kernel as am_k
from repro.kernels.consensus_probe import kernel as probe_k
from repro.kernels.flash_attention import kernel as fa_k
from repro.kernels.opt_step import kernel as opt_k
from repro.kernels.paged_attn import kernel as pa_k
from repro.kernels.rmsnorm import kernel as rms_k
from repro.models import transformer as T
from repro.optim import from_config, schedules
from repro.training import make_round_step, make_train_state

D_MODEL, D_FF, HEADS, KV_HEADS, HEAD_DIM_PAD, SEQ, WINDOW = 2560, 6912, 32, 8, 128, 2048, 4096
WORKERS = 2
PLANE = D_MODEL * D_FF  # one MLP weight's share of a plane bucket (a 128 multiple)
SLOTS, PAGE, MAX_PAGES = 4, 16, 32


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    # compiles for a described chip cannot be read back from a persistent
    # cache without one: keep the cache out of these compiles
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        # a missing or broken TPU compiler fails every test here
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(fn, *args, **static):
    text = fn.lower(*args, **static).compile().as_text()
    assert 'custom_call_target="tpu_custom_call"' in text


def test_pullback_momentum_with_probe_compiles(one_chip):
    x = _sds((WORKERS, PLANE), jnp.bfloat16, one_chip)
    zv = _sds((PLANE,), jnp.bfloat16, one_chip)
    _assert_kernel(am_k.pullback_momentum_flat, x, zv, zv, alpha=0.6, beta=0.7, probe=True, interpret=False)


def test_sgd_step_compiles(one_chip):
    x = _sds((WORKERS, PLANE), jnp.bfloat16, one_chip)
    lr = _sds((1,), jnp.float32, one_chip)
    _assert_kernel(
        opt_k.sgd_step_flat, x, x, x, lr, momentum=0.9, nesterov=True, weight_decay=0.0, interpret=False
    )


def test_consensus_probe_compiles(one_chip):
    _assert_kernel(probe_k.probe_flat, _sds((WORKERS, PLANE), jnp.bfloat16, one_chip), interpret=False)


def test_rmsnorm_compiles(one_chip):
    x = _sds((SEQ, D_MODEL), jnp.bfloat16, one_chip)
    _assert_kernel(rms_k.rmsnorm_2d, x, _sds((D_MODEL,), jnp.bfloat16, one_chip), interpret=False)


def test_flash_attention_gqa_window_compiles(one_chip):
    q = _sds((HEADS, SEQ, HEAD_DIM_PAD), jnp.bfloat16, one_chip)
    kv = _sds((KV_HEADS, SEQ, HEAD_DIM_PAD), jnp.bfloat16, one_chip)
    _assert_kernel(
        fa_k.flash_attention_bhsd, q, kv, kv,
        num_kv_heads=KV_HEADS, causal=True, window=WINDOW, q_offset=0, sk_valid=SEQ, interpret=False,
    )


def _paged_operands(one_chip):
    pool = _sds((SLOTS * MAX_PAGES + 1, KV_HEADS, PAGE, HEAD_DIM_PAD), jnp.bfloat16, one_chip)
    pt = _sds((SLOTS, MAX_PAGES), jnp.int32, one_chip)
    lens = _sds((SLOTS,), jnp.int32, one_chip)
    return pool, pt, lens


def test_paged_attend_decode_compiles(one_chip):
    pool, pt, lens = _paged_operands(one_chip)
    group_pad = 8  # 32 / 8 = 4 q heads per kv head, padded to a sublane multiple
    q = _sds((SLOTS, KV_HEADS, group_pad, HEAD_DIM_PAD), jnp.bfloat16, one_chip)
    _assert_kernel(pa_k.paged_attend_decode, q, pool, pool, pt, lens, window=WINDOW, interpret=False)


def test_paged_append_decode_compiles(one_chip):
    pool, pt, lens = _paged_operands(one_chip)
    new = _sds((SLOTS, KV_HEADS, HEAD_DIM_PAD), jnp.bfloat16, one_chip)
    _assert_kernel(pa_k.paged_append_decode, pool, new, pt, lens, interpret=False)


def test_plane_round_step_compiles_with_kernels(one_chip, monkeypatch):
    """One whole Overlap-Local-SGD round — 2 workers stacked, plane-resident
    SGD, h2o-danube-1.8b widths at 1 layer — compiles for the chip with
    every main-path kernel in it: the kernels are differentiable and tile
    under the worker ``vmap``."""
    monkeypatch.setattr(flags, "interpret_mode", lambda: False)
    cfg = dataclasses.replace(get_arch("h2o-danube-1.8b").model, num_layers=1)
    strat = make_strategy(AlgoConfig(name="overlap_local_sgd", tau=2, alpha=0.6, anchor_beta=0.7))
    opt = from_config(OptimizerConfig(name="sgd", lr=1e-2, momentum=0.9, nesterov=True))
    params, axes = T.init_model(cfg, jax.random.PRNGKey(0), abstract=True)
    state = jax.eval_shape(lambda p: make_train_state(p, WORKERS, opt, strat, axes), params)
    state = jax.tree.map(lambda s: _sds(s.shape, s.dtype, one_chip), state)
    toks = _sds((2, WORKERS, 1, 512), jnp.int32, one_chip)
    step = make_round_step(lambda p, b: T.lm_loss(cfg, p, b), opt, strat, schedules.constant(1e-2), axes)
    with flags.force_pallas():
        text = jax.jit(step).lower(state, dict(tokens=toks, targets=toks)).compile().as_text()
    calls = "\n".join(line for line in text.splitlines() if "tpu_custom_call" in line)
    found = set(re.findall(r"jit\((\w+)\)\)*/pallas_call", calls))
    assert {"sgd_step_flat", "pullback_momentum_flat", "flash_attention_bhsd", "rmsnorm_2d"} <= found
