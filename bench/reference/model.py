"""A plain float32 decoder in jax.numpy: RMSNorm, rotary GQA attention
(causal, optionally windowed, optional QKV bias), SwiGLU, untied head.

It follows the published Llama/Mistral/Qwen2 equations and imports nothing
of the program under test. Parameter names follow the layout the benchmark
fills from the seed (``bench/weights.py``): "tok_emb", "final_norm/scale",
"head", and per layer "ln1/scale", "attn/wq|wk|wv|wo|bq|bk|bv", "ln2/scale",
"ffn/wi_gate|wi_up|wo". Run it under ``jax.default_matmul_precision
("highest")``: ``matmul`` below asks for it as well.

``matmul`` is swappable: ``fp8_matmul`` rounds both operands to float8
(e4m3, scaled per tensor) before the product, which is the control that
the comparison has to fail.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Optional

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class Cfg:
    num_layers: int
    d_model: int
    d_ff: int
    vocab_size: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    sliding_window: Optional[int]
    rope_theta: float
    qkv_bias: bool
    norm_eps: float

    @classmethod
    def from_file(cls, spec: dict) -> "Cfg":
        return cls(**{k: spec[k] for k in cls.__dataclass_fields__})


def matmul(a, b):
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def _fp8(t):
    scale = jnp.maximum(jnp.max(jnp.abs(t)), 1e-30) / 224.0
    return (t / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def fp8_matmul(a, b):
    return matmul(_fp8(a), _fp8(b))


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def rope(x, positions, theta):
    """Rotate-half rotary embedding. x: (B, S, H, D), positions: (S,)."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(q, k, v, window):
    """Causal softmax attention, query head h reading kv head h // (H / Hkv)."""
    b, s, h, d = q.shape
    g = h // k.shape[2]
    k = jnp.repeat(k, g, axis=2)
    v = jnp.repeat(v, g, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=jax.lax.Precision.HIGHEST)
    pos = jnp.arange(s)
    mask = pos[None, :] <= pos[:, None]
    if window is not None:
        mask &= pos[None, :] > pos[:, None] - window
    scores = jnp.where(mask[None, None], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=jax.lax.Precision.HIGHEST)


def block(p, x, cfg: Cfg, mm: Callable = matmul):
    """One decoder layer on x: (B, S, d_model); p maps names to f32 arrays."""
    b, s, _ = x.shape
    h, kv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    a = rmsnorm(x, p["ln1/scale"], cfg.norm_eps)
    q, k, v = mm(a, p["attn/wq"]), mm(a, p["attn/wk"]), mm(a, p["attn/wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["attn/bq"], k + p["attn/bk"], v + p["attn/bv"]
    pos = jnp.arange(s)
    q = rope(q.reshape(b, s, h, hd), pos, cfg.rope_theta) / jnp.sqrt(float(hd))
    k = rope(k.reshape(b, s, kv, hd), pos, cfg.rope_theta)
    o = attention(q, k, v.reshape(b, s, kv, hd), cfg.sliding_window)
    x = x + mm(o.reshape(b, s, h * hd), p["attn/wo"])
    f = rmsnorm(x, p["ln2/scale"], cfg.norm_eps)
    return x + mm(jax.nn.silu(mm(f, p["ffn/wi_gate"])) * mm(f, p["ffn/wi_up"]), p["ffn/wo"])


def layer_params(seg: dict, layer: int) -> dict:
    """Layer ``layer`` of a stacked segment as a flat name -> array dict."""
    out = {}
    for group, leaves in seg.items():
        for name, t in leaves.items():
            out[f"{group}/{name}"] = t[layer]
    return out


def hidden(params, tokens, cfg: Cfg, mm: Callable = matmul):
    """Final-norm hidden states of tokens (B, S) for a parameter tree in the
    benchmark's layout (layers stacked under "seg0")."""
    x = params["tok_emb"][tokens]
    step = jax.checkpoint(lambda p, x: block(p, x, cfg, mm))
    for layer in range(cfg.num_layers):
        x = step(layer_params(params["seg0"], layer), x)
    return rmsnorm(x, params["final_norm"]["scale"], cfg.norm_eps)


def loss(params, tokens, targets, cfg: Cfg, mm: Callable = matmul):
    """Mean next-token cross-entropy over every position of (B, S)."""
    logits = mm(hidden(params, tokens, cfg, mm), params["head"])
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - gold)
