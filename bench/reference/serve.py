"""The plain reference of greedy serving: one full forward pass over a
prompt and the tokens served for it, with each layer's weights drawn from
the seed inside the pass, so that no more than one layer is held at once.

For each served token it gives the gap by which that token's logit lies
below the reference's best at its position: 0 where the server chose the
reference's own first token, small where two tokens were all but tied.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights
from bench.reference import model as ref

LAYER_LEAVES = ("ln1/scale", "attn/wq", "attn/wk", "attn/wv", "attn/wo", "ln2/scale", "ffn/wi_gate", "ffn/wi_up", "ffn/wo")
BIASES = ("attn/bq", "attn/bk", "attn/bv")


def layer_shapes(cfg: ref.Cfg) -> dict:
    d, f = cfg.d_model, cfg.d_ff
    q, kv = cfg.num_heads * cfg.head_dim, cfg.num_kv_heads * cfg.head_dim
    shapes = {"ln1/scale": (d,), "attn/wq": (d, q), "attn/wk": (d, kv), "attn/wv": (d, kv), "attn/wo": (q, d),
              "ln2/scale": (d,), "ffn/wi_gate": (d, f), "ffn/wi_up": (d, f), "ffn/wo": (f, d)}
    if cfg.qkv_bias:
        shapes.update({"attn/bq": (q,), "attn/bk": (kv,), "attn/bv": (kv,)})
    return shapes


def _leaf(key, path, shape, dtype):
    return weights.leaf(key, path, shape, dtype).astype(jnp.float32)


def logits(key, tokens, cfg: ref.Cfg, dtype, mm: Callable = ref.matmul):
    """Logits (S, V) of one sequence of tokens (S,), weights drawn from
    ``key`` in the configuration's dtype and computed on in float32."""
    shapes = layer_shapes(cfg)
    x = _leaf(key, "tok_emb", (cfg.vocab_size, cfg.d_model), dtype)[tokens][None]

    def body(x, layer):
        p = {k: weights.layer_leaf(key, f"seg0/{k}", s, dtype, layer).astype(jnp.float32) for k, s in shapes.items()}
        return ref.block(p, x, cfg, mm), None

    x, _ = jax.lax.scan(body, x, jnp.arange(cfg.num_layers))
    h = ref.rmsnorm(x[0], _leaf(key, "final_norm/scale", (cfg.d_model,), dtype), cfg.norm_eps)
    return mm(h, _leaf(key, "head", (cfg.d_model, cfg.vocab_size), dtype))


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _gaps(key, tokens, targets, cfg, dtype, control):
    with jax.default_matmul_precision("highest"):
        lg = logits(key, tokens, cfg, dtype)
        if control:
            targets = jnp.argmax(logits(key, tokens, cfg, dtype, ref.fp8_matmul), axis=-1)
        return jnp.max(lg, axis=-1) - jnp.take_along_axis(lg, targets[:, None], axis=-1)[:, 0]


def served_gaps(key, cfg: ref.Cfg, dtype, prompt, served, pad_to: int, control: bool = False) -> np.ndarray:
    """The gap of every served token; with ``control``, the gap of the
    token that the float8 control puts first at the same positions."""
    seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
    n = len(seq)
    tokens = np.zeros(pad_to, np.int32)
    tokens[:n] = seq
    targets = np.zeros(pad_to, np.int32)
    targets[len(prompt) - 1:n] = served
    out = np.asarray(_gaps(key, jnp.asarray(tokens), jnp.asarray(targets), cfg, jnp.dtype(dtype), control))
    return out[len(prompt) - 1:n]
