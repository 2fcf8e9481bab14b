"""Fused RMSNorm Pallas TPU kernel.

One pass over the row: mean-of-squares reduction and scale in VMEM, saving
the extra HBM round-trip XLA's unfused reduce+mul pair would take. Rows are
tiled ``block_rows`` at a time; the feature dim stays whole in VMEM (d_model
≤ 12288 ⇒ ≤ 12288·4B·block_rows, well inside the ~16 MB VMEM budget for
block_rows ≤ 256).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _rmsnorm_kernel(x_ref, scale_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(ms + eps)
    o_ref[...] = (y * scale_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("eps", "block_rows", "interpret"))
def rmsnorm_2d(x, scale, *, eps: float = 1e-5, block_rows: int = 128, interpret: bool = False):
    """x: (rows, d) — callers flatten leading dims. scale: (d,).

    The scale rides as a (1, d) block: under ``vmap`` (worker-stacked
    training) a batched 1-D (d,) block would leave a squeezed batch row as
    its second-minor dim, which the TPU cannot tile."""
    rows, d = x.shape
    block_rows = min(block_rows, rows)
    grid = (pl.cdiv(rows, block_rows),)
    return pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, d), x.dtype),
        interpret=interpret,
    )(x, scale.reshape(1, d))
