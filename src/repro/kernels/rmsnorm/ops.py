"""Jit'd public wrapper for the RMSNorm kernel (arbitrary leading dims).

The forward runs the Pallas kernel; the backward is the VJP of the jnp
reference, recomputed from the saved input (a ``pallas_call`` has no
reverse-mode rule of its own, so without this the training path cannot
differentiate through the kernel).
"""
from __future__ import annotations

import functools

import jax

from repro.kernels import flags
from repro.kernels.rmsnorm import kernel as _k
from repro.kernels.rmsnorm import ref as _ref


def _forward(x, scale, eps: float, block_rows: int):
    d = x.shape[-1]
    lead = x.shape[:-1]
    rows = 1
    for s in lead:
        rows *= s
    if rows == 0:
        return x
    x2 = x.reshape(rows, d)
    out = _k.rmsnorm_2d(x2, scale, eps=eps, block_rows=block_rows, interpret=flags.interpret_mode())
    return out.reshape(*lead, d)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def rmsnorm(x, scale, eps: float = 1e-5, block_rows: int = 128):
    return _forward(x, scale, eps, block_rows)


def _fwd(x, scale, eps, block_rows):
    return _forward(x, scale, eps, block_rows), (x, scale)


def _bwd(eps, block_rows, res, g):
    x, scale = res
    _, vjp = jax.vjp(lambda xx, ss: _ref.rmsnorm(xx, ss, eps), x, scale)
    return vjp(g)


rmsnorm.defvjp(_fwd, _bwd)

reference = _ref.rmsnorm
