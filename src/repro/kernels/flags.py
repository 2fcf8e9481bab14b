"""Kernel dispatch flags.

Pallas kernels target TPU; on other backends they execute only in interpret
mode. Model code consults :func:`use_pallas` so the same model definition
runs (a) pure-jnp on CPU / in the dry-run lowering, (b) through the Pallas
kernels on a TPU or in interpret-mode kernel tests.
"""
from __future__ import annotations

import contextlib
import os
import threading

import jax


class _State(threading.local):
    def __init__(self):
        self.forced = None  # None = auto
        self.cost_unroll = False


_STATE = _State()


def cost_unroll() -> bool:
    """When True, chunked jnp recurrences unroll their scans so XLA's HLO
    cost analysis (which counts while-loop bodies once) sees the full FLOP /
    byte / collective count. Used only by the dry-run cost probes."""
    return _STATE.cost_unroll


@contextlib.contextmanager
def unrolled_costs(on: bool = True):
    prev = _STATE.cost_unroll
    _STATE.cost_unroll = on
    try:
        yield
    finally:
        _STATE.cost_unroll = prev


def use_pallas() -> bool:
    if _STATE.forced is not None:
        return _STATE.forced
    if os.environ.get("REPRO_FORCE_PALLAS") == "1":
        return True
    return jax.default_backend() == "tpu"


def interpret_mode() -> bool:
    """Whether pallas_call must run in interpret mode (non-TPU backend)."""
    return jax.default_backend() != "tpu"


@contextlib.contextmanager
def force_pallas(on: bool = True):
    prev = _STATE.forced
    _STATE.forced = on
    try:
        yield
    finally:
        _STATE.forced = prev
