"""Weights and token rows made from the run's seed.

Element ``e`` of a leaf (its flat index, layers first for a stacked run of
layers) takes a value that depends on the seed, the leaf's path
("seg0/attn/wq") and ``e`` alone: a counter-based hash gives two uniforms
and Box-Muller a normal. So the program's stacked leaves, a flat plane
written in one elementwise pass, and a reference that draws one layer at a
time all get the same numbers, and none takes anything from another.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np

LANE = 128  # the plane's leaves start on 128-element boundaries


def seed_key(seed: int) -> jnp.ndarray:
    """Two 32-bit words from any whole number, as a device array, so that
    no program folds them in as constants."""
    return jnp.asarray(np.random.SeedSequence(abs(int(seed))).generate_state(2, dtype=np.uint32))


def seed_rng(seed: int, stream: int = 0) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([abs(int(seed)), stream]))


def _mix(x):
    """A bijective 32-bit integer hash (lowbias32)."""
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    return x ^ (x >> 16)


def normal(key, pid, e):
    """A standard normal for each element index ``e`` of the leaf ``pid``."""
    h1 = _mix(e.astype(jnp.uint32) ^ _mix(key[0] ^ pid))
    h2 = _mix(h1 ^ key[1])
    u1 = ((h1 >> 8).astype(jnp.float32) + 0.5) * (2.0**-24)
    u2 = ((h2 >> 8).astype(jnp.float32) + 0.5) * (2.0**-24)
    return jnp.sqrt(-2.0 * jnp.log(u1)) * jnp.cos((2.0 * np.pi) * u2)


def stacked(path: str) -> bool:
    """Leaves under a ``seg<i>`` run carry a leading layer axis."""
    return path.split("/", 1)[0].startswith("seg")


def rule(path: str, shape) -> tuple:
    """(path id, offset, scale) of a leaf: value = offset + scale * normal.
    ``shape`` is one layer's shape for a stacked leaf."""
    name = path.rsplit("/", 1)[-1]
    pid = zlib.crc32(path.encode())
    if name == "scale":  # norm gains
        return pid, 1.0, 0.05
    if name == "tok_emb":
        return pid, 0.0, 0.02
    if len(shape) == 1:  # biases
        return pid, 0.0, 0.02
    return pid, 0.0, float(1.0 / np.sqrt(shape[0]))


def leaf(key, path: str, shape, dtype):
    """A whole leaf of ``shape`` (layers first, where stacked)."""
    pid, off, scale = rule(path, shape[1:] if stacked(path) else shape)
    e = jnp.arange(int(np.prod(shape)), dtype=jnp.uint32)
    return (off + scale * normal(key, jnp.uint32(pid), e)).reshape(shape).astype(dtype)


def layer_leaf(key, path: str, shape, dtype, layer: int):
    """Layer ``layer`` (an int, or a traced one) of a stacked leaf whose
    per-layer shape is ``shape``."""
    pid, off, scale = rule(path, shape)
    size = int(np.prod(shape))
    e = jnp.arange(size, dtype=jnp.uint32) + jnp.asarray(layer, jnp.uint32) * jnp.uint32(size)
    return (off + scale * normal(key, jnp.uint32(pid), e)).reshape(shape).astype(dtype)


def path_str(path) -> str:
    return "/".join(str(getattr(p, "key", p)) for p in path)


def make_tree(key, shapes):
    """A pytree of ``shapes`` (ShapeDtypeStructs) filled from ``key``; call
    under ``jit``."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    return jax.tree_util.tree_unflatten(treedef, [leaf(key, path_str(p), s.shape, s.dtype) for p, s in flat])


def make_plane_buffers(key, shapes, layout):
    """The flat buffers of ``layout`` (the program's plane layout of
    ``shapes``) in one elementwise pass per bucket: row r of 128 lanes lies
    in one leaf, whose rule a small table gives. Zeros in the padding."""
    flat = jax.tree_util.tree_flatten_with_path(shapes)[0]
    bufs = []
    for b, (n, dtype) in enumerate(zip(layout.bucket_sizes, layout.bucket_dtypes)):
        slots = [s for s in layout.slots if s.bucket == b]
        rules = []
        for s in slots:
            path = path_str(flat[s.index][0])
            rules.append(rule(path, s.shape[1:] if stacked(path) else s.shape))
        starts = jnp.asarray([s.offset // LANE for s in slots], jnp.int32)
        pid = jnp.asarray([r[0] for r in rules], jnp.uint32)
        off = jnp.asarray([r[1] for r in rules], jnp.float32)
        scale = jnp.asarray([r[2] for r in rules], jnp.float32)
        size = jnp.asarray([s.size for s in slots], jnp.int32)
        row = jax.lax.broadcasted_iota(jnp.int32, (n // LANE, LANE), 0)
        lane = jax.lax.broadcasted_iota(jnp.int32, (n // LANE, LANE), 1)
        j = sum((row >= int(s.offset // LANE)).astype(jnp.int32) for s in slots[1:])
        e = (row - starts[j]) * LANE + lane
        v = off[j] + scale[j] * normal(key, pid[j], e)
        bufs.append(jnp.where(e < size[j], v, 0.0).astype(dtype).reshape(n))
    return tuple(bufs)


def row_blocks(seed: int, rows: int, seq: int, vocab: int):
    """Endless blocks of ``rows`` rows of ``seq + 1`` tokens from the seed:
    the inputs are a row's first ``seq`` tokens, the targets its last."""
    rng = seed_rng(seed, 1)
    while True:
        yield rng.integers(0, vocab, (rows, seq + 1), dtype=np.int32)
