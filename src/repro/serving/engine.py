"""Serving: prefill + decode steps, and the paged continuous-batching engine.

``decode_step`` is the dense-cache decode the decode-shape dry-runs lower for
recurrent archs, and the bitwise oracle the paged path is pinned against
(tests/test_paged_attn.py). ``paged_step`` is the production path: one jitted
program serves both chunked prefill (tokens ``(1, C)``) and joint decode
(tokens ``(slots, 1)``) against the shared page pool — appends are O(tokens)
scatters into pages, never a cache copy or `_grow_all`-style pad chain.

:class:`BatchedEngine` is plane-resident: built on a packed consensus/anchor
plane it reads weights through :class:`ParamView` inside the jitted step, so
``swap_plane`` (a zero-copy buffer swap, applied only between decode steps)
retargets a live server at a freshly averaged anchor without recompiling,
copying, or disturbing in-flight requests. ``swap_params`` composes the
:func:`hot_swap` checkpoint-restore retry path with the same boundary.

Robustness: batch entry points validate shapes up front (an empty or
oversized batch fails with a clear error instead of an XLA trace dump), and
:func:`hot_swap` wraps anchor-checkpoint reads in a bounded
retry-with-backoff — a trainer mid-save produces transiently unreadable
files, and serving should ride through that window, not crash.
"""
from __future__ import annotations

import functools
import time
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config.base import ModelConfig
from repro.models import transformer as T
from repro.parallel.packing import Packed, ParamView
from repro.serving.paged_cache import PagedState, init_paged_pools, pages_for, paged_supported
from repro.serving.scheduler import Request, Scheduler
from repro.utils import trace


def prefill(cfg: ModelConfig, params, inputs) -> Tuple[jnp.ndarray, dict]:
    logits, aux = T.apply_model(cfg, params, inputs, mode="prefill")
    return logits, aux["caches"]


def decode_step(cfg: ModelConfig, params, tokens, caches, pos) -> Tuple[jnp.ndarray, dict]:
    """tokens: (B, 1) (text) or (B, K, 1) (audio); pos: scalar absolute position."""
    inputs = dict(tokens=tokens)
    logits, aux = T.apply_model(cfg, params, inputs, mode="decode", caches=caches, decode_pos=pos)
    return logits, aux["caches"]


def paged_step(
    cfg: ModelConfig, params, tokens, caches, page_tables, lengths
) -> Tuple[jnp.ndarray, dict]:
    """One paged-attention step: append ``tokens``' K/V into the slots' pages
    and attend. tokens (S, T) — T == 1 is joint decode across slots, T > 1 a
    prefill chunk (S == 1 in the engine). ``lengths`` is each slot's resident
    token count, i.e. the absolute position of tokens[:, 0]; idle rows carry
    a zero (trash) page-table row and length 0."""
    if isinstance(params, Packed):
        params = ParamView(params)
    t = tokens.shape[1]
    positions = lengths[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]
    inputs = dict(tokens=tokens, positions=positions)
    logits, aux = T.apply_model(
        cfg, params, inputs, mode="decode", caches=caches,
        paged=PagedState(jnp.asarray(page_tables), jnp.asarray(lengths)),
    )
    return logits, aux["caches"]


def _grow_all(caches: dict, cfg: ModelConfig, target_len: int) -> dict:
    from repro.models.layers.attention import grow_cache
    from repro.models.transformer import segments

    out = {}
    segs = segments(cfg)
    for si, (kind, n) in enumerate(segs):
        key = f"seg{si}"
        if key not in caches:
            continue
        c = caches[key]
        if kind in ("attn", "moe", "shared_attn"):
            if kind == "shared_attn":
                out[key] = grow_cache(c, target_len)
            else:
                # stacked over the run's layers: vmap the growth
                out[key] = jax.vmap(lambda ci: grow_cache(ci, target_len))(c)
        else:
            out[key] = c
    return out


def _sample(logits, temperature: float, key):
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1)
    return jax.random.categorical(key, logits / temperature, axis=-1)


def generate(
    cfg: ModelConfig,
    params,
    prompt: jnp.ndarray,  # (B, S0) int32
    max_new: int,
    temperature: float = 0.0,
    seed: int = 0,
) -> np.ndarray:
    """Greedy/sampled generation for the examples (CPU-scale models)."""
    if prompt.ndim != 2:
        raise ValueError(f"prompt must be (batch, seq) int tokens, got shape {tuple(prompt.shape)}")
    if prompt.shape[0] == 0 or prompt.shape[1] == 0:
        raise ValueError(f"empty prompt batch: shape {tuple(prompt.shape)}")
    if max_new < 1:
        raise ValueError(f"max_new must be >= 1, got {max_new}")
    logits, caches = jax.jit(functools.partial(prefill, cfg))(params, dict(tokens=prompt))
    target_len = prompt.shape[-1] + max_new
    caches = _grow_all(caches, cfg, target_len)
    next_tok = _sample(logits[:, -1], temperature, jax.random.PRNGKey(seed))[:, None]
    step_fn = jax.jit(functools.partial(decode_step, cfg))
    out = [next_tok]
    pos = prompt.shape[1]
    key = jax.random.PRNGKey(seed + 1)
    for i in range(max_new - 1):
        key, sub = jax.random.split(key)
        logits, caches = step_fn(params, next_tok, caches, jnp.asarray(pos + i, jnp.int32))
        next_tok = _sample(logits[:, -1], temperature, sub)[:, None]
        out.append(next_tok)
    return np.concatenate([np.asarray(t) for t in out], axis=1)


def hot_swap(path: str, template, retries: int = 3, backoff: float = 0.05, _sleep: Callable[[float], None] = time.sleep):
    """Restore a params checkpoint for serving, retrying transient read
    failures (a trainer mid-save, a slow network filesystem) with bounded
    exponential backoff: attempt k sleeps ``backoff * 2**k``. Structural
    mismatches (``KeyError``: wrong template) are NOT retried — they cannot
    heal by waiting. Raises the last transient error after ``retries``
    failed attempts."""
    from repro.checkpoint import restore

    import zipfile

    last = None
    for attempt in range(max(int(retries), 1)):
        try:
            return restore(path, template)
        except (OSError, EOFError, ValueError, zipfile.BadZipFile) as e:
            last = e
            _sleep(backoff * (2**attempt))
    raise last


class BatchedEngine:
    """Continuous-batching serving engine over a paged KV pool.

    Attention-family text archs run paged (DESIGN.md §10): fixed-size pages
    in a global pool, per-slot page tables, chunked prefill filling pages
    incrementally, and one joint decode program per step across every active
    slot — a short request admits, decodes exactly its own ``max_new`` steps,
    and frees its pages the moment it finishes, regardless of what its
    co-batched neighbours are doing. Prompts are never padded against each
    other (each prefills into its own pages at its own positions), which is
    what makes per-request outputs identical to solo :func:`generate` runs.

    Recurrent/hybrid archs (O(1) decode state — nothing to page) fall back
    to per-request solo generation: exact logits and per-request max_new, at
    fallback throughput.

    ``params`` may be a nested pytree or a packed plane (:class:`Packed`,
    lead ()); a plane is served *in place* through :class:`ParamView` —
    see :meth:`swap_plane`.
    """

    def __init__(
        self,
        cfg: ModelConfig,
        params,
        slots: int = 4,
        max_len: int = 256,
        *,
        page_size: int = 16,
        num_pages: Optional[int] = None,
        chunk: int = 32,
        paged="auto",
    ):
        if slots < 1:
            raise ValueError(f"slots must be >= 1, got {slots}")
        if max_len < 2:
            raise ValueError(f"max_len must be >= 2 (one prompt token + one generated), got {max_len}")
        if page_size < 1 or chunk < 1:
            raise ValueError(f"page_size and chunk must be >= 1, got {page_size}, {chunk}")
        self.cfg = cfg
        self.slots, self.max_len = slots, max_len
        self._plane: Optional[Packed] = None
        self._pending_plane: Optional[Packed] = None
        if isinstance(params, Packed):
            if params.lead_shape != ():
                raise ValueError(f"serving plane must have no lead axis, got {params.lead_shape}")
            self._plane = params
            self.params = None
        else:
            self.params = params
        if paged == "auto":
            self.paged = paged_supported(cfg)
        else:
            self.paged = bool(paged)
            if self.paged and not paged_supported(cfg):
                raise ValueError("paged serving requires an attention-only text arch")
        self.results: dict = {}
        self.queue: list = []  # dense-fallback queue
        # what the paged ticks did: ticks; prefill chunks and their prompt
        # tokens; joint decodes, their slot-tokens, and the resident tokens
        # and pages they attended; tokens generated; bytes copied device -> host
        self.counters = trace.Counters()
        if self.paged:
            self.page_size = page_size
            self.chunk = chunk
            self.max_pages = pages_for(max_len, page_size)
            # default pool: full residency for every slot, plus the trash page
            self.num_pages = int(num_pages) if num_pages is not None else slots * self.max_pages + 1
            self.pools = init_paged_pools(cfg, self.num_pages, page_size)
            self.sched = Scheduler(slots, self.num_pages, page_size, self.max_pages)
            # donation lets XLA scatter appends into the pool in place; CPU
            # has no donation support, so skip it there (avoids the warning —
            # the structural no-copy claim is pinned by the jaxpr test)
            donate = (3,) if jax.default_backend() == "tpu" else ()

            # two names for the one paged step, so that a trace tells its
            # programs apart: jit_serve_prefill_chunk and jit_serve_decode
            def serve_prefill_chunk(params, tokens, caches, page_tables, lengths):
                return paged_step(cfg, params, tokens, caches, page_tables, lengths)

            def serve_decode(params, tokens, caches, page_tables, lengths):
                return paged_step(cfg, params, tokens, caches, page_tables, lengths)

            self._prefill_jit = jax.jit(serve_prefill_chunk, donate_argnums=donate)
            self._decode_jit = jax.jit(serve_decode, donate_argnums=donate)

    # -- request intake ------------------------------------------------------

    def _known(self, req_id) -> bool:
        if req_id in self.results or any(rid == req_id for rid, *_ in self.queue):
            return True
        if not self.paged:
            return False
        return any(r.rid == req_id for r in self.sched.queue) or any(
            a is not None and a.req.rid == req_id for a in self.sched.active
        )

    def submit(self, req_id, prompt: np.ndarray, max_new: int, stop: Optional[int] = None):
        prompt = np.asarray(prompt)
        if prompt.ndim != 1 or prompt.shape[0] == 0:
            raise ValueError(
                f"request {req_id!r}: prompt must be a non-empty 1-D token array, got shape {tuple(prompt.shape)}"
            )
        if max_new < 1:
            raise ValueError(f"request {req_id!r}: max_new must be >= 1, got {max_new}")
        if prompt.shape[0] + max_new > self.max_len:
            raise ValueError(
                f"request {req_id!r}: prompt ({prompt.shape[0]}) + max_new ({max_new}) exceeds "
                f"engine max_len ({self.max_len})"
            )
        if self._known(req_id):
            raise ValueError(f"duplicate request id {req_id!r}")
        if self.paged:
            self.sched.submit(Request(req_id, prompt.astype(np.int32), int(max_new), stop))
        else:
            self.queue.append((req_id, prompt, int(max_new), stop))

    # -- served parameters ---------------------------------------------------

    @property
    def plane(self) -> Optional[Packed]:
        return self._plane

    def _params_arg(self):
        return self._plane if self._plane is not None else self.params

    def swap_plane(self, plane: Packed) -> None:
        """Queue a zero-copy hot-swap of the served plane. Applied at the
        next :meth:`step` boundary — a decode step in flight finishes on the
        old plane; no step ever mixes weights. The plane's buffers are served
        as-is (no unpack/copy), so passing a live anchor plane from a running
        ``Experiment.fit`` costs nothing but the swap itself."""
        if self._plane is None:
            raise ValueError("engine was built on a per-leaf pytree; swap_plane needs a plane-resident engine")
        if not isinstance(plane, Packed):
            raise TypeError(f"swap_plane takes a Packed plane, got {type(plane).__name__}")
        if plane.lead_shape != ():
            raise ValueError(f"serving plane must have no lead axis, got {plane.lead_shape}")
        if plane.layout != self._plane.layout:
            raise ValueError("swap_plane: layout mismatch with the served plane")
        self._pending_plane = plane

    def swap_params(self, path: str, retries: int = 3, backoff: float = 0.05) -> None:
        """Hot-swap the served parameters from a checkpoint (see
        :func:`hot_swap`) — the anchor-following deployment path. On a
        plane-resident engine the restored tree is packed onto the served
        layout and applied at the same between-steps boundary as
        :meth:`swap_plane`."""
        if self._plane is not None:
            restored = hot_swap(path, self._plane, retries=retries, backoff=backoff)
            self.swap_plane(restored)
        else:
            self.params = hot_swap(path, self.params, retries=retries, backoff=backoff)

    # -- paged engine loop ---------------------------------------------------

    def _pages_attended(self, pos: int) -> int:
        """Pages a decode reads for a token appended at ``pos``: its
        window's first page (page 0 without a window) through its own."""
        window = self.cfg.attention.sliding_window
        first = 0 if window is None else max(pos - window + 1, 0) // self.page_size
        return pos // self.page_size - first + 1

    def _run_step(self, program, tokens, page_tables, lengths):
        logits, self.pools = program(
            self._params_arg(),
            jnp.asarray(tokens),
            self.pools,
            jnp.asarray(page_tables),
            jnp.asarray(lengths),
        )
        return logits

    def step(self) -> list:
        """One scheduler tick: apply a pending plane swap, complete finished
        requests (freeing their pages), admit, advance every prefilling slot
        by one chunk, then run one joint decode across active slots. Returns
        the request ids completed this tick. Each phase is a span inside
        ``repro.engine.tick`` (:mod:`repro.utils.trace`), and ``counters``
        counts its work."""
        if not self.paged:
            raise RuntimeError("step() drives the paged engine; the dense fallback runs via run()")
        sched, c = self.sched, self.counters
        with trace.span("engine.tick", tick=c["ticks"]) as tick:
            with trace.span("engine.schedule"):
                if self._pending_plane is not None:  # between decode steps, never mid-step
                    self._plane = self._pending_plane
                    self._pending_plane = None
                finished = []
                for i in range(self.slots):
                    a = sched.active[i]
                    if a is not None and a.finished:
                        self.results[a.req.rid] = np.asarray(a.generated, np.int32)
                        finished.append(a.req.rid)
                        sched.complete(i)
                sched.admit()
            # chunked prefill: each prefilling slot advances one chunk (B=1 call)
            prefilling = 0
            for i in range(self.slots):
                a = sched.active[i]
                if a is None or a.prefill_done:
                    continue
                start = a.length
                end = min(start + self.chunk, len(a.req.prompt))
                with trace.span("engine.schedule"):
                    if not sched.ensure_pages(i, end - 1):
                        continue  # evicted itself to make room; requeued
                prefilling += 1
                with trace.span("engine.prefill", slot=i, tokens=end - start):
                    toks = np.zeros((1, self.chunk), np.int32)
                    toks[0, : end - start] = a.req.prompt[start:end]
                    logits = self._run_step(self._prefill_jit, toks, sched.table[i : i + 1],
                                            np.asarray([start], np.int32))
                a.length = end
                c["prefill_chunks"] += 1
                c["prefill_tokens"] += end - start
                if end == len(a.req.prompt):
                    with trace.span("engine.prefill_readback", slot=i):
                        a.prefill_done = True
                        a.generated.append(int(np.argmax(np.asarray(logits)[0, end - start - 1])))
                    c["readback_bytes"] += logits.nbytes
                    c["generated_tokens"] += 1
            # joint decode across every decode-ready slot
            with trace.span("engine.schedule"):
                dec = []
                for i in range(self.slots):
                    a = sched.active[i]
                    if a is None or not a.prefill_done or a.finished:
                        continue
                    if sched.ensure_pages(i, a.length):  # the append position
                        dec.append((i, a.admit_seq))
                dec = [
                    i for i, seq in dec
                    if sched.active[i] is not None and sched.active[i].admit_seq == seq
                ]
            if dec:
                with trace.span("engine.decode", slots=len(dec)):
                    toks = np.zeros((self.slots, 1), np.int32)
                    tables = np.zeros_like(sched.table)  # idle rows → trash page, length 0
                    lens = np.zeros((self.slots,), np.int32)
                    for i in dec:
                        a = sched.active[i]
                        toks[i, 0] = a.generated[-1]
                        tables[i] = sched.table[i]
                        lens[i] = a.length
                    logits = self._run_step(self._decode_jit, toks, tables, lens)
                with trace.span("engine.sample"):
                    nxt = np.asarray(jnp.argmax(logits[:, 0], axis=-1))
                    for i in dec:
                        a = sched.active[i]
                        c["decode_kv_pages"] += self._pages_attended(a.length)
                        a.length += 1
                        a.generated.append(int(nxt[i]))
                        c["decode_kv_tokens"] += a.length  # the appended token's K/V is attended too
                c["decode_steps"] += 1
                c["decode_tokens"] += len(dec)
                c["generated_tokens"] += len(dec)
                c["readback_bytes"] += nxt.nbytes
            c["ticks"] += 1
            tick.set_metadata(prefilling=prefilling, decoding=len(dec))
        return finished

    def run(self) -> dict:
        if self.paged:
            while self.sched.busy:
                self.step()
            return self.results
        # dense fallback: solo decode per request — exact per-request logits
        # and exactly max_new steps each (no cross-request left-padding, no
        # shared max(max_new))
        while self.queue:
            rid, prompt, max_new, stop = self.queue.pop(0)
            params = ParamView(self._plane) if self._plane is not None else self.params
            row = generate(self.cfg, params, jnp.asarray(prompt)[None], max_new)[0]
            if stop is not None:
                hits = np.nonzero(row == stop)[0]
                if hits.size:
                    row = row[: hits[0] + 1]
            self.results[rid] = row
        return self.results
