"""The program's tracing: spans on the profiler's clock, and counts.

* :func:`span` names a stretch of host time ``repro.<name>``. It is a
  ``jax.profiler.TraceAnnotation``: while a profiler trace is being taken it
  is recorded there, on the clock of the device planes, and kept in memory
  until the trace is written; otherwise it only asks the profiler whether a
  trace is being taken.
* :class:`Counters` is the dict of counts an object keeps of the work it did
  (``BatchedEngine.counters``, ``Experiment.counters``). Counts only grow;
  ``after - before`` of two copies is what happened in between.
* :func:`compiles` counts the programs this process compiled, and those it
  loaded from JAX's persistent compilation cache instead.

Nothing here writes a file or reads the environment: a trace is taken, and
written, by whoever starts the profiler.
"""
from __future__ import annotations

import collections

import jax
from jax import monitoring

Counters = collections.Counter

# JAX records one such duration per program it hands the backend, whether
# the backend compiles it or it comes back from the persistent cache
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_compile_counts = Counters()
_listening = False


class _Off:
    """What :func:`span` gives while no trace is being taken."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **args) -> None:
        pass


_OFF = _Off()
_tracing = jax.profiler.TraceAnnotation.is_enabled


def span(name: str, **args):
    """A context manager that records ``repro.<name>``, with ``args`` as its
    metadata (``set_metadata`` adds more), into a running profiler trace.
    With no trace being taken it is a shared one that does nothing."""
    if not _tracing():
        return _OFF
    return jax.profiler.TraceAnnotation(f"repro.{name}", **args)


def _on_duration(event: str, duration: float, **kwargs) -> None:
    if event == _BACKEND_COMPILE:
        _compile_counts["requests"] += 1


def _on_event(event: str, **kwargs) -> None:
    if event == _CACHE_HIT:
        _compile_counts["cache_loads"] += 1


def compiles() -> Counters:
    """Programs this process has compiled (``compiles``) and loaded from the
    persistent compilation cache (``cache_loads``) since this was first
    called: the first call starts the count. Take it before and after a
    window; the difference is what the window compiled."""
    global _listening
    if not _listening:
        monitoring.register_event_duration_secs_listener(_on_duration)
        monitoring.register_event_listener(_on_event)
        _listening = True
    c = _compile_counts
    return Counters(compiles=c["requests"] - c["cache_loads"], cache_loads=c["cache_loads"])
