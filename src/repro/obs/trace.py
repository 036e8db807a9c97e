"""Nestable span API — the tracing half of ``repro.obs``.

A *span* names one region of the dispatch pipeline: a recursion step, a
batched/fused leaf launch, a kernel wrapper, the solve front door, an SPMD
schedule body. Names are stable dotted paths; a level or a size goes in
the attrs, never in the name. Each span has two parts:

* **the named scope, always on** — :func:`span` enters
  ``jax.named_scope(name)``, so op names in the lowered and compiled HLO
  carry the span path whether or not obs is enabled. It is metadata only:
  it adds no op, leaves the jaxpr, the values and the StableHLO with its
  debug info stripped unchanged, and is not part of JAX's persistent-cache
  key (regression-tested in ``tests/test_obs.py``). So a program compiled
  by an untraced run, and loaded from the cache by a traced one, still
  names its ops. Disabled (the default), that scope is all a span is: no
  import, no recording, no allocation beyond the scope itself.
* **recording, gated by** :func:`enable` (or ``REPRO_OBS=1``) — each span
  also counts itself, records an event into a bounded in-process buffer
  (name, depth, attrs) and opens a ``jax.profiler.TraceAnnotation``, so
  host timelines of ``jax.profiler.trace`` show the same names. The root
  spans (:data:`ROOTS`) also record their host-clock start and end, which
  ``repro.obs.compiles`` uses to find the program they were traced into.
  While enabled, a ``gc.callbacks`` hook puts each garbage-collector pause
  on the profiler's host plane as a ``host.gc`` annotation and into the
  ``host.gc_s`` histogram.

Spans do **not** time compiled code: inside ``jit`` they open and close at
trace time. Device time comes from profiler traces, whose ops the scopes
name; set-up time from JAX's own compile events (``repro.obs.compiles``).
"""

from __future__ import annotations

import gc
import os
import threading
import time
from collections import Counter, deque

import jax

from repro.obs import metrics

__all__ = [
    "enable",
    "disable",
    "enabled",
    "span",
    "span_counts",
    "span_events",
    "root_spans",
    "reset",
    "MAX_EVENTS",
    "ROOTS",
]

_ENABLED = False
_LOCK = threading.Lock()
_COUNTS: Counter = Counter()          # span name -> times entered
_EVENTS: list = []                    # ordered (name, depth, attrs), bounded
_DEPTH = threading.local()

# events beyond this are counted but not stored — an unrolled 7^L recursion
# must never grow host memory unboundedly just because tracing is on.
MAX_EVENTS = 10_000

# the spans that open one planned dispatch; their (name, start, end) on the
# host clock (``time.time``, JAX's clock for compile events) are kept apart
# from the event buffer, newest last, so that no overflow can evict them
ROOTS = frozenset({"ata", "strassen_tn", "solve.lstsq", "distributed.ata"})
_ROOT_SPANS: deque = deque(maxlen=1_000)


def enabled() -> bool:
    return _ENABLED


def enable() -> None:
    """Turn span recording on (events, TraceAnnotations, root times, the
    collector hook)."""
    global _ENABLED
    _ENABLED = True
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)


def disable() -> None:
    global _ENABLED
    _ENABLED = False
    if _on_gc in gc.callbacks:
        gc.callbacks.remove(_on_gc)


def reset() -> None:
    """Drop recorded spans (tests; between benchmark modules)."""
    with _LOCK:
        _COUNTS.clear()
        _EVENTS.clear()
        _ROOT_SPANS.clear()


def span_counts() -> dict:
    """{span name: times entered} since the last :func:`reset`."""
    with _LOCK:
        return dict(_COUNTS)


def span_events() -> list:
    """Ordered recorded events ``(name, depth, attrs)`` (bounded by
    ``MAX_EVENTS``; counts in :func:`span_counts` are always complete)."""
    with _LOCK:
        return list(_EVENTS)


def root_spans() -> list:
    """``(name, start, end)`` of each root span entered while enabled, on
    the ``time.time`` clock, oldest first."""
    with _LOCK:
        return list(_ROOT_SPANS)


class _Span:
    __slots__ = ("name", "attrs", "_scope", "_annotation", "_start")

    def __init__(self, name: str, attrs: dict):
        self.name = name
        self.attrs = attrs

    def __enter__(self):
        depth = getattr(_DEPTH, "v", 0)
        _DEPTH.v = depth + 1
        with _LOCK:
            _COUNTS[self.name] += 1
            if len(_EVENTS) < MAX_EVENTS:
                _EVENTS.append((self.name, depth, self.attrs))
        self._start = time.time()
        self._scope = jax.named_scope(self.name)
        self._scope.__enter__()
        try:
            self._annotation = jax.profiler.TraceAnnotation(self.name)
            self._annotation.__enter__()
        except Exception:
            # host profiler unavailable (stripped containers): the span
            # still records + names scopes; annotation becomes a no-op.
            self._annotation = None
        return self

    def __exit__(self, *exc):
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
        self._scope.__exit__(*exc)
        _DEPTH.v = getattr(_DEPTH, "v", 1) - 1
        if self.name in ROOTS:
            with _LOCK:
                _ROOT_SPANS.append((self.name, self._start, time.time()))
        return False


def span(name: str, **attrs):
    """Context manager naming one region of the dispatch pipeline.

    ``name`` is a stable dotted path (``"ata.encode"``, ``"kernels.syrk"``);
    keyword attrs ride along into the event buffer when enabled (small
    static values only — levels, shapes, leaf counts; never arrays).
    Disabled, this is ``jax.named_scope(name)`` and nothing else.
    """
    if not _ENABLED:
        return jax.named_scope(name)
    return _Span(name, attrs)


# ---------------------------------------------------------------------------
# garbage-collector pauses, while enabled
# ---------------------------------------------------------------------------

_GC: dict = {}


def _on_gc(phase: str, info: dict) -> None:
    """``gc.callbacks`` hook: one ``host.gc`` annotation per collection, on
    the profiler's host plane (the device trace's clock), and its pause in
    the ``host.gc_s`` histogram."""
    if phase == "start":
        try:
            ann = jax.profiler.TraceAnnotation("host.gc")
            ann.__enter__()
        except Exception:
            ann = None
        _GC["annotation"], _GC["start"] = ann, time.perf_counter()
    elif phase == "stop" and "start" in _GC:
        metrics.observe("host.gc_s", time.perf_counter() - _GC.pop("start"))
        ann = _GC.pop("annotation", None)
        if ann is not None:
            ann.__exit__(None, None, None)


if os.environ.get("REPRO_OBS", "") == "1":
    enable()
