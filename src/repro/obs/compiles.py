"""JAX's compile steps, as JAX reports them — always on, like the counters.

JAX emits each step of building a program as a ``jax.monitoring`` time
span on the ``time.time`` clock, with the function's name:

    trace     /jax/core/compile/jaxpr_trace_duration
    lower     /jax/core/compile/jaxpr_to_mlir_module_duration
    compile   /jax/core/compile/backend_compile_duration   (a real compile,
              or hashing the module and loading it from the persistent
              cache)

and the persistent cache's ``cache_hits`` / ``cache_misses`` as plain
events. One listener, registered when ``repro.obs`` is imported, keeps
them as :class:`Event` rows in a bounded list of their own (no span
overflow can evict them; of nested steps only the outermost) and counts
``jax.cache_hits`` / ``jax.cache_misses`` in ``repro.obs.metrics``.

:func:`programs` joins them with the root spans of ``repro.obs.trace``
(recorded while obs is enabled): the outermost trace event that encloses a
root span is the trace of the program that was built, and the first lower
event and then the first compile event after it belong to the same
program. Compiles before that trace (operand makers) and after the
program's compile (references, eager ops) are not its.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from collections import deque
from typing import List, NamedTuple, Optional

from jax import monitoring

from repro.obs import metrics, trace

__all__ = ["Event", "Program", "events", "programs", "reset", "MAX_EVENTS"]

_STEPS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}
_CACHE = {   # event -> (kind, counter)
    "/jax/compilation_cache/cache_hits": ("cache_hit", "jax.cache_hits"),
    "/jax/compilation_cache/cache_misses": ("cache_miss", "jax.cache_misses"),
}
_KEEP = frozenset(kind for kind, _ in _CACHE.values())

# newest kept: a process that compiles for hours keeps its latest programs
MAX_EVENTS = 1_000
_LOCK = threading.Lock()
_EVENTS: deque = deque(maxlen=MAX_EVENTS)


class Event(NamedTuple):
    """One compile step (``start < end``) or cache event (``start == end``),
    on the ``time.time`` clock."""

    kind: str         # trace | lower | compile | cache_hit | cache_miss
    fun_name: str
    start: float
    end: float


@dataclasses.dataclass(frozen=True)
class Program:
    """The steps of one program that holds a root span, in seconds."""

    fun_name: str
    roots: tuple                  # root span names traced into it
    trace_s: float
    lower_s: Optional[float]
    compile_s: Optional[float]
    cache: Optional[str]          # "hit" / "miss" inside its compile, or None


def _on_span(event: str, start_time: float, end_time: float, **kw) -> None:
    kind = _STEPS.get(event)
    if kind is None:
        return
    with _LOCK:
        # JAX reports a step when it ends, after the steps nested in it: a
        # trace holds one trace per jnp function called (each is a jit of
        # its own), a lowering traces the helpers it lowers through. Only
        # the outermost step is kept, with the cache events of a compile.
        nested = []
        while _EVENTS and start_time <= _EVENTS[-1].start \
                and _EVENTS[-1].end <= end_time:
            nested.append(_EVENTS.pop())
        if kind == "compile":
            _EVENTS.extend(e for e in reversed(nested) if e.kind in _KEEP)
        _EVENTS.append(Event(kind, str(kw.get("fun_name", "")),
                             start_time, end_time))


def _on_event(event: str, **kw) -> None:
    if event in _CACHE:
        kind, counter = _CACHE[event]
        now = time.time()
        with _LOCK:
            _EVENTS.append(Event(kind, "", now, now))
        metrics.inc(counter)


monitoring.register_event_time_span_listener(_on_span)
monitoring.register_event_listener(_on_event)


def events() -> List[Event]:
    """Every kept event, in the order JAX reported them."""
    with _LOCK:
        return list(_EVENTS)


def reset() -> None:
    with _LOCK:
        _EVENTS.clear()


def _first_after(evs, kind, t):
    later = [e for e in evs if e.kind == kind and e.start >= t]
    return min(later, key=lambda e: e.start) if later else None


def programs(evs: Optional[List[Event]] = None,
             roots: Optional[list] = None) -> List[Program]:
    """The programs that hold a root span, oldest first (see the module
    docstring for the rule). ``evs`` and ``roots`` default to what this
    process recorded."""
    evs = events() if evs is None else evs
    roots = trace.root_spans() if roots is None else roots
    traces = [e for e in evs if e.kind == "trace"]
    held = {}                                  # trace event -> root names
    for name, r0, r1 in roots:
        around = [e for e in traces if e.start <= r0 and r1 <= e.end]
        if around:
            outer = min(around, key=lambda e: (e.start, -e.end))
            held.setdefault(outer, []).append(name)
    out = []
    for t in sorted(held, key=lambda e: e.start):
        lower = _first_after(evs, "lower", t.end)
        comp = _first_after(evs, "compile", lower.end) if lower else None
        cache = None
        if comp is not None:
            inside = [e.kind for e in evs if e.kind in _KEEP
                      and comp.start <= e.start <= comp.end]
            cache = inside[0][len("cache_"):] if inside else None
        out.append(Program(
            fun_name=t.fun_name, roots=tuple(dict.fromkeys(held[t])),
            trace_s=t.end - t.start,
            lower_s=lower.end - lower.start if lower else None,
            compile_s=comp.end - comp.start if comp else None,
            cache=cache))
    return out
