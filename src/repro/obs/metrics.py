"""Process-local counters / gauges / histograms with JSON snapshot export.

The metrics registry is **always on** — counters are plain integers behind
one lock, incremented at Python dispatch/trace time (never inside the
compiled program), so they cost nanoseconds and can't perturb a jaxpr.
What ``obs.enable()`` gates is the recording half of the spans, which
does real work.

Semantics on traced code paths: a counter incremented inside a function
under ``jax.jit`` counts **traces**, not executions — e.g.
``kernels.launch.syrk`` is the number of syrk launches *in the traced
program*, which is exactly the per-dispatch leaf accounting the cost
model's ``dispatch_calls`` predicts.

Naming convention (dotted, lowercase):

    tune.cache.*       plan-cache hits/misses/migrations/sanitizations
    tune.autotune.*    trials, wins, win-margin histogram
    dispatch.<op>.*    planned dispatches per leaf-dispatch / method
    <op>.leaves.*      leaf counts per dispatch
    kernels.launch.*   Pallas wrapper launches (traced)
    solve.*            solver front-door counters
    collective_bytes.* per-kind HLO collective payload (via record_collective_bytes)
    check.*            repro.check analyzer accounting: rules_run /
                       artifacts / findings.<rule-id> / violations
    jax.cache_{hits,misses}  JAX persistent-cache answers (repro.obs.compiles)
    host.gc_s          garbage-collector pauses while obs is enabled
                       (histogram, repro.obs.trace)

Snapshot schema (``SNAPSHOT_SCHEMA``): see :func:`snapshot` /
:func:`validate_snapshot` — the contract the CI obs-smoke step asserts.
"""

from __future__ import annotations

import json
import threading
from typing import Dict, Optional

__all__ = [
    "inc",
    "set_gauge",
    "observe",
    "get",
    "counters",
    "gauges",
    "histograms",
    "snapshot",
    "validate_snapshot",
    "export_json",
    "record_collective_bytes",
    "reset",
    "SNAPSHOT_SCHEMA",
]

SNAPSHOT_SCHEMA = "repro.obs/v1"

_LOCK = threading.Lock()
_COUNTERS: Dict[str, int] = {}
_GAUGES: Dict[str, float] = {}
_HISTS: Dict[str, dict] = {}   # name -> {count, sum, min, max}


def inc(name: str, value: int = 1) -> None:
    """Add ``value`` to counter ``name`` (created at 0)."""
    with _LOCK:
        _COUNTERS[name] = _COUNTERS.get(name, 0) + int(value)


def set_gauge(name: str, value: float) -> None:
    """Set gauge ``name`` to the latest value."""
    with _LOCK:
        _GAUGES[name] = float(value)


def observe(name: str, value: float) -> None:
    """Record one sample into histogram ``name`` (count/sum/min/max —
    enough for means and ranges without bucket-boundary bikeshedding)."""
    v = float(value)
    with _LOCK:
        h = _HISTS.get(name)
        if h is None:
            _HISTS[name] = {"count": 1, "sum": v, "min": v, "max": v}
        else:
            h["count"] += 1
            h["sum"] += v
            h["min"] = min(h["min"], v)
            h["max"] = max(h["max"], v)


def get(name: str, default: int = 0) -> int:
    """Current value of counter ``name``."""
    with _LOCK:
        return _COUNTERS.get(name, default)


def counters(prefix: str = "") -> Dict[str, int]:
    with _LOCK:
        return {k: v for k, v in _COUNTERS.items() if k.startswith(prefix)}


def gauges(prefix: str = "") -> Dict[str, float]:
    with _LOCK:
        return {k: v for k, v in _GAUGES.items() if k.startswith(prefix)}


def histograms(prefix: str = "") -> Dict[str, dict]:
    with _LOCK:
        return {k: dict(v) for k, v in _HISTS.items() if k.startswith(prefix)}


def reset() -> None:
    """Clear every registered metric (tests; between benchmark modules).
    Spans and calibration rows have their own ``reset`` in their modules."""
    with _LOCK:
        _COUNTERS.clear()
        _GAUGES.clear()
        _HISTS.clear()


def record_collective_bytes(hlo_text: str, prefix: str = "collective_bytes") -> dict:
    """Fold one compiled module's per-device collective payload into the
    registry: counter ``<prefix>.<kind>`` += bytes for every collective
    kind found by :func:`repro.analysis.hlo.collective_bytes`. Returns the
    per-kind dict (nonzero kinds only) for the caller's own reporting."""
    from repro.analysis.hlo import collective_bytes

    by_kind = {k: v for k, v in collective_bytes(hlo_text).items() if v}
    for kind, b in by_kind.items():
        inc(f"{prefix}.{kind}", b)
    return by_kind


def _meta() -> dict:
    """Runtime identity stamped on snapshots — jax imported lazily so the
    registry itself stays importable anywhere."""
    try:
        import jax

        return {"backend": jax.default_backend(), "jax_version": jax.__version__}
    except Exception:
        return {"backend": "unknown", "jax_version": "unknown"}


def snapshot() -> dict:
    """One JSON-serializable view of everything observed this process:
    metrics, span counts (``repro.obs.trace``), and the calibration rows
    (``repro.obs.calibrate``)."""
    from repro.obs import calibrate, trace

    return {
        "schema": SNAPSHOT_SCHEMA,
        "meta": _meta(),
        "counters": counters(),
        "gauges": gauges(),
        "histograms": histograms(),
        "spans": trace.span_counts(),
        "calibration": calibrate.rows(),
    }


def validate_snapshot(d: dict) -> dict:
    """Schema check for :func:`snapshot` output (the CI obs-smoke contract).
    Raises ``ValueError`` on any violation; returns ``d`` unchanged."""
    if not isinstance(d, dict):
        raise ValueError(f"snapshot must be a dict, got {type(d).__name__}")
    if d.get("schema") != SNAPSHOT_SCHEMA:
        raise ValueError(
            f"snapshot schema {d.get('schema')!r} != {SNAPSHOT_SCHEMA!r}"
        )
    for section, typ in (
        ("meta", dict), ("counters", dict), ("gauges", dict),
        ("histograms", dict), ("spans", dict), ("calibration", list),
    ):
        if not isinstance(d.get(section), typ):
            raise ValueError(f"snapshot[{section!r}] must be {typ.__name__}")
    for k, v in d["counters"].items():
        if not isinstance(k, str) or not isinstance(v, int):
            raise ValueError(f"counter {k!r}: {v!r} is not a str->int entry")
    for k, v in d["histograms"].items():
        missing = {"count", "sum", "min", "max"} - set(v)
        if missing:
            raise ValueError(f"histogram {k!r} missing fields {sorted(missing)}")
    for row in d["calibration"]:
        missing = {"key", "op", "backend", "predicted_s", "measured_s"} - set(row)
        if missing:
            raise ValueError(f"calibration row missing fields {sorted(missing)}")
    return d


def export_json(path: str, extra: Optional[dict] = None) -> str:
    """Write the validated snapshot (plus optional extra top-level keys)
    to ``path``; returns the path."""
    snap = validate_snapshot(snapshot())
    if extra:
        snap = {**snap, **extra}
    with open(path, "w") as f:
        json.dump(snap, f, indent=1, sort_keys=True)
    return path
