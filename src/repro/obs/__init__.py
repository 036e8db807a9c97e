"""``repro.obs`` — observability for the ATA stack.

Four small modules, one switch:

* :mod:`repro.obs.trace` — nestable **spans** with stable dotted names
  (recursion steps, the recursion's operand sums and output combinations,
  the packing, kernel wrappers, the solve stages, the SPMD schedule
  bodies). Each span's ``jax.named_scope`` is always compiled in: it is
  metadata only, so op names in every compiled program carry the span path
  and the jaxpr, the values and the stripped StableHLO stay identical
  (tested). :func:`enable` adds recording: event buffer, span counts,
  ``jax.profiler.TraceAnnotation``, the root spans' host-clock times, and
  the garbage collector's pauses as ``host.gc`` annotations.
* :mod:`repro.obs.compiles` — always-on listener for JAX's own compile
  steps (trace, lower, backend compile or cache load) and persistent-cache
  hits and misses; ``compiles.programs()`` attributes them to the program
  that holds a root span.
* :mod:`repro.obs.metrics` — always-on process-local counters / gauges /
  histograms (plan-cache hits/misses/migrations, autotune trials and win
  margins, leaf counts per dispatch, kernel launches, collective bytes,
  solve iterations, JAX cache hits/misses) with a validated JSON snapshot
  (``metrics.export_json`` → ``BENCH_obs.json``).
* :mod:`repro.obs.calibrate` — the autotuner records every timed
  candidate ``(plan, predicted_seconds, measured_seconds)``;
  ``calibrate.report()`` renders the predicted-vs-measured drift table per
  Machine profile.

Quickstart (DESIGN.md §8):

    from repro import obs
    obs.enable()
    f = jax.jit(lambda a: ata(a, out="packed"))
    c = f(a)                             # spans + dispatch counters
    obs.compiles.programs()              # f's trace / lower / compile seconds
    snap = obs.metrics.snapshot()        # JSON-ready

Smoke entry point: ``python -m repro.obs`` runs one planned
``plan → ata → solve.lstsq`` with tracing on, validates the snapshot, the
scopes and the compile events, and writes ``BENCH_obs.json`` — the CI
obs-smoke step.
"""

from __future__ import annotations

from repro.obs import calibrate, compiles, metrics, trace
from repro.obs.trace import disable, enable, enabled, span

__all__ = [
    "trace",
    "compiles",
    "metrics",
    "calibrate",
    "enable",
    "disable",
    "enabled",
    "span",
    "report",
]


def report() -> str:
    """The calibration drift table (text) — see ``calibrate.report``."""
    return calibrate.report()
