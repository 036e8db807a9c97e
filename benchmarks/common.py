"""Shared benchmark helpers: timing, CSV output, effective-GFLOPs metric,
and machine-readable row collection (``BENCH_*.json``, written by ``run.py``).

The warmup/median timing discipline itself lives in
``repro.tune.search`` — one implementation shared by the measured
autotuner and every benchmark, re-exported here unchanged.

Every emitted row carries structured **backend metadata**
(:func:`backend_meta`: ``backend``/``device_kind``/``jax_version``/
``interpret``) so BENCH_*.json trajectories are comparable across machines
— previously "interpret=True" was buried in free-text ``derived`` strings.
"""

from __future__ import annotations

import os

# single timing discipline, shared with the measured autotuner
from repro.tune.search import time_fn, time_pair  # noqa: F401  (re-export)

__all__ = [
    "time_fn",
    "time_pair",
    "effective_gflops",
    "backend_meta",
    "recursion_plan",
    "batched_recursion_plan",
    "emit",
    "drain_rows",
    "smoke",
    "SMOKE",
    "fake_device_env",
]

# rows emitted since the last drain — run.py drains after each bench module
# and writes them to BENCH_<module>.json so the perf trajectory is tracked.
_ROWS: list = []

# --smoke (run.py) / REPRO_BENCH_SMOKE=1: bench modules shrink their shape
# sweeps and iteration counts to CI scale.
SMOKE = False

_META: dict | None = None


def smoke() -> bool:
    return SMOKE or os.environ.get("REPRO_BENCH_SMOKE") == "1"


def fake_device_env(p: int) -> dict:
    """Environment of a child process that emulates ``p`` devices on the CPU.

    ``JAX_PLATFORMS=cpu`` keeps the child off any accelerator: the parent
    process has already opened JAX and holds the chip, if there is one.
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={p}"
    env["PYTHONPATH"] = os.path.abspath("src")
    env["JAX_PLATFORMS"] = "cpu"
    return env


def backend_meta() -> dict:
    """Structured runtime identity stamped on every BENCH row.

    ``backend``: ``jax.default_backend()``; ``device_kind``: the first
    device's hardware name; ``jax_version``: the runtime (it is part of the
    plan-cache key for the same reason); ``interpret``: whether the Pallas
    kernels run in interpret mode here (``kernels.ops.interpret_default``)
    — kernel-path numbers from an interpret-mode machine are correctness
    signals, not performance signals, and now say so machine-readably.
    """
    global _META
    if _META is None:
        import jax

        from repro.kernels.ops import interpret_default

        dev = jax.devices()[0]
        _META = {
            "backend": jax.default_backend(),
            "device_kind": getattr(dev, "device_kind", type(dev).__name__),
            "jax_version": jax.__version__,
            "interpret": bool(interpret_default()),
        }
    return dict(_META)


def recursion_plan(op: str, m: int, n: int, k: int | None = None,
                   *, leaf_dispatch: str = "batched",
                   backend: str | None = None):
    """The planner's best *actually-recursing* candidate with the requested
    leaf dispatch, for the leaf-dispatch BENCH rows — shared by
    ``bench_ata``/``bench_strassen`` so each bench's "batched row"/"fused
    row" means the same thing. The planner's argmin may be a degenerate
    single-leaf (or dense) dispatch, which has nothing to contrast; the
    fallback then forces a couple of levels (classical variant — the one
    every dispatch supports)."""
    import dataclasses

    from repro import tune

    dims = (m, n, k) if op == "gemm_tn" else (m, n)
    kw = {} if backend is None else {"backend": backend}
    cands = tune.candidates(op=op, m=m, n=n, k=k, **kw)
    for cand in cands:
        if (
            cand.algorithm != "dense"
            and cand.leaf_dispatch == leaf_dispatch
            and cand.n_base < min(dims)
        ):
            return cand
    return dataclasses.replace(
        cands[0], algorithm="strassen", n_base=max(128, min(dims) // 4),
        leaf_dispatch=leaf_dispatch,
    )


def batched_recursion_plan(op: str, m: int, n: int, k: int | None = None,
                           *, backend: str | None = None):
    """Pre-fused-PR name for :func:`recursion_plan` at its default dispatch."""
    return recursion_plan(op, m, n, k, leaf_dispatch="batched", backend=backend)


def effective_gflops(m: int, n: int, seconds: float, r: int = 1, k: int | None = None) -> float:
    """Paper Eq. (9) with the *actual* rectangular shape: ``r·m·n·k / time``.

    ``r=1`` for AᵀA-specialized algorithms (A is m×n, C is n×n → m·n² useful
    flops), ``r=2`` for general matmul — comparable across classical & fast
    algorithms. ``k`` defaults to ``n`` (the syrk case); pass it explicitly
    for rectangular gemm outputs. The seed used ``n³`` regardless of shape,
    which overstated tall-skinny syrk GFLOPs by m/n.
    """
    k = n if k is None else k
    return r * m * n * k / (seconds * 1e9)


def emit(name: str, seconds: float, derived: str, *, shape=None, gflops=None, **extra):
    """CSV row ``name,us_per_call,derived`` + JSON row for BENCH_*.json.

    The JSON row always carries :func:`backend_meta`; ``extra`` keys land
    on top (and may override it, e.g. a subprocess bench reporting the
    device count it forced).
    """
    print(f"{name},{seconds*1e6:.1f},{derived}", flush=True)
    row = {"name": name, "seconds": seconds, "derived": derived}
    row.update(backend_meta())
    if shape is not None:
        row["shape"] = list(shape)
    if gflops is not None:
        row["gflops"] = round(float(gflops), 3)
    row.update(extra)
    _ROWS.append(row)


def drain_rows() -> list:
    """Return and clear rows emitted since the last drain."""
    rows = list(_ROWS)
    _ROWS.clear()
    return rows
