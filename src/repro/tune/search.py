"""Measured autotuning: time candidate plans, keep the fastest.

The autotuner's timing discipline:

* every timed call is synced with ``jax.block_until_ready``;
* ``warmup`` calls are discarded (jit compile + first-touch);
* ``time_fn`` reports the **median** over ``iters`` (robust to the
  ±20-30% background jitter of shared containers);
* when the quantity of interest is a *ratio* between two functions,
  ``time_ratio`` interleaves the two with alternating order and keeps the
  minimum of each series, so load drift and bursts hit both equally.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Optional

import jax
import numpy as np

from repro.obs import calibrate, metrics
from repro.tune import cost

__all__ = ["time_fn", "measure_plan", "autotune"]


def time_fn(fn, *args, iters: int = 5, warmup: int = 2) -> float:
    """Median wall time (s) of fn(*args) with device sync."""
    for _ in range(warmup):
        out = fn(*args)
        jax.block_until_ready(out)
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def time_ratio(fn_a, fn_b, *args, iters: int = 8, warmup: int = 1):
    """Robust speed ratio ``t_a / t_b``: **minimum** per series, calls
    interleaved with alternating order.

    Independent per-series medians survive slow drift but not burst noise:
    background spikes on this container last about as long as one call,
    so a median over a handful of samples still swings 30-80%
    even for *identical* functions. Interference only ever ADDS time, so
    the min over an interleaved series is the clean-machine floor of each
    function — measured identical-function min-ratios stay within ~±10%
    where per-iteration medians swung ±35%. Alternating the call order
    cancels cache-warming bias. Returns ``(ratio, min_t_a, min_t_b)``.
    """
    for _ in range(warmup):
        jax.block_until_ready(fn_a(*args))
        jax.block_until_ready(fn_b(*args))
    tas, tbs = [], []
    for k in range(iters):
        first, second = (fn_a, fn_b) if k % 2 == 0 else (fn_b, fn_a)
        t0 = time.perf_counter()
        jax.block_until_ready(first(*args))
        t1 = time.perf_counter()
        jax.block_until_ready(second(*args))
        t2 = time.perf_counter()
        ta, tb = (t1 - t0, t2 - t1) if k % 2 == 0 else (t2 - t1, t1 - t0)
        tas.append(ta)
        tbs.append(tb)
    ta, tb = min(tas), min(tbs)
    return ta / tb, ta, tb


# ---------------------------------------------------------------------------
# plan measurement
# ---------------------------------------------------------------------------


def _operands(plan: cost.Plan, seed: int = 0):
    """Deterministic random operands matching the plan's problem key."""
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    lead = (plan.batch,) if plan.batch else ()
    dt = jnp.dtype(plan.dtype)
    a = jnp.asarray(rng.standard_normal((*lead, plan.m, plan.n)), dt)
    if plan.op == "gemm_tn":
        b = jnp.asarray(rng.standard_normal((*lead, plan.m, plan.k)), dt)
        return (a, b)
    if plan.op == "solve":
        # k is the RHS count; lstsq is unbatched (2-D design matrix)
        b = jnp.asarray(rng.standard_normal((plan.m, plan.k)), dt)
        return (a, b)
    return (a,)


def measure_plan(
    plan: cost.Plan, *, iters: int = 3, warmup: int = 1, seed: int = 0
) -> float:
    """Median seconds of one plan's jitted callable on synthetic operands."""
    from repro.tune.apply import build_callable

    fn = build_callable(plan)
    args = _operands(plan, seed)
    return time_fn(fn, *args, iters=iters, warmup=warmup)


def autotune(
    op: str,
    m: int,
    n: int,
    k: Optional[int] = None,
    *,
    batch: int = 0,
    dtype: str = "float32",
    out: str = "dense",
    backend: str = "cpu",
    devices: int = 1,
    row_devices: int = 1,
    max_candidates: int = 4,
    iters: int = 8,
    warmup: int = 1,
    margin: float = 0.15,
) -> cost.Plan:
    """Measured sweep: every analytic top-``max_candidates`` candidate is
    timed **paired against the hardcoded default** (``time_ratio`` —
    per-iteration ratios with alternating order survive both load drift
    and burst noise), and a candidate replaces the default only when it
    wins by more than ``margin``.

    The default plan is the reference of every comparison, so the tuned
    plan can never be slower than the hardcoded baseline by more than
    measurement noise — and within-noise "wins" (which a later re-measure
    would flip) keep the default outright.
    """
    from repro.tune.apply import build_callable

    key = dict(batch=batch, dtype=dtype, out=out, backend=backend,
               devices=devices, row_devices=row_devices)
    base = cost.default_plan(op, m, n, k, **key)
    cands = [
        c for c in cost.candidates(op, m, n, k, **key)[:max_candidates]
        if not _same_dispatch(c, base)
    ]

    metrics.inc("tune.autotune.runs")
    base_fn = build_callable(base)
    args = _operands(base)
    t_base = time_fn(base_fn, *args, iters=iters, warmup=warmup)
    calibrate.record(base, t_base, source="autotune")
    best = (1.0, base, t_base, t_base)
    for cand in cands:
        metrics.inc("tune.autotune.trials")
        cand_fn = build_callable(cand)
        ratio, tb, tc = time_ratio(
            base_fn, cand_fn, *args, iters=iters, warmup=warmup
        )
        if ratio > 1.0 + margin:
            # a promising win must REPLICATE in a second, independent
            # measurement window (sustained load bursts can corrupt one
            # whole window against a single function); keep the
            # conservative minimum of the two windows.
            r2, tb2, tc2 = time_ratio(base_fn, cand_fn, *args, iters=iters, warmup=0)
            ratio = min(ratio, r2)
            tb, tc = min(tb, tb2), min(tc, tc2)
        # every trial's clean-machine floor is a calibration pair for the
        # candidate's analytic prediction (candidates() stamps predicted_s)
        calibrate.record(cand, tc, source="autotune")
        # ratio > 1: candidate beats the default, burst-noise-robustly
        if ratio > 1.0 + margin and ratio > best[0]:
            best = (ratio, cand, tc, tb)
    ratio_won, plan, t, t_baseline = best
    if plan is base:
        metrics.inc("tune.autotune.kept_default")
    else:
        metrics.inc("tune.autotune.wins")
        metrics.observe("tune.autotune.win_margin", ratio_won - 1.0)
    return dataclasses.replace(
        plan, source="measured", measured_s=t, baseline_s=t_baseline
    )


def _same_dispatch(a: cost.Plan, b: cost.Plan) -> bool:
    """True when two plans dispatch identically (tunables equal)."""
    keys = ("algorithm", "n_base", "packed_block", "use_kernels",
            "syrk_blocks", "gemm_blocks", "leaf_dispatch", "method",
            "nb", "tile_w", "comm_schedule", "row_devices")
    return all(getattr(a, f) == getattr(b, f) for f in keys)
