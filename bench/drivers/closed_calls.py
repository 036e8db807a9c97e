"""Driver ``closed_calls``: one caller, back-to-back calls of one entry.

Set-up makes a pool of ``traffic["pool"]`` operand sets on the device from
the seed, compiles the entry once for their shape and calls it once on
each. The window then calls it on the next operand set of the pool, each
call blocked to completion before the next, until ``seconds`` have passed;
the call that crosses the line ends the window. ``call_s`` is the window's
length over the calls completed in it.

The answers of two calls drawn from the seed among the first eight, and of
the window's last call, are kept and compared with the plain reference
once the window has closed.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List

import jax

__all__ = ["setup", "window", "check", "counts", "end_to_end", "layer_inputs"]

_SAMPLED_AMONG = 8


@dataclasses.dataclass
class State:
    cell: Any
    plan: Any
    pool: List[tuple]
    compiled: Any
    keep: set
    phases: Dict[str, float]     # set-up seconds by step
    hlo_text: str = ""


@dataclasses.dataclass
class Result:
    calls: int
    failed: int
    elapsed_s: float
    kept: Dict[int, Any]
    times: List[float]           # each call's host-clock seconds


def setup(cell) -> State:
    entry, config, traffic = cell.entry, cell.config, cell.traffic
    marks = [time.perf_counter()]
    plan = entry.plan(config, traffic)
    if plan.source != "analytic":
        raise RuntimeError(f"plan came from {plan.source!r}, not the model")
    marks.append(time.perf_counter())
    pool = jax.block_until_ready(
        [entry.operands(jax.random.fold_in(cell.key, i), config, traffic)
         for i in range(traffic["pool"])])
    marks.append(time.perf_counter())
    build = entry.control if cell.variant == "control" else entry.program
    fn = build(plan, config, traffic)
    compiled = fn.lower(*pool[0]).compile()
    marks.append(time.perf_counter())
    for ops in pool:
        jax.block_until_ready(compiled(*ops))
    marks.append(time.perf_counter())
    phases = dict(zip(("plan", "operands", "compile", "warm"),
                      (b - a for a, b in zip(marks, marks[1:]))))
    keep = set(cell.rng.choice(_SAMPLED_AMONG, size=2, replace=False).tolist())
    return State(cell=cell, plan=plan, pool=pool, compiled=compiled, keep=keep,
                 phases=phases,
                 hlo_text=compiled.as_text() if cell.trace else "")


def window(state: State, seconds: float) -> Result:
    span = state.cell.span
    pool, compiled = state.pool, state.compiled
    kept, calls, failed, last, times = {}, 0, 0, None, []
    t0 = time.perf_counter()
    while True:
        i = calls + failed
        t = time.perf_counter()
        try:
            with span("bench.call"):
                out = jax.block_until_ready(compiled(*pool[i % len(pool)]))
        except Exception as e:  # a call that fails is counted, not fatal
            failed += 1
            state.cell.log(f"call {i} failed: {type(e).__name__}: {e}")
        else:
            calls += 1
            if i in state.keep:
                kept[i] = out
            last = (i, out)
        now = time.perf_counter()
        times.append(now - t)
        if now - t0 >= seconds:
            break
    elapsed = time.perf_counter() - t0
    if last is not None:
        kept[last[0]] = last[1]
    return Result(calls=calls, failed=failed, elapsed_s=elapsed, kept=kept,
                  times=times)


def check(state: State, res: Result) -> Dict[str, float]:
    """The entry's numbers over every kept answer; the compiled program is
    released first, so that the reference has its memory."""
    entry, cell = state.cell.entry, state.cell
    state.compiled = None
    by_op: Dict[int, list] = {}
    for i, out in sorted(res.kept.items()):
        by_op.setdefault(i % len(state.pool), []).append(entry.answer(out))
    res.kept.clear()
    numbers: Dict[str, float] = {}
    for j, answers in sorted(by_op.items()):
        for name, v in entry.check(state.pool[j], answers, state.plan,
                                   cell.config, cell.traffic).items():
            numbers[name] = max(numbers.get(name, 0.0), v)
    return numbers


def counts(res: Result):
    """(attempted, failed, lost): a failed call's answer never comes."""
    return res.calls + res.failed, res.failed, res.failed


def end_to_end(res: Result) -> Dict[str, float]:
    return {"call_s": res.elapsed_s / res.calls if res.calls else float("inf")}


def layer_inputs(state: State, res: Result) -> dict:
    """The compiled text for the work counter; and, for the info line, the
    plan's predicted time, the set-up's steps and the fastest, median and
    slowest call."""
    t = sorted(res.times) or [float("nan")]
    return {"hlo_text": state.hlo_text,
            "predicted_s": getattr(state.plan, "predicted_s", None),
            "setup_phases_s": state.phases,
            "call_s_min_med_max": [t[0], t[len(t) // 2], t[-1]]}

