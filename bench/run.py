#!/usr/bin/env python3
"""One run of one benchmark cell, on the chips of the machine it starts on.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is a workload of ``BENCHMARK.json``; its configuration, traffic
and per-layer metrics are found by name (``bench/cells.py``). The run makes
its inputs on the device from ``--seed``, compiles and warms every shape
the window uses (set-up, ``setup_s``), then measures for ``--seconds``.
With ``--trace 0`` it reports the cell's end-to-end metrics; with
``--trace 1`` it turns on the program's named scopes (``repro.obs``),
profiles the window and reports the per-layer metrics read from the trace.

Once the window has closed it compares what the timed path produced with
the plain reference (``bench/reference``) and prints each number compared
beside its limit, as the last lines on standard error and under ``checks``
in the result. The last line on standard output is one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (and with
``--trace 1``, ``breakdown``), then ``checks``.

It exits nonzero, and prints no result, where JAX finds no TPU or fewer
chips than the cell asks for. JAX's compilation cache is kept in
``<checkout>/.jax_cache``, so that only a checkout's first run compiles.
"""

from __future__ import annotations

import time

_T_IMPORT = time.time()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402
from typing import Any, Callable, Dict, Optional  # noqa: E402

_HERE = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.dirname(_HERE)
# Run as a script, Python puts bench/ first on the path, where the module
# names of its files (trace) would shadow the standard library's.
if sys.path and os.path.abspath(sys.path[0] or ".") == _HERE:
    sys.path.pop(0)
for _p in (os.path.join(_ROOT, "src"), _ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import numpy as np  # noqa: E402

from bench import cells, peaks, trace as tracing, work  # noqa: E402

CACHE_DIR = os.path.join(_ROOT, ".jax_cache")


def process_start() -> float:
    """Wall-clock time at which this process started (10 ms resolution)."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        age = uptime - ticks / os.sysconf("SC_CLK_TCK")
        if 0.0 <= age < 3600.0:
            return time.time() - age
    except (OSError, ValueError, IndexError):
        pass
    return _T_IMPORT


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


@dataclasses.dataclass
class Cell:
    """What a driver sees of the run: the cell's files and the run's knobs."""

    name: str
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    entry: Any
    seed: int
    key: Any
    rng: np.random.Generator
    seconds: float
    trace: bool
    variant: str                 # "program", or "control" for bench/control.py
    span: Callable
    log: Callable = log


def make_cell(spec: cells.Spec, seed: int, seconds: float, trace: bool,
              variant: str = "program") -> Cell:
    import jax

    seed32 = int(np.random.SeedSequence(seed).generate_state(1)[0])
    return Cell(name=spec.name, config=spec.config, traffic=spec.traffic,
                entry=spec.entry, seed=seed, key=jax.random.key(seed32),
                rng=np.random.default_rng(seed), seconds=seconds, trace=trace,
                variant=variant, span=jax.profiler.TraceAnnotation)


def enable_compile_cache() -> None:
    import jax

    os.makedirs(CACHE_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def tpu_devices(chips: int):
    """The TPU devices, or ``None`` where JAX finds no TPU or too few."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        log(f"bench: no TPU (JAX sees {len(devs)} {devs[0].platform!r} "
            "device(s)); the benchmark runs only on a TPU")
        return None
    if len(devs) < chips:
        log(f"bench: the cell asks for {chips} chips, JAX sees {len(devs)}")
        return None
    return devs


@dataclasses.dataclass
class Context:
    """What a per-layer metric's reader reads."""

    reduced: Optional[tracing.Reduced]
    launches: Dict[str, work.Launch]     # instruction name -> Pallas launch
    peaks: Dict[str, float]
    inputs: Dict[str, Any]               # the driver's host measurements


def within(check: dict) -> bool:
    """A number passes when it is at or under its limit; an unset limit
    passes nothing."""
    return check["limit"] is not None and check["value"] <= check["limit"]


def memory_peak(devices) -> Optional[int]:
    vals = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    vals = [v for v in vals if v is not None]
    return max(vals) if vals else None


def run_cell(spec: cells.Spec, seed: int, seconds: float, trace: bool, *,
             devices, start: float) -> dict:
    """Set up, measure and check one cell; returns the result object."""
    import jax
    from repro import obs

    if trace:
        obs.enable()
    cell = make_cell(spec, seed, seconds, trace)
    drv = spec.driver
    started_s = time.time() - start
    state = drv.setup(cell)
    setup_s = time.time() - start
    log(f"bench: set up in {setup_s:.1f} s")

    with tracing.record() if trace else contextlib.nullcontext([]) as recorded:
        with jax.profiler.TraceAnnotation("bench.window"):
            res = drv.window(state, seconds)

    log(f"bench: window closed at {time.time() - start:.1f} s")
    peak = memory_peak(devices)
    attempted, failed, lost = drv.counts(res)
    e2e = dict(drv.end_to_end(res), setup_s=setup_s)
    inputs = drv.layer_inputs(state, res)
    numbers = drv.check(state, res)
    del state, res
    log(f"bench: checked at {time.time() - start:.1f} s")

    limits = spec.config["limits"]
    checks = {}
    for name, value in numbers.items():
        if name not in limits:
            raise KeyError(f"number {name!r} has no limit in the configuration")
        checks[name] = {"value": value, "limit": limits[name]}
    correct = (lost == 0 and bool(checks)
               and all(within(c) for c in checks.values()))

    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    out = {"correct": correct, "attempted": attempted, "failed": failed}
    if not trace:
        out["metrics"] = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                          for m in spec.end_to_end}
    else:
        hlo_text = inputs.get("hlo_text", "")
        launches = {l.name: l for l in work.parse_launches(hlo_text)}
        log(f"bench: trace of {os.path.getsize(recorded[0])} bytes")
        red = tracing.reduce(tracing.load(recorded[0]), hlo_text)
        tracing.remove(recorded)
        log(f"bench: trace reduced at {time.time() - start:.1f} s: "
            f"{len(red.ops)} device ops")
        ctx = Context(reduced=red, launches=launches,
                      peaks=peaks.peaks_for(device["kind"]), inputs=inputs)
        metrics = {}
        for m in spec.per_layer:
            value = cells.load_metric(m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        out["metrics"] = metrics
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
        out["breakdown"] = tracing.breakdown(
            red, {n: l.kernel for n, l in launches.items()})
    out["device"] = device
    out["checks"] = checks
    out["info"] = {"end_to_end": e2e, "started_s": started_s,
                   **{k: v for k, v in inputs.items() if k != "hlo_text"}}
    return out


def emit(out: dict) -> None:
    """The info line, the checks on stderr, and the result line last."""
    info = out.pop("info", {})
    print("info " + json.dumps(info), flush=True)
    for name, c in out["checks"].items():
        log(f"check {name}={c['value']!r} limit={c['limit']!r} "
            f"{'ok' if within(c) else 'FAIL'}")
    checks = out.pop("checks")
    out["checks"] = checks                 # the key of its own, last
    print(json.dumps(out), flush=True)


def main(argv=None) -> int:
    start = process_start()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    spec = cells.resolve(args.workload)
    enable_compile_cache()
    devices = tpu_devices(spec.chips)
    if devices is None:
        return 2
    try:
        out = run_cell(spec, args.seed, args.seconds, bool(args.trace),
                       devices=devices[:spec.chips], start=start)
    except Exception:
        traceback.print_exc()
        return 1
    emit(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
