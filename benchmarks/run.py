"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows and, per module, writes a
machine-readable ``BENCH_<key>.json`` (list of ``{name, shape, seconds,
gflops, ...}`` rows — every row stamped with the backend metadata from
``benchmarks.common.backend_meta``) so the perf trajectory is tracked
across PRs.

    PYTHONPATH=src python -m benchmarks.run            # all benches
    PYTHONPATH=src python -m benchmarks.run fig3 fig5  # filter by prefix
    PYTHONPATH=src python -m benchmarks.run --out results/bench
    PYTHONPATH=src python -m benchmarks.run --smoke    # CI-scale subset
    PYTHONPATH=src python -m benchmarks.run --profile  # + obs & traces

``--smoke`` shrinks every module's shape sweep/iteration count
(``common.smoke()``) and skips the subprocess-per-device-count modules
(fig5/fig6) — minutes of wall time instead of tens.

``--profile`` turns the ``repro.obs`` subsystem on for the whole run and
wraps each bench module in ``jax.profiler.trace`` (guarded: containers
whose jax build lacks a working profiler just skip the trace, never
crash), writing trace artifacts under ``<out>/benchmarks/profiles/<key>/``
and one ``BENCH_obs.json`` metrics+calibration snapshot for the run; the
calibration drift report prints at the end (DESIGN.md §8).

After each module, fresh rows are diffed against the **committed**
``BENCH_<key>.json`` baseline (``repro.analysis.perf_diff.bench_diff``)
and the table printed — report-only, never failing, in ``--smoke``/CI runs
included. Cross-machine deltas are flagged via the rows' backend metadata.
"""

from __future__ import annotations

import json
import os
import sys
import traceback

from benchmarks import common

# (display name, module, BENCH json key)
BENCHES = [
    ("fig3_ata_vs_syrk", "benchmarks.bench_ata", "ata"),
    ("fig4_faststrassen_vs_gemm", "benchmarks.bench_strassen", "strassen"),
    ("fig5_shared_memory_scaling", "benchmarks.bench_shared", "shared"),
    ("fig6_distributed_scaling", "benchmarks.bench_distributed", "distributed"),
    ("kernels_pallas", "benchmarks.bench_kernels", "kernels"),
    ("shampoo_integration", "benchmarks.bench_shampoo", "shampoo"),
    ("tune_planner", "benchmarks.bench_tune", "tune"),
    ("solve_normal_equations", "benchmarks.bench_solve", "solve"),
    ("serve_gram_service", "benchmarks.bench_serve", "serve"),
]

# multi-process device sweeps — too slow for the CI smoke job.
# (fig6 is NOT skipped: in smoke mode bench_distributed runs only its
# compile-only packed-vs-dense collective-bytes comparison.)
_SKIP_IN_SMOKE = {"fig5_shared_memory_scaling"}

# committed baselines live next to this package, at the repo root
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_baseline_rows(key: str) -> list:
    try:
        with open(os.path.join(_REPO_ROOT, f"BENCH_{key}.json")) as f:
            return json.load(f).get("rows", [])
    except (OSError, json.JSONDecodeError):
        return []


def _report_diff(key: str, rows: list) -> None:
    """Print the fresh-vs-committed diff table. Report-only by contract:
    any failure here is reported as a note, never propagated."""
    try:
        from repro.analysis.perf_diff import bench_diff, print_bench_diff

        baseline = _load_baseline_rows(key)
        if baseline:
            print_bench_diff(key, bench_diff(baseline, rows))
    except Exception as e:  # pragma: no cover - must never fail the bench
        print(f"# perf diff for {key} unavailable: {type(e).__name__}: {e}")


class _profile_trace:
    """``jax.profiler.trace`` for one bench module, tolerated to fail.

    Interpret-mode CPU containers (and stripped jax builds) can lack a
    working profiler backend; a profiling *bench* run must still produce
    its timing rows, so any profiler error downgrades to a note.
    """

    def __init__(self, log_dir: str):
        self.log_dir = log_dir
        self._active = False

    def __enter__(self):
        try:
            import jax

            os.makedirs(self.log_dir, exist_ok=True)
            jax.profiler.start_trace(self.log_dir)
            self._active = True
        except Exception as e:
            print(f"# profiler trace unavailable: {type(e).__name__}: {e}")
        return self

    def __exit__(self, *exc):
        if self._active:
            try:
                import jax

                jax.profiler.stop_trace()
            except Exception as e:
                print(f"# profiler stop failed: {type(e).__name__}: {e}")
        return False


def main() -> None:
    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    args = sys.argv[1:]
    out_dir = "."
    profile = False
    if "--smoke" in args:
        args.remove("--smoke")
        common.SMOKE = True
        os.environ["REPRO_BENCH_SMOKE"] = "1"  # reaches bench subprocesses
    if "--profile" in args:
        args.remove("--profile")
        profile = True
    if "--out" in args:
        i = args.index("--out")
        if i + 1 >= len(args) or args[i + 1].startswith("-"):
            raise SystemExit(
                "usage: benchmarks.run [--smoke] [--profile] [--out DIR] [filter ...]"
            )
        out_dir = args[i + 1]
        args = args[:i] + args[i + 2 :]
        os.makedirs(out_dir, exist_ok=True)
    if profile:
        from repro import obs

        obs.enable()
    filters = [a for a in args if not a.startswith("-")]
    print("name,us_per_call,derived")
    failed = []
    for name, module, key in BENCHES:
        if filters and not any(f in name for f in filters):
            continue
        if common.SMOKE and not filters and name in _SKIP_IN_SMOKE:
            print(f"# --- {name} skipped (--smoke) ---", flush=True)
            continue
        print(f"# --- {name} ({module}) ---", flush=True)
        common.drain_rows()  # isolate rows per module
        path = os.path.join(out_dir, f"BENCH_{key}.json")
        try:
            mod = __import__(module, fromlist=["run"])
            if profile:
                profile_dir = os.path.join(out_dir, "benchmarks", "profiles", key)
                with _profile_trace(profile_dir):
                    mod.run()
            else:
                mod.run()
        except Exception as e:
            failed.append(name)
            print(f"{name},ERROR,{type(e).__name__}: {e}", flush=True)
            traceback.print_exc()
            # never leave a stale passing JSON behind a failed bench
            with open(path, "w") as f:
                json.dump(
                    {"error": f"{type(e).__name__}: {e}", "rows": common.drain_rows()},
                    f, indent=1,
                )
            continue
        rows = common.drain_rows()
        _report_diff(key, rows)  # diff BEFORE overwriting a root baseline
        with open(path, "w") as f:
            json.dump({"rows": rows}, f, indent=1)
        print(f"# wrote {path} ({len(rows)} rows)", flush=True)
    if profile:
        from repro import obs

        obs_path = os.path.join(out_dir, "BENCH_obs.json")
        obs.metrics.export_json(obs_path)
        print(f"# wrote {obs_path}", flush=True)
        print(obs.report(), flush=True)
    if failed:
        raise SystemExit(f"benchmarks failed: {failed}")


if __name__ == "__main__":
    main()
