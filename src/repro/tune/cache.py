"""Persistent plan cache + the ``repro.tune.plan(...)`` front door.

Resolution order for one problem key:

1. **in-process memo** — every resolved Plan is memoized, so a jit trace
   that dispatches the same shape hundreds of times pays for planning once;
2. **JSON cache file** — *measured* plans persist across processes, keyed
   by ``(op, m, n, k, batch, dtype, out, backend, devices, jax version)``
   (see :func:`plan_key`; the jax version is in the key because a runtime
   upgrade can move the Strassen crossover);
3. **analytic model** (`tune.cost.analytic_plan`) on a cache miss — or the
   **measured autotuner** (`tune.search.autotune`) when ``autotune=True``,
   whose result is written back to the JSON cache.

Only measured plans are persisted: the analytic model is deterministic and
free to recompute, so writing it to disk would only let a stale file shadow
model improvements. Consequently ``plan(...)`` is deterministic for a given
cache state, and a cache file round-trips through JSON bit-exactly
(`Plan.to_json`/`from_json` — tested in ``tests/test_tune.py``).

Cache location: ``$REPRO_TUNE_CACHE`` if set, else
``~/.cache/repro/tune_plans.json``. To fill it, call ``plan(...,
autotune=True)`` per key (``tune.autotune`` measures the candidates and the
winner is written back); :func:`warm` then resolves many keys from the file
in one read (see DESIGN.md §7).
"""

from __future__ import annotations

import json
import logging
import os
import threading
from typing import Optional

import jax

from repro.obs import metrics
from repro.tune import cost, defaults

__all__ = [
    "plan",
    "plan_key",
    "cache_path",
    "load_cache",
    "save_cache",
    "clear_memo",
    "cache_stats",
    "warm",
    "cache_prefetch",
]

_LOG = logging.getLogger("repro.tune.cache")

# metric names of the plan-cache counters (repro.obs.metrics registry) —
# hit/miss count *front-door resolutions* (plan() calls), the load-side
# counters count per-load events (load_cache runs on every non-memo
# resolution, so a migrated entry is counted once per load, not once ever).
_STAT_NAMES = (
    "memo_hit",        # resolved from the in-process memo
    "hit",             # resolved from the persistent JSON cache
    "miss",            # fell through to the analytic model
    "autotuned",       # resolved by a measured autotune run (persisted)
    "migrated",        # old-schema keys migrated on load
    "sanitized",       # unknown-leaf_dispatch entries sanitized on load
    "skipped_entries", # corrupt/undeserializable entries skipped on load
    "load_failure",    # unreadable/corrupt cache file tolerated
    "warm_hit",        # warm(): resolved from the persistent JSON cache
    "warm_miss",       # warm(): fell through to the analytic model
    "warm_memo",       # warm(): key already memoized (left untouched)
)


def cache_stats() -> dict:
    """Current plan-cache counters, ``{short_name: count}``.

    The counters live in the ``repro.obs.metrics`` registry under
    ``tune.cache.<name>`` (always on — see the registry's module
    docstring); this accessor is the stable public view of them.
    """
    return {name: metrics.get(f"tune.cache.{name}") for name in _STAT_NAMES}

_MEMO: dict = {}
_LOCK = threading.Lock()
# v4: the distributed branch gained the `row_devices` key segment
# (``r=<row axis size>``, inserted before ``jax=``) and Plans gained
# `comm_schedule` (the BFS/DFS interleaving string). v3 added the 'fused'
# leaf_dispatch; v2 introduced op='solve' and the `method` field.
# Older-schema ("v1|…".."v3|…") cache files still load: old entries
# deserialize (missing fields default to the psum schedule they were
# measured with) and their keys are migrated on load — prefix swapped and
# the ``r=1`` segment inserted — so old measured plans keep serving.
# Symmetrically, entries written by a *newer* schema may carry
# leaf_dispatch or comm_schedule values this revision has never heard of:
# leaf_dispatch sanitizes to 'unrolled', comm_schedule to None (the psum
# schedule — always valid, bitwise-identical output) instead of raising
# at every planned dispatch.
_SCHEMA = "v4"
_COMPAT_SCHEMAS = ("v1", "v2", "v3")

# every leaf_dispatch this revision's recursions accept (mirrors
# core.strassen.resolve_tunables; kept literal so load never imports jax)
_KNOWN_LEAF_DISPATCHES = ("unrolled", "batched")


def _valid_comm_schedule(cs) -> bool:
    """True iff ``cs`` is a value this revision's schedules accept: None
    (psum) or a non-empty {'B','D'} string (bfs_dfs_assignment's contract)."""
    return cs is None or (
        isinstance(cs, str) and bool(cs) and all(c in "BD" for c in cs)
    )


def cache_path() -> str:
    env = os.environ.get("REPRO_TUNE_CACHE")
    if env:
        return env
    return os.path.join(
        os.path.expanduser("~"), ".cache", "repro", "tune_plans.json"
    )


def plan_key(
    op: str,
    m: int,
    n: int,
    k: int,
    batch: int,
    dtype: str,
    out: str,
    backend: str,
    devices: int = 1,
    row_devices: int = 1,
) -> str:
    """The cache key: problem identity + runtime identity (jax version).

    ``row_devices`` (the reduction-axis size of the two-level distributed
    mesh) joined the key in schema v4 — the BFS/DFS interleaving choice
    depends on it; pre-v4 keys migrate with ``r=1`` on load.
    """
    return (
        f"{_SCHEMA}|{op}|m={m}|n={n}|k={k}|b={batch}|{dtype}|{out}"
        f"|{backend}|p={devices}|r={row_devices}|jax={jax.__version__}"
    )


def load_cache(path: Optional[str] = None) -> dict:
    """{key: Plan} from the JSON file (empty on missing/corrupt file)."""
    path = path or cache_path()
    try:
        with open(path) as f:
            raw = json.load(f)
    except FileNotFoundError:
        # no cache yet is the normal first-run state, not a failure
        return {}
    except (OSError, json.JSONDecodeError) as e:
        # a present-but-unreadable file is tolerated (the analytic model
        # covers every key) but no longer silent: one line names the path
        # and the reason so a corrupt cache stops masquerading as a miss.
        metrics.inc("tune.cache.load_failure")
        _LOG.warning(
            "plan cache %s unreadable (%s: %s); continuing with empty cache",
            path, type(e).__name__, e,
        )
        return {}
    out = {}
    skipped = 0
    for key, d in raw.get("plans", {}).items():
        for old in _COMPAT_SCHEMAS:
            # older-schema keys are migrated in place, so pre-bump measured
            # plans keep serving: prefix swapped to the current schema and
            # (pre-v4 layouts) the row-devices segment inserted with its
            # single-possible historical value.
            if key.startswith(old + "|"):
                key = _SCHEMA + key[len(old):]
                if "|r=" not in key and "|jax=" in key:
                    key = key.replace("|jax=", "|r=1|jax=", 1)
                metrics.inc("tune.cache.migrated")
                break
        try:
            p = cost.Plan.from_json(d)
        except (TypeError, KeyError, ValueError):
            # schema drift (TypeError), truncated/hand-edited entries
            # (KeyError on a missing field, ValueError on a non-dict value):
            # skip the entry; the analytic model covers the key instead of
            # one bad line crashing every planned dispatch in the process.
            skipped += 1
            continue
        if p.leaf_dispatch not in _KNOWN_LEAF_DISPATCHES:
            # a future schema's dispatch value, or one this revision
            # dropped ('fused'): fall back to the always-valid unrolled form (bitwise-identical output) rather than
            # letting resolve_tunables raise on every dispatch of the key.
            import dataclasses

            p = dataclasses.replace(p, leaf_dispatch="unrolled")
            metrics.inc("tune.cache.sanitized")
        if not _valid_comm_schedule(p.comm_schedule):
            # same policy for a future schema's interleaving value: the
            # psum schedule (comm_schedule=None) is always valid and
            # bitwise-identical, so the entry keeps serving instead of
            # bfs_dfs_assignment raising on every planned dispatch.
            import dataclasses

            p = dataclasses.replace(p, comm_schedule=None)
            metrics.inc("tune.cache.sanitized")
        out[key] = p
    if skipped:
        metrics.inc("tune.cache.skipped_entries", skipped)
        _LOG.warning(
            "plan cache %s: skipped %d undeserializable entr%s",
            path, skipped, "y" if skipped == 1 else "ies",
        )
    return out


def save_cache(plans: dict, path: Optional[str] = None) -> str:
    path = path or cache_path()
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    payload = {
        "schema": _SCHEMA,
        "plans": {key: p.to_json() for key, p in sorted(plans.items())},
    }
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(payload, f, indent=1, sort_keys=True)
    os.replace(tmp, path)
    return path


def clear_memo() -> None:
    """Drop the in-process memo (tests; cache-file experiments)."""
    with _LOCK:
        _MEMO.clear()


def warm(specs, *, cache_file: Optional[str] = None) -> list:
    """Bulk-resolve plan keys into the in-process memo in ONE file read.

    The pre-warm API of the serve layer: a server warming dozens of
    buckets would otherwise pay one ``load_cache`` (a full JSON parse) per
    ``plan()`` miss. ``warm`` reads the cache file once, resolves every
    spec against it (persisted plan → ``source='cache'``, else the
    analytic model — the same resolution ``plan()`` performs without
    ``autotune``), and installs the results in the memo so the subsequent
    per-dispatch ``plan()`` calls are memo hits.

    Args:
      specs: iterable of dicts of ``plan()`` keyword arguments, e.g.
        ``{"op": "solve", "m": 96, "n": 64, "k": 8, "out": "packed"}``
        (defaults match ``plan()``: ``k=n``, ``batch=0``,
        ``dtype='float32'``, ``out='dense'``, backend auto).
      cache_file: cache path override (default: :func:`cache_path`).

    Returns:
      The resolved Plans, in spec order. Counters: ``warm_hit`` /
      ``warm_miss`` per resolution, ``warm_memo`` when a key was already
      memoized (the memoized plan wins — warm never clobbers, so an
      autotuned plan resolved earlier in the process keeps serving).
    """
    persisted = load_cache(cache_file)      # the ONE file read
    resolved_plans = []
    for spec in specs:
        kw = dict(spec)
        op = kw.pop("op", "ata")
        if op not in ("ata", "gemm_tn", "solve"):
            raise ValueError(
                f"unknown op {op!r}; use 'ata', 'gemm_tn' or 'solve'")
        m, n = kw.pop("m"), kw.pop("n")
        k = kw.pop("k", None)
        k = n if k is None else k
        batch = kw.pop("batch", 0)
        if op == "solve" and batch:
            raise ValueError("op='solve' plans are unbatched (lstsq is 2-D); "
                             f"got batch={batch}")
        dtype = kw.pop("dtype", "float32")
        out = kw.pop("out", "dense")
        backend = kw.pop("backend", None) or jax.default_backend()
        devices = kw.pop("devices", 1)
        row_devices = kw.pop("row_devices", 1)
        if kw:
            raise TypeError(f"warm spec has unknown keys {sorted(kw)}")
        key = plan_key(op, m, n, k, batch, dtype, out, backend, devices,
                       row_devices)
        hit = persisted.get(key)
        if hit is not None:
            import dataclasses

            metrics.inc("tune.cache.warm_hit")
            resolved = dataclasses.replace(hit, source="cache")
        else:
            metrics.inc("tune.cache.warm_miss")
            resolved = cost.analytic_plan(
                op, m, n, k, batch=batch, dtype=dtype, out=out,
                backend=backend, devices=devices, row_devices=row_devices,
            )
        memo_key = (key, cache_file, False)
        with _LOCK:
            if memo_key in _MEMO:
                metrics.inc("tune.cache.warm_memo")
                resolved = _MEMO[memo_key]
            else:
                _MEMO[memo_key] = resolved
        resolved_plans.append(resolved)
    return resolved_plans


# the serve layer's historical name for the same operation
cache_prefetch = warm


def plan(
    op: str = "ata",
    *,
    m: int,
    n: int,
    k: Optional[int] = None,
    batch: int = 0,
    dtype: str = "float32",
    out: str = "dense",
    backend: Optional[str] = None,
    devices: int = 1,
    row_devices: int = 1,
    autotune: bool = False,
    cache_file: Optional[str] = None,
) -> cost.Plan:
    """The front door: one frozen Plan for every ATA dispatch.

    Args:
      op: ``'ata'`` (``C = AᵀA``), ``'gemm_tn'`` (``C = AᵀB``), or
        ``'solve'`` (the normal-equations pipeline of ``repro.solve`` —
        the plan then carries ``method`` ∈ {'factor', 'cg'}).
      m, n, k: operand shape — A is (m, n), B is (m, k); k defaults to n.
        For ``op='solve'``, k is the right-hand-side count.
      batch: leading batch size (0 = unbatched).
      dtype: operand dtype string (``str(a.dtype)``).
      out: ``'dense'`` or ``'packed'`` output.
      backend: defaults to ``jax.default_backend()``.
      devices: task-axis size for the distributed schedules (fills the
        plan's ``nb``/``tile_w`` stripe tiling — the planner's distributed
        branch).
      row_devices: row (reduction) axis size of the two-level distributed
        mesh — with ``devices > 1`` the planner prices the BFS/DFS
        interleaving candidates against it (α-β communication model +
        per-device memory budget) and fills ``plan.comm_schedule``.
      autotune: measure candidates instead of trusting the analytic model;
        the winner persists to the JSON cache for future processes.
        Single-device only — with ``devices > 1`` the plan stays analytic
        (the autotuner cannot time the distributed schedule).
      cache_file: cache path override (default: :func:`cache_path`).

    Returns:
      A frozen, JSON-serializable :class:`repro.tune.cost.Plan`.
    """
    if op not in ("ata", "gemm_tn", "solve"):
        raise ValueError(f"unknown op {op!r}; use 'ata', 'gemm_tn' or 'solve'")
    if op == "solve" and batch:
        # lstsq takes one 2-D design matrix; a batched solve plan would be
        # unexecutable (and untimeable by the autotuner)
        raise ValueError("op='solve' plans are unbatched (lstsq is 2-D); "
                         f"got batch={batch}")
    backend = backend or jax.default_backend()
    k = n if k is None else k
    key = plan_key(op, m, n, k, batch, dtype, out, backend, devices,
                   row_devices)
    memo_key = (key, cache_file, autotune)

    with _LOCK:
        hit = _MEMO.get(memo_key)
    if hit is not None:
        metrics.inc("tune.cache.memo_hit")
        return hit

    measured_now = False
    persisted = load_cache(cache_file).get(key)
    if persisted is not None and (persisted.source == "measured" or not autotune):
        import dataclasses

        metrics.inc("tune.cache.hit")
        resolved = dataclasses.replace(persisted, source="cache")
    elif autotune and devices == 1:
        from repro.tune import search

        metrics.inc("tune.cache.autotuned")
        resolved = search.autotune(
            op, m, n, k, batch=batch, dtype=dtype, out=out,
            backend=backend, devices=devices,
        )
        plans = load_cache(cache_file)
        plans[key] = resolved
        save_cache(plans, cache_file)
        measured_now = True
    else:
        # devices > 1 with autotune lands here too: the autotuner's timed
        # callable is the single-device op, which says nothing about the
        # distributed tile schedule — distributed plans stay analytic.
        metrics.inc("tune.cache.miss")
        resolved = cost.analytic_plan(
            op, m, n, k, batch=batch, dtype=dtype, out=out,
            backend=backend, devices=devices, row_devices=row_devices,
        )

    with _LOCK:
        _MEMO[memo_key] = resolved
        if measured_now:
            # the cache state just changed: refresh the non-autotune memo
            # slot so default dispatches in THIS process see the measured
            # plan, exactly as a fresh process reading the file would.
            _MEMO[(key, cache_file, False)] = resolved
    return resolved
