"""Tests for the ``repro.obs`` observability subsystem (DESIGN.md §8).

The load-bearing guarantee: spans never change the program. Their named
scopes are always compiled in and are metadata only — same jaxpr, same
stripped StableHLO, bitwise-same values, obs on or off — and they reach
``compiled.as_text()`` even through the persistent cache. Plus JAX's
compile steps attributed to the program that holds a root span, the
registry/calibration contracts, and the `analysis.hlo.collective_bytes`
edge cases the metrics wiring depends on.
"""

import contextlib
import dataclasses
import functools
import gc
import json
import logging
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs, tune
from repro.analysis.hlo import collective_bytes
from repro.core.ata import ata
from repro.core.strassen import strassen_tn
from repro.obs import calibrate, compiles, metrics, trace
from repro.tune import cache as tune_cache
from repro.tune import cost


@pytest.fixture(autouse=True)
def _clean_obs():
    """Every test starts disabled with empty registries and leaves no state."""
    was_enabled = trace.enabled()
    trace.disable()
    trace.reset()
    compiles.reset()
    metrics.reset()
    calibrate.reset()
    yield
    trace.enable() if was_enabled else trace.disable()
    trace.reset()
    compiles.reset()
    metrics.reset()
    calibrate.reset()


def _rng(shape, seed=0):
    return jnp.asarray(
        np.random.default_rng(seed).standard_normal(shape), jnp.float32
    )


# ---------------------------------------------------------------------------
# spans: disabled = the named scope alone; enabled = zero jaxpr ops
# ---------------------------------------------------------------------------


def test_span_disabled_is_shared_noop():
    # disabled, a span is the named scope and nothing of the recording half:
    # no _Span, no count, no event, no root time
    s1 = obs.span("ata", attr=1)
    assert not isinstance(s1, trace._Span)
    with s1:
        pass
    assert trace.span_counts() == {} and trace.span_events() == []
    assert trace.root_spans() == []

    def f(x):
        with obs.span("demo.scope", attr=2):
            return x * 2

    low = jax.jit(f).lower(jnp.ones(3)).as_text(debug_info=True)
    assert "demo.scope/mul" in low
    assert trace.span_counts() == {}


def test_spans_add_zero_ops_to_jaxpr():
    a = _rng((96, 64))

    # two distinct function objects: jax caches traces per (fun, args), so
    # reusing one would hand back the first trace without re-entering ata
    def f_off(x):
        return ata(x, n_base=16, variant="strassen", leaf_dispatch="batched")

    def f_on(x):
        return ata(x, n_base=16, variant="strassen", leaf_dispatch="batched")

    jaxpr_off = jax.make_jaxpr(f_off)(a)
    trace.enable()
    try:
        jaxpr_on = jax.make_jaxpr(f_on)(a)
        assert trace.span_counts()  # spans really fired during tracing
    finally:
        trace.disable()
    assert len(jaxpr_off.eqns) == len(jaxpr_on.eqns)
    assert str(jaxpr_off) == str(jaxpr_on)


def test_enabled_results_bitwise_identical():
    a = _rng((80, 48))
    b = _rng((80, 32), seed=1)
    off_ata = ata(a, n_base=16, variant="strassen")
    off_tn = strassen_tn(a, b, n_base=16, variant="strassen")
    trace.enable()
    try:
        on_ata = ata(a, n_base=16, variant="strassen")
        on_tn = strassen_tn(a, b, n_base=16, variant="strassen")
    finally:
        trace.disable()
    np.testing.assert_array_equal(np.asarray(off_ata), np.asarray(on_ata))
    np.testing.assert_array_equal(np.asarray(off_tn), np.asarray(on_tn))


def test_span_nesting_depth_and_events():
    trace.enable()
    try:
        with obs.span("outer", k=1):
            with obs.span("inner"):
                pass
        with obs.span("outer"):
            pass
    finally:
        trace.disable()
    assert trace.span_counts() == {"outer": 2, "inner": 1}
    events = trace.span_events()
    assert ("outer", 0, {"k": 1}) in events
    assert ("inner", 1, {}) in events


def test_level_spans_cover_every_recursion_level():
    a = _rng((128, 128))
    trace.enable()
    try:
        ata(a, n_base=32, variant="strassen", leaf_dispatch="batched")
    finally:
        trace.disable()
    spans = trace.span_counts()
    L = 2  # 128 / 2^2 = 32 = n_base
    # stable names, the level in the attrs
    assert spans["ata.encode"] == L and spans["ata.decode"] == L
    levels = {(name, attrs["level"]) for name, _, attrs in trace.span_events()
              if name in ("ata.encode", "ata.decode")}
    for lev in range(1, L + 1):
        assert ("ata.encode", lev) in levels
        assert ("ata.decode", lev) in levels
    assert "ata.leaf_dot" in spans and "ata.syrk_batch" in spans


# ---------------------------------------------------------------------------
# scopes always compiled in: metadata only, through the persistent cache
# ---------------------------------------------------------------------------

_LOC = re.compile(r'loc\("([^"]*)"')
_SHAPE = (300, 200)   # L = 3 at n_base 32; 300 rows pad to 304


def _packed_program(leaf_dispatch):
    """A new jitted planned packed ``ata`` (a new function object, so no
    trace cache answers for it)."""
    plan = dataclasses.replace(
        tune.plan(op="ata", m=_SHAPE[0], n=_SHAPE[1], dtype="float32",
                  out="packed"),
        algorithm="winograd" if leaf_dispatch == "unrolled" else "strassen",
        n_base=32, leaf_dispatch=leaf_dispatch)
    return jax.jit(lambda a: ata(a, plan=plan, out="packed"))


@functools.lru_cache(maxsize=None)
def _lowered_op_names(leaf_dispatch):
    a = jax.ShapeDtypeStruct(_SHAPE, jnp.float32)
    text = _packed_program(leaf_dispatch).lower(a).as_text(debug_info=True)
    return frozenset(_LOC.findall(text))


@pytest.mark.parametrize("leaf_dispatch,scope", [
    ("unrolled", "strassen.encode"), ("unrolled", "strassen.decode"),
    ("unrolled", "ata.slab_sum"), ("unrolled", "ata.pad"),
    ("unrolled", "ata.pack"), ("unrolled", "ata.rec"),
    ("batched", "ata.encode"), ("batched", "ata.decode"),
    ("batched", "ata.pack"),
])
def test_scopes_compiled_in_with_obs_off(leaf_dispatch, scope):
    assert not trace.enabled()
    names = _lowered_op_names(leaf_dispatch)
    assert any(f"/{scope}/" in n for n in names), sorted(names)[:20]


def test_scopes_leave_the_stripped_stablehlo_unchanged(monkeypatch):
    a = jax.ShapeDtypeStruct(_SHAPE, jnp.float32)
    scoped = _packed_program("unrolled").lower(a)
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = _packed_program("unrolled").lower(a)
    assert "/ata.pack/" in scoped.as_text(debug_info=True)
    assert "/ata.pack/" not in bare.as_text(debug_info=True)
    assert scoped.as_text() == bare.as_text()


@pytest.fixture
def fresh_compile_cache(tmp_path):
    """JAX's persistent cache in an empty directory, every program kept."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    keys = ("jax_enable_compilation_cache", "jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    was = {k: getattr(jax.config, k) for k in keys}
    cc.reset_cache()
    for k, v in zip(keys, (True, str(tmp_path / "cache"), 0.0, 0)):
        jax.config.update(k, v)
    yield
    for k, v in was.items():
        jax.config.update(k, v)
    cc.reset_cache()


def test_scopes_survive_the_persistent_cache(fresh_compile_cache):
    a = jax.ShapeDtypeStruct(_SHAPE, jnp.float32)
    _packed_program("unrolled").lower(a).compile()
    assert metrics.get("jax.cache_hits") == 0
    jax.clear_caches()
    loaded = _packed_program("unrolled").lower(a).compile()
    assert metrics.get("jax.cache_hits") >= 1
    text = loaded.as_text()
    for scope in ("strassen.encode", "strassen.decode", "ata.slab_sum",
                  "ata.pad", "ata.pack"):
        assert re.search(rf'op_name="[^"]*/{re.escape(scope)}/', text), scope


# ---------------------------------------------------------------------------
# JAX's compile steps, attributed to the program that holds a root span
# ---------------------------------------------------------------------------


def test_compile_steps_belong_to_the_program_that_calls_ata():
    def make_operand(key):
        return jax.random.normal(key, (96, 64))

    def program(a):
        return ata(a, n_base=16, variant="winograd", leaf_dispatch="unrolled",
                   out="packed").blocks

    def reference(a):
        return a.T @ a

    trace.enable()
    try:
        a = jax.jit(make_operand)(jax.random.key(0))
        compiled = jax.jit(program).lower(a).compile()
        compiled(a)
        jax.jit(reference)(a)
    finally:
        trace.disable()
    (prog,) = compiles.programs()
    assert prog.fun_name == "program" and prog.roots == ("ata",)
    evs = compiles.events()
    own = {e.kind: e for e in evs if e.fun_name in ("program", "jit(program)")}
    assert set(own) == {"trace", "lower", "compile"}
    assert prog.trace_s == own["trace"].end - own["trace"].start
    assert prog.lower_s == own["lower"].end - own["lower"].start
    assert prog.compile_s == own["compile"].end - own["compile"].start
    # the maker before and the reference after were compiled, and are not it
    others = {e.fun_name for e in evs if e.kind == "compile"}
    assert {"jit(make_operand)", "jit(reference)"} <= others


def test_programs_rule_on_a_recorded_list():
    E = compiles.Event
    evs = [
        E("trace", "make", 0.0, 1.0), E("lower", "jit(make)", 1.0, 2.0),
        E("compile", "jit(make)", 2.0, 3.0),
        E("trace", "step", 4.0, 6.0),                 # holds the root span
        E("lower", "jit(step)", 6.5, 7.0),
        E("cache_hit", "", 7.5, 7.5),
        E("compile", "jit(step)", 7.25, 8.0),
        E("trace", "ref", 9.0, 9.5), E("lower", "jit(ref)", 9.5, 9.8),
        E("compile", "jit(ref)", 9.8, 11.0),
    ]
    roots = [("solve.lstsq", 4.1, 5.9), ("ata", 4.2, 5.0)]
    (prog,) = compiles.programs(evs, roots)
    assert prog == compiles.Program(
        fun_name="step", roots=("solve.lstsq", "ata"), trace_s=2.0,
        lower_s=0.5, compile_s=0.75, cache="hit")
    assert compiles.programs(evs, []) == []


def test_compile_listener_keeps_outermost_steps_and_their_cache_events():
    on = compiles._on_span
    t = time.time()
    on("/jax/core/compile/jaxpr_trace_duration", t + 0.1, t + 0.2,
       fun_name="add")                            # a jnp helper inside
    on("/jax/core/compile/jaxpr_trace_duration", t, t + 1.0, fun_name="f")
    on("/jax/core/compile/jaxpr_trace_duration", t + 1.2, t + 1.3,
       fun_name="threefry")                       # traced while lowering
    on("/jax/core/compile/jaxpr_to_mlir_module_duration", t + 1.1, t + 1.5,
       fun_name="jit(f)")
    c0 = time.time()
    compiles._on_event("/jax/compilation_cache/cache_misses")
    on("/jax/core/compile/backend_compile_duration", c0, time.time() + 1.0,
       fun_name="jit(f)")
    assert [(e.kind, e.fun_name) for e in compiles.events()] == [
        ("trace", "f"), ("lower", "jit(f)"), ("cache_miss", ""),
        ("compile", "jit(f)")]
    assert metrics.get("jax.cache_misses") == 1


def test_span_overflow_evicts_no_compile_step(monkeypatch):
    monkeypatch.setattr(trace, "MAX_EVENTS", 3)
    a = _rng((96, 64))
    trace.enable()
    try:
        jax.jit(lambda x: ata(x, n_base=16, variant="winograd",
                              leaf_dispatch="unrolled")).lower(a).compile()
    finally:
        trace.disable()
    assert len(trace.span_events()) == 3 < sum(trace.span_counts().values())
    (prog,) = compiles.programs()
    assert prog.lower_s > 0 and prog.compile_s > 0


def test_gc_pauses_recorded_only_while_enabled():
    assert trace._on_gc not in gc.callbacks
    gc.collect()
    assert "host.gc_s" not in metrics.histograms()
    trace.enable()
    try:
        assert trace._on_gc in gc.callbacks
        gc.collect()
        gc.collect()
    finally:
        trace.disable()
    assert trace._on_gc not in gc.callbacks
    h = metrics.histograms()["host.gc_s"]
    assert h["count"] >= 2 and h["min"] >= 0.0


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------


def test_metrics_registry_roundtrip(tmp_path):
    metrics.inc("x.count")
    metrics.inc("x.count", 4)
    metrics.set_gauge("x.gauge", 2.5)
    for v in (1.0, 3.0, 2.0):
        metrics.observe("x.hist", v)
    assert metrics.get("x.count") == 5
    assert metrics.counters("x.") == {"x.count": 5}
    assert metrics.gauges()["x.gauge"] == 2.5
    h = metrics.histograms()["x.hist"]
    assert (h["count"], h["sum"], h["min"], h["max"]) == (3, 6.0, 1.0, 3.0)

    snap = metrics.validate_snapshot(metrics.snapshot())
    out = metrics.export_json(str(tmp_path / "obs.json"))
    with open(out) as f:
        disk = json.load(f)
    assert disk["schema"] == metrics.SNAPSHOT_SCHEMA
    assert disk["counters"] == snap["counters"]


def test_validate_snapshot_rejects_bad_schema():
    snap = metrics.snapshot()
    snap["schema"] = "bogus"
    with pytest.raises(ValueError, match="schema"):
        metrics.validate_snapshot(snap)
    with pytest.raises(ValueError, match="meta"):
        metrics.validate_snapshot({"schema": metrics.SNAPSHOT_SCHEMA})


def test_record_collective_bytes_folds_into_registry():
    hlo = "%ar = f32[16,16]{1,0} all-reduce(f32[16,16]{1,0} %p0)"
    by_kind = metrics.record_collective_bytes(hlo)
    assert by_kind == {"all-reduce": 16 * 16 * 4}
    assert metrics.get("collective_bytes.all-reduce") == 16 * 16 * 4


def test_dispatch_counters_always_on():
    a = _rng((64, 48))
    ata(a, n_base=16, variant="strassen", leaf_dispatch="unrolled")
    assert metrics.get("dispatch.ata.unrolled") == 1
    assert metrics.get("ata.leaves.syrk") > 0
    b = _rng((64, 24), seed=2)
    strassen_tn(a, b, n_base=16, variant="strassen", leaf_dispatch="batched")
    assert metrics.get("dispatch.gemm_tn.batched") == 1
    assert metrics.get("gemm_tn.leaves") >= 7


# ---------------------------------------------------------------------------
# calibration
# ---------------------------------------------------------------------------


def _plan(predicted=1e-3, **kw):
    base = cost.default_plan("ata", 256, 128, backend="cpu")
    import dataclasses

    return dataclasses.replace(base, predicted_s=predicted, **kw)


def test_calibrate_records_against_prediction():
    calibrate.record(_plan(), 2e-3)
    calibrate.record(_plan(predicted=None), 5.0)   # no prediction: skipped
    calibrate.record(_plan(), -1.0)                # non-positive: skipped
    rows = calibrate.rows()
    assert len(rows) == 1
    table = calibrate.drift_table()
    assert table[0]["ratio"] == pytest.approx(2.0)
    assert "geomean measured/predicted" in calibrate.report()


def test_calibrate_drift_aggregates_per_key():
    for meas in (2e-3, 8e-3):
        calibrate.record(_plan(), meas)
    (g,) = calibrate.drift_table(backend="cpu")
    assert g["n"] == 2
    assert g["measured_s"] == pytest.approx(2e-3)   # min over rows
    assert g["ratio"] == pytest.approx(4.0)         # geomean of 2 and 8


# ---------------------------------------------------------------------------
# plan-cache counters (tune.cache satellite)
# ---------------------------------------------------------------------------


def test_cache_stats_miss_then_memo_hit(tmp_path):
    cache_file = str(tmp_path / "plans.json")
    tune_cache.clear_memo()
    tune_cache.plan(op="ata", m=512, n=256, cache_file=cache_file)
    stats = tune_cache.cache_stats()
    assert stats["miss"] == 1 and stats["memo_hit"] == 0
    tune_cache.plan(op="ata", m=512, n=256, cache_file=cache_file)
    assert tune_cache.cache_stats()["memo_hit"] == 1


def test_cache_load_failure_counted_and_logged(tmp_path, caplog):
    bad = tmp_path / "corrupt.json"
    bad.write_text("{not json")
    with caplog.at_level(logging.WARNING, logger="repro.tune.cache"):
        assert tune_cache.load_cache(str(bad)) == {}
    assert tune_cache.cache_stats()["load_failure"] == 1
    assert any("unreadable" in r.message for r in caplog.records)
    # a missing file stays the silent first-run path, not a failure
    caplog.clear()
    assert tune_cache.load_cache(str(tmp_path / "absent.json")) == {}
    assert tune_cache.cache_stats()["load_failure"] == 1
    assert not caplog.records


def test_cache_migration_sanitization_and_skip_counters(tmp_path, caplog):
    plan = cost.default_plan("ata", 128, 128, backend="cpu")
    good = plan.to_json()
    weird = dict(good, leaf_dispatch="quantum")
    payload = {
        "schema": "v4",
        "plans": {
            "v1|ata|old-schema-key": good,       # migrated
            "v4|ata|weird-dispatch": weird,      # sanitized
            "v4|ata|broken": {"nonsense": 1},    # skipped
        },
    }
    path = tmp_path / "plans.json"
    path.write_text(json.dumps(payload))
    with caplog.at_level(logging.WARNING, logger="repro.tune.cache"):
        plans = tune_cache.load_cache(str(path))
    assert set(plans) == {"v4|ata|old-schema-key", "v4|ata|weird-dispatch"}
    assert plans["v4|ata|weird-dispatch"].leaf_dispatch == "unrolled"
    stats = tune_cache.cache_stats()
    assert stats["migrated"] == 1
    assert stats["sanitized"] == 1
    assert stats["skipped_entries"] == 1
    assert any("skipped 1" in r.message for r in caplog.records)


# ---------------------------------------------------------------------------
# analysis.hlo collective-bytes edge cases
# ---------------------------------------------------------------------------


def test_collective_bytes_async_tuple_start_counts_output_only():
    # async tuple form: (operand, result) — the operand element aliases the
    # input buffer and must not be double-counted
    hlo = """
  %ag.s = (f32[32,64]{1,0}, f32[128,64]{1,0}) all-gather-start(f32[32,64] %p), dim=0
  %ag.d = f32[128,64]{1,0} all-gather-done((f32[32,64], f32[128,64]) %ag.s)
"""
    got = collective_bytes(hlo)
    assert got["all-gather"] == 128 * 64 * 4


def test_collective_bytes_async_nontuple_start_and_done_dedup():
    hlo = """
  %ar.s = bf16[64,64]{1,0} all-reduce-start(bf16[64,64]{1,0} %p)
  %ar.d = bf16[64,64]{1,0} all-reduce-done(bf16[64,64]{1,0} %ar.s)
"""
    assert collective_bytes(hlo)["all-reduce"] == 64 * 64 * 2


def test_collective_bytes_variadic_tuple_sums_all_elements():
    hlo = (
        "%aa = (f32[8,8]{1,0}, bf16[4,4]{1,0}, s8[16]{0}) "
        "all-to-all(f32[8,8] %a, bf16[4,4] %b, s8[16] %c)"
    )
    got = collective_bytes(hlo)
    assert got["all-to-all"] == 8 * 8 * 4 + 4 * 4 * 2 + 16


def test_collective_bytes_unknown_dtypes_skipped():
    hlo = """
  %t = token[] all-reduce(token[] %tok)
  %m = (f32[4]{0}, token[]) all-to-all(f32[4] %x, token[] %tok)
"""
    got = collective_bytes(hlo)
    assert got["all-reduce"] == 0
    assert got["all-to-all"] == 4 * 4   # token element contributes nothing


def test_collective_bytes_start_tuple_with_context_elements():
    # some async lowerings append context/scratch elements after the result
    hlo = (
        "%cp.s = (u8[16]{0}, u8[16]{0}, u32[], u32[]) "
        "collective-permute-start(u8[16] %x)"
    )
    assert collective_bytes(hlo)["collective-permute"] == 16


# ---------------------------------------------------------------------------
# snapshot composition: spans + calibration ride along
# ---------------------------------------------------------------------------


def test_snapshot_includes_spans_and_calibration():
    trace.enable()
    try:
        with obs.span("demo"):
            pass
    finally:
        trace.disable()
    calibrate.record_pair("k", "ata", "cpu", 1e-3, 2e-3)
    snap = metrics.validate_snapshot(metrics.snapshot())
    assert snap["spans"] == {"demo": 1}
    assert snap["calibration"][0]["key"] == "k"
