"""Tests for the repro.tune planning subsystem.

Covers the acceptance contract of the tune PR: plan determinism for a given
cache state, JSON cache round-tripping, out-invariant algorithm choice
(packed results stay bitwise equal to dense under default planning), the
measured autotuner always sweeping the hardcoded default, and the consumers
actually honoring a Plan.
"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import tune
from repro.core import ata, strassen_tn
from repro.core.reference import ata_flops, strassen_tn_flops
from repro.tune import cost, defaults
from repro.tune.cache import load_cache, plan_key, save_cache


@pytest.fixture(autouse=True)
def _fresh_memo(tmp_path, monkeypatch):
    """Isolate every test from the user-level cache file and the memo."""
    monkeypatch.setenv("REPRO_TUNE_CACHE", str(tmp_path / "plans.json"))
    tune.cache.clear_memo()
    yield
    tune.cache.clear_memo()


# --- cost model -------------------------------------------------------------


def test_analytic_plan_basic_sanity():
    p = tune.plan(op="ata", m=2048, n=2048)
    assert p.op == "ata" and p.k == 2048
    assert p.algorithm in ("dense", "strassen", "winograd")
    assert p.n_base in defaults.N_BASE_CANDIDATES
    assert p.predicted_s > 0
    assert p.source == "analytic"
    # CPU container: no native Pallas
    assert p.backend != "tpu" or p.use_kernels


def test_cost_model_prefers_recursion_at_scale():
    """The paper's claim must survive the model: at large n the ATA
    recursion beats one classical dot on every backend model."""
    for backend in ("cpu", "tpu"):
        p = cost.analytic_plan("ata", 8192, 8192, backend=backend)
        assert p.algorithm != "dense", backend


def test_cost_model_degenerates_to_dense_dispatch_for_tiny_shapes():
    """Tiny problems must not pay recursion overhead: either an explicit
    dense plan or a cutoff at least the matrix size (same dispatch)."""
    p = cost.analytic_plan("ata", 64, 64, backend="cpu")
    assert p.algorithm == "dense" or p.n_base >= 64


def test_predicted_seconds_monotone_in_problem_size():
    small = cost.analytic_plan("ata", 512, 512).predicted_s
    big = cost.analytic_plan("ata", 4096, 4096).predicted_s
    assert big > small


def test_flop_split_matches_reference_totals():
    """mult + add == the exact reference counters, for both ops."""
    for algo in ("strassen", "winograd"):
        mult, adds = cost._flop_split("ata", algo, 1024, 768, 768, 128)
        total = ata_flops(1024, 768, 128, winograd=algo == "winograd")
        assert mult + adds == total
        mult, adds = cost._flop_split("gemm_tn", algo, 512, 384, 256, 64)
        if algo == "strassen":
            assert mult + adds == strassen_tn_flops(512, 384, 256, 64)


def test_out_invariant_algorithm_choice():
    """Packed and dense plans of one problem must dispatch identically, so
    packed output stays bitwise equal to dense regardless of cache state."""
    for m, n in [(300, 200), (1024, 1024), (4096, 512)]:
        pd = tune.plan(op="ata", m=m, n=n, out="dense")
        pp = tune.plan(op="ata", m=m, n=n, out="packed")
        assert (pd.algorithm, pd.n_base) == (pp.algorithm, pp.n_base)


# --- cache ------------------------------------------------------------------


def test_plan_deterministic_for_fixed_cache_state():
    p1 = tune.plan(op="ata", m=1024, n=512)
    tune.cache.clear_memo()  # force a re-resolution from the same state
    p2 = tune.plan(op="ata", m=1024, n=512)
    assert p1 == p2


def test_plan_json_roundtrip(tmp_path):
    p = tune.plan(op="ata", m=777, n=333, out="packed")
    d = json.loads(json.dumps(p.to_json()))
    assert cost.Plan.from_json(d) == p

    path = str(tmp_path / "c.json")
    key = plan_key("ata", 777, 333, 333, 0, "float32", "packed", p.backend)
    save_cache({key: dataclasses.replace(p, source="measured")}, path)
    loaded = load_cache(path)
    assert loaded[key] == dataclasses.replace(p, source="measured")


def test_measured_cache_entry_is_served(tmp_path):
    """A persisted measured plan must shadow the analytic model (that is
    the point of the cache) and survive the JSON round trip."""
    path = str(tmp_path / "c.json")
    analytic = tune.plan(op="ata", m=640, n=640, cache_file=path)
    fake = dataclasses.replace(
        analytic, n_base=128, source="measured", measured_s=1e-3
    )
    key = plan_key("ata", 640, 640, 640, 0, "float32", "dense", analytic.backend)
    save_cache({key: fake}, path)
    tune.cache.clear_memo()
    served = tune.plan(op="ata", m=640, n=640, cache_file=path)
    assert served.n_base == 128 and served.source == "cache"


def test_corrupt_cache_file_falls_back_to_analytic(tmp_path):
    path = str(tmp_path / "broken.json")
    with open(path, "w") as f:
        f.write("{not json")
    p = tune.plan(op="ata", m=512, n=256, cache_file=path)
    assert p.source == "analytic"


def test_corrupt_cache_entries_are_skipped_not_fatal(tmp_path):
    """Regression: a hand-edited or truncated *entry* (KeyError on a missing
    field, ValueError on a non-dict value, TypeError on schema drift) must
    be skipped by load_cache, not crash every planned dispatch."""
    path = str(tmp_path / "edited.json")
    good = dataclasses.replace(
        tune.plan(op="ata", m=640, n=320), source="measured"
    )
    key_good = plan_key("ata", 640, 320, 320, 0, "float32", "dense", good.backend)
    payload = {
        "schema": "v1",
        "plans": {
            key_good: good.to_json(),
            "k_truncated": {"op": "ata", "m": 1, "n": 1},       # KeyError
            "k_not_a_dict": "garbage string entry",             # ValueError
            "k_schema_drift": dict(good.to_json(), bogus=1),    # TypeError
        },
    }
    with open(path, "w") as f:
        json.dump(payload, f)
    loaded = load_cache(path)
    assert set(loaded) == {key_good}
    assert loaded[key_good] == good
    # and the front door serves the surviving measured entry
    tune.cache.clear_memo()
    served = tune.plan(op="ata", m=640, n=320, cache_file=path)
    assert served.source == "cache"


def test_old_schema_cache_files_still_load_and_serve(tmp_path):
    """Regression (schema bumps v1→v2 op='solve', v2→v3 fused leaves,
    v3→v4 comm_schedule): an old cache file — old schema tag,
    old-prefixed keys, Plan entries WITHOUT later fields — must keep
    loading and serving its measured plans (same tolerance contract as
    the corrupt-entry fix: never fatal)."""
    key_now = plan_key("ata", 640, 640, 640, 0, "float32", "dense", "cpu")
    assert key_now.startswith("v4|")
    for old in ("v1", "v2", "v3"):
        path = str(tmp_path / f"{old}.json")
        p = dataclasses.replace(
            tune.plan(op="ata", m=640, n=640), n_base=128,
            source="measured", measured_s=1e-3,
        )
        key_old = old + "|" + key_now.split("|", 1)[1]
        # pre-v4 keys had no row-devices segment either
        key_old = key_old.replace("|r=1", "")
        entry = p.to_json()
        del entry["comm_schedule"]  # the fields did not exist pre-v4
        del entry["row_devices"]
        if old == "v1":
            del entry["method"]  # the field did not exist pre-PR-5
        with open(path, "w") as f:
            json.dump({"schema": old, "plans": {key_old: entry}}, f)

        loaded = load_cache(path)
        # the old key migrates to the current prefix (r=1 inserted),
        # missing fields default
        assert set(loaded) == {key_now}
        if old == "v1":
            assert loaded[key_now].method is None
        assert loaded[key_now].comm_schedule is None
        assert loaded[key_now].n_base == 128

        tune.cache.clear_memo()
        served = tune.plan(op="ata", m=640, n=640, cache_file=path)
        assert served.source == "cache" and served.n_base == 128


@pytest.mark.parametrize("leaf_dispatch", ["hypercube", "fused"])
def test_unknown_leaf_dispatch_in_cache_falls_back_to_unrolled(tmp_path,
                                                               leaf_dispatch):
    """Regression (fused-leaf PR hardening): a cache entry written by a
    *future* schema may carry a leaf_dispatch this revision has never heard
    of, and an older one may carry 'fused', a dispatch this revision no
    longer has. Loading must sanitize either to 'unrolled' (always valid,
    bitwise-identical output), not raise at every planned dispatch — the
    same never-fatal contract as the corrupt-entry tolerance."""
    path = str(tmp_path / "future.json")
    p = dataclasses.replace(
        tune.plan(op="ata", m=640, n=640), n_base=256,
        leaf_dispatch=leaf_dispatch, source="measured", measured_s=1e-3,
    )
    key = plan_key("ata", 640, 640, 640, 0, "float32", "dense", p.backend)
    with open(path, "w") as f:
        json.dump({"schema": "v3", "plans": {key: p.to_json()}}, f)

    loaded = load_cache(path)
    assert loaded[key].leaf_dispatch == "unrolled"
    assert loaded[key].n_base == 256  # the rest of the entry survives

    # and the front door serves a plan the recursion actually accepts
    tune.cache.clear_memo()
    served = tune.plan(op="ata", m=640, n=640, cache_file=path)
    assert served.source == "cache" and served.leaf_dispatch == "unrolled"
    rng = np.random.default_rng(7)
    a = jnp.asarray(rng.standard_normal((96, 80)), jnp.float32)
    got = ata(a, plan=served)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(a.T @ a), rtol=2e-4, atol=2e-4
    )


def test_unknown_comm_schedule_in_cache_sanitizes_to_psum(tmp_path):
    """Regression (BFS/DFS PR hardening): a cache entry written by a future
    schema may carry an interleaving string this revision's
    bfs_dfs_assignment has never heard of. Loading must sanitize it to
    None — the psum schedule, always valid and bitwise-identical — not
    raise at every planned dispatch."""
    path = str(tmp_path / "future.json")
    p = dataclasses.replace(
        tune.plan(op="ata", m=640, n=640), n_base=256,
        comm_schedule="BQX", source="measured", measured_s=1e-3,
    )
    key = plan_key("ata", 640, 640, 640, 0, "float32", "dense", p.backend)
    with open(path, "w") as f:
        json.dump({"schema": "v4", "plans": {key: p.to_json()}}, f)

    loaded = load_cache(path)
    assert loaded[key].comm_schedule is None
    assert loaded[key].n_base == 256  # the rest of the entry survives

    # a *valid* future-ish interleaving is preserved verbatim
    with open(path, "w") as f:
        json.dump({"schema": "v4", "plans": {
            key: dataclasses.replace(p, comm_schedule="BDB").to_json()}}, f)
    assert load_cache(path)[key].comm_schedule == "BDB"

    # and the front door serves the sanitized plan
    with open(path, "w") as f:
        json.dump({"schema": "v4", "plans": {key: p.to_json()}}, f)
    tune.cache.clear_memo()
    served = tune.plan(op="ata", m=640, n=640, cache_file=path)
    assert served.source == "cache" and served.comm_schedule is None


# --- BFS/DFS comm planning --------------------------------------------------


def test_bfs_tiling_pool_divisible_triangle():
    """The BFS grid's tile triangle must divide the merged device pool
    (tri-direct reduce-scatter chunks exactly; packed retrieval is an
    identity slice) while keeping the usual tiling invariants."""
    from repro.tune.cost import bfs_tiling

    for n in (160, 512, 777, 1024, 4096):
        for pool in (1, 2, 3, 4, 6, 8, 16):
            nb, w = bfs_tiling(n, pool)
            t = nb * (nb + 1) // 2
            if pool > 1:
                assert t % pool == 0, (n, pool, nb)
            assert nb * w >= n
            assert w % 8 == 0


def test_bfs_tiling_balances_bfs_assignment():
    """With ``devices`` given, the grid search penalizes triangles whose
    BFS subgroup split leaves a device group over-assigned (extra tiles
    beyond the ideal ceil(T/devices) makespan, weighted by tile area).
    The chosen grid's imbalance cost never exceeds the device-blind
    choice's, and strictly improves on it at the bench mesh — nb=15's 'B'
    split over-assigns by 6 tiles at 4 devices; the search moves to
    nb=16 (2 extra)."""
    from repro.tune.cost import _bfs_makespan, bfs_tiling

    def extra_cost(nb, w, devices):
        t = nb * (nb + 1) // 2
        return (_bfs_makespan(nb, devices, "B") - -(-t // devices)) * w * w

    nb_blind, w_blind = bfs_tiling(1024, 8)
    for devices in (2, 4, 8):
        nb, w = bfs_tiling(1024, 8, devices=devices)
        assert extra_cost(nb, w, devices) <= \
            extra_cost(nb_blind, w_blind, devices), (devices, nb)
    nb4, w4 = bfs_tiling(1024, 8, devices=4)
    assert extra_cost(nb4, w4, 4) < extra_cost(nb_blind, w_blind, 4)


def test_planner_selects_bfs_interleaving():
    """Acceptance: the *planner* — not a hardcoded string — picks the BFS
    schedule at every multi-device bench mesh (the comm model prices the
    tri-direct scatter under the psum schedule's all-reduce + diag-gather),
    and keeps the psum schedule on a single device."""
    from repro.tune import cost

    for devices, row_devices in ((2, 4), (4, 2), (8, 1), (2, 1), (4, 1)):
        for out in ("dense", "packed"):
            top = cost.candidates("ata", 1024, 1024, out=out,
                                  devices=devices, row_devices=row_devices)[0]
            assert top.comm_schedule and "B" in top.comm_schedule, \
                (devices, row_devices, out, top.comm_schedule)
    single = cost.candidates("ata", 1024, 1024, out="packed", devices=1)[0]
    assert single.comm_schedule is None


def test_comm_model_prices_bfs_under_psum_at_bench_meshes():
    """The alpha-beta totals behind the selection above: at the bench
    meshes the one-chunk tri-direct scatter undercuts the psum schedule's
    row all-reduce + root gather + diag-symmetrization gather."""
    from repro.core.distributed import choose_tiling
    from repro.tune.cost import bfs_tiling, comm_seconds, machine_for

    mach = machine_for("cpu")
    for devices, row_devices in ((2, 4), (4, 2), (8, 1)):
        pool = devices * row_devices
        nb_b, w_b = bfs_tiling(1024, pool, devices=devices)
        nb_d, w_d = choose_tiling(1024, devices, out="packed")
        b = comm_seconds(mach, "B", nb_b, w_b, devices, row_devices,
                         out="packed")
        d = comm_seconds(mach, None, nb_d, w_d, devices, row_devices,
                         out="packed")
        assert b < d, (devices, row_devices, b, d)



def test_machine_for_refuses_an_unknown_backend():
    with pytest.raises(ValueError, match="nonsense"):
        cost.machine_for("nonsense")


def test_tpu_machine_refuses_an_attached_chip_of_another_kind(monkeypatch):
    """The tpu model states the chip it was set for: with no TPU attached it
    prices plans as stated; an attached TPU of another kind is refused."""
    mach = cost.machine_for("tpu")
    assert "TPU v5 lite" in mach.device_kinds

    class Chip:
        device_kind = "TPU v4"

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "devices", lambda *a: [Chip()])
    with pytest.raises(ValueError, match="TPU v4"):
        cost.machine_for("tpu")
    Chip.device_kind = "TPU v5 lite"
    assert cost.machine_for("tpu") == mach

# --- autotune ---------------------------------------------------------------


@pytest.mark.slow
def test_autotune_persists_and_beats_or_matches_default(tmp_path):
    path = str(tmp_path / "tuned.json")
    p = tune.plan(op="ata", m=256, n=256, autotune=True, cache_file=path)
    assert p.source == "measured"
    assert p.measured_s is not None and p.measured_s > 0
    # persisted and re-served from the file
    tune.cache.clear_memo()
    again = tune.plan(op="ata", m=256, n=256, autotune=True, cache_file=path)
    assert again.source == "cache"
    assert (again.algorithm, again.n_base) == (p.algorithm, p.n_base)


def test_autotune_keeps_default_unless_candidate_beats_margin(monkeypatch):
    """The default plan is the reference of every interleaved comparison:
    a candidate that only wins within noise (≤ margin) must NOT displace
    it, and one that clearly wins must."""
    base = cost.default_plan("ata", 96, 96)

    def paired(ratio):
        # fake time_ratio: default takes `ratio`, candidate takes 1.0
        def fake(fa, fb, *a, **kw):
            return ratio, ratio, 1.0

        return fake

    monkeypatch.setattr(tune.search, "time_fn", lambda *a, **kw: 1.0)
    # candidate faster, but only by 10% — inside the noise margin: keep default
    monkeypatch.setattr(tune.search, "time_ratio", paired(1.10))
    kept = tune.search.autotune("ata", 96, 96, max_candidates=3)
    assert tune.search._same_dispatch(kept, base)
    assert kept.source == "measured"
    # candidate 2x faster — clearly outside noise: take it
    monkeypatch.setattr(tune.search, "time_ratio", paired(2.0))
    tuned = tune.search.autotune("ata", 96, 96, max_candidates=3)
    assert not tune.search._same_dispatch(tuned, base)
    assert tuned.baseline_s == 2.0 and tuned.measured_s == 1.0


def test_autotune_refreshes_default_dispatch_memo(tmp_path, monkeypatch):
    """After an in-process autotune, default (non-autotune) dispatches of
    the same key must see the measured plan — the cache state changed."""
    path = str(tmp_path / "c.json")
    monkeypatch.setattr(tune.search, "time_fn", lambda *a, **kw: 1.0)
    monkeypatch.setattr(tune.search, "time_ratio", lambda *a, **kw: (2.0, 2.0, 1.0))
    before = tune.plan(op="ata", m=160, n=160, cache_file=path)  # analytic memo
    tuned = tune.plan(op="ata", m=160, n=160, autotune=True, cache_file=path)
    after = tune.plan(op="ata", m=160, n=160, cache_file=path)
    assert before.source == "analytic"
    assert (after.algorithm, after.n_base) == (tuned.algorithm, tuned.n_base)


def test_autotune_distributed_stays_analytic(tmp_path):
    """devices > 1: the autotuner cannot time the distributed schedule, so
    the plan stays analytic (and nothing is persisted)."""
    path = str(tmp_path / "c.json")
    p = tune.plan(op="ata", m=512, n=512, devices=8, autotune=True, cache_file=path)
    assert p.source == "analytic"
    assert p.nb is not None and p.tile_w is not None
    assert tune.cache.load_cache(path) == {}


# --- distributed branch: retrieval bytes + packed-aligned tiling ------------


def test_distributed_tiling_dense_behavior_unchanged():
    """out='dense' must reproduce the historical search exactly (the
    alignment term is constant there) — guards plan stability."""
    for n in [256, 1000, 4096]:
        for p in [1, 2, 4, 8, 16]:
            assert cost.distributed_tiling(n, p) == cost.distributed_tiling(
                n, p, out="dense"
            )


def test_distributed_tiling_packed_snaps_when_balanced():
    """When the packed-grid-aligned stripe count is as balanced as the best
    candidate, packed mode must pick it (pure-slice retrieval)."""
    from repro.core.symmetric import default_block_size

    # n=1024, p=4: nb=8 (w == bn == 128) has waste 0 → aligned must win
    nb, w = cost.distributed_tiling(1024, 4, out="packed")
    assert w == default_block_size(1024, defaults.DEFAULT_PACKED_BLOCK)
    assert nb * w >= 1024 and w % 8 == 0
    # balance still dominates: a misaligned zero-waste tiling beats an
    # aligned one that idles devices (n=512, p=8: aligned T=10 < 2 tiles/dev)
    nb2, w2 = cost.distributed_tiling(512, 8, out="packed")
    t2 = nb2 * (nb2 + 1) // 2
    assert -(-t2 // 8) * 8 - t2 == 0  # zero waste kept


def test_distributed_tiling_packed_never_forfeits_strassen_depth():
    """Alignment must not shrink stripes below the leaf Strassen cutoff
    when a balanced wide tiling exists: at n=4096 the dense search keeps
    w > DEFAULT_N_BASE (one recursion level per tile) and packed mode must
    keep the same depth rather than snapping to 128-wide dots."""
    for p in (1, 4):
        nbd, wd = cost.distributed_tiling(4096, p, out="dense")
        nbp, wp = cost.distributed_tiling(4096, p, out="packed")
        assert wd > defaults.DEFAULT_N_BASE
        assert wp > defaults.DEFAULT_N_BASE, (p, nbp, wp)
        assert (nbp, wp) == (nbd, wd)


def test_distributed_retrieval_bytes_packed_halves_dense():
    for n, p in [(1024, 4), (2048, 8), (512, 8)]:
        for out in ("dense", "packed"):
            nb, w = cost.distributed_tiling(n, p, out=out)
            t = nb * (nb + 1) // 2
            rb = cost.retrieval_bytes(out, nb, w)
            if out == "packed":
                assert rb == t * w * w * 4
                assert rb < 0.75 * (nb * w) ** 2 * 4  # ≈ half the square
            else:
                assert rb == (nb * w) ** 2 * 4


def test_distributed_plan_prediction_reflects_out_mode():
    """The distributed plan's predicted seconds must price packed retrieval
    below dense replication (same algorithm either way: out-invariance)."""
    pd = tune.plan(op="ata", m=4096, n=2048, devices=8, out="dense")
    pp = tune.plan(op="ata", m=4096, n=2048, devices=8, out="packed")
    assert (pd.algorithm, pd.n_base) == (pp.algorithm, pp.n_base)
    assert pd.nb is not None and pp.nb is not None
    assert pp.predicted_s <= pd.predicted_s


# --- consumers honor the plan ----------------------------------------------


def test_ata_honors_plan_bitwise():
    """ata(plan=p) must equal ata with p's tunables spelled out by hand."""
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.standard_normal((200, 160)), jnp.float32)
    p = dataclasses.replace(
        tune.plan(op="ata", m=200, n=160), algorithm="winograd", n_base=64
    )
    via_plan = ata(a, plan=p)
    by_hand = ata(a, n_base=64, variant="winograd")
    np.testing.assert_array_equal(np.asarray(via_plan), np.asarray(by_hand))


def test_packed_default_plan_bitwise_equals_dense():
    """The acceptance bit: default-planned packed output mirrors to exactly
    the default-planned dense output."""
    rng = np.random.default_rng(1)
    a = jnp.asarray(rng.standard_normal((300, 200)), jnp.float32)
    dense = ata(a)
    packed = ata(a, out="packed")
    np.testing.assert_array_equal(np.asarray(packed.to_dense()), np.asarray(dense))


def test_strassen_tn_honors_plan():
    rng = np.random.default_rng(2)
    a = jnp.asarray(rng.standard_normal((160, 120)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((160, 96)), jnp.float32)
    p = dataclasses.replace(
        tune.plan(op="gemm_tn", m=160, n=120, k=96), algorithm="strassen", n_base=32
    )
    np.testing.assert_array_equal(
        np.asarray(strassen_tn(a, b, plan=p)),
        np.asarray(strassen_tn(a, b, n_base=32, variant="strassen")),
    )


def test_plan_under_jit_and_vmap():
    """Planning happens at trace time: default dispatches must compose with
    jit and vmap (the planner sees the unbatched trace shape)."""
    rng = np.random.default_rng(3)
    a = jnp.asarray(rng.standard_normal((4, 96, 64)), jnp.float32)
    got = jax.jit(jax.vmap(lambda x: ata(x)))(a)
    want = jnp.einsum("bmi,bmj->bij", a, a)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4)


def test_shampoo_unpinned_n_base_runs():
    """Shampoo with planner-dispatched grams still produces finite updates."""
    from repro.optim import constant
    from repro.optim.shampoo import shampoo

    rng = np.random.default_rng(4)
    params = {"w": jnp.asarray(rng.standard_normal((48, 32)), jnp.float32)}
    grads = {"w": jnp.asarray(rng.standard_normal((48, 32)), jnp.float32) * 1e-2}
    opt = shampoo(constant(1e-3), block=16, update_every=1)
    state = opt.init(params)
    u, state = opt.update(grads, state, params)
    assert np.isfinite(np.asarray(u["w"])).all()


def test_distributed_tiling_is_choose_tiling():
    from repro.core.distributed import choose_tiling

    for n, p in [(256, 4), (1000, 8), (4096, 16)]:
        assert choose_tiling(n, p) == cost.distributed_tiling(n, p)


# --- warm / cache_prefetch (the serve layer's bulk pre-warm API) ------------


def test_warm_resolves_analytic_and_seeds_the_memo():
    from repro.tune.cache import cache_stats, warm

    before = cache_stats()
    specs = [dict(op="solve", m=48, n=32, k=4, out="packed"),
             dict(op="ata", m=256, n=128)]
    plans = warm(specs)
    after = cache_stats()
    assert after["warm_miss"] - before["warm_miss"] == 2  # empty cache file
    assert after["warm_hit"] == before["warm_hit"]
    assert [p.op for p in plans] == ["solve", "ata"]      # spec order kept
    # the point of warming: the per-dispatch plan() calls are memo hits
    served = tune.plan(op="solve", m=48, n=32, k=4, out="packed")
    assert served is plans[0]
    assert cache_stats()["memo_hit"] - after["memo_hit"] == 1


def test_warm_serves_persisted_plans_in_one_read(tmp_path):
    from repro.tune.cache import cache_stats, warm

    path = str(tmp_path / "c.json")
    analytic = tune.plan(op="solve", m=96, n=64, k=8, out="packed",
                         cache_file=path)
    key = plan_key("solve", 96, 64, 8, 0, "float32", "packed",
                   analytic.backend, 1, 1)
    save_cache({key: dataclasses.replace(analytic, source="measured")}, path)
    tune.cache.clear_memo()
    before = cache_stats()
    hit, miss = warm([dict(op="solve", m=96, n=64, k=8, out="packed"),
                      dict(op="solve", m=48, n=32, k=4, out="packed")],
                     cache_file=path)
    after = cache_stats()
    assert after["warm_hit"] - before["warm_hit"] == 1
    assert after["warm_miss"] - before["warm_miss"] == 1
    assert hit.source == "cache" and miss.source == "analytic"


def test_warm_never_clobbers_an_existing_memo_entry():
    from repro.tune.cache import cache_stats, warm

    first = tune.plan(op="solve", m=48, n=32, k=4, out="packed")
    before = cache_stats()
    (warmed,) = warm([dict(op="solve", m=48, n=32, k=4, out="packed")])
    assert warmed is first                 # the memoized plan wins
    assert cache_stats()["warm_memo"] - before["warm_memo"] == 1


def test_warm_validates_specs():
    from repro.tune.cache import warm

    with pytest.raises(ValueError, match="unknown op"):
        warm([dict(op="qr", m=8, n=8)])
    with pytest.raises(ValueError, match="unbatched"):
        warm([dict(op="solve", m=8, n=8, batch=4)])
    with pytest.raises(TypeError, match="unknown keys"):
        warm([dict(op="ata", m=8, n=8, block_size=32)])


def test_cache_prefetch_is_warm_and_lazily_exported():
    from repro.tune import cache

    assert cache.cache_prefetch is cache.warm
    assert tune.warm is cache.warm         # repro.tune lazy re-export
