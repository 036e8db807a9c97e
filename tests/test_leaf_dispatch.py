"""Batched-leaf dispatch: bitwise parity with the unrolled recursion,
jaxpr-size regressions, and plan threading.

The contract of the two leaf dispatches:

* ``leaf_dispatch='batched'`` is **bitwise-equal** to ``'unrolled'`` on
  the same plan, for ``strassen_tn``/``ata``/``ata_batched``, across odd
  and rectangular shapes, both variants, dense and packed output, and
  alpha/c/beta accumulation;
* the batched dispatch emits **O(levels)** dots (one batched TN gemm + one
  batched syrk for the whole ATA tree), not O(7^L) — a jaxpr regression
  test;
* the planner carries the choice (``Plan.leaf_dispatch``): candidates
  enumerate both, JSON round-trips it, pre-leaf_dispatch cache entries
  deserialize to ``'unrolled'``, and the overhead pricing makes the
  dispatches distinguishable to the analytic model. No other value
  (``'fused'`` included) is accepted.
"""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import tune
from repro.core import ata, ata_batched, strassen_tn
from repro.core.strassen import tree_depth
from repro.tune import cost, defaults

jax.config.update("jax_enable_x64", True)


def rng(seed=0):
    return np.random.default_rng(seed)


def _bitwise(a, b):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# bitwise parity
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("variant", ["strassen", "winograd"])
@pytest.mark.parametrize(
    "m,n,k",
    [
        (64, 64, 64),
        (128, 96, 80),   # rectangular
        (67, 53, 41),    # odd everywhere
        (100, 200, 50),  # tall/wide mix
        (33, 1, 7),      # degenerate (L = 0: both dispatches ARE one dot)
    ],
)
def test_strassen_batched_bitwise_equals_unrolled(variant, m, n, k):
    r = rng(hash((m, n, k)) % 2**32)
    a = jnp.asarray(r.standard_normal((m, n)))
    b = jnp.asarray(r.standard_normal((m, k)))
    kw = dict(n_base=8, variant=variant, acc_dtype=jnp.float64)
    _bitwise(
        strassen_tn(a, b, leaf_dispatch="unrolled", **kw),
        strassen_tn(a, b, leaf_dispatch="batched", **kw),
    )


def test_strassen_batched_alpha_beta_accumulate_bitwise():
    r = rng(1)
    a = jnp.asarray(r.standard_normal((32, 24)))
    b = jnp.asarray(r.standard_normal((32, 40)))
    c = jnp.asarray(r.standard_normal((24, 40)))
    kw = dict(alpha=2.5, c=c, beta=-0.5, n_base=8, acc_dtype=jnp.float64)
    got = strassen_tn(a, b, leaf_dispatch="batched", **kw)
    _bitwise(strassen_tn(a, b, leaf_dispatch="unrolled", **kw), got)
    np.testing.assert_allclose(got, 2.5 * (a.T @ b) - 0.5 * c, rtol=1e-9, atol=1e-9)


@pytest.mark.parametrize("variant", ["strassen", "winograd"])
@pytest.mark.parametrize("m,n", [(64, 64), (67, 53), (200, 100), (257, 129)])
def test_ata_batched_leaf_bitwise_equals_unrolled(variant, m, n):
    r = rng(hash((m, n, variant)) % 2**32)
    a = jnp.asarray(r.standard_normal((m, n)))
    kw = dict(n_base=8, variant=variant, acc_dtype=jnp.float64)
    dense_u = ata(a, leaf_dispatch="unrolled", **kw)
    dense_b = ata(a, leaf_dispatch="batched", **kw)
    _bitwise(dense_u, dense_b)
    np.testing.assert_allclose(dense_b, a.T @ a, rtol=1e-9, atol=1e-9)
    # packed: the packed *blocks* must agree bitwise, not just to_dense()
    pu = ata(a, leaf_dispatch="unrolled", out="packed", packed_block=32, **kw)
    pb = ata(a, leaf_dispatch="batched", out="packed", packed_block=32, **kw)
    _bitwise(pu.blocks, pb.blocks)
    _bitwise(pb.to_dense(), dense_b)


def test_ata_alpha_beta_accumulation_bitwise_both_outs():
    from repro.core import SymmetricMatrix

    r = rng(2)
    a = jnp.asarray(r.standard_normal((96, 80)))
    c_dense = jnp.asarray(r.standard_normal((80, 80)))
    kw = dict(alpha=0.25, n_base=16, acc_dtype=jnp.float64)
    _bitwise(
        ata(a, c=c_dense, beta=2.0, leaf_dispatch="unrolled", **kw),
        ata(a, c=c_dense, beta=2.0, leaf_dispatch="batched", **kw),
    )
    c_packed = SymmetricMatrix.from_dense(
        jnp.asarray(c_dense + c_dense.T), 32
    )
    pu = ata(a, c=c_packed, beta=2.0, out="packed", packed_block=32,
             leaf_dispatch="unrolled", **kw)
    pb = ata(a, c=c_packed, beta=2.0, out="packed", packed_block=32,
             leaf_dispatch="batched", **kw)
    _bitwise(pu.blocks, pb.blocks)


@pytest.mark.parametrize("out", ["dense", "packed"])
def test_ata_batched_op_bitwise_equals_unrolled(out):
    """The (B, m, n) gram entry point, both output modes."""
    r = rng(11)
    a = jnp.asarray(r.standard_normal((5, 48, 28)))
    kw = dict(n_base=8, acc_dtype=jnp.float64, out=out)
    if out == "packed":
        kw["packed_block"] = 16
    u = ata_batched(a, leaf_dispatch="unrolled", **kw)
    b = ata_batched(a, leaf_dispatch="batched", **kw)
    if out == "packed":
        _bitwise(u.blocks, b.blocks)
    else:
        _bitwise(u, b)
        np.testing.assert_allclose(
            b, jnp.einsum("bmi,bmj->bij", a, a), rtol=1e-9, atol=1e-9
        )


def test_batched_under_jit_and_grad():
    r = rng(3)
    a = jnp.asarray(r.standard_normal((64, 48)))
    f = jax.jit(
        lambda a: ata(a, n_base=16, leaf_dispatch="batched", acc_dtype=jnp.float64)
    )
    _bitwise(f(a), ata(a, n_base=16, leaf_dispatch="unrolled", acc_dtype=jnp.float64))
    g = jax.grad(
        lambda a: strassen_tn(
            a, a, n_base=16, leaf_dispatch="batched", acc_dtype=jnp.float64
        ).sum()
    )(a)
    g_ref = jax.grad(lambda a: (a.T @ a).sum())(a)
    np.testing.assert_allclose(g, g_ref, rtol=1e-9, atol=1e-9)


# ---------------------------------------------------------------------------
# jaxpr-size regression: O(levels) dots, not O(7^L)
# ---------------------------------------------------------------------------


def _dot_count(fn, *args):
    from repro import check

    jaxpr = jax.make_jaxpr(fn)(*args)
    return sum(1 for s in check.walk_eqns(jaxpr.jaxpr)
               if s.eqn.primitive.name == "dot_general")


def test_batched_ata_emits_two_dots():
    """The whole ATA tree = ONE batched syrk + ONE batched TN gemm."""
    a = jnp.zeros((256, 256), jnp.float32)
    n_dots_b = _dot_count(lambda x: ata(x, n_base=32, leaf_dispatch="batched"), a)
    n_dots_u = _dot_count(lambda x: ata(x, n_base=32, leaf_dispatch="unrolled"), a)
    assert n_dots_b == 2, n_dots_b
    # the unrolled tree really is leaf-per-op: 4^3 = 64 syrk leaves plus
    # Σ_ℓ 2^{2ℓ-1}·7^{3-ℓ} = 186 Strassen leaves — and the dispatch_calls
    # counter the cost model prices is exactly that jaxpr dot count
    s, g = cost._ata_leaves(256, 256, 32)
    assert (s, g) == (64, 186)
    assert n_dots_u == s + g, (n_dots_u, s, g)


def test_batched_strassen_emits_one_dot_and_scales_by_levels():
    a = jnp.zeros((512, 512), jnp.float32)
    b = jnp.zeros((512, 512), jnp.float32)
    for n_base, leaves in [(256, 7), (128, 49), (64, 343)]:
        nb_dots = _dot_count(
            lambda x, y: strassen_tn(x, y, n_base=n_base, leaf_dispatch="batched"),
            a, b,
        )
        nu_dots = _dot_count(
            lambda x, y: strassen_tn(x, y, n_base=n_base, leaf_dispatch="unrolled"),
            a, b,
        )
        assert nb_dots == 1, (n_base, nb_dots)
        assert nu_dots == leaves, (n_base, nu_dots)


def test_batched_jaxpr_total_size_grows_linearly_not_geometrically():
    """Total eqn count of the batched dispatch is O(levels): deepening the
    recursion by a level adds the same band of encode/decode ops every
    time, while the unrolled jaxpr multiplies by ~7."""
    a = jnp.zeros((512, 512), jnp.float32)
    b = jnp.zeros((512, 512), jnp.float32)

    def eqns(n_base, ld):
        jaxpr = jax.make_jaxpr(
            lambda x, y: strassen_tn(x, y, n_base=n_base, leaf_dispatch=ld)
        )(a, b)
        return len(jaxpr.jaxpr.eqns)

    sizes = [eqns(n_base, "batched") for n_base in (256, 128, 64, 32)]
    growth = {sizes[i + 1] - sizes[i] for i in range(3)}
    assert len(growth) == 1 and growth.pop() > 0, sizes  # one band a level
    u2, u3 = eqns(128, "unrolled"), eqns(64, "unrolled")
    assert u3 > 5 * u2                    # geometric growth


# ---------------------------------------------------------------------------
# planner threading
# ---------------------------------------------------------------------------


@pytest.fixture
def _fresh_memo(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_TUNE_CACHE", str(tmp_path / "plans.json"))
    tune.cache.clear_memo()
    yield
    tune.cache.clear_memo()


def test_candidates_enumerate_leaf_dispatch():
    cands = cost.candidates("gemm_tn", 4096, 4096, 4096, backend="cpu")
    lds = {(c.algorithm, c.leaf_dispatch) for c in cands}
    assert any(ld == "batched" for _, ld in lds)
    assert any(ld == "unrolled" for _, ld in lds)
    # the two dispatches and nothing else; dense has nothing to batch
    assert {ld for _, ld in lds} == {"unrolled", "batched"}
    assert ("dense", "batched") not in lds


def test_overhead_pricing_separates_the_dispatches():
    """With thousands of leaves, unrolled must be priced above batched on
    the machine models whose stack charge is the nominal write+read (that
    is the launch-overhead term the batched dispatch removes). The cpu
    model is the deliberate exception: its measured stack_word_cost (≈5.5,
    cache-thrash dominated) outweighs even 7^6 thunk launches at depth 6 —
    matching the measured cpu ranking where deep batched trails deep
    unrolled. These two are the whole dispatch axis the planner prices."""
    assert defaults.LEAF_DISPATCH_CANDIDATES == ("unrolled", "batched")
    for backend in ("cpu", "tpu", "gpu"):
        pu, pb = (
            cost.predict_seconds(
                "gemm_tn", "strassen", 8192, 8192, 8192, 128,
                backend=backend, leaf_dispatch=ld,
            )
            for ld in ("unrolled", "batched")
        )
        calls = cost.dispatch_calls(
            "gemm_tn", "strassen", 8192, 8192, 8192, 128, "unrolled"
        )
        assert calls == 7 ** 6
        if backend == "cpu":
            assert pb > pu, backend  # recalibrated: stacks beat launches
        else:
            assert pu > pb, backend


def test_dispatch_calls_counts():
    assert cost.dispatch_calls("gemm_tn", "dense", 1024, 1024, 1024, 512, "unrolled") == 1
    assert cost.dispatch_calls("gemm_tn", "strassen", 1024, 1024, 1024, 256, "unrolled") == 49
    # batched: 2 leaf calls + O(levels) encode/decode stack ops
    assert cost.dispatch_calls("gemm_tn", "strassen", 1024, 1024, 1024, 256, "batched") == 10
    s, g = cost._ata_leaves(1024, 1024, 256)
    assert cost.dispatch_calls("ata", "strassen", 1024, 1024, 1024, 256, "unrolled") == s + g


def test_plan_json_roundtrip_and_legacy_entries(_fresh_memo):
    p = tune.plan(op="ata", m=777, n=333)
    d = json.loads(json.dumps(p.to_json()))
    assert "leaf_dispatch" in d
    assert cost.Plan.from_json(d) == p
    # a pre-leaf_dispatch cache entry must deserialize to 'unrolled' —
    # exactly the dispatch it was measured with
    legacy = dict(d)
    legacy.pop("leaf_dispatch")
    assert cost.Plan.from_json(legacy).leaf_dispatch == "unrolled"


def test_autotuner_distinguishes_leaf_dispatch():
    """_same_dispatch must treat the two dispatches as different (they time
    differently), so a batched candidate can displace the unrolled default."""
    from repro.tune.search import _same_dispatch

    base = cost.default_plan("ata", 512, 512)
    flipped = dataclasses.replace(base, leaf_dispatch="batched")
    assert not _same_dispatch(base, flipped)
    # the autotuner times only what the planner enumerates: the two
    # dispatches, no 'fused'
    assert {c.leaf_dispatch for c in cost.candidates("ata", 4096, 4096)} == {
        "unrolled", "batched"}


def test_ata_honors_plan_leaf_dispatch_bitwise(_fresh_memo):
    """ata(plan=p) with p.leaf_dispatch='batched' must equal the explicit
    kwarg — and both must equal the unrolled dispatch bitwise."""
    r = rng(7)
    a = jnp.asarray(r.standard_normal((200, 160)), jnp.float32)
    p = dataclasses.replace(
        tune.plan(op="ata", m=200, n=160),
        algorithm="strassen", n_base=64, leaf_dispatch="batched",
    )
    via_plan = ata(a, plan=p)
    by_hand = ata(a, n_base=64, variant="strassen", leaf_dispatch="batched")
    _bitwise(via_plan, by_hand)
    _bitwise(via_plan, ata(a, n_base=64, variant="strassen", leaf_dispatch="unrolled"))
    # a plan naming the removed 'fused' dispatch is refused, not run
    with pytest.raises(ValueError, match="leaf_dispatch"):
        ata(a, plan=dataclasses.replace(p, leaf_dispatch="fused"))


def test_root_pad_hoist_depth_matches_legacy_recursion():
    """tree_depth reproduces the legacy per-level pad-to-even depth
    (⌈⌈d/2⌉/2⌉ = ⌈d/4⌉) for ragged dims."""
    def legacy_depth(dims, n_base):
        L = 0
        while min(dims) > n_base:
            dims = [(d + (d & 1)) // 2 for d in dims]
            L += 1
        return L

    r = rng(13)
    for _ in range(200):
        dims = tuple(int(d) for d in r.integers(1, 3000, size=3))
        n_base = int(r.integers(1, 600))
        assert tree_depth(dims, n_base) == legacy_depth(list(dims), n_base), (
            dims, n_base,
        )


def test_leaf_dispatch_validation():
    a = jnp.zeros((16, 16))
    for ld in ("nope", "fused"):
        with pytest.raises(ValueError, match="leaf_dispatch"):
            strassen_tn(a, a, n_base=8, leaf_dispatch=ld)
        with pytest.raises(ValueError, match="leaf_dispatch"):
            ata(a, n_base=8, leaf_dispatch=ld)
