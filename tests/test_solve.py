"""Tests for the packed solver layer (repro.solve) and its base kernels.

Coverage per the PR's acceptance criteria:

* packed Cholesky round-trip (``L·Lᵀ`` reconstructs the input) and parity
  with ``jnp.linalg.cholesky`` on ``to_dense()``, exhaustively over
  odd/rect/bn-misaligned shapes and batch dims;
* **bitwise** packed-vs-dense solve parity (same walk, same rounding);
* the Pallas ``potrf``/``trsm`` kernels against their jnp oracles,
  batched per the kernels' leading-grid-dim contract;
* blocked triangular substitution (multi-RHS, vector RHS, both passes);
* ``solve.lstsq`` against ``jnp.linalg.lstsq``, plus the jaxpr regression
  that the packed factor pipeline materializes **no dense (n, n)**;
* CG convergence on conditioned SPD fixtures;
* the planner's ``op='solve'`` entry (method choice, cache round-trip);
* Shampoo's ``precond_p=2`` packed path vs its dense twin (fp tolerance)
  and the p=4 path's exact indifference to this PR.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import solve, tune
from repro.core.ata import ata, ata_batched
from repro.core.reference import (
    blocked_potrf_flops,
    classical_gemm_flops,
    potrf_flops,
    trsm_flops,
)
from repro.core.symmetric import SymmetricMatrix
from repro.kernels import ops
from repro.kernels.potrf import potrf_pallas
from repro.kernels.trsm import trsm_pallas
from repro.solve.cholesky import CholeskyFactor

try:  # hypothesis is a dev-only dependency (requirements-dev.txt)
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover
    HAVE_HYPOTHESIS = False


def _spd(rng, n, cond=None):
    """Well-conditioned SPD fixture; ``cond`` forces the spectrum."""
    q, _ = np.linalg.qr(rng.standard_normal((n, n)))
    if cond is None:
        eig = rng.uniform(1.0, 2.0, n)
    else:
        eig = np.logspace(0, -np.log10(cond), n)
    a = (q * eig) @ q.T
    return jnp.asarray((a + a.T) / 2, jnp.float32)


def _packed_gram(rng, m, n, bn, ridge=None):
    a = jnp.asarray(rng.standard_normal((m, n)), jnp.float32)
    g = ata(a, n_base=32, out="packed", packed_block=bn)
    return g.add_scaled_identity(float(n) if ridge is None else ridge)


# ---------------------------------------------------------------------------
# Pallas kernels vs oracles
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n", [8, 16, 64, 128])
def test_potrf_kernel_matches_jnp(n):
    rng = np.random.default_rng(n)
    a = _spd(rng, n) + float(n) * jnp.eye(n, dtype=jnp.float32)
    got = potrf_pallas(a, interpret=True)
    ref = jnp.linalg.cholesky(a)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    # strict upper must be exactly zero (the factor-tile contract)
    assert not np.triu(np.asarray(got), 1).any()


def test_potrf_kernel_batched_is_one_stacked_call():
    rng = np.random.default_rng(0)
    a = jnp.stack([_spd(rng, 32) + 32.0 * jnp.eye(32, dtype=jnp.float32) for _ in range(5)])
    got = potrf_pallas(a, interpret=True)
    ref = jax.vmap(jnp.linalg.cholesky)(a)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("transpose", [True, False])
@pytest.mark.parametrize("m", [8, 24, 300])
def test_trsm_kernel_matches_triangular_solve(transpose, m):
    rng = np.random.default_rng(m)
    n = 16
    l = jnp.linalg.cholesky(_spd(rng, n) + float(n) * jnp.eye(n, dtype=jnp.float32))
    b = jnp.asarray(rng.standard_normal((m, n)), jnp.float32)
    got = trsm_pallas(l, b, transpose=transpose, interpret=True)
    ref = jax.lax.linalg.triangular_solve(
        l, b, left_side=False, lower=True, transpose_a=transpose
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


def test_trsm_kernel_batched_per_entry_factors():
    """Each stack entry solves against its OWN factor tile (the packed
    Cholesky panel contract: batch dims x panel rows flattened)."""
    rng = np.random.default_rng(1)
    n = 16
    ls = jnp.stack([jnp.linalg.cholesky(_spd(rng, n) + n * jnp.eye(n, dtype=jnp.float32))
                    for _ in range(4)])
    bs = jnp.asarray(rng.standard_normal((4, 24, n)), jnp.float32)
    got = trsm_pallas(ls, bs, transpose=True, interpret=True)
    ref = jax.lax.linalg.triangular_solve(
        ls, bs, left_side=False, lower=True, transpose_a=True
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# packed Cholesky: parity + round-trip, exhaustive shapes
# ---------------------------------------------------------------------------

# odd n, rect operands, bn-misaligned (n % bn != 0), single-block, and
# bn larger than n (clamped by default_block_size)
CHOL_SHAPES = [
    (64, 48, 16), (100, 37, 8), (129, 65, 16), (300, 200, 64),
    (128, 128, 128), (96, 41, 64), (513, 129, 32), (40, 24, 256),
]


@pytest.mark.parametrize("m,n,bn", CHOL_SHAPES)
def test_packed_cholesky_matches_dense_cholesky(m, n, bn):
    rng = np.random.default_rng(n * 7 + bn)
    g = _packed_gram(rng, m, n, bn)
    f = solve.cholesky(g)
    assert isinstance(f, CholeskyFactor)
    assert f.blocks.shape == g.blocks.shape  # same packed geometry
    ref = jnp.linalg.cholesky(g.to_dense())
    np.testing.assert_allclose(np.asarray(f.to_dense()), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("m,n,bn", CHOL_SHAPES[:4])
def test_packed_cholesky_round_trip(m, n, bn):
    rng = np.random.default_rng(n + bn)
    g = _packed_gram(rng, m, n, bn)
    ld = solve.cholesky(g).to_dense()
    gd = g.to_dense()
    np.testing.assert_allclose(np.asarray(ld @ ld.T), np.asarray(gd),
                               rtol=1e-4, atol=1e-4 * float(jnp.abs(gd).max()))


@pytest.mark.parametrize("batch", [(3,), (2, 2)])
def test_packed_cholesky_batch_dims(batch):
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.standard_normal((*batch, 64, 40)), jnp.float32)
    flat = a.reshape(-1, 64, 40)
    g = ata_batched(flat, n_base=16, out="packed", packed_block=16)
    g = SymmetricMatrix(g.blocks.reshape(*batch, *g.blocks.shape[-3:]),
                        g.n, g.bn).add_scaled_identity(40.0)
    f = solve.cholesky(g)
    assert f.blocks.shape[:-3] == batch
    ref = jnp.linalg.cholesky(g.to_dense())
    np.testing.assert_allclose(np.asarray(f.to_dense()), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_packed_cholesky_bitwise_equals_dense_input_path():
    """cholesky(SymmetricMatrix) and cholesky(dense array of the same
    values) run the identical walk — results must be BITWISE equal."""
    rng = np.random.default_rng(3)
    g = _packed_gram(rng, 120, 72, 16)
    f_packed = solve.cholesky(g)
    f_dense = solve.cholesky(g.to_dense(), packed_block=16)
    np.testing.assert_array_equal(np.asarray(f_packed.blocks),
                                  np.asarray(f_dense.blocks))


def test_packed_cholesky_kernel_base_matches_jnp_base():
    """The Pallas base engines (interpret mode here) drive the same walk to
    the same factor within fp tolerance."""
    rng = np.random.default_rng(4)
    g = _packed_gram(rng, 80, 48, 16)
    f_jnp = solve.cholesky(g)
    f_kern = solve.cholesky(
        g, base_potrf=ops.potrf,
        base_trsm=lambda l, p: ops.trsm(l, p, transpose=True),
    )
    np.testing.assert_allclose(np.asarray(f_kern.to_dense()),
                               np.asarray(f_jnp.to_dense()),
                               rtol=1e-4, atol=1e-4)


def _kernel_engine_plan():
    """A plan whose solver bases are the Pallas kernels (interpret mode on
    CPU): the engine that solves each panel by an inverted diagonal tile."""
    return dataclasses.replace(
        tune.plan(op="solve", m=256, n=128, k=2, out="packed", backend="cpu"),
        use_kernels=True, method="factor")


def _broadcast_panel_walk(a, potrf, trsm):
    """The factor walk with the panel solved tile by tile: ``L[j,j]``
    broadcast to every panel tile and ONE batched ``trsm`` per column,
    op for op as the jnp and explicit-base engines run it."""
    from repro.core.symmetric import sym_tile
    from repro.solve.cholesky import _flat_call, _pad_identity_mask

    nb, bn, n = a.nb, a.bn, a.n
    out = {}
    for j in range(nb):
        s = a.block(j, j)
        if j:
            lrow = jnp.stack([out[(j, k)] for k in range(j)], axis=0)
            s = s - jnp.einsum("k...ab,k...cb->...ac", lrow, lrow,
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)
        s = sym_tile(s)
        if nb * bn > n and j == nb - 1:
            valid, eye_pad = _pad_identity_mask(n, nb, bn)
            s = s * valid + eye_pad
        out[(j, j)] = _flat_call(potrf, s)
        rows = range(j + 1, nb)
        if not rows:
            continue
        p = jnp.moveaxis(a.col_panel(j), -3, 0)
        if j:
            li = jnp.stack(
                [jnp.stack([out[(i, k)] for k in range(j)], 0) for i in rows], 0)
            p = p - jnp.einsum("rk...ab,k...cb->r...ac", li, lrow,
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)
        panel = _flat_call(trsm, jnp.broadcast_to(out[(j, j)], p.shape), p)
        for r, i in enumerate(rows):
            out[(i, j)] = panel[r]
    return jnp.stack([out[(i, j)] for i in range(nb) for j in range(i + 1)],
                     axis=-3)


@pytest.mark.parametrize("m,n,bn", CHOL_SHAPES)
def test_kernel_engine_panel_inverse_matches_jnp_engine(m, n, bn):
    """The kernel engine's panel step (inverted diagonal tile + one einsum)
    factors and solves like the jnp engine's per-tile substitution, over
    bn-misaligned tails and the identity pad."""
    rng = np.random.default_rng(n * 11 + bn)
    g = _packed_gram(rng, m, n, bn)
    plan = _kernel_engine_plan()
    f_kern = solve.cholesky(g, plan=plan)
    f_jnp = solve.cholesky(g)
    np.testing.assert_allclose(np.asarray(f_kern.to_dense()),
                               np.asarray(f_jnp.to_dense()),
                               rtol=2e-4, atol=2e-4)
    b = jnp.asarray(rng.standard_normal((n, 2)), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(solve.solve_cholesky(f_kern, b, plan=plan)),
        np.asarray(solve.solve_cholesky(f_jnp, b)), rtol=2e-3, atol=2e-3)


def test_kernel_engine_panel_inverse_batched_stack():
    """A stack of 3 (Shampoo-style stats) inverts one diagonal tile per
    batch entry per column and matches the jnp engine."""
    rng = np.random.default_rng(13)
    a = jnp.asarray(rng.standard_normal((3, 80, 41)), jnp.float32)
    g = ata_batched(a, n_base=16, out="packed", packed_block=16)
    g = g.add_scaled_identity(41.0)
    plan = _kernel_engine_plan()
    f_kern = solve.cholesky(g, plan=plan)
    f_jnp = solve.cholesky(g)
    assert f_kern.blocks.shape == g.blocks.shape
    np.testing.assert_allclose(np.asarray(f_kern.to_dense()),
                               np.asarray(f_jnp.to_dense()),
                               rtol=2e-4, atol=2e-4)
    b = jnp.asarray(rng.standard_normal((3, 41, 2)), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(solve.solve_cholesky(f_kern, b, plan=plan)),
        np.asarray(jnp.linalg.solve(g.to_dense(), b)), rtol=2e-3, atol=2e-3)


def test_kernel_engine_panel_inverse_ill_conditioned():
    """At cond(A) = 1e6 the inverted tiles cost at most 4x the backward
    error of the jnp engine's substitution."""
    rng = np.random.default_rng(14)
    ad = _spd(rng, 100, cond=1e6)
    a = SymmetricMatrix.from_dense(ad, 16)
    ref = np.asarray(ad, np.float64)

    def backward_err(f):
        ld = np.asarray(f.to_dense(), np.float64)
        return np.linalg.norm(ld @ ld.T - ref) / np.linalg.norm(ref)

    err_kern = backward_err(solve.cholesky(a, plan=_kernel_engine_plan()))
    err_jnp = backward_err(solve.cholesky(a))
    assert np.isfinite(err_kern) and err_kern <= 4.0 * err_jnp, (err_kern, err_jnp)


def test_kernel_engine_packed_and_dense_inputs_factor_bitwise_equal():
    rng = np.random.default_rng(15)
    g = _packed_gram(rng, 120, 72, 16)
    plan = _kernel_engine_plan()
    f_packed = solve.cholesky(g, plan=plan)
    f_dense = solve.cholesky(g.to_dense(), packed_block=16, plan=plan)
    np.testing.assert_array_equal(np.asarray(f_packed.blocks),
                                  np.asarray(f_dense.blocks))


@pytest.mark.parametrize("engine", ["kernel", "jnp", "explicit"])
def test_panel_inverse_counter_and_per_tile_engines_bitwise(engine):
    """``solve.cholesky.panel_inverse`` counts the nb−1 columns that take
    the inverted-tile route on the kernel engine, and none elsewhere; the
    jnp and explicit-base engines keep the per-tile panel solve, bitwise."""
    from repro import obs
    from repro.solve.cholesky import _potrf_jnp, _trsm_panel_jnp

    rng = np.random.default_rng(16)
    g = _packed_gram(rng, 100, 72, 16)   # nb = 5, a padded tail
    explicit = dict(base_potrf=ops.potrf,
                    base_trsm=functools.partial(ops.trsm, transpose=True))
    kwargs = {"kernel": dict(plan=_kernel_engine_plan()), "jnp": {},
              "explicit": explicit}[engine]
    before = obs.metrics.get("solve.cholesky.panel_inverse")
    f = solve.cholesky(g, **kwargs)
    counted = obs.metrics.get("solve.cholesky.panel_inverse") - before
    assert counted == (g.nb - 1 if engine == "kernel" else 0)
    if engine != "kernel":
        bases = {"jnp": (_potrf_jnp, _trsm_panel_jnp),
                 "explicit": (explicit["base_potrf"], explicit["base_trsm"])}
        ref = _broadcast_panel_walk(g, *bases[engine])
        np.testing.assert_array_equal(np.asarray(f.blocks), np.asarray(ref))


def test_cholesky_factor_identity_and_pytree():
    f = CholeskyFactor.identity(40, 16, batch=(2,))
    np.testing.assert_array_equal(
        np.asarray(f.to_dense()), np.stack([np.eye(40, dtype=np.float32)] * 2)
    )
    leaves, treedef = jax.tree_util.tree_flatten(f)
    assert len(leaves) == 1
    f2 = jax.tree_util.tree_unflatten(treedef, leaves)
    assert (f2.n, f2.bn) == (f.n, f.bn)


# ---------------------------------------------------------------------------
# triangular substitution
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("transpose", [False, True])
@pytest.mark.parametrize("r", [1, 5])
def test_solve_triangular_matches_reference(transpose, r):
    rng = np.random.default_rng(7)
    g = _packed_gram(rng, 100, 56, 16)
    f = solve.cholesky(g)
    b = jnp.asarray(rng.standard_normal((56, r)), jnp.float32)
    got = solve.solve_triangular(f, b, transpose=transpose)
    ref = jax.lax.linalg.triangular_solve(
        f.to_dense(), b, left_side=True, lower=True, transpose_a=transpose
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_solve_triangular_vector_rhs_round_trip():
    rng = np.random.default_rng(8)
    g = _packed_gram(rng, 90, 33, 8)
    f = solve.cholesky(g)
    b = jnp.asarray(rng.standard_normal(33), jnp.float32)
    x = solve.solve_cholesky(f, b)
    assert x.shape == (33,)
    np.testing.assert_allclose(np.asarray(g.to_dense() @ x), np.asarray(b),
                               rtol=5e-3, atol=5e-3)


def test_solve_cholesky_matches_linalg_solve_batched():
    rng = np.random.default_rng(9)
    a = jnp.asarray(rng.standard_normal((3, 80, 40)), jnp.float32)
    g = ata_batched(a, n_base=16, out="packed", packed_block=16)
    g = g.add_scaled_identity(40.0)
    f = solve.cholesky(g)
    b = jnp.asarray(rng.standard_normal((3, 40, 2)), jnp.float32)
    x = solve.solve_cholesky(f, b)
    ref = jnp.linalg.solve(g.to_dense(), b)
    np.testing.assert_allclose(np.asarray(x), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


# ---------------------------------------------------------------------------
# lstsq front door
# ---------------------------------------------------------------------------


def test_lstsq_matches_jnp_lstsq():
    rng = np.random.default_rng(10)
    m, n, r = 200, 60, 3
    a = jnp.asarray(rng.standard_normal((m, n)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((m, r)), jnp.float32)
    x = solve.lstsq(a, b, method="factor")
    ref = jnp.linalg.lstsq(a, b)[0]
    np.testing.assert_allclose(np.asarray(x), np.asarray(ref),
                               rtol=1e-2, atol=1e-3)


def test_lstsq_ridge_shrinks_solution():
    rng = np.random.default_rng(11)
    a = jnp.asarray(rng.standard_normal((120, 40)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((120,)), jnp.float32)
    x0 = solve.lstsq(a, b, method="factor", ridge=1e-6)
    x1 = solve.lstsq(a, b, method="factor", ridge=1e3)
    assert float(jnp.linalg.norm(x1)) < float(jnp.linalg.norm(x0))


def test_lstsq_factor_vs_cg_agree():
    rng = np.random.default_rng(12)
    m, n = 300, 40  # tall: benign normal-equations conditioning
    a = jnp.asarray(rng.standard_normal((m, n)), jnp.float32)
    xt = jnp.asarray(rng.standard_normal((n, 2)), jnp.float32)
    b = a @ xt
    xf = solve.lstsq(a, b, method="factor")
    xc = solve.lstsq(a, b, method="cg")
    np.testing.assert_allclose(np.asarray(xf), np.asarray(xt), rtol=1e-3,
                               atol=1e-3)
    np.testing.assert_allclose(np.asarray(xc), np.asarray(xt), rtol=1e-3,
                               atol=1e-3)


def test_lstsq_packed_jaxpr_has_no_dense_square():
    """The acceptance criterion: the whole planned factor pipeline —
    packed gram, packed Cholesky, substitutions — must not materialize any
    (n, n) or (n_pad, n_pad) dense square in its jaxpr (the repro.check
    ``no-dense-square`` rule, run here against the real solve program)."""
    from repro import check

    # n > packed_block so block tiles != the square; m chosen so no input
    # row-slab of the recursion is coincidentally (n, n) (m = 2n would be)
    m, n, r = 384, 256, 4
    # recursion-forcing plan (same style as the PR 3 packed-retrieval
    # test): a degenerate single-leaf gram would legitimately emit one
    # (n, n) base tile, which is not the mirror this test polices.
    plan = dataclasses.replace(
        tune.plan(op="solve", m=m, n=n, k=r, out="packed", backend="cpu"),
        method="factor", algorithm="strassen", n_base=64,
    )
    assert plan.packed_block < n
    a_abs = jax.ShapeDtypeStruct((m, n), jnp.float32)
    b_abs = jax.ShapeDtypeStruct((m, r), jnp.float32)
    jaxpr = jax.make_jaxpr(
        lambda a, b: solve.lstsq(a, b, ridge=1e-4, plan=plan)
    )(a_abs, b_abs)
    art = check.Artifact(label="solve:factor:packed", jaxpr=jaxpr.jaxpr,
                         plan=plan)
    report = check.run(art, rules=["no-dense-square"])
    assert not report.violations, report.summary()


# ---------------------------------------------------------------------------
# CG
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("cond", [10.0, 1e3])
def test_cg_converges_on_conditioned_spd(cond):
    rng = np.random.default_rng(int(cond))
    n = 48
    g = _spd(rng, n, cond=cond)
    xt = jnp.asarray(rng.standard_normal((n, 2)), jnp.float32)
    b = g @ xt
    x = solve.cg_gram(lambda p: g @ p, b, iters=n * 2, tol=1e-10)
    np.testing.assert_allclose(np.asarray(x), np.asarray(xt),
                               rtol=2e-2, atol=2e-2)


def test_cg_vector_rhs_and_early_stop_masking():
    rng = np.random.default_rng(13)
    n = 32
    g = _spd(rng, n, cond=5.0)
    xt = jnp.asarray(rng.standard_normal(n), jnp.float32)
    b = g @ xt
    x = solve.cg_gram(lambda p: g @ p, b, iters=4 * n, tol=1e-12)
    assert x.shape == (n,)
    np.testing.assert_allclose(np.asarray(x), np.asarray(xt), rtol=1e-3,
                               atol=1e-3)


def test_cg_lstsq_never_forms_gram():
    """CG's jaxpr must hold no (n, n) intermediate either — the gram is an
    operator, not a matrix. Plan-less program: the ``forbidden_squares``
    override pins the rule's shape set directly."""
    from repro import check

    m, n = 256, 64
    a_abs = jax.ShapeDtypeStruct((m, n), jnp.float32)
    b_abs = jax.ShapeDtypeStruct((m,), jnp.float32)
    jaxpr = jax.make_jaxpr(
        lambda a, b: solve.cg_lstsq(a, b, iters=8)
    )(a_abs, b_abs)
    art = check.Artifact(label="solve:cg", jaxpr=jaxpr.jaxpr,
                         overrides={"forbidden_squares": {(n, n)}})
    report = check.run(art, rules=["no-dense-square"])
    assert not report.violations, report.summary()


# ---------------------------------------------------------------------------
# planner: op='solve'
# ---------------------------------------------------------------------------


def test_solve_candidates_both_methods_scored():
    cands = tune.candidates("solve", 2048, 512, 4, backend="cpu")
    methods = {c.method for c in cands}
    assert methods == {"factor", "cg"}
    assert all(c.op == "solve" and c.predicted_s is not None for c in cands)
    assert cands[0].predicted_s <= cands[1].predicted_s


def test_solve_planner_prefers_cg_for_tall_skinny_few_rhs():
    """CG's iters·4mnr undercuts the factor's mn² when n is large relative
    to the CG budget and r is small; the analytic argmin must flip."""
    few = tune.candidates("solve", 4096, 4096, 1, backend="cpu")[0]
    many = tune.candidates("solve", 4096, 256, 256, backend="cpu")[0]
    assert few.method == "cg"
    assert many.method == "factor"


def test_solve_plan_front_door_and_cache_roundtrip(tmp_path):
    path = str(tmp_path / "cache.json")
    p = tune.plan(op="solve", m=512, n=128, k=8, out="packed",
                  backend="cpu", cache_file=path)
    assert p.op == "solve" and p.method in ("factor", "cg")
    p2 = tune.cost.Plan.from_json(p.to_json())
    assert p2 == p


def test_solve_plan_unknown_op_still_rejected():
    with pytest.raises(ValueError):
        tune.plan(op="potrf", m=8, n=8)


def test_solve_plan_rejects_batch():
    """lstsq takes one 2-D design matrix; a batched solve plan would be
    unexecutable (and untimeable by the autotuner) — rejected up front."""
    with pytest.raises(ValueError, match="unbatched"):
        tune.plan(op="solve", m=128, n=64, k=2, batch=3, backend="cpu")
    with pytest.raises(ValueError, match="unbatched"):
        tune.candidates("solve", 128, 64, 2, batch=3, backend="cpu")


def test_lstsq_pinned_method_bypasses_planner(tmp_path, monkeypatch):
    """lstsq(method=...) with no plan must not consult the tune front door
    (the bitwise-reproducibility contract of manual pins)."""
    import repro.tune.cache as cache_mod

    def _boom(*a, **k):  # pragma: no cover - must not be reached
        raise AssertionError("planner consulted despite pinned method")

    monkeypatch.setattr(cache_mod, "plan", _boom)
    rng = np.random.default_rng(20)
    a = jnp.asarray(rng.standard_normal((96, 40)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((96,)), jnp.float32)
    for method in ("factor", "cg"):
        x = solve.lstsq(a, b, method=method, ridge=1e-4)
        assert x.shape == (40,)


def test_symmetric_block_views_match_dense():
    """The block views the factor walk reads (block / diag_blocks /
    col_panel) agree with the corresponding to_dense() slices."""
    rng = np.random.default_rng(21)
    n = 56
    g = _packed_gram(rng, 100, n, 16)
    d = np.asarray(g.to_dense())
    bn, nb = g.bn, g.nb
    for i in range(nb):
        for j in range(i + 1):
            h = min(bn, n - i * bn)
            w = min(bn, n - j * bn)
            blk = np.asarray(g.block(i, j))[:h, :w]
            ref = d[i * bn : i * bn + h, j * bn : j * bn + w]
            if i == j:
                # diagonal tiles: LOWER halves are the authoritative
                # content (intra-tile upper corners may be unwritten —
                # to_dense's mirror reconstructs them)
                blk, ref = np.tril(blk), np.tril(ref)
            np.testing.assert_array_equal(blk, ref)
    with pytest.raises(ValueError):
        g.block(0, 1)
    panel = np.asarray(g.col_panel(0))
    assert panel.shape == (nb - 1, bn, bn)
    np.testing.assert_array_equal(panel[0], np.asarray(g.block(1, 0)))
    assert g.diag_blocks().shape == (nb, bn, bn)


def test_flop_counters_consistency():
    # unblocked potrf: classical n^3/3 leading term, exact small cases
    assert potrf_flops(1) == 1
    assert potrf_flops(2) == 1 + (1 + 1 + 2)  # col0: sqrt+div+update, col1: sqrt
    n = 64
    assert abs(potrf_flops(n) - n**3 / 3) / n**3 < 0.05
    assert trsm_flops(n, 8) == n * n * 8
    # blocked counter degenerates to the unblocked one at bn >= n
    assert blocked_potrf_flops(n, n) == potrf_flops(n)
    # and is dominated by the same n^3/3 term for finer grids
    total = blocked_potrf_flops(256, 64)
    assert 0.3 < total / (256**3 / 3) < 1.6
    assert classical_gemm_flops(2, 3, 4) == 48


# ---------------------------------------------------------------------------
# Shampoo p=2: packed Cholesky preconditioning
# ---------------------------------------------------------------------------


def _run_shampoo(precond_p, packed, steps=4):
    from repro.optim.shampoo import shampoo

    params = {"w": jnp.asarray(
        np.random.default_rng(0).standard_normal((96, 48)), jnp.float32)}
    grads = {"w": jnp.asarray(
        np.random.default_rng(1).standard_normal((96, 48)), jnp.float32)}
    opt = shampoo(lambda s: 1e-2, block=32, update_every=2,
                  precond_p=precond_p, packed_grams=packed,
                  n_base=16, gram_block=16)
    state = opt.init(params)
    u = None
    for _ in range(steps):
        u, state = jax.jit(opt.update)(grads, state, params)
    return u["w"], state


def test_shampoo_p2_packed_matches_dense_within_fp():
    u_packed, st_packed = _run_shampoo(2, True)
    u_dense, _ = _run_shampoo(2, False)
    np.testing.assert_allclose(np.asarray(u_packed), np.asarray(u_dense),
                               rtol=2e-3, atol=2e-3)
    # the p=2 preconditioner state IS packed factors — never densified
    s = jax.tree_util.tree_leaves(
        st_packed["shampoo"]["w"]["pl"],
        is_leaf=lambda x: isinstance(x, CholeskyFactor),
    )[0]
    assert isinstance(s, CholeskyFactor)


def test_shampoo_p4_path_unchanged_bitwise():
    u_packed, _ = _run_shampoo(4, True)
    u_dense, _ = _run_shampoo(4, False)
    np.testing.assert_array_equal(np.asarray(u_packed), np.asarray(u_dense))


def test_shampoo_rejects_bad_precond_p():
    from repro.optim.shampoo import shampoo

    with pytest.raises(ValueError):
        shampoo(lambda s: 1e-2, precond_p=3)


# ---------------------------------------------------------------------------
# PowerSGD packed whitening
# ---------------------------------------------------------------------------


def test_powersgd_whiten_packed_matches_dense():
    from repro.optim.powersgd import _whiten

    rng = np.random.default_rng(14)
    p = jnp.asarray(rng.standard_normal((64, 8)), jnp.float32)
    g_dense = jax.lax.dot_general(
        p, p, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    g_packed = SymmetricMatrix.from_dense(g_dense, 8)
    w_dense = _whiten(p, g_dense)
    w_packed = _whiten(p, g_packed)
    np.testing.assert_allclose(np.asarray(w_packed), np.asarray(w_dense),
                               rtol=2e-4, atol=2e-4)
    # whitened columns are orthonormal up to the ridge
    wtw = np.asarray(w_packed.T @ w_packed)
    np.testing.assert_allclose(wtw, np.eye(8), atol=1e-2)


# ---------------------------------------------------------------------------
# optional hypothesis sweep (mirrors test_core_ata's pattern)
# ---------------------------------------------------------------------------

if HAVE_HYPOTHESIS:

    @settings(max_examples=20, deadline=None)
    @given(
        m=st.integers(16, 160),
        n=st.integers(9, 96),
        bn=st.sampled_from([8, 16, 32, 64]),
    )
    def test_property_packed_cholesky_round_trip(m, n, bn):
        rng = np.random.default_rng(m * 1000 + n * 10 + bn)
        g = _packed_gram(rng, max(m, n), n, bn)
        ld = solve.cholesky(g).to_dense()
        gd = g.to_dense()
        np.testing.assert_allclose(
            np.asarray(ld @ ld.T), np.asarray(gd),
            rtol=1e-3, atol=1e-3 * float(jnp.abs(gd).max()),
        )

else:  # pragma: no cover

    @pytest.mark.skip(
        reason="hypothesis not installed (pip install -r requirements-dev.txt)"
    )
    def test_property_packed_cholesky_round_trip():
        pass


# ---------------------------------------------------------------------------
# matmul precision: float32 means float32 on the chip too
# ---------------------------------------------------------------------------


def _kernel_solve_plan():
    return dataclasses.replace(
        tune.plan(op="solve", m=256, n=128, k=2, out="packed", backend="tpu"),
        algorithm="winograd", n_base=32, packed_block=64, use_kernels=True,
        method="factor")


def _traced(case):
    from repro.serve.bucketing import BucketSpec
    from repro.serve.engine import _build_bucket_fn, serve_abstract_args

    plan = _kernel_solve_plan()
    a = jax.ShapeDtypeStruct((256, 128), jnp.float32)
    b = jax.ShapeDtypeStruct((256, 2), jnp.float32)
    if case == "ata_packed":
        ata_plan = dataclasses.replace(plan, op="ata", k=128, method=None)
        return jax.make_jaxpr(lambda a: ata(a, plan=ata_plan, out="packed"))(a)
    if case == "lstsq":
        return jax.make_jaxpr(lambda a, b: solve.lstsq(a, b, plan=plan))(a, b)
    spec = BucketSpec(op="lstsq", m=256, n=128, r=2, batch=2, dtype="float32",
                      exact_m=True)
    return jax.make_jaxpr(_build_bucket_fn(spec, plan))(*serve_abstract_args(spec))


@pytest.mark.parametrize("case", ["ata_packed", "lstsq", "serve_bucket"])
def test_f32_matmuls_ask_for_highest_precision(case):
    """At default precision a TPU multiplies float32 operands in one bf16
    pass (a float32 Gram then carries ~2e-3 relative error). Every dot of
    the float32 Gram → solve → serve path, Pallas kernel bodies included,
    must state HIGHEST."""
    from repro.check.artifacts import walk_eqns

    jaxpr = _traced(case)
    dots = [s for s in walk_eqns(jaxpr.jaxpr, into_pallas=True)
            if s.eqn.primitive.name == "dot_general"]
    assert any("pallas_call" in s.path for s in dots), "no kernel dot traced"
    for s in dots:
        assert all(v.aval.dtype == jnp.float32 for v in s.eqn.invars)
        prec = s.eqn.params["precision"]
        assert prec is not None and all(
            p == jax.lax.Precision.HIGHEST for p in prec), (s.path, prec)
