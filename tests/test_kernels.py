"""Shape/dtype sweeps for the Pallas kernels vs the pure-jnp oracles.

Kernels run in interpret mode on CPU (the kernel body executes in Python);
the same code path compiles through Mosaic on a real TPU.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import gemm_tn, syrk
from repro.kernels.ref import gemm_tn_ref, syrk_ref

SHAPES_GEMM = [
    (8, 128, 128),
    (64, 128, 256),
    (256, 384, 128),
    (128, 256, 256),
    (40, 100, 60),     # unaligned — exercises padding
    (513, 257, 129),   # odd, > one block
    (1024, 512, 256),  # multi-block reduction
]

SHAPES_SYRK = [
    (8, 128),
    (64, 256),
    (256, 384),
    (40, 100),
    (513, 257),
    (1024, 512),
    (300, 700),
]

DTYPES = [jnp.float32, jnp.bfloat16]


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
@pytest.mark.parametrize("m,n,k", SHAPES_GEMM)
def test_gemm_tn_kernel_matches_ref(m, n, k, dtype):
    r = np.random.default_rng(hash((m, n, k)) % 2**32)
    a = jnp.asarray(r.standard_normal((m, n)), dtype=dtype)
    b = jnp.asarray(r.standard_normal((m, k)), dtype=dtype)
    got = gemm_tn(a, b, blocks=(256, 128, 128), interpret=True)
    want = gemm_tn_ref(a, b)
    assert got.shape == (n, k)
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, **_tol(dtype))


@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
@pytest.mark.parametrize("m,n", SHAPES_SYRK)
def test_syrk_kernel_matches_ref(m, n, dtype):
    r = np.random.default_rng(hash((m, n)) % 2**32)
    a = jnp.asarray(r.standard_normal((m, n)), dtype=dtype)
    got = syrk(a, blocks=(256, 128), interpret=True)
    want = syrk_ref(a)
    assert got.shape == (n, n)
    np.testing.assert_allclose(got, want, **_tol(dtype))
    # bitwise symmetry contract
    np.testing.assert_array_equal(np.asarray(got), np.asarray(got).T)


def test_gemm_tn_alpha():
    r = np.random.default_rng(0)
    a = jnp.asarray(r.standard_normal((64, 128)), dtype=jnp.float32)
    b = jnp.asarray(r.standard_normal((64, 128)), dtype=jnp.float32)
    got = gemm_tn(a, b, alpha=-2.0, blocks=(64, 128, 128), interpret=True)
    np.testing.assert_allclose(got, -2.0 * (a.T @ b), rtol=1e-5, atol=1e-5)


def test_syrk_alpha():
    r = np.random.default_rng(1)
    a = jnp.asarray(r.standard_normal((64, 128)), dtype=jnp.float32)
    got = syrk(a, alpha=0.5, blocks=(64, 128), interpret=True)
    np.testing.assert_allclose(got, 0.5 * (a.T @ a), rtol=1e-5, atol=1e-5)


def test_ata_with_pallas_base():
    """End-to-end: the ATA recursion bottoming out in the Pallas kernels."""
    from repro.core import ata

    r = np.random.default_rng(2)
    a = jnp.asarray(r.standard_normal((512, 384)), dtype=jnp.float32)
    got = ata(
        a,
        n_base=128,
        base_syrk=lambda x: syrk(x, blocks=(128, 128), interpret=True),
        base_dot=lambda x, y: gemm_tn(x, y, blocks=(128, 128, 128), interpret=True),
    )
    np.testing.assert_allclose(got, a.T @ a, rtol=2e-4, atol=2e-4)


def test_strassen_with_pallas_base():
    from repro.core import strassen_tn

    r = np.random.default_rng(3)
    a = jnp.asarray(r.standard_normal((512, 256)), dtype=jnp.float32)
    b = jnp.asarray(r.standard_normal((512, 320)), dtype=jnp.float32)
    got = strassen_tn(
        a,
        b,
        n_base=128,
        base_dot=lambda x, y: gemm_tn(x, y, blocks=(128, 128, 128), interpret=True),
    )
    np.testing.assert_allclose(got, a.T @ b, rtol=2e-4, atol=2e-4)


def test_syrk_triangular_grid_only_lower_blocks():
    """The packed-grid index math must enumerate each lower block exactly once."""
    from repro.kernels.syrk import _tri_coords

    nb = 37
    seen = set()
    for t in range(nb * (nb + 1) // 2):
        i, j = _tri_coords(jnp.int32(t))
        i, j = int(i), int(j)
        assert 0 <= j <= i < nb
        seen.add((i, j))
    assert len(seen) == nb * (nb + 1) // 2


# ---------------------------------------------------------------------------
# packed / dual-write / batched output modes
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("m,n", [(64, 256), (40, 100), (513, 257), (300, 700)])
def test_syrk_packed_mode_matches_dense_bitwise(m, n):
    """Packed output must reconstruct the dense dual-write output exactly,
    while allocating only the nb(nb+1)/2 lower blocks."""
    from repro.core import SymmetricMatrix

    r = np.random.default_rng(hash((m, n, "p")) % 2**32)
    a = jnp.asarray(r.standard_normal((m, n)), dtype=jnp.float32)
    dense = syrk(a, blocks=(256, 128), interpret=True)
    packed = syrk(a, blocks=(256, 128), interpret=True, out="packed")
    assert isinstance(packed, SymmetricMatrix)
    nb = packed.nb
    assert packed.blocks.shape == (nb * (nb + 1) // 2, packed.bn, packed.bn)
    np.testing.assert_array_equal(np.asarray(packed.to_dense()), np.asarray(dense))


def test_syrk_dual_write_no_mirror_postpass():
    """The dense mode's symmetry comes from the in-kernel dual write — the
    wrapper must contain no full-square transpose/mirror post-pass. Only
    tile-granular (≤ block) transposes inside the kernel body are allowed.
    The repro.check ``no-full-transpose`` walker is pallas-opaque by
    default (kernel-body mirrors ARE the base-case symmetry contract), so
    ``max_transpose_dim=0`` makes it flag ANY wrapper-level 2-D transpose."""
    from repro import check

    a = jnp.zeros((256, 256), jnp.float32)
    jaxpr = jax.make_jaxpr(lambda x: syrk(x, blocks=(128, 128), interpret=True))(a)
    art = check.Artifact(
        label="kernels:syrk-dual-write", jaxpr=jaxpr.jaxpr,
        overrides={"max_transpose_dim": 0, "mirror_budget": 0})
    report = check.run(art, rules=["no-full-transpose"])
    assert not report.violations, (
        f"wrapper reintroduced a mirror post-pass: {report.summary()}")


@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
def test_syrk_batched_one_launch(dtype):
    """(B, m, n) input runs through a leading batch grid dimension."""
    r = np.random.default_rng(9)
    a = jnp.asarray(r.standard_normal((3, 70, 200)), dtype=dtype)
    got = syrk(a, blocks=(64, 128), interpret=True)
    want = jnp.einsum(
        "bmi,bmj->bij", a.astype(jnp.float32), a.astype(jnp.float32)
    )
    assert got.shape == (3, 200, 200)
    np.testing.assert_allclose(got, want, **_tol(dtype))
    for b in range(3):
        np.testing.assert_array_equal(np.asarray(got[b]), np.asarray(got[b]).T)
    packed = syrk(a, blocks=(64, 128), interpret=True, out="packed")
    assert packed.blocks.shape[0] == 3
    np.testing.assert_array_equal(np.asarray(packed.to_dense()), np.asarray(got))


def test_syrk_packed_layout_compatible_with_other_producers():
    """Packed kernel output must share the common block-size clamp so it can
    be accumulated against ata-packed results and zeros() state (and a small
    matrix is never padded up to a huge single block)."""
    from repro.core import SymmetricMatrix, ata

    r = np.random.default_rng(11)
    a = jnp.asarray(r.standard_normal((32, 64)), jnp.float32)
    p_syrk = syrk(a, interpret=True, out="packed")
    assert p_syrk.bn == 64 and p_syrk.nbytes <= 64 * 64 * 4
    p_ata = ata(a, n_base=256, out="packed", packed_block=128)
    acc = SymmetricMatrix.zeros(64, 128) + p_syrk + p_ata.astype(jnp.float32)
    np.testing.assert_allclose(
        acc.to_dense(), 2.0 * (a.T @ a), rtol=1e-4, atol=1e-4
    )


def test_ata_packed_with_pallas_packed_base():
    """End-to-end packed path: recursion + packed-capable Pallas base."""
    from repro.core import ata

    r = np.random.default_rng(10)
    a = jnp.asarray(r.standard_normal((256, 192)), dtype=jnp.float32)
    got = ata(
        a,
        n_base=128,
        out="packed",
        base_syrk=lambda x: syrk(x, blocks=(128, 128), interpret=True),
        base_dot=lambda x, y: gemm_tn(x, y, blocks=(128, 128, 128), interpret=True),
    )
    np.testing.assert_allclose(got.to_dense(), a.T @ a, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
def test_gemm_tn_batched_one_launch(dtype):
    """(B, m, n) × (B, m, k) runs through a leading batch grid dimension —
    the batched-grid contract the batched-leaf recursion relies on."""
    r = np.random.default_rng(12)
    a = jnp.asarray(r.standard_normal((5, 70, 200)), dtype=dtype)
    b = jnp.asarray(r.standard_normal((5, 70, 130)), dtype=dtype)
    got = gemm_tn(a, b, blocks=(64, 128, 128), interpret=True)
    want = jnp.einsum(
        "bmn,bmk->bnk", a.astype(jnp.float32), b.astype(jnp.float32)
    )
    assert got.shape == (5, 200, 130)
    np.testing.assert_allclose(got, want, **_tol(dtype))
    # per-slice agreement with the unbatched kernel (one grid, same math)
    one = gemm_tn(a[2], b[2], blocks=(64, 128, 128), interpret=True)
    np.testing.assert_array_equal(np.asarray(got[2]), np.asarray(one))


def test_gemm_tn_batched_shape_errors():
    a = jnp.zeros((2, 16, 8))
    with pytest.raises(ValueError):
        gemm_tn(a, jnp.zeros((3, 16, 8)), interpret=True)   # batch mismatch
    with pytest.raises(ValueError):
        gemm_tn(a, jnp.zeros((16, 8)), interpret=True)      # rank mismatch


def test_strassen_batched_leaves_with_pallas_base():
    """leaf_dispatch='batched' hands the Pallas kernel the whole leaf stack
    as its one leading batch dim — values match the unrolled kernel path
    bitwise (identical kernel, identical per-leaf grids)."""
    from functools import partial

    from repro.core import strassen_tn

    r = np.random.default_rng(13)
    a = jnp.asarray(r.standard_normal((128, 128)), jnp.float32)
    b = jnp.asarray(r.standard_normal((128, 128)), jnp.float32)
    base = partial(gemm_tn, blocks=(64, 64, 64), interpret=True)
    u = strassen_tn(a, b, n_base=64, base_dot=base, leaf_dispatch="unrolled")
    got = strassen_tn(a, b, n_base=64, base_dot=base, leaf_dispatch="batched")
    np.testing.assert_array_equal(np.asarray(u), np.asarray(got))
    # f32 + one Strassen level: looser than the plain-kernel sweeps above
    np.testing.assert_allclose(got, gemm_tn_ref(a, b), rtol=1e-3, atol=1e-3)


# ---------------------------------------------------------------------------
# fused-operand leaves (the coefficient-table contract)
# ---------------------------------------------------------------------------


def _slot_path(a, b, L, blocks):
    """The XLA half of the fused contract: per-leaf trace-time slot gather
    (`_combine_slots`' balanced ± tree) + the SAME unbatched blocked kernel
    per leaf. The fused launch must match it bitwise — identical chunk
    dots, identical add-tree association, signs applied in-kernel."""
    from repro.core.strassen import _block_getter, _combine_slots, _slot_tables

    (ar, ac, asg), (br, bc, bsg) = _slot_tables(L)
    ga, gb = _block_getter(a, L), _block_getter(b, L)
    return jnp.stack([
        gemm_tn(
            _combine_slots(ga, ar[t], ac[t], asg[t]),
            _combine_slots(gb, br[t], bc[t], bsg[t]),
            blocks=blocks, interpret=True,
        )
        for t in range(ar.shape[0])
    ])


@pytest.mark.parametrize(
    "m,n,k,L",
    [
        (256, 192, 128, 1),
        (67, 53, 41, 1),    # odd everywhere -> root pad, cropped leaves
        (96, 96, 96, 2),    # two levels: 49 leaves, one launch
    ],
)
def test_gemm_tn_fused_bitwise_vs_slot_gather(m, n, k, L):
    from repro.core.strassen import _pad_root, _slot_tables, _to_blocks
    from repro.kernels import ops

    r = np.random.default_rng(hash((m, n, k, L)) % 2**32)
    a = _pad_root(jnp.asarray(r.standard_normal((m, n)), jnp.float32), L)
    b = _pad_root(jnp.asarray(r.standard_normal((m, k)), jnp.float32), L)
    blocks = (64, 64, 64)
    want = _slot_path(a, b, L, blocks)
    got = ops.gemm_tn_fused(
        _to_blocks(a, L)[None], _to_blocks(b, L)[None], _slot_tables(L),
        blocks=blocks, interpret=True,
    )
    assert got.shape == want.shape and got.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(want), np.asarray(got))


@pytest.mark.parametrize("B", [1, 3])
def test_gemm_tn_fused_batched_grid(B):
    """An operand batch dim rides the grid like everything else — including
    the B=1 degenerate leading dim the level-synchronous ATA tree emits."""
    from repro.core.strassen import _pad_root, _slot_tables, _to_blocks
    from repro.kernels import ops

    r = np.random.default_rng(20 + B)
    a = _pad_root(jnp.asarray(r.standard_normal((B, 128, 96)), jnp.float32), 1)
    b = _pad_root(jnp.asarray(r.standard_normal((B, 128, 64)), jnp.float32), 1)
    blocks = (64, 64, 64)
    want = _slot_path(a, b, 1, blocks)  # (7, B, n/2, k/2): leaf-major stack
    got = ops.gemm_tn_fused(
        _to_blocks(a, 1)[None], _to_blocks(b, 1)[None], _slot_tables(1),
        blocks=blocks, interpret=True,
    )
    assert got.shape == (7, B, 48, 32)
    np.testing.assert_array_equal(np.asarray(want), np.asarray(got))
    # B=1 slices agree with the unbatched launch (same grids, same math)
    one = ops.gemm_tn_fused(
        _to_blocks(a[0], 1)[None], _to_blocks(b[0], 1)[None], _slot_tables(1),
        blocks=blocks, interpret=True,
    )
    np.testing.assert_array_equal(np.asarray(got[:, 0]), np.asarray(one))


def test_gemm_tn_fused_bf16_storage_f32_accumulate():
    """bf16 operand blocks, f32 accumulation. Bitwise parity with the
    trace-time gather is an f32/f64 property only: the slot path rounds its
    bf16 combine at the pallas-call input boundary, while the in-kernel
    combine feeds the dot inside one XLA computation, where float
    normalization may (and on CPU does) keep the bf16 adds at f32 precision
    — strictly *more* accurate, never bitwise. So bf16 asserts the combine
    is at least operand-precision against the f32 oracle, plus the flush
    cast contract."""
    from repro.core.strassen import _pad_root, _slot_tables, _to_blocks
    from repro.kernels import ops

    r = np.random.default_rng(30)
    a = _pad_root(jnp.asarray(r.standard_normal((128, 96)), jnp.bfloat16), 1)
    b = _pad_root(jnp.asarray(r.standard_normal((128, 64)), jnp.bfloat16), 1)
    blocks = (64, 64, 64)
    got = ops.gemm_tn_fused(
        _to_blocks(a, 1)[None], _to_blocks(b, 1)[None], _slot_tables(1),
        blocks=blocks, interpret=True,
    )
    assert got.dtype == jnp.float32
    want = _slot_path(
        a.astype(jnp.float32), b.astype(jnp.float32), 1, blocks
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-2, atol=2e-1)
    # and the slot path in bf16 lands within the same band of the oracle
    slot = _slot_path(a, b, 1, blocks)
    np.testing.assert_allclose(np.asarray(slot), np.asarray(want),
                               rtol=2e-2, atol=2e-1)
    lo = ops.gemm_tn_fused(
        _to_blocks(a, 1)[None], _to_blocks(b, 1)[None], _slot_tables(1),
        blocks=blocks, interpret=True, out_dtype=jnp.bfloat16,
    )
    assert lo.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(lo, np.float32), np.asarray(got), rtol=2e-2, atol=2e-1
    )


@pytest.mark.parametrize("dead", ["a", "b", "both"])
def test_gemm_tn_fused_dead_leaf_skips_its_dot(dead):
    """One-slot leaves over a (1, 1, 3) stripe grid, as the distributed
    tile stack builds them: leaf (i, j) reads stripe i as A and stripe j as
    B. A leaf whose A signs, B signs or both are all 0 is dead: its product
    is exact zeros, even over a NaN stripe (0·NaN would be NaN had its dot
    run), and each live leaf is bitwise the kernel on its cut stripes."""
    from repro.kernels import ops

    m, w, blocks = 96, 64, (64, 64, 64)
    r = np.random.default_rng(31)
    a = r.standard_normal((m, 3 * w)).astype(np.float32)
    a[:, 2 * w:] = np.nan
    stripes = jnp.asarray(a.reshape(m, 3, w).transpose(1, 0, 2))[None, None]
    ii, jj = np.array([[0], [1], [1], [2]]), np.array([[0], [0], [1], [2]])
    live = np.array([[1], [1], [1], [0]])
    one = np.ones_like(live)
    sa, sb = {"a": (live, one), "b": (one, live), "both": (live, live)}[dead]
    rows = np.zeros_like(ii)
    got = np.asarray(ops.gemm_tn_fused(
        stripes, stripes, ((rows, ii, sa), (rows, jj, sb)), blocks=blocks,
        interpret=True))
    np.testing.assert_array_equal(got[3], np.zeros((w, w), np.float32))
    for t in range(3):
        i, j = ii[t, 0], jj[t, 0]
        want = gemm_tn(jnp.asarray(a[:, i * w:(i + 1) * w]),
                       jnp.asarray(a[:, j * w:(j + 1) * w]),
                       blocks=blocks, interpret=True)
        np.testing.assert_array_equal(got[t], np.asarray(want))


def test_gemm_tn_fused_one_dead_leaf_leaves_the_rest():
    """Zeroing one Strassen leaf's signs kills that leaf alone: it reads
    exact zeros and the other six leaves are bitwise what they were."""
    from repro.core.strassen import _pad_root, _slot_tables, _to_blocks
    from repro.kernels import ops

    r = np.random.default_rng(32)
    a = _pad_root(jnp.asarray(r.standard_normal((128, 96)), jnp.float32), 1)
    b = _pad_root(jnp.asarray(r.standard_normal((128, 64)), jnp.float32), 1)
    (ar, ac, asg), (br, bc, bsg) = _slot_tables(1)
    asg, bsg = asg.copy(), bsg.copy()
    asg[4] = bsg[4] = 0
    run = lambda tables: np.asarray(ops.gemm_tn_fused(
        _to_blocks(a, 1)[None], _to_blocks(b, 1)[None], tables,
        blocks=(64, 64, 64), interpret=True))
    want = run(_slot_tables(1))
    got = run(((ar, ac, asg), (br, bc, bsg)))
    np.testing.assert_array_equal(got[4], np.zeros_like(got[4]))
    keep = [t for t in range(7) if t != 4]
    np.testing.assert_array_equal(got[keep], want[keep])


@pytest.mark.parametrize("L", range(6))
def test_slot_tables_have_no_dead_leaf(L):
    """Every leaf of Strassen's fused tables has a live A slot and a live B
    slot, so the fused kernel's dead-leaf skip never fires on them and
    every fused dispatch computes what it did before the skip existed."""
    from repro.core.strassen import _slot_tables

    (_, _, asg), (_, _, bsg) = _slot_tables(L)
    assert asg.shape == bsg.shape == (7 ** L, 2 ** L)
    assert (asg != 0).any(axis=1).all()
    assert (bsg != 0).any(axis=1).all()


@pytest.mark.parametrize("L", [1, 2])
def test_syrk_gather_bitwise_vs_stacked_syrk(L):
    """The diagonal half of the contract: gathering leaf pairs through the
    index maps equals stacking them first and running the batched syrk —
    the stack is simply never built."""
    from repro.core.strassen import _to_blocks
    from repro.kernels import ops

    r = np.random.default_rng(40 + L)
    a = jnp.asarray(r.standard_normal((256, 256)), jnp.float32)
    ab = _to_blocks(a, L)
    R = 1 << L
    s = np.arange(R * R, dtype=np.int32)
    stacked = jnp.swapaxes(ab, 0, 1).reshape(R * R, *ab.shape[-2:])
    want = syrk(stacked, blocks=(128, 64), interpret=True)
    got = ops.syrk_gather(ab, s % R, s // R, blocks=(128, 64), interpret=True)
    np.testing.assert_array_equal(np.asarray(want), np.asarray(got))


def test_syrk_gather_batched_grid():
    from repro.core.strassen import _to_blocks
    from repro.kernels import ops

    r = np.random.default_rng(42)
    a = jnp.asarray(r.standard_normal((2, 128, 128)), jnp.float32)
    ab = _to_blocks(a, 1)
    s = np.arange(4, dtype=np.int32)
    stacked = jnp.swapaxes(ab, 0, 1).reshape(4, 2, *ab.shape[-2:])
    want = syrk(
        stacked.reshape(-1, *ab.shape[-2:]), blocks=(64, 64), interpret=True
    ).reshape(4, 2, ab.shape[-1], ab.shape[-1])
    got = ops.syrk_gather(ab, s % 2, s // 2, blocks=(64, 64), interpret=True)
    np.testing.assert_array_equal(np.asarray(want), np.asarray(got))


def test_fused_leaves_with_pallas_kernels_end_to_end():
    """A use_kernels fused plan runs the whole recursion through ONE fused
    launch per level — bitwise with the unrolled kernel dispatch on the
    same plan, for strassen_tn and both ata output modes (odd shapes)."""
    import dataclasses

    from repro.core import ata, strassen_tn
    from repro.tune import cost

    r = np.random.default_rng(50)

    def mk(op, m, n, k, ld, **kw):
        return dataclasses.replace(
            cost.default_plan(op, m, n, k), algorithm="strassen", n_base=64,
            use_kernels=True, leaf_dispatch=ld, **kw,
        )

    a = jnp.asarray(r.standard_normal((300, 260)), jnp.float32)
    du = ata(a, plan=mk("ata", 300, 260, None, "unrolled"))
    df = ata(a, plan=mk("ata", 300, 260, None, "fused"))
    np.testing.assert_array_equal(np.asarray(du), np.asarray(df))
    np.testing.assert_allclose(df, a.T @ a, rtol=2e-4, atol=2e-4)
    pf = ata(a, plan=mk("ata", 300, 260, None, "fused", out="packed"),
             out="packed")
    np.testing.assert_array_equal(np.asarray(pf.to_dense()), np.asarray(du))

    b = jnp.asarray(r.standard_normal((300, 200)), jnp.float32)
    gu = strassen_tn(a, b, plan=mk("gemm_tn", 300, 260, 200, "unrolled"))
    gf = strassen_tn(a, b, plan=mk("gemm_tn", 300, 260, 200, "fused"))
    np.testing.assert_array_equal(np.asarray(gu), np.asarray(gf))
    np.testing.assert_allclose(gf, a.T @ b, rtol=2e-4, atol=2e-4)
