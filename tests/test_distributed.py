"""Multi-device tests for the SPMD gram schedules.

The main pytest process sees a single CPU device (by design — see the
dry-run rules), so multi-device checks run in subprocesses with
``XLA_FLAGS=--xla_force_host_platform_device_count=8``.
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.core.distributed import (
    ata_tile_parallel,
    choose_tiling,
    gemm_tn_colshard,
    tile_parallel_device_flops,
)


def _run_in_subprocess(script: str, devices: int = 8):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.abspath("src")
    out = subprocess.run(
        [sys.executable, "-c", script],
        env=env,
        capture_output=True,
        text=True,
        timeout=600,
    )
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    assert "OK" in out.stdout


# --- single-device smoke (debuggable in-process) ---------------------------


def test_tile_parallel_single_device():
    mesh = jax.make_mesh((1,), ("model",))
    r = np.random.default_rng(0)
    a = jnp.asarray(r.standard_normal((96, 80)), dtype=jnp.float32)
    c = ata_tile_parallel(a, mesh, task_axis="model", n_base=32)
    np.testing.assert_allclose(c, a.T @ a, rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(np.asarray(c), np.asarray(c).T)


def test_tile_parallel_packed_single_device():
    """out='packed' returns a SymmetricMatrix whose to_dense() is bitwise
    the dense schedule's output (dense IS packed.to_dense() at the root)."""
    from repro.core.symmetric import SymmetricMatrix

    mesh = jax.make_mesh((1,), ("model",))
    r = np.random.default_rng(0)
    a = jnp.asarray(r.standard_normal((96, 80)), dtype=jnp.float32)
    c = ata_tile_parallel(a, mesh, task_axis="model", n_base=32)
    s = ata_tile_parallel(a, mesh, task_axis="model", n_base=32, out="packed")
    assert isinstance(s, SymmetricMatrix)
    np.testing.assert_array_equal(np.asarray(s.to_dense()), np.asarray(c))
    # alpha applies to the packed output too (documented contract)
    s2 = ata_tile_parallel(
        a, mesh, task_axis="model", n_base=32, out="packed", alpha=0.5
    )
    np.testing.assert_array_equal(
        np.asarray(s2.blocks), np.asarray(0.5 * s.blocks)
    )


def _check_tile_schedule_jaxpr(schedule, path):
    """The packed path's jaxpr must not materialize any dense (n, n)
    square — the whole point of packed retrieval. Runs the repro.check
    ``no-dense-square`` rule (its walker descends shard_map/cond bodies)
    with the shape set pinned by override, on a one-device mesh (n = 256,
    two stripes of w = 128, three tiles). The tile stack takes the path the
    plan allows: ``'direct'`` under a kernel plan whose tiles are one leaf
    (n_base = w) cuts no (m, w) column stripe out of A and makes exactly
    one ``gemm_tn_fused`` launch for all three tiles; ``'sliced'`` under
    pinned tunables (n_base = 64 < w: a recursion inside each tile) cuts
    two stripes per tile."""
    import dataclasses

    from repro import check, obs, tune
    from repro.check.artifacts import walk_eqns

    m, n, w, tiles = 128, 256, 128, 3  # w == packed bn → pure-slice retrieval
    if path == "direct":
        plan = dataclasses.replace(
            tune.plan(op="ata", m=m, n=n, dtype="float32", devices=1,
                      out="packed"),
            use_kernels=True, n_base=w, nb=2, tile_w=w, packed_block=w)
        kw = dict(plan=plan)
    else:
        kw = dict(n_base=64, nb=2)
    mesh = jax.make_mesh((1,), ("model",))
    obs.enable()
    obs.metrics.reset()
    jaxpr = jax.make_jaxpr(
        lambda a: schedule(a, mesh, task_axis="model", out="packed", **kw)
    )(jax.ShapeDtypeStruct((m, n), jnp.float32))
    counts = {k: obs.metrics.get(f"distributed.tiles_{k}")
              for k in ("direct", "sliced")}
    art = check.Artifact(label=f"tile:packed:{path}", jaxpr=jaxpr.jaxpr,
                         overrides={"forbidden_squares": {(n, n)}})
    report = check.run(art, rules=["no-dense-square"])
    assert not report.violations, report.summary()
    eqns = [site.eqn for site in walk_eqns(jaxpr.jaxpr)]
    stripe_cuts = [e for e in eqns if e.primitive.name == "dynamic_slice"
                   and e.invars[0].aval.shape == (m, n)
                   and e.outvars[0].aval.shape == (m, w)]
    launches = [e.params["name"] for e in eqns
                if e.primitive.name == "pallas_call"]
    if path == "direct":
        assert counts == {"direct": tiles, "sliced": 0}, counts
        assert not stripe_cuts
        assert launches == ["gemm_tn_fused"], launches
    else:
        assert counts == {"direct": 0, "sliced": tiles}, counts
        assert len(stripe_cuts) == 2 * tiles
        assert not launches


def test_tile_parallel_packed_no_dense_intermediate():
    """The psum schedule's packed jaxpr on the sliced tile path (see
    :func:`_check_tile_schedule_jaxpr`)."""
    _check_tile_schedule_jaxpr(ata_tile_parallel, "sliced")


@pytest.mark.parametrize("schedule,path", [
    ("psum", "direct"), ("bfs_dfs", "direct"), ("bfs_dfs", "sliced")])
def test_tile_schedule_jaxpr_by_path(schedule, path):
    """Both schedules build their tile stacks through one helper, so each
    takes either path: the checks of
    :func:`_check_tile_schedule_jaxpr` on the other three combinations."""
    from repro.core.distributed import ata_bfs_dfs

    fn = {"psum": ata_tile_parallel, "bfs_dfs": ata_bfs_dfs}[schedule]
    _check_tile_schedule_jaxpr(fn, path)


@pytest.mark.parametrize("m", [131072, 262144])
def test_direct_path_needs_room_for_the_stripe_copy(m):
    """The planner's per-device memory figure for a direct-path plan holds
    a second copy of the A shard (the stripe-major copy the fused launch
    reads), and the tile stack takes the direct path only where that
    figure fits the plan's machine. For the f32 Gram of width 16384 on a
    2x2 v5e mesh the planner admits the BFS plan at both heights by the
    figure without the copy; with it, 9.6e9 bytes fit at m = 131072 and
    18.2e9 do not at m = 262144, which keeps the sliced path."""
    from repro import tune
    from repro.core.distributed import _takes_direct
    from repro.tune import cost

    plan = tune.plan(op="ata", m=m, n=16384, dtype="float32", out="packed",
                     devices=2, row_devices=2, backend="tpu")
    assert plan.use_kernels and plan.algorithm != "dense"
    assert plan.n_base >= plan.tile_w  # each tile is one kernel leaf
    args = (plan.comm_schedule, plan.nb, plan.tile_w, 2, 2)
    lean = cost.comm_memory_bytes(*args, m=m, out="packed")
    full = cost.comm_memory_bytes(*args, m=m, out="packed", stripes=True)
    assert full - lean == (m // 2) * plan.nb * plan.tile_w * 4
    room = cost.machine_for("tpu").device_memory_bytes
    assert lean <= room
    direct = _takes_direct(
        plan, True, plan.n_base, m=m, nb=plan.nb, w=plan.tile_w,
        schedule=plan.comm_schedule, p_task=2, d_row=2, out="packed",
        itemsize=4)
    assert direct == (full <= room) == (m == 131072), (full, room)


class _StubMesh:
    """mesh.shape stand-in: the divisibility validations read only the axis
    sizes, which lets the >1-device error paths run on a 1-device host."""

    def __init__(self, shape):
        self.shape = shape


def test_tile_parallel_row_axis_must_divide_m():
    """row_axis sharding of m is validated up front (not an opaque
    shard_map failure): the row_axis size must divide m."""
    mesh = _StubMesh({"data": 2, "model": 1})
    with pytest.raises(ValueError, match=r"row_axis 'data' size 2 must divide m=97"):
        ata_tile_parallel(
            jnp.zeros((97, 64), jnp.float32), mesh,
            task_axis="model", row_axis="data", n_base=32, nb=2,
        )


def test_colshard_divisibility_messages():
    """Regression: the k % p_task check used to raise the inverted message
    'k={k} must divide task axis {p}'; the requirement runs the other way —
    the task axis size must divide k. row_axis divisibility of m is now
    validated the same way instead of failing opaquely inside shard_map."""
    from repro.core.distributed import gemm_tn_colshard

    mesh = _StubMesh({"data": 2, "model": 3})
    a = jnp.zeros((64, 32), jnp.float32)
    with pytest.raises(
        ValueError, match=r"task axis 'model' size 3 must divide k=16"
    ):
        gemm_tn_colshard(a, jnp.zeros((64, 16), jnp.float32), mesh,
                         task_axis="model")
    with pytest.raises(
        ValueError, match=r"row_axis 'data' size 2 must divide the contraction dim m=63"
    ):
        gemm_tn_colshard(
            jnp.zeros((63, 32), jnp.float32),
            jnp.zeros((63, 9), jnp.float32),
            mesh, task_axis="model", row_axis="data",
        )


def test_choose_tiling_properties():
    for n in [256, 1000, 4096]:
        for p in [1, 2, 4, 8, 16]:
            nb, w = choose_tiling(n, p)
            t = nb * (nb + 1) // 2
            assert t >= p
            assert nb * w >= n
            assert w % 8 == 0


def test_choose_tiling_covers_triangle_exactly_once_and_balanced():
    """Property sweep over a broad (n, p) grid: the tile enumeration covers
    the padded lower-triangle block grid exactly once, and the contiguous
    per-device split stays α-balanced (α = 1/2 → makespan ≤ 1.5·ideal;
    the waste-minimizing search actually achieves ≤ ~1.003 on this grid,
    asserted at 1.25 to leave headroom, not to weaken the α claim)."""
    import numpy as np

    for n in [128, 200, 777, 1000, 2048, 4096, 8192]:
        for p in [1, 2, 3, 5, 7, 8, 12, 16, 24, 32, 48, 64]:
            nb, w = choose_tiling(n, p)
            t_total = nb * (nb + 1) // 2
            # exactly-once coverage of the lower block triangle
            cover = np.zeros((nb, nb), dtype=int)
            for t in range(t_total):
                i = int((np.sqrt(8 * t + 1) - 1) // 2)
                if i * (i + 1) // 2 > t:
                    i -= 1
                j = t - i * (i + 1) // 2
                assert j <= i
                cover[i, j] += 1
            low = np.tril_indices(nb)
            assert (cover[low] == 1).all()
            assert np.triu(cover, 1).sum() == 0
            # α-balance of the uniform-tile split (t_per·p within 1.5·T)
            t_per = -(-t_total // p)
            assert t_per * p <= 1.25 * t_total


def test_masked_dummy_tiles_flop_model_matches_lpt():
    """Regression for the dummy-tile recompute: per-device flops of the
    masked schedule must sum to exactly T tiles' worth (the clamped seed
    recomputed tile T−1 up to t_per−1 extra times per device) and the
    makespan must equal the LPT makespan of T uniform tile tasks — checked
    on (nb, p) combinations with T % p != 0."""
    from repro.core.reference import classical_gemm_flops, strassen_tn_flops

    m, n = 256, 192
    for p, nb in [(8, 4), (3, 4), (7, 5), (4, 5)]:
        w = -(-(-(-n // nb)) // 8) * 8
        t_total = nb * (nb + 1) // 2
        assert t_total % p != 0, (p, nb)
        for use_strassen, n_base in [(True, 32), (False, None)]:
            per_dev = tile_parallel_device_flops(
                m, n, p, nb=nb, n_base=n_base, use_strassen=use_strassen
            )
            tile = (
                strassen_tn_flops(m, w, w, 32)
                if use_strassen
                else classical_gemm_flops(m, w, w)
            )
            assert len(per_dev) == p
            # no dummy recompute: total is exactly T tiles
            assert sum(per_dev) == t_total * tile
            # LPT of T uniform tasks: makespan = ceil(T/p) tiles
            assert max(per_dev) == -(-t_total // p) * tile
            # the clamped seed schedule would have computed this instead:
            assert sum(per_dev) < p * -(-t_total // p) * tile


# --- 8-device subprocess checks ---------------------------------------------

TILE_SCRIPT = r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core.distributed import ata_tile_parallel
assert len(jax.devices()) == 8, jax.devices()
mesh = jax.make_mesh((8,), ("model",))
r = np.random.default_rng(0)
a = jnp.asarray(r.standard_normal((256, 192)), dtype=jnp.float32)
c = jax.jit(lambda a: ata_tile_parallel(a, mesh, task_axis="model", n_base=32))(a)
np.testing.assert_allclose(np.asarray(c), np.asarray(a.T @ a), rtol=1e-4, atol=1e-4)
assert (np.asarray(c) == np.asarray(c).T).all()
print("OK")
"""

TILE_2D_SCRIPT = r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core.distributed import ata_tile_parallel
mesh = jax.make_mesh((2, 4), ("data", "model"))
r = np.random.default_rng(1)
a = jnp.asarray(r.standard_normal((128, 160)), dtype=jnp.float32)
a = jax.device_put(a, NamedSharding(mesh, P("data", None)))
f = jax.jit(lambda a: ata_tile_parallel(
    a, mesh, task_axis="model", row_axis="data", n_base=32))
c = f(a)
np.testing.assert_allclose(np.asarray(c), np.asarray(a).T @ np.asarray(a), rtol=1e-4, atol=1e-4)
# collective check: the psum reduces the packed tile stack, not dense (n,n)
from repro.analysis.hlo import compiled_text
hlo = compiled_text(f, a)
assert "all-reduce" in hlo or "all-gather" in hlo
print("OK")
"""

ROWSHARD_SCRIPT = r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core.distributed import gram_rowshard
mesh = jax.make_mesh((8,), ("data",))
r = np.random.default_rng(2)
a = jnp.asarray(r.standard_normal((512, 96)), dtype=jnp.float32)
f = jax.jit(jax.shard_map(
    lambda x: gram_rowshard(x, "data", n_base=32),
    mesh=mesh, in_specs=(P("data", None),), out_specs=P(None, None)))
c = f(a)
np.testing.assert_allclose(np.asarray(c), np.asarray(a.T @ a), rtol=1e-4, atol=1e-4)
print("OK")
"""

COLSHARD_SCRIPT = r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core.distributed import gemm_tn_colshard
mesh = jax.make_mesh((2, 4), ("data", "model"))
r = np.random.default_rng(3)
a = jnp.asarray(r.standard_normal((256, 96)), dtype=jnp.float32)
b = jnp.asarray(r.standard_normal((256, 64)), dtype=jnp.float32)
# replicated inputs, task axis only
c = jax.jit(lambda a, b: gemm_tn_colshard(a, b, mesh, task_axis="model", n_base=32))(a, b)
np.testing.assert_allclose(np.asarray(c), np.asarray(a.T @ b), rtol=1e-4, atol=1e-4)
# row-sharded contraction + psum
a2 = jax.device_put(a, NamedSharding(mesh, P("data", None)))
b2 = jax.device_put(b, NamedSharding(mesh, P("data", "model")))
c2 = jax.jit(lambda a, b: gemm_tn_colshard(
    a, b, mesh, task_axis="model", row_axis="data", n_base=32))(a2, b2)
np.testing.assert_allclose(np.asarray(c2), np.asarray(a.T @ b), rtol=1e-4, atol=1e-4)
print("OK")
"""


TILE_RAGGED_SCRIPT = r"""
import jax, jax.numpy as jnp, numpy as np
from repro.core.distributed import ata_tile_parallel
mesh = jax.make_mesh((8,), ("model",))
r = np.random.default_rng(4)
a = jnp.asarray(r.standard_normal((256, 192)), dtype=jnp.float32)
# nb=4 -> T=10 tiles over 8 devices: t_per=2, 6 dummy slots (devices 5-7
# fully dummy) -- the cond-masked path, not the clamp-recompute path.
c = jax.jit(lambda a: ata_tile_parallel(
    a, mesh, task_axis="model", nb=4, n_base=32))(a)
np.testing.assert_allclose(np.asarray(c), np.asarray(a.T @ a), rtol=1e-4, atol=1e-4)
assert (np.asarray(c) == np.asarray(c).T).all()
print("OK")
"""


TILE_PACKED_SCRIPT = r"""
import jax, jax.numpy as jnp, numpy as np
from repro.core.distributed import ata_tile_parallel
from repro.core.symmetric import SymmetricMatrix
from repro.core.reference import syrk_ref
assert len(jax.devices()) == 8
mesh = jax.make_mesh((8,), ("model",))
r = np.random.default_rng(5)
a = jnp.asarray(r.standard_normal((256, 192)), dtype=jnp.float32)
# nb=4 -> T=10 over 8 devices: T % p != 0 (dummy cond slots) AND w=48 is
# misaligned with the packed bn=96 grid -> the repack path.
for nb in (None, 4):
    dense = jax.jit(lambda a, nb=nb: ata_tile_parallel(
        a, mesh, task_axis="model", n_base=32, nb=nb))(a)
    packed = jax.jit(lambda a, nb=nb: ata_tile_parallel(
        a, mesh, task_axis="model", n_base=32, nb=nb, out="packed"))(a)
    assert isinstance(packed, SymmetricMatrix), type(packed)
    # bitwise parity with the dense schedule on the same tiling
    assert (np.asarray(packed.to_dense()) == np.asarray(dense)).all(), nb
    # and correctness vs the sequential reference
    ref = np.asarray(syrk_ref(a))
    np.testing.assert_allclose(np.asarray(dense), ref, rtol=1e-4, atol=1e-4)
print("OK")
"""

TILE_2D_PACKED_SCRIPT = r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core.distributed import ata_tile_parallel
mesh = jax.make_mesh((2, 4), ("data", "model"))
r = np.random.default_rng(6)
a = jnp.asarray(r.standard_normal((128, 160)), dtype=jnp.float32)
a = jax.device_put(a, NamedSharding(mesh, P("data", None)))
f_dense = jax.jit(lambda a: ata_tile_parallel(
    a, mesh, task_axis="model", row_axis="data", n_base=32))
f_packed = jax.jit(lambda a: ata_tile_parallel(
    a, mesh, task_axis="model", row_axis="data", n_base=32, out="packed"))
dense, packed = f_dense(a), f_packed(a)
assert (np.asarray(packed.to_dense()) == np.asarray(dense)).all()
np.testing.assert_allclose(np.asarray(dense), np.asarray(a).T @ np.asarray(a),
                           rtol=1e-4, atol=1e-4)
print("OK")
"""

ROWSHARD_PACKED_SCRIPT = r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core.distributed import gram_rowshard
from repro.analysis.hlo import collective_bytes, compiled_text
mesh = jax.make_mesh((8,), ("data",))
r = np.random.default_rng(7)
a = jnp.asarray(r.standard_normal((512, 96)), dtype=jnp.float32)
fd = jax.jit(jax.shard_map(
    lambda x: gram_rowshard(x, "data", n_base=32),
    mesh=mesh, in_specs=(P("data", None),), out_specs=P(None, None)))
# packed_block=24 -> a 4x4 packed grid (T=10 of 16 blocks): the psum moves
# T*bn^2 = 0.625*n^2 words; n=96 with the default 128-block would be a
# single block (no saving to observe)
fp = jax.jit(jax.shard_map(
    lambda x: gram_rowshard(x, "data", n_base=32, out="packed",
                            packed_block=24),
    mesh=mesh, in_specs=(P("data", None),), out_specs=P(None, None, None)))
dense, packed = fd(a), fp(a)
np.testing.assert_allclose(np.asarray(packed.to_dense()), np.asarray(dense),
                           rtol=1e-5, atol=1e-5)
np.testing.assert_allclose(np.asarray(dense), np.asarray(a.T @ a),
                           rtol=1e-4, atol=1e-4)
# the psum payload is the packed stack: T/nb^2 = 10/16 of the dense bytes
bd = sum(collective_bytes(compiled_text(fd, a)).values())
bp = sum(collective_bytes(compiled_text(fp, a)).values())
assert 0 < bp < 0.7 * bd, (bp, bd)
print("OK")
"""

TILE_BF16_SCRIPT = r"""
import jax, jax.numpy as jnp, numpy as np
from repro.core.distributed import ata_tile_parallel
mesh = jax.make_mesh((8,), ("model",))
r = np.random.default_rng(8)
a = jnp.asarray(r.standard_normal((128, 192)), dtype=jnp.bfloat16)
# nb=4 -> dummy cond slots on trailing devices; with a bf16 accumulation
# dtype the seed's hardcoded f32 zero tile made the cond branches disagree
# on dtype and fail to trace (regression for the eval_shape-derived dummy).
c = jax.jit(lambda a: ata_tile_parallel(
    a, mesh, task_axis="model", n_base=32, nb=4,
    acc_dtype=jnp.bfloat16))(a)
assert c.dtype == jnp.bfloat16, c.dtype
ref = np.asarray(a, np.float32).T @ np.asarray(a, np.float32)
np.testing.assert_allclose(np.asarray(c, np.float32), ref,
                           rtol=0.1, atol=2.0)
print("OK")
"""

BATCHED_DISPATCH_SCRIPT = r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core.distributed import ata_tile_parallel, gemm_tn_colshard, gram_rowshard
mesh = jax.make_mesh((8,), ("model",))
r = np.random.default_rng(10)
a = jnp.asarray(r.standard_normal((256, 192)), dtype=jnp.float32)
# the per-device tile bodies inherit the batched dispatch: bitwise parity with
# the unrolled schedule on the same tiling (leaf_dispatch never changes
# values, only how the leaves reach the hardware)
mk = lambda ld: jax.jit(lambda a: ata_tile_parallel(
    a, mesh, task_axis="model", n_base=32, variant="strassen",
    leaf_dispatch=ld))
cu, cb = mk("unrolled")(a), mk("batched")(a)
assert (np.asarray(cu) == np.asarray(cb)).all()
np.testing.assert_allclose(np.asarray(cb), np.asarray(a.T @ a),
                           rtol=1e-4, atol=1e-4)
# colshard stripes through the batched per-device body
b = jnp.asarray(r.standard_normal((256, 64)), dtype=jnp.float32)
mkg = lambda ld: jax.jit(lambda a, b: gemm_tn_colshard(
    a, b, mesh, task_axis="model", n_base=32, variant="strassen",
    leaf_dispatch=ld))
gu, gb = mkg("unrolled")(a, b), mkg("batched")(a, b)
assert (np.asarray(gu) == np.asarray(gb)).all()
# rowshard: batched local gram under the psum
mesh2 = jax.make_mesh((8,), ("data",))
a2 = jnp.asarray(r.standard_normal((512, 96)), dtype=jnp.float32)
mkr = lambda ld: jax.jit(jax.shard_map(
    lambda x: gram_rowshard(x, "data", n_base=32, variant="strassen",
                            leaf_dispatch=ld),
    mesh=mesh2, in_specs=(P("data", None),), out_specs=P(None, None)))
ru, rb = mkr("unrolled")(a2), mkr("batched")(a2)
assert (np.asarray(ru) == np.asarray(rb)).all()
print("OK")
"""

BFSDFS_PARITY_SCRIPT = r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core.distributed import ata_bfs_dfs, ata_tile_parallel
from repro.core.symmetric import SymmetricMatrix
mesh = jax.make_mesh((2, 4), ("data", "model"))
r = np.random.default_rng(11)
a = jnp.asarray(r.standard_normal((256, 192)), dtype=jnp.float32)
a = jax.device_put(a, NamedSharding(mesh, P("data", None)))
# pin ONE (nb, packed_block) grid on both schedules: every interleaving is
# value-identical (the tri-direct scatter only adds zeros), so parity with
# the psum schedule is bitwise, not allclose. nb=4 -> T=10 over pool=8:
# t_pad=16, every device owns a padded chunk — the dummy-slot path too.
kw = dict(mesh=mesh, task_axis="model", row_axis="data", n_base=32, nb=4,
          packed_block=48)
dense0 = jax.jit(lambda a: ata_tile_parallel(a, **kw))(a)
packed0 = jax.jit(lambda a: ata_tile_parallel(a, out="packed", **kw))(a)
np.testing.assert_allclose(np.asarray(dense0), np.asarray(a).T @ np.asarray(a),
                           rtol=1e-4, atol=1e-4)
for il in ("D", "B", "BD", "DB"):
    dense = jax.jit(lambda a, il=il: ata_bfs_dfs(a, interleaving=il, **kw))(a)
    assert (np.asarray(dense) == np.asarray(dense0)).all(), il
    packed = jax.jit(lambda a, il=il: ata_bfs_dfs(
        a, interleaving=il, out="packed", **kw))(a)
    assert isinstance(packed, SymmetricMatrix), type(packed)
    assert (np.asarray(packed.to_dense())
            == np.asarray(packed0.to_dense())).all(), il
print("OK")
"""

BFSDFS_LEAF_DISPATCH_SCRIPT = r"""
import jax, jax.numpy as jnp, numpy as np
from repro.core.distributed import ata_bfs_dfs
mesh = jax.make_mesh((8,), ("model",))
r = np.random.default_rng(12)
a = jnp.asarray(r.standard_normal((256, 384)), dtype=jnp.float32)
# nb=4 -> w=96 > n_base: the per-tile Strassen actually recurses, so the
# two leaf bodies compile genuinely different programs — which must still
# agree bitwise (leaf_dispatch never changes values) under the BFS scatter
mk = lambda ld: jax.jit(lambda a: ata_bfs_dfs(
    a, mesh, task_axis="model", interleaving="B", n_base=32, nb=4,
    packed_block=96, variant="strassen", leaf_dispatch=ld))
cu, cb = mk("unrolled")(a), mk("batched")(a)
assert (np.asarray(cu) == np.asarray(cb)).all()
np.testing.assert_allclose(np.asarray(cb), np.asarray(a.T @ a),
                           rtol=1e-4, atol=1e-4)
print("OK")
"""

BFSDFS_PURE_DFS_SCRIPT = r"""
import jax, jax.numpy as jnp, numpy as np
from repro.analysis.hlo import collective_bytes, compiled_text
from repro.core.distributed import ata_bfs_dfs, ata_tile_parallel
mesh = jax.make_mesh((8,), ("model",))
r = np.random.default_rng(13)
a = jnp.asarray(r.standard_normal((256, 192)), dtype=jnp.float32)
# pure 'D' degenerates to the existing schedule: same default tiling
# (choose_tiling, not bfs_tiling), same plain psum, bitwise outputs AND an
# identical collective footprint — no scatter, no staging buffer
for out in ("dense", "packed"):
    fd = jax.jit(lambda a, out=out: ata_bfs_dfs(
        a, mesh, task_axis="model", interleaving="D", n_base=32, out=out))
    ft = jax.jit(lambda a, out=out: ata_tile_parallel(
        a, mesh, task_axis="model", n_base=32, out=out))
    cd, ct = fd(a), ft(a)
    if out == "packed":
        cd, ct = cd.to_dense(), ct.to_dense()
    assert (np.asarray(cd) == np.asarray(ct)).all(), out
    bd = collective_bytes(compiled_text(fd, a))
    bt = collective_bytes(compiled_text(ft, a))
    assert bd == bt, (out, bd, bt)
print("OK")
"""

BFSDFS_6DEV_SCRIPT = r"""
import jax, jax.numpy as jnp, numpy as np
from repro.core.distributed import ata_bfs_dfs, ata_tile_parallel
from repro.tune.cost import bfs_tiling
assert len(jax.devices()) == 6, jax.devices()
mesh = jax.make_mesh((6,), ("model",))
r = np.random.default_rng(14)
a = jnp.asarray(r.standard_normal((192, 160)), dtype=jnp.float32)
# pool=6 (neither a power of two nor 8): bfs_tiling must still hand back a
# pool-divisible triangle so the scatter chunks exactly
nb, w = bfs_tiling(160, 6, devices=6, out="packed")
assert (nb * (nb + 1) // 2) % 6 == 0, (nb, w)
kw = dict(mesh=mesh, task_axis="model", n_base=32, nb=nb, packed_block=w)
dense0 = jax.jit(lambda a: ata_tile_parallel(a, **kw))(a)
np.testing.assert_allclose(np.asarray(dense0), np.asarray(a.T @ a),
                           rtol=1e-4, atol=1e-4)
for il in ("B", "BD"):
    c = jax.jit(lambda a, il=il: ata_bfs_dfs(a, interleaving=il, **kw))(a)
    assert (np.asarray(c) == np.asarray(dense0)).all(), il
    pk = jax.jit(lambda a, il=il: ata_bfs_dfs(
        a, interleaving=il, out="packed", **kw))(a)
    assert (np.asarray(pk.to_dense()) == np.asarray(dense0)).all(), il
# a user-pinned ragged grid (T=10, 10 % 6 != 0) still scatters correctly:
# t_pad rounds up and the sacrificial row swallows the dummy ids
c2 = jax.jit(lambda a: ata_bfs_dfs(
    a, mesh, task_axis="model", n_base=32, nb=4, interleaving="B"))(a)
ct2 = jax.jit(lambda a: ata_tile_parallel(
    a, mesh, task_axis="model", n_base=32, nb=4))(a)
assert (np.asarray(c2) == np.asarray(ct2)).all()
print("OK")
"""

BFSDFS_RANKING_SCRIPT = r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro.analysis.hlo import collective_bytes, compiled_text
from repro.core.distributed import ata_bfs_dfs, ata_tile_parallel, choose_tiling
from repro.tune import cost
# the alpha-beta comm model's per-mesh ranking (BFS tri-direct scatter vs
# psum) must match the measured collective-bytes ranking at every task
# width P of the 8-device pool — the calibration configuration of the
# collectives_bfsdfs bench rows. Wall clock on fake CPU devices is
# emulation noise (obs.calibrate's drift table shows >2x single-device
# drift), but the compiled collective payload is exact, so bytes are the
# honest comm measurement here. Only the per-mesh B-vs-psum ordering is
# contractual: cross-P psum bytes are non-monotone (GSPMD folds parts of
# the retrieval at some widths), which is exactly why the planner prices
# schedules per mesh instead of reusing one measurement.
m, n = 512, 1024
mach = cost.machine_for("cpu")
r = np.random.default_rng(15)
a0 = jnp.asarray(r.standard_normal((m, n)), dtype=jnp.float32)
for pt in (2, 4, 8):
    d = 8 // pt
    mesh = Mesh(np.asarray(jax.devices()).reshape(d, pt), ("data", "model"))
    a = jax.device_put(a0, NamedSharding(mesh, P("data", None)))
    ra = "data" if d > 1 else None
    nb_b, w_b = cost.bfs_tiling(n, 8, devices=pt, out="packed")
    nb_d, w_d = choose_tiling(n, pt, out="packed")
    model = {
        "B": cost.comm_seconds(mach, "B", nb_b, w_b, pt, d, out="packed"),
        "psum": cost.comm_seconds(mach, None, nb_d, w_d, pt, d,
                                  out="packed"),
    }
    fb = jax.jit(lambda a, nb=nb_b, w=w_b, ra=ra: ata_bfs_dfs(
        a, mesh, task_axis="model", row_axis=ra, interleaving="B",
        n_base=64, nb=nb, packed_block=w, out="packed"))
    fp = jax.jit(lambda a, nb=nb_d, ra=ra: ata_tile_parallel(
        a, mesh, task_axis="model", row_axis=ra, n_base=64, nb=nb,
        out="packed"))
    meas = {
        "B": sum(collective_bytes(compiled_text(fb, a)).values()),
        "psum": sum(collective_bytes(compiled_text(fp, a)).values()),
    }
    assert sorted(model, key=model.get) == sorted(meas, key=meas.get), \
        (pt, model, meas)
    assert model["B"] < model["psum"], (pt, model)
    assert meas["B"] < meas["psum"], (pt, meas)
print("OK")
"""

POWERSGD_SHARDED_SCRIPT = r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.optim import powersgd
mesh = jax.make_mesh((8,), ("data",))
r = np.random.default_rng(9)
m, n, rank = 256, 96, 8
g = jnp.asarray(r.standard_normal((m, n)), dtype=jnp.float32)
state = powersgd.init_state(jax.random.key(0), (m, n), rank)
# reference: single-device compress
p_ref, q_ref, st_ref = powersgd.compress(g, state, n_base=32)
# sharded: row-sharded g/error, packed-psum gram, psum'd Q factor
def sharded(g, err, q):
    st = powersgd.PowerSGDState(q=q, error=err)
    p_l, q_new, st_new = powersgd.compress_sharded(g, st, "data", n_base=32)
    return p_l, q_new, st_new.error
f = jax.jit(jax.shard_map(
    sharded, mesh=mesh,
    in_specs=(P("data", None), P("data", None), P(None, None)),
    out_specs=(P("data", None), P(None, None), P("data", None))))
p_sh, q_sh, err_sh = f(g, state.error, state.q)
np.testing.assert_allclose(np.asarray(p_sh), np.asarray(p_ref),
                           rtol=2e-4, atol=2e-4)
np.testing.assert_allclose(np.asarray(q_sh), np.asarray(q_ref),
                           rtol=2e-4, atol=2e-4)
np.testing.assert_allclose(np.asarray(err_sh), np.asarray(st_ref.error),
                           rtol=2e-4, atol=2e-4)
print("OK")
"""


@pytest.mark.parametrize(
    "script",
    [TILE_SCRIPT, TILE_2D_SCRIPT, ROWSHARD_SCRIPT, COLSHARD_SCRIPT,
     TILE_RAGGED_SCRIPT, TILE_PACKED_SCRIPT, TILE_2D_PACKED_SCRIPT,
     ROWSHARD_PACKED_SCRIPT, TILE_BF16_SCRIPT, BATCHED_DISPATCH_SCRIPT,
     BFSDFS_PARITY_SCRIPT, BFSDFS_LEAF_DISPATCH_SCRIPT,
     BFSDFS_PURE_DFS_SCRIPT, BFSDFS_RANKING_SCRIPT,
     POWERSGD_SHARDED_SCRIPT],
    ids=["tile_8dev", "tile_2d", "rowshard", "colshard", "tile_ragged",
         "tile_packed", "tile_2d_packed", "rowshard_packed", "tile_bf16",
         "batched_dispatch", "bfsdfs_parity", "bfsdfs_leaf_dispatch",
         "bfsdfs_pure_dfs", "bfsdfs_ranking", "powersgd_sharded"],
)
def test_multidevice(script):
    _run_in_subprocess(script)


PLANNED_2X2_SCRIPT = r"""
import dataclasses, re
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro import obs, tune
from repro.core.reference import syrk_ref
from repro.core.symmetric import SymmetricMatrix
from repro.obs import compiles
from repro.tune.apply import ata_distributed_with_plan

TOL = 1e-5   # f32 products over 256 rows: about 1e-7 relative, in float64 terms
mesh = jax.make_mesh((2, 2), ("data", "model"))
m, n = 256, 256
plan = tune.plan(op="ata", m=m, n=n, dtype="float32", out="packed",
                 devices=2, row_devices=2)
assert plan.source == "analytic" and "B" in plan.comm_schedule, plan
if SCHEDULE == "psum":
    # 5 stripes: T = 15 tiles over the 2-device task axis, one dummy slot
    plan = dataclasses.replace(plan, comm_schedule=None, nb=5, tile_w=56)
t_total = plan.nb * (plan.nb + 1) // 2
a_host = np.random.default_rng(16).standard_normal((m, n)).astype(np.float32)
a = jax.device_put(a_host, NamedSharding(mesh, P("data", None)))
want = syrk_ref(a_host.astype(np.float64))


def planned():
    return jax.jit(lambda a: ata_distributed_with_plan(
        a, mesh, plan, task_axis="model", row_axis="data"))


def rel_err(sym):
    assert isinstance(sym, SymmetricMatrix), type(sym)
    got = np.asarray(sym.to_dense(), np.float64)
    return np.linalg.norm(got - want) / np.linalg.norm(want)


if CHECK == "correct":
    err = rel_err(planned()(a))
    assert err <= TOL, err
    # the row collective removed: only row shard 0's partial Gram is reduced
    psum, scatter = jax.lax.psum, jax.lax.psum_scatter

    def first_rows(x):
        keep = jax.lax.axis_index("data") == 0
        return jax.tree.map(lambda v: jnp.where(keep, v, 0), x)

    jax.lax.psum = lambda x, axis, **kw: psum(first_rows(x), axis, **kw)
    jax.lax.psum_scatter = lambda x, axis, **kw: scatter(first_rows(x), axis, **kw)
    broken = rel_err(planned()(a))
    jax.lax.psum, jax.lax.psum_scatter = psum, scatter
    assert broken > 1e4 * TOL, (err, broken)
else:
    obs.enable()
    obs.trace.reset()
    obs.metrics.reset()
    compiles.reset()
    compiled = planned().lower(a).compile()
    # one program holds the root span: the set-up readers find it
    progs = compiles.programs()
    assert len(progs) == 1 and "distributed.ata" in progs[0].roots, progs
    # every collective of the compiled program sits under a distributed.* scope
    kinds = "all-reduce|reduce-scatter|all-gather|collective-permute|all-to-all"
    found = 0
    for line in compiled.as_text().splitlines():
        if not re.search(r"\s(%s)(-start)?\(" % kinds, line):
            continue
        found += 1
        path = re.search(r'op_name="([^"]*)"', line).group(1).split("/")
        scopes = [p for p in path[:-1] if "." in p and not p.startswith("jit(")]
        assert scopes and scopes[-1].startswith("distributed."), line
    assert found, "no collective compiled"
    # the counter holds the packed tile stack each device hands its collective
    w = plan.tile_w
    if SCHEDULE == "psum":
        tiles = -(-t_total // 2)           # t_per slots of the psum
    else:
        tiles = -(-t_total // 4) * 4       # the T-padded staging buffer
        assert tiles * w * w * 4 < 0.6 * n * n * 4
    assert obs.metrics.get("distributed.collective_bytes") == tiles * w * w * 4
print("OK")
"""


TILE_STACK_2X2_SCRIPT = r"""
import dataclasses, functools
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro import obs, tune
from repro.core.distributed import ata_bfs_dfs, ata_tile_parallel
from repro.core.strassen import strassen_tn
from repro.kernels import ops
from repro.kernels.gemm_tn import gemm_tn_pallas

# Interpret-mode kernels cannot run under shard_map's varying-axes check
# (the HLO interpreter's scratch buffers carry no mesh axes); the compile
# for a described v5e (tests/test_chip_compile.py) checks that typing.
jax.shard_map = functools.partial(jax.shard_map, check_vma=False)
mesh = jax.make_mesh((2, 2), ("data", "model"))
m, n = 512, 512
a_host = np.random.default_rng(17).standard_normal((m, n)).astype(np.float32)
a = jax.device_put(a_host, NamedSharding(mesh, P("data", None)))
schedule, pins, path = {
    # BFS over the task axis: 4 and 6 tiles, two dummy slots on one device
    "bfs_direct": (ata_bfs_dfs, dict(nb=4, tile_w=128, n_base=128), "direct"),
    # the psum schedule on a ragged grid: 15 tiles, one dummy slot
    "psum_direct": (ata_tile_parallel, dict(comm_schedule=None, nb=5,
                                            tile_w=104, n_base=128), "direct"),
    # w > n_base: a recursion inside each tile keeps the sliced path
    "bfs_sliced": (ata_bfs_dfs, dict(nb=4, tile_w=128, n_base=64), "sliced"),
}[CASE]
plan = dataclasses.replace(
    tune.plan(op="ata", m=m, n=n, dtype="float32", out="packed", devices=2,
              row_devices=2),
    use_kernels=True, packed_block=pins["tile_w"], **pins)
assert schedule is ata_tile_parallel or "B" in plan.comm_schedule
obs.enable()
obs.metrics.reset()
got = jax.jit(lambda a: schedule(a, mesh, plan=plan, task_axis="model",
                                 row_axis="data", out="packed"))(a)
slots = {"bfs_direct": 6, "psum_direct": 8, "bfs_sliced": 6}[CASE]
other = "sliced" if path == "direct" else "direct"
assert obs.metrics.get(f"distributed.tiles_{path}") == slots
assert obs.metrics.get(f"distributed.tiles_{other}") == 0

# the tile each path must reproduce bitwise: the kernel on the cut stripes
# (direct), or the planned Strassen recursion over them (sliced)
if path == "direct":
    tile = lambda x, y: gemm_tn_pallas(x, y, blocks=plan.gemm_blocks,
                                       interpret=True)
else:
    tile = lambda x, y: strassen_tn(
        x, y, n_base=plan.n_base, variant=plan.variant,
        leaf_dispatch=plan.leaf_dispatch,
        base_dot=functools.partial(ops.gemm_tn, blocks=plan.gemm_blocks))
nb, w = plan.nb, plan.tile_w
x = np.pad(a_host, ((0, 0), (0, nb * w - n)))
low = np.zeros((nb * w, nb * w), np.float32)
for i in range(nb):
    for j in range(i + 1):
        # each row shard's partial tile; the collective sums the two
        p0, p1 = (np.asarray(tile(jnp.asarray(h[:, i * w:(i + 1) * w]),
                                   jnp.asarray(h[:, j * w:(j + 1) * w])))
                  for h in np.split(x, 2))
        low[i * w:(i + 1) * w, j * w:(j + 1) * w] = p0 + p1
low = low[:n, :n]
want = np.tril(low) + np.tril(low, -1).T
dense = np.asarray(got.to_dense())
assert (dense == want).all(), np.abs(dense - want).max()
ref = a_host.astype(np.float64).T @ a_host.astype(np.float64)
err = np.linalg.norm(dense - ref) / np.linalg.norm(ref)
assert err <= 1e-6, err
print("OK")
"""


@pytest.mark.parametrize("case", ["bfs_direct", "psum_direct", "bfs_sliced"])
def test_tile_stack_2x2_matches_kernel_on_cut_stripes(case):
    """Under a kernel plan on a (2, 2) mesh (A's rows over ``data``), both
    schedules' tile stacks give the packed Gram bitwise equal to the one
    assembled from the cut stripes: on the direct path (one fused launch
    over the stripe-major copy, dummy slots dead) each tile is
    ``gemm_tn_pallas`` on its two stripes with the plan's blocks; with
    ``w > n_base`` the sliced path's tile is the planned ``strassen_tn``.
    The answer matches the float64 Gram, and the counters name the path."""
    _run_in_subprocess(f"CASE = {case!r}\n" + TILE_STACK_2X2_SCRIPT,
                       devices=4)


@pytest.mark.parametrize("check", ["correct", "obs"])
@pytest.mark.parametrize("schedule", ["plan", "psum"])
def test_planned_2x2_packed_gram(schedule, check):
    """The planned f32 packed Gram on a (2, 2) mesh, A's rows over ``data``
    and the tiles over ``model``, under the planner's BFS interleaving
    (nb=16: 64 and 72 tiles on the two task devices, dummy slots on the
    first) and under the psum schedule on a ragged 15-tile grid: it matches
    the float64 reference; with the row collective reduced to one row
    shard it does not; one program holds the ``distributed.ata`` root span;
    every collective is scoped ``distributed.*``; and
    ``distributed.collective_bytes`` counts the packed stack."""
    _run_in_subprocess(f"SCHEDULE, CHECK = {schedule!r}, {check!r}\n"
                       + PLANNED_2X2_SCRIPT, devices=4)


def test_bfsdfs_six_devices():
    """BFS/DFS on a 6-device pool — not a power of two, not the 8 the other
    scripts assume: bfs_tiling's pool-divisible triangle, subgroup splits
    over {1,2,3,6}-device groups, and the ragged user-pinned grid."""
    _run_in_subprocess(BFSDFS_6DEV_SCRIPT, devices=6)


SP_DECODE_SCRIPT = r"""
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_mesh
from repro.configs.registry import get_smoke
from repro.models import layers as L

mesh = make_mesh((2, 4), ("data", "model"))
cfg = get_smoke("command-r-plus-104b")  # GQA groups > 1
p = L.init_attn(jax.random.key(0), cfg)
rng = np.random.default_rng(0)
b, s_cache = 4, 32
ck = jnp.asarray(rng.standard_normal((b, s_cache, cfg.num_kv_heads, cfg.head_dim)), jnp.float32)
cv = jnp.asarray(rng.standard_normal((b, s_cache, cfg.num_kv_heads, cfg.head_dim)), jnp.float32)
pos = jnp.asarray([5, 9, 13, 31], jnp.int32)
x = jnp.asarray(rng.standard_normal((b, 1, cfg.d_model)), jnp.float32)
for window in (None, 7):
    ref_out, ref_ck, ref_cv = L.attention_decode(p, x, cfg, ck, cv, pos, window=window)
    sp_out, sp_ck, sp_cv = L.attention_decode_sp(p, x, cfg, ck, cv, pos, mesh, window=window)
    np.testing.assert_allclose(np.asarray(sp_out), np.asarray(ref_out), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(sp_ck), np.asarray(ref_ck), rtol=1e-5, atol=1e-5)
print("OK")
"""


def test_seq_parallel_flash_decode():
    """shard_map flash-decode (seq-sharded cache, local slot write, psum
    softmax combine) must match the reference decode attention."""
    _run_in_subprocess(SP_DECODE_SCRIPT)


CP_ATTENTION_SCRIPT = r"""
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_mesh
from repro.configs.registry import get_smoke
from repro.models import layers as L
from repro.models.transformer import forward_train, init

mesh = make_mesh((2, 4), ("data", "model"))
cfg = get_smoke("hymba-1.5b")
p = L.init_attn(jax.random.key(0), cfg)
rng = np.random.default_rng(0)
x = jnp.asarray(rng.standard_normal((2, 64, cfg.d_model)), jnp.float32)
for window in (None, 8):
    want = L.attention_train(p, x, cfg, window=window)
    got = L.attention_train_cp(p, x, cfg, mesh, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
    # return_kv path (prefill)
    got2, (k, v) = L.attention_train_cp(p, x, cfg, mesh, window=window,
                                        return_kv=True)
    np.testing.assert_allclose(np.asarray(got2), np.asarray(want),
                               rtol=2e-4, atol=2e-4)

# end-to-end hybrid forward: mesh (CP+p_major) vs no-mesh reference
params = init(jax.random.key(1), cfg)
tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 64)), jnp.int32)
ref, _ = forward_train(params, {"tokens": tokens}, cfg, None,
                       compute_dtype=jnp.float32)
got, _ = forward_train(params, {"tokens": tokens}, cfg, mesh,
                       compute_dtype=jnp.float32)
np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                           rtol=5e-3, atol=5e-3)
print("OK")
"""


def test_context_parallel_attention():
    """CP attention (q-seq over model, shard_map) must match the reference,
    including the full hymba forward with p_major SSD sharding."""
    _run_in_subprocess(CP_ATTENTION_SCRIPT)
