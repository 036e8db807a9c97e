"""Compile-only checks of the Pallas kernels and planned programs for a v5e.

Nothing runs: each program is lowered and compiled for a described (not
attached) ``v5e:2x2`` topology, which shows what interpret mode cannot —
whether Mosaic accepts the kernels at real block shapes, and whether the
sharded schedules compile with kernels inside ``shard_map``. Every compiled
text must hold a ``tpu_custom_call``.

The topology is described inside a module-scope fixture, never at import:
only one process at a time may load the TPU compiler's library, and the
fixture runs only in the worker that was given this file.
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro import tune
from repro.core.ata import ata
from repro.core.distributed import ata_bfs_dfs, ata_tile_parallel
from repro.core.strassen import _to_blocks
from repro.kernels import ops
from repro.kernels.gemm_tn import gemm_tn_fused_pallas, gemm_tn_pallas
from repro.kernels.potrf import potrf_pallas
from repro.kernels.syrk import syrk_pallas
from repro.kernels.trsm import trsm_pallas


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles for a described chip are written to a persistent cache but
    # cannot be read back without one: keep any cache out of this module.
    # Other test modules turn on 64-bit types at import; the chip runs with
    # them off, and Mosaic cannot lower the kernels' index math with them on.
    was = (jax.config.jax_enable_compilation_cache, jax.config.jax_enable_x64)
    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_enable_x64", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", was[0])
    jax.config.update("jax_enable_x64", was[1])


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_kernels(monkeypatch):
    """Kernel wrappers resolve ``interpret=None`` to compiled Mosaic."""
    monkeypatch.setattr(ops, "interpret_default", lambda: False)


def _text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _strassen_tables():
    """One level of classical Strassen as ``gemm_tn_fused`` slot tables over
    a 2×2 leaf-block grid (blocks numbered 11, 12, 21, 22 → 0..3): seven
    two-slot leaves per side, signs ±1 and 0 for an empty slot."""
    a = ((0, 3, 1), (1, 3, 1), (0, 0, 0), (3, 0, 0), (0, 2, 1), (1, 0, -1),
         (2, 3, -1))
    b = ((0, 3, 1), (0, 0, 0), (1, 3, -1), (2, 0, -1), (3, 0, 0), (0, 1, 1),
         (2, 3, 1))

    def side(combos):
        blocks = np.array([[p, q] for p, q, _ in combos], np.int32)
        sgn = np.array([[1, s] for _, _, s in combos], np.int32)
        return blocks // 2, blocks % 2, sgn

    return side(a), side(b)


def _kernel_programs():
    syrk_blocks, gemm_blocks = (512, 256), (512, 512, 256)
    return {
        "syrk_dense": (lambda a: syrk_pallas(a, blocks=syrk_blocks, interpret=False),
                       [((2048, 1024), jnp.float32)]),
        "syrk_packed": (lambda a: syrk_pallas(a, blocks=syrk_blocks, out="packed",
                                              interpret=False).blocks,
                        [((2048, 1024), jnp.float32)]),
        "syrk_batched": (lambda a: syrk_pallas(a, blocks=syrk_blocks, interpret=False),
                         [((4, 1024, 512), jnp.bfloat16)]),
        "gemm_tn": (lambda a, b: gemm_tn_pallas(a, b, blocks=gemm_blocks, interpret=False),
                    [((2048, 1024), jnp.float32), ((2048, 768), jnp.float32)]),
        "gemm_tn_fused": (lambda a, b: gemm_tn_fused_pallas(
            _to_blocks(a, 1)[None], _to_blocks(b, 1)[None], _strassen_tables(),
            blocks=gemm_blocks, interpret=False),
            [((2048, 2048), jnp.float32), ((2048, 1024), jnp.float32)]),
        "potrf": (lambda a: potrf_pallas(a, interpret=False),
                  [((4, 128, 128), jnp.float32)]),
        "trsm": (lambda l, b: trsm_pallas(l, b, interpret=False),
                 [((4, 128, 128), jnp.float32), ((4, 512, 128), jnp.float32)]),
    }


@pytest.mark.parametrize("name", list(_kernel_programs()))
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, shapes = _kernel_programs()[name]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    assert "tpu_custom_call" in _text(fn, *args)


def test_planned_bf16_ata_compiles_for_v5e(one_chip, compiled_kernels):
    plan = tune.plan(op="ata", m=8192, n=8192, dtype="bfloat16", backend="tpu")
    assert plan.use_kernels
    a = jax.ShapeDtypeStruct((8192, 8192), jnp.bfloat16, sharding=one_chip)
    assert "tpu_custom_call" in _text(lambda a: ata(a, plan=plan), a)


@pytest.mark.parametrize(
    "schedule,shape",
    [(ata_tile_parallel, (1, 4)), (ata_bfs_dfs, (1, 4)), (ata_bfs_dfs, (2, 2))],
    ids=["psum", "bfs_dfs", "bfs_dfs_rowshard"])
def test_sharded_kernel_schedule_compiles_for_v5e(schedule, shape, topo,
                                                  compiled_kernels):
    """Kernels inside ``shard_map`` state their varying mesh axes, and the
    dummy-slot zero tiles of a ragged tiling (nb=5: 15 tiles over the task
    axis) match them; the (2, 2) mesh also shards A's rows."""
    mesh = Mesh(np.asarray(topo.devices).reshape(shape), ("data", "model"))
    row_axis = "data" if shape[0] > 1 else None
    plan = dataclasses.replace(
        tune.plan(op="ata", m=2048, n=2048, dtype="bfloat16", devices=shape[1],
                  row_devices=shape[0], backend="tpu"),
        algorithm="winograd", use_kernels=True)
    a = jax.ShapeDtypeStruct((2048, 2048), jnp.bfloat16,
                             sharding=NamedSharding(mesh, P(row_axis)))
    text = _text(lambda a: schedule(a, mesh, plan=plan, nb=5,
                                    row_axis=row_axis), a)
    assert "tpu_custom_call" in text


def test_row_sharded_f32_gram_cell_compiles_for_v5e(topo, compiled_kernels):
    """The program of the cell ``gram-f32-2x2.rowshard_131072x16384`` at its
    full size: the planner's schedule for an f32 (131072, 16384) A with its
    rows over ``data`` and its tiles over ``model`` of a 2×2 mesh compiles
    with the Pallas launches inside ``shard_map``; each chip's 72 tiles of
    1024² are one ``gemm_tn_fused`` launch over the stripe-major copy of its
    A shard, left in the entry computation (not fused into the staging
    scatter, where the benchmark's launch reader would not find it); its
    one collective is the reduce-scatter of the packed tile stack under
    ``distributed.psum_scatter``; and two operand shards (the cell's pool),
    the temporaries and the answer fit one chip's 16 GiB with a tenth to
    spare."""
    from repro.tune.apply import ata_distributed_with_plan

    mesh = Mesh(np.asarray(topo.devices).reshape(2, 2), ("data", "model"))
    plan = tune.plan(op="ata", m=131072, n=16384, dtype="float32", out="packed",
                     devices=2, row_devices=2, backend="tpu")
    assert plan.use_kernels and "B" in plan.comm_schedule
    a = jax.ShapeDtypeStruct((131072, 16384), jnp.float32,
                             sharding=NamedSharding(mesh, P("data", None)))
    compiled = jax.jit(lambda a: ata_distributed_with_plan(
        a, mesh, plan, task_axis="model", row_axis="data")).lower(a).compile()
    text = compiled.as_text()
    computation, launches = None, []
    for line in text.splitlines():
        if line.endswith("{") and line.startswith(("%", "ENTRY %")):
            computation = line.split()[0]
        if 'custom_call_target="tpu_custom_call"' in line:
            launches.append((computation, line.split()[0]))
    assert len(launches) == 1, launches
    assert launches[0][0] == "ENTRY", launches
    assert launches[0][1].startswith("%gemm_tn_fused"), launches
    collectives = [line for line in text.splitlines()
                   if " reduce-scatter(" in line or " all-reduce(" in line
                   or " all-gather(" in line or " collective-permute(" in line]
    assert len(collectives) == 1, collectives
    assert "distributed.psum_scatter/reduce_scatter" in collectives[0]
    mem = compiled.memory_analysis()
    per_chip = (2 * mem.argument_size_in_bytes + mem.temp_size_in_bytes
                + mem.output_size_in_bytes)
    assert per_chip < 0.9 * 16 * 2**30, per_chip
