"""The four-chip cell ``gram-f32-2x2.rowshard_131072x16384`` on the CPU:
its files resolve, its two readers (``dist.collective_share``,
``dist.imbalance``) read a synthetic four-chip trace, its reference agrees
with a float64 product, and, on four virtual CPU devices at a small size,
the harness's comparison passes the program and fails the control and a
program that sums one row shard.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""

import os
import subprocess
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import cells, trace
from bench.reference import gram_2x2 as ref

CELL = "gram-f32-2x2.rowshard_131072x16384"


def test_the_cell_resolves_to_four_chips_and_its_limits():
    spec = cells.resolve(CELL)
    assert spec.chips == 4
    assert spec.config["mesh"] == {"data": 2, "model": 2}
    assert spec.traffic["shape"] == [131072, 16384]
    assert spec.config["limits"]["tile_rel_err"] is not None
    assert {m["name"] for m in spec.per_layer} >= {
        "dist.collective_share", "dist.imbalance"}
    assert {m["name"] for m in spec.end_to_end} == {"setup_s", "call_s"}


def _ctx(ops, op_names):
    """Four chips' ops, each ``(device, name, opcode, seconds)``, back to
    back on their chip from the window's start."""
    red_ops, ends = [], {}
    for dev, name, opcode, sec in ops:
        t = ends.get(dev, 0.0)
        red_ops.append(trace.Op(dev, t, t + sec * 1e9, name, opcode, ""))
        ends[dev] = t + sec * 1e9
    end = max(ends.values())
    hlo = trace.HloIndex(op_names=dict(op_names), mxu_computations=set())
    red = trace.Reduced(ops=red_ops, spans=[], window=(0.0, end), devices=4,
                        busy_s=0.0, hlo=hlo)
    return types.SimpleNamespace(reduced=red, launches={})


J = "jit(<lambda>)/shard_map"


def _four_chips():
    # chip 3 does 1.5 s of tiles, the others 1.0 s; each ends in a 0.1 s
    # reduce-scatter, and chip 0 adds a 0.05 s all-gather-start under the
    # assembly scope and a 0.05 s fusion under the reduction's scope. Chip
    # 3's last 0.2 s of tiles run inside a conditional, whose span the
    # trace shows beside them: it is no work of its own
    ops, names = [], {
        "reduce-scatter.7": f"{J}/distributed.psum_scatter/reduce_scatter",
        "fusion.2": f"{J}/distributed.psum_scatter/select_n",
        "all-gather-start.1": "jit(<lambda>)/distributed.assemble/gather",
        "gemm_tn.3": f"{J}/distributed.tile_body/kernels.gemm_tn",
        "fusion.9": f"{J}/distributed.tile_body/cond/dynamic_slice",
    }
    for dev in range(4):
        ops.append((dev, "gemm_tn.3", "custom-call", 1.5 if dev == 3 else 0.9))
        ops.append((dev, "fusion.9", "fusion", 0.1))
        ops.append((dev, "reduce-scatter.7", "reduce-scatter", 0.1))
    ops.append((0, "all-gather-start.1", "all-gather-start", 0.05))
    ops.append((0, "fusion.2", "fusion", 0.05))
    ctx = _ctx(ops, names)
    red = ctx.reduced
    tiles = next(o for o in red.ops if o.device == 3 and o.name == "gemm_tn.3")
    red.ops.append(trace.Op(3, tiles.end - 0.2e9, tiles.end, "conditional.4",
                            "conditional", ""))
    return ctx


def test_collective_share_reads_scopes_and_collective_opcodes():
    ctx = _four_chips()
    total = 3 * 1.1 + 1.7 + 0.05 + 0.05
    want = 100.0 * (4 * 0.1 + 0.05 + 0.05) / total
    got = cells.load_metric("dist.collective_share").read(ctx)
    assert got == pytest.approx(want)


def test_imbalance_reads_the_busiest_chip_against_the_mean():
    """Each chip's work outside the collectives and the conditional."""
    ctx = _four_chips()
    per_chip = [1.0, 1.0, 1.0, 1.6]
    want = 100.0 * (max(per_chip) / (sum(per_chip) / 4) - 1.0)
    assert cells.load_metric("dist.imbalance").read(ctx) == pytest.approx(want)


def test_imbalance_sees_work_that_a_collective_wait_evens_out():
    """Chip 1 has half the tiles and waits the rest of the call in the
    reduce-scatter: its op time equals the others', its work does not."""
    ops = [(0, "gemm_tn.3", "custom-call", 1.0), (1, "gemm_tn.3", "custom-call", 0.5)]
    ops += [(0, "reduce-scatter.7", "reduce-scatter", 0.1),
            (1, "reduce-scatter.7", "reduce-scatter", 0.6)]
    ops += [(d, "gemm_tn.3", "custom-call", 1.0) for d in (2, 3)]
    ops += [(d, "reduce-scatter.7", "reduce-scatter", 0.1) for d in (2, 3)]
    ctx = _ctx(ops, {})
    want = 100.0 * (1.0 / (3.5 / 4) - 1.0)
    assert cells.load_metric("dist.imbalance").read(ctx) == pytest.approx(want)


def test_the_readers_read_nothing_where_nothing_is_there():
    one_chip = _ctx([(0, "gemm_tn.3", "custom-call", 1.0)], {})
    one_chip.reduced.devices = 1
    assert cells.load_metric("dist.imbalance").read(one_chip) is None
    no_collective = _ctx([(d, "gemm_tn.3", "custom-call", 1.0) for d in range(4)],
                         {"gemm_tn.3": f"{J}/distributed.tile_body/kernels.gemm_tn"})
    assert cells.load_metric("dist.collective_share").read(no_collective) is None
    assert cells.load_metric("dist.imbalance").read(no_collective) == 0.0


@pytest.mark.parametrize("rows", [64, 96, 1000])
def test_the_reference_agrees_with_a_float64_product(rows):
    """Row blocks of ``rows`` (ragged at 1000 and 96) over 1000 rows: every
    packed tile is the float64 product's to float32 accuracy."""
    a = np.random.default_rng(rows).standard_normal((1000, 96)).astype(np.float32)
    bn = 32
    got = np.asarray(ref.packed_tiles(jnp.asarray(a), bn, "highest", rows=rows))
    nb = 96 // bn
    i, j = np.tril_indices(nb)
    want = np.stack([ref.float64_tile(a, bn, ii, jj) for ii, jj in zip(i, j)])
    assert got.shape == want.shape == (6, bn, bn)
    assert float(ref.tile_rel_err(got, want.astype(np.float32))) < 1e-6
    err = np.linalg.norm(got - want) / np.linalg.norm(want)
    assert err < 1e-6


_SCRIPT = r"""
import time
import jax, jax.numpy as jnp
from bench import cells, control, run

assert len(jax.devices()) == 4
SEED = 2**33 + 16
spec = cells.resolve("gram-f32-2x2.rowshard_131072x16384")
spec.traffic = dict(spec.traffic, shape=[1024, 512])
limit = spec.config["limits"]["tile_rel_err"]
prog = control.readings(spec, SEED, 0.3, "program")
ctrl = control.readings(spec, SEED, 0.3, "control")
assert "lost" not in prog
assert prog["tile_rel_err"] <= limit < ctrl["tile_rel_err"], (prog, ctrl, limit)

# the program fed A with its second row shard zeroed sums one row shard
program = spec.entry.program
def one_row_shard(plan, config, traffic):
    fn = program(plan, config, traffic)
    half = traffic["shape"][0] // config["mesh"]["data"]
    return jax.jit(lambda a: fn(a.at[half:].set(0.0)))
spec.entry.program = one_row_shard
out = run.run_cell(spec, SEED + 1, 0.3, False, devices=jax.devices(),
                   start=time.time())
assert out["correct"] is False, out["checks"]
assert out["checks"]["tile_rel_err"]["value"] > 1e3 * limit, out["checks"]
print("OK", prog, ctrl, out["checks"])
"""


def test_on_four_cpu_devices_the_program_passes_and_the_control_fails():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(cells.ROOT, "src"), cells.ROOT]))
    p = subprocess.run([sys.executable, "-c", _SCRIPT], cwd=cells.ROOT,
                       env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0 and "OK" in p.stdout, p.stdout + p.stderr
