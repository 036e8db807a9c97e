"""Paper Figure 6 + Table 1: distributed ATA-D vs baselines.

Distributed analogue on host-platform devices: the two-level schedule
(rows over 'data' × tiles over 'model' — ATA-D's layout) vs the plain
single-device classical gram ("1-rank baseline"), including the
distribute/retrieve cost (device_put of A + full gather of C), which is
what the paper's shaded areas measure. Also reports the analytic
latency/bandwidth model of Prop. 4.2 for the same (n, P).

The **packed-retrieval comparison** (smoke-safe: compile-only, no timing
loop) lowers the dense and packed output modes of ``ata_tile_parallel``
and ``gram_rowshard`` on an 8-fake-device mesh and records the per-device
collective bytes from the compiled HLO — the Prop. 4.2 low(C) saving as
measured collective payload, tracked in ``BENCH_distributed.json``.

The **BFS/DFS rows** (``collectives_bfsdfs_*``, also compile-only and
smoke-safe) lower the CAPS-style schedule with the *planner-selected*
interleaving at three mesh shapes and record its collective bytes next to
the per-level ``prop42_msgs``/``prop42_words`` attribution of
``tune.cost.comm_levels`` — the perf-diff surface that catches
communication regressions, not just wall clock. ``fig6_bfsdfs_P*`` times
the planned front door (``tune.apply.ata_distributed_with_plan``)
end-to-end against the same 1-rank baseline as ``fig6_atad_P*``.
"""

from __future__ import annotations

import json
import re
import subprocess
import sys

from benchmarks.common import emit, fake_device_env, smoke
from repro.core.task_tree import ell_distributed

_CHILD = r"""
import jax, jax.numpy as jnp, numpy as np, time
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core.distributed import ata_tile_parallel
devs = len(jax.devices())
d = {d}; m = devs // d
from repro.launch.mesh import make_mesh
mesh = make_mesh((d, m), ("data", "model"))
r = np.random.default_rng(0)
a_host = r.standard_normal(({m_}, {n})).astype(np.float32)
f = jax.jit(lambda a: ata_tile_parallel(a, mesh, task_axis="model",
                                        row_axis="data"))
sh = NamedSharding(mesh, P("data", None))
# warm
a = jax.device_put(jnp.asarray(a_host), sh); jax.block_until_ready(f(a))
tc, tt = [], []
for _ in range(5):
    t0 = time.perf_counter()
    a = jax.device_put(jnp.asarray(a_host), sh)      # distribute
    c = f(a)                                          # compute
    jax.block_until_ready(c)
    t1 = time.perf_counter()
    _ = np.asarray(c)                                 # retrieve to host
    t2 = time.perf_counter()
    tc.append(t1 - t0); tt.append(t2 - t0)
print("TIME", float(np.median(tc)), float(np.median(tt)))
"""


def _run_child(p: int, d: int, m: int, n: int):
    env = fake_device_env(p)
    out = subprocess.run(
        [sys.executable, "-c", _CHILD.format(d=d, m_=m, n=n)],
        env=env, capture_output=True, text=True, timeout=900,
    )
    mt = re.search(r"TIME ([0-9.e-]+) ([0-9.e-]+)", out.stdout)
    if not mt:
        raise RuntimeError(f"child failed (P={p}): {out.stderr[-500:]}")
    return float(mt.group(1)), float(mt.group(2))


# compile-only child: per-device collective bytes of dense vs packed
# retrieval (token-templated — the script body contains dict braces).
_COLLECTIVES_CHILD = r"""
import json
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.analysis.hlo import collective_bytes, compiled_text
from repro.core.distributed import ata_tile_parallel, gram_rowshard
from repro.obs import metrics as obs_metrics
m, n = @M@, @N@
mesh = make_mesh((2, 4), ("data", "model"))
a_abs = jax.ShapeDtypeStruct((m, n), jnp.float32)
sh = NamedSharding(mesh, P("data", None))
out = {}
for mode in ("dense", "packed"):
    f = jax.jit(
        lambda a, mode=mode: ata_tile_parallel(
            a, mesh, task_axis="model", row_axis="data", out=mode),
        in_shardings=(sh,),
    )
    hlo = compiled_text(f, a_abs)
    obs_metrics.record_collective_bytes(hlo, prefix="collective_bytes.tile_" + mode)
    out["tile_" + mode] = collective_bytes(hlo)
row_abs = jax.ShapeDtypeStruct((m, n), jnp.float32)
for mode in ("dense", "packed"):
    out_spec = P(None, None, None) if mode == "packed" else P(None, None)
    f = jax.jit(jax.shard_map(
        lambda x, mode=mode: gram_rowshard(x, "data", out=mode),
        mesh=make_mesh((8,), ("data",)),
        in_specs=(P("data", None),), out_specs=out_spec))
    hlo = compiled_text(f, row_abs)
    obs_metrics.record_collective_bytes(hlo, prefix="collective_bytes.rowshard_" + mode)
    out["rowshard_" + mode] = collective_bytes(hlo)
print("BYTES " + json.dumps(out))
"""


def _run_collectives_child(p: int, m: int, n: int) -> dict:
    env = fake_device_env(p)
    script = _COLLECTIVES_CHILD.replace("@M@", str(m)).replace("@N@", str(n))
    out = subprocess.run(
        [sys.executable, "-c", script],
        env=env, capture_output=True, text=True, timeout=900,
    )
    mt = re.search(r"BYTES (\{.*\})", out.stdout)
    if not mt:
        raise RuntimeError(f"collectives child failed: {out.stderr[-800:]}")
    return json.loads(mt.group(1))


def run_collectives(m: int = 1024, n: int = 1024):
    """Packed vs dense retrieval: collective bytes from compiled HLO."""
    bytes_by = _run_collectives_child(8, m, n)
    for schedule in ("tile", "rowshard"):
        dense = sum(bytes_by[f"{schedule}_dense"].values())
        packed = sum(bytes_by[f"{schedule}_packed"].values())
        ratio = packed / dense if dense else float("nan")
        emit(
            f"collectives_{schedule}_{m}x{n}",
            0.0,
            f"dense_bytes={dense} packed_bytes={packed} ratio={ratio:.3f}",
            shape=(m, n),
            dense_bytes=dense,
            packed_bytes=packed,
            packed_over_dense=round(ratio, 4),
        )


# compile-only child: the BFS/DFS schedule with planner-selected
# interleaving at several mesh shapes (token-templated like above).
_BFSDFS_CHILD = r"""
import json
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.analysis.hlo import collective_bytes, compiled_text
from repro.core.distributed import ata_bfs_dfs
from repro.obs import metrics as obs_metrics
from repro.tune import cost
m, n = @M@, @N@
out = {}
for dd, dm in ((2, 4), (4, 2), (8, 1)):
    mesh = make_mesh((dd, dm), ("data", "model"))
    sh = NamedSharding(mesh, P("data", None))
    a_abs = jax.ShapeDtypeStruct((m, n), jnp.float32)
    for mode in ("dense", "packed"):
        plans = cost.candidates("ata", m, n, out=mode, backend="cpu",
                                devices=dm, row_devices=dd)
        top = next((p for p in plans
                    if p.comm_schedule and "B" in p.comm_schedule), None)
        if top is None:
            continue
        f = jax.jit(
            lambda a, top=top, mesh=mesh, mode=mode: ata_bfs_dfs(
                a, mesh, task_axis="model", row_axis="data",
                interleaving=top.comm_schedule, nb=top.nb,
                packed_block=top.packed_block, out=mode),
            in_shardings=(sh,),
        )
        hlo = compiled_text(f, a_abs)
        key = "bfsdfs_%dx%d_%s" % (dd, dm, mode)
        obs_metrics.record_collective_bytes(
            hlo, prefix="collective_bytes." + key)
        levels = cost.comm_levels(top.comm_schedule, top.nb, top.tile_w,
                                  dm, dd, out=mode)
        out[key] = dict(bytes=collective_bytes(hlo), cs=top.comm_schedule,
                        nb=top.nb, tile_w=top.tile_w, levels=levels)
print("BYTES " + json.dumps(out))
"""


def _run_bfsdfs_child(p: int, m: int, n: int) -> dict:
    env = fake_device_env(p)
    script = _BFSDFS_CHILD.replace("@M@", str(m)).replace("@N@", str(n))
    out = subprocess.run(
        [sys.executable, "-c", script],
        env=env, capture_output=True, text=True, timeout=900,
    )
    mt = re.search(r"BYTES (\{.*\})", out.stdout)
    if not mt:
        raise RuntimeError(f"bfsdfs child failed: {out.stderr[-800:]}")
    return json.loads(mt.group(1))


def run_collectives_bfsdfs(m: int = 1024, n: int = 1024):
    """BFS/DFS collective bytes + per-level α-β attribution, per mesh."""
    data = _run_bfsdfs_child(8, m, n)
    for dd, dm in ((2, 4), (4, 2), (8, 1)):
        kd, kp = f"bfsdfs_{dd}x{dm}_dense", f"bfsdfs_{dd}x{dm}_packed"
        if kd not in data or kp not in data:
            continue
        dense = sum(data[kd]["bytes"].values())
        packed = sum(data[kp]["bytes"].values())
        ratio = packed / dense if dense else float("nan")
        lv = data[kp]["levels"]
        msgs = [round(l["msgs"], 1) for l in lv]
        words = [int(round(l["words"])) for l in lv]
        tags = "".join(l["tag"] for l in lv)
        emit(
            f"collectives_bfsdfs_{dd}x{dm}_{m}x{n}",
            0.0,
            f"cs={data[kp]['cs']} nb={data[kp]['nb']} "
            f"dense_bytes={dense} packed_bytes={packed} ratio={ratio:.3f} "
            f"levels={tags} prop42_msgs={msgs} prop42_words={words}",
            shape=(m, n),
            comm_schedule=data[kp]["cs"],
            nb=data[kp]["nb"],
            tile_w=data[kp]["tile_w"],
            dense_bytes=dense,
            packed_bytes=packed,
            packed_over_dense=round(ratio, 4),
            prop42_msgs=msgs,
            prop42_words=words,
        )


_BFS_FIG6_CHILD = r"""
import jax, jax.numpy as jnp, numpy as np, time
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.tune import cost
from repro.tune.apply import ata_distributed_with_plan
devs = len(jax.devices())
d = {d}; m = devs // d
mesh = make_mesh((d, m), ("data", "model"))
plans = cost.candidates("ata", {m_}, {n}, out="packed", backend="cpu",
                        devices=m, row_devices=d)
top = next(p for p in plans if p.comm_schedule and "B" in p.comm_schedule)
r = np.random.default_rng(0)
a_host = r.standard_normal(({m_}, {n})).astype(np.float32)
f = jax.jit(lambda a: ata_distributed_with_plan(
    a, mesh, top, task_axis="model", row_axis="data"))
sh = NamedSharding(mesh, P("data", None))
a = jax.device_put(jnp.asarray(a_host), sh)
jax.block_until_ready(f(a).blocks)
tc, tt = [], []
for _ in range(5):
    t0 = time.perf_counter()
    a = jax.device_put(jnp.asarray(a_host), sh)      # distribute
    c = f(a)                                          # compute
    jax.block_until_ready(c.blocks)
    t1 = time.perf_counter()
    _ = np.asarray(c.blocks)                          # retrieve (packed)
    t2 = time.perf_counter()
    tc.append(t1 - t0); tt.append(t2 - t0)
print("PLAN", top.comm_schedule, top.nb)
print("TIME", float(np.median(tc)), float(np.median(tt)))
"""


def _run_bfs_fig6_child(p: int, d: int, m: int, n: int):
    env = fake_device_env(p)
    out = subprocess.run(
        [sys.executable, "-c", _BFS_FIG6_CHILD.format(d=d, m_=m, n=n)],
        env=env, capture_output=True, text=True, timeout=900,
    )
    mt = re.search(r"TIME ([0-9.e-]+) ([0-9.e-]+)", out.stdout)
    pl = re.search(r"PLAN (\S+) (\d+)", out.stdout)
    if not mt or not pl:
        raise RuntimeError(f"bfs fig6 child failed (P={p}): {out.stderr[-500:]}")
    return float(mt.group(1)), float(mt.group(2)), pl.group(1), int(pl.group(2))


def _prop42(n: int, p: int):
    """Prop. 4.2 analytic latency (messages) and bandwidth (words)."""
    ell = ell_distributed(p)
    lat = 2 * (7 * max(ell - 1, 0) + 5)
    bw = 6 * (n / 2) ** 2 + n * (n + 2) / 2
    if ell >= 2:
        bw += 7 / 6 * n**2 * (1 - 1 / 4 ** (ell - 2))
    return lat, bw


def run():
    # packed-vs-dense collective bytes: cheap (compile-only), runs in
    # --smoke too — this is the CI-tracked Prop. 4.2 retrieval number,
    # and the BFS/DFS rows are the communication-regression surface.
    run_collectives()
    run_collectives_bfsdfs()
    if smoke():
        return
    m, n = 4096, 2048
    base_c, base_t = _run_child(1, 1, m, n)
    emit(f"fig6_atad_P1_{m}x{n}", base_t, f"compute_us={base_c*1e6:.0f} speedup=1.00")
    for p, d in [(2, 2), (4, 2), (8, 2)]:
        tc, tt = _run_child(p, d, m, n)
        lat, bw = _prop42(n, p)
        emit(
            f"fig6_atad_P{p}_{m}x{n}",
            tt,
            f"compute_us={tc*1e6:.0f} speedup={base_t/tt:.2f} "
            f"ell={ell_distributed(p)} prop42_msgs={lat} prop42_words={bw:.2e}",
        )
    # the BFS/DFS schedule through the planned front door, same baseline:
    # packed-native retrieval (the schedule's root mode) + the tri-direct
    # reduce-scatter replacing the psum + root-gather pair.
    for p, d in [(2, 2), (4, 2), (8, 2)]:
        tc, tt, cs, nb_sel = _run_bfs_fig6_child(p, d, m, n)
        emit(
            f"fig6_bfsdfs_P{p}_{m}x{n}",
            tt,
            f"compute_us={tc*1e6:.0f} speedup={base_t/tt:.2f} "
            f"cs={cs} nb={nb_sel}",
        )
    # Table 1 analogue: SM (all devices one task axis) vs DM (2-level) at
    # growing n — speedup of the 2-level layout including retrieval.
    for nn in [1024, 2048]:
        sm_c, sm_t = _run_child(8, 1, 2 * nn, nn)
        dm_c, dm_t = _run_child(8, 2, 2 * nn, nn)
        emit(
            f"table1_sm_vs_dm_n{nn}", dm_t,
            f"sm_us={sm_t*1e6:.0f} dm_us={dm_t*1e6:.0f} speedup={sm_t/dm_t:.2f}",
        )


if __name__ == "__main__":
    run()
