"""Paper Figure 5: shared-memory ATA-S scaling with thread/device count.

The shared-memory analogue on this container: ``ata_tile_parallel`` over a
P-device host-platform mesh (XLA CPU devices = threads on shared memory).
Each P runs in a subprocess (device count is fixed at jax init). Reported:
measured time, measured speedup vs P=1, and the paper's task-tree model
speedup (Eq. 8 via the LPT makespan) for the same P.
"""

from __future__ import annotations

import re
import subprocess
import sys

from benchmarks.common import emit, fake_device_env
from repro.core.task_tree import ell_shared, modeled_speedup

_CHILD = r"""
import jax, jax.numpy as jnp, numpy as np, time
from repro.launch.mesh import make_mesh
from repro.core.distributed import ata_tile_parallel
mesh = make_mesh((len(jax.devices()),), ("model",))
r = np.random.default_rng(0)
a = jnp.asarray(r.standard_normal(({m}, {n})), jnp.float32)
f = jax.jit(lambda a: ata_tile_parallel(a, mesh, task_axis="model"))
out = f(a); jax.block_until_ready(out)
ts = []
for _ in range(5):
    t0 = time.perf_counter(); jax.block_until_ready(f(a)); ts.append(time.perf_counter() - t0)
print("TIME", float(np.median(ts)))
"""


def _run_child(p: int, m: int, n: int) -> float:
    env = fake_device_env(p)
    out = subprocess.run(
        [sys.executable, "-c", _CHILD.format(m=m, n=n)],
        env=env, capture_output=True, text=True, timeout=900,
    )
    mt = re.search(r"TIME ([0-9.e-]+)", out.stdout)
    if not mt:
        raise RuntimeError(f"child failed: {out.stderr[-500:]}")
    return float(mt.group(1))


def run():
    m, n = 2048, 2048
    t1 = None
    for p in [1, 2, 4, 8]:
        t = _run_child(p, m, n)
        t1 = t1 or t
        emit(
            f"fig5_atas_P{p}_{m}x{n}",
            t,
            f"speedup={t1/t:.2f} modeled={modeled_speedup(n, p):.2f} "
            f"ell={ell_shared(p)}",
        )


if __name__ == "__main__":
    run()
