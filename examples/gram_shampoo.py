"""The paper's algorithm inside the optimizer: ATA-powered Shampoo.

Trains a small MLP classifier twice — AdamW vs Shampoo (whose L/R
preconditioner statistics are the paper's ``AᵀA`` products computed by
``repro.core.ata``) — and prints the loss curves, plus a distributed gram
demo with the ATA-S/ATA-D tile schedule on a host-platform mesh.

    PYTHONPATH=src python examples/gram_shampoo.py
"""

import sys

sys.path.insert(0, "src")

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.distributed import ata_tile_parallel
from repro.optim import adamw, apply_updates, constant, shampoo


def train(opt_name: str, steps: int = 150):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((512, 64)), jnp.float32)
    w_true = jnp.asarray(rng.standard_normal((64, 32)), jnp.float32)
    y = jnp.tanh(x @ w_true) @ jnp.asarray(rng.standard_normal((32, 8)), jnp.float32)

    params = {
        "w1": jnp.asarray(rng.standard_normal((64, 32)) * 0.1, jnp.float32),
        "w2": jnp.asarray(rng.standard_normal((32, 8)) * 0.1, jnp.float32),
    }
    # no n_base pin: the gram dispatches are planned per block shape
    opt = (adamw(constant(3e-3)) if opt_name == "adamw"
           else shampoo(constant(3e-3), block=32, update_every=5))
    state = opt.init(params)

    def loss_fn(p):
        return jnp.mean((jnp.tanh(x @ p["w1"]) @ p["w2"] - y) ** 2)

    @jax.jit
    def step(p, s):
        l, g = jax.value_and_grad(loss_fn)(p)
        u, s = opt.update(g, s, p)
        return apply_updates(p, u), s, l

    curve = []
    for i in range(steps):
        params, state, l = step(params, state)
        if i % 30 == 0 or i == steps - 1:
            curve.append((i, float(l)))
    return curve


def main():
    for name in ["adamw", "shampoo"]:
        curve = train(name)
        pts = "  ".join(f"{i}:{l:.4f}" for i, l in curve)
        print(f"{name:8s} loss: {pts}")

    # distributed gram on this host's device pool (1 device here; run with
    # XLA_FLAGS=--xla_force_host_platform_device_count=8 for real sharding)
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((len(jax.devices()),), ("model",))
    a = jnp.asarray(np.random.default_rng(1).standard_normal((1024, 512)), jnp.float32)
    c = ata_tile_parallel(a, mesh, task_axis="model")
    print(f"distributed gram (P={len(jax.devices())}): rel err = "
          f"{float(jnp.abs(c - a.T @ a).max() / jnp.abs(c).max()):.2e}")
    # packed retrieval (paper Prop. 4.2): the result never leaves low(C)
    # form — ~half the payload of the dense replicated square
    s = ata_tile_parallel(a, mesh, task_axis="model", out="packed")
    ratio = s.nbytes / s.dense_nbytes(s.n)
    err = float(jnp.abs(s.to_dense() - a.T @ a).max() / jnp.abs(c).max())
    print(f"packed retrieval: {type(s).__name__} blocks={s.blocks.shape} "
          f"({ratio:.2f}x dense bytes), rel err = {err:.2e}")


if __name__ == "__main__":
    main()
