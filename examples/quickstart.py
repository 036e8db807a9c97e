"""Quickstart: the paper's ATA algorithm as a composable, *planned* JAX op.

Covers: the ``repro.tune.plan`` front door (plan → ata → packed result —
the documented entry point), plain ``alpha·AᵀA`` vs the classical product,
the rectangular FastStrassen ``AᵀB``, flop accounting (the paper's
2/3-of-Strassen claim), packed-native least squares (plan → ata →
``solve.lstsq`` — the gram is factored and solved without ever being
densified), the Pallas kernel base case, and the ``repro.obs``
observability switch (spans, metrics snapshot, the program's compile steps).

    PYTHONPATH=src python examples/quickstart.py
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs, solve, tune
from repro.core import ata, strassen_tn
from repro.core.reference import (
    ata_flops,
    classical_syrk_flops,
    strassen_tn_flops,
)


def main():
    rng = np.random.default_rng(0)

    # --- 1. the front door: plan → ata → packed result ---------------------
    # Every dispatch tunable (algorithm variant, recursion cutoff, kernel
    # blocks, packed block size) is decided by the cost model — or by the
    # measured autotuner with plan(..., autotune=True) — never hardcoded.
    a = jnp.asarray(rng.standard_normal((1537, 771)), jnp.float32)  # odd dims
    p = tune.plan(op="ata", m=1537, n=771, out="packed")
    # cached measured plans carry measured_s but may lack a prediction
    cost_s = p.measured_s or p.predicted_s
    cost_str = f"{cost_s:.2e}s" if cost_s is not None else "n/a"
    print(f"plan: algorithm={p.algorithm} n_base={p.n_base} "
          f"packed_block={p.packed_block} backend={p.backend} "
          f"source={p.source} cost={cost_str}")

    packed = jax.jit(lambda a: ata(a, plan=p, out="packed"))(a)
    print(f"packed result: {packed.t_total} lower-tri blocks of "
          f"{packed.bn}x{packed.bn} ({packed.nbytes} bytes vs "
          f"{packed.dense_nbytes(packed.n)} dense)")

    # --- 2. dense output of the same plan is bitwise the packed mirror -----
    dense = jax.jit(lambda a: ata(a, plan=p))(a)
    err = float(jnp.abs(dense - a.T @ a).max() / jnp.abs(dense).max())
    print(f"ata(1537x771): rel err vs classical = {err:.2e}  "
          f"(bitwise symmetric: {bool((dense == dense.T).all())}, "
          f"packed==dense: {bool((packed.to_dense() == dense).all())})")

    # --- 3. rectangular Strassen AᵀB (self-planned: no plan pinned) --------
    b = jnp.asarray(rng.standard_normal((1537, 500)), jnp.float32)
    cb = strassen_tn(a, b)
    print(f"strassen_tn(AᵀB): rel err = "
          f"{float(jnp.abs(cb - a.T @ b).max() / jnp.abs(cb).max()):.2e}")

    # --- 4. the paper's flop claim at the planned cutoff --------------------
    n = 1 << 14
    big = tune.plan(op="ata", m=n, n=n)
    nb = big.n_base
    r_strassen = ata_flops(n, n, nb) / strassen_tn_flops(n, n, n, nb)
    r_classic = ata_flops(n, n, nb) / classical_syrk_flops(n, n)
    print(f"flops @ n=16384 (planned n_base={nb}): ATA/Strassen = "
          f"{r_strassen:.3f} (→ 2/3), ATA/classical-syrk = {r_classic:.3f}")

    # --- 5. application: packed-native least squares (repro.solve) ---------
    # The ten-line front door: the planner prices factor-vs-CG for this
    # shape/RHS count, the gram comes out of the planned ata packed, the
    # Cholesky factors it in place, and two packed substitutions finish —
    # no dense (771, 771) matrix exists anywhere in the pipeline.
    x_true = rng.standard_normal(771).astype(np.float32)
    y = a @ x_true + 0.01 * rng.standard_normal(1537).astype(np.float32)
    sp = tune.plan(op="solve", m=1537, n=771, k=1, out="packed")
    x_hat = solve.lstsq(a, y, ridge=1e-4, plan=sp)
    print(f"solve.lstsq (method={sp.method}, algorithm={sp.algorithm}): "
          f"||x̂ − x||/||x|| = "
          f"{float(jnp.linalg.norm(x_hat - x_true) / jnp.linalg.norm(x_true)):.3e}")

    # --- 6. Pallas kernels as the recursion base case -----------------------
    # On TPU the planner sets use_kernels=True by itself; forcing it here
    # shows the same plan driving the Pallas base engines (interpret mode
    # on CPU, so keep the operand small).
    a_small = jnp.asarray(rng.standard_normal((512, 384)), jnp.float32)
    pk = dataclasses.replace(
        tune.plan(op="ata", m=512, n=384), use_kernels=True
    )
    c_k = ata(a_small, plan=pk)  # base_syrk/base_dot built from the plan
    print(f"ata with Pallas base (interpret on CPU): max err = "
          f"{float(jnp.abs(c_k - a_small.T @ a_small).max()):.2e}")

    # --- 7. observability: obs.enable() → jitted ata → what it recorded -----
    # Counters (dispatch/leaf/cache accounting) and JAX's compile steps are
    # always on, and every span's named scope is always compiled in (op
    # names carry it; zero ops, bitwise-identical values). enable() adds the
    # recording: span events and counts, profiler annotations, and the root
    # spans' host-clock times that tie a jitted program to its compile steps.
    obs.enable()
    # a recursing batched plan so the per-level spans have levels to name
    pr = dataclasses.replace(p, n_base=128, leaf_dispatch="batched",
                             source="analytic")
    jax.jit(lambda a: ata(a, plan=pr, out="packed"))(a)
    snap = obs.metrics.snapshot()  # JSON-ready, schema "repro.obs/v1"
    obs.metrics.validate_snapshot(snap)
    (prog,) = obs.compiles.programs()
    print(f"obs: dispatch.ata.* counters = "
          f"{ {k: v for k, v in snap['counters'].items() if k.startswith('dispatch.ata')} }, "
          f"spans = {sorted(snap['spans'])}, "
          f"trace/lower/compile = {prog.trace_s:.2f}/{prog.lower_s:.2f}/"
          f"{prog.compile_s:.2f} s")
    obs.disable()

    # --- 8. static contract checks: check.trace_plan → check.run ------------
    # The structural invariants behind all of the above (no dense (n, n)
    # square on packed paths, no materialized Aᵀ, dot/launch counts equal
    # to the cost model's closed forms, f32 accumulation) are machine-
    # checked: trace the exact planned callable and run the rule registry
    # (DESIGN.md §9; CI gates on `python -m repro.check`).
    from repro import check

    art = check.trace_plan(p)   # the step-1 packed plan
    report = check.run(art)
    print(f"repro.check: {len(list(check.rule_ids()))} rules over "
          f"'{art.label}' → {len(report.violations)} violations")
    assert not report.violations, report.summary()

    # --- 9. gram-as-a-service: the serving layer (repro.serve) -------------
    # The ten-line serving story (DESIGN.md §10): warm once (plans + XLA,
    # off the request path), then heterogeneous lstsq requests micro-batch
    # by plan key into single launches — bitwise-equal to per-request
    # solve.lstsq, zero steady-state retraces, p95 from the obs snapshot.
    from repro.serve import Request, Server, metrics as serve_metrics, smoke_config

    server = Server(smoke_config())
    server.warm()
    tickets = [server.submit(Request(
        op="lstsq", a=rng.standard_normal((40 + i % 8, 32)).astype(np.float32),
        b=rng.standard_normal((40 + i % 8, 1 + i % 4)).astype(np.float32),
        ridge=1e-4)) for i in range(100)]
    server.drain()
    serve_metrics.publish_percentiles()
    snap = obs.metrics.snapshot()
    print(f"serve: {sum(t.done() for t in tickets)}/100 served, "
          f"retraces={server.retraces()}, request p95 = "
          f"{snap['gauges']['serve.latency.request.p95']*1e3:.2f}ms")


if __name__ == "__main__":
    main()
