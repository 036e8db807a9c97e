#!/usr/bin/env python3
"""Bring-up smoke of the main path on a TPU: plan → ata → solve.lstsq → serve.

    python chip_smoke.py              # one chip: four phases (below)
    python chip_smoke.py --chips 4    # a 2×2 mesh: the distributed Gram only

One process, data made on the device from ``--seed``, every plan from the
analytic model (``source='analytic'``: no plan-cache file is read). Phases
on one chip:

* ``gram_dense``  — ``ata`` of a (32768, 16384) bf16 A (1 GiB; C is 1 GiB
  f32) against ``A.T @ A`` at HIGHEST precision with f32 accumulation;
* ``gram_packed`` — ``ata(out='packed')`` of a (16384, 8192) f32 A, its
  ``to_dense()`` against the same reference;
* ``solve``       — ``solve.lstsq`` on the factor path, A (65536, 4096) f32
  (1 GiB), 16 right-hand sides, checked by the relative normal-equations
  residual ``‖Aᵀ(Ax−b) + λx‖ / ‖Aᵀb‖``;
* ``serve``       — a ``serve.Server`` warmed on the smoke lattice plus an
  n=1024 lstsq bucket answers 50 mixed requests; every lstsq answer is
  compared with per-request ``solve.lstsq`` (and its largest ULP distance
  printed), every whiten answer with a float64 host reference, and the
  steady state must not retrace.

With ``--chips 4`` only the distributed phase runs: the planner's BFS/DFS
schedule and the psum schedule, dense and packed, on a (1, 4) and a
row-sharded (2, 2) mesh, one of them with a ragged tiling, each against the
single-chip ``ata`` of the same A on device 0.

Each phase prints the plan, compile seconds, one run's seconds (a smoke
timing, not a benchmark), its error and tolerance, ``peak_bytes_in_use``
and whether the compiled program holds a Pallas kernel
(``tpu_custom_call``), which it must wherever the plan uses kernels. The
last line of stdout is ``{"ok": true, "device": {...}}``. Any failed phase
makes the exit code nonzero and that line is not printed; so does a host on
which JAX finds no TPU.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

from repro import tune  # noqa: E402  (src/ joins the path just above)
from repro.core.ata import ata  # noqa: E402
from repro.runtime.compile_cache import enable_compile_cache  # noqa: E402
from repro.serve.bucketing import make_buckets  # noqa: E402
from repro.serve.engine import (  # noqa: E402
    Server, serve_abstract_args, smoke_config)
from repro.serve.queue import Request  # noqa: E402
from repro.solve import lstsq  # noqa: E402
from repro.tune.apply import ata_distributed_with_plan  # noqa: E402

# Tolerances, relative Frobenius norms.
# The Strassen/Winograd recursion forms its operand sums in the storage
# dtype, so a bf16 Gram carries bf16 rounding of those sums: about 2e-2 at
# four recursion levels (measured on the CPU at (2048, 1024), n_base=64).
TOL_GRAM_BF16 = 4e-2
# f32 storage: the recursion's rounding is ~1e-6; this bound holds only if
# the chip's f32 matmuls keep f32 precision.
TOL_GRAM_F32 = 1e-4
# normal-equations residual of an f32 solve whose gram has cond ≈ 2.8
TOL_SOLVE = 1e-4
# served answer against its per-request reference; the workload's grams
# have cond ≤ ~200, so f32 rounding differences stay near 1e-5
TOL_SERVE = 1e-4
# two bf16 Grams of one A with different recursion splits (single chip
# against a tile schedule) each carry TOL_GRAM_BF16-sized rounding
TOL_DIST_BF16 = 2 * TOL_GRAM_BF16

GRAM_DENSE = dict(m=32768, n=16384, dtype="bfloat16")
GRAM_PACKED = dict(m=16384, n=8192, dtype="float32")
SOLVE = dict(m=65536, n=4096, k=16, ridge=1e-3)
# the lstsq bucket served beside the smoke lattice's
SERVE_BIG = dict(m=4096, n=1024, r=8)
# (op, m, n, r, ridge); r=0 is a vector right-hand side
SERVE_SHAPES = [
    ("lstsq", 48, 32, 3, 0.0),
    ("lstsq", 80, 32, 8, 1e-3),
    ("lstsq", 90, 64, 0, 0.0),
    ("lstsq", 96, 64, 5, 1e-2),
    ("whiten", 48, 32, 4, 0.0),
    ("whiten", 44, 32, 2, 1e-3),
    ("lstsq", SERVE_BIG["m"], SERVE_BIG["n"], SERVE_BIG["r"], 1e-3),
    ("lstsq", SERVE_BIG["m"], SERVE_BIG["n"], 0, 0.0),
]
SERVE_REQUESTS = 50


def log(msg: str) -> None:
    print(msg, flush=True)


def tpu_devices():
    """The TPU devices, or exit nonzero: this script never runs elsewhere."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke: no TPU found (JAX sees {len(devs)} "
            f"{devs[0].platform!r} device(s)); it runs only on a TPU")
    return devs


def analytic_plan(**kw):
    plan = tune.plan(**kw)
    if plan.source != "analytic":
        raise RuntimeError(
            f"plan for {kw} came from {plan.source!r}, not the model")
    return plan


def peak_bytes(devices) -> list:
    return [d.memory_stats().get("peak_bytes_in_use") for d in devices]


def compile_timed(fn, *args):
    """(compiled, seconds, has_kernel) of ``jax.jit(fn)`` for ``args``."""
    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    dt = time.perf_counter() - t0
    return compiled, dt, "tpu_custom_call" in compiled.as_text()


def run_timed(compiled, *args):
    t0 = time.perf_counter()
    out = jax.block_until_ready(compiled(*args))
    return out, time.perf_counter() - t0


def rel_err(x, ref) -> float:
    return float(jax.jit(
        lambda x, r: jnp.linalg.norm(x - r) / jnp.linalg.norm(r))(x, ref))


def gram_reference(a):
    return jax.jit(lambda a: jnp.matmul(
        a.T, a, precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32))(a)


def random_matrix(key, shape, dtype):
    return jax.jit(lambda k: jax.random.normal(k, shape, dtype))(key)


def report(name, *, plan, compile_s, run_s, err, tol, has_kernel, devices,
           extra=""):
    """Print one phase's line; returns its failures."""
    fails = []
    if not err <= tol:
        fails.append(f"{name}: error {err!r} above tolerance {tol!r}")
    if plan.use_kernels and not has_kernel:
        fails.append(f"{name}: plan uses kernels but no tpu_custom_call compiled")
    log(f"[{name}] plan={plan}")
    log(f"[{name}] compile_s={compile_s!r} run_s={run_s!r} (smoke timing, not "
        f"a benchmark) err={err!r} tol={tol!r} tpu_custom_call={has_kernel} "
        f"peak_bytes_in_use={peak_bytes(devices)} {extra}".rstrip())
    return fails


# -- one chip -----------------------------------------------------------------


def phase_gram_dense(key, devices):
    cfg = GRAM_DENSE
    plan = analytic_plan(op="ata", m=cfg["m"], n=cfg["n"], dtype=cfg["dtype"])
    a = random_matrix(key, (cfg["m"], cfg["n"]), cfg["dtype"])
    compiled, compile_s, has_kernel = compile_timed(lambda a: ata(a, plan=plan), a)
    c, run_s = run_timed(compiled, a)
    err = rel_err(c, gram_reference(a))
    return report("gram_dense", plan=plan, compile_s=compile_s, run_s=run_s,
                  err=err, tol=TOL_GRAM_BF16, has_kernel=has_kernel,
                  devices=devices)


def phase_gram_packed(key, devices):
    cfg = GRAM_PACKED
    plan = analytic_plan(op="ata", m=cfg["m"], n=cfg["n"], dtype=cfg["dtype"],
                         out="packed")
    a = random_matrix(key, (cfg["m"], cfg["n"]), cfg["dtype"])
    compiled, compile_s, has_kernel = compile_timed(
        lambda a: ata(a, plan=plan, out="packed"), a)
    s, run_s = run_timed(compiled, a)
    err = rel_err(s.to_dense(), gram_reference(a))
    return report("gram_packed", plan=plan, compile_s=compile_s, run_s=run_s,
                  err=err, tol=TOL_GRAM_F32, has_kernel=has_kernel,
                  devices=devices)


def phase_solve(key, devices):
    cfg = SOLVE
    plan = analytic_plan(op="solve", m=cfg["m"], n=cfg["n"], k=cfg["k"],
                         dtype="float32", out="packed")
    if plan.method != "factor":
        return [f"solve: planner chose {plan.method!r}, not the factor path"]
    ka, kb = jax.random.split(key)
    a = random_matrix(ka, (cfg["m"], cfg["n"]), "float32")
    b = random_matrix(kb, (cfg["m"], cfg["k"]), "float32")
    lam = cfg["ridge"]
    compiled, compile_s, has_kernel = compile_timed(
        lambda a, b: lstsq(a, b, ridge=lam, plan=plan), a, b)
    x, run_s = run_timed(compiled, a, b)

    hi = jax.lax.Precision.HIGHEST

    def residual(a, b, x):
        atr = jnp.matmul(a.T, jnp.matmul(a, x, precision=hi) - b, precision=hi)
        atb = jnp.matmul(a.T, b, precision=hi)
        return jnp.linalg.norm(atr + lam * x) / jnp.linalg.norm(atb)

    err = float(jax.jit(residual)(a, b, x))
    return report("solve", plan=plan, compile_s=compile_s, run_s=run_s,
                  err=err, tol=TOL_SOLVE, has_kernel=has_kernel,
                  devices=devices)


def ulp_distance(x, y) -> int:
    """Largest distance in float32 units in the last place."""
    def ordered(v):
        i = np.asarray(v, np.float32).view(np.int32).astype(np.int64)
        return np.where(i < 0, -(i & 0x7FFFFFFF), i)

    return int(np.abs(ordered(x) - ordered(y)).max())


def whiten_reference(a, v, ridge):
    """z = L⁻¹v with AᵀA + ridge·I = L·Lᵀ, in float64 on the host."""
    a64 = np.asarray(a, np.float64)
    g = a64.T @ a64 + ridge * np.eye(a64.shape[1])
    return np.linalg.solve(np.linalg.cholesky(g), np.asarray(v, np.float64))


def phase_serve(seed, devices):
    fails = []
    big = make_buckets(ops=("lstsq",), n_values=(SERVE_BIG["n"],),
                       m_bands=(SERVE_BIG["m"],), r_bands=(SERVE_BIG["r"],),
                       batch=4)
    cfg = smoke_config()
    cfg = dataclasses.replace(cfg, buckets=cfg.buckets + big)
    server = Server(cfg)
    plans = {spec: server.bucket_plan(spec) for spec in cfg.buckets}
    for spec, plan in plans.items():
        if plan.source != "analytic":
            fails.append(f"serve: bucket {spec.label()} plan from {plan.source!r}")

    t0 = time.perf_counter()
    server.warm()
    warm_s = time.perf_counter() - t0

    rng = np.random.default_rng(seed)
    requests = []
    for i in range(SERVE_REQUESTS):
        op, m, n, r, ridge = SERVE_SHAPES[i % len(SERVE_SHAPES)]
        a = rng.standard_normal((m, n)).astype(np.float32)
        rows = m if op == "lstsq" else n
        b = rng.standard_normal((rows,) if r == 0 else (rows, r)).astype(np.float32)
        requests.append(Request(op=op, a=a, b=b, ridge=ridge))
    t0 = time.perf_counter()
    tickets = [server.submit(req) for req in requests]
    server.drain()
    run_s = time.perf_counter() - t0

    worst, ulps = 0.0, {}
    for t in tickets:
        req = t.request
        got = np.asarray(t.result())
        if req.op == "lstsq":
            r = 1 if req.b.ndim == 1 else req.b.shape[-1]
            twin = server.request_twin(t.bucket, req.a.shape[0], r)
            ref = np.asarray(lstsq(req.a, req.b, ridge=req.ridge, plan=twin))
            rhs = r if req.b.ndim == 2 else "vec"
            shape = "x".join(map(str, (*req.a.shape, rhs)))
            ulps[shape] = max(ulps.get(shape, 0), ulp_distance(got, ref))
        else:
            ref = whiten_reference(req.a, req.b, req.ridge)
        err = float(np.linalg.norm(got - ref) / np.linalg.norm(ref))
        worst = max(worst, err)
    if server.retraces():
        fails.append(f"serve: steady state retraced {server.retraces()} times")
    undone = [t.id for t in tickets if not t.done()]
    if undone:
        fails.append(f"serve: {len(undone)} requests never answered")

    # the kernel check: compile each kernel-planned bucket once more, ahead
    # of time, and read its program (warm compiled the same function)
    has_kernel, kernel_buckets = True, 0
    for spec, plan in plans.items():
        if plan.use_kernels:
            fn, _ = server.bucket_callable(spec)
            txt = fn.lower(*serve_abstract_args(spec)).compile().as_text()
            kernel_buckets += 1
            has_kernel = has_kernel and "tpu_custom_call" in txt
    log(f"[serve] max_ulp_vs_per_request_lstsq={max(ulps.values())} "
        f"per (m x n x r): {ulps} (0 = bitwise equal) requests={len(tickets)} "
        f"buckets={len(cfg.buckets)} kernel_buckets={kernel_buckets} "
        f"retraces={server.retraces()}")
    fails += report("serve", plan=plans[big[0]], compile_s=warm_s,
                    run_s=run_s, err=worst, tol=TOL_SERVE,
                    has_kernel=has_kernel, devices=devices,
                    extra="(plan shown: the n=1024 bucket; compile_s is warm-up)")
    return fails


# -- four chips ---------------------------------------------------------------


def distributed_cases(devices):
    """(name, mesh, row_axis, plan) for each distributed schedule checked.

    The (1, 4) mesh has a 4-device task axis; the (2, 2) mesh shards A's
    rows over ``data`` as well. Each mesh runs the planner's BFS/DFS
    interleaving and the plain psum schedule, one output mode each, so both
    schedules run dense and packed. The (1, 4) psum case is pinned to 13
    stripes: T = 91 tiles over 4 devices, a ragged tiling with dummy slots.
    """
    grid = np.asarray(devices[:4]).reshape(2, 2)
    m, n, dtype = GRAM_DENSE["m"], GRAM_DENSE["n"], GRAM_DENSE["dtype"]
    cases = []
    for shape, outs in (((1, 4), ("packed", "dense")),
                        ((2, 2), ("dense", "packed"))):
        mesh = Mesh(grid.reshape(shape), ("data", "model"))
        row_axis = "data" if shape[0] > 1 else None
        for sched, out in zip(("bfs", "psum"), outs):
            plan = analytic_plan(op="ata", m=m, n=n, dtype=dtype, out=out,
                                 devices=shape[1], row_devices=shape[0])
            if sched == "bfs":
                cs = plan.comm_schedule
                plan = dataclasses.replace(
                    plan, comm_schedule=cs if cs and "B" in cs else "B")
            else:
                plan = dataclasses.replace(plan, comm_schedule=None)
                if shape == (1, 4):
                    w = -(-(-(-n // 13)) // 128) * 128
                    plan = dataclasses.replace(plan, nb=13, tile_w=w)
            cases.append((f"{sched}_{out}_{shape[0]}x{shape[1]}", mesh,
                          row_axis, plan))
    return cases


def phase_distributed(key, devices):
    m, n, dtype = GRAM_DENSE["m"], GRAM_DENSE["n"], GRAM_DENSE["dtype"]
    a = jax.device_put(random_matrix(key, (m, n), dtype), devices[0])
    plan1 = analytic_plan(op="ata", m=m, n=n, dtype=dtype)
    compiled, compile_s, has_kernel = compile_timed(lambda a: ata(a, plan=plan1), a)
    ref, run_s = run_timed(compiled, a)
    exact = gram_reference(a)
    fails = report("dist_reference_1chip", plan=plan1, compile_s=compile_s,
                   run_s=run_s, err=rel_err(ref, exact), tol=TOL_GRAM_BF16,
                   has_kernel=has_kernel, devices=devices)

    ragged = 0
    for name, mesh, row_axis, plan in distributed_cases(devices):
        a_sh = jax.device_put(a, NamedSharding(mesh, P(row_axis, None)))
        fn = (lambda a, mesh=mesh, row_axis=row_axis, plan=plan:
              ata_distributed_with_plan(a, mesh, plan, task_axis="model",
                                        row_axis=row_axis))
        compiled, compile_s, has_kernel = compile_timed(fn, a_sh)
        c, run_s = run_timed(compiled, a_sh)
        dense = c if plan.out == "dense" else c.to_dense()
        dense0 = jax.device_put(dense, devices[0])
        t = plan.nb * (plan.nb + 1) // 2
        ragged += t % plan.devices != 0
        fails += report(f"dist_{name}", plan=plan, compile_s=compile_s,
                        run_s=run_s, err=rel_err(dense0, ref),
                        tol=TOL_DIST_BF16, has_kernel=has_kernel,
                        devices=devices,
                        extra=f"tiles={t} task_devices={plan.devices} "
                              f"ragged={t % plan.devices != 0} "
                              f"err_vs_exact={rel_err(dense0, exact)!r}")
        del c, dense, dense0, a_sh
    if not ragged:
        fails.append("distributed: no case had a ragged tiling (T % p != 0)")
    return fails


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the distributed Gram on a 2x2 mesh")
    args = ap.parse_args(argv)

    devices = tpu_devices()
    if len(devices) < args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} but JAX sees "
                         f"{len(devices)} TPU device(s)")
    log(f"compile cache: {enable_compile_cache()}")
    log(f"device: {devices[0].device_kind} x{len(devices)}, jax {jax.__version__}")
    key = jax.random.key(args.seed)
    if args.chips == 4:
        phases = [("distributed", lambda: phase_distributed(key, devices))]
    else:
        k1, k2, k3 = jax.random.split(key, 3)
        phases = [
            ("gram_dense", lambda: phase_gram_dense(k1, devices[:1])),
            ("gram_packed", lambda: phase_gram_packed(k2, devices[:1])),
            ("solve", lambda: phase_solve(k3, devices[:1])),
            ("serve", lambda: phase_serve(args.seed, devices[:1])),
        ]
    failures = []
    for name, phase in phases:
        try:
            failures += phase()
        except Exception:
            # reported and counted as a failure; later phases still run
            traceback.print_exc()
            failures.append(f"{name}: raised")
    if failures:
        for f in failures:
            print(f"FAIL {f}", file=sys.stderr)
        return 1
    d = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
