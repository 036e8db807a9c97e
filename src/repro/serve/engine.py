"""The serve engine: pre-warmed, retrace-free batched bucket dispatch.

One :class:`Server` owns the lattice, the queue, and one jitted **batched
bucket callable** per :class:`BucketSpec`. The callable is the packed
normal-equations pipeline of ``solve.lstsq`` lifted to a leading batch
dim, composed so that each request slice is **bitwise-equal** to the
per-request ``solve.lstsq`` answer under the request-shaped twin of the
bucket plan (``tests/test_serve.py`` holds the property suite):

    a32  = a.astype(f32)                       # lstsq's own cast
    gram = ata_batched(a32, plan=⟨bucket ata plan, batch=B⟩, out='packed')
    gram = gram.add_scaled_identity(ridge[:, None, None, None])
    rhs  = AᵀB via one batched dot_general (f32 accumulation)
    L    = cholesky(gram, plan=sp)             # packed blocked walk
    x    = solve_cholesky(L, rhs, base_trsm=per_slice_trsm)

Two deliberate choices carry the bitwise contract:

* :func:`per_slice_trsm` — the substitution's diagonal-tile solves loop
  over the batch with **rank-2** ``triangular_solve`` calls. XLA's rank-3
  (batched) triangular-solve lowering differs from rank-2 in the last
  bits; every other stage of the pipeline is batch-invariant, so this one
  substitution detail is the whole gap between "close" and "bitwise".
  (The Cholesky walk itself needs no such treatment: its base calls are
  always rank-3 via ``_flat_call``, identically in both paths.)
* ridge is a **traced** per-slice vector, always added. Mixing ridges in
  one flush costs nothing, ridge changes never retrace, and adding 0.0
  on the gram diagonal is bitwise-transparent (verified — gram diagonals
  are sums of squares, never −0.0).

Ragged tails fill their empty slots by **replicating the first real
request** — zero-filled slots would feed a singular gram to the factor.
Fill slots are compiled work, counted (``serve.padded_slots``) and
cropped, never returned.

**The zero-retrace contract is asserted, not hoped**: after
:meth:`Server.warm` the engine snapshots each callable's jit cache size
(1), and every dispatch re-reads it. Growth means a request managed to
retrace on the hot path — the engine increments ``serve.retraces`` and
(by default) raises. Static bucket shapes + traced ridge make this
impossible by construction; the assertion keeps it impossible under
refactoring.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional, Tuple

from repro.obs import metrics as _obs
from repro.serve import metrics as serve_metrics
from repro.serve.bucketing import (
    BucketLattice,
    BucketSpec,
    crop_result,
    make_buckets,
    pad_operands,
)
from repro.serve.queue import FlushPolicy, MicroBatchQueue, Request, Ticket

__all__ = ["ServeConfig", "Server", "smoke_config", "per_slice_trsm",
           "serve_abstract_args"]


def per_slice_trsm(l, c, *, transpose: bool):
    """Diagonal-tile substitution solves, one rank-2 call per batch slice.

    The parity-critical base engine (see module docstring): rank-3
    ``triangular_solve`` lowers differently from rank-2 in the last bits,
    so the batched pipeline loops the batch here — B is the (small) flush
    width, so the unrolled loop is B extra tiny solves per block, not a
    scaling concern.
    """
    import jax
    import jax.numpy as jnp

    def solve2(l2, c2):
        return jax.lax.linalg.triangular_solve(
            l2, c2, left_side=True, lower=True, transpose_a=transpose)

    if l.ndim == 2:
        return solve2(l, c)
    if l.ndim != 3:
        raise ValueError(f"per_slice_trsm expects (B, bn, bn), got {l.shape}")
    return jnp.stack([solve2(l[i], c[i]) for i in range(l.shape[0])], 0)


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """The server's published contract: which buckets exist and how the
    queue behaves. ``packed_block``/``n_base`` override the planner's
    choice uniformly (the check harness uses this to force a real block
    grid); ``strict_retrace=False`` downgrades the zero-retrace assertion
    to a counter (never in production — tests only)."""

    buckets: Tuple[BucketSpec, ...]
    capacity: int = 256
    max_wait_s: float = 0.010
    cache_file: Optional[str] = None
    packed_block: Optional[int] = None
    n_base: Optional[int] = None
    strict_retrace: bool = True


def smoke_config(**overrides) -> ServeConfig:
    """The CI-scale config: a small mixed lattice every tool shares —
    the CLI ``--smoke``, ``bench_serve``, and the check harness all serve
    exactly these buckets, so "the smoke grid" means one thing."""
    buckets = (
        make_buckets(ops=("lstsq",), n_values=(32, 64), m_bands=(48, 96),
                     r_bands=(4, 8), batch=4)
        + make_buckets(ops=("whiten",), n_values=(32,), m_bands=(48,),
                       r_bands=(4,), batch=4)
    )
    kw = dict(buckets=buckets, capacity=64, max_wait_s=0.005)
    kw.update(overrides)
    return ServeConfig(**kw)


def serve_abstract_args(spec: BucketSpec) -> tuple:
    """Abstract (a, b, ridge) matching the bucket callable's signature —
    what the check harness traces and the engine warms on."""
    import jax

    b_rows = spec.m if spec.op == "lstsq" else spec.n
    return (
        jax.ShapeDtypeStruct((spec.batch, spec.m, spec.n), spec.dtype),
        jax.ShapeDtypeStruct((spec.batch, b_rows, spec.r), spec.dtype),
        jax.ShapeDtypeStruct((spec.batch,), "float32"),
    )


class Server:
    """Gram-as-a-service: submit → bucket → micro-batch → one launch."""

    def __init__(self, config: ServeConfig, *,
                 clock: Callable[[], float] = time.perf_counter):
        self.config = config
        self.clock = clock
        self.lattice = BucketLattice(config.buckets)
        self.queue = MicroBatchQueue(
            self.lattice, capacity=config.capacity,
            policy=FlushPolicy(max_wait_s=config.max_wait_s))
        self._plans: Dict[BucketSpec, object] = {}
        self._fns: Dict[BucketSpec, Callable] = {}
        # jit-cache size after warm (or first cold dispatch); any growth
        # past this is a hot-path retrace — the asserted contract
        self._trace_floor: Dict[BucketSpec, int] = {}
        self._warm_s: Dict[BucketSpec, float] = {}
        self.warmed = False

    # -- plan + callable construction ---------------------------------------

    def bucket_plan(self, spec: BucketSpec):
        """The bucket's (unbatched) solve plan — planner-resolved, pinned
        to the factor method (the batched pipeline IS the factor path; a
        cg plan would break the parity contract's reference)."""
        plan = self._plans.get(spec)
        if plan is None:
            from repro import tune

            sp = tune.plan(op="solve", m=spec.m, n=spec.n, k=spec.r,
                           dtype=spec.dtype, out="packed",
                           cache_file=self.config.cache_file)
            repl = {"method": "factor", "predicted_s": None}
            if self.config.packed_block is not None:
                repl["packed_block"] = self.config.packed_block
            if self.config.n_base is not None:
                repl["n_base"] = self.config.n_base
            plan = dataclasses.replace(sp, **repl)
            self._plans[spec] = plan
        return plan

    def request_twin(self, spec: BucketSpec, m: int, r: int):
        """The parity reference's plan: the bucket plan re-shaped to one
        request — what per-request ``solve.lstsq`` must be called with to
        reproduce a bucketed slice bit for bit."""
        return dataclasses.replace(self.bucket_plan(spec), m=m, k=r)

    def bucket_callable(self, spec: BucketSpec) -> Tuple[Callable, object]:
        """(jitted batched callable, unbatched solve plan) for one bucket."""
        fn = self._fns.get(spec)
        sp = self.bucket_plan(spec)
        if fn is None:
            fn = _build_bucket_fn(spec, sp)
            self._fns[spec] = fn
        return fn, sp

    # -- pre-warm ------------------------------------------------------------

    def warm(self, *, verbose: bool = False) -> Dict[str, float]:
        """Populate the plan cache AND compile every bucket, off the
        request path: one bulk plan-cache read (``tune.cache.warm``), then
        one dummy execution per bucket to drive XLA compilation. Returns
        {bucket label: warm seconds}; afterwards the zero-retrace floor is
        armed for every bucket."""
        import numpy as np

        from repro.tune import cache as tune_cache

        # ONE cache-file read resolves every bucket's plan key into the
        # planner memo; the per-bucket plan() calls below are memo hits.
        tune_cache.warm(
            [dict(op="solve", m=s.m, n=s.n, k=s.r, dtype=s.dtype,
                  out="packed") for s in self.config.buckets],
            cache_file=self.config.cache_file)

        report = {}
        for spec in self.config.buckets:
            fn, _sp = self.bucket_callable(spec)
            # a well-conditioned dummy: eye(m, n) has full column rank, so
            # the factor path compiles against a non-singular gram. Numpy
            # operands ON PURPOSE — dispatch feeds numpy-assembled batches,
            # and jit caches committed (device) and uncommitted (numpy)
            # inputs as distinct entries; warming with jnp arrays would
            # leave the first real request to "retrace" the numpy entry.
            a = np.broadcast_to(
                np.eye(spec.m, spec.n, dtype=spec.dtype),
                (spec.batch, spec.m, spec.n))
            b_rows = spec.m if spec.op == "lstsq" else spec.n
            b = np.zeros((spec.batch, b_rows, spec.r), spec.dtype)
            ridge = np.zeros((spec.batch,), np.float32)
            t0 = self.clock()
            fn(a, b, ridge).block_until_ready()
            dt = self.clock() - t0
            self._trace_floor[spec] = _jit_cache_size(fn)
            self._warm_s[spec] = dt
            _obs.observe("serve.warm.seconds", dt)
            report[spec.label()] = dt
            if verbose:
                print(f"  warmed {spec.label()} in {dt:.3f}s", flush=True)
        self.warmed = True
        return report

    # -- request path --------------------------------------------------------

    def submit(self, request: Request) -> Ticket:
        """Admit one request (may raise :class:`Rejected`) and dispatch any
        bucket its arrival filled."""
        now = self.clock()
        ticket = self.queue.offer(request, now)
        self.pump()
        return ticket

    def pump(self, *, force: bool = False) -> int:
        """Dispatch every due batch; returns the number of flushes."""
        batches = self.queue.due(self.clock(), force=force)
        for spec, tickets in batches:
            self._dispatch(spec, tickets)
        return len(batches)

    def drain(self) -> None:
        """Force-flush until the queue is empty (every ticket resolved)."""
        while self.queue.depth():
            self.pump(force=True)

    # -- the flush -----------------------------------------------------------

    def _dispatch(self, spec: BucketSpec, tickets: List[Ticket]) -> None:
        # batch assembly is NUMPY end to end (see pad_operands): every jnp
        # micro-op here — pad, stack, slice — would XLA-compile once per
        # distinct request-shape signature, and those ~100ms compiles were
        # the entire workload tail. The only compiled program a flush runs
        # is the bucket callable; zero-padding in numpy is the same bits.
        import numpy as np

        fn, _sp = self.bucket_callable(spec)
        a_slices, b_slices, ridges, vectors = [], [], [], []
        pad_rows = pad_cols = 0
        for t in tickets:
            req = t.request
            b_np = np.asarray(req.b)
            vec = b_np.ndim == 1
            vectors.append(vec)
            b2 = b_np[:, None] if vec else b_np
            m, r = req.a.shape[0], b2.shape[-1]
            a_pad, b_pad = pad_operands(spec, req.a, b2)
            a_slices.append(a_pad)
            b_slices.append(b_pad)
            ridges.append(float(req.ridge))
            pad_rows += spec.m - m
            pad_cols += spec.r - r
        fill = spec.batch - len(tickets)
        if fill:
            # replicate a REAL request into the empty slots: a zero design
            # matrix would hand the factor a singular gram. Fill slices are
            # compiled work, never returned.
            a_slices += [a_slices[0]] * fill
            b_slices += [b_slices[0]] * fill
            ridges += [ridges[0]] * fill
            _obs.inc("serve.padded_slots", fill)
            _obs.inc("serve.flushes.ragged")
        _obs.inc("serve.flushes")
        _obs.inc("serve.padded_rows", pad_rows)
        _obs.inc("serve.padded_cols", pad_cols)

        a_stk = np.stack(a_slices, 0)
        b_stk = np.stack(b_slices, 0)
        ridge = np.asarray(ridges, np.float32)

        t0 = self.clock()
        out = fn(a_stk, b_stk, ridge)
        out.block_until_ready()
        serve_metrics.record_latency("dispatch", self.clock() - t0)

        self._assert_no_retrace(spec, fn)

        # one device→host transfer; per-ticket crops are then numpy views
        out_np = np.asarray(out)
        done_at = self.clock()
        for i, t in enumerate(tickets):
            r = 1 if vectors[i] else t.request.b.shape[-1]
            x = crop_result(spec, out_np[i], r)
            t.set_result(x[:, 0] if vectors[i] else x)
            t.latency_s = done_at - t.enqueued_at
            serve_metrics.record_latency("request", t.latency_s)
            serve_metrics.record_latency(f"request.{spec.label()}",
                                         t.latency_s)
            dl = t.request.deadline_s
            if dl is not None and t.latency_s > dl:
                t.deadline_missed = True
                _obs.inc("serve.deadline_missed")
            _obs.inc("serve.requests.completed")

    def _assert_no_retrace(self, spec: BucketSpec, fn) -> None:
        size = _jit_cache_size(fn)
        floor = self._trace_floor.get(spec)
        if floor is None:
            # cold dispatch (no warm pass): the first flush compiles by
            # design; it sets the floor the steady state is held to.
            self._trace_floor[spec] = size
            return
        if size > floor:
            grew = size - floor
            self._trace_floor[spec] = size
            _obs.inc("serve.retraces", grew)
            if self.config.strict_retrace:
                raise RuntimeError(
                    f"bucket {spec.label()} retraced on the request path "
                    f"(jit cache {floor} -> {size}); the zero-retrace "
                    "contract is broken")

    # -- introspection -------------------------------------------------------

    def retraces(self) -> int:
        return _obs.get("serve.retraces")

    def stats(self) -> dict:
        """One JSON-serializable serving snapshot."""
        return {
            "buckets": [s.label() for s in self.config.buckets],
            "warmed": self.warmed,
            "warm_seconds": {s.label(): t for s, t in self._warm_s.items()},
            "queue_depth": self.queue.depth(),
            "lane_depths": self.queue.lane_depths(),
            "counters": _obs.counters("serve."),
            "latency": serve_metrics.latency_summary(),
        }


def _jit_cache_size(fn) -> int:
    return int(fn._cache_size())


def _build_bucket_fn(spec: BucketSpec, sp):
    """The jitted batched pipeline for one bucket (module docstring)."""
    import jax
    import jax.numpy as jnp

    from repro.core.ata import ata_batched
    from repro.core.precision import dot_precision
    from repro.solve.cholesky import cholesky
    from repro.solve.triangular import solve_cholesky, solve_triangular

    # the gram plan of the batched pipeline — exactly lstsq's derivation
    # (op='ata', k=n, packed, method/predicted cleared) plus the batch dim
    ata_plan = dataclasses.replace(
        sp, op="ata", k=sp.n, out="packed", method=None, predicted_s=None,
        batch=spec.batch)

    def run(a, b, ridge):
        a32 = a.astype(jnp.float32)
        b32 = b.astype(jnp.float32)
        gram = ata_batched(a32, plan=ata_plan, out="packed",
                           packed_block=sp.packed_block)
        gram = gram.add_scaled_identity(ridge.reshape(-1, 1, 1, 1))
        f = cholesky(gram, plan=sp)
        if spec.op == "lstsq":
            # AᵀB batched, f32 accumulation — the batched twin of lstsq's
            # _dot_tn (Aᵀ never formed)
            rhs = jax.lax.dot_general(
                a32, b32, (((1,), (1,)), ((0,), (0,))),
                precision=dot_precision(a32, b32),
                preferred_element_type=jnp.float32)
            return solve_cholesky(f, rhs, plan=sp, base_trsm=per_slice_trsm)
        # whiten: z = L⁻¹·v — forward substitution only
        return solve_triangular(f, b32, transpose=False, plan=sp,
                                base_trsm=per_slice_trsm)

    return jax.jit(run)
