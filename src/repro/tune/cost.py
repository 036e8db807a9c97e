"""Analytic cost model: predict the best dispatch plan for an ATA product.

The model joins the two quantitative assets the repo already owns:

* the **exact flop counters** of `repro.core.reference` (they walk the same
  floor/ceil recursion as the implementations, so counts are exact for any
  rectangular shape and cutoff), split here into MXU multiply flops and VPU
  addition flops, and
* the **write-traffic model** of `repro.analysis.roofline`
  (`syrk_write_traffic`: packed vs dual-write vs mirrored output bytes).

Per candidate the prediction is a two-term roofline

    compute_s = mult_flops / (peak · mxu_eff(d_base))
    memory_s  = (add_bytes + stream_bytes + output_bytes) / hbm_bw
    predicted = max(compute_s, memory_s)

where ``mxu_eff(d) = d / (d + d_half)`` models the efficiency loss of small
base matmuls (``d_half`` = tile size at which the matmul engine reaches half
its peak). This term is what creates the Strassen crossover the paper
engineers around: each extra recursion level multiplies mult flops by 7/8
but halves the base dimension, so the analytic argmin lands at a finite
``n_base`` instead of "recurse forever".

The memory terms: ``stream_bytes`` is the blocked-matmul operand traffic
``(mult/2)·(1/bn + 1/bk)`` of the *kernel output tile* (the plan's Pallas
blocks on TPU, XLA's ~256 tiling elsewhere) — the same for the one big
dense dot and for the recursion's base tiles, which is what makes the
comparison honest; ``combine_bytes`` charges the operand-combination
traffic — each VPU addition flop ``add_word_cost`` words for unrolled
(≈1 on TPU where XLA fuses operand combinations into the consuming dot's
reads; higher on CPU), ``stack_word_cost`` words for batched's
materialized stacks, and the 3^L slot-gather amplification for fused —
the Strassen memory overhead the paper's Section 3.3 engineers around.
It is an *additive* term, not part of the compute/memory max: the combine
passes serialize with the leaf matmuls on every measured backend.

A third, previously-unpriced term joins the roofline in this revision:
**per-call launch/graph overhead** (``dispatch_calls × launch_overhead_s``).
The unrolled recursion hands the runtime one op per leaf — ``7^L`` dots —
and on small leaves that dispatch tax, not flops, is what loses to a single
plain dot (BENCH_strassen's 0.19–0.61 speedups). The level-synchronous
``leaf_dispatch='batched'`` formulation collapses it to O(levels) calls at
the price of materialized (un-fused) operand-combination stacks;
``leaf_dispatch='fused'`` collapses both at once — one launch per level
and zero materialized stacks, paying only the slot-gather read
amplification (3^L) and the coefficient tables. The model prices all
three so the argmin can pick per shape.

Candidate axes (``candidates``): algorithm (dense-dot vs strassen vs
winograd vs the ATA recursion), output mode (dense vs packed), recursion
cutoff ``n_base``, leaf dispatch (unrolled vs batched vs fused —
value-identical, speed-different; fused is classical-variant-only), and
the Pallas kernel block shapes. The algorithm /
``n_base`` choice is deliberately **out-invariant** (scored with the dense
output term) so that ``out='packed'`` and ``out='dense'`` plans of one
problem always run the identical recursion — packed results stay bitwise
equal to dense ones regardless of cache state (``leaf_dispatch`` cannot
break this: both dispatches are bitwise-equal by construction, tested).

``distributed_tiling`` is the planner's distributed branch: the lower
triangle tiling search that used to live in ``core.distributed
.choose_tiling`` (which now delegates here).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional, Tuple

from repro.core.reference import (
    blocked_potrf_flops,
    cg_iteration_flops,
    classical_gemm_flops,
    classical_syrk_flops,
    ata_flops,
    strassen_tn_flops,
    strassen_tn_flops_winograd,
    trsm_flops,
)
from repro.tune import defaults

__all__ = [
    "Plan",
    "Machine",
    "MACHINES",
    "machine_for",
    "predict_seconds",
    "retrieval_bytes",
    "comm_levels",
    "comm_seconds",
    "comm_memory_bytes",
    "comm_schedule_candidates",
    "choose_comm_schedule",
    "dispatch_calls",
    "solve_dispatch_calls",
    "candidates",
    "analytic_plan",
    "default_plan",
    "distributed_tiling",
    "bfs_tiling",
]

_ITEMSIZE = {"float32": 4, "bfloat16": 2, "float16": 2, "float64": 8}


# ---------------------------------------------------------------------------
# the frozen dispatch plan
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Plan:
    """One fully-resolved ATA/gemm dispatch: problem key + every tunable.

    Frozen and JSON-serializable (``to_json``/``from_json``) — this is the
    value the plan cache stores and the consumers (`core.ata`,
    `core.strassen`, `core.distributed`, `kernels.ops`) read instead of
    loose ints. ``algorithm`` semantics: for ``op='ata'``, 'strassen' /
    'winograd' select the C21 variant of the ATA recursion and 'dense' means
    one classical TN dot; for ``op='gemm_tn'``, they select the FastStrassen
    variant.
    """

    op: str                      # 'ata' | 'gemm_tn' | 'solve'
    m: int
    n: int
    k: int                       # == n for op='ata'; rhs count for op='solve'
    batch: int                   # leading batch size (0 = unbatched)
    dtype: str
    backend: str                 # jax.default_backend() at planning time
    out: str                     # 'dense' | 'packed'
    algorithm: str               # 'dense' | 'strassen' | 'winograd'
    n_base: int
    packed_block: int
    use_kernels: bool            # Pallas base kernels (TPU) vs dot_general
    syrk_blocks: Tuple[int, int]
    gemm_blocks: Tuple[int, int, int]
    # how the recursion's leaves reach the hardware: 'unrolled' = one
    # dot/syrk op per leaf (7^L dots in the jaxpr), 'batched' = the
    # level-synchronous formulation (all leaves in one batched call,
    # bitwise-equal values). Pre-leaf_dispatch cache entries deserialize to
    # 'unrolled' — exactly what they were measured with.
    leaf_dispatch: str = "unrolled"
    # op='solve' only: 'factor' (packed gram → packed Cholesky → two
    # substitutions) or 'cg' (matrix-free CG on the gram operator). None
    # for the product ops — and for pre-solve cache entries, which is why
    # the default keeps them deserializable unchanged.
    method: Optional[str] = None
    devices: int = 1             # distributed branch: task-axis size
    nb: Optional[int] = None     # distributed stripe count (devices > 1)
    tile_w: Optional[int] = None  # distributed stripe width (devices > 1)
    # distributed branch, devices > 1 only: row (reduction) axis size of
    # the two-level ATA-D mesh, and the BFS/DFS interleaving string of the
    # CAPS-style schedule ('B'/'D' per recursion level — the contract of
    # core.distributed.bfs_dfs_assignment). None = the plain-psum schedule
    # (ata_tile_parallel); pre-v4 cache entries deserialize to exactly
    # that, which is what they were measured with.
    row_devices: int = 1
    comm_schedule: Optional[str] = None
    source: str = "analytic"     # 'analytic' | 'measured' | 'cache' | 'default'
    predicted_s: Optional[float] = None
    measured_s: Optional[float] = None
    # seconds of the hardcoded-default dispatch, measured interleaved with
    # this plan by the autotuner (time_pair) — baseline_s/measured_s is the
    # drift-resistant speedup-vs-default the tuning run actually observed.
    baseline_s: Optional[float] = None

    @property
    def variant(self) -> str:
        """Strassen variant usable by the recursion ('dense' plans included:
        the recursion never splits because n_base covers the whole tile)."""
        return "winograd" if self.algorithm == "winograd" else "strassen"

    def to_json(self) -> dict:
        d = dataclasses.asdict(self)
        d["syrk_blocks"] = list(self.syrk_blocks)
        d["gemm_blocks"] = list(self.gemm_blocks)
        return d

    @classmethod
    def from_json(cls, d: dict) -> "Plan":
        d = dict(d)
        d["syrk_blocks"] = tuple(d["syrk_blocks"])
        d["gemm_blocks"] = tuple(d["gemm_blocks"])
        return cls(**d)


# ---------------------------------------------------------------------------
# machine models
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Machine:
    """Roofline parameters of one backend."""

    name: str
    peak_flops: float      # matmul peak, flops/s
    hbm_bw: float          # bytes/s
    d_half: int            # matmul dim at which efficiency reaches 1/2
    kernels: bool          # Pallas kernels compile natively (not interpret)
    add_word_cost: float   # extra HBM words charged per VPU addition flop
    # words charged per addition flop of the *batched* dispatch, whose
    # operand combinations materialize as (7^ℓ,…) stacks the leaf dot then
    # re-reads. Nominally write+read = 2.0; the cpu model carries a larger
    # measured value (see MACHINES) because the block-major relayout and
    # stack concats thrash caches far beyond their linear byte count.
    stack_word_cost: float = 2.0
    xla_tile: int = 256    # nominal output tile of the non-Pallas matmul
    # per dispatched op: runtime launch/dispatch + amortized graph/compile
    # overhead. This is the term the batched leaf dispatch exists to kill:
    # unrolled recursion pays it 7^L times, batched O(L) times.
    launch_overhead_s: float = 5e-6
    # α-β collective model (distributed branch): per-message latency and
    # per-byte transfer time of one collective step. α is what the psum
    # schedule's single all-reduce amortizes and the BFS scatter+gather
    # pair pays twice; β is what the scattered retrieval halves. The cpu
    # values are calibrated on the 8-fake-device container (see MACHINES).
    alpha_s: float = 1e-6
    beta_s_per_byte: float = 2.5e-11
    # per-device memory budget the interleaving choice is priced against
    # (CAPS's memory-vs-bandwidth rule): schedules whose per-device
    # residency exceeds it are infeasible.
    device_memory_bytes: float = 16e9
    # ``device_kind`` strings (as JAX reports them) the parameters were set
    # for; empty for a model not tied to one chip. ``machine_for`` refuses
    # to price an attached device of any other kind.
    device_kinds: Tuple[str, ...] = ()

    def mxu_eff(self, d: int) -> float:
        d = max(int(d), 1)
        return d / (d + self.d_half)


def _tpu_machine() -> Machine:
    # join with the dry-run roofline model so both analyses share one v5e
    # parameterization (PEAK_FLOPS / HBM_BW are defined there).
    from repro.analysis import roofline

    return Machine(
        "tpu", roofline.PEAK_FLOPS, roofline.HBM_BW, 128, True, 1.0,
        launch_overhead_s=1.5e-6,
        # ICI-class interconnect: ~1 µs collective step, ~9e10 B/s per link
        alpha_s=1e-6, beta_s_per_byte=1.1e-11, device_memory_bytes=16e9,
        device_kinds=("TPU v5 lite",),  # the v5e, as JAX names it
    )


MACHINES = {
    "tpu": _tpu_machine,
    # Container-class CPU, recalibrated against the min-of-interleaved
    # floors of the batched-leaf PR's measurement sweep (the old 1e11-peak/
    # d_half=48 numbers predated the per-call overhead term and let deep
    # tiny-leaf recursions look free): XLA's dense dot sustains ~205 GFLOP/s
    # at 1024³ on this container (peak 2.2e11), while 256-leaf recursions
    # run at <0.4 of that (d_half 512 — CPU matmul efficiency falls off far
    # harder than the MXU's), and each dispatched op costs ~50 µs of thunk
    # overhead. ``stack_word_cost`` is re-fit against the fused-leaf PR's
    # min-of-interleaved sweep at 2048³/n_base=1024: the batched dispatch
    # trails the unrolled one by ~0.022 s there, which against its ~1.9e7
    # addition flops prices each materialized-stack add at ≈5.5 words —
    # the nominal 2.0 hid behind the compute roofline and ranked batched
    # above unrolled, inverting the measured order. Under this model the
    # argmin at the bench shapes matches the measured per-shape ranking:
    # dense < unrolled(L=1) < fused(L=1) < batched(L=1) < deep recursions.
    # α-β terms calibrated on the 8-fake-device container via the
    # obs.calibrate drift rows of the distributed sweep (fake devices
    # share one memory): a collective "message" costs a thunk dispatch
    # ≈ the 5e-5 launch floor; β from the same-compute psum-vs-scatter
    # differential at the (1,8) rowshard mesh — Δ2.6 ms over Δ3.9 MB of
    # collective payload ≈ 7e-10 s/B (fake-device "links" run at shared-
    # memcpy-under-contention speed, ~1.4e9 B/s, not the 1e10 B/s a real
    # socket-local memcpy would suggest).
    "cpu": lambda: Machine("cpu", 2.2e11, 2.0e10, 512, False, 1.5,
                           stack_word_cost=5.5, launch_overhead_s=5e-5,
                           alpha_s=5e-5, beta_s_per_byte=7e-10,
                           device_memory_bytes=2e9),
    # A100-class default for completeness (untuned; autotune refines).
    "gpu": lambda: Machine("gpu", 1.56e14, 1.6e12, 128, False, 1.0,
                           launch_overhead_s=8e-6,
                           alpha_s=4e-6, beta_s_per_byte=4e-12,
                           device_memory_bytes=8e10),
}


def machine_for(backend: str) -> Machine:
    """The cost model of ``backend``.

    Raises for a backend that has no model, and for an attached device
    whose ``device_kind`` the model was not set for. Planning for a
    backend that is not attached (``backend='tpu'`` on a CPU host) prices
    the model as stated.
    """
    if backend not in MACHINES:
        raise ValueError(
            f"no cost model for backend {backend!r}; known: {sorted(MACHINES)}"
        )
    mach = MACHINES[backend]()
    if mach.device_kinds:
        import jax

        if jax.default_backend() == backend:
            kind = jax.devices()[0].device_kind
            if kind not in mach.device_kinds:
                raise ValueError(
                    f"the {backend!r} cost model was set for "
                    f"{mach.device_kinds}, not the attached {kind!r}"
                )
    return mach


# ---------------------------------------------------------------------------
# mult/add flop split (exact, mirrors repro.core.reference recursions)
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _strassen_mult_flops(m: int, n: int, k: int, n_base: int) -> int:
    """MXU flops of the TN Strassen recursion (base matmuls only)."""
    if min(m, n, k) <= n_base:
        return classical_gemm_flops(m, n, k)
    mp, np_, kp = m + (m & 1), n + (n & 1), k + (k & 1)
    return 7 * _strassen_mult_flops(mp // 2, np_ // 2, kp // 2, n_base)


@functools.lru_cache(maxsize=None)
def _ata_mult_flops(m: int, n: int, n_base: int) -> int:
    """MXU flops of the ATA recursion (classical-syrk base tiles + Strassen
    leaves; the C11/C22/C21 accumulations are VPU adds, not counted here)."""
    if min(m, n) <= n_base:
        return classical_syrk_flops(m, n)
    mp, np_ = m + (m & 1), n + (n & 1)
    m2, n2 = mp // 2, np_ // 2
    return 4 * _ata_mult_flops(m2, n2, n_base) + 2 * _strassen_mult_flops(
        m2, n2, n2, n_base
    )


@functools.lru_cache(maxsize=None)
def _strassen_leaves(m: int, n: int, k: int, n_base: int) -> int:
    """Leaf (base-matmul) count of the TN Strassen recursion."""
    if min(m, n, k) <= n_base:
        return 1
    mp, np_, kp = m + (m & 1), n + (n & 1), k + (k & 1)
    return 7 * _strassen_leaves(mp // 2, np_ // 2, kp // 2, n_base)


@functools.lru_cache(maxsize=None)
def _ata_leaves(m: int, n: int, n_base: int) -> Tuple[int, int]:
    """(syrk_leaves, gemm_leaves) of the ATA tree (4 sub-ATAs + 2 Strassen
    off-diagonal products per level, mirroring `_ata_mult_flops`)."""
    if min(m, n) <= n_base:
        return 1, 0
    mp, np_ = m + (m & 1), n + (n & 1)
    m2, n2 = mp // 2, np_ // 2
    s, g = _ata_leaves(m2, n2, n_base)
    return 4 * s, 4 * g + 2 * _strassen_leaves(m2, n2, n2, n_base)


def _levels(op, m, n, k, n_base) -> int:
    # the recursion's own depth rule — pricing must count the exact tree
    # the dispatch executes (core.strassen only reaches back into tune
    # lazily, so this import is cycle-free, like core.reference above)
    from repro.core.strassen import tree_depth

    return tree_depth((m, n, k) if op == "gemm_tn" else (m, n), n_base)


def dispatch_calls(op, algorithm, m, n, k, n_base, leaf_dispatch) -> int:
    """Ops the dispatch hands the runtime — the per-call-overhead multiplier.

    ``'unrolled'`` pays one dispatched dot/syrk per leaf (``7^L`` for
    Strassen, ``4^L`` syrks + the off-diagonal leaf dots for ATA);
    ``'batched'`` pays the two batched leaf calls plus O(levels)
    encode/decode stack ops. ``'fused'`` is cheapest of all: the slot
    gather lives inside the kernel prologue, so Strassen is one fused
    leaf launch plus one decode pass per level, and ATA is one gathered
    diagonal syrk plus one fused off-diagonal launch and one decode pass
    per level — one launch per *level*, never per leaf. 'dense' is the
    single classical dot.
    """
    if algorithm == "dense":
        return 1
    if leaf_dispatch == "fused":
        lv = _levels(op, m, n, k, n_base)
        if op == "ata":
            return 2 + 2 * lv
        return 1 + lv
    if leaf_dispatch == "batched":
        return 2 + 4 * _levels(op, m, n, k, n_base)
    if op == "ata":
        s, g = _ata_leaves(m, n, n_base)
        return s + g
    return _strassen_leaves(m, n, k, n_base)


def solve_dispatch_calls(n: int, packed_block: int) -> int:
    """Ops the packed factor-and-substitute pipeline hands the runtime
    beyond the gram product itself: per block column one potrf, one batched
    panel trsm and up to two Schur-update einsums; per substitution pass
    one diagonal solve and one update einsum per block row, twice.
    """
    nb = -(-n // packed_block)
    factor = nb + (nb - 1) + 2 * max(nb - 1, 0)   # potrf + trsm + updates
    substitute = 2 * 2 * nb                        # two passes, solve+update
    return factor + substitute


def _solve_predict(
    method: str,
    algorithm: str,
    m: int,
    n: int,
    r: int,
    n_base: int,
    *,
    dtype: str,
    packed_block: int,
    machine: "Machine",
    blocks,
    leaf_dispatch: str = "unrolled",
) -> float:
    """Roofline prediction for one op='solve' candidate.

    ``method='factor'``: the planned packed gram (priced by the product
    model below) plus the factorization/substitution tail — potrf/trsm
    flops from the exact `core.reference` counters, and the **packed**
    write traffic of the factor (the `analysis.roofline` solve model: the
    factor overwrites T·bn² packed words, never an n² square).
    ``method='cg'``: `CG_MAX_ITERS`-capped iterations, each streaming the
    operand twice through the two planned TN products.
    """
    from repro.analysis.roofline import normal_eq_write_traffic

    itemsize = _ITEMSIZE.get(dtype, 4)
    if method == "cg":
        iters = min(n, defaults.CG_MAX_ITERS)
        flops = iters * cg_iteration_flops(m, n, r)
        d = min(m, n)
        compute_s = flops / (machine.peak_flops * machine.mxu_eff(d))
        # each iteration streams A twice (A·p, then Aᵀ(A·p)) + the vectors
        mem = iters * (2 * m * n + 6 * n * r) * itemsize
        overhead = iters * 8 * machine.launch_overhead_s
        return max(compute_s, mem / machine.hbm_bw) + overhead

    gram_s = predict_seconds(
        "ata", algorithm, m, n, n, n_base,
        dtype=dtype, out="packed", packed_block=packed_block,
        machine=machine, blocks=blocks, leaf_dispatch=leaf_dispatch,
    )
    flops = blocked_potrf_flops(n, packed_block) + 2 * trsm_flops(n, r)
    compute_s = flops / (machine.peak_flops * machine.mxu_eff(packed_block))
    mem = normal_eq_write_traffic(n, packed_block, r, itemsize=itemsize)
    overhead = solve_dispatch_calls(n, packed_block) * machine.launch_overhead_s
    return gram_s + max(compute_s, mem / machine.hbm_bw) + overhead


def _flop_split(op, algorithm, m, n, k, n_base):
    """(mult_flops, add_flops) for one candidate — adds = total − mults."""
    if algorithm == "dense":
        # one classical TN dot over the whole operand (no recursion)
        mult = classical_gemm_flops(m, n, k)
        return mult, 0
    winograd = algorithm == "winograd"
    if op == "ata":
        total = ata_flops(m, n, n_base, winograd=winograd)
        mult = _ata_mult_flops(m, n, n_base)
    else:
        s = strassen_tn_flops_winograd if winograd else strassen_tn_flops
        total = s(m, n, k, n_base)
        mult = _strassen_mult_flops(m, n, k, n_base)
    return mult, max(total - mult, 0)


def _output_bytes(op, out, n, k, packed_block, itemsize) -> int:
    """HBM bytes written for the final output (roofline join point)."""
    from repro.analysis.roofline import syrk_write_traffic

    if op == "ata":
        mode = "packed" if out == "packed" else "dual"
        return syrk_write_traffic(n, packed_block, mode, itemsize)
    return n * k * itemsize


def retrieval_bytes(
    out: str,
    nb: int,
    tile_w: int,
    itemsize: int = 4,
) -> int:
    """Retrieval payload of the distributed tile schedule, per device.

    Both terms are functions of the padded stripe grid alone.
    ``out='packed'`` ships the psum'd/gathered tile stack itself —
    ``T·w² ≈ n²/2`` words (paper Prop. 4.2's low(C) saving as collective
    bytes). ``out='dense'`` additionally materializes the mirrored
    ``(nb·w)²`` square on every device — the dense-replication cost the
    packed mode removes.
    """
    t_total = nb * (nb + 1) // 2
    stack = t_total * tile_w * tile_w * itemsize
    if out == "packed":
        return stack
    return (nb * tile_w) ** 2 * itemsize


# ---------------------------------------------------------------------------
# α-β communication model of the BFS/DFS schedule (CAPS-style, paper §5)
# ---------------------------------------------------------------------------


def _bfs_makespan(nb: int, devices: int, comm_schedule: Optional[str]) -> int:
    """Tiles on the busiest task device under the interleaving (== the
    contiguous ``ceil(T/devices)`` for pure DFS / the psum schedule)."""
    t_total = nb * (nb + 1) // 2
    if not comm_schedule or "B" not in comm_schedule:
        return -(-t_total // devices)
    from repro.core.distributed import bfs_dfs_assignment

    owned, _ = bfs_dfs_assignment(nb, devices, comm_schedule)
    return max(len(o) for o in owned)


def comm_levels(
    comm_schedule: Optional[str],
    nb: int,
    tile_w: int,
    devices: int,
    row_devices: int = 1,
    *,
    out: str = "packed",
    itemsize: int = 4,
) -> list:
    """Per-level (messages, words) attribution of one interleaving.

    Two realized exchange patterns, priced with the standard
    ring-collective α-β counts and attributed to the levels whose tag
    induces them:

    * any ``'B'`` level switches the whole root exchange to the
      **tri-direct reduce-scatter**: one collective over the merged
      ``P = devices·row_devices`` pool moves the ``T``-padded staging
      stack ``S_pad = T_pad·w²`` — ``P−1`` steps, ``S_pad·(P−1)/P``
      words — simultaneously reducing the row-wise partials and dealing
      tri-order chunks, after which the packed retrieval is a pure slice
      (no root gather). Attributed evenly to the ``'B'`` levels (the
      redistribution is what BFS means); dense out adds the
      ``T``-stack gather the mirrored-square assembly forces, at the
      last level;
    * a pure-``'D'`` string (or ``None`` — the psum schedule) pays the
      **row-axis all-reduce** of the slot stack ``S = s_eff·w²``
      (``2(d−1)`` steps, ``2·S·(d−1)/d`` words), attributed evenly to
      the ``'D'`` levels, plus the **root gather** replicating the
      packed result (dense adds the mirrored square) across the pool —
      ``P−1`` steps, ``R·(P−1)/P`` words — and the **diag-symmetrization
      gather**: ``from_tile_stack`` on the pool-sharded stack lowers
      ``_symmetrize_diag``'s cross-shard diag-tile read as a masked
      all-reduce (``P−1`` steps, ``nb·w²`` words — the term the scatter
      schedule deletes by symmetrizing its chunk locally), both at the
      last level.

    Returned as one ``{'tag', 'msgs', 'words'}`` dict per level — the
    per-level ``prop42_msgs``/``prop42_words`` columns of
    ``bench_distributed``.
    """
    sched = comm_schedule or "D"
    t_total = nb * (nb + 1) // 2
    pool = devices * max(row_devices, 1)
    scatter = "B" in sched and pool > 1
    levels = [dict(tag=c, msgs=0.0, words=0.0) for c in sched]
    if scatter:
        t_pad = -(-t_total // pool) * pool
        s_pad = t_pad * tile_w * tile_w
        red_msgs, red_words = pool - 1, s_pad * (pool - 1) / pool
        carriers = [lv for lv in levels if lv["tag"] == "B"]
        for lv in carriers:
            lv["msgs"] += red_msgs / len(carriers)
            lv["words"] += red_words / len(carriers)
        if out == "dense":
            # to_dense gathers the chunked tri stack for the mirrored
            # square on every device
            levels[-1]["msgs"] += pool - 1
            levels[-1]["words"] += s_pad * (pool - 1) / pool
        return levels
    s_max = _bfs_makespan(nb, devices, sched)
    stack_words = s_max * tile_w * tile_w
    d = max(row_devices, 1)
    if d > 1:
        red_msgs, red_words = 2 * (d - 1), 2 * stack_words * (d - 1) / d
        carriers = [lv for lv in levels if lv["tag"] == "D"] or levels
        for lv in carriers:
            lv["msgs"] += red_msgs / len(carriers)
            lv["words"] += red_words / len(carriers)
    res_words = t_total * tile_w * tile_w
    if out == "dense":
        res_words += (nb * tile_w) ** 2
    levels[-1]["msgs"] += pool - 1
    levels[-1]["words"] += res_words * (pool - 1) / pool
    if pool > 1:
        # retrieval's _symmetrize_diag over the pool-sharded stack
        levels[-1]["msgs"] += pool - 1
        levels[-1]["words"] += nb * tile_w * tile_w
    return levels


def comm_seconds(
    machine: Machine,
    comm_schedule: Optional[str],
    nb: int,
    tile_w: int,
    devices: int,
    row_devices: int = 1,
    *,
    out: str = "packed",
    itemsize: int = 4,
) -> float:
    """Total α-β time of one interleaving: ``Σ msgs·α + Σ bytes·β``."""
    levels = comm_levels(comm_schedule, nb, tile_w, devices, row_devices,
                         out=out, itemsize=itemsize)
    msgs = sum(lv["msgs"] for lv in levels)
    words = sum(lv["words"] for lv in levels)
    return msgs * machine.alpha_s + words * itemsize * machine.beta_s_per_byte


def comm_memory_bytes(
    comm_schedule: Optional[str],
    nb: int,
    tile_w: int,
    devices: int,
    row_devices: int = 1,
    *,
    m: int,
    out: str = "packed",
    itemsize: int = 4,
    stripes: bool = False,
) -> int:
    """Per-device residency of one interleaving (the CAPS memory side).

    The textbook CAPS trade: a ``'B'`` level buys its bandwidth saving
    with memory — every device stages its partial tiles in a **full
    ``T``-padded tri-order buffer** (plus the operand slab, the local
    partial stack, and the scattered ``T/P`` chunk it keeps); a
    pure-``'D'`` string stays lean — operand slab + slot stack + the
    all-reduce's full reduced copy + its share of the packed result.
    ``stripes`` counts the operand slab twice: the direct tile path of
    ``core.distributed`` reads its stripes from a stripe-major copy of it,
    and takes that path only where this figure fits the device.
    """
    sched = comm_schedule or "D"
    t_total = nb * (nb + 1) // 2
    d = max(row_devices, 1)
    pool = devices * d
    scatter = "B" in sched and pool > 1
    s_max = _bfs_makespan(nb, devices, sched)
    tile = tile_w * tile_w * itemsize
    operand = (2 if stripes else 1) * (m // d) * nb * tile_w * itemsize
    local_stack = s_max * tile
    if scatter:
        t_pad = -(-t_total // pool) * pool
        staging = (t_pad + 1) * tile
        chunk = (t_pad // pool) * tile
        result = chunk if out == "packed" else (nb * tile_w) ** 2 * itemsize
        return operand + local_stack + staging + result
    reduced = s_max * tile if d > 1 else 0
    result = t_total * tile
    if out == "dense":
        result += (nb * tile_w) ** 2 * itemsize
    return operand + local_stack + reduced + result


def comm_schedule_candidates(nb: int, max_levels: Optional[int] = None) -> list:
    """Interleaving strings the planner enumerates for one stripe grid:
    every string over {'B','D'} up to ``min(max_levels, tree depth)``
    characters (``None`` — the psum schedule — is always candidate 0)."""
    if max_levels is None:
        max_levels = defaults.MAX_COMM_SCHEDULE_LEVELS
    depth = max(1, (nb - 1).bit_length())  # ceil(log2(nb)): tile-tree depth
    max_levels = min(max_levels, depth)
    out = [None]
    frontier = [""]
    for _ in range(max_levels):
        frontier = [s + c for s in frontier for c in ("D", "B")]
        out.extend(frontier)
    return out


def choose_comm_schedule(
    nb: int,
    tile_w: int,
    devices: int,
    row_devices: int = 1,
    *,
    m: int,
    out: str = "packed",
    itemsize: int = 4,
    machine: Optional[Machine] = None,
    backend: str = "cpu",
    n: Optional[int] = None,
) -> Optional[str]:
    """The planner's interleaving argmin for one (shape, mesh, memory).

    Scores every candidate string by α-β communication time plus the
    compute-imbalance penalty of its subgroup assignment (makespan tiles
    over the balanced ``ceil(T/P)``), discards candidates whose
    per-device residency exceeds the machine's memory budget (falling
    back to the minimum-memory candidate when all bust it), and returns
    the argmin — ``None`` means the plain psum schedule wins. With ``n``
    given, BFS-containing candidates are priced at their own
    pool-divisible :func:`bfs_tiling` grid (the grid the dispatch will
    actually run them on) instead of the psum schedule's ``(nb, tile_w)``.
    """
    mach = machine or machine_for(backend)
    pool = devices * max(row_devices, 1)
    scored, overflow = [], []
    for sched in comm_schedule_candidates(nb):
        nb_s, w_s = (nb, tile_w)
        if sched and "B" in sched and pool > 1 and n is not None:
            nb_s, w_s = bfs_tiling(n, pool, devices=devices, out=out)
        secs = comm_seconds(mach, sched, nb_s, w_s, devices, row_devices,
                            out=out, itemsize=itemsize)
        # imbalance: extra tiles on the busiest device, priced as extra
        # launches (the dominant per-tile cost at bench scale is the leaf
        # dispatch; exact flops would need m and double-count compute_s)
        t_per = -(-(nb_s * (nb_s + 1) // 2) // devices)
        extra = _bfs_makespan(nb_s, devices, sched) - t_per
        secs += extra * mach.launch_overhead_s
        mem = comm_memory_bytes(sched, nb_s, w_s, devices, row_devices,
                                m=m, out=out, itemsize=itemsize)
        (scored if mem <= mach.device_memory_bytes else overflow).append(
            (secs, mem, sched))
    if not scored:
        # every candidate busts the budget: least-memory one, by the rule
        return min(overflow, key=lambda t: (t[1], t[0]))[2]
    return min(scored, key=lambda t: t[0])[2]


def predict_seconds(
    op: str,
    algorithm: str,
    m: int,
    n: int,
    k: int,
    n_base: int,
    *,
    batch: int = 0,
    dtype: str = "float32",
    out: str = "dense",
    packed_block: int = defaults.DEFAULT_PACKED_BLOCK,
    machine: Optional[Machine] = None,
    backend: str = "cpu",
    blocks: Optional[Tuple[int, int]] = None,
    devices: int = 1,
    nb: Optional[int] = None,
    tile_w: Optional[int] = None,
    leaf_dispatch: str = "unrolled",
    row_devices: int = 1,
    comm_schedule: Optional[str] = None,
) -> float:
    """Roofline prediction for one candidate configuration.

    ``blocks``: the (bn, bk) output tile of the base matmul engine — the
    plan's Pallas blocks when kernels are in play, the backend's nominal
    XLA tiling otherwise. With ``devices > 1`` (the planner's distributed
    branch) the output term becomes the tile schedule's *retrieval* payload
    (:func:`retrieval_bytes`) — packed tile stack vs replicated dense
    square — for the ``nb``/``tile_w`` stripe tiling.

    ``leaf_dispatch`` moves two terms in opposite directions: ``'unrolled'``
    pays :func:`dispatch_calls` × ``launch_overhead_s`` (one dispatched op
    per leaf — the term that was silently zero before and made tiny-leaf
    recursions look free); ``'batched'`` pays O(levels) calls but its
    operand-combination adds are *materialized* stacks the leaf dot then
    re-reads, charged ``stack_word_cost`` words per add (nominal write+read
    = 2.0, measured higher on cpu); ``'fused'`` pays neither — its stack
    charge drops to ~0, replaced by the slot-gather read amplification
    (each root leaf block is read once per nonzero slot: Strassen's combos
    total 12 terms per 7 children per side, so L levels amplify the operand
    read by (12/4)^L = 3^L) plus the coefficient tables themselves.

    The combine/add traffic is charged *additively* on top of the
    compute/memory roofline max, not inside it: on every backend we
    measured, the operand-combination passes serialize with the leaf
    matmuls (XLA:CPU runs them as separate thunks; the fused kernel runs
    them in the same launch but on the VPU ahead of each MXU tile), and
    folding them into the max() hid them entirely at compute-bound shapes
    — which is exactly where the bench measurements show the dispatches
    separating.
    """
    mach = machine or machine_for(backend)
    itemsize = _ITEMSIZE.get(dtype, 4)
    b = max(batch, 1)

    mult, adds = _flop_split(op, algorithm, m, n, k, n_base)
    d_base = min(n_base, m, n, k) if algorithm != "dense" else min(m, n, k)
    compute_s = b * mult / (mach.peak_flops * mach.mxu_eff(d_base))

    # memory: operand streaming of the blocked base matmuls (each output
    # tile re-reads its operand panels: (mult/2)·(1/bn + 1/bk) words), the
    # fused-add traffic, and the output writes per the roofline model.
    bn, bk = blocks or (mach.xla_tile, mach.xla_tile)
    bn = min(bn, max(d_base, 1))
    bk = min(bk, max(d_base, 1))
    stream_bytes = (mult / 2) * (1.0 / bn + 1.0 / bk) * itemsize
    if leaf_dispatch == "fused" and algorithm != "dense":
        # no materialized stacks: the slot gather reads each root leaf
        # block once per nonzero slot (3^L amplification, see docstring),
        # plus the six (7^L, 2^L) int32 coefficient tables.
        lv = _levels(op, m, n, k, n_base)
        operand_words = (m * n + m * k) if op == "gemm_tn" else 2 * m * n
        combine_bytes = operand_words * 3.0**lv * itemsize + 6 * 14**lv * 4
        if not mach.kernels:
            # interpret/XLA fallback: the gathered combinations still
            # materialize per leaf (briefly — never as cross-leaf stacks)
            # and are re-read by the leaf dot; charge the addition flops
            # like the unrolled form on top of the gather reads.
            combine_bytes += mach.add_word_cost * adds * itemsize
    else:
        add_word_cost = (
            mach.stack_word_cost
            if leaf_dispatch == "batched" and algorithm != "dense"
            else mach.add_word_cost
        )
        combine_bytes = add_word_cost * adds * itemsize
    comm_s = 0.0
    pool = devices * max(row_devices, 1)
    if op == "ata" and pool > 1:
        if nb is None or tile_w is None:
            if comm_schedule and "B" in comm_schedule:
                nb, tile_w = bfs_tiling(n, pool, devices=devices, out=out)
            else:
                # pure row-shard (devices == 1): one full-width stripe —
                # gram_rowshard's whole-matrix row all-reduce
                nb, tile_w = distributed_tiling(
                    n, devices, out=out, packed_block=packed_block
                )
        out_bytes = retrieval_bytes(out, nb, tile_w, itemsize)
        # the α-β collective term: message latency (the piece that was
        # silently zero before this revision) + transfer time of the
        # schedule's reduction and root-gather phases, plus the subgroup
        # assignment's compute-imbalance penalty (makespan tiles over the
        # balanced split, priced like choose_comm_schedule does).
        comm_s = comm_seconds(
            mach, comm_schedule, nb, tile_w, devices, row_devices,
            out=out, itemsize=itemsize,
        )
        t_per = -(-(nb * (nb + 1) // 2) // devices)
        comm_s += (
            _bfs_makespan(nb, devices, comm_schedule) - t_per
        ) * mach.launch_overhead_s
    else:
        out_bytes = _output_bytes(op, out, n, k, packed_block, itemsize)
    memory_s = b * (stream_bytes + out_bytes) / mach.hbm_bw
    combine_s = b * combine_bytes / mach.hbm_bw
    overhead_s = (
        dispatch_calls(op, algorithm, m, n, k, n_base, leaf_dispatch)
        * mach.launch_overhead_s
    )
    return max(compute_s, memory_s) + combine_s + overhead_s + comm_s


# ---------------------------------------------------------------------------
# candidate enumeration and the analytic argmin
# ---------------------------------------------------------------------------


def _kernel_blocks(machine):
    """Best feasible (syrk_blocks, gemm_blocks) under the VMEM budget.

    Blocks only move the memory term: minimize output-tile streaming
    (1/bn [+ 1/bk]), tie-break on the smaller VMEM footprint.
    """
    vmem = 12 * 2**20  # leave headroom below the ~16 MB VMEM
    syrk = [
        (bm, bn)
        for bm, bn in defaults.SYRK_BLOCK_CANDIDATES
        if 2 * bm * bn * 4 + bn * bn * 4 <= vmem
    ]
    gemm = [
        (bm, bn, bk)
        for bm, bn, bk in defaults.GEMM_BLOCK_CANDIDATES
        if bm * (bn + bk) * 4 + bn * bk * 4 <= vmem
    ]
    syrk = sorted(
        syrk or [defaults.SYRK_BLOCKS],
        key=lambda b: (2.0 / b[1], 2 * b[0] * b[1] + b[1] * b[1]),
    )
    gemm = sorted(
        gemm or [defaults.GEMM_BLOCKS],
        key=lambda b: (1.0 / b[1] + 1.0 / b[2], b[0] * (b[1] + b[2]) + b[1] * b[2]),
    )
    return syrk[0], gemm[0]


def candidates(
    op: str,
    m: int,
    n: int,
    k: Optional[int] = None,
    *,
    batch: int = 0,
    dtype: str = "float32",
    out: str = "dense",
    backend: str = "cpu",
    devices: int = 1,
    row_devices: int = 1,
) -> list:
    """Enumerate scored candidate Plans, best predicted first.

    Scoring uses ``out='dense'`` for the algorithm/n_base choice (see module
    docstring: out-invariance keeps packed results bitwise equal to dense),
    then attaches the requested ``out`` and its write-traffic prediction.

    ``op='solve'`` (``k`` = RHS count) enumerates the two solver methods —
    the factor pipeline inheriting the best packed-gram candidate's
    algorithm tunables, and matrix-free CG inheriting the best TN-product
    candidate's — scored by :func:`_solve_predict`.
    """
    k = n if k is None else k
    mach = machine_for(backend)
    if op == "solve":
        return _solve_candidates(
            m, n, k, batch=batch, dtype=dtype, out=out, backend=backend
        )
    syrk_bs, gemm_bs = _kernel_blocks(mach)
    base_tile = (
        (syrk_bs[1], syrk_bs[1]) if op == "ata" else (gemm_bs[1], gemm_bs[2])
    ) if mach.kernels else None
    nb, tile_w = (None, None)
    comm_scheds = [None]
    sched_tiling = {}
    pool = devices * max(row_devices, 1)
    if devices > 1:
        # the requested out feeds the tiling so packed plans snap tile_w
        # to the packed block grid (pure-slice retrieval, no repack)
        nb, tile_w = distributed_tiling(
            n, devices, out=out, packed_block=defaults.DEFAULT_PACKED_BLOCK
        )
    if op == "ata" and pool > 1:
        # the comm_schedule axis: every interleaving within the
        # per-device memory budget (CAPS's memory-vs-bandwidth rule);
        # if all bust it, the least-memory one via the argmin helper.
        # BFS-containing strings run — and are priced — on their own
        # pool-divisible grid (bfs_tiling): exact scatter chunking is
        # what keeps their root retrieval collective-free. A pure
        # row-sharded mesh (devices == 1, row_devices > 1) enumerates
        # only None + BFS strings — the tri-direct reduce-scatter works
        # over the merged pool, replacing the rowshard all-reduce, while
        # pure-'D' strings have no task axis to interleave and would
        # duplicate the psum plan.
        nb_b, w_b = bfs_tiling(n, pool, devices=devices, out=out)
        for cs in comm_schedule_candidates(nb if nb is not None else nb_b):
            bfs = bool(cs) and "B" in cs
            if devices == 1 and cs is not None and not bfs:
                continue
            sched_tiling[cs] = (nb_b, w_b) if bfs else (nb, tile_w)
        comm_scheds = [
            cs for cs, (nb_s, w_s) in sched_tiling.items()
            if nb_s is None or comm_memory_bytes(
                cs, nb_s, w_s, devices, row_devices,
                m=m, out=out, itemsize=_ITEMSIZE.get(dtype, 4),
            ) <= mach.device_memory_bytes
        ] or [choose_comm_schedule(
            nb_b, w_b, devices, row_devices, m=m, out=out,
            itemsize=_ITEMSIZE.get(dtype, 4), machine=mach, n=n,
        )]

    algos = ["dense", "strassen", "winograd"]
    n_bases = sorted({min(nb_c, max(m, n, k)) for nb_c in defaults.N_BASE_CANDIDATES})
    scored = []
    seen_degenerate = False
    for algo in algos:
        for n_base in n_bases if algo != "dense" else [defaults.DEFAULT_N_BASE]:
            lds = defaults.LEAF_DISPATCH_CANDIDATES
            if algo != "strassen":
                # fused slot tables encode the classical 7-term combos
                # only — winograd's chained within-level sums don't fit
                # (core.strassen raises), and dense has nothing to fuse.
                lds = tuple(ld for ld in lds if ld != "fused")
            if algo == "dense":
                lds = ("unrolled",)  # one classical dot — nothing to batch
            elif min(m, n, k) <= n_base:
                # recursion bottoms out immediately — all such cutoffs (and
                # both leaf dispatches: one leaf IS one call) are the same
                # dispatch; keep one canonical representative.
                if seen_degenerate:
                    continue
                seen_degenerate = True
                lds = ("unrolled",)
            for ld in lds:
                pred = predict_seconds(
                    op, algo, m, n, k, n_base,
                    batch=batch, dtype=dtype, out="dense", machine=mach,
                    blocks=base_tile, leaf_dispatch=ld,
                )
                scored.append((pred, algo, n_base, ld))
    scored.sort(key=lambda s: s[0])

    plans = []
    for pred, algo, n_base, ld in scored:
        variants = []
        for cs in comm_scheds:
            nb_s, w_s = sched_tiling.get(cs, (nb, tile_w))
            # BFS plans carry their own aligned packed grid: tile_w IS the
            # packed block, so the scattered chunks slice straight into
            # packed storage (see bfs_tiling)
            pb = (w_s if cs and "B" in cs and w_s is not None
                  else defaults.DEFAULT_PACKED_BLOCK)
            pred_out = predict_seconds(
                op, algo, m, n, k, n_base,
                batch=batch, dtype=dtype, out=out, machine=mach,
                blocks=base_tile, devices=devices, nb=nb_s, tile_w=w_s,
                leaf_dispatch=ld, row_devices=row_devices, comm_schedule=cs,
            )
            variants.append(
                Plan(
                    op=op, m=m, n=n, k=k, batch=batch, dtype=dtype,
                    backend=backend, out=out, algorithm=algo, n_base=n_base,
                    packed_block=pb,
                    use_kernels=mach.kernels,
                    syrk_blocks=syrk_bs, gemm_blocks=gemm_bs,
                    leaf_dispatch=ld,
                    devices=devices, nb=nb_s, tile_w=w_s,
                    row_devices=row_devices, comm_schedule=cs,
                    source="analytic", predicted_s=pred_out,
                )
            )
        # comm_schedule is ranked *within* each algorithm entry (the α-β
        # term is algorithm-invariant), preserving the out-invariant
        # algorithm/n_base ordering above.
        variants.sort(key=lambda p: p.predicted_s)
        plans.extend(variants)
    return plans


def _solve_candidates(
    m: int,
    n: int,
    r: int,
    *,
    batch: int = 0,
    dtype: str = "float32",
    out: str = "packed",
    backend: str = "cpu",
) -> list:
    """Scored op='solve' candidates, best predicted first.

    The factor candidate carries the best *packed-gram* candidate's
    algorithm tunables (the gram dominates its cost and the factor walk
    has no algorithm choice of its own); the CG candidate carries the best
    TN-product candidate's (its iterations are ``Aᵀ(A·p)`` pairs).
    """
    if batch:
        raise ValueError("op='solve' plans are unbatched (lstsq is 2-D); "
                         f"got batch={batch}")
    mach = machine_for(backend)
    syrk_bs, gemm_bs = _kernel_blocks(mach)
    base_tile = (syrk_bs[1], syrk_bs[1]) if mach.kernels else None
    common = dict(
        op="solve", m=m, n=n, k=r, batch=batch, dtype=dtype,
        backend=backend, out=out,
        packed_block=defaults.DEFAULT_PACKED_BLOCK,
        use_kernels=mach.kernels,
        syrk_blocks=syrk_bs, gemm_blocks=gemm_bs, source="analytic",
    )
    gram = candidates(
        "ata", m, n, batch=batch, dtype=dtype, out="packed", backend=backend
    )[0]
    gemm = candidates(
        "gemm_tn", m, n, r, batch=batch, dtype=dtype, out="dense",
        backend=backend,
    )[0]
    plans = []
    for method, donor in (("factor", gram), ("cg", gemm)):
        pred = _solve_predict(
            method, donor.algorithm, m, n, r, donor.n_base,
            dtype=dtype, packed_block=donor.packed_block, machine=mach,
            blocks=base_tile, leaf_dispatch=donor.leaf_dispatch,
        )
        plans.append(
            Plan(
                algorithm=donor.algorithm, n_base=donor.n_base,
                leaf_dispatch=donor.leaf_dispatch, method=method,
                predicted_s=pred, **common,
            )
        )
    plans.sort(key=lambda p: p.predicted_s)
    return plans


def analytic_plan(op, m, n, k=None, **kw) -> Plan:
    """The analytic argmin — what ``repro.tune.plan`` returns on cache miss."""
    return candidates(op, m, n, k, **kw)[0]


def default_plan(
    op: str,
    m: int,
    n: int,
    k: Optional[int] = None,
    *,
    batch: int = 0,
    dtype: str = "float32",
    out: str = "dense",
    backend: str = "cpu",
    devices: int = 1,
    row_devices: int = 1,
) -> Plan:
    """The pre-tune-subsystem hardcoded configuration, as a Plan.

    This is the baseline `bench_tune` measures the planner against, and the
    fallback consumers use when a caller pins *some* tunables manually.
    The distributed default keeps ``comm_schedule=None`` — the plain psum
    schedule the BFS/DFS planner is measured against.
    """
    k = n if k is None else k
    mach = machine_for(backend)
    nb, tile_w = (None, None)
    if devices > 1:
        nb, tile_w = distributed_tiling(
            n, devices, out=out, packed_block=defaults.DEFAULT_PACKED_BLOCK
        )
    return Plan(
        op=op, m=m, n=n, k=k, batch=batch, dtype=dtype, backend=backend,
        out=out, algorithm=defaults.DEFAULT_VARIANT,
        n_base=defaults.DEFAULT_N_BASE,
        packed_block=defaults.DEFAULT_PACKED_BLOCK,
        use_kernels=mach.kernels,
        syrk_blocks=defaults.SYRK_BLOCKS, gemm_blocks=defaults.GEMM_BLOCKS,
        leaf_dispatch=defaults.DEFAULT_LEAF_DISPATCH,
        method=defaults.DEFAULT_SOLVE_METHOD if op == "solve" else None,
        devices=devices, nb=nb, tile_w=tile_w, row_devices=row_devices,
        source="default",
    )


# ---------------------------------------------------------------------------
# distributed branch: lower-triangle tile search (ex core.distributed)
# ---------------------------------------------------------------------------


def distributed_tiling(
    n: int,
    p: int,
    target_tiles_per_dev: Optional[int] = None,
    *,
    out: str = "dense",
    packed_block: Optional[int] = None,
    n_base: Optional[int] = None,
):
    """Pick (nb, w): stripe count and stripe width (multiple of 8) for the
    block-cyclic lower-triangle schedule of ``ata_tile_parallel``.

    Wants: T = nb(nb+1)/2 ≥ p (enough tasks), small T mod p (balance),
    w reasonably large (MXU efficiency). Searches a small static range.

    With ``out='packed'``, stripe widths that **snap to the packed block
    grid** (``w == symmetric.default_block_size(n, packed_block)``) are
    preferred, and the exactly-aligned stripe count ``⌈n/bn⌉`` joins the
    candidate set: an aligned tiling makes the packed retrieval a pure
    slice of the psum'd tile stack (no repack pass). Two things outrank
    alignment, in order: **balance** (a misaligned zero-waste tiling beats
    an aligned one that idles devices) and **leaf Strassen depth** — a
    candidate whose stripes are wide enough for more recursion levels
    (``⌈log₂(w/n_base)⌉``, ``n_base`` defaulting to the static cutoff)
    keeps the 7/8-mult saving that narrow aligned stripes would forfeit,
    which is worth far more than the repack copy it costs. For
    ``out='dense'`` both new terms are order-compatible with the
    historical (waste, −w) search, so dense tilings are unchanged.
    """
    from repro.core.symmetric import default_block_size

    if target_tiles_per_dev is None:
        target_tiles_per_dev = defaults.TARGET_TILES_PER_DEVICE
    if n_base is None:
        n_base = defaults.DEFAULT_N_BASE
    bn_pack = None
    if out == "packed":
        bn_pack = default_block_size(
            n, packed_block or defaults.DEFAULT_PACKED_BLOCK
        )

    def strassen_depth(w: int) -> int:
        d = 0
        while w > n_base:
            w -= w // 2  # ceil-halving, as the recursion splits
            d += 1
        return d

    nb_min = max(1, math.ceil((math.sqrt(8 * p + 1) - 1) / 2))
    cand = list(range(nb_min, 4 * nb_min + 8))
    if bn_pack is not None:
        nb_aligned = -(-n // bn_pack)
        if nb_aligned >= nb_min and nb_aligned not in cand:
            cand.append(nb_aligned)
    best = None
    for nb in cand:
        t = nb * (nb + 1) // 2
        if t < p:
            continue
        per = -(-t // p)
        waste = per * p - t
        w = -(-n // nb)
        w = -(-w // 8) * 8  # round width up to sublane multiple
        # order: balance → leaf Strassen depth → (packed) grid alignment →
        # width. For out='dense', misaligned ≡ 0 and depth is monotone in
        # w, so the argmin coincides with the historical (waste·w², −w).
        misaligned = 1 if (bn_pack is not None and w != bn_pack) else 0
        score = (waste * w * w, -strassen_depth(w), misaligned, -w)
        if best is None or score < best[0]:
            best = (score, nb, w)
        if t >= target_tiles_per_dev * p and waste == 0 and not misaligned:
            break
    _, nb, w = best
    return nb, w


def bfs_tiling(
    n: int,
    pool: int,
    *,
    devices: Optional[int] = None,
    out: str = "packed",
    packed_block: Optional[int] = None,
    n_base: Optional[int] = None,
):
    """Pick (nb, w) for the BFS tri-direct reduce-scatter schedule.

    The scatter deals the reduced tri stack in ``T/pool``-tile chunks over
    the merged ``(task, row)`` device pool, so the stripe count must make
    ``T = nb(nb+1)/2`` **divisible by the pool** — then the chunking is
    exact, the packed retrieval is an identity slice, and the compiled
    program's only collective is the one chunk-sized reduce-scatter (an
    uneven ``T`` forces GSPMD to all-gather the whole stack at the root
    slice, which is exactly the cost the schedule exists to avoid).
    Among the divisible stripe counts the scoring mirrors
    :func:`distributed_tiling`: **subgroup balance** first (with
    ``devices`` given — the task-axis size — the representative
    single-``'B'`` assignment's makespan excess over ``ceil(T/devices)``,
    weighted ``w²`` like the waste term there; region-proportional device
    allotment rounds to integers, and a grid whose region sizes land near
    those multiples idles nobody), then leaf Strassen depth, then packed
    grid alignment (``w == default_block_size(n, w)`` — the dispatch
    passes the chosen width as the packed block so retrieval stays a pure
    slice), then width. A pool-divisible ``nb`` always exists within
    ``2·pool`` candidates (``nb = 2·pool−1`` gives ``T = pool·(2·pool−1)``).
    """
    from repro.core.symmetric import default_block_size

    if pool <= 1:
        return distributed_tiling(n, pool, out=out, packed_block=packed_block)
    if n_base is None:
        n_base = defaults.DEFAULT_N_BASE

    def strassen_depth(w: int) -> int:
        d = 0
        while w > n_base:
            w -= w // 2
            d += 1
        return d

    nb_min = max(1, math.ceil((math.sqrt(8 * pool + 1) - 1) / 2))
    best = None
    for nb in range(nb_min, nb_min + 2 * pool + 8):
        t = nb * (nb + 1) // 2
        if t < pool or t % pool:
            continue
        w = -(-n // nb)
        w = -(-w // 8) * 8
        grid = default_block_size(n, packed_block or w)
        misaligned = 1 if w != grid else 0
        extra = 0
        if devices is not None and devices > 1:
            extra = _bfs_makespan(nb, devices, "B") - (-(-t // devices))
        score = (extra * w * w, -strassen_depth(w), misaligned, -w, nb)
        if best is None or score < best[0]:
            best = (score, nb, w)
    _, nb, w = best
    return nb, w
