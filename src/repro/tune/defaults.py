"""The single home of every ATA-stack tunable constant.

Before the tune subsystem these literals were scattered across the repo
(`DEFAULT_N_BASE` in `core/strassen`, `DEFAULT_BLOCKS` in each Pallas
kernel, `shampoo_n_base` in `configs/base`, ad-hoc `N_BASE = 256` in every
benchmark). They now live here, in one dependency-free module, and reach
the call sites through :func:`repro.tune.plan` — an explicit kwarg at a
call site is a *manual override*, not a tuning decision.

This module must stay import-light (no jax, no repro imports): it is the
one `repro.tune` module that low layers (`core`, `kernels`) may import
without creating a cycle, via the lazy `repro.tune.__init__`.
"""

from __future__ import annotations

__all__ = [
    "DEFAULT_N_BASE",
    "DEFAULT_PACKED_BLOCK",
    "SYRK_BLOCKS",
    "GEMM_BLOCKS",
    "DEFAULT_VARIANT",
    "DEFAULT_LEAF_DISPATCH",
    "LEAF_DISPATCH_CANDIDATES",
    "DEFAULT_SOLVE_METHOD",
    "CG_MAX_ITERS",
    "CG_TOL",
    "TARGET_TILES_PER_DEVICE",
    "MAX_COMM_SCHEDULE_LEVELS",
    "N_BASE_CANDIDATES",
    "SYRK_BLOCK_CANDIDATES",
    "GEMM_BLOCK_CANDIDATES",
]

# Recursion cutoff of the Strassen/ATA trace-time recursion. 512 keeps every
# base-case matmul dimension a multiple of the 128-wide MXU while allowing
# 3-5 Strassen levels on the gram shapes of the framework (d_model/d_ff up
# to 33792).
DEFAULT_N_BASE = 512

# Block size of the packed (SymmetricMatrix) output grid.
DEFAULT_PACKED_BLOCK = 128

# Pallas syrk kernel blocks (bm, bn): contraction block, output block.
SYRK_BLOCKS = (512, 256)

# Pallas gemm_tn kernel blocks (bm, bn, bk): contraction, C-row, C-col.
GEMM_BLOCKS = (512, 256, 256)

# Strassen variant for the off-diagonal products when nothing chose one:
# 'strassen' is the paper-faithful schedule (7 mults / 18 adds).
DEFAULT_VARIANT = "strassen"

# How the recursion's leaf products reach the hardware when nothing chose:
# 'unrolled' emits one dot/syrk per leaf (the historical trace-time form);
# 'batched' runs the whole tree level-synchronously — every leaf in one
# batched call. Both are bitwise-equal; the planner prices launch overhead
# against combine traffic and picks per shape.
DEFAULT_LEAF_DISPATCH = "unrolled"

# Leaf-dispatch axis the planner enumerates (dense and degenerate
# candidates keep 'unrolled' alone, see `cost.candidates`).
LEAF_DISPATCH_CANDIDATES = ("unrolled", "batched")

# Normal-equations solver (repro.solve) when nothing chose a method:
# 'factor' = planned packed gram → packed Cholesky → two substitutions;
# 'cg' = matrix-free CG on the gram operator. The planner's op='solve'
# entry prices both and picks per shape/RHS count; this is the manual-pin
# fallback only.
DEFAULT_SOLVE_METHOD = "factor"

# CG budget: iteration cap (also capped by n — exact termination in exact
# arithmetic) and relative residual tolerance. The cost model prices CG
# with this same cap, so prediction and dispatch agree.
CG_MAX_ITERS = 64
CG_TOL = 1e-6

# Distributed tile schedule: how many lower-triangle tiles the tiling
# search aims to give each device of the task axis (balance ↔ tile width).
TARGET_TILES_PER_DEVICE = 2

# BFS/DFS interleaving search depth: the planner enumerates every string
# over {'B','D'} up to this many recursion levels (≤ the tile-tree depth)
# plus None (the plain-psum schedule). 3 levels = 15 candidates — the α-β
# model separates them well before the strings stop mattering (below tile
# granularity the tags are no-ops).
MAX_COMM_SCHEDULE_LEVELS = 3

# Candidate grids swept by the analytic model and the measured autotuner.
N_BASE_CANDIDATES = (128, 256, 512, 1024)
SYRK_BLOCK_CANDIDATES = ((256, 128), (512, 128), (512, 256), (1024, 256))
GEMM_BLOCK_CANDIDATES = (
    (256, 128, 128),
    (512, 256, 256),
    (512, 512, 256),
    (1024, 256, 256),
)
