"""Pallas TPU kernels for the paper's compute hot-spots.

* ``syrk``   — lower-triangular ``alpha·AᵀA`` (ATA base case; the paper's
  symmetric saving at the tile level).
* ``gemm_tn``— TN matmul ``alpha·AᵀB`` (FastStrassen base case; Aᵀ never
  materialized).
* ``potrf``  — diagonal-block Cholesky (packed-solver base case: one SPD
  ``bn×bn`` tile → its lower factor).
* ``trsm``   — triangular panel solve ``X·Lᵀ = B`` / ``X·L = B`` (the
  blocked-Cholesky panel op and the substitution engine of
  ``repro.solve``).

Three package-wide contracts, stated here once and honored by ALL FOUR
kernels (``repro.kernels.{syrk, gemm_tn, potrf, trsm}``) and their public
wrappers (``repro.kernels.ops``):

* **Interpret mode** (``ops.interpret_default()``): ``interpret=None`` at a
  wrapper resolves to ``jax.default_backend() != "tpu"`` — compiled Mosaic
  on a real TPU, Pallas interpret mode (the kernel body executed in Python
  by XLA, for correctness work) everywhere else. It is a *backend* property,
  not a debug flag: pass ``interpret=`` explicitly only to force one mode.

* **Batched grid** (leading dim = leaf batch): an optional leading operand
  dimension becomes the leading (``"parallel"``) grid dimension — the whole
  batch is ONE kernel launch, never a vmap-of-pallas (machine-checked: the
  ``no-vmap-of-pallas`` rule of ``repro.check`` flags any traced
  ``pallas_call`` with nonempty ``grid_mapping.vmapped_dims``; launch
  counts are policed by ``launch-budget``). The batched-leaf
  recursion (``Plan.leaf_dispatch='batched'``) relies on this: it flattens
  its leaf stack (and any operand batch) into exactly that one leading dim,
  so all ``7^L`` Strassen leaves / all ``4^L`` diagonal leaves land in a
  single launch. The packed Cholesky walk (``repro.solve.cholesky``) leans
  on the same contract: each block column factors its whole panel stack —
  batch dims × panel rows — as ONE ``trsm`` launch, and a batched stat
  stack's diagonal tiles as ONE ``potrf`` launch.

* **Coefficient tables** (fused-operand leaves): the fused leaf launches
  (``ops.gemm_tn_fused``, ``ops.syrk_gather`` — the
  ``Plan.leaf_dispatch='fused'`` engines) take their operands in the
  block-major leaf-grid layout of ``core.strassen._to_blocks`` plus
  per-leaf int32 ``(rows, cols, sign)`` slot tables
  (``core.strassen._slot_tables``), passed as scalar-prefetch operands.
  The kernel PROLOGUE gathers each slot block through the tables in its
  index maps and combines them as the recursion's balanced ± add tree
  before the MXU dot; the epilogue writes one product per leaf into the
  level's decode stack. No operand-combination stack is ever materialized
  in HBM — the combine traffic the batched dispatch pays simply does not
  exist (machine-checked: the ``no-operand-stacks`` rule of ``repro.check``
  flags any 7-multiple leaf-operand stack in a fused-dispatch jaxpr). The blocked dot inside (chunk shapes, contraction order, f32
  VMEM accumulation, flush cast) is identical to the unbatched kernels',
  which is what keeps all three leaf dispatches bitwise-equal for f32/f64
  operands (sub-f32 operands forfeit bitwise: the in-kernel combine feeds
  the dot inside one XLA computation, where float normalization may keep
  bf16 adds at f32 precision — more accurate than the trace-time gather,
  which rounds at the pallas input boundary); sign-0
  (dead) slots contribute an exact ±0 instead of being dropped, so the
  fused launch is value-equal to the trace-time gather (it may flip the
  sign of a zero — invisible to ``==``). A leaf whose A slots or B slots
  are all dead skips its dot and returns exact zeros (the distributed tile
  stack's dummy slots; Strassen's tables hold none). Same kernel body for
  Mosaic and interpret mode, like everything else here.

``ops`` holds the jit'd public wrappers; ``ref`` holds the pure-jnp oracles
used by the kernel test sweeps.
"""

from repro.kernels import ops, ref
from repro.kernels.ops import gemm_tn, potrf, syrk, trsm

__all__ = ["ops", "ref", "gemm_tn", "syrk", "potrf", "trsm"]
