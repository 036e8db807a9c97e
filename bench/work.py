"""The work of each Pallas launch, read from the compiled program's HLO.

Every ``tpu_custom_call`` instruction in ``compiled.as_text()`` names its
kernel in its ``op_name`` metadata (``…/syrk_packed/pallas_call``) and
states its operand and result shapes. From those shapes alone this module
counts the plain work of the launch:

* ``flops``: the multiply-adds the product needs, two operations each. A
  Gram (``syrk_*``) of an ``(m, n)`` operand counts ``m·n·(n+1)``, the
  symmetric half with its diagonal; a ``gemm_tn`` of ``(m, n)ᵀ·(m, k)``
  counts ``2·m·n·k``. Tiles a kernel computes beyond that (the upper half
  of a diagonal tile) are not counted, so a share of the peak never reads
  high.
* ``bytes``: each distinct operand read once and the result written once;
  for the gathering kernels, the gathered slabs only.

The roofline time of a launch is the larger of ``flops / peak FLOP/s`` and
``bytes / peak bytes/s`` (``bench.peaks``). The peak is the chip's bf16
MXU rate for every dtype: a float32 product at ``HIGHEST`` takes six bf16
passes, so such a kernel reads at most about a sixth, and no change of
precision or tiling can make the count read over 100%.
"""

from __future__ import annotations

import dataclasses
import math
import re
from typing import Dict, List, Optional, Tuple

__all__ = ["Launch", "parse_launches", "launch_work", "roofline_seconds"]

_ITEMSIZE = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s8": 1,
             "u8": 1, "pred": 1, "f64": 8, "s64": 8, "u64": 8}

_SHAPE = re.compile(r"\b(f32|bf16|f16|s32|u32|s8|u8|pred|f64|s64|u64)\[([\d,]*)\]")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(.*?)\s+custom-call\(([^)]*)\)")
_OPERANDS = re.compile(r"operand_layout_constraints=\{(.*?)\}, (?:frontend_attributes|metadata|backend_config|custom_call)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


@dataclasses.dataclass(frozen=True)
class Launch:
    """One ``tpu_custom_call`` of a compiled program."""

    name: str                       # HLO instruction name, e.g. syrk_dual.72
    kernel: str                     # kernel name, e.g. syrk_dual
    op_name: str                    # metadata path with the named scopes
    result: Tuple[Tuple[str, Tuple[int, ...]], ...]
    operands: Tuple[Tuple[str, Tuple[int, ...]], ...]
    operand_refs: Tuple[str, ...]   # HLO values passed, in order


def _shapes(text: str):
    return tuple((dt, tuple(int(d) for d in dims.split(",") if d))
                 for dt, dims in _SHAPE.findall(text))


def _kernel_of(op_name: str, instr: str) -> str:
    parts = op_name.split("/")
    if len(parts) >= 2 and parts[-1] == "pallas_call":
        return parts[-2]
    return instr.rsplit(".", 1)[0]


def parse_launches(hlo_text: str) -> List[Launch]:
    """Every ``tpu_custom_call`` in ``hlo_text``, in program order."""
    out = []
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        m = _INSTR.match(line)
        if m is None:
            raise ValueError(f"unparsed tpu_custom_call line: {line[:200]}")
        name, result_text, refs = m.groups()
        ops = _OPERANDS.search(line)
        if ops is None:
            raise ValueError(f"no operand shapes on {name}")
        op_name = _OP_NAME.search(line)
        op_name = op_name.group(1) if op_name else ""
        out.append(Launch(
            name=name,
            kernel=_kernel_of(op_name, name),
            op_name=op_name,
            result=_shapes(result_text),
            operands=_shapes(ops.group(1)),
            operand_refs=tuple(r.strip().lstrip("%") for r in refs.split(",")
                               if r.strip()),
        ))
    return out


def _nbytes(shape) -> int:
    dt, dims = shape
    return _ITEMSIZE[dt] * math.prod(dims)


def _distinct_operand_bytes(launch: Launch, start: int = 0) -> int:
    seen, total = set(), 0
    for ref, shape in list(zip(launch.operand_refs, launch.operands))[start:]:
        if ref in seen:
            continue
        seen.add(ref)
        total += _nbytes(shape)
    return total


def launch_work(launch: Launch) -> Optional[Dict[str, float]]:
    """``{"flops", "bytes"}`` of one launch, or ``None`` for a kernel whose
    work this module does not count."""
    k = launch.kernel
    out_bytes = sum(_nbytes(s) for s in launch.result)
    if k in ("syrk_packed", "syrk_dual"):
        _, a = launch.operands[0]
        batch, m, n = math.prod(a[:-2]), a[-2], a[-1]
        return {"flops": float(batch * m * n * (n + 1)),
                "bytes": float(_distinct_operand_bytes(launch) + out_bytes)}
    if k == "syrk_gather":
        # operands: rows (S,), cols (S,), the block grid twice
        (_, rows), (dt, grid) = launch.operands[0], launch.operands[2]
        s = rows[0]
        lead = grid[2:-2]                   # the optional batch dim
        batch, m, n = math.prod(lead), grid[-2], grid[-1]
        slab = _ITEMSIZE[dt] * m * n
        return {"flops": float(s * batch * m * n * (n + 1)),
                "bytes": float(s * batch * slab + out_bytes)}
    if k == "gemm_tn":
        (_, a), (_, b) = launch.operands[0], launch.operands[1]
        batch, m, n, kk = math.prod(a[:-2]), a[-2], a[-1], b[-1]
        return {"flops": float(2 * batch * m * n * kk),
                "bytes": float(_distinct_operand_bytes(launch) + out_bytes)}
    if k == "gemm_tn_fused":
        # operands: six slot tables, then w copies of A's block grid and
        # w copies of B's; each output leaf reads w slabs of each
        grids = launch.operands[6:]
        w = len(grids) // 2
        (dt, ga), (_, gb) = grids[0], grids[w]
        lead = ga[3:-2]
        batch, m, n, kk = math.prod(lead), ga[-2], ga[-1], gb[-1]
        leaves = launch.result[0][1][0]
        slabs = w * _ITEMSIZE[dt] * m * (n + kk)
        return {"flops": float(2 * leaves * batch * m * n * kk),
                "bytes": float(leaves * batch * slabs + out_bytes)}
    return None


def roofline_seconds(work: Dict[str, float], peaks: Dict[str, float]):
    """(least seconds the chip could take, the bound that sets it)."""
    compute = work["flops"] / peaks["flops"]
    memory = work["bytes"] / peaks["hbm_bytes_s"]
    return (compute, "compute") if compute >= memory else (memory, "memory")
