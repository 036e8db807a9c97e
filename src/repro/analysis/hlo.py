"""HLO-text analysis: collective-byte accounting for the roofline model.

``collective_bytes(hlo)`` parses compiled (post-SPMD) HLO and sums the
result-buffer bytes of every collective op, keyed by op kind. Bytes are
**per device** (the compiled module is the per-device SPMD program), which
matches the per-device flop/byte numbers from ``compiled.cost_analysis()``.

Handles plain and async (``-start``/``-done``) forms — only starts are
counted — and tuple-shaped results. A plain variadic collective's tuple
elements are all payload and sum; an async ``-start`` whose result is a
tuple follows HLO's ``(operand, result[, contexts…])`` convention, so only
element 1 — the actual transferred buffer — is counted (summing would
double-count the payload via its operand alias).
"""

from __future__ import annotations

import re
from typing import Dict

__all__ = [
    "collective_bytes",
    "collective_seconds",
    "compiled_text",
    "DTYPE_BYTES",
    "COLLECTIVE_KINDS",
]

COLLECTIVE_KINDS = (
    "all-reduce",
    "all-gather",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)

DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

# a shape like  bf16[128,1024]{1,0}  or  f32[] ; layout braces optional
_SHAPE_RE = re.compile(r"(\w+)\[([0-9,]*)\]")
# an HLO instruction: `%name = <result-type> <opcode>(...)`
_INSTR_RE = re.compile(
    r"=\s+(?P<result>\([^)]*\)|\S+)\s+"
    r"(?P<op>all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?P<suffix>-start|-done)?\b"
)


def compiled_text(fn, *abstract_args) -> str:
    """Lower + compile ``fn`` once and return the per-device HLO text —
    the input :func:`collective_bytes` parses.

    The single lowering path shared by every collective-accounting
    consumer (``repro.check``'s collective-budget rule,
    ``obs.metrics.record_collective_bytes`` call sites): callers that only need byte counts never hold the
    compiled executable, and nothing lowers the same function twice.
    ``fn`` may already be jitted (it is reused as-is) or a plain callable.
    """
    import jax

    jitted = fn if hasattr(fn, "lower") else jax.jit(fn)
    return jitted.lower(*abstract_args).compile().as_text()


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(type_str):
        if dtype not in DTYPE_BYTES:
            continue  # e.g. token[] / opaque
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * DTYPE_BYTES[dtype]
    return total


def _tuple_elems(type_str: str):
    """Top-level elements of a parenthesized tuple type, or ``None`` for a
    non-tuple result. Splits on commas outside ``[]``/``{}`` (shape dims and
    layouts carry commas of their own)."""
    if not (type_str.startswith("(") and type_str.endswith(")")):
        return None
    elems, depth, cur = [], 0, []
    for ch in type_str[1:-1]:
        if ch in "[{":
            depth += 1
        elif ch in "]}":
            depth -= 1
        elif ch == "," and depth == 0:
            elems.append("".join(cur))
            cur = []
            continue
        cur.append(ch)
    elems.append("".join(cur))
    return elems


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Per-device result bytes of every collective, keyed by kind."""
    out: Dict[str, int] = {k: 0 for k in COLLECTIVE_KINDS}
    for m in _INSTR_RE.finditer(hlo_text):
        if m.group("suffix") == "-done":
            continue  # counted at -start
        result = m.group("result")
        if m.group("suffix") == "-start":
            # async tuple result is (operand, result[, contexts…]): count
            # the transferred buffer only, not its aliased operand
            elems = _tuple_elems(result)
            if elems is not None and len(elems) >= 2:
                out[m.group("op")] += _shape_bytes(elems[1])
                continue
        out[m.group("op")] += _shape_bytes(result)
    return out


def collective_seconds(
    bytes_by_kind: Dict[str, int],
    link_bw: float = 50e9,
    scale: float = 1.0,
) -> float:
    """Time model: all-reduce moves ≈2× its buffer over the bottleneck link
    (ring reduce-scatter + all-gather); the others ≈1×. ``scale`` multiplies
    byte counts (used by the layer-differencing composition)."""
    factors = {
        "all-reduce": 2.0,
        "all-gather": 1.0,
        "reduce-scatter": 1.0,
        "all-to-all": 1.0,
        "collective-permute": 1.0,
    }
    t = 0.0
    for kind, b in bytes_by_kind.items():
        t += factors.get(kind, 1.0) * b * scale / link_bw
    return t
