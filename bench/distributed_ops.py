"""Which device ops the four-chip readers (``bench/metrics/dist.*``) count.

* A collective: an op whose innermost program scope is ``distributed.psum``
  or ``distributed.psum_scatter`` (the schedule's reductions of the packed
  tile stack), or whose opcode is a collective (``all-reduce``,
  ``reduce-scatter``, ``all-gather``, ``collective-permute``, each also as
  its ``-start`` / ``-done`` pair).
* A control-flow op (``conditional``, ``while``, ``call``) is no work of its
  own: its span holds the ops of its body, which the trace shows on their
  own, so device op time leaves it out.
"""

from __future__ import annotations

from bench.trace import _scope

__all__ = ["is_collective", "is_control_flow"]

_SCOPES = frozenset({"distributed.psum", "distributed.psum_scatter"})
_OPCODES = frozenset(
    f"{op}{suffix}"
    for op in ("all-reduce", "reduce-scatter", "all-gather", "collective-permute")
    for suffix in ("", "-start", "-done"))
_CONTROL_FLOW = frozenset({"conditional", "while", "call"})


def is_collective(red, op) -> bool:
    return op.opcode in _OPCODES or _scope(red.op_name(op)) in _SCOPES


def is_control_flow(op) -> bool:
    return op.opcode in _CONTROL_FLOW
