"""From a profiler trace (``.xplane.pb``) to device intervals and host spans.

What the trace holds, as JAX 0.9 writes it on a TPU v5e:

* one plane per chip, ``/device:TPU:<i>``, whose line ``XLA Ops`` has one
  event per executed HLO instruction, named by the instruction's text
  (``%syrk_dual.7 = f32[…] custom-call(…), custom_call_target=…``);
* the host plane ``/host:CPU``, whose ``python`` line carries the
  benchmark's own ``TraceAnnotation`` spans (``bench.window``,
  ``bench.call``).

Both are on one clock, in nanoseconds from the start of the profile.

:func:`load` reads the file; :func:`index_hlo` reads the compiled program's
text for what the trace leaves out (each instruction's named-scope path,
and which fusions hold a matrix product); :func:`reduce` joins the two over
the measured window: per-op device intervals, the busy union, the top ops
and the idle gaps labelled by what the host was doing.
"""

from __future__ import annotations

import bisect
import contextlib
import dataclasses
import glob
import os
import re
import shutil
import tempfile
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

__all__ = ["Op", "Span", "Trace", "HloIndex", "Reduced", "record", "remove",
           "load", "index_hlo", "reduce", "union_seconds", "breakdown"]

_INSTR_NAME = re.compile(r"^%([\w.\-]+)\s*=")
_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")
_CALLS = re.compile(r"calls=%([\w.\-]+)")
_HLO_LINE = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_COMPUTATION = re.compile(r"^\s*(?:ENTRY\s+)?%([\w.\-]+)\s.*\{\s*$")


@dataclasses.dataclass(frozen=True)
class Op:
    """One device op: ``[start, end)`` in ns on the profile's clock."""

    device: int
    start: float
    end: float
    name: str          # HLO instruction name, e.g. ``fusion.135``
    opcode: str        # e.g. ``custom-call``, ``fusion``, ``copy``
    calls: str         # called computation of a fusion, or ""


@dataclasses.dataclass(frozen=True)
class Span:
    start: float
    end: float
    name: str


@dataclasses.dataclass
class Trace:
    ops: List[Op]
    spans: List[Span]      # the benchmark's host spans (name starts "bench.")
    devices: int


@contextlib.contextmanager
def record():
    """Profile the body, Python's own calls left out; yields a list that
    holds the ``.xplane.pb`` path and then its directory once the body has
    ended. The directory is made under ``TMPDIR``; :func:`remove` deletes
    it."""
    import jax

    d = tempfile.mkdtemp(prefix="bench-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0          # no span per Python call
    out: List[str] = []
    jax.profiler.start_trace(d, profiler_options=opts)
    try:
        yield out
    finally:
        jax.profiler.stop_trace()
        out.extend(glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                             recursive=True))
        out.append(d)


def remove(recorded: List[str]) -> None:
    """Delete what :func:`record` wrote."""
    if recorded:
        shutil.rmtree(recorded[-1], ignore_errors=True)


def load(path: str) -> Trace:
    """Device ops and the benchmark's host spans of one ``.xplane.pb``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops, spans, devices = [], [], 0
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = int(plane.name.rsplit(":", 1)[1])
            devices += 1
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for e in line.events:
                    text = e.name
                    m = _INSTR_NAME.match(text)
                    if m is None:
                        continue
                    op = _OPCODE.search(text, m.end())
                    calls = _CALLS.search(text)
                    ops.append(Op(dev, e.start_ns, e.start_ns + e.duration_ns,
                                  m.group(1), op.group(1) if op else "",
                                  calls.group(1) if calls else ""))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        spans.append(Span(e.start_ns,
                                          e.start_ns + e.duration_ns, e.name))
    ops.sort(key=lambda o: o.start)
    spans.sort(key=lambda s: s.start)
    return Trace(ops=ops, spans=spans, devices=devices)


@dataclasses.dataclass
class HloIndex:
    """What the compiled program's text says about its instructions."""

    op_names: Dict[str, str]      # instruction -> named-scope path
    mxu_computations: set         # computations holding a dot/convolution


def index_hlo(hlo_text: str) -> HloIndex:
    op_names, mxu, current = {}, set(), None
    for line in hlo_text.splitlines():
        c = _COMPUTATION.match(line)
        if c is not None and "=" not in line.split("{", 1)[0]:
            current = c.group(1)
            continue
        m = _HLO_LINE.match(line)
        if m is None:
            continue
        n = _OP_NAME.search(line)
        if n is not None:
            op_names[m.group(1)] = n.group(1)
        if current and (" convolution(" in line or " dot(" in line):
            mxu.add(current)
    return HloIndex(op_names=op_names, mxu_computations=mxu)


def union_seconds(intervals: List[Tuple[float, float]]) -> float:
    """Length, in seconds, of the union of ``[start, end)`` ns intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total * 1e-9


@dataclasses.dataclass
class Reduced:
    """The measured window of one trace, reduced.

    ``ops`` are the device ops that started inside the window, clipped to
    its end; ``busy_s`` is the union of their intervals averaged over the
    chips; ``spans`` are the benchmark's host spans inside the window.
    """

    ops: List[Op]
    spans: List[Span]
    window: Tuple[float, float]
    devices: int
    busy_s: float
    hlo: Optional[HloIndex]

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def seconds(self, ops) -> float:
        """Summed device seconds of ``ops`` (not a union)."""
        return sum(o.end - o.start for o in ops) * 1e-9

    def op_name(self, op: Op) -> str:
        return self.hlo.op_names.get(op.name, "") if self.hlo else ""

    def is_mxu(self, op: Op) -> bool:
        """An XLA matrix product: a dot, a convolution, or a fusion of one."""
        if op.opcode in ("dot", "convolution"):
            return True
        return bool(self.hlo and op.opcode == "fusion"
                    and op.calls in self.hlo.mxu_computations)


def _window_of(trace: Trace) -> Tuple[float, float]:
    for s in trace.spans:
        if s.name == "bench.window":
            return (s.start, s.end)
    if not trace.ops:
        raise ValueError("trace has no device ops and no bench.window span")
    return (trace.ops[0].start, max(o.end for o in trace.ops))


def reduce(trace: Trace, hlo_text: Optional[str] = None,
           window: Optional[Tuple[float, float]] = None) -> Reduced:
    w0, w1 = window or _window_of(trace)
    ops = [dataclasses.replace(o, end=min(o.end, w1))
           for o in trace.ops if w0 <= o.start < w1]
    per_dev = defaultdict(list)
    for o in ops:
        per_dev[o.device].append((o.start, o.end))
    devices = max(trace.devices, 1)
    busy = sum(union_seconds(v) for v in per_dev.values()) / devices
    spans = [s for s in trace.spans
             if s.end > w0 and s.start < w1 and s.name != "bench.window"]
    return Reduced(ops=ops, spans=spans, window=(w0, w1), devices=devices,
                   busy_s=busy, hlo=index_hlo(hlo_text) if hlo_text else None)


def _scope(op_name: str) -> str:
    """The last named scope of an op's path (``solve.cholesky``), or ""."""
    parts = [p for p in op_name.split("/") if p and not p.startswith("jit(")]
    scopes = [p for p in parts[:-1] if "." in p]
    return scopes[-1] if scopes else ""


def op_label(red: Reduced, op: Op, kernels: Dict[str, str]) -> str:
    """A stable label for grouping: the kernel name of a Pallas launch,
    else ``<opcode>@<last scope>``."""
    if op.name in kernels:
        return kernels[op.name]
    scope = _scope(red.op_name(op))
    return f"{op.opcode}@{scope}" if scope else op.opcode


def breakdown(red: Reduced, kernels: Dict[str, str], top: int = 10) -> dict:
    """The top device ops by time, and the idle gaps of device 0 summed by
    the innermost benchmark span the host was in at the gap's middle."""
    by_label = defaultdict(float)
    for o in red.ops:
        by_label[op_label(red, o, kernels)] += (o.end - o.start) * 1e-9
    device_ops = sorted(by_label.items(), key=lambda kv: -kv[1])[:top]

    first = min((o.device for o in red.ops), default=0)
    dev0 = sorted((o.start, o.end) for o in red.ops if o.device == first)
    gaps, cursor = [], red.window[0]
    for s, e in dev0:
        if s > cursor:
            gaps.append((cursor, s))
        cursor = max(cursor, e)
    if red.window[1] > cursor:
        gaps.append((cursor, red.window[1]))
    # the benchmark's spans inside the window do not nest: the one that
    # started last before a gap's middle is the only one that can hold it
    starts = [s.start for s in red.spans]
    by_host = defaultdict(float)
    for g0, g1 in gaps:
        mid = 0.5 * (g0 + g1)
        i = bisect.bisect_right(starts, mid) - 1
        label = (red.spans[i].name if i >= 0 and mid < red.spans[i].end
                 else "host.outside_bench_spans")
        by_host[label] += (g1 - g0) * 1e-9
    idle = sorted(by_host.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in device_ops],
            "idle_gaps": [[k, v] for k, v in idle]}
