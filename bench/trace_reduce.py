"""Reduction of a profiler trace to the numbers the per-layer metrics read.

A trace is read with ``jax.profiler.ProfileData`` (the ``.xplane.pb``
file that ``jax.profiler.start_trace`` writes).  From it:

  * device operations: the events on the device plane's ``XLA Ops``
    line, as [start, end) intervals in nanoseconds;
  * host spans: the harness's own ``TraceAnnotation`` events
    (``dispatch``, ``block``, ``evaluate``) on the host plane;
  * busy time: the union of the device intervals inside the traced
    window, which is the span from the first harness span's start to the
    last one's end;
  * idle gaps: the holes in that union, each named by the host span that
    covers most of it;
  * kernel time: the summed durations of the device events whose
    operation name (the HLO instruction's name, before " = ") contains a
    given fragment, and where the kernel's operands and results live:
    the event text is the HLO instruction, whose layouts mark on-chip
    memory with ``S(1)``.

An operation that encloses others on the line (a ``while`` loop around
its body) counts toward busy time but is left out of the per-operation
breakdown, which lists the innermost operations.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[int, int]
HOST_SPANS = ("dispatch", "block", "evaluate")


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def read_events(path: str) -> List[Tuple[str, str, str, int, int]]:
    """Every event of the trace as (plane, line, name, start_ns, end_ns)."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                start = int(ev.start_ns)
                out.append((plane.name, line.name, ev.name, start,
                            start + int(ev.duration_ns)))
    return out


def device_ops(events, device: str = "/device:TPU:0",
               line: str = "XLA Ops") -> List[Tuple[str, int, int]]:
    return [(n, s, e) for p, ln, n, s, e in events
            if p == device and ln == line]


def host_spans(events, names: Sequence[str] = HOST_SPANS
               ) -> List[Tuple[str, int, int]]:
    return sorted(((n, s, e) for p, ln, n, s, e in events
                   if p.startswith("/host") and n in names),
                  key=lambda t: t[1])


def op_name(text: str) -> str:
    """The HLO instruction's name: ``%fusion.4 = f32[..] fusion(..)`` ->
    ``fusion.4``."""
    return text.split(" = ", 1)[0].lstrip("%")


_SHAPE = re.compile(r"([a-z]+[0-9]*)\[([0-9,]*)\]")


def operand_shapes(text: str) -> List[Tuple[str, Tuple[int, ...]]]:
    """(dtype, dims) of each operand of a custom call's HLO text."""
    operands = text.partition(" custom-call(")[2].split(
        "), custom_call_target", 1)[0]
    return [(t, tuple(int(d) for d in dims.split(",") if d))
            for t, dims in _SHAPE.findall(operands)]


def is_kernel(text: str) -> bool:
    """A Pallas kernel: an XLA custom call to ``tpu_custom_call``."""
    return 'custom_call_target="tpu_custom_call"' in text


def innermost(ops: List[Tuple[str, int, int]]) -> List[Tuple[str, int, int]]:
    """The operations that enclose no other operation of the line."""
    ops = sorted(ops, key=lambda t: (t[1], -t[2]))
    return [op for i, op in enumerate(ops)
            if i + 1 == len(ops) or ops[i + 1][1] >= op[2]]


def union(intervals: Iterable[Interval], lo: int, hi: int) -> List[Interval]:
    """Merged, clipped to [lo, hi)."""
    merged: List[List[int]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def holes(busy: List[Interval], lo: int, hi: int) -> List[Interval]:
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def name_gap(gap: Interval, spans: List[Tuple[str, int, int]]) -> str:
    """The host span that covers most of the gap ("other" if none)."""
    best, cover = "other", 0
    for n, s, e in spans:
        c = min(e, gap[1]) - max(s, gap[0])
        if c > cover:
            best, cover = n, c
    return best


class Summary:
    """The reduced trace of one traced window.  ``ops`` overrides the
    device operations (the CPU test passes the host threads' XLA ops)."""

    def __init__(self, events, ops=None):
        self.spans = host_spans(events)
        if not self.spans:
            raise ValueError("no harness spans in the trace")
        self.lo = self.spans[0][1]
        self.hi = max(e for _, _, e in self.spans)
        ops = device_ops(events) if ops is None else ops
        self.ops = [(n, s, e) for n, s, e in ops
                    if e > self.lo and s < self.hi]
        self.leaves = innermost(self.ops)
        self.busy = union(((s, e) for _, s, e in self.ops), self.lo, self.hi)
        self.window_s = (self.hi - self.lo) * 1e-9
        self.busy_s = sum(e - s for s, e in self.busy) * 1e-9

    def kernel_seconds(self, pick) -> Optional[float]:
        """Summed device seconds of the Pallas kernel events whose HLO
        text ``pick`` accepts (None when there are none)."""
        ns = [e - s for n, s, e in self.leaves if is_kernel(n) and pick(n)]
        return sum(ns) * 1e-9 if ns else None

    def top_ops(self, k: int = 10) -> List[List]:
        by: Dict[str, int] = {}
        for n, s, e in self.leaves:
            n = op_name(n)
            by[n] = by.get(n, 0) + (e - s)
        top = sorted(by.items(), key=lambda t: -t[1])[:k]
        return [[n, ns * 1e-9] for n, ns in top]

    def top_gaps(self, k: int = 10) -> List[List]:
        gaps = sorted(holes(self.busy, self.lo, self.hi),
                      key=lambda g: g[0] - g[1])[:k]
        return [[name_gap(g, self.spans), (g[1] - g[0]) * 1e-9]
                for g in gaps]
