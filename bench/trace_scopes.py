"""Device time per phase of the MMFL round, and where the device idles,
from a profiler trace of the round engine.

    python3 bench/trace_scopes.py <trace dir or .xplane.pb>

prints one JSON line for the traced window, which runs from the first to
the last host span of the trace: the harness's (``dispatch``, ``block``,
``evaluate``; ``bench/run.py``) or, in an operator's own trace, the
engine's (``mmfl.rollout``, whose ``rounds`` stat counts the rounds, and
``mmfl.evaluate``).

The engine names each phase of its round with a ``jax.named_scope``
(``mmfl.stats``, ``mmfl.sample``, ``mmfl.local_train``, ``mmfl.aggregate``,
and ``mmfl.evaluate`` for the test accuracy; ``repro.core.engine``).  XLA
keeps the name stack in each HLO instruction's ``metadata.op_name``, and
the profiler stores each program's optimised HLO (an ``HloProto``) in the
trace's ``/host:metadata`` plane, one event metadata per program id.  A
device operation of the ``XLA Ops`` line is the HLO instruction of the same
name in the program whose execution holds it (the ``XLA Modules`` line
names each execution ``<module>(<program id>)``), so

  * its phase is the innermost ``mmfl.*`` component of that instruction's
    name stack (of the first stack that has one, where XLA joined several
    with ``;``); an instruction with none is unscoped;
  * the round program is every program with an operation of a round phase
    (``stats``, ``sample``, ``local_train``, ``aggregate``); its unscoped
    operations are the scan's plumbing and the copies XLA inserts;
  * the idle time of the window (the holes in the union of the device
    operations, as ``trace_reduce`` takes them, so that the two parts sum
    to ``device_idle_share``) splits into the part inside a program's
    execution and the part between executions.

Operations are taken innermost, as ``trace_reduce`` takes them.
``jax.profiler.ProfileData`` does not expose event metadata, so the
``/host:metadata`` plane is read from the protobuf wire format here.  A
program loaded from JAX's persistent compilation cache carries the names
of the code that compiled it: the cache's key leaves op metadata out.
"""
from __future__ import annotations

import argparse
import json
import os
import re
from typing import Dict, Iterator, List, Optional, Tuple

import trace_reduce as tr

ROUND_PHASES = ("stats", "sample", "local_train", "aggregate")
PHASES = ROUND_PHASES + ("evaluate",)
_SCOPE = re.compile(r"mmfl\.(" + "|".join(PHASES) + r")\b")


def phase_of(op_name: str) -> Optional[str]:
    """The innermost ``mmfl.*`` phase of a name stack, or None."""
    for stack in op_name.split(";"):
        found = _SCOPE.findall(stack)
        if found:
            return found[-1]
    return None


# ---- protobuf wire format: just what the metadata plane needs ------------
def _varint(buf: memoryview, i: int) -> Tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf: memoryview) -> Iterator[Tuple[int, object]]:
    """(field number, value) of a message: an int for varint and fixed
    fields, a memoryview for length-delimited ones."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 1:
            value, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire == 5:
            value, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"protobuf wire type {wire} not handled")
        yield field, value


def _first(buf: memoryview, field: int, default=None):
    for f, v in _fields(buf):
        if f == field:
            return v
    return default


def _hlo_op_names(hlo_proto: memoryview) -> Dict[str, str]:
    """{instruction name: metadata.op_name} over every computation of an
    ``HloProto`` (hlo_module 1 > computations 3 > instructions 2 > name 1,
    metadata 7 > op_name 2)."""
    out: Dict[str, str] = {}
    module = _first(hlo_proto, 1)
    if module is None:
        return out
    for f, comp in _fields(module):
        if f != 3:
            continue
        for g, ins in _fields(comp):
            if g != 2:
                continue
            name = op_name = None
            for h, v in _fields(ins):
                if h == 1:
                    name = bytes(v).decode()
                elif h == 7:
                    op_name = bytes(_first(v, 2, b"")).decode()
            if name is not None and op_name:
                out[name] = op_name
    return out


def program_op_names(path: str) -> Dict[int, Dict[str, str]]:
    """{program id: {instruction name: op_name}} from the ``HloProto``s of
    the trace's ``/host:metadata`` plane (XSpace planes 1; XPlane name 2,
    event_metadata 4, stat_metadata 5; XEventMetadata id 1, stats 5;
    XStat metadata_id 1, bytes_value 6; XStatMetadata id 1, name 2)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out: Dict[int, Dict[str, str]] = {}
    for field, plane in _fields(space):
        if field != 1 or _first(plane, 2, b"") != b"/host:metadata":
            continue
        stat_names, events = {}, []
        for g, entry in _fields(plane):
            if g == 5:
                md = _first(entry, 2)
                stat_names[_first(md, 1, 0)] = bytes(_first(md, 2, b""))
            elif g == 4:
                events.append(_first(entry, 2))
        for em in events:
            pid = _first(em, 1, 0)
            for g, stat in _fields(em):
                if g != 5:
                    continue
                if stat_names.get(_first(stat, 1, 0)) == b"Hlo Proto":
                    out[pid] = _hlo_op_names(_first(stat, 6, b""))
    return out


# ---- the reduction --------------------------------------------------------
_PROGRAM = re.compile(r"\((\d+)\)$")


def with_programs(ops: List[tuple], modules: List[tuple]) -> List[tuple]:
    """Each operation (name, start_ns, end_ns) with the program id of the
    ``XLA Modules`` event that holds it, ``jit_roll(<id>)``: None outside
    every one."""
    modules = sorted(modules, key=lambda m: m[1])
    out, j = [], 0
    for name, s, e in sorted(ops, key=lambda o: o[1]):
        while j < len(modules) and modules[j][2] <= s:
            j += 1
        pid = None
        if j < len(modules) and modules[j][1] <= s:
            m = _PROGRAM.search(modules[j][0])
            pid = int(m.group(1)) if m else None
        out.append((name, s, e, pid))
    return out


def overlap(a: List[tr.Interval], b: List[tr.Interval]) -> int:
    """Length of the intersection of two sorted lists of disjoint
    intervals."""
    total = i = j = 0
    while i < len(a) and j < len(b):
        total += max(0, min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


class Phases:
    """Per-phase device seconds of the window [lo, hi) (ns on the trace's
    clock), and the split of its idle time."""

    def __init__(self, ops: List[tuple], modules: List[tuple],
                 op_names: Dict[int, Dict[str, str]], lo: int, hi: int):
        ops = [op for op in with_programs(ops, modules)
               if op[2] > lo and op[1] < hi]
        self.seconds: Dict[str, float] = {p: 0.0 for p in PHASES}
        per_program: Dict[Optional[int], List[Tuple[Optional[str], int]]] = {}
        for name, s, e, pid in tr.innermost(ops):
            phase = phase_of(op_names.get(pid, {}).get(tr.op_name(name), ""))
            per_program.setdefault(pid, []).append((phase, e - s))
        self.round_programs = {
            pid for pid, leaves in per_program.items()
            if any(p in ROUND_PHASES for p, _ in leaves)}
        self.round_busy_s = self.unscoped_s = 0.0
        for pid, leaves in per_program.items():
            for phase, ns in leaves:
                if phase is not None:
                    self.seconds[phase] += ns * 1e-9
                if pid in self.round_programs:
                    self.round_busy_s += ns * 1e-9
                    if phase is None:
                        self.unscoped_s += ns * 1e-9
        busy = tr.union(((s, e) for _, s, e, _ in ops), lo, hi)
        idle = tr.holes(busy, lo, hi)
        in_program = tr.union(((s, e) for _, s, e in modules), lo, hi)
        self.window_s = (hi - lo) * 1e-9
        self.idle_s = sum(e - s for s, e in idle) * 1e-9
        self.idle_in_program_s = overlap(idle, in_program) * 1e-9
        self.idle_between_programs_s = self.idle_s - self.idle_in_program_s
        self.has_modules = bool(modules)


ENGINE_SPANS = ("mmfl.rollout", "mmfl.evaluate")


def summarize(events, op_names: Dict[int, Dict[str, str]],
              rounds: int) -> Dict[str, Optional[float]]:
    """The numbers of the traced window, by the names the benchmark's
    metrics would give them; None where the trace holds nothing to read
    (no scoped round program, no ``mmfl.evaluate`` operation, no
    ``XLA Modules`` line)."""
    spans = tr.host_spans(events, tr.HOST_SPANS + ENGINE_SPANS)
    if not spans:
        raise ValueError("no harness or engine spans in the trace")
    lo, hi = spans[0][1], max(e for _, _, e in spans)
    ph = Phases(tr.device_ops(events),
                tr.device_ops(events, line="XLA Modules"), op_names, lo, hi)
    chunks = sum(1 for n, _, _ in spans if n == "mmfl.evaluate")
    scoped = bool(ph.round_programs) and rounds > 0
    share = lambda s: 100.0 * s / ph.window_s if ph.has_modules else None
    out: Dict[str, Optional[float]] = {
        "window_s": ph.window_s, "rounds": rounds, "chunks": chunks}
    for p in ROUND_PHASES:
        out[f"round.{p}.device_ms"] = (1e3 * ph.seconds[p] / rounds
                                       if scoped else None)
    out["round.unscoped_share"] = (100.0 * ph.unscoped_s / ph.round_busy_s
                                   if scoped else None)
    out["evaluate.device_ms"] = (1e3 * ph.seconds["evaluate"] / chunks
                                 if chunks and ph.seconds["evaluate"] > 0
                                 else None)
    out["device_idle_share"] = 100.0 * ph.idle_s / ph.window_s
    out["idle.between_programs_share"] = share(ph.idle_between_programs_s)
    out["idle.in_program_share"] = share(ph.idle_in_program_s)
    return out


def read(path: str) -> Dict[str, Optional[float]]:
    """``summarize`` of one ``.xplane.pb`` file: its events as
    ``trace_reduce.read_events`` gives them, the rounds the
    ``mmfl.rollout`` spans ran, and its programs' HLO op names."""
    from jax.profiler import ProfileData
    events, rounds = [], 0
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                start = int(ev.start_ns)
                events.append((plane.name, line.name, ev.name, start,
                               start + int(ev.duration_ns)))
                if ev.name == "mmfl.rollout" and plane.name.startswith(
                        "/host"):
                    rounds += int(dict(ev.stats).get("rounds", 0))
    return summarize(events, program_op_names(path), rounds)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("trace", help="a .xplane.pb file, or a directory that "
                    "jax.profiler.start_trace wrote")
    args = ap.parse_args(argv)
    path = (tr.find_xplane(args.trace) if os.path.isdir(args.trace)
            else args.trace)
    print(json.dumps(read(path)))


if __name__ == "__main__":
    main()
