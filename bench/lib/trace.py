"""Reduction of a profiler trace (``.xplane.pb``) to what the metrics read.

``reduce(path, spans)`` reads the trace with ``jax.profiler.ProfileData``
and returns a :class:`Trace`:

* device operations of the chip's plane (``/device:TPU:<n>``, line
  ``XLA Ops``), each under its HLO instruction's name (a Pallas call is
  named after its kernel's entry point: ``ternary_matmul_actq_pallas.26``);
* executions of each compiled program (line ``XLA Modules``), keyed by
  the jitted function's name (``jit_step(...)`` -> ``step``);
* the busy intervals (the union of the device operations) inside the
  traced window, the idle gaps between them, and for each gap the host
  span of the client that was open over most of it.

The traced window is the extent of the client's ``bench_window``
annotation. All times are in seconds.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import re
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW_SPAN = "bench_window"
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_MODULE_NAME = re.compile(r"^(?:jit_)?([^(\s]+)")
# ops that hold other ops (a scanned layer loop): busy, but no op of their own
_CONTAINERS = ("while", "conditional", "call")


@dataclasses.dataclass
class Op:
    name: str  # the HLO instruction's name ("ternary_matmul_actq_pallas.26")
    start: float
    end: float

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Trace:
    window: Tuple[float, float]
    ops: List[Op]  # device operations inside the window
    programs: Dict[str, List[Tuple[float, float]]]  # name -> executions
    busy: List[Tuple[float, float]]  # merged device-busy intervals
    gaps: List[Tuple[str, float]]  # (host span over the gap, seconds)

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    @property
    def busy_s(self) -> float:
        return sum(b - a for a, b in self.busy)

    def program_time(self, name: str) -> Tuple[int, float]:
        """(executions, summed device seconds) of one jitted function."""
        runs = self.programs.get(name, [])
        return len(runs), sum(b - a for a, b in runs)

    def kernel_time(self, patterns: Sequence[str],
                    within: Optional[str] = None) -> Tuple[int, float]:
        """(calls, summed device seconds) of the operations whose name
        starts with any of ``patterns``; with ``within`` only those that
        ran inside an execution of that program."""
        runs = self.programs.get(within, []) if within else None
        calls, total = 0, 0.0
        for op in self.ops:
            if not op.name.startswith(tuple(patterns)):
                continue
            if runs is not None and not _inside(op, runs):
                continue
            calls += 1
            total += op.dur
        return calls, total

    def top_ops(self, n: int = 10) -> List[list]:
        """The operations that took most device time (layer loops, which
        hold the others, left out)."""
        by: Dict[str, float] = {}
        for op in self.ops:
            if op.name.split(".")[0] in _CONTAINERS:
                continue
            by[op.name] = by.get(op.name, 0.0) + op.dur
        return [[k, v] for k, v in sorted(by.items(), key=lambda kv: -kv[1])[:n]]

    def top_gaps(self, n: int = 10) -> List[list]:
        by: Dict[str, float] = {}
        for label, s in self.gaps:
            by[label] = by.get(label, 0.0) + s
        return [[k, v] for k, v in sorted(by.items(), key=lambda kv: -kv[1])[:n]]


def _inside(op: Op, runs: List[Tuple[float, float]]) -> bool:
    """Whether ``op`` lies inside one of the sorted executions ``runs``."""
    i = bisect.bisect_right(runs, (op.start, float("inf"))) - 1
    return i >= 0 and op.end <= runs[i][1]


def merge(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def label_gaps(busy, window, spans) -> List[Tuple[str, float]]:
    """Each idle gap of the window, named after the host span that covers
    most of it (``idle`` where none does)."""
    lo, hi = window
    edges = [lo] + [x for ab in busy for x in ab] + [hi]
    spans = sorted(spans, key=lambda s: s[1])
    ends = [s[2] for s in spans]
    # spans of one thread do not overlap, so their ends rise with their
    # starts and a gap's candidates start at the first span ending in it
    gaps = []
    for a, b in zip(edges[0::2], edges[1::2]):
        if b <= a:
            continue
        best, cover = "idle", 0.0
        j = bisect.bisect_right(ends, a)
        while j < len(spans) and spans[j][1] < b:
            name, s0, s1 = spans[j]
            c = min(b, s1) - max(a, s0)
            if c > cover:
                best, cover = name, c
            j += 1
        gaps.append((best, b - a))
    return gaps


def find(directory: Path) -> Path:
    files = sorted(glob.glob(str(Path(directory) / "**" / "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return Path(files[-1])


def op_name(event_name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def reduce(path: Path, span_names: Sequence[str], device: int = 0) -> Trace:
    """Read one trace file; ``span_names`` are the client's host spans."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    ns = 1e-9
    window = None
    spans: List[Tuple[str, float, float]] = []
    ops: List[Op] = []
    programs: Dict[str, List[Tuple[float, float]]] = {}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m and int(m.group(2)) == device:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    for ev in line.events:
                        ops.append(Op(op_name(ev.name), ev.start_ns * ns,
                                      ev.end_ns * ns))
                elif line.name == MODULES_LINE:
                    for ev in line.events:
                        mm = _MODULE_NAME.match(ev.name)
                        key = mm.group(1) if mm else ev.name
                        programs.setdefault(key, []).append(
                            (ev.start_ns * ns, ev.end_ns * ns))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW_SPAN:
                        window = (ev.start_ns * ns, ev.end_ns * ns)
                    elif ev.name in span_names:
                        spans.append((ev.name, ev.start_ns * ns,
                                      ev.end_ns * ns))
    if window is None:
        raise ValueError(f"{path}: no {WINDOW_SPAN!r} annotation")
    lo, hi = window
    ops = [op for op in ops if op.start >= lo and op.end <= hi]
    programs = {k: sorted((a, b) for a, b in v if a >= lo and b <= hi)
                for k, v in programs.items()}
    busy = merge([(op.start, op.end) for op in ops])
    return Trace(window=window, ops=ops, programs=programs, busy=busy,
                 gaps=label_gaps(busy, window, spans))
