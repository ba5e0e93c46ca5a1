"""Where the decode step's device time goes, and what the engine's host
phases cost, read from the trace file that ``trace.reduce`` reads.

The program names its work: leaf regions (``jax.named_scope``) inside the
jitted steps, and a host span ``engine.<phase>``
(``jax.profiler.TraceAnnotation``) around each phase of
``Engine.run_iteration``. ``jax.profiler.ProfileData`` gives a device
operation's times but not the scope it was written in; the file holds
that too, as the ``tf_op`` stat (the HLO op_name path, e.g.
``jit(step)/while/body/closed_call/kv_write/dynamic_update_slice``) of
each device operation's event metadata. ``read`` wire-decodes the file
with ``google.protobuf`` against the few fields of the XPlane schema it
needs, on the same clock as ``trace.reduce``.

The leaf regions are the common ones (``COMMON_REGIONS``: the engine's,
the KV cache's and attention's, which every model has) and those that the
run's model module adds (its ``LEAF_REGIONS``, ``bench/models/``). An
operation belongs to the innermost leaf region on its op_name path; an
operation on no leaf region's path (the layer scan's own slicing and
restacking, copies XLA inserts with empty metadata) is ``unattributed``.
Times are in seconds.
"""

from __future__ import annotations

import bisect
import dataclasses
import functools
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

from bench.lib import trace
from bench.lib.spec import ROOT

COMMON_REGIONS = ("embed", "kv_write", "attention", "lm_head", "sample",
                  "bookkeeping")
UNATTRIBUTED = "unattributed"
PHASE_PREFIX = "engine."
# phases in which the host feeds or waits for the device: the rest is
# host work that keeps the chip idle between iterations
DEVICE_PHASES = ("dispatch", "sync")
# where bench/run.py's runs leave their trace (``run_cell``'s work_dir)
RUN_TRACE_DIR = ROOT / ".bench_work" / "trace"


@dataclasses.dataclass
class Scoped:
    window: Tuple[float, float]
    ops: List[Tuple[str, float, float, str]]  # (name, start, end, region)
    programs: Dict[str, List[Tuple[float, float]]]  # name -> executions
    spans: List[Tuple[str, float, float]]  # host spans inside the window

    def phases(self) -> List[Tuple[str, float, float]]:
        return [(n[len(PHASE_PREFIX):], a, b) for n, a, b in self.spans
                if n.startswith(PHASE_PREFIX)]


def region(op_name: str, leaves: Sequence[str] = ()) -> str:
    """The innermost leaf region on an op_name path: one of
    ``COMMON_REGIONS`` or of the model's ``leaves``."""
    for part in reversed(op_name.split("/")):
        if part in COMMON_REGIONS or part in leaves:
            return part
    return UNATTRIBUTED


@functools.cache
def _space_class():
    """The message class of an XSpace, cut to the fields read here
    (``tsl/profiler/protobuf/xplane.proto``; a map is a repeated entry)."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory

    F = descriptor_pb2.FieldDescriptorProto
    fdp = descriptor_pb2.FileDescriptorProto(
        name="bench_xplane_subset.proto", package="bx", syntax="proto3")
    scalar = {"i": F.TYPE_INT64, "u": F.TYPE_UINT64, "s": F.TYPE_STRING}

    def message(name, *fields):
        m = fdp.message_type.add(name=name)
        for fname, number, kind in fields:
            f = m.field.add(name=fname, number=number)
            if kind in scalar:
                f.type, f.label = scalar[kind], F.LABEL_OPTIONAL
            else:  # "*Msg": repeated, ".Msg": one
                f.type, f.type_name = F.TYPE_MESSAGE, ".bx." + kind[1:]
                f.label = (F.LABEL_REPEATED if kind[0] == "*"
                           else F.LABEL_OPTIONAL)

    message("Stat", ("metadata_id", 1, "i"), ("str_value", 5, "s"),
            ("ref_value", 7, "u"))
    message("EventMetadata", ("id", 1, "i"), ("name", 2, "s"),
            ("stats", 5, "*Stat"))
    message("StatMetadata", ("id", 1, "i"), ("name", 2, "s"))
    message("EventMetadataEntry", ("key", 1, "i"),
            ("value", 2, ".EventMetadata"))
    message("StatMetadataEntry", ("key", 1, "i"),
            ("value", 2, ".StatMetadata"))
    message("Event", ("metadata_id", 1, "i"), ("offset_ps", 2, "i"),
            ("duration_ps", 3, "i"))
    message("Line", ("name", 2, "s"), ("timestamp_ns", 3, "i"),
            ("events", 4, "*Event"))
    message("Plane", ("name", 2, "s"), ("lines", 3, "*Line"),
            ("event_metadata", 4, "*EventMetadataEntry"),
            ("stat_metadata", 5, "*StatMetadataEntry"))
    message("Space", ("planes", 1, "*Plane"))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fdp)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bx.Space"))


def _times(line, ev) -> Tuple[float, float]:
    """An event's start and end, in whole nanoseconds as
    ``ProfileData`` gives them, in seconds."""
    start_ns = (line.timestamp_ns * 1000 + ev.offset_ps) // 1000
    return start_ns * 1e-9, (start_ns + ev.duration_ps // 1000) * 1e-9


def read(path: Path, span_names: Sequence[str] = (),
         leaves: Sequence[str] = ()) -> Scoped:
    """Device operations of chip 0 with their regions (the common ones
    and the model's ``leaves``), program executions, and the host spans
    ``engine.*`` plus ``span_names``, inside the traced window
    (``trace.WINDOW_SPAN``)."""
    space = _space_class()()
    space.ParseFromString(Path(path).read_bytes())
    window = None
    ops: List[Tuple[str, float, float, str]] = []
    programs: Dict[str, List[Tuple[float, float]]] = {}
    spans: List[Tuple[str, float, float]] = []
    for plane in space.planes:
        meta = {e.key: e.value for e in plane.event_metadata}
        m = trace.DEVICE_PLANE.match(plane.name)
        if m and int(m.group(2)) == 0:
            stat_names = {e.key: e.value.name for e in plane.stat_metadata}
            tf_op = next((k for k, v in stat_names.items() if v == "tf_op"),
                         None)
            regions: Dict[int, str] = {}
            for key, em in meta.items():
                path_ = ""
                for st in em.stats:
                    if st.metadata_id == tf_op:
                        path_ = (st.str_value if st.str_value
                                 else stat_names.get(st.ref_value, ""))
                regions[key] = region(path_.rsplit(":", 1)[0], leaves)
            for line in plane.lines:
                if line.name == trace.OPS_LINE:
                    for ev in line.events:
                        a, b = _times(line, ev)
                        ops.append((trace.op_name(meta[ev.metadata_id].name),
                                    a, b, regions[ev.metadata_id]))
                elif line.name == trace.MODULES_LINE:
                    for ev in line.events:
                        name = meta[ev.metadata_id].name
                        mm = trace._MODULE_NAME.match(name)
                        programs.setdefault(mm.group(1) if mm else name,
                                            []).append(_times(line, ev))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    name = meta[ev.metadata_id].name.split("#", 1)[0]
                    if name == trace.WINDOW_SPAN:
                        window = _times(line, ev)
                    elif (name.startswith(PHASE_PREFIX)
                          or name in span_names):
                        spans.append((name, *_times(line, ev)))
    if window is None:
        raise ValueError(f"{path}: no {trace.WINDOW_SPAN!r} annotation")
    lo, hi = window
    return Scoped(
        window=window,
        ops=[o for o in ops if o[1] >= lo and o[2] <= hi],
        programs={k: sorted(r for r in v if r[0] >= lo and r[1] <= hi)
                  for k, v in programs.items()},
        spans=sorted((s for s in spans if s[1] >= lo and s[2] <= hi),
                     key=lambda s: s[1]))


def step_split(sc: Scoped, program: str = "step") -> Optional[Dict[str, float]]:
    """Device seconds per execution of ``program``: one entry per leaf
    region that ran, ``unattributed``, and ``idle`` (time inside an
    execution in which no operation ran); with ``step``, the execution
    itself, and ``executions``. Operations are counted as
    ``Trace.top_ops`` counts them, without the layer loops that hold the
    others. None where no operation of ``program`` carries a leaf region
    (a program built without them)."""
    runs = sc.programs.get(program, [])
    if not runs:
        return None
    starts = [a for a, _ in runs]
    by: Dict[str, float] = {}
    inside: List[List[Tuple[float, float]]] = [[] for _ in runs]
    for name, a, b, reg in sc.ops:
        if name.split(".")[0] in trace._CONTAINERS:
            continue
        i = bisect.bisect_right(starts, a) - 1
        if i < 0 or b > runs[i][1]:
            continue
        by[reg] = by.get(reg, 0.0) + (b - a)
        inside[i].append((a, b))
    if set(by) <= {UNATTRIBUTED}:
        return None
    n = len(runs)
    step_s = sum(b - a for a, b in runs)
    busy = sum(b - a for ivs in inside for a, b in trace.merge(ivs))
    out = {k: v / n for k, v in by.items()}
    out["idle"] = (step_s - busy) / n
    out["step"] = step_s / n
    out["executions"] = n
    return out


def phase_seconds(sc: Scoped) -> Optional[Dict[str, float]]:
    """Host seconds per traced iteration in each ``engine.*`` phase, the
    iterations counted by their ``sync`` phase; None without phases."""
    phases = sc.phases()
    n = sum(name == "sync" for name, _, _ in phases)
    if not n:
        return None
    out: Dict[str, float] = {}
    for name, a, b in phases:
        out[name] = out.get(name, 0.0) + (b - a) / n
    return out


def label_gaps(busy, window, spans) -> List[Tuple[str, float]]:
    """Each idle gap of the window, named after the innermost (shortest)
    span that covers more than half of it; where none does, after the
    span that covers most of it (``idle`` where none covers any). Spans
    may nest (``engine.*`` phases inside the client's
    ``run_iteration``); for spans that do not, this is
    ``trace.label_gaps``."""
    lo, hi = window
    edges = [lo] + [x for ab in busy for x in ab] + [hi]
    spans = sorted(spans, key=lambda s: s[1])
    starts = [s[1] for s in spans]
    longest = max((s[2] - s[1] for s in spans), default=0.0)
    gaps = []
    for a, b in zip(edges[0::2], edges[1::2]):
        if b <= a:
            continue
        best, key = "idle", (False, 0.0, 0.0)
        j = bisect.bisect_left(starts, a - longest)
        while j < len(spans) and spans[j][1] < b:
            name, s0, s1 = spans[j]
            c = min(b, s1) - max(a, s0)
            # a span over most of the gap beats one over less; among
            # those, the innermost; among the rest, the widest cover
            k = (2 * c > b - a, s0 - s1 if 2 * c > b - a else c, s0 - s1)
            if c > 0 and k > key:
                best, key = name, k
            j += 1
        gaps.append((best, b - a))
    return gaps


def gaps_by_phase(sc: Scoped, n: int = 10) -> List[list]:
    """Idle seconds of the window by the span over each gap, most first
    (the ``idle_gaps`` breakdown with the engine's phases in it)."""
    busy = trace.merge([(a, b) for _, a, b, _ in sc.ops])
    by: Dict[str, float] = {}
    for label, s in label_gaps(busy, sc.window, sc.spans):
        by[label] = by.get(label, 0.0) + s
    return [[k, v] for k, v in sorted(by.items(), key=lambda kv: -kv[1])[:n]]


@functools.lru_cache(maxsize=1)
def _read_run_file(path: str, mtime_ns: int,
                   leaves: Tuple[str, ...]) -> Scoped:
    from bench.lib.harness import SPAN_NAMES

    return read(Path(path), SPAN_NAMES, leaves)


def of_run(run) -> Optional[Scoped]:
    """The scoped reading of a traced run of ``bench/run.py``, from the
    trace file its reduction read (found where ``run_cell`` leaves it by
    default, and matched to the run by the traced window), with the leaf
    regions of the run's model; None where there is none."""
    if run.trace is None:
        return None
    try:
        path = trace.find(RUN_TRACE_DIR)
    except FileNotFoundError:
        return None
    sc = _read_run_file(str(path), path.stat().st_mtime_ns,
                        tuple(run.model.LEAF_REGIONS))
    if any(abs(x - y) > 1e-6 for x, y in zip(sc.window, run.trace.window)):
        return None  # a trace left by another run
    return sc
