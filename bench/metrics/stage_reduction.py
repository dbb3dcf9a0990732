"""Reduction of a profiler trace to the simulator's own spans and scopes:
device self time per cycle-body stage, and host time per program span.

The names are the program's (:data:`repro.DEVICE_SCOPES`,
:data:`repro.HOST_SPANS`); a program without them reads as all
``unscoped`` and no span.

Where the scope comes from. On a TPU v5e trace (JAX 0.9) each event of the
device plane's ``XLA Ops`` line points at an event metadata entry whose
stats include ``tf_op``: the HLO instruction's ``op_name`` metadata with the
op type after a colon, e.g. ``jit(_run_jit)/while/body/while/body/
ovl.route/ge:``. The scope is the innermost path component that names one,
bare or under a transformation (``vmap(ovl.select)``). Copies, ``while``
and other instructions the compiler adds carry no ``tf_op`` and count as
unscoped. ``jax.profiler.ProfileData`` does not expose metadata stats, so
the ``.xplane.pb`` is read with the ``XSpace`` protobuf schema installed
with TensorFlow (``tsl/profiler/protobuf/xplane_pb2.py``), loaded from its
file alone; times are floored to whole nanoseconds as ``ProfileData`` gives
them, so :func:`plain` of a read equals ``trace_reduction.read_xplane``.

A long trace is cut short: on a paper-scale query the TPU's trace buffers
hold the first ~6.3M op events (about the first 5 s of the device loop)
and one ``Trace Buffers Dropped`` event on the ``XLA TraceMe`` line stands
for the rest (the plane's ``dropped_traces`` stat counts the buffers).
That time is busy but has no op to name, so it is counted apart
(``DROPPED``), and the split of the recorded part stands for it.

Plain data: ``{"device": {plane: {line: [[start_ns, dur_ns, name, op_name],
...]}}, "host": [[start_ns, dur_ns, name], ...]}``, host events kept when
at least ``trace_reduction.HOST_MIN_NS`` long or a program span.
"""
from __future__ import annotations

import collections
import importlib.util
import os
import re

import numpy as np

from bench.metrics import trace_reduction as tr

try:
    from repro import DEVICE_SCOPES, HOST_SPANS
except ImportError:  # the harness also runs a program from before the spans
    DEVICE_SCOPES, HOST_SPANS = (), ()

OPS_LINE = "XLA Ops"
OP_NAME_STAT = "tf_op"
#: The event a TPU trace puts in place of what its full device buffers lost.
DROPPED_EVENT = "Trace Buffers Dropped"
UNSCOPED = "unscoped"
#: Recorded device time that no event of the ops line covers.
NO_OP = "(no op event)"
#: Device time the trace lost (``DROPPED_EVENT`` and nothing else).
DROPPED = "(dropped)"


def _xplane_pb2():
    """The ``XSpace`` message module, or ``None`` where it is not installed.
    Loaded from its file: importing the ``tensorflow`` package would run all
    of TensorFlow's start-up."""
    spec = importlib.util.find_spec("tensorflow")
    if spec is None or not spec.submodule_search_locations:
        return None
    path = os.path.join(spec.submodule_search_locations[0], "tsl", "profiler",
                        "protobuf", "xplane_pb2.py")
    if not os.path.exists(path):
        return None
    mod_spec = importlib.util.spec_from_file_location("_bench_xplane_pb2", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def read_xplane_ops(path: str) -> dict | None:
    """Plain data of an ``.xplane.pb``, each device event with its
    ``op_name`` (``""`` where it has none); ``None`` without the schema."""
    pb2 = _xplane_pb2()
    if pb2 is None:
        return None
    with open(path, "rb") as f:
        space = pb2.XSpace.FromString(f.read())
    device: dict = {}
    host: list = []
    for plane in space.planes:
        is_device = plane.name.startswith("/device:")
        if not is_device and not plane.name.startswith("/host:"):
            continue
        names = {k: m.name for k, m in plane.event_metadata.items()}
        op_names: dict = {}
        if is_device:
            stat = [k for k, m in plane.stat_metadata.items()
                    if m.name == OP_NAME_STAT]
            for k, m in plane.event_metadata.items():
                for s in m.stats:
                    if s.metadata_id in stat:
                        op_names[k] = s.str_value.rsplit(":", 1)[0]
        lines: dict = {}
        for line in plane.lines:
            t0_ps = line.timestamp_ns * 1000
            for e in line.events:
                start = (t0_ps + e.offset_ps) // 1000
                dur = e.duration_ps // 1000
                name = names[e.metadata_id]
                if is_device:
                    lines.setdefault(line.name, []).append(
                        [start, dur, name, op_names.get(e.metadata_id, "")])
                elif dur >= tr.HOST_MIN_NS or name in HOST_SPANS:
                    host.append([start, dur, name])
        if is_device and any(lines.values()):
            device[plane.name] = lines
    return {"device": device, "host": host}


def plain(trace: dict) -> dict:
    """The data ``trace_reduction.reduce_trace`` takes, from a read of
    :func:`read_xplane_ops`: what ``trace_reduction.read_xplane`` reads."""
    return {"device": {p: {ln: [e[:3] for e in evs]
                           for ln, evs in lines.items()}
                       for p, lines in trace["device"].items()},
            "host": [e for e in trace["host"] if e[1] >= tr.HOST_MIN_NS]}


def scope_of(op_name: str) -> str:
    """The innermost device scope named in ``op_name``, or ``UNSCOPED``."""
    for part in reversed(op_name.split("/")):
        bare = re.sub(r"^\w+\((.*)\)$", r"\1", part)
        if bare in DEVICE_SCOPES:
            return bare
    return UNSCOPED


def self_segments(events) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Each event's self time on one line: ``(starts, ends, owner)`` of
    disjoint segments, each owned by the latest-started event covering it.
    An enclosing event (a ``while``) owns only what none of its body's
    events covers."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][0], -events[i][1]))
    starts, ends, owner = [], [], []
    stack: list = []  # (end, index), latest started on top
    cursor = 0.0

    def emit(upto):
        nonlocal cursor
        if stack and upto > cursor:
            starts.append(cursor)
            ends.append(upto)
            owner.append(stack[-1][1])
        cursor = max(cursor, upto)

    for i in order:
        s, d = events[i][0], events[i][1]
        while stack and stack[-1][0] <= s:
            emit(stack[-1][0])
            stack.pop()
        emit(s)
        stack.append((s + d, i))
        cursor = max(cursor, s)
    while stack:
        emit(stack[-1][0])
        stack.pop()
    return (np.asarray(starts, np.float64), np.asarray(ends, np.float64),
            np.asarray(owner, np.int64))


def _ops_line(lines: dict) -> list:
    return lines.get(OPS_LINE) or max(lines.values(), key=len)


def _spans(trace: dict, span_name: str) -> list:
    return sorted((s, s + d) for s, d, name in trace["host"]
                  if name == span_name)


def _union(lines: dict, *, dropped: bool = True) -> np.ndarray:
    """Union of the events of a device plane, with or without the event
    that stands for lost trace buffers."""
    return tr.merge([[e[0], e[0] + e[1]] for evs in lines.values()
                     for e in evs if dropped or e[2] != DROPPED_EVENT])


def _inside(union: np.ndarray, inside: np.ndarray) -> float:
    return sum(tr.overlap(union, a, b) for a, b in inside)


def _busy_planes(trace: dict, inside: np.ndarray) -> dict:
    """``{plane: union of its events}`` of the device planes busy inside the
    disjoint intervals ``inside``."""
    unions = {p: _union(lines) for p, lines in trace["device"].items()}
    return {p: u for p, u in unions.items() if _inside(u, inside) > 0}


def stage_times(trace: dict, span_name: str, top: int = 10) -> dict | None:
    """Device busy time inside the spans named ``span_name``, split by the
    scope of the ops-line event that owns each instant (its self time).

    ``stages`` maps every scope, ``UNSCOPED``, ``NO_OP`` and ``DROPPED`` to
    seconds and sums to ``busy_s``; ``recorded_s`` is ``busy_s`` less
    ``DROPPED``. ``unscoped_ops`` names the unscoped events with the most
    self time. Averaged over the devices that ran an operation in the
    spans; ``None`` where there are none."""
    spans = _spans(trace, span_name)
    if not spans:
        return None
    inside = tr.merge(spans)
    used = _busy_planes(trace, inside)
    if not used:
        return None
    stages: collections.Counter = collections.Counter()
    unscoped_ops: collections.Counter = collections.Counter()
    busy_ns = 0.0
    for plane, union in used.items():
        busy_ns += _inside(union, inside)
        recorded = _inside(_union(trace["device"][plane], dropped=False),
                           inside)
        stages[DROPPED] += _inside(union, inside) - recorded
        events = _ops_line(trace["device"][plane])
        seg_a, seg_b, owner = self_segments(events)
        length = np.zeros(len(seg_a))
        for a, b in inside:
            length += np.clip(seg_b, a, b) - np.clip(seg_a, a, b)
        per_event = np.bincount(owner, weights=length, minlength=len(events))
        for i in np.flatnonzero(per_event):
            scope = scope_of(events[i][3])
            stages[scope] += per_event[i]
            if scope == UNSCOPED:
                unscoped_ops[tr._label(events[i][2])] += per_event[i]
    n = len(used)
    stages[NO_OP] = busy_ns - sum(stages.values())
    return {
        "busy_s": busy_ns * 1e-9 / n,
        "recorded_s": (busy_ns - stages[DROPPED]) * 1e-9 / n,
        "stages": {k: stages[k] * 1e-9 / n
                   for k in (*DEVICE_SCOPES, UNSCOPED, NO_OP, DROPPED)},
        "unscoped_ops": [[k, v * 1e-9 / n]
                         for k, v in unscoped_ops.most_common(top)],
    }


def host_spans(trace: dict, span_name: str) -> dict:
    """Per program span (``repro.*``): its time inside the spans named
    ``span_name`` (``s``), the device busy time inside it (``device_s``,
    averaged over the devices busy in the spans) and how often it ran."""
    spans = _spans(trace, span_name)
    if not spans:
        return {}
    inside = tr.merge(spans)
    unions = list(_busy_planes(trace, inside).values())
    out: dict = {}
    for s, d, name in trace["host"]:
        if name not in HOST_SPANS:
            continue
        for a, b in inside:
            lo, hi = max(s, a), min(s + d, b)
            if hi <= lo:
                continue
            rec = out.setdefault(name, {"s": 0.0, "device_s": 0.0, "count": 0})
            rec["s"] += (hi - lo) * 1e-9
            rec["device_s"] += (sum(tr.overlap(u, lo, hi) for u in unions)
                                / max(1, len(unions)) * 1e-9)
            rec["count"] += 1
    return out
