"""Reduction of a profiler trace to device busy time, idle share, device time
inside host spans, and the longest idle gaps with what the host was doing.

Nothing here depends on the names of the program's operations: a device's
busy time is the union of the intervals of every event on its plane, and the
spans are the benchmark's own annotations, found by the name it gave them.
A trace is first read into plain data (:func:`read_xplane`), so the
reduction can be checked on a small recorded trace with no profiler at hand.

Plain data: ``{"device": {plane: {line: [[start_ns, dur_ns, name], ...]}},
"host": [[start_ns, dur_ns, name], ...]}``.
"""
from __future__ import annotations

import collections
import re

import numpy as np

#: Host events shorter than this are left out of the plain data; they only
#: label idle gaps, and the gaps worth reading are longer.
HOST_MIN_NS = 100_000


def read_xplane(path: str) -> dict:
    """Plain data of an ``.xplane.pb`` file written by ``jax.profiler``."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    device: dict = {}
    host: list = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            lines = {line.name: [[e.start_ns, e.duration_ns, e.name]
                                 for e in line.events]
                     for line in plane.lines}
            if any(lines.values()):
                device[plane.name] = lines
        elif plane.name.startswith("/host:"):
            host.extend([e.start_ns, e.duration_ns, e.name]
                        for line in plane.lines for e in line.events
                        if e.duration_ns >= HOST_MIN_NS)
    return {"device": device, "host": host}


def merge(intervals) -> np.ndarray:
    """Sorted, disjoint ``[k, 2]`` union of ``(start, end)`` intervals."""
    iv = np.asarray(intervals, np.float64).reshape(-1, 2)
    if not len(iv):
        return iv
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    ends = np.maximum.accumulate(iv[:, 1])
    # A new run starts where an interval begins after every earlier one ended.
    new = np.empty(len(iv), bool)
    new[0] = True
    new[1:] = iv[1:, 0] > ends[:-1]
    starts = iv[new, 0]
    stop_idx = np.append(np.flatnonzero(new)[1:] - 1, len(iv) - 1)
    return np.stack([starts, ends[stop_idx]], axis=1)


def overlap(merged: np.ndarray, lo: float, hi: float) -> float:
    """Length of ``merged`` (disjoint) inside ``[lo, hi]``."""
    if not len(merged):
        return 0.0
    clipped = np.clip(merged, lo, hi)
    return float((clipped[:, 1] - clipped[:, 0]).sum())


def _plane_union(lines: dict) -> np.ndarray:
    return merge([[s, s + d] for evs in lines.values() for s, d, _ in evs])


def reduce_trace(trace: dict, span_name: str) -> dict | None:
    """Busy and idle time of the devices over the window of the spans named
    ``span_name``, and each span's device time.

    Returns ``None`` when the trace holds no such span or no device event
    inside the window. Times are in seconds; device times are averaged over
    the devices that ran an operation in the window.
    """
    spans = sorted((s, s + d) for s, d, name in trace["host"] if name == span_name)
    if not spans:
        return None
    w0, w1 = spans[0][0], max(e for _, e in spans)
    unions = {p: _plane_union(lines) for p, lines in trace["device"].items()}
    used = {p: u for p, u in unions.items() if overlap(u, w0, w1) > 0}
    if not used:
        return None
    n = len(used)
    busy_ns = sum(overlap(u, w0, w1) for u in used.values()) / n
    span_device = [sum(overlap(u, a, b) for u in used.values()) / n
                   for a, b in spans]
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": busy_ns * 1e-9,
        "spans": [{"s": (b - a) * 1e-9, "device_s": dev * 1e-9}
                  for (a, b), dev in zip(spans, span_device)],
        "idle_gaps": _idle_gaps(trace["host"], used, w0, w1, span_name),
        "device_ops": _device_ops(trace["device"], used, w0, w1),
    }


def _idle_gaps(host, used, w0, w1, span_name, top=10) -> list:
    """The longest stretches in ``[w0, w1]`` with no device busy, each named
    by the innermost host event that covers its middle."""
    merged = merge(np.concatenate([u for u in used.values()]))
    edges = np.concatenate([[w0], merged.ravel(), [w1]]).reshape(-1, 2)
    edges = np.clip(edges, w0, w1)
    gaps = [(b - a, a, b) for a, b in edges if b > a]
    gaps.sort(reverse=True)
    out = []
    for length, a, b in gaps[:top]:
        mid = (a + b) / 2
        covering = [(d, name) for s, d, name in host if s <= mid <= s + d]
        label = min(covering)[1] if covering else "no host event"
        if label == span_name:
            label = f"{span_name} (no deeper host event)"
        out.append([label, length * 1e-9])
    return out


def _label(name: str) -> str:
    """``%fusion.475 fusion`` for an HLO event name ``%fusion.475 = <shape>
    fusion(...)``; other names as they are."""
    head, sep, rest = name.partition(" = ")
    op = re.search(r" ([a-z][\w-]*)\(", " " + rest) if sep else None
    return f"{head} {op.group(1)}" if op else name


def _device_ops(device, used, w0, w1, top=10) -> list:
    """Device time by operation name in the window, on each used device's
    finest line (the one with the most events), averaged over devices."""
    total: collections.Counter = collections.Counter()
    for p in used:
        finest = max(device[p].values(), key=len)
        for s, d, name in finest:
            total[_label(name)] += max(0.0, min(s + d, w1) - max(s, w0))
    return [[name, ns * 1e-9 / len(used)] for name, ns in total.most_common(top)]
