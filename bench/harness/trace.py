"""Reduce a profiler trace to device busy time, idle gaps and top ops.

The trace is first flattened to plain data (planes -> lines -> events of
``[name, start_ns, duration_ns]``), the form a small recorded trace is kept
in for the tests.  Device operations are found by plane and by time, not
by program name: every event on a ``/device:`` plane's op line that
overlaps the measured window counts.  The window is the host span named
``window``.  Idle gaps are named by the benchmark's own host span
(``submit``, ``pump``, ``wait``, ...) that covers most of each gap.
"""
from __future__ import annotations

import collections
import glob
import os

WINDOW = "window"
OPS_LINE = "XLA Ops"
TOP = 10


def load_xplane(log_dir: str) -> dict:
    """The newest ``.xplane.pb`` under ``log_dir`` as plain data."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    pd = ProfileData.from_file(max(paths, key=os.path.getmtime))
    return {"planes": [
        {"name": p.name, "lines": [
            {"name": ln.name,
             "events": [[e.name, e.start_ns, e.duration_ns]
                        for e in ln.events]}
            for ln in p.lines]}
        for p in pd.planes]}


def op_name(hlo: str) -> str:
    """``%fusion.2 = s32[8,262144]{...} fusion(...)`` -> ``fusion.2
    s32[8,262144]``: the op and the shape of what it makes."""
    lhs, _, rhs = hlo.partition(" = ")
    return f"{lhs.lstrip('%')} {rhs.split('{')[0].split(' ')[0]}".strip()


def _union(intervals) -> list:
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _device_planes(trace: dict) -> list:
    return [p for p in trace["planes"] if p["name"].startswith("/device:")
            and not p["name"].startswith("/device:CPU")]


def _op_events(plane: dict) -> list:
    lines = [ln for ln in plane["lines"] if ln["name"] == OPS_LINE]
    return [e for ln in (lines or plane["lines"]) for e in ln["events"]]


def _host_spans(trace: dict) -> list:
    return [e for p in trace["planes"] if p["name"].startswith("/host:")
            for ln in p["lines"] for e in ln["events"]]


def window_of(trace: dict) -> tuple:
    spans = [e for e in _host_spans(trace) if e[0] == WINDOW]
    if not spans:
        raise ValueError(f"no host span named {WINDOW!r} in the trace")
    _, s, d = max(spans, key=lambda e: e[2])
    return s, s + d


def reduce(trace: dict, labels=("submit", "pump", "wait", "drain")) -> dict:
    """``busy_s`` (union of device-op time in the window, averaged over the
    device planes that ran an op), ``window_s``, ``device_ops`` (top ops by total time) and
    ``idle_gaps`` (idle time by the host span that covers it).  None when
    the trace holds no device op in the window."""
    w0, w1 = window_of(trace)
    planes = _device_planes(trace)
    busy_ns, per_op, gaps = 0.0, collections.Counter(), collections.Counter()
    spans = sorted((e for e in _host_spans(trace) if e[0] in labels),
                   key=lambda e: e[1])
    used = 0
    for p in planes:
        clipped = []
        for name, s, d in _op_events(p):
            s0, e0 = max(s, w0), min(s + d, w1)
            if e0 > s0:
                clipped.append((s0, e0))
                per_op[op_name(name)] += e0 - s0
        if not clipped:
            continue
        used += 1
        busy = _union(clipped)
        busy_ns += sum(e - s for s, e in busy)
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        for (g0, g1), name in zip(zip(edges[::2], edges[1::2]),
                                  _labels(spans, edges)):
            if g1 > g0:
                gaps[name] += g1 - g0
    if not used:
        return None
    return dict(
        busy_s=busy_ns / used / 1e9,
        window_s=(w1 - w0) / 1e9,
        device_ops=[[n, t / used / 1e9] for n, t in per_op.most_common(TOP)],
        idle_gaps=[[n, t / used / 1e9] for n, t in gaps.most_common(TOP)],
    )


def _labels(spans, edges):
    """For each idle gap (``edges[2i]``, ``edges[2i+1]``), in time order, the
    host span that covers most of it; one sweep over the sorted spans."""
    j = 0
    for g0, g1 in zip(edges[::2], edges[1::2]):
        while j < len(spans) and spans[j][1] + spans[j][2] <= g0:
            j += 1
        best, cover, k = "no_host_span", 0.0, j
        while k < len(spans) and spans[k][1] < g1:
            name, s, d = spans[k]
            c = min(s + d, g1) - max(s, g0)
            if c > cover:
                best, cover = name, c
            k += 1
        yield best
