"""Reduction of a JAX profiler trace (``.xplane.pb``) to what the metrics
read: per-chip busy time, device time per operation, and the idle gaps of
each chip with what the host thread was doing in them.

A chip is busy while any operation of its ``XLA Ops`` line runs; busy
time is the union of those intervals inside the window.  On that line an
event is named by its HLO instruction (``%seg_waterfill.12 = f32[...]
custom-call(...)``), and control flow nests: a ``while`` spans the ops of
its body.  Each operation is counted by its self time (its span less the
spans nested in it) under its instruction name (``seg_waterfill.12``).
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import gzip
import os
import re

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
OPS_LINE = "XLA Ops"
HLO_NAME = re.compile(r"^%?([^\s=]+)")


@dataclasses.dataclass
class Chip:
    busy_ns: float = 0.0
    op_ns: dict = dataclasses.field(default_factory=dict)
    op_calls: dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class Reduced:
    chips: dict            # chip id -> Chip
    window_ns: float
    host_gap_ns: dict      # host activity -> idle ns summed over chips

    def busy_s(self) -> list:
        return [c.busy_ns * 1e-9 for _, c in sorted(self.chips.items())]

    def op_seconds(self, match) -> float:
        """Device seconds, summed over chips, of the operations whose name
        ``match`` accepts."""
        return sum(ns for c in self.chips.values()
                   for name, ns in c.op_ns.items() if match(name)) * 1e-9

    def top_ops(self, n: int = 10) -> list:
        tot = {}
        for c in self.chips.values():
            for name, ns in c.op_ns.items():
                tot[name] = tot.get(name, 0.0) + ns
        return [[k, v * 1e-9] for k, v in
                sorted(tot.items(), key=lambda kv: -kv[1])[:n]]

    def top_gaps(self, n: int = 10) -> list:
        return [[k, v * 1e-9] for k, v in
                sorted(self.host_gap_ns.items(), key=lambda kv: -kv[1])[:n]]


def op_name(event_name: str) -> str:
    """``%fusion.2 = f32[...] fusion(...)`` -> ``fusion.2``."""
    m = HLO_NAME.match(event_name)
    return m.group(1) if m else event_name


def load(path: str):
    """The trace at ``path``: an ``.xplane.pb``, or one gzipped."""
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


def find(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {len(paths)}")
    return paths[0]


def _self_times(events):
    """(name, self ns) of properly nested (name, start, end) events."""
    out = []
    stack = []                         # [name, start, end, child ns]
    for name, s, e in sorted(events, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][2] <= s:
            n, s0, e0, child = stack.pop()
            out.append((n, e0 - s0 - child))
        if stack:
            stack[-1][3] += e - s
        stack.append([name, s, e, 0.0])
    out += [(n, e0 - s0 - child) for n, s0, e0, child in stack]
    return out


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def reduce(data, window: tuple | None = None) -> Reduced:
    """Reduce a loaded trace.  ``window`` (start, end, host line), as
    :func:`span` gives it, clips every interval to the measured window, in
    the trace's clock, and names idle gaps by that host line's spans; by
    default the window spans the device operations."""
    host_line = window[2] if window else None
    ops = {}
    host = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[int(m.group(2))] = [
                        (op_name(e.name), e.start_ns,
                         e.start_ns + e.duration_ns) for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                if line.name == host_line:
                    host += [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                             for e in line.events]
    if not ops:
        raise RuntimeError("no device operations in the trace")
    if window is None:
        w0 = min(s for ev in ops.values() for _, s, _ in ev)
        w1 = max(e for ev in ops.values() for _, _, e in ev)
    else:
        w0, w1 = window[:2]
    host.sort()
    starts = [s for s, _, _ in host]
    chips = {}
    host_gap = {}
    for chip, events in ops.items():
        c = Chip()
        clipped = [(name, max(s, w0), min(e, w1)) for name, s, e in events
                   if min(e, w1) > max(s, w0)]
        for name, ns in _self_times(clipped):
            c.op_ns[name] = c.op_ns.get(name, 0.0) + ns
            c.op_calls[name] = c.op_calls.get(name, 0) + 1
        busy = _union([(s, e) for _, s, e in clipped])
        c.busy_ns = sum(e - s for s, e in busy)
        edges = [w0] + [x for s, e in busy for x in (s, e)] + [w1]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        for s, e in gaps:
            what = _host_activity(host, starts, (s + e) / 2)
            host_gap[what] = host_gap.get(what, 0.0) + (e - s)
        chips[chip] = c
    return Reduced(chips=chips, window_ns=float(w1 - w0),
                   host_gap_ns=host_gap)


def _host_activity(host, starts, t, look_back: int = 4096) -> str:
    """The innermost host span running at time ``t``: of the spans that
    contain ``t``, the one that started last (host spans nest)."""
    i = bisect.bisect_right(starts, t)
    for s, e, name in reversed(host[max(0, i - look_back):i]):
        if e > t:
            return name
    return "(no host span)"


def span(data, name: str) -> tuple:
    """(start, end, line) of the host span ``name``, in the trace's clock,
    and the name of the host line that holds it.  Every host line is
    searched: the runtime names the main thread's line (``python`` on one
    machine, ``python3 <thread id>`` on another)."""
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == name:
                        return (e.start_ns, e.start_ns + e.duration_ns,
                                line.name)
    lines = [(plane.name, line.name, len(line.events))
             for plane in data.planes if plane.name.startswith("/host:")
             for line in plane.lines]
    raise RuntimeError(f"no host span {name!r} in the trace; host lines "
                       f"(plane, line, events): {lines}")
