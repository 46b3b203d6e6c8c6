"""Per-phase device time and chunk-loop idle, read from the program's own
names in a profiler trace.

The tick program runs each phase under a ``jax.named_scope`` of its name
(:data:`PHASES`), and ``engine.run_sim_chunked`` writes the host spans
``sim.run`` (the chunk loop) and ``sim.chunk`` (one chunk, from its
dispatch to the end of its fold; args ``t0`` and ``ticks``).  On a device
plane the scope path of an operation, ``jit(step)/while/body/flows/...``,
is the ``tf_op`` stat of the operation's event metadata.  ``ProfileData``
does not expose event metadata, so this module reads it from the
``.xplane.pb`` wire format itself, and maps each device op event to its
path by the event's name.  Times, the window and the host spans come from
``ProfileData`` through :mod:`harness.trace`, on the clock the device ops
and ``bench.window`` share.

An op counts under the outermost phase on its path, a path component
matching a phase only as a whole; ops on no phase's path (the chunk step's
``while``/``cond`` control ops, which carry no ``tf_op``, and the
question's eager ops) are the unattributed remainder.
"""
from __future__ import annotations

import dataclasses
import functools
import gzip
import pathlib

from harness import trace

PHASES = ("arrive", "schedule", "flows", "communicate", "migrate", "execute",
          "complete", "cost", "refresh", "collect")
TRACE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".bench_trace"

# field numbers of tsl/profiler/protobuf/xplane.proto
_SPACE_PLANES = 1
_PLANE_NAME, _PLANE_LINES, _PLANE_EVENT_MD, _PLANE_STAT_MD = 2, 3, 4, 5
_LINE_NAME, _LINE_EVENTS = 2, 4
_EVENT_MD_ID = 1
_MAP_KEY, _MAP_VALUE = 1, 2
_MD_NAME, _MD_STATS = 2, 5
_STAT_MD_ID, _STAT_STR, _STAT_REF = 1, 5, 7


def _varint(b, i):
    x = shift = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return x, i


def _fields(b, i, j):
    """(field number, value) of the message in ``b[i:j]``; a
    length-delimited value is its (start, end) in ``b``."""
    while i < j:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 1:
            v, i = None, i + 8
        elif wire == 5:
            v, i = None, i + 4
        elif wire == 2:
            n, i = _varint(b, i)
            v, i = (i, i + n), i + n
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, v


def _text(b, v) -> str:
    return bytes(b[v[0]:v[1]]).decode("utf-8", "replace")


def _device_planes(b):
    """(plane name, plane bounds) of each device plane of an XSpace."""
    for f, v in _fields(b, 0, len(b)):
        if f == _SPACE_PLANES:
            name = next((_text(b, pv) for pf, pv in _fields(b, *v)
                         if pf == _PLANE_NAME), "")
            if trace.DEVICE_PLANE.match(name):
                yield name, v


def _map_entries(b, v):
    key = value = None
    for f, x in _fields(b, *v):
        if f == _MAP_KEY:
            key = x
        elif f == _MAP_VALUE:
            value = x
    return key, value


def op_paths(raw: bytes) -> dict:
    """Device plane name -> {event name: scope path} from the planes'
    event metadata: the ``tf_op`` stat less its ``:<op type>`` tail.  A
    name whose entries disagree on the path maps to None."""
    b = memoryview(raw)
    out = {}
    for plane, pv in _device_planes(b):
        stat_names, metadata = {}, []
        for f, v in _fields(b, *pv):
            if f == _PLANE_STAT_MD:
                key, value = _map_entries(b, v)
                stat_names[key] = next((_text(b, x) for g, x in
                                        _fields(b, *value) if g == _MD_NAME),
                                       "")
            elif f == _PLANE_EVENT_MD:
                metadata.append(_map_entries(b, v)[1])
        tf_op = next((k for k, n in stat_names.items() if n == "tf_op"), None)
        paths = {}
        for value in metadata:
            name, path = None, None
            for f, x in _fields(b, *value):
                if f == _MD_NAME:
                    name = _text(b, x)
                elif f == _MD_STATS:
                    stat = dict(_fields(b, *x))
                    if stat.get(_STAT_MD_ID) == tf_op:
                        path = (_text(b, stat[_STAT_STR])
                                if _STAT_STR in stat
                                else stat_names.get(stat.get(_STAT_REF)))
            if path is not None:
                path = path.rsplit(":", 1)[0]
            if name is not None:
                paths[name] = path if paths.get(name, path) == path else None
        out[plane] = paths
    return out


def event_names(raw: bytes, plane: str, line: str) -> list:
    """The names of a device line's events, in the file's order: the
    cross-check that this decoder and ``ProfileData`` see one trace."""
    b = memoryview(raw)
    for name, pv in _device_planes(b):
        if name != plane:
            continue
        names, ids = {}, None
        for f, v in _fields(b, *pv):
            if f == _PLANE_EVENT_MD:
                key, value = _map_entries(b, v)
                names[key] = next((_text(b, x) for g, x in _fields(b, *value)
                                   if g == _MD_NAME), "")
            elif f == _PLANE_LINES:
                fields = list(_fields(b, *v))
                if any(g == _LINE_NAME and _text(b, x) == line
                       for g, x in fields):
                    ids = [next((m for h, m in _fields(b, *x)
                                 if h == _EVENT_MD_ID), 0)
                           for g, x in fields if g == _LINE_EVENTS]
        return [names.get(i, "") for i in ids or []]
    return []


def phase_of(path: str | None) -> str | None:
    """The outermost phase on a scope path, matched as a whole component."""
    for part in (path or "").split("/"):
        if part in PHASES:
            return part
    return None


@dataclasses.dataclass
class Reading:
    phase_ns: dict        # phase (None: no phase) -> self ns over chips
    run_idle_ns: float    # chip idle inside ``sim.run``, mean over chips
    has_run: bool         # a ``sim.run`` span overlaps the window
    chunk_ticks: int      # ``ticks`` of the window's ``sim.chunk`` spans
    window_ns: float


def _host_spans(data, name: str) -> list:
    return [e for plane in data.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events if e.name == name]


def read_trace(raw: bytes, window_span: str = "bench.window") -> Reading:
    """Phase self times, chunk-loop idle and chunk ticks of the window."""
    from jax.profiler import ProfileData
    data = ProfileData.from_serialized_xspace(raw)
    w0, w1, _ = trace.span(data, window_span)
    paths = op_paths(raw)
    runs = trace._union(
        [(max(e.start_ns, w0), min(e.start_ns + e.duration_ns, w1))
         for e in _host_spans(data, "sim.run")
         if min(e.start_ns + e.duration_ns, w1) > max(e.start_ns, w0)])
    phase_ns, run_idle = {}, []
    for plane in data.planes:
        if plane.name not in paths:
            continue
        path_of = paths[plane.name]
        for line in plane.lines:
            if line.name != trace.OPS_LINE:
                continue
            clipped = [(phase_of(path_of.get(e.name)), max(s, w0), min(t, w1))
                       for e in line.events
                       for s, t in [(e.start_ns, e.start_ns + e.duration_ns)]
                       if min(t, w1) > max(s, w0)]
            for phase, ns in trace._self_times(clipped):
                phase_ns[phase] = phase_ns.get(phase, 0.0) + ns
            busy = trace._union([(s, t) for _, s, t in clipped])
            run_idle.append(sum(e - s for s, e in runs)
                            - _overlap(runs, busy))
    ticks = sum(int(dict(e.stats).get("ticks", 0))
                for e in _host_spans(data, "sim.chunk")
                if w0 <= e.start_ns < w1)
    return Reading(phase_ns=phase_ns,
                   run_idle_ns=sum(run_idle) / max(len(run_idle), 1),
                   has_run=bool(runs), chunk_ticks=ticks,
                   window_ns=float(w1 - w0))


def _overlap(a, b) -> float:
    """Length of the intersection of two sorted, disjoint interval lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        total += max(hi - lo, 0.0)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def load_raw(path: str) -> bytes:
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        return f.read()


@functools.lru_cache(maxsize=1)
def _reading_of(path: str, mtime_ns: int) -> Reading:
    return read_trace(load_raw(path))


def current() -> Reading:
    """The reading of the run's trace under ``<root>/.bench_trace``."""
    path = pathlib.Path(trace.find(str(TRACE_DIR)))
    return _reading_of(str(path), path.stat().st_mtime_ns)


def ms_per_tick(run, phases) -> float | None:
    """Device self milliseconds under ``phases``, summed over chips, per
    cell-tick of the window; None where no op runs under any of them."""
    r = current()
    times = [r.phase_ns[p] for p in phases if p in r.phase_ns]
    if not times or run.cell_ticks <= 0:
        return None
    return 1e-6 * sum(times) / run.cell_ticks
