"""The per-phase reading (``harness.scopes``) on traces recorded on a TPU
v5e chip: ``small_scopes`` (four ticks of a 20-host cell with the
program's phase scopes and chunk-loop spans, recorded by
``record_small_trace.py``) and ``small`` (the same cell recorded before
the program had any scope or span)."""
from __future__ import annotations

import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "bench"), str(ROOT / "src")]

from harness import scopes, spec, trace  # noqa: E402
from harness.runner import TraceView  # noqa: E402

DATA = pathlib.Path(__file__).parent / "data"
SCOPED, BARE = "small_scopes.xplane.pb.gz", "small.xplane.pb.gz"
TICKS = 4
READERS = ("schedule_ms_per_tick", "flows_ms_per_tick", "refresh_ms_per_tick",
           "other_phases_ms_per_tick", "chunk_loop_idle_share")


@pytest.fixture(scope="module", params=[SCOPED, BARE])
def recorded(request):
    raw = scopes.load_raw(str(DATA / request.param))
    data = trace.load(str(DATA / request.param))
    return request.param, raw, data


def _view(data):
    red = trace.reduce(data, window=trace.span(data, "bench.window"))
    return TraceView(trace=red, cell_ticks=TICKS, refreshes=2, shapes={},
                     peaks=None)


def _read_all(monkeypatch, tmp_path, name):
    """Every new reader on the fixture ``name``, laid out as a run's
    ``.bench_trace``."""
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(scopes.load_raw(str(DATA / name)))
    monkeypatch.setattr(scopes, "TRACE_DIR", tmp_path)
    view = _view(trace.load(str(DATA / name)))
    return {m: spec.reader(ROOT, m)(view) for m in READERS}


def test_decoder_event_names_match_profile_data(recorded):
    _, raw, data = recorded
    (plane,) = [p for p in data.planes if trace.DEVICE_PLANE.match(p.name)]
    (line,) = [ln for ln in plane.lines if ln.name == trace.OPS_LINE]
    names = [e.name for e in line.events]
    assert scopes.event_names(raw, plane.name, trace.OPS_LINE) == names
    assert set(names) <= set(scopes.op_paths(raw)[plane.name])


def test_phase_self_times_sum_to_op_self_time(recorded):
    """The phase self times and the unattributed remainder partition the
    chip's op self time in the window."""
    name, raw, data = recorded
    reading = scopes.read_trace(raw)
    red = trace.reduce(data, window=trace.span(data, "bench.window"))
    assert sum(reading.phase_ns.values()) == pytest.approx(
        sum(red.chips[0].op_ns.values()), rel=1e-9)
    if name == SCOPED:
        assert set(scopes.PHASES) <= set(reading.phase_ns)
        assert None in reading.phase_ns        # control ops, eager ops
    else:
        assert set(reading.phase_ns) == {None}


def test_kernels_fall_under_their_phase():
    raw = scopes.load_raw(str(DATA / SCOPED))
    (paths,) = scopes.op_paths(raw).values()
    phase = {trace.op_name(n): scopes.phase_of(p) for n, p in paths.items()}
    assert {v for k, v in phase.items() if k.startswith("seg_waterfill")} \
        == {"flows"}
    assert {v for k, v in phase.items() if k.startswith("fw_phase")} \
        == {"refresh"}


def test_chunk_ticks_sum_to_cell_ticks(recorded):
    name, raw, _ = recorded
    reading = scopes.read_trace(raw)
    assert reading.chunk_ticks == (TICKS if name == SCOPED else 0)
    assert reading.has_run == (name == SCOPED)


def test_readers_on_the_scoped_trace(monkeypatch, tmp_path):
    got = _read_all(monkeypatch, tmp_path, SCOPED)
    assert all(v is not None and v > 0 for v in got.values()), got
    idle = 100.0 * (1 - sum(_view(trace.load(str(DATA / SCOPED))).trace
                            .busy_s()) / (scopes.current().window_ns * 1e-9))
    assert got["chunk_loop_idle_share"] <= idle + 1e-9


@pytest.mark.parametrize("metric", READERS)
def test_reader_is_none_without_scopes(monkeypatch, tmp_path, metric):
    """A trace of a program without the scopes and spans reads None,
    never 0, so a renamed scope shows as a missing metric."""
    assert _read_all(monkeypatch, tmp_path, BARE)[metric] is None


@pytest.mark.parametrize("path,phase", [
    ("jit(step)/while/body/schedule/while/body/add", "schedule"),
    ("cond/branch_1_fun/refresh/jit(clip)", "refresh"),
    ("jit(step)/while/body/flows/collect/add", "flows"),
    ("jit(step)/while/body/jit(schedule)/scheduled/add", None),
    ("jit(step)/while/body/closed_call/select_n", None),
    (None, None),
])
def test_phase_is_outermost_whole_component(path, phase):
    assert scopes.phase_of(path) == phase


def test_overlap_of_interval_lists():
    assert scopes._overlap([[0, 10], [20, 30]], [[5, 25]]) == 10
    assert scopes._overlap([[0, 10]], []) == 0
