"""The trace reduction on a trace recorded on a TPU v5e chip: four ticks of
a 20-host cell, ``netaware`` with the ``fw`` refresh every two ticks, one
``bench.window`` host span around the run."""
from __future__ import annotations

import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "bench")]

from harness import trace  # noqa: E402

SMALL = str(pathlib.Path(__file__).parent / "data" / "small.xplane.pb.gz")


@pytest.fixture(scope="module")
def data():
    return trace.load(SMALL)


@pytest.fixture(scope="module")
def reduced(data):
    return trace.reduce(data, window=trace.span(data, "bench.window"))


def test_one_chip_busy_inside_window(reduced):
    assert list(reduced.chips) == [0]
    busy = reduced.busy_s()[0]
    assert 0 < busy < reduced.window_ns * 1e-9


def test_kernel_events_counted_by_name(reduced):
    calls = reduced.chips[0].op_calls
    wf = sum(n for k, n in calls.items() if k.startswith("seg_waterfill"))
    fw3 = sum(n for k, n in calls.items() if k.startswith("fw_phase3"))
    assert wf == 4            # one flow allocation per tick
    assert fw3 == 2           # refreshes at ticks 0 and 2
    assert reduced.op_seconds(lambda k: k.startswith("seg_waterfill")) > 0


def test_self_time_sums_to_nested_spans():
    events = [("while.1", 0, 100), ("fusion.1", 10, 30), ("cond.1", 40, 90),
              ("fw_phase1.1", 50, 60), ("fusion.2", 120, 130)]
    selfs = dict(trace._self_times(events))
    assert selfs == {"while.1": 30, "fusion.1": 20, "cond.1": 40,
                     "fw_phase1.1": 10, "fusion.2": 10}
    assert trace._union([(s, e) for _, s, e in events]) == [[0, 100],
                                                             [120, 130]]


def test_op_seconds_never_exceed_busy(reduced):
    total = reduced.op_seconds(lambda k: True)
    assert total == pytest.approx(reduced.busy_s()[0], rel=1e-9)


def test_idle_gaps_attributed_to_host(reduced):
    gaps = reduced.top_gaps()
    assert gaps and all(s > 0 for _, s in gaps)
    idle = reduced.window_ns * 1e-9 - reduced.busy_s()[0]
    assert sum(s for _, s in reduced.top_gaps(10**6)) == \
        pytest.approx(idle, rel=1e-9)


def test_op_name_of_hlo_text():
    assert trace.op_name("%seg_waterfill.12 = f32[1,12000] custom-call(x)") \
        == "seg_waterfill.12"
    assert trace.op_name("fusion") == "fusion"
