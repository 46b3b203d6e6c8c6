"""The yardstick's arithmetic: bytes of a kernel call from its shapes, the
peaks table, and the per-layer readers on a hand-made trace."""
from __future__ import annotations

import json
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "bench")]

from harness import inputs, peaks, spec, trace, work  # noqa: E402
from harness.runner import TraceView  # noqa: E402

@pytest.mark.parametrize("config_name,flows,links,nodes,wf_bytes", [
    # F = 2 x 6000 containers, E = 2000 + 400 x 100 links
    ("dcsim_2000h", 12000, 42000, 2500,
     12000 * (16 + 1 + 4 + 4) + 42000 * (4 + 4)),
])
def test_bytes_at_cell_shapes(config_name, flows, links, nodes, wf_bytes):
    config = json.loads(
        (ROOT / "bench" / "configs" / f"{config_name}.json").read_text())
    fl = inputs.fleet(config)
    assert 2 * int(config["containers"]) == flows
    assert (fl.n_links, fl.n_nodes) == (links, nodes)
    assert work.seg_waterfill_bytes(flows, links) == wf_bytes
    assert work.fw_minplus_bytes(nodes) == 2 * nodes * nodes * 4


def test_peaks_known_and_unknown():
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9 imaginary")


def fake_view(busy_ns, ops, window_ns=1e9, ticks=100, refreshes=10):
    chips = {}
    for i, b in enumerate(busy_ns):
        chips[i] = trace.Chip(busy_ns=b, op_ns=dict(ops))
    red = trace.Reduced(chips=chips, window_ns=window_ns, host_gap_ns={})
    return TraceView(trace=red, cell_ticks=ticks, refreshes=refreshes,
                     shapes=dict(hosts=500, nodes=625, links=3000,
                                 flows=6000),
                     peaks=peaks.peaks("TPU v5 lite"))


def read(metric, view):
    return spec.reader(ROOT, metric)(view)


def test_readers_on_hand_made_trace():
    ops = {"seg_waterfill": 2e8, "fw_phase1": 1e7, "fw_phase2": 2e7,
           "fw_phase3": 3e7, "fusion.1": 1e8}
    v = fake_view([8e8, 6e8], ops)
    assert read("device_idle_share", v) == pytest.approx(30.0)
    assert read("device_ms_per_tick", v) == pytest.approx(14.0)
    wf = 100 * 100 * work.seg_waterfill_bytes(6000, 3000) / 819e9 / 0.4
    assert read("seg_waterfill_roofline", v) == pytest.approx(wf)
    fw = 100 * 10 * work.fw_minplus_bytes(625) / 819e9 / 0.12
    assert read("fw_minplus_roofline", v) == pytest.approx(fw)


def test_readers_find_nothing_return_nothing():
    v = fake_view([5e8], {"fusion.1": 1e8})
    assert read("seg_waterfill_roofline", v) is None
    assert read("fw_minplus_roofline", v) is None
