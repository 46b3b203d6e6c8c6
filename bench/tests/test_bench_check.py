"""The check that decides ``correct``, on the CPU at a size a test run
holds: a sound run passes, and the control and every planted fault a cell
can have come out not correct under the cell's own limits."""
from __future__ import annotations

import pathlib
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(HERE)]

from test_bench_harness import tiny_root  # noqa: E402

from harness import faults, runner  # noqa: E402

CELLS = ("dcsim2000.fw.netaware",)
# a fleet small enough for the CPU with the load of the real ones: about
# six containers a host, so hosts fill, overload and migrate
SIZES = dict(hosts=60, leaves=12, spines=3, containers=360, tasks=360,
             jobs=120, horizon_ticks=40)


def _run(tmp_path, workload, seed, hook=None):
    root = tiny_root(tmp_path, **SIZES)
    return runner.run(root, workload, seed, 0.1, False, 0.0,
                      require_chip=False, hook=hook)


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(tmp_path, workload):
    res = _run(tmp_path, workload, 2**33 + 1)
    assert res["correct"], res["check"]
    assert res["failed"] == 0
    assert list(res)[-1] == "check"


@pytest.mark.parametrize("workload", CELLS)
@pytest.mark.parametrize("seed", (11, 12, 13))
def test_control_is_not_correct(tmp_path, workload, seed):
    res = _run(tmp_path, workload, seed, hook=faults.control)
    assert not res["correct"], res["check"]


@pytest.mark.parametrize("workload", CELLS)
def test_altered_answer_is_not_correct(tmp_path, workload):
    res = _run(tmp_path, workload, 21, hook=faults.altered)
    assert not res["correct"], res["check"]
    assert res["check"]["invariant_faults"]["value"] > 0


@pytest.mark.parametrize("workload", CELLS)
def test_unchanged_state_is_not_correct(tmp_path, workload, monkeypatch):
    faults.unchanged_patch(monkeypatch)
    res = _run(tmp_path, workload, 31)
    assert not res["correct"], res["check"]
