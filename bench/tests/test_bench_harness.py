"""The benchmark harness on the CPU: its files resolve, a cell is added
by adding files, and a run without a TPU refuses to measure."""
from __future__ import annotations

import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"
sys.path[:0] = [str(BENCH), str(ROOT / "src")]

from harness import spec  # noqa: E402

MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())


def _env(**extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    env.update(extra)
    return env


@pytest.mark.parametrize("path", sorted(
    p.relative_to(BENCH).as_posix()
    for d in ("configs", "traffic", "limits") for p in (BENCH / d).iterdir()
    if p.suffix == ".json"))
def test_data_file_loads(path):
    data = json.loads((BENCH / path).read_text())
    assert isinstance(data, dict) and data


@pytest.mark.parametrize("metric", [m["name"] for m in MANIFEST["per_layer"]])
def test_metric_reader_loads(metric):
    assert callable(spec.reader(ROOT, metric))


@pytest.mark.parametrize("workload", [w["name"] for w in
                                      MANIFEST["workloads"]])
def test_cell_resolves(workload):
    cell = spec.load(ROOT, workload)
    assert cell.chips in (1, 4)
    assert cell.traffic["driver"] in ("episodes",)
    names = {m["name"] for m in cell.end_to_end}
    assert {"setup_s", "cell_ticks_per_s"} <= names
    assert cell.per_layer, "every cell reports a per-layer metric"
    assert set(cell.limits) == {"stat_gap", "arrivals_diff",
                                "invariant_faults", "used_drift"}
    for m in cell.per_layer:
        assert spec.reader(ROOT, m["name"])


def tiny_root(tmp_path: pathlib.Path, **sizes) -> pathlib.Path:
    """A checkout with the real harness and every configuration shrunk; a
    cell without limits of its own takes the first cell's."""
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    manifest = json.loads(json.dumps(MANIFEST))
    for c in manifest["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        cfg.update(dict(hosts=40, leaves=8, spines=2, containers=240,
                        tasks=240, jobs=80, horizon_ticks=40), **sizes)
        (root / c["file"]).write_text(json.dumps(cfg))
    limits = root / "bench" / "limits"
    first = (limits / f"{MANIFEST['workloads'][0]['name']}.json").read_text()
    for w in manifest["workloads"]:
        if not (limits / f"{w['name']}.json").exists():
            (limits / f"{w['name']}.json").write_text(first)
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    return root


def test_new_cell_is_found_from_new_files(tmp_path):
    root = tiny_root(tmp_path)
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*")
              if p.is_file()}
    (root / "bench" / "traffic" / "path.firstfit.json").write_text(
        json.dumps({"driver": "episodes", "policy": "firstfit",
                    "delay_mode": "path", "chunk": 10, "check_sample": 1}))
    (root / "bench" / "limits" / "dcsim2000.path.firstfit.json").write_text(
        json.dumps({"stat_gap": 0.05, "arrivals_diff": 0,
                    "invariant_faults": 0, "used_drift": 1e-4}))
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    manifest["workloads"].append(
        {"name": "dcsim2000.path.firstfit", "config": "dcsim_2000h",
         "traffic": "path.firstfit", "chips": 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    unchanged = {p: p.read_bytes() for p in before}
    assert unchanged == before, "adding a cell edited an existing file"
    cell = spec.load(root, "dcsim2000.path.firstfit")
    assert cell.traffic["policy"] == "firstfit"
    from harness import runner
    res = runner.run(root, "dcsim2000.path.firstfit", 2**31 + 5, 0.2, False,
                     0.0, require_chip=False)
    assert res["correct"] and res["attempted"] >= 1
    assert res["metrics"]["cell_ticks_per_s"]["value"] > 0


def test_no_tpu_exits_nonzero_and_names_it():
    p = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload",
         "dcsim2000.fw.netaware", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=_env(),
        timeout=300)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert p.stdout.strip() == ""


def test_checkout_without_program_exits_nonzero(tmp_path):
    root = tmp_path / "bare"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "dcsim2000.fw.netaware",
         "--seed", "1", "--seconds", "1", "--trace", "0"], cwd=root,
        capture_output=True, text=True, env=_env(), timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
