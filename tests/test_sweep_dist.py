"""Multi-host sweep fabric acceptance (PR 8).

The tentpole property: the distributed slab-per-process sweep is a pure
EXECUTION change, never a results change —

* 2 spawned processes x 2 forced CPU devices produce finals and online
  summaries BIT-IDENTICAL to the single-process sweep, in <= 2 compiles
  per process (the oracle CI's ``dist-smoke`` step runs);
* wrap-padded slab partitioning reproduces the unpartitioned sweep
  bit-for-bit under uneven plans: grids not divisible by the slab, slabs
  smaller than a worker's fair share, the 1-cell grid;
* ``stats.online_merge`` (the cross-host reduction) is an exact identity
  over zero partials and matches a direct Welford pass when supports
  overlap;
* a partial run dir RESUMES: completed slabs are skipped and merged even
  when their worker died before writing its meta (orphan adoption).
"""
import dataclasses
import json
import os

import jax
import numpy as np
import pytest

from repro.core import SimConfig, stats
from repro.core.scenario import ScenarioSpec
from repro.core.types import OnlineSummary
from repro.launch import dist
from repro.launch.sweep import run_sweep

from test_streaming import assert_trees_bitwise_equal

SCEN = [ScenarioSpec("baseline"), ScenarioSpec("slow_net", bw=200.0)]
POLS = ["firstfit", "netaware"]


def tiny_cfg(**kw):
    base = dict(horizon=20, n_jobs=6, n_tasks=12, n_containers=12,
                arrival_window=8.0, placements_per_tick=8,
                migrations_per_tick=2)
    base.update(kw)
    return SimConfig(**base)


def tiny_spec(cfg, *, scenarios=SCEN, policies=POLS, seeds=(0, 1, 2),
              chunk=8, slab=None, devices_per_proc=1):
    return dist.GridSpec.build(
        cfg=cfg, scenarios=scenarios, seeds=seeds, policies=policies,
        n_hosts=6, n_spine=2, n_leaf=4, chunk=chunk, slab=slab,
        overlap=True, devices_per_proc=devices_per_proc)


def reference(spec):
    """The single-process streamed sweep over the same grid (itself pinned
    bit-identical to the stacked sweep by tests/test_streaming.py)."""
    return run_sweep(policies=spec.policy_names(),
                     scenarios=spec.scenario_specs(),
                     seeds=spec.seeds, cfg=spec.sim_config(),
                     n_hosts=spec.n_hosts, n_spine=spec.n_spine,
                     n_leaf=spec.n_leaf, chunk=spec.chunk, slab=spec.slab)


def assert_summary_bitwise(a: OnlineSummary, b: OnlineSummary):
    for name, xa, xb in zip(OnlineSummary._fields, a, b):
        xa, xb = np.asarray(xa), np.asarray(xb)
        assert xa.dtype == xb.dtype and xa.shape == xb.shape, name
        assert (xa == xb).all(), name


# ---------------------------------------------------------------------------
# online_merge: the cross-host reduction
# ---------------------------------------------------------------------------

def _rand_summary(rng, shape):
    n = rng.integers(0, 50, shape)
    xs = [rng.normal(0.5, 0.2, shape) * (n > 0) for _ in range(2)]
    f = lambda x: np.asarray(x, np.float64)
    i = lambda x: np.asarray(x, np.int64)
    return OnlineSummary(
        n_ticks=i(n), sum_util_var=f(xs[0]), sum_mean_util=f(xs[1]),
        sum_flow_rate=f(xs[0] * 3), w_mean_util=f(xs[1] * (n > 0)),
        w_m2_util=f(np.abs(xs[0]) * (n > 0)),
        sum_active_flows=i(n * 2), sum_arrivals=i(n // 2),
        sum_decisions=i(n // 3), sum_migrations=i(n // 5),
        peak_running=i(n % 7), peak_deployed=i(n % 5),
        peak_overloaded=i(n % 3), peak_inactive=i(n % 11),
        sum_soft_comm=f(xs[0] * 2), sum_soft_util=f(xs[1] * 2),
        sum_soft_n=f(n // 2), sum_soft_mig=f(xs[0] * (n > 0)),
        sum_soft_mig_n=f(n // 4))


def test_online_merge_disjoint_support_is_exact_identity():
    # the fabric's invariant: each cell is owned by exactly ONE process,
    # so every merge pairs real data with an n == 0 partial — and that
    # must be bitwise lossless, or distributed != single-process
    rng = np.random.default_rng(0)
    full = _rand_summary(rng, (32,))
    own = rng.random(32) < 0.5
    mask = lambda s, m: OnlineSummary(*(np.where(m, x, x.dtype.type(0))
                                        for x in s))
    a, b = mask(full, own), mask(full, ~own)
    for merged in (stats.online_merge(a, b), stats.online_merge(b, a)):
        assert_summary_bitwise(merged, full)
    # zero is the identity on both sides, and merging in a third zero
    # partial (the 'resumed' owner with no slabs) changes nothing
    zero = stats.online_init((32,))
    assert_summary_bitwise(stats.online_merge(full, zero), full)
    assert_summary_bitwise(stats.online_merge(zero, full), full)
    assert_summary_bitwise(
        stats.online_merge(stats.online_merge(a, zero), b), full)


def test_online_merge_overlapping_matches_direct_welford():
    # general Chan combine (not required by the fabric, but online_merge
    # must be a correct parallel Welford, not just a zero-identity hack)
    rng = np.random.default_rng(1)
    xs = rng.normal(0.4, 0.1, 37)
    def welford(vals):
        mean, m2 = 0.0, 0.0
        for k, v in enumerate(vals):
            d = v - mean
            mean += d / (k + 1)
            m2 += d * (v - mean)
        return OnlineSummary(
            *(np.asarray(x, t) for x, t in zip(
                [len(vals), 0, sum(vals), 0, mean, m2,
                 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0],
                [np.int64] + [np.float64] * 5 + [np.int64] * 8
                + [np.float64] * 5)))
    for split in (1, 13, 36):
        merged = stats.online_merge(welford(xs[:split]), welford(xs[split:]))
        ref = welford(xs)
        assert int(merged.n_ticks) == 37
        np.testing.assert_allclose(merged.w_mean_util, ref.w_mean_util,
                                   rtol=1e-12)
        np.testing.assert_allclose(merged.w_m2_util, ref.w_m2_util,
                                   rtol=1e-9, atol=1e-12)
        np.testing.assert_allclose(merged.sum_mean_util, ref.sum_mean_util,
                                   rtol=1e-12)


# ---------------------------------------------------------------------------
# GridSpec: the launcher <-> worker contract
# ---------------------------------------------------------------------------

def test_grid_spec_json_roundtrip(tmp_path):
    cfg = tiny_cfg(duration_range=(5.0, 9.0))
    spec = tiny_spec(cfg, slab=5)
    p = str(tmp_path / "spec.json")
    spec.save(p)
    back = dist.GridSpec.load(p)
    assert back.sim_config() == cfg          # tuple fields restored
    assert back.scenario_specs() == spec.scenario_specs()
    assert back.policy_names() == POLS
    np.testing.assert_array_equal(np.asarray(back.policy_params().weights),
                                  np.asarray(spec.policy_params().weights))
    assert back.n_cells == 2 * 2 * 3

    W = np.asarray(spec.policy_params().weights)  # raw-weights variant
    wspec = dist.GridSpec.build(
        cfg=cfg, scenarios=SCEN, seeds=(0,), weights=W, n_hosts=6,
        n_spine=2, n_leaf=4, chunk=8, slab=None, overlap=False,
        devices_per_proc=2)
    wspec.save(p)
    wback = dist.GridSpec.load(p)
    assert wback.policy_names() == ["w000", "w001"]
    np.testing.assert_array_equal(np.asarray(wback.policy_params().weights),
                                  W)
    with pytest.raises(ValueError, match="exactly one"):
        dist.GridSpec.build(cfg=cfg, scenarios=SCEN, seeds=(0,),
                            policies=POLS, weights=W, n_hosts=6, n_spine=2,
                            n_leaf=4, chunk=8, slab=None, overlap=True,
                            devices_per_proc=1)


# ---------------------------------------------------------------------------
# Uneven partitions: wrap-padded slab-per-worker == unpartitioned, bitwise
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("plan", [
    # (slab, worker share of the slab-start list) — B = 12 cells
    (5, [1, 2]),          # B % slab != 0: last slab wraps; uneven 1-vs-2
    (2, [1, 4, 1]),       # slab far below fair share, 3 workers, lopsided
    (12, [1]),            # one worker owns the whole grid in one slab
])
def test_uneven_partitions_bitwise(tmp_path, plan):
    slab, shares = plan
    cfg = tiny_cfg()
    spec = tiny_spec(cfg, slab=slab)
    B = spec.n_cells
    starts = list(range(0, B, dist._slab_cells(B, spec.slab, 1)))
    assert sum(shares) == len(starts), "plan must cover every slab"
    ref = reference(spec)

    out = str(tmp_path / "run")
    k = 0
    for wid, share in enumerate(shares):
        dist.run_worker_inline(spec, out, wid, starts[k:k + share])
        k += share
    finals, summary, metas = dist.merge_out_dir(spec, out)
    assert_trees_bitwise_equal(ref.finals, finals)
    assert_summary_bitwise(ref.summary, summary)
    assert sorted(s for m in metas for s in m["slabs"]) == starts


def test_one_cell_grid_bitwise(tmp_path):
    cfg = tiny_cfg()
    spec = tiny_spec(cfg, scenarios=[SCEN[0]], policies=["netaware"],
                     seeds=(0,), slab=None, devices_per_proc=1)
    assert spec.n_cells == 1
    ref = reference(spec)
    out = str(tmp_path / "run")
    dist.run_worker_inline(spec, out, 0, [0])
    finals, summary, _ = dist.merge_out_dir(spec, out)
    assert_trees_bitwise_equal(ref.finals, finals)
    assert_summary_bitwise(ref.summary, summary)


def test_slab_plan_mismatch_is_loud(tmp_path):
    # a worker whose local device count pads the slab differently than the
    # spec planned must refuse to run, not silently diverge ownership
    spec = tiny_spec(tiny_cfg(), slab=5, devices_per_proc=4)
    with pytest.raises(RuntimeError, match="pad the slab"):
        dist.run_worker_inline(spec, str(tmp_path), 0, [0])


# ---------------------------------------------------------------------------
# Failure semantics: missing slabs, resume, orphan adoption
# ---------------------------------------------------------------------------

def test_resume_skips_done_and_adopts_orphans(tmp_path):
    cfg = tiny_cfg()
    spec = tiny_spec(cfg, slab=5)
    B = spec.n_cells
    starts = list(range(0, B, dist._slab_cells(B, spec.slab, 1)))
    ref = reference(spec)
    out = str(tmp_path / "run")

    # "crashed" first run: one slab completed, but the worker died before
    # writing its meta -> the slab is an orphan on disk
    dist.run_worker_inline(spec, out, 0, starts[:1])
    os.remove(os.path.join(out, "worker_00.json"))
    assert dist.completed_slab_starts(out) == {starts[0]}
    with pytest.raises(RuntimeError, match="incomplete"):
        dist.merge_out_dir(spec, out)

    # resume: a fresh worker takes only the remaining slabs
    remaining = [s for s in starts
                 if s not in dist.completed_slab_starts(out)]
    assert remaining == starts[1:]
    dist.run_worker_inline(spec, out, 1, remaining)
    finals, summary, metas = dist.merge_out_dir(spec, out)
    assert_trees_bitwise_equal(ref.finals, finals)
    assert_summary_bitwise(ref.summary, summary)
    assert [m["process_index"] for m in metas] == [1]   # orphan adopted


def test_merge_rejects_foreign_slab_plan(tmp_path):
    cfg = tiny_cfg()
    spec = tiny_spec(cfg, slab=5)
    out = str(tmp_path / "run")
    dist.run_worker_inline(spec, out, 0,
                           range(0, spec.n_cells,
                                 dist._slab_cells(spec.n_cells, 5, 1)))
    other = dataclasses.replace(spec, slab=4)
    with pytest.raises(RuntimeError, match="different grid/slab plan"):
        dist.merge_out_dir(other, out)


def test_fabric_refuses_accelerator_host(tmp_path, monkeypatch):
    """Workers cannot claim a chip the launcher holds, and CPU workers
    would report CPU results for a chip run: refuse before spawning."""
    monkeypatch.setattr(dist.jax, "default_backend", lambda: "tpu")
    spec = tiny_spec(tiny_cfg())
    with pytest.raises(RuntimeError, match="cannot run on a tpu host"):
        dist.run_spec(spec, num_procs=1, out_dir=str(tmp_path / "run"))
    assert not (tmp_path / "run").exists()


# ---------------------------------------------------------------------------
# The oracle: 2 spawned processes x 2 forced CPU devices, jax.distributed
# ---------------------------------------------------------------------------

def test_dist_sweep_oracle_2proc_2dev(tmp_path):
    """CI's ``dist-smoke``: real ``jax.distributed`` workers, forced
    2-device CPU meshes, dynamic slab handout — finals and summaries
    bit-identical to the single-process run, <= 2 compiles/process."""
    cfg = tiny_cfg()
    out = str(tmp_path / "run")
    ref = run_sweep(policies=POLS, scenarios=SCEN, seeds=(0, 1, 2),
                    cfg=cfg, n_hosts=6, n_spine=2, n_leaf=4, chunk=8,
                    slab=4)
    res = dist.run_dist_sweep(
        policies=POLS, scenarios=SCEN, seeds=(0, 1, 2), cfg=cfg,
        n_hosts=6, n_spine=2, n_leaf=4, num_procs=2, devices_per_proc=2,
        chunk=8, slab=4, out_dir=out, timeout_s=480.0)

    assert_trees_bitwise_equal(ref.finals, res.finals)
    assert_summary_bitwise(ref.summary, res.summary)
    assert res.n_devices == 4
    assert res.compile_cache_misses <= 2, \
        f"{res.compile_cache_misses} compiles/process (want <= 2)"
    for m in res.worker_meta:
        assert m["compile_cache_misses"] <= 2
        assert m["n_local_devices"] == 2
    assert len(res.worker_meta) == 2
    # dynamic handout: every slab assigned exactly once, none lost
    with open(os.path.join(out, "coordinator.json")) as f:
        coord = json.load(f)
    assigned = sorted(s for ss in coord["assignments"].values() for s in ss)
    B = len(POLS) * len(SCEN) * 3
    assert assigned == list(range(0, B, dist._slab_cells(B, 4, 2)))

    # summaries() rides the online summary exactly like the streamed sweep
    rows = res.summaries()
    ref_rows = ref.summaries()
    assert len(rows) == len(ref_rows) == B
    for ra, rb in zip(ref_rows, rows):
        assert ra.keys() == rb.keys()
        for k in ra:
            va, vb = ra[k], rb[k]
            if isinstance(va, float) and np.isnan(va):
                assert np.isnan(vb), k      # nan != nan, but same cell
            else:
                assert va == vb, k
