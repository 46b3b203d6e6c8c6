"""Tracked engine benchmark -> BENCH_engine.json (ISSUE 1 acceptance).

Measures steady-state ``ticks_per_s`` and ``state_mb`` per scale point so
the perf trajectory is tracked across PRs.  At the 500-host/3000-container
point BOTH flow engines run in the same process, giving an apples-to-apples
``sparse_speedup`` of the segment-based flow path over the dense [F, E]
oracle; the 2000-host point runs sparse-only (the dense membership tensor
at that scale is the OOM ceiling this PR removes).

ISSUE 3 adds the ``sweep`` entry: the 6-policy x 4-scenario ladder as ONE
compiled call (compile-cache-miss counter recorded), against the per-point
cold (compile + run) loop the pre-policy-as-data architecture paid — one
XLA compilation per (policy, scenario) point, reproduced with
``jax.clear_caches()`` between calls.

ISSUE 4: the sweep is fully vmapped (policy x scenario x seed), the entry
grows ``vmap_cell_tax`` (vmapped per-cell steady time / mean warm
standalone cell), and full mode re-measures the quick-scale grid into
``sweep_quick`` — the committed baseline ``benchmarks/check_regression.py``
gates CI quick runs against (30% tolerance).

ISSUE 5 (branch-free scoring): the policy axis no longer evaluates every
registered branch under ``vmap`` — ``vmap_cell_tax`` is the tracked
acceptance number (target <= 1.25 at the 24-cell 500h/3000c grid) — and a
``tune`` smoke entry measures the weight-search driver
(``repro.launch.tune``: weight samples on the policy batch axis, one
compile) so the learned-weights path is regression-gated too.

ISSUE 7 (streaming engine) adds the ``longhorizon`` entry
(``benchmarks/longhorizon_bench.py``): subprocess max-RSS of the chunked
streaming run vs the stacked per-tick path at a long horizon.  Full mode
demonstrates the crossing — streaming completes under a fixed
``ceiling_mb`` the stacked run's scan-ys buffer exceeds (the stacked child
is killed at the crossing by a VmHWM poll); quick mode re-measures the
streaming side only, gated absolutely against the committed ceiling.

ISSUE 6 turns this into a backend LADDER: every point records the JAX
``backend``/``device`` it ran on, and the full bench adds kernel-on
('auto') vs kernel-off ('off') variants of the 500h/3000c and 2000h/6000c
points under ``delay_mode='fw'`` — the APSP refresh the ``fw_minplus``
Pallas kernel fuses — plus a cheap 100h/1500c fw pair both modes measure
(so the CI quick gate exercises the kernel dispatch path too).  On CPU,
'auto' resolves to the jnp reference (``kernels_active: false`` in the
row), so the on/off pair measures the same code there; the pair only
separates on TPU/GPU.  check_regression.py refuses cross-backend
comparisons outright.

ISSUE 8 (multi-process fabric) adds the ``sweep_dist`` entry: the same
smoke grid through the in-process streamed sweep and three spawned
``repro.launch.dist`` arms (1 proc, 2 procs, 2 procs serial-gather),
gated on bit-identical results, the <=2/process compile bill, and the
within-run overlap ratio; full mode also appends a headline row to
``BENCH_history.jsonl`` via ``benchmarks.archive``.

ISSUE 9 (differentiable simulator) adds the ``tune_grad`` entry: gradient
descent on the soft-placement surrogate (``run_tune_grad`` — one
value_and_grad executable + one hard-oracle executable, tau annealed as a
traced RunParams field) raced against an equal-oracle-budget random
search.  Gated numbers (``grad_vs_random``, the 2-executable compile
bill) are within-run and machine-independent; the cold wall stays out of
the skew-normalized pack.

ISSUE 10 (event-horizon telescoping) adds the ``telescope`` entry: the
sparse-event long-horizon point (4h/16c, 30k ticks, 8 seeds, refresh
interval 100) through the vmapped streaming driver with the macro-tick
engine on vs off.  Gated numbers: ``finals_bitwise_equal`` (must be true
— telescoping is an exact transform, docs/events.md), the within-run
``telescope_speedup`` (the ISSUE 10 >= 3x acceptance), and the ON-side
``ticks_per_s`` in the skew-normalized ratio pack.  Both modes measure
the same grid, so quick CI runs gate like-for-like.

    PYTHONPATH=src python -m benchmarks.engine_bench [--quick]
"""
from __future__ import annotations

import argparse
import json
import os
import time

from benchmarks.common import measure_scale_point

BENCH_PATH = os.path.join(os.path.dirname(__file__), "..",
                          "BENCH_engine.json")
# --quick runs must not clobber the tracked full-ladder artifact
BENCH_QUICK_PATH = os.path.join(os.path.dirname(__file__), "..",
                                "experiments", "BENCH_engine_quick.json")

# the quick-mode sweep grid — the FULL bench measures the same grid into
# the committed ``sweep_quick`` entry, so the CI regression gate
# (benchmarks/check_regression.py) has a like-for-like baseline
QUICK_SWEEP = dict(n_hosts=50, n_containers=300, horizon=40)
# the tune smoke grid: both modes measure the SAME grid (the quick run is
# gated against the committed entry like-for-like)
TUNE_SMOKE = dict(n_hosts=50, n_containers=300, horizon=40, samples=8)
# the differentiable-tuning smoke grid (ISSUE 9): the slow-net scenario
# where placement weights have headroom, small enough that 6 grad steps +
# the equal-budget random race fit in the quick bench.  steps=6 with
# eval_every=3 spends exactly 3 oracle rounds x batch candidates, so the
# random arm gets n_samples = oracle_evals — a like-for-like budget.
TUNE_GRAD_SMOKE = dict(n_hosts=20, n_containers=40, horizon=30, steps=6,
                       batch=4)
# the multi-process fabric smoke grid (ISSUE 8): small enough that three
# spawned arms fit in the quick bench, large enough for several slabs per
# worker (24 cells / slab 6 = 4 slabs) so the handout and the overlapped
# gather actually cycle
DIST_SMOKE = dict(n_hosts=20, n_containers=120, horizon=40, chunk=20,
                  slab=6)
# the telescoping point (ISSUE 10): a tiny fleet at a LONG horizon with a
# sparse event stream (1 placement/tick, refresh every 100 ticks) — the
# regime the macro-tick engine exists for, quiescent tail included.  Both
# modes measure the same grid; the off arm dominates the wall (~tens of
# seconds of per-tick streaming on CPU).
TELESCOPE_SMOKE = dict(n_hosts=4, n_containers=16, horizon=30_000, seeds=8,
                       chunk=4096, interval=100)


def _timed(f) -> float:
    t0 = time.time()
    f()
    return time.time() - t0


def bench_scenarios():
    """The 4-scenario ladder of the sweep entry: the scenario layer's own
    healthy-fabric + Fig 5/8 bw/loss degradations, plus a benchmark-only
    runtime-threshold variant."""
    from repro.core.scenario import ScenarioSpec, default_scenarios
    return default_scenarios()[:3] + [
        ScenarioSpec("tight", overload_threshold=0.5, queue_coef=1.0),
    ]


def measure_sweep_point(n_hosts: int, n_containers: int, horizon: int,
                        with_loop: bool = True) -> dict:
    """6 policies x 4 scenarios x 1 seed in one fully-vmapped compiled call,
    vs (a) warm standalone cells — the ``vmap_cell_tax`` the scatter-free
    tick is accountable for — and (b, full mode) the old-world per-point
    cold loop (compile + run each, via clear_caches)."""
    import jax

    from repro.core import SimConfig, get_policy, list_policies, run_sim
    from repro.core.scenario import build_scenarios
    from repro.launch.sweep import make_sweep_fn, stack_policies

    cfg = SimConfig(n_jobs=max(10, n_containers // 3), n_tasks=n_containers,
                    n_containers=n_containers, horizon=horizon)
    n_leaf = max(4, n_hosts // 5)
    specs = bench_scenarios()
    net_spec, sims, rps = build_scenarios(
        specs, cfg, n_hosts=n_hosts, n_spine=max(2, n_leaf // 4),
        n_leaf=n_leaf, seeds=(0,))
    pols = list_policies()
    pol = stack_policies(pols)
    cells = len(pols) * len(specs)

    jax.clear_caches()
    fn = make_sweep_fn(cfg, net_spec.n_hosts, net_spec.n_nodes, horizon)
    t0 = time.time()
    fn(sims, pol, rps)[0].t.block_until_ready()
    cold = time.time() - t0

    # Warm standalone reference: mean steady cell over ALL (policy,
    # scenario) cells — the denominator of the vmapped per-cell tax.
    # Scenarios do genuinely different amounts of work (lossy fabrics
    # retransmit, bursts pile up queues), so a baseline-scenario-only
    # reference would overstate the tax.  One compilation covers all
    # cells (policy and runtime params are data), so this is warm after
    # the first pass.  The sweep reps and the standalone passes are
    # INTERLEAVED in rounds, taking the min over rounds of each side:
    # host-level contention on a shared box is bursty on the minutes
    # scale, and measuring numerator and denominator minutes apart turns
    # one burst into a bogus tax ratio — with interleaving, any clean
    # round yields a clean ratio.
    def solo_pass():
        solo = 0.0
        for s in range(len(specs)):
            sim0 = jax.tree.map(lambda x: x[s, 0], sims)
            rp0 = jax.tree.map(lambda x: x[s], rps)
            for p in pols:
                solo += _timed(
                    lambda: run_sim(sim0, cfg, get_policy(p),
                                    net_spec.n_hosts, net_spec.n_nodes,
                                    horizon,
                                    params=rp0)[0].t.block_until_ready())
        return solo / cells

    solo_pass()                                   # warm every cell's cache
    sweeps, solos = [], []
    for _ in range(4):
        sweeps.append(_timed(
            lambda: fn(sims, pol, rps)[0].t.block_until_ready()))
        solos.append(solo_pass())
    steady = min(sweeps)
    standalone_cell = min(solos)

    out = {
        "n_hosts": n_hosts,
        "n_containers": n_containers,
        "horizon": horizon,
        "policies": len(pols),
        "scenarios": len(specs),
        "seeds": 1,
        "cells": cells,
        "vmap_axes": "policy,scenario,seed",
        "compile_cache_misses": fn._cache_size(),
        "sweep_cold_s": round(cold, 2),
        "sweep_steady_s": round(steady, 2),
        "cells_per_s": round(cells / max(steady, 1e-9), 2),
        "per_cell_steady_s": round(steady / cells, 4),
        "standalone_cell_s": round(standalone_cell, 4),
        "vmap_cell_tax": round(steady / cells / max(standalone_cell, 1e-9),
                               2),
    }
    if with_loop:
        total = 0.0
        for s in range(len(specs)):
            sim0 = jax.tree.map(lambda x: x[s, 0], sims)
            rp = jax.tree.map(lambda x: x[s], rps)
            for p in pols:
                jax.clear_caches()
                t0 = time.time()
                run_sim(sim0, cfg, get_policy(p), net_spec.n_hosts,
                        net_spec.n_nodes, horizon,
                        params=rp)[0].t.block_until_ready()
                total += time.time() - t0
        out["per_point_cold_loop_s"] = round(total, 2)
        out["sweep_speedup_vs_loop"] = round(total / cold, 2)
    return out


def measure_tune_point(n_hosts: int, n_containers: int, horizon: int,
                       samples: int) -> dict:
    """Weight-search smoke: ``samples`` weight vectors x 3 scenarios x 1
    seed through the compiled sweep (one jit; ``run_tune``'s wall clock
    includes the cold compile after ``clear_caches``).  Also records how
    much the best random sample improves on the registered incumbent —
    the simplest tracked signal that the search finds signal."""
    import jax

    from repro.core import SimConfig
    from repro.launch.tune import run_tune

    cfg = SimConfig(n_jobs=max(10, n_containers // 3), n_tasks=n_containers,
                    n_containers=n_containers, horizon=horizon)
    n_leaf = max(4, n_hosts // 5)
    jax.clear_caches()
    res = run_tune(n_samples=samples, seeds=(0,), cfg=cfg, n_hosts=n_hosts,
                   n_spine=max(2, n_leaf // 4), n_leaf=n_leaf,
                   objective="avg_runtime", reps=3)
    import numpy as np
    cells = samples * len(res.scenarios) * len(res.seeds)
    incumbent, best = float(res.scores[0]), float(res.scores[res.best])
    return {
        "n_hosts": n_hosts,
        "n_containers": n_containers,
        "horizon": horizon,
        "samples": samples,
        "scenarios": len(res.scenarios),
        "seeds": len(res.seeds),
        "cells": cells,
        "compile_cache_misses": res.compile_cache_misses,
        "tune_cold_s": res.wall_s,
        # min warm repeat of the SAME compiled call — runtime-dominated,
        # unlike the cold wall (mostly XLA compile on this small grid);
        # this is the number check_regression's ratio pack gates
        "tune_steady_s": res.steady_s,
        "cells_per_s": round(cells / max(res.steady_s or res.wall_s, 1e-9),
                             2),
        "objective": res.objective,
        "incumbent_score": round(incumbent, 4),
        "best_score": round(best, 4),
        "best_vs_incumbent": (round(incumbent / best, 4)
                              if np.isfinite(best) and best > 0 else None),
    }


def measure_tune_grad_point(n_hosts: int, n_containers: int, horizon: int,
                            steps: int, batch: int) -> dict:
    """Differentiable-tuning smoke (ISSUE 9): descend the soft-placement
    surrogate with ``jax.grad`` through the compiled sweep
    (``run_tune_grad``: one value_and_grad executable + one hard-oracle
    executable, tau annealed as a traced RunParams field), then race the
    SAME oracle budget of random search through ``run_tune``.  Tracked
    numbers are within-run and machine-independent:

    * ``grad_vs_random``    — random-best / grad-best oracle score on the
      minimized objective (>1 means gradient search wins at equal budget
      — the ISSUE 9 acceptance claim);
    * ``grad_vs_incumbent`` — incumbent / grad-best (>= 1 by
      construction: the incumbent is oracle-scored before step 0);
    * ``compile_cache_misses`` — must stay at 2 (surrogate + oracle);
      tau/weights ride traced leaves, so annealing never recompiles.

    The cold wall is compile-bound at smoke scale and stays out of
    check_regression's skew-normalized ratio pack (like tune_cold_s)."""
    import jax
    import numpy as np

    from repro.core import SimConfig
    from repro.core.scenario import ScenarioSpec
    from repro.launch.tune import run_tune, run_tune_grad

    cfg = SimConfig(n_jobs=max(10, n_containers // 4), n_tasks=n_containers,
                    n_containers=n_containers, horizon=horizon,
                    arrival_window=10.0, placements_per_tick=16,
                    migrations_per_tick=2)
    scen = [ScenarioSpec("slow_net", bw=200.0)]
    jax.clear_caches()
    t0 = time.time()
    g = run_tune_grad(steps=steps, batch=batch, lr=0.3, eval_every=3,
                      seeds=(0,), scenarios=scen, cfg=cfg, n_hosts=n_hosts,
                      n_spine=2, n_leaf=4, objective="avg_runtime", seed=0)
    grad_wall = time.time() - t0
    # the equal-budget random arm: as many oracle-scored samples as the
    # grad run spent, same base/space/seed machinery, same hard oracle —
    # its row 0 is the untouched incumbent, which the grad result does
    # not carry separately
    r = run_tune(n_samples=g.oracle_evals, seeds=(0,), scenarios=scen,
                 cfg=cfg, n_hosts=n_hosts, n_spine=2, n_leaf=4,
                 objective="avg_runtime", seed=0)
    random_best = float(r.scores[r.best])
    incumbent = float(r.scores[0])

    def vs(a, b):
        return (round(a / b, 4)
                if np.isfinite(a) and np.isfinite(b) and b > 0 else None)

    return {
        "n_hosts": n_hosts,
        "n_containers": n_containers,
        "horizon": horizon,
        "steps": steps,
        "batch": batch,
        "scenarios": len(scen),
        "seeds": 1,
        "objective": g.objective,
        "surrogate": g.surrogate_name,
        "compile_cache_misses": g.compile_cache_misses,
        "tune_grad_cold_s": round(grad_wall, 2),
        "surrogate_evals": g.surrogate_evals,
        "oracle_evals": g.oracle_evals,
        "tau_final": g.history[-1]["tau"] if g.history else None,
        "incumbent_score": round(incumbent, 4),
        "best_oracle": round(g.best_oracle, 4),
        "random_best": round(random_best, 4),
        "grad_vs_incumbent": vs(incumbent, g.best_oracle),
        "grad_vs_random": vs(random_best, g.best_oracle),
    }


def measure_telescope_point(n_hosts: int, n_containers: int, horizon: int,
                            seeds: int, chunk: int, interval: int) -> dict:
    """Event-horizon telescoping (ISSUE 10): the vmapped streaming run at
    a sparse-event long horizon, macro-tick engine off vs on.

    The off arm is the PR 7 chunked per-tick path; the on arm is
    ``engine.simulate_telescoped`` through the same driver
    (``run_sim_vmapped(telescope=True)``).  Tracked numbers:

    * ``finals_bitwise_equal`` — telescoping is an exact transform; the
      final states must agree to the bit (hard gate);
    * ``telescope_speedup``   — within-run off/on wall ratio (the >= 3x
      ISSUE 10 acceptance; machine-independent);
    * ``on_ticks_per_s``      — the ON-side throughput for the
      skew-normalized ratio pack;
    * ``n_full_ticks_seed0``  — how many ticks actually ran as full ticks
      on seed 0 (``with_stats``), i.e. how much telescoping there was.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import (SimConfig, build_paper_network, get_policy,
                            init_sim, paper_workload, scaled_hosts)
    from repro.core import stats
    from repro.core.engine import simulate_telescoped
    from repro.launch.sweep import run_sim_vmapped

    cfg = SimConfig(n_jobs=max(4, n_containers // 3), n_tasks=n_containers,
                    n_containers=n_containers, horizon=horizon,
                    placements_per_tick=1, migrations_per_tick=1,
                    waterfill_rounds=2, delay_update_interval=interval)
    hosts = scaled_hosts(n_hosts, 2)
    spec, net = build_paper_network(cfg, n_hosts=n_hosts, n_spine=2,
                                    n_leaf=2)
    pol = get_policy("firstfit")
    params = cfg.run_params()
    sim_list = [init_sim(hosts, paper_workload(cfg, seed=s), net, seed=s)
                for s in range(seeds)]
    sims = jax.tree.map(lambda *xs: jnp.stack(xs), *sim_list)

    def timed(telescope: bool):
        def run():
            return run_sim_vmapped(sims, cfg, pol, spec.n_hosts,
                                   spec.n_nodes, horizon, params=params,
                                   chunk=chunk, telescope=telescope)
        f, s = run()                                  # compile + warm
        jax.tree.leaves(f)[0].block_until_ready()
        t0 = time.time()
        f, s = run()
        jax.tree.leaves(f)[0].block_until_ready()
        return time.time() - t0, f, s

    off_t, off_f, off_s = timed(False)
    on_t, on_f, on_s = timed(True)

    def close(a, b):
        return all(np.allclose(np.asarray(x), np.asarray(y),
                               rtol=3e-6, atol=1e-6)
                   for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))

    import functools
    n_full_fn = jax.jit(functools.partial(
        simulate_telescoped, cfg=cfg, policy=pol, n_hosts=spec.n_hosts,
        n_nodes=spec.n_nodes, chunk=horizon, params=params,
        with_stats=True))
    _, _, n_full = n_full_fn(sim_list[0], stats.acc_init(),
                             jnp.zeros((), jnp.int32))
    total_ticks = horizon * seeds
    return {
        "n_hosts": n_hosts,
        "n_containers": n_containers,
        "horizon": horizon,
        "seeds": seeds,
        "chunk": chunk,
        "delay_update_interval": interval,
        "policy": "firstfit",
        "off_wall_s": round(off_t, 2),
        "on_wall_s": round(on_t, 2),
        "off_ticks_per_s": round(total_ticks / max(off_t, 1e-9), 1),
        "on_ticks_per_s": round(total_ticks / max(on_t, 1e-9), 1),
        "telescope_speedup": round(off_t / max(on_t, 1e-9), 2),
        "finals_bitwise_equal": _trees_bitwise_equal(off_f, on_f),
        "summary_close": close(off_s, on_s),
        "n_full_ticks_seed0": int(n_full),
        "full_tick_fraction": round(int(n_full) / horizon, 4),
    }


def _trees_bitwise_equal(a, b) -> bool:
    """Leaf-by-leaf byte equality (NaN-safe: same bits compare equal)."""
    import jax
    import numpy as np

    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    if len(la) != len(lb):
        return False
    for x, y in zip(la, lb):
        x, y = np.asarray(x), np.asarray(y)
        if x.shape != y.shape or x.dtype != y.dtype \
                or x.tobytes() != y.tobytes():
            return False
    return True


def measure_dist_point(n_hosts: int, n_containers: int, horizon: int,
                       chunk: int, slab: int) -> dict:
    """Multi-process sweep fabric smoke (ISSUE 8): the same grid through
    (a) the in-process streamed sweep — the bit-identity reference — then
    three SPAWNED arms: 1 process, 2 processes, and 2 processes with the
    overlapped slab driver disabled.  Every arm must reproduce the
    reference finals+summary bit-for-bit and compile at most twice per
    process (steady jstep + final-slab remainder).  Spawned walls are
    COLD (python + jax import and XLA compile dominate at smoke scale),
    so they stay out of check_regression's skew-normalized ratio pack —
    the tracked numbers are the within-run ratios:

    * ``overlap_ratio``       — serial / overlapped max worker wall at
      2 processes (>1 means the overlapped gather hides transfer time);
    * ``dist_parallel_ratio`` — 1-proc / 2-proc max worker wall.

    On a single-core box both sit near 1.0 BY DESIGN: two worker
    processes time-share the core and there is no spare compute to hide
    gathers under.  The committed baseline records whatever the bench box
    offers and the gate compares like-for-like (plus cross-backend skip).
    """
    import jax

    from repro.core import ExecPlan, SimConfig, list_policies
    from repro.launch import dist
    from repro.launch.sweep import run_sweep

    cfg = SimConfig(n_jobs=max(10, n_containers // 3), n_tasks=n_containers,
                    n_containers=n_containers, horizon=horizon)
    n_leaf = max(4, n_hosts // 5)
    n_spine = max(2, n_leaf // 4)
    pols = list_policies()
    specs = bench_scenarios()
    cells = len(pols) * len(specs)

    jax.clear_caches()
    t0 = time.time()
    ref = run_sweep(pols, specs, seeds=(0,), cfg=cfg, n_hosts=n_hosts,
                    n_spine=n_spine, n_leaf=n_leaf,
                    plan=ExecPlan(chunk=chunk, slab=slab))
    inproc_wall = time.time() - t0

    def arm(num_procs: int, overlap: bool) -> dict:
        res = dist.run_dist_sweep(
            pols, specs, seeds=(0,), cfg=cfg, n_hosts=n_hosts,
            n_spine=n_spine, n_leaf=n_leaf,
            plan=ExecPlan(procs=num_procs, devices_per_proc=1, chunk=chunk,
                          slab=slab, overlap=overlap),
            timeout_s=600.0)
        metas = sorted(res.worker_meta, key=lambda m: m["process_index"])
        return {
            "procs": num_procs,
            "overlap": overlap,
            "backend": ",".join(sorted({m["backend"] for m in metas})),
            "wall_s": res.wall_s,
            "max_worker_wall_s": round(max(m["wall_s"] for m in metas), 2),
            "compile_cache_misses": res.compile_cache_misses,
            "slabs_per_worker": [len(m["slabs"]) for m in metas],
            "finals_match": (
                _trees_bitwise_equal(res.finals, ref.finals)
                and _trees_bitwise_equal(res.summary, ref.summary)),
        }

    arms = {
        "1proc": arm(1, True),
        "2proc": arm(2, True),
        "2proc_serial": arm(2, False),
    }

    def ratio(num, den):
        return round(arms[num]["max_worker_wall_s"]
                     / max(arms[den]["max_worker_wall_s"], 1e-9), 2)

    return {
        # the platform the workers ran on, from their own metadata
        "backend": ",".join(sorted({a["backend"] for a in arms.values()})),
        "n_hosts": n_hosts,
        "n_containers": n_containers,
        "horizon": horizon,
        "policies": len(pols),
        "scenarios": len(specs),
        "seeds": 1,
        "cells": cells,
        "chunk": chunk,
        "slab": slab,
        "devices_per_proc": 1,
        "inproc_wall_s": round(inproc_wall, 2),
        "arms": arms,
        "overlap_ratio": ratio("2proc_serial", "2proc"),
        "dist_parallel_ratio": ratio("1proc", "2proc"),
        "finals_match": all(a["finals_match"] for a in arms.values()),
    }


def bench_engine(quick: bool = False):
    """Rows + claims for benchmarks.run; writes BENCH_engine.json."""
    # first, while this process has not touched JAX: the longhorizon entry
    # measures child processes, and a child can only claim an accelerator
    # this process does not hold
    from benchmarks.longhorizon_bench import measure_longhorizon
    longhorizon = measure_longhorizon(quick=quick)
    import jax

    points = []
    # small tracking points (cheap, both engines)
    for sparse in (True, False):
        points.append(measure_scale_point(100, 1500, horizon=40,
                                          sparse=sparse))
    # kernel ladder, small rung (both modes, so the CI quick gate covers
    # the dispatch path): APSP delay refresh, kernel-on vs kernel-off
    for kernels in ("auto", "off"):
        points.append(measure_scale_point(100, 1500, horizon=40,
                                          delay_mode="fw", kernels=kernels))
    # the headline comparison: 500 hosts / 3000 containers, same run
    if not quick:
        for sparse in (True, False):
            points.append(measure_scale_point(500, 3000, horizon=40,
                                              sparse=sparse))
        # policy ladder at the headline scale: static score vs the two
        # scan-carried co-location scores (jobgroup, netaware)
        for pol in ("jobgroup", "netaware"):
            points.append(measure_scale_point(500, 3000, horizon=40,
                                              policy=pol))
        # kernel ladder, headline + ceiling rungs.  The fw refresh is the
        # O(N^3) hot loop the fw_minplus kernel fuses; the 2000h point runs
        # horizon 30 (3 refreshes) because the CPU jnp reference costs ~10 s
        # per refresh at N=2500 — the ladder's point is the TPU/GPU rows,
        # where 'auto' resolves to the compiled kernel.
        for kernels in ("auto", "off"):
            points.append(measure_scale_point(500, 3000, horizon=40,
                                              delay_mode="fw",
                                              kernels=kernels))
            points.append(measure_scale_point(2000, 6000, horizon=30,
                                              delay_mode="fw",
                                              kernels=kernels))
        # beyond the dense ceiling: sparse-only 2000-host point.  Horizon 60
        # (was 20): with ~30-unit durations and a 36 s arrival window, no
        # container can FINISH inside 20 ticks, so the point used to report
        # completed: 0 and validated nothing end-to-end.
        p2000 = measure_scale_point(2000, 6000, horizon=60, sparse=True)
        assert p2000["completed"] > 0, (
            f"2000-host point completed nothing — horizon too short to "
            f"validate end-to-end behavior: {p2000}")
        points.append(p2000)

    def tps(h, c, mode, policy="firstfit", delay_mode="path",
            kernels="off"):
        for p in points:
            if ((p["n_hosts"], p["n_containers"], p["mode"],
                 p.get("policy", "firstfit"), p.get("delay_mode", "path"),
                 p.get("kernels", "off"))
                    == (h, c, mode, policy, delay_mode, kernels)):
                return p["ticks_per_s"]
        return None

    cmp_h, cmp_c = (100, 1500) if quick else (500, 3000)
    sp, de = tps(cmp_h, cmp_c, "sparse"), tps(cmp_h, cmp_c, "dense")
    speedup = round(sp / de, 2) if sp and de else None
    # the sweep entry: quick mode measures a small grid (compile-once +
    # regression-gate numbers for CI); full mode measures the 500h/3000c
    # grid against the per-point cold loop (the ISSUE 3 >=3x acceptance)
    # AND re-measures the quick grid into ``sweep_quick`` — the committed
    # baseline benchmarks/check_regression.py gates quick CI runs against
    if quick:
        sweep = measure_sweep_point(**QUICK_SWEEP, with_loop=False)
        sweep_quick = None
    else:
        sweep = measure_sweep_point(500, 3000, horizon=20, with_loop=True)
        sweep_quick = measure_sweep_point(**QUICK_SWEEP, with_loop=False)
    tune = measure_tune_point(**TUNE_SMOKE)
    # the differentiable-tuning arm (ISSUE 9): measured in BOTH modes on
    # the same smoke grid — the gated numbers (grad_vs_random, the 2-
    # executable compile bill) are within-run and machine-independent
    tune_grad = measure_tune_grad_point(**TUNE_GRAD_SMOKE)
    # the multi-process fabric arms (ISSUE 8): measured in BOTH modes on
    # the same smoke grid so the CI quick gate has a like-for-like
    # committed twin (bit-identity + compile bill + overlap ratio).  The
    # fabric refuses an accelerator host (its workers could not claim the
    # chip this process holds), so there the entry says so instead.
    backend = jax.default_backend()
    if backend == "cpu":
        sweep_dist = measure_dist_point(**DIST_SMOKE)
    else:
        sweep_dist = {"backend": backend,
                      "not_measured": f"the dist fabric refuses a "
                                      f"{backend} host"}
    # the telescoping arm (ISSUE 10): measured in BOTH modes on the same
    # sparse-event long-horizon grid — the gated numbers (bitwise
    # equality, the within-run on/off speedup) are machine-independent
    telescope = measure_telescope_point(**TELESCOPE_SMOKE)
    sweep["backend"] = backend
    tune["backend"] = backend
    tune_grad["backend"] = backend
    telescope["backend"] = backend
    out = {
        "bench": "engine_tick_throughput",
        "backend": backend,
        "device": jax.devices()[0].device_kind,
        "points": points,
        "comparison_point": {"n_hosts": cmp_h, "n_containers": cmp_c},
        "sparse_speedup": speedup,
        "sweep": sweep,
        "tune": tune,
        "tune_grad": tune_grad,
        "sweep_dist": sweep_dist,
        "telescope": telescope,
        "longhorizon": longhorizon,
    }
    if sweep_quick is not None:
        sweep_quick["backend"] = backend
        out["sweep_quick"] = sweep_quick
    if not quick:
        out["policy_comparison"] = {
            pol: tps(500, 3000, "sparse", pol)
            for pol in ("firstfit", "jobgroup", "netaware")
        }
    path = BENCH_QUICK_PATH if quick else BENCH_PATH
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    kon = tps(cmp_h if not quick else 100, cmp_c if not quick else 1500,
              "sparse", delay_mode="fw", kernels="auto")
    koff = tps(cmp_h if not quick else 100, cmp_c if not quick else 1500,
               "sparse", delay_mode="fw", kernels="off")
    claims = [
        (f"sparse vs dense ticks_per_s @ {cmp_h}h/{cmp_c}c",
         f"{sp} vs {de} ({speedup}x)"),
        (f"fw kernel ladder [{backend}] kernels=auto vs off ticks_per_s",
         f"{kon} vs {koff}"
         + ("" if backend in ("tpu", "gpu")
            else " (CPU: 'auto' -> jnp ref; pair separates on TPU/GPU)")),
        (f"sweep {sweep['cells']} cells @ {sweep['n_hosts']}h "
         f"compiled {sweep['compile_cache_misses']}x, vmap all axes",
         f"cold {sweep['sweep_cold_s']}s, steady {sweep['sweep_steady_s']}s, "
         f"per-cell {sweep['per_cell_steady_s']}s = "
         f"{sweep['vmap_cell_tax']}x standalone"
         + (f", {sweep['sweep_speedup_vs_loop']}x vs per-point cold loop"
            if "sweep_speedup_vs_loop" in sweep else "")),
        (f"tune {tune['cells']} cells ({tune['samples']} weight samples) "
         f"compiled {tune['compile_cache_misses']}x",
         f"cold {tune['tune_cold_s']}s, best/incumbent "
         f"{tune['best_vs_incumbent']}x on {tune['objective']}"),
        (f"tune-grad {tune_grad['steps']} steps x {tune_grad['batch']} "
         f"candidates ({tune_grad['compile_cache_misses']} executables: "
         f"surrogate grad + hard oracle)",
         f"oracle best {tune_grad['best_oracle']} vs random "
         f"{tune_grad['random_best']} at {tune_grad['oracle_evals']} "
         f"oracle evals = {tune_grad['grad_vs_random']}x, "
         f"vs incumbent {tune_grad['grad_vs_incumbent']}x on "
         f"{tune_grad['objective']}"),
        ("dist fabric", sweep_dist["not_measured"])
        if "not_measured" in sweep_dist else
        (f"dist fabric {sweep_dist['cells']} cells (chunk "
         f"{sweep_dist['chunk']}, slab {sweep_dist['slab']}) x "
         f"{{1,2}} procs",
         f"bitwise match: {sweep_dist['finals_match']}, "
         f"overlap {sweep_dist['overlap_ratio']}x, 2-proc parallel "
         f"{sweep_dist['dist_parallel_ratio']}x, compiles/process <= "
         f"{max(a['compile_cache_misses'] for a in sweep_dist['arms'].values())}"),
        (f"telescope @ {telescope['horizon']} ticks x "
         f"{telescope['seeds']} seeds (refresh interval "
         f"{telescope['delay_update_interval']})",
         f"on {telescope['on_ticks_per_s']} vs off "
         f"{telescope['off_ticks_per_s']} ticks/s = "
         f"{telescope['telescope_speedup']}x, bitwise equal: "
         f"{telescope['finals_bitwise_equal']}, full ticks seed0: "
         f"{telescope['n_full_ticks_seed0']}/{telescope['horizon']}"),
        (f"longhorizon streaming @ {longhorizon['horizon']} ticks x "
         f"{longhorizon['seeds']} seeds",
         f"{longhorizon['stream']['max_rss_mb']} MB peak RSS, "
         f"{longhorizon['stream']['ticks_per_s']} ticks/s"
         + (f"; stacked exceeded {longhorizon['ceiling_mb']} MB ceiling: "
            f"{longhorizon['stacked']['exceeded_ceiling']}"
            if "stacked" in longhorizon else " (quick: streaming only)")),
        ("json", os.path.abspath(path)),
    ]
    if not quick:
        p2000 = [p for p in points if p["n_hosts"] == 2000]
        if p2000:
            claims.append(("2000-host point (dense cannot run)",
                           f"{p2000[0]['ticks_per_s']} ticks/s, "
                           f"{p2000[0]['state_mb']} MB state"))
        claims.append(("policy ticks/s @ 500h/3000c "
                       "(firstfit vs jobgroup vs netaware)",
                       str(out.get("policy_comparison"))))
        # every full refresh appends one headline row to the perf-history
        # log (deduped by content digest — a no-change rerun appends none)
        from benchmarks.archive import HISTORY_PATH, append_history
        claims.append(("bench history",
                       f"appended={append_history()} -> "
                       f"{os.path.abspath(HISTORY_PATH)}"))
    return points, claims


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="only the 100-host tracking points")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    rows, claims = bench_engine(quick=args.quick)
    for r in rows:
        print(r)
    for c in claims:
        print(f"# {c[0]}: {c[1]}")


if __name__ == "__main__":
    main()
