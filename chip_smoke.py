"""Smoke run of the simulator's main path on a TPU chip.

    python chip_smoke.py              # one chip: phases 1-4 below
    python chip_smoke.py --chips 4    # four chips: the sharded sweep only

Every input is built from ``--seed``; the phases drive the public entry
points (``init_sim``, ``run_sim`` with an ``ExecPlan``, ``run_sweep``).

1. Device check: exits non-zero unless JAX's first device is a TPU.
2. Paper testbed, 20 hosts / 300 containers: all six policies, horizon
   120; every policy completes all 300 containers.
3. 500 hosts / 3000 containers, ``netaware``, ``path`` delays, kernels
   'auto' (the compiled ``seg_waterfill``), horizon 40: the stacked and
   the streamed (donated-carry) finals are bit-equal, and on a mid-run
   state the kernel's flow allocation meets the docs/kernels.md contract
   against the jnp reference (rates bit-exact, link utilisation within
   rtol 2e-6).
4. 2000 hosts / 6000 containers, ``fw`` delays, kernels 'auto' (compiled
   ``fw_minplus`` and ``seg_waterfill``), horizon 30, end to end; on a
   mid-run state the kernel's delay refresh matches the jnp reference at
   rtol 1e-5.

``--chips 4`` runs only the 24-cell sweep (6 policies x 4 scenarios) at
500h/3000c with its flattened cell axis split over four chips, and the
same grid on one chip: finals, per-tick metrics and per-cell summaries
must be bit-equal.

Each phase prints one informational JSON line (compile and steady
seconds, completed and deployed counts, peak device bytes).  The last
line is ``{"ok": true, "device": {...}}``; a failed check raises, and the
script then exits non-zero without printing it.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks.engine_bench import bench_scenarios  # noqa: E402
from repro.core import (ExecPlan, SimConfig, build_paper_hosts,  # noqa: E402
                        build_paper_network, get_policy, init_sim,
                        list_policies, paper_workload, run_sim,
                        scaled_hosts, simulate, summarize)
from repro.core import network  # noqa: E402
from repro.core.engine import phase_flows  # noqa: E402
from repro.core.network import SpineLeafSpec, build_network  # noqa: E402
from repro.core.types import (STATUS_COMMUNICATING,  # noqa: E402
                              STATUS_COMPLETED, STATUS_MIGRATING,
                              STATUS_RUNNING)
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.sweep import run_sweep  # noqa: E402


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def require_tpu() -> dict:
    devices = jax.devices()
    d = devices[0]
    if d.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX's first device is "
                 f"{d.platform!r}, {d.device_kind!r}); this script only "
                 f"runs on a TPU")
    info = {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}
    emit("device", jax=jax.__version__, **info)
    return info


def peak_bytes(device=None) -> int:
    """Peak device bytes so far in this process (not reset per phase)."""
    device = device or jax.devices()[0]
    return int(device.memory_stats()["peak_bytes_in_use"])


def timed(fn):
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


def counts(final) -> dict:
    status = np.asarray(final.containers.status)
    return {"completed": int((status == STATUS_COMPLETED).sum()),
            "deployed": int(np.isin(status, [STATUS_RUNNING,
                                             STATUS_COMMUNICATING,
                                             STATUS_MIGRATING]).sum())}


def bitwise_diff(a, b) -> list[str]:
    """Leaves of two pytrees that differ, each with its largest gap."""
    out = []
    for (path, x), y in zip(jax.tree_util.tree_flatten_with_path(a)[0],
                            jax.tree.leaves(b)):
        x, y = np.asarray(x), np.asarray(y)
        if not np.array_equal(x, y):
            gap = np.abs(x.astype(np.float64) - y.astype(np.float64)).max()
            out.append(f"{jax.tree_util.keystr(path)}: "
                       f"{int((x != y).sum())} of {x.size} differ, "
                       f"max |diff| {gap!r}")
    return out


def kernel_calls(fn, *args) -> int:
    """Mosaic kernels in the lowered program: a kernel run by the Pallas
    interpreter lowers to plain XLA ops and is not counted."""
    return jax.jit(fn).lower(*args).as_text().count("tpu_custom_call")


def fleet(n_hosts: int, n_containers: int, horizon: int, seed: int,
          **cfg_kw):
    """The engine benchmark's scaled fleet (benchmarks/common.py)."""
    cfg = SimConfig(n_jobs=max(10, n_containers // 3), n_tasks=n_containers,
                    n_containers=n_containers, horizon=horizon, **cfg_kw)
    n_leaf = max(4, n_hosts // 5)
    spec = SpineLeafSpec(n_spine=max(2, n_leaf // 4), n_leaf=n_leaf,
                         n_hosts=n_hosts)
    sim0 = init_sim(scaled_hosts(n_hosts, n_leaf),
                    paper_workload(cfg, seed=seed), build_network(spec),
                    seed=seed)
    return cfg, spec, sim0


def phase_paper(seed: int) -> None:
    cfg = SimConfig(horizon=120)
    spec, net = build_paper_network(cfg)
    sim0 = init_sim(build_paper_hosts(), paper_workload(cfg, seed=seed), net,
                    seed=seed)
    walls, done = [], {}
    for name in list_policies():
        (final, metrics), wall = timed(lambda: run_sim(
            sim0, cfg, get_policy(name), spec.n_hosts, spec.n_nodes,
            cfg.horizon))
        walls.append(wall)
        done[name] = summarize(final, metrics)["n_completed"]
    # one compile serves every policy (a policy is data): the first run
    # pays it, the rest are steady
    steady = min(walls[1:])
    emit("paper_testbed", hosts=spec.n_hosts, containers=cfg.n_containers,
         horizon=cfg.horizon, compile_s=walls[0] - steady, steady_s=steady,
         completed=done, peak_bytes_in_use=peak_bytes())
    short = {k: v for k, v in done.items() if v != cfg.n_containers}
    assert not short, f"policies that did not complete every container: " \
                      f"{short}"


def phase_500h(seed: int, hosts: int = 500, containers: int = 3000) -> None:
    cfg, spec, sim0 = fleet(hosts, containers, 40, seed, delay_mode="path")
    pol, rp = get_policy("netaware"), cfg.run_params()
    H, N = spec.n_hosts, spec.n_nodes
    n_kernels = kernel_calls(
        lambda s: simulate(s, cfg, pol, H, N, 1, rp), sim0)
    assert n_kernels >= 1, "seg_waterfill is not compiled into the tick"

    def run(plan=None, horizon=cfg.horizon):
        return run_sim(sim0, cfg, pol, H, N, horizon, plan=plan)

    (stacked, _), first = timed(run)
    (stacked, _), steady = timed(run)
    (streamed, _), stream_first = timed(lambda: run(ExecPlan(chunk=20)))
    (streamed, _), stream_steady = timed(lambda: run(ExecPlan(chunk=20)))
    diff = bitwise_diff(stacked, streamed)
    assert not diff, f"stacked and streamed finals differ: {diff}"

    # the allocation tick 21 would make, from the state after tick 20
    (mid, _), _ = timed(lambda: run(ExecPlan(chunk=20), horizon=20))
    flows = jax.jit(phase_flows, static_argnames=("cfg", "use_kernel"))
    k_sim, _, _, active, k_rates = flows(mid, cfg, use_kernel=True)
    r_sim, _, _, _, r_rates = flows(mid, cfg, use_kernel=False)
    k_util = np.asarray(k_sim.net.link_util)
    r_util = np.asarray(r_sim.net.link_util)
    n_active = int(np.asarray(active).sum())
    assert n_active > 0, "no active flow at the mid-run state"
    rates_equal = np.array_equal(np.asarray(k_rates), np.asarray(r_rates))
    util_ulps = int(np.abs(k_util.view(np.int32).astype(np.int64)
                           - r_util.view(np.int32)).max())
    emit(f"{hosts}h_{containers}c", delay_mode=cfg.delay_mode, policy="netaware",
         horizon=cfg.horizon, kernel_calls=n_kernels,
         compile_s=first - steady, steady_s=steady,
         streamed_compile_s=stream_first - stream_steady,
         streamed_steady_s=stream_steady, stacked_eq_streamed=True,
         mid_tick=20, active_flows=n_active, rates_bit_equal=rates_equal,
         util_max_ulps=util_ulps, **counts(stacked),
         peak_bytes_in_use=peak_bytes())
    assert rates_equal, "seg_waterfill rates differ from the jnp reference"
    np.testing.assert_allclose(k_util, r_util, rtol=2e-6, atol=0)


def phase_2000h(seed: int, hosts: int = 2000, containers: int = 6000
                ) -> None:
    cfg, spec, sim0 = fleet(hosts, containers, 30, seed, delay_mode="fw")
    pol, rp = get_policy("netaware"), cfg.run_params()
    H, N = spec.n_hosts, spec.n_nodes
    n_kernels = kernel_calls(
        lambda s: simulate(s, cfg, pol, H, N, 1, rp), sim0)
    assert n_kernels >= 2, "fw_minplus and seg_waterfill are not both " \
                           "compiled into the tick"
    plan = ExecPlan(chunk=15)

    # the first call compiles the chunk step and stops at the mid-run
    # state; the full horizon then reuses it
    (mid, _), first = timed(lambda: run_sim(sim0, cfg, pol, H, N, 15,
                                            plan=plan))
    (final, _), steady = timed(lambda: run_sim(sim0, cfg, pol, H, N,
                                               cfg.horizon, plan=plan))
    refresh = jax.jit(network.update_delay_matrix, static_argnames=(
        "n_hosts", "n_nodes", "mode", "use_kernel"))
    (k_net, r_net), _ = timed(lambda: tuple(
        refresh(mid.net, n_hosts=H, n_nodes=N, mode="fw", use_kernel=k)
        for k in (True, False)))
    k_d, r_d = np.asarray(k_net.delay_matrix), np.asarray(r_net.delay_matrix)
    rel = float((np.abs(k_d - r_d) / np.maximum(np.abs(r_d), 1e-30)).max())
    c = counts(final)
    emit(f"{hosts}h_{containers}c", delay_mode=cfg.delay_mode, policy="netaware",
         horizon=cfg.horizon, network_nodes=N, kernel_calls=n_kernels,
         compile_s=first - steady / 2, steady_s=steady, mid_tick=15,
         delay_max_rel_diff=rel, **c, peak_bytes_in_use=peak_bytes())
    assert c["completed"] + c["deployed"] > 0, "nothing was deployed"
    np.testing.assert_allclose(k_d, r_d, rtol=1e-5)


def phase_sweep(seed: int, chips: int, hosts: int = 500,
                containers: int = 3000) -> None:
    devices = jax.devices()
    assert len(devices) >= chips, f"{chips} chips asked, {len(devices)} seen"
    cfg = SimConfig(n_jobs=max(10, containers // 3), n_tasks=containers,
                    n_containers=containers, horizon=20)
    n_leaf = max(4, hosts // 5)
    grid = dict(policies=list_policies(), scenarios=bench_scenarios(),
                seeds=(seed,), cfg=cfg, n_hosts=hosts,
                n_spine=max(2, n_leaf // 4), n_leaf=n_leaf)
    one = run_sweep(**grid, plan=ExecPlan(devices=1))
    split = run_sweep(**grid, plan=ExecPlan(devices=chips))
    diff = bitwise_diff((one.finals, one.metrics),
                        (split.finals, split.metrics))
    # repr: bit-exact for floats, and a NaN (no container finished)
    # matches a NaN
    rows_equal = repr(one.summaries()) == repr(split.summaries())
    emit("sweep", cells=len(one.summaries()), hosts=hosts,
         containers=containers,
         horizon=cfg.horizon, devices=[one.n_devices, split.n_devices],
         wall_s=[one.wall_s, split.wall_s],
         peak_bytes_in_use=[peak_bytes(d) for d in devices[:chips]],
         bit_equal=not diff, summaries_equal=rows_equal, differing=diff)
    assert split.n_devices == chips, split.n_devices
    assert not diff, f"sharded and one-chip sweeps differ: {diff}"
    assert rows_equal, "sharded and one-chip sweep summaries differ"


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sweep split over four chips")
    args = ap.parse_args()
    enable_compile_cache()
    device = require_tpu()
    if args.chips == 4:
        phase_sweep(args.seed, 4)
    else:
        phase_paper(args.seed)
        phase_500h(args.seed)
        phase_2000h(args.seed)
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
