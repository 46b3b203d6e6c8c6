"""Long-horizon memory bench: streaming O(state) vs stacked O(horizon).

The tentpole claim of the streaming engine is a MEMORY property, so it is
measured as one: each measurement runs in its own subprocess (a fresh
process is the only honest max-RSS scope — the parent's warm XLA arenas
would pollute ``ru_maxrss``), reporting its own peak RSS on exit.

One trap makes the child-side peak subtle: ``subprocess`` here uses
fork+exec (``cwd=`` disables the posix_spawn fast path), and between fork
and exec the child *shares the parent's entire resident set*, so its
VmHWM / ``ru_maxrss`` high-water starts at the PARENT's current RSS.
Launched from a warm ``engine_bench`` parent holding >1 GB of XLA arenas,
that inherited peak buries the real measurement (both modes once reported
the identical parent RSS).  The child therefore resets its peak counter
via ``/proc/self/clear_refs`` as its very first act, and the parent-side
ceiling poll reads current ``VmRSS`` (never the fork-tainted ``VmHWM``),
demanding two consecutive over-ceiling samples before killing.

Full mode demonstrates the crossing at one (config, horizon) point:

* the STREAMING child runs ``run_sim_vmapped(..., chunk=...)`` to
  completion and reports its peak RSS — O(seeds x state), independent of
  horizon;
* ``ceiling_mb`` is fixed at 1.25x the streaming peak (rounded up);
* the STACKED child runs the same (seeds, horizon) with stacked per-tick
  metrics.  Its scan-ys buffer (seeds x horizon x 16 f32/i32 fields) is
  allocated up front by XLA, so the parent's ``/proc/<pid>/status`` VmRSS
  poll sees the crossing within seconds and kills the child early —
  ``exceeded_ceiling: true`` plus the RSS at kill — instead of paying the
  hours the full stacked run would take.

Quick mode runs the streaming child only, at a short horizon;
``benchmarks/check_regression.py`` gates its peak RSS against the
committed ``ceiling_mb`` absolutely (same backend only) and its ticks/s
through the skew-normalized ratio pack, and re-asserts that the committed
baseline's stacked child did exceed the ceiling.

    PYTHONPATH=src python -m benchmarks.longhorizon_bench [--quick]
"""
from __future__ import annotations

import argparse
import json
import os
import resource
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(HERE, "..", "src")

# the minimal-tick micro config: small enough that the tick costs ~0.6 ms
# at seeds=8 on CPU, so a few hundred thousand ticks stream in minutes,
# while the stacked ys buffer (seeds x horizon x 64 B) still dwarfs the
# ceiling margin at the full-mode horizon
LONGHORIZON = dict(n_hosts=4, n_containers=16, seeds=8, chunk=4096)
FULL_HORIZON = 400_000      # stacked buffer: 8 x 4e5 x 64 B ~ 205 MB
QUICK_HORIZON = 30_000
CEILING_FACTOR = 1.25       # ceiling = streaming peak x this, rounded up
STACKED_TIMEOUT_S = 600.0


def _build(horizon: int):
    import jax

    from repro.core import SimConfig, get_policy
    from repro.core.scenario import ScenarioSpec, build_scenarios

    lh = LONGHORIZON
    cfg = SimConfig(n_jobs=max(4, lh["n_containers"] // 3),
                    n_tasks=lh["n_containers"],
                    n_containers=lh["n_containers"], horizon=horizon,
                    placements_per_tick=1, migrations_per_tick=1,
                    waterfill_rounds=2, delay_update_interval=100)
    net_spec, sims, rps = build_scenarios(
        [ScenarioSpec("baseline")], cfg, n_hosts=lh["n_hosts"], n_spine=2,
        n_leaf=2, seeds=tuple(range(lh["seeds"])))
    sims1 = jax.tree.map(lambda x: x[0], sims)
    rp1 = jax.tree.map(lambda x: x[0], rps)
    return cfg, net_spec, sims1, rp1, get_policy("firstfit")


def _reset_peak_rss() -> None:
    """Reset this process's peak-RSS counter to its current RSS.

    Writing "5" to ``/proc/self/clear_refs`` (Linux) drops the VmHWM
    high-water back to the live resident set — discarding the fork-time
    inheritance of the parent's RSS (module docstring).  Best-effort: on a
    kernel without it the report falls back to the tainted peak, which is
    at worst conservative for the stream child (inflated, never deflated).
    """
    try:
        with open("/proc/self/clear_refs", "w") as f:
            f.write("5\n")
    except OSError:
        pass


def _self_peak_mb() -> float:
    """This process's peak RSS in MB (VmHWM; ru_maxrss fallback)."""
    hwm = _vm_field_mb(os.getpid(), "VmHWM")
    if hwm is not None:
        return hwm
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0


def child_main(mode: str, horizon: int) -> None:
    """Run one measurement in THIS process and print a JSON line.

    The peak counter is reset before anything allocates, so the reported
    number covers interpreter + jax import + XLA compile + run — exactly
    the RSS an operator's cgroup limit would see — but NOT the fork-time
    snapshot of the launching process.
    """
    _reset_peak_rss()
    import jax

    from repro.launch.sweep import run_sim_vmapped

    cfg, net_spec, sims, rp, pol = _build(horizon)
    chunk = LONGHORIZON["chunk"] if mode == "stream" else None
    # warm the compile on a tail-sized prefix so the timed section is
    # runtime; the stacked child skips warming — its point is allocation
    if mode == "stream":
        run_sim_vmapped(sims, cfg, pol, net_spec.n_hosts, net_spec.n_nodes,
                        min(chunk, horizon), rp, chunk=chunk)
    t0 = time.time()
    final, _ = run_sim_vmapped(sims, cfg, pol, net_spec.n_hosts,
                               net_spec.n_nodes, horizon, rp, chunk=chunk)
    jax.tree.leaves(final)[0].block_until_ready()
    wall = time.time() - t0
    rss_mb = _self_peak_mb()
    print(json.dumps({
        "mode": mode, "horizon": horizon, "seeds": LONGHORIZON["seeds"],
        "wall_s": round(wall, 2),
        "ticks_per_s": round(horizon / max(wall, 1e-9), 1),
        "max_rss_mb": round(rss_mb, 1),
        "backend": jax.default_backend(),
    }))


def _child_cmd(mode: str, horizon: int) -> tuple[list[str], dict]:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(SRC) + os.pathsep \
        + env.get("PYTHONPATH", "")
    cmd = [sys.executable, "-m", "benchmarks.longhorizon_bench", "--child",
           "--mode", mode, "--horizon", str(horizon)]
    return cmd, env


def _vm_field_mb(pid: int, field: str) -> float | None:
    try:
        with open(f"/proc/{pid}/status") as f:
            for line in f:
                if line.startswith(field + ":"):
                    return int(line.split()[1]) / 1024.0
    except (FileNotFoundError, ProcessLookupError, ValueError):
        pass
    return None


def run_stream_child(horizon: int) -> dict:
    cmd, env = _child_cmd("stream", horizon)
    out = subprocess.run(cmd, env=env, cwd=os.path.join(HERE, ".."),
                         capture_output=True, text=True, check=True)
    return json.loads(out.stdout.strip().splitlines()[-1])


def run_stacked_child(horizon: int, ceiling_mb: float) -> dict:
    """Launch the stacked run and poll its live VmRSS; kill at the ceiling.

    The stacked scan's ys buffer is allocated when execution starts AND
    stays allocated for the whole run, so a genuine O(horizon) path holds
    above the ceiling within seconds — letting it run on would just burn
    hours proving the same number.  The poll reads current ``VmRSS``, not
    ``VmHWM`` (fork-tainted by the parent's RSS — module docstring), and
    kills only after TWO consecutive over-ceiling samples so the sub-ms
    fork window can never fake a crossing.
    """
    cmd, env = _child_cmd("stacked", horizon)
    proc = subprocess.Popen(cmd, env=env, cwd=os.path.join(HERE, ".."),
                            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                            text=True)
    t0 = time.time()
    peak = 0.0
    over = 0
    try:
        while proc.poll() is None:
            rss = _vm_field_mb(proc.pid, "VmRSS")
            if rss is not None:
                peak = max(peak, rss)
                over = over + 1 if rss > ceiling_mb else 0
            if over >= 2:
                proc.kill()
                proc.wait()
                return {"mode": "stacked", "horizon": horizon,
                        "seeds": LONGHORIZON["seeds"],
                        "exceeded_ceiling": True, "killed": True,
                        "max_rss_mb": round(peak, 1),
                        "wall_to_exceed_s": round(time.time() - t0, 2)}
            if time.time() - t0 > STACKED_TIMEOUT_S:
                proc.kill()
                proc.wait()
                return {"mode": "stacked", "horizon": horizon,
                        "seeds": LONGHORIZON["seeds"],
                        "exceeded_ceiling": False, "killed": True,
                        "timeout": True, "max_rss_mb": round(peak, 1)}
            time.sleep(0.2)
    finally:
        if proc.poll() is None:
            proc.kill()
    row = json.loads(proc.stdout.read().strip().splitlines()[-1])
    row["exceeded_ceiling"] = row["max_rss_mb"] > ceiling_mb
    row["killed"] = False
    return row


def _check_parent_off_device() -> None:
    """The children run on the default platform, and a chip belongs to one
    process: if this process already holds an accelerator, a child that
    needs it would fail or hang.  Refuse instead."""
    from jax._src import xla_bridge
    if xla_bridge.backends_are_initialized():
        import jax
        if jax.default_backend() != "cpu":
            raise RuntimeError(
                "measure_longhorizon spawns child processes that need the "
                f"{jax.default_backend()} device this process already "
                "holds; call it before anything in this process touches JAX")


def measure_longhorizon(quick: bool = False) -> dict:
    """The BENCH_engine.json ``longhorizon`` entry."""
    _check_parent_off_device()
    horizon = QUICK_HORIZON if quick else FULL_HORIZON
    stream = run_stream_child(horizon)
    entry = {
        **{k: LONGHORIZON[k] for k in ("n_hosts", "n_containers", "seeds",
                                       "chunk")},
        "horizon": horizon,
        "stacked_buffer_mb": round(
            LONGHORIZON["seeds"] * horizon * 64 / 2**20, 1),
        "backend": stream["backend"],      # the children's, not this one's
        "stream": stream,
    }
    if not quick:
        ceiling = int(-(-stream["max_rss_mb"] * CEILING_FACTOR // 32) * 32)
        entry["ceiling_mb"] = ceiling
        entry["stacked"] = run_stacked_child(horizon, ceiling)
    return entry


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--child", action="store_true")
    ap.add_argument("--mode", choices=["stream", "stacked"])
    ap.add_argument("--horizon", type=int)
    args = ap.parse_args()
    if args.child:
        child_main(args.mode, args.horizon)
        return
    entry = measure_longhorizon(quick=args.quick)
    print(json.dumps(entry, indent=1))


if __name__ == "__main__":
    main()
