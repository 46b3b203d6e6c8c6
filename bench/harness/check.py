"""What decides ``correct``: the program's answers against the plain
reference, and against the semantics a final state must keep.

An answer is one question's outcome: its final containers and hosts and
its streamed statistics.  Four numbers are compared, each with a limit of
its own (``bench/limits/<workload>.json``):

* ``arrivals_diff`` (exact): arrivals and ticks counted by the program
  against the reference.  Arrivals depend on the inputs alone.
* ``invariant_faults`` (exact): containers and hosts of every answer in
  the window whose final record breaks the semantics (a completed
  container with work or communications left, a deployed one without a
  host, a host whose slot count is not its containers', ...).
* ``used_drift``: the largest gap, as a share of capacity, between a
  host's committed resources and the requests of its containers.
* ``stat_gap``: the largest relative gap between the program's and the
  reference's statistics of a sampled question (completions, decisions,
  migrations, flow-ticks, peaks, mean utilisation and its variance, mean
  flow rate, cost, response and run times).  The two runs make the same
  decisions until a near tie falls the other way, so this is a widest
  gap, not a rounding error.
"""
from __future__ import annotations

import numpy as np

from harness.inputs import (COMMUNICATING, COMPLETED, INACTIVE, MIGRATING,
                            RUNNING, UNBORN, WAITING)

# statistics compared, each with the floor under its denominator
STATS = {
    "completed": 1.0, "decisions": 1.0, "migration_starts": 10.0,
    "migrations_done": 10.0, "flow_ticks": 1.0, "peak_running": 1.0,
    "peak_deployed": 1.0, "peak_queue": 1.0, "mean_util": 1e-6,
    "util_variance": 1e-9, "mean_flow_rate": 1.0, "total_cost": 1.0,
    "response_s": 1.0, "runtime_s": 1.0, "comm_time_s": 1.0,
}


def reference_summary(metrics: dict) -> dict:
    """The reference's per-tick statistics folded in float64."""
    f = lambda k: np.asarray(metrics[k], np.float64)
    i = lambda k: np.asarray(metrics[k]).astype(np.int64)
    return dict(
        n_ticks=np.int64(f("mean_util").shape[-1]),
        sum_util_var=f("util_variance").sum(), sum_mean_util=f("mean_util")
        .sum(), sum_flow_rate=f("mean_flow_rate").sum(),
        sum_active_flows=i("active_flows").sum(),
        sum_arrivals=i("new_arrivals").sum(),
        sum_decisions=i("decisions").sum(),
        sum_migrations=i("migrations").sum(),
        peak_running=i("n_running").max(), peak_deployed=i("n_deployed").max(),
        peak_overloaded=i("n_overloaded").max(),
        peak_inactive=i("n_inactive").max())


def reference_outcome(final: dict) -> dict:
    c = final["c"]
    return dict(status=c["status"], host=c["host"], run_at=c["run_at"],
                start_t=c["start_t"], finish_t=c["finish_t"],
                n_comms_left=c["n_comms_left"], comm_time=c["comm_time"],
                mig_dst=c["mig_dst"], n_mig=c["n_mig"], used=final["used"],
                ncont=final["ncont"], total_cost=final["total_cost"],
                t=final["t"])


def _mean(x):
    return float(x.mean()) if x.size else 0.0


def statistics(out: dict, summ: dict, conts: dict) -> dict:
    f64 = lambda x: np.asarray(x, np.float64)
    status = np.asarray(out["status"])
    submit = conts["submit_t"].astype(np.float64)
    start, finish = f64(out["start_t"]), f64(out["finish_t"])
    done = status == COMPLETED
    started = start >= 0
    n = max(int(summ["n_ticks"]), 1)
    return dict(
        completed=float(done.sum()),
        decisions=float(summ["sum_decisions"]),
        migration_starts=float(summ["sum_migrations"]),
        migrations_done=float(np.asarray(out["n_mig"]).sum()),
        flow_ticks=float(summ["sum_active_flows"]),
        peak_running=float(summ["peak_running"]),
        peak_deployed=float(summ["peak_deployed"]),
        peak_queue=float(summ["peak_inactive"]),
        mean_util=float(summ["sum_mean_util"]) / n,
        util_variance=float(summ["sum_util_var"]) / n,
        mean_flow_rate=float(summ["sum_flow_rate"]) / n,
        total_cost=float(np.asarray(out["total_cost"], np.float64)),
        response_s=_mean((start - submit)[started]),
        runtime_s=_mean((finish - submit)[done]),
        comm_time_s=_mean(f64(out["comm_time"])[status != UNBORN]))


def stat_gaps(prog: dict, ref: dict) -> dict:
    return {k: abs(prog[k] - ref[k]) / max(abs(ref[k]), floor)
            for k, floor in STATS.items()}


def arrivals_diff(summ: dict, ref_summ: dict) -> int:
    return int(abs(int(summ["sum_arrivals"]) - int(ref_summ["sum_arrivals"]))
               + abs(int(summ["n_ticks"]) - int(ref_summ["n_ticks"])))


def invariant_faults(out: dict, summ: dict, conts: dict, cap: np.ndarray,
                     horizon: int) -> int:
    """Containers and hosts whose final record breaks the semantics."""
    H = cap.shape[0]
    st = np.asarray(out["status"])
    host = np.asarray(out["host"])
    dst = np.asarray(out["mig_dst"])
    run_at = np.asarray(out["run_at"], np.float64)
    start = np.asarray(out["start_t"], np.float64)
    finish = np.asarray(out["finish_t"], np.float64)
    submit = conts["submit_t"].astype(np.float64)
    last = horizon - 1
    deployed = np.isin(st, (RUNNING, COMMUNICATING, MIGRATING))
    bad = ~np.isin(st, (UNBORN, INACTIVE, RUNNING, COMMUNICATING, MIGRATING,
                        WAITING, COMPLETED))
    done = st == COMPLETED
    bad |= done & ((run_at < conts["duration"])
                   | (np.asarray(out["n_comms_left"]) > 0) | (host != -1)
                   | (finish < start) | (start < submit) | (finish > last))
    bad |= deployed & ((host < 0) | (host >= H) | (start < submit))
    bad |= (st == MIGRATING) & ((dst < 0) | (dst >= H) | (dst == host))
    bad |= np.isin(st, (UNBORN, INACTIVE, WAITING)) & (host != -1)
    bad |= (st == UNBORN) != (submit > last)
    count = np.bincount(host[deployed], minlength=H) + \
        np.bincount(dst[st == MIGRATING], minlength=H)
    bad_hosts = count[:H] != np.asarray(out["ncont"])
    arrived = int((st != UNBORN).sum())
    return int(bad.sum() + bad_hosts.sum()
               + (arrived != int(summ["sum_arrivals"]))
               + (int(summ["n_ticks"]) != horizon))


def used_drift(out: dict, conts: dict, cap: np.ndarray) -> float:
    """Largest gap between committed and requested resources, as a share
    of the host's capacity."""
    H = cap.shape[0]
    st = np.asarray(out["status"])
    host = np.asarray(out["host"])
    dst = np.asarray(out["mig_dst"])
    req = conts["req"].astype(np.float64)
    want = np.zeros((H, 3))
    dep = np.isin(st, (RUNNING, COMMUNICATING, MIGRATING)) & (host >= 0)
    np.add.at(want, host[dep], req[dep])
    mig = (st == MIGRATING) & (dst >= 0)
    np.add.at(want, dst[mig], req[mig])
    gap = np.abs(np.asarray(out["used"], np.float64) - want) / cap
    return float(gap.max())


def verdict(numbers: dict, limits: dict) -> bool:
    return all(numbers[k] <= limits[k] for k in limits)


def limit_lines(numbers: dict, limits: dict) -> list[str]:
    return [f"{k}: {numbers[k]!r} (limit {limits[k]!r})" for k in limits]
