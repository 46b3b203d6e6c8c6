"""The system under test, reached through its public constructors only.

The benchmark's generated inputs become the program's state here
(``make_hosts``, ``build_network``, ``ContainerState``, ``init_sim``), and
the static ``SimConfig`` is filled from the configuration and the traffic
mix.  Every other module of the harness stays free of the program.
"""
from __future__ import annotations

import numpy as np

from harness.inputs import UNBORN, Fleet


def sim_config(config: dict, traffic: dict):
    from repro.core import SimConfig
    eng = config["engine"]
    return SimConfig(
        n_jobs=int(config["jobs"]), n_tasks=int(config["tasks"]),
        n_containers=int(config["containers"]),
        horizon=int(config["horizon_ticks"]),
        delay_mode=traffic["delay_mode"],
        placements_per_tick=int(eng["placements_per_tick"]),
        migrations_per_tick=int(eng["migrations_per_tick"]),
        waterfill_rounds=int(eng["waterfill_rounds"]),
        delay_update_interval=int(eng["delay_update_interval"]),
        max_containers_per_host=int(eng["max_containers_per_host"]),
        max_retries=int(eng["max_retries"]),
        stall_rate_floor=float(eng["stall_rate_floor_kbps"]),
        overload_threshold=float(eng["overload_threshold"]),
        idle_threshold=float(eng["idle_threshold"]),
        queue_coef=float(eng["queue_coef"]),
        mig_kb_per_gb=float(eng["mig_kb_per_gb"]))


def network(fl: Fleet):
    from repro.core.network import SpineLeafSpec, build_network
    spec = SpineLeafSpec(n_spine=fl.n_spine, n_leaf=fl.n_leaf,
                         n_hosts=fl.n_hosts,
                         host_leaf_bw=fl.host_leaf_mbps,
                         leaf_spine_bw=fl.leaf_spine_mbps,
                         link_delay_ms=fl.link_delay_ms, loss=fl.link_loss)
    return spec, build_network(spec)


def containers(conts: dict):
    """The program's container state for one question's containers."""
    import jax.numpy as jnp
    from repro.core.types import ContainerState
    C = conts["req"].shape[0]
    f = lambda x: jnp.asarray(np.asarray(x, np.float32))
    i = lambda x: jnp.asarray(np.asarray(x, np.int32))
    return ContainerState(
        status=i(np.full(C, UNBORN)), ctype=i(conts["ctype"]),
        req=f(conts["req"]), duration=f(conts["duration"]),
        run_at=f(np.zeros(C)), host=i(np.full(C, -1)), job=i(conts["job"]),
        task=i(conts["task"]), submit_t=f(conts["submit_t"]),
        start_t=f(np.full(C, -1.0)), finish_t=f(np.full(C, -1.0)),
        n_comms_left=i(conts["n_comms"]), comm_work_gap=f(conts["comm_gap"]),
        next_comm_at=f(conts["comm_gap"]), comm_bytes=f(conts["comm_kb"]),
        comm_bytes_left=f(np.zeros(C)), comm_peer=i(np.full(C, -1)),
        comm_time=f(np.zeros(C)), retry=i(np.zeros(C)),
        mig_dst=i(np.full(C, -1)), mig_bytes_left=f(np.zeros(C)),
        n_migrations=i(np.zeros(C)))


def hosts(fl: Fleet):
    from repro.core.types import make_hosts
    return make_hosts(fl.cap, fl.speed, fl.price, fl.leaf)


def sim_state(host_state, net, conts: dict, seed: int):
    from repro.core import init_sim
    return init_sim(host_state, containers(conts), net, seed=seed)


# the fields of a final state that the check reads, as host numpy
def outcome(final) -> dict:
    ct, hs = final.containers, final.hosts
    return dict(status=ct.status, host=ct.host, run_at=ct.run_at,
                start_t=ct.start_t, finish_t=ct.finish_t,
                n_comms_left=ct.n_comms_left, comm_time=ct.comm_time,
                mig_dst=ct.mig_dst, n_mig=ct.n_migrations,
                used=hs.used, ncont=hs.n_containers,
                total_cost=final.total_cost, t=final.t)


def summary(online) -> dict:
    """The program's streamed statistics of one question (host numpy)."""
    return {name: np.asarray(getattr(online, name)) for name in (
        "n_ticks", "sum_util_var", "sum_mean_util", "sum_flow_rate",
        "sum_active_flows", "sum_arrivals", "sum_decisions",
        "sum_migrations", "peak_running", "peak_deployed",
        "peak_overloaded", "peak_inactive")}
