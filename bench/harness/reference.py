"""Plain reference of the DCSim tick, written from the simulator's stated
semantics and independent of the program's code.

One question (a fleet, its containers, a policy) is simulated tick by tick
in straightforward ``jax.numpy``: a sequential admit loop over the FIFO
head, a sequential migration loop, progressive filling of link capacity
over the flows, the Mathis loss bound, a Floyd-Warshall or fixed-path
delay refresh, and the per-tick statistics.  No kernels, no
batching over questions, no caches.  ``dtype`` sets the precision of every
float quantity: float32 is what the configurations state; bfloat16 is the
control that the check must refuse.

A tick, in order (one tick is one simulated second):

1. arrive: unborn containers whose submit time has come join the queue;
2. place: the ``placements_per_tick`` earliest-submitted queued or waiting
   containers are taken in order, each to the best feasible host under the
   policy's rule (ties to the lowest host index), against the live
   resources of the hosts;
3. migrate: up to ``migrations_per_tick`` times, the most overloaded host
   gives up its running container with the largest use of the host's
   bottleneck resource to an idle feasible host, whose resources are
   reserved;
4. flows: each communicating container sends to its peer's host and each
   migrating container to its destination; rates are the max-min fair
   share of link capacity by progressive filling, capped by the loopback
   rate and the Mathis bound of the path's loss and delay;
5. communicate and migrate: transfers progress; a flow under the stall
   floor for more than ``max_retries`` ticks fails and its container goes
   back to the queue, undeployed;
6. execute: running containers do work at their host's speed for their
   primary resource; reaching a communication point starts a transfer to
   the lowest-index deployed container of the same job (itself if none);
7. complete: a container with all its work and communications done
   finishes and frees its host;
8. cost: every host with a container adds its price;
9. refresh: every ``delay_update_interval`` ticks the delays are rebuilt
   from the links' congestion, and with them the pairwise comm cost.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from harness.inputs import (COMMUNICATING, COMPLETED, INACTIVE, MIGRATING,
                            RUNNING, UNBORN, WAITING, Fleet)

INF = 1e9                 # "no bound" and "no edge"
BIG = 1e18                # score of an infeasible host
LOCAL_KBPS = 4.0e6        # a transfer between containers on one host
MBPS_TO_KBPS = 125.0
MSS_KB, MATHIS_C = 1.46, 1.22
UTIL_WEIGHT, CROSS_SPINE_MS = 1.0, 0.05   # the comm cost of every policy
MAX_QUEUE_MS = 20.0

# placement rule and migration rule of each policy (DCSim section 3.5 and
# the repository's network-aware pair)
PLACE = {"firstfit": 0, "round": 1, "performance_first": 2, "jobgroup": 3,
         "netaware": 4, "overload_migrate": 0}
MIGRATE = {"firstfit": 0, "round": 0, "performance_first": 0, "jobgroup": 0,
           "netaware": 2, "overload_migrate": 1}


def topology(fl: Fleet) -> dict:
    """Links and the fixed ECMP path of every host pair.

    Nodes: hosts, then leaves, then spines.  Link ``i < H`` joins host
    ``i`` to its leaf; link ``H + l * S + s`` joins leaf ``l`` to spine
    ``s``.  Hosts on one leaf talk over their two access links; hosts on
    different leaves go up to spine ``(i + j) % S`` and down again.
    ``path[n, i, j]`` is the ``n``-th link from host ``i`` to host ``j``
    (-1 past the path's end); the slot axis leads, so that a chip's tiled
    layout does not pad it.
    """
    H, L, S = fl.n_hosts, fl.n_leaf, fl.n_spine
    link_u = np.concatenate([np.arange(H), H + np.repeat(np.arange(L), S)])
    link_v = np.concatenate([H + fl.leaf, H + L + np.tile(np.arange(S), L)])
    bw = np.concatenate([np.full(H, fl.host_leaf_mbps),
                         np.full(L * S, fl.leaf_spine_mbps)])
    i = np.arange(H)[:, None]
    j = np.arange(H)[None, :]
    li, lj = fl.leaf[i], fl.leaf[j]
    sp = (i + j) % S
    up, down = H + li * S + sp, H + lj * S + sp
    ii, jj = np.broadcast_arrays(i, j)
    same = (li == lj) & (ii != jj)
    cross = li != lj
    links = np.stack([np.where(same | cross, ii, -1),
                      np.where(same, jj, np.where(cross, up, -1)),
                      np.where(cross, down, -1),
                      np.where(cross, jj, -1)]).astype(np.int32)
    return dict(link_u=link_u.astype(np.int32),
                link_v=link_v.astype(np.int32),
                link_mbps=bw.astype(np.float32),
                path=links, cross=cross)


def _pad(x, fill):
    return jnp.concatenate([x, jnp.full((1,), fill, x.dtype)])


def _path_sum(per_link, path):
    """Sum of a per-link quantity along every host pair's path."""
    return _pad(per_link, 0)[path].sum(axis=0)


def _path_max(per_link, path):
    return _pad(per_link, 0)[path].max(axis=0)


def _fair_rates(links, active, cap, rounds, fd):
    """Progressive filling: each round, the flows whose tightest link share
    is (within 1e-6) the smallest of all are frozen at that share, and
    their rate is taken off their links.  Flows left after ``rounds``
    rounds get their current share."""
    E = cap.shape[0]
    valid = (links >= 0) & active[:, None]
    seg = jnp.where(valid, links, E)

    def per_link(x):                       # [F] -> [E]
        w = jnp.where(valid, x[:, None], 0).astype(fd)
        return jax.ops.segment_sum(w.ravel(), seg.ravel(),
                                   num_segments=E + 1)[:E]

    def bound(live, rem):
        n = per_link(live.astype(fd))
        share = jnp.where(n > 0, rem / jnp.maximum(n, 1), INF).astype(fd)
        per_slot = jnp.where(valid, _pad(share, INF)[seg], INF)
        return per_slot.min(axis=1)

    rate = jnp.where(active, LOCAL_KBPS, 0).astype(fd)
    frozen = active & ~valid.any(axis=1)   # flows inside one host

    def one_round(_, carry):
        rate, frozen, rem = carry
        live = active & ~frozen
        b = jnp.where(live, bound(live, rem), INF).astype(fd)
        lo = b.min()
        now = live & (b <= lo * 1.000001 + 1e-6)
        rate = jnp.where(now, jnp.minimum(b, LOCAL_KBPS), rate).astype(fd)
        rem = jnp.maximum(rem - per_link(jnp.where(now, rate, 0)), 0)
        return rate, frozen | now, rem.astype(fd)

    rate, frozen, rem = jax.lax.fori_loop(0, rounds, one_round,
                                          (rate, frozen, cap))
    left = active & ~frozen
    rate = jnp.where(left, jnp.minimum(bound(left, rem), LOCAL_KBPS), rate)
    return jnp.where(active, rate, 0).astype(fd)


def _floyd_warshall(A):
    def relax(k, D):
        return jnp.minimum(D, D[:, k][:, None] + D[k, :][None, :])
    return jax.lax.fori_loop(0, A.shape[0], relax, A)


def _refresh(net, k, cfg, fd):
    """Delays from the links' congestion, and the pairwise comm cost."""
    u = jnp.clip(net["util"], 0, 0.97)
    d = (k["delay0"] + jnp.minimum(k["q_coef"] * u / (1 - u),
                                   MAX_QUEUE_MS)).astype(fd)
    if cfg["mode"] == "path":
        D = _path_sum(d, k["path"])
    else:
        H = k["path"].shape[1]
        N = cfg["n_nodes"]
        A = jnp.full((N, N), INF, fd)
        A = A.at[k["link_u"], k["link_v"]].min(d)
        A = A.at[k["link_v"], k["link_u"]].min(d)
        A = jnp.where(jnp.eye(N, dtype=bool), 0, A).astype(fd)
        D = _floyd_warshall(A)[:H, :H]
    return dict(net, delay=D.astype(fd), cost=_comm_cost(D, net["util"], k, fd))


def _comm_cost(D, util, k, fd):
    return (D + UTIL_WEIGHT * _path_max(util, k["path"])
            + CROSS_SPINE_MS * k["cross"]).astype(fd)


def _release(used, ncont, req, host, mask):
    """Give back the resources of the containers in ``mask``."""
    H = used.shape[0]
    seg = jnp.where(mask & (host >= 0), host, H)
    used = used - jax.ops.segment_sum(
        jnp.where(mask[:, None], req, 0), seg, num_segments=H + 1)[:H]
    ncont = ncont - jax.ops.segment_sum(
        mask.astype(jnp.int32), seg, num_segments=H + 1)[:H]
    return used, ncont


def _tick(k, cfg, st, tt):
    fd = k["cap"].dtype
    H = k["cap"].shape[0]
    C = k["req"].shape[0]
    cap, req, job = k["cap"], k["req"], k["job"]
    c = dict(st["c"])
    t = st["t"]

    # 1. arrive
    arriving = (c["status"] == UNBORN) & (k["submit_t"] <= t)
    c["status"] = jnp.where(arriving, INACTIVE, c["status"])

    # 2. place, the FIFO head first
    queued = (k["submit_t"] <= t) & ((c["status"] == INACTIVE)
                                     | (c["status"] == WAITING))
    order = jnp.argsort(jnp.where(queued, k["submit_t"], jnp.inf),
                        stable=True)[:cfg["K"]]
    deployed0 = ((c["status"] == RUNNING) | (c["status"] == COMMUNICATING)
                 | (c["status"] == MIGRATING)) & (c["host"] >= 0)
    hidx = jnp.arange(H)
    pol = k["place"]

    def admit(i, carry):
        used, ncont, where_, rr, chosen = carry
        cand = order[i]
        ok_c = queued[cand]
        r = req[cand]
        feas = (jnp.all(used + r[None, :] <= cap, axis=1)
                & (ncont < cfg["max_per_host"]) & ok_c)
        peers = (job == job[cand]) & (where_ >= 0)
        cnt = jax.ops.segment_sum(peers.astype(fd), jnp.where(peers, where_, H),
                                  num_segments=H + 1)[:H]
        total = cnt.sum()
        free = ((cap - used) / jnp.maximum(cap, 1e-6)).sum(axis=1)
        worst_fit = -free
        comm = (cnt[:, None] * st["net"]["cost"]).sum(axis=0) / \
            jnp.maximum(total, 1)
        score = jnp.select(
            [pol == 0, pol == 1, pol == 2, pol == 3],
            [hidx.astype(fd),
             jnp.mod(hidx - rr - 1, H).astype(fd),
             -k["speed"][:, k["ctype"][cand]],
             jnp.where(total > 0, -cnt, worst_fit)],
            jnp.where(total > 0, comm, worst_fit)).astype(fd)
        h = jnp.where(feas.any(), jnp.argmin(jnp.where(feas, score, BIG)), -1)
        ok = h >= 0
        hot = (hidx == h) & ok
        used = jnp.where(hot[:, None], used + r[None, :], used)
        ncont = jnp.where(hot, ncont + 1, ncont)
        where_ = where_.at[cand].set(jnp.where(ok, h, where_[cand]))
        rr = jnp.where(ok & (pol == 1), h, rr)
        chosen = chosen.at[i].set(h)
        return used, ncont, where_, rr, chosen

    where0 = jnp.where(deployed0, c["host"], -1)
    used, ncont, _, rr, chosen = jax.lax.fori_loop(
        0, cfg["K"], admit,
        (st["used"], st["ncont"], where0, st["rr"],
         jnp.full((cfg["K"],), -1, jnp.int32)))
    placed = jnp.zeros((C,), bool).at[order].set(chosen >= 0)
    to_host = jnp.zeros((C,), jnp.int32).at[order].set(chosen)
    c["status"] = jnp.where(placed, RUNNING, c["status"])
    c["host"] = jnp.where(placed, to_host, c["host"])
    c["start_t"] = jnp.where(placed & (c["start_t"] < 0), t, c["start_t"])
    c["retry"] = jnp.where(placed, 0, c["retry"])
    decisions = placed.sum()

    # 3. migrate
    mig_rule = k["migrate"]

    def move(_, carry):
        used, ncont, status, moved_to = carry
        util = used / jnp.maximum(cap, 1e-6)
        worst = util.max(axis=1)
        over = worst > k["over_thr"]
        src = jnp.argmax(jnp.where(over, worst, -jnp.inf))
        bott = jnp.argmax(util[src])
        movable = (status == RUNNING) & (c["host"] == src)
        cont = jnp.argmax(jnp.where(movable, req[:, bott], -jnp.inf))
        r = req[cont]
        idle = jnp.all(util < k["idle_thr"], axis=1)
        feas = (jnp.all(used + r[None, :] <= cap, axis=1)
                & (ncont < cfg["max_per_host"]) & idle & (hidx != src))
        score = jnp.where(mig_rule == 1, hidx.astype(fd),
                          _path_max(st["net"]["util"], k["path"][:, src]))
        dst = jnp.argmin(jnp.where(feas, score, BIG))
        ok = (mig_rule > 0) & over.any() & movable.any() & feas.any()
        hot = (hidx == dst) & ok
        used = jnp.where(hot[:, None], used + r[None, :], used)
        ncont = jnp.where(hot, ncont + 1, ncont)
        status = jnp.where((jnp.arange(C) == cont) & ok, MIGRATING, status)
        moved_to = jnp.where((jnp.arange(C) == cont) & ok, dst, moved_to)
        return used, ncont, status, moved_to

    used, ncont, c["status"], moved_to = jax.lax.fori_loop(
        0, cfg["n_mig"], move,
        (used, ncont, c["status"], jnp.full((C,), -1, jnp.int32)))
    started = moved_to >= 0
    c["mig_dst"] = jnp.where(started, moved_to, c["mig_dst"])
    c["mig_left"] = jnp.where(started, k["mig_kb_per_gb"] * req[:, 1],
                              c["mig_left"]).astype(fd)
    c["retry"] = jnp.where(started, 0, c["retry"])
    migrations = started.sum()

    # 4. flows: comm flows, then migration flows
    comm_on = c["status"] == COMMUNICATING
    mig_on = c["status"] == MIGRATING
    peer_host = c["host"][jnp.clip(c["peer"], 0, C - 1)]
    src = jnp.clip(jnp.concatenate([c["host"], c["host"]]), 0, H - 1)
    dst = jnp.clip(jnp.concatenate([peer_host, c["mig_dst"]]), 0, H - 1)
    active = jnp.concatenate([comm_on, mig_on])
    links = jnp.where(active[:, None], k["path"][:, src, dst].T, -1)
    net = st["net"]
    fair = _fair_rates(links, active, k["bw_kbps"], cfg["rounds"], fd)
    p = k["path_loss"][src, dst]
    rtt_s = jnp.maximum(2 * net["delay"][src, dst], 1e-2) * 1e-3
    tcp = jnp.where(p > 1e-9,
                    MATHIS_C * MSS_KB / (rtt_s * jnp.sqrt(jnp.maximum(p, 1e-12))),
                    INF)
    rate = (jnp.minimum(fair, tcp) * active).astype(fd)
    E = k["bw_kbps"].shape[0]
    valid = links >= 0
    load = jax.ops.segment_sum(
        jnp.where(valid, rate[:, None], 0).ravel(),
        jnp.where(valid, links, E).ravel(), num_segments=E + 1)[:E]
    util = jnp.clip(jnp.where(k["bw_kbps"] > 0,
                              load / jnp.maximum(k["bw_kbps"], 1e-6), 0),
                    0, 1).astype(fd)
    net = dict(net, util=util)
    comm_rate, mig_rate = rate[:C], rate[C:]

    # 5a. communicate
    left = jnp.where(comm_on, c["comm_left"] - comm_rate, c["comm_left"])
    done = comm_on & (left <= 0)
    stalled = comm_on & ~done & (comm_rate < k["stall_floor"])
    retry = jnp.where(stalled, c["retry"] + 1,
                      jnp.where(comm_on, 0, c["retry"]))
    failed = stalled & (retry > cfg["max_retries"])
    used, ncont = _release(used, ncont, req, c["host"], failed)
    c["status"] = jnp.where(done, RUNNING, jnp.where(failed, WAITING,
                                                     c["status"]))
    c["comm_left"] = jnp.where(done | failed, 0,
                               jnp.maximum(left, 0)).astype(fd)
    c["n_comms_left"] = jnp.where(done, c["n_comms_left"] - 1,
                                  c["n_comms_left"])
    c["next_comm_at"] = jnp.where(done, c["next_comm_at"] + k["comm_gap"],
                                  c["next_comm_at"]).astype(fd)
    c["peer"] = jnp.where(done | failed, -1, c["peer"])
    c["comm_time"] = (c["comm_time"] + comm_on).astype(fd)
    c["retry"] = jnp.where(failed, 0, retry)
    c["host"] = jnp.where(failed, -1, c["host"])

    # 5b. migrations progress
    left = jnp.where(mig_on, c["mig_left"] - mig_rate, c["mig_left"])
    done = mig_on & (left <= 0)
    stalled = mig_on & ~done & (mig_rate < k["stall_floor"])
    retry = jnp.where(stalled, c["retry"] + 1,
                      jnp.where(mig_on, 0, c["retry"]))
    failed = stalled & (retry > cfg["max_retries"])
    used, ncont = _release(used, ncont, req, c["host"], done | failed)
    used, ncont = _release(used, ncont, req, c["mig_dst"], failed)
    c["status"] = jnp.where(done, RUNNING, jnp.where(failed, WAITING,
                                                     c["status"]))
    c["host"] = jnp.where(done, c["mig_dst"],
                          jnp.where(failed, -1, c["host"]))
    c["mig_dst"] = jnp.where(done | failed, -1, c["mig_dst"])
    c["mig_left"] = jnp.where(done | failed, 0,
                              jnp.maximum(left, 0)).astype(fd)
    c["n_mig"] = jnp.where(done, c["n_mig"] + 1, c["n_mig"])
    c["retry"] = jnp.where(failed, 0, retry)

    # 6. execute; a communication point starts a transfer to a peer
    deployed = ((c["status"] == RUNNING) | (c["status"] == COMMUNICATING)
                | (c["status"] == MIGRATING)) & (c["host"] >= 0)
    running = c["status"] == RUNNING
    speed = k["speed"][jnp.clip(c["host"], 0, H - 1), k["ctype"]]
    c["run_at"] = jnp.where(running, c["run_at"] + speed,
                            c["run_at"]).astype(fd)
    trigger = running & (c["n_comms_left"] > 0) & \
        (c["run_at"] >= c["next_comm_at"])
    cidx = jnp.arange(C)
    n_jobs = cfg["n_jobs"]
    first = jax.ops.segment_min(jnp.where(deployed, cidx, C), job,
                                num_segments=n_jobs)
    second = jax.ops.segment_min(
        jnp.where(deployed & (cidx != first[job]), cidx, C), job,
        num_segments=n_jobs)
    peer = jnp.where(first[job] != cidx, first[job], second[job])
    peer = jnp.where(peer < C, peer, cidx)
    c["status"] = jnp.where(trigger, COMMUNICATING, c["status"])
    c["comm_left"] = jnp.where(trigger, k["comm_kb"],
                               c["comm_left"]).astype(fd)
    c["peer"] = jnp.where(trigger, peer, c["peer"])
    c["retry"] = jnp.where(trigger, 0, c["retry"])

    # 7. complete
    fin = (c["status"] == RUNNING) & (c["run_at"] >= k["duration"]) & \
        (c["n_comms_left"] <= 0)
    used, ncont = _release(used, ncont, req, c["host"], fin)
    c["status"] = jnp.where(fin, COMPLETED, c["status"])
    c["finish_t"] = jnp.where(fin, t, c["finish_t"]).astype(fd)
    c["host"] = jnp.where(fin, -1, c["host"])

    # 8. cost
    busy = ncont > 0
    total_cost = (st["total_cost"] + (k["price"] * busy).sum()).astype(fd)
    busy_time = (st["busy_time"] + busy).astype(fd)

    # 9. refresh
    if cfg["interval"] == 0:
        due = tt == 0
    else:
        due = jnp.mod(tt, cfg["interval"]) == 0
    net = jax.lax.cond(due, lambda n: _refresh(n, k, cfg, fd),
                       lambda n: n, net)

    # statistics of the tick
    u = used / jnp.maximum(cap, 1e-6)
    per_host = u.mean(axis=1)
    status = c["status"]
    n_active = active.sum()
    metrics = dict(
        n_overloaded=(u.max(axis=1) > k["over_thr"]).sum(),
        n_inactive=((status == INACTIVE) | (status == WAITING)).sum(),
        n_running=(status == RUNNING).sum(),
        n_deployed=((status == RUNNING) | (status == COMMUNICATING)
                    | (status == MIGRATING)).sum(),
        n_completed=(status == COMPLETED).sum(),
        new_arrivals=arriving.sum(),
        decisions=decisions, migrations=migrations,
        util_variance=jnp.var(per_host.astype(jnp.float32)),
        mean_util=per_host.astype(jnp.float32).mean(),
        active_flows=n_active,
        mean_flow_rate=jnp.where(
            n_active > 0,
            (rate * active).astype(jnp.float32).sum()
            / jnp.maximum(n_active, 1), 0.0))
    st = dict(t=(t + 1).astype(fd), used=used.astype(fd), ncont=ncont, c=c,
              net=net, rr=rr, total_cost=total_cost, busy_time=busy_time)
    return st, metrics


def simulate(fl: Fleet, topo: dict, conts: dict, *, policy: str, engine: dict,
             mode: str, horizon: int, dtype=jnp.float32, device=None,
             fetch: bool = True):
    """Run one question for ``horizon`` ticks on ``device``.

    Returns ``(final, metrics)``: ``final`` the containers, hosts and
    network at the end, ``metrics`` the per-tick statistics stacked over
    the horizon; as host numpy, or with ``fetch=False`` as device arrays
    still being computed (so that several questions are enqueued before
    the first is waited for).
    """
    fd = jnp.dtype(dtype)
    H, C = fl.n_hosts, conts["req"].shape[0]
    mbps = topo["link_mbps"]
    loss = np.full(mbps.shape, fl.link_loss, np.float32)
    put = functools.partial(jax.device_put, device=device)
    f = lambda x: put(jnp.asarray(np.asarray(x, np.float32)).astype(fd))
    i32 = lambda x: put(jnp.asarray(np.asarray(x, np.int32)))
    path = i32(topo["path"])
    keep = np.concatenate([np.log1p(-np.clip(loss, 0.0, 0.99)), [0.0]])
    path_loss = 1.0 - np.exp(keep[topo["path"]].sum(axis=0))
    delay0 = np.full(mbps.shape, fl.link_delay_ms, np.float32)
    k = dict(
        cap=f(fl.cap), speed=f(fl.speed), price=f(fl.price),
        req=f(conts["req"]), ctype=i32(conts["ctype"]), job=i32(conts["job"]),
        submit_t=f(conts["submit_t"]), duration=f(conts["duration"]),
        comm_kb=f(conts["comm_kb"]), comm_gap=f(conts["comm_gap"]),
        link_u=i32(topo["link_u"]), link_v=i32(topo["link_v"]),
        path=path, cross=f(topo["cross"]), path_loss=f(path_loss),
        bw_kbps=f(mbps * MBPS_TO_KBPS), delay0=f(delay0),
        place=i32(PLACE[policy]), migrate=i32(MIGRATE[policy]),
        q_coef=f(engine["queue_coef"]),
        over_thr=f(engine["overload_threshold"]),
        idle_thr=f(engine["idle_threshold"]),
        stall_floor=f(engine["stall_rate_floor_kbps"]),
        mig_kb_per_gb=f(engine["mig_kb_per_gb"]))
    cfg = dict(K=min(int(engine["placements_per_tick"]), C),
               n_mig=int(engine["migrations_per_tick"]),
               rounds=int(engine["waterfill_rounds"]),
               interval=int(engine["delay_update_interval"]),
               max_per_host=int(engine["max_containers_per_host"]),
               max_retries=int(engine["max_retries"]),
               mode=mode, horizon=int(horizon),
               n_jobs=int(conts["job"].max()) + 1, n_nodes=fl.n_nodes)
    D0 = _path_sum(k["delay0"], path)
    util0 = jnp.zeros(mbps.shape, fd)
    zf = lambda: put(jnp.zeros((C,), fd))
    zi = lambda fill=0: put(jnp.full((C,), fill, jnp.int32))
    st = dict(
        t=put(jnp.zeros((), fd)), used=put(jnp.zeros((H, 3), fd)),
        ncont=put(jnp.zeros((H,), jnp.int32)),
        c=dict(status=zi(UNBORN), host=zi(-1), run_at=zf(),
               start_t=f(np.full(C, -1.0)), finish_t=f(np.full(C, -1.0)),
               n_comms_left=i32(conts["n_comms"]),
               next_comm_at=f(conts["comm_gap"]), comm_left=zf(),
               peer=zi(-1), comm_time=zf(), retry=zi(), mig_dst=zi(-1),
               mig_left=zf(), n_mig=zi()),
        net=dict(util=util0, delay=D0.astype(fd),
                 cost=_comm_cost(D0, util0, k, fd)),
        rr=put(jnp.asarray(-1, jnp.int32)),
        total_cost=put(jnp.zeros((), fd)),
        busy_time=put(jnp.zeros((H,), fd)))
    with jax.default_matmul_precision("highest"):
        out = _episode(k, st, tuple(sorted(cfg.items())))
    return jax.device_get(out) if fetch else out


@functools.partial(jax.jit, static_argnames=("cfg_items",))
def _episode(k, st, cfg_items):
    cfg = dict(cfg_items)
    return jax.lax.scan(functools.partial(_tick, k, cfg), st,
                        jnp.arange(cfg["horizon"], dtype=jnp.int32))
