"""Discrete event driver module (paper §3.6), tensor-native.

The paper drives eight SimPy processes, all with a 1-second period
(Table 3: generate_containers / schedule / run / communicate / migrate /
pre_treatment / save_stats / update_delay_matrix).  A set of processes that
all fire on the same period *is* a synchronous time-stepped simulation, so
the JAX port runs one ``lax.scan`` over ticks; each tick applies the paper's
processes as phase-ordered pure transitions:

    arrive -> schedule(+migrate decisions) -> flow rates -> communicate
           -> migrate(progress) -> execute(+comm triggers) -> complete
           -> cost/stats -> delay-matrix refresh (every K ticks)

Everything is masked SoA updates, so the whole simulation compiles to one
XLA program and ``vmap`` over seeds/scenarios is free — the capability the
paper's process-per-entity design fundamentally lacks (its Table 7 shows
0.8 s + ~1.3 MB of host overhead *per network node*).
"""
from __future__ import annotations

import functools
import warnings
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.core import network, scheduling, stats, workload
from repro.core.datacenter import SimConfig
from repro.kernels import resolve_kernel
from repro.core.scheduling import BIG, INT_BIG, feasible_hosts
from repro.core.types import (
    F_COMM, F_HOST_UTIL, STATUS_COMMUNICATING, STATUS_COMPLETED,
    STATUS_INACTIVE, STATUS_MIGRATING, STATUS_RUNNING, STATUS_UNBORN,
    STATUS_WAITING, W_CROSS_LEAF, W_MIG_ENABLE, W_UTIL, ContainerState,
    ExecPlan, HostState, NetState, PolicyParams, RunParams, SchedState,
    SimState, TickMetrics,
)

I32 = jnp.int32
F32 = jnp.float32


# ---------------------------------------------------------------------------
# State assembly
# ---------------------------------------------------------------------------
def init_sim(hosts: HostState, containers: ContainerState, net: NetState,
             seed: int = 0) -> SimState:
    return SimState(
        t=jnp.zeros((), F32),
        hosts=hosts,
        containers=containers,
        net=net,
        sched=SchedState(rr_pointer=jnp.array(-1, I32),
                         decisions=jnp.zeros((), I32),
                         migrations=jnp.zeros((), I32)),
        total_cost=jnp.zeros((), F32),
        rng=jax.random.PRNGKey(seed),
    )


# ---------------------------------------------------------------------------
# Resource bookkeeping helpers (masked, scan-safe for c == -1 / h == -1)
#
# The tick is SCATTER-FREE: every ``.at[idx].set/add`` state update is
# expressed as a where-mask (scalar/distinct indices — bit-exact, a
# single float add with identical operands) or a ``segment_sum`` reduction
# with the pad-slot trick (duplicate indices).  XLA:CPU lowers *batched*
# scatters off its fast path (~2x per sweep cell, docs/sweeps.md), so the
# scatter-heavy PR 3 tick forced ``lax.map`` over the policy/scenario sweep
# axes; the masked forms lower to elementwise selects that ``vmap``
# batches for free.  (The PR 3 scatter forms survived one deprecation
# cycle behind ``cfg.scatter_tick`` as the bit-for-bit oracle and are now
# gone; the cheap unit oracles that don't fork the tick remain —
# ``scheduling.same_job_host_counts_scatter``, dense ``flow_rates``.)
# ---------------------------------------------------------------------------
def _one_hot(n: int, idx: jnp.ndarray, ok: jnp.ndarray) -> jnp.ndarray:
    """bool[n] mask selecting ``idx`` when ``ok`` — the where-mask
    replacement for a scalar-index scatter."""
    return (jnp.arange(n) == idx) & ok


def _deploy(sim: SimState, c: jnp.ndarray, h: jnp.ndarray) -> SimState:
    C = sim.containers.status.shape[0]
    H = sim.hosts.cap.shape[0]
    cc = jnp.clip(c, 0, C - 1)
    hh = jnp.clip(h, 0, H - 1)
    ok = (c >= 0) & (h >= 0)
    ct = sim.containers
    hot_h = _one_hot(H, hh, ok)
    hot_c = _one_hot(C, cc, ok)
    req = ct.req[cc]
    hosts = sim.hosts._replace(
        used=jnp.where(hot_h[:, None], sim.hosts.used + req[None, :],
                       sim.hosts.used),
        n_containers=jnp.where(hot_h, sim.hosts.n_containers + 1,
                               sim.hosts.n_containers),
    )
    conts = ct._replace(
        status=jnp.where(hot_c, STATUS_RUNNING, ct.status),
        host=jnp.where(hot_c, hh, ct.host),
        start_t=jnp.where(hot_c & (ct.start_t < 0), sim.t, ct.start_t),
        retry=jnp.where(hot_c, 0, ct.retry),
    )
    return sim._replace(hosts=hosts, containers=conts)


def _free_resources(hosts: HostState, req: jnp.ndarray, host_idx: jnp.ndarray,
                    mask: jnp.ndarray) -> HostState:
    """Vectorized release of ``req[c]`` on ``host_idx[c]`` where ``mask``.

    Shared by both tick paths: per-host totals are accumulated with one
    ``segment_sum`` (pad slot H collects the unmasked rows) and subtracted
    in a single pass.  This regroups the float sum relative to the PR 3
    incremental ``.at[hh].add`` (delta first, then one subtract), which is
    exactly why it is shared — the scatter oracle and the scatter-free tick
    must agree bit-for-bit, and duplicate-index accumulation order is the
    one place the two formulations could round differently.
    """
    H = hosts.cap.shape[0]
    m = (mask & (host_idx >= 0))
    seg = jnp.where(m, host_idx, H)
    dreq = jax.ops.segment_sum(req * m.astype(F32)[:, None], seg,
                               num_segments=H + 1)[:H]
    dcnt = jax.ops.segment_sum(m.astype(I32), seg, num_segments=H + 1)[:H]
    return hosts._replace(
        used=hosts.used - dreq,
        n_containers=hosts.n_containers - dcnt,
    )


# ---------------------------------------------------------------------------
# Tick phases
# ---------------------------------------------------------------------------
def phase_arrive(sim: SimState) -> Tuple[SimState, jnp.ndarray]:
    """UNBORN -> INACTIVE once submit_t <= t (generate_containers process)."""
    ct = sim.containers
    arriving = (ct.status == STATUS_UNBORN) & (ct.submit_t <= sim.t)
    status = jnp.where(arriving, STATUS_INACTIVE, ct.status)
    return sim._replace(containers=ct._replace(status=status)), arriving.sum()


def _pick_host(sim: SimState, cfg: SimConfig, params: RunParams,
               policy: PolicyParams, carry, k, cand, used, feas):
    """Evaluate the policy's [H] preference row and argmin it over the
    feasible hosts — the single scoring step both placement paths share."""
    row = scheduling.host_row(sim, cfg, params, policy, carry, k, cand, used)
    return jnp.where(feas.any(), jnp.argmin(jnp.where(feas, row, BIG)), -1)


def _place_sequential(sim: SimState, cfg: SimConfig, params: RunParams,
                      policy: PolicyParams) -> SimState:
    """Sequential reference path, derived from the same scoring API.

    Each scan step is a K=1 degenerate placement round against the fully
    live state: re-evaluate the selection key, score the head candidate's
    hosts, deploy.  Because the hooks are shared with ``_place_batched``,
    the two paths produce identical placements whenever every candidate is
    feasible (an infeasible head blocks this path — the paper's semantics —
    while the batched round skips it).
    """
    H = sim.hosts.cap.shape[0]

    def place_body(s: SimState, _):
        key = scheduling.select_key(s, policy)
        c = jnp.argmin(key)
        valid = key[c] < INT_BIG
        cand = c[None]
        pcarry = scheduling.init_place_carry(s, cand, policy)
        feas = feasible_hosts(s.hosts.cap, s.hosts.used,
                              s.hosts.n_containers,
                              s.containers.req[c], cfg) & valid
        h = _pick_host(s, cfg, params, policy, pcarry, 0, cand,
                       s.hosts.used, feas)
        ok = h >= 0
        hh = jnp.clip(h, 0, H - 1)
        pcarry = scheduling.update_place_carry(s, policy, pcarry, 0, cand,
                                               hh, ok)
        s = s._replace(sched=scheduling.commit_place_carry(s.sched, pcarry))
        s = _deploy(s, jnp.where(valid, c, -1), h)
        s = s._replace(sched=s.sched._replace(
            decisions=s.sched.decisions + ok.astype(I32)))
        return s, None

    sim, _ = jax.lax.scan(place_body, sim, None,
                          length=cfg.placements_per_tick)
    return sim


def _scatter_to_containers(C: int, idx: jnp.ndarray, ok: jnp.ndarray):
    """Map a round's (distinct) per-decision indices onto the container
    axis WITHOUT a scatter: ``sel[c]`` marks containers hit by an admitted
    decision and ``slot_of[c]`` is the decision slot that hit them (0 where
    unhit — always masked by ``sel``).  O(C*K) compares, elementwise, so it
    vmaps for free where the ``.at[idx].set`` form forced XLA:CPU's slow
    batched-scatter lowering."""
    hit = (idx[None, :] == jnp.arange(C)[:, None]) & ok[None, :]   # [C, K]
    return hit.any(axis=1), jnp.argmax(hit, axis=1)


def _place_batched(sim: SimState, cfg: SimConfig, params: RunParams,
                   policy: PolicyParams):
    """Batched conflict-resolved placement round.

    Instead of ``placements_per_tick`` full select+score passes (each one
    O(C + H) work serialized by the scan), rank all schedulable containers
    once by the policy's selection key, take the top-K candidates
    (K = placements_per_tick << C), compute the policy's [K, H] placement
    score once, and admit the candidates with a short K-length scan that
    carries the live host ``used`` / slot counters plus the policy's
    dynamic-term carry — so later decisions observe both earlier ones'
    resource consumption AND their score impact (the rotating pointer, the
    co-location counts).  Container-state updates are applied in one
    vectorized pass of where-masks afterwards (top-k candidate indices are
    distinct).

    One deliberate semantic upgrade over the sequential reference: a
    candidate with no feasible host no longer blocks the rest of the round
    (the sequential argmin re-selected the same stuck head every step).

    Returns ``(sim', (soft_comm, soft_util, soft_n))``.  With
    ``cfg.soft_placement`` the admit scan ALSO carries the softmax
    expected-cost sums of the surrogate (``scheduling.soft_assign`` over
    the same score row the argmin consumes; docs/autodiff.md) — the
    decisions themselves are computed identically, so the final state is
    bit-for-bit the ``soft_placement=False`` state.  With it off the soft
    terms are constant 0.0 and this is exactly the old round.
    """
    C = sim.containers.status.shape[0]
    H = sim.hosts.cap.shape[0]
    K = min(cfg.placements_per_tick, C)
    soft_on = cfg.soft_placement

    key = scheduling.select_key(sim, policy)              # i32[C]
    neg_vals, cand = jax.lax.top_k(-key, K)               # K smallest keys
    valid = -neg_vals < INT_BIG                           # bool[K]
    req_k = sim.containers.req[cand]                      # [K, 3]
    pcarry0 = scheduling.init_place_carry(sim, cand, policy)

    def admit(carry, k):
        if soft_on:
            used, ncont, pcarry, s_comm, s_util, s_n = carry
        else:
            used, ncont, pcarry = carry
        feas = feasible_hosts(sim.hosts.cap, used, ncont,
                              req_k[k], cfg) & valid[k]
        if soft_on:
            row, cols = scheduling.host_row_cols(sim, cfg, params, policy,
                                                 pcarry, k, cand, used)
            h = jnp.where(feas.any(), jnp.argmin(jnp.where(feas, row, BIG)),
                          -1)
            q = scheduling.soft_assign(row, feas, params.tau)
            s_comm = s_comm + (q * cols[F_COMM]).sum()
            s_util = s_util + (q * cols[F_HOST_UTIL]).sum()
            s_n = s_n + feas.any().astype(F32)
        else:
            h = _pick_host(sim, cfg, params, policy, pcarry, k, cand, used,
                           feas)
        ok = h >= 0
        hh = jnp.clip(h, 0, H - 1)
        hot = _one_hot(H, hh, ok)
        used = jnp.where(hot[:, None], used + req_k[k][None, :], used)
        ncont = jnp.where(hot, ncont + 1, ncont)
        pcarry = scheduling.update_place_carry(sim, policy, pcarry, k, cand,
                                               hh, ok)
        if soft_on:
            return (used, ncont, pcarry, s_comm, s_util, s_n), h
        return (used, ncont, pcarry), h

    zero = jnp.zeros((), F32)
    if soft_on:
        init = (sim.hosts.used, sim.hosts.n_containers, pcarry0,
                zero, zero, zero)
        (used, ncont, pcarry, s_comm, s_util, s_n), chosen = jax.lax.scan(
            admit, init, jnp.arange(K))
    else:
        init = (sim.hosts.used, sim.hosts.n_containers, pcarry0)
        (used, ncont, pcarry), chosen = jax.lax.scan(admit, init,
                                                     jnp.arange(K))
        s_comm = s_util = s_n = zero

    ok = chosen >= 0
    hh = jnp.clip(chosen, 0, H - 1)
    ct = sim.containers
    sel, k_of = _scatter_to_containers(C, cand, ok)
    conts = ct._replace(
        status=jnp.where(sel, STATUS_RUNNING, ct.status),
        host=jnp.where(sel, hh[k_of], ct.host),
        start_t=jnp.where(sel & (ct.start_t < 0), sim.t, ct.start_t),
        retry=jnp.where(sel, 0, ct.retry),
    )
    hosts = sim.hosts._replace(used=used, n_containers=ncont)
    sched = scheduling.commit_place_carry(sim.sched, pcarry)._replace(
        decisions=sim.sched.decisions + ok.sum().astype(I32))
    return (sim._replace(hosts=hosts, containers=conts, sched=sched),
            (s_comm, s_util, s_n))


def _migrate_batched(sim: SimState, cfg: SimConfig, params: RunParams,
                     policy: PolicyParams):
    """Migration decision round.

    The decision scan carries only the fields a migration start can change
    (host ``used``/slot counters, container status) instead of threading the
    whole SimState; the chosen (container, destination) pairs are applied in
    one vectorized pass afterwards.  The migration rule is the weighted
    destination score of ``scheduling.migrate`` — a policy whose
    ``W_MIG_ENABLE`` weight is zero yields uniform (-1, -1) decisions and
    the round leaves the state untouched.

    Returns ``(sim', (soft_mig, soft_mig_n))``; with ``cfg.soft_placement``
    the scan also sums ``scheduling.migrate_soft``'s expected-path-util
    surrogate (hard decisions unchanged), otherwise constant 0.0.
    """
    C = sim.containers.status.shape[0]
    H = sim.hosts.cap.shape[0]
    soft_on = cfg.soft_placement

    def decide(carry, _):
        if soft_on:
            used, ncont, status, s_mig, s_n = carry
        else:
            used, ncont, status = carry
        view = sim._replace(
            hosts=sim.hosts._replace(used=used, n_containers=ncont),
            containers=sim.containers._replace(status=status))
        if soft_on:
            c, dst, sv, sc = scheduling.migrate_soft(view, cfg, params,
                                                     policy)
            s_mig, s_n = s_mig + sv, s_n + sc
        else:
            c, dst = scheduling.migrate(view, cfg, params, policy)
        ok = (c >= 0) & (dst >= 0)
        cc = jnp.clip(c, 0, C - 1)
        hh = jnp.clip(dst, 0, H - 1)
        # reserve destination resources for the duration of the transfer
        hot_h = _one_hot(H, hh, ok)
        used = jnp.where(hot_h[:, None],
                         used + sim.containers.req[cc][None, :], used)
        ncont = jnp.where(hot_h, ncont + 1, ncont)
        status = jnp.where(_one_hot(C, cc, ok), STATUS_MIGRATING, status)
        out = (jnp.where(ok, cc, -1), jnp.where(ok, hh, -1))
        if soft_on:
            return (used, ncont, status, s_mig, s_n), out
        return (used, ncont, status), out

    zero = jnp.zeros((), F32)
    if soft_on:
        init = (sim.hosts.used, sim.hosts.n_containers,
                sim.containers.status, zero, zero)
        (used, ncont, status, s_mig, s_n), (cs, dsts) = jax.lax.scan(
            decide, init, None, length=cfg.migrations_per_tick)
    else:
        init = (sim.hosts.used, sim.hosts.n_containers,
                sim.containers.status)
        (used, ncont, status), (cs, dsts) = jax.lax.scan(
            decide, init, None, length=cfg.migrations_per_tick)
        s_mig = s_n = zero

    ok = cs >= 0
    # chosen containers are distinct (STATUS_MIGRATING removes them from the
    # movable set mid-scan)
    sel, m_of = _scatter_to_containers(C, cs, ok)
    dst_arr = jnp.where(sel, dsts[m_of], -1)
    ct = sim.containers
    conts = ct._replace(
        status=status,                       # MIGRATING set inside the scan
        mig_dst=jnp.where(sel, dst_arr, ct.mig_dst),
        mig_bytes_left=jnp.where(sel, cfg.mig_kb_per_gb * ct.req[:, 1],
                                 ct.mig_bytes_left),
        retry=jnp.where(sel, 0, ct.retry),
    )
    hosts = sim.hosts._replace(used=used, n_containers=ncont)
    sched = sim.sched._replace(
        migrations=sim.sched.migrations + ok.sum().astype(I32))
    return (sim._replace(hosts=hosts, containers=conts, sched=sched),
            (s_mig, s_n))


def phase_schedule_soft(sim: SimState, cfg: SimConfig, policy: PolicyParams,
                        params: RunParams | None = None):
    """:func:`phase_schedule` plus the tick's soft-surrogate terms.

    Returns ``(sim', (soft_comm, soft_util, soft_n, soft_mig,
    soft_mig_n))`` — all exact 0.0 unless ``cfg.soft_placement``.  The
    state transition is identical to :func:`phase_schedule` either way.
    """
    params = cfg.run_params() if params is None else params
    if cfg.soft_placement and not cfg.batched_placement:
        raise ValueError(
            "SimConfig.soft_placement requires batched_placement: the "
            "sequential reference path has no admit round to relax")
    sim = sim._replace(sched=sim.sched._replace(
        decisions=jnp.zeros((), I32), migrations=jnp.zeros((), I32)))

    if cfg.batched_placement:
        sim, (s_comm, s_util, s_n) = _place_batched(sim, cfg, params, policy)
    else:
        sim = _place_sequential(sim, cfg, params, policy)
        s_comm = s_util = s_n = jnp.zeros((), F32)

    sim, (s_mig, s_mig_n) = _migrate_batched(sim, cfg, params, policy)
    return sim, (s_comm, s_util, s_n, s_mig, s_mig_n)


def phase_schedule(sim: SimState, cfg: SimConfig, policy: PolicyParams,
                   params: RunParams | None = None) -> SimState:
    """Paper ``schedule`` process: place up to ``placements_per_tick``
    containers, then start up to ``migrations_per_tick`` migrations.

    Both placement paths evaluate the same weighted scoring hooks
    (``scheduling.select_key`` / ``host_row`` / the ``PlaceCarry``);
    ``cfg.batched_placement`` selects the batched round or the K=1-derived
    sequential reference.  The migration round always runs — whether the
    policy migrates, and where to, is its weight vector, not Python
    structure.
    """
    return phase_schedule_soft(sim, cfg, policy, params)[0]


def pick_comm_peers(ct: ContainerState) -> jnp.ndarray:
    """Dependent-container peer: lowest-index *deployed* container of the same
    job.  Falls back to self (same-host => loopback-rate flow) when the
    container is the only deployed member of its job.

    Containers are grouped by job id, so the lowest-index deployed member of
    each job is a ``segment_min`` over job ids — O(C), no C x C candidate
    matrix.  The second-lowest member covers the case where a container *is*
    its job's lowest-index member (the dense version excluded self via the
    identity mask).
    """
    C = ct.status.shape[0]
    deployed = ((ct.status == STATUS_RUNNING) |
                (ct.status == STATUS_COMMUNICATING) |
                (ct.status == STATUS_MIGRATING)) & (ct.host >= 0)
    idx = jnp.arange(C)
    member = deployed & (ct.job >= 0)
    seg = jnp.clip(ct.job, 0, C - 1)                     # job ids < C
    key = jnp.where(member, idx, C)                      # C = "none" sentinel
    first = jax.ops.segment_min(key, seg, num_segments=C)    # [C] per job
    is_first = member & (idx == first[seg])
    key2 = jnp.where(member & ~is_first, idx, C)
    second = jax.ops.segment_min(key2, seg, num_segments=C)
    peer = jnp.where(first[seg] == idx, second[seg], first[seg])
    has = (ct.job >= 0) & (peer < C)
    return jnp.where(has, peer, idx)


def pick_comm_peers_dense(ct: ContainerState) -> jnp.ndarray:
    """O(C^2) reference implementation of :func:`pick_comm_peers` (oracle)."""
    C = ct.status.shape[0]
    deployed = ((ct.status == STATUS_RUNNING) |
                (ct.status == STATUS_COMMUNICATING) |
                (ct.status == STATUS_MIGRATING)) & (ct.host >= 0)
    same_job = (ct.job[:, None] == ct.job[None, :]) & (ct.job[:, None] >= 0)
    cand = same_job & deployed[None, :] & ~jnp.eye(C, dtype=bool)
    first = jnp.argmax(cand, axis=1)
    has = cand.any(axis=1)
    return jnp.where(has, first, jnp.arange(C))


def phase_flows(sim: SimState, cfg: SimConfig, use_kernel: bool = False):
    """Compute this tick's flow rates (paper: iperf transfers).

    Flow f in [0, C)    = container f's active communication flow.
    Flow f in [C, 2C)   = container (f - C)'s migration flow.
    ``use_kernel`` (resolved from ``cfg.waterfill_kernel`` by the tick
    builder) routes the sparse allocation through the fused Pallas kernel.
    """
    ct = sim.containers
    C = ct.status.shape[0]
    comm_active = ct.status == STATUS_COMMUNICATING
    mig_active = ct.status == STATUS_MIGRATING

    peer = jnp.clip(ct.comm_peer, 0, C - 1)
    comm_src = ct.host
    comm_dst = ct.host[peer]
    mig_src = ct.host
    mig_dst = ct.mig_dst

    src = jnp.concatenate([comm_src, mig_src])
    dst = jnp.concatenate([comm_dst, mig_dst])
    active = jnp.concatenate([comm_active, mig_active])
    rates, util = network.flow_rates(sim.net, src, dst, active,
                                     n_rounds=cfg.waterfill_rounds,
                                     sparse=cfg.sparse_flows,
                                     use_kernel=use_kernel)
    sim = sim._replace(net=sim.net._replace(link_util=util))
    return sim, rates[:C], rates[C:], active, rates


def phase_communicate(sim: SimState, cfg: SimConfig,
                      comm_rates: jnp.ndarray) -> SimState:
    """Progress communication flows; bounded retransmission -> WAITING."""
    ct = sim.containers
    comm = ct.status == STATUS_COMMUNICATING
    new_left = jnp.where(comm, ct.comm_bytes_left - comm_rates, ct.comm_bytes_left)
    done = comm & (new_left <= 0.0)
    stalled = comm & ~done & (comm_rates < cfg.stall_rate_floor)
    retry = jnp.where(stalled, ct.retry + 1,
                      jnp.where(comm, 0, ct.retry))
    failed = stalled & (retry > cfg.max_retries)

    # failure: paper Table 2 — waiting is *undeployed*; hand back to scheduler
    hosts = _free_resources(sim.hosts, ct.req, ct.host, failed)

    status = jnp.where(done, STATUS_RUNNING, ct.status)
    status = jnp.where(failed, STATUS_WAITING, status)
    conts = ct._replace(
        status=status,
        comm_bytes_left=jnp.where(done | failed, 0.0,
                                  jnp.maximum(new_left, 0.0)),
        n_comms_left=jnp.where(done, ct.n_comms_left - 1, ct.n_comms_left),
        next_comm_at=jnp.where(done, ct.next_comm_at + ct.comm_work_gap,
                               ct.next_comm_at),
        comm_peer=jnp.where(done | failed, -1, ct.comm_peer),
        comm_time=ct.comm_time + comm.astype(F32),
        retry=jnp.where(failed, 0, retry),
        host=jnp.where(failed, -1, ct.host),
    )
    return sim._replace(hosts=hosts, containers=conts)


def phase_migrate(sim: SimState, cfg: SimConfig,
                  mig_rates: jnp.ndarray) -> SimState:
    """Progress migration flows: done -> switch host; stalled out -> WAITING."""
    ct = sim.containers
    mig = ct.status == STATUS_MIGRATING
    new_left = jnp.where(mig, ct.mig_bytes_left - mig_rates, ct.mig_bytes_left)
    done = mig & (new_left <= 0.0)
    stalled = mig & ~done & (mig_rates < cfg.stall_rate_floor)
    retry = jnp.where(stalled, ct.retry + 1, jnp.where(mig, 0, ct.retry))
    failed = stalled & (retry > cfg.max_retries)

    # done: release source; container now lives on mig_dst (already reserved)
    hosts = _free_resources(sim.hosts, ct.req, ct.host, done)
    # failed: release BOTH source and reserved destination; back to queue
    hosts = _free_resources(hosts, ct.req, ct.host, failed)
    hosts = _free_resources(hosts, ct.req, ct.mig_dst, failed)

    status = jnp.where(done, STATUS_RUNNING, ct.status)
    status = jnp.where(failed, STATUS_WAITING, status)
    conts = ct._replace(
        status=status,
        host=jnp.where(done, ct.mig_dst, jnp.where(failed, -1, ct.host)),
        mig_dst=jnp.where(done | failed, -1, ct.mig_dst),
        mig_bytes_left=jnp.where(done | failed, 0.0,
                                 jnp.maximum(new_left, 0.0)),
        n_migrations=jnp.where(done, ct.n_migrations + 1, ct.n_migrations),
        retry=jnp.where(failed, 0, retry),
    )
    return sim._replace(hosts=hosts, containers=conts)


def phase_execute(sim: SimState, cfg: SimConfig) -> SimState:
    """Paper ``run`` process: run_at += speed-of-primary-resource per second;
    crossing a communication trigger point pauses into COMMUNICATING."""
    ct = sim.containers
    H = sim.hosts.cap.shape[0]
    running = ct.status == STATUS_RUNNING
    hh = jnp.clip(ct.host, 0, H - 1)
    speed = sim.hosts.speed[hh, ct.ctype]                    # [C]
    run_at = jnp.where(running, ct.run_at + speed, ct.run_at)

    trigger = (running & (ct.n_comms_left > 0) & (run_at >= ct.next_comm_at))
    peers = pick_comm_peers(ct)
    conts = ct._replace(
        run_at=run_at,
        status=jnp.where(trigger, STATUS_COMMUNICATING, ct.status),
        comm_bytes_left=jnp.where(trigger, ct.comm_bytes, ct.comm_bytes_left),
        comm_peer=jnp.where(trigger, peers, ct.comm_peer),
        retry=jnp.where(trigger, 0, ct.retry),
    )
    return sim._replace(containers=conts)


def phase_complete(sim: SimState) -> SimState:
    ct = sim.containers
    fin = ((ct.status == STATUS_RUNNING) & (ct.run_at >= ct.duration) &
           (ct.n_comms_left <= 0))
    hosts = _free_resources(sim.hosts, ct.req, ct.host, fin)
    conts = ct._replace(
        status=jnp.where(fin, STATUS_COMPLETED, ct.status),
        finish_t=jnp.where(fin, sim.t, ct.finish_t),
        host=jnp.where(fin, -1, ct.host),
    )
    return sim._replace(hosts=hosts, containers=conts)


def phase_cost(sim: SimState) -> SimState:
    busy = sim.hosts.n_containers > 0
    cost = (sim.hosts.price * busy.astype(F32)).sum()
    hosts = sim.hosts._replace(busy_time=sim.hosts.busy_time + busy.astype(F32))
    return sim._replace(hosts=hosts, total_cost=sim.total_cost + cost)


# ---------------------------------------------------------------------------
# The tick and the scan driver
# ---------------------------------------------------------------------------
class TickInfo(NamedTuple):
    """Side-channel outputs of one full tick the telescoping driver needs
    to judge quiescence (docs/events.md) — the frozen flow rates and the
    flow inputs ``phase_flows`` consumed, so the next tick's rates are
    provably the same values without re-running waterfilling."""
    comm_rates: jnp.ndarray     # f32[C] this tick's comm allocation
    mig_rates: jnp.ndarray      # f32[C] this tick's migration allocation
    flow_active: jnp.ndarray    # bool[2C]
    all_rates: jnp.ndarray      # f32[2C]
    mid_status: jnp.ndarray     # container fields phase_flows read
    mid_host: jnp.ndarray       #   (captured post-schedule, pre-flows)
    mid_peer: jnp.ndarray
    mid_mig_dst: jnp.ndarray
    refreshed: jnp.ndarray      # bool: delay refresh fired this tick


def make_refresh_fn(cfg: SimConfig, policy: PolicyParams, params: RunParams,
                    n_hosts: int, n_nodes: int):
    """The periodic delay-matrix rebuild as a ``net -> net`` branch fn —
    ONE definition for the per-tick cond and the telescoping driver's
    hoisted boundary cond, so both compile the identical XLA region."""
    use_fw_kernel = resolve_kernel(cfg.delay_kernel)

    def refresh(net):
        with jax.named_scope("refresh"):
            return network.update_delay_matrix(
                net, n_hosts, n_nodes, mode=cfg.delay_mode,
                use_kernel=use_fw_kernel, q_coef=params.queue_coef,
                util_weight=policy.weights[W_UTIL],
                cross_leaf_ms=policy.weights[W_CROSS_LEAF])

    return refresh


def make_tick_ext(cfg: SimConfig, policy: PolicyParams, params: RunParams,
                  n_hosts: int, n_nodes: int, refresh: bool = True):
    """Build the extended tick ``(sim, tt) -> (sim', metrics, TickInfo)``.

    The scan drivers wrap it through :func:`make_tick` (dropping the
    info); the telescoping driver consumes the info directly.  Both paths
    trace the IDENTICAL phase sequence — that is what keeps a telescoped
    full tick bit-for-bit equal to a scanned one.

    ``refresh=False`` statically drops the periodic delay-refresh cond:
    the telescoping driver segments its chunk at the refresh boundaries
    and applies the refresh OUTSIDE the tick through a real ``lax.cond``
    (the boundary clock is unbatched there — see ``simulate_telescoped``),
    so its in-loop ticks must not carry a second, select-lowered copy.
    ``stats.collect`` reads nothing the refresh writes (``net`` leaves
    only), so hoisting the refresh past it is bit-exact.
    """
    use_wf_kernel = cfg.sparse_flows and resolve_kernel(cfg.waterfill_kernel)

    def tick_ext(sim: SimState, tt: jnp.ndarray):
        # each phase runs under a named scope of its own name, so a
        # profiler trace attributes every device op to its phase
        # (docs/perf.md, "Profiling a run"); scopes are metadata only
        with jax.named_scope("arrive"):
            sim, n_arrived = phase_arrive(sim)
        with jax.named_scope("schedule"):
            sim, soft = phase_schedule_soft(sim, cfg, policy, params)
        mid = sim.containers          # the state phase_flows consumes
        with jax.named_scope("flows"):
            sim, comm_rates, mig_rates, flow_active, all_rates = \
                phase_flows(sim, cfg, use_kernel=use_wf_kernel)
        with jax.named_scope("communicate"):
            sim = phase_communicate(sim, cfg, comm_rates)
        with jax.named_scope("migrate"):
            sim = phase_migrate(sim, cfg, mig_rates)
        with jax.named_scope("execute"):
            sim = phase_execute(sim, cfg)
        with jax.named_scope("complete"):
            sim = phase_complete(sim)
        with jax.named_scope("cost"):
            sim = phase_cost(sim)

        # paper ``update_delay_matrix`` process: periodic refresh
        # The predicate reads the scan's tick counter ``tt`` (== sim.t at
        # every step), NOT the carried clock: the carry is batched under a
        # vmapped sweep, and a batched predicate turns ``lax.cond`` into a
        # select that evaluates BOTH branches — every cell would pay the
        # O(H^2) refresh on every tick (measured ~1.6x per cell at
        # 500h/3000c).  ``tt`` comes from an unbatched xs, so the cond
        # survives every vmap and the refresh stays periodic.
        # ``delay_update_interval == 0`` = refresh once at t=0, then
        # frozen: a static branch, because ``mod(tt, 0)`` is undefined and
        # static-topology runs should not re-enter the O(H^2) rebuild at
        # all.
        if not refresh:
            every = jnp.asarray(False)
        elif cfg.delay_update_interval == 0:
            every = tt == 0
        else:
            every = jnp.mod(tt, cfg.delay_update_interval) == 0
        if refresh:
            sim = sim._replace(
                net=jax.lax.cond(every,
                                 make_refresh_fn(cfg, policy, params,
                                                 n_hosts, n_nodes),
                                 lambda n: n, sim.net))

        with jax.named_scope("collect"):
            m = stats.collect(sim, n_arrived, sim.sched.decisions,
                              sim.sched.migrations, params,
                              flow_active, all_rates, soft=soft)
        sim = sim._replace(t=sim.t + 1.0)
        info = TickInfo(comm_rates=comm_rates, mig_rates=mig_rates,
                        flow_active=flow_active, all_rates=all_rates,
                        mid_status=mid.status, mid_host=mid.host,
                        mid_peer=mid.comm_peer, mid_mig_dst=mid.mig_dst,
                        refreshed=every)
        return sim, m, info

    return tick_ext


def make_tick(cfg: SimConfig, policy: PolicyParams, params: RunParams,
              n_hosts: int, n_nodes: int):
    """Build the jit-able tick function ``(sim, _) -> (sim', metrics)``.

    ``policy`` and ``params`` are traced pytrees closed over by the tick —
    the whole point of the policy-as-data split: a different policy id,
    weight vector, or runtime knob is new *data* through the SAME compiled
    tick, and a batch axis on either sweeps them under ``vmap``.

    The Pallas kernel flags are resolved at trace time in
    :func:`make_tick_ext` (``repro.kernels.resolve_kernel``: compiled
    kernel on TPU/GPU, jnp reference on CPU under 'auto') — they are
    static config, part of the jit cache key via ``cfg``, never traced
    values.
    """
    tick_ext = make_tick_ext(cfg, policy, params, n_hosts, n_nodes)

    def tick(sim: SimState, tt: jnp.ndarray) -> Tuple[SimState, TickMetrics]:
        sim, m, _ = tick_ext(sim, tt)
        return sim, m

    return tick


def simulate(sim0: SimState, cfg: SimConfig, policy: PolicyParams,
             n_hosts: int, n_nodes: int, horizon: int,
             params: RunParams) -> Tuple[SimState, TickMetrics]:
    """The un-jitted simulation core: apply the runtime link params, then
    scan ``horizon`` ticks.  ``run_sim`` jits it for standalone runs;
    ``repro/launch/sweep.py`` vmaps it over policy x scenario x seed and
    jits ONCE — both paths trace the identical function, which is what
    makes sweep cells bit-for-bit equal to standalone runs.
    """
    sim0 = sim0._replace(net=network.apply_link_params(
        sim0.net, params.bw_mbps, params.loss))
    tick = make_tick(cfg, policy, params, n_hosts, n_nodes)
    # xs = the tick counter, deliberately NOT part of the carried state: it
    # stays unbatched under the sweep's vmaps, so the periodic delay
    # refresh keeps its lax.cond (see make_tick).
    return jax.lax.scan(tick, sim0, jnp.arange(horizon, dtype=I32))


# ---------------------------------------------------------------------------
# Streaming (chunked) driver: O(state) memory at any horizon
# ---------------------------------------------------------------------------
def simulate_chunk(sim: SimState, acc, t0: jnp.ndarray, cfg: SimConfig,
                   policy: PolicyParams, n_hosts: int, n_nodes: int,
                   chunk: int, params: RunParams):
    """One streaming chunk: ``chunk`` ticks starting at tick ``t0``, folding
    each tick's metrics into the ``SummaryAcc`` carry instead of stacking
    them as scan ys — the scan emits NOTHING, so device memory is O(state)
    regardless of horizon.

    ``t0`` is a *traced* scalar (one compilation covers every chunk) and,
    like the tick counter xs, deliberately unbatched under the sweep's
    vmaps — both the periodic delay-refresh cond and the t0 == 0 cond below
    survive as real branches.  The runtime link params are applied inside
    the t0 == 0 cond, NOT unconditionally: ``apply_link_params`` rebuilds
    ``comm_cost`` from the static tables, so re-applying it at a chunk
    boundary would clobber the dynamically refreshed matrix mid-run and
    break chunked == unchunked equality.
    """
    sim = jax.lax.cond(
        t0 == 0,
        lambda s: s._replace(net=network.apply_link_params(
            s.net, params.bw_mbps, params.loss)),
        lambda s: s, sim)
    tick = make_tick(cfg, policy, params, n_hosts, n_nodes)

    def body(carry, tt):
        s, a = carry
        s, m = tick(s, tt)
        with jax.named_scope("collect"):
            a = stats.acc_update(a, m)
        return (s, a), None

    (sim, acc), _ = jax.lax.scan(body, (sim, acc),
                                 t0 + jnp.arange(chunk, dtype=I32))
    return sim, acc


# ---------------------------------------------------------------------------
# Telescoping (macro-tick) driver: closed-form advancement over quiescent
# intervals (docs/events.md)
# ---------------------------------------------------------------------------
def _event_horizon(sim: SimState, cfg: SimConfig, info: TickInfo,
                   t: jnp.ndarray, t_end: jnp.ndarray,
                   speed: jnp.ndarray) -> jnp.ndarray:
    """Closed-form event horizon after the full tick at ``t``: the first
    tick index that could be a non-quiescent event, as an f32 bound on the
    cheap-tick indices (cheap ticks allowed while ``t' < horizon``).

    Exact components (integer / monotone arithmetic):
    * segment end ``t_end`` — the telescoping driver segments its chunk at
      the ``delay_update_interval`` refresh boundaries, so the next
      refresh (and the chunk end) both arrive through this cap;
    * next container arrival — ``ceil`` of the min pending ``submit_t``
      (``phase_arrive`` fires at the first integer tick >= submit).

    Estimated components (ceil-divisions of remaining work by the frozen
    rates — the per-tick path subtracts the rate REPEATEDLY in f32, so
    these can be off by a tick either way from rounding):
    * earliest comm / migration flow finish;
    * earliest comm trigger or completion of a running container.

    The estimates are only a bound: the telescoping loop re-checks the
    exact one-step predicates (the same comparisons the per-tick phases
    make) before every cheap tick, so an overestimate stops early on the
    exact check and an underestimate merely costs one extra full tick.
    Equality with the per-tick path never rests on the divisions.
    """
    ct = sim.containers
    inf = jnp.float32(jnp.inf)

    def ceil_ticks(remaining, rate, mask):
        k = jnp.ceil(remaining / jnp.maximum(rate, 1e-30))
        return jnp.where(mask & (rate > 0), k, inf).min()

    comm = ct.status == STATUS_COMMUNICATING
    mig = ct.status == STATUS_MIGRATING
    running = ct.status == STATUS_RUNNING
    t_f = t.astype(F32)
    # arrivals after the full tick at t: phase_arrive at tick ti fires on
    # submit_t <= ti, so the first arrival event is ceil(min pending
    # submit).  Queried against t (NOT the post-tick clock t+1): a submit
    # inside (t, t+1] arrives at the very next tick.
    horizon = jnp.minimum(t_end.astype(F32),
                          jnp.ceil(workload.next_arrival_after(ct, t_f)))
    horizon = jnp.minimum(
        horizon, t_f + ceil_ticks(ct.comm_bytes_left, info.comm_rates, comm))
    horizon = jnp.minimum(
        horizon, t_f + ceil_ticks(ct.mig_bytes_left, info.mig_rates, mig))
    horizon = jnp.minimum(
        horizon, t_f + ceil_ticks(ct.next_comm_at - ct.run_at, speed,
                                  running & (ct.n_comms_left > 0)))
    horizon = jnp.minimum(
        horizon, t_f + ceil_ticks(ct.duration - ct.run_at, speed,
                                  running & (ct.n_comms_left <= 0)))
    return horizon


def simulate_telescoped(sim: SimState, acc, t0: jnp.ndarray, cfg: SimConfig,
                        policy: PolicyParams, n_hosts: int, n_nodes: int,
                        chunk: int, params: RunParams,
                        with_stats: bool = False):
    """:func:`simulate_chunk` twin with event-horizon tick telescoping.

    Each macro step runs ONE full tick, then — if the resulting state is
    *quiescent* (nothing schedulable, no migration trigger armed, no
    stalled flow, and the tick changed none of the inputs waterfilling
    reads, so the frozen rates provably carry forward) — advances up to
    the closed-form event horizon in cheap ticks: only the linear O(C+H)
    updates a quiescent full tick would make (work progress at frozen
    rates and speeds, busy/comm clocks, cost), each applying the SAME f32
    operations in the SAME order, so the final state is bit-for-bit the
    per-tick path's.  The dt skipped ticks' metrics — constant over the
    interval by construction — fold in closed form through
    ``stats.acc_update_weighted`` (dt-weighted Kahan, weighted Welford):
    integer sums/counts/peaks exact, float means to ~1 ulp.

    Under a vmapped sweep ``dt`` is per-cell: the while loops run until
    every lane's clock reaches the segment end (``max(t)`` across the
    batch), finished lanes riding along masked.  The chunk is SEGMENTED
    at the ``delay_update_interval`` refresh boundaries — every lane
    stops there (the event horizon is capped by the segment end), so the
    lanes re-synchronize at each boundary and the periodic delay refresh
    applies through a real ``lax.cond`` on an UNBATCHED boundary clock.
    That is a bitwise requirement, not a nicety: a batched predicate
    lowers the cond to a select whose branch fuses into the loop body,
    and XLA's fusion-dependent f32 contraction measurably shifted
    ``delay_matrix`` (~1 ulp) against the per-tick path; a real cond
    branch is its own XLA region and compiles identically in both
    drivers.  ``delay_update_interval=0`` (refresh once at t=0, then
    frozen) collapses the chunk to one segment.  docs/events.md walks
    the exactness argument and the honest list of what forces dt=1.

    ``cfg.soft_placement`` is rejected: ``lax.while_loop`` has no
    reverse-mode autodiff, so the surrogate's gradient path cannot thread
    a telescoped run — use the chunked scan for grad work.
    ``with_stats`` additionally returns the number of FULL ticks executed
    (i32; ``horizon - n_full`` ticks were telescoped) for benches/tests.
    """
    if cfg.soft_placement:
        raise ValueError(
            "telescope + soft_placement is unsupported: the surrogate "
            "exists for jax.grad, and lax.while_loop (the telescoping "
            "driver) has no reverse-mode autodiff — run grad work through "
            "the chunked scan (ExecPlan(chunk=...)) instead")
    sim = jax.lax.cond(
        t0 == 0,
        lambda s: s._replace(net=network.apply_link_params(
            s.net, params.bw_mbps, params.loss)),
        lambda s: s, sim)
    tick_ext = make_tick_ext(cfg, policy, params, n_hosts, n_nodes,
                             refresh=False)
    refresh_fn = make_refresh_fn(cfg, policy, params, n_hosts, n_nodes)
    K = cfg.delay_update_interval
    H = sim.hosts.cap.shape[0]
    t_end = t0 + chunk
    zero_i = jnp.zeros((), I32)
    # Topology leaves no phase ever writes (the sweep keeps them UNBATCHED
    # through its vmap for the fast-path gathers, sweep.py's
    # STATIC_TOPOLOGY_LEAVES).  The batched-cond while_loop select-masks
    # every carry leaf, which would swap in lane-batched copies and flip
    # the delay-refresh gathers to batched indices — a different f32
    # reduction order than the per-tick path, breaking bitwise equality.
    # Pin them to the closed-over inputs each step: values are identical
    # either way, the gathers keep unbatched operands, and the returned
    # state's topology leaves stay unbatched through the vmap.
    net0, leaf0 = sim.net, sim.hosts.leaf

    def pin(s):
        return s._replace(
            hosts=s.hosts._replace(leaf=leaf0),
            net=s.net._replace(link_u=net0.link_u, link_v=net0.link_v,
                               path_links=net0.path_links,
                               path_nlinks=net0.path_nlinks))

    def advance(sim, acc, t, info, blocked, seg_end):
        """Quiescence test + cheap-tick advancement after the full tick
        at ``t``: returns ``(sim, acc, t2)`` with ``t2`` in
        ``(t, seg_end]``.  ``blocked`` forces dt=1 when the caller just
        applied the boundary delay refresh — the rebuilt fabric means the
        frozen rates do not provably carry forward."""
        ct = sim.containers
        st = ct.status
        # Quiescence: the tick's own post-flow phases changed none of the
        # inputs waterfilling reads and no refresh touched the fabric, so
        # the frozen rates ARE the next tick's rates; nothing is waiting
        # for the scheduler; the migration trigger cannot arm (hosts.used
        # is constant over the interval); no active flow is stalling
        # (stalls increment retry every tick).
        quiet = ((st == info.mid_status).all()
                 & (ct.host == info.mid_host).all()
                 & (ct.comm_peer == info.mid_peer).all()
                 & (ct.mig_dst == info.mid_mig_dst).all()
                 & ~blocked)
        quiet &= ~((st == STATUS_INACTIVE) | (st == STATUS_WAITING)).any()
        util = sim.hosts.used / jnp.maximum(sim.hosts.cap, 1e-6)
        quiet &= ~((policy.weights[W_MIG_ENABLE] > 0)
                   & (util.max(axis=1) > params.overload_threshold).any())
        quiet &= ~(info.flow_active
                   & (info.all_rates < cfg.stall_rate_floor)).any()

        # Per-interval constants (statuses and placement are frozen).
        comm = st == STATUS_COMMUNICATING
        mig = st == STATUS_MIGRATING
        running = st == STATUS_RUNNING
        speed = sim.hosts.speed[jnp.clip(ct.host, 0, H - 1), ct.ctype]
        comm_f = comm.astype(F32)
        busy_f = (sim.hosts.n_containers > 0).astype(F32)
        cost_q = (sim.hosts.price * busy_f).sum()
        horizon = _event_horizon(sim, cfg, info, t, seg_end, speed)
        comm_rates, mig_rates = info.comm_rates, info.mig_rates

        def cheap_cond(c):
            s, ti = c
            cc = s.containers
            ok = ti.astype(F32) < horizon
            # exact one-step event predicates — the comparisons the
            # per-tick phases would make at tick ti, on the live state
            ok &= ~(comm & (cc.comm_bytes_left - comm_rates <= 0.0)).any()
            ok &= ~(mig & (cc.mig_bytes_left - mig_rates <= 0.0)).any()
            new_run = cc.run_at + speed
            ok &= ~(running & (cc.n_comms_left > 0)
                    & (new_run >= cc.next_comm_at)).any()
            ok &= ~(running & (cc.n_comms_left <= 0)
                    & (new_run >= cc.duration)).any()
            return quiet & ok

        def cheap_body(c):
            s, ti = c
            cc = s.containers
            # exactly the f32 updates a quiescent full tick makes, in the
            # per-tick operation order (phase_communicate / phase_migrate
            # clamp through maximum(new_left, 0); phase_execute adds the
            # speed gather; phase_cost re-adds the same cost scalar)
            conts = cc._replace(
                comm_bytes_left=jnp.maximum(
                    jnp.where(comm, cc.comm_bytes_left - comm_rates,
                              cc.comm_bytes_left), 0.0),
                mig_bytes_left=jnp.maximum(
                    jnp.where(mig, cc.mig_bytes_left - mig_rates,
                              cc.mig_bytes_left), 0.0),
                comm_time=cc.comm_time + comm_f,
                run_at=jnp.where(running, cc.run_at + speed, cc.run_at),
                retry=jnp.where(comm | mig, 0, cc.retry),
            )
            hosts = s.hosts._replace(busy_time=s.hosts.busy_time + busy_f)
            sched = s.sched._replace(decisions=zero_i, migrations=zero_i)
            s = s._replace(containers=conts, hosts=hosts, sched=sched,
                           total_cost=s.total_cost + cost_q,
                           t=s.t + 1.0)
            return s, ti + 1

        sim, t2 = jax.lax.while_loop(cheap_cond, cheap_body, (sim, t + 1))
        dt = t2 - (t + 1)
        # the skipped ticks' metrics, constant over the interval: no
        # arrivals/decisions/migrations, frozen flows, same state counts
        with jax.named_scope("collect"):
            m_q = stats.collect(sim, zero_i, zero_i, zero_i, params,
                                info.flow_active, info.all_rates)
            acc = stats.acc_update_weighted(acc, m_q, dt)
        return sim, acc, t2

    def macro_of(seg_end):
        def macro(carry):
            sim, acc, t, n_full = carry
            sim = pin(sim)
            sim, m, info = tick_ext(sim, t)
            with jax.named_scope("collect"):
                acc = stats.acc_update(acc, m)
            sim, acc, t2 = advance(sim, acc, t, info, jnp.asarray(False),
                                   seg_end)
            return sim, acc, t2, n_full + 1
        return macro

    def run_segment(carry, seg_start, seg_end, refresh_due):
        """One refresh-bounded segment ``[seg_start, seg_end)``.  Every
        lane enters at exactly ``seg_start`` — the previous segment's
        event horizon was capped there — so the first tick runs on the
        UNBATCHED boundary clock and the delay refresh applies through a
        real ``lax.cond``: the same insulated XLA branch region the
        per-tick path compiles (see the docstring's bitwise argument)."""
        sim, acc, n_full = carry
        sim = pin(sim)
        sim, m, info = tick_ext(sim, seg_start)
        sim = sim._replace(net=jax.lax.cond(refresh_due, refresh_fn,
                                            lambda n: n, sim.net))
        with jax.named_scope("collect"):
            acc = stats.acc_update(acc, m)
        sim, acc, t2 = advance(sim, acc, seg_start, info, refresh_due,
                               seg_end)
        sim, acc, _, n_full = jax.lax.while_loop(
            lambda c: c[2] < seg_end, macro_of(seg_end),
            (sim, acc, t2, n_full + 1))
        return sim, acc, n_full

    if K == 0:
        # one segment: refresh once at t=0 (first chunk only), then the
        # fabric is frozen for the whole run — the documented fast path
        sim, acc, n_full = run_segment((sim, acc, zero_i), t0, t_end,
                                       t0 == 0)
    else:
        # chunk//K + 2 boundary-aligned segments cover [t0, t_end) for
        # ANY t0: a partial head segment up to the next multiple of K,
        # then K-sized segments; trailing empties are skipped below
        n_seg = chunk // K + 2

        def seg_step(carry, s):
            start = jnp.where(s == 0, t0, (t0 // K + s) * K)
            end = jnp.minimum((t0 // K + s + 1) * K, t_end)
            due = jnp.mod(start, K) == 0
            # real cond — s and t0 stay unbatched under the sweep's
            # vmap, so empty segments (start past t_end) skip entirely
            return jax.lax.cond(start < end,
                                lambda c: run_segment(c, start, end, due),
                                lambda c: c, carry), None

        (sim, acc, n_full), _ = jax.lax.scan(
            seg_step, (sim, acc, zero_i), jnp.arange(n_seg, dtype=I32))
    sim = pin(sim)
    if with_stats:
        return sim, acc, n_full
    return sim, acc


@functools.lru_cache(maxsize=None)
def _chunk_step_jit(telescope: bool = False):
    """The jitted per-chunk step.  The (state, accumulator) carry is
    donated, so XLA reuses its buffers across chunks.  ``telescope`` swaps
    the scan for the macro-tick driver — same signature, same carry."""
    fn = simulate_telescoped if telescope else simulate_chunk
    def step(sim, acc, t0, policy, params, cfg, n_hosts, n_nodes, chunk):
        return fn(sim, acc, t0, cfg, policy, n_hosts, n_nodes, chunk, params)
    return jax.jit(step, static_argnames=("cfg", "n_hosts", "n_nodes",
                                          "chunk"),
                   donate_argnums=(0, 1))


def run_sim_chunked(sim0: SimState, cfg: SimConfig, policy: PolicyParams,
                    n_hosts: int, n_nodes: int, horizon: int, chunk: int,
                    params: RunParams | None = None,
                    telescope: bool = False):
    """Streaming ``run_sim``: host loop over jit-per-chunk steps with a
    donated carry; returns (final state, ``OnlineSummary``).

    The device accumulator resets every chunk and the host folds it into
    f64/i64 totals (``stats.online_fold``), so integer sums stay exact and
    float sums hold ~f32-ulp accuracy out to arbitrary horizons —
    ``check_chunk`` bounds the chunk size so no i32 sum can overflow
    within one chunk (the dt-weighted telescoping folds total exactly what
    the repeated folds would, so the same bound covers both drivers).
    Final state is bit-for-bit the stacked path's (tests/test_streaming.py
    / test_telescope.py); only the metrics representation differs.
    """
    params = cfg.run_params() if params is None else params
    stats.check_chunk(chunk, int(sim0.containers.status.shape[-1]))
    step = _chunk_step_jit(telescope)
    # donation consumes the caller's buffers on the first chunk — keep
    # sim0 valid for reuse (launch/sim.py shares one built state across
    # every policy run)
    sim = jax.tree.map(jnp.array, sim0)
    online = stats.online_init()
    t0 = 0
    # host spans on the profiler's clock (docs/perf.md, "Profiling a
    # run"): one ``sim.run`` around the loop, one ``sim.chunk`` per chunk
    # from its dispatch to the end of its fold; ``ticks`` sums to the
    # horizon.  With the profiler off each is one cheap TraceMe check.
    with jax.profiler.TraceAnnotation("sim.run", horizon=horizon,
                                      chunk=chunk):
        while t0 < horizon:
            sz = min(chunk, horizon - t0)   # tail chunk: one extra compile
            with jax.profiler.TraceAnnotation("sim.chunk", t0=t0, ticks=sz):
                sim, acc = step(sim, stats.acc_init(), jnp.asarray(t0, I32),
                                policy, params, cfg=cfg, n_hosts=n_hosts,
                                n_nodes=n_nodes, chunk=sz)
                # syncs; promotes to 64-bit
                online = stats.online_fold(online, acc)
            t0 += sz
    return sim, online


# Nothing about the policy registry is baked into compiled programs with
# branch-free scoring — a policy is a weight vector, so registering a new
# one after a compiled run simply feeds new data through the executable.
@functools.partial(jax.jit, static_argnames=("cfg", "n_hosts", "n_nodes",
                                             "horizon"))
def _run_sim_jit(sim0, cfg, policy, params, n_hosts, n_nodes, horizon):
    return simulate(sim0, cfg, policy, n_hosts, n_nodes, horizon, params)


def resolve_plan(plan: ExecPlan | None, cfg: SimConfig,
                 **legacy) -> tuple[ExecPlan, SimConfig]:
    """Shared plan/legacy-kwarg resolution for every run entry point.

    ``legacy`` maps old kwarg names to their (possibly None) values; any
    non-None value raises a loud ``DeprecationWarning`` and is folded into
    the plan (one deprecation cycle, then the kwargs go away).  Passing
    both a plan and a legacy kwarg is an error — silently preferring one
    would hide the conflict.  Returns the resolved plan and the config
    with the plan's kernel selectors applied (the jit cache key stays the
    config, exactly as before).
    """
    used = {k: v for k, v in legacy.items() if v is not None}
    if used:
        if plan is not None:
            raise TypeError(
                f"pass execution options via plan= OR the deprecated "
                f"kwargs {sorted(used)}, not both")
        warnings.warn(
            f"the {sorted(used)} kwargs are deprecated; pass "
            f"plan=ExecPlan({', '.join(f'{k}={v!r}' for k, v in sorted(used.items()))}) "
            f"instead", DeprecationWarning, stacklevel=3)
        plan = ExecPlan(**used)
    plan = ExecPlan() if plan is None else plan
    return plan, plan.apply_to_config(cfg)


def run_sim(sim0: SimState, cfg: SimConfig, policy: PolicyParams,
            n_hosts: int, n_nodes: int, horizon: int,
            params: RunParams | None = None, chunk: int | None = None,
            plan: ExecPlan | None = None
            ) -> Tuple[SimState, TickMetrics]:
    """Run ``horizon`` ticks; returns (final state, metrics).

    Execution options ride in ``plan`` (:class:`~repro.core.types.ExecPlan`
    — chunking and kernel selection apply here; sweep/dist fields are
    ignored).  ``plan=None`` (default, right for short horizons) stacks
    per-tick ``TickMetrics`` over the whole run — O(horizon) memory, the
    streaming path's oracle.  A ``plan.chunk`` streams the run through
    :func:`run_sim_chunked` instead: same final state bit-for-bit, an
    f64/i64 ``OnlineSummary`` instead of the stacked series, O(state)
    memory at any horizon.  ``report.summarize`` accepts either form.
    The bare ``chunk=`` kwarg is deprecated (one cycle).

    A ``plan.telescope`` routes the run through the macro-tick driver
    (:func:`simulate_telescoped`): quiescent intervals advance in cheap
    linear ticks up to the closed-form event horizon, metrics fold
    dt-weighted.  Telescoped runs always report an ``OnlineSummary``
    (skipped ticks have no per-tick rows to stack); without ``plan.chunk``
    the whole horizon runs as one span.  Final state stays bit-for-bit
    the per-tick path's; docs/events.md.

    Only ``cfg`` (after the plan's kernel selectors fold in), the shape
    arguments, and the chunk size are static.  ``policy`` (a weight
    vector) and ``params`` (bw/loss/queue/threshold knobs, defaulting from
    the config) are DATA: every policy — including ones registered after
    this call — and every runtime-parameter point reuses one compilation
    per (config, shapes) combination.
    """
    plan, cfg = resolve_plan(plan, cfg, chunk=chunk)
    params = cfg.run_params() if params is None else params
    if plan.telescope:
        return run_sim_chunked(sim0, cfg, policy, n_hosts, n_nodes, horizon,
                               plan.chunk or horizon, params=params,
                               telescope=True)
    if plan.chunk is not None:
        return run_sim_chunked(sim0, cfg, policy, n_hosts, n_nodes, horizon,
                               plan.chunk, params=params)
    return _run_sim_jit(sim0, cfg, policy, params, n_hosts, n_nodes, horizon)
