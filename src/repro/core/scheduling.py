"""Container scheduling module (paper §3.5) — branch-free scoring.

A scheduling algorithm IS a weight vector.  The engine computes one shared
**feature bank** and every decision is a weighted sum:

* selection: ``priority[c] = sel_features(c) @ w`` ranked by
  :func:`rank_key` (lower = scheduled earlier);
* placement: ``score[h] = placement_features(h) @ w`` for each candidate,
  argmin over the feasible hosts (free CPU/mem, host utilization,
  round-robin recency, same-job co-location count, mean ``comm_cost`` to
  deployed peers, access-link utilization, cross-leaf peer fraction — the
  ``F_*`` enum in ``types.py``);
* migration: the trigger is a mask weight (``W_MIG_ENABLE``; 0 reproduces
  the old no-op branch exactly) and the destination is
  ``migration_features(h) @ w`` (host index, bottleneck path utilization
  from the source, cross-leaf indicator, worst fit).

There is no ``lax.switch``, no branch table and no per-policy code: the
six paper/DRAPS policies ship as named weight vectors in the registry
(one-hot or disjoint-support vectors, so each reproduces its former
branch's scores **bit-for-bit** — every feature is finite by construction
and a zero weight contributes an exact ``0.0``).  Consequences the old
branch dispatch could not offer:

* a policy-batched sweep pays ONE feature-bank evaluation per cell instead
  of evaluating every registered branch under ``vmap``
  (``docs/sweeps.md``);
* registering a policy never invalidates compiled programs — new policies
  are new *data* through the same executable;
* weight search (``repro.launch.tune``) is just a batch axis on
  ``PolicyParams.weights``.

Users extend by registering a weight vector — ``register("mine",
dict(row_worst_fit=1.0, sel_duration=0.1))`` — the paper's "flexible and
scalable interface for scheduling algorithms" with no code at all.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import network
from repro.core.datacenter import SimConfig
from repro.core.types import (
    M_PATH_UTIL, NUM_MIG_FEATURES, NUM_POLICY_WEIGHTS, NUM_ROW_FEATURES,
    STATUS_COMMUNICATING, STATUS_INACTIVE, STATUS_MIGRATING, STATUS_RUNNING,
    STATUS_WAITING, W_MIG0, W_MIG_ENABLE, W_ROW0, W_RR_TRACK, W_SEL_DURATION,
    W_SEL_SUBMIT, WEIGHT_NAMES, PolicyParams, RunParams, SimState,
)

BIG = np.float32(1e18)           # host-score sentinel (infeasible)
INT_BIG = np.int32(2**31 - 1)    # selection-key sentinel (unschedulable)


# ---------------------------------------------------------------------------
# Shared predicates
# ---------------------------------------------------------------------------
def feasible_hosts(cap: jnp.ndarray, used: jnp.ndarray, ncont: jnp.ndarray,
                   req: jnp.ndarray, cfg: SimConfig) -> jnp.ndarray:
    """Hosts that can take a container requesting ``req``: resource headroom
    + a free container slot (``max_containers_per_host``, the per-host
    net-node cap).

    Takes the raw counters rather than the SimState so the engine can feed
    it either the live state (sequential path, migration sources) or the
    in-round counters carried by the batched admit scan — one predicate,
    every feasibility decision.
    """
    fits = ((used + req[None, :]) <= cap).all(axis=1)
    return fits & (ncont < cfg.max_containers_per_host)


def schedulable_mask(sim: SimState) -> jnp.ndarray:
    """Containers eligible for (re)placement: submitted+unscheduled or waiting."""
    st = sim.containers.status
    arrived = sim.containers.submit_t <= sim.t
    return arrived & ((st == STATUS_INACTIVE) | (st == STATUS_WAITING))


def rank_key(values: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """Sortable i32 selection key: rank under lexicographic (values, index).

    A stable argsort gives every slot its rank (< C, so no overflow at any
    capacity — unlike ``values * C + index`` float encodings, which lose the
    index tie-break once the combined key exceeds f32's 2^24 integer range).
    Slots outside ``mask`` get ``INT_BIG``.

    The rank is the inverse of the sort permutation, so a second argsort
    computes it scatter-free — identical integers to the former
    ``zeros.at[order].set(arange)`` scatter, without XLA:CPU's slow
    batched-scatter lowering when the tick is vmapped over sweep cells.
    """
    order = jnp.argsort(values, stable=True)
    rank = jnp.argsort(order).astype(jnp.int32)
    return jnp.where(mask, rank, INT_BIG)


def select_key_fifo(sim: SimState) -> jnp.ndarray:
    """Paper default selection: earliest-submitted first, index tie-break.
    (== the generic :func:`select_key` with ``sel_submit=1`` and every other
    selection weight 0 — kept as the named reference.)"""
    return rank_key(sim.containers.submit_t, schedulable_mask(sim))


def _first_true(order_key: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """Index minimizing order_key among mask; -1 if mask empty."""
    key = jnp.where(mask, order_key, BIG)
    return jnp.where(mask.any(), jnp.argmin(key), -1)


def soft_assign(row: jnp.ndarray, feas: jnp.ndarray,
                tau: jnp.ndarray) -> jnp.ndarray:
    """Softmax relaxation of ``argmin over the feasible hosts``.

    ``q[h] = softmax(-row/tau)[h]`` over ``feas``; infeasible hosts get an
    exact 0.0 and an all-infeasible row returns all-zero (NOT uniform — a
    no-decision contributes nothing to the surrogate sums).  NaN-safety
    under ``jax.grad`` is load-bearing: the row is shifted by its feasible
    minimum BEFORE the masked exp, so every exponent is finite and
    non-positive (``exp <= 1``) and no ``0 * inf`` appears in either the
    primal or the cotangent.  As ``tau -> 0`` the weights underflow to the
    exact one-hot of the hard argmin — the annealing limit the oracle
    tests rely on.
    """
    feas_f = feas.astype(row.dtype)
    lo = jnp.min(jnp.where(feas, row, BIG))
    shifted = jnp.where(feas, row - lo, 0.0)
    e = jnp.exp(-shifted / tau) * feas_f
    return e / jnp.maximum(e.sum(), jnp.float32(1e-30))


# ---------------------------------------------------------------------------
# The placement carry
#
# The one pytree every policy's round shares: Round's rotating pointer
# (tracked only when ``W_RR_TRACK`` is set) and the same-job co-location
# counts the F_COLOC / F_COMM / F_CROSS_LEAF features read.
# ---------------------------------------------------------------------------
class PlaceCarry(NamedTuple):
    rr: jnp.ndarray      # i32[]    Round's rotating last-used-host pointer
    counts: jnp.ndarray  # f32[K,H] deployed same-job containers per host
    # same-job peers on the HOST'S OWN leaf, per (candidate, host) — the
    # F_CROSS_LEAF numerator.  Maintained incrementally (exact integer
    # adds): the alternative, a segment_sum over leaf ids per admit step,
    # is a batched scatter inside the hot scan — the PR 4 anti-pattern.
    leafpeers: jnp.ndarray  # f32[K,H]


def same_job_host_counts(sim: SimState, cand: jnp.ndarray) -> jnp.ndarray:
    """[K, H] deployed same-job container count per host, per candidate.

    One ``segment_sum`` of the C deployed containers onto a small [K, H]
    table keyed by (first candidate sharing the container's job, host) —
    the pad-slot trick in two dimensions (slot K*H swallows containers
    matching no candidate).  Candidates sharing a job then gather the first
    sharer's row.  Replaces the K vmapped per-candidate scatter-adds of the
    PR 2 form (kept as :func:`same_job_host_counts_scatter`); counts are
    integer-valued, so the regrouped sum is exact and both forms agree
    bit-for-bit.
    """
    H = sim.hosts.cap.shape[0]
    K = cand.shape[0]
    ct = sim.containers
    st = ct.status
    deployed = (((st == STATUS_RUNNING) | (st == STATUS_COMMUNICATING) |
                 (st == STATUS_MIGRATING)) & (ct.host >= 0))
    jobs_k = ct.job[cand]                                    # [K]
    eq = ct.job[:, None] == jobs_k[None, :]                  # [C, K]
    hit = eq.any(axis=1) & deployed
    k_first = jnp.argmax(eq, axis=1)                         # [C]
    hostc = jnp.clip(ct.host, 0, H - 1)
    seg = jnp.where(hit, k_first * H + hostc, K * H)
    table = jax.ops.segment_sum(
        hit.astype(jnp.float32), seg, num_segments=K * H + 1)[:K * H]
    kk_first = jnp.argmax(jobs_k[None, :] == jobs_k[:, None], axis=1)
    return table.reshape(K, H)[kk_first]


def same_job_host_counts_scatter(sim: SimState,
                                 cand: jnp.ndarray) -> jnp.ndarray:
    """PR 2 per-candidate scatter-add form — unit oracle for the segment-sum
    rewrite (tests/test_scatter_free.py)."""
    H = sim.hosts.cap.shape[0]
    ct = sim.containers
    st = ct.status
    deployed = (((st == STATUS_RUNNING) | (st == STATUS_COMMUNICATING) |
                 (st == STATUS_MIGRATING)) & (ct.host >= 0))
    same = deployed[None, :] & (ct.job[None, :] == ct.job[cand][:, None])
    hostc = jnp.clip(ct.host, 0, H - 1)
    return jax.vmap(
        lambda s: jnp.zeros((H,), jnp.float32).at[hostc].add(s)
    )(same.astype(jnp.float32))


def _worst_fit_row(sim: SimState, used: jnp.ndarray) -> jnp.ndarray:
    """Most total normalized free resources first (lower key = better)."""
    free = (sim.hosts.cap - used) / jnp.maximum(sim.hosts.cap, 1e-6)
    return -free.sum(axis=1)


# ---------------------------------------------------------------------------
# The generic scoring hooks — the ONLY policy surface the engine consumes.
# Everything is a weighted sum over a feature bank, so a batch of policies
# is a batch axis on ``PolicyParams.weights`` and nothing else.
#
# EXACTNESS CONTRACT: every feature must be FINITE for every reachable
# state.  A zero weight then contributes an exact 0.0 to the dot product,
# which is what lets one-hot legacy vectors reproduce the former per-policy
# branches bit-for-bit (0.0 * inf would poison the score with NaN).
# ---------------------------------------------------------------------------
def select_key(sim: SimState, pol: PolicyParams) -> jnp.ndarray:
    """i32[C] selection ranks from the weighted container-priority score.

    ``priority = w[sel_submit] * submit_t + w[sel_duration] * duration``;
    lower = scheduled earlier, ``INT_BIG`` = not schedulable this tick.
    (``submit_t`` is +inf on unborn slots; they are masked out, and NaNs a
    zero submit-weight would produce there sort last without disturbing
    the ranks of schedulable containers.)
    """
    ct = sim.containers
    w = pol.weights
    priority = w[W_SEL_SUBMIT] * ct.submit_t + w[W_SEL_DURATION] * ct.duration
    return rank_key(priority, schedulable_mask(sim))


def init_place_carry(sim: SimState, cand: jnp.ndarray,
                     pol: PolicyParams) -> PlaceCarry:
    """One generic carry for every policy: the co-location counts feed the
    F_COLOC/F_COMM/F_CROSS_LEAF features (an exact 0.0 in the score when
    their weights are zero), the pointer starts from the persisted
    ``rr_pointer`` and only moves when ``W_RR_TRACK`` is set.

    The per-leaf peer totals are reduced ONCE per round here (and then
    maintained by elementwise adds in :func:`update_place_carry`), so the
    admit scan itself stays free of segment reductions."""
    H = sim.hosts.cap.shape[0]
    counts = same_job_host_counts(sim, cand)
    per_leaf = jax.vmap(lambda c: jax.ops.segment_sum(
        c, sim.hosts.leaf, num_segments=H))(counts)          # [K, leafslot]
    return PlaceCarry(rr=sim.sched.rr_pointer, counts=counts,
                      leafpeers=per_leaf[:, sim.hosts.leaf])


def _row_feature_columns(sim: SimState, cfg: SimConfig, params: RunParams,
                         carry: PlaceCarry, k, cand,
                         used: jnp.ndarray) -> tuple:
    """The shared feature columns (``F_*`` order) for candidate ``k`` —
    computed ONCE per admit step, whatever the weights select.  All
    columns are finite (the exactness contract)."""
    hosts = sim.hosts
    H = hosts.cap.shape[0]
    ct = sim.containers

    # recency: mod-distance past the rotating pointer.  With rr pinned at
    # -1 (untracked) this is exactly the host index — FirstFit's score.
    recency = jnp.mod(jnp.arange(H) - carry.rr - 1, H).astype(jnp.float32)
    neg_speed = -hosts.speed[:, ct.ctype[cand[k]]]
    free = (hosts.cap - used) / jnp.maximum(hosts.cap, 1e-6)     # [H, 3]
    worst = -free.sum(axis=1)

    cnt = carry.counts[k]                                        # [H]
    total = cnt.sum()
    has = total > 0
    coloc = jnp.where(has, -cnt, 0.0)
    comm = jnp.where(has, (cnt @ sim.net.comm_cost)
                     / jnp.maximum(total, 1.0), 0.0)
    fallback = jnp.where(has, 0.0, worst)

    host_util = (used / jnp.maximum(hosts.cap, 1e-6)).max(axis=1)
    # host i's access link is link i (network.build_network numbering)
    uplink = sim.net.link_util[:H]
    cross_leaf = jnp.where(has, (total - carry.leafpeers[k])
                           / jnp.maximum(total, 1.0), 0.0)
    return (recency, neg_speed, worst, coloc, comm, fallback,
            host_util, free[:, 0], free[:, 1], uplink, cross_leaf)


def placement_features(sim: SimState, cfg: SimConfig, params: RunParams,
                       carry: PlaceCarry, k, cand,
                       used: jnp.ndarray) -> jnp.ndarray:
    """The [H, NUM_ROW_FEATURES] bank view of the feature columns —
    the introspection/debugging surface (the hot path sums the columns
    directly, see :func:`host_row`)."""
    return jnp.stack(_row_feature_columns(sim, cfg, params, carry, k, cand,
                                          used), axis=1)


def host_row_cols(sim: SimState, cfg: SimConfig, params: RunParams,
                  pol: PolicyParams, carry: PlaceCarry, k, cand,
                  used) -> tuple:
    """:func:`host_row` plus the raw feature columns it was summed from —
    the soft-placement path needs both (the score for the softmax, the
    columns for the expected-cost surrogate) without paying the bank
    twice."""
    cols = _row_feature_columns(sim, cfg, params, carry, k, cand, used)
    w = pol.weights
    score = cols[0] * w[W_ROW0]
    for i in range(1, NUM_ROW_FEATURES):
        score = score + cols[i] * w[W_ROW0 + i]
    return score, cols


def host_row(sim: SimState, cfg: SimConfig, params: RunParams,
             pol: PolicyParams, carry: PlaceCarry, k, cand,
             used) -> jnp.ndarray:
    """The one scoring rule both engine paths evaluate: candidate ``k``'s
    f32[H] preference row = weighted sum of the feature columns (lower =
    better; argmin breaks ties toward the lowest host index).  Summed as
    an elementwise chain rather than a [H, F] matmul — no bank
    materialization inside the admit scan, and exactness is unaffected:
    legacy vectors have one-hot / disjoint-support weights, so every term
    but the live one is an exact 0.0 in any order.  Feasibility is NOT
    baked in — the engine masks infeasible hosts against its live
    resource counters so intra-round decisions see each other."""
    return host_row_cols(sim, cfg, params, pol, carry, k, cand, used)[0]


def update_place_carry(sim: SimState, pol: PolicyParams, carry: PlaceCarry,
                       k, cand, hh, ok) -> PlaceCarry:
    """Admit bookkeeping after candidate ``k`` lands on ``hh``: the pointer
    follows the admit when ``W_RR_TRACK`` is set, and every later same-job
    candidate's co-location column is raised (a masked column add — one
    float add, scatter-free) so intra-round decisions see each other and
    batched == sequential placements exactly."""
    track = pol.weights[W_RR_TRACK] > 0
    rr = jnp.where(ok & track, hh, carry.rr)
    same = sim.containers.job[cand] == sim.containers.job[cand[k]]
    hot = (jnp.arange(carry.counts.shape[1]) == hh) & ok
    counts = jnp.where(hot[None, :] & same[:, None],
                       carry.counts + 1.0, carry.counts)
    # the admitted peer lands on leaf[hh]: same-job candidates gain one
    # same-leaf peer at every host on that leaf (elementwise, exact)
    leaf = sim.hosts.leaf
    on_leaf = (leaf == leaf[hh]) & ok
    leafpeers = jnp.where(on_leaf[None, :] & same[:, None],
                          carry.leafpeers + 1.0, carry.leafpeers)
    return PlaceCarry(rr=rr, counts=counts, leafpeers=leafpeers)


def commit_place_carry(sched, carry: PlaceCarry):
    """Persist the round's carry across ticks.  Only the rotating pointer
    outlives the round; policies without ``W_RR_TRACK`` never move it, so
    the write is an identity for them."""
    return sched._replace(rr_pointer=carry.rr)


# ---------------------------------------------------------------------------
# Migration (paper §3.5 algorithm 1, DRAPS-derived) — weighted like
# placement: shared overload-source rule, scored destination, mask-weight
# trigger.
# ---------------------------------------------------------------------------
def _overload_source(sim: SimState, cfg: SimConfig, params: RunParams):
    """Shared source/container selection for every migrating policy.

    Returns (src, cont, src_c, dst_mask):
    * src: host with max over-threshold utilization on any resource (-1 none);
    * cont: RUNNING container on it consuming the most of the host's
      bottleneck resource;
    * dst_mask: feasible hosts with all utilizations < idle threshold.
    """
    util = sim.hosts.used / jnp.maximum(sim.hosts.cap, 1e-6)   # [H, 3]
    worst = util.max(axis=1)
    overloaded = worst > params.overload_threshold
    H = worst.shape[0]
    src = _first_true(-worst, overloaded)
    src_c = jnp.clip(src, 0, H - 1)
    bottleneck = jnp.argmax(util[src_c])                       # resource index
    st = sim.containers.status
    movable = (st == STATUS_RUNNING) & (sim.containers.host == src_c)
    usage = sim.containers.req[:, bottleneck]
    cont = _first_true(-usage, movable)
    C = movable.shape[0]
    cont_c = jnp.clip(cont, 0, C - 1)

    req = sim.containers.req[cont_c]
    feas = feasible_hosts(sim.hosts.cap, sim.hosts.used,
                          sim.hosts.n_containers, req, cfg)
    idle = (util < params.idle_threshold).all(axis=1)
    dst_mask = feas & idle & (jnp.arange(H) != src_c)
    return src, cont, src_c, dst_mask


def migration_features(sim: SimState, src_c: jnp.ndarray) -> jnp.ndarray:
    """[H, NUM_MIG_FEATURES] destination bank (``M_*`` enum, all finite):
    host index, bottleneck ECMP-path utilization from the source
    (``network.path_util_row``, O(H·4)), cross-leaf indicator, worst fit."""
    H = sim.hosts.cap.shape[0]
    idx = jnp.arange(H, dtype=jnp.float32)
    putil = network.path_util_row(sim.net, src_c)              # f32[H]
    cross = (sim.hosts.leaf != sim.hosts.leaf[src_c]).astype(jnp.float32)
    return jnp.stack([idx, putil, cross,
                      _worst_fit_row(sim, sim.hosts.used)], axis=1)


def _migration_pair(src, cont, dst):
    ok = (src >= 0) & (cont >= 0) & (dst >= 0)
    return jnp.where(ok, cont, -1), jnp.where(ok, dst, -1)


def _migrate_core(sim: SimState, cfg: SimConfig, params: RunParams,
                  pol: PolicyParams):
    """The shared decision: hard (container | -1, dst | -1) outputs plus the
    destination score row / feature bank / mask the soft surrogate reads."""
    w = pol.weights
    src, cont, src_c, dst_mask = _overload_source(sim, cfg, params)
    feats = migration_features(sim, src_c)
    score = feats @ w[W_MIG0:W_MIG0 + NUM_MIG_FEATURES]
    dst = _first_true(score, dst_mask)
    cont_out, dst_out = _migration_pair(src, cont, dst)
    enabled = w[W_MIG_ENABLE] > 0
    minus1 = jnp.full((), -1, jnp.int32)
    return (jnp.where(enabled, cont_out, minus1),
            jnp.where(enabled, dst_out, minus1), feats, score, dst_mask)


def migrate(sim: SimState, cfg: SimConfig, params: RunParams,
            pol: PolicyParams):
    """(container | -1, dst | -1) for this decision step.

    ``W_MIG_ENABLE`` is the trigger mask weight: 0 returns the uniform
    (-1, -1) no-op the engine's where-masks turn into an identity — the
    exact behavior of the old no-op branch, without a branch.
    """
    cont_out, dst_out, _, _, _ = _migrate_core(sim, cfg, params, pol)
    return cont_out, dst_out


def migrate_soft(sim: SimState, cfg: SimConfig, params: RunParams,
                 pol: PolicyParams):
    """:func:`migrate` plus the softmax surrogate terms.

    Returns ``(cont, dst, soft_val, soft_cnt)`` where the hard pair is
    bit-identical to :func:`migrate` and ``soft_val`` is the expected
    bottleneck-path utilization of the destination under
    ``q = soft_assign(score, dst_mask, tau)`` — differentiable in the
    migration weights (the score is ``features @ w[W_MIG0:]``).  Both soft
    terms are exact 0.0 when no migration actually fires this step, so
    disabled policies contribute nothing to the surrogate sums.
    """
    cont_out, dst_out, feats, score, dst_mask = _migrate_core(
        sim, cfg, params, pol)
    q = soft_assign(score, dst_mask, params.tau)
    fired = (dst_out >= 0).astype(jnp.float32)
    soft_val = fired * (q * feats[:, M_PATH_UTIL]).sum()
    return cont_out, dst_out, soft_val, fired


def overload_migrate(sim: SimState, cfg: SimConfig,
                     params: RunParams | None = None):
    """Relieve the most overloaded host; first-fit destination.
    (= the generic :func:`migrate` under ``overload_migrate``'s weights.)"""
    params = cfg.run_params() if params is None else params
    return migrate(sim, cfg, params, get_policy("overload_migrate"))


def congestion_migrate(sim: SimState, cfg: SimConfig,
                       params: RunParams | None = None):
    """Congestion-aware variant: same source/container selection, but the
    destination minimizes the bottleneck link utilization of the ECMP path
    the migration flow will traverse (index tie-break).
    (= the generic :func:`migrate` under ``netaware``'s weights.)"""
    params = cfg.run_params() if params is None else params
    return migrate(sim, cfg, params, get_policy("netaware"))


# ---------------------------------------------------------------------------
# Registry (paper: "easy extensibility of container scheduling algorithms")
# — a name -> canonical weight vector table.  Nothing here is baked into
# compiled programs: registration after a compiled run is fine, the new
# policy rides the existing executable as data.
# ---------------------------------------------------------------------------
_REGISTRY: dict[str, np.ndarray] = {}


def weight_index(name: str) -> int:
    """Index of a named weight slot, failing loudly on unknown names — the
    ONE lookup every by-name surface (:func:`weight_vector`,
    :func:`get_policy` dict overrides, ``tune.sample_weights``) routes
    through."""
    try:
        return WEIGHT_NAMES.index(name)
    except ValueError:
        raise KeyError(f"unknown weight {name!r}; known: "
                       f"{list(WEIGHT_NAMES)}") from None


def weight_vector(**overrides) -> np.ndarray:
    """Build a canonical-length weight vector by name.

    Starts from the neutral defaults every built-in shares — FIFO selection
    (``sel_submit=1``) and the comm-cost model weights
    (``util``/``cross_leaf``, consumed by the ``NetState.comm_cost``
    refresh) — with every scoring weight at zero; keyword overrides use the
    ``types.WEIGHT_NAMES`` names.
    """
    w = np.zeros(NUM_POLICY_WEIGHTS, np.float32)
    w[weight_index("util")] = network.DEFAULT_UTIL_WEIGHT
    w[weight_index("cross_leaf")] = network.DEFAULT_CROSS_LEAF_MS
    w[weight_index("sel_submit")] = 1.0
    for name, val in overrides.items():
        w[weight_index(name)] = val
    return w


def validate_weights(w, context: str = "") -> None:
    """Loud canonical-length check.  A short vector would silently clamp
    jit-mode gathers (``weights[W_MIG_ENABLE]`` -> index 0) and a ragged
    batch would break stacking — reject both up front."""
    shape = jnp.shape(w)
    if len(shape) == 0 or shape[-1] != NUM_POLICY_WEIGHTS:
        raise ValueError(
            f"{context}weights must have the canonical length "
            f"{NUM_POLICY_WEIGHTS} (types.WEIGHT_NAMES), got shape {shape}")


def register(name: str, weights) -> np.ndarray:
    """Add (or replace, by name) a policy: a weight vector, or a dict of
    by-name overrides passed to :func:`weight_vector`.  Pure data — no
    compiled program is invalidated by a registration."""
    if isinstance(weights, dict):
        weights = weight_vector(**weights)
    # np.array (not asarray): the registry must own its vector — storing
    # the caller's array by reference would let later in-place mutation
    # silently rewrite a registered policy
    w = np.array(weights, np.float32)
    validate_weights(w, f"policy {name!r}: ")
    _REGISTRY[name] = w
    return w


def get_policy(name: str, weights=None) -> PolicyParams:
    """The data handle for a registered policy.

    ``weights`` overrides the registered vector — a full canonical-length
    vector, or a dict of by-name deltas (e.g. ``{"cross_leaf": 0.5}`` for
    a heavier spine penalty).  Variants are new *data*, not new code, so
    they share every compiled program.
    """
    try:
        base = _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown policy {name!r}; known: {sorted(_REGISTRY)}") from None
    if weights is None:
        w = base
    elif isinstance(weights, dict):
        w = base.copy()
        for k, v in weights.items():
            w[weight_index(k)] = v
    else:
        w = np.asarray(weights, np.float32)
        validate_weights(w, f"policy {name!r}: ")
    return PolicyParams(weights=jnp.asarray(w, jnp.float32))


def list_policies() -> list[str]:
    return sorted(_REGISTRY)


# ---------------------------------------------------------------------------
# The six built-ins (paper §3.5 + the PR 2 network-aware pair) as weight
# vectors.  Each is one-hot (or disjoint-support) over features computed
# exactly as the former branches computed them, so every vector reproduces
# its PR 4 switch-dispatched run bit-for-bit
# (tests/test_policy_equivalence.py).
# ---------------------------------------------------------------------------
# FirstFit [36]: lowest-numbered feasible host (recency with rr pinned -1).
register("firstfit", dict(row_recency=1.0))
# Round (paper §3.5): first feasible host after the last used one.
register("round", dict(row_recency=1.0, rr_track=1.0))
# PerformanceFirst (DRAPS-derived): fastest host for the primary resource.
register("performance_first", dict(row_neg_speed=1.0))
# JobGroup (CA-WFD-derived): most same-job containers; worst fit while the
# job has none deployed.
register("jobgroup", dict(row_coloc=1.0, row_fallback_worst=1.0))
# NetAware: mean expected comm cost to deployed same-job peers under the
# current fabric state (NetState.comm_cost), worst-fit fallback;
# congestion-aware migration destination.
register("netaware", dict(row_comm=1.0, row_fallback_worst=1.0,
                          mig_enable=1.0, mig_path_util=1.0))
# FirstFit placement + DRAPS overload migration, first-fit destination.
register("overload_migrate", dict(row_recency=1.0, mig_enable=1.0,
                                  mig_idx=1.0))
