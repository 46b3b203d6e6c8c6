"""Network simulation module (paper §3.4), tensor-native.

Mininet's emulated fabric is replaced by an analytic flow-level model that
reproduces the quantities the paper *measures*:

* ``ping``-refreshed delay matrix  -> min-plus Floyd-Warshall over the
  congestion-adjusted link-delay graph (Pallas kernel on TPU; jnp ref here).
* ``iperf`` transfers under (bw, delay, loss) -> per-flow rate =
  min(max-min-fair share via progressive filling, Mathis TCP bound
  MSS / (RTT * sqrt(p))).
* bounded retransmissions -> flows stalled below a rate floor accrue retries
  and fail after ``max_retries`` ticks (paper: failed traffic is handed back
  to the scheduling module).
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.types import NetState

INF = np.float32(1e9)
MBPS_TO_KBPS = 125.0  # 1 Mbps = 125 KB/s
LOCAL_RATE_KBPS = 4.0e6  # same-host "loopback" transfer rate
# comm-cost weights: single source of truth — every policy's weight vector
# defaults to these (scheduling.weight_vector seeds its util/cross_leaf
# slots from them), and build_network/set_link_params (which have no policy
# in scope) use them for the initial table; the engine re-weights from the
# policy's weight vector at every delay refresh.
DEFAULT_UTIL_WEIGHT = 1.0     # ms-equivalent at 100% path utilization
DEFAULT_CROSS_LEAF_MS = 0.05  # penalty for transiting the spine


# ---------------------------------------------------------------------------
# Topology construction (spine-leaf, paper Fig 3)
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class SpineLeafSpec:
    n_spine: int = 2
    n_leaf: int = 4
    n_hosts: int = 20
    host_leaf_bw: float = 1000.0   # Mbps
    leaf_spine_bw: float = 1000.0  # Mbps
    link_delay_ms: float = 0.05    # per-link base delay
    loss: float = 0.0              # per-link packet loss fraction

    @property
    def n_nodes(self) -> int:
        return self.n_hosts + self.n_leaf + self.n_spine

    @property
    def n_links(self) -> int:
        return self.n_hosts + self.n_leaf * self.n_spine


def build_network(spec: SpineLeafSpec) -> NetState:
    """Build link tables + deterministic ECMP paths for a spine-leaf fabric.

    Node numbering: hosts [0, H), leaves [H, H+L), spines [H+L, H+L+S).
    Link numbering: host-leaf links [0, H) (link i connects host i to its
    leaf), then leaf-spine links H + l * S + s.
    """
    H, L, S = spec.n_hosts, spec.n_leaf, spec.n_spine
    E = spec.n_links

    host_leaf = np.arange(H) % L                      # host -> leaf id
    link_u = np.zeros(E, np.int32)
    link_v = np.zeros(E, np.int32)
    link_bw = np.zeros(E, np.float32)
    # host-leaf links
    link_u[:H] = np.arange(H)
    link_v[:H] = H + host_leaf
    link_bw[:H] = spec.host_leaf_bw
    # leaf-spine links
    for leaf in range(L):
        for s in range(S):
            e = H + leaf * S + s
            link_u[e] = H + leaf
            link_v[e] = H + L + s
            link_bw[e] = spec.leaf_spine_bw

    # Deterministic ECMP: pair (i, j) hashes onto spine (i + j) % S.
    # Vectorized over the H^2 pairs so multi-thousand-host fabrics build in
    # milliseconds (the Python double loop was itself a scalability ceiling).
    I, J = np.meshgrid(np.arange(H), np.arange(H), indexing="ij")
    li, lj = host_leaf[I], host_leaf[J]
    same = (li == lj) & (I != J)
    cross = li != lj
    spine = (I + J) % S
    path_links = np.full((H, H, 4), -1, np.int32)
    path_links[same, 0] = I[same]
    path_links[same, 1] = J[same]
    path_links[cross, 0] = I[cross]
    path_links[cross, 1] = (H + li * S + spine)[cross]
    path_links[cross, 2] = (H + lj * S + spine)[cross]
    path_links[cross, 3] = J[cross]
    path_nlinks = np.where(same, 2, np.where(cross, 4, 0)).astype(np.int32)

    base_delay = np.full(E, spec.link_delay_ms, np.float32)
    loss = np.full(E, spec.loss, np.float32)
    delay0 = path_delay_matrix(
        jnp.asarray(base_delay), jnp.asarray(path_links))
    pl = jnp.asarray(path_links)
    net = NetState(
        link_bw=jnp.asarray(link_bw),
        link_delay=jnp.asarray(base_delay),
        link_loss=jnp.asarray(loss),
        link_u=jnp.asarray(link_u),
        link_v=jnp.asarray(link_v),
        path_links=pl,
        path_nlinks=jnp.asarray(path_nlinks),
        link_bw_kbps=jnp.asarray(link_bw) * MBPS_TO_KBPS,
        path_loss=path_loss_matrix(jnp.asarray(loss), pl),
        link_util=jnp.zeros((E,), jnp.float32),
        delay_matrix=delay0,
        comm_cost=jnp.zeros((H, H), jnp.float32),
    )
    return net._replace(comm_cost=pairwise_comm_cost(net))


def apply_link_params(net: NetState, bw_mbps: jnp.ndarray,
                      loss: jnp.ndarray) -> NetState:
    """Trace-friendly uniform bandwidth/loss override (RunParams semantics).

    ``bw_mbps <= 0`` / ``loss < 0`` keep the topology's per-link values, so
    the no-override default is expressible as data and a (bw, loss) ladder
    is a batch axis on two scalars — the engine applies this at t=0, which
    is how ``launch/sweep.py`` runs a whole Fig 5/8-style sweep in one
    compiled program.  The derived tables (``link_bw_kbps``, ``path_loss``,
    ``comm_cost``) are rebuilt in the same pass.
    """
    bw_mbps = jnp.asarray(bw_mbps, jnp.float32)
    loss = jnp.asarray(loss, jnp.float32)
    new_bw = jnp.where(bw_mbps > 0, bw_mbps, net.link_bw)
    new_loss = jnp.where(loss >= 0, loss, net.link_loss)
    net = net._replace(
        link_bw=new_bw,
        link_bw_kbps=new_bw * MBPS_TO_KBPS,
        link_loss=new_loss,
        path_loss=path_loss_matrix(new_loss, net.path_links))
    return net._replace(comm_cost=pairwise_comm_cost(net))


def set_link_params(net: NetState, bw: float | None = None,
                    loss: float | None = None) -> NetState:
    """Override bandwidth / loss on every link (paper Fig 5/8 sweeps).

    Host-side convenience over :func:`apply_link_params`; ``None`` maps to
    the keep-the-topology sentinel.  Values inside the sentinel domain
    (``bw <= 0``, ``loss < 0``) are rejected loudly — they would otherwise
    silently keep the topology instead of overriding it.
    """
    if bw is not None and bw <= 0:
        raise ValueError(f"bw override must be > 0 Mbps, got {bw}")
    if loss is not None and loss < 0:
        raise ValueError(f"loss override must be >= 0, got {loss}")
    return apply_link_params(net,
                             -1.0 if bw is None else bw,
                             -1.0 if loss is None else loss)


# ---------------------------------------------------------------------------
# Delay model
# ---------------------------------------------------------------------------
def congested_link_delay(net: NetState, q_coef: float = 0.5,
                         max_q: float = 20.0) -> jnp.ndarray:
    """Per-link delay = base + M/M/1-style queueing term from utilization."""
    u = jnp.clip(net.link_util, 0.0, 0.97)
    return net.link_delay + jnp.minimum(q_coef * u / (1.0 - u), max_q)


def path_delay_matrix(link_delay: jnp.ndarray,
                      path_links: jnp.ndarray) -> jnp.ndarray:
    """Host-to-host delay along the fixed ECMP path (fast path, 'path' mode)."""
    padded = jnp.concatenate([link_delay, jnp.zeros((1,), link_delay.dtype)])
    d = padded[path_links].sum(axis=-1)          # [-1] pad indexes the 0
    return d


def path_loss_matrix(link_loss: jnp.ndarray,
                     path_links: jnp.ndarray) -> jnp.ndarray:
    """Host-to-host end-to-end loss 1 - prod(1 - loss_e) along the ECMP path.

    Static per topology, so it is precomputed onto ``NetState.path_loss`` and
    the per-tick Mathis bound becomes a single [F] gather.
    """
    keep = jnp.concatenate([jnp.log1p(-jnp.clip(link_loss, 0.0, 0.99)),
                            jnp.zeros((1,), link_loss.dtype)])
    return 1.0 - jnp.exp(keep[path_links].sum(axis=-1))  # [-1] pad hits the 0


def path_util_matrix(net: NetState) -> jnp.ndarray:
    """Max link utilization along the ECMP path between every host pair.

    The bottleneck view of current congestion: a flow between (i, j) is
    limited by the hottest link on its fixed path.  Pad slots (-1) index the
    appended zero, so same-host pairs report 0 utilization.
    """
    padded = jnp.concatenate([net.link_util,
                              jnp.zeros((1,), net.link_util.dtype)])
    return padded[net.path_links].max(axis=-1)


def path_util_row(net: NetState, src: jnp.ndarray) -> jnp.ndarray:
    """One source row of :func:`path_util_matrix` — f32[H].

    The congestion-aware migration picker needs the bottleneck utilization
    from ONE source host to every destination; gathering ``path_links[src]``
    first keeps that O(H·4) instead of materializing the O(H²·4) matrix
    inside the per-tick migration scan.
    """
    padded = jnp.concatenate([net.link_util,
                              jnp.zeros((1,), net.link_util.dtype)])
    return padded[net.path_links[src]].max(axis=-1)


def pairwise_comm_cost(net: NetState,
                       util_weight: float = DEFAULT_UTIL_WEIGHT,
                       cross_leaf_ms: float = DEFAULT_CROSS_LEAF_MS
                       ) -> jnp.ndarray:
    """Expected cost [ms-equivalent] of communicating between host pairs.

    ``delay_matrix`` (the paper's ping-refreshed D, already congestion-
    adjusted at refresh time) + ``util_weight`` * bottleneck utilization of
    the ECMP path + a ``cross_leaf_ms`` penalty for pairs whose traffic must
    transit the spine (path_nlinks == 4; same-leaf pairs use 2 links and
    same-host pairs 0).  Refreshed onto ``NetState.comm_cost`` together with
    the delay matrix; the network-aware policies score hosts against it.
    """
    cross_spine = (net.path_nlinks >= 4).astype(jnp.float32)
    return (net.delay_matrix + util_weight * path_util_matrix(net)
            + cross_leaf_ms * cross_spine)


def adjacency_from_links(net: NetState, link_delay: jnp.ndarray,
                         n_nodes: int) -> jnp.ndarray:
    """Symmetric node-graph adjacency with link delays; INF where no edge.

    Built with a ``segment_min`` over flattened (u, v) pair ids instead of
    the former ``.at[u, v].min`` scatters — min is order-independent, so
    the result is bit-identical, and the delay-refresh arm of the tick
    ('fw' mode) stays scatter-free under a vmapped sweep.  Parallel links
    (none on the spine-leaf fabric, but allowed) still take the min.
    """
    seg = jnp.concatenate([net.link_u * n_nodes + net.link_v,
                           net.link_v * n_nodes + net.link_u])
    vals = jnp.concatenate([link_delay, link_delay])
    A = jax.ops.segment_min(vals, seg, num_segments=n_nodes * n_nodes)
    A = jnp.minimum(A, INF).reshape(n_nodes, n_nodes)  # empty segments: +inf
    eye = jnp.arange(n_nodes)[:, None] == jnp.arange(n_nodes)[None, :]
    return jnp.where(eye, 0.0, A)


def floyd_warshall_ref(A: jnp.ndarray) -> jnp.ndarray:
    """Pure-jnp min-plus APSP (oracle for the Pallas kernel)."""
    n = A.shape[0]

    def body(D, k):
        D = jnp.minimum(D, D[:, k, None] + D[None, k, :])
        return D, None

    D, _ = jax.lax.scan(body, A, jnp.arange(n))
    return D


def update_delay_matrix(net: NetState, n_hosts: int, n_nodes: int,
                        mode: str = "path", use_kernel: bool = False,
                        q_coef: float = 0.5,
                        util_weight: float = DEFAULT_UTIL_WEIGHT,
                        cross_leaf_ms: float = DEFAULT_CROSS_LEAF_MS
                        ) -> NetState:
    """Refresh the paper's delay_matrix (and comm_cost) from congestion.

    mode='path'  — sum link delays along the fixed ECMP path (O(H^2)).
    mode='fw'    — full APSP over the node graph (the SDN-controller view);
                   uses the Pallas blocked kernel when ``use_kernel``.
    The pairwise communication-cost table consumed by the network-aware
    policies is rebuilt from the fresh delay matrix in the same pass.
    """
    d_link = congested_link_delay(net, q_coef=q_coef)
    if mode == "path":
        D = path_delay_matrix(d_link, net.path_links)
    else:
        A = adjacency_from_links(net, d_link, n_nodes)
        if use_kernel:
            from repro.kernels.fw_minplus import ops as fw_ops
            D_full = fw_ops.floyd_warshall(A)
        else:
            D_full = floyd_warshall_ref(A)
        D = D_full[:n_hosts, :n_hosts]
    net = net._replace(delay_matrix=D)
    return net._replace(comm_cost=pairwise_comm_cost(
        net, util_weight=util_weight, cross_leaf_ms=cross_leaf_ms))


# ---------------------------------------------------------------------------
# Flow-level rate allocation
#
# Two interchangeable engines (docs/perf.md):
#   sparse (default) — every ECMP path has <= 4 links, so each per-link
#     reduction is a [F, 4] gather + segment_sum scatter-add: O(F*4 + E)
#     per waterfilling round.
#   dense (reference oracle, ``sparse=False``) — materializes the [F, E]
#     membership matrix the seed engine used: O(F*E) per round.  Kept so
#     property tests can assert the sparse path is numerically equivalent.
# ---------------------------------------------------------------------------
def path_membership(path_links: jnp.ndarray, src: jnp.ndarray,
                    dst: jnp.ndarray, n_links: int) -> jnp.ndarray:
    """[F, E] bool: does flow f traverse link e. Same-host flows hit no link."""
    links = path_links[src, dst]                      # [F, 4]
    return (links[:, :, None] == jnp.arange(n_links)[None, None, :]).any(1)


def max_min_fair_rates(member: jnp.ndarray, active: jnp.ndarray,
                       link_bw_kbps: jnp.ndarray,
                       n_rounds: int = 8) -> jnp.ndarray:
    """Progressive-filling max-min fair allocation, fixed rounds, jit-safe.

    Each round saturates (at least) the globally most contended link and
    freezes the flows crossing it at their fair share.  Dense [F, E]
    reference implementation.
    """
    F = member.shape[0]
    member_f = member.astype(jnp.float32) * active[:, None]

    def fair_bound(unfrozen, cap_rem):
        live = member_f * unfrozen[:, None].astype(jnp.float32)
        cnt = live.sum(0)                                      # [E]
        share = jnp.where(cnt > 0, cap_rem / jnp.maximum(cnt, 1.0), INF)
        # per-flow bound = min share along its path (INF for no-link flows)
        return jnp.where(member, share[None, :], INF).min(1)   # [F]

    def round_body(carry, _):
        alloc, frozen, cap_rem = carry
        unfrozen = active & ~frozen
        bound = jnp.where(unfrozen, fair_bound(unfrozen, cap_rem), INF)
        m = bound.min()
        newly = unfrozen & (bound <= m * 1.000001 + 1e-6)
        new_alloc = jnp.where(newly, jnp.minimum(bound, LOCAL_RATE_KBPS), alloc)
        used = (member_f * (newly * new_alloc)[:, None]).sum(0)
        return (new_alloc, frozen | newly, jnp.maximum(cap_rem - used, 0.0)), None

    alloc0 = jnp.where(active, LOCAL_RATE_KBPS, 0.0)  # no-link flows: local bw
    init = (alloc0, active & ~member.any(1), link_bw_kbps)
    (alloc, frozen, cap_rem), _ = jax.lax.scan(round_body, init, None,
                                               length=n_rounds)
    # Flows still unfrozen after n_rounds (more distinct bottleneck levels
    # than rounds) get their current fair-share bound, NOT the LOCAL_RATE
    # alloc0 they were initialized with — the latter oversubscribed links.
    leftover = active & ~frozen
    tail = jnp.minimum(fair_bound(leftover, cap_rem), LOCAL_RATE_KBPS)
    alloc = jnp.where(leftover, tail, alloc)
    return jnp.where(active, alloc, 0.0)


def max_min_fair_rates_sparse(flow_links: jnp.ndarray, active: jnp.ndarray,
                              link_bw_kbps: jnp.ndarray,
                              n_rounds: int = 8) -> jnp.ndarray:
    """Sparse progressive filling over the [F, 4] per-flow link lists.

    Numerically equivalent to :func:`max_min_fair_rates` (same round
    structure, same freeze rule) but every per-link reduction is a
    ``segment_sum`` over at most 4 link ids per flow — no [F, E] tensor.
    """
    F = flow_links.shape[0]
    E = link_bw_kbps.shape[0]
    valid = (flow_links >= 0) & active[:, None]          # [F, 4]
    seg = jnp.where(valid, flow_links, E).reshape(-1)    # pad slots -> seg E
    w_valid = valid.astype(jnp.float32)

    def per_link_sum(per_flow):                          # [F] -> [E]
        w = (per_flow[:, None] * w_valid).reshape(-1)
        return jax.ops.segment_sum(w, seg, num_segments=E + 1)[:E]

    def fair_bound(unfrozen, cap_rem):
        cnt = per_link_sum(unfrozen.astype(jnp.float32))
        share = jnp.where(cnt > 0, cap_rem / jnp.maximum(cnt, 1.0), INF)
        padded = jnp.concatenate([share, jnp.full((1,), INF)])
        return jnp.where(valid, padded[seg.reshape(F, 4)], INF).min(1)

    def round_body(carry, _):
        alloc, frozen, cap_rem = carry
        unfrozen = active & ~frozen
        bound = jnp.where(unfrozen, fair_bound(unfrozen, cap_rem), INF)
        m = bound.min()
        newly = unfrozen & (bound <= m * 1.000001 + 1e-6)
        new_alloc = jnp.where(newly, jnp.minimum(bound, LOCAL_RATE_KBPS), alloc)
        used = per_link_sum(jnp.where(newly, new_alloc, 0.0))
        return (new_alloc, frozen | newly, jnp.maximum(cap_rem - used, 0.0)), None

    alloc0 = jnp.where(active, LOCAL_RATE_KBPS, 0.0)
    init = (alloc0, active & ~valid.any(1), link_bw_kbps)
    (alloc, frozen, cap_rem), _ = jax.lax.scan(round_body, init, None,
                                               length=n_rounds)
    leftover = active & ~frozen
    tail = jnp.minimum(fair_bound(leftover, cap_rem), LOCAL_RATE_KBPS)
    alloc = jnp.where(leftover, tail, alloc)
    return jnp.where(active, alloc, 0.0)


def mathis_cap(delay_matrix: jnp.ndarray, link_loss: jnp.ndarray,
               member: jnp.ndarray, src: jnp.ndarray, dst: jnp.ndarray,
               mss_kb: float = 1.46, c_mathis: float = 1.22) -> jnp.ndarray:
    """TCP throughput ceiling under loss: C * MSS / (RTT * sqrt(p)) [KB/s]."""
    # path loss: 1 - prod(1 - loss_e)
    log_keep = jnp.where(member, jnp.log1p(-jnp.clip(link_loss, 0, 0.99))[None, :], 0.0)
    p = 1.0 - jnp.exp(log_keep.sum(1))
    return _mathis_from_loss(delay_matrix, p, src, dst, mss_kb, c_mathis)


def mathis_cap_sparse(delay_matrix: jnp.ndarray, path_loss: jnp.ndarray,
                      src: jnp.ndarray, dst: jnp.ndarray,
                      mss_kb: float = 1.46,
                      c_mathis: float = 1.22) -> jnp.ndarray:
    """Mathis bound from the precomputed [H, H] path-loss table: one gather."""
    return _mathis_from_loss(delay_matrix, path_loss[src, dst], src, dst,
                             mss_kb, c_mathis)


def _mathis_from_loss(delay_matrix, p, src, dst, mss_kb, c_mathis):
    rtt_ms = 2.0 * delay_matrix[src, dst]
    rtt_s = jnp.maximum(rtt_ms, 1e-2) * 1e-3
    cap = c_mathis * mss_kb / (rtt_s * jnp.sqrt(jnp.maximum(p, 1e-12)))
    return jnp.where(p > 1e-9, cap, INF)


def flow_rates(net: NetState, src: jnp.ndarray, dst: jnp.ndarray,
               active: jnp.ndarray, n_rounds: int = 8, sparse: bool = True,
               use_kernel: bool = False
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Allocate KB/s to each (src_host -> dst_host) flow; also new link util.

    ``sparse`` selects the segment-based engine (default); ``sparse=False``
    runs the dense [F, E] membership oracle.  ``use_kernel`` routes the
    sparse allocation through the fused Pallas ``seg_waterfill`` kernel
    (all waterfilling rounds + Mathis min + link load in one kernel; the
    unfused jnp chain below is its oracle — docs/kernels.md).  Returns
    (rates [F], util [E]).
    """
    E = net.link_bw.shape[0]
    src_c = jnp.clip(src, 0, None)
    dst_c = jnp.clip(dst, 0, None)
    bw_kbps = net.link_bw_kbps

    if sparse and use_kernel:
        from repro.kernels.seg_waterfill import ops as wf_ops
        links = jnp.where(active[:, None], net.path_links[src_c, dst_c], -1)
        tcp = mathis_cap_sparse(net.delay_matrix, net.path_loss, src_c, dst_c)
        rates, load = wf_ops.seg_waterfill(
            links, active, bw_kbps, tcp, n_rounds=n_rounds,
            local_rate=float(LOCAL_RATE_KBPS), inf=float(INF))
    elif sparse:
        links = jnp.where(active[:, None], net.path_links[src_c, dst_c], -1)
        fair = max_min_fair_rates_sparse(links, active, bw_kbps, n_rounds)
        tcp = mathis_cap_sparse(net.delay_matrix, net.path_loss, src_c, dst_c)
        rates = jnp.minimum(fair, tcp) * active
        valid = links >= 0                                    # [F, 4]
        seg = jnp.where(valid, links, E).reshape(-1)
        w = (rates[:, None] * valid.astype(jnp.float32)).reshape(-1)
        load = jax.ops.segment_sum(w, seg, num_segments=E + 1)[:E]
    else:
        member = path_membership(net.path_links, src_c, dst_c, E)
        member = member & active[:, None]
        fair = max_min_fair_rates(member, active, bw_kbps, n_rounds)
        tcp = mathis_cap(net.delay_matrix, net.link_loss, member, src_c, dst_c)
        rates = jnp.minimum(fair, tcp) * active
        load = (member.astype(jnp.float32) * rates[:, None]).sum(0)
    util = jnp.where(bw_kbps > 0, load / jnp.maximum(bw_kbps, 1e-6), 0.0)
    return rates, jnp.clip(util, 0.0, 1.0)
