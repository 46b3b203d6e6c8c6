"""Data collection module (paper §3.7): per-tick metric extraction.

The paper's ``Stat`` class samples host/container/network state once per
second (``save_stats`` process).  Two collection modes share one
``collect`` pass:

* stacked — each tick's metrics are the ``ys`` of the engine's
  ``lax.scan``, so the full time series materializes (O(horizon) memory;
  the default for short horizons and the oracle the streaming mode is
  tested against);
* streaming — the tick folds its metrics into a ``SummaryAcc`` carried
  through the scan (``acc_update``), and the host folds finished chunks
  into an f64/i64 ``OnlineSummary`` (``online_fold``), so memory is
  O(state) at any horizon.  ``online_from_metrics`` computes the SAME
  summary from a stacked series — integer sums/counts/peaks agree
  bit-for-bit, float sums to ~1 ulp (Kahan-compensated f32 on device,
  folded in f64 host-side).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.types import (
    STATUS_COMMUNICATING, STATUS_COMPLETED, STATUS_INACTIVE, STATUS_MIGRATING,
    STATUS_RUNNING, STATUS_WAITING, OnlineSummary, RunParams, SimState,
    SummaryAcc, TickMetrics,
)

I32 = jnp.int32
F32 = jnp.float32


def collect(sim: SimState, new_arrivals: jnp.ndarray, decisions: jnp.ndarray,
            migrations: jnp.ndarray, params: RunParams,
            flow_active: jnp.ndarray, flow_rates: jnp.ndarray,
            soft=None) -> TickMetrics:
    """Per-tick metrics; ``params`` carries the (traced, sweepable)
    overload threshold the ``n_overloaded`` count is judged against.

    ``soft`` is the scheduling round's surrogate 5-tuple ``(soft_comm,
    soft_util, soft_n, soft_mig, soft_mig_n)`` from
    ``engine.phase_schedule_soft`` — exact 0.0 scalars when soft placement
    is off (or when the caller omits it).

    Pure gathers and reductions — no scatters, so the whole collection
    phase batches cleanly when the sweep vmaps the tick.  All lifecycle
    counts come from ONE [C, 6] comparison pass instead of six [C] sweeps.
    """
    if soft is None:
        soft = (jnp.zeros((), F32),) * 5
    soft_comm, soft_util, soft_n, soft_mig, soft_mig_n = soft
    st = sim.containers.status
    util = sim.hosts.used / jnp.maximum(sim.hosts.cap, 1e-6)      # [H, 3]
    worst = util.max(axis=1)
    mean_util = util.mean(axis=1)                                 # per-host
    n_active_flows = flow_active.sum()
    mean_rate = jnp.where(
        n_active_flows > 0,
        (flow_rates * flow_active).sum() / jnp.maximum(n_active_flows, 1),
        0.0)
    codes = (STATUS_INACTIVE, STATUS_RUNNING, STATUS_COMMUNICATING,
             STATUS_MIGRATING, STATUS_WAITING, STATUS_COMPLETED)
    counts = (st[:, None] == jnp.array(codes)[None, :]).sum(axis=0)
    count = dict(zip(codes, counts)).__getitem__
    return TickMetrics(
        t=sim.t,
        n_overloaded=(worst > params.overload_threshold).sum(),
        n_inactive=count(STATUS_INACTIVE) + count(STATUS_WAITING),
        n_running=count(STATUS_RUNNING),
        n_deployed=(count(STATUS_RUNNING) + count(STATUS_COMMUNICATING)
                    + count(STATUS_MIGRATING)),
        n_communicating=count(STATUS_COMMUNICATING),
        n_waiting=count(STATUS_WAITING),
        n_completed=count(STATUS_COMPLETED),
        n_migrating=count(STATUS_MIGRATING),
        new_arrivals=new_arrivals,
        decisions=decisions,
        migrations=migrations,
        util_variance=jnp.var(mean_util),
        mean_util=mean_util.mean(),
        active_flows=n_active_flows,
        mean_flow_rate=mean_rate,
        soft_comm=soft_comm, soft_util=soft_util, soft_n=soft_n,
        soft_mig=soft_mig, soft_mig_n=soft_mig_n,
    )


# ---------------------------------------------------------------------------
# Streaming accumulation: SummaryAcc (device, per chunk) -> OnlineSummary
# (host, f64/i64, whole run)
# ---------------------------------------------------------------------------
def max_chunk_ticks(n_containers: int) -> int:
    """Largest chunk size whose i32 accumulator sums cannot overflow.

    The fastest-growing integer series is ``active_flows`` (at most one
    communication + one migration flow per container = 2C per tick); every
    other counted series is bounded by C per tick.  The bound is loose by
    design — hitting it means the caller asked for ~10^7-tick chunks.
    """
    return (2**31 - 1) // max(2 * n_containers, 1)


def check_chunk(chunk: int, n_containers: int) -> None:
    limit = max_chunk_ticks(n_containers)
    if chunk < 1:
        raise ValueError(f"chunk must be >= 1, got {chunk}")
    if chunk > limit:
        raise ValueError(
            f"chunk={chunk} can overflow i32 accumulator sums at "
            f"C={n_containers} containers (2C flows/tick); use "
            f"chunk <= {limit} — the host-side fold promotes to i64 "
            f"between chunks, so total horizon is unbounded")


def acc_init() -> SummaryAcc:
    """Zero accumulator (peaks start at 0: every counted series is >= 0).

    Every leaf is a buffer of its own: the streaming driver donates the
    accumulator, and one buffer cannot be donated twice."""
    def z_i():
        return jnp.zeros((), I32)

    def z_f():
        return jnp.zeros((), F32)

    return SummaryAcc(
        n_ticks=z_i(),
        sum_util_var=z_f(), c_util_var=z_f(),
        sum_mean_util=z_f(), c_mean_util=z_f(),
        sum_flow_rate=z_f(), c_flow_rate=z_f(),
        w_mean_util=z_f(), w_m2_util=z_f(),
        sum_active_flows=z_i(), sum_arrivals=z_i(), sum_decisions=z_i(),
        sum_migrations=z_i(), peak_running=z_i(), peak_deployed=z_i(),
        peak_overloaded=z_i(), peak_inactive=z_i(),
        sum_soft_comm=z_f(), c_soft_comm=z_f(),
        sum_soft_util=z_f(), c_soft_util=z_f(),
        sum_soft_n=z_f(), c_soft_n=z_f(),
        sum_soft_mig=z_f(), c_soft_mig=z_f(),
        sum_soft_mig_n=z_f(), c_soft_mig_n=z_f(),
    )


def _kahan(s, c, x):
    """One compensated-summation step: returns (s', c')."""
    y = x - c
    t = s + y
    return t, (t - s) - y


def acc_update(acc: SummaryAcc, m: TickMetrics) -> SummaryAcc:
    """Fold one tick's metrics into the accumulator (pure, scan-carry safe).

    f32 sums are Kahan-compensated; ``mean_util`` additionally feeds a
    Welford (mean, M2) pair so the run's utilization variance over TIME is
    available without the stacked series.  Integer sums stay i32 — exact
    as long as the host loop respects ``max_chunk_ticks``.
    """
    su, cu = _kahan(acc.sum_util_var, acc.c_util_var, m.util_variance)
    sm, cm = _kahan(acc.sum_mean_util, acc.c_mean_util, m.mean_util)
    sf, cf = _kahan(acc.sum_flow_rate, acc.c_flow_rate, m.mean_flow_rate)
    ssc, csc = _kahan(acc.sum_soft_comm, acc.c_soft_comm, m.soft_comm)
    ssu, csu = _kahan(acc.sum_soft_util, acc.c_soft_util, m.soft_util)
    ssn, csn = _kahan(acc.sum_soft_n, acc.c_soft_n, m.soft_n)
    ssm, csm = _kahan(acc.sum_soft_mig, acc.c_soft_mig, m.soft_mig)
    ssmn, csmn = _kahan(acc.sum_soft_mig_n, acc.c_soft_mig_n, m.soft_mig_n)
    n = acc.n_ticks + 1
    delta = m.mean_util - acc.w_mean_util
    w_mean = acc.w_mean_util + delta / n.astype(F32)
    w_m2 = acc.w_m2_util + delta * (m.mean_util - w_mean)
    return SummaryAcc(
        n_ticks=n,
        sum_util_var=su, c_util_var=cu,
        sum_mean_util=sm, c_mean_util=cm,
        sum_flow_rate=sf, c_flow_rate=cf,
        w_mean_util=w_mean, w_m2_util=w_m2,
        sum_active_flows=acc.sum_active_flows + m.active_flows.astype(I32),
        sum_arrivals=acc.sum_arrivals + m.new_arrivals.astype(I32),
        sum_decisions=acc.sum_decisions + m.decisions.astype(I32),
        sum_migrations=acc.sum_migrations + m.migrations.astype(I32),
        peak_running=jnp.maximum(acc.peak_running, m.n_running),
        peak_deployed=jnp.maximum(acc.peak_deployed, m.n_deployed),
        peak_overloaded=jnp.maximum(acc.peak_overloaded, m.n_overloaded),
        peak_inactive=jnp.maximum(acc.peak_inactive, m.n_inactive),
        sum_soft_comm=ssc, c_soft_comm=csc,
        sum_soft_util=ssu, c_soft_util=csu,
        sum_soft_n=ssn, c_soft_n=csn,
        sum_soft_mig=ssm, c_soft_mig=csm,
        sum_soft_mig_n=ssmn, c_soft_mig_n=csmn,
    )


def acc_update_weighted(acc: SummaryAcc, m: TickMetrics,
                        dt: jnp.ndarray) -> SummaryAcc:
    """Fold ``dt`` identical ticks' metrics into the accumulator at once.

    The telescoping engine's closed-form fold (docs/events.md): over a
    quiescent interval the per-tick metrics are constant by construction,
    so ``dt`` repeated :func:`acc_update` calls collapse to one weighted
    update — Kahan steps absorb ``dt * x`` in one compensation, the
    Welford pair takes Chan's merge of a group of ``dt`` equal values
    (within-group M2 is exactly 0), integer sums add ``dt * v`` (exact in
    i32 under the same ``max_chunk_ticks`` bound: the weighted total
    equals the repeated total), and peaks are idempotent under repeats.
    Integer sums/counts/peaks match the repeated folds bit-for-bit; the
    float sums and moments agree to ~1 ulp (tests/test_telescope.py).

    ``dt == 0`` is an exact no-op — every field keeps its old value
    bitwise (a Kahan step with x = 0 would still fold the compensation
    term into the sum), so the engine can call this unconditionally after
    an interval that telescoped zero ticks.
    """
    w = dt.astype(F32)
    su, cu = _kahan(acc.sum_util_var, acc.c_util_var, w * m.util_variance)
    sm, cm = _kahan(acc.sum_mean_util, acc.c_mean_util, w * m.mean_util)
    sf, cf = _kahan(acc.sum_flow_rate, acc.c_flow_rate, w * m.mean_flow_rate)
    ssc, csc = _kahan(acc.sum_soft_comm, acc.c_soft_comm, w * m.soft_comm)
    ssu, csu = _kahan(acc.sum_soft_util, acc.c_soft_util, w * m.soft_util)
    ssn, csn = _kahan(acc.sum_soft_n, acc.c_soft_n, w * m.soft_n)
    ssm, csm = _kahan(acc.sum_soft_mig, acc.c_soft_mig, w * m.soft_mig)
    ssmn, csmn = _kahan(acc.sum_soft_mig_n, acc.c_soft_mig_n,
                        w * m.soft_mig_n)
    n = acc.n_ticks + dt.astype(I32)
    nf = jnp.maximum(n.astype(F32), 1.0)
    delta = m.mean_util - acc.w_mean_util
    # ratio-first like online_merge: w/nf is exactly 1.0 on an empty acc,
    # so the first fold lands mean_util bitwise.
    w_mean = acc.w_mean_util + delta * (w / nf)
    w_m2 = acc.w_m2_util + delta * delta * (acc.n_ticks.astype(F32) * w / nf)
    new = SummaryAcc(
        n_ticks=n,
        sum_util_var=su, c_util_var=cu,
        sum_mean_util=sm, c_mean_util=cm,
        sum_flow_rate=sf, c_flow_rate=cf,
        w_mean_util=w_mean, w_m2_util=w_m2,
        sum_active_flows=(acc.sum_active_flows
                          + dt * m.active_flows.astype(I32)),
        sum_arrivals=acc.sum_arrivals + dt * m.new_arrivals.astype(I32),
        sum_decisions=acc.sum_decisions + dt * m.decisions.astype(I32),
        sum_migrations=acc.sum_migrations + dt * m.migrations.astype(I32),
        peak_running=jnp.maximum(acc.peak_running, m.n_running),
        peak_deployed=jnp.maximum(acc.peak_deployed, m.n_deployed),
        peak_overloaded=jnp.maximum(acc.peak_overloaded, m.n_overloaded),
        peak_inactive=jnp.maximum(acc.peak_inactive, m.n_inactive),
        sum_soft_comm=ssc, c_soft_comm=csc,
        sum_soft_util=ssu, c_soft_util=csu,
        sum_soft_n=ssn, c_soft_n=csn,
        sum_soft_mig=ssm, c_soft_mig=csm,
        sum_soft_mig_n=ssmn, c_soft_mig_n=csmn,
    )
    keep = dt > 0
    return jax.tree.map(lambda old, upd: jnp.where(keep, upd, old), acc, new)


def online_init(batch_shape: tuple = ()) -> OnlineSummary:
    """Empty host-side summary (f64/i64, optional leading batch axes).

    Every field gets its OWN buffer — the streaming sweep fills summaries
    slab-by-slab with in-place slice writes, so shared zero arrays would
    alias every integer (or float) field onto one buffer.
    """
    z_i = lambda: np.zeros(batch_shape, np.int64)
    z_f = lambda: np.zeros(batch_shape, np.float64)
    return OnlineSummary(
        n_ticks=z_i(), sum_util_var=z_f(), sum_mean_util=z_f(),
        sum_flow_rate=z_f(), w_mean_util=z_f(), w_m2_util=z_f(),
        sum_active_flows=z_i(), sum_arrivals=z_i(), sum_decisions=z_i(),
        sum_migrations=z_i(), peak_running=z_i(), peak_deployed=z_i(),
        peak_overloaded=z_i(), peak_inactive=z_i(),
        sum_soft_comm=z_f(), sum_soft_util=z_f(), sum_soft_n=z_f(),
        sum_soft_mig=z_f(), sum_soft_mig_n=z_f(),
    )


def online_fold(host: OnlineSummary, acc: SummaryAcc) -> OnlineSummary:
    """Fold one finished device chunk into the host summary.

    This is the ONLY place 64-bit arithmetic happens (satellite: the tick
    stays f32/i32 end to end).  A Kahan pair folds as ``f64(s) + f64(c)``
    — the compensation term recovers the low bits the f32 sum dropped —
    and the per-chunk Welford moments merge with Chan's parallel-combine
    rule.  Broadcasts over leading batch axes.
    """
    a = SummaryAcc(*(np.asarray(x) for x in acc))
    na = host.n_ticks.astype(np.float64)
    nb = a.n_ticks.astype(np.float64)
    n = na + nb
    safe_n = np.where(n > 0, n, 1.0)
    delta = a.w_mean_util.astype(np.float64) - host.w_mean_util
    w_mean = host.w_mean_util + delta * nb / safe_n
    w_m2 = (host.w_m2_util + a.w_m2_util.astype(np.float64)
            + delta * delta * na * nb / safe_n)
    f64 = lambda s, c: s.astype(np.float64) + c.astype(np.float64)
    i64 = lambda x: x.astype(np.int64)
    return OnlineSummary(
        n_ticks=host.n_ticks + i64(a.n_ticks),
        sum_util_var=host.sum_util_var + f64(a.sum_util_var, a.c_util_var),
        sum_mean_util=(host.sum_mean_util
                       + f64(a.sum_mean_util, a.c_mean_util)),
        sum_flow_rate=(host.sum_flow_rate
                       + f64(a.sum_flow_rate, a.c_flow_rate)),
        w_mean_util=w_mean, w_m2_util=w_m2,
        sum_active_flows=host.sum_active_flows + i64(a.sum_active_flows),
        sum_arrivals=host.sum_arrivals + i64(a.sum_arrivals),
        sum_decisions=host.sum_decisions + i64(a.sum_decisions),
        sum_migrations=host.sum_migrations + i64(a.sum_migrations),
        peak_running=np.maximum(host.peak_running, i64(a.peak_running)),
        peak_deployed=np.maximum(host.peak_deployed, i64(a.peak_deployed)),
        peak_overloaded=np.maximum(host.peak_overloaded,
                                   i64(a.peak_overloaded)),
        peak_inactive=np.maximum(host.peak_inactive, i64(a.peak_inactive)),
        sum_soft_comm=(host.sum_soft_comm
                       + f64(a.sum_soft_comm, a.c_soft_comm)),
        sum_soft_util=(host.sum_soft_util
                       + f64(a.sum_soft_util, a.c_soft_util)),
        sum_soft_n=host.sum_soft_n + f64(a.sum_soft_n, a.c_soft_n),
        sum_soft_mig=(host.sum_soft_mig
                      + f64(a.sum_soft_mig, a.c_soft_mig)),
        sum_soft_mig_n=(host.sum_soft_mig_n
                        + f64(a.sum_soft_mig_n, a.c_soft_mig_n)),
    )


def online_merge(a: OnlineSummary, b: OnlineSummary) -> OnlineSummary:
    """Merge two host-side summaries (both already f64/i64).

    The cross-host reduction of the distributed sweep
    (``repro.launch.dist``): each process folds its owned cells into a
    grid-shaped partial summary whose non-owned cells are all-zero
    (``online_init``), and the coordinator reduces the partials with this
    combine.  It is the same Chan parallel-combine rule as
    :func:`online_fold`, but over two finished summaries instead of a
    summary and a device chunk — associative, and EXACT on zero cells
    (``n_ticks == 0`` makes the Welford delta term collapse to the other
    side's value bit-for-bit, sums add 0.0, peaks max with 0), so merging
    disjoint-support partials reproduces the single-process summary
    bit-identically, in any merge order.  Broadcasts over leading batch
    axes.
    """
    na = a.n_ticks.astype(np.float64)
    nb = b.n_ticks.astype(np.float64)
    n = na + nb
    safe_n = np.where(n > 0, n, 1.0)
    delta = b.w_mean_util - a.w_mean_util
    # the ratios are formed FIRST: on empty sides nb/n is exactly 1.0
    # (na == 0) or 0.0 (nb == 0), so the delta term collapses bitwise.
    # Left-to-right (delta * nb) / n would round twice and break the
    # zero-partial identity (caught by test_sweep_dist).
    w_mean = a.w_mean_util + delta * (nb / safe_n)
    w_m2 = (a.w_m2_util + b.w_m2_util
            + delta * delta * (na * nb / safe_n))
    return OnlineSummary(
        n_ticks=a.n_ticks + b.n_ticks,
        sum_util_var=a.sum_util_var + b.sum_util_var,
        sum_mean_util=a.sum_mean_util + b.sum_mean_util,
        sum_flow_rate=a.sum_flow_rate + b.sum_flow_rate,
        w_mean_util=w_mean, w_m2_util=w_m2,
        sum_active_flows=a.sum_active_flows + b.sum_active_flows,
        sum_arrivals=a.sum_arrivals + b.sum_arrivals,
        sum_decisions=a.sum_decisions + b.sum_decisions,
        sum_migrations=a.sum_migrations + b.sum_migrations,
        peak_running=np.maximum(a.peak_running, b.peak_running),
        peak_deployed=np.maximum(a.peak_deployed, b.peak_deployed),
        peak_overloaded=np.maximum(a.peak_overloaded, b.peak_overloaded),
        peak_inactive=np.maximum(a.peak_inactive, b.peak_inactive),
        sum_soft_comm=a.sum_soft_comm + b.sum_soft_comm,
        sum_soft_util=a.sum_soft_util + b.sum_soft_util,
        sum_soft_n=a.sum_soft_n + b.sum_soft_n,
        sum_soft_mig=a.sum_soft_mig + b.sum_soft_mig,
        sum_soft_mig_n=a.sum_soft_mig_n + b.sum_soft_mig_n,
    )


def online_from_metrics(metrics: TickMetrics) -> OnlineSummary:
    """The stacked-path twin: the same summary computed from a full
    [..., T] ``TickMetrics`` series in f64.

    ``report.summarize`` routes BOTH paths through this shape, so stacked
    and streaming runs report identical keys — integer sums/peaks agree
    bit-for-bit with the chunked fold, float sums to ~1 ulp of f32.
    """
    f = lambda x: np.asarray(x, np.float64)
    i = lambda x: np.asarray(x).astype(np.int64)
    mu = f(metrics.mean_util)
    n = np.full(mu.shape[:-1], mu.shape[-1], np.int64)
    w_mean = mu.mean(axis=-1) if mu.shape[-1] else np.zeros(mu.shape[:-1])
    w_m2 = ((mu - w_mean[..., None]) ** 2).sum(axis=-1)
    return OnlineSummary(
        n_ticks=n,
        sum_util_var=f(metrics.util_variance).sum(axis=-1),
        sum_mean_util=mu.sum(axis=-1),
        sum_flow_rate=f(metrics.mean_flow_rate).sum(axis=-1),
        w_mean_util=w_mean, w_m2_util=w_m2,
        sum_active_flows=i(metrics.active_flows).sum(axis=-1),
        sum_arrivals=i(metrics.new_arrivals).sum(axis=-1),
        sum_decisions=i(metrics.decisions).sum(axis=-1),
        sum_migrations=i(metrics.migrations).sum(axis=-1),
        peak_running=i(metrics.n_running).max(axis=-1),
        peak_deployed=i(metrics.n_deployed).max(axis=-1),
        peak_overloaded=i(metrics.n_overloaded).max(axis=-1),
        peak_inactive=i(metrics.n_inactive).max(axis=-1),
        sum_soft_comm=f(metrics.soft_comm).sum(axis=-1),
        sum_soft_util=f(metrics.soft_util).sum(axis=-1),
        sum_soft_n=f(metrics.soft_n).sum(axis=-1),
        sum_soft_mig=f(metrics.soft_mig).sum(axis=-1),
        sum_soft_mig_n=f(metrics.soft_mig_n).sum(axis=-1),
    )


# ---------------------------------------------------------------------------
# Differentiable surrogate objectives (SimConfig.soft_placement)
# ---------------------------------------------------------------------------
# name -> which surrogate sums form the mean.  'soft_blend' mixes the
# comm- and util-expectation columns: a single-column objective is
# invariant to scaling ITS one weight (softmax over a rescaled row moves,
# but for the disjoint-support legacy vectors the hard argmin does not),
# so the blend is the default the grad tuner descends.  Lower = better.
SOFT_OBJECTIVES: tuple = ("soft_blend", "soft_comm", "soft_util",
                          "soft_mig_util")


def soft_num_den(m, objective: str = "soft_blend"):
    """(numerator, denominator) of a named surrogate objective.

    ``m`` may be stacked ``TickMetrics`` (trailing time axis, summed
    here), a ``SummaryAcc`` (in-jit streaming carry — the Kahan pair is
    collapsed as ``sum + c``, matching ``online_fold``'s recovery), or a
    host-side ``OnlineSummary``.  Stays inside jit and is differentiable
    end to end — this is the reduction ``jax.grad`` flows through.
    """
    if objective not in SOFT_OBJECTIVES:
        raise KeyError(f"unknown soft objective {objective!r}; known: "
                       f"{list(SOFT_OBJECTIVES)}")
    if isinstance(m, SummaryAcc):
        comm = m.sum_soft_comm + m.c_soft_comm
        util = m.sum_soft_util + m.c_soft_util
        n = m.sum_soft_n + m.c_soft_n
        mig = m.sum_soft_mig + m.c_soft_mig
        mig_n = m.sum_soft_mig_n + m.c_soft_mig_n
    elif isinstance(m, OnlineSummary):
        comm, util, n = m.sum_soft_comm, m.sum_soft_util, m.sum_soft_n
        mig, mig_n = m.sum_soft_mig, m.sum_soft_mig_n
    elif isinstance(m, TickMetrics):
        comm = m.soft_comm.sum(axis=-1)
        util = m.soft_util.sum(axis=-1)
        n = m.soft_n.sum(axis=-1)
        mig = m.soft_mig.sum(axis=-1)
        mig_n = m.soft_mig_n.sum(axis=-1)
    else:
        raise TypeError(f"expected TickMetrics, SummaryAcc or "
                        f"OnlineSummary, got {type(m).__name__}")
    if objective == "soft_comm":
        return comm, n
    if objective == "soft_util":
        return util, n
    if objective == "soft_mig_util":
        return mig, mig_n
    return comm + util, n


def soft_objective(m, objective: str = "soft_blend"):
    """Mean surrogate cost (lower = better): numerator / max(count, 1).

    The count denominator comes from non-differentiable feasibility
    decisions, so it is piecewise-constant in the weights — the gradient
    is the exact gradient of the numerator scaled by it.
    """
    num, den = soft_num_den(m, objective)
    return num / jnp.maximum(den, 1.0)
