"""Weight search: learn scheduling-policy weights with the compiled sweep.

With branch-free scoring a policy IS a point in weight space
(``PolicyParams.weights``), so "learning a policy" degenerates to search:
sample W weight vectors, stack them on the sweep's policy axis, and run
the whole W x scenario x seed population as ONE jit — the same
``make_sweep_fn`` program the policy sweep uses, with weights instead of
named policies on the batch axis (and the same split across devices).  This is the ROADMAP "learned netaware weights" item in its
simplest honest form: random (or per-dimension grid) search, one
compilation, a ranked best-weights table via ``report.tune_table``.

    PYTHONPATH=src python -m repro.launch.tune --samples 16 --seeds 2 \\
        --objective avg_runtime --out tune.json
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import SimConfig, get_policy, sweep_summaries, tune_table
from repro.core import stats
from repro.core.engine import resolve_plan
from repro.core.scenario import ScenarioSpec, build_scenarios
from repro.core.scheduling import validate_weights, weight_index
from repro.core.types import (NUM_POLICY_WEIGHTS, WEIGHT_NAMES, ExecPlan,
                              PolicyParams)
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.execargs import add_exec_args
from repro.launch.sweep import make_grad_fn, make_stream_fn, make_sweep_fn

# Default search space: the cost-model weights of the network-aware score
# plus the co-location / consolidation trade-off — the knobs the paper's
# comparison says matter.  Everything not named here keeps the base
# policy's value (FIFO selection, migration rule, ...).
DEFAULT_SPACE: dict[str, tuple[float, float]] = {
    "util": (0.0, 4.0),
    "cross_leaf": (0.0, 1.0),
    "row_comm": (0.0, 2.0),
    "row_coloc": (0.0, 2.0),
    "row_fallback_worst": (0.0, 2.0),
    "row_worst_fit": (0.0, 1.0),
    "row_cross_leaf": (0.0, 1.0),
}

# summary metrics where bigger is better — negated so "lower = better"
# holds for every objective
MAXIMIZE = {"completion_rate", "n_completed", "peak_running",
            "peak_deployed"}


def sample_weights(n: int, seed: int = 0, base: str = "netaware",
                   space: dict[str, tuple[float, float]] | None = None,
                   grid: bool = False) -> np.ndarray:
    """[n, NUM_POLICY_WEIGHTS] search population around a registered base.

    Random mode draws each searched dimension uniformly from its range;
    grid mode sweeps ONE dimension at a time over ``(n - 1) // len(space)``
    evenly spaced points per dimension (coordinate profile, not a full
    product — the honest grid at small budgets).  The grid points span
    ``(lo, hi]`` from the top: the lower bound is excluded (it is 0 =
    "feature off" for most ranges and often the base value itself), so a
    1-point-per-dimension budget tests ``hi``, not a duplicate of the
    incumbent.  Sample 0 is always the untouched base vector, so the
    incumbent appears in every ranking.
    """
    space = DEFAULT_SPACE if space is None else space
    idx = {name: weight_index(name) for name in space}   # loud on unknowns
    base_w = np.asarray(get_policy(base).weights, np.float32)
    W = np.tile(base_w, (n, 1))
    rng = np.random.default_rng(seed)
    if grid:
        names = list(space)
        per = max(1, (n - 1) // len(names))
        i = 1
        for name in names:
            lo, hi = space[name]
            for v in np.linspace(lo, hi, per + 1)[1:]:
                if i < n:
                    W[i, idx[name]] = v
                    i += 1
    else:
        for name, (lo, hi) in space.items():
            W[1:, idx[name]] = rng.uniform(lo, hi, n - 1)
    return W


@dataclasses.dataclass
class TuneResult:
    weights: np.ndarray       # [W, NUM_POLICY_WEIGHTS]
    scores: np.ndarray        # [W] TRUE objective values (NaN = failed)
    objective: str
    minimize: bool            # ranking direction (False for MAXIMIZE)
    rows: list[dict[str, Any]]
    scenarios: list[ScenarioSpec]
    seeds: tuple[int, ...]
    wall_s: float             # first (cold: compile + run) call
    steady_s: float | None    # min warm repeat of the same compiled call
    compile_cache_misses: int
    n_devices: int

    def ranking(self) -> np.ndarray:
        """Sample indices best-first (NaN scores last either way)."""
        return np.argsort(self.scores if self.minimize else -self.scores)

    @property
    def best(self) -> int:
        return int(self.ranking()[0])

    def best_weights(self) -> dict[str, float]:
        return {name: float(v)
                for name, v in zip(WEIGHT_NAMES, self.weights[self.best])}

    def table(self, top: int = 10) -> str:
        return tune_table(self.weights, self.scores, self.objective,
                          top=top, minimize=self.minimize)


def _default_scenarios() -> list[ScenarioSpec]:
    return [ScenarioSpec("baseline"),
            ScenarioSpec("slow_net", bw=200.0),
            ScenarioSpec("bursty", arrival="bursty")]


def _mean_scores(fn, sims, W, rps, scenarios, seeds, objective):
    """Oracle-score a weight population: run the compiled sweep with the
    weights on the policy axis and mean the summary ``objective`` over
    every (scenario, seed) cell — (scores [W], summary rows)."""
    n = W.shape[0]
    finals, metrics = fn(sims, PolicyParams(weights=jnp.asarray(W)), rps)
    names = [f"w{i:03d}" for i in range(n)]
    rows = sweep_summaries(finals, metrics, names,
                           [s.name for s in scenarios], seeds)
    per = {name: [] for name in names}
    for r in rows:
        per[r["policy"]].append(float(r[objective]))
    return np.asarray([np.mean(per[name]) for name in names]), rows


def run_tune(n_samples: int = 16, seeds: Sequence[int] = (0,),
             scenarios: Sequence[ScenarioSpec] | None = None,
             cfg: SimConfig | None = None, n_hosts: int = 20,
             n_spine: int = 2, n_leaf: int = 4,
             objective: str = "avg_runtime", base: str = "netaware",
             space: dict[str, tuple[float, float]] | None = None,
             grid: bool = False, seed: int = 0,
             devices=None, reps: int = 1, chunk: int | None = None,
             slab: int | None = None, overlap: bool | None = None,
             procs: int | None = None, devices_per_proc: int | None = None,
             plan: ExecPlan | None = None) -> TuneResult:
    """One compiled call over the whole search population.

    The per-sample score is the objective's plain mean over every
    (scenario, seed) cell, reported in the metric's TRUE sign (the
    ranking direction comes from ``MAXIMIZE``) — a sample that fails the
    objective anywhere (e.g. completes nothing, NaN ``avg_runtime``)
    scores NaN and ranks last, deliberately NOT nan-skipped.

    ``reps > 1`` re-runs the SAME compiled call warm and records the
    minimum as ``steady_s`` — the runtime-dominated number the bench
    regression gate tracks (the first call's ``wall_s`` is mostly XLA
    compile on small grids).

    ``chunk`` streams the search through ``make_stream_fn`` — [W, S, N]
    summaries via online folds, never a [W, S, N, T] metrics stack, with
    the population optionally slabbed ``slab`` cells at a time (and, with
    ``overlap``, gathered one slab behind the async dispatch).  Scores
    match the stacked search to float precision (integer objectives
    exactly).

    A ``plan.procs > 1`` runs the streamed search MULTI-PROCESS through
    the distributed sweep fabric (``repro.launch.dist``): the weight
    population rides the same slab-per-process handout as a policy sweep
    (weights are just the policy batch axis), each process owning
    ``plan.devices_per_proc`` forced CPU devices locally or one
    accelerator process slot on a real fleet, and the partial summaries
    reduced with ``stats.online_merge``.  Requires ``plan.chunk``; scores
    are bit-identical to the single-process streamed search.

    Execution options ride in ``plan``; the bare ``devices``/``chunk``/
    ``slab``/``overlap``/``procs``/``devices_per_proc`` kwargs are
    deprecated (one cycle).
    """
    cfg = cfg or SimConfig()
    plan, cfg = resolve_plan(plan, cfg, devices=devices, chunk=chunk,
                             slab=slab, overlap=overlap, procs=procs,
                             devices_per_proc=devices_per_proc)
    scenarios = list(scenarios if scenarios is not None
                     else _default_scenarios())
    W = sample_weights(n_samples, seed=seed, base=base, space=space,
                       grid=grid)
    validate_weights(W, "tune samples: ")
    pol = PolicyParams(weights=jnp.asarray(W))
    net_spec, sims, rps = build_scenarios(scenarios, cfg, n_hosts=n_hosts,
                                          n_spine=n_spine, n_leaf=n_leaf,
                                          seeds=seeds)
    if plan.procs > 1:
        if plan.chunk is None:
            raise ValueError("procs > 1 requires chunk (the distributed "
                             "fabric streams slabs; there is no stacked "
                             "multi-process path)")
        if plan.telescope:
            raise ValueError("telescope is not threaded through the "
                             "multi-process fabric yet — drop procs or "
                             "telescope")
        from repro.launch.dist import make_dist_fn
        fn = make_dist_fn(cfg, scenarios, seeds, weights=W,
                          n_hosts=n_hosts, n_spine=n_spine, n_leaf=n_leaf,
                          plan=plan)
    elif plan.chunk is not None or plan.telescope:
        fn = make_stream_fn(cfg, net_spec.n_hosts, net_spec.n_nodes,
                            cfg.horizon, chunk=plan.chunk or cfg.horizon,
                            slab=plan.slab, devices=plan.devices,
                            overlap=plan.overlap, telescope=plan.telescope)
    else:
        fn = make_sweep_fn(cfg, net_spec.n_hosts, net_spec.n_nodes,
                           cfg.horizon, devices=plan.devices)
    def ready(x):   # streaming finals are already host-side numpy
        leaf = jax.tree.leaves(x)[0]
        if hasattr(leaf, "block_until_ready"):
            leaf.block_until_ready()

    t0 = time.time()
    finals, metrics = fn(sims, pol, rps)   # streaming: OnlineSummary
    ready(finals)
    wall = time.time() - t0
    steady = None
    if reps > 1:
        reruns = []
        for _ in range(reps - 1):
            t0 = time.time()
            ready(fn(sims, pol, rps)[0])
            reruns.append(time.time() - t0)
        steady = round(min(reruns), 2)

    names = [f"w{i:03d}" for i in range(n_samples)]
    rows = sweep_summaries(finals, metrics, names,
                           [s.name for s in scenarios], seeds)
    per = {n: [] for n in names}
    for r in rows:
        per[r["policy"]].append(float(r[objective]))
    scores = np.asarray([np.mean(per[n]) for n in names])
    return TuneResult(weights=W, scores=scores, objective=objective,
                      minimize=objective not in MAXIMIZE,
                      rows=rows, scenarios=scenarios, seeds=tuple(seeds),
                      wall_s=round(wall, 2), steady_s=steady,
                      compile_cache_misses=fn._cache_size(),
                      n_devices=fn.n_devices)


@dataclasses.dataclass
class GradTuneResult(TuneResult):
    """A :class:`TuneResult` (final population + ORACLE scores — the
    ranking/table surface is unchanged) plus the optimizer's trajectory:
    the overall-best oracle-scored candidate (never worse than the
    incumbent: the initial population, incumbent row 0 included, is
    oracle-scored before the first step) and the per-step history of the
    surrogate/oracle values — the honest view of how well descending the
    soft surrogate tracks the hard objective (docs/autodiff.md)."""

    method: str = "grad"
    surrogate: np.ndarray | None = None   # [M] final surrogate per candidate
    surrogate_name: str | None = None
    best_oracle: float = float("nan")     # best oracle score ever seen
    best_oracle_weights: np.ndarray | None = None
    history: list | None = None           # per-step dicts (step, tau, ...)
    surrogate_evals: int = 0              # candidate-evals spent on grad steps
    oracle_evals: int = 0                 # candidate-evals spent on re-scoring


def _space_bounds(space: dict[str, tuple[float, float]]):
    """(searched index array, mask [W], lo [W], hi [W]) — the gradient /
    sampling machinery only touches the searched dimensions."""
    idx = np.asarray([weight_index(name) for name in space], np.int64)
    mask = np.zeros((NUM_POLICY_WEIGHTS,), np.float32)
    lo = np.full((NUM_POLICY_WEIGHTS,), -np.inf, np.float32)
    hi = np.full((NUM_POLICY_WEIGHTS,), np.inf, np.float32)
    mask[idx] = 1.0
    for name, (a, b) in space.items():
        lo[weight_index(name)] = a
        hi[weight_index(name)] = b
    return idx, mask, lo, hi


def _make_oracle(cfg: SimConfig, net_spec, horizon: int, plan: ExecPlan):
    """The hard-placement scorer the grad/CEM loops re-score against —
    ``soft_placement`` OFF, so every score is the true simulator's."""
    hard = dataclasses.replace(cfg, soft_placement=False)
    if plan.chunk is not None or plan.telescope:
        # soft placement is OFF here, so the oracle may telescope even
        # though the surrogate descent itself stays per-tick (while_loop
        # has no reverse-mode autodiff — docs/events.md)
        return make_stream_fn(hard, net_spec.n_hosts, net_spec.n_nodes,
                              horizon, chunk=plan.chunk or horizon,
                              slab=plan.slab, devices=plan.devices,
                              overlap=plan.overlap,
                              telescope=plan.telescope)
    return make_sweep_fn(hard, net_spec.n_hosts, net_spec.n_nodes, horizon,
                         devices=plan.devices)


def run_tune_grad(steps: int = 24, batch: int = 8, lr: float = 0.1,
                  tau0: float = 1.0, tau_decay: float = 0.85,
                  tau_min: float = 0.05, eval_every: int = 6,
                  seeds: Sequence[int] = (0,),
                  scenarios: Sequence[ScenarioSpec] | None = None,
                  cfg: SimConfig | None = None, n_hosts: int = 20,
                  n_spine: int = 2, n_leaf: int = 4,
                  objective: str = "avg_runtime",
                  surrogate: str = "soft_blend", base: str = "netaware",
                  space: dict[str, tuple[float, float]] | None = None,
                  seed: int = 0,
                  plan: ExecPlan | None = None) -> GradTuneResult:
    """Gradient search: descend the DIFFERENTIABLE soft-placement
    surrogate, trust only the hard oracle.

    A batch of ``batch`` candidates (row 0 = the untouched ``base``
    incumbent) rides the policy axis of ONE compiled
    ``jax.value_and_grad`` sweep (``sweep.make_grad_fn``, built from a
    ``soft_placement=True`` twin of ``cfg``); each step applies plain
    gradient descent on the searched dimensions only (masked to
    ``space``, clipped to its bounds).  The softmax temperature anneals
    ``tau0 -> tau_min`` by ``tau_decay`` per step — ``tau`` is a traced
    ``RunParams`` field, so annealing never recompiles.

    The surrogate is a guide, not the objective: every ``eval_every``
    steps (and before the first, and after the last) the CURRENT
    candidates are re-scored on the hard oracle (``soft_placement=False``
    — bit-for-bit the production simulator) under the TRUE ``objective``,
    and the best-ever oracle candidate is tracked.  Because the incumbent
    is oracle-scored up front, the result never ranks below it.  Both
    trajectories land in ``history``; ``scores`` is the final
    population's oracle score so ``table()`` ranks real numbers.
    """
    cfg = cfg or SimConfig()
    plan = ExecPlan() if plan is None else plan
    cfg = plan.apply_to_config(cfg)
    if plan.procs > 1:
        raise ValueError("grad mode is single-process (the oracle rides "
                         "plan.chunk/devices; procs is random/grid only)")
    scenarios = list(scenarios if scenarios is not None
                     else _default_scenarios())
    space = DEFAULT_SPACE if space is None else space
    idx, mask, lo, hi = _space_bounds(space)
    minimize = objective not in MAXIMIZE
    better = (lambda a, b: a < b) if minimize else (lambda a, b: a > b)

    W = sample_weights(batch, seed=seed, base=base, space=space)
    validate_weights(W, "tune grad candidates: ")
    soft = dataclasses.replace(cfg, soft_placement=True)
    net_spec, sims, rps = build_scenarios(scenarios, soft, n_hosts=n_hosts,
                                          n_spine=n_spine, n_leaf=n_leaf,
                                          seeds=seeds)
    gfn = make_grad_fn(soft, net_spec.n_hosts, net_spec.n_nodes,
                       cfg.horizon, objective=surrogate, chunk=plan.chunk,
                       devices=plan.devices)
    ofn = _make_oracle(cfg, net_spec, cfg.horizon, plan)

    t_start = time.time()
    history: list[dict[str, Any]] = []
    surrogate_evals = 0
    scores, rows = _mean_scores(ofn, sims, W, rps, scenarios, seeds,
                                objective)
    oracle_evals = batch
    k = int(np.nanargmin(scores) if minimize else np.nanargmax(scores))
    best_score, best_w = float(scores[k]), W[k].copy()
    tau = float(tau0)
    for step in range(steps):
        rps_t = rps._replace(tau=jnp.full_like(rps.tau, tau))
        obj_s, g = gfn(sims, PolicyParams(weights=jnp.asarray(W)), rps_t)
        surrogate_evals += batch
        g = np.asarray(g, np.float32) * mask[None, :]
        W = np.clip(W - lr * g, lo[None, :], hi[None, :]).astype(np.float32)
        rec = {"step": step, "tau": round(tau, 6),
               "surrogate_mean": float(np.mean(np.asarray(obj_s))),
               "grad_norm": float(np.linalg.norm(g) / max(batch, 1))}
        if (step + 1) % eval_every == 0 or step == steps - 1:
            scores, rows = _mean_scores(ofn, sims, W, rps, scenarios,
                                        seeds, objective)
            oracle_evals += batch
            finite = np.isfinite(scores)
            if finite.any():
                k = int(np.nanargmin(scores) if minimize
                        else np.nanargmax(scores))
                if better(scores[k], best_score):
                    best_score, best_w = float(scores[k]), W[k].copy()
            rec["oracle_best"] = (float(np.nanmin(scores)) if minimize
                                  else float(np.nanmax(scores)))
        history.append(rec)
        tau = max(tau * tau_decay, tau_min)

    rps_t = rps._replace(tau=jnp.full_like(rps.tau, tau))
    final_sur, _ = gfn(sims, PolicyParams(weights=jnp.asarray(W)), rps_t)
    surrogate_evals += batch
    return GradTuneResult(
        weights=W, scores=scores, objective=objective, minimize=minimize,
        rows=rows, scenarios=scenarios, seeds=tuple(seeds),
        wall_s=round(time.time() - t_start, 2), steady_s=None,
        compile_cache_misses=gfn._cache_size() + ofn._cache_size(),
        n_devices=gfn.n_devices, method="grad",
        surrogate=np.asarray(final_sur), surrogate_name=surrogate,
        best_oracle=best_score, best_oracle_weights=best_w,
        history=history, surrogate_evals=surrogate_evals,
        oracle_evals=oracle_evals)


def run_tune_cem(steps: int = 6, batch: int = 16, elite_frac: float = 0.25,
                 init_std_frac: float = 0.3, seeds: Sequence[int] = (0,),
                 scenarios: Sequence[ScenarioSpec] | None = None,
                 cfg: SimConfig | None = None, n_hosts: int = 20,
                 n_spine: int = 2, n_leaf: int = 4,
                 objective: str = "avg_runtime", base: str = "netaware",
                 space: dict[str, tuple[float, float]] | None = None,
                 seed: int = 0,
                 plan: ExecPlan | None = None) -> GradTuneResult:
    """Cross-entropy search on the HARD oracle (no surrogate): iterate
    sample -> score -> refit a diagonal Gaussian to the elite fraction.
    Every population re-enters the one compiled sweep (same shapes), the
    incumbent is re-injected as row 0 each round, and the best-ever
    oracle candidate is tracked — the derivative-free arm the grad mode
    is compared against."""
    cfg = cfg or SimConfig()
    plan = ExecPlan() if plan is None else plan
    cfg = plan.apply_to_config(cfg)
    scenarios = list(scenarios if scenarios is not None
                     else _default_scenarios())
    space = DEFAULT_SPACE if space is None else space
    idx, _, lo, hi = _space_bounds(space)
    minimize = objective not in MAXIMIZE
    better = (lambda a, b: a < b) if minimize else (lambda a, b: a > b)

    base_w = np.asarray(get_policy(base).weights, np.float32)
    net_spec, sims, rps = build_scenarios(scenarios, cfg, n_hosts=n_hosts,
                                          n_spine=n_spine, n_leaf=n_leaf,
                                          seeds=seeds)
    ofn = _make_oracle(cfg, net_spec, cfg.horizon, plan)
    rng = np.random.default_rng(seed)
    mu = base_w[idx].astype(np.float64)
    sd = (hi[idx] - lo[idx]).astype(np.float64) * init_std_frac
    n_elite = max(1, int(round(batch * elite_frac)))

    t_start = time.time()
    history: list[dict[str, Any]] = []
    oracle_evals = 0
    best_score, best_w = float("inf") if minimize else -float("inf"), base_w
    W = scores = rows = None
    for step in range(steps):
        W = np.tile(base_w, (batch, 1))
        W[1:, idx] = np.clip(rng.normal(mu, sd, (batch - 1, idx.size)),
                             lo[idx], hi[idx])
        W = W.astype(np.float32)
        scores, rows = _mean_scores(ofn, sims, W, rps, scenarios, seeds,
                                    objective)
        oracle_evals += batch
        order = np.argsort(scores if minimize else -scores)
        elite = W[order[:n_elite]][:, idx].astype(np.float64)
        mu = elite.mean(axis=0)
        sd = np.maximum(elite.std(axis=0), 1e-3)
        k = int(order[0])
        if np.isfinite(scores[k]) and better(scores[k], best_score):
            best_score, best_w = float(scores[k]), W[k].copy()
        history.append({"step": step,
                        "oracle_best": (float(np.nanmin(scores)) if minimize
                                        else float(np.nanmax(scores))),
                        "mu": [round(float(v), 4) for v in mu],
                        "sd": [round(float(v), 4) for v in sd]})
    return GradTuneResult(
        weights=W, scores=scores, objective=objective, minimize=minimize,
        rows=rows, scenarios=scenarios, seeds=tuple(seeds),
        wall_s=round(time.time() - t_start, 2), steady_s=None,
        compile_cache_misses=ofn._cache_size(), n_devices=ofn.n_devices,
        method="cem", surrogate=None, surrogate_name=None,
        best_oracle=best_score, best_oracle_weights=best_w,
        history=history, surrogate_evals=0, oracle_evals=oracle_evals)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--method", default="random",
                    choices=["random", "grid", "grad", "cem"],
                    help="random/grid = one-shot population ranking; "
                         "grad = descend the soft-placement surrogate "
                         "with hard-oracle re-scoring; cem = "
                         "cross-entropy on the hard oracle")
    ap.add_argument("--samples", type=int, default=16)
    ap.add_argument("--seeds", type=int, default=1,
                    help="number of seeds (0..n-1) per cell")
    ap.add_argument("--horizon", type=int, default=120)
    ap.add_argument("--hosts", type=int, default=20)
    ap.add_argument("--objective", default="avg_runtime",
                    help="summary metric to optimize (lower = better; "
                         f"negated for {sorted(MAXIMIZE)})")
    ap.add_argument("--base", default="netaware",
                    help="registered policy the search perturbs")
    ap.add_argument("--grid", action="store_true",
                    help="(random/grid) coordinate-profile grid instead of "
                         "random draws")
    ap.add_argument("--seed", type=int, default=0, help="search RNG seed")
    g = ap.add_argument_group("grad / cem")
    g.add_argument("--steps", type=int, default=None,
                   help="optimizer steps (default: 24 grad, 6 cem)")
    g.add_argument("--batch", type=int, default=None,
                   help="candidates per step (default: 8 grad, 16 cem)")
    g.add_argument("--lr", type=float, default=0.1,
                   help="(grad) gradient-descent step size")
    g.add_argument("--tau0", type=float, default=1.0,
                   help="(grad) initial softmax temperature")
    g.add_argument("--tau-decay", type=float, default=0.85,
                   help="(grad) per-step temperature decay factor")
    g.add_argument("--tau-min", type=float, default=0.05,
                   help="(grad) temperature floor")
    g.add_argument("--eval-every", type=int, default=6,
                   help="(grad) hard-oracle re-scoring period in steps")
    g.add_argument("--surrogate", default="soft_blend",
                   choices=sorted(stats.SOFT_OBJECTIVES),
                   help="(grad) differentiable objective to descend")
    g.add_argument("--elite-frac", type=float, default=0.25,
                   help="(cem) elite fraction per refit")
    add_exec_args(ap, dist=True)
    ap.add_argument("--top", type=int, default=10)
    ap.add_argument("--out", default=None,
                    help="write best weights + ranked samples as JSON")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = SimConfig(horizon=args.horizon)
    plan = ExecPlan.from_args(args)
    n_leaf = max(4, args.hosts // 5)
    common = dict(seeds=range(args.seeds), cfg=cfg, n_hosts=args.hosts,
                  n_spine=max(2, n_leaf // 4), n_leaf=n_leaf,
                  objective=args.objective, base=args.base, seed=args.seed,
                  plan=plan)
    if args.method == "grad":
        res = run_tune_grad(steps=args.steps or 24, batch=args.batch or 8,
                            lr=args.lr, tau0=args.tau0,
                            tau_decay=args.tau_decay, tau_min=args.tau_min,
                            eval_every=args.eval_every,
                            surrogate=args.surrogate, **common)
    elif args.method == "cem":
        res = run_tune_cem(steps=args.steps or 6, batch=args.batch or 16,
                           elite_frac=args.elite_frac, **common)
    else:
        res = run_tune(n_samples=args.samples,
                       grid=(args.method == "grid" or args.grid), **common)

    n_cand = res.weights.shape[0]
    cells = n_cand * len(res.scenarios) * len(res.seeds)
    print(f"# {args.method}: {cells} cells/eval ({n_cand} candidates x "
          f"{len(res.scenarios)} scenarios x {len(res.seeds)} seeds) in "
          f"{res.wall_s}s, {res.compile_cache_misses} compilation(s), "
          f"{res.n_devices} device(s)")
    if isinstance(res, GradTuneResult):
        arrow = "min" if res.minimize else "max"
        print(f"# best oracle {res.objective} ({arrow}): "
              f"{res.best_oracle:.4f} after {res.oracle_evals} oracle + "
              f"{res.surrogate_evals} surrogate evals")
        if res.method == "grad" and res.history:
            taus = [h["tau"] for h in res.history]
            print(f"# tau annealed {taus[0]:g} -> {taus[-1]:g} "
                  f"({res.surrogate_name} surrogate)")
    print(res.table(args.top))
    if args.out:
        from repro.core.report import json_clean
        out = {"method": args.method,
               "objective": res.objective,
               "best_sample": res.best,
               "best_weights": res.best_weights(),
               "scores": json_clean(list(map(float, res.scores))),
               "weights": [list(map(float, w)) for w in res.weights]}
        if isinstance(res, GradTuneResult):
            out["best_oracle"] = res.best_oracle
            if res.best_oracle_weights is not None:
                out["best_oracle_weights"] = dict(
                    zip(WEIGHT_NAMES,
                        map(float, res.best_oracle_weights)))
            out["history"] = res.history
        with open(args.out, "w") as f:
            json.dump(json_clean(out), f, indent=1)
        print(f"# wrote {args.out}")


if __name__ == "__main__":
    main()
