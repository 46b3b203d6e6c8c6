"""DCSim CLI: run the paper's container-scheduling simulation.

    PYTHONPATH=src python -m repro.launch.sim --policy jobgroup --horizon 120
    PYTHONPATH=src python -m repro.launch.sim --policy netaware --bw 200
    PYTHONPATH=src python -m repro.launch.sim --policy all --bw 200 --loss 0.02
    PYTHONPATH=src python -m repro.launch.sim --policy all --hosts 500 \\
        --containers 3000 --horizon 40 --out reports.json

With policies as weight vectors, ``--policy all`` is six runs of ONE
compiled program over ONE prebuilt state — no per-policy rebuild, no
per-policy compile — and ``--weights name=value,...`` runs a by-name
weight variant (``types.WEIGHT_NAMES``) through the same executable:

    PYTHONPATH=src python -m repro.launch.sim --policy netaware \\
        --weights cross_leaf=0.5,row_coloc=0.3

The full policy x scenario x seed grid lives in ``repro.launch.sweep``;
weight *search* lives in ``repro.launch.tune``.
"""
from __future__ import annotations

import argparse
import json
import time

from repro.core import (ExecPlan, SimConfig, build_paper_hosts,
                        build_paper_network, get_policy, init_sim,
                        list_policies, paper_workload, run_sim, scaled_hosts,
                        summarize, to_csv, trace_workload)
from repro.core.report import json_clean
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.execargs import add_exec_args


def build_once(cfg: SimConfig, bw=None, loss=None, seed=0, workload="paper",
               n_hosts=20):
    """Hosts + network + workload + initial state, built ONCE and reused
    for every policy: the policy is data, the state is shared.  The bw/loss
    overrides ride the RunParams (applied at t=0 inside the run) instead of
    mutating the built network per policy."""
    # same domain checks as set_link_params/ScenarioSpec: values inside the
    # RunParams keep-sentinel range must fail loudly, not silently no-op
    if bw is not None and bw <= 0:
        raise ValueError(f"--bw must be > 0 Mbps, got {bw}")
    if loss is not None and loss < 0:
        raise ValueError(f"--loss must be >= 0, got {loss}")
    hosts = (build_paper_hosts() if n_hosts == 20
             else scaled_hosts(n_hosts, max(4, n_hosts // 5)))
    spec, net = build_paper_network(cfg, n_hosts=n_hosts,
                                    n_leaf=max(4, n_hosts // 5))
    gen = paper_workload if workload == "paper" else trace_workload
    sim0 = init_sim(hosts, gen(cfg, seed=seed), net, seed=seed)
    params = cfg.run_params()._replace(
        **{k: v for k, v in
           (("bw_mbps", bw), ("loss", loss)) if v is not None})
    return spec, sim0, params


def parse_weights(arg: str | None) -> dict[str, float] | None:
    """``"cross_leaf=0.5,row_coloc=0.3"`` -> by-name override dict
    (validated against ``types.WEIGHT_NAMES`` by ``get_policy``)."""
    if not arg:
        return None
    out = {}
    for item in arg.split(","):
        name, _, val = item.partition("=")
        if not _:
            raise ValueError(f"--weights items must be name=value, "
                             f"got {item!r}")
        out[name.strip()] = float(val)
    return out


def run_one(policy_name: str, cfg: SimConfig, spec, sim0, params, csv=None,
            weights=None, plan: ExecPlan | None = None):
    from repro.kernels import kernel_backend, resolve_kernel
    plan = ExecPlan() if plan is None else plan
    if csv and plan.chunk is not None:
        raise ValueError("--csv needs the stacked per-tick series; "
                         "drop --chunk to export one")
    if csv and plan.telescope:
        raise ValueError("--csv needs the stacked per-tick series; "
                         "telescoping skips quiescent ticks and keeps only "
                         "online summaries — drop --telescope to export one")
    t0 = time.time()
    final, metrics = run_sim(sim0, cfg, get_policy(policy_name, weights),
                             spec.n_hosts, spec.n_nodes, cfg.horizon,
                             params=params, plan=plan)
    final.t.block_until_ready()
    rep = summarize(final, metrics)   # metrics: stack OR OnlineSummary
    rep["policy"] = policy_name
    rep["wall_s"] = round(time.time() - t0, 2)
    # self-describing rows: which backend ran this, and whether the delay /
    # waterfill hot paths went through their Pallas kernels (flag + what it
    # resolved to on this backend)
    rep["backend"] = kernel_backend()
    rep["delay_mode"] = cfg.delay_mode
    rep["delay_kernel"] = cfg.delay_kernel
    rep["delay_kernel_active"] = (cfg.delay_mode == "fw"
                                  and resolve_kernel(cfg.delay_kernel))
    rep["waterfill_kernel"] = cfg.waterfill_kernel
    rep["waterfill_kernel_active"] = (cfg.sparse_flows
                                      and resolve_kernel(cfg.waterfill_kernel))
    if csv:
        to_csv(metrics, csv)
    return rep


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--policy", default="all",
                    help=f"one of {list_policies()} or 'all'")
    ap.add_argument("--horizon", type=int, default=120)
    ap.add_argument("--hosts", type=int, default=20,
                    help="fleet size (paper Table 5 mix, scaled)")
    ap.add_argument("--containers", type=int, default=None,
                    help="workload size (containers; jobs/tasks scale along)")
    ap.add_argument("--bw", type=float, default=None, help="link Mbps")
    ap.add_argument("--loss", type=float, default=None,
                    help="link loss fraction")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--workload", default="paper",
                    choices=["paper", "trace"])
    ap.add_argument("--csv", default=None, help="per-tick metrics CSV path "
                    "(stacked mode only — incompatible with --chunk)")
    ap.add_argument("--out", default=None,
                    help="write the summary reports as a JSON list")
    ap.add_argument("--sequential", action="store_true",
                    help="run the sequential reference placement path "
                         "instead of the batched round")
    ap.add_argument("--delay-mode", default="path", choices=["path", "fw"],
                    help="delay refresh: ECMP path sum or full APSP "
                         "(the fw_minplus kernel's algebra)")
    # one run = no grid: the slab/devices/dist ExecPlan flags don't apply
    # (argparse rejects them loudly); --chunk + kernel selectors do
    add_exec_args(ap, slab=False, devices=False, overlap=False)
    ap.add_argument("--weights", default=None,
                    help="by-name weight overrides for the chosen policy, "
                         "e.g. 'cross_leaf=0.5,row_coloc=0.3' "
                         "(types.WEIGHT_NAMES; not valid with --policy all)")
    args = ap.parse_args()
    enable_compile_cache()

    weights = parse_weights(args.weights)
    if weights and args.policy == "all":
        raise SystemExit("--weights needs a single --policy to override")

    wl = ({} if args.containers is None else
          dict(n_containers=args.containers, n_tasks=args.containers,
               n_jobs=max(10, args.containers // 3)))
    cfg = SimConfig(horizon=args.horizon,
                    batched_placement=not args.sequential,
                    delay_mode=args.delay_mode, **wl)
    plan = ExecPlan.from_args(args)
    cfg = plan.apply_to_config(cfg)
    spec, sim0, params = build_once(cfg, bw=args.bw, loss=args.loss,
                                    seed=args.seed, workload=args.workload,
                                    n_hosts=args.hosts)
    policies = list_policies() if args.policy == "all" else [args.policy]
    reports = []
    for p in policies:
        rep = json_clean(run_one(p, cfg, spec, sim0, params, csv=args.csv,
                                 weights=weights, plan=plan))
        reports.append(rep)
        print(json.dumps(rep, indent=None, sort_keys=True))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(reports, f, indent=1)


if __name__ == "__main__":
    main()
