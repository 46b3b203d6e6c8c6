"""Benchmark runner: one entry per paper table/figure (+ roofline feed +
beyond-paper bridge).  Prints ``name,us_per_call,derived`` CSV and dumps
full rows to experiments/bench_rows.json.

    PYTHONPATH=src python -m benchmarks.run [--only NAME]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    from benchmarks import paper_figs
    from benchmarks.bridge_scheduling import bridge_scheduling
    from benchmarks.engine_bench import bench_engine
    from benchmarks.fig11_scalability import (fig11_scalability,
                                              scenario_vmap_throughput)
    from benchmarks.roofline_table import run_table

    benches = {
        "engine_bench": bench_engine,
        "fig4_datacenter": paper_figs.fig4_datacenter,
        "fig5_network": paper_figs.fig5_network,
        "fig6_scheduling": paper_figs.fig6_scheduling,
        "fig7_migration": paper_figs.fig7_migration,
        "fig8_system": paper_figs.fig8_system,
        "fig9_10_variance": paper_figs.fig9_10_variance,
        "fig11_scalability": fig11_scalability,
        "vmap_scenarios": scenario_vmap_throughput,
        "roofline_table": run_table,
        "bridge_scheduling": bridge_scheduling,
    }
    if args.only:
        benches = {args.only: benches[args.only]}

    all_rows = {}
    failed = []
    print("name,us_per_call,derived")
    for name, fn in benches.items():
        t0 = time.time()
        try:
            rows, claims = fn()
            status_bits = []
            for c in claims:
                label, val = c
                status_bits.append(f"{label}={val}")
            derived = "; ".join(status_bits)
        except Exception as e:  # run the other entries, then fail the run
            traceback.print_exc()
            failed.append(name)
            rows, derived = [], f"ERROR {type(e).__name__}: {e}"
        us = (time.time() - t0) * 1e6
        all_rows[name] = rows
        print(f"{name},{us:.0f},{derived!r}")

    out = os.path.join(os.path.dirname(__file__), "..", "experiments",
                       "bench_rows.json")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(all_rows, f, indent=1, default=str)
    print(f"# rows -> {out}")
    if failed:
        sys.exit(f"benchmark entries failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()
