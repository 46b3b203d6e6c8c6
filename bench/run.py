"""Chip benchmark of the DCSim container-scheduling simulator.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the chips of this machine: builds
the cell's inputs from ``--seed``, compiles (or loads) and warms up the
program, poses questions back to back for ``--seconds`` seconds, checks
the answers against the plain reference, and prints one JSON line last on
standard output.  ``--trace 0`` reports the end-to-end metrics, ``--trace
1`` profiles the window and reports the per-layer metrics.  Without a TPU,
or with fewer chips than the cell asks for, it exits non-zero and prints
no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "bench"), str(ROOT / "src")]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    from harness import runner
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    try:
        result = runner.run(ROOT, args.workload, args.seed, args.seconds,
                            bool(args.trace), T_START)
    except runner.NoChip as e:
        print(f"bench/run.py: {e}", file=sys.stderr)
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
