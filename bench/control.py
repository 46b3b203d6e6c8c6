"""Run one cell with the control, or a planted fault, in the program's place.

    python bench/control.py --workload <cell> --seed <n> [--hook control]

Drives a whole run (set-up, a short window, the check) with
``harness.faults.HOOKS[--hook]`` put in the program's place, at the cell's
own size on this machine's chips, and prints the run's result line; its
``correct`` has to read false.  The benchmark's own runs never call it.
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
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--hook", default="control")
    args = ap.parse_args()

    from harness import faults, runner
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    try:
        result = runner.run(ROOT, args.workload, args.seed, args.seconds,
                            False, T_START, hook=faults.HOOKS[args.hook])
    except runner.NoChip as e:
        print(f"bench/control.py: {e}", file=sys.stderr)
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
