"""Worker entry point for the multi-process sweep fabric.

``jax.distributed.initialize`` must run before ANY jax computation, and
importing ``repro.launch.dist`` already executes some (the policy
registry builds device arrays at import) — so this module stays LIGHT:
it parses the worker args and initializes the distributed runtime first,
then imports the fabric and hands over.

    python -m repro.launch.dist_worker --spec grid_spec.json --out RUN \\
        --process-id 1 --num-processes 4 --coordinator host0:1234 \\
        --handout host0:1235
"""
from __future__ import annotations

import argparse
import sys


def parse_args(argv):
    ap = argparse.ArgumentParser("repro.launch.dist_worker")
    ap.add_argument("--spec", required=True,
                    help="GridSpec JSON (see repro.launch.dist)")
    ap.add_argument("--out", required=True, help="shared run directory")
    ap.add_argument("--process-id", type=int, required=True)
    ap.add_argument("--num-processes", type=int, required=True)
    ap.add_argument("--handout", default=None,
                    help="host:port of the slab coordinator (process 0 "
                         "serves it); omitted = static round-robin slabs")
    ap.add_argument("--coordinator", default=None,
                    help="host:port for jax.distributed.initialize")
    ap.add_argument("--no-dist-init", action="store_true",
                    help="skip jax.distributed (pure slab-worker mode)")
    ap.add_argument("--server-timeout", type=float, default=120.0)
    return ap.parse_args(argv)


def main(argv=None) -> None:
    a = parse_args(sys.argv[1:] if argv is None else list(argv))
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if not a.no_dist_init:
        if not a.coordinator:
            raise SystemExit("--coordinator required unless --no-dist-init")
        import jax                       # importing jax computes nothing
        jax.distributed.initialize(a.coordinator, a.num_processes,
                                   a.process_id)
    from repro.launch import dist        # heavy: touches the backend
    dist.worker_run(a)


if __name__ == "__main__":
    main()
