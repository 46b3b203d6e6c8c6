"""Record the small scoped trace that ``test_bench_scopes.py`` reads, on a
TPU chip: four ticks of a 20-host cell (``netaware``, the ``fw`` refresh
every two ticks) through the chunk loop in chunks of two ticks, with one
``bench.window`` host span around the run; compiled before the trace.

    python bench/tests/record_small_trace.py <out.xplane.pb.gz>
"""
import glob
import gzip
import pathlib
import sys
import tempfile

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src")]

HOSTS, LEAVES, SPINES, CONTAINERS, TICKS, CHUNK = 20, 4, 1, 60, 4, 2


def main(out: str) -> int:
    import jax
    from repro.core import (SimConfig, get_policy, init_sim, paper_workload,
                            run_sim, scaled_hosts)
    from repro.core.network import SpineLeafSpec, build_network
    from repro.core.types import ExecPlan
    if jax.devices()[0].platform != "tpu":
        print("record_small_trace.py: no TPU found", file=sys.stderr)
        return 3
    cfg = SimConfig(n_jobs=CONTAINERS // 3, n_tasks=CONTAINERS,
                    n_containers=CONTAINERS, horizon=TICKS, delay_mode="fw",
                    delay_update_interval=2)
    spec = SpineLeafSpec(n_spine=SPINES, n_leaf=LEAVES, n_hosts=HOSTS)
    sim0 = init_sim(scaled_hosts(HOSTS, LEAVES), paper_workload(cfg),
                    build_network(spec))
    policy = get_policy("netaware")

    def run():
        return jax.block_until_ready(run_sim(
            sim0, cfg, policy, spec.n_hosts, spec.n_nodes, TICKS,
            plan=ExecPlan(chunk=CHUNK)))

    run()
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        with jax.profiler.TraceAnnotation("bench.window"):
            run()
        jax.profiler.stop_trace()
        (path,) = glob.glob(f"{d}/**/*.xplane.pb", recursive=True)
        pathlib.Path(out).write_bytes(
            gzip.compress(pathlib.Path(path).read_bytes()))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
