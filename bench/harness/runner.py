"""One run of one cell: set-up, the measured window, the check, the result.

The window poses questions back to back for ``seconds`` seconds; the
question in flight when the time is up runs to its end and its time
counts.  Nothing may compile inside the window.  After it, the device's
peak memory is read, the program's state is freed, and the answers are
checked against the reference and the semantics.
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
import pathlib
import shutil
import sys
import time

import numpy as np

from harness import check, drivers, peaks, reference, spec, trace


class NoChip(RuntimeError):
    pass


@contextlib.contextmanager
def compilations():
    """Collects the names of compile and compile-cache events while the
    ``with`` body runs."""
    import jax.monitoring as mon
    events = []

    def on_duration(name, _secs, **_kw):
        if "/jax/core/compile" in name or "cache_retrieval" in name:
            events.append(name)

    def on_event(name, **_kw):
        if "compile_requests_use_cache" in name:
            events.append(name)

    mon.register_event_duration_secs_listener(on_duration)
    mon.register_event_listener(on_event)
    try:
        yield events
    finally:
        mon.unregister_event_duration_listener(on_duration)
        mon.unregister_event_listener(on_event)


@dataclasses.dataclass
class TraceView:
    """What a per-layer metric reader gets."""
    trace: trace.Reduced
    cell_ticks: int
    refreshes: int
    shapes: dict
    peaks: dict | None


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def devices_for(cell, require_chip: bool):
    import jax
    devs = jax.devices()
    if require_chip:
        if devs[0].platform != "tpu":
            raise NoChip(f"no TPU found: JAX's devices are "
                         f"{devs[0].platform} ({devs[0].device_kind}); this "
                         f"benchmark runs only on a TPU")
        if len(devs) < cell.chips:
            raise NoChip(f"cell {cell.name} needs {cell.chips} TPU chips, "
                         f"JAX sees {len(devs)}")
    return devs


def run(root: pathlib.Path, workload: str, seed: int, seconds: float,
        traced: bool, t_start: float, require_chip: bool = True,
        hook=None) -> dict:
    """Measure one run; returns the result line as a dict.  ``hook(driver)``
    may replace the driver's entry into the program (the check's tests)."""
    import jax
    cell = spec.load(root, workload)
    devs = devices_for(cell, require_chip)
    dev0 = devs[0]
    drv = drivers.DRIVERS[cell.traffic["driver"]](cell, seed, devs)
    if hook is not None:
        hook(drv)
    drv.warm_up()
    trace_dir = root / ".bench_trace"
    if traced:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir))
    setup_s = time.perf_counter() - t_start

    answers, walls = [], []
    t0 = time.perf_counter()
    with compilations() as compiled, (
            jax.profiler.TraceAnnotation("bench.window") if traced
            else contextlib.nullcontext()):
        while True:
            q0 = time.perf_counter()
            with (jax.profiler.TraceAnnotation("bench.question") if traced
                  else contextlib.nullcontext()):
                answers += drv.question(len(walls))
            walls.append(time.perf_counter() - q0)
            if time.perf_counter() - t0 >= seconds:
                break
    window_s = time.perf_counter() - t0
    if traced:
        jax.profiler.stop_trace()
    n_q = len(walls)
    cell_ticks = n_q * drv.ticks_per_question
    if compiled:
        raise RuntimeError(f"{len(compiled)} compilations inside the "
                           f"measured window: {sorted(set(compiled))}")
    stats = [d.memory_stats() for d in drv.devices] if require_chip else []
    peak = max((int(s["peak_bytes_in_use"]) for s in stats), default=0)
    for q, w in enumerate(walls):
        print(json.dumps({"question": q, "wall_s": w,
                          "cell_ticks": drv.ticks_per_question}), flush=True)
    # what the window holds on the fullest chip as it closes (no bound; the
    # peak above may have been set in set-up)
    print(json.dumps({"bytes_in_use_at_close": max(
        (int(s["bytes_in_use"]) for s in stats), default=0)}), flush=True)
    refreshes = n_q * drv.refreshes_per_question
    shapes = drv.shapes
    drv.release()

    numbers, failed = check_answers(cell, drv, answers, seed)
    correct = check.verdict(numbers, cell.limits)
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": len(answers),
              "failed": failed}
    if traced:
        data = trace.load(trace.find(str(trace_dir)))
        red = trace.reduce(data, window=trace.span(data, "bench.window"))
        view = TraceView(trace=red, cell_ticks=cell_ticks,
                         refreshes=refreshes, shapes=shapes,
                         peaks=peaks.peaks(dev0.device_kind)
                         if require_chip else None)
        metrics = {}
        for m in cell.per_layer:
            v = spec.reader(root, m["name"])(view)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        busy = red.busy_s()
        device.update(busy_s=sum(busy) / len(busy),
                      window_s=red.window_ns * 1e-9)
        result["metrics"] = metrics
        result["breakdown"] = {"device_ops": red.top_ops(),
                               "idle_gaps": red.top_gaps()}
    else:
        values = {"cell_ticks_per_s": cell_ticks / window_s,
                  "setup_s": setup_s}
        result["metrics"] = {m["name"]: {"value": values[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end}
    result["device"] = device
    result["check"] = {k: {"value": numbers[k], "limit": cell.limits[k]}
                       for k in cell.limits}
    for line in check.limit_lines(numbers, cell.limits):
        log(line)
    return result


def check_answers(cell, drv, answers, seed: int):
    """The compared numbers over a window's answers, and the count of
    answers that failed a check of their own."""
    import jax
    fl = drv.fleet
    horizon = int(cell.config["horizon_ticks"])
    inv = [check.invariant_faults(a.out, a.summ, a.conts, fl.cap, horizon)
           for a in answers]
    drift = [check.used_drift(a.out, a.conts, fl.cap) for a in answers]
    n = int(cell.traffic.get("check_sample", 1))
    rng = np.random.default_rng([seed % 2**64, 7])
    sample = sorted(rng.choice(len(answers), size=min(n, len(answers)),
                               replace=False).tolist())
    topo = reference.topology(fl)
    pending = [reference.simulate(
        fl, topo, answers[i].conts, policy=answers[i].policy,
        engine=cell.config["engine"], mode=cell.traffic["delay_mode"],
        horizon=horizon, device=drv.devices[0], fetch=False)
        for i in sample]
    gaps, arrivals = [], 0
    bad = {i for i, v in enumerate(inv) if v}
    for i, (rf, rm) in zip(sample, jax.device_get(pending)):
        a = answers[i]
        r_out, r_sum = check.reference_outcome(rf), check.reference_summary(rm)
        g = check.stat_gaps(check.statistics(a.out, a.summ, a.conts),
                            check.statistics(r_out, r_sum, a.conts))
        worst = max(g, key=g.get)
        log(f"answer {i} ({a.policy}, seed "
            f"{a.seed}): widest stat gap {g[worst]!r} in {worst}")
        gaps.append(g[worst])
        d = check.arrivals_diff(a.summ, r_sum)
        arrivals += d
        if d or g[worst] > cell.limits.get("stat_gap", np.inf):
            bad.add(i)
    numbers = dict(stat_gap=max(gaps), arrivals_diff=arrivals,
                   invariant_faults=int(sum(inv)), used_drift=max(drift))
    return numbers, len(bad)
