"""The flow allocation kernel's share of its HBM roofline: the least time
its calls' bytes take at the chip's HBM peak, over the device time of its
``seg_waterfill`` events.  One call per simulated cell-tick."""
from harness.work import seg_waterfill_bytes


def read(run):
    t = run.trace.op_seconds(lambda name: "seg_waterfill" in name)
    if t <= 0:
        return None
    s = run.shapes
    least = run.cell_ticks * seg_waterfill_bytes(s["flows"], s["links"]) \
        / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least / t
