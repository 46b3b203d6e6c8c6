"""The all-pairs delay refresh kernel's share of its HBM roofline: the
least time its calls' bytes take at the chip's HBM peak, over the device
time of its ``fw_phase1``/``fw_phase2``/``fw_phase3`` events.  One call
per delay refresh of a cell."""
from harness.work import fw_minplus_bytes


def read(run):
    t = run.trace.op_seconds(lambda name: name.startswith("fw_phase"))
    if t <= 0 or run.refreshes <= 0:
        return None
    least = run.refreshes * fw_minplus_bytes(run.shapes["nodes"]) \
        / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least / t
