"""Share of the traced window in which no operation ran on the chip,
averaged over the cell's chips (host drivers: the chunk loop's per-chunk
sync, the sweep's slab gather and host fold)."""


def read(run):
    busy = run.trace.busy_s()
    window = run.trace.window_ns * 1e-9
    if not busy or window <= 0:
        return None
    return 100.0 * (1.0 - sum(busy) / len(busy) / window)
