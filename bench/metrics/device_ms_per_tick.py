"""Device busy milliseconds, summed over the cell's chips, per simulated
cell-tick of the traced window (the tick program)."""


def read(run):
    if run.cell_ticks <= 0:
        return None
    return 1e3 * sum(run.trace.busy_s()) / run.cell_ticks
