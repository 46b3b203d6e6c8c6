"""Device self milliseconds of the ops under the ``refresh`` scope (the
periodic delay-matrix rebuild: the path-utilisation gather and the
all-pairs ``fw_minplus``), summed over the cell's chips, per simulated
cell-tick of the traced window (network)."""
from harness import scopes


def read(run):
    return scopes.ms_per_tick(run, ("refresh",))
