"""Device self milliseconds of the ops under the tick's ``flows`` scope
(the flow allocation: ``seg_waterfill`` or its stand-in, and the gathers
that feed it), summed over the cell's chips, per simulated cell-tick of
the traced window (network)."""
from harness import scopes


def read(run):
    return scopes.ms_per_tick(run, ("flows",))
