"""Device self milliseconds of the ops under the tick's ``schedule`` scope
(admission scan, placement scoring, migration decisions), summed over the
cell's chips, per simulated cell-tick of the traced window (tick
program)."""
from harness import scopes


def read(run):
    return scopes.ms_per_tick(run, ("schedule",))
