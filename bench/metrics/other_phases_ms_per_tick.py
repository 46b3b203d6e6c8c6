"""Device self milliseconds of the ops under the tick's remaining phase
scopes (arrive, communicate, migrate, execute, complete, cost, collect),
summed over the cell's chips, per simulated cell-tick of the traced window
(tick program)."""
from harness import scopes

OTHER = ("arrive", "communicate", "migrate", "execute", "complete", "cost",
         "collect")


def read(run):
    return scopes.ms_per_tick(run, OTHER)
