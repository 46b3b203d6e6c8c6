"""Share of the traced window in which the chip is idle while the host is
inside the chunk loop's ``sim.run`` span, averaged over the cell's chips
(host drivers: each chunk's dispatch and its host fold); None where the
trace holds no ``sim.run`` span."""
from harness import scopes


def read(run):
    r = scopes.current()
    if not r.has_run or r.window_ns <= 0:
        return None
    return 100.0 * r.run_idle_ns / r.window_ns
