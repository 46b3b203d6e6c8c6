"""The control and the planted faults that the check must refuse.

Each is a hook for ``runner.run(..., hook=...)``: it takes the driver and
puts something broken in the program's place, so that a whole run (the
window, the answers, the check) goes through with it.

* ``control``: the plain reference itself, computed in bfloat16 (the
  nearest precision below the float32 the configurations state), answers
  every question.
* ``unchanged``: the compiled step returns its state unchanged.
* ``altered``: one container of each answer is marked completed where the
  program produced it.
"""
from __future__ import annotations

import numpy as np

from harness import check, drivers, inputs, reference


def control(drv, dtype=None):
    import jax.numpy as jnp
    dtype = dtype or jnp.bfloat16
    topo = reference.topology(drv.fleet)
    horizon = int(drv.config["horizon_ticks"])
    mode = drv.traffic["delay_mode"]

    def question(i):
        seed = drv.seeds[i]
        conts = inputs.containers(drv.config, seed)
        rf, rm = reference.simulate(
            drv.fleet, topo, conts, policy=drv.policy_name,
            engine=drv.config["engine"], mode=mode, horizon=horizon,
            dtype=dtype, device=drv.devices[0])
        return [drivers.Answer(
            conts=conts, out=check.reference_outcome(rf),
            summ=check.reference_summary(rm), policy=drv.policy_name,
            seed=seed)]

    drv.warm_up = lambda: question(-1)
    drv.question = question


def unchanged_patch(monkeypatch):
    """Patch the program so that its compiled chunk step returns its carry
    as it got it; apply before the driver builds its step."""
    import repro.core.engine as engine

    def step_of(telescope=False):
        return lambda sim, acc, *a, **k: (sim, acc)

    monkeypatch.setattr(engine, "_chunk_step_jit", step_of)


def _alter(status: np.ndarray) -> np.ndarray:
    status = np.array(status)
    idx = np.flatnonzero((status != inputs.COMPLETED)
                         & (status != inputs.UNBORN))
    if idx.size:
        status[idx[0]] = inputs.COMPLETED
    return status


def altered(drv):
    run = drv.run_sim

    def run_sim(sim0, horizon):
        import jax.numpy as jnp
        final, online = run(sim0, horizon)
        st = jnp.asarray(_alter(np.asarray(final.containers.status)))
        return final._replace(containers=final.containers._replace(
            status=st)), online
    drv.run_sim = run_sim


HOOKS = {"control": control, "altered": altered}
