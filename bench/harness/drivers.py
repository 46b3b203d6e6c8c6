"""How a traffic mix drives the system under test.  A mix names its driver
in its ``driver`` key; a driver builds the compiled program once, in
set-up, and then poses questions back to back.

* ``episodes``: one question is one cell, run through ``run_sim`` with an
  ``ExecPlan(chunk=...)`` (the streamed, donated-carry host loop).

A question's inputs are drawn from a seed that the run's ``--seed`` fixes,
and built inside the window: preparing a question is part of asking it.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from harness import inputs, system


@dataclasses.dataclass
class Answer:
    """One cell's result: what the check reads."""
    conts: dict           # the question's inputs (host numpy)
    out: dict             # final containers and hosts (host numpy)
    summ: dict            # streamed statistics (host numpy)
    policy: str
    seed: int


class _Driver:
    """What every driver builds in set-up: the fleet, the static config, the
    network, the hosts, the question seeds."""

    def __init__(self, cell, seed: int, devices):
        self.config, self.traffic = cell.config, cell.traffic
        self.devices = devices[:cell.chips]
        self.fleet = inputs.fleet(self.config)
        self.cfg = system.sim_config(self.config, self.traffic)
        self.spec, self.net = system.network(self.fleet)
        self.host_state = system.hosts(self.fleet)
        self.horizon = int(self.config["horizon_ticks"])
        self.seeds = inputs.question_seeds(seed, 4096)
        interval = int(self.config["engine"]["delay_update_interval"])
        self.refreshes_per_cell = (
            0 if self.traffic["delay_mode"] != "fw"
            else 1 if interval == 0 else -(-self.horizon // interval))
        self.shapes = dict(hosts=self.fleet.n_hosts, nodes=self.fleet.n_nodes,
                           links=self.fleet.n_links,
                           flows=2 * int(self.config["containers"]))

    def release(self):
        self.net = self.host_state = None


class Episodes(_Driver):
    def __init__(self, cell, seed: int, devices):
        from repro.core import get_policy
        from repro.core.types import ExecPlan
        super().__init__(cell, seed, devices)
        self.policy_name = self.traffic["policy"]
        self.policy = get_policy(self.policy_name)
        self.plan = ExecPlan(chunk=int(self.traffic["chunk"]))
        self.ticks_per_question = self.horizon
        self.refreshes_per_question = self.refreshes_per_cell

    def run_sim(self, sim0, horizon):
        from repro.core import run_sim
        return run_sim(sim0, self.cfg, self.policy, self.spec.n_hosts,
                       self.spec.n_nodes, horizon, plan=self.plan)

    def warm_up(self):
        """Compile (or load) the chunk step and every eager op a question
        uses, on a question of its own, one chunk long."""
        import jax
        conts = inputs.containers(self.config, self.seeds[-1])
        sim0 = system.sim_state(self.host_state, self.net, conts,
                                self.seeds[-1])
        final, online = self.run_sim(sim0, int(self.traffic["chunk"]))
        jax.device_get(system.outcome(final))
        system.summary(online)

    def question(self, i: int) -> list:
        import jax
        seed = self.seeds[i]
        conts = inputs.containers(self.config, seed)
        sim0 = system.sim_state(self.host_state, self.net, conts, seed)
        final, online = self.run_sim(sim0, self.horizon)
        out = jax.device_get(system.outcome(final))
        return [Answer(conts=conts, out=out, summ=system.summary(online),
                       policy=self.policy_name, seed=seed)]


DRIVERS = {"episodes": Episodes}
