"""Inputs of a cell, drawn from its seed: the fleet, the topology sizes and
the containers of each question.

The benchmark keeps its own copy of the generators (the DCSim Table 5
host classes round-robin over the fleet; Table 6 requests; uniform,
exponential or lognormal laws), so a change to the program's generators
cannot move the yardstick.  Everything is host numpy; the drivers hand it
to the program through its public constructors, and the reference reads
the same arrays.
"""
from __future__ import annotations

import dataclasses

import numpy as np

# container status codes (the DCSim container lifecycle, paper Table 2)
UNBORN, INACTIVE, RUNNING, COMMUNICATING, MIGRATING, WAITING, COMPLETED = \
    -1, 0, 1, 2, 3, 4, 5
# divisors of the dominant-resource rule that sets a container's type
TYPE_SCALE = np.array([1700.0, 32.0, 200.0], np.float32)


@dataclasses.dataclass(frozen=True)
class Fleet:
    """Hosts and fabric sizes of one deployment."""
    cap: np.ndarray      # f32[H, 3] cpu (%), mem (GB), gpu (%)
    speed: np.ndarray    # f32[H, 3]
    price: np.ndarray    # f32[H]
    leaf: np.ndarray     # i32[H]
    n_leaf: int
    n_spine: int
    host_leaf_mbps: float
    leaf_spine_mbps: float
    link_delay_ms: float
    link_loss: float

    @property
    def n_hosts(self) -> int:
        return int(self.cap.shape[0])

    @property
    def n_nodes(self) -> int:
        return self.n_hosts + self.n_leaf + self.n_spine

    @property
    def n_links(self) -> int:
        return self.n_hosts + self.n_leaf * self.n_spine


def fleet(config: dict) -> Fleet:
    """The host classes round-robin over ``config['hosts']`` hosts: an equal
    share of each class in class order, the remainder to the first class;
    host ``i`` hangs off leaf ``i % leaves``."""
    H, L = int(config["hosts"]), int(config["leaves"])
    cols = config["host_classes"]["columns"]
    rows = [dict(zip(cols, r)) for r in config["host_classes"]["rows"]]
    per = max(1, H // len(rows))
    counts = [per] * len(rows)
    counts[0] += H - per * len(rows)
    cap, speed, price = [], [], []
    for row, n in zip(rows, counts):
        cap += [[row["cpu_cores"] * 100.0, float(row["mem_gb"]),
                 row["gpus"] * 100.0]] * n
        speed += [[row["cpu_speed"], row["mem_speed"], row["gpu_speed"]]] * n
        price += [row["price"]] * n
    return Fleet(cap=np.asarray(cap, np.float32),
                 speed=np.asarray(speed, np.float32),
                 price=np.asarray(price, np.float32),
                 leaf=(np.arange(H) % L).astype(np.int32),
                 n_leaf=L, n_spine=int(config["spines"]),
                 host_leaf_mbps=float(config["host_leaf_mbps"]),
                 leaf_spine_mbps=float(config["leaf_spine_mbps"]),
                 link_delay_ms=float(config["link_delay_ms"]),
                 link_loss=float(config["link_loss"]))


def containers(config: dict, seed: int) -> dict:
    """One question's containers, drawn from ``seed``.

    Tasks are spread over jobs and containers over tasks (each job and
    task gets at least one); a job's containers arrive together.  The
    ``n``-th communication of a container is due after ``n`` gaps of
    ``duration / (comms + 1)`` work units.
    """
    rng = np.random.default_rng(seed)
    J, T, C = int(config["jobs"]), int(config["tasks"]), \
        int(config["containers"])
    task_job = np.sort(rng.integers(0, J, size=T))
    task_job[:J] = np.arange(J)
    task_job = np.sort(task_job)
    cont_task = np.sort(rng.integers(0, T, size=C))
    cont_task[:T] = np.arange(T)
    cont_task = np.sort(cont_task)
    job = task_job[cont_task].astype(np.int32)

    arr = config["arrivals"]
    if arr["law"] == "uniform":
        job_t = np.sort(rng.uniform(0.0, arr["window_s"], size=J))
    elif arr["law"] == "exponential":
        job_t = np.cumsum(rng.exponential(arr["window_s"] / J, size=J))
    else:
        raise ValueError(f"unknown arrival law {arr['law']!r}")

    rq = config["requests"]
    req = np.stack([rng.uniform(*rq["cpu_pct"], size=C),
                    rng.uniform(*rq["mem_gb"], size=C),
                    rng.uniform(*rq["gpu_pct"], size=C)],
                   axis=1).astype(np.float32)
    ctype = np.argmax(req / TYPE_SCALE[None, :], axis=1).astype(np.int32)

    dur = config["durations"]
    if dur["law"] == "uniform":
        duration = rng.uniform(*dur["range_s"], size=C)
    elif dur["law"] == "lognormal":
        duration = np.clip(rng.lognormal(np.log(dur["median_s"]),
                                         dur["sigma"], size=C),
                           *dur["clip_s"])
    else:
        raise ValueError(f"unknown duration law {dur['law']!r}")
    duration = duration.astype(np.float32)

    cm = config["comms"]
    n_comms = rng.integers(cm["count"][0], cm["count"][1] + 1,
                           size=C).astype(np.int32)
    comm_kb = rng.uniform(*cm["kb"], size=C).astype(np.float32)
    comm_gap = (duration / (n_comms + 1)).astype(np.float32)
    return dict(job=job, task=cont_task.astype(np.int32),
                submit_t=job_t.astype(np.float32)[job], req=req,
                ctype=ctype, duration=duration, n_comms=n_comms,
                comm_kb=comm_kb, comm_gap=comm_gap)


def question_seeds(seed: int, n: int) -> list[int]:
    """``n`` question seeds derived from the run's ``--seed`` (any size)."""
    rng = np.random.default_rng(seed % 2**64)
    return [int(s) for s in rng.integers(0, 2**31 - 1, size=n)]
