"""Multi-host sweep fabric: ``jax.distributed`` slab scheduling with
overlapped cross-host reduction (PR 8, ROADMAP item 5).

The single-process sweep already runs policy x scenario x seed as ONE
sharded program (``repro.launch.sweep``); this module scales the SAME
compiled slab-chunk step across processes.  The design is deliberately
*slab-per-process with a host-side reduction*, never a global-SPMD
program:

* every process builds the full grid spec from a JSON ``GridSpec`` (the
  grid is cheap to construct and deterministic), makes a LOCAL mesh over
  ``jax.local_devices()``, and integrates only the wrap-padded slabs it
  owns via ``make_stream_fn(...).iter_slabs`` — there is no cross-process
  collective inside the compiled step, so a straggler host never stalls
  another host's compute;
* slab ownership is DYNAMIC: process 0 runs a tiny TCP ``SlabServer``
  (the coordinator of the issue text) handing out start offsets on
  request, so fast processes take more slabs and a straggler — flagged by
  the rolling-median ``StragglerDetector`` from ``repro.distributed.fault``
  — simply receives fewer (``--handout`` omitted falls back to a static
  round-robin partition for fleets that cannot open the side channel);
* each finished slab is written ATOMICALLY (tmp dir + rename) as a tiny
  checkpoint through ``repro.distributed.checkpoint`` — finals leaves plus
  the slab's f64/i64 ``OnlineSummary`` partial — so a crashed or killed
  run RESUMES by rerunning with the same ``out_dir`` (the coordinator
  skips slabs already on disk; the merge picks them up as resumed);
* the cross-host reduction is ``stats.online_merge`` (Chan's parallel
  combine) over per-process partial ``OnlineSummary``s with disjoint cell
  support.  Merging a cell with an ``n == 0`` partial is an exact identity
  (``nb/nb == 1.0`` in f64; sums add ``+0.0``; peaks max with ``0``), so
  the distributed result is BIT-IDENTICAL to the single-process sweep —
  asserted by ``tests/test_sweep_dist.py`` at 2 processes x 2 forced CPU
  devices.

``jax.distributed.initialize`` is still called by default (workers form a
real distributed system: shared coordination service, global device list)
— the compute simply never depends on it, which is what makes the fabric
testable on a CPU box with ``--xla_force_host_platform_device_count``.

    PYTHONPATH=src python -m repro.launch.dist --policies all --seeds 2 \\
        --horizon 120 --procs 2 --devices-per-proc 2 --chunk 40

Worker mode (what the launcher spawns; on a real fleet, run one per
host — the entry point is ``repro.launch.dist_worker`` because
``jax.distributed.initialize`` must run before this module's imports):

    python -m repro.launch.dist_worker --spec grid_spec.json --out RUN \\
        --process-id 1 --num-processes 4 --coordinator host0:1234 \\
        --handout host0:1235
"""
from __future__ import annotations

import argparse
import collections
import dataclasses
import functools
import json
import os
import pathlib
import re
import shutil
import socket
import subprocess
import sys
import tempfile
import threading
import time
import warnings
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import SimConfig, list_policies, stats
from repro.core.scenario import (ScenarioSpec, build_scenarios,
                                 default_scenarios)
from repro.core.scheduling import validate_weights
from repro.core.types import ExecPlan, OnlineSummary, PolicyParams
from repro.distributed import checkpoint as ckpt
from repro.distributed.fault import FaultConfig, StragglerDetector
from repro.launch.sweep import (SweepResult, _is_static_leaf, make_stream_fn,
                                stack_policies)

_SRC = pathlib.Path(__file__).resolve().parents[2]   # .../src
_SLAB_RE = re.compile(r"slab_(\d{8})$")
_META_RE = re.compile(r"worker_(\d+)\.json$")


def _resolve_dist_plan(plan: ExecPlan | None, cfg: SimConfig,
                       **legacy) -> tuple[ExecPlan, SimConfig]:
    """Dist twin of ``engine.resolve_plan``: same deprecation cycle for
    the bare kwargs, but the no-plan default keeps the fabric's historical
    2-worker spawn (``ExecPlan.procs`` defaults to 1 = in-process, which
    is right for ``run_sim``/``run_sweep`` but would silently turn the
    dist entry points into single-worker runs)."""
    used = {k: v for k, v in legacy.items() if v is not None}
    if used:
        if plan is not None:
            raise TypeError(
                f"pass execution options via plan= OR the deprecated "
                f"kwargs {sorted(used)}, not both")
        warnings.warn(
            f"the {sorted(used)} kwargs are deprecated; pass "
            f"plan=ExecPlan(...) instead", DeprecationWarning, stacklevel=3)
    if plan is None:
        plan = ExecPlan(
            chunk=used.get("chunk"), slab=used.get("slab"),
            overlap=used.get("overlap", True),
            procs=used.get("num_procs", 2),
            devices_per_proc=used.get("devices_per_proc", 1))
    if plan.telescope:
        # the GridSpec worker contract has no telescope field — passing
        # it through would silently run workers per-tick while the caller
        # believes they telescope
        raise ValueError(
            "telescope is not threaded through the multi-process fabric "
            "yet — drop procs (the in-process sweep telescopes) or drop "
            "telescope")
    return plan, plan.apply_to_config(cfg)


def _slab_cells(B: int, slab: int | None, n_dev: int) -> int:
    """The slab plan: ``min(slab, B)`` padded to a device multiple.  Every
    process MUST compute the same value or slab ownership diverges — the
    worker cross-checks its local device count against the spec."""
    Bs = B if slab is None else min(slab, B)
    return Bs + (-Bs) % n_dev


# ---------------------------------------------------------------------------
# GridSpec: the JSON contract between launcher and workers
# ---------------------------------------------------------------------------

_TUPLE_FIELDS = {f.name for f in dataclasses.fields(SimConfig)
                 if isinstance(f.default, tuple)}


@dataclasses.dataclass
class GridSpec:
    """Everything a worker needs to rebuild the grid bit-for-bit: the
    static config, the scenario ladder, seeds, the policy batch (names OR
    a raw weight matrix — tune ships sampled weights), topology sizes and
    the streaming plan.  JSON-serializable; ``SimConfig`` tuple fields are
    restored from JSON lists on load."""

    config: dict
    scenarios: list
    seeds: list
    n_hosts: int
    n_spine: int
    n_leaf: int
    chunk: int
    slab: int | None
    overlap: bool
    devices_per_proc: int
    policies: list | None = None
    weights: list | None = None

    @classmethod
    def build(cls, *, cfg: SimConfig, scenarios: Sequence[ScenarioSpec],
              seeds: Sequence[int], policies: Sequence[str] | None = None,
              weights=None, n_hosts: int, n_spine: int, n_leaf: int,
              chunk: int, slab: int | None, overlap: bool,
              devices_per_proc: int) -> "GridSpec":
        if (policies is None) == (weights is None):
            raise ValueError("exactly one of policies/weights")
        return cls(
            config=dataclasses.asdict(cfg),
            scenarios=[dataclasses.asdict(s) for s in scenarios],
            seeds=[int(s) for s in seeds],
            n_hosts=int(n_hosts), n_spine=int(n_spine), n_leaf=int(n_leaf),
            chunk=int(chunk), slab=None if slab is None else int(slab),
            overlap=bool(overlap), devices_per_proc=int(devices_per_proc),
            policies=None if policies is None else [str(p) for p in policies],
            weights=None if weights is None
            else np.asarray(weights, np.float32).tolist())

    def sim_config(self) -> SimConfig:
        return SimConfig(**{
            k: tuple(v) if k in _TUPLE_FIELDS else v
            for k, v in self.config.items()})

    def scenario_specs(self) -> list[ScenarioSpec]:
        return [ScenarioSpec(**d) for d in self.scenarios]

    def policy_params(self) -> PolicyParams:
        if self.policies is not None:
            return stack_policies(self.policies)
        W = jnp.asarray(np.asarray(self.weights, np.float32))
        validate_weights(W, "dist grid spec weights: ")
        return PolicyParams(weights=W)

    def policy_names(self) -> list[str]:
        if self.policies is not None:
            return list(self.policies)
        return [f"w{i:03d}" for i in range(len(self.weights))]

    @property
    def n_cells(self) -> int:   # P * S * N, no jax needed (coordinator)
        P = len(self.policies if self.policies is not None else self.weights)
        return P * len(self.scenarios) * len(self.seeds)

    def save(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(dataclasses.asdict(self), f, indent=1)

    @classmethod
    def load(cls, path: str) -> "GridSpec":
        with open(path) as f:
            return cls(**json.load(f))


GridBundle = collections.namedtuple(
    "GridBundle", "cfg net_spec sims rps pol scenarios")


def build_grid(spec: GridSpec) -> GridBundle:
    """Spec -> batched simulator inputs.  Deterministic: every process
    (and the merging launcher) reconstructs the identical grid."""
    cfg = spec.sim_config()
    scen = spec.scenario_specs()
    net_spec, sims, rps = build_scenarios(
        scen, cfg, n_hosts=spec.n_hosts, n_spine=spec.n_spine,
        n_leaf=spec.n_leaf, seeds=spec.seeds)
    return GridBundle(cfg, net_spec, sims, rps, spec.policy_params(), scen)


# ---------------------------------------------------------------------------
# Dynamic slab handout: process 0's coordinator + the worker-side queue
# ---------------------------------------------------------------------------

class SlabServer(threading.Thread):
    """Process 0's slab coordinator: a one-line-per-connection TCP queue.

    Protocol: a worker connects and sends ``NEXT <wid>\\n``; the reply is
    a start offset or ``DONE``.  The server measures each worker's
    request cadence (~ one slab period under the overlapped driver) and
    feeds it to the rolling-median ``StragglerDetector`` — a straggler is
    not stalled on, it just wins fewer slabs.  The thread exits once every
    worker has been told DONE (daemon: a crashed worker cannot wedge
    process 0 past ``--server-timeout``)."""

    def __init__(self, addr: tuple[str, int], starts: Sequence[int],
                 n_workers: int, fault_cfg: FaultConfig | None = None):
        super().__init__(daemon=True, name="slab-server")
        self.sock = socket.create_server(addr)
        self.sock.settimeout(0.5)
        self.queue = collections.deque(int(s) for s in starts)
        self.n_workers = n_workers
        self.assigned: dict[int, list[int]] = {}
        self.done: set[int] = set()
        self.detector = StragglerDetector(fault_cfg or FaultConfig())
        self._last_req: dict[int, float] = {}
        self._lock = threading.Lock()

    def _serve_one(self) -> None:
        try:
            conn, _ = self.sock.accept()
        except socket.timeout:
            return
        with conn:
            try:
                parts = conn.recv(4096).decode().split()
                wid = int(parts[1]) if len(parts) >= 2 else -1
            except (ValueError, UnicodeDecodeError, OSError):
                return
            now = time.monotonic()
            with self._lock:
                if wid in self._last_req:
                    self.detector.record(f"proc{wid}",
                                         now - self._last_req[wid])
                self._last_req[wid] = now
                if self.queue:
                    s0 = self.queue.popleft()
                    self.assigned.setdefault(wid, []).append(s0)
                    reply = str(s0)
                else:
                    self.done.add(wid)
                    reply = "DONE"
            try:
                conn.sendall((reply + "\n").encode())
            except OSError:
                pass

    def run(self) -> None:
        while len(self.done) < self.n_workers:
            self._serve_one()
        self.sock.close()

    def report(self) -> dict:
        with self._lock:
            return {
                "handout": "dynamic",
                "assignments": {str(w): list(s)
                                for w, s in sorted(self.assigned.items())},
                "stragglers": self.detector.stragglers(),
                "median_slab_s": round(self.detector.median_step(), 4),
            }


def _request_next(addr: str, wid: int, retry_s: float = 60.0) -> int | None:
    """One handout round-trip; retries while the coordinator comes up."""
    host, port = addr.rsplit(":", 1)
    deadline = time.monotonic() + retry_s
    while True:
        try:
            with socket.create_connection((host, int(port)),
                                          timeout=10.0) as s:
                s.sendall(f"NEXT {wid}\n".encode())
                buf = b""
                while not buf.endswith(b"\n"):
                    got = s.recv(64)
                    if not got:
                        break
                    buf += got
            reply = buf.decode().strip()
            return None if reply == "DONE" else int(reply)
        except (OSError, ValueError):
            if time.monotonic() > deadline:
                raise
            time.sleep(0.2)


def _handout_queue(addr: str, wid: int):
    """Lazy slab-start iterable driven by the coordinator.  Fed straight
    to ``fn.iter_slabs``: under the overlapped driver the next start is
    requested while the previous slab is still integrating on device."""
    while True:
        s0 = _request_next(addr, wid)
        if s0 is None:
            return
        yield s0


# ---------------------------------------------------------------------------
# Worker: integrate owned slabs, checkpoint each one atomically
# ---------------------------------------------------------------------------

def completed_slab_starts(out_dir: str) -> set[int]:
    """Start offsets with a complete slab checkpoint on disk (manifest +
    shard both present — the atomic rename means a dir either exists fully
    or not at all; stray ``.tmp*`` dirs from a crash are ignored)."""
    done = set()
    if not os.path.isdir(out_dir):
        return done
    for name in os.listdir(out_dir):
        m = _SLAB_RE.fullmatch(name)
        if not m:
            continue
        p = os.path.join(out_dir, name)
        if (os.path.exists(os.path.join(p, "manifest.json"))
                and os.path.exists(os.path.join(p, "shard_0.npz"))):
            done.add(int(m.group(1)))
    return done


def _write_slab(out_dir: str, s0: int, real: int, leaves, statics,
                slab_sum: OnlineSummary) -> None:
    final = os.path.join(out_dir, f"slab_{s0:08d}")
    tmp = final + f".tmp{os.getpid()}"
    state = {
        "finals": {f"leaf_{i:03d}": x[:real]
                   for i, x in enumerate(leaves) if i not in statics},
        "summary": {k: v[:real]
                    for k, v in zip(OnlineSummary._fields, slab_sum)},
    }
    ckpt.save_checkpoint(tmp, state, step=s0, process_index=0)
    shutil.rmtree(final, ignore_errors=True)   # stale dir from a dead run
    os.rename(tmp, final)


def _worker_loop(spec: GridSpec, out_dir: str, process_id: int, *,
                 slab_starts=None, handout: str | None = None) -> dict:
    """The per-process slab loop: build the grid, drive the overlapped
    ``iter_slabs`` runner over this process's starts (a coordinator queue
    or an explicit list), checkpoint each slab, write the worker meta."""
    t_start = time.monotonic()
    g = build_grid(spec)
    P = g.pol.weights.shape[0]
    S, N = g.sims.t.shape
    B = P * S * N
    fn = make_stream_fn(g.cfg, g.net_spec.n_hosts, g.net_spec.n_nodes,
                        g.cfg.horizon, chunk=spec.chunk, slab=spec.slab,
                        overlap=spec.overlap)
    Bs = fn.slab_cells(B)
    planned = _slab_cells(B, spec.slab, spec.devices_per_proc)
    if Bs != planned:
        raise RuntimeError(
            f"process {process_id}: {len(jax.local_devices())} local "
            f"device(s) pad the slab to {Bs} cells but the spec planned "
            f"{planned} (devices_per_proc={spec.devices_per_proc}); every "
            "process must pad identically or slab ownership diverges")
    flat_sims = jax.tree_util.tree_flatten_with_path(g.sims)[0]
    statics = {i for i, (p, _) in enumerate(flat_sims)
               if _is_static_leaf(p)}
    starts = (iter(slab_starts) if slab_starts is not None
              else _handout_queue(handout, process_id))
    owned, walls = [], []
    t_prev = time.monotonic()
    for s0, leaves, slab_sum in fn.iter_slabs(g.sims, g.pol, g.rps, starts):
        _write_slab(out_dir, s0, min(Bs, B - s0), leaves, statics, slab_sum)
        owned.append(int(s0))
        now = time.monotonic()
        walls.append(round(now - t_prev, 4))
        t_prev = now
    meta = {
        "process_index": int(process_id),
        "slabs": owned,
        "slab_walls_s": walls,
        "compile_cache_misses": int(fn._cache_size()),
        "n_local_devices": len(jax.local_devices()),
        "backend": jax.default_backend(),
        "wall_s": round(time.monotonic() - t_start, 3),
    }
    path = os.path.join(out_dir, f"worker_{process_id:02d}.json")
    with open(path + ".tmp", "w") as f:
        json.dump(meta, f, indent=1)
    os.replace(path + ".tmp", path)
    return meta


def run_worker_inline(spec: GridSpec, out_dir: str, process_id: int,
                      slab_starts: Sequence[int]) -> dict:
    """One virtual worker in-process — the test hook for uneven-partition
    and resume properties without spawning (same loop the subprocess
    worker runs, minus ``jax.distributed`` and the TCP handout)."""
    os.makedirs(out_dir, exist_ok=True)
    return _worker_loop(spec, out_dir, process_id,
                        slab_starts=list(slab_starts))


# ---------------------------------------------------------------------------
# Merge: cross-host reduction of per-process partials
# ---------------------------------------------------------------------------

def merge_out_dir(spec: GridSpec, out_dir: str, grid: GridBundle | None = None):
    """Reassemble ``(finals, summary, worker_metas)`` from the slab
    checkpoints in ``out_dir``.

    Finals rows are disjoint slices — pure assembly.  Summaries reduce as
    a tree: one [B]-support partial per owner (each worker's slabs, plus a
    synthetic ``resumed`` owner for slabs left by a previous run), folded
    with ``stats.online_merge`` — associative, and exact over disjoint
    support, so the reduction order can never change the result.  Raises
    with the missing-slab list when coverage is incomplete (the resume
    path: rerun with the same ``out_dir``)."""
    g = grid or build_grid(spec)
    jtu = jax.tree_util
    P = g.pol.weights.shape[0]
    S, N = g.sims.t.shape
    B = P * S * N
    Bs = _slab_cells(B, spec.slab, spec.devices_per_proc)
    expected = set(range(0, B, Bs))

    flat_sims, sims_def = jtu.tree_flatten_with_path(g.sims)
    statics = {i for i, (p, _) in enumerate(flat_sims)
               if _is_static_leaf(p)}
    host = [np.asarray(x) for _, x in flat_sims]

    metas = []
    for name in sorted(os.listdir(out_dir)):
        if _META_RE.fullmatch(name):
            with open(os.path.join(out_dir, name)) as f:
                metas.append(json.load(f))
    claimed: dict[int, int] = {}
    for m in metas:
        for s0 in m["slabs"]:
            if s0 in claimed:
                raise RuntimeError(
                    f"slab {s0} claimed by workers {claimed[s0]} and "
                    f"{m['process_index']} — handout protocol violation")
            claimed[s0] = m["process_index"]

    on_disk = completed_slab_starts(out_dir)
    extra = sorted(on_disk - expected)   # diagnose plan mismatch FIRST: a
    if extra:                            # foreign plan also looks 'missing'
        raise RuntimeError(
            f"out_dir holds slabs from a different grid/slab plan "
            f"(e.g. start {extra[:4]}; this grid: B={B}, slab={Bs}); "
            "use a fresh out_dir")
    missing = sorted(expected - on_disk)
    if missing:
        raise RuntimeError(
            f"distributed sweep incomplete: {len(missing)}/{len(expected)} "
            f"slabs missing (first: {missing[:4]}); rerun with the same "
            "out_dir to resume")

    groups: dict = {m["process_index"]: [s for s in m["slabs"]]
                    for m in metas}
    orphans = sorted(on_disk - set(claimed))
    if orphans:
        groups["resumed"] = orphans

    finals_flat = [host[i][0, 0] if i in statics
                   else np.empty((B,) + host[i].shape[2:], host[i].dtype)
                   for i in range(len(host))]
    partials = []
    for _, slabs in sorted(groups.items(), key=lambda kv: str(kv[0])):
        if not slabs:
            continue
        part = stats.online_init((B,))
        for s0 in slabs:
            real = min(Bs, B - s0)
            like = {
                "finals": {f"leaf_{i:03d}":
                           np.empty((real,) + host[i].shape[2:],
                                    host[i].dtype)
                           for i in range(len(host)) if i not in statics},
                "summary": dict(zip(OnlineSummary._fields,
                                    stats.online_init((real,)))),
            }
            state, step = ckpt.restore_checkpoint(
                os.path.join(out_dir, f"slab_{s0:08d}"), like)
            if step != s0:
                raise RuntimeError(
                    f"slab_{s0:08d} manifest says step {step}")
            for i in range(len(host)):
                if i not in statics:
                    finals_flat[i][s0:s0 + real] = \
                        state["finals"][f"leaf_{i:03d}"]
            for j, fname in enumerate(OnlineSummary._fields):
                part[j][s0:s0 + real] = state["summary"][fname]
        partials.append(part)

    summary = (functools.reduce(stats.online_merge, partials)
               if partials else stats.online_init((B,)))
    leaves = [np.broadcast_to(x, (P, S, N) + x.shape).copy()
              if i in statics
              else x.reshape((P, S, N) + x.shape[1:])
              for i, x in enumerate(finals_flat)]
    finals = jtu.tree_unflatten(sims_def, leaves)
    summary = OnlineSummary(*(x.reshape((P, S, N)) for x in summary))
    return finals, summary, metas


# ---------------------------------------------------------------------------
# Launcher: spawn N workers, join, merge
# ---------------------------------------------------------------------------

def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _log_tail(out_dir: str, i: int, lines: int = 30) -> str:
    path = os.path.join(out_dir, f"worker_{i:02d}.log")
    try:
        with open(path, errors="replace") as f:
            tail = f.readlines()[-lines:]
        return f"--- {path} ---\n" + "".join(tail)
    except OSError:
        return f"--- {path}: unreadable ---"


def _check_worker_platform() -> None:
    """Workers run on this process's platform, and only the CPU can be
    shared with them: a process that has touched an accelerator holds it,
    so a worker that needs it would fail or hang.  Refuse up front."""
    backend = jax.default_backend()
    if backend != "cpu":
        raise RuntimeError(
            f"the multi-process sweep fabric cannot run on a {backend} "
            "host: this launcher process already holds the accelerator, so "
            "its workers could not claim it, and running them on the CPU "
            "instead would report CPU results for an accelerator run.  Use "
            "the in-process sweep (repro.launch.sweep, which shards cells "
            "over every local device), or launch with JAX_PLATFORMS=cpu.")


def _spawn_and_wait(spec_path: str, out_dir: str, num_procs: int,
                    devices_per_proc: int, dist_init: bool,
                    timeout_s: float) -> None:
    coord = f"127.0.0.1:{_free_port()}" if dist_init else None
    handout = f"127.0.0.1:{_free_port()}"
    procs = []
    logs = []
    try:
        for i in range(num_procs):
            env = dict(os.environ)
            env["PYTHONPATH"] = (str(_SRC) + os.pathsep
                                 + env.get("PYTHONPATH", ""))
            # the launcher's own platform (_check_worker_platform), with
            # each worker's CPU devices forced to devices_per_proc
            env["JAX_PLATFORMS"] = "cpu"
            flags = re.sub(
                r"--xla_force_host_platform_device_count=\d+", "",
                env.get("XLA_FLAGS", ""))
            env["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count="
                + str(devices_per_proc)).strip()
            cmd = [sys.executable, "-m", "repro.launch.dist_worker",
                   "--spec", spec_path, "--out", out_dir,
                   "--process-id", str(i),
                   "--num-processes", str(num_procs),
                   "--handout", handout]
            cmd += ["--coordinator", coord] if dist_init \
                else ["--no-dist-init"]
            log = open(os.path.join(out_dir, f"worker_{i:02d}.log"), "w")
            logs.append(log)
            procs.append(subprocess.Popen(cmd, env=env, stdout=log,
                                          stderr=subprocess.STDOUT))
        deadline = time.monotonic() + timeout_s
        while True:
            rcs = [p.poll() for p in procs]
            for i, rc in enumerate(rcs):
                if rc not in (None, 0):
                    for q in procs:
                        q.kill()
                    raise RuntimeError(
                        f"worker {i} exited with rc={rc}\n"
                        + _log_tail(out_dir, i))
            if all(rc == 0 for rc in rcs):
                return
            if time.monotonic() > deadline:
                for q in procs:
                    q.kill()
                raise TimeoutError(
                    f"distributed sweep timed out after {timeout_s}s\n"
                    + "\n".join(_log_tail(out_dir, i)
                                for i in range(num_procs)))
            time.sleep(0.1)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        for log in logs:
            log.close()


DistRun = collections.namedtuple("DistRun", "finals summary metas wall_s")


def run_spec(spec: GridSpec, *, num_procs: int, out_dir: str | None = None,
             dist_init: bool = True,
             timeout_s: float = 900.0) -> DistRun:
    """Spawn ``num_procs`` workers over ``spec``, join, merge.  With a
    persistent ``out_dir`` a rerun resumes (completed slabs are skipped by
    the coordinator and merged from disk); the default is a temp dir
    cleaned up after the merge.  Refuses on an accelerator host
    (``_check_worker_platform``)."""
    _check_worker_platform()
    tmp = None
    if out_dir is None:
        tmp = tempfile.TemporaryDirectory(prefix="dist_sweep_")
        out_dir = tmp.name
    try:
        os.makedirs(out_dir, exist_ok=True)
        spec_path = os.path.join(out_dir, "grid_spec.json")
        spec.save(spec_path)
        t0 = time.time()
        _spawn_and_wait(spec_path, out_dir, num_procs,
                        spec.devices_per_proc, dist_init, timeout_s)
        finals, summary, metas = merge_out_dir(spec, out_dir)
        return DistRun(finals, summary, metas, round(time.time() - t0, 2))
    finally:
        if tmp is not None:
            tmp.cleanup()


def make_dist_fn(cfg: SimConfig, scenarios: Sequence[ScenarioSpec],
                 seeds: Sequence[int], *,
                 policies: Sequence[str] | None = None, weights=None,
                 n_hosts: int = 20, n_spine: int = 2, n_leaf: int = 4,
                 num_procs: int | None = None,
                 devices_per_proc: int | None = None,
                 chunk: int | None = None, slab: int | None = None,
                 overlap: bool | None = None,
                 plan: ExecPlan | None = None,
                 out_dir: str | None = None, dist_init: bool = True,
                 timeout_s: float = 900.0):
    """Drop-in sweep callable (``fn(sims, pols, rps) -> (finals,
    summary)`` with ``fn._cache_size``/``fn.n_devices``, like
    ``make_stream_fn``) that runs the grid MULTI-PROCESS.  Execution
    options ride in ``plan`` (``procs`` = worker processes; the bare
    ``num_procs``/``devices_per_proc``/``chunk``/``slab``/``overlap``
    kwargs are deprecated, one cycle).  The spec — not the passed trees —
    is the source of truth: workers rebuild the grid from it, so the call
    only sanity-checks that the caller's batch matches (``launch.tune``
    rides this for ``--procs``)."""
    plan, cfg = _resolve_dist_plan(plan, cfg, num_procs=num_procs,
                                   devices_per_proc=devices_per_proc,
                                   chunk=chunk, slab=slab, overlap=overlap)
    if plan.chunk is None:
        raise ValueError("the dist fabric streams slabs: the plan needs a "
                         "chunk (there is no stacked multi-process path)")
    num_procs = plan.procs
    devices_per_proc = plan.devices_per_proc
    spec = GridSpec.build(cfg=cfg, scenarios=scenarios, seeds=seeds,
                          policies=policies, weights=weights,
                          n_hosts=n_hosts, n_spine=n_spine, n_leaf=n_leaf,
                          chunk=plan.chunk, slab=plan.slab,
                          overlap=plan.overlap,
                          devices_per_proc=devices_per_proc)
    state: dict = {"metas": []}

    def fn(sims, pols, rps):
        P = len(spec.policy_names())
        S, N = len(spec.scenarios), len(spec.seeds)
        if pols.weights.shape[0] != P or sims.t.shape != (S, N):
            raise ValueError(
                f"grid mismatch: spec is [{P},{S},{N}] but got "
                f"P={pols.weights.shape[0]}, (S,N)={tuple(sims.t.shape)}")
        if not np.array_equal(np.asarray(pols.weights, np.float32),
                              np.asarray(spec.policy_params().weights)):
            raise ValueError("policy weights differ from the dist spec — "
                             "workers rebuild the grid from the spec")
        run = run_spec(spec, num_procs=num_procs, out_dir=out_dir,
                       dist_init=dist_init, timeout_s=timeout_s)
        state["metas"] = run.metas
        fn.last_run = run
        return run.finals, run.summary

    fn._cache_size = lambda: max(
        (m["compile_cache_misses"] for m in state["metas"]), default=0)
    fn.n_devices = num_procs * devices_per_proc
    fn.spec = spec
    return fn


def run_dist_sweep(policies: Sequence[str] | None = None,
                   scenarios: Sequence[ScenarioSpec] | None = None,
                   seeds: Sequence[int] = (0,),
                   cfg: SimConfig | None = None, n_hosts: int = 20,
                   n_spine: int = 2, n_leaf: int = 4,
                   num_procs: int | None = None,
                   devices_per_proc: int | None = None,
                   chunk: int | None = None, slab: int | None = None,
                   overlap: bool | None = None,
                   plan: ExecPlan | None = None,
                   out_dir: str | None = None, dist_init: bool = True,
                   timeout_s: float = 900.0) -> SweepResult:
    """The multi-process twin of ``sweep.run_sweep`` — always streaming
    (a missing ``plan.chunk`` defaults to the largest bound-safe chunk).
    Execution options ride in ``plan`` (bare kwargs: one deprecation
    cycle; no plan at all spawns the historical 2 workers).  Returns the
    same ``SweepResult``; ``compile_cache_misses`` is the MAX across
    processes (the per-process compile bill), ``worker_meta`` carries each
    process's slab assignment and walls."""
    policies = list(policies if policies is not None else list_policies())
    scenarios = list(scenarios if scenarios is not None
                     else default_scenarios())
    cfg = cfg or SimConfig()
    plan, cfg = _resolve_dist_plan(plan, cfg, num_procs=num_procs,
                                   devices_per_proc=devices_per_proc,
                                   chunk=chunk, slab=slab, overlap=overlap)
    chunk = plan.chunk
    if chunk is None:
        chunk = min(cfg.horizon, stats.max_chunk_ticks(cfg.n_containers))
    spec = GridSpec.build(cfg=cfg, scenarios=scenarios, seeds=seeds,
                          policies=policies, n_hosts=n_hosts,
                          n_spine=n_spine, n_leaf=n_leaf, chunk=chunk,
                          slab=plan.slab, overlap=plan.overlap,
                          devices_per_proc=plan.devices_per_proc)
    run = run_spec(spec, num_procs=plan.procs, out_dir=out_dir,
                   dist_init=dist_init, timeout_s=timeout_s)
    return SweepResult(
        policies=policies, scenarios=scenarios, seeds=tuple(seeds),
        finals=run.finals, metrics=None, summary=run.summary,
        wall_s=run.wall_s,
        compile_cache_misses=max(
            (m["compile_cache_misses"] for m in run.metas), default=0),
        n_devices=plan.procs * plan.devices_per_proc,
        worker_meta=run.metas)


# ---------------------------------------------------------------------------
# CLI: launcher mode + worker mode
# ---------------------------------------------------------------------------

def worker_run(a) -> None:
    """The worker body, AFTER ``jax.distributed.initialize`` — entered via
    ``repro.launch.dist_worker`` (this module's imports already execute
    jax computations, so the init must happen before they run)."""
    spec = GridSpec.load(a.spec)
    os.makedirs(a.out, exist_ok=True)
    B = spec.n_cells
    Bs = _slab_cells(B, spec.slab, spec.devices_per_proc)
    all_starts = list(range(0, B, Bs))

    server = None
    if a.process_id == 0 and a.handout:
        # coordinator comes up BEFORE the grid build/compile so other
        # workers' first requests never wait on process 0's compile
        # (clients also retry for 60s while it boots)
        done = completed_slab_starts(a.out)
        host, port = a.handout.rsplit(":", 1)
        server = SlabServer((host, int(port)),
                            [s for s in all_starts if s not in done],
                            a.num_processes)
        server.start()

    if a.handout:
        meta = _worker_loop(spec, a.out, a.process_id, handout=a.handout)
    else:
        done = completed_slab_starts(a.out)
        starts = [s for k, s in enumerate(all_starts)
                  if k % a.num_processes == a.process_id and s not in done]
        meta = _worker_loop(spec, a.out, a.process_id, slab_starts=starts)

    if server is not None:
        server.join(timeout=a.server_timeout)
        path = os.path.join(a.out, "coordinator.json")
        with open(path + ".tmp", "w") as f:
            json.dump(server.report(), f, indent=1)
        os.replace(path + ".tmp", path)
    print(f"worker {a.process_id}: {len(meta['slabs'])} slab(s), "
          f"{meta['compile_cache_misses']} compile(s), "
          f"{meta['n_local_devices']} device(s), {meta['wall_s']}s")


def _launcher_main(argv) -> None:
    ap = argparse.ArgumentParser(
        description="multi-process sweep: spawn N slab workers and merge")
    ap.add_argument("--policies", default="all")
    ap.add_argument("--seeds", type=int, default=1)
    ap.add_argument("--horizon", type=int, default=120)
    ap.add_argument("--hosts", type=int, default=20)
    ap.add_argument("--procs", type=int, default=2)
    ap.add_argument("--devices-per-proc", type=int, default=1,
                    help="forced CPU devices per worker process")
    ap.add_argument("--chunk", type=int, default=None)
    ap.add_argument("--slab", type=int, default=None)
    ap.add_argument("--no-overlap", action="store_true")
    ap.add_argument("--no-dist-init", action="store_true")
    ap.add_argument("--out-dir", default=None,
                    help="persistent run dir (enables resume)")
    ap.add_argument("--timeout", type=float, default=900.0)
    ap.add_argument("--table", default="avg_runtime")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    policies = (list_policies() if args.policies == "all"
                else args.policies.split(","))
    cfg = SimConfig(horizon=args.horizon)
    n_leaf = max(4, args.hosts // 5)
    plan = ExecPlan(chunk=args.chunk, slab=args.slab,
                    overlap=not args.no_overlap, procs=args.procs,
                    devices_per_proc=args.devices_per_proc)
    res = run_dist_sweep(
        policies=policies, seeds=range(args.seeds), cfg=cfg,
        n_hosts=args.hosts, n_spine=max(2, n_leaf // 4), n_leaf=n_leaf,
        plan=plan, out_dir=args.out_dir, dist_init=not args.no_dist_init,
        timeout_s=args.timeout)
    cells = len(res.policies) * len(res.scenarios) * len(res.seeds)
    print(f"# {cells} cells over {args.procs} process(es) x "
          f"{args.devices_per_proc} device(s) in {res.wall_s}s, "
          f"<= {res.compile_cache_misses} compile(s)/process")
    print(res.table(args.table))
    if args.out:
        from repro.core.report import json_clean
        with open(args.out, "w") as f:
            json.dump(json_clean(res.summaries()), f, indent=1)
        print(f"# wrote {args.out}")


def main(argv=None) -> None:
    argv = sys.argv[1:] if argv is None else list(argv)
    if "--worker" in argv:
        raise SystemExit(
            "worker mode lives in `python -m repro.launch.dist_worker` — "
            "jax.distributed must initialize before this module imports")
    _launcher_main(argv)


if __name__ == "__main__":
    main()
