"""Sweep driver: policy x scenario x seed in ONE compiled program.

The paper's headline use case is comparing scheduling strategies under
varying network conditions (Figs 4-10).  With policies as weight vectors
and runtime parameters as data (``PolicyParams``/``RunParams``), the whole
evaluation grid is one ``vmap`` over ONE flattened batch axis of P*S*N
cells, jitted exactly once — and that single axis is split across every
available device with a ``shard_map`` (each device integrates its slice of
cells independently; there is no cross-cell communication):

    policies [P] --+
    scenarios [S] --+--> flatten [P*S*N] --vmap--> jit --> [P, S, N]
    seeds     [N] --+         |
                              +-- shard_map over the 'grid' mesh axis

The split is a ``shard_map`` and not a sharding constraint because the
tick's Pallas kernels cannot be partitioned by XLA: each device runs the
vmapped cell program on its own cells, kernels included.

    PYTHONPATH=src python -m repro.launch.sweep --policies all \\
        --seeds 2 --horizon 120 --table avg_runtime --out sweep.json
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import time
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from repro.core import (SimConfig, get_policy, list_policies,
                        sweep_summaries, sweep_table)
from repro.core import stats
from repro.core.engine import (resolve_plan, simulate, simulate_chunk,
                               simulate_telescoped)
from repro.core.scenario import (ScenarioSpec, build_scenarios,
                                 default_scenarios)
from repro.core.scheduling import validate_weights
from repro.core.types import (ExecPlan, OnlineSummary, PolicyParams,
                              RunParams, SimState, TickMetrics)
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.execargs import add_exec_args
from repro.launch.mesh import auto_mesh

I32 = jnp.int32

# SimState leaves that are TOPOLOGY, not state: identical across every
# sweep cell by construction (build_scenarios builds one network and every
# host mix assigns leaves as arange % n_leaf; a ScenarioSpec cannot vary
# topology).  They stay UNBATCHED through the grid vmap (in_axes=None):
# the delay-refresh and ECMP-path gathers then keep unbatched *indices*,
# which XLA:CPU lowers on its fast path — batching the index operand of a
# gather was measured at 2.6x per cell on the periodic refresh alone.
STATIC_TOPOLOGY_LEAVES = frozenset({
    ("hosts", "leaf"),
    ("net", "link_u"), ("net", "link_v"),
    ("net", "path_links"), ("net", "path_nlinks"),
})


def _leaf_path_names(path) -> tuple:
    return tuple(p.name for p in path if hasattr(p, "name"))


def _is_static_leaf(path) -> bool:
    names = _leaf_path_names(path)
    return any(names[-len(s):] == s for s in STATIC_TOPOLOGY_LEAVES)


def stack_policies(names_or_params: Sequence) -> PolicyParams:
    """[P]-batched PolicyParams from registered names (or ready-made
    ``PolicyParams``).  Validates every vector against the canonical weight
    length up front — a ragged batch would fail deep inside a trace."""
    pols = [p if isinstance(p, PolicyParams) else get_policy(p)
            for p in names_or_params]
    for i, p in enumerate(pols):
        validate_weights(p.weights, f"stack_policies entry {i}: ")
    return jax.tree.map(lambda *xs: jnp.stack(xs), *pols)


def grid_mesh(devices=None) -> Mesh | None:
    """1-axis device mesh for the flattened sweep batch.

    ``devices``: None = all addressable devices, an int = that many, or an
    explicit device sequence.  Returns None for a single device — the
    unsharded sweep needs no mesh at all.  Defaults to
    ``jax.local_devices()`` (not ``jax.devices()``): under
    ``jax.distributed`` the global list contains other processes'
    non-addressable devices, and the sweep fabric's cross-host story is
    slab-per-process with a host-side reduction (``repro.launch.dist``),
    never a global-SPMD program.  Built through ``mesh.auto_mesh`` —
    the repo's one mesh constructor.
    """
    if devices is None:
        devices = jax.local_devices()
    elif isinstance(devices, int):
        devices = jax.local_devices()[:devices]
    devices = list(devices)
    if len(devices) <= 1:
        return None
    return auto_mesh((len(devices),), ("grid",), devices=devices)


def _per_device(fn, mesh, sim_tree, out_sims: bool):
    """Run the vmapped cell program ``fn(sims, cell_args)`` on each
    device's slice of the flattened cell axis (``shard_map``), topology
    leaves replicated.  ``out_sims`` says whether the first output is a
    ``SimState`` whose topology leaves stay unbatched.  ``mesh=None`` (one
    device) returns ``fn`` unchanged."""
    if mesh is None:
        return fn
    cells = PartitionSpec("grid")
    flat, treedef = jax.tree_util.tree_flatten_with_path(sim_tree)
    sim_specs = jax.tree_util.tree_unflatten(
        treedef, [PartitionSpec() if _is_static_leaf(p) else cells
                  for p, _ in flat])
    out_specs = (sim_specs, cells) if out_sims else cells
    return jax.shard_map(fn, mesh=mesh, in_specs=(sim_specs, cells),
                         out_specs=out_specs, check_vma=False)


def make_sweep_fn(cfg: SimConfig, n_hosts: int, n_nodes: int, horizon: int,
                  devices=None):
    """The compiled sweep: (sims [S,N], policies [P], params [S]) ->
    (finals, metrics) with [P, S, N] leading axes.

    One jit over the SAME ``engine.simulate`` trace standalone ``run_sim``
    jits — so each cell is bit-for-bit a standalone run, and the whole grid
    costs exactly one XLA compilation (asserted in ``tests/test_sweep.py``
    via the jit cache-miss counter).

    The grid rides ONE ``vmap``: the three axes are broadcast and
    flattened to a single [P*S*N] batch inside the jitted function
    (branch-free scoring makes the policy axis pure data like the others —
    no ``lax.switch`` evaluating every branch per cell).  With more than
    one device the flattened axis is split over the 1-axis ``grid`` mesh
    (``shard_map``), padded to a device multiple by repeating cells
    (the pad cells are sliced off before reshaping back to [P, S, N]);
    cells are independent, so sharded == unsharded bit-for-bit
    (``tests/test_sweep_sharded.py``).
    """
    mesh = grid_mesh(devices)
    n_dev = 1 if mesh is None else mesh.devices.size
    grid = _make_grid(cfg, n_hosts, n_nodes, horizon, mesh, n_dev)
    jitted = jax.jit(grid)

    def fn(sims, pols, rps):
        _check_topology_uniform(sims)
        return jitted(sims, pols, rps)

    fn._cache_size = jitted._cache_size
    fn.n_devices = n_dev
    return fn


def _make_grid(cfg: SimConfig, n_hosts: int, n_nodes: int, horizon: int,
               mesh, n_dev: int):
    """The un-jitted [P, S, N]-grid function both ``make_sweep_fn`` (jit)
    and ``make_grad_fn`` (jit of ``value_and_grad`` through it) trace —
    one definition, so the differentiated sweep IS the stacked sweep."""
    jtu = jax.tree_util

    def cell(sim: SimState, pol: PolicyParams, rp: RunParams):
        return simulate(sim, cfg, pol, n_hosts, n_nodes, horizon, rp)

    def grid(sims, pols, rps):
        P = pols.weights.shape[0]
        S, N = sims.t.shape
        B = P * S * N

        def flat(x, bshape):                     # bshape -> [B, ...]
            shape = (P, S, N) + x.shape[len(bshape):]
            x = x.reshape(tuple(d if ax in bshape else 1
                                for ax, d in zip("PSN", (P, S, N)))
                          + x.shape[len(bshape):])
            return jnp.broadcast_to(x, shape).reshape((B,) + shape[3:])

        args = (jax.tree.map(lambda x: flat(x, "SN"), sims),
                jax.tree.map(lambda x: flat(x, "P"), pols),
                jax.tree.map(lambda x: flat(x, "S"), rps))
        # Pad to a device multiple by repeating cells round-robin.  The pad
        # cells RECOMPUTE real cells and their results are sliced off —
        # deliberate waste: under vmap+SPMD every lane executes the same
        # ops regardless of data, so "masking" a pad cell's workload to
        # near-zero saves nothing, while zeroed/degenerate states would
        # fork the tick's branches.  The measured cost is the pad fraction
        # itself (<= (n_dev-1)/B of the grid; numbers in docs/sweeps.md).
        pad = (-B) % n_dev
        if pad:
            idx = jnp.arange(B + pad) % B
            args = jax.tree.map(lambda x: x[idx], args)
        # de-batch the topology leaves (every cell carries the same
        # tables; uniformity is checked host-side in fn below) and build
        # the matching in_axes tree: 0 everywhere, None at the statics.
        flat_sims, treedef = jtu.tree_flatten_with_path(args[0])
        sim_arg = jtu.tree_unflatten(
            treedef, [x[0] if _is_static_leaf(p) else x
                      for p, x in flat_sims])
        sim_axes = jtu.tree_unflatten(
            treedef, [None if _is_static_leaf(p) else 0
                      for p, x in flat_sims])
        run = jax.vmap(lambda s, rest: cell(s, *rest),
                       in_axes=(sim_axes, (0, 0)))
        out = _per_device(run, mesh, sim_arg, out_sims=False)(
            sim_arg, (args[1], args[2]))
        if pad:
            out = jax.tree.map(lambda x: x[:B], out)
        return jax.tree.map(
            lambda x: x.reshape((P, S, N) + x.shape[1:]), out)

    return grid


def _check_topology_uniform(sims) -> None:
    """Every cell of one grid must share the network topology — the static
    leaves are de-batched through the vmap (``STATIC_TOPOLOGY_LEAVES``)."""
    for p, x in jax.tree_util.tree_flatten_with_path(sims)[0]:
        if _is_static_leaf(p):
            x = np.asarray(x)
            ref = x.reshape((-1,) + x.shape[2:])[0]
            if not (x == ref).all():
                names = ".".join(_leaf_path_names(p))
                raise ValueError(
                    f"sweep cells disagree on topology leaf {names!r}; "
                    "all scenarios of one grid must share the network "
                    "topology (build_scenarios builds exactly one)")


def make_grad_fn(cfg: SimConfig, n_hosts: int, n_nodes: int, horizon: int,
                 objective: str = "soft_blend", chunk: int | None = None,
                 devices=None):
    """The differentiated sweep: ``fn(sims, pols, rps) -> (obj [P],
    grad [P, NUM_POLICY_WEIGHTS])`` — the per-policy mean surrogate
    objective over the [S, N] scenario/seed cells, and its gradient in
    ``PolicyParams.weights`` (docs/autodiff.md).

    Requires ``cfg.soft_placement``: the objective is the softmax
    expected-cost surrogate accumulated by the soft admit/migration
    rounds (``stats.soft_objective``); the simulated dynamics stay the
    hard argmin, so gradients flow through the per-decision score rows.
    Almost every state-mediated path crosses an integer decision and
    carries exact zero cotangent — the one exception is the periodic
    delay refresh, which bakes ``weights[util]``/``weights[cross_leaf]``
    into the persistent ``net.comm_cost`` cache (a continuous w -> state
    path, docs/autodiff.md).

    ``chunk=None`` differentiates the SAME grid function ``make_sweep_fn``
    jits — one ``jax.jit(value_and_grad(...))`` over the whole stacked
    grid, weights riding the policy batch axis, sharded over ``devices``
    exactly like the forward sweep.  A ``chunk`` streams the horizon
    instead (the ``make_stream_fn`` regime): a host loop drives ONE jitted
    ``value_and_grad`` chunk step (+ one tail compile when ``chunk`` does
    not divide ``horizon`` — never more, asserted in
    ``tests/test_autodiff.py``) whose value is the chunk's surrogate
    NUMERATOR sum; per-cell numerator gradients are summed host-side in
    f64 and scaled by the final count denominator (piecewise-constant in
    the weights, so this is the exact objective gradient), memory
    O(cells x state) at any horizon.  Values match the stacked path at
    any chunk size; gradients match to f32 summation order EXCEPT the
    comm_cost-carried ``util``/``cross_leaf`` components, which are
    truncated-BPTT at chunk boundaries that land while decisions are
    still being made (boundaries past the admit window see no truncation
    — pinned exactly in ``tests/test_autodiff.py``).
    """
    if not cfg.soft_placement:
        raise ValueError(
            "make_grad_fn requires cfg.soft_placement=True — with it off "
            "the surrogate sums are constant 0.0 and every gradient "
            "vanishes identically")
    if objective not in stats.SOFT_OBJECTIVES:
        raise KeyError(f"unknown soft objective {objective!r}; known: "
                       f"{list(stats.SOFT_OBJECTIVES)}")
    mesh = grid_mesh(devices)
    n_dev = 1 if mesh is None else mesh.devices.size
    jtu = jax.tree_util

    if chunk is None:
        grid = _make_grid(cfg, n_hosts, n_nodes, horizon, mesh, n_dev)

        def value(w, sims, rps):
            _, metrics = grid(sims, PolicyParams(weights=w), rps)
            num, den = stats.soft_num_den(metrics, objective)
            per_pol = (num / jnp.maximum(den, 1.0)).mean(axis=(1, 2))
            # policies are independent cells: d(sum)/dw is the [P, W]
            # per-policy gradient stack, no cross terms
            return per_pol.sum(), per_pol

        vg = jax.jit(jax.value_and_grad(value, has_aux=True))

        def fn(sims, pols, rps):
            _check_topology_uniform(sims)
            (_, per_pol), g = vg(pols.weights, sims, rps)
            return per_pol, g

        fn._cache_size = vg._cache_size
        fn.n_devices = n_dev
        return fn

    stats.check_chunk(chunk, cfg.n_containers)

    def gstep(w, sims, accs, rps, t0, csz):
        flat, treedef = jtu.tree_flatten_with_path(sims)
        sim_axes = jtu.tree_unflatten(
            treedef, [None if _is_static_leaf(p) else 0 for p, _ in flat])

        def chunk_num(w):
            def cell(sim, acc, pol, rp):
                return simulate_chunk(sim, acc, t0, cfg, pol, n_hosts,
                                      n_nodes, csz, rp)
            sims2, accs2 = jax.vmap(
                cell, in_axes=(sim_axes, 0, 0, 0),
                out_axes=(sim_axes, 0))(sims, accs,
                                        PolicyParams(weights=w), rps)
            num, _ = stats.soft_num_den(accs2, objective)   # [B]
            return num.sum(), (sims2, accs2)

        (_, (sims2, accs2)), g = jax.value_and_grad(
            chunk_num, has_aux=True)(w)
        return sims2, accs2, g

    jstep = jax.jit(gstep, static_argnames=("csz",))

    def fn(sims, pols, rps):
        _check_topology_uniform(sims)
        P, W = pols.weights.shape
        S, N = sims.t.shape
        B = P * S * N
        idx = np.arange(B)
        p_i, s_i, n_i = idx // (S * N), (idx // N) % S, idx % N
        flat_sims, sims_def = jtu.tree_flatten_with_path(sims)
        sim_flat = jtu.tree_unflatten(
            sims_def, [x[0, 0] if _is_static_leaf(p) else x[s_i, n_i]
                       for p, x in flat_sims])
        w = pols.weights[p_i]                               # [B, W]
        rp_flat = jax.tree.map(lambda x: x[s_i], rps)
        online = stats.online_init((B,))
        gnum = np.zeros((B, W), np.float64)
        t0 = 0
        while t0 < horizon:
            sz = min(chunk, horizon - t0)
            accs = jax.tree.map(lambda x: jnp.zeros((B,), x.dtype),
                                stats.acc_init())
            sim_flat, accs, g = jstep(w, sim_flat, accs, rp_flat,
                                      jnp.asarray(t0, I32), csz=sz)
            online = stats.online_fold(online, accs)
            gnum += np.asarray(g, np.float64)
            t0 += sz
        num, den = stats.soft_num_den(online, objective)
        den = np.maximum(den, 1.0)
        obj = (num / den).reshape(P, S * N)
        gobj = (gnum / den[:, None]).reshape(P, S * N, W)
        return (jnp.asarray(obj.mean(axis=1), jnp.float32),
                jnp.asarray(gobj.mean(axis=1), jnp.float32))

    fn._cache_size = jstep._cache_size
    fn.n_devices = 1          # chunked grads run unsharded (single process)
    return fn


def make_stream_fn(cfg: SimConfig, n_hosts: int, n_nodes: int, horizon: int,
                   chunk: int, slab: int | None = None, devices=None,
                   overlap: bool = True, telescope: bool = False):
    """The streaming sweep: the same [P, S, N] grid as ``make_sweep_fn``,
    but iterated in device-multiple SLABS of cells through ONE compiled
    slab-chunk step, with per-tick metrics folded into ``SummaryAcc``
    carries instead of stacked — so peak memory is O(slab x state), never
    O(cells x horizon).

    Returns ``fn(sims, pols, rps) -> (finals, summary)`` where ``finals``
    has [P, S, N] leading axes (numpy; bit-for-bit the stacked sweep's
    finals) and ``summary`` is a [P, S, N] ``stats.OnlineSummary``.

    Chunking the horizon and slabbing the grid compose in one loop nest:

        for each slab of cells:                # wrap-padded start offsets
            enqueue every chunk step           # ONE jitted function, async
            gather the PREVIOUS slab's finals + accs   # one device_get
            fold its accs into the host f64/i64 summary

    The jitted step is compiled once for the main chunk size (+ one tail
    compile when ``chunk`` does not divide ``horizon``): ``t0`` is traced,
    the per-cell link-param application rides a ``t0 == 0`` cond, and the
    static topology leaves stay unbatched through the vmap in BOTH
    directions (``in_axes``/``out_axes`` None) so every slab re-enters the
    same compiled program.  The (state, accumulator) carry is donated, so
    a slab's device footprint never doubles.

    The driver is OVERLAPPED (PR 8): jax dispatch is asynchronous, so the
    loop never blocks between chunks — per-chunk accumulators are kept as
    device arrays and the whole slab (every finals leaf + every chunk's
    ``SummaryAcc``) comes back in ONE batched ``jax.device_get``, issued
    only after the NEXT slab's steps are already enqueued
    (``overlap=True``).  The host-side fold and slice-write of slab *k*
    then runs while the device integrates slab *k+1*; peak footprint is
    two slabs (the in-flight one plus the one being gathered).
    ``overlap=False`` keeps the gather synchronous (slab *k* is fetched
    before slab *k+1* is touched) — the PR 7 behavior, minus its per-leaf
    ``np.asarray`` and per-chunk host-fold stalls, kept as the bench
    comparison arm.

    ``fn.iter_slabs(sims, pols, rps, slab_starts)`` exposes the runner
    itself — a generator of ``(s0, finals_leaves, slab_summary)`` per
    start offset — so the distributed launcher (``repro.launch.dist``)
    can drive the SAME compiled step from a coordinator-fed slab queue
    instead of ``range(0, B, Bs)``.
    """
    stats.check_chunk(chunk, cfg.n_containers)
    mesh = grid_mesh(devices)
    n_dev = 1 if mesh is None else mesh.devices.size
    jtu = jax.tree_util
    # the telescoped cell is signature-identical to simulate_chunk — the
    # macro-tick engine slots into the SAME slab/chunk/overlap machinery,
    # each vmapped lane telescoping independently (per-cell dt; the inner
    # while_loop runs until every lane's horizon, select-masked per lane)
    cell_fn = simulate_telescoped if telescope else simulate_chunk

    def step(sims, accs, pols, rps, t0, csz):
        def cell(sim, rest):
            acc, pol, rp = rest
            return cell_fn(sim, acc, t0, cfg, pol, n_hosts, n_nodes,
                           csz, rp)

        flat, treedef = jtu.tree_flatten_with_path(sims)
        sim_axes = jtu.tree_unflatten(
            treedef, [None if _is_static_leaf(p) else 0 for p, _ in flat])
        run = jax.vmap(cell, in_axes=(sim_axes, 0), out_axes=(sim_axes, 0))
        return _per_device(run, mesh, sims, out_sims=True)(
            sims, (accs, pols, rps))

    jstep = jax.jit(step, static_argnames=("csz",), donate_argnums=(0, 1))

    def slab_cells(B: int) -> int:
        """Wrap-padded device-multiple slab size for a B-cell grid."""
        Bs = B if slab is None else min(slab, B)
        return Bs + (-Bs) % n_dev

    def iter_slabs(sims, pols, rps, slab_starts):
        """Run the wrap-padded slab at each start offset; yield
        ``(s0, finals_leaves, slab_summary)`` — finals as host numpy per
        flattened ``SimState`` leaf (statics de-batched), summary a [Bs]
        ``OnlineSummary``.  ``slab_starts`` may be any iterable (a lazy
        coordinator queue included); each start owns cells
        ``(s0 + arange(Bs)) % B`` of which the first ``min(Bs, B - s0)``
        are real."""
        _check_topology_uniform(sims)
        P = pols.weights.shape[0]
        S, N = sims.t.shape
        B = P * S * N
        Bs = slab_cells(B)
        flat_sims, sims_def = jtu.tree_flatten_with_path(sims)
        statics = {i for i, (p, _) in enumerate(flat_sims)
                   if _is_static_leaf(p)}
        if mesh is not None:
            # pre-place slab inputs in their final layout: the FIRST jstep
            # call then compiles for grid-sharded carries, the same
            # signature every later chunk re-enters — without this the
            # unsharded first call costs a third compilation per process
            gspec = NamedSharding(mesh, PartitionSpec("grid"))
            repl = NamedSharding(mesh, PartitionSpec())
            place = lambda x, s: jax.device_put(x, s)
        else:
            gspec = repl = None
            place = lambda x, s: x
        zero_accs = lambda: jax.tree.map(
            lambda x: place(jnp.zeros((Bs,), x.dtype), gspec),
            stats.acc_init())

        def enqueue(s0):
            idx = (s0 + np.arange(Bs)) % B       # wrap-pad the last slab
            p_i, s_i, n_i = idx // (S * N), (idx // N) % S, idx % N
            sim_slab = jtu.tree_unflatten(
                sims_def, [place(x[0, 0], repl) if i in statics
                           else place(x[s_i, n_i], gspec)
                           for i, (_, x) in enumerate(flat_sims)])
            pol_slab = jax.tree.map(lambda x: place(x[p_i], gspec), pols)
            rp_slab = jax.tree.map(lambda x: place(x[s_i], gspec), rps)
            accs = []
            t0 = 0
            while t0 < horizon:
                sz = min(chunk, horizon - t0)    # tail: one extra compile
                # the accumulator RESETS every chunk (the i32 bound and the
                # f32 precision argument are per-chunk properties); the
                # host fold in finish() carries the running 64-bit totals
                sim_slab, acc = jstep(sim_slab, zero_accs(), pol_slab,
                                      rp_slab, jnp.asarray(t0, I32),
                                      csz=sz)
                accs.append(acc)
                t0 += sz
            return s0, sim_slab, accs

        def finish(pend):
            s0, sim_slab, accs = pend
            # ONE host transfer for the whole slab: every finals leaf and
            # every chunk's SummaryAcc in a single batched device_get
            # (PR 7 issued one blocking np.asarray per leaf per slab plus
            # one per-chunk sync inside the fold loop)
            host_leaves, host_accs = jax.device_get(
                (jtu.tree_leaves(sim_slab), accs))
            slab_sum = stats.online_init((Bs,))
            for a in host_accs:
                slab_sum = stats.online_fold(slab_sum, a)
            return s0, host_leaves, slab_sum

        pending = None
        for s0 in slab_starts:
            cur = enqueue(s0)                    # async: nothing blocks yet
            if not overlap:
                yield finish(cur)
                continue
            if pending is not None:              # gather k AFTER k+1 is in
                yield finish(pending)
            pending = cur
        if pending is not None:
            yield finish(pending)

    def fn(sims, pols, rps):
        P = pols.weights.shape[0]
        S, N = sims.t.shape
        B = P * S * N
        Bs = slab_cells(B)
        flat_sims, sims_def = jtu.tree_flatten_with_path(sims)
        statics = {i for i, (p, _) in enumerate(flat_sims)
                   if _is_static_leaf(p)}
        summary = stats.online_init((B,))
        finals_flat = None                       # host [B, ...] per leaf
        for s0, host_slab, slab_sum in iter_slabs(sims, pols, rps,
                                                  range(0, B, Bs)):
            real = min(Bs, B - s0)               # wrap rows are duplicates
            if finals_flat is None:
                finals_flat = [
                    x if i in statics
                    else np.empty((B,) + x.shape[1:], x.dtype)
                    for i, x in enumerate(host_slab)]
            for i, x in enumerate(host_slab):
                if i not in statics:
                    finals_flat[i][s0:s0 + real] = x[:real]
            for h, a in zip(summary, slab_sum):
                h[s0:s0 + real] = a[:real]

        leaves = [np.broadcast_to(x, (P, S, N) + x.shape).copy()
                  if i in statics               # restore the batched shape
                  else x.reshape((P, S, N) + x.shape[1:])
                  for i, x in enumerate(finals_flat)]
        finals = jtu.tree_unflatten(sims_def, leaves)
        summary = OnlineSummary(*(x.reshape((P, S, N)) for x in summary))
        return finals, summary

    fn._cache_size = jstep._cache_size
    fn.n_devices = n_dev
    fn.iter_slabs = iter_slabs
    fn.slab_cells = slab_cells
    return fn


@dataclasses.dataclass
class SweepResult:
    policies: list[str]
    scenarios: list[ScenarioSpec]
    seeds: tuple[int, ...]
    finals: SimState          # [P, S, N, ...]
    metrics: TickMetrics | None   # [P, S, N, T, ...]; None when streamed
    wall_s: float
    compile_cache_misses: int  # jit cache entries the sweep call created
    n_devices: int = 1         # devices the flattened grid axis spans
    summary: OnlineSummary | None = None  # [P, S, N] streaming fold
    worker_meta: list | None = None  # per-process slabs/walls (launch.dist)
    _rows: list | None = dataclasses.field(default=None, repr=False)

    def summaries(self) -> list[dict[str, Any]]:
        if self._rows is None:  # per-cell summarize is host-side O(cells)
            self._rows = sweep_summaries(
                self.finals,
                self.metrics if self.metrics is not None else self.summary,
                self.policies,
                [s.name for s in self.scenarios], self.seeds)
        return self._rows

    def table(self, value: str = "avg_runtime") -> str:
        return sweep_table(self.summaries(), value=value)


def run_sweep(policies: Sequence[str] | None = None,
              scenarios: Sequence[ScenarioSpec] | None = None,
              seeds: Sequence[int] = (0,), cfg: SimConfig | None = None,
              n_hosts: int = 20, n_spine: int = 2,
              n_leaf: int = 4, devices=None, chunk: int | None = None,
              slab: int | None = None, overlap: bool | None = None,
              plan: ExecPlan | None = None) -> SweepResult:
    """Build the grid and run it as one compiled call.

    Execution options ride in ``plan`` (:class:`~repro.core.types.ExecPlan`
    — the bare ``devices``/``chunk``/``slab``/``overlap`` kwargs are
    deprecated, one cycle).  ``plan.devices`` shards the flattened grid
    (default: every local device).  A ``plan.chunk`` switches to the
    STREAMING sweep (``make_stream_fn``): the horizon runs in chunks with
    online summary folds and the grid is iterated in slabs of
    ``plan.slab`` cells (default: the whole grid) through one compiled
    step — [P, S, N] summaries without ever holding [P, S, N, T] metrics.
    Cell results are bit-identical either way.  ``plan.overlap``
    (streaming only) gathers each slab's results one slab behind the
    dispatch so host transfers hide under device compute.
    ``plan.telescope`` swaps the streaming cell for the macro-tick engine
    (``engine.simulate_telescoped``, docs/events.md): each lane advances
    dt >= 1 ticks per step over quiescent intervals with closed-form
    summary folds — finals stay bit-identical, summaries exact to the
    documented fold precision; without a ``plan.chunk`` the whole horizon
    runs as one chunk.  The plan's kernel selectors fold into ``cfg``
    before compilation.
    """
    policies = list(policies if policies is not None else list_policies())
    scenarios = list(scenarios if scenarios is not None
                     else default_scenarios())
    cfg = cfg or SimConfig()
    plan, cfg = resolve_plan(plan, cfg, devices=devices, chunk=chunk,
                             slab=slab, overlap=overlap)
    net_spec, sims, rps = build_scenarios(scenarios, cfg, n_hosts=n_hosts,
                                          n_spine=n_spine, n_leaf=n_leaf,
                                          seeds=seeds)
    pol = stack_policies(policies)
    if plan.chunk is not None or plan.telescope:
        # telescoping rides the streaming path (there is no per-tick series
        # to stack); without an explicit chunk the whole horizon is one
        # macro-stepped chunk
        fn = make_stream_fn(cfg, net_spec.n_hosts, net_spec.n_nodes,
                            cfg.horizon, chunk=plan.chunk or cfg.horizon,
                            slab=plan.slab, devices=plan.devices,
                            overlap=plan.overlap, telescope=plan.telescope)
        t0 = time.time()
        finals, summary = fn(sims, pol, rps)
        return SweepResult(policies=policies, scenarios=scenarios,
                           seeds=tuple(seeds), finals=finals, metrics=None,
                           summary=summary,
                           wall_s=round(time.time() - t0, 2),
                           compile_cache_misses=fn._cache_size(),
                           n_devices=fn.n_devices)
    fn = make_sweep_fn(cfg, net_spec.n_hosts, net_spec.n_nodes, cfg.horizon,
                       devices=plan.devices)
    t0 = time.time()
    finals, metrics = fn(sims, pol, rps)
    jax.tree.leaves(finals)[0].block_until_ready()
    return SweepResult(policies=policies, scenarios=scenarios,
                       seeds=tuple(seeds), finals=finals, metrics=metrics,
                       wall_s=round(time.time() - t0, 2),
                       compile_cache_misses=fn._cache_size(),
                       n_devices=fn.n_devices)


@functools.partial(jax.jit, static_argnames=("cfg", "n_hosts", "n_nodes",
                                             "horizon"))
def _run_sim_vmapped_jit(sims, cfg, policy, params, n_hosts, n_nodes,
                         horizon):
    return jax.vmap(lambda s: simulate(s, cfg, policy, n_hosts, n_nodes,
                                       horizon, params))(sims)


@functools.lru_cache(maxsize=None)
def _vmapped_chunk_step_jit(telescope: bool = False):
    """Jitted seed-batched chunk step with a donated carry (like
    ``engine._chunk_step_jit``)."""
    fn = simulate_telescoped if telescope else simulate_chunk

    def step(sims, accs, t0, policy, params, cfg, n_hosts, n_nodes, chunk):
        return jax.vmap(
            lambda s, a: fn(s, a, t0, cfg, policy, n_hosts,
                            n_nodes, chunk, params))(sims, accs)
    return jax.jit(step, static_argnames=("cfg", "n_hosts", "n_nodes",
                                          "chunk"),
                   donate_argnums=(0, 1))


def run_sim_vmapped(sims: SimState, cfg: SimConfig, policy: PolicyParams,
                    n_hosts: int, n_nodes: int, horizon: int,
                    params: RunParams | None = None,
                    chunk: int | None = None, telescope: bool = False):
    """Seed-batched single-policy run (leading axis on every SimState leaf)
    — the degenerate 1x1xN sweep, kept as a convenience for benchmarks.
    Jitted at module level so repeat calls hit the warm cache (keyed on
    config/shapes, like ``run_sim``; policies are data, never cache keys).

    ``chunk`` streams the batch through per-chunk steps with online
    summary folds — (finals, [N] ``OnlineSummary``) instead of
    (finals, [N, T] stacked metrics), O(batch x state) memory at any
    horizon.  ``t0`` stays unbatched through the vmap, so the periodic
    delay-refresh cond survives exactly as in the stacked path.

    ``telescope`` swaps the chunk cell for the macro-tick engine
    (``engine.simulate_telescoped``, docs/events.md) — per-lane dt,
    finals bit-identical; implies the streaming path (whole horizon as
    one chunk when ``chunk`` is None).
    """
    params = cfg.run_params() if params is None else params
    if chunk is None and not telescope:
        return _run_sim_vmapped_jit(sims, cfg, policy, params, n_hosts,
                                    n_nodes, horizon)
    chunk = chunk or horizon
    N = sims.t.shape[0]
    stats.check_chunk(chunk, int(sims.containers.status.shape[-1]))
    step = _vmapped_chunk_step_jit(telescope)
    cur = jax.tree.map(jnp.array, sims)      # the caller's sims survive
    online = stats.online_init((N,))
    t0 = 0
    while t0 < horizon:
        sz = min(chunk, horizon - t0)
        accs = jax.tree.map(lambda x: jnp.zeros((N,), x.dtype),
                            stats.acc_init())
        cur, accs = step(cur, accs, jnp.asarray(t0, I32), policy, params,
                         cfg=cfg, n_hosts=n_hosts, n_nodes=n_nodes,
                         chunk=sz)
        online = stats.online_fold(online, accs)
        t0 += sz
    return cur, online


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--policies", default="all",
                    help=f"comma-separated subset of {list_policies()} "
                         "or 'all'")
    ap.add_argument("--seeds", type=int, default=1,
                    help="number of seeds (0..n-1) per cell")
    ap.add_argument("--horizon", type=int, default=120)
    ap.add_argument("--hosts", type=int, default=20)
    ap.add_argument("--table", default="avg_runtime",
                    help="summary metric for the grouped table")
    ap.add_argument("--out", default=None,
                    help="write per-cell summary rows as JSON")
    ap.add_argument("--delay-mode", default="path", choices=["path", "fw"],
                    help="delay refresh: ECMP path sum or full APSP")
    add_exec_args(ap)
    return ap


def main() -> None:
    args = build_parser().parse_args()
    enable_compile_cache()

    policies = (list_policies() if args.policies == "all"
                else args.policies.split(","))
    cfg = SimConfig(horizon=args.horizon, delay_mode=args.delay_mode)
    plan = ExecPlan.from_args(args)
    cfg = plan.apply_to_config(cfg)
    n_leaf = max(4, args.hosts // 5)
    res = run_sweep(policies=policies, seeds=range(args.seeds), cfg=cfg,
                    n_hosts=args.hosts, n_spine=max(2, n_leaf // 4),
                    n_leaf=n_leaf, plan=plan)
    cells = len(res.policies) * len(res.scenarios) * len(res.seeds)
    from repro.kernels import kernel_backend, resolve_kernel
    backend = kernel_backend()
    kernel_note = (f"delay={args.delay_mode}/{cfg.delay_kernel}"
                   f"(-> {'kernel' if resolve_kernel(cfg.delay_kernel) else 'ref'}), "
                   f"waterfill={cfg.waterfill_kernel}"
                   f"(-> {'kernel' if resolve_kernel(cfg.waterfill_kernel) else 'ref'})")
    print(f"# {cells} cells ({len(res.policies)} policies x "
          f"{len(res.scenarios)} scenarios x {len(res.seeds)} seeds) in "
          f"{res.wall_s}s, {res.compile_cache_misses} compilation(s), "
          f"{res.n_devices} device(s), backend={backend}, {kernel_note}")
    print(res.table(args.table))
    if args.out:
        from repro.core.report import json_clean
        rows = res.summaries()
        for row in rows:   # self-describing rows: backend + kernel dispatch
            row["backend"] = backend
            row["delay_mode"] = args.delay_mode
            row["delay_kernel"] = cfg.delay_kernel
            row["waterfill_kernel"] = cfg.waterfill_kernel
        with open(args.out, "w") as f:
            json.dump(json_clean(rows), f, indent=1)
        print(f"# wrote {args.out}")


if __name__ == "__main__":
    main()
