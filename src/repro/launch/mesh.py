"""Production meshes (DESIGN.md §5).

Defined as FUNCTIONS so importing this module never touches jax device
state — only ``dryrun.py`` (which sets XLA_FLAGS first) builds the 256/512
device meshes; smoke tests build 1-device meshes from the same code path.
"""
from __future__ import annotations

import jax


def auto_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with every axis ``AxisType.Auto``.

    ``devices`` optionally pins an explicit device sequence (e.g. a subset,
    or ``jax.local_devices()`` under ``jax.distributed`` where the global
    ``jax.devices()`` list contains non-addressable devices) — the sweep
    fabric's ``grid_mesh`` builds through here, so the repo has one mesh
    constructor.
    """
    kwargs = {} if devices is None else {"devices": devices}
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
                         **kwargs)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 chips) or 2x16x16 (2 pods, 512 chips).

    Axes: ``pod`` — pure data parallelism across pods (params replicated,
    only gradient all-reduce crosses the DCN); ``data`` — FSDP + batch;
    ``model`` — TP/EP.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_host_mesh():
    """1-device mesh with the production axis names (CPU tests)."""
    return auto_mesh((1, 1), ("data", "model"))


def make_mesh_for(n_devices: int, model_parallel: int = 1):
    """Generic mesh over however many devices are actually present."""
    assert n_devices % model_parallel == 0
    return auto_mesh((n_devices // model_parallel, model_parallel),
                 ("data", "model"))
