"""The tick's Pallas kernels compile for a TPU v5e chip.

Interpret mode (tests/test_kernels.py) checks what the kernels compute;
only the TPU compiler says whether Mosaic can lower them.  The compiler is
installed here and compiles for a chip that is described, not attached,
so these tests need no accelerator.  Shapes are the engine benchmark's
two large fleets:

* 500 hosts / 3000 containers: F = 6000 flows, E = 3000 links, N = 625
  network nodes;
* 2000 hosts / 6000 containers: F = 12000, E = 42000, N = 2500.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import repro.kernels
from repro.core import (SimConfig, get_policy, init_sim, paper_workload,
                        scaled_hosts)
from repro.core.engine import simulate
from repro.core.network import SpineLeafSpec, build_network
from repro.kernels.fw_minplus.fw_minplus import floyd_warshall
from repro.kernels.seg_waterfill.seg_waterfill import seg_waterfill

FLEETS = {"500h": dict(F=6000, E=3000, N=625),
          "2000h": dict(F=12000, E=42000, N=2500)}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to a persistent cache but
    # cannot be read back without the chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("fleet", sorted(FLEETS))
def test_seg_waterfill_lowers(one_chip, fleet):
    F, E = FLEETS[fleet]["F"], FLEETS[fleet]["E"]
    s = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    _compile(lambda *a: seg_waterfill(*a, interpret=False),
             s((F, 4), jnp.int32), s((F,), jnp.bool_), s((E,), jnp.float32),
             s((F,), jnp.float32))


@pytest.mark.parametrize("fleet", sorted(FLEETS))
def test_fw_minplus_lowers(one_chip, fleet):
    N = FLEETS[fleet]["N"]
    _compile(lambda a: floyd_warshall(a, interpret=False),
             jax.ShapeDtypeStruct((N, N), jnp.float32, sharding=one_chip))


@pytest.mark.parametrize("delay_mode,cells", [("path", None), ("fw", 2)])
def test_tick_lowers_with_kernels(one_chip, monkeypatch, delay_mode, cells):
    """The 500h/3000c tick with 'auto' kernel dispatch steered to TPU:
    catches a kernel that lowers alone but not inside the tick's scan and
    conds, or not under the sweep's vmap (``cells``)."""
    monkeypatch.setattr(repro.kernels, "kernel_backend", lambda: "tpu")
    H, C = 500, 3000
    cfg = SimConfig(n_jobs=C // 3, n_tasks=C, n_containers=C, horizon=2,
                    delay_mode=delay_mode)
    n_leaf = H // 5
    spec = SpineLeafSpec(n_spine=n_leaf // 4, n_leaf=n_leaf, n_hosts=H)
    sim0 = init_sim(scaled_hosts(H, n_leaf), paper_workload(cfg),
                    build_network(spec))
    args = (sim0, get_policy("netaware"), cfg.run_params())

    def run(sim, pol, rp):
        return simulate(sim, cfg, pol, spec.n_hosts, spec.n_nodes,
                        cfg.horizon, rp)

    if cells:
        run = jax.vmap(run)
        args = jax.tree.map(lambda x: jnp.stack([x] * cells), args)
    shapes = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        args)
    text = _compile(run, *shapes).as_text()
    want = 2 if delay_mode == "fw" else 1      # seg_waterfill (+ fw phases)
    assert text.count("tpu_custom_call") >= want
