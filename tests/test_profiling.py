"""The program's own names in a profiler trace (docs/perf.md, "Profiling a
run").

* Every tick phase runs under a ``jax.named_scope`` of its name, in both
  chunk drivers: the scan (``simulate_chunk``) and the telescoping driver,
  whose delay refresh runs through its hoisted boundary cond.
* The chunk loop writes one ``sim.run`` host span and one ``sim.chunk``
  span per chunk, whose ``ticks`` args sum to the horizon.
"""
import glob
import math

import jax
import jax.numpy as jnp
import pytest

from repro.core import get_policy, run_sim, stats
from repro.core.engine import _chunk_step_jit
from repro.core.types import ExecPlan

from test_streaming import build_small, small_cfg

PHASES = ("arrive", "schedule", "flows", "communicate", "migrate", "execute",
          "complete", "cost", "refresh", "collect")


@pytest.fixture(scope="module")
def scope_components():
    """telescope -> the set of path components of the op names in the
    chunk step's HLO metadata, lowered at small shapes."""
    import re
    cfg = small_cfg(delay_mode="fw", delay_update_interval=4)
    net_spec, sim0, rp = build_small(cfg)
    out = {}
    for telescope in (False, True):
        lowered = _chunk_step_jit(telescope).lower(
            sim0, stats.acc_init(), jnp.asarray(0, jnp.int32),
            get_policy("netaware"), rp, cfg=cfg, n_hosts=net_spec.n_hosts,
            n_nodes=net_spec.n_nodes, chunk=8)
        text = lowered.as_text(dialect="hlo", debug_info=True)
        out[telescope] = {part for path in re.findall(r'op_name="([^"]*)"',
                                                      text)
                          for part in path.split("/")}
    return out


@pytest.mark.parametrize("telescope", [False, True])
@pytest.mark.parametrize("phase", PHASES)
def test_phase_scope_in_chunk_step(scope_components, telescope, phase):
    """Each phase names ops of the chunk step as a whole path component;
    under ``telescope`` the tick drops its refresh cond, so ``refresh``
    comes from the driver's hoisted cond through ``make_refresh_fn``."""
    assert phase in scope_components[telescope]


def _host_spans(trace_dir, name):
    from jax.profiler import ProfileData
    (path,) = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    data = ProfileData.from_file(path)
    return [dict(e.stats) for plane in data.planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events if e.name == name]


@pytest.mark.parametrize("chunk,telescope", [(7, False), (40, False),
                                             (64, False), (17, True)])
def test_chunk_loop_host_spans(tmp_path, chunk, telescope):
    """One ``sim.run`` (args horizon, chunk) and ceil(horizon / chunk)
    ``sim.chunk`` spans whose ``t0`` tile the run and ``ticks`` sum to
    the horizon."""
    cfg = small_cfg()
    net_spec, sim0, rp = build_small(cfg)
    pol = get_policy("netaware")
    plan = ExecPlan(chunk=chunk, telescope=telescope)
    run = lambda: run_sim(sim0, cfg, pol, net_spec.n_hosts,
                          net_spec.n_nodes, cfg.horizon, params=rp, plan=plan)
    jax.block_until_ready(run())             # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        jax.block_until_ready(run())
    runs = _host_spans(tmp_path, "sim.run")
    chunks = sorted(_host_spans(tmp_path, "sim.chunk"),
                    key=lambda s: s["t0"])
    assert runs == [{"horizon": cfg.horizon, "chunk": chunk}]
    assert len(chunks) == math.ceil(cfg.horizon / chunk)
    assert sum(c["ticks"] for c in chunks) == cfg.horizon
    assert [c["t0"] for c in chunks] == \
        [i * chunk for i in range(len(chunks))]
