"""Multi-device integration guard: the optimized distribution configs
(seq_parallel=full, moe_impl=a2a) must produce the same training loss as
the single-device baseline.  Runs in a subprocess with 8 fake CPU devices
(the main test process must keep exactly 1 device)."""
import json
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses, json
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_reduced
from repro.launch.mesh import auto_mesh
from repro.models import sharding as shd
from repro.train.optimizer import OptimizerConfig
from repro.train.step import init_train_state, make_train_step

out = {}
for arch, overrides in [
    ("smollm_360m", {"seq_parallel": "full"}),
    ("olmoe_1b_7b", {"moe_impl": "a2a", "capacity_factor": 2.0}),
    ("qwen2_5_3b", {"seq_parallel": "full"}),
]:
    base = get_reduced(arch)
    rng = np.random.default_rng(0)
    B, S = 8, 32
    batch = {"tokens": jnp.asarray(rng.integers(0, base.vocab, (B, S)), jnp.int32),
             "labels": jnp.asarray(rng.integers(0, base.vocab, (B, S)), jnp.int32)}

    losses = {}
    for name, cfg, mesh in [
        ("1dev", base, None),
        ("8dev", dataclasses.replace(base, **overrides),
         auto_mesh((2, 4), ("data", "model"))),
    ]:
        state = init_train_state(cfg, jax.random.PRNGKey(0))
        dp = ("data",)
        step = make_train_step(cfg, OptimizerConfig(), mesh=mesh, dp=dp)
        if mesh is not None:
            with mesh:
                pspec = shd.param_specs(cfg, state.params, mesh)
                shardings = type(state)(
                    params=shd.to_shardings(pspec, mesh),
                    opt=type(state.opt)(m=shd.to_shardings(pspec, mesh),
                                        v=shd.to_shardings(pspec, mesh),
                                        step=NamedSharding(mesh, P())))
                state = jax.device_put(state, shardings)
                _, m = jax.jit(step)(state, batch)
                losses[name] = float(m["loss"])
        else:
            _, m = jax.jit(step)(state, batch)
            losses[name] = float(m["loss"])
    out[arch] = losses
print(json.dumps(out))
"""


@pytest.mark.slow
def test_optimized_configs_match_baseline_loss():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("JAX_PLATFORMS", None)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    for arch, losses in out.items():
        # same params/batch; sharded math is bf16-reduction-order sensitive
        assert abs(losses["1dev"] - losses["8dev"]) < 0.05, (arch, losses)


ELASTIC_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, tempfile
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_reduced
from repro.distributed import checkpoint as ckpt
from repro.launch.mesh import auto_mesh
from repro.models import sharding as shd
from repro.train.optimizer import OptimizerConfig
from repro.train.step import init_train_state, make_train_step

cfg = get_reduced("qwen2_5_3b")
rng = np.random.default_rng(0)
batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (8, 32)), jnp.int32),
         "labels": jnp.asarray(rng.integers(0, cfg.vocab, (8, 32)), jnp.int32)}

def sharded_state(mesh, state):
    pspec = shd.param_specs(cfg, state.params, mesh)
    sh = type(state)(params=shd.to_shardings(pspec, mesh),
                     opt=type(state.opt)(m=shd.to_shardings(pspec, mesh),
                                         v=shd.to_shardings(pspec, mesh),
                                         step=NamedSharding(mesh, P())))
    return jax.device_put(state, sh), sh

# "2-pod" mesh: (pod=2, data=2, model=2); train 2 steps; checkpoint
mesh_big = auto_mesh((2, 2, 2), ("pod", "data", "model"))
state = init_train_state(cfg, jax.random.PRNGKey(0))
with mesh_big:
    state, _ = sharded_state(mesh_big, state)
    step = jax.jit(make_train_step(cfg, OptimizerConfig(), mesh=mesh_big,
                                   dp=("pod", "data")))
    for s in range(2):
        state, m = step(state, batch)
    loss_big = float(m["loss"])

d = tempfile.mkdtemp() + "/step_2"
ckpt.save_checkpoint(d, state, 2)

# elastic downsize: restore the same checkpoint onto a 1-pod (2,2) mesh
# (pod lost), continue training — the DCSim fault plan's 'elastic_downsize'
mesh_small = auto_mesh((2, 2), ("data", "model"))
with mesh_small:
    fresh = init_train_state(cfg, jax.random.PRNGKey(0))
    _, sh_small = sharded_state(mesh_small, fresh)
    restored, step_idx = ckpt.restore_checkpoint(d, fresh, shardings=sh_small)
    step2 = jax.jit(make_train_step(cfg, OptimizerConfig(), mesh=mesh_small,
                                    dp=("data",)))
    restored2, m2 = step2(restored, batch)
    loss_small = float(m2["loss"])

# the restored params are bit-identical; the next-step loss must be very
# close to what the big mesh would produce (reduction-order noise only)
with mesh_big:
    state3, m3 = step(state, batch)
    loss_big_next = float(m3["loss"])
print(json.dumps({"step_idx": step_idx, "loss_small": loss_small,
                  "loss_big_next": loss_big_next}))
"""


@pytest.mark.slow
def test_elastic_downsize_restores_across_meshes():
    """2-pod checkpoint -> 1-pod mesh restore -> training continues with
    matching loss (the recovery path of distributed/fault.plan_recovery)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, "-c", ELASTIC_SCRIPT], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["step_idx"] == 2
    assert abs(out["loss_small"] - out["loss_big_next"]) < 0.05, out
