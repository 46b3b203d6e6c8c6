"""Mixture-of-Experts layer with an explicit expert-parallel (EP) path.

Router runs under plain pjit; dispatch/compute/combine run under
``shard_map``:

* tokens are sharded over the data axes and *replicated* over ``model``;
* experts are sharded over ``model`` (E_l = E / |model| per shard) with their
  weights FSDP-sharded over ``data`` (gathered per layer inside the shard —
  the all_gather's AD transpose is the reduce-scatter of expert grads);
* each shard scatter-packs the tokens routed to ITS experts into a
  fixed-capacity buffer [E_l, C, d] (GShard-style capacity drop), runs the
  grouped SwiGLU, scatters results back weighted, and a single
  ``psum('model')`` combines partial token outputs.

This avoids the classic [T, E, C] one-hot dispatch einsum, whose FLOPs are
quadratic in tokens and would drown the roofline's useful-compute ratio.

A dense "oracle" path (every expert on every token, one-hot combine) exists
for tiny smoke tests and as the correctness reference.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.layers import BF16, F32, init_dense

MODEL_AXIS = "model"


def init_moe(key, cfg):
    ks = jax.random.split(key, 5)
    E, d, f = cfg.n_experts, cfg.d_model, cfg.d_ff_expert
    p = {
        "router": init_dense(ks[0], d, E, scale=0.02),
        "w_gate": jax.random.normal(ks[1], (E, d, f), F32) * (d ** -0.5),
        "w_up": jax.random.normal(ks[2], (E, d, f), F32) * (d ** -0.5),
        "w_down": jax.random.normal(ks[3], (E, f, d), F32) * (f ** -0.5),
    }
    if cfg.n_shared_experts:
        from repro.models.layers import init_mlp
        p["shared"] = init_mlp(ks[4], d, cfg.n_shared_experts * f)
    return p


def router_topk(params, x, cfg) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Top-k routing probabilities.  Returns (weights [B,S,k], idx [B,S,k],
    aux_loss scalar) — aux is the standard load-balancing loss."""
    logits = (x.astype(BF16) @ params["router"].astype(BF16)).astype(F32)
    probs = jax.nn.softmax(logits, axis=-1)                     # [B,S,E]
    topw, topi = jax.lax.top_k(probs, cfg.top_k)
    topw = topw / jnp.maximum(topw.sum(-1, keepdims=True), 1e-9)
    # load-balance aux: E * sum_i f_i * p_i
    E = cfg.n_experts
    onehot = jax.nn.one_hot(topi, E, dtype=F32).sum(-2)         # [B,S,E]
    f = onehot.mean((0, 1)) / cfg.top_k
    p_mean = probs.mean((0, 1))
    aux = E * jnp.sum(f * p_mean)
    return topw, topi, aux


def _capacity(tokens_per_shard: int, cfg) -> int:
    c = int(tokens_per_shard * cfg.top_k * cfg.capacity_factor
            / cfg.n_experts)
    return max(8, ((c + 7) // 8) * 8)


def _ep_shard(x, topw, topi, w_gate, w_up, w_down, *, cfg, n_model: int,
              data_axes: tuple):
    """Per-(data, model)-shard body.  x [b,S,d] local tokens (replicated over
    model); w_* [E_l, d/|data|, f] local expert shards."""
    b, S, d = x.shape
    T = b * S
    E_l = cfg.n_experts // n_model
    C = _capacity(T, cfg)

    # FSDP: gather this layer's expert weights over the FSDP axis.
    # Cast to bf16 FIRST so the all-gather moves half the bytes (its AD
    # transpose reduce-scatters bf16 grads, cast up afterwards).  Weights
    # are sharded P(model, data, ...): only 'data' is gathered — on the
    # multi-pod mesh they are REPLICATED over 'pod' (gathering there would
    # duplicate the tensor).
    w_gate, w_up, w_down = (w_gate.astype(BF16), w_up.astype(BF16),
                            w_down.astype(BF16))
    for ax in ("data",):
        w_gate = jax.lax.all_gather(w_gate, ax, axis=1, tiled=True)
        w_up = jax.lax.all_gather(w_up, ax, axis=1, tiled=True)
        w_down = jax.lax.all_gather(w_down, ax, axis=2, tiled=True)

    xt = x.reshape(T, d)
    wk = topw.reshape(T * cfg.top_k)
    ek = topi.reshape(T * cfg.top_k)
    tok = jnp.repeat(jnp.arange(T), cfg.top_k)

    shard = jax.lax.axis_index(MODEL_AXIS)
    lo = shard * E_l
    e_loc = ek - lo
    in_range = (e_loc >= 0) & (e_loc < E_l)
    e_bucket = jnp.where(in_range, e_loc, E_l)                 # E_l = dump

    # rank of each assignment within its expert (stable arrival order)
    onehot = jax.nn.one_hot(e_bucket, E_l + 1, dtype=jnp.int32)
    rank = jnp.take_along_axis(jnp.cumsum(onehot, axis=0) - 1,
                               e_bucket[:, None], axis=1)[:, 0]
    keep = in_range & (rank < C)
    slot = jnp.where(keep, e_loc * C + rank, E_l * C)          # OOB -> drop

    buf = jnp.zeros((E_l * C, d), BF16)
    buf = buf.at[slot].add(xt[tok].astype(BF16) * keep[:, None], mode="drop")
    buf = buf.reshape(E_l, C, d)

    g = jnp.einsum("ecd,edf->ecf", buf, w_gate.astype(BF16))
    u = jnp.einsum("ecd,edf->ecf", buf, w_up.astype(BF16))
    h = jax.nn.silu(g.astype(F32)).astype(BF16) * u
    y_buf = jnp.einsum("ecf,efd->ecd", h, w_down.astype(BF16))
    y_buf = y_buf.reshape(E_l * C, d)

    vals = y_buf[jnp.clip(slot, 0, E_l * C - 1)]
    vals = vals * (wk * keep).astype(BF16)[:, None]
    y = jnp.zeros((T, d), BF16).at[tok].add(vals)
    y = jax.lax.psum(y, MODEL_AXIS)
    return y.reshape(b, S, d)


def _ep_a2a_shard(x, topw, topi, w_gate, w_up, w_down, *, cfg,
                  n_model: int, data_axes: tuple):
    """All-to-all EP body (cfg.moe_impl='a2a').  x [b, S_l, d]: tokens
    SEQUENCE-SHARDED over the model axis (no replication), experts sharded
    over model.  Each shard routes its own tokens, exchanges them with the
    shard owning the chosen expert via all_to_all, computes, and exchanges
    back — no [T, d] psum, no 16x redundant dispatch.
    """
    b, S_l, d = x.shape
    T = b * S_l
    E, k = cfg.n_experts, cfg.top_k
    E_l = E // n_model

    # gather expert weights over the FSDP axis (bf16; transpose = RS
    # grads).  'data' only — weights are pod-replicated (see _ep_shard).
    w_gate, w_up, w_down = (w_gate.astype(BF16), w_up.astype(BF16),
                            w_down.astype(BF16))
    for ax in ("data",):
        w_gate = jax.lax.all_gather(w_gate, ax, axis=1, tiled=True)
        w_up = jax.lax.all_gather(w_up, ax, axis=1, tiled=True)
        w_down = jax.lax.all_gather(w_down, ax, axis=2, tiled=True)

    xt = x.reshape(T, d)
    wk = topw.reshape(T * k)
    ek = topi.reshape(T * k)                         # global expert ids
    tok = jnp.repeat(jnp.arange(T), k)

    # ---- send side: pack assignments by destination shard ----------------
    dest = ek // E_l                                 # [A] target shard
    c = int(T * k * cfg.capacity_factor / n_model)   # per-destination slots
    C_send = max(8, ((c + 7) // 8) * 8)
    onehot_d = jax.nn.one_hot(dest, n_model, dtype=jnp.int32)
    rank_d = jnp.take_along_axis(jnp.cumsum(onehot_d, axis=0) - 1,
                                 dest[:, None], axis=1)[:, 0]
    keep = rank_d < C_send
    slot = jnp.where(keep, dest * C_send + rank_d, n_model * C_send)

    send_x = jnp.zeros((n_model * C_send, d), BF16)
    send_x = send_x.at[slot].add(xt[tok].astype(BF16) * keep[:, None],
                                 mode="drop")
    # payload metadata: local expert id at the destination (-1 = empty)
    send_e = jnp.full((n_model * C_send,), E_l, jnp.int32)
    send_e = send_e.at[slot].set(jnp.where(keep, ek % E_l, E_l),
                                 mode="drop")

    recv_x = jax.lax.all_to_all(send_x.reshape(n_model, C_send, d),
                                MODEL_AXIS, split_axis=0, concat_axis=0,
                                tiled=False)         # [n_model, C_send, d]
    recv_e = jax.lax.all_to_all(send_e.reshape(n_model, C_send),
                                MODEL_AXIS, split_axis=0, concat_axis=0,
                                tiled=False)
    R = n_model * C_send
    rx = recv_x.reshape(R, d)
    re = recv_e.reshape(R)

    # ---- receiver: pack by local expert, grouped matmul ------------------
    C_exp = _capacity(T * n_model, cfg)
    onehot_e = jax.nn.one_hot(re, E_l + 1, dtype=jnp.int32)
    rank_e = jnp.take_along_axis(jnp.cumsum(onehot_e, axis=0) - 1,
                                 re[:, None], axis=1)[:, 0]
    ok = (re < E_l) & (rank_e < C_exp)
    eslot = jnp.where(ok, re * C_exp + rank_e, E_l * C_exp)

    buf = jnp.zeros((E_l * C_exp, d), BF16)
    buf = buf.at[eslot].add(rx * ok[:, None], mode="drop")
    buf = buf.reshape(E_l, C_exp, d)
    g = jnp.einsum("ecd,edf->ecf", buf, w_gate)
    u = jnp.einsum("ecd,edf->ecf", buf, w_up)
    h = jax.nn.silu(g.astype(F32)).astype(BF16) * u
    y_buf = jnp.einsum("ecf,efd->ecd", h, w_down).reshape(E_l * C_exp, d)

    # ---- route results back ----------------------------------------------
    y_recv = y_buf[jnp.clip(eslot, 0, E_l * C_exp - 1)] * ok[:, None]
    y_send = jax.lax.all_to_all(y_recv.reshape(n_model, C_send, d),
                                MODEL_AXIS, split_axis=0, concat_axis=0,
                                tiled=False).reshape(n_model * C_send, d)
    vals = y_send[jnp.clip(slot, 0, n_model * C_send - 1)]
    vals = vals * (wk * keep).astype(BF16)[:, None]
    y = jnp.zeros((T, d), BF16).at[tok].add(vals)
    return y.reshape(b, S_l, d)


def moe_layer_ep(params, x, cfg, mesh, data_axes: tuple):
    """Expert-parallel MoE layer.  x [B,S,d] sharded over ``data_axes``."""
    topw, topi, aux = router_topk(params, x, cfg)
    a2a = cfg.moe_impl == "a2a" and x.shape[1] % mesh.shape[MODEL_AXIS] == 0
    if a2a:
        # tokens sequence-sharded over the model axis inside the layer
        tok_spec = P(data_axes, MODEL_AXIS, None)
        fn = functools.partial(_ep_a2a_shard, cfg=cfg,
                               n_model=mesh.shape[MODEL_AXIS],
                               data_axes=data_axes)
    else:
        tok_spec = P(data_axes, None, None)
        fn = functools.partial(_ep_shard, cfg=cfg,
                               n_model=mesh.shape[MODEL_AXIS],
                               data_axes=data_axes)
    y = jax.shard_map(
        fn, mesh=mesh,
        in_specs=(tok_spec, tok_spec, tok_spec,
                  P(MODEL_AXIS, "data", None),
                  P(MODEL_AXIS, "data", None),
                  P(MODEL_AXIS, None, "data")),
        out_specs=tok_spec,
        check_vma=False,
    )(x, topw.astype(x.dtype), topi,
      params["w_gate"], params["w_up"], params["w_down"])
    y = y.astype(x.dtype)

    if cfg.n_shared_experts:
        from repro.models.layers import mlp
        y = y + mlp(params["shared"], x)
    return y, aux


def moe_layer_dense(params, x, cfg):
    """Dense oracle: run every expert on every token, combine by gate.
    O(E) compute — tiny configs/tests only."""
    topw, topi, aux = router_topk(params, x, cfg)
    gates = jnp.sum(jax.nn.one_hot(topi, cfg.n_experts, dtype=F32)
                    * topw[..., None], axis=-2)                # [B,S,E]
    xb = x.astype(BF16)
    g = jnp.einsum("bsd,edf->bsef", xb, params["w_gate"].astype(BF16))
    u = jnp.einsum("bsd,edf->bsef", xb, params["w_up"].astype(BF16))
    h = jax.nn.silu(g.astype(F32)).astype(BF16) * u
    y_e = jnp.einsum("bsef,efd->bsed", h, params["w_down"].astype(BF16))
    y = jnp.einsum("bsed,bse->bsd", y_e, gates.astype(BF16)).astype(x.dtype)
    if cfg.n_shared_experts:
        from repro.models.layers import mlp
        y = y + mlp(params["shared"], x)
    return y, aux


def moe_layer(params, x, cfg, mesh=None, data_axes: tuple = ("data",)):
    if mesh is not None and cfg.n_experts % mesh.shape[MODEL_AXIS] == 0:
        return moe_layer_ep(params, x, cfg, mesh, data_axes)
    return moe_layer_dense(params, x, cfg)
