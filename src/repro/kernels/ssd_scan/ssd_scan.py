"""Mamba2 SSD chunk scan — Pallas TPU kernel.

State-space duality splits the sequence into chunks of Q steps:

  intra-chunk (quadratic, MXU):  Y_q += sum_{s<=q} (C_q.B_s) e^{L_q-L_s} dt_s X_s
  inter-chunk (recurrence):      h   <- e^{L_Q} h + sum_s e^{L_Q-L_s} dt_s B_s (x) X_s
                                 Y_q += e^{L_q} C_q h_prev

Grid layout: (batch, head, chunk) with the chunk axis iterated sequentially
("arbitrary" semantics) so the [P, N] SSM state lives in a VMEM scratch
that carries across chunk steps — the TPU analogue of the paper's
chunk-parallel GPU kernel, but with the recurrence kept on-core instead of
a separate inter-block pass.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(xs_ref, b_ref, c_ref, dt_ref, alog_ref, h0_ref,
                y_ref, hout_ref, h_ref, *, Q):
    ci = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = h0_ref[0, 0]                    # [P, N]

    xs = xs_ref[0, 0].astype(jnp.float32)            # [Q, P]
    Bm = b_ref[0].astype(jnp.float32)                # [Q, N]
    Cm = c_ref[0].astype(jnp.float32)                # [Q, N]
    dt = dt_ref[0, 0].astype(jnp.float32)            # [Q, 1]
    A = -jnp.exp(alog_ref[0, 0])                     # scalar (per head)

    a_log = A * dt                                   # [Q, 1] (<= 0)
    cum = jnp.cumsum(a_log, axis=0)                  # [Q, 1]

    # ---- intra-chunk quadratic term (MXU) ---------------------------------
    G = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [Q, Q]
    decay = cum - cum.T                              # cum[q] - cum[s]
    qi = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    si = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    L = jnp.where(qi >= si, jnp.exp(decay), 0.0)
    M = G * L * dt.T                                 # [Q, Q] (dt_s on cols)
    y = jax.lax.dot_general(M, xs, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [Q, P]

    # ---- inter-chunk: contribution of the carried state -------------------
    h = h_ref[...]                                   # [P, N]
    y += jnp.exp(cum) * jax.lax.dot_general(
        Cm, h, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)          # [Q, P]

    # ---- state update ------------------------------------------------------
    total = cum[Q - 1]                               # [1]
    w = jnp.exp(total[None, :] - cum) * dt           # [Q, 1]
    upd = jax.lax.dot_general(xs, w * Bm, (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)  # [P, N]
    h_ref[...] = jnp.exp(total)[0] * h + upd

    y_ref[0, 0] = y.astype(y_ref.dtype)

    @pl.when(ci == nc - 1)
    def _emit_state():
        hout_ref[0, 0] = h_ref[...].astype(hout_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("Q", "interpret"))
def ssd_chunked_kernel(xs, Bm, Cm, dt, A_log, Q: int = 256, h0=None,
                       interpret: bool = True):
    """xs [B,S,H,P], Bm/Cm [B,S,N], dt [B,S,H], A_log [H]
    -> (y [B,S,H,P], h_final [B,H,P,N])."""
    B, S, H, P = xs.shape
    N = Bm.shape[-1]
    Q = min(Q, S)
    assert S % Q == 0
    nc = S // Q

    xs_t = xs.transpose(0, 2, 1, 3)                  # [B,H,S,P]
    dt_t = dt.transpose(0, 2, 1)[..., None]          # [B,H,S,1]
    if h0 is None:
        h0 = jnp.zeros((B, H, P, N), jnp.float32)

    grid = (B, H, nc)
    y, h_fin = pl.pallas_call(
        functools.partial(_ssd_kernel, Q=Q),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, Q, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, Q, N), lambda b, h, c: (b, c, 0)),
            pl.BlockSpec((1, Q, N), lambda b, h, c: (b, c, 0)),
            pl.BlockSpec((1, 1, Q, 1), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1), lambda b, h, c: (h, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, Q, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, P), xs.dtype),
            jax.ShapeDtypeStruct((B, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret, name="ssd_scan",
    )(xs_t, Bm, Cm, dt_t, A_log.reshape(H, 1), h0)
    return y.transpose(0, 2, 1, 3), h_fin
