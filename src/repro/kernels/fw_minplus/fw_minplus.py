"""Blocked min-plus Floyd-Warshall APSP — Pallas TPU kernels.

The simulator's delay matrix (paper eq. 1) is APSP over the congestion-
adjusted link graph — the O(N^3) hot spot, refreshed every
``delay_update_interval`` ticks.  TPU adaptation: the classic 3-phase
blocked decomposition with (bs, bs) tiles resident in VMEM:

  phase 1: pivot block    D[k,k]  <- in-block FW           (sequential in p)
  phase 2: pivot row/col  D[k,j] / D[i,k]                  (panel updates)
  phase 3: everything     D[i,j] = min(D[i,j], D[i,k] (+) D[k,j])
           -- a min-plus "matmul": runs on the VPU as bs broadcast-add-mins.

All phases are bandwidth-friendly: each tile is read/written once per pivot
step, and phase 3 (the bulk) has arithmetic intensity ~bs/8 ops/byte.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _col(x, p):
    """Column ``p`` of a tile as a [bs, 1] column, ``p`` traced.

    A masked min over the lane axis: Mosaic has no lowering for a value
    slice at a traced lane offset, and the min picks the one unmasked
    element exactly."""
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.where(lane == p, x, jnp.inf).min(axis=1, keepdims=True)


def _row(x, p):
    """Row ``p`` of a tile as a [1, bs] row, ``p`` traced (see _col)."""
    sub = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    return jnp.where(sub == p, x, jnp.inf).min(axis=0, keepdims=True)


def _inblock_fw(d):
    """Sequential in-block FW over a [bs, bs] tile (returns updated tile)."""
    bs = d.shape[0]

    def body(p, d):
        return jnp.minimum(d, _col(d, p) + _row(d, p))

    return jax.lax.fori_loop(0, bs, body, d)


def _minplus(a, b):
    """min-plus product  out[r,c] = min_p a[r,p] + b[p,c]  ([bs,bs] tiles).

    Loops p to keep the VMEM working set at 3 tiles (no [bs,bs,bs]
    intermediate)."""
    bs = a.shape[0]
    init = _col(a, 0) + _row(b, 0)

    def body(p, acc):
        return jnp.minimum(acc, _col(a, p) + _row(b, p))

    return jax.lax.fori_loop(1, bs, body, init)


# --- phase kernels ----------------------------------------------------------
def _phase1_kernel(d_ref, o_ref):
    o_ref[...] = _inblock_fw(d_ref[...])


def _phase2_row_kernel(kk_ref, d_ref, o_ref, *, bs):
    """D[k,j] update: out = min(out, kk (+) D[k,j]) with in-block order."""
    kk = kk_ref[...]
    d = d_ref[...]

    def body(p, d):
        return jnp.minimum(d, _col(kk, p) + _row(d, p))

    o_ref[...] = jax.lax.fori_loop(0, bs, body, d)


def _phase2_col_kernel(kk_ref, d_ref, o_ref, *, bs):
    """D[i,k] update: out = min(out, D[i,k] (+) kk)."""
    kk = kk_ref[...]
    d = d_ref[...]

    def body(p, d):
        return jnp.minimum(d, _col(d, p) + _row(kk, p))

    o_ref[...] = jax.lax.fori_loop(0, bs, body, d)


def _phase3_kernel(row_ref, col_ref, d_ref, o_ref):
    o_ref[...] = jnp.minimum(d_ref[...], _minplus(col_ref[...], row_ref[...]))


# --- driver -----------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("bs", "interpret"))
def floyd_warshall(A: jnp.ndarray, bs: int = 128,
                   interpret: bool = True) -> jnp.ndarray:
    """Blocked APSP.  A [n,n] f32; n padded up to a multiple of ``bs``."""
    n = A.shape[0]
    bs = min(bs, n)
    n_pad = ((n + bs - 1) // bs) * bs
    if n_pad != n:
        big = jnp.float32(1e9)
        A = jnp.pad(A, ((0, n_pad - n), (0, n_pad - n)),
                    constant_values=big)
        # keep the padded diagonal at 0 so padding never relays paths
        idx = jnp.arange(n, n_pad)
        A = A.at[idx, idx].set(0.0)
    nb = n_pad // bs

    tile = lambda i, j: pl.BlockSpec((bs, bs), lambda *_: (i, j))

    def phase1(D, k):
        return pl.pallas_call(
            _phase1_kernel,
            grid=(1,),         # a windowed block needs a grid on Mosaic
            out_shape=jax.ShapeDtypeStruct((bs, bs), D.dtype),
            in_specs=[pl.BlockSpec((bs, bs), lambda _: (k, k))],
            out_specs=pl.BlockSpec((bs, bs), lambda _: (0, 0)),
            interpret=interpret, name="fw_phase1",
        )(D)

    def phase2(D, kk, k, row: bool):
        kern = _phase2_row_kernel if row else _phase2_col_kernel
        grid = (nb,)
        if row:
            d_spec = pl.BlockSpec((bs, bs), lambda j: (k, j))
        else:
            d_spec = pl.BlockSpec((bs, bs), lambda i: (i, k))
        return pl.pallas_call(
            functools.partial(kern, bs=bs),
            grid=grid,
            out_shape=jax.ShapeDtypeStruct((n_pad, n_pad), D.dtype),
            in_specs=[pl.BlockSpec((bs, bs), lambda j: (0, 0)), d_spec],
            out_specs=d_spec,
            # alias D -> out: the grid only writes the pivot row/col panel,
            # every other tile must carry through unchanged
            input_output_aliases={1: 0},
            interpret=interpret, name="fw_phase2",
        )(kk, D)

    def phase3(D, k):
        return pl.pallas_call(
            _phase3_kernel,
            grid=(nb, nb),
            out_shape=jax.ShapeDtypeStruct((n_pad, n_pad), D.dtype),
            in_specs=[
                pl.BlockSpec((bs, bs), lambda i, j: (k, j)),   # pivot row
                pl.BlockSpec((bs, bs), lambda i, j: (i, k)),   # pivot col
                pl.BlockSpec((bs, bs), lambda i, j: (i, j)),
            ],
            out_specs=pl.BlockSpec((bs, bs), lambda i, j: (i, j)),
            interpret=interpret, name="fw_phase3",
        )(D, D, D)

    D = A.astype(jnp.float32)
    for k in range(nb):                     # nb pivot steps (static unroll)
        kk = phase1(D, k)
        D = jax.lax.dynamic_update_slice(D, kk, (k * bs, k * bs))
        D = phase2(D, kk, k, row=True)
        D = phase2(D, kk, k, row=False)
        D = phase3(D, k)
    return D[:n, :n]
