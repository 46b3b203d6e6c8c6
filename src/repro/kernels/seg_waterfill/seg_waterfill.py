"""ECMP progressive-filling waterfill — Pallas kernel on the scalar core.

The sparse flow engine's hottest loop (`network.max_min_fair_rates_sparse`)
runs ``n_rounds`` progressive-filling rounds, each of which is a chain of
XLA ops with HBM round-trips between them:

    gather [F,4] link ids -> segment_sum unfrozen counts onto [E]
      -> fair share per link -> per-flow bound (min over <= 4 links)
      -> global min -> freeze mask -> alloc update
      -> segment_sum newly-allocated load -> capacity update

This kernel runs ALL rounds and the leftover-flow tail in one
``pallas_call`` whose every array lives in SMEM (scalar memory), and does
the two segment reductions the way the operation is defined: a scalar
loop over the flow slots that scatter-adds into (or gathers from) the
``[E]`` link arrays.  It needs no vector gather or scatter, which Mosaic
has no general lowering for.

Only a few per cent of the ``F`` flow slots are active in a tick, so one
pass over ``F`` lists the active flows and every later pass walks that
list; a pass over the links walks the active flows' slots, not all ``E``
links.  A round costs O(n_active), where the jnp path pays O(4F + E) and
a vectorised one-hot formulation O(4F * E).

Numerics: the per-link sums add the slots in flattened index order, one
at a time — the order a serial ``segment_sum`` uses; the flows the walk
skips would add only zeros, or to the pad slot — and every per-flow step
(fair-share divide, freeze rule ``bound <= m * 1.000001 + 1e-6``,
local-rate min) is the jnp path's own op, so the fair allocation is meant
to be bit-for-bit the reference's.  The Mathis min and the per-link load
run after the kernel as the reference's own XLA ops (docs/kernels.md).

SMEM is 1 MiB on a v5e chip; :func:`smem_bytes` gives the footprint, and
the wrapper refuses shapes that do not fit rather than fail at lowering.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
I32 = jnp.int32

SMEM_BYTES = 1 << 20          # scalar memory of one TPU v5e core


def smem_bytes(n_flows: int, n_links: int) -> int:
    """SMEM the kernel holds for ``n_flows`` flows and ``n_links`` links:
    the [4F] slot ids, three [F] f32 flow arrays, the [F] i32 list of
    active flows, the [E] capacity input and two [E + 1] link arrays (each
    padded to 1024-word tiles)."""
    def words(n):
        return -(-n // 1024) * 1024
    return 4 * (words(4 * n_flows) + 4 * words(n_flows) + words(n_links)
                + 2 * words(n_links + 1))


def _waterfill_kernel(lid_ref, active_ref, cap_ref, fair_ref,
                      cap_rem, acc, bnd, act, *,
                      n_rounds: int, n_flows: int, n_links: int,
                      local_rate: float, inf: float):
    """Single-invocation scalar kernel.

    ``lid`` [4F] i32 flattened slot link ids (``E`` = pad / inactive slot),
    ``active`` [F] i32, ``cap`` [E] f32 link capacity (KB/s).  Output
    ``fair`` [F] f32 doubles as the per-flow alloc; ``bnd`` [F] holds the
    round's bound, or -1 once the flow is frozen.  ``cap_rem`` [E + 1] and
    ``acc`` [E + 1] are the link arrays; slot ``E`` absorbs pad slots and
    reads as ``inf``.  ``act`` [F] i32 lists the active flows in ascending
    order; only its first ``n_active`` entries are walked after the first
    pass.

    A pass over the links walks the active flows' slots, so a link that
    several slots share is visited more than once, and every link step is
    idempotent: the counts are scattered as negative numbers and turned
    into a fair share only where still negative, and ``spend`` zeroes the
    link's sum as it subtracts it.  A link no unfrozen flow uses keeps a
    value nobody reads.
    """
    F, E = n_flows, n_links

    def each(n, body):
        jax.lax.fori_loop(0, n, lambda i, c: (body(i), c)[1], 0)

    def init_flow(f, n):
        a = active_ref[0, f] != 0
        fair_ref[0, f] = jnp.where(a, F32(local_rate), F32(0.0))
        # flows with no valid link freeze at the local rate up front
        any_link = ((lid_ref[0, 4 * f] < E) | (lid_ref[0, 4 * f + 1] < E)
                    | (lid_ref[0, 4 * f + 2] < E) | (lid_ref[0, 4 * f + 3] < E))
        bnd[f] = jnp.where(a & ~any_link, F32(-1.0), F32(0.0))
        act[n] = f                    # kept only if the next flow moves n
        return n + a.astype(I32)

    n_active = jax.lax.fori_loop(0, F, init_flow, I32(0))

    def each_active(body):
        """body(f) for the active flows, in ascending order."""
        each(n_active, lambda i: body(act[i]))

    def each_link(body):
        """body(l) for every link an active flow uses, once per slot (pad
        slot ``E`` among them)."""
        def slots(f):
            for s in range(4):
                body(lid_ref[0, 4 * f + s])
        each_active(slots)

    def init_link(l):
        # pad slot E has no capacity input; it is reset just below
        cap_rem[l] = cap_ref[0, jnp.minimum(l, E - 1)]
        acc[l] = F32(0.0)

    each_link(init_link)
    cap_rem[E] = F32(0.0)
    acc[E] = F32(0.0)

    def unfrozen(f):                  # f is active
        return bnd[f] >= 0.0

    def scatter(weight):
        """acc[lid[i]] += weight(i // 4) in slot order (the segment_sum);
        inactive flows would add to pad slot E only, so they are skipped."""
        def body(f):
            w = weight(f)
            for s in range(4):
                l = lid_ref[0, 4 * f + s]
                acc[l] = acc[l] + w
        each_active(body)

    def share(l):
        cnt = -acc[l]
        acc[l] = jnp.where(cnt > 0.0,
                           cap_rem[l] / jnp.maximum(cnt, F32(1.0)), acc[l])

    def fair_share():
        """acc, zero on the active flows' links (init and spend leave it
        so): unfrozen-flow counts -> per-link fair share."""
        scatter(lambda f: jnp.where(unfrozen(f), F32(-1.0), F32(0.0)))
        each_link(share)
        acc[E] = F32(inf)

    def bound_of(f):
        b = acc[lid_ref[0, 4 * f]]
        for s in range(1, 4):
            b = jnp.minimum(b, acc[lid_ref[0, 4 * f + s]])
        return b

    def round_body(_, carry):
        fair_share()

        def bound_pass(i, m):
            f = act[i]
            live = unfrozen(f)
            b = jnp.where(live, bound_of(f), F32(inf))
            bnd[f] = jnp.where(live, b, bnd[f])
            return jnp.minimum(m, b)

        m = jax.lax.fori_loop(0, n_active, bound_pass, F32(inf))
        thr = m * F32(1.000001) + F32(1e-6)

        def freeze(f):
            b = bnd[f]
            newly = unfrozen(f) & (b <= thr)
            fair_ref[0, f] = jnp.where(newly, jnp.minimum(b, F32(local_rate)),
                                    fair_ref[0, f])
            bnd[f] = jnp.where(newly, F32(-1.0), b)
            return jnp.where(newly, fair_ref[0, f], F32(0.0))

        def zero(l):
            acc[l] = F32(0.0)

        # the newly-frozen weight must be read before the freeze marks the
        # flow, so freeze inside the scatter's per-flow step
        each_link(zero)
        scatter(freeze)

        def spend(l):
            cap_rem[l] = jnp.maximum(cap_rem[l] - acc[l], F32(0.0))
            acc[l] = F32(0.0)
        each_link(spend)
        return carry

    jax.lax.fori_loop(0, n_rounds, round_body, 0)

    # leftover tail (more bottleneck levels than rounds): current fair share
    fair_share()

    def tail(f):
        left = unfrozen(f)
        fair_ref[0, f] = jnp.where(left,
                                jnp.minimum(bound_of(f), F32(local_rate)),
                                fair_ref[0, f])
    each_active(tail)


@functools.partial(jax.jit, static_argnames=("n_rounds", "interpret",
                                             "local_rate", "inf"))
def seg_waterfill(links: jnp.ndarray, active: jnp.ndarray,
                  link_bw_kbps: jnp.ndarray, tcp_cap: jnp.ndarray,
                  n_rounds: int = 8, interpret: bool = True,
                  local_rate: float = 4.0e6, inf: float = 1e9):
    """Max-min-fair + Mathis allocation.  Returns (rates [F], load [E]).

    ``links`` [F, 4] i32 ECMP link ids (-1 padded), ``active`` [F] bool/i32,
    ``link_bw_kbps`` [E] f32, ``tcp_cap`` [F] f32 per-flow Mathis ceiling
    (use ``inf`` for loss-free paths).  The progressive filling runs in
    the kernel; the Mathis min and the per-link load are the reference's
    own ops on its output.
    """
    F = links.shape[0]
    E = link_bw_kbps.shape[0]
    need = smem_bytes(F, E)
    if need > SMEM_BYTES:
        raise ValueError(
            f"seg_waterfill needs {need} B of SMEM for {F} flows x {E} "
            f"links, over the {SMEM_BYTES} B a v5e core has; run this "
            f"shape with waterfill_kernel='off'")
    active = active.astype(bool)
    valid = (links >= 0) & active[:, None]
    seg = jnp.where(valid, links, E).astype(I32)                # [F, 4]
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    kernel = functools.partial(
        _waterfill_kernel, n_rounds=n_rounds, n_flows=F, n_links=E,
        local_rate=local_rate, inf=inf)
    fair = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((1, F), F32),
        in_specs=[smem, smem, smem],
        out_specs=smem,
        scratch_shapes=[pltpu.SMEM((E + 1,), F32),
                        pltpu.SMEM((E + 1,), F32),
                        pltpu.SMEM((F,), F32),
                        pltpu.SMEM((F,), I32)],
        interpret=interpret, name="seg_waterfill",
    )(seg.reshape(1, 4 * F), active.astype(I32).reshape(1, F),
      link_bw_kbps.astype(F32).reshape(1, E))[0]
    rates = jnp.minimum(fair, tcp_cap) * active
    w = (rates[:, None] * (links >= 0).astype(F32)).reshape(-1)
    lseg = jnp.where(links >= 0, links, E).reshape(-1)
    load = jax.ops.segment_sum(w, lseg, num_segments=E + 1)[:E]
    return rates, load
