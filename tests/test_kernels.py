"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs jnp oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import resolve_kernel, use_interpret
from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.flash_attention.ref import attention as fa_ref
from repro.kernels.fw_minplus import ops as fw_ops
from repro.kernels.fw_minplus.ref import floyd_warshall_ref
from repro.kernels.seg_waterfill import ops as wf_ops
from repro.kernels.seg_waterfill.ref import seg_waterfill_ref
from repro.kernels.ssd_scan import ops as ssd_ops
from repro.kernels.ssd_scan.ref import ssd_chunked_ref

rng = np.random.default_rng(42)

INF = 1e9


def random_adjacency(n, p_edge=0.5, dyadic=False):
    """Symmetric adjacency with INF non-edges and a zero diagonal.

    ``dyadic=True`` draws weights from multiples of 1/64 — path sums of
    dyadic rationals are EXACT in f32, so the blocked kernel's different
    add association cannot round differently and kernel == ref bit-for-bit.
    Arbitrary floats get the documented ~1 ulp tolerance instead
    (docs/kernels.md).
    """
    if dyadic:
        A = (rng.integers(8, 512, (n, n)) / 64.0).astype(np.float32)
    else:
        A = rng.uniform(0.1, 10, (n, n)).astype(np.float32)
    A[rng.uniform(size=(n, n)) < 1 - p_edge] = INF
    A = np.minimum(A, A.T)
    np.fill_diagonal(A, 0.0)
    return A


# --- fw_minplus -------------------------------------------------------------
@pytest.mark.parametrize("n,bs", [(8, 4), (24, 8), (64, 16), (100, 32),
                                  (128, 64), (30, 16)])
def test_fw_matches_ref(n, bs):
    A = random_adjacency(n)
    D_ref = np.asarray(floyd_warshall_ref(jnp.asarray(A)))
    D_k = np.asarray(fw_ops.floyd_warshall(jnp.asarray(A), bs=bs))
    np.testing.assert_allclose(D_k, D_ref, rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("n,bs", [(8, 4), (24, 8), (37, 16), (64, 16),
                                  (100, 32)])
def test_fw_bit_exact_on_dyadic_weights(n, bs):
    """On dyadic-rational weights every path sum is exact, so the blocked
    pivot decomposition must agree with the scan ref BIT-FOR-BIT — the
    ISSUE 6 oracle contract (fp-associativity excuses don't apply here)."""
    A = random_adjacency(n, dyadic=True)
    D_ref = np.asarray(floyd_warshall_ref(jnp.asarray(A)))
    D_k = np.asarray(fw_ops.floyd_warshall(jnp.asarray(A), bs=bs))
    np.testing.assert_array_equal(D_k, D_ref)


def test_fw_non_block_multiple_padding_is_invisible():
    """N not a multiple of bs: the INF/0-diag padding must not leak into
    the real block (shortest paths never route through pad nodes)."""
    A = random_adjacency(45, dyadic=True)
    D_ref = np.asarray(floyd_warshall_ref(jnp.asarray(A)))
    for bs in (8, 16, 32, 64):
        D_k = np.asarray(fw_ops.floyd_warshall(jnp.asarray(A), bs=bs))
        np.testing.assert_array_equal(D_k, D_ref)


def test_fw_disconnected_stays_inf():
    A = np.full((12, 12), INF, np.float32)
    np.fill_diagonal(A, 0)
    A[0, 1] = A[1, 0] = 1.0          # only one edge
    D = np.asarray(fw_ops.floyd_warshall(jnp.asarray(A), bs=4))
    assert D[0, 1] == 1.0
    assert D[0, 2] >= 1e8            # unreachable remains "inf"


def test_fw_matches_ref_under_vmap():
    """The sweep vmaps the delay refresh over grid cells; the kernel must
    agree with the vmapped ref (bit-for-bit on dyadic weights)."""
    batch = np.stack([random_adjacency(24, dyadic=True) for _ in range(3)])
    A = jnp.asarray(batch)
    D_ref = np.asarray(jax.vmap(floyd_warshall_ref)(A))
    D_k = np.asarray(jax.vmap(
        lambda a: fw_ops.floyd_warshall(a, bs=8))(A))
    np.testing.assert_array_equal(D_k, D_ref)


# --- kernel dispatch --------------------------------------------------------
def test_resolve_kernel_flags():
    assert resolve_kernel("on", backend="cpu") is True
    assert resolve_kernel("off", backend="tpu") is False
    assert resolve_kernel("auto", backend="tpu") is True
    assert resolve_kernel("auto", backend="gpu") is True   # compiled Triton,
    assert resolve_kernel("auto", backend="cpu") is False  # NOT interpreter
    assert resolve_kernel(True, backend="cpu") is True
    with pytest.raises(ValueError):
        resolve_kernel("maybe")


def test_use_interpret_only_on_cpu():
    # the satellite-1 fix: GPU gets the compiled Triton lowering, the
    # interpreter is strictly a CPU test vehicle
    assert use_interpret(backend="cpu") is True
    assert use_interpret(backend="gpu") is False
    assert use_interpret(backend="tpu") is False


# --- seg_waterfill ----------------------------------------------------------
def random_flows(F, E, seed=0, p_active=0.8, p_local=0.1, p_lossy=0.3,
                 hot=0.0, zero_cap=0.0):
    """Random ECMP flows; ``hot`` of the flows' first valid slot goes to
    link 0, and ``zero_cap`` of the links have no capacity left."""
    r = np.random.default_rng(seed)
    links = r.integers(0, E, (F, 4)).astype(np.int32)
    # ECMP lists are -1 padded; local (same-host) flows have NO links
    n_valid = r.integers(0, 5, F)
    links[np.arange(4)[None, :] >= n_valid[:, None]] = -1
    links[r.uniform(size=F) < p_local] = -1
    active = (r.uniform(size=F) < p_active)
    bw = r.uniform(1e3, 1e5, E).astype(np.float32)
    tcp = np.where(r.uniform(size=F) < p_lossy,
                   r.uniform(10, 1e4, F), INF).astype(np.float32)
    if hot:
        links[(r.uniform(size=F) < hot) & (links[:, 0] >= 0), 0] = 0
    if zero_cap:
        bw = np.where(r.uniform(size=E) < zero_cap, 0.0, bw).astype(np.float32)
    return (jnp.asarray(links), jnp.asarray(active), jnp.asarray(bw),
            jnp.asarray(tcp))


def assert_waterfill_matches(links, active, bw, tcp, n_rounds=8):
    r_ref, l_ref = seg_waterfill_ref(links, active, bw, tcp,
                                     n_rounds=n_rounds)
    r_k, l_k = wf_ops.seg_waterfill(links, active, bw, tcp,
                                    n_rounds=n_rounds)
    # rates: identical op order per flow -> bit-for-bit; load: tree-reduce
    # per tile vs segment_sum scatter order -> documented ~1 ulp tolerance
    np.testing.assert_array_equal(np.asarray(r_k), np.asarray(r_ref))
    np.testing.assert_allclose(np.asarray(l_k), np.asarray(l_ref),
                               rtol=2e-6, atol=1e-3)


@pytest.mark.parametrize("F,E,seed", [(5, 7, 0), (33, 16, 1), (200, 40, 2),
                                      (64, 9, 3), (128, 130, 4)])
def test_waterfill_matches_ref(F, E, seed):
    assert_waterfill_matches(*random_flows(F, E, seed=seed))


@pytest.mark.parametrize("F,E,seed", [(16, 8, 5), (300, 50, 300),
                                      (1200, 4000, 1200)])
def test_waterfill_no_active_flows(F, E, seed):
    links, _, bw, tcp = random_flows(F, E, seed=seed)
    active = jnp.zeros(F, bool)
    r_k, l_k = wf_ops.seg_waterfill(links, active, bw, tcp)
    assert (np.asarray(r_k) == 0).all()
    assert (np.asarray(l_k) == 0).all()
    assert_waterfill_matches(links, active, bw, tcp)


def test_waterfill_local_flows_get_local_rate():
    """Flows with no links (same-host loopback) freeze at the local rate
    (capped by Mathis), and contribute nothing to any link's load."""
    links = jnp.full((6, 4), -1, jnp.int32)
    active = jnp.ones(6, bool)
    bw = jnp.full(4, 1e4, jnp.float32)
    tcp = jnp.asarray([INF, INF, 100.0, INF, 5e6, 1e3], jnp.float32)
    r_k, l_k = wf_ops.seg_waterfill(links, active, bw, tcp)
    np.testing.assert_array_equal(
        np.asarray(r_k), np.minimum(np.asarray(tcp), 4.0e6))
    assert (np.asarray(l_k) == 0).all()
    assert_waterfill_matches(links, active, bw, tcp)


def test_waterfill_all_lossless_tcp_inf():
    links, active, bw, _ = random_flows(40, 12, seed=6)
    tcp = jnp.full(40, INF, jnp.float32)
    assert_waterfill_matches(links, active, bw, tcp)


def test_waterfill_fewer_rounds_than_bottlenecks():
    """n_rounds=1 exercises the leftover tail (flows never frozen get the
    current fair share) — same rule in kernel and ref."""
    assert_waterfill_matches(*random_flows(50, 6, seed=7), n_rounds=1)


@pytest.mark.parametrize("F,E,hot,lanes", [
    (48, 10, 0.0, ((8, 0.8), (9, 0.8), (10, 0.8))),
    # lanes with their own active counts share the kernel's scratch
    (400, 500, 0.3, ((12, 0.01), (13, 0.1), (14, 0.6), (15, 0.0))),
])
def test_waterfill_matches_ref_under_vmap(F, E, hot, lanes):
    """The sweep's grid vmap batches every flow-engine input; the kernel
    must stay equal to the ref under vmap (grid-less pallas_call)."""
    packs = [random_flows(F, E, seed=s, p_active=p, hot=hot)
             for s, p in lanes]
    links = jnp.stack([p[0] for p in packs])
    active = jnp.stack([p[1] for p in packs])
    bw = jnp.stack([p[2] for p in packs])
    tcp = jnp.stack([p[3] for p in packs])
    r_ref, l_ref = jax.vmap(seg_waterfill_ref)(links, active, bw, tcp)
    r_k, l_k = jax.vmap(
        lambda *a: wf_ops.seg_waterfill(*a))(links, active, bw, tcp)
    np.testing.assert_array_equal(np.asarray(r_k), np.asarray(r_ref))
    np.testing.assert_allclose(np.asarray(l_k), np.asarray(l_ref),
                               rtol=2e-6, atol=1e-3)


@pytest.mark.parametrize("F,E,p_active,kw", [
    (300, 400, 0.01, {}),
    (600, 900, 0.02, {}),
    (1200, 2000, 0.01, {}),
    (1200, 3000, 0.02, {}),
    (1200, 400, 0.10, {}),                        # more live slots than links
    (800, 1500, 0.10, {}),
    (600, 700, 0.10, dict(hot=0.8)),              # one link shared by most
    (600, 700, 0.10, dict(zero_cap=0.3)),
    (900, 1000, 0.05, dict(hot=0.5, zero_cap=0.2)),
    (900, 1000, 0.05, dict(n_rounds=1)),          # the leftover tail
    (900, 1000, 0.10, dict(hot=0.9, n_rounds=2)),
], ids=lambda v: str(v).replace(" ", ""))
def test_waterfill_sparse_active_matches_ref(F, E, p_active, kw):
    """Simulator-like live shares (a few per cent of the flow slots), where
    the kernel walks only the active flows and their slots: rates stay
    bit-for-bit the reference's."""
    kw = dict(kw)
    n_rounds = kw.pop("n_rounds", 8)
    pack = random_flows(F, E, seed=F + E, p_active=p_active, **kw)
    assert 0 < int(pack[1].sum()) < F
    assert_waterfill_matches(*pack, n_rounds=n_rounds)


@pytest.mark.parametrize("n_rounds", [1, 8])
def test_waterfill_unused_links_do_not_move_rates(n_rounds):
    """The same flows over E links and over E padded by links no flow uses,
    which the slot walk never visits: the rates are bit-equal and the
    padding carries no load."""
    links, active, bw, tcp = random_flows(300, 100, seed=11, p_active=0.1,
                                          hot=0.3, zero_cap=0.1)
    bw_pad = jnp.concatenate([bw, jnp.full(300, 5e4, jnp.float32)])
    r, _ = wf_ops.seg_waterfill(links, active, bw, tcp, n_rounds=n_rounds)
    r_pad, l_pad = wf_ops.seg_waterfill(links, active, bw_pad, tcp,
                                        n_rounds=n_rounds)
    np.testing.assert_array_equal(np.asarray(r_pad), np.asarray(r))
    assert (np.asarray(l_pad)[100:] == 0).all()
    assert_waterfill_matches(links, active, bw_pad, tcp, n_rounds=n_rounds)


def test_waterfill_smem_fits_largest_fleet():
    """2000 hosts / 6000 containers (F = 12000, E = 42000) fits a v5e
    core's 1 MiB of SMEM with the active-flow list."""
    from repro.kernels.seg_waterfill.seg_waterfill import SMEM_BYTES, smem_bytes
    assert smem_bytes(12000, 42000) == 905_216 <= SMEM_BYTES == 1 << 20


# --- flash attention ---------------------------------------------------------
@pytest.mark.parametrize(
    "B,S,Hq,Hkv,D,causal,dtype",
    [(2, 128, 4, 4, 64, True, jnp.float32),
     (2, 256, 8, 2, 64, True, jnp.bfloat16),
     (1, 256, 15, 5, 64, True, jnp.float32),    # smollm GQA 15/5
     (2, 128, 4, 1, 128, True, jnp.bfloat16),   # MQA
     (2, 128, 4, 4, 64, False, jnp.float32),
     (1, 512, 2, 2, 32, True, jnp.float32)])
def test_flash_attention_matches_ref(B, S, Hq, Hkv, D, causal, dtype):
    q = jnp.asarray(rng.standard_normal((B, S, Hq, D)), dtype)
    k = jnp.asarray(rng.standard_normal((B, S, Hkv, D)), dtype)
    v = jnp.asarray(rng.standard_normal((B, S, Hkv, D)), dtype)
    o_k = fa_ops.flash_attention(q, k, v, causal)
    o_r = fa_ref(q, k, v, causal=causal)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(o_k, np.float32),
                               np.asarray(o_r, np.float32),
                               atol=tol, rtol=tol)


def test_flash_attention_grads_match_ref():
    q = jnp.asarray(rng.standard_normal((1, 128, 4, 64)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 128, 2, 64)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 128, 2, 64)), jnp.float32)

    def loss_k(q, k, v):
        return (fa_ops.flash_attention(q, k, v) ** 2).sum()

    def loss_r(q, k, v):
        return (fa_ref(q, k, v) ** 2).sum()

    gk = jax.grad(loss_k, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_r, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-4)


def test_flash_attention_causality():
    """Changing future K/V must not change past outputs."""
    q = jnp.asarray(rng.standard_normal((1, 128, 2, 32)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 128, 2, 32)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 128, 2, 32)), jnp.float32)
    o1 = fa_ops.flash_attention(q, k, v, True)
    k2 = k.at[:, 64:].set(99.0)
    v2 = v.at[:, 64:].set(-99.0)
    o2 = fa_ops.flash_attention(q, k2, v2, True)
    np.testing.assert_allclose(np.asarray(o1[:, :64]),
                               np.asarray(o2[:, :64]), atol=1e-6)


# --- ssd scan ----------------------------------------------------------------
@pytest.mark.parametrize(
    "B,S,H,P,N,Q",
    [(2, 64, 4, 32, 16, 16), (1, 128, 2, 64, 32, 32),
     (2, 256, 4, 64, 128, 64), (1, 64, 8, 16, 8, 64),
     (1, 96, 2, 32, 16, 32)])
def test_ssd_matches_ref(B, S, H, P, N, Q):
    xs = jnp.asarray(rng.standard_normal((B, S, H, P)) * 0.5, jnp.float32)
    Bm = jnp.asarray(rng.standard_normal((B, S, N)) * 0.5, jnp.float32)
    Cm = jnp.asarray(rng.standard_normal((B, S, N)) * 0.5, jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.2, (B, S, H)), jnp.float32)
    A_log = jnp.asarray(rng.uniform(-1, 0.5, (H,)), jnp.float32)
    y_r, h_r = ssd_chunked_ref(xs, Bm, Cm, dt, A_log, Q)
    y_k, h_k = ssd_ops.ssd_chunked(xs, Bm, Cm, dt, A_log, Q)
    scale = max(float(np.abs(np.asarray(y_r)).max()), 1.0)
    np.testing.assert_allclose(np.asarray(y_k) / scale,
                               np.asarray(y_r) / scale, atol=2e-2)
    np.testing.assert_allclose(np.asarray(h_k), np.asarray(h_r), atol=1e-2)


def test_ssd_chunking_invariance():
    """Different chunk sizes must give the same sequence output."""
    B, S, H, P, N = 1, 128, 2, 32, 16
    xs = jnp.asarray(rng.standard_normal((B, S, H, P)) * 0.5, jnp.float32)
    Bm = jnp.asarray(rng.standard_normal((B, S, N)) * 0.5, jnp.float32)
    Cm = jnp.asarray(rng.standard_normal((B, S, N)) * 0.5, jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.2, (B, S, H)), jnp.float32)
    A_log = jnp.zeros((H,), jnp.float32)
    y16, h16 = ssd_chunked_ref(xs, Bm, Cm, dt, A_log, 16)
    y64, h64 = ssd_chunked_ref(xs, Bm, Cm, dt, A_log, 64)
    np.testing.assert_allclose(np.asarray(y16), np.asarray(y64),
                               atol=3e-2)
    np.testing.assert_allclose(np.asarray(h16), np.asarray(h64), atol=1e-2)


def test_ssd_matches_sequential_recurrence():
    """Chunked SSD == naive per-step recurrence (the definition)."""
    B, S, H, P, N, Q = 1, 32, 2, 8, 4, 8
    xs = np.asarray(rng.standard_normal((B, S, H, P)) * 0.5, np.float32)
    Bm = np.asarray(rng.standard_normal((B, S, N)) * 0.5, np.float32)
    Cm = np.asarray(rng.standard_normal((B, S, N)) * 0.5, np.float32)
    dt = np.asarray(rng.uniform(0.05, 0.3, (B, S, H)), np.float32)
    A_log = np.asarray(rng.uniform(-0.5, 0.5, (H,)), np.float32)

    h = np.zeros((B, H, P, N), np.float64)
    y_seq = np.zeros((B, S, H, P), np.float64)
    A = -np.exp(A_log)
    for t in range(S):
        a_t = np.exp(A[None] * dt[:, t])                     # [B,H]
        upd = np.einsum("bn,bh,bhp->bhpn", Bm[:, t], dt[:, t], xs[:, t])
        h = a_t[..., None, None] * h + upd
        y_seq[:, t] = np.einsum("bn,bhpn->bhp", Cm[:, t], h)

    y_c, h_c = ssd_chunked_ref(jnp.asarray(xs), jnp.asarray(Bm),
                               jnp.asarray(Cm), jnp.asarray(dt),
                               jnp.asarray(A_log), Q)
    np.testing.assert_allclose(np.asarray(y_c), y_seq, atol=3e-2)
    np.testing.assert_allclose(np.asarray(h_c), h, atol=1e-2)
