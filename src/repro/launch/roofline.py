"""Roofline-term extraction from a compiled dry-run artifact.

    compute    = HLO_FLOPs / (chips * peak_FLOP/s)
    memory     = HLO_bytes / (chips * HBM_bw)
    collective = collective_bytes / (chips * link_bw)

FLOPs/bytes come from ``compiled.cost_analysis()``; collective bytes are
parsed out of the optimized HLO text (cost_analysis does not expose them)
with ring-algorithm wire-byte multipliers per op kind.

Hardware model: TPU v5e — 197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s/link ICI.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any, Dict

# --- TPU v5e hardware constants -------------------------------------------
PEAK_FLOPS = 197e12          # bf16 FLOP/s per chip
HBM_BW = 819e9               # bytes/s per chip
ICI_BW = 50e9                # bytes/s per link (~per-chip injection budget)

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}

# one tuple-typed or plain-typed result, e.g.
#   %ag = bf16[8,128]{1,0} all-gather(...)  or  (bf16[..], u32[]) all-reduce-start
_COLL_RE = re.compile(
    r"=\s*(?P<shapes>\([^)]*\)|[a-z0-9]+\[[0-9,]*\][^ ]*)\s*"
    r"(?P<op>all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute|ragged-all-to-all)(?:-start)?\(")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=(\{\{[^}]*\}[^}]*\}|\[\d+,\d+\]<=)")


def _shape_bytes(shapes_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(shapes_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _group_size(line: str) -> int:
    m = _GROUPS_RE.search(line)
    if not m:
        return 2
    g = m.group(1)
    if g.startswith("{{"):
        first = g[2:].split("}")[0]
        return max(1, first.count(",") + 1)
    # iota form: replica_groups=[G,S]<=[...] -> S members per group
    dims = re.match(r"\[(\d+),(\d+)\]<=", g)
    return int(dims.group(2)) if dims else 2


def collective_bytes(hlo_text: str) -> Dict[str, float]:
    """Per-device wire bytes by collective kind (ring-algorithm model).

    all-reduce: 2(n-1)/n x buffer; all-gather: (n-1)/n x result;
    reduce-scatter: (n-1) x result (operand = n x result);
    all-to-all: (n-1)/n x buffer; collective-permute: 1 x buffer.
    """
    out: Dict[str, float] = {}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        op = m.group("op")
        size = _shape_bytes(m.group("shapes"))
        n = _group_size(line)
        if n <= 1:
            continue
        if op == "all-reduce":
            wire = 2.0 * (n - 1) / n * size
        elif op == "all-gather":
            wire = (n - 1) / n * size
        elif op == "reduce-scatter":
            wire = float(n - 1) * size
        elif op in ("all-to-all", "ragged-all-to-all"):
            wire = (n - 1) / n * size
        else:  # collective-permute
            wire = float(size)
        out[op] = out.get(op, 0.0) + wire
    out["total"] = sum(v for k, v in out.items() if k != "total")
    return out


@dataclasses.dataclass
class RooflineTerms:
    """All quantities are PER DEVICE: ``compiled.cost_analysis()`` describes
    the SPMD-partitioned per-partition module (verified: num_partitions=256
    in the entry layout, flops scale with 1/partitions)."""

    flops: float                 # per-device HLO flops
    hbm_bytes: float             # per-device bytes accessed
    coll_bytes: float            # per-device wire bytes
    n_devices: int
    t_compute: float = 0.0
    t_memory: float = 0.0
    t_collective: float = 0.0
    bottleneck: str = ""
    model_flops: float = 0.0     # whole-step model flops (all devices)
    useful_ratio: float = 0.0    # model_flops / (flops * n_devices)
    coll_breakdown: Dict[str, float] = dataclasses.field(default_factory=dict)

    def finalize(self) -> "RooflineTerms":
        self.t_compute = self.flops / PEAK_FLOPS
        self.t_memory = self.hbm_bytes / HBM_BW
        self.t_collective = self.coll_bytes / ICI_BW
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        self.bottleneck = max(terms, key=terms.get)
        if self.model_flops:
            self.useful_ratio = self.model_flops / max(
                self.flops * self.n_devices, 1.0)
        return self


def raw_costs(compiled, hlo_text: str) -> Dict[str, float]:
    """Per-device (flops, bytes, collective bytes + breakdown) of one
    compiled executable — no loop-body correction (see dryrun probes)."""
    cost = compiled.cost_analysis()
    coll = collective_bytes(hlo_text)
    return {
        "flops": float(cost.get("flops", 0.0)),
        "hbm_bytes": float(cost.get("bytes accessed", 0.0)),
        "coll_bytes": coll["total"],
        "coll_breakdown": coll,
    }


def analyze(compiled, hlo_text: str, n_devices: int,
            model_flops: float = 0.0) -> RooflineTerms:
    c = raw_costs(compiled, hlo_text)
    return RooflineTerms(
        flops=c["flops"], hbm_bytes=c["hbm_bytes"],
        coll_bytes=c["coll_bytes"], n_devices=n_devices,
        model_flops=model_flops, coll_breakdown=c["coll_breakdown"],
    ).finalize()


def from_probes(c1: Dict, c2: Dict, k1: int, k2: int, L: int,
                n_devices: int, model_flops: float = 0.0) -> RooflineTerms:
    """Linear depth-extrapolation of two shallow UNROLLED probe lowerings.

    Scanned (deploy) programs hide per-layer cost inside a while body that
    HloCostAnalysis counts once; fully unrolled programs are cost-exact but
    compile in O(L) (minutes at 256 devices).  For a homogeneous stack,
    cost(L) is affine in L, so two shallow unrolled probes k1 < k2 recover
    slope + intercept exactly:  cost(L) = c1 + (c2-c1)/(k2-k1) * (L-k1).
    """
    def extrap(a, b):
        return a + (b - a) / (k2 - k1) * (L - k1)

    coll = {k: extrap(c1["coll_breakdown"].get(k, 0.0),
                      c2["coll_breakdown"].get(k, 0.0))
            for k in set(c1["coll_breakdown"]) | set(c2["coll_breakdown"])}
    return RooflineTerms(
        flops=extrap(c1["flops"], c2["flops"]),
        hbm_bytes=extrap(c1["hbm_bytes"], c2["hbm_bytes"]),
        coll_bytes=extrap(c1["coll_bytes"], c2["coll_bytes"]),
        n_devices=n_devices, model_flops=model_flops,
        coll_breakdown=coll,
    ).finalize()


def model_flops_for(cfg, shape) -> float:
    """MODEL_FLOPS = 6 N D (dense) / 6 N_active D (MoE) per step; decode
    steps process one token per sequence."""
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * tokens
    return 2.0 * n_active * shape.global_batch        # decode: 1 tok/seq
