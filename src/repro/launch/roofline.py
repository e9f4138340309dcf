"""Roofline-term derivation from the compiled dry-run artifact.

Per (arch, shape, mesh) cell:

    compute    = FLOPs_dev / peak_FLOPs_chip
    memory     = bytes_dev / HBM_bw_chip
    collective = wire_bytes_dev / ICI_bw_chip

FLOPs/bytes come from ``compiled.cost_analysis()`` (per-device, post-SPMD).
Collective wire bytes are NOT in cost_analysis: we parse the optimized HLO
(``compiled.as_text()``) and sum shape bytes over every all-gather /
all-reduce / reduce-scatter / all-to-all / collective-permute, applying the
standard ring-transfer factors (all-reduce 2(n-1)/n, gather/scatter
(n-1)/n, permute 1).
"""
from __future__ import annotations

import re
from dataclasses import dataclass, asdict
from typing import Dict, Optional

# TPU v5e-class hardware constants (per chip), per the assignment
PEAK_FLOPS = 197e12          # bf16
HBM_BW = 819e9               # B/s
ICI_BW = 50e9                # B/s per link (aggregate assumption documented)

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
    "f64": 8, "c64": 8, "c128": 16,
}

_COLL_RE = re.compile(
    r"=\s+(?:\()?([a-z0-9]+)\[([0-9,]*)\][^ ]*\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")
_TUPLE_COLL_RE = re.compile(
    r"=\s+\((.+?)\)\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def _group_size(line: str, default: int) -> int:
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    return default


def collective_wire_bytes(hlo_text: str, default_group: int = 16,
                          top: Optional[list] = None) -> Dict[str, float]:
    """Per-device wire bytes by collective kind (ring-transfer factors).

    ``top`` (optional list) collects (wire_bytes, kind, shape) per op for
    bottleneck diagnosis."""
    out: Dict[str, float] = {}
    for line in hlo_text.splitlines():
        if "-start(" not in line and not re.search(
                r"\s(all-reduce|all-gather|reduce-scatter|all-to-all|"
                r"collective-permute)\(", line):
            if not any(k in line for k in
                       ("all-reduce(", "all-gather(", "reduce-scatter(",
                        "all-to-all(", "collective-permute(")):
                continue
        m = _COLL_RE.search(line)
        shapes = []
        kind = None
        if m:
            kind = m.group(3)
            shapes = [(m.group(1), m.group(2))]
        else:
            mt = _TUPLE_COLL_RE.search(line)
            if not mt:
                continue
            kind = mt.group(2)
            shapes = _SHAPE_RE.findall(mt.group(1))
        n = _group_size(line, default_group)
        nbytes = sum(_shape_bytes(dt, dims) for dt, dims in shapes)
        if kind == "all-reduce":
            wire = 2.0 * (n - 1) / max(n, 1) * nbytes
        elif kind in ("all-gather", "all-to-all"):
            wire = (n - 1) / max(n, 1) * nbytes
        elif kind == "reduce-scatter":
            wire = (n - 1) / max(n, 1) * nbytes * n  # operand = result * n
        else:  # collective-permute
            wire = float(nbytes)
        out[kind] = out.get(kind, 0.0) + wire
        out["total"] = out.get("total", 0.0) + wire
        if top is not None:
            top.append((wire, kind,
                        ";".join(f"{d}[{s}]" for d, s in shapes)))
    return out


@dataclass
class RooflineTerms:
    arch: str
    shape: str
    mesh: str
    flops_dev: float
    bytes_dev: float
    wire_bytes_dev: float
    t_compute: float
    t_memory: float
    t_collective: float
    dominant: str
    model_flops_global: float
    useful_ratio: float          # MODEL_FLOPS / global HLO FLOPs
    mem_per_device: Optional[float] = None
    note: str = ""

    def to_dict(self):
        return asdict(self)


def derive_terms(arch: str, shape_name: str, mesh_name: str, *,
                 cost: Dict, hlo_text: str, n_devices: int,
                 model_flops_global: float,
                 mem_per_device: Optional[float] = None,
                 default_group: int = 16,
                 wire_override: Optional[float] = None) -> RooflineTerms:
    flops = float(cost.get("flops", 0.0))
    nbytes = float(cost.get("bytes accessed", 0.0))
    wire = (wire_override if wire_override is not None else
            collective_wire_bytes(hlo_text, default_group).get("total", 0.0))
    t_c = flops / PEAK_FLOPS
    t_m = nbytes / HBM_BW
    t_x = wire / ICI_BW
    dominant = max((("compute", t_c), ("memory", t_m), ("collective", t_x)),
                   key=lambda kv: kv[1])[0]
    hlo_global = flops * n_devices
    ratio = model_flops_global / hlo_global if hlo_global else 0.0
    return RooflineTerms(arch, shape_name, mesh_name, flops, nbytes, wire,
                         t_c, t_m, t_x, dominant, model_flops_global, ratio,
                         mem_per_device)


# ---------------------------------------------------------------------------
# kernel tile tables (seed candidates for kernels/autotune.py)
# ---------------------------------------------------------------------------

VMEM_BYTES = 16 * 2 ** 20    # per-core VMEM (v5e-class); tiles must fit
VMEM_BUDGET = 0.7            # leave headroom for double buffering

# MXU/VPU-aligned tile menus: sublane multiples for the token dim (decode
# blocks are tiny), lane multiples for N, PACK_BLOCK multiples for K
BM_CANDIDATES = (8, 16, 32, 64, 128)
BN_CANDIDATES = (128, 256, 512)
BK_CANDIDATES = (128, 256, 512, 1024)


def fused_tile_vmem_bytes(bm: int, bn: int, bk: int, bits: int,
                          group_size: int, rank: int) -> int:
    """Resident VMEM footprint of one fused-kernel grid step: x tile,
    packed planes, scale/zero, compensator factors, f32 accumulator and
    rank-space scratch (see ``kernels/quant_matmul.py::_fused_kernel``)."""
    plane_b = _packed_nbytes(bits, bk, bn)
    scales_b = 2 * (bk // group_size) * bn * 4
    factors_b = bk * rank + rank * bn + rank * 4 + rank * 4
    return (bm * bk * 4 + plane_b + scales_b + factors_b
            + bm * bn * 4 + bm * rank * 4 + bm * bn * 4)


def _plane_widths(bits: int):
    from ..core.quantize import PLANES
    return tuple(p for p, _ in PLANES[bits])


def _packed_nbytes(bits: int, k: int, n: int) -> int:
    from ..core.quantize import packed_nbytes
    return packed_nbytes(bits, k, n)


def fused_tile_candidates(m: int, k: int, n: int, bits: int,
                          group_size: int, rank: int):
    """Roofline-derived (bm, bn, bk) candidates for the fused decode
    kernel, best-first.

    Ranking: prefer the largest K tile (amortizes the sequential-grid
    revisits of x), then the largest N tile that keeps the step under
    the VMEM budget; bm clamps to the token block (decode C is tiny, so
    the small-m preset bm=8 dominates serving shapes).  This static
    table seeds the autotuner; on-device timing can reorder it."""
    out = []
    for bm in BM_CANDIDATES:
        if bm > max(8, m):
            continue
        for bn in BN_CANDIDATES:
            if bn > n:
                continue
            for bk in BK_CANDIDATES:
                if bk > k or bk % group_size or bk % 64:
                    continue
                if (fused_tile_vmem_bytes(bm, bn, bk, bits, group_size, rank)
                        > VMEM_BYTES * VMEM_BUDGET):
                    continue
                out.append((bm, bn, bk))
    # best-first: big K, then big N, then the smallest viable bm
    out.sort(key=lambda t: (-t[2], -t[1], t[0]))
    return out


def fused_hbm_bytes(e: int, m: int, k: int, n: int, bits: int,
                    group_size: int, rank: int, bm: int, bn: int,
                    bk: int) -> int:
    """Analytic HBM traffic of one fused-kernel invocation (per expert
    stack), tile-multiplicity aware.

    The grid is (E, m/bm, n/bn, k/bk) with N and K sequential; every
    operand block is fetched once per grid step that maps to it, except
    where its index map is constant across consecutive steps (Mosaic
    elides those refetches; the rest of this count is an upper bound):

    - x:        (bm, bk) per (i, j, kk)   -> m*k*4      x  n/bn
    - planes:   packed bits per (j, kk)   -> packed(k,n) x  m/bm
    - scale/zero: f32 per (j, kk)         -> 2*(k/g)*n*4 x m/bm
    - U (int8): (bk, r) per (i, kk), only on the first N tile, whose
      rank-space activation the later N tiles reuse -> k*r x m/bm
    - V (int8): (r, bn) per (i, j)        -> r*n        x  m/bm
    - me/gates: (bm,) per (i, j)          -> m*4        x  n/bn (x2)
    - out:      written once              -> m*n*4

    The unfused op-sequence additionally round-trips the dequantized
    weights (k*n*4), the rank-space activation, and the ungated output
    through HBM — ``benchmarks/bench_kernels.py`` measures that side
    from ``cost_analysis`` of the compiled XLA sequence and reports the
    reduction against this bound.
    """
    from ..core.quantize import packed_nbytes
    mi, nj = -(-m // bm), -(-n // bn)
    planes_b = packed_nbytes(bits, k, n)
    scales_b = 2 * (k // group_size) * n * 4
    x_b = m * k * 4 * nj
    u_b = k * rank * mi
    v_b = rank * n * mi
    f_scales_b = rank * 4 * 2 * mi * nj
    masks_b = 2 * m * 4 * nj
    out_b = m * n * 4
    per_expert = (x_b + planes_b * mi + scales_b * mi + u_b + v_b
                  + f_scales_b + masks_b + out_b)
    return e * per_expert


def model_flops(cfg, shape, active_params: int) -> float:
    """MODEL_FLOPS = 6·N·D (train) or 2·N_active·D (inference)."""
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * active_params * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * active_params * tokens
    tokens = shape.global_batch  # one decode step
    return 2.0 * active_params * tokens
