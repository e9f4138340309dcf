"""Operations and bytes the work needs, from shapes and routing.

Counts what the routed tokens require, not what the program's container
shapes make it do: a projection call needs the packed codes, scales and
zeros of the experts that received at least one live token, the
compensator factors at each expert's TRUE rank only for experts that
hold a top-n token, and the activations of the live assignments.  A
program that skips idle experts or drops rank padding therefore moves
toward 100% of its roofline; one that reads every expert at the padded
rank on every step reads as far from it.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Sequence, Tuple

import numpy as np

PROJECTIONS = ("w1", "w3", "w2")


@dataclasses.dataclass(frozen=True)
class Geometry:
    """The sizes that the counts below need, read from a configuration
    file by its model's ``geometry``."""
    layers: int
    d_model: int
    d_expert: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    vocab: int
    num_experts: int
    top_k: int
    top_n: int
    bits: int
    group_size: int
    factor_bits: int
    scale_bytes: int = 4        # scale and zero are stored as float32
    factor_scale_bytes: int = 4  # per-rank factor scales, float32
    act_bytes: int = 2          # the kernel's input rows are bf16
    out_bytes: int = 4          # and its output rows float32

    def shape(self, proj: str) -> Tuple[int, int]:
        d, f = self.d_model, self.d_expert
        return (f, d) if proj == "w2" else (d, f)


def weight_bytes(g: Geometry, proj: str) -> int:
    """One expert's packed codes plus its scales and zeros."""
    k, n = g.shape(proj)
    return k * n * g.bits // 8 + 2 * (k // g.group_size) * n * g.scale_bytes


def factor_bytes(g: Geometry, proj: str, rank) -> np.ndarray:
    """One expert's compensator U (K x r), V (r x N) and their per-rank
    scales, at rank ``rank`` (array-valued)."""
    k, n = g.shape(proj)
    r = np.asarray(rank, np.int64)
    return r * (k + n) * g.factor_bits // 8 + 2 * r * g.factor_scale_bytes


def assignment_counts(ids: np.ndarray, num_experts: int, top_n: int
                      ) -> Tuple[np.ndarray, np.ndarray]:
    """``ids``: (..., S, k) routed expert ids, -1 for a slot with no live
    token.  Returns (rows, rows_n), each (..., E): live assignments per
    expert, and those among each token's first ``top_n`` experts."""
    e = np.arange(num_experts)
    hot = ids[..., None] == e                       # (..., S, k, E)
    rows = hot.sum(axis=(-3, -2))
    rows_n = hot[..., :top_n, :].sum(axis=(-3, -2))
    return rows, rows_n


def projection_work(g: Geometry, proj: str, rows: np.ndarray,
                    rows_n: np.ndarray, ranks: Sequence[int]
                    ) -> Tuple[np.ndarray, np.ndarray]:
    """(flops, bytes) one call of the fused expert kernel needs for one
    projection, for every leading index of ``rows`` (..., E)."""
    k, n = g.shape(proj)
    r = np.asarray(ranks, np.int64)
    rows = rows.astype(np.int64)
    rows_n = rows_n.astype(np.int64)
    comp = (rows_n > 0) & (r > 0)
    nbytes = ((rows > 0) * weight_bytes(g, proj)
              + comp * factor_bytes(g, proj, r)).sum(-1)
    nbytes = nbytes + rows.sum(-1) * (k * g.act_bytes + n * g.out_bytes)
    flops = (2 * rows * k * n + 2 * rows_n * r * (k + n)).sum(-1)
    return flops.astype(np.float64), nbytes.astype(np.float64)


def kernel_roofline_s(g: Geometry, trace: np.ndarray,
                      ranks: Sequence[Dict[str, Sequence[int]]],
                      peaks: Dict) -> Dict[str, float]:
    """Least device time of the fused expert kernel calls of a run of
    decode steps.

    ``trace``: (steps, layers, S, k) routed ids with -1 on slots that
    held no live token; ``ranks[l][proj]``: the E true ranks.  Each
    (step, layer, projection) is one kernel call, bound by the larger
    of its operations over peak FLOP/s and its bytes over HBM
    bandwidth."""
    flops_peak = peaks["bf16_flops_per_s"]
    bw = peaks["hbm_bytes_per_s"]
    total = {"flops": 0.0, "bytes": 0.0, "roofline_s": 0.0, "calls": 0}
    for layer in range(trace.shape[1]):
        rows, rows_n = assignment_counts(trace[:, layer], g.num_experts,
                                         g.top_n)
        for proj in PROJECTIONS:
            f, b = projection_work(g, proj, rows, rows_n,
                                   ranks[layer][proj])
            tf, tb = f / flops_peak, b / bw
            total["flops"] += float(f.sum())
            total["bytes"] += float(b.sum())
            total["roofline_s"] += float(np.maximum(tf, tb).sum())
            total["calls"] += int(f.size)
    return total


def container_bytes(g: Geometry, pad_rank: int, slots: int) -> float:
    """Bytes one decode step's kernel calls read when every expert of
    every layer is read whole at the padded rank for every slot: what
    the container shapes make the kernel do, for comparison only."""
    per_layer = 0
    e = g.num_experts
    for proj in PROJECTIONS:
        k, n = g.shape(proj)
        per_layer += e * (weight_bytes(g, proj)
                          + int(factor_bytes(g, proj, pad_rank)))
        per_layer += e * slots * (k * g.act_bytes + n * g.out_bytes)
    return float(per_layer * g.layers)
