"""Offline compression pipeline (paper §3.1): HQQ quantize -> kurtosis ->
rank allocation -> one-time SVD -> packed artifact.

Operates on *expert stacks*: a (E, K, N) weight tensor holding one
projection (w1/w2/w3) for all E experts of a layer.  Dense models use E=1
stacks (the degenerate static quantize-then-compensate form — see
DESIGN.md §5).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..config import QuantConfig
from .compensator import _sym_quant_cols
from .hqq import hqq_params
from .kurtosis import allocate_ranks, kurtosis, uniform_ranks
from .quantize import (QuantizedTensor, dequantize, factor_wire_bytes,
                       pack_bits, packed_nbytes, quant_wire_bytes,
                       quantize_with_params, unpack_bits)


@partial(jax.tree_util.register_dataclass,
         data_fields=("planes", "scale", "zero", "u", "v", "u_scale", "v_scale"),
         meta_fields=("bits", "group_size", "shape", "ranks", "pad_rank",
                      "factor_bits", "expert_bits"))
@dataclass
class CompressedExpertStack:
    """Quantized weights + padded low-rank compensators for E experts.

    planes[i]: (E, K//c_i, N) uint8;  scale/zero: (E, K//G, N) f32
    u: (E, K, R) int8/bf16;  v: (E, R, N);  R = pad_rank
    ranks: per-expert TRUE ranks (tuple, static) for bandwidth accounting.

    Heterogeneous precision (calibrated allocation): ``bits`` is the
    bit-plane CONTAINER width shared by the stacked layout, while
    ``expert_bits[e]`` is expert e's true quantization width (codes fit
    in the container; scale/zero were fit at the true width, so the
    dequant math is bit-exact) — the same container-vs-wire idiom as the
    sub-byte compensator factors in an int8 container.  ``expert_bits``
    is None for uniform stacks (every expert at ``bits``).
    """
    planes: Tuple[jax.Array, ...]
    scale: jax.Array
    zero: jax.Array
    u: jax.Array
    v: jax.Array
    u_scale: jax.Array
    v_scale: jax.Array
    bits: int
    group_size: int
    shape: Tuple[int, int, int]        # (E, K, N)
    ranks: Tuple[int, ...]
    pad_rank: int
    factor_bits: int
    expert_bits: Optional[Tuple[int, ...]] = None

    # -- helpers ----------------------------------------------------------
    def expert_qt(self, e: int) -> QuantizedTensor:
        """Expert e's packed tensor at the CONTAINER width (unpacking
        semantics); wire accounting must use :meth:`bits_of` /
        :meth:`expert_wire_bytes`, not this view's ``nbytes_packed``."""
        return QuantizedTensor(tuple(p[e] for p in self.planes),
                               self.scale[e], self.zero[e],
                               self.bits, self.group_size, self.shape[1:])

    def dequantize_all(self, dtype=jnp.float32) -> jax.Array:
        """(E, K, N) dequantized (no compensation).

        E is taken from the runtime leaves (inside shard_map the stack
        carries the LOCAL expert slice, not the global count in ``shape``).
        """
        _, K, N = self.shape
        E = self.scale.shape[0]
        q = jax.vmap(lambda *pl: unpack_bits(tuple(pl), self.bits))(*self.planes)
        g = q.astype(jnp.float32).reshape(E, K // self.group_size,
                                          self.group_size, N)
        w = (g - self.zero[:, :, None, :]) * self.scale[:, :, None, :]
        return w.reshape(E, K, N).astype(dtype)

    def compensation_all(self, dtype=jnp.float32) -> jax.Array:
        """(E, K, N) dense U V term for every expert."""
        u = self.u.astype(jnp.float32) * self.u_scale
        v = self.v.astype(jnp.float32) * self.v_scale
        return jnp.einsum("ekr,ern->ekn", u, v).astype(dtype)

    # -- bandwidth accounting (bytes on the wire) --------------------------
    def bits_of(self, e: int) -> int:
        """Expert e's TRUE quantization width (wire accounting)."""
        return self.bits if self.expert_bits is None else self.expert_bits[e]

    def expert_wire_bytes(self, e: int, compensated: bool) -> int:
        _, K, N = self.shape
        b = quant_wire_bytes(self.bits_of(e), K, N, self.group_size)
        if compensated:
            b += factor_wire_bytes(self.ranks[e], K, N, self.factor_bits)
        return b

    @property
    def fp16_wire_bytes(self) -> int:
        _, K, N = self.shape
        return K * N * 2


def whiten_vector(moment: np.ndarray, eps: float = 1e-6) -> np.ndarray:
    """(K,) scale-free whitening weights sqrt(m / mean(m) + eps) from a
    calibrated input second-moment diagonal.  THE single definition of
    the whitening recipe — shared by the compensator SVD below and the
    budget allocator's error model (``calib/allocate.py``), so the
    allocator optimizes exactly what compression realizes."""
    m = np.asarray(moment, np.float64).reshape(-1)
    m = m / max(float(m.mean()), 1e-30)
    return np.sqrt(m + eps)


def _host_svd(a: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Thin SVD by the host's LAPACK (``gesdd``), returned as
    uncommitted arrays on the default device, as ``jnp.linalg.svd``
    would return them (committed outputs would give the params another
    jit signature and the decode loop a second compile).

    XLA's TPU SVD of one expert residual at Mixtral width (4096 x 14336)
    compiles into a 400 MB program and ran a one-chip host out of its
    40 GiB; the host takes about 15 s.  One routine on every platform
    also keeps the compensators independent of the accelerator.  A wide
    matrix is factored through its transpose, which LAPACK does about a
    third faster."""
    import scipy.linalg
    h = np.asarray(a, np.float32)
    wide = h.shape[0] < h.shape[1]
    uu, ss, vt = scipy.linalg.svd(h.T if wide else h, full_matrices=False,
                                  lapack_driver="gesdd", check_finite=False)
    if wide:
        uu, vt = vt.T, uu.T
    return jnp.asarray(uu), jnp.asarray(ss), jnp.asarray(vt)


def whitened_residual_factors(resid: jax.Array, rank: int, pad_rank: int,
                              moment: Optional[np.ndarray] = None,
                              eps: float = 1e-6
                              ) -> Tuple[jax.Array, jax.Array]:
    """Rank-``rank`` factors (u (K, R), v (R, N)) of one expert's quant
    residual, optionally whitened by the calibrated input second moment.

    ``moment`` is the (K,) diagonal of E[x x^T] over the calibration
    tokens routed to this expert.  The SVD then truncates in the
    activation-weighted norm ||diag(sqrt(m)) (R - UV)||_F — rank goes to
    the input directions the router actually exercises — while the
    STORED factors still approximate R itself (U is un-whitened), so the
    runtime restoration math is unchanged.  ``moment=None`` is the
    paper's plain weight-space SVD, bit-identical to the previous
    behaviour.
    """
    if moment is None:
        white = None
        r_in = resid
    else:
        white = jnp.asarray(whiten_vector(moment, eps), jnp.float32)
        r_in = resid * white[:, None]
    uu, ss, vt = _host_svd(r_in)
    sq = jnp.sqrt(ss[:pad_rank])
    uu = uu[:, :pad_rank] * sq[None, :]
    vv = vt[:pad_rank, :] * sq[:, None]
    if white is not None:
        uu = uu / white[:, None]
    mask = (jnp.arange(pad_rank) < rank)
    return uu * mask[None, :], vv * mask[:, None]


def compress_expert_stack(w: jax.Array, qcfg: QuantConfig,
                          ranks: Optional[np.ndarray] = None,
                          bits: Optional[np.ndarray] = None,
                          moments: Optional[np.ndarray] = None
                          ) -> Tuple[CompressedExpertStack, Dict]:
    """Full offline pipeline for one (E, K, N) projection stack.

    ``ranks``/``bits``: optional per-expert allocations from a
    ``CompressionPlan`` (calibrated heterogeneous precision); ``bits``
    None means uniform ``qcfg.bits``.  ``moments``: optional (E, K)
    calibrated input second-moment diagonals — compensator SVDs are then
    computed in the activation-weighted norm (see
    :func:`whitened_residual_factors`).

    Returns the packed artifact plus a report dict (kurtosis, ranks,
    bits, residual norms before/after compensation) used by benchmarks.

    Work runs one expert at a time: no (E, K, N) float32 temporary is
    ever formed, so a stack whose dense form barely fits the device
    still compresses.
    """
    E, K, N = w.shape

    def w32(e):
        return jnp.asarray(w[e], jnp.float32)

    # group_size <= 0 means per-channel (one group spanning all of K) —
    # the coarse granularity at which RTN/GPTQ-class int2 collapses
    if qcfg.group_size <= 0 or qcfg.group_size > K:
        qcfg = dataclasses.replace(qcfg, group_size=K)

    # 1. per-expert kurtosis (paper §3.1 step 1)
    kurt = np.array([float(kurtosis(w32(e))) for e in range(E)])

    # 2. HQQ quantization (paper §3.1 step 2; done before allocation so the
    # 'error' strategy can rank by measured residuals).  Heterogeneous
    # per-expert bits share one bit-plane container at the layer max
    # width; each expert's scale/zero are fit at its TRUE width, which
    # stays the wire-accounting width.
    if bits is None:
        expert_bits = np.full((E,), qcfg.bits, np.int64)
    else:
        expert_bits = np.asarray(bits, np.int64).reshape(E)
    store_bits = int(expert_bits.max())

    def _q(we, b):
        s, z = hqq_params(we, b, qcfg.group_size, qcfg.hqq_iters,
                          qcfg.hqq_p, qcfg.hqq_beta, qcfg.hqq_beta_scale)
        return quantize_with_params(we, s, z, b, qcfg.group_size,
                                    store_bits=store_bits)

    qts = [_q(w32(e), int(expert_bits[e])) for e in range(E)]

    # 3. rank allocation: kurtosis proxy (paper) | measured residual
    # (beyond-paper) | uniform (ablation)
    max_rank = min(K, N)
    strategy = qcfg.rank_alloc if qcfg.kurtosis_guided else "uniform"
    if ranks is None:
        if strategy == "error":
            from .quantize import quant_error
            errs = np.array([float(quant_error(w32(e), qts[e]))
                             for e in range(E)])
            ranks = allocate_ranks(errs, qcfg.rank_budget, qcfg.rank_buckets,
                                   max_rank=max_rank)
        elif strategy == "kurtosis":
            ranks = allocate_ranks(kurt, qcfg.rank_budget, qcfg.rank_buckets,
                                   max_rank=max_rank)
        else:
            r = (qcfg.uniform_rank if qcfg.uniform_rank is not None
                 else qcfg.rank_budget)
            ranks = uniform_ranks(E, r, qcfg.rank_buckets)
    ranks = np.minimum(np.asarray(ranks, np.int64), max_rank)
    pad_rank = int(max(int(ranks.max()), 1))
    planes = tuple(jnp.stack([qt.planes[i] for qt in qts])
                   for i in range(len(qts[0].planes)))
    scale = jnp.stack([qt.scale for qt in qts])
    zero = jnp.stack([qt.zero for qt in qts])

    # 4. residual SVD at the allocated rank (activation-whitened when
    # calibrated moments are given), zero-padded to pad_rank; a rank-0
    # expert's factors are exact zeros and need no SVD
    us, vs, uss, vss = [], [], [], []
    err_quant, err_comp = [], []
    for e in range(E):
        r = int(ranks[e])
        we = w32(e)
        resid = we - dequantize(qts[e])
        if r > 0:
            uu, vv = whitened_residual_factors(
                resid, r, pad_rank,
                moment=None if moments is None else moments[e])
        else:
            uu = jnp.zeros((K, pad_rank), jnp.float32)
            vv = jnp.zeros((pad_rank, N), jnp.float32)
        if qcfg.factor_bits >= 16:
            qu, qv = uu.astype(jnp.bfloat16), vv.astype(jnp.bfloat16)
            su = jnp.ones((1, pad_rank), jnp.float32)
            sv = jnp.ones((pad_rank, 1), jnp.float32)
        else:
            qu, su = _sym_quant_cols(uu, qcfg.factor_bits, axis=0)
            qv, sv = _sym_quant_cols(vv, qcfg.factor_bits, axis=1)
        us.append(qu); vs.append(qv); uss.append(su); vss.append(sv)
        # 5. report: residual norms before/after compensation
        comp = (qu.astype(jnp.float32) * su) @ (qv.astype(jnp.float32) * sv)
        nw = jnp.maximum(jnp.linalg.norm(we), 1e-12)
        err_quant.append(float(jnp.linalg.norm(resid) / nw))
        err_comp.append(float(jnp.linalg.norm(resid - comp) / nw))

    hetero = bool((expert_bits != expert_bits[0]).any()) \
        or int(expert_bits[0]) != store_bits
    stack = CompressedExpertStack(
        planes=planes, scale=scale, zero=zero,
        u=jnp.stack(us), v=jnp.stack(vs),
        u_scale=jnp.stack(uss), v_scale=jnp.stack(vss),
        bits=store_bits, group_size=qcfg.group_size, shape=(E, K, N),
        ranks=tuple(int(r) for r in ranks), pad_rank=pad_rank,
        factor_bits=qcfg.factor_bits,
        expert_bits=tuple(int(b) for b in expert_bits) if hetero else None)
    report = {
        "kurtosis": kurt,
        "ranks": np.asarray(ranks),
        "bits": np.asarray(expert_bits),
        "rel_err_quant": np.asarray(err_quant),
        "rel_err_comp": np.asarray(err_comp),
    }
    return stack, report


def compress_ffn_weights(w1: jax.Array, w2: jax.Array, w3: jax.Array,
                         qcfg: QuantConfig, allocation=None, stats=None):
    """Compress the three projections of a (shared or routed) FFN stack.

    Rank allocation runs per projection pool (paper computes kurtosis per
    projection matrix w1/w2/w3 and budgets over the N experts of a pool)
    unless ``allocation`` (one layer of a ``calib.CompressionPlan``)
    pins per-expert bits and per-(projection, expert) ranks from the
    offline budget allocator.  ``stats`` (a ``calib.LayerCalibStats``)
    supplies the calibrated input second moments that make the
    compensator SVDs activation-weighted: w1/w3 whiten by the MoE-layer
    input moment, w2 by the expert-hidden moment.
    """
    out, reports = {}, {}
    for name, w in (("w1", w1), ("w2", w2), ("w3", w3)):
        if w is None:
            continue
        kw = {}
        if allocation is not None:
            kw["bits"] = allocation.bits
            kw["ranks"] = allocation.ranks[name]
        if stats is not None:
            kw["moments"] = stats.moment_for(name)
        stack, rep = compress_expert_stack(w, qcfg, **kw)
        out[name] = stack
        reports[name] = rep
    return out, reports
