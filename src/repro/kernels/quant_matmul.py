"""Pallas TPU kernel: matmul against bit-plane-packed quantized weights.

y = x @ dequant(Wq).  The packed planes are streamed HBM->VMEM at their
native sub-byte width (bits/8 bytes per weight), unpacked in VMEM with
uniform shift/mask lanes, dequantized per quantization group, and fed to
the MXU tile-by-tile.  This is the TPU-native analogue of the paper's
"transfer low-bit experts over PCIe": the HBM term of the decode roofline
drops by ~16/bits on every expert matmul.

An optional fused epilogue adds the router-guided low-rank compensation
``+ xu @ V`` (paper §3.2) on the final K step, so the compensated result
never round-trips through HBM.

Grid: (M/bm, N/bn, K/bk) with a VMEM f32 accumulator; K is the innermost
(sequential) dimension.  Constraints: bk % PACK_BLOCK == 0 (block-local
packing), bk % group_size == 0 (whole quant groups per tile).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.quantize import PACK_BLOCK, PLANES


def _unpack_tile(plane_vals, bits: int, bk: int, bn: int,
                 width=None) -> jax.Array:
    """Unpack loaded plane tiles -> (bk, bn) int32 codes (VMEM, vectorized).

    Bytes are widened to int32 before any shift or mask: Mosaic has no
    sub-word shifts and no uint8 -> float cast.  ``width`` (traced
    scalar, optional) is the expert's TRUE bit width: planes whose bit
    offset lies at or above it carry no information in a wider shared
    container and are masked out.
    """
    out = None
    for (p, off), pk in zip(PLANES[bits], plane_vals):
        c = 8 // p
        mask = (1 << p) - 1
        blocks = pk.astype(jnp.int32).reshape(bk // PACK_BLOCK,
                                              PACK_BLOCK // c, bn)
        chunks = [(blocks >> (j * p)) & mask for j in range(c)]
        sub = jnp.stack(chunks, axis=1).reshape(bk, bn) << off
        if width is not None:
            sub = jnp.where(width > off, sub, 0)
        out = sub if out is None else out | sub
    return out


def _dequant_tile(codes: jax.Array, scale, zero, group_size: int,
                  bk: int, bn: int) -> jax.Array:
    g = codes.astype(jnp.float32).reshape(bk // group_size, group_size, bn)
    w = (g - zero[:, None, :]) * scale[:, None, :]
    return w.reshape(bk, bn)


def _qmm_kernel(bits, group_size, n_k, bk, bn, fuse_lowrank, x_ref, *refs):
    """refs: [planes..., scale, zero, (xu, v)] + [out] + [acc scratch]."""
    n_planes = len(PLANES[bits])
    planes = refs[:n_planes]
    scale_ref, zero_ref = refs[n_planes], refs[n_planes + 1]
    pos = n_planes + 2
    if fuse_lowrank:
        xu_ref, v_ref = refs[pos], refs[pos + 1]
        pos += 2
    out_ref, acc_ref = refs[pos], refs[pos + 1]

    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    codes = _unpack_tile([p[...] for p in planes], bits, bk, bn)
    w = _dequant_tile(codes, scale_ref[...], zero_ref[...], group_size, bk, bn)
    x = x_ref[...].astype(jnp.float32)
    acc_ref[...] += jnp.dot(x, w, preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _done():
        acc = acc_ref[...]
        if fuse_lowrank:
            # rank-r compensation epilogue: acc += xu @ V (scales pre-folded)
            vd = v_ref[...].astype(jnp.float32)
            acc = acc + jnp.dot(xu_ref[...], vd,
                                preferred_element_type=jnp.float32)
        out_ref[...] = acc.astype(out_ref.dtype)


def _pallas_qmm(x, planes, scale, zero, xu, v, *, bits, group_size,
                bm, bn, bk, out_dtype, interpret):
    m, k = x.shape
    n = scale.shape[-1]
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (m, n, k, bm, bn, bk)
    assert bk % PACK_BLOCK == 0 and bk % group_size == 0
    n_k = k // bk
    fuse = xu is not None

    in_specs = [pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk))]
    in_specs += [pl.BlockSpec((bk // (8 // p), bn), lambda i, j, kk: (kk, j))
                 for p, _ in PLANES[bits]]
    in_specs += [pl.BlockSpec((bk // group_size, bn),
                              lambda i, j, kk: (kk, j))] * 2
    args = [x, *planes, scale, zero]
    if fuse:
        r = xu.shape[-1]
        in_specs += [pl.BlockSpec((bm, r), lambda i, j, kk: (i, 0)),
                     pl.BlockSpec((r, bn), lambda i, j, kk: (0, j))]
        args += [xu, v]

    kernel = functools.partial(_qmm_kernel, bits, group_size, n_k, bk, bn,
                               fuse)
    return pl.pallas_call(
        kernel,
        grid=(m // bm, n // bn, n_k),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name=f"quant_matmul_b{bits}" + ("_lowrank" if fuse else ""),
    )(*args)


@functools.partial(jax.jit, static_argnames=(
    "bits", "group_size", "bm", "bn", "bk", "out_dtype", "interpret"))
def quant_matmul_pallas(x: jax.Array, planes: Tuple[jax.Array, ...],
                        scale: jax.Array, zero: jax.Array, *,
                        bits: int, group_size: int,
                        bm: int = 128, bn: int = 256, bk: int = 512,
                        out_dtype=jnp.float32, interpret: bool = False
                        ) -> jax.Array:
    """x: (M, K) @ packed (K, N) -> (M, N)."""
    return _pallas_qmm(x, planes, scale, zero, None, None, bits=bits,
                       group_size=group_size, bm=bm, bn=bn, bk=bk,
                       out_dtype=out_dtype, interpret=interpret)


@functools.partial(jax.jit, static_argnames=(
    "bits", "group_size", "bm", "bn", "bk", "out_dtype", "interpret"))
def lowrank_comp_matmul_pallas(x: jax.Array, planes: Tuple[jax.Array, ...],
                               scale: jax.Array, zero: jax.Array,
                               xu: jax.Array, v: jax.Array, *,
                               bits: int, group_size: int,
                               bm: int = 128, bn: int = 256, bk: int = 512,
                               out_dtype=jnp.float32, interpret: bool = False
                               ) -> jax.Array:
    """Fused y = x @ dequant(Wq) + xu @ V.

    ``xu`` is the (M, R) rank-space activation ``(x * mask) @ (U * u_scale)
    * v_scale`` computed by the ops wrapper (rank-r, negligible FLOPs);
    ``v`` is the (R, N) int8 code matrix with its scale pre-folded into xu.
    """
    return _pallas_qmm(x, planes, scale, zero, xu, v, bits=bits,
                       group_size=group_size, bm=bm, bn=bn, bk=bk,
                       out_dtype=out_dtype, interpret=interpret)


# ---------------------------------------------------------------------------
# fused expert-stack decode kernel
# ---------------------------------------------------------------------------

def _fused_kernel(bits, group_size, n_k, pad_rank, has_gates,
                  cap_ref, eb_ref, x_ref, *refs):
    """One grid step of the fused decode kernel (see fused_expert_matmul).

    Grid (e, i, j, kk): expert e, token tile i, output tile j, K step kk
    (innermost, sequential).  ``cap_ref`` (1,) / ``eb_ref`` (E,) are the
    scalar-prefetched rank cap and TRUE per-expert widths (SMEM).  refs
    layout:
      [planes..., scale, zero, u, u_scale, v, v_scale, me, (ge,)]
      + [out] + [acc, xu_acc scratch]
    Everything accumulates in f32 VMEM scratch; only the finished
    (bm, bn) gate-weighted tile is ever written back to HBM.
    """
    n_planes = len(PLANES[bits])
    planes = refs[:n_planes]
    pos = n_planes
    scale_ref, zero_ref = refs[pos], refs[pos + 1]
    u_ref, us_ref, v_ref, vs_ref = refs[pos + 2:pos + 6]
    me_ref = refs[pos + 6]
    pos += 7
    if has_gates:
        ge_ref = refs[pos]
        pos += 1
    out_ref, acc_ref, xu_ref = refs[pos], refs[pos + 1], refs[pos + 2]

    e = pl.program_id(0)
    kk = pl.program_id(3)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        xu_ref[...] = jnp.zeros_like(xu_ref)

    # -- dequant at this expert's TRUE width: planes at or above
    # expert_bits[e] are masked out of the unpack (hetero stacks store
    # sub-width codes in a shared container), so the true width is
    # first-class in the kernel rather than silently widened
    x = x_ref[0].astype(jnp.float32)                       # (bm, bk)
    bk, bn = x.shape[1], out_ref.shape[2]
    codes = _unpack_tile([r[0] for r in planes], bits, bk, bn,
                         width=eb_ref[e])
    w = _dequant_tile(codes, scale_ref[0], zero_ref[0], group_size, bk, bn)
    acc_ref[...] += jnp.dot(x, w, preferred_element_type=jnp.float32)

    # -- rank-space compensator activation: (x * me) @ (U * u_scale),
    # accumulated over K alongside the main matmul (j-invariant; cheap
    # rank-R duplicate work per j tile beats an HBM round-trip for xu)
    xm = x * me_ref[0]                                     # (bm, 1) mask
    ud = u_ref[0].astype(jnp.float32) * us_ref[0]          # (bk, R)
    xu_ref[...] += jnp.dot(xm, ud, preferred_element_type=jnp.float32)

    @pl.when(kk == n_k - 1)
    def _done():
        acc = acc_ref[...]
        # traced rank cap: 0/1 mask over the padded rank dim (a plan-row
        # change is data, never a recompile)
        rmask = (jax.lax.broadcasted_iota(jnp.int32, (1, pad_rank), 1)
                 < cap_ref[0]).astype(jnp.float32)
        vd = v_ref[0].astype(jnp.float32) * vs_ref[0]      # (R, bn)
        acc = acc + jnp.dot(xu_ref[...] * rmask, vd,
                            preferred_element_type=jnp.float32)
        if has_gates:
            # top-n combine epilogue: fold the router gate in-kernel so
            # the (E, C, N) buffer leaves as ready-to-scatter partials
            acc = acc * ge_ref[0]
        out_ref[0] = acc.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "bits", "group_size", "bm", "bn", "bk", "out_dtype", "interpret"))
def fused_expert_matmul_pallas(xe: jax.Array, planes: Tuple[jax.Array, ...],
                               scale: jax.Array, zero: jax.Array,
                               u: jax.Array, u_scale: jax.Array,
                               v: jax.Array, v_scale: jax.Array,
                               me: jax.Array, ge: Optional[jax.Array],
                               rank_cap: jax.Array, expert_bits: jax.Array,
                               *, bits: int, group_size: int,
                               bm: int = 8, bn: int = 256, bk: int = 512,
                               out_dtype=jnp.float32, interpret: bool = False
                               ) -> jax.Array:
    """Fused decode-path expert FFN projection over a routed token block.

    One kernel invocation covers every expert of one (layer, projection):

        ye[e] = (xe[e] @ dequant_e(planes_e)            # true-width HQQ
                 + ((xe[e] * me[e]) @ U_e) @ V_e)       # rank-capped comp
                * ge[e]                                 # gate-weighted

    xe: (E, C, K) dispatched tokens;  planes[i]: (E, K//c_i, N)
    scale/zero: (E, K//G, N);  u: (E, K, R);  v: (E, R, N)
    u_scale: (E, 1, R);  v_scale: (E, R, 1)
    me: (E, C, 1) top-n compensation mask;  ge: (E, C, 1) router gates
    (None = unweighted) -- the trailing unit dim keeps the blocks
    Mosaic-legal (a block's last two dims tile by (8, 128) or span the
    array);  rank_cap: (1,) i32 traced plan value;  expert_bits: (E,) i32
    TRUE per-expert widths.  The last two ride scalar prefetch (SMEM).

    The f32 accumulator and the (bm, R) rank-space activation live in
    VMEM scratch for the whole K walk — no intermediate (dequantized
    weight, compensator product, or pre-gate output) ever round-trips
    to HBM.
    """
    e, m, k = xe.shape
    n = scale.shape[-1]
    r = u.shape[-1]
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (e, m, n, k, bm, bn, bk)
    assert bk % PACK_BLOCK == 0 and bk % group_size == 0
    n_k = k // bk
    has_gates = ge is not None

    # index maps take the grid indices, then the scalar-prefetch refs
    def spec(shape, index):
        return pl.BlockSpec(shape, lambda e, i, j, kk, cap, eb:
                            index(e, i, j, kk))

    in_specs = [spec((1, bm, bk), lambda e, i, j, kk: (e, i, kk))]
    in_specs += [spec((1, bk // (8 // p), bn),
                      lambda e, i, j, kk: (e, kk, j))
                 for p, _ in PLANES[bits]]
    in_specs += [spec((1, bk // group_size, bn),
                      lambda e, i, j, kk: (e, kk, j))] * 2
    in_specs += [spec((1, bk, r), lambda e, i, j, kk: (e, kk, 0)),
                 spec((1, 1, r), lambda e, i, j, kk: (e, 0, 0)),
                 spec((1, r, bn), lambda e, i, j, kk: (e, 0, j)),
                 spec((1, r, 1), lambda e, i, j, kk: (e, 0, 0)),
                 spec((1, bm, 1), lambda e, i, j, kk: (e, i, 0))]
    args = [xe, *planes, scale, zero, u, u_scale, v, v_scale, me]
    if has_gates:
        in_specs += [spec((1, bm, 1), lambda e, i, j, kk: (e, i, 0))]
        args += [ge]

    kernel = functools.partial(_fused_kernel, bits, group_size, n_k, r,
                               has_gates)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(e, m // bm, n // bn, n_k),
            in_specs=in_specs,
            out_specs=spec((1, bm, bn), lambda e, i, j, kk: (e, i, j)),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32),
                            pltpu.VMEM((bm, r), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((e, m, n), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name=f"fused_expert_b{bits}" + ("_gated" if has_gates else ""),
    )(rank_cap, expert_bits, *args)
