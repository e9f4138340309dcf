"""Public jit'd wrappers around the Pallas kernels.

Dispatch policy (``impl``):
  'auto'              pallas on TPU, ref elsewhere (CPU dry-run lowers real
                      einsum FLOPs rather than interpreter scaffolding)
  'pallas'            compiled Mosaic kernel (TPU)
  'pallas_interpret'  kernel body executed by the Pallas interpreter on CPU
                      (used by tests to validate the kernel against ref)
  'ref'               pure-jnp oracle

Wrappers pad M to the tile size and slice back, fold the compensator factor
scales into the rank-space activation, and expose QuantizedTensor /
CompressedExpertStack-level entry points.
"""
from __future__ import annotations

import functools
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..core.quantize import QuantizedTensor
from . import ref as ref_ops
from .quant_matmul import (fused_expert_matmul_pallas,
                           lowrank_comp_matmul_pallas, quant_matmul_pallas)

_ENV = "REPRO_KERNEL_IMPL"


def default_impl() -> str:
    env = os.environ.get(_ENV)
    if env and env != "auto":           # 'auto' = platform-based selection
        return env
    return "pallas" if jax.default_backend() == "tpu" else "ref"


IMPLS = ("pallas", "pallas_interpret", "ref")


def resolve_impl(impl: Optional[str] = None) -> str:
    """Resolve an ``impl`` request ('auto'/None, 'pallas', 'pallas_interpret',
    'ref') to the concrete implementation that will run, honouring the
    ``REPRO_KERNEL_IMPL`` env override.  This is the single dispatch policy
    shared by the kernel wrappers below and the model-level ExpertBackend."""
    impl = impl or "auto"
    resolved = default_impl() if impl == "auto" else impl
    if resolved not in IMPLS:
        raise ValueError(
            f"unknown kernel impl {resolved!r} (from "
            f"{'$' + _ENV if impl == 'auto' else 'impl argument'}); "
            f"expected one of {('auto',) + IMPLS}")
    return resolved


_pick = resolve_impl


def _pad_m(x: jax.Array, bm: int):
    """Right-pad the token dim to a multiple of ``bm``.  Callers pair
    this with the small-m tile sizes from ``_tile_sizes`` /
    ``autotune.choose_tiles`` so a single decode token pads to the 8-row
    sublane minimum, not a full 128-row tile per expert."""
    m = x.shape[0]
    pm = (-m) % bm
    if pm:
        x = jnp.pad(x, ((0, pm), (0, 0)))
    return x, m


def _tile_sizes(m: int, k: int, n: int, bm: int, bn: int, bk: int):
    """Clamp tiles to the problem and keep pack/group divisibility.

    ``bm`` clamps to the token count rounded up to the f32 sublane
    minimum (8): decode-sized blocks (m <= 8) run the bm=8 preset
    instead of padding m into a 128-row tile, and ragged m stays
    sublane-aligned so the compiled kernel's tiles are MXU-admissible.
    """
    bm = max(8, min(bm, -(-m // 8) * 8))
    bk = min(bk, k)
    bn = min(bn, n)
    while k % bk:
        bk //= 2
    while n % bn:
        bn //= 2
    return bm, bn, bk


def quant_matmul(x: jax.Array, qt: QuantizedTensor, *,
                 impl: Optional[str] = None, out_dtype=None,
                 bm: int = 128, bn: int = 256, bk: int = 512) -> jax.Array:
    """y = x @ dequant(qt);  x: (M, K) -> (M, N)."""
    out_dtype = out_dtype or x.dtype
    impl = _pick(impl)
    if impl == "ref":
        return ref_ops.quant_matmul_ref(x, qt.planes, qt.scale, qt.zero,
                                        qt.bits, qt.group_size, out_dtype)
    k, n = qt.shape
    bm, bn, bk = _tile_sizes(x.shape[0], k, n, bm, bn, bk)
    xp, m = _pad_m(x, bm)
    y = quant_matmul_pallas(xp, qt.planes, qt.scale, qt.zero,
                            bits=qt.bits, group_size=qt.group_size,
                            bm=bm, bn=bn, bk=bk, out_dtype=out_dtype,
                            interpret=(impl == "pallas_interpret"))
    return y[:m]


def lowrank_comp_matmul(x: jax.Array, qt: QuantizedTensor,
                        u: jax.Array, v: jax.Array,
                        u_scale: jax.Array, v_scale: jax.Array,
                        mask: Optional[jax.Array] = None, *,
                        impl: Optional[str] = None, out_dtype=None,
                        rank_cap: Optional[jax.Array] = None,
                        bm: int = 128, bn: int = 256, bk: int = 512
                        ) -> jax.Array:
    """Router-guided compensated matmul (paper §3.2).

    y = x @ dequant(qt) + ((x * mask) @ (U u_s)) diag(v_s) @ V_codes

    ``rank_cap`` (traced scalar, None = full padded rank) zeroes rank
    dims >= cap in the rank-space activation — the bandwidth controller's
    runtime rank truncation, a mask rather than a re-SVD, applied before
    the kernel so the fused Pallas path needs no shape change.
    """
    out_dtype = out_dtype or x.dtype
    impl = _pick(impl)
    if impl == "ref":
        return ref_ops.lowrank_comp_matmul_ref(
            x, qt.planes, qt.scale, qt.zero, qt.bits, qt.group_size,
            u, v, u_scale, v_scale, mask, out_dtype, rank_cap=rank_cap)
    # rank-space activation with both factor scales folded in (rank-r cost)
    xf = x.astype(jnp.float32)
    if mask is not None:
        xf = xf * mask[:, None].astype(jnp.float32)
    ud = u.astype(jnp.float32) * u_scale          # (K, R)
    xu = jnp.dot(xf, ud, preferred_element_type=jnp.float32)
    if rank_cap is not None:
        xu = xu * (jnp.arange(u.shape[-1]) < rank_cap).astype(jnp.float32)
    xu = xu * v_scale[None, :, 0]                 # fold (R,1) v_scale
    k, n = qt.shape
    bm, bn, bk = _tile_sizes(x.shape[0], k, n, bm, bn, bk)
    xp, m = _pad_m(x, bm)
    xup, _ = _pad_m(xu, bm)
    y = lowrank_comp_matmul_pallas(
        xp, qt.planes, qt.scale, qt.zero, xup, v,
        bits=qt.bits, group_size=qt.group_size, bm=bm, bn=bn, bk=bk,
        out_dtype=out_dtype, interpret=(impl == "pallas_interpret"))
    return y[:m]


def compensated_matmul_stack(x: jax.Array, stack, mask: jax.Array, *,
                             impl: Optional[str] = None, out_dtype=None,
                             rank_cap: Optional[jax.Array] = None
                             ) -> jax.Array:
    """vmap of lowrank_comp_matmul over an expert stack.

    x: (E, C, K), stack: CompressedExpertStack, mask: (E, C) -> (E, C, N).
    ``rank_cap`` (traced scalar shared by all experts of the layer) caps
    the compensator rank via the padded-factor mask.
    """
    out_dtype = out_dtype or x.dtype

    def one(xe, planes, scale, zero, u, v, us, vs, me):
        qt = QuantizedTensor(planes, scale, zero, stack.bits,
                             stack.group_size, stack.shape[1:])
        return lowrank_comp_matmul(xe, qt, u, v, us, vs, me, impl=impl,
                                   out_dtype=out_dtype, rank_cap=rank_cap)

    return jax.vmap(one)(x, stack.planes, stack.scale, stack.zero,
                         stack.u, stack.v, stack.u_scale, stack.v_scale,
                         mask)


def fused_expert_matmul(xe: jax.Array, stack, me: jax.Array, *,
                        gates: Optional[jax.Array] = None,
                        rank_cap: Optional[jax.Array] = None,
                        impl: Optional[str] = None, out_dtype=None,
                        bm: Optional[int] = None, bn: Optional[int] = None,
                        bk: Optional[int] = None) -> jax.Array:
    """Fused decode-path projection over one expert stack (the tentpole
    kernel entry point; see ``quant_matmul.fused_expert_matmul_pallas``).

    xe: (E, C, K) dispatched tokens, stack: CompressedExpertStack,
    me: (E, C) top-n compensation mask, gates: optional (E, C) router
    gates folded into the output in-kernel (the gate-weighted combine),
    rank_cap: traced per-layer plan scalar (None = full padded rank).

    One ``pallas_call`` covers every expert of the (layer, projection):
    bitplane unpack + HQQ dequant at each expert's TRUE width
    (``stack.expert_bits``), the rank-capped compensator GEMM, and the
    gate weighting — accumulated in f32 VMEM scratch, no HBM
    round-trips.  Block sizes come from ``kernels.autotune`` unless
    pinned by the caller; the traced ``rank_cap``/``gates`` enter as
    data, so controller plan changes never recompile.
    """
    out_dtype = out_dtype or xe.dtype
    impl = _pick(impl)
    if impl == "ref":
        return ref_ops.fused_expert_matmul_ref(
            xe, stack.planes, stack.scale, stack.zero, stack.bits,
            stack.group_size, stack.u, stack.v, stack.u_scale,
            stack.v_scale, me, ge=gates, rank_cap=rank_cap,
            out_dtype=out_dtype)
    e, c, k = xe.shape
    n = stack.scale.shape[-1]
    r = stack.pad_rank
    if bm is None or bn is None or bk is None:
        from .autotune import choose_tiles
        abm, abn, abk = choose_tiles("fused", bits=stack.bits,
                                     group_size=stack.group_size, rank=r,
                                     m=c, k=k, n=n)
        bm, bn, bk = bm or abm, bn or abn, bk or abk
    pc = (-c) % bm
    xep = jnp.pad(xe, ((0, 0), (0, pc), (0, 0))) if pc else xe
    mep = jnp.pad(me, ((0, 0), (0, pc))) if pc else me
    gep = (jnp.pad(gates, ((0, 0), (0, pc)))
           if gates is not None and pc else gates)
    cap = jnp.full((1,), r, jnp.int32) if rank_cap is None else \
        jnp.asarray(rank_cap, jnp.int32).reshape(1)
    # TRUE per-expert widths; inside shard_map regions the runtime leaves
    # carry a local expert slice while ``expert_bits`` (static metadata)
    # stays global — fall back to the container width there (bit-exact:
    # sub-width codes leave the upper planes zero)
    ebs = stack.expert_bits
    if ebs is not None and len(ebs) != e:
        ebs = None
    eb = jnp.asarray(ebs if ebs is not None else (stack.bits,) * e,
                     jnp.int32)
    ye = fused_expert_matmul_pallas(
        xep, stack.planes, stack.scale, stack.zero, stack.u, stack.u_scale,
        stack.v, stack.v_scale, mep[..., None],
        None if gep is None else gep[..., None], cap, eb,
        bits=stack.bits, group_size=stack.group_size, bm=bm, bn=bn, bk=bk,
        out_dtype=out_dtype, interpret=(impl == "pallas_interpret"))
    return ye[:, :c] if pc else ye
