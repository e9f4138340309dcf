"""Pallas TPU kernel: flash-decode attention over an (optionally int8) KV
cache.

Decode's second dominant HBM stream (after expert weights) is the KV
cache.  This kernel streams KV blocks HBM->VMEM once, keeps the online-
softmax state (m, l, acc) in VMEM scratch, and — when the cache is int8 —
folds the per-(slot, head) scales into the score/probability domain so the
dequantized cache never materializes: KV traffic is exactly the packed
bytes (~1.06 B/elem incl. scales vs 2 for bf16).

Grid: (B, KVH, S/bs); GQA handled by evaluating all G = H/KVH query heads
of the kv-head per block.  Ring caches pass ``kv_pos`` (-1 = empty slot)
and masking is pure position arithmetic — no sorting after wraparound.

Blocks are Mosaic-legal: K/V enter as ``(B, S, KVH * hd)`` (a free
reshape) blocked ``(bs, hd)`` at lane block ``kv-head``, and positions
and scales enter with a unit dim before the sequence, blocked
``(1, bs)``; so on a TPU ``hd`` divides by 128 and ``bs`` divides by 128
or spans the cache.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG = -2.0 ** 30


def _kernel(n_s, bs, window, q_ref, k_ref, v_ref, ks_ref, vs_ref,
            pos_ref, cur_ref, o_ref, m_ref, l_ref, acc_ref):
    s_idx = pl.program_id(2)

    @pl.when(s_idx == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)                 # (G, hd)
    k = k_ref[...].astype(jnp.float32)               # (bs, hd)
    v = v_ref[...].astype(jnp.float32)               # (bs, hd)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (G, bs)
    if ks_ref is not None:
        s = s * ks_ref[...].astype(jnp.float32)      # (1, bs)
    pos = pos_ref[...]                               # (1, bs)
    cur = cur_ref[...]                               # (1, 1)
    valid = (pos >= 0) & (pos <= cur)
    if window is not None and window > 0:
        valid &= pos > cur - window
    s = jnp.where(valid, s, NEG)

    m_prev = m_ref[...]                              # (G, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)                           # (G, bs)
    if vs_ref is not None:
        pv = p * vs_ref[...].astype(jnp.float32)
    else:
        pv = p
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        pv, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(s_idx == n_s - 1)
    def _done():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "bs", "interpret"))
def flash_decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                           kv_pos: jax.Array, cur_pos: jax.Array,
                           k_scale: Optional[jax.Array] = None,
                           v_scale: Optional[jax.Array] = None, *,
                           window: Optional[int] = None, bs: int = 512,
                           interpret: bool = False) -> jax.Array:
    """q: (B, H, hd) pre-scaled by 1/sqrt(hd); k/v: (B, S, KVH, hd);
    kv_pos: (B, S); cur_pos: (B,); scales: (B, S, KVH) for int8 KV.
    Returns (B, H, hd)."""
    b, h, hd = q.shape
    s_len, kvh = k.shape[1], k.shape[2]
    g = h // kvh
    bs = min(bs, s_len)
    assert s_len % bs == 0, (s_len, bs)
    n_s = s_len // bs
    qg = q.reshape(b, kvh, g, hd)

    grid = (b, kvh, n_s)
    in_specs = [
        pl.BlockSpec((1, g, hd), lambda bb, kk, ss: (bb * kvh + kk, 0, 0)),
        pl.BlockSpec((None, bs, hd), lambda bb, kk, ss: (bb, ss, kk)),
        pl.BlockSpec((None, bs, hd), lambda bb, kk, ss: (bb, ss, kk)),
    ]
    args = [qg.reshape(b * kvh, g, hd), k.reshape(b, s_len, kvh * hd),
            v.reshape(b, s_len, kvh * hd)]
    use_scales = k_scale is not None
    if use_scales:
        # (B, S, KVH) -> (B, KVH, 1, S): a row of scales per kv-head
        scale_spec = pl.BlockSpec((None, None, 1, bs),
                                  lambda bb, kk, ss: (bb, kk, 0, ss))
        in_specs += [scale_spec, scale_spec]
        args += [jnp.transpose(k_scale, (0, 2, 1))[:, :, None, :],
                 jnp.transpose(v_scale, (0, 2, 1))[:, :, None, :]]
    in_specs += [pl.BlockSpec((None, 1, bs), lambda bb, kk, ss: (bb, 0, ss)),
                 pl.BlockSpec((None, 1, 1), lambda bb, kk, ss: (bb, 0, 0))]
    args += [kv_pos[:, None, :], cur_pos[:, None, None]]

    kernel = functools.partial(
        _kernel, n_s, bs, window) if use_scales else functools.partial(
        _wrap_noscale, n_s, bs, window)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, g, hd), lambda bb, kk, ss: (bb * kvh + kk,
                                                               0, 0)),
        out_shape=jax.ShapeDtypeStruct((b * kvh, g, hd), q.dtype),
        scratch_shapes=[pltpu.VMEM((g, 1), jnp.float32),
                        pltpu.VMEM((g, 1), jnp.float32),
                        pltpu.VMEM((g, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_decode" + ("_kv8" if use_scales else ""),
    )(*args)
    return out.reshape(b, kvh, g, hd).reshape(b, h, hd)


def _wrap_noscale(n_s, bs, window, q_ref, k_ref, v_ref, pos_ref, cur_ref,
                  o_ref, m_ref, l_ref, acc_ref):
    _kernel(n_s, bs, window, q_ref, k_ref, v_ref, None, None, pos_ref,
            cur_ref, o_ref, m_ref, l_ref, acc_ref)
