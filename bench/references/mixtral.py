"""Plain float32 reference of a Mixtral model served with low-rank
compensation, written from the published description and imports
nothing of the program under test.

Per layer: RMSNorm, grouped-query attention with rotary positions
(rotate-half pairing, theta from the configuration) under a causal
mask, residual; RMSNorm, router softmax over all experts, top-k with
the gates renormalised, and for each token the sum over its k experts
of gate x SwiGLU(x; W1, W3, W2).  An expert's weights are its 2-bit
codes dequantized per group of rows, ``(code - zero) * scale``; for the
first ``top_n`` of a token's experts they are compensated, ``W + U V``
with ``U = u * u_scale`` and ``V = v * v_scale``.  Then the final
RMSNorm and the head.  No kernel, no cache, no batching: each sequence
runs whole, one layer at a time, every expert over every token with the
gate zero where the token is not routed to it.

The weights are the benchmark's own draw (``model.draw``), made again
from the seed layer by layer, so the reference takes nothing the
program made.  ``mode="fp8"`` rounds both operands of every matrix
product to float8 e4m3 with one scale per tensor: the computation one
precision step below the bf16 the configuration states (the control);
``mode="bf16"`` rounds them to bf16, the configuration's own precision
(a witness of how far bf16 alone moves the logits).
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
PACK_BLOCK = 64          # K rows per packing block of the code planes
Q_BLOCK = 512            # query rows per attention block
PAD = 512                # sequences are padded to a multiple of this


def _round(x, mode):
    if mode == "f32":
        return x
    if mode == "bf16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    s = 448.0 / amax
    return (x * s).astype(jnp.float8_e4m3fn).astype(jnp.float32) / s


def mm(eq, a, b, mode):
    return jnp.einsum(eq, _round(a, mode), _round(b, mode),
                      precision=HIGHEST, preferred_element_type=jnp.float32)


def rms(x, offset, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * (1.0 + offset.astype(jnp.float32))


def rope(x, theta):
    """x: (S, H, hd), positions 0..S-1; pairs (i, i + hd/2) rotate."""
    s, _, hd = x.shape
    inv = 1.0 / theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd)
    ang = np.arange(s, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[:, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def unpack_codes(packed, bits):
    """(K * bits / 8, N) bytes -> (K, N) codes.  Within each block of 64
    rows the rows are cut into 8 / bits chunks, chunk j held at bit
    offset j * bits of the block's bytes."""
    c = 8 // bits
    kc, n = packed.shape
    pk = packed.astype(jnp.int32).reshape(kc * c // PACK_BLOCK,
                                          PACK_BLOCK // c, n)
    chunks = [(pk >> (j * bits)) & ((1 << bits) - 1) for j in range(c)]
    return jnp.stack(chunks, axis=1).reshape(kc * c, n)


def dequant(planes, scale, zero, bits, group):
    codes = unpack_codes(planes, bits).astype(jnp.float32)
    k, n = codes.shape
    g = codes.reshape(k // group, group, n)
    return ((g - zero[:, None, :]) * scale[:, None, :]).reshape(k, n)


def attention(x, w, c, mode):
    s = x.shape[0]
    h, kv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    wf = {k: w[k].astype(jnp.float32) for k in ("wq", "wk", "wv", "wo")}
    q = rope(mm("sd,dhk->shk", x, wf["wq"], mode), c["rope_theta"])
    k = rope(mm("sd,dhk->shk", x, wf["wk"], mode), c["rope_theta"])
    v = mm("sd,dhk->shk", x, wf["wv"], mode)
    q = q.reshape(s, kv, h // kv, hd) / math.sqrt(hd)
    outs = []
    for q0 in range(0, s, Q_BLOCK):
        qb = q[q0:q0 + Q_BLOCK]
        sc = mm("qkgd,skd->kgqs", qb, k, mode)
        qpos = np.arange(q0, q0 + qb.shape[0])[:, None]
        mask = jnp.asarray(np.arange(s)[None, :] <= qpos)
        p = jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), axis=-1)
        outs.append(mm("kgqs,skd->qkgd", p, v, mode))
    o = jnp.concatenate(outs, 0).reshape(s, h, hd)
    return mm("shk,hkd->sd", o, wf["wo"], mode)


def experts(x, w, c, q, mode):
    """Sum over each token's routed experts of gate x compensated-or-not
    SwiGLU, one expert at a time (a scan bounds the dequantized weights
    held at once to one expert's)."""
    e_n, top_k, top_n = (c["num_local_experts"], c["num_experts_per_tok"],
                         q["top_n"])
    probs = jax.nn.softmax(mm("sd,de->se", x, w["router"], mode), axis=-1)
    gates, idx = jax.lax.top_k(probs, top_k)
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True)

    def proj(a, inp, comp):
        wd = dequant(a["planes"], a["scale"], a["zero"], q["bits"],
                     q["group_size"])
        u = a["u"].astype(jnp.float32) * a["u_scale"]
        v = a["v"].astype(jnp.float32) * a["v_scale"]
        out = mm("sk,kn->sn", inp, wd, mode)
        return out + mm("sr,rn->sn",
                        mm("sk,kr->sr", inp * comp[:, None], u, mode), v,
                        mode)

    def one(y, xs):
        e, a1, a3, a2 = xs
        sel = idx == e
        gate = jnp.sum(jnp.where(sel, gates, 0.0), axis=-1)
        comp = jnp.any(sel[:, :top_n], axis=-1).astype(jnp.float32)
        hid = jax.nn.silu(proj(a1, x, comp)) * proj(a3, x, comp)
        return y + gate[:, None] * proj(a2, hid, comp), None

    xs = (jnp.arange(e_n), w["w1"], w["w3"], w["w2"])
    y, _ = jax.lax.scan(one, jnp.zeros_like(x), xs)
    return y


@functools.partial(jax.jit, static_argnums=(2, 3))
def _layer(h, w, meta, mode):
    c, q = dict(meta[0]), dict(meta[1])
    eps = c["rms_norm_eps"]
    h = h + attention(rms(h, w["pre_norm"], eps), w, c, mode)
    return h + experts(rms(h, w["ffn_norm"], eps), w, c, q, mode)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _head(h, rows, outer, eps, mode):
    x = rms(h[rows], outer["final_norm"], eps)
    return mm("sd,dv->sv", x, outer["head"].astype(jnp.float32), mode)


def _meta(spec):
    keys = ("num_attention_heads", "num_key_value_heads", "head_dim",
            "rope_theta", "rms_norm_eps", "num_local_experts",
            "num_experts_per_tok")
    c = tuple((k, spec["config"][k]) for k in keys)
    q = tuple((k, spec["compression"][k])
              for k in ("top_n", "bits", "group_size"))
    return c, q


def logits_at(spec: Dict, draw, seqs: Sequence[np.ndarray],
              rows: Sequence[np.ndarray], mode: str = "f32"
              ) -> List[np.ndarray]:
    """Float32 logits of each sequence at the positions ``rows``.

    ``draw(layer)`` returns layer ``layer``'s weights as the benchmark
    draws them, ``draw(None)`` the embedding, final norm and head."""
    meta = _meta(spec)
    outer = draw(None)
    hs = []
    n = -(-max(len(t) for t in seqs) // PAD) * PAD   # one shape: one compile
    for t in seqs:
        ids = np.zeros((n,), np.int32)
        ids[:len(t)] = t
        hs.append(outer["embed"][jnp.asarray(ids)].astype(jnp.float32))
    for layer in range(spec["config"]["num_hidden_layers"]):
        w = draw(layer)
        hs = [_layer(h, w, meta, mode) for h in hs]
        del w
    return [np.asarray(_head(h, jnp.asarray(r, jnp.int32), outer,
                             spec["config"]["rms_norm_eps"], mode))
            for h, r in zip(hs, rows)]
