"""Mixtral: the weights a cell serves, drawn on the device from
``--seed`` at the configuration's published widths in the form they
are served in, handed to the program's own layout; and the model FLOPs
a request needs.

No dense expert is ever drawn and no HQQ fit or SVD runs: the
compressed experts (the paper's offline precomputation, which a
deployment loads) are drawn directly as a ``CompressedExpertStack``'s
leaves.  One jitted call draws one layer whole; the same call, with the
same key, gives the reference the same numbers.

A configuration file names this file by its ``"model"`` key; another
architecture is another file under ``bench/models/`` with the same
functions (``geometry``, ``rank_table``, ``draw``, ``OUTER``, ``build``,
``request_flops``).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Dict, List, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from model import layer_key
from work import PROJECTIONS, Geometry

# std of the int8 codes of a uniform draw over [-127, 127]
_I8_STD = 127 / math.sqrt(3)
# (codes - zero) of uniform 2-bit codes about a zero of 1.5: std sqrt(1.25)
_CODE_STD = math.sqrt(1.25)


class Shapes(NamedTuple):
    """The static sizes a draw is compiled for."""
    d: int
    f: int
    heads: int
    kv_heads: int
    head_dim: int
    experts: int
    bits: int
    group: int
    pad_rank: int
    comp_ratio: float
    vocab: int


def shapes(spec: Dict) -> Shapes:
    c, q = spec["config"], spec["compression"]
    return Shapes(c["hidden_size"], c["intermediate_size"],
                  c["num_attention_heads"], c["num_key_value_heads"],
                  c["head_dim"], c["num_local_experts"], q["bits"],
                  q["group_size"], q["pad_rank"], q["compensation_ratio"],
                  c["vocab_size"])


def geometry(spec: Dict) -> Geometry:
    """The sizes the fused expert kernel's work counts need."""
    c, q = spec["config"], spec["compression"]
    return Geometry(
        layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        d_expert=c["intermediate_size"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        vocab=c["vocab_size"], num_experts=c["num_local_experts"],
        top_k=c["num_experts_per_tok"], top_n=q["top_n"], bits=q["bits"],
        group_size=q["group_size"], factor_bits=q["factor_bits"])


def rank_table(spec: Dict) -> List[Dict[str, Tuple[int, ...]]]:
    """True compensator ranks ``[layer][proj] -> (E,)``: one expert of
    each (layer, projection) at the full rank, the expert
    ``(3 * layer + p) mod E`` for projections p = 0, 1, 2 (w1, w3, w2),
    and rank 0 for the rest (the kurtosis allocation at budget 32 gives
    the whole budget of eight experts to one)."""
    c, q = spec["config"], spec["compression"]
    e = c["num_local_experts"]
    out = []
    for layer in range(c["num_hidden_layers"]):
        row = {}
        for p, name in enumerate(PROJECTIONS):
            r = [0] * e
            r[(3 * layer + p) % e] = q["pad_rank"]
            row[name] = tuple(r)
        out.append(row)
    return out


def _dense(key, shape, fan_in):
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            / math.sqrt(fan_in)).astype(jnp.bfloat16)


def _gain(key, n):
    """Norm gains as offsets from 1 (1 + offset is the gain)."""
    return (0.1 * jax.random.normal(key, (n,), jnp.float32)
            ).astype(jnp.bfloat16)


def _stack(key, k, n, ranks, s: Shapes) -> Dict:
    """One projection's compressed experts: 2-bit codes as uint8 bit
    planes (uniform codes, so the packed bytes are uniform), float32
    scale and zero per group of ``group`` rows, int8 U/V with float32
    per-rank scales, zero past each expert's true rank."""
    ks = jax.random.split(key, 6)
    e, g, r = s.experts, s.group, s.pad_rank
    w_std = 1.0 / math.sqrt(k)
    scale = (w_std / _CODE_STD) * jnp.exp(
        0.2 * jax.random.normal(ks[0], (e, k // g, n), jnp.float32))
    zero = 1.5 + 0.25 * jax.random.normal(ks[1], (e, k // g, n),
                                          jnp.float32)
    planes = jax.random.bits(ks[2], (e, k * s.bits // 8, n), jnp.uint8)
    live = (jnp.arange(r)[None, :] < ranks[:, None])          # (E, R)
    u = jax.random.randint(ks[3], (e, k, r), -127, 128, jnp.int32)
    v = jax.random.randint(ks[4], (e, r, n), -127, 128, jnp.int32)
    u = jnp.where(live[:, None, :], u, 0).astype(jnp.int8)
    v = jnp.where(live[:, :, None], v, 0).astype(jnp.int8)
    # |UV| about comp_ratio of the weights' std at rank pad_rank
    unit = math.sqrt(s.comp_ratio * w_std / math.sqrt(r)) / _I8_STD
    us = jnp.where(live, unit, 0.0).astype(jnp.float32)
    return {"planes": planes, "scale": scale, "zero": zero, "u": u, "v": v,
            "u_scale": us[:, None, :], "v_scale": us[:, :, None]}


@functools.partial(jax.jit, static_argnums=(2,))
def draw_layer(key, ranks, s: Shapes) -> Dict:
    """One layer's weights; ``ranks`` (3, E) int32 in PROJECTIONS order."""
    ks = jax.random.split(key, 10)
    d, hd = s.d, s.head_dim
    out = {
        "pre_norm": _gain(ks[0], d),
        "ffn_norm": _gain(ks[1], d),
        "wq": _dense(ks[2], (d, s.heads, hd), d),
        "wk": _dense(ks[3], (d, s.kv_heads, hd), d),
        "wv": _dense(ks[4], (d, s.kv_heads, hd), d),
        "wo": _dense(ks[5], (s.heads, hd, d), s.heads * hd),
        "router": _dense(ks[6], (d, s.experts), d).astype(jnp.float32),
    }
    for p, name in enumerate(PROJECTIONS):
        k, n = (s.f, d) if name == "w2" else (d, s.f)
        out[name] = _stack(ks[7 + p], k, n, ranks[p], s)
    return out


@functools.partial(jax.jit, static_argnums=(1,))
def draw_outer(key, s: Shapes) -> Dict:
    ks = jax.random.split(key, 3)
    return {"embed": (0.02 * jax.random.normal(ks[0], (s.vocab, s.d),
                                               jnp.float32)
                      ).astype(jnp.bfloat16),
            "final_norm": _gain(ks[1], s.d),
            "head": _dense(ks[2], (s.d, s.vocab), s.d)}


OUTER = 1 << 20       # fold-in index of the embedding and head


def draw(spec: Dict, seed: int, layer: int, ranks=None) -> Dict:
    """Layer ``layer``'s weights of the model ``seed`` draws (``layer``
    = OUTER for the embedding, final norm and head)."""
    s = shapes(spec)
    key = layer_key(seed, layer)
    if layer == OUTER:
        return draw_outer(key, s)
    if ranks is None:
        ranks = rank_table(spec)[layer]
    rk = jnp.asarray([ranks[p] for p in PROJECTIONS], jnp.int32)
    return draw_layer(key, rk, s)


def program_config(spec: Dict):
    """The program's config for this file: its registry arch at the
    file's depth and norm epsilon; every width is checked against the
    file, so the file is what runs."""
    from repro.registry import get_config
    c = spec["config"]
    base = get_config(spec["arch"], reduced=spec.get("registry_reduced",
                                                     False))
    cfg = dataclasses.replace(base,
                              num_layers=c["num_hidden_layers"],
                              norm_eps=c["rms_norm_eps"])
    m = cfg.moe
    have = {"hidden_size": cfg.d_model, "intermediate_size": m.d_expert,
            "num_attention_heads": cfg.num_heads,
            "num_key_value_heads": cfg.num_kv_heads,
            "head_dim": cfg.head_dim, "vocab_size": cfg.vocab_size,
            "num_local_experts": m.num_experts,
            "num_experts_per_tok": m.top_k, "rope_theta": cfg.rope_theta,
            "tie_word_embeddings": cfg.tie_embeddings}
    bad = {k: (v, c[k]) for k, v in have.items() if v != c[k]}
    q = spec["compression"]
    qc = m.quant
    for k, v in (("bits", qc.bits), ("group_size", qc.group_size),
                 ("top_n", qc.top_n_restore),
                 ("factor_bits", qc.factor_bits)):
        if v != q[k]:
            bad[k] = (v, q[k])
    if bad or cfg.block_pattern != ("global",) or cfg.act != "silu":
        raise ValueError(f"{spec['arch']}: the program's config differs "
                         f"from the file: {bad}")
    return cfg


def build(spec: Dict, seed: int, compensate: bool = True):
    """``(cfg, params, stacks_by_layer)`` in the program's layout, drawn
    layer by layer on the device.  ``compensate=False`` is a fault for
    the limits' readings: the compensators' scales are zeroed, so the
    program serves the 2-bit experts alone while the reference, which
    draws its own weights, still compensates."""
    from repro.core.pipeline import CompressedExpertStack
    from repro.models.transformer import apply_compressed_stacks
    cfg = dataclasses.replace(program_config(spec), force_unroll_plan=True)
    s = shapes(spec)
    q = spec["compression"]
    ranks = rank_table(spec)
    outer = draw(spec, seed, OUTER)
    params = {"embed": {"tok": outer["embed"]},
              "final_norm": outer["final_norm"],
              "head": {"w": outer["head"]}}
    segments, stacks_by_layer = [], []
    for layer in range(cfg.num_layers):
        w = draw(spec, seed, layer, ranks[layer])
        stacks = {}
        for name in PROJECTIONS:
            a = w[name]
            if not compensate:
                a = dict(a, u_scale=jnp.zeros_like(a["u_scale"]),
                         v_scale=jnp.zeros_like(a["v_scale"]))
            k, n = (s.f, s.d) if name == "w2" else (s.d, s.f)
            stacks[name] = CompressedExpertStack(
                planes=(a["planes"],), scale=a["scale"], zero=a["zero"],
                u=a["u"], v=a["v"], u_scale=a["u_scale"],
                v_scale=a["v_scale"], bits=q["bits"],
                group_size=q["group_size"], shape=(s.experts, k, n),
                ranks=ranks[layer][name], pad_rank=q["pad_rank"],
                factor_bits=q["factor_bits"])
        stacks_by_layer.append(stacks)
        segments.append(({
            "pre_norm": w["pre_norm"], "ffn_norm": w["ffn_norm"],
            "attn": {"wq": w["wq"], "wk": w["wk"], "wv": w["wv"],
                     "wo": w["wo"]},
            "moe": {"router": w["router"], "w1": None, "w2": None,
                    "w3": None}},))
    params["segments"] = tuple(segments)
    qparams, cfg_q = apply_compressed_stacks(params, cfg, stacks_by_layer)
    return cfg_q, qparams, stacks_by_layer


def layer_flops(g: Geometry, ctx: np.ndarray, mean_rank: float
                ) -> np.ndarray:
    """Model FLOPs of one token through one layer, at context ``ctx``
    (the token attends to ``ctx`` positions, itself included)."""
    d, hd = g.d_model, g.head_dim
    q, kv = g.num_heads * hd, g.num_kv_heads * hd
    proj = 2 * d * (q + 2 * kv) + 2 * q * d
    core = 4 * q * np.asarray(ctx, np.float64)
    router = 2 * d * g.num_experts
    experts = g.top_k * 3 * 2 * d * g.d_expert
    comp = g.top_n * 3 * 2 * mean_rank * (d + g.d_expert)
    return proj + core + router + experts + comp


def request_flops(g: Geometry, prompt_len: int, generated: int,
                  mean_rank: float) -> float:
    """Model FLOPs a served request requires: its prompt through every
    layer with the head on the last prompt position, then one pass per
    generated token after the first, each with the head."""
    head = 2.0 * g.d_model * g.vocab
    p = int(prompt_len)
    ctx = np.arange(1, p + max(int(generated) - 1, 0) + 1)
    body = float(layer_flops(g, ctx, mean_rank).sum()) * g.layers
    return body + head * max(int(generated), 1)
