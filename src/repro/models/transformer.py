"""Layer-stack engine: plan derivation, parameter init, scanned forward.

A model is a sequence of *segments*; each segment is a short pattern of
heterogeneous layers (e.g. gemma3's 5 local + 1 global) repeated ``repeat``
times via ``lax.scan`` — one trace per distinct layer kind regardless of
depth, which keeps dry-run compiles of 62-layer models fast and HLO small.
Remainder layers that don't fill a pattern become repeat-1 segments.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ..config import ModelConfig
from .attention import attention, decode_attention
from .ffn import ffn_apply, ffn_apply_quantized
from .kvcache import (TRASH_PAGE, claim_slot, init_attn_cache,
                      init_mlstm_cache, init_paged_attn_cache,
                      init_rglru_cache, init_slstm_cache, paged_claim,
                      paged_gather, paged_reset, paged_seed_prefix,
                      paged_update_attn_cache, prefill_attn_cache,
                      reset_slot, update_attn_cache)
from .layers import (apply_mrope, apply_rope, dense_init, embed_init,
                     rms_norm, softcap)
from .moe import moe_apply
from .rglru import rglru_seq, rglru_step
from .xlstm import mlstm_chunkwise, mlstm_step, slstm_seq


class LayerSpec(NamedTuple):
    mixer: str          # global | local | recurrent | mlstm | slstm
    ffn: str            # dense | moe | none
    cross: bool = False # enc-dec decoder cross-attention


class Segment(NamedTuple):
    layers: Tuple[LayerSpec, ...]
    repeat: int


@dataclasses.dataclass
class ExecContext:
    """Runtime execution knobs threaded through the forward pass."""
    mode: str = "train"              # train | prefill | step
    quantized: bool = False          # serve on compressed experts/FFNs
    ep_mode: str = "none"            # none | a2a | replicated
    mesh: Any = None
    constrain: Callable = staticmethod(lambda x, axes: x)
    moe_ep_fn: Optional[Callable] = None   # injected by distributed layer
    remat: bool = False
    q_block: int = 1024
    mlstm_chunk: int = 256
    exact_capacity: bool = False     # drop-free MoE (tests / tiny batches)
    scan_unroll: bool = False        # unroll every scan (cost-analysis pass)
    # prefill/train attention parallelism: shard q heads over `model` when
    # they divide; otherwise shard fresh K/V along seq (partial-softmax) so
    # attention FLOPs never replicate across the model axis
    attn_heads_sharded: bool = False
    attn_seq_sharded: bool = False
    remat_policy: str = "full"       # full | dots (save matmul outputs)
    # expert-backend dispatch: None/'auto' -> REPRO_KERNEL_IMPL policy;
    # 'ref' | 'pallas' | 'pallas_interpret' force an implementation
    kernel_impl: Optional[str] = None
    # return per-MoE-layer top-k routing as a first-class forward output
    collect_trace: bool = False
    # return per-MoE-layer FFN inputs (T, d) as a first-class output —
    # the offline calibration pass (calib/stats.py) feeds on these to
    # accumulate routing frequency / gate mass / input second moments
    collect_moe_inputs: bool = False


# ---------------------------------------------------------------------------
# plan derivation
# ---------------------------------------------------------------------------

def layer_specs(cfg: ModelConfig) -> List[LayerSpec]:
    cross = cfg.encoder is not None
    specs = []
    for i in range(cfg.num_layers):
        mixer = cfg.layer_kind(i)
        if mixer in ("mlstm", "slstm"):
            ffn = "none"
        elif cfg.moe is not None and cfg.is_moe_layer(i) and not (
                i == 0 and cfg.first_layer_dense):
            ffn = "moe"
        else:
            ffn = "dense"
        specs.append(LayerSpec(mixer, ffn, cross))
    return specs


def derive_plan(cfg: ModelConfig) -> Tuple[Segment, ...]:
    specs = layer_specs(cfg)
    if cfg.force_unroll_plan:
        return tuple(Segment((s,), 1) for s in specs)
    p = len(cfg.block_pattern)
    segments: List[Segment] = []
    i = 0
    n = len(specs)
    while i < n:
        # try the full block pattern first
        if p > 1 and i + p <= n:
            pat = tuple(specs[i:i + p])
            r = 1
            while i + (r + 1) * p <= n and tuple(specs[i + r * p:i + (r + 1) * p]) == pat:
                r += 1
            if r >= 1 and all(specs[i + j * p:i + (j + 1) * p] == list(pat)
                              for j in range(r)):
                segments.append(Segment(pat, r))
                i += r * p
                continue
        # fall back to run-length of identical single layers
        r = 1
        while i + r < n and specs[i + r] == specs[i]:
            r += 1
        segments.append(Segment((specs[i],), r))
        i += r
    return tuple(segments)


# ---------------------------------------------------------------------------
# parameter init (single layer, then vmapped stacks)
# ---------------------------------------------------------------------------

def _init_attn(key, cfg: ModelConfig, cross: bool, dtype):
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 8)
    p = {
        "wq": dense_init(ks[0], (d, h, hd), d, dtype),
        "wk": dense_init(ks[1], (d, kv, hd), d, dtype),
        "wv": dense_init(ks[2], (d, kv, hd), d, dtype),
        "wo": dense_init(ks[3], (h, hd, d), h * hd, dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h, hd), dtype)
        p["bk"] = jnp.zeros((kv, hd), dtype)
        p["bv"] = jnp.zeros((kv, hd), dtype)
    if cross:
        p["cross_wq"] = dense_init(ks[4], (d, h, hd), d, dtype)
        p["cross_wk"] = dense_init(ks[5], (cfg.encoder.d_model, h, hd),
                                   cfg.encoder.d_model, dtype)
        p["cross_wv"] = dense_init(ks[6], (cfg.encoder.d_model, h, hd),
                                   cfg.encoder.d_model, dtype)
        p["cross_wo"] = dense_init(ks[7], (h, hd, d), h * hd, dtype)
        p["cross_norm"] = jnp.zeros((d,), dtype)
    return p


def _init_ffn(key, d: int, ff: int, gated: bool, dtype):
    ks = jax.random.split(key, 3)
    p = {"w1": dense_init(ks[0], (d, ff), d, dtype),
         "w2": dense_init(ks[1], (ff, d), ff, dtype)}
    if gated:
        p["w3"] = dense_init(ks[2], (d, ff), d, dtype)
    return p


def _init_moe(key, cfg: ModelConfig, dtype):
    m = cfg.moe
    d, fe = cfg.d_model, m.d_expert
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], (d, m.num_experts), d, jnp.float32),
        "w1": dense_init(ks[1], (m.num_experts, d, fe), d, dtype),
        "w3": dense_init(ks[2], (m.num_experts, d, fe), d, dtype),
        "w2": dense_init(ks[3], (m.num_experts, fe, d), fe, dtype),
    }
    if m.num_shared_experts:
        fs = (m.d_shared or m.d_expert) * m.num_shared_experts
        p["shared"] = _init_ffn(ks[4], d, fs, True, dtype)
    return p


def _init_rglru(key, cfg: ModelConfig, dtype):
    d = cfg.d_model
    w = cfg.lru_width or d
    ks = jax.random.split(key, 6)
    return {
        "wx": dense_init(ks[0], (d, w), d, dtype),
        "wgate": dense_init(ks[1], (d, w), d, dtype),
        "conv_w": dense_init(ks[2], (cfg.conv1d_width, w), cfg.conv1d_width,
                             jnp.float32),
        "conv_b": jnp.zeros((w,), jnp.float32),
        "rg_wa": dense_init(ks[3], (w, w), w, jnp.float32),
        "rg_ba": jnp.zeros((w,), jnp.float32),
        "rg_wx": dense_init(ks[4], (w, w), w, jnp.float32),
        "rg_bx": jnp.zeros((w,), jnp.float32),
        # init recurrence a^c in (0.9, 0.999): lam = softplus^-1(-log a)
        "lam": jnp.full((w,), 0.65, jnp.float32),
        "wo": dense_init(ks[5], (w, d), w, dtype),
    }


def _init_mlstm(key, cfg: ModelConfig, dtype):
    d = cfg.d_model
    di = 2 * d
    nh = cfg.num_heads
    hd = di // nh
    ks = jax.random.split(key, 7)
    return {
        "w_up": dense_init(ks[0], (d, 2 * di), d, dtype),      # (u, z gate)
        "wq": dense_init(ks[1], (di, nh, hd), di, dtype),
        "wk": dense_init(ks[2], (di, nh, hd), di, dtype),
        "wv": dense_init(ks[3], (di, nh, hd), di, dtype),
        "w_if": dense_init(ks[4], (di, 2 * nh), di, jnp.float32),
        "b_if": jnp.concatenate([jnp.zeros((nh,)), 3.0 * jnp.ones((nh,))]),
        "w_down": dense_init(ks[5], (di, d), di, dtype),
        "out_norm": jnp.zeros((di,), dtype),
    }


def _init_slstm(key, cfg: ModelConfig, dtype):
    d = cfg.d_model
    nh = cfg.num_heads
    hd = d // nh
    ks = jax.random.split(key, 7)
    ff = int(d * 4 / 3 / 64 + 1) * 64
    return {
        "w_zifo": dense_init(ks[0], (d, 4, nh, hd), d, dtype),
        "b_zifo": jnp.zeros((4, nh, hd), jnp.float32),
        "rz": dense_init(ks[1], (nh, hd, hd), hd, jnp.float32),
        "ri": dense_init(ks[2], (nh, hd, hd), hd, jnp.float32),
        "rf": dense_init(ks[3], (nh, hd, hd), hd, jnp.float32),
        "ro": dense_init(ks[4], (nh, hd, hd), hd, jnp.float32),
        "out_norm": jnp.zeros((d,), dtype),
        "ffn": _init_ffn(ks[5], d, ff, True, dtype),
        "ffn_norm": jnp.zeros((d,), dtype),
    }


def init_layer(key, spec: LayerSpec, cfg: ModelConfig, dtype):
    ks = jax.random.split(key, 3)
    p: Dict[str, Any] = {"pre_norm": jnp.zeros((cfg.d_model,), dtype)}
    if cfg.post_attn_norm:
        p["post_norm"] = jnp.zeros((cfg.d_model,), dtype)
    if spec.mixer in ("global", "local"):
        p["attn"] = _init_attn(ks[0], cfg, spec.cross, dtype)
    elif spec.mixer == "recurrent":
        p["rglru"] = _init_rglru(ks[0], cfg, dtype)
    elif spec.mixer == "mlstm":
        p["mlstm"] = _init_mlstm(ks[0], cfg, dtype)
        return p  # self-contained block
    elif spec.mixer == "slstm":
        p["slstm"] = _init_slstm(ks[0], cfg, dtype)
        return p
    if spec.ffn != "none":
        p["ffn_norm"] = jnp.zeros((cfg.d_model,), dtype)
        if cfg.post_attn_norm:
            p["post_ffn_norm"] = jnp.zeros((cfg.d_model,), dtype)
    if spec.ffn == "dense":
        p["ffn"] = _init_ffn(ks[1], cfg.d_model, cfg.d_ff, cfg.gated_ffn,
                             dtype)
    elif spec.ffn == "moe":
        p["moe"] = _init_moe(ks[1], cfg, dtype)
    return p


def _init_outer(keys, cfg: ModelConfig, dtype) -> Dict:
    """Embedding, final norm and head: the params outside the stack."""
    params: Dict[str, Any] = {
        "embed": {"tok": embed_init(keys[0], (cfg.vocab_size, cfg.d_model),
                                    dtype)},
        "final_norm": jnp.zeros((cfg.d_model,), dtype),
    }
    if not cfg.tie_embeddings:
        params["head"] = {"w": dense_init(keys[1], (cfg.d_model,
                                                    cfg.vocab_size),
                                          cfg.d_model, dtype)}
    return params


# one layer's draw, compiled whole so that no float32 temporary of the
# random draw outlives the layer's own arrays (a full-width layer's
# dense experts are GBs)
_draw_layer = jax.jit(init_layer, static_argnums=(1, 2, 3))


def _draw_params(key, cfg: ModelConfig, dtype, finish: Callable,
                 place: Callable = lambda tree: tree):
    """The one key schedule of every param init.

    Draws the params outside the stack, then every layer of the plan
    unrolled in order, each passed through ``finish(params, spec)`` as
    soon as it is drawn, then the encoder (None without one); the outer
    params and the encoder go through ``place`` as soon as they are
    drawn.  Returns ``(outer, layers, encoder)``; ``layers[si][r][pi]``
    is what ``finish`` made of segment ``si``'s repeat ``r``, position
    ``pi``.
    """
    plan = derive_plan(cfg)
    keys = jax.random.split(key, len(plan) + 4)
    outer = place(_init_outer(keys, cfg, dtype))
    layers = []
    for si, seg in enumerate(plan):
        skeys = jax.random.split(keys[2 + si], seg.repeat)
        layers.append([
            tuple(finish(_draw_layer(jax.random.fold_in(skeys[r], pi), spec,
                                     cfg, dtype), spec)
                  for pi, spec in enumerate(seg.layers))
            for r in range(seg.repeat)])
    encoder = (place(init_encoder_params(keys[-1], cfg, dtype))
               if cfg.encoder is not None else None)
    return outer, layers, encoder


def init_params(key, cfg: ModelConfig, dtype=jnp.bfloat16) -> Dict:
    outer, layers, encoder = _draw_params(key, cfg, dtype,
                                          lambda lp, spec: lp)
    params = dict(outer)
    params["segments"] = tuple(
        reps[0] if len(reps) == 1
        else jax.tree.map(lambda *xs: jnp.stack(xs), *reps)
        for reps in layers)
    if encoder is not None:
        params["encoder"] = encoder
    return params


def init_encoder_params(key, cfg: ModelConfig, dtype) -> Dict:
    e = cfg.encoder
    ks = jax.random.split(key, e.num_layers + 1)

    def one(k):
        kk = jax.random.split(k, 2)
        return {
            "pre_norm": jnp.zeros((e.d_model,), dtype),
            "attn": {
                "wq": dense_init(kk[0], (e.d_model, e.num_heads,
                                         e.d_model // e.num_heads),
                                 e.d_model, dtype),
                "wk": dense_init(jax.random.fold_in(kk[0], 1),
                                 (e.d_model, e.num_heads,
                                  e.d_model // e.num_heads), e.d_model, dtype),
                "wv": dense_init(jax.random.fold_in(kk[0], 2),
                                 (e.d_model, e.num_heads,
                                  e.d_model // e.num_heads), e.d_model, dtype),
                "wo": dense_init(jax.random.fold_in(kk[0], 3),
                                 (e.num_heads, e.d_model // e.num_heads,
                                  e.d_model), e.d_model, dtype),
            },
            "ffn_norm": jnp.zeros((e.d_model,), dtype),
            "ffn": _init_ffn(kk[1], e.d_model, e.d_ff, False, dtype),
        }

    stacked = jax.vmap(one)(ks[:e.num_layers])
    return {"layers": stacked, "final_norm": jnp.zeros((e.d_model,), dtype)}


def unstack_params(params, cfg: ModelConfig):
    """Convert scanned (stacked) segment params into the unrolled per-layer
    layout matching ``force_unroll_plan=True`` — required before offline
    compression, whose per-layer compensator ranks break scan homogeneity."""
    plan = derive_plan(cfg)
    new_segs = []
    for si, seg in enumerate(plan):
        seg_params = params["segments"][si]
        for r in range(seg.repeat):
            for pi in range(len(seg.layers)):
                lp = seg_params[pi]
                if seg.repeat > 1:
                    lp = jax.tree.map(lambda x: x[r], lp)
                new_segs.append((lp,))
    out = dict(params)
    out["segments"] = tuple(new_segs)
    return out


def compress_moe_params(params, cfg: ModelConfig, qcfg=None, plan=None,
                        stats=None):
    """Offline-compress every MoE layer's experts for quantized serving.

    Runs the full pipeline (DESIGN.md) over the routed-expert stacks of
    each MoE layer and swaps w1/w3/w2 for ``CompressedExpertStack``s.
    Returns ``(qparams, cfg_q, stacks_by_layer)``: the *unrolled* param
    tree (per-layer compensator ranks break scan homogeneity), the
    matching ``force_unroll_plan`` config, and the per-layer stacks
    dicts the offload ``ExpertStore``s are built from.  One helper
    shared by ``launch/serve.py``, benchmarks, examples, and tests so
    the compressed-param layout has a single definition.

    ``plan`` (a ``calib.CompressionPlan``) pins per-expert bits and
    per-projection ranks per MoE layer from the offline budget
    allocator; ``stats`` (per-MoE-layer ``calib.LayerCalibStats``)
    makes the compensator SVDs activation-weighted.  Both None keeps the
    paper's kurtosis-guided uniform-bit path bit-identically.
    """
    qcfg = qcfg or cfg.moe.quant
    up = unstack_params(params, cfg)
    segs, stacks_by_layer = [], []
    for (lp,), spec in zip(up["segments"], layer_specs(cfg)):
        lp = _compress_layer(lp, spec, qcfg, plan, stats, stacks_by_layer)
        segs.append((lp,))
    qparams = dict(up)
    qparams["segments"] = tuple(segs)
    return (qparams, dataclasses.replace(cfg, force_unroll_plan=True),
            stacks_by_layer)


def _compress_layer(lp, spec: LayerSpec, qcfg, plan, stats,
                    stacks_by_layer: List[Dict]) -> Dict:
    """One unrolled layer's params with its MoE experts compressed (the
    stacks are also appended to ``stacks_by_layer``)."""
    from ..core.pipeline import compress_ffn_weights
    lp = dict(lp)
    if spec.ffn != "moe":
        return lp
    li = len(stacks_by_layer)
    mp = dict(lp["moe"])
    stacks, _ = compress_ffn_weights(
        mp.pop("w1"), mp.pop("w2"), mp.pop("w3"), qcfg,
        allocation=plan.layers[li] if plan is not None else None,
        stats=stats[li] if stats is not None else None)
    stacks_by_layer.append(stacks)
    mp["stacks"] = stacks
    lp["moe"] = mp
    return lp


def init_compressed_params(key, cfg: ModelConfig, dtype=jnp.bfloat16,
                           place: Optional[Callable] = None):
    """``compress_moe_params(init_params(key, cfg, dtype), cfg)``, built
    one layer at a time.

    The same keys draw the same weights, but only one layer's dense
    experts exist at any moment, so a model whose dense form does not
    fit the device still boots compressed.  ``place`` (optional) maps
    each finished layer's params, e.g. onto a serving mesh, before the
    next layer is drawn, so the model is never whole on one device.
    Returns ``(qparams, cfg_q, stacks_by_layer)`` like
    ``compress_moe_params``.
    """
    place = place or (lambda tree: tree)
    stacks_by_layer: List[Dict] = []

    def finish(lp, spec):
        lp = place(_compress_layer(lp, spec, cfg.moe.quant, None, None,
                                   stacks_by_layer))
        if spec.ffn == "moe":
            stacks_by_layer[-1] = lp["moe"]["stacks"]
        return lp

    outer, layers, encoder = _draw_params(key, cfg, dtype, finish, place)
    qparams = dict(outer)
    qparams["segments"] = tuple((lp,) for reps in layers for rep in reps
                                for lp in rep)
    if encoder is not None:
        qparams["encoder"] = encoder
    return (qparams, dataclasses.replace(cfg, force_unroll_plan=True),
            stacks_by_layer)


def apply_compressed_stacks(params, cfg: ModelConfig, stacks_by_layer):
    """Swap precompressed ``CompressedExpertStack`` dicts into the MoE
    layers of a freshly-initialized param tree — the artifact boot path
    (``launch/serve.py --artifact``): no HQQ / SVD runs, the stacks come
    straight off disk.  Returns ``(qparams, cfg_q)`` in exactly the
    layout ``compress_moe_params`` produces, so serving from an artifact
    is bit-identical to serving from in-memory compression of the same
    plan."""
    up = unstack_params(params, cfg)
    specs = layer_specs(cfg)
    n_moe = sum(1 for s in specs if s.ffn == "moe")
    if n_moe != len(stacks_by_layer):
        raise ValueError(f"artifact has {len(stacks_by_layer)} MoE layers; "
                         f"config {cfg.name} has {n_moe}")
    segs = []
    li = 0
    for (lp,), spec in zip(up["segments"], specs):
        lp = dict(lp)
        if spec.ffn == "moe":
            mp = dict(lp["moe"])
            mp["stacks"] = stacks_by_layer[li]
            for k in ("w1", "w2", "w3"):
                mp.pop(k)
            lp["moe"] = mp
            li += 1
        segs.append((lp,))
    qparams = dict(up)
    qparams["segments"] = tuple(segs)
    return qparams, dataclasses.replace(cfg, force_unroll_plan=True)


# ---------------------------------------------------------------------------
# cache init
# ---------------------------------------------------------------------------

def init_caches(cfg: ModelConfig, batch: int, max_len: int,
                dtype=jnp.bfloat16) -> Dict:
    plan = derive_plan(cfg)

    def one_cache(spec: LayerSpec):
        if spec.mixer in ("global", "local"):
            length = (min(cfg.window_size, max_len)
                      if spec.mixer == "local" else max_len)
            c = init_attn_cache(batch, length, cfg.num_kv_heads, cfg.head_dim,
                                dtype, kv_bits=cfg.kv_bits)
            if spec.cross:
                e = cfg.encoder
                c["cross_k"] = jnp.zeros((batch, e.source_len, cfg.num_heads,
                                          cfg.head_dim), dtype)
                c["cross_v"] = jnp.zeros((batch, e.source_len, cfg.num_heads,
                                          cfg.head_dim), dtype)
            return c
        if spec.mixer == "recurrent":
            return init_rglru_cache(batch, cfg.lru_width or cfg.d_model,
                                    cfg.conv1d_width)
        if spec.mixer == "mlstm":
            di = 2 * cfg.d_model
            return init_mlstm_cache(batch, cfg.num_heads, di // cfg.num_heads)
        if spec.mixer == "slstm":
            return init_slstm_cache(batch, cfg.num_heads,
                                    cfg.d_model // cfg.num_heads)
        raise ValueError(spec.mixer)

    segs = []
    for seg in plan:
        pos = []
        for spec in seg.layers:
            c = one_cache(spec)
            if seg.repeat > 1:
                c = jax.tree.map(
                    lambda x: jnp.broadcast_to(x, (seg.repeat,) + x.shape), c)
            pos.append(c)
        segs.append(tuple(pos))
    return {"segments": tuple(segs), "pos": jnp.zeros((batch,), jnp.int32)}


def init_paged_caches(cfg: ModelConfig, num_slots: int, num_pages: int,
                      page_size: int, max_blocks: int,
                      dtype=jnp.bfloat16) -> Dict:
    """Slotted serve cache with *paged* global-attention layers.

    Global layers get a (num_pages, page_size, ...) physical pool plus a
    (num_slots, max_blocks) block table — each layer owns its own pool
    buffers, but all layers share one logical page-id space, so the host
    allocator hands out a single page list per request.  Local ring
    caches are already window-bounded (no padded-prefill waste to
    reclaim) and recurrent/xLSTM states are O(1), so those stay in their
    contiguous slot-indexed form.
    """
    plan = derive_plan(cfg)

    def one_cache(spec: LayerSpec):
        if spec.mixer == "global":
            if spec.cross:
                raise NotImplementedError("paged cache with cross-attention")
            return init_paged_attn_cache(num_slots, num_pages, page_size,
                                         max_blocks, cfg.num_kv_heads,
                                         cfg.head_dim, dtype,
                                         kv_bits=cfg.kv_bits)
        if spec.mixer == "local":
            length = min(cfg.window_size, max_blocks * page_size)
            return init_attn_cache(num_slots, length, cfg.num_kv_heads,
                                   cfg.head_dim, dtype, kv_bits=cfg.kv_bits)
        if spec.mixer == "recurrent":
            return init_rglru_cache(num_slots, cfg.lru_width or cfg.d_model,
                                    cfg.conv1d_width)
        if spec.mixer == "mlstm":
            di = 2 * cfg.d_model
            return init_mlstm_cache(num_slots, cfg.num_heads,
                                    di // cfg.num_heads)
        if spec.mixer == "slstm":
            return init_slstm_cache(num_slots, cfg.num_heads,
                                    cfg.d_model // cfg.num_heads)
        raise ValueError(spec.mixer)

    segs = []
    for seg in plan:
        pos = []
        for spec in seg.layers:
            c = one_cache(spec)
            if seg.repeat > 1:
                c = jax.tree.map(
                    lambda x: jnp.broadcast_to(x, (seg.repeat,) + x.shape), c)
            pos.append(c)
        segs.append(tuple(pos))
    return {"segments": tuple(segs),
            "pos": jnp.zeros((num_slots,), jnp.int32)}


# ---------------------------------------------------------------------------
# slot-indexed cache ops (continuous-batching scheduler)
# ---------------------------------------------------------------------------

def _map_segments(cfg: ModelConfig, fn, *cache_trees):
    """Apply ``fn(layer_cache..., batch_axis)`` to every per-layer cache
    dict; scanned segments carry a leading repeat axis, so their batch
    axis is 1 instead of 0."""
    plan = derive_plan(cfg)
    segs = []
    for si, seg in enumerate(plan):
        ax = 1 if seg.repeat > 1 else 0
        pos = []
        for pi in range(len(seg.layers)):
            pos.append(fn(*[t["segments"][si][pi] for t in cache_trees], ax))
        segs.append(tuple(pos))
    return tuple(segs)


def cache_claim_slot(cfg: ModelConfig, caches: Dict, req_caches: Dict,
                     slot: int) -> Dict:
    """Write a batch-1 prefilled cache into batch row ``slot`` of a slotted
    cache (same cfg / cache length); the slot's absolute position comes
    along from ``req_caches['pos']``."""
    segs = _map_segments(
        cfg, lambda g, r, ax: claim_slot(g, r, slot, ax), caches, req_caches)
    pos = jax.lax.dynamic_update_slice_in_dim(
        caches["pos"], req_caches["pos"].astype(jnp.int32), slot, 0)
    return {"segments": segs, "pos": pos}


def cache_reset_slot(cfg: ModelConfig, caches: Dict, slot: int) -> Dict:
    """Clear batch row ``slot`` back to the empty state (pos planes -1)."""
    segs = _map_segments(cfg, lambda g, ax: reset_slot(g, slot, ax), caches)
    pos = jax.lax.dynamic_update_slice_in_dim(
        caches["pos"], jnp.zeros((1,), jnp.int32), slot, 0)
    return {"segments": segs, "pos": pos}


def cache_claim_slot_paged(cfg: ModelConfig, caches: Dict, req_caches: Dict,
                           slot, pages, write_mask) -> Dict:
    """Paged twin of ``cache_claim_slot``: paged layers map ``pages`` into
    their block-table row and scatter the request's contiguous prefilled
    chunks into the pool; non-paged layers (local rings, recurrent state)
    claim their slot row as before.  ``slot``/``pages``/``write_mask``
    are traced, so one compile serves every admission of a given
    prompt-length bucket."""
    def claim(g, r, ax: int):
        if "block" in g:
            if ax == 1:   # scanned segment: map over the repeat axis
                return jax.vmap(
                    lambda gc, rc: paged_claim(gc, rc, slot, pages,
                                               write_mask))(g, r)
            return paged_claim(g, r, slot, pages, write_mask)
        return claim_slot(g, r, slot, ax)

    segs = _map_segments(cfg, claim, caches, req_caches)
    pos = jax.lax.dynamic_update_slice_in_dim(
        caches["pos"], req_caches["pos"].astype(jnp.int32), slot, 0)
    return {"segments": segs, "pos": pos}


def cache_reset_slot_paged(cfg: ModelConfig, caches: Dict, slot) -> Dict:
    """Paged twin of ``cache_reset_slot``: paged layers only unmap the
    slot's block-table row (page contents are rewritten on next claim)."""
    def reset(g, ax: int):
        if "block" in g:
            if ax == 1:
                return jax.vmap(lambda gc: paged_reset(gc, slot))(g)
            return paged_reset(g, slot)
        return reset_slot(g, slot, ax)

    segs = _map_segments(cfg, reset, caches)
    pos = jax.lax.dynamic_update_slice_in_dim(
        caches["pos"], jnp.zeros((1,), jnp.int32), slot, 0)
    return {"segments": segs, "pos": pos}


def cache_seed_prefix(cfg: ModelConfig, req_caches: Dict, caches: Dict,
                      pages) -> Dict:
    """Seed a batch-1 contiguous request cache with the shared-prefix
    pages of a paged serve cache (``pages``: (max_blocks,) page ids, -1
    past the shared span), so a suffix-only prefill attends over reused
    prefix KV without recomputing it.  Only paged (global) layers seed;
    prefix reuse requires an all-global plan, so there is nothing to
    seed elsewhere."""
    def seed(r, g, ax: int):
        if "block" not in g:
            return r
        if ax == 1:
            return jax.vmap(
                lambda rc, gc: paged_seed_prefix(rc, gc, pages))(r, g)
        return paged_seed_prefix(r, g, pages)

    segs = _map_segments(cfg, seed, req_caches, caches)
    return {"segments": segs, "pos": req_caches["pos"]}


def mask_cache_padding(cfg: ModelConfig, caches: Dict, plen: jax.Array
                       ) -> Dict:
    """Invalidate cache entries written by right-padded prefill tokens.

    ``plen``: (B,) true prompt lengths.  Attention position planes at
    absolute positions >= plen become -1 (the decode-attention "empty"
    sentinel), and the per-row decode position is pinned to plen — so a
    prompt padded up to its length bucket decodes exactly like an unpadded
    one.  Recurrent states have no per-position plane and cannot be
    unpolluted this way; callers only right-pad attention-only plans."""
    def mask(c, ax):
        if not (isinstance(c, dict) and "pos" in c):
            return c
        if "block" in c:   # paged pos plane is pool-shaped, not per-slot
            return c
        lim = plen[None, :, None] if ax == 1 else plen[:, None]
        out = dict(c)
        out["pos"] = jnp.where(c["pos"] >= lim, -1, c["pos"])
        return out

    segs = _map_segments(cfg, mask, caches)
    return {"segments": segs, "pos": plen.astype(jnp.int32)}


def cache_rollback(cfg: ModelConfig, caches: Dict, new_len: jax.Array
                   ) -> Dict:
    """Roll a slotted cache back to ``new_len`` (B,) committed tokens.

    Speculative decoding's verify pass appends KV for every drafted
    token; rejection keeps only a per-row accepted prefix.  Attention
    entries at absolute positions >= new_len are invalidated (pos -> -1)
    AND their K/V payloads (plus int8 scales) are zeroed — fresh cache
    planes are zero-filled and, under an all-'global' plan with enough
    ring headroom, append-only, so the rolled-back cache is bit-identical
    to one that never saw the rejected suffix.

    Paged layers mask the pool through the block table: each mapped page
    takes the min ``new_len`` over its owner slots.  Refcount-shared
    prefix pages hold only positions below every owner's prompt length
    (<= any new_len), so they are untouched, and the trash page is
    exempted from the scatter so out-of-range verify writes parked there
    don't leak a limit onto it.  Recurrent / local-ring states have no
    per-position plane and cannot roll back; callers gate speculation to
    all-'global' mixer plans.
    """
    new_len = new_len.astype(jnp.int32)

    def wipe(out, bad):
        out["pos"] = jnp.where(bad, -1, out["pos"])
        for kk in ("k", "v"):
            out[kk] = jnp.where(bad[..., None, None],
                                jnp.zeros_like(out[kk]), out[kk])
        for kk in ("k_scale", "v_scale"):
            # dict-key membership on a static plane name, not traced:
            if kk in out:  # repro-lint: disable=RL102
                out[kk] = jnp.where(bad[..., None],
                                    jnp.zeros_like(out[kk]), out[kk])
        return out

    def roll(c, ax):
        if not (isinstance(c, dict) and "pos" in c):
            return c
        out = dict(c)
        if "block" in c:
            imax = jnp.iinfo(jnp.int32).max

            def pool_mask(blk, pos):
                # per-page limit = min new_len over owner slots; unmapped
                # block entries (-1) land on the trash page, which is
                # reset to "no limit" afterwards
                lim = jnp.full((pos.shape[0],), imax, jnp.int32)
                lim = lim.at[jnp.maximum(blk, 0)].min(
                    jnp.broadcast_to(new_len[:, None], blk.shape))
                lim = lim.at[TRASH_PAGE].set(imax)
                return pos >= lim[:, None]

            # ax is the segment's static batch axis (derive_plan), not
            # traced:
            if ax == 1:  # repro-lint: disable=RL102
                # scanned segment: map over the repeat axis
                bad = jax.vmap(pool_mask)(c["block"], c["pos"])
            else:
                bad = pool_mask(c["block"], c["pos"])
            return wipe(out, bad)
        lim = new_len[None, :, None] if ax == 1 else new_len[:, None]
        return wipe(out, c["pos"] >= lim)

    segs = _map_segments(cfg, roll, caches)
    return {"segments": segs, "pos": new_len}


# ---------------------------------------------------------------------------
# layer application
# ---------------------------------------------------------------------------

def _project_qkv(x, ap, cfg: ModelConfig):
    q = jnp.einsum("bsd,dhk->bshk", x, ap["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, ap["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, ap["wv"])
    if "bq" in ap:
        q, k, v = q + ap["bq"], k + ap["bk"], v + ap["bv"]
    return q, k, v


def _rope(q, k, cfg: ModelConfig, kind: str, positions, mrope_pos):
    if cfg.rope_kind == "none":
        return q, k
    theta = cfg.rope_theta
    if kind == "local" and cfg.rope_local_theta:
        theta = cfg.rope_local_theta
    if cfg.rope_kind == "mrope" and mrope_pos is not None:
        return (apply_mrope(q, mrope_pos, theta),
                apply_mrope(k, mrope_pos, theta))
    return apply_rope(q, positions, theta), apply_rope(k, positions, theta)


def _heads_out(out, wo):
    """The out-projection, summed over heads in float32 and rounded once:
    a serve mesh shards the heads, and a shard that rounded its partial
    sum to bf16 before the all-reduce would change the tokens with the
    shard count."""
    y = jnp.einsum("bshk,hkd->bsd", out, wo,
                   preferred_element_type=jnp.float32)
    return y.astype(jnp.result_type(out, wo))


def _attn_layer(x, ap, cfg: ModelConfig, ctx: ExecContext, spec: LayerSpec,
                positions, cache, mrope_pos, enc_out):
    window = cfg.window_size if spec.mixer == "local" else None
    q, k, v = _project_qkv(x, ap, cfg)
    q, k = _rope(q, k, cfg, spec.mixer, positions, mrope_pos)
    if ctx.mode != "step":
        if ctx.attn_heads_sharded:
            q = ctx.constrain(q, ("batch", None, "heads", None))
            k = ctx.constrain(k, ("batch", None, "kv_heads", None))
            v = ctx.constrain(v, ("batch", None, "kv_heads", None))
        elif ctx.attn_seq_sharded:
            k = ctx.constrain(k, ("batch", "kv_seq", None, None))
            v = ctx.constrain(v, ("batch", "kv_seq", None, None))
    new_cache = cache
    if ctx.mode == "step":
        new_cache = dict(cache)
        kv_keys = ("k", "v", "pos") + (("k_scale", "v_scale")
                                       if "k_scale" in cache else ())
        if "block" in cache:
            # paged: scatter through the block table, then gather each
            # slot's logical view back out of the pool — block-table
            # contents are data, so one compile covers every length mix
            upd = paged_update_attn_cache(
                {kk: cache[kk] for kk in kv_keys + ("block",)},
                k, v, positions)
            new_cache.update(upd)
            kf, vf, posf, ksf, vsf = paged_gather(upd)
            with jax.named_scope("attention"):
                out = decode_attention(q, kf, vf, posf, positions,
                                       window=window, k_scale=ksf,
                                       v_scale=vsf)
        else:
            upd = update_attn_cache({kk: cache[kk] for kk in kv_keys},
                                    k, v, positions)
            new_cache.update(upd)
            with jax.named_scope("attention"):
                out = decode_attention(q, upd["k"], upd["v"], upd["pos"],
                                       positions, window=window,
                                       k_scale=upd.get("k_scale"),
                                       v_scale=upd.get("v_scale"))
    else:
        with jax.named_scope("attention"):
            out = attention(q, k, v, positions, positions, causal=True,
                            window=window, q_block=ctx.q_block,
                            unroll=ctx.scan_unroll)
        if ctx.mode == "prefill" and cache is not None:
            new_cache = dict(cache)
            kv_keys = ("k", "v", "pos") + (("k_scale", "v_scale")
                                           if "k_scale" in cache else ())
            upd = prefill_attn_cache({kk: cache[kk] for kk in kv_keys},
                                     k, v, positions)
            new_cache.update(upd)
    y = _heads_out(out, ap["wo"])
    # cross-attention (enc-dec decoder)
    if spec.cross:
        xc = rms_norm(x + y, ap["cross_norm"], cfg.norm_eps)
        qc = jnp.einsum("bsd,dhk->bshk", xc, ap["cross_wq"])
        if ctx.mode == "step":
            ck, cv = cache["cross_k"], cache["cross_v"]
        else:
            ck = jnp.einsum("bsd,dhk->bshk", enc_out, ap["cross_wk"])
            cv = jnp.einsum("bsd,dhk->bshk", enc_out, ap["cross_wv"])
            if ctx.mode == "prefill" and new_cache is not None:
                new_cache["cross_k"] = ck.astype(new_cache["cross_k"].dtype)
                new_cache["cross_v"] = cv.astype(new_cache["cross_v"].dtype)
        src = ck.shape[1]
        src_pos = jnp.broadcast_to(jnp.arange(src), (ck.shape[0], src))
        with jax.named_scope("attention"):
            co = attention(qc, ck, cv,
                           jnp.zeros_like(positions) + src,  # no causal mask
                           src_pos, causal=False, q_block=ctx.q_block,
                           unroll=ctx.scan_unroll)
        y = y + _heads_out(co, ap["cross_wo"])
    return y, new_cache


def _mlstm_block(x, p, cfg: ModelConfig, ctx: ExecContext, cache):
    mp = p["mlstm"]
    h_in = rms_norm(x, p["pre_norm"], cfg.norm_eps)
    u, z = jnp.split(jnp.einsum("bsd,de->bse", h_in, mp["w_up"]), 2, axis=-1)
    q = jnp.einsum("bse,ehk->bshk", u, mp["wq"])
    k = jnp.einsum("bse,ehk->bshk", u, mp["wk"])
    v = jnp.einsum("bse,ehk->bshk", u, mp["wv"])
    gates = jnp.einsum("bse,eg->bsg", u.astype(jnp.float32), mp["w_if"])
    gates = gates + mp["b_if"]
    nh = cfg.num_heads
    log_i, log_f = gates[..., :nh], jax.nn.log_sigmoid(gates[..., nh:])
    state = cache
    if ctx.mode == "step":
        h, new_state = mlstm_step(q, k, v, log_i, log_f, state)
    else:
        h, new_state = mlstm_chunkwise(q, k, v, log_i, log_f,
                                       state if ctx.mode == "prefill" else None,
                                       chunk=ctx.mlstm_chunk,
                                       unroll=ctx.scan_unroll)
    b, s = x.shape[0], x.shape[1]
    h = h.reshape(b, s, -1)
    h = rms_norm(h, mp["out_norm"], cfg.norm_eps) * jax.nn.silu(z)
    out = jnp.einsum("bse,ed->bsd", h, mp["w_down"])
    return x + out, (new_state if ctx.mode in ("prefill", "step") else cache)


def _slstm_block(x, p, cfg: ModelConfig, ctx: ExecContext, cache):
    sp = p["slstm"]
    h_in = rms_norm(x, p["pre_norm"], cfg.norm_eps)
    pre = jnp.einsum("bsd,dghk->bsghk", h_in, sp["w_zifo"]) + sp["b_zifo"]
    rec = {k: sp[k] for k in ("rz", "ri", "rf", "ro")}
    state = cache if ctx.mode in ("prefill", "step") else None
    h, new_state = slstm_seq(pre, rec, state)
    b, s = x.shape[0], x.shape[1]
    h = h.reshape(b, s, -1)
    h = rms_norm(h, sp["out_norm"], cfg.norm_eps)
    x = x + h
    # post-cell gated FFN
    hf = rms_norm(x, sp["ffn_norm"], cfg.norm_eps)
    x = x + ffn_apply(hf, sp["ffn"], cfg.act, True)
    return x, (new_state if ctx.mode in ("prefill", "step") else cache)


def apply_layer(x, p, spec: LayerSpec, cfg: ModelConfig, ctx: ExecContext,
                positions, cache, mrope_pos=None, enc_out=None,
                plan_row=None):
    """One transformer layer.  Returns (x, aux, new_cache, trace, moe_in).

    ``trace`` is the (T, k) top-k expert ids of this layer's router when
    ``ctx.collect_trace`` is set and the layer is MoE, else None (static).
    ``moe_in`` is the (T, d) normed MoE-FFN input when
    ``ctx.collect_moe_inputs`` is set (calibration pass), else None.
    ``plan_row`` is this layer's (2,) int32 [top_n, rank_cap] row of the
    bandwidth controller's restoration plan (None = static QuantConfig).
    """
    aux = {}
    if spec.mixer == "mlstm":
        x, nc = _mlstm_block(x, p, cfg, ctx, cache)
        return x, aux, nc, None, None
    if spec.mixer == "slstm":
        x, nc = _slstm_block(x, p, cfg, ctx, cache)
        return x, aux, nc, None, None

    h = rms_norm(x, p["pre_norm"], cfg.norm_eps)
    if spec.mixer in ("global", "local"):
        y, nc = _attn_layer(h, p["attn"], cfg, ctx, spec, positions, cache,
                            mrope_pos, enc_out)
    elif spec.mixer == "recurrent":
        if ctx.mode == "step":
            y, new_state = rglru_step(h, p["rglru"], cache)
        else:
            y, new_state = rglru_seq(
                h, p["rglru"],
                h0=cache["h"] if (ctx.mode == "prefill" and cache) else None,
                conv_state=cache["conv"] if (ctx.mode == "prefill" and cache)
                else None)
        nc = new_state if ctx.mode in ("prefill", "step") else cache
    if cfg.post_attn_norm:
        y = rms_norm(y, p["post_norm"], cfg.norm_eps)
    x = x + y

    if spec.ffn == "none":
        return x, aux, nc, None, None
    trace = None
    moe_in = None
    h = rms_norm(x, p["ffn_norm"], cfg.norm_eps)
    if spec.ffn == "dense":
        if ctx.quantized and "stacks" in p.get("ffn", {}):
            y = ffn_apply_quantized(h, p["ffn"]["stacks"], cfg.act,
                                    cfg.gated_ffn, impl=ctx.kernel_impl)
        else:
            y = ffn_apply(h, p["ffn"], cfg.act, cfg.gated_ffn)
    else:  # moe
        mp = p["moe"]
        if ctx.moe_ep_fn is not None and ctx.ep_mode != "none":
            # topk: (b, s, k); the controller's plan row rides into the
            # shard_map region as replicated data (no recompile on change)
            y, aux, topk = ctx.moe_ep_fn(h, mp, cfg, ctx, plan_row)
        else:
            b, s, d = h.shape
            y2, aux, info = moe_apply(
                h.reshape(-1, d), mp, cfg.moe, act=cfg.act,
                quantized=ctx.quantized and "stacks" in mp,
                exact_capacity=ctx.exact_capacity, impl=ctx.kernel_impl,
                plan=plan_row)
            y = y2.reshape(b, s, d)
            topk = info.topk_idx.reshape(b, s, -1)
        if ctx.collect_trace:
            trace = topk.reshape(-1, topk.shape[-1]).astype(jnp.int32)
        if ctx.collect_moe_inputs:
            moe_in = h.reshape(-1, h.shape[-1]).astype(jnp.float32)
        if "shared" in mp:
            y = y + ffn_apply(h, mp["shared"], cfg.act, True)
    if cfg.post_attn_norm:
        y = rms_norm(y, p["post_ffn_norm"], cfg.norm_eps)
    return x + y, aux, nc, trace, moe_in


# ---------------------------------------------------------------------------
# stack application (scan over segment repeats)
# ---------------------------------------------------------------------------

def _remat(fn, ctx: ExecContext):
    if not ctx.remat:
        return fn
    if ctx.remat_policy == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(fn)


def _zero_aux():
    return {"load_balance": jnp.zeros((), jnp.float32),
            "router_z": jnp.zeros((), jnp.float32)}


def _merge_aux(a, b):
    out = dict(a)
    for k, v in b.items():
        out[k] = out.get(k, 0.0) + v
    return out


def apply_stack(params, x, cfg: ModelConfig, ctx: ExecContext, positions,
                caches=None, mrope_pos=None, enc_out=None, plan=None):
    """Run all segments.  Returns (x, aux, new_caches, trace, moe_inputs).

    ``trace`` is the stacked (moe_layers, T, k) router top-k ids in global
    layer order when ``ctx.collect_trace`` is set (None otherwise) — the
    first-class replacement for hooking ``moe.route``.  ``moe_inputs``
    is the stacked (moe_layers, T, d) normed MoE-FFN inputs in the same
    order when ``ctx.collect_moe_inputs`` is set (the calibration pass).

    ``plan`` is the bandwidth controller's (num_moe_layers, 2) int32
    [top_n, rank_cap] array in the same global MoE-layer order as the
    trace.  It is *data*, not structure: the array threads into scanned
    segments as scan xs, so runtime plan updates reuse the compiled fn.
    """
    seg_plan_all = derive_plan(cfg)
    aux = _zero_aux()
    new_segs = []
    traces: List[jax.Array] = []
    moe_ins: List[jax.Array] = []
    use_cache = caches is not None and ctx.mode in ("prefill", "step")
    moe_off = 0

    for si, seg in enumerate(seg_plan_all):
        seg_params = params["segments"][si]
        seg_caches = (caches["segments"][si] if use_cache
                      else tuple(None for _ in seg.layers))
        n_moe = sum(1 for spec in seg.layers if spec.ffn == "moe")
        seg_plan = None
        if plan is not None and n_moe:
            cnt = n_moe * seg.repeat
            # global order interleaves positions within each repeat
            # (matches _unstack_scan_traces), so the reshape below lines
            # plan rows up with the scanned repeats
            seg_plan = plan[moe_off:moe_off + cnt]
            moe_off += cnt
            if seg.repeat > 1:
                seg_plan = seg_plan.reshape(seg.repeat, n_moe, 2)

        def group(x, gp, gc, gpl):
            dtype0 = x.dtype
            ga = _zero_aux()
            ncs = []
            trs = []
            mis = []
            mi = 0
            for pi, spec in enumerate(seg.layers):
                row = None
                if gpl is not None and spec.ffn == "moe":
                    row = gpl[mi]
                    mi += 1
                x, a, nc, tr, m_in = apply_layer(x, gp[pi], spec, cfg, ctx,
                                                 positions,
                                                 gc[pi] if use_cache else None,
                                                 mrope_pos, enc_out,
                                                 plan_row=row)
                x = x.astype(dtype0)  # keep scan carry dtype stable
                ga = _merge_aux(ga, a)
                ncs.append(nc if use_cache else 0)
                if tr is not None:
                    trs.append(tr)
                if m_in is not None:
                    mis.append(m_in)
            return x, ga, tuple(ncs), tuple(trs), tuple(mis)

        if seg.repeat == 1:
            x, ga, nc, trs, mis = group(x, seg_params, seg_caches, seg_plan)
            aux = _merge_aux(aux, ga)
            new_segs.append(nc)
            traces.extend(trs)
            moe_ins.extend(mis)
        elif use_cache:
            # the plan (when present) rides the scan as an extra xs leaf
            xs = (seg_params, seg_caches) + (
                (seg_plan,) if seg_plan is not None else ())

            def body_c(carry, xs):
                gp, gc, *gpl = xs
                fn = _remat(group, ctx)
                xo, ga, nc, trs, mis = fn(carry, gp, gc,
                                          gpl[0] if gpl else None)
                return xo, (ga, nc, trs, mis)

            x, (gas, ncs, trs, mis) = jax.lax.scan(body_c, x, xs,
                                                   unroll=ctx.scan_unroll)
            aux = _merge_aux(aux, jax.tree.map(jnp.sum, gas))
            new_segs.append(ncs)
            traces.extend(_unstack_scan_traces(trs))
            moe_ins.extend(_unstack_scan_traces(mis))
        else:
            dummy = tuple(None for _ in seg.layers)
            xs = (seg_params,) + (
                (seg_plan,) if seg_plan is not None else ())

            def body(carry, xs):
                gp, *gpl = xs
                fn = _remat(group, ctx)
                xo, ga, _, trs, mis = fn(carry, gp, dummy,
                                         gpl[0] if gpl else None)
                return xo, (ga, trs, mis)

            x, (gas, trs, mis) = jax.lax.scan(body, x, xs,
                                              unroll=ctx.scan_unroll)
            aux = _merge_aux(aux, jax.tree.map(jnp.sum, gas))
            new_segs.append(0)
            traces.extend(_unstack_scan_traces(trs))
            moe_ins.extend(_unstack_scan_traces(mis))

    new_caches = None
    if use_cache:
        new_caches = {"segments": tuple(new_segs), "pos": positions[:, -1] + 1}
    trace = jnp.stack(traces, axis=0) if traces else None
    moe_inputs = jnp.stack(moe_ins, axis=0) if moe_ins else None
    return x, aux, new_caches, trace, moe_inputs


def _unstack_scan_traces(trs) -> List[jax.Array]:
    """Scan-stacked per-position traces -> flat global layer order.

    ``trs`` is a tuple (one per MoE position in the segment pattern) of
    (repeat, T, k) arrays; global order interleaves positions within each
    repeat: [rep0/pos0, rep0/pos1, ..., rep1/pos0, ...].
    """
    # tuple emptiness test, not array truthiness:
    if not trs:  # repro-lint: disable=RL102
        return []
    stacked = jnp.stack(trs, axis=1)          # (repeat, npos, T, k)
    r, p, t, k = stacked.shape
    return list(stacked.reshape(r * p, t, k))


def apply_encoder(params, embeds, cfg: ModelConfig, ctx: ExecContext):
    """Whisper-style bidirectional encoder over stub frame embeddings."""
    e = cfg.encoder
    dtype = params["encoder"]["layers"]["ffn"]["w1"].dtype
    x = embeds.astype(dtype)
    src = x.shape[1]
    pos = jnp.broadcast_to(jnp.arange(src), (x.shape[0], src))

    def body(carry, lp):
        h = rms_norm(carry, lp["pre_norm"], cfg.norm_eps)
        q = jnp.einsum("bsd,dhk->bshk", h, lp["attn"]["wq"])
        k = jnp.einsum("bsd,dhk->bshk", h, lp["attn"]["wk"])
        v = jnp.einsum("bsd,dhk->bshk", h, lp["attn"]["wv"])
        o = attention(q, k, v, pos, pos, causal=False, q_block=ctx.q_block,
                      unroll=ctx.scan_unroll)
        carry = carry + jnp.einsum("bshk,hkd->bsd", o, lp["attn"]["wo"])
        h = rms_norm(carry, lp["ffn_norm"], cfg.norm_eps)
        carry = carry + ffn_apply(h, lp["ffn"], "gelu", False)
        return carry.astype(dtype), 0

    x, _ = jax.lax.scan(body, x, params["encoder"]["layers"],
                        unroll=ctx.scan_unroll)
    return rms_norm(x, params["encoder"]["final_norm"], cfg.norm_eps)
