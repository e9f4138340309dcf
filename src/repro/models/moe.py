"""Mixture-of-Experts layer with router-guided low-rank restoration.

Three execution paths share one routing/dispatch core:

- ``moe_apply`` (single-shard): capacity dispatch via scatter/gather —
  used by smoke tests, examples, and *inside* the shard_map paths.
- ``moe_apply_ep_a2a`` (train/prefill): tokens sharded over (pod, data[,
  model]); experts sharded over ``model``; two ``lax.all_to_all``s move
  dispatched tokens to their expert shard and back.
- ``moe_apply_ep_replicated`` (decode): tokens replicated over ``model``;
  each shard computes only its resident experts and a psum combines.

The paper's technique rides the same dispatch: when expert weights are
``CompressedExpertStack``s, each (expert, slot) carries a 0/1 top-n mask
and the expert FFN applies the low-rank compensator only where masked.
Execution of the expert FFN itself (dense einsum / reference quantized /
fused Pallas kernel) is owned by ``models.expert_backend`` and selected
via the ``kernels.ops`` impl policy.  Every path also returns its
``RoutingInfo`` so callers (serve engine, offload metering) get the
router trace as a first-class output instead of hooking ``route``.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.lax import axis_size

from ..config import MoEConfig
from .expert_backend import (ExpertBackend, expert_ffn_dense,
                             select_backend)


class RoutingInfo(NamedTuple):
    gates: jax.Array        # (T, k) normalized top-k gate values
    topk_idx: jax.Array     # (T, k) expert ids, descending score
    probs: jax.Array        # (T, E) full softmax (aux losses)
    logits: jax.Array       # (T, E)


def route(x2: jax.Array, w_router: jax.Array, mcfg: MoEConfig) -> RoutingInfo:
    """x2: (T, d) -> routing for top-k experts (softmax-then-topk)."""
    logits = jnp.einsum("td,de->te", x2.astype(jnp.float32),
                        w_router.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gates, topi = jax.lax.top_k(probs, mcfg.top_k)
    if mcfg.router_norm_topk:
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    return RoutingInfo(gates, topi, probs, logits)


def aux_losses(info: RoutingInfo, mcfg: MoEConfig) -> Dict[str, jax.Array]:
    """Switch-style load-balance + router z-loss (mean over local tokens)."""
    t, e = info.probs.shape
    top1 = info.topk_idx[:, 0]
    frac = jnp.mean(jax.nn.one_hot(top1, e, dtype=jnp.float32), axis=0)
    mean_prob = jnp.mean(info.probs, axis=0)
    lb = e * jnp.sum(frac * mean_prob)
    z = jnp.mean(jax.scipy.special.logsumexp(info.logits, axis=-1) ** 2)
    return {"load_balance": lb * mcfg.router_aux_weight,
            "router_z": z * mcfg.router_z_weight}


# ---------------------------------------------------------------------------
# capacity dispatch (index-based: O(T k d), no (T, E, C) einsum blowup)
# ---------------------------------------------------------------------------

class Dispatch(NamedTuple):
    e_idx: jax.Array        # (T*k,) target expert per assignment
    slot: jax.Array         # (T*k,) capacity slot (>=C means dropped)
    t_idx: jax.Array        # (T*k,) source token per assignment
    gates: jax.Array        # (T*k,)
    comp: jax.Array         # (T*k,) 1.0 if assignment rank < top_n_restore
    capacity: int


def make_dispatch(info: RoutingInfo, num_experts: int, capacity: int,
                  top_n) -> Dispatch:
    """``top_n`` may be a static int or a traced scalar (the bandwidth
    controller's per-layer plan): the comp mask is a compare either way,
    so a runtime plan change never retriggers compilation."""
    t, k = info.topk_idx.shape
    e_idx = info.topk_idx.reshape(-1)
    # slot within expert: exclusive running count of prior assignments
    oh = jax.nn.one_hot(e_idx, num_experts, dtype=jnp.int32)     # (T*k, E)
    slot = (jnp.cumsum(oh, axis=0) - oh)[jnp.arange(t * k), e_idx]
    t_idx = jnp.repeat(jnp.arange(t), k)
    rank = jnp.tile(jnp.arange(k), t)
    comp = (rank < top_n).astype(jnp.float32)
    return Dispatch(e_idx, slot, t_idx, info.gates.reshape(-1), comp,
                    capacity)


def dispatch_tokens(x2: jax.Array, d: Dispatch, num_experts: int
                    ) -> Tuple[jax.Array, jax.Array]:
    """Scatter (T, dm) tokens into (E, C, dm) expert buffers + comp mask."""
    dm = x2.shape[-1]
    xe = jnp.zeros((num_experts, d.capacity, dm), x2.dtype)
    xe = xe.at[d.e_idx, d.slot].set(x2[d.t_idx], mode="drop")
    me = jnp.zeros((num_experts, d.capacity), jnp.float32)
    me = me.at[d.e_idx, d.slot].set(d.comp, mode="drop")
    return xe, me


def dispatch_gates(d: Dispatch, num_experts: int) -> jax.Array:
    """Scatter router gates into the (E, C) slot layout.

    Companion buffer for backends with ``fuses_gates``: the kernel
    multiplies each expert-slot output by its gate (the gate-weighted
    combine), and ``combine_tokens(pre_weighted=True)`` then just
    gathers and scatter-adds.  Dropped assignments (slot >= C) are
    out of bounds for the scatter and vanish via ``mode='drop'``."""
    ge = jnp.zeros((num_experts, d.capacity), jnp.float32)
    return ge.at[d.e_idx, d.slot].set(d.gates, mode="drop")


def combine_tokens(ye: jax.Array, d: Dispatch, num_tokens: int, *,
                   pre_weighted: bool = False) -> jax.Array:
    """Gather (E, C, dm) expert outputs back to (T, dm), gate-weighted.

    ``pre_weighted=True`` means the backend already folded the gates in
    (``ExpertBackend.fuses_gates`` + ``dispatch_gates``): skip the gate
    multiply here — the ``mode='fill'`` gather already zeroes dropped
    assignments (slot >= C reads out of bounds)."""
    ya = ye.at[d.e_idx, d.slot].get(mode="fill", fill_value=0.0)  # (T*k, dm)
    if not pre_weighted:
        # dropped assignments (slot >= C) must contribute zero
        keep = (d.slot < d.capacity).astype(ya.dtype)
        ya = ya * (d.gates * keep)[:, None].astype(ya.dtype)
    y = jnp.zeros((num_tokens, ye.shape[-1]), ya.dtype)
    return y.at[d.t_idx].add(ya)


def _capacity(tokens: int, mcfg: MoEConfig, exact: bool) -> int:
    if exact:
        return tokens
    c = int(math.ceil(tokens * mcfg.top_k * mcfg.capacity_factor
                      / mcfg.num_experts))
    return max(8, -(-c // 8) * 8)  # round up to 8 for layout friendliness


# ---------------------------------------------------------------------------
# single-shard path
# ---------------------------------------------------------------------------

def _plan_knobs(mcfg: MoEConfig, quantized: bool, plan):
    """Resolve (top_n, rank_cap) for one MoE layer invocation.

    ``plan`` is this layer's (2,) int32 row of the bandwidth controller's
    per-layer plan array — traced values, so runtime plan changes reuse
    the compiled fn.  None (controller absent) keeps the static
    ``QuantConfig.top_n_restore`` / uncapped-rank behaviour bit-exactly.
    """
    if not quantized:
        return 0, None
    if plan is None:
        return mcfg.quant.top_n_restore, None
    return plan[0], plan[1]


def moe_apply(x2: jax.Array, params: Dict, mcfg: MoEConfig, *,
              act: str = "silu", quantized: bool = False,
              exact_capacity: bool = False,
              impl: Optional[str] = None,
              backend: Optional[ExpertBackend] = None,
              plan: Optional[jax.Array] = None
              ) -> Tuple[jax.Array, Dict[str, jax.Array], RoutingInfo]:
    """x2: (T, d) -> (T, d), aux losses, routing info.  Runs on one shard."""
    t = x2.shape[0]
    backend = backend or select_backend(params, quantized, impl)
    info = route(x2, params["router"], mcfg)
    cap = _capacity(t, mcfg, exact_capacity)
    top_n, rank_cap = _plan_knobs(mcfg, quantized, plan)
    disp = make_dispatch(info, mcfg.num_experts, cap, top_n)
    xe, me = dispatch_tokens(x2, disp, mcfg.num_experts)
    fuse = getattr(backend, "fuses_gates", False)
    ge = dispatch_gates(disp, mcfg.num_experts) if fuse else None
    ye = backend(xe, params, me, act, rank_cap=rank_cap, gates=ge)
    y = combine_tokens(ye, disp, t, pre_weighted=fuse)
    return y.astype(x2.dtype), aux_losses(info, mcfg), info


# ---------------------------------------------------------------------------
# expert-parallel paths (run INSIDE shard_map; 'model' = EP axis)
# ---------------------------------------------------------------------------

def moe_apply_ep_a2a(x2: jax.Array, params: Dict, mcfg: MoEConfig, *,
                     act: str = "silu", quantized: bool = False,
                     axis: str = "model", impl: Optional[str] = None,
                     backend: Optional[ExpertBackend] = None,
                     plan: Optional[jax.Array] = None,
                     exact_capacity: bool = False
                     ) -> Tuple[jax.Array, Dict[str, jax.Array], RoutingInfo]:
    """Tokens local, experts sharded on ``axis``: dispatch via all_to_all.

    params['w*'] / stack leaves carry the LOCAL expert slice (E_local, ...).
    ``exact_capacity`` dispatches at capacity = local tokens (drop-free),
    so a sharded serve matches the single-device engine's drop behaviour
    token for token.
    """
    t = x2.shape[0]
    ep = axis_size(axis)
    e_total = mcfg.num_experts
    backend = backend or select_backend(params, quantized, impl)
    info = route(x2, params["router"], mcfg)
    cap = _capacity(t, mcfg, exact_capacity)
    top_n, rank_cap = _plan_knobs(mcfg, quantized, plan)
    disp = make_dispatch(info, e_total, cap, top_n)
    xe, me = dispatch_tokens(x2, disp, e_total)          # (E, C, d) local
    fuse = getattr(backend, "fuses_gates", False)
    ge = dispatch_gates(disp, e_total) if fuse else None
    # -> (E_local, C * ep, d): every shard receives its experts' slots
    xe = jax.lax.all_to_all(xe, axis, split_axis=0, concat_axis=1, tiled=True)
    me = jax.lax.all_to_all(me, axis, split_axis=0, concat_axis=1, tiled=True)
    if ge is not None:
        ge = jax.lax.all_to_all(ge, axis, split_axis=0, concat_axis=1,
                                tiled=True)
    ye = backend(xe, params, me, act, rank_cap=rank_cap, gates=ge)
    ye = jax.lax.all_to_all(ye, axis, split_axis=1, concat_axis=0, tiled=True)
    y = combine_tokens(ye, disp, t, pre_weighted=fuse)
    aux = jax.tree.map(lambda v: jax.lax.pmean(v, axis),
                       aux_losses(info, mcfg))
    return y.astype(x2.dtype), aux, info


def moe_apply_ep_replicated(x2: jax.Array, params: Dict, mcfg: MoEConfig, *,
                            act: str = "silu", quantized: bool = False,
                            axis: str = "model", impl: Optional[str] = None,
                            backend: Optional[ExpertBackend] = None,
                            plan: Optional[jax.Array] = None
                            ) -> Tuple[jax.Array, Dict[str, jax.Array],
                                       RoutingInfo]:
    """Decode path: tokens replicated over ``axis``; each shard runs its
    resident experts at exact capacity and a psum combines partials."""
    t = x2.shape[0]
    ep = axis_size(axis)
    m = jax.lax.axis_index(axis)
    e_total = mcfg.num_experts
    e_local = e_total // ep
    backend = backend or select_backend(params, quantized, impl)
    info = route(x2, params["router"], mcfg)
    # map global expert ids into the local slice; foreign ids -> OOB (drop)
    topi_local = info.topk_idx - m * e_local
    oob = (topi_local < 0) | (topi_local >= e_local)
    topi_local = jnp.where(oob, e_local, topi_local)     # OOB sentinel
    local_info = RoutingInfo(jnp.where(oob, 0.0, info.gates), topi_local,
                             info.probs, info.logits)
    top_n, rank_cap = _plan_knobs(mcfg, quantized, plan)
    disp = make_dispatch(local_info, e_local + 1, t, top_n)
    xe, me = dispatch_tokens(x2, disp, e_local + 1)
    xe, me = xe[:e_local], me[:e_local]
    fuse = getattr(backend, "fuses_gates", False)
    ge = dispatch_gates(disp, e_local + 1)[:e_local] if fuse else None
    ye = backend(xe, params, me, act, rank_cap=rank_cap, gates=ge)
    ye = jnp.concatenate([ye, jnp.zeros_like(ye[:1])], axis=0)
    y = combine_tokens(ye, disp, t, pre_weighted=fuse)
    y = jax.lax.psum(y, axis)
    return y.astype(x2.dtype), aux_losses(info, mcfg), info
