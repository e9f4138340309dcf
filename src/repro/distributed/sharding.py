"""Logical-axis sharding: path rules -> PartitionSpecs with divisibility
fallback.

Parameters, optimizer state, caches and activations are annotated with
*logical* axes via path-suffix regex rules; a per-run ``ParallelConfig``
maps logical names to mesh axes.  A mapping that does not divide the
dimension falls back to successively shorter mesh-axis prefixes and
finally to replication — e.g. gemma3-1b's 4 query heads on a 16-way
``model`` axis end up replicated while its d_ff=6912 shards 16-way.
"""
from __future__ import annotations

import re
from typing import Any, Optional, Sequence, Tuple

import jax
import numpy as np
from jax import shard_map  # noqa: F401  (re-export for EP/collectives)
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..config import ParallelConfig

# (path-suffix regex, logical axes aligned to the TRAILING dims)
PARAM_RULES: Tuple[Tuple[str, Tuple[Optional[str], ...]], ...] = (
    (r"embed/tok$",                ("vocab", "embed")),
    (r"head/w$",                   ("embed", "vocab")),
    (r"attn/wq$",                  ("embed", "heads", None)),
    (r"attn/(wk|wv)$",             ("embed", "kv_heads", None)),
    (r"attn/wo$",                  ("heads", None, "embed")),
    (r"attn/(bq|bk|bv)$",          (None, None)),
    (r"cross_wq$",                 ("embed", "heads", None)),
    (r"cross_(wk|wv)$",            (None, "heads", None)),
    (r"cross_wo$",                 ("heads", None, "embed")),
    (r"ffn/(w1|w3)$",              ("embed", "mlp")),
    (r"ffn/w2$",                   ("mlp", "embed")),
    (r"shared/(w1|w3)$",           ("embed", "mlp")),
    (r"shared/w2$",                ("mlp", "embed")),
    (r"moe/router$",               ("embed", None)),
    (r"moe/(w1|w3)$",              ("expert", "embed", "expert_mlp")),
    (r"moe/w2$",                   ("expert", "expert_mlp", "embed")),
    # compressed expert stacks (serving): shard by expert, keep factors local
    (r"moe/stacks/\w+/(planes/\d+|scale|zero|u|v|u_scale|v_scale)$",
     ("expert", None, None)),
    (r"ffn/stacks/(w1|w3)/(planes/\d+|scale|zero)$", (None, None, "mlp")),
    (r"ffn/stacks/w2/(planes/\d+|scale|zero)$",      (None, "mlp_in", "embed")),
    (r"ffn/stacks/\w+/(u|v|u_scale|v_scale)$",       (None, None, None)),
    (r"rglru/(wx|wgate)$",         ("embed", "lru")),
    (r"rglru/wo$",                 ("lru", "embed")),
    (r"rglru/(rg_wa|rg_wx)$",      (None, "lru")),
    (r"rglru/(conv_w)$",           (None, "lru")),
    (r"rglru/(conv_b|rg_ba|rg_bx|lam)$", ("lru",)),
    (r"mlstm/w_up$",               ("embed", "mlp")),
    (r"mlstm/(wq|wk|wv)$",         ("mlp", None, None)),
    (r"mlstm/w_if$",               ("mlp", None)),
    (r"mlstm/w_down$",             ("mlp", "embed")),
    (r"slstm/w_zifo$",             ("embed", None, None, None)),
    (r"(norm|scale|bias|b_if|b_zifo|lam)\w*$", None),  # replicate small
)

CACHE_RULES: Tuple[Tuple[str, Tuple[Optional[str], ...]], ...] = (
    (r"/(k|v)$",        ("batch", "kv_seq", "kv_heads", None)),
    (r"/(k_scale|v_scale)$", ("batch", "kv_seq", "kv_heads")),
    (r"/pos$",          ("batch", "kv_seq")),
    (r"/(cross_k|cross_v)$", ("batch", None, "heads", None)),
    (r"/h$",            ("batch", "lru")),
    (r"/conv$",         ("batch", None, "lru")),
    (r"/c$",            ("batch", None, None, None)),
    (r"/(n|m)$",        ("batch", None, None)),
)


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        elif hasattr(p, "name"):
            parts.append(str(p.name))
        else:
            parts.append(str(p))
    return "/".join(parts)


def logical_axes_for(path: str, ndim: int, rules) -> Tuple[Optional[str], ...]:
    """Match path suffix against rules; align to trailing dims."""
    for pat, axes in rules:
        if re.search(pat, path):
            if axes is None:
                return (None,) * ndim
            axes = tuple(axes)
            if len(axes) > ndim:  # unstacked (repeat-1) leaf
                axes = axes[len(axes) - ndim:]
            return (None,) * (ndim - len(axes)) + axes
    return (None,) * ndim


def mesh_spec(mesh: Mesh, logical: Sequence[Optional[str]],
              shape: Sequence[int], pcfg: ParallelConfig) -> P:
    """Logical axes -> PartitionSpec with divisibility fallback."""
    used: set = set()
    out = []
    for dim, name in zip(shape, logical):
        entry: Any = None
        if name is not None:
            axes = tuple(a for a in pcfg.rule_for(name)
                         if a in mesh.shape and a not in used)
            # longest divisible prefix
            while axes:
                size = int(np.prod([mesh.shape[a] for a in axes]))
                if dim % size == 0:
                    break
                axes = axes[:-1]
            if axes:
                entry = axes if len(axes) > 1 else axes[0]
                used.update(axes)
        out.append(entry)
    # normalize: trailing Nones are semantically replicated but make
    # PartitionSpec(None, ...) != PartitionSpec() — distinct jit cache
    # keys, which would force a spurious first-chunk recompile when a
    # placed input meets a constraint-normalized output sharding
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def tree_shardings(mesh: Mesh, tree, pcfg: ParallelConfig, rules=PARAM_RULES):
    """NamedSharding tree for an (abstract) pytree by path rules."""
    def one(path, leaf):
        p = _path_str(path)
        logical = logical_axes_for(p, len(leaf.shape), rules)
        return NamedSharding(mesh, mesh_spec(mesh, logical, leaf.shape, pcfg))

    return jax.tree_util.tree_map_with_path(one, tree)


def tree_constraint(mesh: Optional[Mesh], tree, pcfg: ParallelConfig,
                    rules=PARAM_RULES):
    """``with_sharding_constraint`` every leaf of a (traced) pytree by the
    same path rules ``tree_shardings`` uses for placement.

    Applied by the serve engine to the cache/logits *outputs* of its
    jitted entry points: pinning outputs to the same rule-derived
    shardings the inputs were placed with keeps the chunked decode loop's
    call signature at a fixpoint — one compile per shape bucket instead
    of a sharding-propagation churn across the first chunks."""
    if mesh is None:
        return tree

    def one(path, leaf):
        logical = logical_axes_for(_path_str(path), leaf.ndim, rules)
        spec = mesh_spec(mesh, logical, leaf.shape, pcfg)
        return jax.lax.with_sharding_constraint(
            leaf, NamedSharding(mesh, spec))

    return jax.tree_util.tree_map_with_path(one, tree)


def constraint_fn(mesh: Optional[Mesh], pcfg: ParallelConfig):
    """ExecContext.constrain: logical activation axes -> constraint."""
    if mesh is None:
        return lambda x, axes: x

    def constrain(x, axes):
        spec = mesh_spec(mesh, axes, x.shape, pcfg)
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))

    return constrain
