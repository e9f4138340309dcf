"""RL3xx Pallas tile-legality rules.

The fused decode kernel's contracts are numeric, so these rules evaluate
real values instead of pattern-matching: the block-size sources checked
are the literal ``DEFAULT_TABLE`` in ``kernels/autotune.py``, the
candidate menus in ``launch/roofline.py``, and the default tile keyword
values of kernel wrappers that invoke ``pl.pallas_call`` on packed
operands.

RL301 tile-pack-divisibility  a bk (K-tile) entry not divisible by
                              ``PACK_BLOCK`` — a K tile that splits a
                              packing block reads bytes it cannot fully
                              consume and breaks the block-local unpack.
RL302 tile-vmem-budget        a (bm, bn, bk) entry whose resident
                              footprint per grid step —
                              ``launch/roofline.py::fused_tile_vmem_bytes``
                              at the documented worst case (8-bit
                              container, group 64, rank 256) — exceeds
                              ``VMEM_BYTES * VMEM_BUDGET``.
RL303 pallas-missing-guard    a ``pl.pallas_call`` on packed planes whose
                              enclosing function neither asserts
                              ``bk % PACK_BLOCK`` nor routes tiles
                              through ``clamp_tiles``/``_tile_sizes``.
RL304 block-one-row           a 2-D block shape written ``(1, x)``:
                              Mosaic needs a block's last two dims to
                              divide by (8, 128) or span the array, and a
                              one-row block of a per-expert ``(E, C)``
                              operand does neither (the TPU compiler
                              refused ``(1, bm)`` and ``(1, 1)`` blocks of
                              the fused kernel).  Give the operand a
                              trailing unit dim (``(E, C, 1)`` blocked
                              ``(1, bm, 1)``) or pass it by scalar
                              prefetch.  Checked on ``BlockSpec`` calls
                              and on calls of local helpers that build
                              one from their first argument.

``PACK_BLOCK`` is read from the ``core/quantize.py`` AST (so the lint
needs no jax import to parse); the VMEM formula is imported from
``launch/roofline.py`` — the check uses the same equation the autotuner
candidates do.
"""
from __future__ import annotations

import ast
from typing import List, Optional, Tuple

from .core import Finding, rule
from .jitscope import _dotted

# worst-case problem parameters the static budget check evaluates at:
# widest supported container, default quant group, generous padded rank
WORST_CASE = {"bits": 8, "group_size": 64, "rank": 256}


def _literal_assign(tree: ast.Module, name: str):
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign):
            for t in node.targets:
                if isinstance(t, ast.Name) and t.id == name:
                    try:
                        return ast.literal_eval(node.value), node
                    except ValueError:
                        return None, node
        if isinstance(node, ast.AnnAssign) and \
                isinstance(node.target, ast.Name) and \
                node.target.id == name and node.value is not None:
            try:
                return ast.literal_eval(node.value), node
            except ValueError:
                return None, node
    return None, None


def _pack_block(ctx) -> int:
    for module, tree in ctx.index.trees.items():
        if str(ctx.index.module_paths[module]).endswith("core/quantize.py"):
            val, _ = _literal_assign(tree, "PACK_BLOCK")
            if isinstance(val, int):
                return val
    return 64


def _vmem_formula():
    """(fused_tile_vmem_bytes, budget_bytes) from the live roofline module;
    None when the repro package is not importable (fixture-only runs)."""
    try:
        from ..launch.roofline import (VMEM_BUDGET, VMEM_BYTES,
                                       fused_tile_vmem_bytes)
        return fused_tile_vmem_bytes, VMEM_BYTES * VMEM_BUDGET
    except Exception:
        return None, None


def _table_entries(ctx):
    """Yield (path, node, key, (bm, bn, bk)) from autotune DEFAULT_TABLE
    and (path, node, ('BK_CANDIDATES', i), bk) style candidate menus."""
    for module, tree in ctx.index.trees.items():
        path = ctx.index.module_paths[module]
        sp = str(path)
        if sp.endswith("kernels/autotune.py"):
            table, node = _literal_assign(tree, "DEFAULT_TABLE")
            if isinstance(table, dict):
                for key, tiles in table.items():
                    yield "table", path, node, key, tiles
        if sp.endswith("launch/roofline.py"):
            for cname in ("BK_CANDIDATES",):
                vals, node = _literal_assign(tree, cname)
                if isinstance(vals, tuple):
                    for bk in vals:
                        yield "bk_menu", path, node, (cname, bk), bk


@rule("RL301", "kernel K-tile not divisible by PACK_BLOCK")
def rl301(scope, ctx) -> List[Finding]:
    out = []
    pack = _pack_block(ctx)
    for kind, path, node, key, val in _table_entries(ctx):
        if kind == "table":
            bm, bn, bk = val
        else:
            bk = val
        if bk % pack:
            out.append(ctx.finding_at(
                "RL301", path, node,
                f"tile entry {key}: bk={bk} is not a multiple of "
                f"PACK_BLOCK={pack}; a K tile that splits a packing "
                f"block breaks the block-local unpack"))
    # default tile kwargs of pallas wrappers over packed planes
    for path, fnode, defaults in _kernel_defaults(ctx):
        bk = defaults.get("bk")
        if isinstance(bk, int) and bk % pack:
            out.append(ctx.finding_at(
                "RL301", path, fnode,
                f"{fnode.name}() default bk={bk} is not a multiple of "
                f"PACK_BLOCK={pack}"))
    return out


@rule("RL302", "kernel tile exceeds the roofline VMEM budget")
def rl302(scope, ctx) -> List[Finding]:
    vmem, budget = _vmem_formula()
    if vmem is None:
        return []
    out = []
    for kind, path, node, key, val in _table_entries(ctx):
        if kind != "table":
            continue
        bm, bn, bk = val
        need = vmem(bm, bn, bk, **WORST_CASE)
        if need > budget:
            out.append(ctx.finding_at(
                "RL302", path, node,
                f"tile entry {key}: ({bm}, {bn}, {bk}) needs "
                f"{need / 2**20:.2f} MiB VMEM at the worst case "
                f"{WORST_CASE}, over the {budget / 2**20:.2f} MiB "
                f"budget (fused_tile_vmem_bytes)"))
    for path, fnode, defaults in _kernel_defaults(ctx):
        bm, bn, bk = (defaults.get("bm"), defaults.get("bn"),
                      defaults.get("bk"))
        if all(isinstance(v, int) for v in (bm, bn, bk)):
            need = vmem(bm, bn, bk, **WORST_CASE)
            if need > budget:
                out.append(ctx.finding_at(
                    "RL302", path, fnode,
                    f"{fnode.name}() default tiles ({bm}, {bn}, {bk}) "
                    f"need {need / 2**20:.2f} MiB VMEM at the worst "
                    f"case, over the {budget / 2**20:.2f} MiB budget"))
    return out


@rule("RL303", "pallas_call on packed planes without a PACK_BLOCK guard")
def rl303(scope, ctx) -> List[Finding]:
    out = []
    for module, tree in ctx.index.trees.items():
        path = ctx.index.module_paths[module]
        for fnode in ast.walk(tree):
            if not isinstance(fnode, (ast.FunctionDef,
                                      ast.AsyncFunctionDef)):
                continue
            calls = [n for n in ast.walk(fnode)
                     if isinstance(n, ast.Call)
                     and (_dotted(n.func) or "").endswith("pallas_call")]
            if not calls:
                continue
            if not _uses_planes(fnode):
                continue                      # unquantized kernel (attn...)
            if _has_pack_guard(fnode):
                continue
            for call in calls:
                out.append(ctx.finding_at(
                    "RL303", path, call,
                    f"{fnode.name}() launches a Pallas kernel over packed "
                    f"planes without asserting bk % PACK_BLOCK (or "
                    f"clamping via clamp_tiles/_tile_sizes)"))
    return out


@rule("RL304", "one-row 2-D Pallas block shape")
def rl304(scope, ctx) -> List[Finding]:
    out = []
    for module, tree in ctx.index.trees.items():
        path = ctx.index.module_paths[module]
        makers = {"BlockSpec"} | {
            f.name for f in ast.walk(tree)
            if isinstance(f, (ast.FunctionDef, ast.AsyncFunctionDef))
            and any(_is_blockspec(n) for n in ast.walk(f))}
        for call in ast.walk(tree):
            if not isinstance(call, ast.Call) or not call.args:
                continue
            head = (_dotted(call.func) or "").split(".")[-1]
            shape = call.args[0]
            if head in makers and _one_row_2d(shape):
                out.append(ctx.finding_at(
                    "RL304", path, call,
                    f"block shape {ast.unparse(shape)} is one row of a 2-D "
                    f"operand: Mosaic needs the last two block dims to "
                    f"divide by (8, 128) or span the array; add a trailing "
                    f"unit dim or use scalar prefetch"))
    return out


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def _is_blockspec(node: ast.AST) -> bool:
    return isinstance(node, ast.Call) and \
        (_dotted(node.func) or "").endswith("BlockSpec")


def _one_row_2d(shape: ast.AST) -> bool:
    return (isinstance(shape, ast.Tuple) and len(shape.elts) == 2
            and isinstance(shape.elts[0], ast.Constant)
            and shape.elts[0].value == 1)


def _kernel_defaults(ctx):
    """(path, FunctionDef, {kw: default int}) for functions that launch
    pallas_call on packed planes and take bm/bn/bk tile kwargs."""
    for module, tree in ctx.index.trees.items():
        path = ctx.index.module_paths[module]
        for fnode in ast.walk(tree):
            if not isinstance(fnode, (ast.FunctionDef,
                                      ast.AsyncFunctionDef)):
                continue
            if not any(isinstance(n, ast.Call)
                       and (_dotted(n.func) or "").endswith("pallas_call")
                       for n in ast.walk(fnode)):
                continue
            if not _uses_planes(fnode):
                continue
            a = fnode.args
            names = [p.arg for p in a.args] + [p.arg for p in a.kwonlyargs]
            defaults = ([None] * (len(a.args) - len(a.defaults))
                        + list(a.defaults) + list(a.kw_defaults))
            kv = {}
            for nm, d in zip(names, defaults):
                if nm in ("bm", "bn", "bk") and isinstance(d, ast.Constant) \
                        and isinstance(d.value, int):
                    kv[nm] = d.value
            if kv:
                yield path, fnode, kv


def _uses_planes(fnode: ast.AST) -> bool:
    for n in ast.walk(fnode):
        if isinstance(n, ast.Name) and n.id in ("planes", "PLANES"):
            return True
        if isinstance(n, ast.Attribute) and n.attr == "planes":
            return True
    return False


def _has_pack_guard(fnode: ast.AST) -> bool:
    for n in ast.walk(fnode):
        if isinstance(n, ast.Assert):
            for sub in ast.walk(n.test):
                if isinstance(sub, ast.Name) and sub.id == "PACK_BLOCK":
                    return True
        if isinstance(n, ast.Call):
            head = (_dotted(n.func) or "").split(".")[-1]
            if head in ("clamp_tiles", "_tile_sizes"):
                return True
    return False
