"""Pinned host-memory images of compressed expert stacks (streaming).

Real offloaded serving keeps the compressed experts in page-locked
("pinned") host memory so the DMA engine can source async H2D copies
from them.  :class:`HostExpertImage` is that staging area for one MoE
layer: per-projection numpy snapshots of every
``CompressedExpertStack`` leaf, taken once at attach time, from which
the transfer engine (``offload/staging.py``) slices per-expert copy
payloads — bit-plane codes + scale/zero for a weight fetch, factor rank
rows for a compensator fetch.  On this CPU-hosted reproduction "pinned"
is emulated by ordinary host numpy buffers; the contract that matters
(payloads are sliced host-side and cross to the device via
``jax.device_put``, never read in place by compute) is the real one.

The companion :func:`build_fallback_stack` produces the device-resident
low-bit fallback copy — MoBiLE's "little expert": a plain RTN
requantization of the dequantized layer at ``fallback_bits``, packed
into the SAME container layout (bit width, group size, padded rank, all
meta identical), with zeroed compensator factors.  The streaming engine
boots every device container from it, so a routed expert whose copy has
not landed is served degraded instead of stalling the scan, and
streamed payloads can be scattered into the container without any
shape/meta (and therefore any jit-signature) change.

No wire-byte arithmetic lives here: byte accounting stays with the
canonical formulas in ``core/quantize.py`` via the store's metering
(``offload/store.py``); this module only assembles payload pytrees.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.pipeline import CompressedExpertStack
from ..core.quantize import PLANES, _group_minmax, dequantize, pack_bits

# leaves that move with a weight fetch vs a factor fetch
WEIGHT_LEAVES = ("planes", "scale", "zero")
FACTOR_LEAVES = ("u", "v", "u_scale", "v_scale")


class HostExpertImage:
    """Host-side per-expert image of one MoE layer's compressed stacks.

    ``stacks``: {proj: CompressedExpertStack} with the TRUE (offline
    compressed) contents.  Leaves are snapshotted to numpy immediately,
    so later in-place container swaps in the serving param tree cannot
    corrupt the copy source.
    """

    def __init__(self, stacks: Dict[str, CompressedExpertStack]):
        self.meta = {name: s for name, s in stacks.items()}
        self.num_experts = next(iter(stacks.values())).scale.shape[0]
        self._host: Dict[str, Dict] = {}
        for name, s in stacks.items():
            self._host[name] = {
                "planes": tuple(np.asarray(p) for p in s.planes),
                "scale": np.asarray(s.scale),
                "zero": np.asarray(s.zero),
                "u": np.asarray(s.u),
                "v": np.asarray(s.v),
                "u_scale": np.asarray(s.u_scale),
                "v_scale": np.asarray(s.v_scale),
            }

    @property
    def host_nbytes(self) -> int:
        """Actual host staging-buffer footprint (container form)."""
        total = 0
        for leaves in self._host.values():
            total += sum(p.nbytes for p in leaves["planes"])
            total += sum(leaves[k].nbytes for k in
                         ("scale", "zero", "u", "v", "u_scale", "v_scale"))
        return total

    def weight_payload(self, e: int) -> Dict[str, Dict]:
        """Copy payload for expert ``e``'s quantized weights: one
        container-form slice per projection (codes + scale/zero)."""
        out = {}
        for name, leaves in self._host.items():
            out[name] = {
                "planes": tuple(p[e] for p in leaves["planes"]),
                "scale": leaves["scale"][e],
                "zero": leaves["zero"][e],
            }
        return out

    def factor_payload(self, e: int, ranks: Dict[str, Tuple[int, int]]
                       ) -> Dict[str, Dict]:
        """Copy payload for expert ``e``'s compensator factor rows.

        ``ranks``: {proj: (lo, hi)} row window per projection (a raised
        rank cap fetches only the missing delta rows).  Projections with
        an empty window are omitted."""
        out = {}
        for name, leaves in self._host.items():
            lo, hi = ranks.get(name, (0, 0))
            if hi <= lo:
                continue
            out[name] = {
                "u": leaves["u"][e][:, lo:hi],
                "v": leaves["v"][e][lo:hi, :],
                "u_scale": leaves["u_scale"][e][:, lo:hi],
                "v_scale": leaves["v_scale"][e][lo:hi, :],
            }
        return out


def _clamp_fallback_bits(bits: int, container_bits: int) -> int:
    """Largest supported plane width <= min(bits, container width)."""
    cap = min(int(bits), int(container_bits))
    ok = [b for b in PLANES if b <= cap]
    if not ok:
        raise ValueError(f"no supported fallback width <= {cap}")
    return max(ok)


def build_fallback_stack(stack: CompressedExpertStack,
                         fallback_bits: int = 2) -> CompressedExpertStack:
    """Device-resident low-bit fallback ("little expert") for one stack.

    RTN-requantizes the dequantized stack at ``fallback_bits`` (clamped
    to the container width), packs the codes back into the ORIGINAL
    container layout, and zeroes the compensator factors.  Every meta
    field — container bits, group size, ranks, pad_rank, expert_bits —
    is preserved, so the fallback is pytree/shape/dtype-identical to the
    true stack: the streaming engine can boot the serving containers
    from it and later scatter true expert payloads in without touching
    the jitted decode loop's signature.
    """
    fb = _clamp_fallback_bits(fallback_bits, stack.bits)
    G = stack.group_size
    qmax = (1 << fb) - 1

    def _rtn_one(e):
        # one expert at a time: no (E, K, N) float32 temporary
        we = dequantize(stack.expert_qt(e))
        g, lo, hi = _group_minmax(we, G)
        scale = jnp.maximum((hi - lo) / qmax, 1e-8)
        zero = -lo / scale
        q = jnp.clip(jnp.round(g / scale + zero), 0, qmax)
        q = q.reshape(we.shape).astype(jnp.uint8)
        n = we.shape[1]
        return (pack_bits(q, stack.bits), scale.reshape(-1, n),
                zero.reshape(-1, n))

    per_expert = [_rtn_one(e) for e in range(stack.scale.shape[0])]
    planes = tuple(jnp.stack([pe[0][i] for pe in per_expert])
                   for i in range(len(per_expert[0][0])))
    return dataclasses.replace(
        stack,
        planes=planes,
        scale=jnp.stack([pe[1] for pe in per_expert]).astype(
            stack.scale.dtype),
        zero=jnp.stack([pe[2] for pe in per_expert]).astype(
            stack.zero.dtype),
        u=jnp.zeros_like(stack.u), v=jnp.zeros_like(stack.v),
        u_scale=jnp.zeros_like(stack.u_scale),
        v_scale=jnp.zeros_like(stack.v_scale))


def build_fallback_stacks(stacks: Dict[str, CompressedExpertStack],
                          fallback_bits: int = 2
                          ) -> Dict[str, CompressedExpertStack]:
    """Fallback copies for every projection of one MoE layer."""
    return {name: build_fallback_stack(s, fallback_bits)
            for name, s in stacks.items()}
