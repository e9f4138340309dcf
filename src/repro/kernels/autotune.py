"""Block-size autotuner for the fused Pallas decode kernel.

Three-level lookup, cheapest first:

1. in-memory cache (one entry per problem key per process);
2. the persisted per-device cache ``<checkout>/.autotune.json``
   (override with ``REPRO_AUTOTUNE_CACHE``), written only by an actual
   on-device timing sweep and read only when ``REPRO_AUTOTUNE`` asks for
   tuning, so a default run depends on tracked files alone;
3. the deterministic in-repo ``DEFAULT_TABLE`` seeded from the roofline
   tile menus (``launch/roofline.py::fused_tile_candidates``) — CI and
   fresh checkouts never tune, they look up.

Problem key: ``(kind, bits, group_size, rank, m, k, n)`` per device
kind.  ``m`` buckets to the next power of two (ragged decode blocks
share an entry); the traced plan values (top_n, rank_cap) are DATA and
deliberately not part of the key, so a controller plan change can never
force a retune or a recompile.

Tuning itself (``tune_fused``) times every roofline candidate with the
compiled kernel on the local device and persists the winner.  It only
runs when explicitly asked (``REPRO_AUTOTUNE=1`` or a direct call) —
never implicitly on the serving path.
"""
from __future__ import annotations

import json
import os
import time
import warnings
from pathlib import Path
from typing import Dict, Optional, Tuple

_CACHE_ENV = "REPRO_AUTOTUNE_CACHE"
_TUNE_ENV = "REPRO_AUTOTUNE"

# Deterministic defaults: (kind, bits, group_size, rank, m_bucket) ->
# (bm, bn, bk).  Derived offline from the roofline tile menu (largest
# K tile, then largest N tile under the VMEM budget; bm = the decode
# small-m preset for m <= 8).  ``None`` entries in a key match any
# value, checked most-specific-first.
DEFAULT_TABLE: Dict[Tuple, Tuple[int, int, int]] = {
    # decode presets: single-token / few-slot blocks never pad past the
    # f32 sublane minimum (the `_pad_m` decode-waste fix)
    ("fused", None, None, None, 8): (8, 256, 512),
    ("fused", None, None, None, 16): (16, 256, 512),
    ("fused", None, None, None, 32): (32, 256, 512),
    # prefill / calibration blocks: larger token tiles
    ("fused", None, None, None, None): (64, 256, 512),
    ("qmm", None, None, None, None): (128, 256, 512),
}


def _m_bucket(m: int) -> int:
    b = 8
    while b < m:
        b *= 2
    return b


def device_kind() -> str:
    """The local device kind (the persisted table's top-level key);
    raises where JAX cannot name a device."""
    import jax
    return jax.devices()[0].device_kind.replace(" ", "_")


def cache_path() -> Path:
    env = os.environ.get(_CACHE_ENV)
    if env:
        return Path(env)
    from ..paths import CHECKOUT
    return CHECKOUT / ".autotune.json"


def _key_str(kind: str, bits: int, group_size: int, rank: int,
             m: int, k: int, n: int) -> str:
    return f"{kind}/b{bits}/g{group_size}/r{rank}/m{_m_bucket(m)}/k{k}/n{n}"


class Autotuner:
    """Process-wide tile chooser (see module docstring for the policy)."""

    def __init__(self):
        self._mem: Dict[str, Tuple[int, int, int]] = {}
        self._disk: Optional[Dict] = None

    # -- persisted cache ---------------------------------------------------
    def _load_disk(self) -> Dict:
        if self._disk is None:
            self._disk = {}
            p = cache_path()
            try:
                loaded = json.loads(p.read_text())
                if isinstance(loaded, dict):
                    self._disk = loaded
            except (OSError, ValueError):
                pass        # missing/corrupt/truncated cache -> defaults
        return self._disk

    def _store_disk(self, key: str, tiles: Tuple[int, int, int],
                    us: float) -> None:
        disk = self._load_disk()
        dev = disk.setdefault(device_kind(), {})
        dev[key] = {"tiles": list(tiles), "us": round(us, 2),
                    "time": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                          time.gmtime())}
        p = cache_path()
        try:
            p.parent.mkdir(parents=True, exist_ok=True)
            # write-then-rename: a reader (or a crash) mid-write must see
            # either the old complete file or the new one, never a torn mix
            tmp = p.with_name(f"{p.name}.tmp.{os.getpid()}")
            tmp.write_text(json.dumps(disk, indent=1, sort_keys=True) + "\n")
            os.replace(tmp, p)
        except OSError:
            pass            # cache persistence is best-effort

    # -- lookup ------------------------------------------------------------
    def _default(self, kind: str, bits: int, group_size: int, rank: int,
                 m: int) -> Optional[Tuple[int, int, int]]:
        mb = _m_bucket(m)
        for key in ((kind, bits, group_size, rank, mb),
                    (kind, bits, None, None, mb),
                    (kind, None, None, None, mb),
                    (kind, None, None, None, None)):
            if key in DEFAULT_TABLE:
                return DEFAULT_TABLE[key]
        return None

    def choose(self, kind: str, *, bits: int, group_size: int, rank: int,
               m: int, k: int, n: int) -> Tuple[int, int, int]:
        """(bm, bn, bk) for a problem, clamped to its actual dims."""
        key = _key_str(kind, bits, group_size, rank, m, k, n)
        if key in self._mem:
            return self._mem[key]
        disk = (self._load_disk().get(device_kind(), {})
                if autotune_enabled() else {})
        hit = disk.get(key) if isinstance(disk, dict) else None
        try:
            tiles = tuple(hit["tiles"]) if hit else None
            if tiles is not None and (len(tiles) != 3 or not all(
                    isinstance(t, int) and t > 0 for t in tiles)):
                tiles = None
        except (KeyError, TypeError):
            tiles = None    # structurally corrupt entry -> defaults
        if tiles is None:
            tiles = self._default(kind, bits, group_size, rank, m)
        if tiles is None:
            tiles = (8 if m <= 8 else 128, 256, 512)
        tiles = clamp_tiles(m, k, n, *tiles, group_size=group_size)
        self._mem[key] = tiles
        return tiles

    def record(self, kind: str, tiles: Tuple[int, int, int], us: float, *,
               bits: int, group_size: int, rank: int,
               m: int, k: int, n: int, persist: bool = True) -> None:
        key = _key_str(kind, bits, group_size, rank, m, k, n)
        self._mem[key] = tuple(tiles)
        if persist:
            self._store_disk(key, tuple(tiles), us)


def clamp_tiles(m: int, k: int, n: int, bm: int, bn: int, bk: int, *,
                group_size: int) -> Tuple[int, int, int]:
    """Fit a tile request to the problem, preserving the divisibility
    contracts (bk multiple of PACK_BLOCK and group_size; bm a sublane
    multiple so single-token decode pads to 8 rows, not a full tile)."""
    from ..core.quantize import PACK_BLOCK
    bm = min(bm, -(-max(m, 1) // 8) * 8)      # round m up to sublane, clamp
    bm = max(8, bm)
    bn = min(bn, n)
    while n % bn:
        bn //= 2
    bk = min(bk, k)
    while k % bk:
        bk //= 2
    step = max(PACK_BLOCK, group_size)
    if bk % step:
        bk = step if k % step == 0 else k
    return bm, bn, bk


_TUNER = Autotuner()


def choose_tiles(kind: str, *, bits: int, group_size: int, rank: int,
                 m: int, k: int, n: int) -> Tuple[int, int, int]:
    """Module-level convenience over the process-wide :class:`Autotuner`."""
    return _TUNER.choose(kind, bits=bits, group_size=group_size, rank=rank,
                         m=m, k=k, n=n)


def autotune_enabled() -> bool:
    return os.environ.get(_TUNE_ENV, "") not in ("", "0")


def tune_fused(xe, stack, me, ge, rank_cap, *, out_dtype, interpret: bool,
               repeats: int = 3) -> Tuple[int, int, int]:
    """Time every roofline candidate of the fused kernel on this device
    and persist the winner.  Called explicitly (bench / REPRO_AUTOTUNE=1
    serving boot) — never implicitly from the hot path."""
    from ..launch.roofline import fused_tile_candidates
    from . import ops

    e, m, k = xe.shape
    n = stack.scale.shape[-1]
    rank = stack.pad_rank
    cands = fused_tile_candidates(m, k, n, stack.bits, stack.group_size,
                                  rank)
    if not cands:
        cands = [clamp_tiles(m, k, n, 8, 256, 512,
                             group_size=stack.group_size)]
    best, best_us = None, float("inf")
    failed = []
    for bm, bn, bk in cands:
        bm, bn, bk = clamp_tiles(m, k, n, bm, bn, bk,
                                 group_size=stack.group_size)
        try:
            def run():
                y = ops.fused_expert_matmul(
                    xe, stack, me, gates=ge, rank_cap=rank_cap,
                    impl="pallas_interpret" if interpret else "pallas",
                    out_dtype=out_dtype, bm=bm, bn=bn, bk=bk)
                y.block_until_ready()
            run()                                    # compile + warm
            t0 = time.perf_counter()
            for _ in range(repeats):
                run()
            us = (time.perf_counter() - t0) / repeats * 1e6
        except Exception as exc:      # a candidate the compiler refuses
            failed.append(((bm, bn, bk), f"{type(exc).__name__}: {exc}"))
            warnings.warn(f"autotune: fused tiles {(bm, bn, bk)} failed "
                          f"for b{stack.bits} k{k} n{n}: {failed[-1][1]}")
            continue
        if us < best_us:
            best, best_us = (bm, bn, bk), us
    if best is None:
        raise RuntimeError(f"autotune: every fused tile candidate failed "
                           f"for b{stack.bits} k{k} n{n}: {failed}")
    _TUNER.record("fused", best, best_us, bits=stack.bits,
                  group_size=stack.group_size, rank=rank, m=m, k=k, n=n,
                  persist=not interpret)
    return best
