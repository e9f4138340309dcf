"""Production mesh builders.

Defined as FUNCTIONS so importing this module never touches jax device
state; the 512-device host-platform override happens only in dryrun.py.
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def _auto_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axis types: the model code places
    arrays through sharding constraints and shard_map, which the default
    Explicit axes would turn into type errors (e.g. the embedding
    gather)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2x16x16 = 512 chips across two pods."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_debug_mesh(data: int = 2, model: int = 2, pod: int = 0):
    """Small mesh for CPU tests (requires XLA host device override)."""
    if pod:
        return _auto_mesh((pod, data, model), ("pod", "data", "model"))
    return _auto_mesh((data, model), ("data", "model"))


def make_serve_mesh(ep: int = 1, devices=None) -> Optional[Mesh]:
    """Expert-parallel serving mesh: a 1-D ``('model',)`` mesh over the
    first ``ep`` devices.

    The serve engine's decode/prefill contexts map the MoE expert dim
    onto the ``model`` axis (``distributed/sharding.py`` PARAM_RULES), so
    an ``ep``-way mesh partitions each layer's experts — quantized planes,
    scales, and low-rank compensator factors included — across ``ep``
    shards.  ``ep == 1`` returns None (single-device path, no shard_map).
    On CPU, multi-device meshes need
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``.
    """
    if ep <= 1:
        return None
    devices = list(jax.devices() if devices is None else devices)
    if len(devices) < ep:
        raise ValueError(
            f"mesh ep={ep} needs {ep} devices but only {len(devices)} are "
            f"visible (on CPU, set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={ep})")
    return Mesh(np.asarray(devices[:ep]), ("model",))


def parse_mesh_spec(spec: str) -> Dict[str, int]:
    """Parse a ``--mesh`` serving spec like ``"ep=4"`` into a dict.

    Comma-separated ``axis=N`` entries; only ``ep`` (expert parallelism)
    is currently meaningful for serving."""
    out: Dict[str, int] = {}
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        if "=" not in part:
            raise ValueError(f"bad --mesh entry {part!r}; expected axis=N")
        k, v = part.split("=", 1)
        out[k.strip()] = int(v)
    unknown = set(out) - {"ep"}
    if unknown:
        raise ValueError(f"unknown --mesh axes {sorted(unknown)}; "
                         f"serving supports ep=N")
    return out
