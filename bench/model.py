"""The model a cell serves, found by name: a configuration file's
``"model"`` key names ``bench/models/<model>.py``, which draws the
weights from the seed and counts the work; the engine is the program's
own, built as ``launch/serve.py`` builds it."""
from __future__ import annotations

import importlib.util
import os
from typing import Dict

import jax

HERE = os.path.dirname(os.path.abspath(__file__))


def load(spec: Dict):
    """The module ``bench/models/<spec["model"]>.py``."""
    name = spec["model"]
    path = os.path.join(HERE, "models", f"{name}.py")
    mod_spec = importlib.util.spec_from_file_location(f"bench_model_{name}",
                                                      path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def base_key(seed: int):
    """A key from any whole number up to 2**64 (JAX seeds hold 32 bits
    directly; the rest is folded in)."""
    k = jax.random.key(int(seed) & 0xFFFFFFFF)
    return jax.random.fold_in(k, (int(seed) >> 32) & 0xFFFFFFFF)


def layer_key(seed: int, layer: int):
    return jax.random.fold_in(base_key(seed), layer)


def engine(spec: Dict, model, slots: int, chunk: int):
    """The serving engine, through the program's own option parser and
    ``build_engine``, as ``launch/serve.py`` would build it."""
    from repro.launch.serve import build_engine, build_parser
    dep = spec["serve"]
    argv = ["--arch", spec["arch"], "--full-config", "--layers",
            str(spec["config"]["num_hidden_layers"]), "--offload",
            "--requests", "1", "--slots", str(slots), "--chunk",
            str(chunk), "--cache-experts", str(dep["cache_experts"])]
    return build_engine(build_parser().parse_args(argv), model)
