"""The serving CLI's build functions: layer-by-layer compressed init, the depth
override, and the compile-cache location (launch/serve.py, paths.py)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.serve import (build_engine, build_parser, model_config,
                                validate, workload)
from repro.models import init_params
from repro.models.transformer import (compress_moe_params,
                                      init_compressed_params)
from repro.registry import get_config


@pytest.mark.parametrize("dtype", (jnp.float32, jnp.bfloat16))
def test_layerwise_compressed_init_matches_whole_model(dtype):
    """Drawing and compressing one layer at a time gives bit for bit the
    params of drawing the whole model and compressing it after."""
    cfg = get_config("mixtral-8x7b", reduced=True)
    want, cfg_a, stacks_a = compress_moe_params(
        init_params(jax.random.key(3), cfg, dtype), cfg)
    got, cfg_b, stacks_b = init_compressed_params(jax.random.key(3), cfg,
                                                  dtype)
    assert cfg_a == cfg_b
    la, ta = jax.tree_util.tree_flatten(want)
    lb, tb = jax.tree_util.tree_flatten(got)
    assert ta == tb
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x).view(np.uint8),
                                      np.asarray(y).view(np.uint8))
    # the returned stacks are the very objects the params serve from
    moe = [lp["moe"]["stacks"] for (lp,) in got["segments"] if "moe" in lp]
    assert all(a is b for a, b in zip(moe, stacks_b))


def test_place_sees_every_layer_before_the_next_is_drawn():
    cfg = get_config("mixtral-8x7b", reduced=True)
    seen = []

    def place(tree):
        seen.append(sorted(tree))
        return tree

    init_compressed_params(jax.random.key(0), cfg, jnp.float32, place=place)
    assert seen[0] == ["embed", "final_norm", "head"]
    assert len(seen) == 1 + cfg.num_layers
    assert all("moe" in keys for keys in seen[1:])


def test_layers_override_needs_full_config():
    ap = build_parser()
    args = ap.parse_args(["--arch", "mixtral-8x7b", "--layers", "2"])
    with pytest.raises(SystemExit):
        validate(ap, args)
    args = ap.parse_args(["--arch", "mixtral-8x7b", "--full-config",
                          "--layers", "2"])
    validate(ap, args)
    cfg = model_config(args)
    assert cfg.num_layers == 2 and cfg.d_model == 4096


def test_build_engine_serves_the_cli_workload():
    args = build_parser().parse_args(
        ["--arch", "mixtral-8x7b", "--offload", "--requests", "2",
         "--slots", "2", "--chunk", "4", "--max-new", "4"])
    eng = build_engine(args)
    stats = eng.serve(workload(args, eng.cfg), num_slots=args.slots,
                      chunk=args.chunk, seed=args.seed)
    assert [len(r.tokens) for r in stats.results] == [4, 4]
    assert stats.offload_report["total_bytes"] > 0


def test_compile_cache_location(monkeypatch):
    from jax.experimental.compilation_cache import compilation_cache
    from repro.paths import CHECKOUT, enable_compile_cache
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert enable_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == was
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert enable_compile_cache() == str(CHECKOUT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(
            CHECKOUT / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
        compilation_cache.reset_cache()
