"""Ahead-of-time compiles of the fused expert kernel for a TPU v5e.

The TPU compiler is installed with jax and compiles for a chip that is
described, not attached, so Mosaic's refusals (illegal block shapes,
unsupported casts and shifts) show here, where interpret mode accepts
them.  Shapes are Mixtral-8x7B's: 8 experts, the w1/w3 projection
(K=4096, N=14336) and the w2 projection (K=14336, N=4096), rank 256
(what kurtosis allocation gives one expert of eight at budget 32).
Tiles come from the same ``autotune`` lookup the serving path uses: the
decode tile (8 tokens) and the prefill tile (64 tokens).

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.core.pipeline import CompressedExpertStack
from repro.core.quantize import PLANES
from repro.kernels import ops

E, D_MODEL, D_EXPERT, RANK, GROUP = 8, 4096, 14336, 256, 64
PROJ = {"w1": (D_MODEL, D_EXPERT), "w2": (D_EXPERT, D_MODEL)}
TOKENS = {"decode": 8, "prefill": 64}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip can be written to the persistent
    cache but never read back without the chip: keep the cache off."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def abstract(one_chip):
    """``shape, dtype -> ShapeDtypeStruct`` on one described v5e chip."""
    def make(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return make


@pytest.fixture(scope="module")
def stacks(abstract):
    def stack(bits, k, n):
        return CompressedExpertStack(
            planes=tuple(abstract((E, k // (8 // p), n), jnp.uint8)
                         for p, _ in PLANES[bits]),
            scale=abstract((E, k // GROUP, n), jnp.float32),
            zero=abstract((E, k // GROUP, n), jnp.float32),
            u=abstract((E, k, RANK), jnp.int8),
            v=abstract((E, RANK, n), jnp.int8),
            u_scale=abstract((E, 1, RANK), jnp.float32),
            v_scale=abstract((E, RANK, 1), jnp.float32),
            bits=bits, group_size=GROUP, shape=(E, k, n),
            ranks=(RANK,) + (0,) * (E - 1), pad_rank=RANK, factor_bits=8)
    return {(bits, proj): stack(bits, *PROJ[proj])
            for bits in (2, 3) for proj in PROJ}


@pytest.mark.parametrize("tile", sorted(TOKENS))
@pytest.mark.parametrize("gated", (False, True))
@pytest.mark.parametrize("proj", sorted(PROJ))
@pytest.mark.parametrize("bits", (2, 3))
def test_fused_expert_kernel_compiles_for_v5e(bits, proj, gated, tile,
                                              stacks, abstract,
                                              no_compile_cache):
    stack = stacks[(bits, proj)]
    k = PROJ[proj][0]
    c = TOKENS[tile]
    xe = abstract((E, c, k), jnp.float32)
    me = abstract((E, c), jnp.float32)
    ge = abstract((E, c), jnp.float32) if gated else None

    def proj_fn(xe, stack, me, ge):
        return ops.fused_expert_matmul(xe, stack, me, gates=ge,
                                       impl="pallas", out_dtype=jnp.float32)

    compiled = jax.jit(proj_fn).lower(xe, stack, me, ge).compile()
    name = f"fused_expert_b{bits}" + ("_gated" if gated else "")
    calls = [ln for ln in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert calls and all(name in ln for ln in calls), calls
    out = compiled.out_info
    assert out.shape == (E, c, PROJ[proj][1]) and out.dtype == jnp.float32


@pytest.mark.parametrize("int8_kv", (False, True))
def test_flash_decode_attention_compiles_for_v5e(int8_kv, abstract,
                                                 no_compile_cache):
    """The flash-decode kernel at Mixtral's attention widths (32 query
    heads over 8 kv-heads of 128) on a 1024-position cache."""
    from repro.kernels.decode_attention import flash_decode_attention
    b, s, kvh, h, hd = 2, 1024, 8, 32, 128
    kv_dtype = jnp.int8 if int8_kv else jnp.bfloat16
    q = abstract((b, h, hd), jnp.bfloat16)
    k = abstract((b, s, kvh, hd), kv_dtype)
    v = abstract((b, s, kvh, hd), kv_dtype)
    pos = abstract((b, s), jnp.int32)
    cur = abstract((b,), jnp.int32)
    scales = ((abstract((b, s, kvh), jnp.float32),) * 2 if int8_kv
              else (None, None))

    compiled = jax.jit(flash_decode_attention).lower(
        q, k, v, pos, cur, *scales).compile()
    name = "flash_decode" + ("_kv8" if int8_kv else "")
    calls = [ln for ln in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert calls and all(name in ln for ln in calls), calls
    assert compiled.out_info.shape == (b, h, hd)
