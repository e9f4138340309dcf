"""Fused decode kernel validation (the tentpole kernel).

Parity matrix: the single fused ``pallas_call`` (bitplane unpack + HQQ
dequant at true per-expert width + rank-capped compensator GEMM +
gate-weighted combine), executed by the Pallas interpreter on CPU, must
bit-match the pure-jnp oracle across

    bits x rank_cap {0, half, full} x comp-mask {none, partial, all}
    x gates {absent, present} x heterogeneous expert_bits, and at
    pinned tiles that give several token, N and K tiles per expert,

and the traced (top_n, rank_cap) plan row must never trigger a
recompile (the compile-count pin).  A compiled-Mosaic parity cell runs
when a TPU is attached; CI covers the interpreter path.
"""
import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import ModelConfig, MoEConfig, QuantConfig
from repro.core import compress_ffn_weights
from repro.core.pipeline import compress_expert_stack
from repro.kernels import ops
from repro.models.moe import moe_apply

TOL = dict(rtol=1e-4, atol=1e-3)


def _stack(bits=2, e=4, k=128, n=128, rank_budget=8, seed=0,
           expert_bits=None):
    rng = np.random.default_rng(seed)
    qcfg = QuantConfig(enabled=True, bits=bits, group_size=64,
                       rank_budget=rank_budget, top_n_restore=1,
                       hqq_iters=2)
    w = jnp.asarray(rng.standard_normal((e, k, n)), jnp.float32) * 0.05
    stack, _ = compress_expert_stack(
        w, qcfg, bits=None if expert_bits is None
        else np.asarray(expert_bits))
    return stack, w


def _inputs(e, c, k, mask_mode, gated, seed=1):
    rng = np.random.default_rng(seed)
    xe = jnp.asarray(rng.standard_normal((e, c, k)), jnp.float32)
    me = {"none": jnp.zeros((e, c), jnp.float32),
          "partial": jnp.asarray((rng.random((e, c)) < 0.5), jnp.float32),
          "all": jnp.ones((e, c), jnp.float32)}[mask_mode]
    ge = (jnp.asarray(rng.random((e, c)), jnp.float32) if gated else None)
    return xe, me, ge


def _parity(stack, xe, me, ge, rank_cap):
    y_ref = ops.fused_expert_matmul(xe, stack, me, gates=ge,
                                    rank_cap=rank_cap, impl="ref",
                                    out_dtype=jnp.float32)
    y_pl = ops.fused_expert_matmul(xe, stack, me, gates=ge,
                                   rank_cap=rank_cap,
                                   impl="pallas_interpret",
                                   out_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(y_pl), np.asarray(y_ref), **TOL)


@pytest.mark.parametrize("bits", [2, 3, 4, 8])
@pytest.mark.parametrize("rank_mode", ["zero", "half", "full"])
def test_fused_parity_bits_x_rank(bits, rank_mode):
    stack, _ = _stack(bits=bits)
    xe, me, ge = _inputs(4, 8, 128, "partial", gated=True, seed=bits)
    cap = {"zero": jnp.int32(0),
           "half": jnp.int32(stack.pad_rank // 2),
           "full": None}[rank_mode]
    _parity(stack, xe, me, ge, cap)


@pytest.mark.parametrize("mask_mode", ["none", "partial", "all"])
@pytest.mark.parametrize("gated", [False, True])
def test_fused_parity_topn_x_gates(mask_mode, gated):
    """mask 'none'/'partial'/'all' are the (E, C) images of plan
    top_n = 0 / 0<n<k / k; gates off covers backends that leave the
    combine to the caller."""
    stack, _ = _stack(bits=2)
    xe, me, ge = _inputs(4, 8, 128, mask_mode, gated, seed=7)
    _parity(stack, xe, me, ge, jnp.int32(stack.pad_rank // 2))


@pytest.mark.parametrize("mask_mode", ["partial", "all"])
@pytest.mark.parametrize("gated", [False, True])
@pytest.mark.parametrize("rank_mode", ["full", "partial", "zero"])
def test_fused_parity_multi_tile(rank_mode, gated, mask_mode):
    """Several token, N and K tiles per expert (5 x 4 x 4 with pinned
    tiles): the rank-space activation is built on the first N tile of
    each (expert, token tile) and reused by the later ones, so an
    activation left stale from another expert or token tile, or never
    built for a later N tile, shows here (one N tile cannot show it)."""
    stack, _ = _stack(bits=2, e=3, k=512, n=512, rank_budget=16, seed=31)
    xe, me, ge = _inputs(3, 40, 512, mask_mode, gated, seed=37)
    cap = {"full": None, "partial": jnp.int32(stack.pad_rank // 2),
           "zero": jnp.int32(0)}[rank_mode]
    y_ref = ops.fused_expert_matmul(xe, stack, me, gates=ge, rank_cap=cap,
                                    impl="ref", out_dtype=jnp.float32)
    y_pl = ops.fused_expert_matmul(xe, stack, me, gates=ge, rank_cap=cap,
                                   impl="pallas_interpret",
                                   out_dtype=jnp.float32,
                                   bm=8, bn=128, bk=128)
    np.testing.assert_allclose(np.asarray(y_pl), np.asarray(y_ref), **TOL)


def test_fused_parity_heterogeneous_expert_bits():
    """Sub-width experts in a shared max-width container must dequantize
    at their TRUE width inside the kernel (expert_bits input)."""
    stack, _ = _stack(bits=3, expert_bits=[2, 3, 2, 3])
    assert stack.expert_bits == (2, 3, 2, 3) and stack.bits == 3
    xe, me, ge = _inputs(4, 8, 128, "partial", gated=True, seed=11)
    _parity(stack, xe, me, ge, None)


def test_fused_parity_ragged_capacity():
    """C not divisible by bm exercises the pad/slice wrapper."""
    stack, _ = _stack(bits=4)
    xe, me, ge = _inputs(4, 5, 128, "partial", gated=True, seed=13)
    _parity(stack, xe, me, ge, jnp.int32(3))


def test_fused_matches_unfused_sequence():
    """The fused kernel computes exactly what the unfused op-sequence
    (compensated matmul stack, then gate multiply) computes."""
    stack, _ = _stack(bits=2)
    xe, me, ge = _inputs(4, 8, 128, "partial", gated=True, seed=17)
    cap = jnp.int32(stack.pad_rank // 2)
    y_seq = ops.compensated_matmul_stack(xe, stack, me, impl="ref",
                                         out_dtype=jnp.float32,
                                         rank_cap=cap) * ge[..., None]
    y_fused = ops.fused_expert_matmul(xe, stack, me, gates=ge,
                                      rank_cap=cap,
                                      impl="pallas_interpret",
                                      out_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(y_fused), np.asarray(y_seq),
                               **TOL)


@pytest.mark.skipif(jax.default_backend() != "tpu",
                    reason="compiled Mosaic parity needs a TPU")
def test_fused_parity_compiled_mosaic():
    stack, _ = _stack(bits=2)
    xe, me, ge = _inputs(4, 8, 128, "partial", gated=True, seed=23)
    y_ref = ops.fused_expert_matmul(xe, stack, me, gates=ge,
                                    rank_cap=jnp.int32(4), impl="ref",
                                    out_dtype=jnp.float32)
    y_tpu = ops.fused_expert_matmul(xe, stack, me, gates=ge,
                                    rank_cap=jnp.int32(4), impl="pallas",
                                    out_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(y_tpu), np.asarray(y_ref), **TOL)


def test_fused_fuzz_hypothesis():
    """Randomized parity cells (shapes, seeds, caps) when hypothesis is
    installed; the parametrized matrix above is the CI floor."""
    hypothesis = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=10, deadline=None)
    @given(bits=st.sampled_from([2, 3, 4, 8]),
           c=st.integers(min_value=1, max_value=9),
           cap=st.integers(min_value=0, max_value=8),
           gated=st.booleans(), seed=st.integers(0, 2 ** 16))
    def prop(bits, c, cap, gated, seed):
        stack, _ = _stack(bits=bits, seed=seed % 7)
        xe, me, ge = _inputs(4, c, 128, "partial", gated, seed=seed)
        _parity(stack, xe, me, ge, jnp.int32(cap))

    prop()


# ---------------------------------------------------------------------------
# compile-count pin: the controller plan is data, never a shape
# ---------------------------------------------------------------------------

def test_rank_cap_change_does_not_recompile():
    stack, _ = _stack(bits=2)
    xe, me, ge = _inputs(4, 8, 128, "partial", gated=True, seed=29)

    f = jax.jit(lambda cap: ops.fused_expert_matmul(
        xe, stack, me, gates=ge, rank_cap=cap, impl="pallas_interpret",
        out_dtype=jnp.float32))
    f(jnp.int32(0)).block_until_ready()
    logger = logging.getLogger("jax._src.dispatch")
    seen = []
    handler = logging.Handler()
    handler.emit = lambda record: seen.append(record.getMessage())
    logger.addHandler(handler)
    try:
        with jax.log_compiles():
            for cap in (1, 3, stack.pad_rank):
                f(jnp.int32(cap)).block_until_ready()
    finally:
        logger.removeHandler(handler)
    compiles = [m for m in seen if "Compiling" in m or "compil" in m]
    assert not compiles, f"plan change recompiled: {compiles}"
    assert f._cache_size() == 1


def test_plan_row_change_does_not_recompile_moe_apply():
    """End to end through the MoE layer: differing (top_n, rank_cap)
    plan rows reuse one compiled executable of the fused serving path."""
    rng = np.random.default_rng(0)
    e, d, fe = 4, 64, 128
    qcfg = QuantConfig(enabled=True, bits=2, rank_budget=8,
                       top_n_restore=1, hqq_iters=2)
    mcfg = MoEConfig(num_experts=e, top_k=2, d_expert=fe, quant=qcfg)
    w1 = jnp.asarray(rng.standard_normal((e, d, fe)), jnp.float32) * 0.05
    w3 = jnp.asarray(rng.standard_normal((e, d, fe)), jnp.float32) * 0.05
    w2 = jnp.asarray(rng.standard_normal((e, fe, d)), jnp.float32) * 0.05
    stacks, _ = compress_ffn_weights(w1, w2, w3, qcfg)
    params = {"router": jnp.asarray(rng.standard_normal((d, e)),
                                    jnp.float32), "stacks": stacks}
    x2 = jnp.asarray(rng.standard_normal((16, d)), jnp.float32)

    f = jax.jit(lambda x2, plan: moe_apply(
        x2, params, mcfg, quantized=True, impl="pallas_interpret",
        plan=plan)[0])
    outs = [f(x2, jnp.asarray(row, jnp.int32)).block_until_ready()
            for row in ((0, 0), (1, 4), (2, 8))]
    assert f._cache_size() == 1
    # and the plan genuinely changes the computation (not a dead input)
    assert not np.allclose(np.asarray(outs[0]), np.asarray(outs[2]),
                           atol=1e-6)
