"""Expert-parallel sharded serving: the PR-4 headline invariants.

One multi-device script (via the shared ``dist_run`` fixture) serves the
same request set through ``ServeEngine`` across the full parity matrix

    ``REPRO_KERNEL_IMPL`` in {ref, pallas_interpret}
  x arch in {MoE (E=8, k=2), dense-degenerate (E=1, k=1), MoE with bf16
             weights and 8 query heads, which the mesh shards}
  x shard counts {1, 2, 8}

and the tests pin:

- token-identical decode across shard counts AND kernel impls, with
  allclose per-token logprobs (the psum/a2a reduction order may differ
  in low-order bits; the sampled streams may not);
- conserved offload metering: total wire bytes, metered tokens, and
  cache hit/miss counts are IDENTICAL across shard counts (per-shard
  caches large enough to hold their residents — eviction-free regime,
  where the per-expert residency state decomposes exactly over any
  expert partition), and the per-shard bytes sum to the total;
- the bandwidth controller drives the plan under sharding with ZERO new
  decode-scan compiles across plan/budget changes, and a a sharded serve
  with per-shard metering feeds chunk updates at every boundary;
- (PR 5) a calibrated heterogeneous-precision artifact saved on a
  1-device mesh restores into ep=2 / ep=8 serving token-identically,
  with the per-expert (heterogeneous-bit) wire bytes conserved EXACTLY
  across shard counts and per-shard bytes summing to the total.
"""
import textwrap

import numpy as np
import pytest

pytestmark = pytest.mark.dist

IMPLS = ("ref", "pallas_interpret")
ARCHS = ("moe", "dense_e1", "moe_bf16")
EPS = (1, 2, 8)

SCRIPT = textwrap.dedent("""
    import json
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.config import ControlConfig, ModelConfig, MoEConfig, \\
        QuantConfig
    from repro.launch.mesh import make_serve_mesh
    from repro.models import init_params
    from repro.models.transformer import compress_moe_params
    from repro.serve import ServeEngine, synthetic_workload

    def make_cfg(e, k, heads=(2, 1)):
        return ModelConfig(
            name=f"ep-serve-{e}", family="moe", num_layers=2, d_model=64,
            num_heads=heads[0], num_kv_heads=heads[1], head_dim=32, d_ff=0,
            vocab_size=64,
            block_pattern=("global",), max_position=512,
            moe=MoEConfig(num_experts=e, top_k=k, d_expert=64,
                          quant=QuantConfig(enabled=True, bits=2,
                                            rank_budget=8, top_n_restore=1,
                                            hqq_iters=2)))

    prompts = [np.random.default_rng(i).integers(0, 64, (5 + 3 * i,))
               for i in range(3)]
    results = {}

    for arch, (e, k, heads, dtype) in (
            ("moe", (8, 2, (2, 1), jnp.float32)),
            ("dense_e1", (1, 1, (2, 1), jnp.float32)),
            ("moe_bf16", (8, 2, (8, 2), jnp.bfloat16))):
        cfg = make_cfg(e, k, heads)
        params = init_params(jax.random.key(0), cfg, dtype)
        qparams, cfg_q, stacks = compress_moe_params(params, cfg)
        for impl in ("ref", "pallas_interpret"):
            for ep in (1, 2, 8):
                eng = ServeEngine(cfg_q, qparams, quantized=True,
                                  kernel_impl=impl, mesh=make_serve_mesh(ep))
                # eviction-free regime: per-shard capacity >= residents at
                # every shard count, so byte totals must conserve exactly
                eng.attach_offload(stacks, policy="ours", cache_capacity=8,
                                   prefetch=False)
                stats = eng.generate_many(prompts, max_new=6, num_slots=2,
                                          chunk=3)
                rep = stats.offload_report
                results[f"{arch}/{impl}/ep{ep}"] = {
                    "tokens": np.concatenate(
                        [r.tokens for r in stats.results]).tolist(),
                    "logprobs": np.concatenate(
                        [r.logprobs for r in stats.results]).tolist(),
                    "total_bytes": rep["total_bytes"],
                    "metered_tokens": rep["tokens"],
                    "hits_misses": [int(1e9 * rep["hit_rate"])],
                    "per_shard_bytes": rep["per_shard_bytes"],
                    "ep": rep["ep"],
                    "shard_bytes": (stats.shard_bytes.tolist()
                                    if stats.shard_bytes is not None
                                    else None),
                }

    # controller under sharding: plan moves, decode scan never recompiles
    cfg = make_cfg(8, 2)
    params = init_params(jax.random.key(0), cfg, jnp.float32)
    qparams, cfg_q, stacks = compress_moe_params(params, cfg)
    eng = ServeEngine(cfg_q, qparams, quantized=True, mesh=make_serve_mesh(2))
    eng.attach_offload(stacks, policy="ours", cache_capacity=2)
    eng.attach_controller(ControlConfig(enabled=True, bytes_per_token=1.0,
                                        gain=0.5))
    wl = lambda: synthetic_workload(5, 64, max_new=8, seed=3)
    s1 = eng.serve(wl(), num_slots=2, chunk=4)
    compiles_warm = eng.num_compiles["decode"]
    # a very different budget => different per-chunk plans, same compile
    eng.attach_offload(stacks, policy="ours", cache_capacity=2)
    eng.attach_controller(ControlConfig(enabled=True,
                                        bytes_per_token=50_000.0, gain=0.5))
    s2 = eng.serve(wl(), num_slots=2, chunk=4)
    results["controller"] = {
        "plan_moved": bool(not (s1.plan_trace == s1.plan_trace[0]).all()),
        "plans_differ_across_budgets": bool(
            not (s2.plan_trace == s1.plan_trace).all()),
        "decode_compiles_warm": compiles_warm,
        "decode_compiles_after": eng.num_compiles["decode"],
        "controller_updates": len(eng.controller.history),
        "chunks": s2.chunks,
    }

    # calibrated heterogeneous artifact: save once (1-device mesh),
    # restore into every shard count (extends the parity matrix)
    import tempfile
    from repro.calib import (allocate_budget, collect_calibration_stats,
                             load_compression_artifact,
                             moe_weights_by_layer,
                             save_compression_artifact, uniform_plan)
    from repro.models.transformer import apply_compressed_stacks
    cfg = make_cfg(8, 2)
    params = init_params(jax.random.key(0), cfg, jnp.float32)
    cstats = collect_calibration_stats(cfg, params, batches=1,
                                       batch_size=2, seq_len=32)
    weights = moe_weights_by_layer(params, cfg)
    qcfg = cfg.moe.quant
    plan = allocate_budget(
        weights, qcfg, uniform_plan(weights, qcfg, 3, 4).spent_bytes,
        stats=cstats)
    qparams, cfg_q, stacks = compress_moe_params(params, cfg, plan=plan,
                                                 stats=cstats)
    tmp = tempfile.mkdtemp()
    save_compression_artifact(tmp, cfg, stacks, plan=plan)
    loaded, _, _ = load_compression_artifact(tmp, cfg)
    qp_art, _ = apply_compressed_stacks(params, cfg, loaded)
    for label, prm, stk, eps in (("mem", qparams, stacks, (1,)),
                                 ("art", qp_art, loaded, (1, 2, 8))):
        for ep in eps:
            eng = ServeEngine(cfg_q, prm, quantized=True,
                              mesh=make_serve_mesh(ep))
            eng.attach_offload(stk, policy="ours", cache_capacity=8,
                               prefetch=False)
            st = eng.generate_many(prompts, max_new=4, num_slots=2, chunk=2)
            rep = st.offload_report
            store0 = eng._stores[0]
            results[f"artifact/{label}/ep{ep}"] = {
                "tokens": np.concatenate(
                    [r.tokens for r in st.results]).tolist(),
                "logprobs": np.concatenate(
                    [r.logprobs for r in st.results]).tolist(),
                "total_bytes": rep["total_bytes"],
                "per_shard_bytes": rep["per_shard_bytes"],
                "expert_bytes": [store0.expert_bytes(e, "ours")
                                 for e in range(8)],
            }
    print("RESULTS:" + json.dumps(results))
""")


@pytest.fixture(scope="module")
def serve_results(dist_run):
    return dist_run(SCRIPT, timeout=580)


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("arch", ARCHS)
def test_sharded_serve_token_identical(serve_results, arch, impl):
    """ep=2 / ep=8 decode must reproduce the ep=1 token stream exactly,
    with allclose per-token logprobs."""
    base = serve_results[f"{arch}/{impl}/ep1"]
    for ep in EPS[1:]:
        got = serve_results[f"{arch}/{impl}/ep{ep}"]
        assert got["tokens"] == base["tokens"], (arch, impl, ep)
        np.testing.assert_allclose(got["logprobs"], base["logprobs"],
                                   rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("arch", ARCHS)
def test_cross_impl_token_identical(serve_results, arch):
    """ref and pallas_interpret backends agree token-for-token at every
    shard count (the dispatch policy changes kernels, not results)."""
    for ep in EPS:
        a = serve_results[f"{arch}/ref/ep{ep}"]
        b = serve_results[f"{arch}/pallas_interpret/ep{ep}"]
        assert a["tokens"] == b["tokens"], (arch, ep)


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("arch", ARCHS)
def test_metered_bytes_conserved_across_shard_counts(serve_results, arch,
                                                     impl):
    """Total wire bytes/token, metered tokens, and hit rates are identical
    across shard counts in the eviction-free regime; per-shard bytes sum
    exactly to the total (the ServeStats reduction loses nothing)."""
    base = serve_results[f"{arch}/{impl}/ep1"]
    assert base["total_bytes"] > 0
    for ep in EPS:
        got = serve_results[f"{arch}/{impl}/ep{ep}"]
        assert got["total_bytes"] == base["total_bytes"], (arch, impl, ep)
        assert got["metered_tokens"] == base["metered_tokens"]
        assert got["hits_misses"] == base["hits_misses"]
        assert sum(got["per_shard_bytes"]) == got["total_bytes"]
        assert got["shard_bytes"] == got["per_shard_bytes"]


def test_moe_experts_actually_spread_across_shards(serve_results):
    """At ep=8 the MoE arch's traffic crosses several distinct links —
    the partition is real, not one shard doing all the work."""
    got = serve_results["moe/ref/ep8"]
    assert got["ep"] == 8 and len(got["per_shard_bytes"]) == 8
    assert sum(1 for b in got["per_shard_bytes"] if b > 0) >= 4
    # E=1 cannot partition: the engine falls back to a single store
    assert serve_results["dense_e1/ref/ep8"]["ep"] == 1


def test_artifact_restores_bit_identically_on_one_device(serve_results):
    """Booting the saved calibrated artifact reproduces in-memory
    compression of the same plan exactly (tokens, logprobs, bytes)."""
    mem = serve_results["artifact/mem/ep1"]
    art = serve_results["artifact/art/ep1"]
    assert art["tokens"] == mem["tokens"]
    assert art["logprobs"] == mem["logprobs"]
    assert art["total_bytes"] == mem["total_bytes"] > 0


def test_artifact_sharded_serving_token_identical(serve_results):
    """A 1-device-saved artifact restored into ep=2 / ep=8 serving
    decodes the identical token stream."""
    base = serve_results["artifact/art/ep1"]
    for ep in (2, 8):
        got = serve_results[f"artifact/art/ep{ep}"]
        assert got["tokens"] == base["tokens"], ep
        np.testing.assert_allclose(got["logprobs"], base["logprobs"],
                                   rtol=1e-4, atol=1e-4)


def test_artifact_hetero_bytes_conserved_across_shards(serve_results):
    """The calibrated plan's heterogeneous per-expert wire bytes flow
    through the sharded metering with EXACT conservation: totals match
    at every shard count and per-shard bytes sum to the total."""
    base = serve_results["artifact/art/ep1"]
    # the allocation is really heterogeneous, or this test proves nothing
    assert len(set(base["expert_bytes"])) > 1
    for ep in (1, 2, 8):
        got = serve_results[f"artifact/art/ep{ep}"]
        assert got["total_bytes"] == base["total_bytes"]
        assert sum(got["per_shard_bytes"]) == got["total_bytes"]
        assert got["expert_bytes"] == base["expert_bytes"]


def test_controller_moves_plan_without_decode_recompile(serve_results):
    """Under an ep=2 mesh the budget controller changes the per-chunk
    restoration plan (both within a serve and across budgets) while the
    compiled decode scan is reused — plan is data, not shape."""
    c = serve_results["controller"]
    assert c["plan_moved"]
    assert c["plans_differ_across_budgets"]
    assert c["decode_compiles_after"] == c["decode_compiles_warm"]
    assert c["controller_updates"] >= c["chunks"]
