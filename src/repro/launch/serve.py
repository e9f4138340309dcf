"""Serving CLI: ``python -m repro.launch.serve --arch <id> [...]``.

Boots the reduced config on CPU (or full config on a real pod), randomly
initializes or restores weights, and serves synthetic traffic through
the continuous-batching engine — one slot-indexed KV cache and one
compiled ``lax.scan`` decode chunk stay resident while the scheduler
admits, retires, and refills requests between chunks:

- default: one fixed batch (``--batch`` x ``--prompt-len``), reporting
  prefill latency and decode tokens/s;
- ``--requests N``: a scheduled workload of N ragged-length requests
  (optionally arriving at ``--rate`` req/s) onto ``--slots`` decode
  slots in ``--chunk``-step scan chunks, reporting throughput and
  p50/p95 request latency;
- ``--offload``: compress the MoE experts offline (BEAM-LRC: low-bit +
  rank-padded compensators) and serve from byte-metered host-side
  expert stores, reporting live wire bytes/token and cache hit rate;
- ``--artifact DIR`` (with ``--offload``): boot from a serialized
  compression artifact (``launch/compress.py``) instead of
  recompressing at startup — the stacks (possibly heterogeneous
  per-expert bits/ranks from the calibrated allocator) load off disk
  after a config-fingerprint + checksum check, and serving is
  bit-identical to in-memory compression of the same plan;
- ``--bytes-per-token B`` / ``--target-tokens-per-s T`` (with
  ``--offload``): close the loop with the runtime bandwidth-budget
  controller — between scan chunks it retunes the per-layer
  (top_n, rank_cap) restoration plan to meet the budget (B directly, or
  the bytes/token a ``--link-bw`` link affords at T tokens/s), budgeting
  either the aggregate link or (``--budget-scope per_shard``) the
  hottest shard's link;
- ``--stream`` (with ``--offload``): serve through the REAL async
  expert-streaming engine — compressed experts live in host memory and
  stream into device containers through per-layer staging rings
  overlapped with decode; ``--stream-miss block`` keeps decode
  token-identical to all-resident (stage + re-run on a true miss),
  ``--stream-miss degrade`` serves misses from the device-resident
  ``--stream-fallback-bits`` fallback instead of stalling; the report
  adds overlap efficiency, stalls, and the metered==observed byte check;
- ``--spec-k K`` (with ``--requests``): speculative decoding — a
  ``--drafter`` (backoff n-gram / small draft model / windowed
  self-draft) proposes K tokens per slot per round, one batched target
  pass verifies them by rejection sampling (token-identical to plain
  decode at temperature 0), accepted prefixes commit their KV entries
  and rejected suffixes roll back; with ``--offload`` the verify pass's
  router trace drives the lookahead prefetcher, and the report adds
  acceptance rate, lookahead prefetch accuracy, and the wasted-
  speculation draft overhead bytes;
- ``--mesh ep=N``: expert-parallel sharded serving — experts (and their
  quantized planes + compensator factors) partition over an N-way
  ``('model',)`` mesh, decode runs resident-expert partials + psum under
  shard_map, and the offload meter splits into per-shard stores whose
  link bytes reduce into the report.  On CPU this needs
  ``XLA_FLAGS=--xla_force_host_platform_device_count=N``;
- ``--full-config --layers N``: the published widths at a depth cut to
  N layers, so a model whose full depth does not fit one device still
  serves at full width.  Full-width weights are drawn in bf16 (the
  reduced CPU config keeps f32) and, with ``--offload``, compressed one
  layer at a time, so the dense model never exists whole on the device.

``build_engine(args)`` is the one way an engine is built from these
options; other entry points (``chip_smoke.py``) call it with
``build_parser().parse_args([...])``.
"""
import argparse
import dataclasses
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..config import ControlConfig, ModelConfig
from ..registry import get_config
from ..models import init_params
from ..models.transformer import init_compressed_params
from ..paths import enable_compile_cache
from ..serve import ServeEngine, synthetic_workload
from .mesh import make_serve_mesh, parse_mesh_spec


def _maybe_autotune(stacks_by_layer):
    """``REPRO_AUTOTUNE=1`` boot-time tile sweep for the fused decode
    kernel: time the roofline candidates once per unique (bits,
    group_size, rank, K, N) decode shape on the local device and persist
    the winners (``kernels/autotune.py``).  Only runs where the compiled
    Mosaic kernel is the serving path — on CPU the lookup table already
    decides, and interpreter timings would be meaningless."""
    from ..kernels.autotune import autotune_enabled, tune_fused
    from ..kernels.ops import resolve_impl
    if not autotune_enabled() or resolve_impl(None) != "pallas":
        return
    seen = set()
    for stacks in stacks_by_layer:
        for name, stack in stacks.items():
            e, k, n = stack.shape
            key = (stack.bits, stack.group_size, stack.pad_rank, k, n)
            if key in seen:
                continue
            seen.add(key)
            xe = jnp.zeros((len(stack.ranks), 8, k), jnp.float32)
            me = jnp.ones((len(stack.ranks), 8), jnp.float32)
            tiles = tune_fused(xe, stack, me, None, None,
                               out_dtype=jnp.float32, interpret=False)
            print(f"autotune: fused b{stack.bits} k{k} n{n} -> "
                  f"bm,bn,bk={tiles}")


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        description="serve synthetic traffic through the continuous-"
                    "batching engine (scheduler + fixed-shape scan chunks)")
    ap.add_argument("--arch", required=True)
    ap.add_argument("--batch", type=int, default=2,
                    help="fixed-batch mode: rows decoded side by side")
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--full-config", action="store_true",
                    help="published widths (bf16 weights) instead of the "
                         "reduced CPU config (f32)")
    ap.add_argument("--layers", type=int, default=0,
                    help="with --full-config: cut the depth to this many "
                         "layers at the published widths (0 = all)")
    ap.add_argument("--requests", type=int, default=0,
                    help="schedule N ragged requests through the slot pool "
                         "instead of one fixed batch")
    ap.add_argument("--rate", type=float, default=0.0,
                    help="offered load in requests/s (0 = all at t=0)")
    ap.add_argument("--slots", type=int, default=4,
                    help="decode-slot pool size (compiled batch rows)")
    ap.add_argument("--chunk", type=int, default=8,
                    help="decode steps per scan chunk; the scheduler "
                         "refills finished slots between chunks")
    ap.add_argument("--page-size", type=int, default=0,
                    help="paged KV cache: allocate attention cache in "
                         "pages of this many tokens (power of two; 0 = "
                         "contiguous bucketed cache). Capacity is "
                         "per-request instead of worst-case-bucketed, "
                         "decode still compiles once")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="refcount-share physical pages across requests "
                         "with a common prompt prefix so the shared "
                         "span's prefill runs once (needs --page-size)")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="speculative decoding: draft this many tokens "
                         "per slot per round and verify them in one "
                         "batched target pass (0 = off; needs "
                         "--requests; token-identical at temperature 0)")
    ap.add_argument("--drafter", default="ngram",
                    choices=("ngram", "model", "self"),
                    help="speculative drafter: backoff n-gram over each "
                         "slot's committed stream, a small random-init "
                         "dense draft model, or the serving model itself "
                         "re-read over a token window (the idealized "
                         "high-acceptance drafter)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mesh", default="",
                    help="expert-parallel serving mesh, e.g. 'ep=4': "
                         "partition experts over N devices (CPU needs "
                         "XLA_FLAGS=--xla_force_host_platform_device_"
                         "count=N)")
    # -- offload + bandwidth-budget controller ---------------------------
    ap.add_argument("--offload", action="store_true",
                    help="compress MoE experts and meter offloaded serving "
                         "(wire bytes, cache hits) from live decode routing")
    ap.add_argument("--artifact", default="",
                    help="boot the compressed stacks from a "
                         "launch/compress.py artifact directory instead "
                         "of recompressing at startup (needs --offload)")
    ap.add_argument("--cache-experts", type=int, default=4,
                    help="device-resident expert LRU capacity per layer")
    ap.add_argument("--bytes-per-token", type=float, default=0.0,
                    help="bandwidth budget: adapt per-layer (top_n, "
                         "rank_cap) to this many wire bytes per token")
    ap.add_argument("--target-tokens-per-s", type=float, default=0.0,
                    help="bandwidth SLO: budget = link-bw / target tok/s")
    ap.add_argument("--link-bw", type=float, default=25e9,
                    help="link bandwidth (bytes/s) for --target-tokens-per-s")
    ap.add_argument("--budget-scope", default="aggregate",
                    choices=("aggregate", "per_shard"),
                    help="what the byte budget constrains under --mesh: "
                         "the summed links or the hottest shard's link")
    # -- async expert streaming -------------------------------------------
    ap.add_argument("--stream", action="store_true",
                    help="serve through the async expert-streaming engine "
                         "(needs --offload): experts live in host memory "
                         "and stream into device containers via per-layer "
                         "staging rings; decode blocks only on a true miss")
    ap.add_argument("--stream-ring", type=int, default=2,
                    help="staging-ring slots per layer (in-flight H2D "
                         "copies; 2 = double buffer)")
    ap.add_argument("--stream-miss", default="block",
                    choices=("block", "degrade"),
                    help="on a routed expert whose copy has not landed: "
                         "'block' stages + re-runs the chunk (token-"
                         "identical to all-resident), 'degrade' serves it "
                         "from the resident low-bit fallback")
    ap.add_argument("--stream-fallback-bits", type=int, default=2,
                    help="bit width of the device-resident fallback copy "
                         "that serves missed experts under 'degrade'")
    return ap


def validate(ap: argparse.ArgumentParser, args) -> None:
    """Reject option combinations the engine cannot serve."""
    want_budget = args.bytes_per_token > 0 or args.target_tokens_per_s > 0
    if args.layers and not args.full_config:
        ap.error("--layers cuts the depth of the published config; it "
                 "needs --full-config")
    if want_budget and not args.offload:
        ap.error("--bytes-per-token/--target-tokens-per-s need --offload "
                 "(the controller feeds on the offload byte meters)")
    if args.artifact and not args.offload:
        ap.error("--artifact needs --offload (it replaces the startup "
                 "compression of the offload path)")
    if args.stream and not args.offload:
        ap.error("--stream needs --offload (the stream engine is driven "
                 "by the offload stores' metering events)")
    if args.stream and args.mesh:
        ap.error("--stream requires the single-device serving path "
                 "(mesh-sharded streaming is not supported)")
    if args.spec_k > 0 and args.requests <= 0:
        ap.error("--spec-k needs --requests (speculative rounds run "
                 "through the continuous-batching scheduler)")
    if args.offload and model_config(args).moe is None:
        ap.error(f"--offload needs an MoE arch; {args.arch} has none")


def model_config(args) -> ModelConfig:
    cfg = get_config(args.arch, reduced=not args.full_config)
    if args.layers:
        cfg = dataclasses.replace(cfg, num_layers=args.layers)
    return cfg


def serve_mesh(args):
    return make_serve_mesh(parse_mesh_spec(args.mesh).get("ep", 1)
                           if args.mesh else 1)


def build_model(args, mesh=None
                ) -> Tuple[ModelConfig, Dict, Optional[List[Dict]]]:
    """``(cfg, params, stacks_by_layer)`` to serve: dense params (stacks
    None) or, with ``--offload``, the compressed model and its per-layer
    stacks.  Weights follow ``--seed`` on every path, so ``--offload``
    (in-memory compression) and ``--offload --artifact`` compare
    bit-identically at any seed.  With a ``mesh`` each compressed layer
    is placed on it as soon as it exists."""
    cfg = model_config(args)
    if cfg.encoder is not None or cfg.rope_kind == "mrope":
        print(f"note: {cfg.name} needs frontend inputs; serving the "
              f"text-only path")
    dtype = jnp.bfloat16 if args.full_config else jnp.float32
    key = jax.random.key(args.seed)
    if not args.offload:
        return cfg, init_params(key, cfg, dtype), None
    if args.artifact:
        from ..calib import load_compression_artifact
        from ..models.transformer import apply_compressed_stacks
        stacks_by_layer, plan, meta = load_compression_artifact(
            args.artifact, cfg)
        if meta.get("seed", 0) != args.seed:
            raise ValueError(f"artifact was compressed against params seed "
                             f"{meta.get('seed')}, serving seed {args.seed}")
        qparams, cfg_q = apply_compressed_stacks(
            init_params(key, cfg, dtype), cfg, stacks_by_layer)
        print(f"booted artifact {args.artifact}: "
              f"{meta['moe_layers']} MoE layers, "
              f"plan={'none (uniform)' if plan is None else plan.scorer},"
              f" checksum ok — no startup recompression")
        return cfg_q, qparams, stacks_by_layer
    place = None
    if mesh is not None:
        from ..config import ParallelConfig
        from ..distributed.sharding import tree_shardings

        def place(tree):
            return jax.device_put(tree, tree_shardings(
                mesh, jax.eval_shape(lambda: tree), ParallelConfig()))
    qparams, cfg_q, stacks_by_layer = init_compressed_params(
        key, cfg, dtype, place=place)
    return cfg_q, qparams, stacks_by_layer


def build_engine(args, model=None) -> ServeEngine:
    """The serving engine the options describe.  ``model`` (a
    ``build_model`` result) reuses weights already built, e.g. to serve
    the same compressed model resident and streamed."""
    mesh = serve_mesh(args)
    cfg, params, stacks_by_layer = model or build_model(args, mesh)
    if stacks_by_layer is None:
        return ServeEngine(cfg, params, mesh=mesh)
    _maybe_autotune(stacks_by_layer)
    eng = ServeEngine(cfg, params, quantized=True, mesh=mesh)
    eng.attach_offload(stacks_by_layer, policy="ours",
                       cache_capacity=args.cache_experts)
    if args.bytes_per_token > 0 or args.target_tokens_per_s > 0:
        eng.attach_controller(ControlConfig(
            enabled=True, bytes_per_token=args.bytes_per_token,
            tokens_per_s=args.target_tokens_per_s,
            link_bw=args.link_bw, budget_scope=args.budget_scope))
    if args.stream:
        from ..config import StreamConfig
        eng.attach_streaming(StreamConfig(
            enabled=True, ring_slots=args.stream_ring,
            miss_policy=args.stream_miss,
            fallback_bits=args.stream_fallback_bits))
    return eng


def workload(args, cfg: ModelConfig):
    """The ``--requests`` workload: ragged prompts from ``--seed``."""
    return synthetic_workload(
        args.requests, cfg.vocab_size, rate=args.rate,
        max_new=args.max_new, min_len=max(args.prompt_len // 2, 1),
        max_len=args.prompt_len, seed=args.seed)


def main():
    enable_compile_cache()
    ap = build_parser()
    args = ap.parse_args()
    validate(ap, args)
    try:
        eng = build_engine(args)
    except ValueError as exc:
        ap.error(str(exc))
    cfg = eng.cfg

    if args.requests > 0:
        stats = eng.serve(workload(args, cfg), num_slots=args.slots, chunk=args.chunk,
                          seed=args.seed, page_size=args.page_size,
                          prefix_cache=args.prefix_cache,
                          spec_k=args.spec_k,
                          drafter=args.drafter if args.spec_k > 0 else None)
        lat = stats.latency_percentiles((50.0, 95.0))
        print(f"{cfg.name}: {args.requests} requests on {args.slots} slots "
              f"(chunk {args.chunk}, rate "
              f"{args.rate if args.rate > 0 else 'closed-loop'}): "
              f"{stats.tokens_per_s:.1f} tok/s, "
              f"latency p50 {lat[50.0] * 1e3:.0f}ms "
              f"p95 {lat[95.0] * 1e3:.0f}ms, "
              f"{stats.chunks} chunks, compiles {eng.num_compiles}")
        print(f"cache: {stats.cache_hbm_bytes / 2**20:.2f} MiB HBM "
              f"({stats.cache_hbm_bytes_per_token / 2**10:.1f} KiB/token), "
              f"{stats.prefill_tokens} prefill tokens")
        pr = stats.page_report
        if pr is not None:
            print(f"pages ({pr['num_pages']}x{pr['page_size']}): "
                  f"{pr['allocs']} allocs, prefix hit "
                  f"{pr['prefix_hit_rate']:.0%} "
                  f"({pr['prefix_hits']}/{pr['prefix_queries']}), "
                  f"peak shared ref {pr['peak_shared_ref']}, "
                  f"{pr['evictions']} evictions")
        rep = stats.offload_report
        if rep is not None:
            print(f"offload ({rep['policy']}): "
                  f"{rep['bytes_per_token'] / 2**10:.1f} KiB/token, "
                  f"cache hit {rep['hit_rate']:.0%}, prefetch accuracy "
                  f"{rep['prefetch_accuracy']:.0%}")
            if rep["ep"] > 1:
                shares = ", ".join(f"{b / 2**10:.0f}"
                                   for b in rep["per_shard_bytes"])
                print(f"  per-shard links (ep={rep['ep']}): [{shares}] KiB, "
                      f"hottest {rep['max_shard_bytes_per_token'] / 2**10:.1f}"
                      f" KiB/token")
        sp = stats.spec_report
        if sp is not None:
            print(f"speculative (k={sp['spec_k']}, {sp['drafter']}): "
                  f"acceptance {sp['acceptance_rate']:.0%} "
                  f"({sp['accepted_draft_tokens']}/{sp['drafted_tokens']} "
                  f"drafts over {sp['rounds']} rounds), lookahead "
                  f"prefetch accuracy {sp['lookahead_accuracy']:.0%}, "
                  f"draft overhead "
                  f"{sp['draft_overhead_bytes'] / 2**10:.1f} KiB")
        sr = stats.stream_report
        if sr is not None:
            print(f"stream ({sr['miss_policy']}, ring {sr['ring_slots']}): "
                  f"overlap {sr['overlap_efficiency']:.0%}, "
                  f"{sr['observed_copies']} copies "
                  f"({sr['observed_copy_bytes'] / 2**20:.1f} MiB observed "
                  f"== {sr['metered_bytes'] / 2**20:.1f} MiB metered), "
                  f"{sr['stalls']} stalls ({sr['stall_s'] * 1e3:.0f}ms), "
                  f"{sr['reruns']} re-runs, "
                  f"{sr['degraded_tokens']} degraded tokens")
        if eng.controller is not None and eng.controller.history:
            c = eng.controller
            tail = c.history[len(c.history) // 2:]
            meas = float(np.mean([h.bytes_per_token for h in tail]))
            plan = c.plan().summary()
            print(f"controller: budget "
                  f"{c.ccfg.target_bytes_per_token / 2**10:.1f} KiB/token, "
                  f"converged tail {meas / 2**10:.1f} KiB/token "
                  f"({len(c.history)} updates), plan mean top_n "
                  f"{plan['mean_top_n']:.2f} rank_cap "
                  f"{plan['mean_rank_cap']:.1f}")
        return

    prompts = np.random.default_rng(args.seed).integers(
        0, cfg.vocab_size, (args.batch, args.prompt_len), dtype=np.int32)
    res = eng.generate(prompts, max_new=args.max_new)
    print(f"{cfg.name}: prefill {res.prefill_s * 1e3:.0f}ms, "
          f"decode {res.decode_tokens_per_s:.1f} tok/s "
          f"({args.batch}x{args.max_new} tokens)")
    if res.offload_report is not None:
        rep = res.offload_report
        print(f"offload ({rep['policy']}): "
              f"{rep['bytes_per_token'] / 2**10:.1f} KiB/token, "
              f"cache hit {rep['hit_rate']:.0%}")
    if res.stream_report is not None:
        sr = res.stream_report
        print(f"stream ({sr['miss_policy']}, ring {sr['ring_slots']}): "
              f"overlap {sr['overlap_efficiency']:.0%}, "
              f"{sr['observed_copies']} copies "
              f"({sr['observed_copy_bytes'] / 2**20:.1f} MiB observed == "
              f"{sr['metered_bytes'] / 2**20:.1f} MiB metered), "
              f"{sr['stalls']} stalls, {sr['degraded_tokens']} degraded "
              f"tokens")


if __name__ == "__main__":
    main()
