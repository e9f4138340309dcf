"""Batched serving engine: chunked prefill + jitted streaming decode loop
+ continuous-batching request scheduling.

The decode loop is a single ``lax.scan`` over steps: sampling happens
on-device (no per-token host round-trip), cache buffers are donated into
the loop, and the per-step router trace is a first-class output of the
forward pass (``ExecContext.collect_trace``).

Compiled shapes are *bucketed* so they survive ragged traffic:

- cache lengths round up to powers of two, so every (prompt, max_new)
  pair in a bucket reuses the same compiled prefill + decode loop;
- prompts right-pad to a power-of-two length and the padded cache slots
  are invalidated (``mask_cache_padding``: pos = -1) so padded decode is
  bit-identical to unpadded;
- ``serve``/``generate_many`` run the decode scan in fixed-size chunks
  over a slot-indexed cache: between chunks the ``serve/scheduler.py``
  scheduler retires finished requests and refills their slots from the
  queue — many requests, one resident compiled loop.

When expert stores are attached (``attach_offload``), every generated
step's routing decisions are replayed into the per-layer metered
``ExpertStore`` + ``LayerAheadPrefetcher``, so wire bytes / cache hits /
prefetch accuracy come from live serving rather than only the synthetic
simulator; inactive scheduler slots are masked (expert id -1) before
metering.

With a serving mesh (``mesh=make_serve_mesh(ep)``) the same entry
points run expert-parallel: experts partition over the mesh's ``model``
axis, the decode scan executes the MoE layers under shard_map
(resident-expert partials + psum), the offload meter splits into
per-shard stores whose link bytes reduce into ``ServeStats``, and the
controller can budget either the aggregate or the hottest shard link
(``ControlConfig.budget_scope``).  See ARCHITECTURE.md
§Expert-parallel sharded serving.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Dict, Iterable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..config import ControlConfig, ModelConfig, ParallelConfig, ServeConfig
from ..distributed.moe_parallel import ep_size
from ..distributed.sharding import (CACHE_RULES, PARAM_RULES,
                                    tree_constraint, tree_shardings)
from ..models import model as lm
from ..models.transformer import (ExecContext, cache_claim_slot,
                                  cache_claim_slot_paged, cache_reset_slot_paged,
                                  cache_rollback, cache_seed_prefix,
                                  init_caches, init_paged_caches, layer_specs,
                                  mask_cache_padding)
from ..launch.steps import make_context
from .controller import BandwidthController, ControllerPlan
from .paging import PagePool, prefix_page_hashes
from .scheduler import Request, RequestResult, Scheduler
from .speculative import accept_drafts, make_drafter, mask_banned
from .timeline import Timeline

PROMPT_BUCKET_MIN = 16     # smallest padded-prompt length
CACHE_BUCKET_MIN = 32      # smallest bucketed cache length


def bucket_len(n: int, minimum: int = CACHE_BUCKET_MIN) -> int:
    """Round ``n`` up to the next power of two (>= minimum) — the length
    buckets that keep jit cache keys finite under ragged traffic."""
    return max(minimum, 1 << max(int(n) - 1, 0).bit_length())


def _round_up(n: int, m: int) -> int:
    return (n + m - 1) // m * m


@dataclasses.dataclass
class GenerationResult:
    tokens: np.ndarray             # (B, max_new)
    logprobs: Optional[np.ndarray]
    prefill_s: float
    decode_s: float
    steps: int
    # (steps, moe_layers, B, k) decode-time router decisions (None when the
    # model has no MoE layers)
    router_trace: Optional[np.ndarray] = None
    # live offload metering (attach_offload): bytes/token, hit rate, ...
    offload_report: Optional[Dict[str, float]] = None
    # async streaming engine counters (attach_streaming): overlap
    # efficiency, stalls, degraded tokens, observed copies, ...
    stream_report: Optional[Dict] = None

    @property
    def decode_tokens_per_s(self) -> float:
        b = self.tokens.shape[0]
        return b * self.steps / self.decode_s if self.decode_s else 0.0

    def request_trace(self, b: int = 0) -> Optional[np.ndarray]:
        """(steps, layers, k) routing of one request stream — the shape the
        offload simulator and fig-7 benchmarks consume."""
        if self.router_trace is None:
            return None
        return self.router_trace[:, :, b, :]


@dataclasses.dataclass
class ServeStats:
    """Outcome of one continuous-batching ``serve`` run."""
    results: List[RequestResult]       # submission order
    num_slots: int
    chunk: int
    total_s: float
    # host wall time inside the timeline's ``prefill`` + ``claim`` spans:
    # mostly the asynchronous dispatch, not the device's prefill work
    prefill_s: float
    # host wall time inside ``decode.dispatch`` + ``decode.sync``: the
    # sync also waits out the device time of every prefill and claim
    # queued ahead of the chunk
    decode_s: float
    chunks: int
    generated_tokens: int              # accepted tokens across requests
    offload_report: Optional[Dict] = None
    # (total_steps, moe_layers, num_slots, k) with -1 on inactive slots
    router_trace: Optional[np.ndarray] = None
    # (chunks, moe_layers, 2) per-chunk controller plan [top_n, rank_cap]
    # (None when no bandwidth controller is attached)
    plan_trace: Optional[np.ndarray] = None
    # (ep,) wire bytes that crossed each expert-parallel shard's link
    # (the per-shard reduction; length 1 on the single-device path)
    shard_bytes: Optional[np.ndarray] = None
    # async streaming counters (attach_streaming): overlap efficiency,
    # transfer/stall seconds, degraded tokens, observed copies, ...
    stream_report: Optional[Dict] = None
    # device bytes held by the serve run's KV/recurrent cache (every
    # plane, incl. page pools + block tables on the paged path) — the
    # HBM-side cost the paged cache exists to shrink
    cache_hbm_bytes: int = 0
    # padded prompt tokens pushed through prefill (suffix-only prefills
    # count only their suffix, so shared-prefix reuse shows up here)
    prefill_tokens: int = 0
    # page-pool accounting (paged runs): allocs/frees, prefix hit rate,
    # peak shared refcount, evictions (None on the contiguous path)
    page_report: Optional[Dict] = None
    # speculative decoding (serve(spec_k=)): draft acceptance rate,
    # lookahead prefetch accuracy, draft overhead bytes (None = spec off)
    spec_report: Optional[Dict] = None
    # the serve loop's host spans and counters (serve/timeline.py)
    timeline: Optional[Timeline] = None

    def __post_init__(self):
        # zero-token requests carry first_token_s = NaN (an explicit
        # sentinel, excluded from percentiles); any *negative* finite
        # latency is a scheduler timing bug and must never leak out
        for r in self.results:
            if r.latency_s < 0:
                raise AssertionError(
                    f"negative latency {r.latency_s} for uid {r.uid}")
            if np.isfinite(r.first_token_s) and r.ttft_s < 0:
                raise AssertionError(
                    f"negative ttft {r.ttft_s} for uid {r.uid}")

    @property
    def cache_hbm_bytes_per_token(self) -> float:
        return (self.cache_hbm_bytes / self.generated_tokens
                if self.generated_tokens else 0.0)

    @property
    def tokens_per_s(self) -> float:
        return self.generated_tokens / self.total_s if self.total_s else 0.0

    @property
    def busy_s(self) -> float:
        """Host wall time inside the prefill, claim and decode spans
        (``prefill_s + decode_s``), which leaves out the arrival waits.
        Not device busy time: prefill device work lands in ``decode_s``,
        and the host's own work between chunks is in neither."""
        return self.prefill_s + self.decode_s

    @property
    def goodput_tokens_per_s(self) -> float:
        """Accepted tokens per ``busy_s`` second (host wall time, see
        there).  Under open-loop (rated) traffic the wall-clock
        ``tokens_per_s`` folds arrival idle time into the denominator
        and collapses as the offered rate drops; this ratio leaves the
        waits out, so it compares better across offered loads."""
        return (self.generated_tokens / self.busy_s) if self.busy_s else 0.0

    @property
    def busy_frac(self) -> float:
        """``busy_s`` over ``total_s``: both host wall time."""
        return self.busy_s / self.total_s if self.total_s else 0.0

    def latency_percentiles(self, qs: Sequence[float] = (50.0, 95.0)
                            ) -> Dict[float, float]:
        lat = [r.latency_s for r in self.results]
        return {q: float(np.percentile(lat, q)) for q in qs} if lat else {}

    def ttft_percentiles(self, qs: Sequence[float] = (50.0, 95.0)
                         ) -> Dict[float, float]:
        """First-token latency percentiles over requests that emitted at
        least one token (NaN-sentinel zero-budget requests excluded)."""
        tt = [r.ttft_s for r in self.results if np.isfinite(r.ttft_s)]
        return {q: float(np.percentile(tt, q)) for q in qs} if tt else {}


def sample(logits: jax.Array, key, temperature: float) -> jax.Array:
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jax.random.categorical(key, logits / temperature, axis=-1) \
        .astype(jnp.int32)


class ServeEngine:
    def __init__(self, cfg: ModelConfig, params, scfg: ServeConfig = None,
                 quantized: bool = False, collect_router_trace: bool = True,
                 kernel_impl: Optional[str] = None,
                 cache_dtype: Optional[Any] = None,
                 mesh: Optional[Any] = None,
                 pcfg: Optional[ParallelConfig] = None):
        """``mesh``: optional expert-parallel serving mesh
        (``launch.mesh.make_serve_mesh``).  Expert weights — quantized
        planes, scales, and low-rank compensator factors — are partitioned
        over the mesh's ``model`` axis, prefill dispatches tokens to their
        expert shards via all_to_all and decode runs resident-expert
        partials + psum under ``shard_map`` (``distributed/moe_parallel``),
        all inside the same jitted entry points as the single-device path.
        The expert-FFN implementation inside each shard still follows the
        ``REPRO_KERNEL_IMPL`` / ``kernel_impl`` dispatch policy."""
        self.cfg = cfg
        self.scfg = scfg or ServeConfig()
        self.quantized = quantized
        self.kernel_impl = kernel_impl
        self.mesh = mesh
        self.pcfg = pcfg or ParallelConfig()
        self.ep = ep_size(mesh)
        if mesh is not None:
            # partition params by the logical-axis rules (expert dim and
            # compressed stacks onto the EP axis; small leaves replicate)
            params = jax.device_put(
                params, tree_shardings(mesh, jax.eval_shape(lambda: params),
                                       self.pcfg))
        self.params = params
        # KV caches follow the model's compute dtype (bf16 params must not
        # silently double KV memory with f32 caches); overridable, e.g.
        # cache_dtype=jnp.float32 for f32 accumulation studies.
        self.cache_dtype = (jnp.asarray(params["embed"]["tok"]).dtype
                            if cache_dtype is None else cache_dtype)
        # trace collection is free inside the scan (a few int32s per step);
        # it feeds GenerationResult.router_trace and the offload meter.
        # Gate on the PLAN's MoE layers (cfg.moe alone isn't enough: e.g.
        # first_layer_dense or recurrent-only patterns yield no MoE FFNs)
        specs = layer_specs(cfg)
        has_moe = any(s.ffn == "moe" for s in specs)
        self.collect_router_trace = collect_router_trace and has_moe
        # right-padded prefill is only exact when every mixer attends with
        # a full-length position-masked cache: recurrent states and local
        # ring buffers can't invalidate padding after the fact
        self._pad_prompts = all(s.mixer == "global" for s in specs)
        self._stores = None            # per-MoE-layer ExpertStore
        self._prefetcher = None
        self._offload_policy = "ours"
        self._controller = None        # BandwidthController (attach_controller)
        self._stream = None            # ExpertStreamEngine (attach_streaming)
        self._prefill_traced = None    # lazy trace-collecting prefill jit
        self._prefill_ctx = make_context(cfg, "prefill", quantized=quantized,
                                         exact_capacity=True,
                                         kernel_impl=kernel_impl,
                                         mesh=mesh, pcfg=self.pcfg)
        self._step_ctx = make_context(
            cfg, "step", quantized=quantized, exact_capacity=True,
            kernel_impl=kernel_impl, mesh=mesh, pcfg=self.pcfg,
            collect_trace=self.collect_router_trace)

        @jax.jit
        def prefill(params, caches, tokens, plen):
            """Prefill a (possibly right-padded) prompt batch.

            ``plen``: (B,) true prompt lengths.  Padding-written cache
            slots are invalidated (pos = -1) and the last-real-token
            logits are gathered per row, so two prompt lengths in the
            same bucket share one compile and decode identically."""
            out = lm.forward(params, tokens, cfg, self._prefill_ctx,
                             caches=caches)
            caches = mask_cache_padding(cfg, out.caches, plen)
            logits = jnp.take_along_axis(
                out.logits, (plen - 1)[:, None, None], axis=1)[:, 0]
            return self._pin_logits(logits), self._pin_caches(caches)

        def decode_loop(params, caches, logits0, key, plan, max_new,
                        temperature):
            """scan over decode steps: sample on device, step, stack trace.

            ``temperature`` is static (it selects the greedy/categorical
            branch in ``sample``) and read per call, so mutating
            ``scfg.temperature`` between generates takes effect.  The
            final RNG key is returned so chunked serving threads one key
            stream across scan chunks.  ``plan`` is the bandwidth
            controller's (moe_layers, 2) [top_n, rank_cap] array (None =
            static restoration): traced data with a static shape, so the
            per-chunk plan updates never recompile this loop."""

            def body(carry, _):
                logits, caches, key = carry
                with jax.named_scope("sampling"):
                    key, k2 = jax.random.split(key)
                    nxt = sample(logits, k2, temperature)
                out = lm.decode_step(params, nxt[:, None], caches, cfg,
                                     self._step_ctx, plan=plan)
                with jax.named_scope("sampling"):
                    lp = jax.nn.log_softmax(logits.astype(jnp.float32),
                                            axis=-1)
                    lp_tok = jnp.take_along_axis(lp, nxt[:, None],
                                                 axis=-1)[:, 0]
                ys = (nxt, lp_tok)
                if self.collect_router_trace:
                    ys = ys + (out.trace,)        # (moe_layers, B, k)
                return (out.logits[:, 0], out.caches, key), ys

            (logits, caches, key), ys = jax.lax.scan(
                body, (logits0, caches, key), xs=None, length=max_new)
            return self._pin_logits(logits), self._pin_caches(caches), key, ys

        @functools.partial(jax.jit, donate_argnums=(0, 2))
        def claim(caches, req_caches, logits, req_logits, slot):
            """Donated slot claim: writes one request's prefilled cache and
            last-token logits into row ``slot`` in place (``slot`` is a
            traced scalar, so admissions to any slot share one compile)."""
            caches = cache_claim_slot(cfg, caches, req_caches, slot)
            logits = jax.lax.dynamic_update_slice_in_dim(
                logits, req_logits.astype(logits.dtype), slot, 0)
            return self._pin_caches(caches), self._pin_logits(logits)

        @functools.partial(jax.jit, donate_argnums=(0, 2))
        def claim_paged(caches, req_caches, logits, req_logits, slot, pages,
                        write_mask):
            """Paged slot claim: ``slot``/``pages``/``write_mask`` are all
            traced, so one compile serves every admission of a given
            request-cache length."""
            caches = cache_claim_slot_paged(cfg, caches, req_caches, slot,
                                            pages, write_mask)
            logits = jax.lax.dynamic_update_slice_in_dim(
                logits, req_logits.astype(logits.dtype), slot, 0)
            return self._pin_caches(caches), self._pin_logits(logits)

        @functools.partial(jax.jit, donate_argnums=(0,))
        def reset_paged(caches, slot):
            """Unmap a retired slot's block-table row so its garbage
            decode writes land on the trash page instead of pages the
            host allocator has already handed to another request."""
            return self._pin_caches(cache_reset_slot_paged(cfg, caches, slot))

        @functools.partial(jax.jit, donate_argnums=(0,))
        def seed_prefix(req_caches, caches, pages):
            """Pull shared-prefix pages out of the pool into the leading
            span of a fresh batch-1 request cache (suffix prefill seed)."""
            return cache_seed_prefix(cfg, req_caches, caches, pages)

        @functools.partial(jax.jit, donate_argnums=(1,))
        def prefill_suffix(params, req_caches, tokens, start, plen):
            """Append-only prefill of a prompt *suffix* over a cache whose
            leading ``start`` positions were seeded from reused prefix
            pages: step-mode forward with explicit (B, S) positions writes
            and attends the suffix in one pass, so the shared span's
            prefill FLOPs are paid once per unique prefix.  Padded suffix
            tokens land at positions >= plen and are invalidated after."""
            s = tokens.shape[1]
            positions = start[:, None] + jnp.arange(s, dtype=jnp.int32)[None]
            out = lm.forward(params, tokens, cfg, self._step_ctx,
                             positions=positions, caches=req_caches)
            caches2 = mask_cache_padding(cfg, out.caches, plen)
            logits = jnp.take_along_axis(
                out.logits, (plen - start - 1)[:, None, None], axis=1)[:, 0]
            return self._pin_logits(logits), self._pin_caches(caches2)

        def spec_round(params, caches, logits0, key, plan, t1, draft,
                       temperature):
            """One speculative draft/verify round (serve/speculative.py).

            ``t1``: (S,) this round's first token — already sampled (by
            the PREVIOUS round's bonus sample, or from the claim logits
            on admission) and fed back as data, so the host-side drafter
            conditioned its ``draft`` (S, k) proposals on it.  One
            batched step-mode forward scores all k+1 round positions;
            acceptance is computed on device and only the final (S,)
            accepted lengths cross to the host scheduler (no per-token
            sync).  The cache commits the accepted prefix and rolls the
            rejected suffix back to a bit-identical never-drafted state
            (``cache_rollback``).

            The round ends with the bonus sample: the NEXT round's first
            token, drawn from the carry distribution with the first
            rejected draft banned — the exact residual of point-mass
            rejection sampling, so temperature > 0 stays
            distribution-preserving (and at temperature 0 a rejected
            draft is never the argmax, so banning it changes nothing).
            """
            s, k = draft.shape
            key, k1, k2 = jax.random.split(key, 3)
            toks = jnp.concatenate([t1.astype(jnp.int32)[:, None],
                                    draft.astype(jnp.int32)],
                                   axis=1)                    # (S, k+1)
            pos0 = caches["pos"]
            positions = (pos0[:, None]
                         + jnp.arange(k + 1, dtype=jnp.int32)[None])
            out = lm.forward(params, toks, cfg, self._step_ctx,
                             positions=positions, caches=caches, plan=plan)
            la = out.logits.astype(jnp.float32)               # (S, k+1, V)
            acc_d = accept_drafts(la[:, :-1], draft, k2, temperature)
            acc_len = 1 + acc_d.sum(axis=1).astype(jnp.int32) # in [1, k+1]
            # carry = the distribution after the last accepted token; for
            # a rejection at draft i it is la[:, i] — exactly the
            # distribution that rejected draft i, so banning that token
            # from the bonus sample realizes the residual
            carry = jnp.take_along_axis(
                la, (acc_len - 1)[:, None, None], axis=1)[:, 0]
            first_rej = jnp.take_along_axis(
                draft, jnp.minimum(acc_len - 1, k - 1)[:, None], axis=1)[:, 0]
            banned = jnp.where(acc_len > k, -1,
                               first_rej).astype(jnp.int32)
            t1_next = sample(mask_banned(carry, banned), k1, temperature)
            caches2 = cache_rollback(cfg, out.caches, pos0 + acc_len)
            # per-token logprobs under the raw (unmasked, untempered)
            # target distributions — the non-speculative loop's
            # convention; t1's distribution is ``logits0``, the carry
            # that produced it
            lp0 = jax.nn.log_softmax(logits0.astype(jnp.float32), axis=-1)
            lp_t1 = jnp.take_along_axis(
                lp0, t1.astype(jnp.int32)[:, None], axis=-1)[:, 0]
            lpd = jax.nn.log_softmax(la[:, :-1], axis=-1)
            lp_dr = jnp.take_along_axis(
                lpd, draft[..., None].astype(jnp.int32), axis=-1)[..., 0]
            lps = jnp.concatenate([lp_t1[:, None], lp_dr], axis=1)
            trace = None
            if self.collect_router_trace:
                # (moe_layers, S*(k+1), kr) row-major over (S, k+1) ->
                # (round_steps=k+1, moe_layers, S, kr), the layout
                # record_chunk / replay_spec_round consume
                tr = out.trace
                trace = tr.reshape(tr.shape[0], s, k + 1, tr.shape[-1]) \
                    .transpose(2, 0, 1, 3)
            ys = (toks, lps, trace, acc_len, t1_next)
            return (self._pin_logits(carry), self._pin_caches(caches2),
                    key, ys)

        self._prefill = prefill
        # the same decode body, wrapped twice: the donating loop is the
        # steady-state path (cache buffers reused in place); the
        # NON-donating twin runs the streaming fixpoint's speculative
        # attempts — a rejected attempt must leave the input caches
        # valid for the re-run, which donation would invalidate
        self._decode_loop = jax.jit(
            decode_loop, static_argnames=("max_new", "temperature"),
            donate_argnums=(1,))
        self._decode_loop_spec = jax.jit(
            decode_loop, static_argnames=("max_new", "temperature"))
        # spec rounds get the same two wrappings; the draft operand's
        # (S, k) shape keys the jit cache, so one compile serves every
        # round of a given (slots, spec_k) serve call
        self._spec_round = jax.jit(
            spec_round, static_argnames=("temperature",),
            donate_argnums=(1,))
        self._spec_round_nd = jax.jit(
            spec_round, static_argnames=("temperature",))
        self._claim = claim
        self._claim_paged = claim_paged
        self._reset_paged = reset_paged
        self._seed_prefix = seed_prefix
        self._prefill_suffix = prefill_suffix

    # -- compile accounting ------------------------------------------------
    @property
    def num_compiles(self) -> Dict[str, int]:
        """Compiled-variant counts of the two jitted entry points (-1 if
        the jax internal is unavailable) — the regression hook pinning
        'one bucket, one compile'."""
        def size(f):
            try:
                return int(f._cache_size())
            except Exception:
                return -1
        return {"prefill": size(self._prefill),
                "decode": size(self._decode_loop)}

    # -- offload wiring ----------------------------------------------------
    def attach_offload(self, stacks_by_layer: List[Dict],
                       policy: str = "ours",
                       cache_capacity: Optional[int] = None,
                       prefetch: bool = True, ep: Optional[int] = None):
        """Meter every generated token's expert fetches through per-layer
        host-side ``ExpertStore``s (LRU device cache + compensator bytes).

        ``ep`` (default: the engine mesh's expert-parallel degree)
        partitions each layer's store into per-shard sub-stores matching
        the device-side expert placement: each shard meters only its
        resident experts' wire bytes over its own device LRU, and the
        per-shard counters reduce into ``ServeStats`` (``shard_bytes``,
        ``offload_report['per_shard_bytes']``) and feed the bandwidth
        controller's ``budget_scope``."""
        from ..offload.store import make_expert_stores
        from ..offload.prefetch import LayerAheadPrefetcher
        cap = (self.scfg.cache_experts if cache_capacity is None
               else cache_capacity)
        self._stores = make_expert_stores(
            stacks_by_layer, ep=self.ep if ep is None else ep,
            cache_capacity=cap)
        self._offload_policy = policy
        if prefetch:
            self._prefetcher = LayerAheadPrefetcher(
                len(stacks_by_layer), self.cfg.moe.top_k)
        if self.scfg.control.enabled:
            # ServeConfig-driven controller: budgeted serving without a
            # separate attach_controller call (which can still override)
            self.attach_controller(self.scfg.control)
        if self.scfg.stream.enabled:
            self.attach_streaming()
        return self

    def attach_streaming(self, stream=None, backend=None) -> "ServeEngine":
        """Turn the metered offload into a real streamed data path.

        The MoE layers' serving stacks are pointer-swapped for
        fallback-initialized device *containers* (same pytree / shapes /
        dtypes — the jitted loops never recompile); an
        ``ExpertStreamEngine`` stages true expert payloads into them from
        pinned host images, driven by the stores' metering events, with a
        per-layer ring of async H2D copies for the prefetcher's
        layer-ahead predictions.  Decode runs optimistically on the
        current containers and blocks only on a true miss
        (``StreamConfig.miss_policy='block'``: stage + re-run until the
        routing is fully served, token-identical to all-resident;
        ``'degrade'``: accept the chunk served by the resident low-bit
        fallback and stage in the background).

        ``stream``: ``StreamConfig`` override (default ``scfg.stream``);
        ``backend``: transfer backend override (fault injection).
        Requires ``attach_offload`` on the LIVE serving stacks, the
        single-device path (store-level ``ep`` sharding still applies),
        and an 'ours'/'quant' fetch policy.
        """
        from ..offload.staging import ExpertStreamEngine
        stream = stream or self.scfg.stream
        if self._stores is None:
            raise ValueError("attach_offload must be called before "
                             "attach_streaming (the stream engine is "
                             "driven by its metered stores)")
        if self.mesh is not None:
            raise ValueError("streaming requires the single-device serving "
                             "path; expert-parallel byte accounting still "
                             "works via attach_offload(ep=...)")
        if not self.collect_router_trace:
            raise ValueError("streaming detects misses from the router "
                             "trace; collect_router_trace must be on")
        if self._offload_policy not in ("ours", "quant"):
            raise ValueError("streaming moves compressed containers; fetch "
                             f"policy {self._offload_policy!r} unsupported")
        moe_params = [lp["moe"] for seg in self.params["segments"]
                      for lp in seg
                      if isinstance(lp, dict) and isinstance(lp.get("moe"),
                                                             dict)
                      and "stacks" in lp["moe"]]
        if len(moe_params) != len(self._stores):
            raise ValueError(f"{len(moe_params)} compressed MoE layers in "
                             f"params vs {len(self._stores)} stores")
        for mp, store in zip(moe_params, self._stores):
            if mp["stacks"] is not store.stacks:
                raise ValueError("attach_offload was given stacks that are "
                                 "not the live serving stacks; streaming "
                                 "must stage into the containers the "
                                 "decode loop reads")
        self._stream = ExpertStreamEngine(self._stores, stream,
                                          policy=self._offload_policy,
                                          backend=backend)
        for li, mp in enumerate(moe_params):
            mp["stacks"] = self._stream.layer_containers(li)
        return self

    @property
    def stream(self):
        return self._stream

    def attach_controller(self, control: ControlConfig
                          ) -> "ServeEngine":
        """Close the loop from offload metering to restoration intensity.

        Requires ``attach_offload`` (the controller reads the stores'
        byte counters and derives its rank ladder from their stacks).
        With no budget set (``target_bytes_per_token == 0``) the plan
        stays pinned at the static ``top_n_restore`` / full-rank point
        and decode + metering are bit-identical to the uncontrolled path.
        """
        if self._stores is None:
            raise ValueError("attach_offload must be called before "
                             "attach_controller (it provides the metered "
                             "stores the controller feeds on)")
        self._controller = BandwidthController.from_stacks(
            [s.stacks for s in self._stores], self.cfg.moe.top_k, control,
            static_top_n=self.cfg.moe.quant.top_n_restore)
        return self

    @property
    def controller(self) -> Optional[BandwidthController]:
        return self._controller

    def _current_plan(self) -> Optional[ControllerPlan]:
        return self._controller.plan() if self._controller else None

    @staticmethod
    def _plan_device(plan: Optional[ControllerPlan]):
        return None if plan is None else jnp.asarray(plan.as_array())

    def _shard_totals(self) -> np.ndarray:
        """(ep,) cumulative wire bytes per expert-parallel shard link,
        reduced over layers (length 1 for unsharded stores)."""
        if not self._stores:
            return np.zeros((1,), np.int64)
        return sum(np.asarray(s.shard_totals, np.int64)
                   for s in self._stores)

    # -- mesh placement / sharding pins ------------------------------------
    def _pin_caches(self, caches):
        """Rule-derived sharding constraint on (traced) cache outputs —
        the same rules their initial placement uses, so every chunked
        call of the jitted entry points sees one fixed cache-sharding
        signature (one compile per bucket, no propagation churn)."""
        if self.mesh is None:
            return caches
        return tree_constraint(self.mesh, caches, self.pcfg,
                               CACHE_RULES + PARAM_RULES)

    def _logits_sharding(self, shape):
        """Rule-derived logits sharding (batch logical, rest replicated)
        — single definition shared by the output pin and the initial
        placement so the two can never diverge into a recompile."""
        from jax.sharding import NamedSharding
        from ..distributed.sharding import mesh_spec
        return NamedSharding(self.mesh, mesh_spec(
            self.mesh, ("batch",) + (None,) * (len(shape) - 1), shape,
            self.pcfg))

    def _pin_logits(self, logits):
        if self.mesh is None:
            return logits
        return jax.lax.with_sharding_constraint(
            logits, self._logits_sharding(logits.shape))

    def _place_replicated(self, x):
        """Commit a host-created array (RNG key, zeros logits) to the
        serving mesh replicated — an uncommitted single-device input
        would give the first chunked call a different sharding signature
        than the loop's own (mesh-sharded) outputs and cost one spurious
        recompile."""
        if self.mesh is None:
            return x
        from jax.sharding import NamedSharding, PartitionSpec
        return jax.device_put(x, NamedSharding(self.mesh, PartitionSpec()))

    def _make_caches(self, batch: int, cache_len: int):
        """Fresh caches, placed by the CACHE_RULES shardings when serving
        on a mesh (committed placement: the first jitted call already has
        the fixpoint input sharding)."""
        caches = init_caches(self.cfg, batch, max_len=cache_len,
                             dtype=self.cache_dtype)
        if self.mesh is not None:
            caches = jax.device_put(
                caches, tree_shardings(self.mesh,
                                       jax.eval_shape(lambda: caches),
                                       self.pcfg, CACHE_RULES + PARAM_RULES))
        return caches

    def _make_paged_caches(self, num_slots: int, num_pages: int,
                           page_size: int, max_blocks: int):
        caches = init_paged_caches(self.cfg, num_slots, num_pages,
                                   page_size, max_blocks,
                                   dtype=self.cache_dtype)
        if self.mesh is not None:
            caches = jax.device_put(
                caches, tree_shardings(self.mesh,
                                       jax.eval_shape(lambda: caches),
                                       self.pcfg, CACHE_RULES + PARAM_RULES))
        return caches

    def _meter_offload(self, trace: np.ndarray,
                       plan: Optional[ControllerPlan] = None
                       ) -> Dict[str, float]:
        """Feed decode routing (steps, layers, B, k) into the stores."""
        from ..offload.store import meter_decode_trace
        top_n = (self.cfg.moe.quant.top_n_restore if plan is None
                 else plan.top_n)
        return meter_decode_trace(
            self._stores, trace, policy=self._offload_policy,
            top_n=top_n,
            rank_caps=None if plan is None else plan.rank_cap,
            prefetcher=self._prefetcher)

    # -- prefill helpers ---------------------------------------------------
    def _pad_prompt(self, prompt_tokens: np.ndarray) -> np.ndarray:
        """Right-pad prompts to their length bucket (id 0; the padded cache
        slots are invalidated after prefill)."""
        b, plen = prompt_tokens.shape
        if not self._pad_prompts:
            return prompt_tokens
        lp = bucket_len(plen, PROMPT_BUCKET_MIN)
        if lp == plen:
            return prompt_tokens
        out = np.zeros((b, lp), np.int32)
        out[:, :plen] = prompt_tokens
        return out

    def _prefill_request(self, req: Request, cache_len: int):
        """(last-token logits (1, V), batch-1 prefilled cache) for one
        request, against a fresh cache of the serve run's bucket length."""
        toks = self._pad_prompt(np.asarray(req.tokens,
                                           np.int32).reshape(1, -1))
        plen = jnp.full((1,), req.prompt_len, jnp.int32)
        if self._stream is not None:
            return self._prefill_streamed(toks, plen, cache_len)
        caches = self._make_caches(1, cache_len)
        return self._prefill(self.params, caches, jnp.asarray(toks), plen)

    def _prefill_streamed(self, toks: np.ndarray, plen, cache_len: int):
        """Prefill under streaming: run optimistically on the current
        containers, stage every expert the prompt's routing touched that
        is not yet resident (at the static top_n, full rank), and re-run
        until the routing is fully served by true weights — so a streamed
        request's FIRST sampled token already matches the all-resident
        path.  Prefill always blocks on its stages (it is off the decode
        critical path); a stalled copy degrades the prefill after
        ``stall_timeout_s`` like any other miss."""
        eng = self._stream
        if self._prefill_traced is None:
            ctx = make_context(self.cfg, "prefill", quantized=self.quantized,
                               exact_capacity=True,
                               kernel_impl=self.kernel_impl, mesh=self.mesh,
                               pcfg=self.pcfg, collect_trace=True)

            @jax.jit
            def prefill_traced(params, caches, tokens, plen):
                out = lm.forward(params, tokens, self.cfg, ctx,
                                 caches=caches)
                caches = mask_cache_padding(self.cfg, out.caches, plen)
                logits = jnp.take_along_axis(
                    out.logits, (plen - 1)[:, None, None], axis=1)[:, 0]
                return (self._pin_logits(logits), self._pin_caches(caches),
                        out.trace)

            self._prefill_traced = prefill_traced
        top_n = (self.cfg.moe.quant.top_n_restore
                 if self.cfg.moe is not None else 0)
        b = toks.shape[0]
        lg = rc = None
        for _ in range(eng.cfg.max_reruns + 1):
            caches = self._make_caches(b, cache_len)
            lg, rc, tr = self._prefill_traced(self.params, caches,
                                              jnp.asarray(toks), plen)
            needs = eng.missing_for_forward_trace(np.asarray(tr), top_n)
            if not needs:
                return lg, rc
            unresolved = eng.demand_stage(needs)
            eng.reruns += 1
            if unresolved:
                break          # stalled copies: serve this prefill degraded
        return lg, rc

    def _admit_paged(self, req: Request, pool: PagePool, caches, slot: int,
                     slot_pages: Dict[int, List[int]], *, max_blocks: int,
                     page_size: int, ring_len: int, use_prefix: bool):
        """Admit one request into the paged cache.

        Maps a page list (shared prefix pages first, fresh pages after),
        runs prefill — full, or suffix-only over a prefix seeded straight
        from the shared physical pages — and returns ``(logits,
        req_caches, claim_operands)`` for ``_claim_paged``.  Host-side
        only; the device work is the prefill itself plus the claim the
        caller issues.
        """
        ps = page_size
        plen = req.prompt_len
        plen_pad = (bucket_len(plen, PROMPT_BUCKET_MIN)
                    if self._pad_prompts else plen)
        need = -(-(plen_pad + req.max_new + 1) // ps)
        shared: List[int] = []
        hashes: List[bytes] = []
        if use_prefix:
            hashes = prefix_page_hashes(
                np.asarray(req.tokens).reshape(-1).tolist(), ps)
            hit = pool.lookup(hashes)
            # keep at least the final prompt token in the suffix so the
            # suffix prefill yields the last-token logits decode starts
            # from
            shared = hit[:min(len(hit), (plen - 1) // ps)]
            # retain BEFORE alloc: alloc may LRU-evict parked pages, and
            # the matched run must not be its own victim
            pool.retain(shared)
        n_sh = len(shared)
        fresh = pool.alloc(need - n_sh)
        page_list = list(shared) + fresh
        pages = np.full((max_blocks,), -1, np.int32)
        pages[:need] = page_list
        write_mask = np.zeros((max_blocks,), bool)
        write_mask[n_sh:need] = True     # shared pages are read-only

        # request-cache length: page-aligned prompt capacity, raised to
        # the serve cache's ring length so local layers claim 1:1
        req_len = max(_round_up(plen_pad, ps), _round_up(ring_len, ps))
        start = n_sh * ps
        if start > 0:
            seed = np.full((max_blocks,), -1, np.int32)
            seed[:n_sh] = shared
            rc = self._seed_prefix(self._make_caches(1, req_len), caches,
                                   jnp.asarray(seed))
            suf = np.asarray(req.tokens, np.int32).reshape(-1)[start:]
            # pad the suffix to page granularity — never past req_len, so
            # padded steps cannot ring-wrap onto the seeded prefix
            spad = _round_up(len(suf), ps)
            toks = np.zeros((1, spad), np.int32)
            toks[0, :len(suf)] = suf
            lg, rc = self._prefill_suffix(
                self.params, rc, jnp.asarray(toks),
                jnp.full((1,), start, jnp.int32),
                jnp.full((1,), plen, jnp.int32))
            n_prefill = spad
        else:
            lg, rc = self._prefill_request(req, req_len)
            n_prefill = plen_pad
        if use_prefix:
            # publish every full prompt page (fresh ones get their
            # content from the claim below; register is first-writer-wins)
            for j in range(n_sh, plen // ps):
                pool.register(page_list[j], hashes[j])
        slot_pages[slot] = page_list
        return lg, rc, {"pages": jnp.asarray(pages),
                        "write_mask": jnp.asarray(write_mask),
                        "prefill_tokens": n_prefill}

    def _run_chunk(self, caches, logits, key, plan, steps: int, active):
        """One decode chunk under streaming.

        Warm steady state (``may_miss`` False) runs the donating loop
        untouched.  Otherwise: optimistic execution on the current
        containers through the NON-donating twin, then — on a true miss —
        either stage-and-re-run to a fixpoint (miss_policy 'block':
        accepted chunk is token-identical to all-resident) or accept the
        fallback-served chunk and stage asynchronously for later chunks
        ('degrade').  Returns ``((logits, caches, key, ys), degraded)``.
        """
        eng = self._stream
        eng.integrate_ready()
        top_ns, caps = eng.plan_vectors(
            len(self._stores), plan,
            self.cfg.moe.quant.top_n_restore if self.cfg.moe else 0)
        plan_dev = self._plan_device(plan)
        temp = self.scfg.temperature
        if not eng.may_miss(top_ns, caps):
            return self._decode_loop(self.params, caches, logits, key,
                                     plan_dev, steps, temp), 0
        out = needs = None
        for _ in range(eng.cfg.max_reruns + 1):
            out = self._decode_loop_spec(self.params, caches, logits, key,
                                         plan_dev, steps, temp)
            tr = np.asarray(out[3][2])
            needs = eng.missing_for_trace(tr, active, top_ns, caps)
            if not needs:
                return out, 0
            if eng.cfg.miss_policy == "degrade":
                eng.stage_async(needs)
                break
            unresolved = eng.demand_stage(needs)
            eng.reruns += 1
            if unresolved:
                bad = set(unresolved)
                needs = [n for n in needs if (n[0], n[1]) in bad]
                break
        degraded = eng.count_affected_tokens(
            np.asarray(out[3][2]), active,
            [(l, e) for (l, e, _w, _f) in needs])
        eng.degraded_tokens += degraded
        return out, degraded

    def _run_spec_round(self, caches, logits, key, plan, t1, draft,
                        active):
        """One speculative verify round under streaming — ``_run_chunk``
        for spec rounds.  The miss check covers the FULL round trace
        (which positions survive rejection is unknown before the verify
        runs, and under 'block' the accepted prefix must be
        token-identical to all-resident), and re-runs are exact: the
        same key and draft reproduce the same round."""
        eng = self._stream
        eng.integrate_ready()
        top_ns, caps = eng.plan_vectors(
            len(self._stores), plan,
            self.cfg.moe.quant.top_n_restore if self.cfg.moe else 0)
        plan_dev = self._plan_device(plan)
        temp = self.scfg.temperature
        if not eng.may_miss(top_ns, caps):
            return self._spec_round(self.params, caches, logits, key,
                                    plan_dev, t1, draft, temp), 0
        out = needs = None
        for _ in range(eng.cfg.max_reruns + 1):
            out = self._spec_round_nd(self.params, caches, logits, key,
                                      plan_dev, t1, draft, temp)
            tr = np.asarray(out[3][2])
            needs = eng.missing_for_trace(tr, active, top_ns, caps)
            if not needs:
                return out, 0
            if eng.cfg.miss_policy == "degrade":
                eng.stage_async(needs)
                break
            unresolved = eng.demand_stage(needs)
            eng.reruns += 1
            if unresolved:
                bad = set(unresolved)
                needs = [n for n in needs if (n[0], n[1]) in bad]
                break
        degraded = eng.count_affected_tokens(
            np.asarray(out[3][2]), active,
            [(l, e) for (l, e, _w, _f) in needs])
        eng.degraded_tokens += degraded
        return out, degraded

    # -- generation (one fixed batch) --------------------------------------
    def generate(self, prompt_tokens: np.ndarray, max_new: int = 32,
                 seed: int = 0) -> GenerationResult:
        cfg = self.cfg
        b, plen = prompt_tokens.shape
        padded = self._pad_prompt(np.asarray(prompt_tokens, np.int32))
        cache_len = bucket_len(padded.shape[1] + max_new + 1)
        plen_arr = jnp.full((b,), plen, jnp.int32)
        t0 = time.time()
        if self._stream is not None:
            logits, caches = self._prefill_streamed(padded, plen_arr,
                                                    cache_len)
        else:
            caches = self._make_caches(b, cache_len)
            logits, caches = self._prefill(
                self.params, caches, jnp.asarray(padded), plen_arr)
        logits.block_until_ready()
        t_prefill = time.time() - t0

        plan = self._current_plan()
        key = self._place_replicated(jax.random.key(seed))
        t1 = time.time()
        if self._stream is not None:
            (logits, caches, _key, ys), _deg = self._run_chunk(
                caches, logits, key, plan, max_new, np.ones((b,), bool))
        else:
            logits, caches, _key, ys = self._decode_loop(
                self.params, caches, logits, key,
                self._plan_device(plan), max_new, self.scfg.temperature)
        logits.block_until_ready()
        t_decode = time.time() - t1

        toks = np.asarray(ys[0]).T                    # (B, max_new)
        logprobs = np.asarray(ys[1]).T                # (B, max_new)
        trace = (np.asarray(ys[2])
                 if self.collect_router_trace and ys[2] is not None else None)
        report = None
        if trace is not None and self._stores:
            if self._stream is not None:
                # replay the accepted routing (ledgered stages are
                # consumed), then flush staged-but-unrouted copies as
                # wasted prefetch INSIDE the report window, so the report
                # covers every byte the chunk put on the link
                from ..offload.store import (offload_report,
                                             replay_decode_trace,
                                             snapshot_offload)
                top_n = (cfg.moe.quant.top_n_restore if plan is None
                         else plan.top_n)
                snap = snapshot_offload(self._stores, self._prefetcher)
                ntok, _sb = replay_decode_trace(
                    self._stores, trace, policy=self._offload_policy,
                    top_n=top_n,
                    rank_caps=None if plan is None else plan.rank_cap,
                    prefetcher=self._prefetcher)
                self._stream.flush_unclaimed()
                report = offload_report(self._stores, self._prefetcher,
                                        snap, ntok, self._offload_policy)
            else:
                report = self._meter_offload(trace, plan)
        if report is not None and self._controller is not None:
            self._controller.update(report["total_bytes"], report["tokens"],
                                    shard_bytes=report["per_shard_bytes"])
        return GenerationResult(
            toks, logprobs, t_prefill, t_decode, max_new,
            router_trace=trace, offload_report=report,
            stream_report=(self._stream.report()
                           if self._stream is not None else None))

    # -- continuous-batching serving ---------------------------------------
    def serve(self, requests: Iterable[Request], *,
              num_slots: Optional[int] = None, chunk: Optional[int] = None,
              seed: int = 0, page_size: Optional[int] = None,
              prefix_cache: Optional[bool] = None,
              pool_pages: Optional[int] = None,
              spec_k: Optional[int] = None, drafter=None) -> ServeStats:
        """Serve a request workload through the continuous-batching loop.

        One slot-indexed cache of ``num_slots`` rows and one compiled
        ``chunk``-step decode scan stay resident for the whole workload;
        between chunks the scheduler retires finished requests (EOS /
        max-token) and refills their slots from the arrival queue.
        Requests with future ``arrival_s`` wait in the queue (offered-load
        benchmarking); latencies are wall-clock from arrival.  Each step
        of the loop runs under a host span of ``ServeStats.timeline``
        (``serve/timeline.py``), on the profiler's clock too.

        ``page_size`` (default ``scfg.page_size``; 0 = off) switches the
        cache's global-attention layers to block-table paging: capacity
        is allocated in page quanta per request instead of one
        power-of-two bucket for the whole mix, block tables are traced
        data (still exactly one compiled decode signature), and
        ``prefix_cache`` refcount-shares the physical pages of common
        prompt prefixes so their prefill runs once.  ``pool_pages``
        overrides the allocatable pool size (excluding the trash page).

        With a bandwidth controller attached, each chunk decodes under
        the controller's current (moe_layers, 2) restoration plan (traced
        data — no recompile), the chunk's metered wire bytes feed
        ``controller.update`` at the chunk boundary, and the per-chunk
        plans come back as ``ServeStats.plan_trace``.

        ``spec_k`` (default ``scfg.spec.k``; 0 = off) switches the
        decode chunk for speculative draft/verify *rounds*: a drafter
        (``'ngram'`` | ``'model'`` | a reset_slot/observe/propose_all
        object; default from ``scfg.spec``) proposes ``spec_k`` tokens
        per slot, one batched verify pass scores all spec_k+1 round
        positions, rejection sampling commits a per-slot prefix
        (token-identical to the non-speculative loop at temperature 0),
        and the rejected cache suffix rolls back bit-exactly.  The
        verify trace warms the expert stores through a
        ``LookaheadPrefetcher`` — exact in-round routing rather than the
        layer-ahead guess — and ``ServeStats.spec_report`` carries the
        acceptance rate, lookahead accuracy, and wasted-speculation
        bytes.  Requires an all-'global' attention plan (recurrent /
        ring states cannot roll back rejected suffixes).
        """
        from ..offload.store import (offload_report, replay_decode_trace,
                                     replay_spec_round, snapshot_offload)
        from ..offload.prefetch import LookaheadPrefetcher
        cfg = self.cfg
        num_slots = num_slots or self.scfg.num_slots
        chunk = chunk or self.scfg.chunk_steps
        ps = self.scfg.page_size if page_size is None else page_size
        use_prefix = (self.scfg.prefix_cache if prefix_cache is None
                      else prefix_cache)
        paged = ps > 0
        spec_k = self.scfg.spec.k if spec_k is None else spec_k
        spec_on = spec_k > 0
        spec_pf = None
        if spec_on:
            if not self._pad_prompts or cfg.encoder is not None:
                raise ValueError("speculative decoding needs an all-'global' "
                                 "decoder-only attention plan: recurrent and "
                                 "local-ring states cannot roll back a "
                                 "rejected draft suffix")
            if drafter is None:
                drafter = self.scfg.spec.drafter
            if isinstance(drafter, str):
                drafter = make_drafter(
                    dataclasses.replace(self.scfg.spec, drafter=drafter,
                                        k=spec_k),
                    cfg, target_params=self.params,
                    target_quantized=self.quantized,
                    kernel_impl=self.kernel_impl)
            next_t1 = np.zeros((num_slots,), np.int32)
            adm_key = jax.random.key(seed + 1)   # admission bonus samples
            spec_drafted = spec_acc = 0
            chunk = spec_k + 1          # round length, for stats/reporting
            if self._stores:
                spec_pf = LookaheadPrefetcher(len(self._stores),
                                              cfg.moe.top_k)
        pf_used = spec_pf if spec_on else self._prefetcher
        reqs = list(requests)
        order = [r.uid for r in reqs]       # results in submission order
        reqs = sorted(reqs, key=lambda r: r.arrival_s)
        if not reqs:
            return ServeStats([], num_slots, chunk, 0.0, 0.0, 0.0, 0, 0,
                              timeline=Timeline())

        def padded_plen(r: Request) -> int:
            return (bucket_len(r.prompt_len, PROMPT_BUCKET_MIN)
                    if self._pad_prompts else r.prompt_len)

        pool = None
        slot_pages: Dict[int, List[int]] = {}
        if paged:
            if ps & (ps - 1):
                raise ValueError(f"page_size must be a power of two: {ps}")
            if use_prefix and not self._pad_prompts:
                raise ValueError("prefix_cache needs an all-global "
                                 "attention plan (recurrent / ring states "
                                 "cannot seed from reused pages)")
            if use_prefix and self._stream is not None:
                raise ValueError("prefix_cache under expert streaming is "
                                 "unsupported (suffix prefill bypasses the "
                                 "stage-and-rerun fixpoint)")
            # per-request page need; +1 matches the contiguous headroom
            needs = sorted((-(-(padded_plen(r) + r.max_new + 1) // ps)
                            for r in reqs), reverse=True)
            max_blocks = needs[0]
            # pool: the num_slots largest concurrent residents (plus the
            # reserved trash page) — strictly less HBM than bucketing
            # every slot to the global worst case
            n_alloc = (pool_pages if pool_pages
                       else min(sum(needs[:num_slots]),
                                num_slots * max_blocks))
            caches = self._make_paged_caches(num_slots, 1 + n_alloc, ps,
                                             max_blocks)
            pool = PagePool(1 + n_alloc, ps)
            specs = layer_specs(cfg)
            ring_len = (min(cfg.window_size, max_blocks * ps)
                        if any(s.mixer == "local" for s in specs) else 0)
        else:
            # spec_k extra headroom: a verify pass may append up to spec_k
            # rejected positions past a slot's final token, and the ring
            # must absorb them without wrapping onto live entries (the
            # rollback can only restore what the write didn't destroy)
            cache_len = bucket_len(
                max(bucket_len(r.prompt_len, PROMPT_BUCKET_MIN) + r.max_new
                    for r in reqs) + 1 + (spec_k if spec_on else 0))
            caches = self._make_caches(num_slots, cache_len)
        cache_hbm = int(sum(x.nbytes for x in jax.tree.leaves(caches)))
        self._page_pool = pool              # test/introspection handle
        sched = Scheduler(num_slots)
        for r in reqs:
            sched.submit(r)

        key = self._place_replicated(jax.random.key(seed))
        logits = None
        top_n = cfg.moe.quant.top_n_restore if cfg.moe is not None else 1
        snap = (snapshot_offload(self._stores, pf_used)
                if self._stores else None)
        traces: List[np.ndarray] = []
        plans: List[np.ndarray] = []
        chunks = generated = metered_tokens = prefill_tok = 0
        t0 = time.perf_counter()
        tl = Timeline(t0)
        while sched.has_work():
            with tl.span("admit"):
                now = time.perf_counter() - t0
                admits = sched.admit(now)
            if not admits and sched.num_active == 0:
                # idle: nothing resident, next request hasn't arrived yet
                # — sleep the exact gap once (the old 0.25 s cap spun the
                # loop awake repeatedly under sparse offered load)
                with tl.span("arrival_wait"):
                    gap = max(sched.next_arrival() - now, 0.0)
                    time.sleep(gap + 1e-4)
                continue
            for slot, req in admits:
                tl.count("admissions")
                with tl.span("prefill", uid=req.uid,
                             prompt_len=req.prompt_len,
                             bucket=padded_plen(req)):
                    if paged:
                        lg, rc, claim_args = self._admit_paged(
                            req, pool, caches, slot, slot_pages,
                            max_blocks=max_blocks, page_size=ps,
                            ring_len=ring_len, use_prefix=use_prefix)
                        prefill_tok += claim_args.pop("prefill_tokens")
                    else:
                        lg, rc = self._prefill_request(req, cache_len)
                        claim_args = None
                        prefill_tok += padded_plen(req)
                with tl.span("claim", uid=req.uid, slot=slot):
                    if logits is None:
                        logits = jnp.zeros((num_slots,) + lg.shape[1:],
                                           lg.dtype)
                        if self.mesh is not None:
                            logits = jax.device_put(
                                logits, self._logits_sharding(logits.shape))
                    if paged:
                        caches, logits = self._claim_paged(
                            caches, rc, logits, lg, jnp.int32(slot),
                            claim_args["pages"], claim_args["write_mask"])
                    else:
                        caches, logits = self._claim(caches, rc, logits, lg,
                                                     jnp.int32(slot))
                if spec_on:
                    with tl.span("draft", uid=req.uid):
                        # sample the new tenant's first token from its
                        # claim logits now (the non-speculative loop does
                        # this as its first scan step), so the drafter can
                        # condition its first proposals on it
                        adm_key, k1 = jax.random.split(adm_key)
                        t1_new = int(np.asarray(
                            sample(lg, k1, self.scfg.temperature))[0])
                        next_t1[slot] = t1_new
                        # rebind the slot's draft history to the new
                        # tenant; no residual carries across requests
                        drafter.reset_slot(slot, np.asarray(req.tokens))
                        drafter.observe(slot, np.asarray([t1_new]))

            with tl.span("plan"):
                plan = self._current_plan()
                plan_dev = self._plan_device(plan)
                if plan is not None:
                    plans.append(plan.as_array())
            # the chunk's decode start: per-step stamps interpolate from
            # here (record_chunk's t_start)
            td = time.perf_counter()
            if spec_on:
                with tl.span("draft"):
                    draft_dev = jnp.asarray(
                        drafter.propose_all(num_slots, spec_k), jnp.int32)
                    t1_dev = jnp.asarray(next_t1)
            with tl.span("decode.dispatch"):
                if spec_on and self._stream is not None:
                    (logits, caches, key, ys), _deg = self._run_spec_round(
                        caches, logits, key, plan, t1_dev, draft_dev,
                        sched.active_mask())
                elif spec_on:
                    logits, caches, key, ys = self._spec_round(
                        self.params, caches, logits, key, plan_dev, t1_dev,
                        draft_dev, self.scfg.temperature)
                elif self._stream is not None:
                    (logits, caches, key, ys), _deg = self._run_chunk(
                        caches, logits, key, plan, chunk,
                        sched.active_mask())
                else:
                    logits, caches, key, ys = self._decode_loop(
                        self.params, caches, logits, key, plan_dev, chunk,
                        self.scfg.temperature)
            with tl.span("decode.sync"):
                logits.block_until_ready()

            with tl.span("pull"):
                if spec_on:
                    # round outputs are already slot-major (S, k+1);
                    # acc_len crosses to the host HERE, once per round, as
                    # one (S,) array — never a per-token sync inside the
                    # jitted round
                    toks = np.asarray(ys[0])
                    lps = np.asarray(ys[1])
                    acc_len = np.asarray(ys[3])
                    next_t1 = np.array(ys[4])   # writable: admits reset
                    pulled = [toks, lps, acc_len, next_t1]
                else:
                    toks = np.asarray(ys[0]).T                # (S, chunk)
                    lps = np.asarray(ys[1]).T
                    acc_len = None
                    pulled = [toks, lps]
                tr = (np.asarray(ys[2]) if self.collect_router_trace
                      else None)
                if tr is not None:
                    pulled.append(tr)
                tl.count("pulled_bytes", sum(a.nbytes for a in pulled))

            with tl.span("record"):
                chunks += 1
                uid_map = sched.uid_by_slot()
                live_mask = sched.active_mask()
                tl.count("live_slot_steps",
                         int(live_mask.sum()) * toks.shape[1])
                now = time.perf_counter() - t0
                # per-step times interpolate from the chunk's decode
                # start, so first-token stamps land on their step instead
                # of quantizing to the chunk boundary
                accepted = sched.record_chunk(toks, lps, tr, now,
                                              t_start=td - t0,
                                              valid_len=acc_len)
                generated += int(accepted.sum())
                if spec_on:
                    live_after = sched.uid_by_slot()
                    for i in uid_map:
                        spec_drafted += spec_k
                        spec_acc += int(acc_len[i]) - 1
                        # toks[i, 0] (the round's t1) was observed when
                        # it was sampled — at admission or as the
                        # previous round's bonus token — so only the
                        # accepted draft suffix is new to the drafter
                        n_new = int(accepted[:, i].sum())
                        if n_new > 1:
                            drafter.observe(i, toks[i, 1:n_new])
                        if live_after.get(i) == uid_map[i]:
                            # slot survives the round: the bonus token it
                            # will commit next round conditions proposals
                            drafter.observe(i, np.asarray([next_t1[i]]))
                if tr is not None:
                    masked = np.where(accepted[:, None, :, None], tr,
                                      -1).astype(tr.dtype)
                    traces.append(masked)
            if paged:
                with tl.span("page_reset"):
                    live = sched.uid_by_slot()
                    for slot_i, uid in uid_map.items():
                        if live.get(slot_i) != uid:   # retired this chunk
                            pool.release(slot_pages.pop(slot_i))
                            # unmap before the next chunk decodes: the
                            # freed pages may be re-allocated, and a dead
                            # slot keeps scan-stepping (its writes must
                            # hit the trash page, not the new tenant)
                            caches = self._reset_paged(caches,
                                                       jnp.int32(slot_i))
            if tr is not None and self._stores:
                with tl.span("metering"):
                    before = sum(s.total_bytes for s in self._stores)
                    shard_before = self._shard_totals()
                    if spec_on:
                        # lookahead warms cover every LIVE round position
                        # (rejected ones included — that is the wasted
                        # speculation the report attributes); demand
                        # metering stays accepted-only
                        full = np.where(live_mask[None, None, :, None], tr,
                                        -1).astype(tr.dtype)
                        ntok, slot_bytes, _ohb = replay_spec_round(
                            self._stores, full, accepted,
                            policy=self._offload_policy,
                            top_n=top_n if plan is None else plan.top_n,
                            rank_caps=(None if plan is None
                                       else plan.rank_cap),
                            lookahead=spec_pf)
                    else:
                        ntok, slot_bytes = replay_decode_trace(
                            self._stores, masked,
                            policy=self._offload_policy,
                            top_n=top_n if plan is None else plan.top_n,
                            rank_caps=(None if plan is None
                                       else plan.rank_cap),
                            prefetcher=self._prefetcher)
                    metered_tokens += ntok
                    sched.add_slot_bytes(slot_bytes, uid_map)
                if self._stream is not None:
                    with tl.span("controller"):
                        # staged copies the accepted routing never
                        # touched become wasted prefetch THIS chunk, so
                        # the controller's `moved` sees every byte the
                        # chunk put on the link
                        self._stream.flush_unclaimed()
                if self._controller is not None:
                    with tl.span("controller"):
                        # chunk boundary: the chunk's wire bytes (demand
                        # + compensator + prefetch) close the control
                        # loop; per-shard deltas feed the per_shard
                        # budget scope
                        moved = sum(s.total_bytes
                                    for s in self._stores) - before
                        self._controller.update(
                            moved, ntok,
                            shard_bytes=self._shard_totals() - shard_before)

        total_s = time.perf_counter() - t0
        if pool is not None:
            pool.check_leaks()     # every retire released its pages
        report = (offload_report(self._stores, pf_used, snap,
                                 metered_tokens, self._offload_policy)
                  if snap is not None and traces else None)
        spec_report = None
        if spec_on:
            spec_report = {
                "spec_k": spec_k,
                "drafter": type(drafter).__name__,
                "rounds": chunks,
                "drafted_tokens": spec_drafted,
                "accepted_draft_tokens": spec_acc,
                # verify-pass acceptance (EOS / max_new scheduler trims
                # excluded): the drafter-quality number
                "acceptance_rate": spec_acc / max(spec_drafted, 1),
                "lookahead_accuracy": (spec_pf.stats.accuracy
                                       if spec_pf is not None else None),
                "lookahead_prefetch_bytes": (spec_pf.bytes_issued
                                             if spec_pf is not None else 0),
                "draft_overhead_bytes": (spec_pf.bytes_wasted
                                         if spec_pf is not None else 0),
            }
        by_uid = {res.uid: res for res in sched.finished}
        results = [by_uid[u] for u in order]
        return ServeStats(results, num_slots, chunk, total_s,
                          tl.total_s("prefill", "claim"),
                          tl.total_s("decode.dispatch", "decode.sync"),
                          chunks, generated, timeline=tl,
                          cache_hbm_bytes=cache_hbm,
                          prefill_tokens=prefill_tok,
                          page_report=(pool.report() if pool is not None
                                       else None),
                          spec_report=spec_report,
                          offload_report=report,
                          router_trace=(np.concatenate(traces)
                                        if traces else None),
                          plan_trace=(np.stack(plans) if plans else None),
                          shard_bytes=(np.asarray(report["per_shard_bytes"],
                                                  np.int64)
                                       if report is not None else None),
                          stream_report=(self._stream.report()
                                         if self._stream is not None
                                         else None))

    def generate_many(self, prompts: Sequence[np.ndarray],
                      max_new: int = 32, *,
                      eos_id: Optional[int] = None,
                      num_slots: Optional[int] = None,
                      chunk: Optional[int] = None,
                      seed: int = 0) -> ServeStats:
        """Serve a list of ragged prompts (all arriving at t=0) through the
        continuous-batching loop; results come back in submission order."""
        reqs = [Request(uid=i, tokens=np.asarray(p, np.int32).reshape(-1),
                        max_new=max_new, eos_id=eos_id)
                for i, p in enumerate(prompts)]
        return self.serve(reqs, num_slots=num_slots, chunk=chunk, seed=seed)

    def score(self, tokens: np.ndarray) -> float:
        """Mean next-token NLL (perplexity proxy) under the serving path."""
        ctx = make_context(self.cfg, "train", quantized=self.quantized,
                           exact_capacity=True,
                           kernel_impl=self.kernel_impl)
        out = lm.forward(self.params, jnp.asarray(tokens), self.cfg, ctx)
        logits = out.logits[:, :-1].astype(jnp.float32)
        tgt = jnp.asarray(tokens)[:, 1:]
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        sel = jnp.take_along_axis(logits, tgt[..., None], axis=-1)[..., 0]
        return float(jnp.mean(lse - sel))


@functools.lru_cache(maxsize=64)
def _trace_forward(cfg: ModelConfig, quantized: bool,
                   kernel_impl: Optional[str]):
    """One jitted trace-collecting forward per (cfg, quantized, impl) —
    re-jitting a fresh lambda per call would recompile every time."""
    ctx = make_context(cfg, "train", quantized=quantized,
                       exact_capacity=True, collect_trace=True,
                       kernel_impl=kernel_impl)
    return jax.jit(lambda p, t: lm.forward(p, t, cfg, ctx).trace)


def router_trace(cfg: ModelConfig, params, tokens: np.ndarray,
                 quantized: bool = False,
                 kernel_impl: Optional[str] = None) -> np.ndarray:
    """Export per-token routing decisions (tokens, moe_layers, k).

    Runs the jitted forward pass with ``collect_trace`` — the trace is a
    first-class model output, so this works under jit/scan with no
    ``disable_jit`` or ``moe.route`` hook.  The compiled function is
    cached per (cfg, quantized, kernel_impl), so repeated exports reuse
    one executable instead of recompiling a fresh lambda per call.
    """
    fn = _trace_forward(cfg, quantized, kernel_impl)
    out = fn(params, jnp.asarray(tokens))
    # (moe_layers, T, k) -> (T, layers, k)
    return np.asarray(out).transpose(1, 0, 2)
