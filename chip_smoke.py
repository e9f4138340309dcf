#!/usr/bin/env python3
"""Bring-up smoke of the serving main path on one TPU chip.

    python3 chip_smoke.py [--layers N] [--seed S]
    python3 chip_smoke.py --four-chips

Run from the checkout root, on a machine with a TPU.  One process drives
every phase; nothing falls back to the CPU, the Pallas interpreter or the
pure-jnp reference path.  Model: Mixtral-8x7B at its published widths
(d_model 4096, 32/8 heads of 128, 8 experts top-2 of width 14336, vocab
32000), depth cut to ``--layers``, bf16 weights drawn from ``--seed`` and
compressed the paper's way (2-bit HQQ, kurtosis-allocated rank budget 32,
top-n 1) one layer at a time.

Phases (any failure exits non-zero before the result line):

1. the device is a TPU and the kernel dispatch resolves to ``pallas``;
2. build + compress the model through ``launch/serve.py``'s own
   ``build_model``/``build_engine`` and serve 4 scheduled requests (2
   slots, chunk 4, prompts of 32-64 tokens, 16 new tokens) with offload
   metering attached;
3. the compiled decode program contains the fused kernel's Mosaic custom
   call (``fused_expert_b2``) for every MoE projection;
4. one MoE layer on the chip: ``impl="pallas"`` against ``impl="ref"``
   on the same weights and input, within ``PARITY_TOL``;
5. the same requests streamed (``--stream --stream-miss block``): tokens
   identical to phase 2, metered bytes equal to observed copy bytes.

``--four-chips`` runs only the expert-parallel check: the same model
served with ``--mesh ep=4``, each layer placed on the mesh as soon as
it is compressed, against the ep=1 engine, tokens identical at
temperature 0, with per-shard link bytes.

The last line of standard output is one JSON object naming the device;
it is printed only when every phase passed.  Tokens/s printed on the way
are smoke figures, not metrics.
"""
import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

# Full-width depth.  A layer holds 0.68 GiB on the device compressed
# and 0.48 GiB go to embedding and head; the streamed phase adds a
# 0.59 GiB container copy of each layer's experts, the reference pass of
# phase 4 5.3 GiB of scratch.  The build draws one layer (2.7 GiB of
# bf16, no float32 temporaries) at a time, so the streamed phase sets
# the peak: about 13.2 GiB of the chip's 15.7 at 10 layers and 14.5 at
# 11, which leaves too little for the programs' own buffers (PERF.md,
# "Cells").
DEFAULT_LAYERS = 10

# Relative Frobenius gap allowed between the fused kernel and the
# reference on one MoE layer.  Both paths dequantize the same codes with
# the same scales in float32 and hand the matrix unit the same operands,
# so they differ only by the order of float32 accumulation and the
# rounding of the intermediate activation: 4.5e-5 on a v5e.  Dropping
# the compensators moves the layer by 5e-2; scales off by one percent
# would move it by about 1e-2.  1e-3 sits 20x above the one and 10x
# below the others.  Phase 4 also prints how far default-precision
# float32 matmuls stand from full precision: the gap the two paths would
# show if they rounded their operands differently.
PARITY_TOL = 1e-3


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


def check(cond: bool, msg: str) -> None:
    if not cond:
        fail(msg)


class CompileClock:
    """Seconds XLA spends compiling, from JAX's own monitoring events."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration


class RecordArgs:
    """Wraps a jitted function and keeps the abstract arguments of its
    first call, so the very program that ran can be compiled again and
    read."""

    def __init__(self, fn):
        self.fn, self.args = fn, None

    def __call__(self, *args, **kw):
        if self.args is None:
            import jax

            def spec(x):
                if isinstance(x, jax.Array):
                    return jax.ShapeDtypeStruct(x.shape, x.dtype,
                                                sharding=x.sharding)
                return x
            self.args = jax.tree.map(spec, (args, kw))
        return self.fn(*args, **kw)

    def compiled_text(self) -> str:
        args, kw = self.args
        return self.fn.lower(*args, **kw).compile().as_text()


def serve_args(layers: int, seed: int, *extra: str):
    from repro.launch.serve import build_parser
    return build_parser().parse_args(
        ["--arch", "mixtral-8x7b", "--full-config", "--layers", str(layers),
         "--offload", "--requests", "4", "--slots", "2", "--chunk", "4",
         "--prompt-len", "64", "--max-new", "16", "--seed", str(seed),
         *extra])


def memory(device, key: str) -> int:
    return int(device.memory_stats()[key])


def tree_bytes(tree) -> int:
    import jax
    return int(sum(x.nbytes for x in jax.tree.leaves(tree)))


def serve(eng, args):
    from repro.launch.serve import workload
    t = time.perf_counter()
    stats = eng.serve(workload(args, eng.cfg), num_slots=args.slots,
                      chunk=args.chunk, seed=args.seed)
    wall = time.perf_counter() - t
    check(len(stats.results) == args.requests,
          f"{len(stats.results)} of {args.requests} requests finished")
    for r in stats.results:
        check(len(r.tokens) == args.max_new,
              f"request {r.uid} generated {len(r.tokens)} tokens")
        check(bool(((r.tokens >= 0) & (r.tokens < eng.cfg.vocab_size))
                   .all()), f"request {r.uid}: token id out of range")
        import numpy as np
        check(bool(np.isfinite(r.logprobs).all()),
              f"request {r.uid}: non-finite log-probs")
    return stats, wall


def tokens_of(stats):
    import numpy as np
    return np.stack([r.tokens for r in stats.results])


def moe_parity(eng, seed: int):
    """Phase 4: one MoE layer, fused kernel vs reference, same input.

    Returns the relative gaps to the reference at default matmul
    precision of: the fused kernel, the reference at full precision, and
    the reference with no compensator."""
    import functools

    import jax
    import jax.numpy as jnp
    from repro.models.moe import moe_apply

    moe = next(lp["moe"] for seg in eng.params["segments"] for lp in seg
               if "moe" in lp)
    mcfg = eng.cfg.moe
    x2 = jax.random.normal(jax.random.key(seed + 7), (8, eng.cfg.d_model),
                           jnp.float32)

    @functools.partial(jax.jit, static_argnames="impl")
    def layer(x, moe, plan, impl):
        return moe_apply(x, moe, mcfg, quantized=True, impl=impl, plan=plan,
                         exact_capacity=True)[0]

    y_ref = layer(x2, moe, None, "ref")
    y_pl = layer(x2, moe, None, "pallas")
    with jax.default_matmul_precision("highest"):
        y_full = layer(x2, moe, None, "ref")
    pad = max(s.pad_rank for s in moe["stacks"].values())
    y_nocomp = layer(x2, moe, jnp.asarray([0, pad], jnp.int32), "ref")
    norm = float(jnp.linalg.norm(y_ref))
    check(norm > 0 and bool(jnp.isfinite(y_pl).all()),
          "MoE output is zero or non-finite")
    return tuple(float(jnp.linalg.norm(y - y_ref)) / norm
                 for y in (y_pl, y_full, y_nocomp))


def one_chip(opts) -> None:
    import jax
    import numpy as np
    from repro.kernels.ops import resolve_impl
    from repro.launch.serve import build_engine, build_model

    dev = jax.devices()[0]
    impl = resolve_impl(None)
    print(f"[1] device {dev.platform} {dev.device_kind} x"
          f"{len(jax.devices())}; kernel impl {impl}")
    check(impl == "pallas", f"kernel dispatch resolved to {impl!r}, "
          f"not 'pallas' (is REPRO_KERNEL_IMPL set?)")

    clock = CompileClock()
    args = serve_args(opts.layers, opts.seed)
    t = time.perf_counter()
    model = build_model(args)
    build_s = time.perf_counter() - t
    cfg, params, stacks = model
    m = cfg.moe
    print(f"[2] {cfg.name}: {cfg.num_layers} layers (depth cut from 32), "
          f"d_model {cfg.d_model}, heads {cfg.num_heads}/{cfg.num_kv_heads}"
          f"x{cfg.head_dim}, {m.num_experts} experts top-{m.top_k} "
          f"d_expert {m.d_expert}, vocab {cfg.vocab_size}; "
          f"{m.quant.bits}-bit, rank budget {m.quant.rank_budget}, "
          f"top-n {m.quant.top_n_restore}")
    print(f"    params on device {tree_bytes(params) / 2**30:.3f} GiB "
          f"(expert stacks {tree_bytes(stacks) / 2**30:.3f} GiB); "
          f"built + compressed in {build_s:.1f}s "
          f"({build_s / cfg.num_layers:.1f}s a layer); device memory in use "
          f"{memory(dev, 'bytes_in_use') / 2**30:.3f} GiB, peak so far "
          f"{memory(dev, 'peak_bytes_in_use') / 2**30:.3f} GiB")
    eng = build_engine(args, model)
    eng._decode_loop = rec = RecordArgs(eng._decode_loop)
    c0 = clock.seconds
    stats, cold_s = serve(eng, args)
    compile_s = clock.seconds - c0
    stats2, warm_s = serve(eng, args)
    toks = tokens_of(stats)
    check(np.array_equal(toks, tokens_of(stats2)),
          "a second serve of the same requests gave other tokens")
    rep = stats2.offload_report
    print(f"    served {args.requests} requests x {args.max_new} tokens: "
          f"cold {cold_s:.1f}s (XLA compile {compile_s:.1f}s), warm "
          f"{warm_s:.2f}s = {stats2.tokens_per_s:.1f} tok/s (smoke figure, "
          f"not a metric); offload {rep['bytes_per_token'] / 2**20:.2f} "
          f"MiB/token metered")

    text = rec.compiled_text()
    calls = [ln for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln
             and "fused_expert_b2" in ln]
    want = 3 * len(stacks)
    print(f"[3] decode program: {len(calls)} fused_expert_b2 Mosaic custom "
          f"calls (want >= {want}: w1, w3, gated w2 per MoE layer)")
    check(len(calls) >= want, "the compiled decode program lacks the "
          "fused kernel's custom calls")

    err, full, comp = moe_parity(eng, opts.seed)
    print(f"[4] MoE layer 0, pallas vs ref: relative error {err:.3e} "
          f"(tolerance {PARITY_TOL:.0e}); ref at full matmul precision "
          f"vs default: {full:.3e}; dropping the compensators moves the "
          f"reference by {comp:.3e}; peak device memory so far "
          f"{memory(dev, 'peak_bytes_in_use') / 2**30:.3f} GiB")
    check(err <= PARITY_TOL, f"pallas vs ref error {err:.3e} > "
          f"{PARITY_TOL:.0e}")

    sargs = serve_args(opts.layers, opts.seed, "--stream",
                       "--stream-miss", "block")
    seng = build_engine(sargs, model)
    sstats, stream_s = serve(seng, sargs)
    sr = sstats.stream_report
    same = np.array_equal(tokens_of(sstats), toks)
    print(f"[5] streamed (block): tokens identical to resident: {same}; "
          f"{sr['observed_copies']} copies, "
          f"{sr['observed_copy_bytes']} B observed == "
          f"{sr['metered_bytes']} B metered; {sr['stalls']} stalls, "
          f"{sr['reruns']} re-runs, {sr['degraded_tokens']} degraded "
          f"tokens; {stream_s:.1f}s")
    check(same, "streamed tokens differ from resident tokens")
    check(sr["observed_copy_bytes"] == sr["metered_bytes"],
          "metered bytes differ from observed copy bytes")
    check(sr["degraded_tokens"] == 0, "block policy served degraded tokens")

    print(f"    peak device memory "
          f"{memory(dev, 'peak_bytes_in_use') / 2**30:.3f} GiB of "
          f"{memory(dev, 'bytes_limit') / 2**30:.3f} GiB")


def four_chips(opts) -> None:
    import jax
    import numpy as np
    from repro.launch.serve import build_engine, build_model

    devs = jax.devices()
    check(len(devs) >= 4, f"--four-chips needs 4 devices, found "
          f"{len(devs)}")
    args4 = serve_args(opts.layers, opts.seed, "--mesh", "ep=4")
    from repro.launch.serve import serve_mesh
    mesh = serve_mesh(args4)
    t = time.perf_counter()
    cfg, params4, stacks4 = model4 = build_model(args4, mesh)
    whole = tree_bytes(params4)
    in_use = [memory(d, "bytes_in_use") for d in devs[:4]]
    print(f"[ep] {cfg.name}: {cfg.num_layers} layers built + compressed "
          f"onto ep=4 in {time.perf_counter() - t:.1f}s; whole model "
          f"{whole / 2**30:.3f} GiB, per device in use "
          f"{[round(b / 2**30, 3) for b in in_use]} GiB")
    check(in_use[0] < 0.5 * whole, "device 0 holds most of the model: it "
          "was not placed layer by layer")
    e = cfg.moe.num_experts
    for li, layer in enumerate(stacks4):
        for name, st in layer.items():
            shards = st.planes[0].addressable_shards
            held = sorted((s.device.id, s.data.shape[0]) for s in shards)
            check(len({d for d, _ in held}) == 4
                  and all(n == e // 4 for _, n in held),
                  f"layer {li} {name}: expert shards {held}")
    print(f"     every MoE projection holds {e // 4} experts per chip")

    eng4 = build_engine(args4, model4)
    stats4, s4 = serve(eng4, args4)
    rep = stats4.offload_report
    print(f"     ep=4 served in {s4:.1f}s; per-shard link bytes "
          f"{list(rep['per_shard_bytes'])}")

    args1 = serve_args(opts.layers, opts.seed)
    params1 = jax.device_put(params4, devs[0])
    stacks1 = [lp["moe"]["stacks"] for seg in params1["segments"]
               for lp in seg if "moe" in lp]
    eng1 = build_engine(args1, (cfg, params1, stacks1))
    stats1, s1 = serve(eng1, args1)
    same = np.array_equal(tokens_of(stats4), tokens_of(stats1))
    ep1 = {r.uid: r for r in stats1.results}
    for r4 in stats4.results:
        r1 = ep1[r4.uid]
        diff = np.flatnonzero(r4.tokens != r1.tokens)
        n = int(diff[0]) if diff.size else len(r1.tokens)
        gap = float(np.abs(r4.logprobs[:n] - r1.logprobs[:n]).max(
            initial=0.0))
        print(f"     request {r4.uid}: "
              + (f"first differs at token {n}" if diff.size
                 else "identical")
              + f"; largest log-prob gap before that {gap:.3e}")
    print(f"     ep=1 served in {s1:.1f}s; ep=4 tokens identical to ep=1: "
          f"{same}; total bytes ep=4 {int(sum(rep['per_shard_bytes']))} "
          f"vs ep=1 {int(sum(stats1.offload_report['per_shard_bytes']))}")
    check(same, "ep=4 tokens differ from ep=1")


def main() -> None:
    ap = argparse.ArgumentParser(
        description="bring-up smoke of the serving main path on a TPU")
    ap.add_argument("--layers", type=int, default=DEFAULT_LAYERS,
                    help="depth of the full-width model")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the ep=4 vs ep=1 check on four chips")
    opts = ap.parse_args()
    check(opts.layers >= 2, "--layers must be at least 2")
    if not (ROOT / "src" / "repro").is_dir():
        fail(f"no repro package under {ROOT / 'src'}; run this script "
             f"from a checkout of the repository")
    sys.path.insert(0, str(ROOT / "src"))
    from repro.paths import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}")

    import jax
    devs = jax.devices()
    dev = devs[0]
    check(dev.platform == "tpu", f"no TPU: JAX sees {dev.platform} "
          f"devices only")
    if opts.four_chips:
        four_chips(opts)
    else:
        one_chip(opts)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
