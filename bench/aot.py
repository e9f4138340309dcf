#!/usr/bin/env python3
"""Compile a cell's serving programs for a described TPU v5e, with no
chip, and print their memory: the rehearsal that fixes a cell's depth
and slot count before it goes to the chip.

    JAX_PLATFORMS=cpu REPRO_KERNEL_IMPL=pallas \
        python3 bench/aot.py --workload <name> [--layers N] [--slots S]

Prints, for the decode chunk and for the prefill of each prompt bucket
the cell's traffic fills, the bytes of arguments, outputs and
temporaries, and the number of fused expert kernel calls.  A compile
that passes here is not a chip run.
"""
import argparse
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--layers", type=int, default=0)
    ap.add_argument("--slots", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--prefill", default="max",
                    help="'max' (largest bucket only), 'all' or 'none'")
    a = ap.parse_args()
    bench, cell, spec, traffic, _ = run.cell_files(a.workload)
    run.prepare()
    import jax
    # a compile for a described chip cannot be read back without one
    jax.config.update("jax_enable_compilation_cache", False)
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    import model as bmodel
    import workload as wl
    from repro.models.transformer import init_caches
    from repro.serve.engine import ServeEngine, bucket_len

    if a.layers:
        spec["config"]["num_hidden_layers"] = a.layers
    slots = a.slots or spec["serve"]["slots"]
    chunk = spec["serve"]["chunk"]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def place(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one), tree)

    box = {}

    def params_only():
        box["cfg"], p, _ = bmodel.load(spec).build(spec, 0)
        return p
    params = place(jax.eval_shape(params_only))
    cfg = box["cfg"]
    eng = ServeEngine(cfg, params, quantized=True,
                      cache_dtype=jnp.bfloat16)
    reqs = wl.make(traffic, spec["config"]["vocab_size"], 0, a.seconds)
    clen = wl.cache_len(reqs, bucket_len)
    buckets = sorted({bucket_len(len(p), 16) for _, p, _, _ in reqs})
    gib = 2 ** 30
    pbytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    print(f"{a.workload}: {cfg.num_layers} layers, {slots} slots, cache "
          f"{clen}, prompt buckets {buckets}; params {pbytes / gib:.3f} GiB")

    def report(name, lowered):
        c = lowered.compile()
        m = c.memory_analysis()
        calls = c.as_text().count("fused_expert_b")
        print(f"  {name}: args {m.argument_size_in_bytes / gib:.3f} GiB, "
              f"out {m.output_size_in_bytes / gib:.3f}, temp "
              f"{m.temp_size_in_bytes / gib:.3f}, alias "
              f"{m.alias_size_in_bytes / gib:.3f}; fused kernel mentions "
              f"{calls}", flush=True)
        return m

    caches = place(jax.eval_shape(lambda: init_caches(
        cfg, slots, clen, jnp.bfloat16)))
    logits = jax.ShapeDtypeStruct((slots, cfg.vocab_size), jnp.bfloat16,
                                  sharding=one)
    key = place(jax.eval_shape(lambda: jax.random.key(0)))
    report(f"decode x{chunk}", eng._decode_loop.lower(
        params, caches, logits, key, None, max_new=chunk, temperature=0.0))
    want = {"max": buckets[-1:], "all": buckets, "none": []}[a.prefill]
    for b in want:
        c1 = place(jax.eval_shape(lambda: init_caches(cfg, 1, clen,
                                                      jnp.bfloat16)))
        toks = jax.ShapeDtypeStruct((1, b), jnp.int32, sharding=one)
        plen = jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one)
        report(f"prefill {b}", eng._prefill.lower(params, c1, toks, plen))


if __name__ == "__main__":
    main()
