#!/usr/bin/env python3
"""Find a cell's knee once, on the chip: serve the cell's traffic mix at
several offered rates in one process (one model, one warm-up) and print
the medians, the tails and the backlog at each.

    python3 bench/sweep.py --workload <name> --rates 2,3,4 --seconds 80

Arrivals fill ``--seconds`` at each rate (no drain time), once for
each seed of ``--seeds``.

For each rate: requests, tokens/s, TTFT and time per output token at
p50/p90, queue wait p90, and the backlog: how long after the last
arrival the last request finished, and the queue wait of the last
tenth of the requests against the first tenth.  A rate the system
sustains drains in about one request's time and keeps its queue wait
flat; above the knee the late requests wait longer and longer.  The
peak of requests live at once (admitted, not finished) is the slot
count the load fills.
``--trace-dump DIR`` also traces one short window at the first rate
and writes a text map of the trace there.
"""
import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--trace-dump", default="")
    a = ap.parse_args()
    _, _, spec, traffic, _ = run.cell_files(a.workload)
    run.prepare()
    import jax
    import numpy as np
    import workload as wl
    if jax.devices()[0].platform != "tpu":
        run.fail("no TPU")
    slots, chunk = spec["serve"]["slots"], spec["serve"]["chunk"]
    vocab = spec["config"]["vocab_size"]
    rates = [float(r) for r in a.rates.split(",")]

    seeds = [int(s) for s in a.seeds.split(",")]
    seed0 = seeds[0]

    def mix(rate, seed):
        t = json.loads(json.dumps(traffic))
        t["arrivals"] = {"kind": "poisson", "rate_per_s": rate}
        return wl.make(t, vocab, seed, a.seconds)

    t0 = time.time()
    eng = run.warm_engine(spec, seed0, mix(rates[-1], seed0))
    print(f"set-up {time.time() - t0:.1f}s", flush=True)
    if a.trace_dump:
        import trace_reduce
        from jax.profiler import ProfileData
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        d = run.TRACE_DIR
        undo = run.add_spans(eng)
        jax.profiler.start_trace(d, profiler_options=opts)
        with jax.profiler.TraceAnnotation("bench:window"):
            st = eng.serve(run.to_requests(mix(rates[0], seed0)[:8]),
                           num_slots=slots, chunk=chunk, seed=seed0)
        jax.profiler.stop_trace()
        undo()
        import glob
        path = max(glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
        planes = list(ProfileData.from_file(path).planes)
        print([p.name for p in planes], flush=True)
        with open(os.path.join(a.trace_dump, "trace_map.txt"), "w") as f:
            f.write(trace_reduce.describe(planes, limit=40))
        dt = trace_reduce.from_planes(planes)[0]
        print(f"trace: window {dt.window_s:.3f}s busy {dt.busy_s:.3f}s "
              f"decode runs {dt.module_runs('decode_loop')} fused "
              f"{dt.kernel_s('fused_expert_b'):.4f}s; top ops "
              f"{dt.top_ops(8)}; idle {dt.idle_by_host(6)}; "
              f"{st.generated_tokens} tokens", flush=True)
    def row(reqs, seed, rate):
        st = eng.serve(run.to_requests(reqs), num_slots=slots, chunk=chunk,
                       seed=seed)
        res = st.results
        tt = np.array([r.ttft_s for r in res])
        tp = np.array([(r.finished_s - r.first_token_s) / (len(r.tokens) - 1)
                       for r in res if len(r.tokens) > 1])
        qw = np.array([r.admitted_s - r.arrival_s for r in res])
        tenth = max(1, len(res) // 10)
        order = np.argsort([r.arrival_s for r in res])
        last_arr = max(r.arrival_s for r in res)
        edges = sorted([(r.admitted_s, 1) for r in res]
                       + [(r.finished_s, -1) for r in res])
        live = np.cumsum([e for _, e in edges])
        out = {
            "rate": rate, "seed": seed, "requests": len(res),
            "tokens_per_s": st.generated_tokens / st.total_s,
            "ttft_p50_ms": 1e3 * np.percentile(tt, 50),
            "ttft_p90_ms": 1e3 * np.percentile(tt, 90),
            "tpot_p50_ms": 1e3 * np.percentile(tp, 50),
            "tpot_p90_ms": 1e3 * np.percentile(tp, 90),
            "queue_p50_ms": 1e3 * np.percentile(qw, 50),
            "queue_p90_ms": 1e3 * np.percentile(qw, 90),
            "drain_s": st.total_s - last_arr,
            "queue_first_tenth_ms": 1e3 * qw[order[:tenth]].mean(),
            "queue_last_tenth_ms": 1e3 * qw[order[-tenth:]].mean(),
            "live_peak": int(live.max()),
            "live_mean": sum(r.finished_s - r.admitted_s for r in res)
            / st.total_s,
            "window_s": st.total_s, "chunks": st.chunks,
            "decode_s": st.decode_s, "prefill_s": st.prefill_s}
        print(json.dumps({k: (round(v, 3) if isinstance(v, float) else v)
                          for k, v in out.items()}), flush=True)

    for rate in rates:
        for seed in seeds:
            row(mix(rate, seed), seed, rate)


if __name__ == "__main__":
    main()
