"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV per the scaffold contract; quality
benchmarks put their headline metric in the `derived` column.

  fig4   kurtosis <-> quant-error correlation; compensator residual gain
  fig6   accuracy ladder (fp32 / rtn / hqq / ours at int2+int3)
  alloc  calibrated vs uniform precision allocation at equal wire bytes
  fig7   offloaded decode throughput (GPU-only + GPU-NDP simulator)
  fig8   ablations: top-n count, rank budget, kurtosis vs uniform
  serving  continuous-batching offered-load sweep (tok/s, p50/p95 latency)
  table2 positional restoration (only-top1 vs only-top2)
  kernel quant/lowrank matmul microbenches + wire-byte accounting
  roofline  dry-run roofline summary (requires dryrun JSONs)
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback


def main() -> None:
    from repro.paths import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="longer training / more tokens")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset (fig4,fig6,...)")
    args = ap.parse_args()
    quick = not args.full

    from . import (bench_ablation, bench_accuracy, bench_breakdown,
                   bench_kernels, bench_kurtosis, bench_position,
                   bench_serving, bench_throughput, roofline_table)
    suites = {
        "kernel": bench_kernels.run,
        "fig1": bench_breakdown.run,
        "fig4": bench_kurtosis.run,
        "fig6": bench_accuracy.run,
        "alloc": bench_accuracy.run_alloc,
        "fig8": bench_ablation.run,
        "table2": bench_position.run,
        "fig7": bench_throughput.run,
        "serving": bench_serving.run,
        "roofline": roofline_table.run,
    }
    if args.only:
        keep = set(args.only.split(","))
        suites = {k: v for k, v in suites.items() if k in keep}

    failures = []
    print("name,us_per_call,derived")
    for key, fn in suites.items():
        t0 = time.time()
        try:
            rows = fn(quick=quick)
        except Exception as e:
            failures.append((key, repr(e)))
            traceback.print_exc()
            continue
        dt = (time.time() - t0) * 1e6
        for r in rows:
            name = r.pop("name")
            us = r.pop("us_per_call", dt / max(len(rows), 1))
            derived = ";".join(
                f"{k}={v:.4f}" if isinstance(v, float) else f"{k}={v}"
                for k, v in r.items())
            print(f"{name},{us:.1f},{derived}", flush=True)
    if failures:
        print("FAILURES:", failures, file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
