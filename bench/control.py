#!/usr/bin/env python3
"""Readings that set a cell's limits, on the chip.  For each seed: serve
the cell's traffic for ``--seconds`` (the benchmark's run length by
default) as a run does, then judge, through the same ``check.verdict``
and the cell's limits file that a run uses:

- ``program``: the served tokens against the float32 reference (the
  lower reading's candidates; ``correct`` has to be true);
- ``control``: the reference in float8 in the program's place, over the
  same prompts and tokens (the upper reading; ``correct`` has to be
  false);
- ``no_compensation`` (``--no-compensation``): the program again with
  its compensators dropped, served on the same requests;
- ``bf16_witness`` (``--witness``): the reference in bf16 in the
  program's place, a witness of what bf16 rounding alone reads.

One JSON line per seed.

    python3 bench/control.py --workload <name> --seeds 11,12,13 [--no-compensation]
"""
import argparse
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run  # noqa: E402

THRESHOLDS = (0.05, 0.1, 0.25, 0.5, 1.0)


def judged(gap, limits, unfinished=0, short=0):
    """The verdict a run would give ``gap``, with the gaps' shape."""
    import numpy as np
    import check
    checks, correct = check.verdict(gap, limits, unfinished, short)
    return {"correct": correct,
            "checks": {n: c["value"] for n, c in checks.items()},
            "gaps": {**{n: f(gap) for n, f in check.STATISTICS.items()},
                     "q50_90_99": [float(x) for x in
                                   np.quantile(gap, [.5, .9, .99])],
                     "over": {str(t): int((gap > t).sum())
                              for t in THRESHOLDS},
                     "n": int(gap.size)} if gap.size else None}


def serve_and_check(spec, traffic, limits, seed, seconds, compensate=True):
    """Serve the cell's requests for ``seed`` and judge the served tokens
    against the float32 reference; returns (reading, reference logits,
    sequences, rows)."""
    import check
    import workload as wl
    slots, chunk = spec["serve"]["slots"], spec["serve"]["chunk"]
    reqs = wl.make(traffic, spec["config"]["vocab_size"], seed, seconds)
    eng = run.warm_engine(spec, seed, reqs, compensate=compensate)
    st = eng.serve(run.to_requests(reqs), num_slots=slots, chunk=chunk,
                   seed=seed)
    picked, unfinished, short = run.served_sample(reqs, st.results, seed)
    del eng, st
    gc.collect()
    seqs, rows, served = check.sequences(picked)
    ref = run.reference_logits(spec, seed, seqs, rows, "f32")
    return (judged(check.gaps(ref, served), limits, unfinished, short),
            ref, seqs, rows)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=0.0,
                    help="window (default: the benchmark's run_seconds)")
    ap.add_argument("--no-compensation", action="store_true",
                    help="also serve with the compensators dropped")
    ap.add_argument("--witness", action="store_true",
                    help="also the reference in bf16 in the program's "
                         "place")
    a = ap.parse_args()
    bench, cell, spec, traffic, limits = run.cell_files(a.workload)
    seconds = a.seconds or bench["run_seconds"]
    run.prepare()
    import jax
    import check
    if jax.devices()[0].platform != "tpu":
        run.fail("no TPU")
    for seed in (int(s) for s in a.seeds.split(",")):
        t0 = time.time()
        prog, ref, seqs, rows = serve_and_check(spec, traffic, limits, seed,
                                                seconds)
        ctl = run.reference_logits(spec, seed, seqs, rows, "fp8")
        row = {"seed": seed, "program": prog,
               "control": judged(check.control_gaps(ref, ctl), limits)}
        if a.witness:
            wit = run.reference_logits(spec, seed, seqs, rows, "bf16")
            row["bf16_witness"] = judged(check.control_gaps(ref, wit),
                                         limits)
        del ref
        if a.no_compensation:
            row["no_compensation"] = serve_and_check(
                spec, traffic, limits, seed, seconds, compensate=False)[0]
        row["total_s"] = time.time() - t0
        print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
