#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout, on a machine with the TPU chips the cell
asks for.  The cell (``BENCHMARK.json`` ``workloads``) names a
configuration file and a traffic file; both are found by name, as are
the per-layer metric readers under ``bench/metrics/`` and the cell's
limits under ``bench/limits/``.

A run draws the model on the device from ``--seed``, builds the engine
through the program's own ``launch/serve.py::build_engine``, warms up the
shapes the traffic will use (set-up ends there), serves the seeded
requests through ``ServeEngine.serve`` (the window: first arrival to the
last request finished), reads the device's peak memory, frees the
program, and checks a seeded sample of the served tokens against the
configuration's plain float32 reference.  With ``--trace 1`` the window
runs under the profiler and the line carries the per-layer metrics
instead of the end-to-end ones.

The last line of standard output is one JSON object; the numbers
compared for ``correct`` come last in it, under ``limits``, and as the
last lines of standard error.  No TPU, or fewer chips than the cell
asks for: exit 3 and no result.
"""
import time

T_START = time.time()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402
from typing import Dict, Optional  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# JAX's persistent compilation cache: a fixed directory inside the
# checkout (its path is part of every entry's key)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
TRACE_DIR = os.path.join(ROOT, ".bench_trace")
SAMPLE_TOKENS = 384        # served tokens the reference checks, at least,
SAMPLE_REQUESTS = 6        # from 3 requests or more and at most 6


class Refused(Exception):
    """The run cannot produce a result here (exit 3, no result line)."""


def fail(msg: str, code: int = 3):
    print(f"bench: {msg}", file=sys.stderr)
    sys.exit(code)


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def cell_files(workload: str, root: str = ROOT):
    """(benchmark, cell, config spec, traffic, limits) for a cell name."""
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise Refused(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    cfg = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    spec = load_json(os.path.join(root, cfg["file"]))
    traffic = load_json(os.path.join(HERE, "traffic",
                                     f"{cell['traffic']}.json"))
    limits = load_json(os.path.join(HERE, "limits", f"{workload}.json"))
    return bench, cell, spec, traffic, limits


def prepare(root: str = ROOT):
    """Put the program and the benchmark on the path and turn on the
    compile cache; nothing here touches a device."""
    src = os.path.join(root, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise Refused(f"no program under {src}: run from a checkout")
    for p in (src, HERE):
        if p not in sys.path:
            sys.path.insert(0, p)
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # no eviction: a handful of programs, and eviction's bookkeeping
    # (an access-time file beside each entry) is what failed on the chip
    jax.config.update("jax_compilation_cache_max_size", -1)


class CompileCount:
    """XLA compilations, from JAX's own monitoring events."""

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


def percentile(xs, q: float) -> Optional[float]:
    import numpy as np
    return float(np.percentile(np.asarray(xs, float), q)) if xs else None


def to_requests(reqs):
    from repro.serve.scheduler import Request
    return [Request(uid=u, tokens=p, max_new=o, arrival_s=t)
            for u, p, o, t in reqs]


# -- spans of the traced run ------------------------------------------------

def _span(name: str, fn):
    import jax

    def wrapped(*a, **kw):
        with jax.profiler.TraceAnnotation(f"bench:{name}"):
            return fn(*a, **kw)
    return wrapped


def add_spans(eng):
    """Host spans around the program's calls into each layer, so that
    the trace can name what the host did in each idle gap of the device.
    Returns an undo function."""
    import repro.offload.store as store
    import repro.serve.engine as engine_mod
    from repro.serve.scheduler import Scheduler
    undo = []

    def patch(obj, attr, name):
        old = getattr(obj, attr)
        setattr(obj, attr, _span(name, old))
        undo.append(lambda: setattr(obj, attr, old))

    for attr, name in (("_prefill", "prefill"), ("_claim", "claim"),
                       ("_decode_loop", "decode_dispatch")):
        patch(eng, attr, name)
    patch(store, "replay_decode_trace", "offload_metering")
    patch(Scheduler, "record_chunk", "record_chunk")
    patch(Scheduler, "admit", "admit")
    clock = engine_mod.time
    engine_mod.time = types.SimpleNamespace(
        perf_counter=clock.perf_counter, time=clock.time,
        sleep=_span("arrival_wait", clock.sleep))
    undo.append(lambda: setattr(engine_mod, "time", clock))
    return lambda: [u() for u in reversed(undo)]


# -- metrics -----------------------------------------------------------------

def end_to_end(stats, setup_s: float) -> Dict[str, float]:
    """The host-clock end-to-end metrics of a window: medians over all
    its requests (a window holds some tens, too few for a tail)."""
    res = stats.results
    tpot = [(r.finished_s - r.first_token_s) / (len(r.tokens) - 1)
            for r in res if len(r.tokens) > 1]
    ttft = [r.ttft_s for r in res]
    spans = [(r.finished_s - r.first_token_s, len(r.tokens) - 1)
             for r in res if len(r.tokens) > 1]
    return {
        "tpot_p50_ms": 1e3 * percentile(tpot, 50),
        "tpot_mean_ms": 1e3 * sum(s for s, _ in spans)
        / max(sum(n for _, n in spans), 1),
        "ttft_p50_ms": 1e3 * percentile(ttft, 50),
        "ttft_mean_ms": 1e3 * sum(ttft) / max(len(ttft), 1),
        "tokens_per_s": stats.generated_tokens / stats.total_s,
        "setup_s": setup_s,
    }


def reader(name: str):
    path = os.path.join(HERE, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        "metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def applies(metric: Dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


# -- shared steps -------------------------------------------------------------

def warm_engine(spec: Dict, seed: int, reqs, phases=None,
                compensate: bool = True):
    """Draw the model from ``seed``, build the engine and serve the
    warm-up requests that compile every shape ``reqs`` will use.
    ``phases`` (a dict) gets the seconds of each step;
    ``compensate=False`` drops the compensators (a fault, for the
    limits' readings)."""
    import jax
    import model as bmodel
    import workload as wl
    from repro.serve.engine import bucket_len
    phases = {} if phases is None else phases
    slots, chunk = spec["serve"]["slots"], spec["serve"]["chunk"]
    t = time.time()
    built = bmodel.load(spec).build(spec, seed, compensate)
    jax.block_until_ready(built[1])
    phases["draw_s"] = time.time() - t
    t = time.time()
    eng = bmodel.engine(spec, built, slots, chunk)
    del built
    phases["engine_s"] = time.time() - t
    t = time.time()
    warm = wl.warmup(reqs, bucket_len, chunk, spec["config"]["vocab_size"])
    eng.serve(to_requests(warm), num_slots=slots, chunk=chunk, seed=seed)
    phases["warmup_s"] = time.time() - t
    return eng


def reference_logits(spec: Dict, seed: int, seqs, rows, mode: str):
    """The configuration's reference over ``seqs`` with the weights
    ``seed`` draws, at the positions ``rows``."""
    import check
    import model as bmodel
    ref = check.load_reference(spec["reference"])
    mod = bmodel.load(spec)

    def draw(layer):
        return mod.draw(spec, seed, mod.OUTER if layer is None else layer)
    return ref.logits_at(spec, draw, seqs, rows, mode)


def served_sample(reqs, results, seed: int):
    """``(picked, unfinished, short)``: the seeded sample of finished
    requests the reference checks, the requests that never finished,
    and those that served another number of tokens than asked."""
    import numpy as np
    import check
    prompts = {u: p for u, p, _, _ in reqs}
    want = {u: o for u, _, o, _ in reqs}
    picked = check.sample(
        [types.SimpleNamespace(uid=r.uid, prompt=prompts[r.uid],
                               tokens=np.asarray(r.tokens))
         for r in results], seed, SAMPLE_TOKENS, SAMPLE_REQUESTS)
    short = sum(1 for r in results if len(r.tokens) != want[r.uid])
    return picked, len(reqs) - len(results), short


# -- one run -----------------------------------------------------------------

def run_cell(bench: Dict, cell: Dict, spec: Dict, traffic: Dict,
             limits: Dict, seed: int, seconds: float, trace: bool,
             require_tpu: bool = True, t_start: float = T_START) -> Dict:
    """One run of one cell; returns the result line as a dict.
    ``require_tpu=False`` (the CPU tests) skips the look for a chip."""
    import jax
    import check
    import model as bmodel
    import workload as wl
    from peaks import peaks_for
    from repro.kernels.ops import resolve_impl

    devs = jax.devices()
    dev = devs[0]
    if require_tpu:
        if dev.platform != "tpu":
            raise Refused(f"no TPU: JAX sees {dev.platform} devices only")
        if len(devs) < cell["chips"]:
            raise Refused(f"cell {cell['name']} asks for {cell['chips']} "
                          f"chips, JAX sees {len(devs)}")
        if resolve_impl(None) != "pallas":
            raise Refused(f"kernel dispatch resolves to "
                          f"{resolve_impl(None)!r}, not 'pallas'")
    compiles = CompileCount()
    slots, chunk = spec["serve"]["slots"], spec["serve"]["chunk"]
    vocab = spec["config"]["vocab_size"]

    # -- set-up: draw, build, warm the cell's own shapes
    reqs = wl.make(traffic, vocab, seed, seconds)
    phases = {"start_s": time.time() - t_start}
    eng = warm_engine(spec, seed, reqs, phases)
    setup_s = time.time() - t_start
    phases["compiles"] = compiles.n

    # -- the window
    c0 = compiles.n
    undo = None
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        undo = add_spans(eng)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("bench:window"):
            stats = eng.serve(to_requests(reqs), num_slots=slots,
                              chunk=chunk, seed=seed)
    finally:
        if trace:
            jax.profiler.stop_trace()
            undo()
    window_compiles = compiles.n - c0
    window_s = stats.total_s
    ms = dev.memory_stats() or {}
    peak = int(ms.get("peak_bytes_in_use", 0))

    mod = bmodel.load(spec)
    record = types.SimpleNamespace(
        spec=spec, cell=cell, stats=stats, seconds=seconds, model=mod,
        geometry=mod.geometry(spec), ranks=mod.rank_table(spec),
        peaks=peaks_for(dev.device_kind) if require_tpu else None,
        trace=None)

    metrics: Dict[str, Dict] = {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    breakdown = None
    if trace:
        import trace_reduce
        dt = trace_reduce.read(TRACE_DIR)[0]
        record.trace = dt
        device["busy_s"] = dt.busy_s
        device["window_s"] = dt.window_s
        breakdown = {"device_ops": [[n, s] for n, s in dt.top_ops(10)],
                     "idle_gaps": [[n, s] for n, s in dt.idle_by_host(10)]}
        for m in bench["per_layer"]:
            if applies(m, cell["name"]):
                v = reader(m["name"])(record)
                if v is not None:
                    metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    e2e = end_to_end(stats, setup_s)
    if not trace:
        for m in bench["end_to_end"]:
            if applies(m, cell["name"]):
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}

    # -- free the program, then the reference
    picked, unfinished, short = served_sample(reqs, stats.results, seed)
    del eng, stats, record
    gc.collect()
    t_ref = time.time()
    seqs, rows, served = check.sequences(picked)
    logits = reference_logits(spec, seed, seqs, rows, "f32")
    gap = check.gaps(logits, served)
    ref_s = time.time() - t_ref

    checks, correct = check.verdict(gap, limits, unfinished, short)
    notes = {"window_compiles": window_compiles, "setup": phases,
             "window_s": window_s, "reference_s": ref_s,
             "checked_tokens": int(gap.size),
             "checked_requests": len(picked),
             "host_clock": e2e,
             "gaps": ({n: f(gap) for n, f in check.STATISTICS.items()}
                      if gap.size else None)}
    out = {"correct": correct, "attempted": len(reqs),
           "failed": unfinished + short, "metrics": metrics,
           "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["notes"] = notes
    out["limits"] = checks
    return out


def main():
    ap = argparse.ArgumentParser(description="run one benchmark cell once")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    try:
        bench, cell, spec, traffic, limits = cell_files(args.workload)
        prepare()
        out = run_cell(bench, cell, spec, traffic, limits, args.seed,
                       args.seconds, bool(args.trace))
    except Refused as exc:
        fail(str(exc))
    for name, c in out["limits"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
