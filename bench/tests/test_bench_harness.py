"""The yardstick's arithmetic on small synthesised inputs: trace
reduction, required work, traffic generation."""
import json
import os
import types

import numpy as np
import pytest

import run
import trace_reduce as tr
import model
import work
import workload as wl

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)


def ev(name, start, dur, **stats):
    return types.SimpleNamespace(name=name, start_ns=float(start),
                                 duration_ns=float(dur),
                                 stats=list(stats.items()))


def plane(name, **lines):
    return types.SimpleNamespace(name=name, lines=[
        types.SimpleNamespace(name=k.replace("_", " "), events=v)
        for k, v in lines.items()])


def synthetic():
    ops = [ev("fusion.1", 100, 100), ev("fusion.2", 150, 100),   # overlap
           ev("custom-call.7", 400, 200, hlo_op="fused_expert_b2_gated"),
           ev("fused_expert_b2.3", 700, 50),
           ev("copy.4", 900, 300)]                               # past end
    mods = [ev("jit_decode_loop", 380, 400), ev("jit_prefill", 90, 170)]
    dev = plane("/device:TPU:0", XLA_Ops=ops, XLA_Modules=mods)
    host = plane("/host:CPU", main=[
        ev("bench:window", 50, 1000),
        ev("bench:offload_metering", 260, 130),
        ev("bench:arrival_wait", 760, 140)])
    return tr.from_planes([host, dev])[0]


def test_busy_idle_union():
    assert tr.merge([(0, 10), (5, 20), (30, 40), (40, 45)]) == \
        [(0, 20), (30, 45)]
    assert tr.gaps([(10, 20), (30, 40)], 0, 50) == \
        [(0, 10), (20, 30), (40, 50)]
    t = synthetic()
    assert t.window == (50.0, 1050.0)
    # busy: [100, 250) + [400, 600) + [700, 750) + [900, 1050) clipped
    assert t.busy_s == pytest.approx(550e-9)
    assert t.window_s == pytest.approx(1000e-9)
    idle = dict(t.idle_by_host())
    # gaps [50,100) none, [250,400) metering (a gap goes whole to the
    # span that covers most of it), [600,700) none, [750,900) arrival
    assert idle["offload_metering"] == pytest.approx(150e-9)
    assert idle["host:none"] == pytest.approx(150e-9)
    assert idle["arrival_wait"] == pytest.approx(150e-9)
    assert sum(idle.values()) == pytest.approx(450e-9)


def test_kernel_time_by_name_and_module():
    t = synthetic()
    assert t.kernel_s("fused_expert_b") == pytest.approx(250e-9)
    assert t.kernel_s("fused_expert_b", inside=t.module_spans(
        "decode_loop")) == pytest.approx(250e-9)
    assert t.kernel_s("fused_expert_b", inside=t.module_spans(
        "prefill")) == 0.0
    assert t.module_runs("decode_loop") == (1, pytest.approx(400e-9))
    top = dict(t.top_ops())
    assert top["fusion"] == pytest.approx(200e-9)
    assert top["copy"] == pytest.approx(300e-9)


def test_nested_ops_keep_only_their_self_time():
    outer = tr.Event("while.3", 0, 100)
    a, b = tr.Event("fusion.1", 10, 20), tr.Event("fusion.2", 50, 30)
    inner = tr.Event("copy.9", 55, 10)          # nested two deep
    own = {e.name: t for e, t in tr.self_times([inner, b, outer, a])}
    assert own == {"while.3": 50, "fusion.1": 20, "fusion.2": 20,
                   "copy.9": 10}
    assert tr.op_family("%fused_expert_b2_gated.66 = f32[8,32] custom-call"
                        "(s32[1] %x)") == "fused_expert_b2_gated"


def spec(name="mixtral-8x7b"):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        return json.load(f)


def test_required_work_counts_hit_experts_and_true_ranks():
    s = spec()
    mod = model.load(s)
    g = mod.geometry(s)
    ranks = mod.rank_table(s)
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    slots, steps = 4, 3
    trace = -np.ones((steps, g.layers, slots, g.top_k), np.int64)
    trace[:, :, 0] = [0, 1]           # one live token on experts 0 and 1
    need = work.kernel_roofline_s(g, trace, ranks, peaks)
    full = work.container_bytes(g, s["compression"]["pad_rank"], slots)
    assert need["calls"] == steps * g.layers * 3
    assert need["bytes"] < 0.3 * full * steps
    # two experts' weights per call, and factors only where expert 0
    # (the top-n expert) holds the full rank
    per_step = sum(2 * work.weight_bytes(g, p) for p in work.PROJECTIONS)
    per_step *= g.layers
    assert need["bytes"] >= per_step * steps
    # every expert hit at the padded rank is what the container reads
    trace[:] = np.arange(slots * g.top_k).reshape(slots, g.top_k) % 8
    all_hit = work.kernel_roofline_s(g, trace, ranks, peaks)
    assert all_hit["bytes"] > need["bytes"]
    assert all_hit["bytes"] < full * steps


def test_request_flops_grow_with_context():
    s = spec()
    mod = model.load(s)
    g = mod.geometry(s)
    a = mod.request_flops(g, 100, 10, 32.0)
    b = mod.request_flops(g, 200, 10, 32.0)
    assert b > a > 0
    per_tok = float(mod.layer_flops(g, np.array([1]), 32.0)[0])
    assert a > 109 * per_tok * g.layers


@pytest.mark.parametrize("fixed", [True, False])
def test_traffic_is_a_function_of_the_seed(fixed):
    t = wl.load(os.path.join(BENCH, "traffic", "chat.json"))
    if not fixed:
        t.pop("order_seed")
    a = wl.make(t, 32000, 2 ** 31 + 12345, 30)
    b = wl.make(t, 32000, 2 ** 31 + 12345, 30)
    c = wl.make(t, 32000, 7, 30)
    assert len(a) == len(c) == wl.request_count(t, 30)
    for x, y in zip(a, b):
        assert x[0] == y[0] and x[2] == y[2] and x[3] == y[3]
        np.testing.assert_array_equal(x[1], y[1])
    # another seed: the same set of lengths and gaps, other token ids;
    # in the same order where the mix fixes it, else in another
    sched = [(len(r[1]), r[2], r[3]) for r in a]
    other = [(len(r[1]), r[2], r[3]) for r in c]
    assert (sched == other) == fixed
    assert sorted(x[0] for x in sched) == sorted(x[0] for x in other)
    assert sorted(x[1] for x in sched) == sorted(x[1] for x in other)
    assert np.allclose(sorted(np.diff([x[2] for x in sched])),
                       sorted(np.diff([x[2] for x in other])))
    assert any(len(x[1]) == len(y[1]) and (x[1] != y[1]).any()
               for x in a for y in c)
    # the schedule is a mix of lengths, not sorted
    assert [len(r[1]) for r in a] != sorted(len(r[1]) for r in a)
    lo, hi = t["prompt_tokens"]["min"], t["prompt_tokens"]["max"]
    assert all(lo <= len(r[1]) <= hi for r in a)


@pytest.mark.parametrize("seconds", [10, 51])
def test_every_seed_compiles_the_same_shapes(seconds):
    """The cache length and prompt buckets, the shapes the warm-up
    compiles, do not depend on the seed's order."""
    from repro.serve.engine import bucket_len
    t = wl.load(os.path.join(BENCH, "traffic", "chat.json"))
    t.pop("order_seed")
    seen = set()
    for seed in (1, 2, 3, 2 ** 31 + 5, 2 ** 32 + 9):
        reqs = wl.make(t, 32000, seed, seconds)
        seen.add((wl.cache_len(reqs, bucket_len),
                  tuple(sorted({bucket_len(len(p), 16)
                                for _, p, _, _ in reqs}))))
    assert len(seen) == 1


def test_warmup_matches_cache_length():
    from repro.serve.engine import bucket_len
    t = wl.load(os.path.join(BENCH, "traffic", "chat.json"))
    reqs = wl.make(t, 32000, 3, 30)
    warm = wl.warmup(reqs, bucket_len, 8, 32000)
    assert wl.cache_len(warm, bucket_len) == wl.cache_len(reqs, bucket_len)
    assert sorted({bucket_len(len(p), 16) for _, p, _, _ in warm}) == \
        sorted({bucket_len(len(p), 16) for _, p, _, _ in reqs})


NAME = r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$"


def test_benchmark_file_keeps_its_shape():
    import re
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        b = json.load(f)
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= b["run_seconds"] <= 51
    cfgs = {c["name"]: c for c in b["configs"]}
    cells = {w["name"]: w for w in b["workloads"]}
    e2e = {m["name"]: m for m in b["end_to_end"]}
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert os.path.isfile(os.path.join(os.path.dirname(BENCH),
                                           c["file"]))
        assert any(w["config"] == c["name"] for w in b["workloads"])
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in cfgs and w["chips"] in (1, 4)
        assert os.path.isfile(os.path.join(BENCH, "traffic",
                                           f"{w['traffic']}.json"))
        assert os.path.isfile(os.path.join(BENCH, "limits",
                                           f"{w['name']}.json"))
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in b["end_to_end"] + b["per_layer"]:
        assert re.match(NAME, m["name"]) and m["better"] in ("lower",
                                                             "higher")
        assert re.match(r"^[A-Za-z0-9_/%.\-]{1,16}$", m["unit"])
        assert set(m.get("workloads", [])) <= set(cells)
    for m in b["per_layer"]:
        assert m["moves"] in e2e
        assert os.path.isfile(os.path.join(BENCH, "metrics",
                                           f"{m['name']}.py"))
        for w in m.get("workloads", cells):
            assert run.applies(e2e[m["moves"]], w)
    for text in [x["why"] for x in b["configs"] + b["workloads"]] + [
            m["layer"] for m in b["per_layer"]]:
        assert 1 <= len(text) <= 200 and "\n" not in text
    for name in list(cfgs) + list(cells):
        assert re.match(NAME, name)

