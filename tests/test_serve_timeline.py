"""The serve loop's own spans and counters (``serve/timeline.py``).

One tiny compressed MoE engine with live offload metering serves a
ragged workload on 2 slots; the tests read its ``ServeStats.timeline``:
span order within a chunk, one prefill and one claim per admission,
``prefill_s`` / ``decode_s`` as span sums, the counters, compiles booked
to the span that compiled, and the spans on the profiler's host plane.
"""
import dataclasses
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import ModelConfig, MoEConfig, QuantConfig
from repro.core import compress_ffn_weights
from repro.models import init_params
from repro.models.transformer import unstack_params
from repro.serve import Request, ServeEngine, Timeline

SLOTS, CHUNK = 2, 4
LOOP = ("decode.dispatch", "decode.sync", "pull", "record", "metering")


def tiny_engine():
    cfg = ModelConfig(
        name="tiny-moe", family="moe", num_layers=2, d_model=64,
        num_heads=2, num_kv_heads=1, head_dim=32, d_ff=0, vocab_size=128,
        block_pattern=("global",), max_position=512,
        moe=MoEConfig(num_experts=4, top_k=2, d_expert=64,
                      quant=QuantConfig(enabled=True, bits=2, rank_budget=16,
                                        top_n_restore=1, hqq_iters=3)))
    up = unstack_params(init_params(jax.random.key(4), cfg, jnp.float32),
                        cfg)
    segs, stacks_by_layer = [], []
    for seg in up["segments"]:
        p = dict(seg[0])
        mp = dict(p["moe"])
        stacks, _ = compress_ffn_weights(mp.pop("w1"), mp.pop("w2"),
                                         mp.pop("w3"), cfg.moe.quant)
        stacks_by_layer.append(stacks)
        mp["stacks"] = stacks
        p["moe"] = mp
        segs.append((p,))
    params = dict(up, segments=tuple(segs))
    eng = ServeEngine(dataclasses.replace(cfg, force_unroll_plan=True),
                      params, quantized=True)
    eng.attach_offload(stacks_by_layer, policy="ours", cache_capacity=2)
    return eng


def workload(late_s=0.0):
    """Four ragged requests at t=0 on 2 slots (so two wait for a slot),
    plus one arriving ``late_s`` after the start when it is set."""
    rng = np.random.default_rng(7)
    reqs = [Request(uid=10 + i, tokens=rng.integers(0, 128, (n,),
                                                    dtype=np.int32),
                    max_new=m)
            for i, (n, m) in enumerate([(5, 6), (9, 3), (12, 9), (7, 5)])]
    if late_s:
        reqs.append(Request(uid=20, tokens=rng.integers(0, 128, (6,),
                                                        dtype=np.int32),
                            max_new=4, arrival_s=late_s))
    return reqs


@pytest.fixture(scope="module")
def served():
    """(first serve of a fresh engine, an identical second serve, the
    engine): the first compiles, the second reuses every program."""
    eng = tiny_engine()
    first = eng.serve(workload(late_s=2.0), num_slots=SLOTS, chunk=CHUNK)
    again = eng.serve(workload(late_s=2.0), num_slots=SLOTS, chunk=CHUNK)
    return first, again, eng


def chunks_of(tl):
    """The loop spans of each chunk, from one ``decode.dispatch`` to the
    next."""
    out = []
    for s in tl.spans:
        if s.name == "decode.dispatch":
            out.append([])
        if out and s.name in LOOP:
            out[-1].append(s)
    return out


def test_spans_follow_the_loop_within_each_chunk(served):
    for stats in served[:2]:
        chunks = chunks_of(stats.timeline)
        assert len(chunks) == stats.chunks
        for spans in chunks:
            assert [s.name for s in spans] == list(LOOP)
            for a, b in zip(spans, spans[1:]):
                assert a.start_s <= a.end_s <= b.start_s


def test_top_level_spans_are_disjoint_and_cover_the_loop(served):
    stats = served[1]
    spans = sorted(stats.timeline.spans, key=lambda s: s.start_s)
    for a, b in zip(spans, spans[1:]):
        assert a.end_s <= b.start_s
    covered = sum(s.duration_s for s in spans)
    assert 0.95 * stats.total_s <= covered <= stats.total_s


def test_one_prefill_and_one_claim_per_admission(served):
    reqs = {r.uid: r for r in workload(late_s=2.0)}
    for stats in served[:2]:
        tl = stats.timeline
        pre, claim = tl.named("prefill"), tl.named("claim")
        assert tl.counters["admissions"] == len(reqs)
        assert [s.attrs["uid"] for s in pre] == \
            [s.attrs["uid"] for s in claim]
        assert sorted(s.attrs["uid"] for s in pre) == sorted(reqs)
        for p, c in zip(pre, claim):
            assert p.end_s <= c.start_s
            r = reqs[p.attrs["uid"]]
            assert p.attrs["prompt_len"] == r.prompt_len
            assert p.attrs["bucket"] >= r.prompt_len
            assert c.attrs["slot"] in range(SLOTS)


def test_dispatch_sync_and_pull_spans_match_chunks(served):
    for stats in served[:2]:
        tl = stats.timeline
        for name in ("decode.dispatch", "decode.sync", "pull", "record"):
            assert len(tl.named(name)) == stats.chunks


def test_arrival_wait_spans_the_idle_gap(served):
    """Without compiles the first four finish well before the late
    request arrives 2 s in: the loop sleeps under ``arrival_wait``."""
    stats = served[1]
    waits = stats.timeline.named("arrival_wait")
    assert waits
    late = next(r for r in stats.results if r.uid == 20)
    assert late.arrival_s <= waits[-1].end_s <= late.admitted_s + 0.05


def test_prefill_and_decode_seconds_are_span_sums(served):
    for stats in served[:2]:
        tl = stats.timeline
        assert stats.prefill_s == tl.total_s("prefill", "claim") > 0
        assert stats.decode_s == \
            tl.total_s("decode.dispatch", "decode.sync") > 0
        assert stats.busy_s == stats.prefill_s + stats.decode_s
        assert stats.busy_s < stats.total_s


def test_counters_count_occupancy_and_pulled_bytes(served):
    first, again, _ = served
    moe_layers, k = 2, 2
    for stats in (first, again):
        c = stats.timeline.counters
        steps = stats.chunks * CHUNK
        assert stats.generated_tokens <= c["live_slot_steps"] \
            <= steps * SLOTS
        # per chunk: tokens + logprobs (S, chunk) and the router trace
        # (chunk, moe_layers, S, k), 4 bytes each
        per_chunk = 4 * (2 * SLOTS * CHUNK + CHUNK * moe_layers * SLOTS * k)
        assert c["pulled_bytes"] == stats.chunks * per_chunk
    assert first.timeline.counters["live_slot_steps"] == \
        again.timeline.counters["live_slot_steps"]


def test_compiles_are_booked_to_the_span_that_compiled(served):
    first, again, _ = served
    c = first.timeline.counters
    assert c.get("compiles.prefill", 0) > 0
    assert c.get("compiles.decode.dispatch", 0) > 0
    assert not [n for n in again.timeline.counters
                if n.startswith("compiles.")]


def test_empty_workload_returns_an_empty_timeline(served):
    stats = served[2].serve([], num_slots=SLOTS, chunk=CHUNK)
    assert isinstance(stats.timeline, Timeline)
    assert stats.timeline.spans == [] and stats.prefill_s == 0.0


def test_spans_sit_on_the_profiler_host_plane(served, tmp_path):
    from jax.profiler import ProfileData
    eng = served[2]
    jax.profiler.start_trace(str(tmp_path))
    try:
        stats = eng.serve(workload(), num_slots=SLOTS, chunk=CHUNK)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    events = [e for plane in ProfileData.from_file(path).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for e in line.events
              if e.name.startswith("serve.")]
    names = [e.name for e in events]
    assert names.count("serve.decode.dispatch") == stats.chunks
    assert names.count("serve.metering") == stats.chunks
    pre = [dict(e.stats) for e in events if e.name == "serve.prefill"]
    assert sorted(s["uid"] for s in pre) == sorted(
        r.uid for r in workload())
    want = {r.uid: r.prompt_len for r in workload()}
    for s in pre:
        assert s["prompt_len"] == want[s["uid"]] and s["bucket"] >= 16
    # the recorded spans and the profiler's agree one for one
    assert len(events) == len(stats.timeline.spans)
