"""The readers of the program's spans and counters
(``host.chunk_gap_ms``, ``admit.prefill_ms``) on a synthesised timeline
and trace, and on a program that keeps no timeline."""
import types

import pytest

import run
import trace_reduce as tr
from repro.serve.timeline import Span, Timeline


def ev(name, start, dur):
    return types.SimpleNamespace(name=name, start_ns=float(start),
                                 duration_ns=float(dur), stats=[])


def plane(name, **lines):
    return types.SimpleNamespace(name=name, lines=[
        types.SimpleNamespace(name=k.replace("_", " "), events=v)
        for k, v in lines.items()])


def timeline():
    """Three chunks.  After chunk 0 the host spends 10 ms recording, an
    admission's admit/prefill/claim of 0.5 s, and 4 ms more; after
    chunk 1 it waits 2 s for an arrival and spends 6 ms."""
    tl = Timeline(t0=0.0)
    spans = [("decode.dispatch", 0.000, 0.001), ("decode.sync", 0.001, 0.100),
             ("record", 0.100, 0.110),
             ("admit", 0.110, 0.111), ("prefill", 0.111, 0.500),
             ("claim", 0.500, 0.611),
             ("plan", 0.611, 0.615),
             ("decode.dispatch", 0.615, 0.616), ("decode.sync", 0.616, 0.700),
             ("metering", 0.700, 0.706),
             ("arrival_wait", 0.706, 2.706),
             ("decode.dispatch", 2.706, 2.707), ("decode.sync", 2.707, 2.800),
             ("record", 2.800, 2.900)]
    tl.spans = [Span(n, a, b, {}) for n, a, b in spans]
    tl.counters = {"admissions": 2}
    return tl


def trace():
    """A window of [0, 10000) ns: two decode runs, two prefills and one
    claim."""
    ops = [ev("fusion.1", 100, 50), ev("fusion.2", 2100, 40),
           ev("fusion.3", 6000, 500)]
    mods = [ev("jit_decode_loop", 50, 1000), ev("jit_decode_loop", 2050, 1000),
            ev("jit_prefill", 5000, 2000), ev("jit_prefill", 7500, 1000),
            ev("jit_claim", 9000, 300)]
    dev = plane("/device:TPU:0", XLA_Ops=ops, XLA_Modules=mods)
    host = plane("/host:CPU", main=[ev("bench:window", 0, 10000)])
    return tr.from_planes([host, dev])[0]


def record(tl=timeline(), t=None, chunk=2):
    return types.SimpleNamespace(
        stats=types.SimpleNamespace(timeline=tl, chunk=chunk), trace=t)


def test_chunk_gap_leaves_out_admissions_and_arrival_waits():
    gaps = run.reader("host.chunk_gap_ms").__globals__["gaps_s"](timeline())
    assert gaps == pytest.approx([0.014, 0.006])
    assert run.reader("host.chunk_gap_ms")(record()) == pytest.approx(10.0)


def test_prefill_per_admission_sums_prefill_and_claim_runs():
    # (2000 + 1000 + 300) ns over 2 admissions
    assert run.reader("admit.prefill_ms")(record(t=trace())) == \
        pytest.approx(1e3 * 3300e-9 / 2)


@pytest.mark.parametrize("name", ["host.chunk_gap_ms", "admit.prefill_ms"])
def test_readers_return_none_where_nothing_is_there(name):
    """A program without a timeline and a run without a trace read None
    and do not raise."""
    read = run.reader(name)
    # the parent program: a trace, but no timeline on its stats
    parent = types.SimpleNamespace(stats=types.SimpleNamespace(chunk=2),
                                   trace=trace())
    assert read(parent) is None
    if name == "host.chunk_gap_ms":
        assert read(record(t=None)) is not None
    else:
        assert read(record(t=None)) is None
