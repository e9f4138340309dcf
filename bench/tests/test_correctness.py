"""The comparison that decides ``correct``, driven through the rest of a
run at a tiny size on the CPU (the harness's look for a chip skipped):
a sound run's line, the control against the program, and a fault
planted where tokens are produced."""
import json
import os

import jax.numpy as jnp
import pytest

import check
import run

HERE = os.path.dirname(os.path.abspath(__file__))
SEED = 2 ** 31 + 77


@pytest.fixture(scope="module")
def tiny():
    os.environ["REPRO_KERNEL_IMPL"] = "pallas_interpret"
    run.prepare()
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(HERE, "tiny.json")) as f:
        spec = json.load(f)
    with open(os.path.join(HERE, "tiny-traffic.json")) as f:
        traffic = json.load(f)
    cell = dict(bench["workloads"][0])
    return bench, cell, spec, traffic


def one_run(tiny, limit, seconds=4):
    bench, cell, spec, traffic = tiny
    return run.run_cell(bench, cell, spec, traffic,
                        {"mean_logit_gap": {"limit": limit}}, SEED,
                        seconds, False, require_tpu=False)


def test_sound_run_line(tiny):
    out = one_run(tiny, limit=100.0)
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(out)[-1] == "limits"
    assert out["correct"] and out["failed"] == 0
    names = {m["name"] for m in tiny[0]["end_to_end"]
             if run.applies(m, tiny[1]["name"])}
    assert set(out["metrics"]) == names
    assert out["notes"]["window_compiles"] == 0
    assert out["limits"]["mean_logit_gap"]["value"] < 0.35


def test_altered_token_is_not_correct(tiny, monkeypatch):
    import repro.serve.engine as engine

    def altered(logits, key, temperature):
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return (tok + 1) % logits.shape[-1]

    monkeypatch.setattr(engine, "sample", altered)
    out = one_run(tiny, limit=0.35)
    assert not out["correct"]
    assert out["limits"]["mean_logit_gap"]["value"] > 1.0


def test_control_fails_where_the_program_passes(tiny):
    """The control goes through the verdict a run gives, against a limit
    set between the tiny size's readings (program 0 to 0.04, control
    0.13 to 0.25 over three seeds)."""
    import control
    _, _, spec, traffic = tiny
    limits = {"mean_logit_gap": {"limit": 0.1}}
    prog, ref, seqs, rows = control.serve_and_check(spec, traffic, limits,
                                                    SEED, 4)
    ctl = run.reference_logits(spec, SEED, seqs, rows, "fp8")
    judged = control.judged(check.control_gaps(ref, ctl), limits)
    assert prog["correct"] and not judged["correct"]
    assert judged["checks"]["mean_logit_gap"] > \
        3 * prog["checks"]["mean_logit_gap"]
