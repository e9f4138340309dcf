"""repro-lint engine + rules: every rule fires on a seeded violation
fixture, stays quiet on the real tree, and the suppression/baseline
mechanisms behave (src/repro/analysis/, tools/repro_lint.py)."""
import json
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from repro.analysis import Baseline, all_rules, lint_paths

REPO = Path(__file__).resolve().parent.parent


def run_fixture(tmp_path, files, select=None):
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    return lint_paths([tmp_path], tmp_path, select=select)


def rules_of(result):
    return sorted({f.rule for f in result.findings})


# ---------------------------------------------------------------------------
# seeded violations: each rule must fire on its fixture
# ---------------------------------------------------------------------------

FIXTURES = {
    "RL101": {"m.py": """
        import jax
        import jax.numpy as jnp

        @jax.jit
        def f(x):
            y = jnp.sum(x)
            return float(y) + y.item()
        """},
    "RL102": {"m.py": """
        import jax

        @jax.jit
        def f(x):
            if x > 0:
                return x
            return -x
        """},
    "RL103": {"m.py": """
        import jax
        import jax.numpy as jnp

        @jax.jit
        def f(x, n):
            return jnp.zeros(n) + x
        """},
    "RL104": {"m.py": """
        import jax

        @jax.jit
        def f(x):
            return jax.device_get(x)
        """},
    "RL201": {"pipeline.py": """
        def wire(bits, k, n):
            return k * n * bits // 8
        """},
    "RL202": {"meter.py": """
        from repro.core.quantize import SCALE_WIRE_BYTES

        def scales(k, g, n):
            return (k // g) * n * SCALE_WIRE_BYTES
        """},
    "RL301": {"kernels/autotune.py": """
        DEFAULT_TABLE = {
            ("fused", 3, 64, 32, 128): (32, 256, 96),
        }
        """},
    "RL302": {"kernels/autotune.py": """
        DEFAULT_TABLE = {
            ("fused", 3, 64, 32, 128): (1024, 4096, 8192),
        }
        """},
    "RL303": {"kernels/k.py": """
        from jax.experimental import pallas as pl

        def kern(planes_ref, o_ref):
            o_ref[...] = planes_ref[...]

        def launch(planes, x, bk=128):
            return pl.pallas_call(kern)(planes[0])
        """},
    "RL304": {"kernels/k.py": """
        from jax.experimental import pallas as pl

        def launch(me, bm):
            return pl.BlockSpec((1, bm), lambda e, i: (e, i))
        """},
    "RL401": {"m.py": """
        import jax
        from repro.distributed.sharding import tree_constraint

        @jax.jit
        def step(caches, x):
            caches = advance(caches, x)
            return caches
        """},
}


@pytest.mark.parametrize("rule_id", sorted(FIXTURES))
def test_rule_fires_on_fixture(tmp_path, rule_id):
    result = run_fixture(tmp_path, FIXTURES[rule_id], select={rule_id})
    assert rule_id in rules_of(result), \
        f"{rule_id} silent on its seeded violation"


# ---------------------------------------------------------------------------
# precision: known-legal idioms must NOT fire
# ---------------------------------------------------------------------------

def test_static_idioms_stay_quiet(tmp_path):
    result = run_fixture(tmp_path, {"m.py": """
        import jax
        import jax.numpy as jnp

        @jax.jit
        def f(params, x, temperature: float = 1.0, cfg=None):
            b, s = x.shape                  # shape access is static
            if temperature <= 0.0:          # float-annotated scalar
                x = x * 2
            if "bias" in params:            # pytree key membership
                x = x + params["bias"]
            if cfg is None:                 # identity test
                x = -x
            for i in range(s):              # range over a static dim
                x = x + i
            return jnp.zeros((b, s)) + x    # static shape tuple
        """})
    assert result.findings == [], [f.render() for f in result.findings]


def test_pack_guard_silences_rl303(tmp_path):
    result = run_fixture(tmp_path, {"kernels/k.py": """
        from jax.experimental import pallas as pl

        PACK_BLOCK = 64

        def kern(planes_ref, o_ref):
            o_ref[...] = planes_ref[...]

        def launch(planes, x, bk=128):
            assert bk % PACK_BLOCK == 0
            return pl.pallas_call(kern)(planes[0])
        """}, select={"RL303"})
    assert result.findings == []


def test_rl304_checks_local_block_helpers_and_spares_legal_blocks(tmp_path):
    result = run_fixture(tmp_path, {"kernels/k.py": """
        from jax.experimental import pallas as pl

        def launch(bm, bk, r):
            def spec(shape, index):
                return pl.BlockSpec(shape, index)
            legal = [spec((1, bm, 1), None), spec((1, 1, r), None),
                     pl.BlockSpec((bm, bk), None)]
            return legal + [spec((1, 1), None)]
        """}, select={"RL304"})
    assert [f.content for f in result.findings] == [
        "return legal + [spec((1, 1), None)]"]


# ---------------------------------------------------------------------------
# the real tree is clean
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_real_tree_is_clean():
    result = lint_paths([REPO / "src", REPO / "tools", REPO / "benchmarks"],
                        REPO,
                        baseline_path=REPO / "tools" /
                        "repro_lint_baseline.json")
    assert result.ok, "\n".join(f.render() for f in result.findings)


def test_all_rules_registered():
    ids = set(all_rules())
    assert {"RL101", "RL102", "RL103", "RL104", "RL201", "RL202",
            "RL301", "RL302", "RL303", "RL304", "RL401"} <= ids


# ---------------------------------------------------------------------------
# suppression + baseline
# ---------------------------------------------------------------------------

def test_inline_suppression(tmp_path):
    src = FIXTURES["RL102"]["m.py"].replace(
        "if x > 0:", "if x > 0:  # repro-lint: disable=RL102")
    result = run_fixture(tmp_path, {"m.py": src}, select={"RL102"})
    assert result.findings == []
    assert result.suppressed == 1


def test_baseline_roundtrip(tmp_path):
    result = run_fixture(tmp_path, FIXTURES["RL102"], select={"RL102"})
    assert result.findings
    bpath = tmp_path / "baseline.json"
    Baseline.dump(result.findings, bpath)

    again = lint_paths([tmp_path], tmp_path, baseline_path=bpath,
                       select={"RL102"})
    assert again.findings == []
    assert again.baselined == len(result.findings)

    # editing the flagged line invalidates its baseline entry
    m = tmp_path / "m.py"
    m.write_text(m.read_text().replace("if x > 0:", "if x > 1:"))
    edited = lint_paths([tmp_path], tmp_path, baseline_path=bpath,
                        select={"RL102"})
    assert edited.findings and edited.baselined == 0


def test_corrupt_baseline_is_ignored(tmp_path):
    bpath = tmp_path / "baseline.json"
    bpath.write_text("{not json")
    result = run_fixture(tmp_path, FIXTURES["RL102"], select={"RL102"})
    # corrupt baseline -> empty baseline -> findings still reported
    again = lint_paths([tmp_path], tmp_path, baseline_path=bpath,
                       select={"RL102"})
    assert rules_of(again) == rules_of(result) == ["RL102"]


# ---------------------------------------------------------------------------
# CLI exit codes
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_cli_exit_codes(tmp_path):
    (tmp_path / "bad.py").write_text(textwrap.dedent(
        FIXTURES["RL102"]["m.py"]))
    (tmp_path / "ok.py").write_text("x = 1\n")
    script = str(REPO / "tools" / "repro_lint.py")

    dirty = subprocess.run(
        [sys.executable, script, "--root", str(tmp_path), "--baseline",
         "none", "--select", "RL102", "bad.py"], capture_output=True,
        text=True)
    assert dirty.returncode == 1, dirty.stdout + dirty.stderr
    assert "RL102" in dirty.stdout

    clean = subprocess.run(
        [sys.executable, script, "--root", str(tmp_path), "--baseline",
         "none", "--select", "RL102", "ok.py"], capture_output=True,
        text=True)
    assert clean.returncode == 0, clean.stdout + clean.stderr

    missing = subprocess.run(
        [sys.executable, script, "--root", str(tmp_path), "--baseline",
         "none", "nonexistent_dir"], capture_output=True, text=True)
    assert missing.returncode == 2
