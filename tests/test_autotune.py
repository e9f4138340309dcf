"""Autotuner policy: deterministic lookup, clamping contracts, and
cache persistence (kernels/autotune.py)."""
import json

import jax.numpy as jnp
import numpy as np

from repro.core.quantize import PACK_BLOCK
from repro.kernels import autotune
from repro.kernels.autotune import Autotuner, clamp_tiles, choose_tiles
from repro.launch.roofline import (fused_hbm_bytes, fused_tile_candidates,
                                   fused_tile_vmem_bytes)


def test_default_table_lookup_is_deterministic():
    a = choose_tiles("fused", bits=2, group_size=64, rank=16,
                     m=8, k=1024, n=1024)
    b = choose_tiles("fused", bits=2, group_size=64, rank=16,
                     m=8, k=1024, n=1024)
    assert a == b == (8, 256, 512)      # the decode preset, clamp-stable


def test_decode_preset_small_m():
    """Single-token decode blocks must get bm=8 (the `_pad_m` waste fix),
    never a 128-row tile."""
    for m in (1, 2, 8):
        bm, _, _ = choose_tiles("fused", bits=2, group_size=64, rank=16,
                                m=m, k=512, n=512)
        assert bm == 8


def test_clamp_preserves_divisibility():
    for m, k, n in ((1, 192, 384), (8, 512, 128), (33, 1024, 1024)):
        bm, bn, bk = clamp_tiles(m, k, n, 128, 512, 1024, group_size=64)
        assert k % bk == 0 and n % bn == 0
        assert bk % PACK_BLOCK == 0 and bk % 64 == 0
        assert bm % 8 == 0 and bm <= max(8, -(-m // 8) * 8)


def test_roofline_candidates_fit_vmem_and_problem():
    from repro.launch.roofline import VMEM_BUDGET, VMEM_BYTES
    cands = fused_tile_candidates(8, 1024, 1024, 2, 64, 16)
    assert cands, "decode shape must have at least one candidate"
    for bm, bn, bk in cands:
        assert bm <= 8 and bn <= 1024 and bk <= 1024
        assert bk % 64 == 0
        assert (fused_tile_vmem_bytes(bm, bn, bk, 2, 64, 16)
                <= VMEM_BYTES * VMEM_BUDGET)
    # best-first: the first candidate has the largest K tile
    assert cands[0][2] == max(c[2] for c in cands)


def test_fused_hbm_bytes_reads_u_once_per_token_tile():
    """Mixtral-8x7B decode w1 (8 experts, 16 slots, K 4096, N 14336,
    2-bit codes in groups of 64, rank 256) at the decode tiles (16, 256,
    512): U is read once per token tile, 8 MiB, not once per N tile
    (56 times that), so the call reads about 340 MB."""
    e, m, k, n, r = 8, 16, 4096, 14336, 256
    tiles = choose_tiles("fused", bits=2, group_size=64, rank=r,
                         m=m, k=k, n=n)
    assert tiles == (16, 256, 512)
    total = fused_hbm_bytes(e, m, k, n, 2, 64, r, *tiles)
    assert total == 339_664_896
    # one more token tile reads U once more, one more N tile does not
    # read it again
    u = e * k * r
    assert fused_hbm_bytes(e, 2 * m, k, n, 2, 64, r, *tiles) - total > u
    more_n = fused_hbm_bytes(e, m, k, n + 256, 2, 64, r, *tiles) - total
    assert more_n < u


def test_record_and_disk_roundtrip(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE",
                       str(tmp_path / "autotune.json"))
    monkeypatch.setenv("REPRO_AUTOTUNE", "1")   # tuning runs read the table
    t = Autotuner()
    t.record("fused", (8, 128, 256), 42.0, bits=2, group_size=64,
             rank=16, m=8, k=512, n=512)
    # a fresh tuner (fresh memory) must see the persisted winner
    t2 = Autotuner()
    assert t2.choose("fused", bits=2, group_size=64, rank=16,
                     m=8, k=512, n=512) == (8, 128, 256)
    data = json.loads((tmp_path / "autotune.json").read_text())
    dev = next(iter(data.values()))
    assert dev["fused/b2/g64/r16/m8/k512/n512"]["tiles"] == [8, 128, 256]


def test_tune_fused_interpret_smoke(tmp_path, monkeypatch):
    """tune_fused times the candidates under the interpreter and records
    an in-memory winner without touching the disk cache."""
    from repro.config import QuantConfig
    from repro.core.pipeline import compress_expert_stack

    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE",
                       str(tmp_path / "autotune.json"))
    rng = np.random.default_rng(0)
    qcfg = QuantConfig(enabled=True, bits=2, group_size=64, rank_budget=8,
                       top_n_restore=1, hqq_iters=1)
    w = jnp.asarray(rng.standard_normal((2, 128, 128)), jnp.float32) * 0.05
    stack, _ = compress_expert_stack(w, qcfg)
    xe = jnp.asarray(rng.standard_normal((2, 8, 128)), jnp.float32)
    me = jnp.ones((2, 8), jnp.float32)
    best = autotune.tune_fused(xe, stack, me, None, None,
                               out_dtype=jnp.float32, interpret=True,
                               repeats=1)
    assert 128 % best[2] == 0 and 128 % best[1] == 0
    assert not (tmp_path / "autotune.json").exists()   # interpret: no persist


def test_store_disk_is_atomic(tmp_path, monkeypatch):
    """The cache write must go through a same-directory temp file and
    os.replace, leaving no partial file behind."""
    cache = tmp_path / "autotune.json"
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(cache))
    t = Autotuner()
    t.record("fused", (8, 128, 256), 1.0, bits=2, group_size=64,
             rank=16, m=8, k=512, n=512)
    t.record("fused", (8, 256, 512), 2.0, bits=4, group_size=64,
             rank=16, m=8, k=1024, n=1024)
    leftovers = [p for p in tmp_path.iterdir() if p.name != cache.name]
    assert leftovers == [], leftovers
    data = json.loads(cache.read_text())       # complete, parseable JSON
    dev = next(iter(data.values()))
    assert len(dev) == 2


def test_corrupt_disk_cache_falls_back_to_defaults(tmp_path, monkeypatch):
    cache = tmp_path / "autotune.json"
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(cache))
    monkeypatch.setenv("REPRO_AUTOTUNE", "1")
    expected = Autotuner().choose("fused", bits=2, group_size=64, rank=16,
                                  m=8, k=1024, n=1024)
    for payload in ('{"truncated', '[1, 2, 3]', '"just a string"', ""):
        cache.write_text(payload)
        t = Autotuner()
        assert t.choose("fused", bits=2, group_size=64, rank=16,
                        m=8, k=1024, n=1024) == expected
        # and a later record must recover the file to valid JSON
        t.record("fused", (8, 128, 256), 1.0, bits=2, group_size=64,
                 rank=16, m=8, k=512, n=512)
        assert isinstance(json.loads(cache.read_text()), dict)


def test_structurally_corrupt_entry_is_ignored(tmp_path, monkeypatch):
    """Valid JSON whose entries have the wrong shape must not raise."""
    cache = tmp_path / "autotune.json"
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(cache))
    monkeypatch.setenv("REPRO_AUTOTUNE", "1")
    expected = Autotuner().choose("fused", bits=2, group_size=64, rank=16,
                                  m=8, k=1024, n=1024)
    key = "fused/b2/g64/r16/m8/k1024/n1024"
    from repro.kernels.autotune import device_kind
    for bad in (None, 7, {"us": 1.0}, {"tiles": "wat"},
                {"tiles": [8, 128]}, {"tiles": [8, "x", 512]}):
        cache.write_text(json.dumps({device_kind(): {key: bad}}))
        assert Autotuner().choose("fused", bits=2, group_size=64, rank=16,
                                  m=8, k=1024, n=1024) == expected


def test_persisted_table_not_read_unless_tuning(tmp_path, monkeypatch):
    """A default run depends on tracked files alone: a persisted winner
    is looked up only when REPRO_AUTOTUNE asks for tuning."""
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE",
                       str(tmp_path / "autotune.json"))
    monkeypatch.delenv("REPRO_AUTOTUNE", raising=False)
    Autotuner().record("fused", (8, 128, 256), 42.0, bits=2, group_size=64,
                       rank=16, m=8, k=512, n=512)
    assert Autotuner().choose("fused", bits=2, group_size=64, rank=16,
                              m=8, k=512, n=512) == (8, 256, 512)
    monkeypatch.setenv("REPRO_AUTOTUNE", "1")
    assert Autotuner().choose("fused", bits=2, group_size=64, rank=16,
                              m=8, k=512, n=512) == (8, 128, 256)


def test_default_cache_path_is_inside_the_checkout(monkeypatch):
    from repro.paths import CHECKOUT
    monkeypatch.delenv("REPRO_AUTOTUNE_CACHE", raising=False)
    assert autotune.cache_path().parent == CHECKOUT


def test_device_kind_raises_without_a_device(monkeypatch):
    import jax
    import pytest

    def no_devices():
        raise RuntimeError("no backend")

    monkeypatch.setattr(jax, "devices", no_devices)
    with pytest.raises(RuntimeError, match="no backend"):
        autotune.device_kind()


def test_tune_fused_reports_failures_and_raises_when_all_fail(
        tmp_path, monkeypatch):
    import pytest
    from repro.config import QuantConfig
    from repro.core.pipeline import compress_expert_stack
    from repro.kernels import ops

    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE",
                       str(tmp_path / "autotune.json"))
    rng = np.random.default_rng(0)
    qcfg = QuantConfig(enabled=True, bits=2, group_size=64, rank_budget=8,
                       top_n_restore=1, hqq_iters=1)
    w = jnp.asarray(rng.standard_normal((2, 128, 128)), jnp.float32) * 0.05
    stack, _ = compress_expert_stack(w, qcfg)
    xe = jnp.asarray(rng.standard_normal((2, 8, 128)), jnp.float32)
    me = jnp.ones((2, 8), jnp.float32)

    def refused(*a, **k):
        raise ValueError("block shape refused")

    monkeypatch.setattr(ops, "fused_expert_matmul", refused)
    with pytest.warns(UserWarning, match="block shape refused"):
        with pytest.raises(RuntimeError, match="every fused tile"):
            autotune.tune_fused(xe, stack, me, None, None,
                                out_dtype=jnp.float32, interpret=True,
                                repeats=1)
