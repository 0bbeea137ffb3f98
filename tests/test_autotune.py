"""Autotuner: candidate validity, cache hit/miss determinism, dispatch."""
import json

import numpy as np
import pytest

import jax.numpy as jnp

from repro.kernels import autotune, ops, ref
from repro.roofline import hw


@pytest.fixture
def cache(tmp_path):
    return autotune.AutotuneCache(path=tmp_path / "cache.json",
                                  seed_path=None)


def test_matmul_candidates_fit_vmem_and_clamp():
    for M, K, N in [(512, 3072, 256), (16, 128, 64), (2048, 8192, 4096)]:
        cands = autotune.matmul_candidates(M, K, N)
        assert cands
        for c in cands:
            bm, bn, bk = c["blk_m"], c["blk_n"], c["blk_k"]
            assert bm <= autotune._round_up(M, hw.SUBLANE)
            assert bn <= autotune._round_up(N, hw.LANE)
            assert bk <= autotune._round_up(K, hw.LANE)
            vmem = 2 * (bm * bk + bk * bn) * 4 + bm * bn * 8
            assert vmem <= autotune._VMEM_BUDGET


def test_matmul_tiling_miss_then_hit(cache, monkeypatch):
    t1 = autotune.matmul_tiling(512, 3072, 256, cache=cache)
    assert set(t1) == {"blk_m", "blk_n", "blk_k"}
    assert cache.path.is_file()
    # a hit must not re-run the sweep: poison the scorer
    monkeypatch.setattr(autotune, "matmul_cost_us",
                        lambda *a, **k: 1 / 0)
    t2 = autotune.matmul_tiling(512, 3072, 256, cache=cache)
    assert t2 == t1


def test_matmul_tiling_persists_across_cache_objects(cache):
    t1 = autotune.matmul_tiling(512, 3072, 256, cache=cache)
    fresh = autotune.AutotuneCache(path=cache.path, seed_path=None)
    entry = fresh.lookup(autotune.matmul_key(512, 3072, 256, "float32"))
    assert entry is not None and entry["blocks"] == t1


def test_matmul_tiling_deterministic(tmp_path):
    a = autotune.AutotuneCache(path=tmp_path / "a.json", seed_path=None)
    b = autotune.AutotuneCache(path=tmp_path / "b.json", seed_path=None)
    for M, K, N in [(512, 3072, 256), (64, 200, 48), (1, 6912, 256)]:
        assert autotune.matmul_tiling(M, K, N, cache=a) == \
            autotune.matmul_tiling(M, K, N, cache=b)


def test_m_bucketing_shares_keys():
    """Ragged batch rows land in the same pow2 bucket as the padded
    call facerec actually makes, so one tuning serves the whole bucket."""
    assert autotune.matmul_key(5, 3072, 256, "float32") == \
        autotune.matmul_key(8, 3072, 256, "float32")
    assert autotune.matmul_key(8, 3072, 256, "float32") != \
        autotune.matmul_key(16, 3072, 256, "float32")


def test_corrupt_cache_is_empty_cache(tmp_path):
    p = tmp_path / "bad.json"
    p.write_text("{not json")
    c = autotune.AutotuneCache(path=p, seed_path=None)
    assert c.lookup("anything") is None
    t = autotune.matmul_tiling(64, 128, 128, cache=c)
    assert set(t) == {"blk_m", "blk_n", "blk_k"}
    assert json.loads(p.read_text())   # rewritten valid


def test_seed_cache_overlay(tmp_path):
    seed = tmp_path / "seed.json"
    key = autotune.matmul_key(512, 3072, 256, "float32")
    seed.write_text(json.dumps(
        {key: {"blocks": {"blk_m": 8, "blk_n": 128, "blk_k": 128},
               "v": autotune.SCHEMA_VERSION}}))
    c = autotune.AutotuneCache(path=tmp_path / "user.json", seed_path=seed)
    assert autotune.matmul_tiling(512, 3072, 256, cache=c) == \
        {"blk_m": 8, "blk_n": 128, "blk_k": 128}
    assert not (tmp_path / "user.json").is_file()   # hit: nothing written


def test_stale_schema_entries_ignored(tmp_path):
    """An overlay written under an older schema can't shadow a refresh:
    its entries are dropped at load and re-tuned under the new stamp."""
    p = tmp_path / "stale.json"
    key = autotune.matmul_key(512, 3072, 256, "float32")
    p.write_text(json.dumps(
        {key: {"blocks": {"blk_m": 7, "blk_n": 100, "blk_k": 100},
               "v": autotune.SCHEMA_VERSION - 1}}))
    c = autotune.AutotuneCache(path=p, seed_path=None)
    assert c.lookup(key) is None
    fresh = autotune.matmul_tiling(512, 3072, 256, cache=c)
    assert fresh != {"blk_m": 7, "blk_n": 100, "blk_k": 100}
    assert json.loads(p.read_text())[key]["v"] == autotune.SCHEMA_VERSION


def test_resize_and_attention_tilings(cache):
    r = autotune.resize_tiling(216, 384, 108, 192, cache=cache)
    assert 1 <= r["blk_oh"] <= 108
    at = autotune.attention_tiling(2048, 2048, 128, cache=cache)
    assert 2048 % at["blk_q"] == 0 and 2048 % at["blk_k"] == 0
    # prime length: candidates clamp to the full sequence, which divides
    at_p = autotune.attention_tiling(127, 127, 64, cache=cache)
    assert 127 % at_p["blk_q"] == 0 and 127 % at_p["blk_k"] == 0


def test_committed_seed_matches_battery():
    """`make autotune` output is committed; this is --check as a test."""
    committed = json.loads(autotune.SEED_PATH.read_text())
    swept = autotune.hot_path_battery()
    assert {k: v["blocks"] for k, v in committed.items()} == \
        {k: v["blocks"] for k, v in swept.items()}


def test_tuned_matmul_matches_ref(cache, monkeypatch):
    monkeypatch.setattr(autotune, "_CACHE", cache)
    a = jnp.asarray(np.random.default_rng(0).normal(size=(13, 200)),
                    jnp.float32)
    b = jnp.asarray(np.random.default_rng(1).normal(size=(200, 37)),
                    jnp.float32)
    out = ops.matmul(a, b, impl="pallas_interpret")   # tuned blocks
    np.testing.assert_allclose(out, ref.matmul(a, b), atol=1e-4, rtol=1e-4)
    key = autotune.matmul_key(13, 200, 37, "float32")
    assert cache.lookup(key) is not None


def test_no_overlay_unless_asked(tmp_path, monkeypatch):
    """A run from a checkout reads committed tilings and writes nothing:
    without an explicit path or $REPRO_AUTOTUNE_CACHE there is no
    overlay, and a miss is memoized in memory only."""
    monkeypatch.delenv("REPRO_AUTOTUNE_CACHE", raising=False)
    monkeypatch.setenv("HOME", str(tmp_path))
    c = autotune.AutotuneCache(seed_path=None)
    assert c.path is None
    t = autotune.matmul_tiling(64, 200, 48, cache=c)
    assert c.lookup(autotune.matmul_key(64, 200, 48, "float32"))["blocks"] == t
    assert not any(tmp_path.rglob("*"))
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "o.json"))
    assert autotune.AutotuneCache().path == tmp_path / "o.json"


def test_measured_mode_needs_a_tpu(cache):
    with pytest.raises(RuntimeError, match="needs a TPU"):
        autotune.matmul_tiling(8, 128, 128, cache=cache, mode="measured")
