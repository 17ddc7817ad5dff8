"""Persisted kernel-autotune table: mode gating, cache-path precedence,
persist -> load -> reuse round-trips (including through mixed_grid_plan,
the consumer the kernels actually resolve statics through), sweep
winner selection, and the engine's sweep-at-warm-up path."""

import json
import os

import pytest

from arks_tpu.ops import autotune


@pytest.fixture(autouse=True)
def _isolated_table(monkeypatch, tmp_path):
    """Every test gets its own table file and a cold in-memory cache."""
    monkeypatch.setenv("ARKS_KERNEL_TUNE_CACHE",
                       str(tmp_path / "kernel_tune.json"))
    monkeypatch.setenv("ARKS_KERNEL_TUNE", "cached")
    autotune.invalidate_cache()
    yield
    autotune.invalidate_cache()


def test_mode_validation(monkeypatch):
    for m in ("off", "cached", "sweep"):
        monkeypatch.setenv("ARKS_KERNEL_TUNE", m)
        assert autotune.mode() == m
    monkeypatch.setenv("ARKS_KERNEL_TUNE", "always")
    with pytest.raises(ValueError, match="ARKS_KERNEL_TUNE"):
        autotune.mode()


def test_cache_path_precedence(monkeypatch, tmp_path):
    monkeypatch.setenv("ARKS_KERNEL_TUNE_CACHE", str(tmp_path / "x.json"))
    monkeypatch.setenv("ARKS_MODEL_DIR", str(tmp_path / "model"))
    assert autotune.cache_path() == str(tmp_path / "x.json")
    monkeypatch.delenv("ARKS_KERNEL_TUNE_CACHE")
    assert autotune.cache_path() == str(tmp_path / "model" /
                                        "kernel_tune.json")
    monkeypatch.delenv("ARKS_MODEL_DIR")
    assert autotune.cache_path().endswith(
        os.path.join(".cache", "arks_tpu", "kernel_tune.json"))


def test_record_persists_and_lookup_round_trips():
    sig = autotune.mixed_signature(hkv=2, g=3, d=32, page=128, qmax=16,
                                   kv="int8")
    assert autotune.lookup("paged_mixed", sig) is None
    autotune.record("paged_mixed", sig, {"block_q": 8, "dma_depth": 4})
    # Through the write-through in-memory table...
    assert autotune.lookup("paged_mixed", sig) == {"block_q": 8,
                                                   "dma_depth": 4}
    # ...and through a cold LOAD from the JSON on disk.
    autotune.invalidate_cache()
    assert autotune.lookup("paged_mixed", sig) == {"block_q": 8,
                                                   "dma_depth": 4}
    on_disk = json.loads(open(autotune.cache_path()).read())
    assert on_disk["paged_mixed"][sig] == {"block_q": 8, "dma_depth": 4}


def test_mode_off_ignores_table(monkeypatch):
    sig = autotune.decode_signature(b=4, hkv=2, g=3, d=32, page=128,
                                    kv="int8")
    autotune.record("paged_decode", sig, {"block_b": 32})
    monkeypatch.setenv("ARKS_KERNEL_TUNE", "off")
    assert autotune.lookup("paged_decode", sig) is None
    monkeypatch.setenv("ARKS_KERNEL_TUNE", "cached")
    assert autotune.lookup("paged_decode", sig) == {"block_b": 32}


def test_signatures_embed_topology_and_shape():
    a = autotune.mixed_signature(hkv=2, g=3, d=32, page=128, qmax=16,
                                 kv="int8")
    b = autotune.mixed_signature(hkv=2, g=3, d=32, page=128, qmax=16,
                                 kv="int4")
    assert a != b and autotune.topology() in a


def test_mixed_grid_plan_honors_cached_entry():
    """The consumer path: mixed_grid_plan resolves block_q/dma_depth from
    the table, falls back to the heuristic on a miss, and explicit
    arguments always win over the table."""
    from arks_tpu.ops.paged_attention import mixed_grid_plan

    kw = dict(hkv=2, g=3, d=32, page=128, kv="float32")
    plan = mixed_grid_plan(48, **kw)
    assert plan["block_q"] == 32 and plan["dma_depth"] == 2  # heuristics
    sig = autotune.mixed_signature(qmax=48, **kw)
    autotune.record("paged_mixed", sig, {"block_q": 16, "dma_depth": 4})
    autotune.invalidate_cache()
    plan = mixed_grid_plan(48, **kw)
    assert plan["block_q"] == 16 and plan["dma_depth"] == 4
    assert plan["qpad"] == 48 and plan["num_qb"] == 3
    # Explicit overrides beat the table.
    assert mixed_grid_plan(48, block_q=8, **kw)["block_q"] == 8
    # A different qmax is a different signature: heuristic again.
    assert mixed_grid_plan(40, **kw)["block_q"] == 32


def test_sweep_picks_and_persists_fastest(monkeypatch):
    import time

    sig = autotune.mixed_signature(hkv=1, g=1, d=8, page=8, qmax=4,
                                   kv="float32")
    calls = []

    def bench(block_q):
        calls.append(block_q)
        time.sleep(0.02 if block_q == 4 else 0.001)

    best = autotune.sweep("paged_mixed", sig,
                          [{"block_q": 4}, {"block_q": 2}], bench,
                          repeats=2)
    assert best == {"block_q": 2}
    assert calls.count(4) == calls.count(2) == 3  # warm-up + 2 timed
    autotune.invalidate_cache()
    assert autotune.lookup("paged_mixed", sig) == {"block_q": 2}


def test_sweep_skips_infeasible_candidates(caplog):
    def bench(block_q):
        if block_q == 8:
            raise ValueError("Mosaic failed to compile TPU kernel: why\n"
                             "a long dump that must not reach the log")

    with caplog.at_level("WARNING", logger="arks.autotune"):
        best = autotune.sweep("k", "s", [{"block_q": 8}, {"block_q": 2}],
                              bench)
    assert best == {"block_q": 2}
    # Skipped, but never silently: the refusal is logged with the
    # compiler's first line, so a candidate the chip cannot launch does
    # not read as one that merely lost the timing.
    (rec,) = [r for r in caplog.records if r.levelname == "WARNING"]
    msg = rec.getMessage()
    assert "'block_q': 8" in msg and msg.endswith("TPU kernel: why")
    with pytest.raises(RuntimeError, match="every candidate"):
        autotune.sweep("k", "s2", [{"block_q": 8}], bench)


def test_ensure_is_mode_aware(monkeypatch):
    sig = "s"
    swept = []

    def bench(block_q):
        swept.append(block_q)

    # cached + miss: no sweep, heuristics (None).
    assert autotune.ensure("k", sig, [{"block_q": 2}], bench) is None
    assert not swept
    # sweep + miss: sweeps once, then the cached entry short-circuits.
    monkeypatch.setenv("ARKS_KERNEL_TUNE", "sweep")
    assert autotune.ensure("k", sig, [{"block_q": 2}], bench) == {
        "block_q": 2}
    n = len(swept)
    assert autotune.ensure("k", sig, [{"block_q": 2}], bench) == {
        "block_q": 2}
    assert len(swept) == n


def test_engine_sweep_mode_tunes_mixed_kernel(monkeypatch):
    """ARKS_KERNEL_TUNE=sweep at engine construction: _warm_autotune
    benches the mixed kernel on the engine's own pool BEFORE the first
    dispatch and persists a winner under the engine's mixed signature —
    and the served stream matches the untuned engine's byte-for-byte
    (block sizes change the schedule, never the math)."""
    from arks_tpu.engine import (EngineConfig, InferenceEngine, Request,
                                 SamplingParams)
    from arks_tpu.engine.tokenizer import ByteTokenizer
    from arks_tpu.models import get_config
    from arks_tpu.models import transformer as tf

    monkeypatch.setenv("ARKS_MIXED_STEP", "1")
    monkeypatch.setenv("ARKS_ATTN_IMPL", "pallas")
    cfg = get_config("tiny")

    def run(tune_mode):
        monkeypatch.setenv("ARKS_KERNEL_TUNE", tune_mode)
        autotune.invalidate_cache()
        eng = InferenceEngine(cfg, EngineConfig(
            model="tiny", num_slots=2, max_cache_len=64,
            prefill_buckets=(8, 16, 32), steps_per_dispatch=4,
            prefill_chunk=16, kv_layout="paged", prefix_cache_mb=0),
            ByteTokenizer())
        req = Request("t0", [5, 6, 7], SamplingParams(
            max_tokens=4, temperature=0.0, ignore_eos=True))
        eng.add_request(req)
        for _ in range(400):
            eng.step(block_s=0.01)
            if (eng.num_running == 0 and eng._queue.empty()
                    and eng._deferred is None
                    and not eng._prefilling):
                break
        ids = []
        while True:
            out = req.outputs.get(timeout=120)
            ids.extend(out.token_ids)
            if out.finished:
                return eng, ids

    eng, swept_ids = run("sweep")
    sig = autotune.mixed_signature(
        hkv=cfg.num_kv_heads, g=cfg.num_heads // cfg.num_kv_heads,
        d=tf.cache_head_dim(cfg, eng._pad_head()), page=eng._page_size(),
        qmax=eng._mixed_budget + 1,
        kv=str(eng._cache.k.dtype))
    autotune.invalidate_cache()
    entry = autotune.lookup("paged_mixed", sig)
    assert entry and "block_q" in entry and "dma_depth" in entry
    assert eng.resolved_config["kernel_tune"] == "sweep"

    _, off_ids = run("off")
    assert swept_ids == off_ids
