"""Tier-2 disk prefix store: warm prefixes survive an engine restart.

The acceptance surface of the fleet-prefix PR's persistence half:

- an engine relaunched on the same ``ARKS_PREFIX_DISK_DIR`` serves a
  previously-warm prefix with ZERO re-prefilled full-page tokens (the
  admission parks in the fetch path, the disk blocks stage into tier 1,
  and the ordinary restore path scatters them back);
- the round trip is bit-exact for int8/int4-packed blocks with scales
  (blocks are raw pool-native bytes, so spill -> restore cannot drift);
- blocks written under a different pool layout epoch are rejected, not
  served (manifest wipe on boot + per-file epoch check on read);
- a corrupt/truncated file is swallowed, deleted, and counted — never
  returned to a restore.
"""

import functools
import os

import numpy as np
import pytest

from arks_tpu.engine import (EngineConfig, InferenceEngine, Request,
                             SamplingParams)
from arks_tpu.engine import kv_transfer
from arks_tpu.engine.paged import chain_digests
from arks_tpu.engine.prefix_cache import DiskPrefixTier
from arks_tpu.engine.tokenizer import ByteTokenizer
from arks_tpu.models import get_config

import harness


def _mk(monkeypatch, ddir, host_mb="64", disk_mb="8", **kw):
    monkeypatch.setenv("ARKS_PIPELINE_DEPTH", "0")
    monkeypatch.setenv("ARKS_MIXED_STEP", "auto")
    monkeypatch.setenv("ARKS_PREFIX_HOST_MB", host_mb)
    monkeypatch.setenv("ARKS_PREFIX_DISK_MB", disk_mb)
    monkeypatch.setenv("ARKS_PREFIX_DISK_DIR", str(ddir))
    cfg = get_config("tiny")
    defaults = dict(model="tiny", num_slots=2, max_cache_len=64,
                    prefill_buckets=(8, 16, 32), steps_per_dispatch=4,
                    prefill_chunk=16, kv_layout="paged", prefix_cache_mb=0)
    defaults.update(kw)
    return cfg, InferenceEngine(cfg, EngineConfig(**defaults),
                                ByteTokenizer())


_drive = functools.partial(harness.drive, recover=True)


_collect = harness.collect


def _run_one(eng, rid, ids, max_tokens=4):
    req = Request(rid, ids, SamplingParams(
        max_tokens=max_tokens, temperature=0.0, ignore_eos=True))
    eng.add_request(req)
    _drive(eng)
    return _collect(req)


def _block(rng, dtype, with_scales, page=16, heads=8, dim=8, layers=2):
    shape = (layers, heads, page, dim)
    if np.issubdtype(dtype, np.integer):
        info = np.iinfo(dtype)
        k = rng.integers(info.min, info.max + 1, size=shape, dtype=dtype)
        v = rng.integers(info.min, info.max + 1, size=shape, dtype=dtype)
    else:
        k = rng.standard_normal(shape).astype(dtype)
        v = rng.standard_normal(shape).astype(dtype)
    blk = {"k": k, "v": v}
    if with_scales:
        blk["k_scale"] = rng.standard_normal(
            (layers, heads, page, 1)).astype(np.float32)
        blk["v_scale"] = rng.standard_normal(
            (layers, heads, page, 1)).astype(np.float32)
    return blk


# --------------------------------------------------- engine restart


def test_restart_serves_warm_prefix_from_disk(monkeypatch, tmp_path):
    """Kill/relaunch on the same ARKS_PREFIX_DISK_DIR: the relaunched
    engine serves the warm prompt byte-identically with zero re-prefilled
    full-page tokens — every full page comes back through the disk fetch
    + tier-1 restore path, and only the tail is chunk-prefilled."""
    ddir = tmp_path / "store"
    cfg, a = _mk(monkeypatch, ddir)
    warm = [int(x) % cfg.vocab_size for x in range(3, 36)]  # 2 pages + tail
    base = _run_one(a, "w1", warm)
    a_chunk = a.metrics.mixed_chunk_tokens_total.total()
    assert base[1].finish_reason == "length"
    a.stop()  # graceful stop publishes warm state into the disk store

    digests = chain_digests(warm, 16, 2)
    files = {f.name for f in ddir.iterdir()}
    assert DiskPrefixTier.MANIFEST in files
    for d in digests:
        assert d.hex() + DiskPrefixTier.SUFFIX in files, \
            "warm block missing from the disk store after stop()"

    cfg, b = _mk(monkeypatch, ddir)
    assert b._disk.num_blocks >= 2, "boot scan did not adopt the blocks"
    got = _run_one(b, "w2", warm)
    try:
        assert got[0] == base[0], "stream diverged across the restart"
        assert got[1].finish_reason == base[1].finish_reason
        # Zero re-prefilled warm-prefix tokens: both full pages restored
        # from disk; the chunked path saw strictly less than one cold run.
        assert b.metrics.prefix_cache_hit_tokens_total.get(tier="disk") == 32
        assert b.metrics.prefix_peer_fetch_blocks_total.get(
            source="disk") == 2
        assert b.metrics.prefix_restore_blocks_total.total() >= 2
        assert b.metrics.mixed_chunk_tokens_total.total() < a_chunk
    finally:
        b.stop()


def test_restart_on_other_layout_epoch_rejects_stale_blocks(
        monkeypatch, tmp_path):
    """A directory written by engine A must never be served under a
    different pool layout.  Simulated by re-stamping the tier with a
    different epoch: boot wipes the stale files, and a stale-epoch file
    smuggled behind the manifest's back is rejected on read (defense in
    depth), not reinterpreted as pool bytes."""
    ddir = tmp_path / "store"
    rng = np.random.default_rng(0)
    t1 = DiskPrefixTier(16, 1 << 20, str(ddir), epoch="layout-A")
    d1 = b"\x01" * 20
    assert t1.put(d1, _block(rng, np.int8, True))

    # Relaunch under another layout: manifest mismatch wipes the store.
    t2 = DiskPrefixTier(16, 1 << 20, str(ddir), epoch="layout-B")
    assert not t2.has(d1)
    assert t2.get(d1) is None
    assert not list(ddir.glob("*" + DiskPrefixTier.SUFFIX))

    # Defense in depth: a layout-A file appearing under a layout-B
    # manifest (crashed writer from the previous layout) is adopted by
    # the boot scan but REJECTED on read and dropped.
    d2 = b"\x02" * 20
    buf = kv_transfer.pack_block(d2, "layout-A", _block(rng, np.int8, True))
    (ddir / (d2.hex() + DiskPrefixTier.SUFFIX)).write_bytes(buf)
    t3 = DiskPrefixTier(16, 1 << 20, str(ddir), epoch="layout-B")
    assert t3.has(d2)            # indexed by the scan...
    assert t3.get(d2) is None    # ...but never served
    assert not t3.has(d2)
    assert t3.corrupt_blocks == 1


# ------------------------------------------------ bit-exact round trip


@pytest.mark.parametrize("dtype,scales", [
    (np.int8, True),       # int8-quantized pool pages + f32 scales
    (np.uint8, True),      # int4-packed pages ride uint8 nibbles
    (np.float32, False),   # full-width pool
], ids=["int8", "int4-packed", "f32"])
def test_disk_round_trip_is_bit_exact(monkeypatch, tmp_path, dtype, scales):
    rng = np.random.default_rng(7)
    t = DiskPrefixTier(16, 1 << 20, str(tmp_path), epoch="e")
    blk = _block(rng, dtype, scales)
    dg = b"\x0a" * 20
    assert t.put(dg, blk)

    # Same process and a fresh adoption of the directory both serve the
    # exact bytes that went in.
    t2 = DiskPrefixTier(16, 1 << 20, str(tmp_path), epoch="e")
    for tier in (t, t2):
        out = tier.get(dg)
        assert set(out) == set(blk)
        for f in blk:
            assert out[f].dtype == blk[f].dtype
            assert out[f].shape == blk[f].shape
            assert out[f].tobytes() == blk[f].tobytes()


def test_corrupt_block_is_swallowed_and_dropped(tmp_path):
    rng = np.random.default_rng(3)
    t = DiskPrefixTier(16, 1 << 20, str(tmp_path), epoch="e")
    dg = b"\x0b" * 20
    assert t.put(dg, _block(rng, np.int8, True))
    path = tmp_path / (dg.hex() + DiskPrefixTier.SUFFIX)
    path.write_bytes(path.read_bytes()[:40])  # truncate mid-header

    assert t.get(dg) is None
    assert t.corrupt_blocks == 1
    assert not t.has(dg)
    assert not path.exists()


def test_eviction_honors_byte_budget_and_unlinks(tmp_path):
    rng = np.random.default_rng(5)
    t = DiskPrefixTier(16, 1 << 20, str(tmp_path), epoch="e")
    one = t  # size one block first to learn the budget unit
    b0 = _block(rng, np.int8, True)
    d0 = bytes([0]) * 20
    assert one.put(d0, b0)
    unit = t.bytes_used
    t.capacity = int(unit * 2.5)  # room for two blocks
    digs = [bytes([i + 1]) * 20 for i in range(3)]
    for d in digs:
        assert t.put(d, _block(rng, np.int8, True))
    assert t.num_blocks == 2
    assert t.evicted_blocks == 2
    assert t.bytes_used <= t.capacity
    # Evicted files are gone from disk, survivors still present.
    on_disk = {f.name for f in tmp_path.glob("*" + DiskPrefixTier.SUFFIX)}
    assert on_disk == {d.hex() + DiskPrefixTier.SUFFIX
                      for d in (digs[-2], digs[-1])}


def test_tmp_orphans_are_cleaned_on_boot(tmp_path):
    rng = np.random.default_rng(9)
    t = DiskPrefixTier(16, 1 << 20, str(tmp_path), epoch="e")
    t.put(b"\x0c" * 20, _block(rng, np.int8, True))
    orphan = tmp_path / ("deadbeef" + DiskPrefixTier.SUFFIX + ".123.tmp")
    orphan.write_bytes(b"torn write")
    t2 = DiskPrefixTier(16, 1 << 20, str(tmp_path), epoch="e")
    assert not orphan.exists()
    assert t2.num_blocks == 1


def test_disk_dir_defaults_under_tmpdir(monkeypatch, tmp_path):
    """ARKS_PREFIX_DISK_MB alone is enough to turn the tier on — the
    directory defaults under the system tempdir."""
    monkeypatch.setenv("TMPDIR", str(tmp_path))
    import tempfile
    tempfile.tempdir = None  # re-read TMPDIR
    try:
        monkeypatch.setenv("ARKS_PIPELINE_DEPTH", "0")
        monkeypatch.setenv("ARKS_MIXED_STEP", "auto")
        monkeypatch.setenv("ARKS_PREFIX_HOST_MB", "64")
        monkeypatch.setenv("ARKS_PREFIX_DISK_MB", "8")
        monkeypatch.delenv("ARKS_PREFIX_DISK_DIR", raising=False)
        cfg = get_config("tiny")
        eng = InferenceEngine(
            cfg, EngineConfig(model="tiny", num_slots=2, max_cache_len=64,
                              prefill_buckets=(8, 16, 32),
                              steps_per_dispatch=4, prefill_chunk=16,
                              kv_layout="paged", prefix_cache_mb=0),
            ByteTokenizer())
        try:
            assert eng._disk is not None
            assert eng._disk.dir.startswith(str(tmp_path))
            assert os.path.isdir(eng._disk.dir)
        finally:
            eng.stop()
    finally:
        tempfile.tempdir = None
