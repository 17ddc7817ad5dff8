"""Training data pipeline (train/data.py): packing math, SFT masking,
shard disjointness, determinism, prefetch, and an end-to-end train step."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from arks_tpu.engine.tokenizer import ByteTokenizer
from arks_tpu.models import get_config
from arks_tpu.train.data import PackedDataset, prefetch, read_jsonl


def _records(n=40):
    return [{"text": f"document number {i} " + "x" * (i % 17)}
            for i in range(n)]


def test_packing_covers_stream_exactly():
    """Windows tile the EOS-joined token stream: tokens are contiguous,
    targets are tokens shifted by one, nothing repeats or is skipped
    until the dropped tail."""
    tok = ByteTokenizer()
    ds = PackedDataset(_records(), tok, seq_len=32, batch_size=2, seed=3)
    # Rebuild the reference stream in the SAME shuffled order.
    order = list(range(len(ds.records)))
    import random as _r
    _r.Random("3/0").shuffle(order)
    stream = []
    for i in order:
        stream.extend(tok.encode(ds.records[i]["text"]) + [0])

    flat_toks, flat_tgts = [], []
    for batch in ds.epoch(0):
        assert batch["tokens"].shape == (2, 32)
        assert batch["tokens"].dtype == np.int32
        assert batch["loss_mask"].dtype == np.float32
        flat_toks.extend(batch["tokens"].reshape(-1).tolist())
        flat_tgts.extend(batch["targets"].reshape(-1).tolist())
    n = len(flat_toks)
    assert n > 0 and n % 64 == 0
    # Window w starts at position w*T of the stream; its targets at +1.
    for w in range(n // 32):
        assert flat_toks[w * 32: (w + 1) * 32] == \
            stream[w * 32: w * 32 + 32]
        assert flat_tgts[w * 32: (w + 1) * 32] == \
            stream[w * 32 + 1: w * 32 + 33]


def test_sft_prompt_masking():
    """prompt/completion records train on completions (+EOS) only."""
    tok = ByteTokenizer()
    recs = [{"prompt": "Q: abc", "completion": " A: de"}] * 8
    ds = PackedDataset(recs, tok, seq_len=13, batch_size=1, seed=0)
    plen = len(tok.encode("Q: abc"))
    batch = next(iter(ds.epoch(0)))
    toks = batch["tokens"][0].tolist()
    mask = batch["loss_mask"][0].tolist()
    # Document length = 6 + 6 + 1(EOS) = 13 = seq_len, so window 0 holds
    # one document PLUS one lookahead target (the next doc's first prompt
    # token).  Target positions 0..plen-2 predict prompt tokens -> masked;
    # completion + EOS -> trained; the final cross-document target is the
    # next prompt's first token -> masked again.
    assert toks[:plen] == tok.encode("Q: abc")
    assert mask[: plen - 1] == [0.0] * (plen - 1)
    assert mask[plen - 1: -1] == [1.0] * (13 - plen)
    assert mask[-1] == 0.0  # next document's prompt token


def test_shards_are_disjoint_equal_and_cover():
    """Window-level sharding: disjoint stripes, EVERY shard yields the
    same batch count (unequal counts would deadlock the collective train
    step at the epoch tail), and the union covers the capped windows."""
    tok = ByteTokenizer()
    recs = _records(30)
    # The shard-independent window basis (what every process computes).
    full = PackedDataset(recs, tok, seq_len=16, batch_size=2, seed=1)
    windows = full._windows(0)
    per_shard = len(windows) // 3
    counts = []
    for s in range(3):
        ds = PackedDataset(recs, tok, seq_len=16, batch_size=2, seed=1,
                           shard_index=s, shard_count=3)
        batches = list(ds.epoch(0))
        counts.append(len(batches))
        assert len(batches) == ds.batches_per_epoch(0)
        # Shard s's rows are exactly stripe s of the shared basis —
        # disjoint BY POSITION (content can repeat in a repetitive
        # corpus) and in order.
        rows = [row.tolist() for b in batches for row in b["tokens"]]
        expect = [w[0] for w in windows[s::3][:per_shard]]
        assert rows == expect[: len(rows)]
    assert counts[0] > 0 and len(set(counts)) == 1  # equal batch counts
    with pytest.raises(ValueError, match="shard_index"):
        PackedDataset(recs, tok, 16, 1, shard_index=3, shard_count=3)


def test_prefetch_propagates_errors_and_releases_worker():
    """A crash mid-iterator re-raises in the consumer (not a silent short
    epoch), and abandoning the generator unblocks the worker thread."""
    import threading
    import time

    def boom():
        yield {"tokens": np.zeros((1, 4), np.int32)}
        raise RuntimeError("malformed record")

    it = prefetch(boom(), depth=2)
    next(it)
    with pytest.raises(RuntimeError, match="malformed record"):
        next(it)

    n_before = threading.active_count()
    many = prefetch(iter([{"i": i} for i in range(100)]), depth=1)
    next(many)
    many.close()  # abandon: cancel flag must release the blocked worker
    deadline = time.monotonic() + 5
    while time.monotonic() < deadline:
        if threading.active_count() <= n_before:
            break
        time.sleep(0.02)
    assert threading.active_count() <= n_before


def test_determinism_and_epoch_reshuffle():
    tok = ByteTokenizer()
    ds = PackedDataset(_records(), tok, seq_len=24, batch_size=2, seed=7)
    a = [b["tokens"] for b in ds.epoch(0)]
    b = [b["tokens"] for b in ds.epoch(0)]
    c = [b["tokens"] for b in ds.epoch(1)]
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert len(a) == len(b)
    assert any(not np.array_equal(x, y) for x, y in zip(a, c))


def test_read_jsonl_and_prefetch(tmp_path):
    path = tmp_path / "d.jsonl"
    path.write_text("\n".join(json.dumps(r) for r in _records(12)) + "\n")
    tok = ByteTokenizer()
    ds = PackedDataset(read_jsonl(str(path)), tok, seq_len=16,
                       batch_size=2, seed=0)
    direct = [b["tokens"] for b in ds.epoch(0)]
    fetched = [b["tokens"] for b in prefetch(ds.epoch(0), depth=2)]
    assert len(direct) == len(fetched) > 0
    assert all(np.array_equal(x, y) for x, y in zip(direct, fetched))


def test_feeds_train_step():
    """The pipeline's batches drive a real sharded train step (dp batch
    axis) and the loss goes down over a few epochs of a tiny corpus."""
    from arks_tpu.parallel.mesh import make_mesh
    from arks_tpu.train.sft import make_train_step, train_init

    cfg = get_config("tiny")
    tok = ByteTokenizer()
    mesh = make_mesh(tensor_parallel=2, data_parallel=2,
                     devices=jax.devices()[:4])
    optimizer = optax.adamw(3e-3)
    state = train_init(cfg, jax.random.PRNGKey(0), optimizer, mesh)
    step_fn = make_train_step(cfg, optimizer, mesh)
    ds = PackedDataset(_records(16), tok, seq_len=32, batch_size=4, seed=0)
    losses = []
    for epoch in range(6):
        for batch in prefetch(ds.epoch(epoch)):
            state, loss = step_fn(state, jnp.asarray(batch["tokens"]),
                                  jnp.asarray(batch["targets"]),
                                  jnp.asarray(batch["loss_mask"]))
            losses.append(float(loss))
    assert np.mean(losses[-3:]) < np.mean(losses[:3])


def test_trainer_cli_end_to_end_with_resume(tmp_path):
    """python -m arks_tpu.train: train N steps with checkpointing, then a
    SECOND invocation resumes from the latest step and reaches the target
    — the full training surface (data + sharded step + Orbax resume)
    through the real CLI."""
    import re
    import subprocess
    import sys

    data = tmp_path / "corpus.jsonl"
    data.write_text("\n".join(json.dumps(r) for r in _records(24)) + "\n")
    ckpt = tmp_path / "run"

    def run(steps):
        r = subprocess.run(
            [sys.executable, "-m", "arks_tpu.train", "--model", "tiny",
             "--data", str(data), "--seq-len", "32", "--batch-size", "4",
             "--steps", str(steps), "--lr", "3e-3",
             "--ckpt-dir", str(ckpt), "--ckpt-every", "5",
             "--log-every", "5", "--platform", "cpu"],
            capture_output=True, text=True, timeout=120)
        assert r.returncode == 0, r.stderr[-2000:]
        return r.stderr  # logging goes to stderr

    out1 = run(10)
    assert "step 10 loss" in out1
    assert "final checkpoint at step 10" in out1

    out2 = run(20)
    assert "resumed from step 10" in out2
    assert "final checkpoint at step 20" in out2
    # Loss kept improving across the restart boundary.
    losses = [float(m) for m in re.findall(r"loss (\d+\.\d+)", out1 + out2)]
    assert len(losses) >= 4 and losses[-1] < losses[0]


def test_trainer_cli_resume_fence_rejects_changed_shape(tmp_path):
    """Resuming with different data-shaping args must FAIL LOUDLY — a
    silently different stream would break the bit-identical replay."""
    import subprocess
    import sys

    data = tmp_path / "c.jsonl"
    data.write_text("\n".join(json.dumps(r) for r in _records(24)) + "\n")

    def run(extra):
        return subprocess.run(
            [sys.executable, "-m", "arks_tpu.train", "--model", "tiny",
             "--data", str(data), "--seq-len", "32", "--steps", "2",
             "--ckpt-dir", str(tmp_path / "run"), "--platform", "cpu",
             *extra],
            capture_output=True, text=True, timeout=120)

    assert run(["--batch-size", "4"]).returncode == 0
    r = run(["--batch-size", "8"])
    assert r.returncode != 0
    assert "different data-shaping args" in r.stderr
    assert "batch_size" in r.stderr
    # Original arguments still resume fine.
    assert run(["--batch-size", "4", "--steps", "4"]).returncode == 0
