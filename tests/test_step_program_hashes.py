"""The lowered step programs of the configurations a change must NOT move
(PR 29's method): an engine a preset on the CPU, its sequential and
pipelined mixed programs lowered on their own operands, sha256 of the
StableHLO text (no source locations in it, so only the traced ops count).

The pins are the values of commit eaa1b59 (PR 32), taken with this file in
that tree.  PR 33 rewrote the dispatch of a routed layer held whole with
QUANTISED experts; ``tiny`` has no experts, and ``tiny-mla-moe`` (the
latent block) and ``tiny-swa-moe`` (window and full layers) under a share
run ``moe._batched_dispatch``'s loop as they did, int8 leaves included, so
all twelve stand.  A PR that means to change one of these
programs re-pins it and says so.
"""

import hashlib

import pytest

from arks_tpu.engine import EngineConfig, InferenceEngine
from arks_tpu.engine.tokenizer import ByteTokenizer
from arks_tpu.models import get_config

PINS = {
    "tiny.seq": "ed53d9ecb362f937",
    "tiny.seq_lp": "31d2702fc1c7b6af",
    "tiny.pipe": "a6579a2a5236124a",
    "tiny.pipe_lp": "d059fb1f2834e04b",
    "tiny-mla-moe.seq": "217d97c245413946",
    "tiny-mla-moe.seq_lp": "110bf912e3087c12",
    "tiny-mla-moe.pipe": "892ad0d2c5590329",
    "tiny-mla-moe.pipe_lp": "42fe3682f6a6855b",
    "tiny-swa-moe.seq": "cd926330e174396e",
    "tiny-swa-moe.seq_lp": "a25e688a91fafb31",
    "tiny-swa-moe.pipe": "973796d815677dce",
    "tiny-swa-moe.pipe_lp": "e428a82b85955814",
}


def _programs(eng):
    operands, _ = eng._mixed_pack.host()
    seq = (eng.params, eng._cache, eng._sampling, operands, eng._guide_dev)
    return {
        "seq": eng._mixed_fn.lower(*seq),
        "seq_lp": eng._mixed_lp_fn.lower(*seq),
        "pipe": eng._mixed_pipe_fn.lower(*eng._pipe_signature()),
        "pipe_lp": eng._mixed_pipe_lp_fn.lower(*eng._pipe_signature()),
    }


def step_program_hashes(model: str, monkeypatch) -> dict:
    monkeypatch.setenv("ARKS_PIPELINE_DEPTH", "2")
    monkeypatch.setenv("ARKS_MIXED_STEP", "auto")
    cfg = get_config(model)
    kw = dict(model=model, num_slots=2, max_cache_len=128,
              prefill_buckets=(16,), prefill_chunk=16, kv_layout="paged")
    if cfg.num_experts:
        # A share, int8 leaves, and a step of 2 + 64 rows: the sequential
        # programs take the grouped path (the batched dispatch's loop).
        cfg = cfg.with_expert_share(2, 0)
        kw.update(weight_dtype="int8", prefill_chunk=64)
    if cfg.windowed:
        kw.update(max_cache_len=256, kv_cache_dtype="bf16")
    eng = InferenceEngine(cfg, EngineConfig(**kw), ByteTokenizer())
    try:
        assert eng._pipe_warm_wait(300) == "ready"
        return {f"{model}.{name}": hashlib.sha256(
            low.as_text().encode()).hexdigest()[:16]
            for name, low in _programs(eng).items()}
    finally:
        eng.stop()


@pytest.fixture(scope="module")
def hashes():
    """Every pinned program's hash, an engine a model, built once."""
    out = {}
    with pytest.MonkeyPatch.context() as mp:
        for model in sorted({k.split(".")[0] for k in PINS}):
            out.update(step_program_hashes(model, mp))
    return out


@pytest.mark.parametrize("program", sorted(PINS))
def test_step_program_hashes_equal_to_the_parents(hashes, program):
    assert hashes[program] == PINS[program]
