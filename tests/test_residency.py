"""Windowed-residency decode (ARKS_RESIDENCY_WINDOW_PAGES): contexts
strictly larger than the device page pool must decode BYTE-IDENTICAL to
a big-pool control engine.

The windowed engine's pool holds only ``num_slots * window`` pages; a
slot whose decode-grown context outgrows the window engages the
span-streaming path (engine/residency.py) — cold pages live in host RAM
and rotate through on-device staging halves while the resident spans
attend via the carry-chained ragged kernel.  The control engine runs the
same workload with the full logical pool resident.  Token ids, finish
reasons AND logprob floats must match exactly: the residency forward is
built from the same blocks as the mixed program (same batch shapes, same
embed/qkv/update/tail/sampler functions) with only the attend swapped
for the bitwise-proven span chain.
"""

import functools

import numpy as np
import pytest

from arks_tpu.engine import EngineConfig, InferenceEngine, Request, SamplingParams
from arks_tpu.engine.tokenizer import ByteTokenizer
from arks_tpu.models import get_config

import harness

WINDOW = 6  # pages; pool = num_slots * WINDOW


def _mk_engine(monkeypatch, *, window, depth=0, impl="pallas", **kw):
    monkeypatch.setenv("ARKS_MIXED_STEP", "1")
    monkeypatch.setenv("ARKS_ATTN_IMPL", impl)
    monkeypatch.setenv("ARKS_PIPELINE_DEPTH", str(depth))
    if window:
        monkeypatch.setenv("ARKS_RESIDENCY_WINDOW_PAGES", str(window))
    else:
        monkeypatch.delenv("ARKS_RESIDENCY_WINDOW_PAGES", raising=False)
    eng = harness.warmed("tiny", base=dict(
        num_slots=1, max_cache_len=256, prefill_buckets=(8, 16, 32),
        steps_per_dispatch=4, prefill_chunk=16, kv_layout="paged",
        prefix_cache_mb=0), **kw)
    return eng.cfg, eng


_drive = harness.drive


_collect = functools.partial(harness.collect, logprobs=True)


# Prompt (40 tokens, chunked prefill) + 70 decode tokens = 110-token
# final context: strictly larger than the windowed pool (6 pages x 16 =
# 96 tokens) while fitting the control's full 256-token table.
PROMPT_LEN, GEN = 40, 70


def _run_one(monkeypatch, *, window, depth, seeded):
    cfg, eng = _mk_engine(monkeypatch, window=window, depth=depth)
    prompt = [int(x) % cfg.vocab_size for x in range(3, 3 + PROMPT_LEN)]
    if seeded:
        sp = SamplingParams(max_tokens=GEN, temperature=0.8, top_p=0.9,
                            top_k=40, seed=17, ignore_eos=True)
    else:
        sp = SamplingParams(max_tokens=GEN, temperature=0.0,
                            ignore_eos=True, logprobs=2)
    req = Request("lc", prompt, sp)
    eng.add_request(req)
    _drive(eng)
    ids, lps, fin = _collect(req)
    return (ids, lps, fin.finish_reason), eng


_RUNS: dict = {}


def _run_once(monkeypatch, **kw):
    """``_run_one``, made once a configuration for the cases that read the
    same run (the environment only matters while the engine is built)."""
    key = tuple(sorted(kw.items()))
    if key not in _RUNS:
        _RUNS[key] = _run_one(monkeypatch, **kw)
    return _RUNS[key]


@pytest.mark.parametrize("depth,seeded", [
    (0, False),
    pytest.param(0, True, marks=pytest.mark.slow),
    pytest.param(2, False, marks=pytest.mark.slow),
    pytest.param(2, True, marks=pytest.mark.slow),
], ids=["d0-greedy-lp", "d0-seeded", "d2-greedy-lp", "d2-seeded"])
def test_long_context_byte_identity_vs_big_pool_control(
        monkeypatch, depth, seeded):
    """The acceptance gate: a decode-grown context STRICTLY larger than
    the windowed engine's device pool emits a token stream (and logprob
    floats) byte-identical to a control engine whose pool holds the whole
    context resident — at pipeline depths 0 and 2."""
    got, eng = _run_once(monkeypatch, window=WINDOW, depth=depth,
                         seeded=seeded)
    base, _ = _run_one(monkeypatch, window=0, depth=depth, seeded=seeded)

    # The context really outgrew the windowed pool.
    final_len = PROMPT_LEN + len(got[0])
    pool_tokens = eng._alloc.num_pages * eng._page_size()
    assert final_len > pool_tokens, (final_len, pool_tokens)
    # ...and the span path actually ran.
    assert eng.metrics.residency_spans_total.total() > 0
    assert eng.metrics.residency_prefetch_pages_total.total() > 0

    assert got[0] == base[0], "token stream diverged from the control"
    assert got[2] == base[2] == "length"
    assert got[1] == base[1], "logprobs diverged from the control"


def test_prefetch_is_issued_ahead_of_the_attend_that_needs_it(monkeypatch):
    """The overlap schedule, from the flight recorder's residency.prefetch
    / residency.attend pairs (their arg is the page span): a prefetch is
    issued AHEAD when the very next attend dispatched after it is over
    another span, so the scatter for span i+1 is on the device stream
    before span i's attend runs and never serialises with its consumer."""
    _, eng = _run_once(monkeypatch, window=WINDOW, depth=0, seeded=False)
    evs = [e for e in eng.trace.tail(65536)
           if e["name"] in ("residency.prefetch", "residency.attend")]
    n = {"residency.prefetch": 0, "residency.attend": 0}
    ahead, pending = 0, []
    for e in evs:
        if e["ph"] != "B":
            continue
        n[e["name"]] += 1
        if e["name"] == "residency.prefetch":
            pending.append(e["arg"])
        else:
            ahead += sum(1 for a in pending if a != e["arg"])
            pending.clear()
    assert n["residency.prefetch"] > 0 and n["residency.attend"] > 0, n
    assert ahead > 0, "no prefetch landed ahead of its consuming attend"


@pytest.mark.slow
def test_residency_slot_releases_pages_on_finish(monkeypatch):
    """After a windowed stream finishes, its staging + tail pages return
    to the allocator and the manager drops the slot — a fresh request
    then admits and completes on the same engine."""
    got, eng = _run_one(monkeypatch, window=WINDOW, depth=0, seeded=False)
    assert not eng._residency.slots
    assert eng._alloc.free_pages == eng._alloc.num_pages
    nxt = Request("post", [5, 6, 7], SamplingParams(
        max_tokens=4, temperature=0.0, ignore_eos=True))
    eng.add_request(nxt)
    _drive(eng)
    ids, _, fin = _collect(nxt)
    assert len(ids) == 4 and fin.finish_reason == "length"


def test_prompt_larger_than_window_is_rejected(monkeypatch):
    """Windowed residency streams DECODE-grown context; a prompt that
    cannot fit the resident window is rejected at admission with
    context_length_exceeded (not a crash deep inside the allocator)."""
    cfg, eng = _mk_engine(monkeypatch, window=WINDOW)
    too_long = [5] * (WINDOW * 16 + 1)  # page=prefill_chunk=16
    req = Request("big", [int(x) % cfg.vocab_size for x in too_long],
                  SamplingParams(max_tokens=2, temperature=0.0,
                                 ignore_eos=True))
    eng.add_request(req)
    _drive(eng)
    out = req.outputs.get(timeout=60)
    assert out.finished and out.finish_reason == "error"
    assert out.error == "context_length_exceeded"


def test_residency_config_validation(monkeypatch):
    """The window knob's failure modes are startup ValueErrors, not
    latent dispatch crashes: windows below 4 pages can't hold the
    2-tail + 2-staging-half layout; the span chain needs the Pallas
    ragged path; spec decode's draft cache has no windowed story."""
    cfg = get_config("tiny")

    def mk(**kw):
        defaults = dict(model="tiny", num_slots=1, max_cache_len=256,
                        prefill_buckets=(8, 16, 32), steps_per_dispatch=4,
                        prefill_chunk=16, kv_layout="paged",
                        prefix_cache_mb=0)
        defaults.update(kw)
        return InferenceEngine(cfg, EngineConfig(**defaults),
                               ByteTokenizer())

    monkeypatch.setenv("ARKS_MIXED_STEP", "1")
    monkeypatch.setenv("ARKS_ATTN_IMPL", "pallas")
    monkeypatch.setenv("ARKS_RESIDENCY_WINDOW_PAGES", "3")
    with pytest.raises(ValueError, match=">= 4"):
        mk()
    monkeypatch.setenv("ARKS_RESIDENCY_WINDOW_PAGES", "-1")
    with pytest.raises(ValueError, match=">= 0"):
        mk()
    monkeypatch.setenv("ARKS_RESIDENCY_WINDOW_PAGES", str(WINDOW))
    monkeypatch.setenv("ARKS_ATTN_IMPL", "xla")
    with pytest.raises(ValueError, match="pallas"):
        mk()
    monkeypatch.setenv("ARKS_ATTN_IMPL", "pallas")
    with pytest.raises(ValueError, match="speculative"):
        mk(draft_model="tiny", draft_len=3)
    # A window >= the logical table width is a no-op, not an error.
    monkeypatch.setenv("ARKS_RESIDENCY_WINDOW_PAGES", "64")
    eng = mk()
    assert eng._residency is None
    assert eng._alloc.num_pages == eng._max_pages * eng.ecfg.num_slots


def test_window_smaller_pool_is_allocated(monkeypatch):
    """The pool shrinks to num_slots * window pages while the logical
    tables keep the full max_cache_len width — the whole point: device
    HBM no longer scales with the model's context length."""
    cfg, eng = _mk_engine(monkeypatch, window=WINDOW, num_slots=2)
    assert eng._alloc.num_pages == 2 * WINDOW
    assert eng._tables.shape == (2, eng._max_pages)
    assert eng._max_pages == 256 // 16
