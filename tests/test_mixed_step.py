"""Mixed prefill+decode scheduling (ARKS_MIXED_STEP): token-exact parity
vs the legacy chunk+decode path, single-dispatch-per-step, aborts mid-
prefill, and guides publishing while mixed batches flow."""

import functools
import json
import time

import pytest

from arks_tpu.engine import EngineConfig, InferenceEngine, Request, SamplingParams
from arks_tpu.engine.tokenizer import ByteTokenizer
from arks_tpu.models import get_config

import harness

# Every op on the dispatch channel that runs the MODEL (admission state
# writes like set_slot/clear_penalties are not dispatches of the model).
MODEL_DISPATCH_OPS = {
    "mixed", "spec_mixed", "decode", "chunk", "chunk_paged", "admit_batch",
    "admit_batch_lp", "draft_prefill", "prefill_detached",
    "prefill_detached_lp", "sample_one", "sample_one_lp",
}


class RecordingDispatcher:
    def __init__(self):
        self.ops = []

    def broadcast(self, op, payload):
        self.ops.append((op, payload))


def _mk_engine(monkeypatch, mixed: str, **kw):
    monkeypatch.setenv("ARKS_MIXED_STEP", mixed)
    cfg = get_config("tiny")
    defaults = dict(model="tiny", num_slots=2, max_cache_len=64,
                    prefill_buckets=(8, 16, 32), steps_per_dispatch=4,
                    prefill_chunk=16, kv_layout="paged")
    defaults.update(kw)
    ecfg = EngineConfig(**defaults)
    return cfg, InferenceEngine(cfg, ecfg, ByteTokenizer())


_collect = functools.partial(harness.collect, logprobs=True)


_drive = harness.drive


def test_mixed_matches_legacy_token_exact(monkeypatch):
    """Mixed vs legacy must produce IDENTICAL token streams on CPU: greedy
    and fixed-seed sampled, short (one-shot-sized) and chunked prompts,
    logprobs on and off, with slot churn (more requests than slots)."""
    cfg = get_config("tiny")
    prompts = [[5, 6, 7], [3] * 20, list(range(3, 51)), [9] * 10, [4, 8]]

    def run(mixed):
        _, eng = _mk_engine(monkeypatch, mixed)
        assert eng._mixed == (mixed == "auto")
        reqs = []
        for i, p in enumerate(prompts):
            if i % 2 == 0:
                sp = SamplingParams(max_tokens=6, temperature=0.0,
                                    ignore_eos=True,
                                    logprobs=2 if i == 0 else None)
            else:
                sp = SamplingParams(max_tokens=6, temperature=0.8,
                                    top_p=0.9, top_k=40, seed=42 + i,
                                    ignore_eos=True)
            reqs.append(Request(f"r{i}", [int(x) % cfg.vocab_size for x in p],
                                sp))
        for r in reqs:
            eng.add_request(r)
        _drive(eng)
        outs = []
        for r in reqs:
            ids, lps, fin = _collect(r)
            outs.append((ids, lps, fin.finish_reason,
                         fin.num_prompt_tokens))
        return outs

    mixed, legacy = run("auto"), run("0")
    # Token streams are EXACT; logprob floats come from different compiled
    # programs (mixed forward vs prefill/decode loop) — same math,
    # blockwise, so only fp reassociation separates them.
    for (m_ids, m_lps, m_fin, m_np), (l_ids, l_lps, l_fin, l_np) in zip(
            mixed, legacy):
        assert m_ids == l_ids
        assert (m_fin, m_np) == (l_fin, l_np)
        assert len(m_lps) == len(l_lps)
        for (m_clp, m_top), (l_clp, l_top) in zip(m_lps, l_lps):
            assert abs(m_clp - l_clp) < 5e-3
            assert [t for t, _ in m_top] == [t for t, _ in l_top]
            for (_, mv), (_, lv) in zip(m_top, l_top):
                assert abs(mv - lv) < 5e-3


def test_mixed_single_model_dispatch_per_step(monkeypatch):
    """With decodes active AND a prefill chunk pending, one scheduler step
    issues EXACTLY ONE model dispatch — the acceptance criterion the whole
    tentpole exists for (legacy pays one chunk dispatch + one decode
    dispatch in that state)."""
    cfg, eng = _mk_engine(monkeypatch, "auto")
    eng.dispatcher = RecordingDispatcher()

    # A short request reaches decode...
    short = Request("s", [5, 6], SamplingParams(max_tokens=40,
                                                temperature=0.0,
                                                ignore_eos=True))
    eng.add_request(short)
    for _ in range(50):
        eng.step(block_s=0.01)
        if eng._slots:
            break
    assert eng._slots
    # ...then a long prompt starts chunked prefill (48 tokens, chunk 16).
    long_req = Request("l", [int(x) % cfg.vocab_size for x in range(3, 51)],
                       SamplingParams(max_tokens=2, temperature=0.0,
                                      ignore_eos=True))
    eng.add_request(long_req)
    for _ in range(50):
        eng.step(block_s=0.01)
        if eng._prefilling:
            break
    assert eng._slots and eng._prefilling

    pos_before = next(iter(eng._prefilling.values())).pos
    tokens_before = len(eng._slots[next(iter(eng._slots))].generated)
    eng.dispatcher.ops.clear()
    eng.step(block_s=0.01)
    model_ops = [op for op, _ in eng.dispatcher.ops
                 if op in MODEL_DISPATCH_OPS]
    assert model_ops == ["mixed"], model_ops
    # ...and that single dispatch advanced BOTH the decode and the prefill.
    assert len(eng._slots[next(iter(eng._slots))].generated) \
        == tokens_before + 1
    st = next(iter(eng._prefilling.values()), None)
    assert st is None or st.pos > pos_before
    _drive(eng)
    _collect(short)
    _collect(long_req)


def test_mixed_round_robin_spreads_budget_across_prefills(monkeypatch):
    """Two concurrent long prompts must BOTH make progress in one mixed
    step (the legacy scheduler only ever advanced the FIFO head)."""
    cfg, eng = _mk_engine(monkeypatch, "auto", num_slots=4)
    longs = [Request(f"l{i}", [(3 + i + x) % cfg.vocab_size
                               for x in range(48)],
                     SamplingParams(max_tokens=2, temperature=0.0,
                                    ignore_eos=True))
             for i in range(2)]
    for r in longs:
        eng.add_request(r)
    for _ in range(10):
        eng.step(block_s=0.01)
        if len(eng._prefilling) == 2:
            break
    assert len(eng._prefilling) == 2
    before = {s: st.pos for s, st in eng._prefilling.items()}
    eng.step(block_s=0.01)
    after = {s: st.pos for s, st in eng._prefilling.items()}
    advanced = [s for s in before if s not in after or after[s] > before[s]]
    assert len(advanced) == 2, (before, after)
    _drive(eng)
    for r in longs:
        _collect(r)


def test_mixed_abort_prefilling_between_steps(monkeypatch):
    """Aborting a sequence mid-chunked-prefill frees its slot and pages at
    the next mixed boundary and fails the request with reason=abort."""
    cfg, eng = _mk_engine(monkeypatch, "auto", prefix_cache_mb=0)
    free_pages = eng._alloc.free_pages
    long_req = Request("al", [int(x) % cfg.vocab_size for x in range(3, 51)],
                       SamplingParams(max_tokens=2, temperature=0.0,
                                      ignore_eos=True))
    eng.add_request(long_req)
    st = None
    for _ in range(30):
        eng.step(block_s=0.01)
        st = next(iter(eng._prefilling.values()), None)
        if st is not None and st.pos > 0:
            break
    assert st is not None and 0 < st.pos < len(st.ids)  # mid-prefill
    eng.abort("al")
    eng.step(block_s=0.01)
    assert not eng._prefilling
    ids, _, fin = _collect(long_req)
    assert fin.finish_reason == "abort" and not ids
    assert eng._alloc.free_pages == free_pages  # pages reclaimed
    assert len(eng._free) == eng.ecfg.num_slots

    # The engine still serves afterwards.
    ok = Request("ok", [5, 6, 7], SamplingParams(max_tokens=3,
                                                 temperature=0.0,
                                                 ignore_eos=True))
    eng.add_request(ok)
    _drive(eng)
    ids, _, fin = _collect(ok)
    assert len(ids) == 3 and fin.finish_reason == "length"


def test_mixed_guided_request_publishes_mid_batches(monkeypatch):
    """A guided request whose guide compiles WHILE mixed dispatches are in
    flight: the request parks (never blocking the scheduler), decode keeps
    flowing through mixed steps, and once the guide publishes the request
    admits through the chunked path and its output obeys the grammar."""
    cfg, eng = _mk_engine(monkeypatch, "auto", max_cache_len=96)
    eng.start()
    try:
        tok = ByteTokenizer()
        # Keep a decode stream alive for the whole compile window.
        load = Request("load", tok.encode("zz"), SamplingParams(
            max_tokens=200, temperature=0.0, ignore_eos=True))
        eng.add_request(load)
        load.outputs.get(timeout=120)  # decoding

        orig = eng.guides._build

        def slow_build(rx):
            time.sleep(1.5)
            return orig(rx)

        eng.guides._build = slow_build
        pat = r'\{"k": (true|false)\}'
        greq = Request("g", tok.encode("zz"), SamplingParams(
            max_tokens=48, temperature=0.0, guide=("regex", pat)))
        eng.add_request(greq)
        time.sleep(0.1)
        # While the compile is in flight, the mixed scheduler must keep
        # producing decode tokens (the request parks; nothing blocks).
        produced = 0
        deadline = time.monotonic() + 1.0
        while time.monotonic() < deadline:
            try:
                produced += len(load.outputs.get(timeout=0.2).token_ids)
            except Exception:
                pass
        assert produced > 0, "decode stalled behind the guide compile"
        toks = []
        while True:
            out = greq.outputs.get(timeout=120)
            toks.extend(out.token_ids)
            if out.finished:
                break
        assert out.finish_reason == "stop"
        assert json.loads(ByteTokenizer().decode(toks))["k"] in (True, False)
        eng.abort("load")
    finally:
        eng.stop()


def test_mixed_disabled_for_unsupported_engines(monkeypatch):
    """Non-paged engines stay on the legacy scheduler even when
    ARKS_MIXED_STEP=1 asks for mixed (with a warning, not a crash).
    Spec engines are different: they REQUIRE mixed and raise instead
    (tests/test_spec_decode.py::test_spec_decode_config_validation)."""
    monkeypatch.setenv("ARKS_MIXED_STEP", "1")
    cfg = get_config("tiny")
    ecfg = EngineConfig(model="tiny", num_slots=2, max_cache_len=64,
                        prefill_buckets=(8, 16, 32), steps_per_dispatch=4,
                        kv_layout="slot")
    eng = InferenceEngine(cfg, ecfg, ByteTokenizer())
    assert not eng._mixed
    assert eng.resolved_config["mixed_step"] == "false"
    req = Request("x", [5, 6, 7], SamplingParams(max_tokens=3,
                                                 temperature=0.0,
                                                 ignore_eos=True))
    eng.add_request(req)
    _drive(eng)
    ids, _, fin = _collect(req)
    assert len(ids) == 3


def test_mixed_env_validation(monkeypatch):
    monkeypatch.setenv("ARKS_MIXED_STEP", "bogus")
    cfg = get_config("tiny")
    ecfg = EngineConfig(model="tiny", num_slots=2, max_cache_len=64,
                        prefill_buckets=(8,), steps_per_dispatch=2,
                        kv_layout="paged", prefill_chunk=16)
    with pytest.raises(ValueError):
        InferenceEngine(cfg, ecfg, ByteTokenizer())


# ---------------------------------------------------------------------------
# The dispatcher the model calls, kernels against the XLA oracle
# ---------------------------------------------------------------------------

_DISPATCH_BATCHES = {
    # (lane, rows, first position), packed in this order into lanes + 16 rows
    "flood": [(0, 1, 40), (1, 1, 9), (2, 1, 77), (3, 3, 0), (4, 3, 12),
              (5, 2, 30), (6, 3, 5), (7, 2, 2)],
    "open": [(0, 1, 40), (5, 1, 9), (2, 16, 16)],
    "pipe": [(s, 1, 3 + 11 * s) for s in range(8) if s != 3],
    "empty": [],
}


@pytest.mark.parametrize("kv", ["int8", "bf16"])
@pytest.mark.parametrize("batch", sorted(_DISPATCH_BATCHES))
def test_dispatcher_kernels_match_xla_oracle(batch, kv):
    """paged_mixed_update_and_attend through the Pallas kernels (the KV
    write, the block-compacted query layout, the ragged grid) against the
    XLA oracle on the same flat batch: the written pool bit for bit, the
    attention output on every real row within rounding, padding rows
    zero."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from arks_tpu.ops.attention import paged_mixed_update_and_attend

    lanes, hkv, g, d, max_pages = 8, 2, 3, 32, 2
    page = 128 if kv == "int8" else 64
    t_flat = lanes if batch == "pipe" else lanes + 16
    n = lanes * max_pages + 1
    ks = jax.random.split(jax.random.PRNGKey(3), 8)
    if kv == "int8":
        kp = jax.random.randint(ks[0], (2, n, hkv, page, d), -127, 128,
                                jnp.int8)
        vp = jax.random.randint(ks[1], (2, n, hkv, page, d), -127, 128,
                                jnp.int8)
        kps = jax.random.uniform(ks[2], (2, n, hkv, page), jnp.float32,
                                 0.01, 0.03)
        vps = jax.random.uniform(ks[3], (2, n, hkv, page), jnp.float32,
                                 0.01, 0.03)
    else:
        kp = jax.random.normal(ks[0], (2, n, hkv, page, d), jnp.bfloat16)
        vp = jax.random.normal(ks[1], (2, n, hkv, page, d), jnp.bfloat16)
        kps = vps = None
    tables = jnp.arange(lanes * max_pages, dtype=jnp.int32).reshape(
        lanes, max_pages)
    q = jax.random.normal(ks[4], (t_flat, hkv * g, d), jnp.bfloat16)
    kn = jax.random.normal(ks[5], (t_flat, hkv, d), jnp.bfloat16)
    vn = jax.random.normal(ks[6], (t_flat, hkv, d), jnp.bfloat16)
    token_slot = np.full((t_flat,), -1, np.int32)
    token_pos = np.zeros((t_flat,), np.int32)
    q_start, q_len, pos0 = (np.zeros((lanes,), np.int32) for _ in range(3))
    t = 0
    for lane, rows, p0 in _DISPATCH_BATCHES[batch]:
        token_slot[t:t + rows] = lane
        token_pos[t:t + rows] = p0 + np.arange(rows)
        q_start[lane], q_len[lane], pos0[lane] = t, rows, p0
        t += rows

    def run(impl):
        out = paged_mixed_update_and_attend(
            q, kn, vn, kp, vp, tables, jnp.asarray(token_slot),
            jnp.asarray(token_pos), jnp.asarray(q_start),
            jnp.asarray(q_len), jnp.asarray(pos0), 1, impl=impl,
            k_scale=kps, v_scale=vps)
        return [None if x is None else np.asarray(x.astype(jnp.float32))
                for x in out]

    got, ref = run("pallas"), run("xla")
    for a, b in zip(got[1:], ref[1:]):
        assert (a is None and b is None) or np.array_equal(a, b)
    real = token_slot >= 0
    assert np.isfinite(got[0]).all()
    np.testing.assert_array_equal(got[0][~real], 0.0)
    if real.any():
        scale = np.abs(ref[0][real]).max()
        assert np.abs(got[0][real] - ref[0][real]).max() <= 0.02 * scale
