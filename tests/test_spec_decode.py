"""Speculative decoding: draft proposes, the target verifies the block as
ragged q_len=draft_len rows of the MIXED dispatch (one program per
iteration serves decode feeds + prefill chunks + spec verify).

The load-bearing invariants:
- GREEDY speculative output is IDENTICAL to target-only output on the
  same (paged/mixed) engine — the draft only changes how many tokens land
  per dispatch, never which tokens — at pipeline depths 0 AND 2, with
  guided requests active in the same batch.
- Sampled output is exact in DISTRIBUTION
  (test_speculative_accept_distribution_exact) and deterministic per seed.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from arks_tpu.engine import EngineConfig, InferenceEngine, Request, SamplingParams
from arks_tpu.engine.tokenizer import ByteTokenizer
from arks_tpu.models import get_config, transformer as tf

import harness


_drive = harness.drive


_collect = harness.collect


def _mk_engine(draft_model, depth=0, draft_len=4, shared_params=None,
               monkeypatch=None, **kw):
    """Spec engines require the mixed scheduler (paged + chunked prefill);
    baselines run the SAME engine shape without a draft so exactness
    comparisons are apples-to-apples."""
    if monkeypatch is not None:
        monkeypatch.setenv("ARKS_PIPELINE_DEPTH", str(depth))
    cfg = get_config("tiny")
    defaults = dict(model="tiny", num_slots=4, max_cache_len=64,
                    prefill_buckets=(16, 32), steps_per_dispatch=4,
                    prefill_chunk=16, kv_layout="paged",
                    draft_model=draft_model, draft_len=draft_len,
                    prefix_cache_mb=0)
    defaults.update(kw)
    ecfg = EngineConfig(**defaults)
    ekw = {}
    if shared_params is not None:
        ekw["params"] = shared_params
        if draft_model:
            ekw["draft_params"] = shared_params
            ekw["draft_cfg"] = cfg
    eng = InferenceEngine(cfg, ecfg, ByteTokenizer(), **ekw)
    if depth:
        assert eng._pipe_warm_wait(120) == "ready", eng._pipe_warm_state
    return cfg, eng


def _run(draft_model, prompts, max_tokens=12, temperature=0.0, seed=None,
         draft_len=4, depth=0, shared_params=None, monkeypatch=None, **kw):
    cfg, eng = _mk_engine(draft_model, depth=depth, draft_len=draft_len,
                          shared_params=shared_params,
                          monkeypatch=monkeypatch, **kw)
    reqs = [Request(f"r{i}", p, SamplingParams(
        max_tokens=max_tokens, temperature=temperature, seed=seed,
        ignore_eos=True)) for i, p in enumerate(prompts)]
    for r in reqs:
        eng.add_request(r)
    _drive(eng)
    return [_collect(r)[0] for r in reqs], eng


PROMPTS = [[5, 6, 7, 8, 9], [20, 21, 22], [3] * 18]


def test_greedy_exactness_vs_baseline():
    """Draft ("tiny-gqa", a DIFFERENT model) -> imperfect acceptance, but
    byte-identical greedy output vs the target-only mixed engine."""
    base, beng = _run(None, PROMPTS)
    assert beng._mixed
    spec, eng = _run("tiny-gqa", PROMPTS)
    assert spec == base
    # The spec path actually ran inside the mixed dispatch.
    assert eng._spec_proposed > 0
    assert eng.resolved_config["spec_mixed"] == "true"
    text = eng.metrics.registry.render()
    assert "spec_decode_acceptance_rate" in text
    assert "spec_decode_accepted_length" in text


def test_self_draft_accepts_everything():
    """Draft sharing the target's WEIGHTS: every proposal matches, so each
    dispatch lands the full draft block and acceptance is ~100%."""
    cfg = get_config("tiny")
    params = tf.init_params(cfg, jax.random.PRNGKey(0))
    base, _ = _run(None, PROMPTS[:1], shared_params=params)
    spec, eng = _run("tiny", PROMPTS[:1], shared_params=params)
    assert spec == base
    assert eng._spec_accepted == eng._spec_proposed > 0


def test_sampled_requests_ride_spec_path():
    """temperature > 0 slots take the rejection-sampled spec path: valid
    tokens, deterministic per seed, proposals accounted."""
    cfg = get_config("tiny")
    out1, eng = _run("tiny-gqa", PROMPTS[:1], temperature=0.8, seed=3)
    assert eng._spec_proposed > 0  # the spec path DID fire
    assert len(out1[0]) == 12
    assert all(0 <= t < cfg.vocab_size for t in out1[0])
    # Same seed, same engine shape -> same token stream.
    out2, _ = _run("tiny-gqa", PROMPTS[:1], temperature=0.8, seed=3)
    assert out2 == out1


@pytest.mark.parametrize("temperature,seed", [(0.0, None), (0.8, 7)])
def test_pipeline_depth_parity(monkeypatch, temperature, seed):
    """THE tentpole gate: spec streams are byte-identical at pipeline
    depths 0 and 2 (greedy AND seeded-sampled) — the spec_pipe program
    threads accepted-length/last-token state on device with the same
    kernel math as the fresh-entry spec-mixed program."""
    cfg = get_config("tiny")
    params = tf.init_params(cfg, jax.random.PRNGKey(0))
    d0, e0 = _run("tiny", PROMPTS, max_tokens=20, temperature=temperature,
                  seed=seed, depth=0, shared_params=params,
                  monkeypatch=monkeypatch)
    d2, e2 = _run("tiny", PROMPTS, max_tokens=20, temperature=temperature,
                  seed=seed, depth=2, shared_params=params,
                  monkeypatch=monkeypatch)
    assert d0 == d2
    assert e0._spec_proposed > 0 and e2._spec_proposed > 0
    # Depth 2 actually pipelined (occupancy histogram advanced).
    assert e2.metrics.pipeline_depth_occupancy._data


def test_guided_requests_speculate(monkeypatch):
    """Guided x spec compose: a guided request rides the spec path
    ENABLED (verify-aware DFA advancement), its stream byte-identical to
    the target-only guided baseline under greedy at depths 0 and 2, with
    an unguided request sharing the batch."""
    import re
    tok = ByteTokenizer()
    cfg = get_config("tiny")
    params = tf.init_params(cfg, jax.random.PRNGKey(0))

    def run(draft, depth):
        _, eng = _mk_engine(draft, depth=depth, shared_params=params,
                            monkeypatch=monkeypatch, max_cache_len=96)
        g = Request("g", tok.encode("zz"), SamplingParams(
            max_tokens=40, temperature=0.0, guide=("regex", r"ab+a")))
        plain = Request("p", [5, 6, 7], SamplingParams(
            max_tokens=20, temperature=0.0, ignore_eos=True))
        eng.add_request(g)
        eng.add_request(plain)
        _drive(eng, n_steps=1500)
        gids, gfin = _collect(g)
        pids, _ = _collect(plain)
        return gids, gfin.finish_reason, pids, eng

    g0, r0, p0, _ = run(None, 0)
    assert re.fullmatch(r"ab+a", tok.decode(g0)) and r0 == "stop"
    g1, r1, p1, eng1 = run("tiny", 0)
    assert (g1, r1, p1) == (g0, r0, p0)
    # The guided lane was spec-ENABLED and accepted drafts (self-draft).
    assert eng1._spec_accepted > 0
    g2, r2, p2, _ = run("tiny", 2)
    assert (g2, r2, p2) == (g0, r0, p0)


def test_guided_sampled_spec_respects_grammar():
    """Sampled guided requests through the spec path: grammar-valid and
    deterministic per seed (the per-position DFA mask keeps the emitted
    distribution exactly the engine's guided sampling dist)."""
    import re
    tok = ByteTokenizer()
    cfg = get_config("tiny")
    params = tf.init_params(cfg, jax.random.PRNGKey(0))

    def run():
        _, eng = _mk_engine("tiny", shared_params=params, max_cache_len=96)
        g = Request("g", tok.encode("zz"), SamplingParams(
            max_tokens=40, temperature=0.9, seed=11,
            guide=("regex", r"ab+a")))
        eng.add_request(g)
        _drive(eng, n_steps=1500)
        return _collect(g)[0]

    out1, out2 = run(), run()
    assert out1 == out2
    assert re.fullmatch(r"ab+a", tok.decode(out1))


def test_speculative_accept_distribution_exact():
    """Brute-force the rejection kernel: over many trials the emitted first
    token's empirical distribution matches the target's effective sampling
    distribution (the Leviathan guarantee), for a draft that is WRONG."""
    from arks_tpu.engine import sampler as sm

    V, K, N = 12, 3, 4000
    rng = np.random.default_rng(0)
    t_logits = jnp.asarray(rng.standard_normal((1, K, V)), jnp.float32)
    d_logits = jnp.asarray(rng.standard_normal((1, V)), jnp.float32)
    state = sm.init_sampling_state(1, seed=0, vocab_size=V)._replace(
        temperature=jnp.asarray([1.0]))

    @jax.jit
    def one_trial(key):
        keys = key[None]
        tok, q, qp, qi, keys = sm.draft_sample(d_logits, state, keys)
        # Second draft step from the same (stale) draft dist — a crude but
        # legal proposer.
        tok2, q2, qp2, qi2, keys = sm.draft_sample(d_logits, state, keys)
        drafts = jnp.stack([tok, tok2], axis=1)          # [1, K-1]
        q_sel = jnp.stack([q, q2], axis=1)
        q_probs = jnp.stack([qp, qp2], axis=1)
        q_idx = jnp.stack([qi, qi2], axis=1)
        out, counts, _, _ = sm.speculative_accept(
            drafts, q_sel, q_probs, q_idx, t_logits, state, keys)
        return out[0, 0]  # the FIRST emitted token

    keys = jax.random.split(jax.random.PRNGKey(42), N)
    toks = np.asarray(jax.vmap(one_trial)(keys))
    emp = np.bincount(toks, minlength=V) / N
    expected = np.asarray(sm.filtered_probs(t_logits[:, 0], state)[0][0])
    # Map window order back to vocab order.
    idx = np.asarray(sm.filtered_probs(t_logits[:, 0], state)[1][0])
    exp_vocab = np.zeros(V)
    exp_vocab[idx] = expected
    tv = 0.5 * np.abs(emp - exp_vocab).sum()
    assert tv < 0.05, f"total variation {tv:.3f} vs target dist"


def test_speculative_accept_guided_distribution_exact():
    """Guided variant of the kernel brute-force: with a DFA masking half
    the vocab at every state, the emitted first token matches the MASKED
    target distribution — even though the draft proposes from the
    unmasked one (forbidden proposals are always rejected; the residual
    resamples legally)."""
    from arks_tpu.engine import sampler as sm

    V, K, N = 12, 3, 4000
    rng = np.random.default_rng(1)
    t_logits = jnp.asarray(rng.standard_normal((1, K, V)), jnp.float32)
    d_logits = jnp.asarray(rng.standard_normal((1, V)), jnp.float32)
    # One guide, one state: tokens with class 0 allowed (self-loop to row
    # 0), class 1 dead.  Even token ids are forbidden.
    class_ids = jnp.asarray(
        [[1 if v % 2 == 0 else 0 for v in range(V)]], jnp.int32)  # [G, V]
    trans = jnp.asarray([[0, -1]], jnp.int32)                     # [R, C]
    gtables = (class_ids, trans)
    state = sm.init_sampling_state(1, seed=0, vocab_size=V)._replace(
        temperature=jnp.asarray([1.0]),
        guide=jnp.asarray([0], jnp.int32))

    @jax.jit
    def one_trial(key):
        keys = key[None]
        tok, q, qp, qi, keys = sm.draft_sample(d_logits, state, keys)
        tok2, q2, qp2, qi2, keys = sm.draft_sample(d_logits, state, keys)
        drafts = jnp.stack([tok, tok2], axis=1)
        q_sel = jnp.stack([q, q2], axis=1)
        q_probs = jnp.stack([qp, qp2], axis=1)
        q_idx = jnp.stack([qi, qi2], axis=1)
        out, counts, _, grow = sm.speculative_accept(
            drafts, q_sel, q_probs, q_idx, t_logits, state, keys,
            enable=jnp.asarray([True]), guide_tables=gtables)
        return out[0, 0]

    keys = jax.random.split(jax.random.PRNGKey(43), N)
    toks = np.asarray(jax.vmap(one_trial)(keys))
    assert (toks % 2 == 1).all(), "grammar-forbidden token emitted"
    emp = np.bincount(toks, minlength=V) / N
    masked = np.asarray(t_logits[0, 0])
    masked = np.where(np.arange(V) % 2 == 0, -1e30, masked)
    mstate = state._replace(guide=jnp.asarray([-1], jnp.int32))
    expected = np.asarray(sm.filtered_probs(
        jnp.asarray(masked)[None], mstate)[0][0])
    idx = np.asarray(sm.filtered_probs(
        jnp.asarray(masked)[None], mstate)[1][0])
    exp_vocab = np.zeros(V)
    exp_vocab[idx] = expected
    tv = 0.5 * np.abs(emp - exp_vocab).sum()
    assert tv < 0.05, f"total variation {tv:.3f} vs masked target dist"


def test_stop_token_mid_block():
    """A stop token inside an accepted block truncates the output there."""
    base, _ = _run(None, PROMPTS[:1], max_tokens=40)
    stop_tok = base[0][5]
    cfg, eng = _mk_engine("tiny", num_slots=2)
    req = Request("s", PROMPTS[0], SamplingParams(
        max_tokens=40, temperature=0.0, ignore_eos=True,
        stop_token_ids=[stop_tok]))
    eng.add_request(req)
    _drive(eng)
    ids, fin = _collect(req)
    assert fin.finish_reason == "stop"
    assert ids == base[0][:5]  # truncated before the stop token


def test_verify_step_matches_sequential_decode():
    """tf.verify_step stays as the multi-token scoring ORACLE (the serving
    path now rides mixed_step; tests/test_paged_attention.py closes the
    loop between the two)."""
    cfg = get_config("tiny")
    params = tf.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    B, K, L0 = 2, 4, 9
    cache_a = tf.init_cache(cfg, B, 32, jnp.float32)
    cache_b = tf.init_cache(cfg, B, 32, jnp.float32)
    toks0 = jax.random.randint(jax.random.PRNGKey(1), (1, L0), 0, cfg.vocab_size)
    _, ks, vs = tf.prefill(params, cfg, toks0, jnp.asarray([L0], jnp.int32))
    for s in range(B):
        cache_a = tf.insert(cache_a, ks, vs, jnp.asarray(s))
        cache_b = tf.insert(cache_b, ks, vs, jnp.asarray(s))
    block = jax.random.randint(jax.random.PRNGKey(2), (B, K), 0, cfg.vocab_size)
    lengths = jnp.full((B,), L0, jnp.int32)
    seq = []
    ca, ln = cache_a, lengths
    for i in range(K):
        lg, ca = tf.decode_step(params, cfg, ca, block[:, i], ln)
        seq.append(lg)
        ln = ln + 1
    seq = jnp.stack(seq, axis=1)
    ver, cb = tf.verify_step(params, cfg, cache_b, block, lengths)
    np.testing.assert_allclose(np.asarray(seq), np.asarray(ver), atol=1e-5)
    np.testing.assert_allclose(np.asarray(ca.k), np.asarray(cb.k), atol=1e-6)


def test_spec_decode_config_validation():
    """The new compatibility surface: draft_len >= 2 and pp/dp exclusion
    as before, plus the mixed-scheduler requirement — a slot layout or
    ARKS_MIXED_STEP=0 cannot host a draft model (there is no legacy spec
    scheduler to fall back to anymore)."""
    cfg = get_config("tiny")
    with pytest.raises(ValueError, match="draft_len"):
        InferenceEngine(cfg, EngineConfig(model="tiny", draft_model="tiny",
                                          draft_len=1), ByteTokenizer())
    with pytest.raises(ValueError, match="pipeline_parallel"):
        InferenceEngine(cfg, EngineConfig(model="tiny", draft_model="tiny",
                                          pipeline_parallel=2),
                        ByteTokenizer())
    with pytest.raises(ValueError, match="mixed scheduler"):
        InferenceEngine(cfg, EngineConfig(model="tiny", draft_model="tiny",
                                          kv_layout="slot",
                                          prefill_chunk=16),
                        ByteTokenizer())
    with pytest.raises(ValueError, match="mixed scheduler"):
        InferenceEngine(cfg, EngineConfig(model="tiny", draft_model="tiny",
                                          prefill_chunk=None,
                                          kv_layout="paged"),
                        ByteTokenizer())


def test_spec_mixed_env_off_rejected(monkeypatch):
    monkeypatch.setenv("ARKS_MIXED_STEP", "0")
    cfg = get_config("tiny")
    with pytest.raises(ValueError, match="mixed scheduler"):
        InferenceEngine(cfg, EngineConfig(model="tiny", draft_model="tiny",
                                          kv_layout="paged",
                                          prefill_chunk=16),
                        ByteTokenizer())


def test_auto_layout_resolves_paged_for_draft_engines():
    """kv_layout=auto resolves to paged for draft engines even on CPU —
    speculation requires the mixed scheduler, and "auto" must not turn a
    valid spec config into an init error off-TPU."""
    _, eng = _mk_engine("tiny-gqa", kv_layout="auto")
    assert eng._paged and eng._mixed
    _, base = _mk_engine(None, kv_layout="auto")
    assert not base._paged  # non-draft CPU engines keep the slot layout


def test_mixed_batch_greedy_exactness():
    """Greedy and sampled slots share spec dispatches (rejection kernel
    handles both); the greedy request's output must STILL be byte-identical
    to the target-only baseline."""
    base, _ = _run(None, [PROMPTS[0]], max_tokens=20)
    cfg, eng = _mk_engine("tiny-gqa", num_slots=2)
    greedy = Request("g", PROMPTS[0], SamplingParams(max_tokens=20,
                                                     temperature=0.0,
                                                     ignore_eos=True))
    sampled = Request("s", PROMPTS[1], SamplingParams(max_tokens=20,
                                                      temperature=0.9,
                                                      seed=1,
                                                      ignore_eos=True))
    eng.add_request(greedy)
    eng.add_request(sampled)
    _drive(eng)
    g_ids, _ = _collect(greedy)
    s_ids, _ = _collect(sampled)
    assert eng._spec_proposed > 0      # mixed batch rode the spec path
    assert g_ids == base[0]            # greedy exactness survives company
    assert len(s_ids) == 20
    assert all(0 <= t < cfg.vocab_size for t in s_ids)


def test_long_prompt_skips_draft_prefill():
    """Prompts beyond the one-shot buckets skip the (monolithic) draft
    prefill; the lane rides the dispatch permanently DISABLED — still
    correct, only the draft speedup is forfeited."""
    cfg, eng = _mk_engine("tiny-gqa", num_slots=2, prefill_buckets=(16,))
    long_prompt = [int(x) % cfg.vocab_size for x in range(3, 45)]  # 42 > 16
    r = Request("l", long_prompt, SamplingParams(max_tokens=4,
                                                 temperature=0.0,
                                                 ignore_eos=True))
    eng.add_request(r)
    _drive(eng)
    ids, fin = _collect(r)
    assert fin.num_prompt_tokens == 42 and len(ids) == 4
    assert eng._spec_proposed == 0  # slot never draft-synced


def test_penalized_requests_ride_disabled():
    """Presence/frequency penalties evolve per-token counts, which the spec
    kernel doesn't model within a block — penalized slots ride the spec
    dispatch DISABLED (one penalty-correct token per dispatch), matching
    the no-draft baseline byte-for-byte."""
    base, _ = _run(None, PROMPTS[:1], max_tokens=10, temperature=0.0)
    cfg, eng = _mk_engine("tiny-gqa", num_slots=2)
    req = Request("pen", PROMPTS[0], SamplingParams(
        max_tokens=10, temperature=0.0, ignore_eos=True,
        frequency_penalty=1.0))
    eng.add_request(req)
    _drive(eng)
    ids, _ = _collect(req)
    assert len(ids) == 10
    assert eng._spec_proposed == 0  # the only slot was disabled

    # And the penalized stream matches a penalized no-draft baseline.
    _, beng = _mk_engine(None, num_slots=2)
    breq = Request("pen", PROMPTS[0], SamplingParams(
        max_tokens=10, temperature=0.0, ignore_eos=True,
        frequency_penalty=1.0))
    beng.add_request(breq)
    _drive(beng)
    bids, _ = _collect(breq)
    assert ids == bids


def test_mixed_penalized_batch_keeps_speculating():
    """One penalized request must NOT drop the whole batch off the
    speculative path — clean slots keep speculating (per-slot enable mask)
    while the penalized slot advances one normally-sampled,
    penalty-correct token per dispatch.  Outputs of BOTH must match their
    no-draft baselines (greedy byte-exactness)."""
    cfg = get_config("tiny")
    params = tf.init_params(cfg, jax.random.PRNGKey(0))

    def run(draft):
        _, eng = _mk_engine(draft, shared_params=params)
        pen = Request("pen", PROMPTS[0], SamplingParams(
            max_tokens=10, temperature=0.0, ignore_eos=True,
            frequency_penalty=1.0))
        clean = Request("clean", PROMPTS[1], SamplingParams(
            max_tokens=10, temperature=0.0, ignore_eos=True))
        eng.add_request(pen)
        eng.add_request(clean)
        _drive(eng)
        return _collect(pen)[0], _collect(clean)[0], eng

    base_pen, base_clean, _ = run(None)
    spec_pen, spec_clean, eng = run("tiny")  # self-draft: accepts everything
    assert spec_clean == base_clean
    assert spec_pen == base_pen
    # Speculation actually ran for the clean slot despite the penalized one.
    assert eng._spec_proposed > 0
    assert eng._spec_accepted > 0


def test_mixed_logprob_batch_keeps_speculating():
    """A logprob-bearing request rides the spec dispatch disabled: it gets
    one token + logprob entry per dispatch while clean slots speculate."""
    cfg, eng = _mk_engine("tiny")
    lp_req = Request("lp", PROMPTS[0], SamplingParams(
        max_tokens=6, temperature=0.0, ignore_eos=True, logprobs=2))
    clean = Request("clean", PROMPTS[1], SamplingParams(
        max_tokens=10, temperature=0.0, ignore_eos=True))
    eng.add_request(lp_req)
    eng.add_request(clean)
    _drive(eng)
    ids, lps = [], []
    while True:
        out = lp_req.outputs.get(timeout=60)
        ids.extend(out.token_ids)
        if out.logprobs:
            lps.extend(out.logprobs)
        if out.finished:
            break
    clean_ids, _ = _collect(clean)
    assert len(ids) == 6 and len(clean_ids) == 10
    assert eng._spec_proposed > 0
    # Full logprob stream for the disabled slot: one entry per token, each
    # a (chosen_logprob <= 0, top list) pair.
    assert len(lps) == 6
    assert all(entry[0] <= 0 and len(entry[1]) == 2 for entry in lps)


# ---------------------------------------------------------------------------
# Paged mechanics under speculative decoding (prefix sharing, page release,
# page-boundary-crossing verify blocks)
# ---------------------------------------------------------------------------


def test_paged_spec_page_hygiene():
    """All request pages released after finish (no leak through the spec
    write path); verify blocks cross page boundaries (page 16, 20
    generated tokens) and the spec path actually fires."""
    base, _ = _run(None, PROMPTS, max_tokens=20)
    spec, eng = _run("tiny-gqa", PROMPTS, max_tokens=20)
    assert spec == base
    assert eng._paged
    assert eng._spec_proposed > 0
    assert eng._alloc.free_pages == (
        eng._alloc.num_pages - eng._alloc.retained_pages)


def test_paged_spec_prefix_sharing_stays_clean():
    """A shared prefix page must survive a sibling's speculative decode:
    the verify block writes land only in slot-owned tail pages."""
    shared = list(range(3, 23))           # 20 tokens -> one full page of 16
    prompts = [shared + [30], shared + [40]]

    def run_sequential(draft):
        cfg, eng = _mk_engine(draft, prefix_cache_mb=256)
        outs = []
        for i, p in enumerate(prompts):
            r = Request(f"r{i}", p, SamplingParams(
                max_tokens=12, temperature=0.0, ignore_eos=True))
            eng.add_request(r)
            _drive(eng)
            outs.append(_collect(r)[0])
        return outs, eng

    base, _ = run_sequential(None)
    spec, eng = run_sequential("tiny-gqa")
    assert spec == base
    assert eng._alloc.hit_tokens > 0      # the second prompt reused pages
    assert eng._spec_proposed > 0
