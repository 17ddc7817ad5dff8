"""Sampler correctness on CPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from arks_tpu.engine import sampler as sm


def _state(batch, temperature=1.0, top_p=1.0, top_k=0, seed=0,
           vocab_size=100):
    st = sm.init_sampling_state(batch, seed, vocab_size=vocab_size)
    return st._replace(
        temperature=jnp.full((batch,), temperature, jnp.float32),
        top_p=jnp.full((batch,), top_p, jnp.float32),
        top_k=jnp.full((batch,), top_k, jnp.int32))


def test_greedy_is_argmax():
    logits = jax.random.normal(jax.random.PRNGKey(0), (4, 100))
    ids, _ = sm.sample(logits, _state(4, temperature=0.0))
    np.testing.assert_array_equal(np.asarray(ids), np.argmax(np.asarray(logits), -1))


def test_top_k_1_is_argmax():
    logits = jax.random.normal(jax.random.PRNGKey(1), (4, 100))
    ids, _ = sm.sample(logits, _state(4, temperature=1.0, top_k=1))
    np.testing.assert_array_equal(np.asarray(ids), np.argmax(np.asarray(logits), -1))


def test_tiny_top_p_is_argmax():
    logits = jax.random.normal(jax.random.PRNGKey(2), (4, 100))
    ids, _ = sm.sample(logits, _state(4, temperature=1.0, top_p=1e-6))
    np.testing.assert_array_equal(np.asarray(ids), np.argmax(np.asarray(logits), -1))


def test_sampling_respects_top_k_support():
    # With top_k=3, only the 3 highest-logit ids may ever be sampled.
    logits = jnp.tile(jnp.arange(50.0)[None], (2, 1))  # argsorted: 49,48,47
    state = _state(2, temperature=5.0, top_k=3, seed=7, vocab_size=50)
    seen = set()
    for _ in range(50):
        ids, state = sm.sample(logits, state)
        seen.update(np.asarray(ids).tolist())
    assert seen <= {47, 48, 49}
    assert len(seen) > 1  # actually samples, not greedy


def test_keys_advance():
    logits = jnp.zeros((2, 64))  # uniform: successive draws should differ
    state = _state(2, temperature=1.0, vocab_size=64)
    draws = []
    for _ in range(8):
        ids, state = sm.sample(logits, state)
        draws.append(tuple(np.asarray(ids).tolist()))
    assert len(set(draws)) > 1


def test_mixed_greedy_and_sampled_slots():
    logits = jax.random.normal(jax.random.PRNGKey(3), (2, 100))
    st = _state(2, temperature=1.0, top_k=1)
    st = st._replace(temperature=jnp.asarray([0.0, 1.0], jnp.float32))
    ids, _ = sm.sample(logits, st)
    assert int(ids[0]) == int(jnp.argmax(logits[0]))


def test_presence_frequency_penalties_suppress_repeats():
    """A strong frequency penalty makes a repeated token's adjusted logit
    lose to the runner-up; counts drive the adjustment."""
    logits = jnp.zeros((1, 10)).at[0, 3].set(5.0).at[0, 7].set(4.0)
    st = _state(1, temperature=0.0, vocab_size=10)
    st = st._replace(frequency=jnp.asarray([0.6]))
    seen = []
    for _ in range(4):
        ids, st = sm.sample(logits, st)
        tok = int(ids[0])
        seen.append(tok)
        st = sm.count_tokens(st, ids)
    # Token 3 wins until its cumulative penalty (0.6/count) crosses the
    # 1.0 logit gap: 3, 3, then 7 takes over.
    assert seen[0] == 3 and seen[1] == 3
    assert 7 in seen[2:]


def test_penalties_are_identity_at_zero():
    logits = jax.random.normal(jax.random.PRNGKey(5), (3, 100))
    st = _state(3, temperature=0.0)
    st = sm.count_tokens(st, jnp.asarray([1, 2, 3]))  # counts but no penalty
    ids, _ = sm.sample(logits, st)
    assert np.array_equal(np.asarray(ids), np.asarray(jnp.argmax(logits, -1)))


def test_np_prng_key_matches_jax():
    """The host-side key constructor must be byte-identical to
    jax.random.PRNGKey — leader admissions and follower replay both use
    it, and a mismatch would silently diverge gang sampling."""
    import jax
    import numpy as np

    from arks_tpu.engine.sampler import np_prng_key

    for seed in (0, 1, 7, 2**31 - 1, 2**31, 2**63 - 1, -1, -2**31,
                 123456789):
        np.testing.assert_array_equal(
            np_prng_key(seed), np.asarray(jax.random.PRNGKey(seed)),
            err_msg=f"seed={seed}")


def test_logit_bias_forces_and_blocks_tokens():
    """OpenAI logit_bias: +100 forces a token, -100 (or -inf-ish) removes
    it — greedy and sampled alike, through the engine end to end."""
    from arks_tpu.engine import EngineConfig, InferenceEngine
    from arks_tpu.engine.tokenizer import ByteTokenizer
    from arks_tpu.engine.types import Request, SamplingParams
    from arks_tpu.models import get_config

    cfg = get_config("tiny")
    ecfg = EngineConfig(model="tiny", num_slots=2, max_cache_len=64,
                        prefill_buckets=(8, 16), steps_per_dispatch=4)
    eng = InferenceEngine(cfg, ecfg, ByteTokenizer())
    eng.start()
    try:
        def run(bias):
            r = Request(f"b{bias}", [5, 6, 7], SamplingParams(
                max_tokens=5, temperature=0.0, ignore_eos=True,
                logit_bias=bias))
            eng.add_request(r)
            ids = []
            while True:
                out = r.outputs.get(timeout=60)
                ids.extend(out.token_ids)
                if out.finished:
                    return ids

        base = run(())
        # +100 on an arbitrary token dominates every real logit (tiny
        # random models have |logits| << 100): the whole stream pins to it.
        forced = run(((123, 100.0),))
        assert forced == [123] * 5
        # -100 on the baseline's first token evicts it everywhere.
        banned = run(((base[0], -100.0),))
        assert base[0] not in banned
    finally:
        eng.stop()


def test_min_tokens_suppresses_stop_until_minimum():
    """min_tokens holds eos/stop ids out of the distribution until the
    minimum is generated: a stop id that greedy decoding would emit early
    cannot terminate the stream before min_tokens."""
    from arks_tpu.engine import EngineConfig, InferenceEngine
    from arks_tpu.engine.tokenizer import ByteTokenizer
    from arks_tpu.engine.types import Request, SamplingParams
    from arks_tpu.models import get_config

    cfg = get_config("tiny")
    ecfg = EngineConfig(model="tiny", num_slots=2, max_cache_len=64,
                        prefill_buckets=(8, 16), steps_per_dispatch=4)
    eng = InferenceEngine(cfg, ecfg, ByteTokenizer())
    eng.start()
    try:
        def run(params):
            r = Request(f"m{params.min_tokens}{params.stop_token_ids}",
                        [5, 6, 7], params)
            eng.add_request(r)
            ids = []
            while True:
                out = r.outputs.get(timeout=60)
                ids.extend(out.token_ids)
                if out.finished:
                    return ids, out

        base, _ = run(SamplingParams(max_tokens=8, temperature=0.0,
                                     ignore_eos=True))
        stop = base[1]  # greedy would emit this as token #2
        # Without min_tokens the stream stops right there.
        early, fin = run(SamplingParams(max_tokens=8, temperature=0.0,
                                        ignore_eos=True,
                                        stop_token_ids=(stop,)))
        assert fin.finish_reason == "stop" and len(early) <= 2
        # With min_tokens=5 the stop id is suppressed until 5 tokens exist.
        late, fin5 = run(SamplingParams(max_tokens=8, temperature=0.0,
                                        ignore_eos=True, min_tokens=5,
                                        stop_token_ids=(stop,)))
        assert len(late) >= 5
        assert stop not in late[:4]
    finally:
        eng.stop()


# ---------------------------------------------------------------------------
# The window search (sampler._top_window): two stages where the shape pays,
# the one lax.top_k call elsewhere; the same values and ids either way.

def _lanes_for(vocab):
    """The fewest lanes at which the rule takes two stages for ``vocab``
    (enough values in all), so the cases stay small on the CPU."""
    return -(-sm._MIN_VALUES // vocab)


def _window_logits(kind, lanes, vocab, seed):
    rng = np.random.default_rng(seed)
    x = (rng.standard_normal((lanes, vocab)) * 3.0).astype(np.float32)
    if kind == "bf16":       # what an lm_head in bfloat16 leaves: many equal values
        return np.asarray(jnp.asarray(x).astype(jnp.bfloat16)
                          .astype(jnp.float32))
    if kind == "ties":       # half-integers: every window is full of ties
        return np.round(x * 2.0) / 2.0
    if kind == "masked":     # a grammar mask: -inf but for fewer than 64 columns
        keep = rng.integers(0, vocab, size=(lanes, 40))
        out = np.full((lanes, vocab), -np.inf, np.float32)
        np.put_along_axis(out, keep, np.take_along_axis(x, keep, 1), 1)
        return out
    return x


# (vocabulary, the form the rule must take at k = 64, at k = 8).  151,937
# and 50,257 are divided by no block width; 12,544 is too few blocks for 64
# of them to be a saving; 512 is a test model's.
_WINDOW_VOCABS = [(152064, True, True), (32000, True, True),
                  (24576, True, True), (20480, True, True),
                  (12544, False, True), (151937, True, True),
                  (50257, True, True), (512, False, False)]


@pytest.mark.parametrize("k", [64, 8])
@pytest.mark.parametrize("kind", ["f32", "bf16", "ties", "masked"])
@pytest.mark.parametrize("vocab,two64,two8", _WINDOW_VOCABS)
def test_top_window_equals_lax_top_k(vocab, two64, two8, kind, k):
    lanes = _lanes_for(vocab) if vocab > 512 else 4
    blocks = sm.window_blocks(lanes, vocab, k)
    assert bool(blocks) == (two64 if k == 64 else two8)
    if blocks:
        assert blocks == -(-vocab // sm.WINDOW_BLOCK)
    x = jnp.asarray(_window_logits(kind, lanes, vocab, seed=vocab + k))
    vals, ids = jax.jit(sm._top_window, static_argnums=1)(x, k)
    want_vals, want_ids = jax.lax.top_k(x, k)
    assert ids.dtype == want_ids.dtype
    np.testing.assert_array_equal(np.asarray(vals), np.asarray(want_vals))
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(want_ids))


def test_window_rule_follows_the_static_shape():
    # The cells' step shapes (PERF.md §6, PR 37): qwen, mixtral and solar
    # take two stages; kimi's 8 lanes and laguna's 98 blocks the one call.
    assert sm.window_blocks(192, 152064) == 1188
    assert sm.window_blocks(64, 32000) == 250
    assert sm.window_blocks(64, 24576) == 192
    assert sm.window_blocks(8, 20480) == 0
    assert sm.window_blocks(32, 12544) == 0
    # One prompt's first token, and every test model: the one call.
    assert sm.window_blocks(1, 152064) == 0
    assert sm.window_blocks(192, 512) == 0
    # Too few lanes for the fixed cost, whatever the vocabulary's blocks.
    assert sm.window_blocks(4, 152064) == 0
    jaxpr = str(jax.make_jaxpr(lambda x: sm._top_window(x, 64))(
        jnp.zeros((4, 512))))
    assert jaxpr.count("top_k") == 1 and "gather" not in jaxpr


def _mixed_lanes(vocab):
    """Greedy and sampling lanes side by side: top_k 0 / 5 / 64, top_p 0.9 /
    1.0, one lane penalised."""
    lanes = 8
    st = sm.init_sampling_state(lanes, seed=11, vocab_size=vocab)
    st = st._replace(
        temperature=jnp.asarray([0.0, 0.7, 1.0, 1.3, 0.0, 0.9, 1.0, 2.0],
                                jnp.float32),
        top_k=jnp.asarray([0, 0, 5, 64, 5, 64, 0, 5], jnp.int32),
        top_p=jnp.asarray([1.0, 0.9, 1.0, 0.9, 0.9, 1.0, 1.0, 0.9],
                          jnp.float32),
        presence=jnp.zeros((lanes,), jnp.float32).at[3].set(0.5),
        counts=st.counts.at[3, :100].set(2))
    logits = jnp.asarray(_window_logits("bf16", lanes, vocab, seed=5))
    return logits, st


@pytest.mark.parametrize("fn", ["sample", "filtered_probs", "top_logprobs"])
def test_sampler_outputs_as_with_the_one_call(fn, monkeypatch):
    """What ``sample``, ``filtered_probs`` and ``top_logprobs`` return
    through the two-stage window is what they return through the one
    ``lax.top_k`` call, bit for bit (keys included)."""
    vocab = 152064
    logits, st = _mixed_lanes(vocab)
    assert sm.window_blocks(logits.shape[0], vocab) > 0
    assert sm.window_blocks(logits.shape[0], vocab, sm.TOP_LOGPROBS_MAX) > 0

    def run():
        if fn == "sample":
            out, state = [], st
            for _ in range(3):
                ids, state = sm.sample(logits, state)
                out.append(ids)
            return out + [state.key]
        if fn == "filtered_probs":
            return list(sm.filtered_probs(logits, st))
        chosen = jnp.argmax(logits, -1).astype(jnp.int32)
        return list(sm.top_logprobs(logits, chosen))

    got = run()
    monkeypatch.setattr(sm, "_top_window", jax.lax.top_k)
    want = run()
    for g, w in zip(got, want, strict=True):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
