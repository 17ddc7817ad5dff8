"""Window and full attention layers in one model (the ``laguna`` block) on
the CPU at ``tiny-swa-moe`` size: the configuration as ``from_hf_config``
reads it and what it refuses, the two RoPEs, the softmax router's scaling
factor, a share of a routed layer against the uncut layer, the window
launch (work list, kernel, host mirror) against the XLA oracle over tables
whose entries behind the window are stale, the window layers' page pool
(what is released, and when), the engine over both pools at pipeline depth
0 and 2, and what such a model refuses by name.

The served-against-reference comparison (with the must-fail controls) is
``benchmarks/tests/test_reference_swa_moe.py``, imported into tier-1 by
``tests/test_contract_swa_moe.py``."""

import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from arks_tpu.engine import paged
from arks_tpu.models import moe, transformer as tf
from arks_tpu.models.config import ModelConfig, get_config
from arks_tpu.ops import paged_attention as pa
from arks_tpu.ops.rope import apply_rope

import harness

TINY = "tiny-swa-moe"
LAGUNA = os.path.join(harness.CONFIGS, "laguna-s-2.1-ep8")


def _published() -> dict:
    """Laguna-S-2.1's published ``config.json``: the benchmark's file with
    what its ``reduced`` lists put back (48 layers, 256 experts, the whole
    vocabulary)."""
    d = harness.published("laguna-s-2.1-ep8", num_hidden_layers=48,
                          num_experts=256, vocab_size=100352)
    for k in ("layer_types", "mlp_layer_types", "gating_types",
              "num_attention_heads_per_layer"):
        head, period = d[k][:1], d[k][1:5]
        d[k] = head + (period * 12)[:47]
    return d


# ---------------------------------------------------------------------------
# The configuration
# ---------------------------------------------------------------------------


def test_from_hf_config_reads_the_published_file_key_for_key():
    d = _published()
    assert d["layer_types"].count("full_attention") == 12
    cfg = ModelConfig.from_hf_config(d, name="laguna")
    assert cfg.windowed and not cfg.latent
    # 48 = 1 dense + 11 x (3 window + 1 full) + 3 window.
    assert (cfg.num_layers, cfg.first_k_dense, cfg.window_period,
            cfg.num_periods, cfg.window_tail) == (48, 1, 3, 11, 3)
    assert (cfg.num_full_layers, cfg.num_window_layers) == (12, 36)
    assert list(cfg.layer_kinds()) == [
        {"full_attention": "full", "sliding_attention": "window"}[k]
        for k in d["layer_types"]]
    assert (cfg.num_heads, cfg.window_num_heads, cfg.num_kv_heads,
            cfg.head_dim, cfg.sliding_window) == (48, 72, 8, 128, 512)
    assert (cfg.rope_theta, cfg.window_rope_theta,
            cfg.partial_rotary_factor) == (5e5, 1e4, 0.5)
    assert cfg.rope_hf_yarn == (128.0, 8192.0, 32.0, 1.0,
                                1.4852030263919618)
    assert cfg.attn_gate and not cfg.qkv_bias
    assert (cfg.num_experts, cfg.num_experts_per_tok,
            cfg.moe_intermediate_size, cfg.shared_expert_intermediate_size,
            cfg.intermediate_size) == (256, 10, 1024, 1024, 12288)
    assert cfg.norm_topk_prob and cfg.routed_scaling_factor == 2.5
    assert cfg.scoring_func == "softmax" and cfg.vocab_size == 100352
    # About 118 B parameters.
    assert 1.1e11 < cfg.num_params() < 1.25e11


def test_the_benchmark_configuration_is_whole_periods_and_a_share():
    cfg = ModelConfig.from_hf_config(LAGUNA, name="laguna-ep8") \
        .with_expert_share(8, 0)
    assert cfg.layer_kinds() == ("full",) + ("window",) * 3 + ("full",) \
        + (("window",) * 3 + ("full",)) * 3
    assert (cfg.num_full_layers, cfg.num_window_layers) == (5, 12)
    assert (cfg.num_experts, cfg.router_width, cfg.vocab_size) == \
        (32, 256, 12544)
    # One byte a parameter: 6.2 GB (ISSUE 32 reckoned 6.16).
    assert 6.1e9 < cfg.num_params() < 6.3e9


def test_the_tiny_preset_is_what_its_config_file_says():
    want = get_config("tiny-swa-moe")
    got = ModelConfig.from_hf_config(
        harness.published(TINY, num_experts=16), name=TINY)
    assert got == want
    shapes = jax.eval_shape(lambda k: tf.init_params(got, k),
                            jax.random.PRNGKey(0))
    n = sum(math.prod(x.shape) for x in jax.tree.leaves(shapes))
    assert n == got.num_params()


@pytest.mark.parametrize("key", ["layer_types", "sliding_window",
                                 "rope_parameters",
                                 "num_attention_heads_per_layer"])
def test_a_plain_config_with_a_window_key_is_refused_not_served_full(key):
    """Before, ``from_hf_config`` dropped these keys and served another
    model: full attention in every layer, one head count, one RoPE."""
    plain = dict(model_type="mistral", vocab_size=512, hidden_size=64,
                 intermediate_size=128, num_hidden_layers=2,
                 num_attention_heads=8, num_key_value_heads=4)
    assert ModelConfig.from_hf_config(plain, name="ok").num_layers == 2
    laguna = harness.published(TINY, num_experts=16)
    with pytest.raises(ValueError, match=key):
        ModelConfig.from_hf_config({**plain, key: laguna[key]}, name="bad")


def test_a_sliding_window_that_the_file_switches_off_is_no_window():
    """Qwen2's published files carry ``sliding_window`` beside
    ``use_sliding_window: false`` (the benchmark's qwen2.5-7b does)."""
    cfg = ModelConfig.from_hf_config(
        os.path.join(harness.CONFIGS, "qwen2.5-7b"), name="q")
    assert not cfg.windowed and cfg.num_layers == 28
    cfg = ModelConfig.from_hf_config(
        os.path.join(harness.CONFIGS, "mixtral-8x7b-l4"),
        name="m")                                   # sliding_window: null
    assert not cfg.windowed


def test_a_windowed_checkpoint_raises_by_name_instead_of_being_mis_mapped(
        tmp_path):
    from arks_tpu.models import weights
    (tmp_path / "model.safetensors").write_bytes(b"")
    cfg = get_config("tiny-swa-moe")
    with pytest.raises(weights.WindowedCheckpointError, match="per-head gate"):
        weights.load_params(cfg, str(tmp_path))
    with pytest.raises(weights.WindowedCheckpointError):
        weights.params_from_hf(cfg, str(tmp_path))


# ---------------------------------------------------------------------------
# RoPE of the two kinds; the router's scaling factor; a share of a layer
# ---------------------------------------------------------------------------


def _hf_yarn_inv_freq(rot, theta, factor, original, beta_fast, beta_slow):
    """HF ``_compute_yarn_parameters`` written out in numpy."""
    pos_freqs = theta ** (np.arange(0, rot, 2, dtype=np.float64) / rot)

    def correction_dim(turns):
        return rot * math.log(original / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), rot - 1)
    ramp = np.clip((np.arange(rot // 2) - low) / max(high - low, 0.001),
                   0, 1)
    extrapolation = 1 - ramp
    return (1 / (factor * pos_freqs)) * (1 - extrapolation) \
        + (1 / pos_freqs) * extrapolation


def test_a_full_layer_rotates_half_a_head_under_hf_yarn():
    cfg = ModelConfig.from_hf_config(_published(), name="laguna")
    factor, original, fast, slow, att = cfg.rope_hf_yarn
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 5, 2, 128), jnp.float32)
    pos = jnp.asarray([[0, 7, 511, 8191, 9015]], jnp.int32)
    got = np.asarray(apply_rope(x, pos, cfg.rope_theta,
                                cfg.rope_hf_yarn[:4], rotary_dim=64,
                                attention_factor=att))
    inv = _hf_yarn_inv_freq(64, cfg.rope_theta, factor, original, fast, slow)
    ang = np.asarray(pos, np.float64)[..., None, None] * inv
    cos, sin = np.cos(ang) * att, np.sin(ang) * att
    xn = np.asarray(x, np.float64)
    x1, x2 = xn[..., :32], xn[..., 32:64]
    want = np.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                           xn[..., 64:]], -1)
    # (float32 angles at position 9015: a few parts in a thousand.)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=5e-3)
    np.testing.assert_allclose(got[0, :3], want[0, :3], rtol=2e-4, atol=2e-4)
    # The lanes that pass through are untouched, factor and all.
    assert np.array_equal(got[..., 64:], np.asarray(x)[..., 64:])
    # Position 0 shows the attention factor alone on the rotated lanes.
    np.testing.assert_allclose(got[0, 0, :, :64],
                               np.asarray(x)[0, 0, :, :64] * att, rtol=1e-6)


def test_softmax_routing_scales_after_the_normalisation():
    cfg = get_config("tiny-swa-moe")
    logits = jax.random.normal(jax.random.PRNGKey(3), (9, 16), jnp.float32)
    vals, idx = moe.router_topk(logits, cfg)
    p = np.asarray(jax.nn.softmax(logits, axis=-1), np.float64)
    for t in range(9):
        top = np.argsort(-p[t])[:4]
        assert sorted(np.asarray(idx[t]).tolist()) == sorted(top.tolist())
        want = 2.5 * p[t, np.asarray(idx[t])] / p[t, top].sum()
        np.testing.assert_allclose(np.asarray(vals[t]), want, rtol=1e-5)
    # 1.0 (every older configuration) adds nothing to the traced program.
    import dataclasses
    one = dataclasses.replace(cfg, routed_scaling_factor=1.0)
    assert "mul" not in str(jax.make_jaxpr(
        lambda x: moe.router_topk(x, one)[0])(logits)).split("div")[-1]


# ---------------------------------------------------------------------------
# The window launch: work list, kernel, host mirror
# ---------------------------------------------------------------------------


def test_the_work_list_starts_at_the_first_page_the_window_meets():
    page, bq, w = 16, 8, 24
    pos = jnp.asarray([0, 100, 40, 7], jnp.int32)
    qlen = jnp.asarray([20, 1, 16, 0], jnp.int32)
    kw = dict(page=page, block_q=bq, num_qb=3, max_pages=16)
    seq, _, qb, plo, pages, _ = (np.asarray(x) for x in
                                 pa.build_mixed_work_list(pos, qlen,
                                                          window=w, **kw))
    _, _, _, plo0, pages0, _ = (np.asarray(x) for x in
                                pa.build_mixed_work_list(pos, qlen, **kw))
    assert np.array_equal(pages, pages0) and not plo0.any()
    n_real = 3 + 1 + 2
    for i in range(n_real):
        first_q = int(pos[seq[i]]) + int(qb[i]) * bq
        assert plo[i] == max(first_q - (w - 1), 0) // page, i
        assert plo[i] < pages[i]         # a query's window holds itself
    assert not plo[n_real:].any() and not pages[n_real:].any()
    # The host mirror counts the same pages (engine/paged.py).
    got = paged.mixed_grid_steps(np.asarray(pos), np.asarray(qlen),
                                 window=w, **kw)
    assert got == int((pages - plo)[:n_real].sum())
    assert got < paged.mixed_grid_steps(np.asarray(pos), np.asarray(qlen),
                                        **kw)


def _window_batch(kv: str, window: int, seed: int = 0):
    """A mixed batch over a pool whose table entries BEHIND the window name
    a poisoned page: a launch that read one would show it.  ``window`` is
    in sixteenths of a page (the row-write kernels want pages of 16 rows,
    128 where the pool is quantised)."""
    page = 128 if kv == "int8" else 16
    window = max(window * page // 16, 1)
    hkv, g, d, maxp, lanes = 2, 3, 16, 12, 3
    rng = np.random.default_rng(seed)
    n_pages = lanes * maxp + 1
    poison = n_pages - 1
    # Two decode lanes and a chunk that starts a sequence.
    pos0 = np.asarray([page * 37 // 8, page * 70 // 8, 0], np.int32)
    qlen = np.asarray([1, 1, page * 21 // 8], np.int32)
    tables = np.arange(lanes * maxp, dtype=np.int32).reshape(lanes, maxp)
    for s in range(lanes):
        behind = max(int(pos0[s]) - window + 1, 0) // page
        tables[s, :behind] = poison
    t = int(qlen.sum())
    token_slot = np.repeat(np.arange(lanes), qlen).astype(np.int32)
    q_start = (np.cumsum(qlen) - qlen).astype(np.int32)
    token_pos = np.concatenate([pos0[s] + np.arange(qlen[s])
                                for s in range(lanes)]).astype(np.int32)
    shape = (2, n_pages, hkv, page, d)
    if kv == "int8":
        k = rng.integers(-127, 128, shape).astype(np.int8)
        v = rng.integers(-127, 128, shape).astype(np.int8)
        ks = rng.uniform(0.005, 0.02, shape[:-1]).astype(np.float32)
        vs = rng.uniform(0.005, 0.02, shape[:-1]).astype(np.float32)
        k[:, poison], v[:, poison] = 127, 127
        ks[:, poison], vs[:, poison] = 1e4, 1e4
    else:
        k = rng.normal(size=shape).astype(np.float32)
        v = rng.normal(size=shape).astype(np.float32)
        k[:, poison], v[:, poison] = 1e4, 1e4
        ks = vs = None
    q = rng.normal(size=(t, hkv * g, d)).astype(np.float32)
    new = rng.normal(size=(2, t, hkv, d)).astype(np.float32)
    j = jnp.asarray
    return window, dict(
        q=j(q), k_new=j(new[0]), v_new=j(new[1]), k_pool=j(k),
        v_pool=j(v), tables=j(tables), token_slot=j(token_slot),
        token_pos=j(token_pos), seq_q_start=j(q_start),
        seq_q_len=j(qlen), seq_pos_start=j(pos0), layer=1,
        k_scale=None if ks is None else j(ks),
        v_scale=None if vs is None else j(vs))


@pytest.mark.parametrize("kv", ["float32", "int8"])
@pytest.mark.parametrize("window", [10, 32, 48])
def test_the_window_launch_matches_the_oracle_and_reads_no_released_page(
        kv, window):
    from arks_tpu.ops.attention import paged_mixed_update_and_attend
    window, b = _window_batch(kv, window)
    got = paged_mixed_update_and_attend(**b, impl="pallas", window=window)
    want = paged_mixed_update_and_attend(**b, impl="xla", window=window)
    assert np.isfinite(np.asarray(got[0])).all()
    assert np.abs(np.asarray(got[0])).max() < 50       # no poisoned page
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                               rtol=2e-3, atol=2e-3)
    for a, c in zip(got[1:], want[1:]):                # the rows written
        if a is not None:   # (a quantised row's scale: to a float32 ulp)
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(c, np.float32),
                rtol=1e-6, atol=1 if a.dtype == jnp.int8 else 0)
    # A dense masked softmax over the gathered rows says the same.
    full = paged_mixed_update_and_attend(
        **{**b, "tables": jnp.where(b["tables"] == b["tables"].max(), 0,
                                    b["tables"])}, impl="xla", window=0)
    assert np.abs(np.asarray(full[0]) - np.asarray(want[0])).max() > 1e-3


def test_a_window_as_wide_as_the_context_is_full_attention():
    from arks_tpu.ops.attention import paged_mixed_update_and_attend
    wide, b = _window_batch("float32", window=10**6)
    for impl in ("xla", "pallas"):
        wide = paged_mixed_update_and_attend(**b, impl=impl, window=10**6)
        none = paged_mixed_update_and_attend(**b, impl=impl)
        np.testing.assert_allclose(np.asarray(wide[0]), np.asarray(none[0]),
                                   rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# The window layers' pages
# ---------------------------------------------------------------------------


def test_a_slot_holds_at_most_the_window_and_a_step_whatever_its_context():
    assert paged.window_pages_per_slot(512, 1024, 256, 64) == 7   # ISSUE 32
    assert paged.window_pages_per_slot(512, 3, 256, 64) == 4
    assert paged.window_pages_per_slot(16, 40, 256, 4) == 2
    per = paged.window_pages_per_slot(24, 16, 8, 64)
    win = paged.WindowPages(num_slots=2, max_pages=64, page=8, window=24,
                            per_slot=per)
    pos = released = 0
    for chunk in [16] * 10 + [1] * 200:        # prefill in chunks, decode
        gone = win.cover(0, pos, chunk)
        released += gone
        first, pages = win.held(0)
        # Every page a query of this step attends is held ...
        lo = max(pos - 23, 0) // 8
        hi = (pos + chunk - 1) // 8
        assert first <= lo and first + len(pages) == hi + 1
        # ... the table names them, and nothing wholly behind is kept.
        assert list(win.tables[0, first:hi + 1]) == pages
        assert first == lo and len(pages) <= per
        assert gone == 0 or pos >= 24
        pos += chunk
    assert win.pages_in_use <= per
    assert win.unreleased_pages == hi + 1      # what no release would hold
    assert released == hi + 1 - len(win.held(0)[1])
    win.release(0)
    assert win.pages_in_use == 0 and win.unreleased_pages == 0
    assert win.alloc.free_pages == win.alloc.num_pages


def test_the_unreleased_count_is_the_pages_given_whatever_a_slot_skips():
    """``unreleased_pages`` feeds ``kv_window_resident_share``: a slot's
    part of it is every page it was GIVEN, taken off again when the slot
    is done, also where its first cover starts past position 0 or jumps a
    gap (it used to take off ``first + len(pages)`` and drifted below
    zero)."""
    win = paged.WindowPages(num_slots=2, max_pages=64, page=8, window=24,
                            per_slot=6)
    win.cover(0, 100, 16)                      # starts in page 9
    first, pages = win.held(0)
    assert first == 9 and win.unreleased_pages == len(pages) == 6
    win.cover(0, 300, 8)                       # a gap: everything goes
    assert win.held(0)[0] == 34
    given = 6 + len(win.held(0)[1])
    assert win.unreleased_pages == given
    win.cover(1, 0, 8)
    assert win.unreleased_pages == given + 1
    win.release(0)
    assert win.unreleased_pages == 1
    win.release(1)
    assert win.unreleased_pages == 0 and win.pages_in_use == 0


def test_a_page_is_never_released_while_a_dispatched_step_can_read_it():
    """Pipelined decode: the host covers from its RESOLVED length while up
    to ``depth`` dispatches run ahead of it.  Whatever page a dispatch in
    flight reads (by the work list's own bound, from the DEVICE's
    position) is still held by its slot, so no other slot can have been
    handed it."""
    page, window, depth = 8, 24, 3
    per = paged.window_pages_per_slot(window, depth + 1, page, 64)
    win = paged.WindowPages(num_slots=2, max_pages=64, page=page,
                            window=window, per_slot=per)
    resolved, released = 5, 0
    inflight: list[int] = []                   # device positions issued
    for step in range(300):
        ahead = len(inflight)
        released += win.cover(0, resolved, 1 * (ahead + 1))
        inflight.append(resolved + ahead)      # the device's own position
        first, pages = win.held(0)
        for dev_pos in inflight:
            plo = max(dev_pos - (window - 1), 0) // page
            assert first <= plo and dev_pos // page < first + len(pages), \
                (step, dev_pos, first, len(pages))
        if len(inflight) == depth or step % 7 == 0:
            inflight.pop(0)                    # the oldest resolves
            resolved += 1
    assert released > 20
    assert win.pages_in_use <= per


# ---------------------------------------------------------------------------
# The engine over both pools
# ---------------------------------------------------------------------------


def _counted(eng):
    m = eng.metrics
    return dict(
        released=m.kv_window_pages_released_total.total(),
        held_steps=m.kv_window_page_steps_total.get(state="held"),
        unreleased_steps=m.kv_window_page_steps_total.get(state="unreleased"),
        kv_full=m.mixed_kv_bytes_total.get(kind="full"),
        kv_window=m.mixed_kv_bytes_total.get(kind="window"),
        hits=m.prefix_cache_hit_tokens_total.total())


@pytest.fixture(scope="module")
def depth0_streams():
    """One drain of one engine: its streams, and what its counters rose by
    across the drain."""
    with harness.fresh(TINY) as eng:
        assert eng.resolved_config["kv_page"] == "kv+window"
        assert eng.resolved_config["pipeline_depth"] == "0"
        before = _counted(eng)
        toks, lps = harness.serve(eng)
        stats = {k: v - before[k] for k, v in _counted(eng).items()}
        stats.update(
            per_slot=eng._win.per_slot, win_pages=eng._win.alloc.num_pages,
            full_pages=eng._alloc.num_pages,
            free_after=eng._win.alloc.free_pages)
    return toks, lps, stats


def test_the_engine_releases_window_pages_behind_the_window(depth0_streams):
    toks, _, s = depth0_streams
    assert [len(toks[f"r{i}"]) for i in range(3)] == [12, 12, 12]
    # Window 16, pages of 16, a chunk of 16: 3 pages a slot at the most,
    # whatever the context (16 full pages a slot).
    assert s["per_slot"] == 3 and s["win_pages"] == 9
    assert s["full_pages"] >= 3 * 16
    # 70 + 12, 9 + 12 and 133 + 12 tokens: every page wholly behind the
    # window of the last row written went back while the sequence lived.
    want = sum(max(n + 11 - 15, 0) // 16 for n in (70, 9, 133))
    assert s["released"] == want
    assert s["free_after"] == s["win_pages"]
    assert 0 < s["held_steps"] < 0.6 * s["unreleased_steps"]
    # A window layer's launch streams the window, a full layer's the
    # context.
    assert 0 < s["kv_window"] < 0.5 * s["kv_full"]


def test_no_prefix_is_reused_for_a_model_with_window_layers(depth0_streams):
    """A matched prefix's full pages would be there and its window pages
    gone: the index is off for such a model, so the same prompt twice is
    computed twice and reads the same."""
    toks, _, s = depth0_streams
    assert s["hits"] == 0
    with harness.fresh(TINY) as eng:
        hits = eng.metrics.prefix_cache_hit_tokens_total.total()
        first, _ = harness.drain(eng, harness.requests(12)[2:])
        again, _ = harness.drain(eng, harness.requests(12)[2:])
        assert first == again
        assert first["r2"] == toks["r2"]
        assert eng.metrics.prefix_cache_hit_tokens_total.total() == hits
        assert eng._alloc.retained_pages == 0


def test_the_kernel_path_gives_the_oracle_paths_streams(depth0_streams,
                                                        monkeypatch):
    """The same engine through the ragged kernel (interpret mode here):
    the window launch over the released tables."""
    toks0, lps0, _ = depth0_streams
    monkeypatch.setenv("ARKS_ATTN_IMPL", "pallas")
    with harness.fresh(TINY) as eng:
        assert eng.resolved_config["decode_impl"] == "pallas"
        toks, lps = harness.drain(eng, harness.requests(12, logprobs=1))
        for rid in lps:
            np.testing.assert_allclose(lps[rid][:4], lps0[rid][:4],
                                       atol=5e-2)
        assert toks["r1"][:2] == toks0["r1"][:2]


# ---------------------------------------------------------------------------
# A full pool under slots x context: admission reserves pages
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("depth", ["0", "2"])
def test_a_full_pool_under_the_worst_case_admits_by_pages(depth0_streams,
                                                          depth, monkeypatch):
    """16 pages where three slots' whole contexts would be 48: the three
    requests need 7 + 3 + 11 pages (prompt + max_tokens + the rows in
    flight), so the third finds a free slot and no pages, waits at the
    head of the queue until the second is done, and every stream reads
    what the worst-case pool gives."""
    monkeypatch.setenv("ARKS_PIPELINE_DEPTH", depth)
    toks0, _, s0 = depth0_streams
    assert s0["full_pages"] >= 48
    with harness.fresh(TINY, kv_pool_pages=16) as eng:
        assert eng._alloc.num_pages == eng._pool_budget == 16
        reqs = harness.requests(12)
        need = [eng._pool_need(r, r.prompt_ids) for r in reqs]
        assert need[0] + need[1] <= 16 < sum(need)
        waited = []
        waits = eng.metrics.admission_page_waits_total.total()

        def each(e):
            harness.pool_invariant(e)
            if e._pool_waiting is not None:
                waited.append(e._pool_waiting[0].request_id)
                assert len(e._free) >= 1       # a slot was free: pages not
        toks, _ = harness.drain(eng, reqs, each)
        assert toks == toks0
        assert set(waited) == {"r2"}
        m = eng.metrics
        assert m.admission_page_waits_total.total() - waits == 1
        assert m.num_requests_waiting.get() == 0
        assert not eng._pool_reserved and eng._pool_waiting is None
        assert eng._alloc.free_pages == 16 and eng.idle


def test_a_request_that_waits_for_pages_can_be_aborted():
    with harness.fresh(TINY, kv_pool_pages=16) as eng:
        reqs = harness.requests(12)
        for r in reqs:
            eng.add_request(r)
        for _ in range(50):
            eng.step()
            if eng._pool_waiting is not None:
                break
        assert eng._pool_waiting[0].request_id == "r2"
        eng.abort("r2")
        for _ in range(400):
            eng.step()
            if eng.idle:
                break
        assert eng.idle and eng._pool_waiting is None
        out = []
        while not reqs[2].outputs.empty():
            out.append(reqs[2].outputs.get())
        assert [o.finish_reason for o in out if o.finished] == ["abort"]


def test_kv_pool_pages_is_refused_by_name_where_nothing_reserves_pages():
    with pytest.raises(ValueError, match="one whole context"):
        harness.engine(TINY, kv_pool_pages=15)    # 256 tokens = 16 pages
    with pytest.raises(ValueError, match="one whole context"):
        harness.engine(TINY, kv_pool_pages=49)    # over every slot's
    with pytest.raises(ValueError, match="reserves pages"):
        harness.engine("tiny", kv_pool_pages=16, kv_layout="paged",
                        weight_dtype="bf16")


# ---------------------------------------------------------------------------
# The sequential step's second, smaller shape
# ---------------------------------------------------------------------------


def _shapes_drain(eng, reqs):
    """Drain ``reqs``, recording (prefill rows of the shape, rows the
    prefilling sequences still had) of every sequential dispatch."""
    seen = []
    shape = eng._mixed_shape

    def spy():
        pack, budget = shape()
        seen.append((budget, sum(len(st.ids) - st.pos
                                 for st in eng._prefilling.values())))
        return pack, budget
    eng._mixed_shape = spy
    toks, lps = harness.drain(
        eng, reqs, harness.held_invariant if eng._win else None)
    return toks, lps, seen


@pytest.mark.parametrize("model", ["tiny-swa-moe", "tiny"])
def test_a_short_sequential_step_takes_the_tail_shape(model, monkeypatch):
    """A budget of four pages: a step whose prompts have a page of rows
    left or fewer (none at all included) runs the quarter-size program,
    every other the whole budget's; both are compiled before the first
    dispatch, and the streams are those of the one-shape engine."""
    monkeypatch.setenv("ARKS_MIXED_CHUNK_TOKENS", "64")
    over = {} if model == TINY else dict(
        num_slots=3, kv_layout="paged", weight_dtype="bf16")
    runs = {}
    for tail in (True, False):
        # Fresh: ``_mixed_shape`` is patched, the tail pack taken away, and
        # the programs an engine has compiled are counted from none.
        with harness.fresh(model, **over) as eng:
            assert eng._mixed_budget == 64 and eng._mixed_tail == 16
            if not tail:                       # the one-shape engine
                eng._mixed_tail_pack, eng._mixed_tail_warm = None, True
            runs[tail] = _shapes_drain(eng, harness.requests(12, logprobs=1))
            assert eng._mixed_tail_warm
            # Programs compiled: the tail shape's two at the first
            # dispatch, the whole budget's as a step asks for it (these
            # requests ask for log-probabilities).
            sizes = (eng._mixed_fn._cache_size(),
                     eng._mixed_lp_fn._cache_size())
            assert sizes == ((1, 2) if tail else (0, 1)), sizes
    toks, lps, seen = runs[True]
    toks1, lps1, seen1 = runs[False]
    assert toks == toks1
    for rid in lps:
        # (a share's expert batch and the kernel's query blocks follow the
        # step's rows, so sums are taken in another order: bf16 noise)
        np.testing.assert_allclose(lps[rid], lps1[rid], atol=5e-2)
    assert {b for b, _ in seen1} == {64}
    assert {b for b, _ in seen} == {16, 64}
    for budget, left in seen:
        assert budget == (16 if left <= 16 else 64)
    # 70 + 9 + 133 prompt rows: more of the steps are short than long.
    assert sum(b == 16 for b, _ in seen) >= sum(b == 64 for b, _ in seen)


def test_a_budget_under_four_pages_keeps_one_shape():
    with harness.fresh(TINY) as eng:
        assert eng._mixed_budget == 16 and eng._mixed_tail == 0
        assert eng._mixed_tail_pack is None and eng._mixed_tail_warm


# ---------------------------------------------------------------------------
# The benchmark's window readers (benchmarks/kernels, layer_metrics)
# ---------------------------------------------------------------------------


def test_the_window_kernels_work_counts_unmasked_pairs_and_window_bytes():
    from benchmarks.kernels import paged_window_attention as k
    for q, ctx, w in [(1, 1, 8), (1, 500, 8), (5, 5, 8), (20, 20, 8),
                      (7, 30, 8), (16, 16, 16), (3, 9, 512), (1024, 4096, 512)]:
        want = sum(min(p + 1, w) for p in range(ctx - q, ctx))
        assert k.pairs(q, ctx, w) == want, (q, ctx, w)
    one = k.work(heads=72, kv_heads=8, head_dim=128, layers=12, window=512,
                 kv_bytes=1.0, kv_scale_bytes=4.0, calls=[(1, 9000)])
    # A decode row at 9000 tokens reads 512 keys, not 9000.
    assert one["flops"] == 12 * 4.0 * 512 * 72 * 128
    assert one["bytes"] == 12 * (2.0 * 512 * 8 * 132 + 2.0 * 72 * 128 * 2)
    far = k.work(heads=72, kv_heads=8, head_dim=128, layers=12, window=512,
                 kv_bytes=1.0, kv_scale_bytes=4.0, calls=[(1, 16000)])
    assert far == one


@pytest.mark.parametrize("name", [
    "window_attn_roofline.tput", "window_attn_share.tput",
    "full_attn_share.tput", "kv_window_resident_share"])
def test_a_window_reader_finds_nothing_in_a_program_without_window_layers(
        name):
    """The driver lays this PR's benchmark files over the parent's
    checkout: there the readers return None and do not raise."""
    from benchmarks import manifest
    read = manifest.load_reader(name)
    ctx = {"device": {"ops": [], "busy_s": 1.0, "xplane": None,
                      "slice_monotonic": (0.0, 1.0)},
           "metrics_open": {}, "metrics_close": {}, "cell": {}, "run": {},
           "engine": None, "kind": "TPU v5 lite"}
    try:
        assert read(ctx) is None
    except (TypeError, OSError):
        # _scopes walks a trace file: none here, which a rehearsal has.
        assert "share" in name
    assert read({**ctx, "device": None}) is None


def test_the_resident_share_reads_the_page_steps_counters():
    from benchmarks import manifest
    read = manifest.load_reader("kv_window_resident_share")
    n = "kv_window_page_steps_total"
    ctx = {"metrics_open": {n: [({"state": "held"}, 100.0),
                                ({"state": "unreleased"}, 400.0)]},
           "metrics_close": {n: [({"state": "held"}, 400.0),
                                 ({"state": "unreleased"}, 1600.0)]}}
    assert read(ctx) == 25.0
