"""Linear-attention layers beside gated NoPE GQA layers (the ``solar_open2``
block) on the CPU at ``tiny-linear-moe`` size: the configuration as
``from_hf_config`` reads it and what it refuses, the delta rule's chunked
scan against the one-step recurrence on ragged batches, the step program
against the reference family's full forward (a prompt cut at odd lengths,
decode and prefill lanes in one batch, a slot another sequence just left),
a share of a routed layer against the uncut layer, the slots' state holder,
the engine at pipeline depth 0 and 2, and what such a model refuses by name.

The served-against-reference comparison (with the must-fail controls) is
``benchmarks/tests/test_reference_linear_moe.py``, imported into tier-1 by
``tests/test_contract_linear_moe.py``."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from arks_tpu.models import transformer as tf
from arks_tpu.models.config import ModelConfig, get_config

import harness

TINY = "tiny-linear-moe"
SOLAR = "solar-open2-250b-ep8-l8"


def _published() -> dict:
    """Solar-Open2-250B's published ``config.json``: the benchmark's file
    with what its ``reduced`` lists put back (48 layers, 320 experts, the
    whole vocabulary, a GQA layer every fourth)."""
    return harness.published(
        SOLAR, num_hidden_layers=48, n_routed_experts=320, vocab_size=196608,
        gqa_layers=list(range(0, 48, 4)))


# ---------------------------------------------------------------------------
# The configuration
# ---------------------------------------------------------------------------


def test_from_hf_config_reads_the_published_file_key_for_key():
    cfg = ModelConfig.from_hf_config(_published(), name="solar")
    kinds = cfg.layer_kinds()
    assert [i for i, k in enumerate(kinds) if k == "full"] \
        == list(range(0, 48, 4))
    assert kinds.count("linear") == 36 == cfg.num_linear_layers
    assert (cfg.head_layers, cfg.num_periods, cfg.inner_tail) == (1, 11, 3)
    assert cfg.num_full_layers == 12 and cfg.num_window_layers == 0
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (64, 8, 128)
    assert (cfg.linear_num_heads, cfg.linear_head_dim, cfg.linear_conv) \
        == (64, 128, 4)
    assert cfg.linear_neg_eigval and cfg.attn_out_gate and not cfg.use_rope
    assert not cfg.attn_gate and not cfg.windowed and not cfg.latent
    assert (cfg.num_experts, cfg.num_experts_per_tok,
            cfg.moe_intermediate_size, cfg.n_shared_experts) \
        == (320, 8, 1280, 1)
    assert cfg.scoring_func == "sigmoid" and cfg.norm_topk_prob
    assert cfg.routed_scaling_factor == 1.0 and cfg.first_k_dense == 0
    assert cfg.num_routed_layers == 48
    assert cfg.max_position_embeddings == 1048576
    # 250 B: every layer's 320 experts of 3 x 4096 x 1280 are 242 B of it.
    assert 245e9 < cfg.num_params() < 256e9


def test_the_benchmark_configuration_is_whole_periods_and_a_share():
    deploy = harness.deploy(SOLAR)
    share = deploy["share"]
    cfg = ModelConfig.from_hf_config(
        os.path.join(harness.CONFIGS, SOLAR), name="s").with_expert_share(
        share["chips_per_layer"], share["index"])
    assert cfg.layer_kinds() == ("full",) + ("linear",) * 3 + ("full",) \
        + ("linear",) * 3
    assert (cfg.num_experts, cfg.router_width) == (40, 320)
    assert cfg.vocab_size * 8 == share["published"]["vocab_size"]
    assert 6.3e9 < cfg.num_params() < 6.5e9     # one byte a parameter
    assert deploy["state_dtype"] == "float32"
    pub, here = _published(), harness.published(SOLAR)
    assert sorted(k for k in pub if pub[k] != here[k]) \
        == sorted(deploy["reduced"])


def test_the_tiny_preset_is_what_its_config_file_says():
    cfg = ModelConfig.from_hf_config(
        harness.published(TINY, n_routed_experts=16), name=TINY)
    assert cfg == get_config("tiny-linear-moe")
    assert cfg.layer_kinds() == ("full", "linear", "linear") * 3


@pytest.mark.parametrize("key, value", [
    ("linear_attn_config", dict(num_heads=4, head_dim=16)),
    ("gqa_layers", [0, 4]), ("gqa_interval", 3), ("use_gqa_gate", True),
    ("kda_allow_neg_eigval", True), ("kda_use_full_proj", True),
    ("use_rope", False)])
@pytest.mark.parametrize("model_type", ["qwen2", "deepseek_v3"])
def test_a_linear_key_under_another_model_type_is_refused_by_name(
        key, value, model_type):
    """Before, such a file was read as a RoPE GQA model with softmax
    attention in every layer (or, with ``n_routed_experts``, handed to the
    latent reader)."""
    d = dict(model_type=model_type, vocab_size=512, hidden_size=64,
             intermediate_size=128, num_hidden_layers=2,
             num_attention_heads=4, n_routed_experts=8, **{key: value})
    with pytest.raises(ValueError, match=key):
        ModelConfig.from_hf_config(d, name="m")


def test_a_linear_checkpoint_raises_by_name_instead_of_being_mis_mapped(
        tmp_path):
    from arks_tpu.models import weights
    (tmp_path / "model.safetensors").write_bytes(b"")
    cfg = get_config("tiny-linear-moe")
    with pytest.raises(weights.LinearCheckpointError, match="delta-rule"):
        weights.load_params(cfg, str(tmp_path))


# ---------------------------------------------------------------------------
# The delta rule: the chunked scan against the one-step recurrence
# ---------------------------------------------------------------------------


def _recurrence(q, k, v, g, beta, s):
    """The plain recurrence in float64, one token at a time: rows ``[n, H,
    d]``, ``s [H, d, d]``.  Returns (o [n, H, d], s)."""
    out = []
    s = s.astype(np.float64)
    for t in range(q.shape[0]):
        s = np.exp(g[t])[..., None] * s
        u = beta[t][:, None] * (v[t] - np.einsum("hkv,hk->hv", s, k[t]))
        s = s + k[t][..., None] * u[:, None, :]
        out.append(np.einsum("hkv,hk->hv", s, q[t]))
    return np.stack(out), s


# (q_start, q_len, position of the lane's first row) a lane, over 6 slots.
_LAYOUTS = {
    # Decode lanes packed at the front, two prompts' chunks behind them:
    # one of 150 rows (three blocks, the last of 22) from position 0 into
    # a slot whose last sequence left a state, one of 65 continuing.
    "mixed": [(0, 1, 9), (1, 1, 40), (0, 0, 0), (2, 150, 0), (152, 65, 31),
              (0, 0, 0)],
    # Lane t == slot t, a row each or none: the pipelined step.
    "decode": [(0, 1, 5), (1, 0, 0), (2, 1, 17), (3, 1, 1), (4, 0, 0),
               (5, 1, 300)],
    # Prompts of 1 and 2 tokens that start in this step, a whole block of
    # 64 and one row more, a chunk of 63.
    "odd": [(0, 1, 0), (1, 2, 0), (3, 64, 10), (67, 65, 0), (132, 63, 7),
            (0, 0, 0)],
}


@pytest.mark.parametrize("layout", sorted(_LAYOUTS))
def test_the_chunked_scan_is_the_one_step_recurrence(layout):
    h, d, slots = 3, 8, 6
    lanes = _LAYOUTS[layout]
    t = max(s + n for s, n, _ in lanes) + 5          # padding rows behind
    rng = np.random.default_rng(len(layout))
    q, k, v = (rng.standard_normal((t, h, d)).astype(np.float32)
               for _ in range(3))
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    g = -rng.uniform(0.001, 0.9, (t, h, d)).astype(np.float32)
    beta = rng.uniform(0.0, 2.0, (t, h)).astype(np.float32)
    state = rng.standard_normal((slots, h, d, d)).astype(np.float32)
    q_start, q_len, pos = (np.asarray(x, np.int32) for x in zip(*lanes))
    fresh = (q_len > 0) & (pos == 0)
    # The layer's states sit in a stack of three, the middle one's.
    stack = np.stack([state + 1, state, state - 1])
    o, new = jax.jit(tf._linear_state)(
        *(jnp.asarray(x) for x in (q, k, v, g, beta, stack, 1, q_start,
                                   q_len, fresh)))
    o, new = np.asarray(o), np.asarray(new)
    assert np.array_equal(new[0], stack[0]) and np.array_equal(new[2],
                                                               stack[2])
    new = new[1]
    for b, (s0, n, p) in enumerate(lanes):
        if not n:
            assert np.array_equal(new[b], state[b])        # untouched
            continue
        rows = slice(s0, s0 + n)
        want_o, want_s = _recurrence(
            q[rows], k[rows], v[rows], g[rows], beta[rows],
            np.zeros_like(state[b]) if p == 0 else state[b])
        np.testing.assert_allclose(o[rows], want_o, rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(new[b], want_s, rtol=2e-4, atol=2e-4)


def test_the_convolution_reaches_into_the_slots_carry_and_leaves_one():
    """A lane's first K - 1 rows read the carry its slot holds (zeros where
    the sequence starts), every later row the flat rows before it; the
    carry a lane leaves is the last K - 1 rows of carry + rows."""
    cfg = get_config("tiny-linear-moe")
    e, c = cfg.hidden_size, 3 * cfg.linear_dim
    lp = jax.tree.map(
        lambda a: a[0].astype(jnp.float32),
        tf.init_params(cfg, jax.random.PRNGKey(1), jnp.float32)["lin_layers"])
    rng = np.random.default_rng(0)
    lanes = [(0, 1, 7), (1, 2, 0), (3, 9, 5), (0, 0, 0)]
    t = 14
    x = rng.standard_normal((t, e)).astype(np.float32)
    conv = rng.standard_normal((4, 3, c)).astype(np.float32)
    q_start, q_len, pos = (np.asarray(v, np.int32) for v in zip(*lanes))
    fresh = (q_len > 0) & (pos == 0)
    q, k, v, g, beta, new = tf._linear_qkv(
        jnp.asarray(x), lp, cfg, jnp.asarray(conv), jnp.asarray(q_start),
        jnp.asarray(q_len), jnp.asarray(fresh))
    pre = np.concatenate([x @ np.asarray(lp[n]) for n in ("wq", "wk", "wv")],
                         axis=-1)
    w = np.concatenate([np.asarray(lp[n]) for n in ("conv_q", "conv_k",
                                                    "conv_v")], axis=-1)
    for b, (s0, n, p) in enumerate(lanes):
        if not n:
            assert np.array_equal(np.asarray(new[b]), conv[b])
            continue
        line = np.concatenate([np.zeros_like(conv[b]) if p == 0 else conv[b],
                               pre[s0:s0 + n]])
        np.testing.assert_allclose(np.asarray(new[b]), line[-3:], rtol=1e-5,
                                   atol=1e-6)
        y = sum(line[i: i + n] * w[i] for i in range(4))        # [n, 3Hd]
        y = (y / (1 + np.exp(-y))).reshape(n, 3, cfg.linear_num_heads, -1)
        np.testing.assert_allclose(np.asarray(v[s0:s0 + n]), y[:, 2],
                                   rtol=1e-4, atol=1e-6)
        unit = y[:, 1] / np.sqrt((y[:, 1] ** 2).sum(-1, keepdims=True) + 1e-6)
        np.testing.assert_allclose(np.asarray(k[s0:s0 + n]), unit, rtol=1e-4,
                                   atol=1e-6)
    assert float(g.max()) < 0 and 0 < float(beta.min()) \
        and float(beta.max()) < 2            # negative eigenvalues allowed


# ---------------------------------------------------------------------------
# The slots' state: the holder, the cache tuple, the engine
# ---------------------------------------------------------------------------


def test_the_state_is_a_fixed_number_of_bytes_a_slot():
    cfg = get_config("tiny-linear-moe")
    small = tf.init_paged_cache(cfg, 8, 16, jnp.bfloat16, state_slots=3)
    big = tf.init_paged_cache(cfg, 64, 16, jnp.bfloat16, state_slots=3)
    assert small.k.shape[0] == cfg.num_full_layers == 3     # GQA layers only
    assert small.lin.s.shape == (6, 3, 4, 16, 16)
    assert small.lin.s.dtype == jnp.float32
    assert small.lin.conv.shape == (6, 3, 3, 3 * 64)
    per = 6 * (4 * 16 * 16 * 4 + 3 * 192 * 2)
    assert small.lin.slot_bytes == big.lin.slot_bytes == per     # no context
    with pytest.raises(ValueError, match="state_slots"):
        tf.init_paged_cache(cfg, 8, 16, jnp.bfloat16)


def _counted(eng, seen):
    m = eng.metrics
    return dict(
        starts=m.linear_state_starts_total.total(),
        state_steps=m.kv_held_byte_steps_total.get(kind="state"),
        page_steps=m.kv_held_byte_steps_total.get(kind="pages"),
        hits=m.prefix_cache_hit_tokens_total.total(), seen=len(seen))


@pytest.fixture(scope="module")
def depth0_streams():
    """One drain of one engine: its streams, and what its counters rose by
    across the drain."""
    seen = []
    with harness.fresh(TINY) as eng:
        assert eng.resolved_config["kv_page"] == "kv+state"
        assert eng.resolved_config["pipeline_depth"] == "0"
        # The pool holds the three GQA layers of nine.
        assert eng._cache.k.shape[0] == 3 and eng._cache.lin is not None
        before = _counted(eng, seen)
        toks, lps = harness.drain(
            eng, harness.requests(logprobs=1),
            lambda e: seen.append((e.metrics.linear_state_bytes.get(),
                                   e.ecfg.num_slots - len(e._free))))
        stats = {k: v - before[k] for k, v in _counted(eng, seen).items()}
        stats.update(slot_bytes=eng._lin_slot_bytes, seen=seen,
                     state_dtype=eng.resolved_config["state_dtype"])
    return toks, lps, stats


def test_the_engine_counts_state_and_pages_of_the_live_sequences(
        depth0_streams):
    toks, _, s = depth0_streams
    assert all(len(t) == 10 for t in toks.values())
    assert s["starts"] == 3                     # each took a slot at 0
    assert s["slot_bytes"] == 6 * (4 * 16 * 16 * 4 + 3 * 192 * 2)
    # The gauge, set at a step's dispatch, counts the taken slots: a slot
    # freed by the step's resolve is off it at the next dispatch.
    assert all(live <= 2 * s["slot_bytes"] and live % s["slot_bytes"] == 0
               for live, _ in s["seen"])
    assert max(live for live, _ in s["seen"]) == 2 * s["slot_bytes"]
    assert max(n for _, n in s["seen"]) == 2
    assert s["state_steps"] > 0 and s["page_steps"] > 0


def test_the_engine_says_what_the_state_is_kept_in(depth0_streams,
                                                    monkeypatch):
    """The timed path's guard of the state's precision (PERF.md §2: on the
    chip a bfloat16 state does not fail ``logprob_err``): the label is read
    off the cache the engine built, and the cell's ``expect_labels`` holds
    the pod to what its configuration states, so the harness refuses a pod
    whose state is stored narrower before it times anything."""
    assert depth0_streams[2]["state_dtype"] == "float32"
    deploy = harness.deploy(SOLAR)
    assert deploy["expect_labels"]["state_dtype"] == deploy["state_dtype"]
    real = tf.init_paged_cache

    def narrow(*a, **kw):
        cache = real(*a, **kw)     # it calls itself for the pool alone
        return cache if cache.lin is None else cache._replace(
            lin=cache.lin._replace(s=cache.lin.s.astype(jnp.bfloat16)))
    monkeypatch.setattr(tf, "init_paged_cache", narrow)
    # Fresh: it is built on a patched cache.
    with harness.fresh(TINY) as eng:
        assert eng.resolved_config["state_dtype"] == "bfloat16"
        assert 'state_dtype="bfloat16"' in eng.metrics.registry.render()


def test_no_prefix_is_reused_for_a_model_with_linear_layers(depth0_streams):
    """A matched prefix would need the state AT its end: the index is off
    for such a model, so the same prompt twice is computed twice and reads
    the same, in whichever slot."""
    toks, _, s = depth0_streams
    assert s["hits"] == 0
    with harness.fresh(TINY) as eng:
        hits = eng.metrics.prefix_cache_hit_tokens_total.total()
        first, _ = harness.drain(eng, harness.requests()[2:])
        again, _ = harness.drain(eng, harness.requests()[2:])
        assert first == again
        assert first["r2"] == toks["r2"]
        assert eng.metrics.prefix_cache_hit_tokens_total.total() == hits
        assert eng._alloc.retained_pages == 0


@pytest.mark.parametrize("depth", ["0", "2"])
def test_the_engine_counts_the_slots_the_state_update_steps_walks_and_skips(
        depth, monkeypatch):
    """``linear_state_lane_steps_total{path}``, once a dispatch from the
    dispatch's own rows a slot: ``step`` rises by the lanes of one row (the
    one-step kernel's list: the decoding slots), ``chunk`` by the lanes of
    more rows (the prefilling ones) and ``idle`` by the rest."""
    monkeypatch.setenv("ARKS_PIPELINE_DEPTH", depth)
    # Fresh: ``_emit`` is patched, and the counter is read from its first
    # sample (no series is rendered before it).
    eng = harness.engine(TINY)
    c = eng.metrics.linear_state_lane_steps_total
    slots = eng.ecfg.num_slots
    seen, emit = [], eng._emit

    def spy(op, **payload):
        if op == "mixed":
            q_len = payload["seq_q_len"]
            seen.append((int((q_len == 1).sum()), int((q_len > 1).sum()),
                         tuple(c.get(path=p) for p in ("step", "chunk",
                                                       "idle"))))
        return emit(op, **payload)

    monkeypatch.setattr(eng, "_emit", spy)
    try:
        assert "linear_state_lane_steps_total{" not in \
            eng.metrics.registry.render()
        if depth == "2":
            assert eng._pipe_warm_wait(120.0) == "ready"
        toks, _ = harness.drain(eng, harness.requests())
        assert all(len(t) == 10 for t in toks.values())
        # A sequential dispatch: counted in its ``count`` section, before
        # it is emitted, by what it emits.
        before = (0, 0, 0)
        piped = 0
        for step, chunk, after in seen:
            rose = tuple(a - b for a, b in zip(after, before))
            # (Pipelined dispatches in between: a row a decoding slot.)
            n = (sum(rose) - slots) // slots
            piped += n
            assert sum(rose) == (n + 1) * slots
            assert rose[1] == chunk and rose[0] >= step
            assert depth == "2" or rose == (step, chunk,
                                            slots - step - chunk)
            before = after
        assert any(chunk == 2 for _, chunk, _ in seen)     # two prompts
        assert any((step, chunk) == (1, 1) for step, chunk, _ in seen)
        assert any((step, chunk) == (2, 0) for step, chunk, _ in seen) \
            or piped
        assert (piped > 0) == (depth == "2")
        # Each request's nine tokens behind its first are a row of its own.
        assert c.get(path="step") >= 27
        text = eng.metrics.registry.render()
        assert all(f'linear_state_lane_steps_total{{path="{p}"}}' in text
                   for p in ("step", "chunk", "idle"))
    finally:
        eng.stop()


def test_a_pod_without_linear_layers_has_no_lane_steps_to_count():
    # Fresh: its prompt's page stays in the prefix index.
    with harness.fresh("tiny", kv_layout="paged") as eng:
        from arks_tpu.engine.types import Request, SamplingParams
        harness.drain(eng, [Request("r", list(range(2, 22)), SamplingParams(
            max_tokens=3, temperature=0.0, ignore_eos=True))])
        text = eng.metrics.registry.render()
        assert "mixed_batch_tokens_count" in text
        assert "linear_state_lane_steps_total{" not in text
        assert "kv_held_byte_steps_total{" not in text


# ---------------------------------------------------------------------------
# The benchmark's readers (benchmarks/kernels, layer_metrics)
# ---------------------------------------------------------------------------


def test_the_state_updates_work_is_one_read_and_one_write_a_lane_step():
    from benchmarks.kernels import linear_state_update as k
    shape = dict(heads=64, head_dim=128, layers=6, state_bytes=4)
    one = k.work(**shape, calls=[(1, 9000)])
    state = 64 * 128 * 128
    rows = 64 * (3 * 128 * 2 + 128 * 4 + 4 + 128 * 4)
    assert one["bytes"] == 6 * (2.0 * state * 4 + rows)
    assert one["flops"] == 6 * 7.0 * state
    # The context does not enter; a chunk of 64 rows reads and writes the
    # state once, a state kept in half the bytes halves that part.
    assert k.work(**shape, calls=[(1, 5)]) == one
    chunk = k.work(**shape, calls=[(64, 64)])
    assert chunk["bytes"] == 6 * (2.0 * state * 4 + 64 * rows)
    half = k.work(**dict(shape, state_bytes=2), calls=[(1, 1)])
    assert half["bytes"] == 6 * (2.0 * state * 2 + rows)
    least, bound = k.least_seconds(one, {"bf16_flops": 197e12,
                                         "hbm_bytes_per_s": 819e9})
    assert bound == "memory" and 60e-6 < least < 70e-6


@pytest.mark.parametrize("name", [
    "linear_state_roofline.tput", "linear_attn_share.tput",
    "gqa_attn_share.tput", "kv_state_resident_share"])
def test_a_linear_reader_finds_nothing_in_a_program_without_linear_layers(
        name):
    """The driver lays this PR's benchmark files over the parent's
    checkout: there the readers return None and do not raise."""
    from benchmarks import manifest
    read = manifest.load_reader(name)
    ctx = {"device": {"ops": [], "busy_s": 1.0, "xplane": None,
                      "slice_monotonic": (0.0, 1.0)},
           "metrics_open": {}, "metrics_close": {}, "cell": {}, "run": {},
           "engine": None, "kind": "TPU v5 lite"}
    assert read(ctx) is None
    assert read({**ctx, "device": None}) is None
    # A trace whose ops carry other scopes only (the parent's program).
    other = {**ctx["device"], "xplane": "x", "ops": [1],
             "scope_seconds": {"arks.ffn": 0.5, "arks.attn_qkv": 0.2,
                               "arks.attn_win_qkv": 0.1, None: 0.1}}
    assert read({**ctx, "device": other}) is None


def test_the_linear_readers_read_the_scopes_and_the_byte_steps():
    from benchmarks import manifest
    dev = {"ops": [1], "busy_s": 2.0, "xplane": "x",
           "slice_monotonic": (0.0, 1.0),
           "scope_seconds": {"arks.linear_qkv": 0.2, "arks.linear_state": 0.5,
                             "arks.linear_out": 0.1, "arks.moe_dot": 1.0,
                             "arks.attn_qkv": 0.02, "arks.attn_kernel": 0.03,
                             "arks.attn_layout": 0.01, "arks.attn_out": 0.02,
                             "arks.attn_gate": 0.02}}
    assert manifest.load_reader("linear_attn_share.tput")(
        {"device": dev}) == pytest.approx(40.0)
    assert manifest.load_reader("gqa_attn_share.tput")(
        {"device": dev}) == pytest.approx(5.0)
    n = "kv_held_byte_steps_total"
    ctx = {"metrics_open": {n: [({"kind": "state"}, 100.0),
                                ({"kind": "pages"}, 50.0)]},
           "metrics_close": {n: [({"kind": "state"}, 400.0),
                                 ({"kind": "pages"}, 150.0)]}}
    assert manifest.load_reader("kv_state_resident_share")(ctx) == 75.0
    # The roofline: one decode token a layer of the tiny family in a slice
    # whose scope took 1 ms.
    ref, config = harness.reference(TINY, "linear_moe", n_routed_experts=8)
    run = {"records": [{"frames": [(0.5, 1)], "prompt_tokens": 9,
                        "first": 0.1, "sent": 0.2}]}
    dev["scope_seconds"]["arks.linear_state"] = 1e-3
    got = manifest.load_reader("linear_state_roofline.tput")(
        {"device": dev, "cell": {"reference": ref, "config": config,
                                 "deploy": {}},
         "run": run, "kind": "TPU v5 lite"})
    state = 4 * 16 * 16
    want = 6 * (2.0 * state * 4 + 4 * (3 * 16 * 2 + 16 * 4 + 4 + 16 * 4))
    assert dev["linear_state_roofline_detail"]["bytes"] == want
    assert 0 < got < 1
