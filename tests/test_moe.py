"""MoE block correctness: routing, decode/prefill agreement, expert
parallelism over the mesh.

Reference parity note: the reference serves MoE models only by naming them
in runtime container commands; the block itself (Mixtral / Qwen2-MoE
semantics) is native here and tested on the CPU mesh.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from arks_tpu.models import get_config
from arks_tpu.models import moe
from arks_tpu.models import transformer as tf
from arks_tpu.parallel.mesh import make_mesh


def test_router_weights_topk_semantics():
    cfg = get_config("tiny-mixtral")  # top-2 of 4, normalized
    logits = jnp.asarray([[2.0, 1.0, 0.5, -1.0]])
    w = np.asarray(moe.router_weights(logits, cfg))
    assert (w[0] > 0).sum() == 2            # exactly k nonzero
    assert w[0, 3] == 0 and w[0, 2] == 0    # lowest logits dropped
    np.testing.assert_allclose(w[0].sum(), 1.0, rtol=1e-6)  # renormalized

    cfg2 = get_config("tiny-moe")  # norm_topk_prob=False
    w2 = np.asarray(moe.router_weights(logits, cfg2))
    assert 0 < w2[0].sum() < 1.0  # global-softmax probs used as-is


@pytest.mark.parametrize("name", ["tiny-moe", "tiny-mixtral"])
def test_moe_decode_matches_prefill(name):
    cfg = get_config(name)
    params = tf.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    ids = [int(x) for x in
           jax.random.randint(jax.random.PRNGKey(1), (8,), 0, cfg.vocab_size)]

    # Oracle: full prefill over each prefix.
    ref = []
    for i in range(1, len(ids) + 1):
        toks = jnp.asarray([ids[:i]], jnp.int32)
        logits, _, _ = tf.prefill(params, cfg, toks, jnp.asarray([i], jnp.int32))
        ref.append(np.asarray(logits[0]))

    n_prefill = 3
    cache = tf.init_cache(cfg, num_slots=2, max_len=32, dtype=jnp.float32)
    toks = jnp.asarray([ids[:n_prefill]], jnp.int32)
    logits, ks, vs = tf.prefill(params, cfg, toks, jnp.asarray([n_prefill], jnp.int32))
    np.testing.assert_allclose(np.asarray(logits[0]), ref[n_prefill - 1],
                               rtol=2e-4, atol=2e-4)
    cache = tf.insert(cache, ks, vs, jnp.asarray(0))
    lengths = jnp.zeros((2,), jnp.int32).at[0].set(n_prefill)
    tokens = jnp.zeros((2,), jnp.int32)
    for i in range(n_prefill, len(ids)):
        tokens = tokens.at[0].set(ids[i])
        logits, cache = tf.decode_step(params, cfg, cache, tokens, lengths)
        np.testing.assert_allclose(np.asarray(logits[0]), ref[i],
                                   rtol=2e-4, atol=2e-4)
        lengths = lengths.at[0].set(i + 1)


@pytest.mark.parametrize("tp,dp", [(4, 1), (2, 2), (8, 1)])
def test_moe_expert_parallel_equivalence(tp, dp):
    """Experts sharded over the model axis must match single-device.
    tp=8 with 8 experts = one expert per device; tp also shards kv heads
    when divisible (tiny-moe has 4)."""
    cfg = get_config("tiny-moe")
    params = tf.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    ids = jax.random.randint(jax.random.PRNGKey(2), (2, 6), 0, cfg.vocab_size)
    lengths = jnp.asarray([6, 6], jnp.int32)

    ref_logits, _, _ = tf.prefill(params, cfg, jnp.asarray(ids), lengths)
    mesh = make_mesh(tensor_parallel=tp, data_parallel=dp,
                     devices=jax.devices()[: tp * dp])
    params_s = tf.shard_params(params, cfg, mesh)
    got_logits, _, _ = tf.prefill(params_s, cfg, jnp.asarray(ids), lengths, mesh)
    np.testing.assert_allclose(np.asarray(got_logits), np.asarray(ref_logits),
                               rtol=5e-4, atol=5e-4)


@pytest.mark.parametrize("name", ["tiny-moe", "tiny-mixtral"])
def test_moe_grouped_matches_dense(name):
    """The dropless grouped (sort + ragged_dot) dispatch is numerically
    equivalent to the dense all-expert dispatch."""
    cfg = get_config(name)
    mp = moe.init_moe_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    lp = jax.tree_util.tree_map(lambda t: t[0], mp)  # layer 0 slice
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 40, cfg.hidden_size),
                          jnp.float32)
    dense = moe.moe_ffn(x, lp, cfg, grouped=False)
    grouped = moe.moe_ffn(x, lp, cfg, grouped=True)
    np.testing.assert_allclose(np.asarray(grouped), np.asarray(dense),
                               rtol=2e-5, atol=2e-5)


def test_moe_grouped_auto_threshold(monkeypatch):
    """Auto mode routes large unsharded [B, T, E] batches through the
    grouped path, decode-shaped [B, E] and small batches through dense —
    verified by counting actual grouped-path invocations."""
    cfg = get_config("tiny-moe")
    mp = moe.init_moe_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    lp = jax.tree_util.tree_map(lambda t: t[0], mp)
    calls = []
    real = moe.moe_ffn_grouped
    monkeypatch.setattr(moe, "moe_ffn_grouped",
                        lambda *a, **kw: calls.append(1) or real(*a, **kw))

    big = jax.random.normal(jax.random.PRNGKey(1), (2, 64, cfg.hidden_size))
    moe.moe_ffn(big, lp, cfg)
    assert len(calls) == 1  # large prefill → grouped
    moe.moe_ffn(big[:, :4], lp, cfg)
    assert len(calls) == 1  # small prefill → dense
    decode = jax.random.normal(jax.random.PRNGKey(2), (128, cfg.hidden_size))
    moe.moe_ffn(decode, lp, cfg)
    assert len(calls) == 1  # decode stays dense no matter the slot count
    moe.moe_ffn(big, lp, cfg, constrain=lambda t, d: t)
    assert len(calls) == 1  # sharded (constrained) → dense


def test_moe_grouped_grad():
    """Training uses the grouped path when unsharded — it must be
    differentiable (ragged_dot grads + scatter-add transpose)."""
    cfg = get_config("tiny-moe")
    mp = moe.init_moe_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    lp = jax.tree_util.tree_map(lambda t: t[0], mp)
    x = jax.random.normal(jax.random.PRNGKey(1), (64, cfg.hidden_size))

    def loss(lp, grouped):
        return jnp.sum(moe.moe_ffn(x, lp, cfg, grouped=grouped) ** 2)

    g_dense = jax.grad(loss)(lp, False)
    g_grouped = jax.grad(loss)(lp, True)
    for k in g_dense:
        np.testing.assert_allclose(np.asarray(g_grouped[k]),
                                   np.asarray(g_dense[k]),
                                   rtol=5e-4, atol=5e-4, err_msg=k)


def test_moe_param_counts():
    assert 40e9 < get_config("mixtral-8x7b").num_params() < 50e9
    assert 50e9 < get_config("qwen2-57b-a14b").num_params() < 62e9


def test_moe_hf_config_roundtrip():
    from arks_tpu.models.config import ModelConfig
    d = {
        "architectures": ["MixtralForCausalLM"], "model_type": "mixtral",
        "vocab_size": 1000, "hidden_size": 64, "intermediate_size": 128,
        "num_hidden_layers": 2, "num_attention_heads": 8,
        "num_key_value_heads": 4, "num_local_experts": 8,
        "num_experts_per_tok": 2, "eos_token_id": 2,
    }
    cfg = ModelConfig.from_hf_config(d)
    assert cfg.num_experts == 8 and cfg.num_experts_per_tok == 2
    assert cfg.norm_topk_prob and cfg.moe_intermediate_size == 128
    d2 = {
        "architectures": ["Qwen2MoeForCausalLM"], "model_type": "qwen2_moe",
        "vocab_size": 1000, "hidden_size": 64, "intermediate_size": 128,
        "num_hidden_layers": 2, "num_attention_heads": 8,
        "num_key_value_heads": 4, "num_experts": 16, "num_experts_per_tok": 4,
        "moe_intermediate_size": 48, "shared_expert_intermediate_size": 96,
        "norm_topk_prob": False,
    }
    cfg2 = ModelConfig.from_hf_config(d2)
    assert cfg2.qkv_bias and cfg2.num_experts == 16
    assert cfg2.shared_expert_intermediate_size == 96 and not cfg2.norm_topk_prob


# ---------------------------------------------------------------------------
# Quantised experts of a layer held whole: read as stored, never widened
# ---------------------------------------------------------------------------


def _quantised_layer(name, bits, forced):
    """Layer 0 of ``name`` with int8 / int4 expert leaves, and the same
    leaves widened to float32 (the oracle's).  ``forced``: the router's
    first input row sends expert 0 every token and the last expert none
    (the tests set feature 0 of every token to 1)."""
    cfg = get_config(name)
    mp = moe.init_moe_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    lp = jax.tree.map(lambda t: t[0], mp)
    if forced:
        push = jnp.zeros((cfg.num_experts,)).at[0].set(50.).at[-1].set(-50.)
        lp["router"] = lp["router"].at[0].set(push)
    return (cfg, *_quantised(lp, bits))


def _quantised(lp, bits):
    """``lp`` with int8 / int4 expert leaves, and the same leaves widened
    to float32 (the oracle's)."""
    from arks_tpu.models import quant
    qp = quant.quantize_params(lp, bits=bits, group=32)
    wide = {k: (quant.dequantize(v, jnp.float32) if quant.is_quantized(v)
                else v) for k, v in qp.items()}
    return qp, wide


def _tokens(cfg, rows, seed=1):
    x = jax.random.normal(jax.random.PRNGKey(seed),
                          (1, rows, cfg.hidden_size), jnp.float32)
    return x.at[..., 0].set(1.0)


@pytest.mark.parametrize("forced", [False, True], ids=["seeded", "forced"])
@pytest.mark.parametrize("rows", [64, 300, 320])
@pytest.mark.parametrize("bits", [8, 4], ids=["int8", "int4"])
def test_quantised_experts_match_the_dense_float32_dispatch(bits, rows,
                                                            forced):
    """The dispatch a step takes for a quantised layer held whole (dense
    at 64 rows, batched with its fixed overflow tiles at 300 and 320, the
    last tile ragged at 300) against the dense dispatch on the widened
    float32 leaves, with a seeded router and with one that sends an
    expert every row and another none."""
    cfg, qp, wide = _quantised_layer("tiny-mixtral", bits, forced)
    x = _tokens(cfg, rows)
    want = moe.moe_ffn(x, wide, cfg, grouped=False)
    if forced:
        load = np.asarray(moe.router_weights(
            jnp.einsum("...e,ex->...x", x, qp["router"]), cfg) != 0
        ).reshape(rows, -1).sum(0)
        assert load[0] == rows and load[-1] == 0
    for grouped in (None, True):
        got = moe.moe_ffn(x, qp, cfg, grouped=grouped)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-4 * float(jnp.abs(want).max()),
                                   err_msg=str(grouped))


@pytest.mark.parametrize("bits", [8, 4], ids=["int8", "int4"])
def test_quantised_experts_at_the_cells_shape_and_with_padding_rows(bits):
    """8 experts top-2 at 320 rows, the shape rule of the benchmark's
    Mixtral step (a batch of 128 rows an expert and four overflow tiles),
    with a shared expert beside them; and rows that carry no token: they
    take no place in the batch and count no pair."""
    cfg, qp, wide = _quantised_layer("tiny-moe", bits, forced=True)
    assert moe._held_capacity(320, cfg) == 128
    assert moe._held_capacity(64, cfg) == 64      # the dense dispatch's
    x = _tokens(cfg, 320)
    want = moe.moe_ffn(x, wide, cfg, grouped=False)
    tol = 2e-4 * float(jnp.abs(want).max())
    np.testing.assert_allclose(np.asarray(moe.moe_ffn(x, qp, cfg)),
                               np.asarray(want), atol=tol)
    valid = (jnp.arange(320) < 201)[None]
    got, held = moe.moe_ffn(x, qp, cfg, row_valid=valid)
    # a layer held whole: every valid pair, and no share's overflow counts
    assert held.tolist() == [201 * cfg.num_experts_per_tok, 0, 0]
    np.testing.assert_allclose(np.asarray(got[0, :201]),
                               np.asarray(want[0, :201]), atol=tol)
    only_shared = moe._shared_expert(x, qp, cfg)
    np.testing.assert_allclose(np.asarray(got[0, 201:]),
                               np.asarray(only_shared[0, 201:]), atol=tol)


# The benchmark's share configurations: (chips a layer, slots), and what the
# rule reads of each (k, router width, held experts, fair rows of a
# whole-budget step).
_SHARES = {
    "kimi-k2.5-ep32-l9": (32, 8),          # 8 of 384, 12 held: fair 22
    "laguna-s-2.1-ep8": (8, 32),           # 10 of 256, 32 held: fair 42
    "solar-open2-250b-ep8-l8": (8, 64),    # 8 of 320, 40 held: fair 28
    "gigachat3.5-432b-ep8-l5": (8, 64),    # 8 of 256, 32 held: fair 34
    "mimo-v2.5-ep16-l13": (16, 64),        # 8 of 256, 16 held: fair 34
}


def _cell_config(name):
    """The benchmark's configuration ``name``, from its own file."""
    import os
    from arks_tpu.models.config import ModelConfig
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return ModelConfig.from_hf_config(
        os.path.join(root, "benchmarks", "configs", name), name="m")


@pytest.mark.parametrize("step", ["pipelined", "quarter", "whole-budget"])
@pytest.mark.parametrize("name", sorted(_SHARES))
def test_a_shares_batch_an_expert_is_one_tile_at_every_cells_step(name, step):
    """The capacity rule as a table, on the configurations' own files: a
    share's expert takes the fewest 128-row tiles that hold three times its
    fair load, so ONE tile at every chunk-carrying step of the five cells
    (laguna, gigachat and mimo took two at their whole-budget steps while
    the rule said four times: 168 / 136 / 136 rows rounded up to 256, and
    every routed layer ran 8192 / 8192 / 4096 batch rows for the ~1,300 /
    1,100 / 540 pairs that land), and every row at a pipelined step, which
    is the dense dispatch's.  ``share_rows`` is what the host counts a
    layer (``moe_batch_rows_total``)."""
    share, slots = _SHARES[name]
    cfg = _cell_config(name).with_expert_share(share, 0)
    n = slots + {"pipelined": 0, "quarter": 256, "whole-budget": 1024}[step]
    if step == "pipelined":
        assert moe._held_capacity(n, cfg) == n
        assert not moe._batch_pays(n, None, cfg)
        assert moe.share_rows(n, cfg) == (n * cfg.num_experts, 0)
        return
    fair = -(-n * cfg.num_experts_per_tok // cfg.router_width)
    assert 3 * fair <= 128
    assert moe._held_capacity(n, cfg) == 128
    assert moe._batch_pays(n, None, cfg)
    assert moe.share_rows(n, cfg) == (
        (cfg.num_experts + moe._SPARE_TILES) * 128, 128)


def test_a_shares_batch_grows_by_tiles_with_the_fair_load():
    """Past one tile the rule goes on in whole tiles of 128 (it is not a
    cap of one): 3 x fair rounded up, at most every row; a layer held whole
    keeps one and a half times its fair load."""
    import types
    cfg = types.SimpleNamespace(num_experts=32, num_experts_per_tok=10,
                                router_width=256, expert_parallel_size=8,
                                expert_parallel_rank=0)
    caps = {n: moe._held_capacity(n, cfg) for n in (96, 1056, 1100, 2080,
                                                    4128, 8224)}
    assert caps == {96: 96, 1056: 128, 1100: 256, 2080: 256, 4128: 512,
                    8224: 1024}
    cfg.expert_parallel_size, cfg.router_width = 1, 32
    assert moe._held_capacity(1056, cfg) == 512      # 1.5 x 330, in tiles


def _cell_layer(name, share, bits=0, star=None):
    """One routed layer shaped as the benchmark's configuration ``name``
    routes (its own ``config.json``: k, the router's width, the experts a
    chip of ``share`` holds, its scoring) at test widths, 64 x 32: ``(cfg,
    leaves, the same leaves widened to float32)``, quantised to ``bits``.
    With ``star`` the router's first input row sends THAT held expert every
    token whose feature 0 is 1 (``_tokens`` sets it)."""
    import dataclasses
    cfg = dataclasses.replace(_cell_config(name), hidden_size=64,
                              moe_intermediate_size=32)
    if share > 1:
        cfg = cfg.with_expert_share(share, 0)
    lp = jax.tree.map(lambda t: t[0], moe.init_moe_params(
        cfg, jax.random.PRNGKey(3), jnp.float32, layers=1))
    if star is not None:
        lp["router"] = lp["router"].at[0, moe.held_first(cfg) + star].set(50.)
    return (cfg, *(_quantised(lp, bits) if bits else (lp, lp)))


# Every chunk-carrying step program of the benchmark's routed cells: the
# five shares (``_SHARES``) at their quarter and whole-budget shapes and
# mixtral, a layer held whole, at its one (64 slots + a page).
_COMBINE_STEPS = [(name, _SHARES[name][0], _SHARES[name][1] + chunk)
                  for name in sorted(_SHARES) for chunk in (256, 1024)
                  ] + [("mixtral-8x7b-l4", 1, 64 + 256)]


@pytest.mark.parametrize("router", ["seeded", "one expert"])
@pytest.mark.parametrize("bits", [8, 4], ids=["int8", "int4"])
@pytest.mark.parametrize("name,share,n", _COMBINE_STEPS,
                         ids=[f"{c[0]}-{c[2]}" for c in _COMBINE_STEPS])
def test_the_combine_contracts_at_every_cells_chunk_step(name, share, n, bits,
                                                         router):
    """The batched dispatch AS THE AUTO RULE TAKES IT at every cell's
    chunk-carrying step shape, its combine one contraction (nothing
    patched), against the dense dispatch on the widened float32 leaves,
    with rows that carry no token ahead of the chunk and behind it.  Under
    the seeded router the tiles whose count the shape fixes run dead or
    nearly; under one that sends every valid row to ONE held expert that
    expert fills its batch, every unrolled tile and, in a share, the trips
    of the loop behind them, counted here by hand.  The counts are the
    dense dispatch's own (which counts non-zero weights and sorts
    nothing)."""
    cfg, qp, wide = _cell_layer(name, share, bits,
                                star=2 if router == "one expert" else None)
    cap = moe._held_capacity(n, cfg)
    assert cap == 128 and moe._batch_pays(n, qp, cfg)
    assert moe._contract_pays(n)
    x = _tokens(cfg, n)
    valid = ((jnp.arange(n) > 0) & (jnp.arange(n) < n - n // 5))[None]
    want, counts_d = moe.moe_ffn(x, wide, cfg, grouped=False, row_valid=valid)
    got, counts = moe.moe_ffn(x, qp, cfg, row_valid=valid)
    # What the router chose, on the host: the pairs that land on an expert
    # held here, and the tiles those experts need beyond their batch.
    vals, idx = moe.router_topk(
        jnp.einsum("te,ex->tx", x[0], qp["router"]), cfg,
        qp.get("router_bias"))
    local = np.asarray(idx)[np.asarray(valid[0])] - moe.held_first(cfg)
    sizes = np.bincount(local[(local >= 0) & (local < cfg.num_experts)],
                        minlength=cfg.num_experts)
    needed = int(np.sum(-(-np.maximum(sizes - cap, 0) // cap)))
    if router == "one expert":
        assert sizes[2] == int(valid.sum())
        assert n < 512 or needed > moe._SPARE_TILES    # the loop runs
    assert counts_d.tolist() == [int(sizes.sum()), 0, 0]
    if share > 1:
        assert counts.tolist() == [
            int(sizes.sum()), needed, max(needed - moe._SPARE_TILES, 0)]
    else:
        assert counts.tolist() == counts_d.tolist()
    # The dispatch's own ``held`` and ``tiles``, as the sort made them.
    _, held, tiles = moe._batched_dispatch(x[0], vals, idx, qp, cfg, valid[0])
    local = np.asarray(idx) - moe.held_first(cfg)
    np.testing.assert_array_equal(
        np.asarray(held), (local >= 0) & (local < cfg.num_experts)
        & np.asarray(valid[0])[:, None])
    assert (tiles is None) if share == 1 else (
        tiles.tolist() == counts.tolist()[1:])
    # A row that carries no token takes no place in the batch: the shared
    # expert's alone, where the configuration has one.
    if "shared_gate_proj" in wide:
        want = jnp.where(valid[..., None], want,
                         moe._shared_expert(x, wide, cfg))
    else:
        want = jnp.where(valid[..., None], want, 0.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-4 * float(jnp.abs(want).max()))


def _lowered_ops(lowered, scope: str, op: str) -> list:
    """The scope paths under which the lowered program holds an ``op``
    inside ``scope``: the text's location table names every op it holds by
    its name stack and primitive (``"jit(f)/arks.moe_route/scatter-add"``),
    one entry a site."""
    import re
    return sorted(set(re.findall(
        rf'loc\("([^"]*{re.escape(scope)}[^"]*/{op})"',
        lowered.as_text(debug_info=True))))


@pytest.mark.parametrize("name,share,n,contracts", [
    ("laguna-s-2.1-ep8", 8, 32 + 1024, True),
    ("kimi-k2.5-ep32-l9", 32, 8 + 256, True),
    ("mixtral-8x7b-l4", 1, 64 + 256, True),
    ("laguna-s-2.1-ep8", 8, 32 + 8192, False),
    ("mixtral-8x7b-l4", 1, 64 + 8192, False),
], ids=["share-1056", "share-264", "whole-320", "share-8224", "whole-8256"])
def test_the_lowered_layer_scatters_only_beyond_the_crossover(name, share, n,
                                                              contracts):
    """The form of the combine follows the step's rows alone: the lowered
    routed layer of a share and of a layer held whole holds NO ``scatter``
    under ``arks.moe_route`` at the cells' step shapes (the combine is the
    ``ns,se->ne`` contraction there, the experts' sizes a compare and a
    sum), and beyond :func:`moe._contract_pays`'s crossover the scatter-add
    is back and the contraction gone: one site for the batch and its
    unrolled tiles, and one in a share's loop."""
    cfg, qp, _ = _cell_layer(name, share, bits=8)
    low = jax.jit(lambda x, v: moe.moe_ffn(x, qp, cfg, row_valid=v)).lower(
        jax.ShapeDtypeStruct((1, n, cfg.hidden_size), jnp.bfloat16),
        jax.ShapeDtypeStruct((1, n), jnp.bool_))
    scatters = _lowered_ops(low, "arks.moe_route", "scatter-add")
    dots = _lowered_ops(low, "arks.moe_route", "ns,se->ne/dot_general")
    sites = 2 if share > 1 else 1
    assert (len(scatters), len(dots)) == ((0, sites) if contracts
                                          else (sites, 0)), (scatters, dots)
    assert _lowered_ops(low, "", "scatter[-a-z]*") == scatters


def test_the_contraction_is_no_farther_from_float32_than_the_scatter_add(
        monkeypatch):
    """bfloat16 rows at laguna's whole-budget step (4,608 slots onto 1,056
    tokens, a quarter of them live, ten to a token): the contraction sums a
    token's products in float32 and rounds once, the scatter-add rounds
    each product and every add.  The combine alone against float64 on the
    same bfloat16 rows and weights: the contraction's error is the smaller,
    by rms and at the worst element; and through the whole layer, where the
    bfloat16 dots ahead of the combine carry most of the error, it is no
    larger."""
    n, s, e = 1056, 4608, 64
    rng = np.random.default_rng(53)
    token = jnp.asarray(np.repeat(np.arange(n), 10)[rng.permutation(
        10 * n)[:s]], jnp.int32)
    w = jnp.where(jnp.asarray(rng.random(s) < 0.25),
                  jnp.asarray(rng.random(s), jnp.float32), 0)
    down = jax.random.normal(jax.random.PRNGKey(1), (s, e), jnp.bfloat16)
    exact = np.zeros((n, e))
    np.add.at(exact, np.asarray(token), np.asarray(down, np.float64)
              * np.asarray(w.astype(jnp.bfloat16), np.float64)[:, None])

    cfg, lp, _ = _cell_layer("laguna-s-2.1-ep8", 8)
    x = _tokens(cfg, n)
    want = np.asarray(moe.moe_ffn(x, lp, cfg, grouped=False))
    half = jax.tree.map(lambda a: a.astype(jnp.bfloat16), lp)

    def errors(contracts):
        monkeypatch.setattr(moe, "_contract_pays", lambda *a: contracts)
        alone = np.asarray(moe._combine(n, down, token, w), np.float64)
        layer = np.asarray(moe.moe_ffn(x.astype(jnp.bfloat16), half, cfg,
                                       grouped=True), np.float32)
        return [f(d) for d in (alone - exact, layer - want)
                for f in (lambda d: float(np.sqrt(np.mean(d * d))),
                          lambda d: float(np.abs(d).max()))]

    contraction, scatter = errors(True), errors(False)
    assert contraction[0] < 0.9 * scatter[0]           # the combine: rms
    assert contraction[1] <= scatter[1]                # its worst element
    assert contraction[2] <= 1.01 * scatter[2]         # the layer: rms


def test_a_row_that_is_not_finite_stays_off_the_other_tokens():
    """The contraction multiplies every slot's row by every token's zeros:
    a NaN in a dead slot or an infinity in a live one would reach all
    ``n`` tokens (0 x NaN).  Such values count as 0 in the combine (the
    token that brought one keeps it in the residual stream), so every
    token reads what it reads with those values zeroed, all finite."""
    n, s, e = 300, 640, 64
    rng = np.random.default_rng(7)
    token = jnp.asarray(rng.integers(0, n, s), jnp.int32)
    w = jnp.where(jnp.asarray(rng.random(s) < 0.3),
                  jnp.asarray(rng.random(s), jnp.float32), 0)
    down = jax.random.normal(jax.random.PRNGKey(2), (s, e), jnp.bfloat16)
    dead, live = int(np.argmin(np.asarray(w))), int(np.argmax(np.asarray(w)))
    bad = down.at[dead].set(jnp.nan).at[live, 3].set(jnp.inf)
    clean = down.at[dead].set(0).at[live, 3].set(0)
    assert moe._contract_pays(n)
    got = np.asarray(moe._combine(n, bad, token, w), np.float32)
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(
        got, np.asarray(moe._combine(n, clean, token, w), np.float32))
    # on top of a carried result too (a trip of a share's loop)
    carried = jnp.ones((n, e), jnp.bfloat16)
    assert np.isfinite(np.asarray(
        moe._combine(n, bad, token, w, out=carried), np.float32)).all()


def test_the_auto_rule_batches_quantised_experts_only_where_it_pays(
        monkeypatch):
    """Quantised leaves of a layer held whole go grouped only while an
    expert's batch is smaller than the step's rows; plain leaves keep the
    token threshold (``test_moe_grouped_auto_threshold``)."""
    cfg, qp, _ = _quantised_layer("tiny-moe", 8, forced=False)
    calls = []
    real = moe.moe_ffn_grouped
    monkeypatch.setattr(moe, "moe_ffn_grouped",
                        lambda *a, **kw: calls.append(1) or real(*a, **kw))
    moe.moe_ffn(_tokens(cfg, 64), qp, cfg)
    assert calls == []                      # cap == rows: dense
    moe.moe_ffn(_tokens(cfg, 320), qp, cfg)
    assert calls == [1]                     # 128 of 320 rows an expert
    moe.moe_ffn(_tokens(cfg, 320)[0], qp, cfg)
    assert calls == [1]                     # decode-shaped: dense


@pytest.mark.parametrize("rows", [64, 320])
@pytest.mark.parametrize("bits", [8, 4], ids=["int8", "int4"])
def test_no_step_widens_a_quantised_expert_stack(bits, rows):
    """The traced prefill of a quantised ``tiny-mixtral`` holds no
    ``ragged_dot`` and multiplies no array of an expert leaf's shape by
    its scales in the activations' width (int8: the scale lands on the
    contraction's output)."""
    from arks_tpu.models import quant
    cfg = get_config("tiny-mixtral")
    params = quant.quantize_params(
        tf.init_params(cfg, jax.random.PRNGKey(0), jnp.bfloat16), bits=bits)
    text = str(jax.make_jaxpr(lambda p, t, n: tf.prefill(p, cfg, t, n))(
        params, jnp.zeros((1, rows), jnp.int32),
        jnp.asarray([rows], jnp.int32)))
    assert "ragged_dot" not in text
    x, e, f = cfg.num_experts, cfg.hidden_size, cfg.moe_intermediate_size
    assert f"i{bits}[{x},{e},{f}]" in text
    if bits == 8:
        for shape in (f"[{x},{e},{f}]", f"[{x},{f},{e}]"):
            assert f"bf16{shape} = mul" not in text
            assert f"bf16{shape} = convert_element_type" in text


def test_plain_leaves_keep_ragged_dot():
    """Unquantised leaves (training, float tests) still take the sorted
    ``ragged_dot`` contractions, which are differentiable."""
    cfg = get_config("tiny-moe")
    lp = jax.tree.map(lambda t: t[0], moe.init_moe_params(
        cfg, jax.random.PRNGKey(0), jnp.float32))
    text = str(jax.make_jaxpr(lambda x: moe.moe_ffn(x, lp, cfg))(
        _tokens(cfg, 64)))
    assert "ragged_dot" in text


def _whole_step(cfg, key: int, lanes, rows: int, max_pages: int,
                shapes_only: bool = False):
    """``(with_held -> ((params, cache) -> mixed_step(...)), params,
    cache)`` of ``cfg`` on seeded int8 weights and float32 activations
    (their shapes alone with ``shapes_only``) over a flat batch of ``rows``
    rows: a padding row, then lane i's ``lanes[i]`` rows from position 0,
    then padding."""
    from arks_tpu.models import quant
    slots, page = len(lanes), 16

    def state():
        params = jax.tree.map(
            lambda a: a.astype(jnp.float32) if a.dtype == jnp.bfloat16 else a,
            quant.init_params_quantized(cfg, jax.random.PRNGKey(key),
                                        jnp.bfloat16, bits=8))
        return params, tf.init_paged_cache(
            cfg, slots * max_pages, page, jnp.float32,
            win_pages=slots * max_pages if cfg.windowed else 0,
            state_slots=slots if cfg.linear else 0)

    params, cache = jax.eval_shape(state) if shapes_only else state()
    tables = jnp.arange(slots * max_pages, dtype=jnp.int32).reshape(
        slots, max_pages)
    tokens = np.zeros(rows, np.int32)
    slot = np.full(rows, -1, np.int32)
    pos = np.full(rows, page * max_pages, np.int32)
    src, qs, ql = (np.zeros(slots, np.int32) for _ in range(3))
    at = 1                                            # a padding row ahead
    for lane, n in enumerate(lanes):
        tokens[at:at + n] = np.random.default_rng(lane).integers(2, 500, n)
        slot[at:at + n], pos[at:at + n] = lane, np.arange(n)
        qs[lane], ql[lane], src[lane] = at, n, at + n - 1
        at += n
    kw = {"win_tables": tables} if cfg.windowed else {}
    return lambda held: lambda p, c: tf.mixed_step(
        p, cfg, c, tables, *(jnp.asarray(a) for a in (
            tokens, slot, pos, src, qs, ql, np.zeros(slots, np.int32))),
        with_held=held, **kw), params, cache


@pytest.mark.parametrize("preset", ["tiny-mla-moe", "tiny-linear-moe",
                                    "tiny-latent-linear-moe"])
def test_a_whole_steps_overflow_loops_read_the_layers_own_experts(
        preset, monkeypatch):
    """``mixed_step`` of the blocks that serve a share (the latent scans;
    the period scan with linear, GQA and latent layers, a routed head
    stack, a first period cut short, a tail; window layers are the GQA
    layer function under another flag) on a chunk of 100 rows beside one of
    50, int8 leaves, float32 activations: with an expert's batch forced
    down to 8 rows every routed layer runs far more overflow tiles than
    the spare ones, each read out of the stacked tree at the index the
    caller handed down, and the logits are the dense dispatch's.  (Every
    layer's weights differ: an index into the wrong stack, or off by the
    layer a first period took, reads another layer's expert.)"""
    cfg = get_config(preset).with_expert_share(2, 1)
    run, *state = _whole_step(cfg, 11, (100, 50), rows=160, max_pages=16)

    def step():
        logits, _, counts = jax.jit(run(True))(*state)
        return np.asarray(logits), counts.tolist()

    monkeypatch.setattr(moe, "_batch_pays", lambda n, mp, cfg: False)
    want, (held, *tiles) = step()
    assert tiles == [0, 0]
    monkeypatch.setattr(moe, "_batch_pays", lambda n, mp, cfg: True)
    monkeypatch.setattr(moe, "_held_capacity", lambda n, cfg: 8)
    got, (held_b, needed, extra) = step()
    assert held_b == held
    assert extra == needed - moe._SPARE_TILES * cfg.num_routed_layers
    assert extra > 20 * cfg.num_routed_layers
    np.testing.assert_allclose(got, want, atol=2e-5 * np.abs(want).max())


@pytest.mark.parametrize("preset", [
    "tiny", "tiny-mixtral", "tiny-mla-moe", "tiny-swa-moe", "tiny-linear-moe",
    "tiny-latent-linear-moe"])
def test_every_block_honours_with_held(preset):
    """``mixed_step`` is one forward for every block, and ``with_held`` its
    one static argument: True, a routed layer is handed the mask of the
    valid rows and the step returns counts (every routed pair of a valid
    row, for a model that holds every expert; zeros for one without
    experts); False, two results.  No valid row's logits depend on whether
    the padding rows were masked (run for the blocks without inner layers,
    where ``with_held`` is new; the others, which every engine test of
    theirs serves ``with_held``, are traced both ways and not run)."""
    cfg = get_config(preset)
    lanes = (20, 1)                                   # a chunk, a decode row
    step, *state = _whole_step(cfg, 5, lanes, rows=32, max_pages=4,
                               shapes_only=bool(cfg.inner_period))
    assert jax.eval_shape(step(True), *state)[2].shape == (3,)
    assert len(jax.eval_shape(step(False), *state)) == 2
    if cfg.inner_period:
        return
    logits, _, counts = jax.jit(step(True))(*state)
    plain, _ = jax.jit(step(False))(*state)
    assert counts.tolist() == [
        sum(lanes) * cfg.num_experts_per_tok * cfg.num_routed_layers, 0, 0]
    np.testing.assert_allclose(np.asarray(logits), np.asarray(plain),
                               atol=2e-5 * np.abs(np.asarray(plain)).max())
