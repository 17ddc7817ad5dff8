"""What the one-sublayer block (``nemotron_h``) brings that no family file
holds: the selective scan's one-step kernel against the three lines of the
recurrence in float64 and against the chunked scan on the same rows; a
prompt cut at EVERY offset of a block of the scan leaves the state, the
carry and the outputs of one pass; the grouped norm behind the gate; the
walk over periods of unequal length, the PUBLISHED 52-character pattern at
test widths through ``mixed_step`` against the reference family's one
forward; what the reader makes of the published file.

Why two of the family's readings are low at a width of 64 (tests/
test_family_prompt_cut.py, benchmarks/tests/test_reference_ssm_moe.py): the
mixer's input projection draws x | B | C at ``0.02 sqrt(64)`` = 0.16 where
the published width draws them at ``0.02 sqrt(2688)`` = 1.04, they leave the
convolution at 0.08 (0.5), the state read out by C is their third power and
the skip ``D x`` their first: here the skip outweighs the state eight to
one, there the state outweighs the skip."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from arks_tpu.models import quant, transformer as tf
from arks_tpu.models.config import ModelConfig, get_config
from arks_tpu.ops.ssm_state import pack_state, ssm_state_step, unpack_state

import harness

PUBLISHED = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


def _rows(rng, t, h, p, g, n):
    """A flat batch's rows as ``_ssm_in`` hands them over: x dt, B, C and a
    log decay a head."""
    return (rng.standard_normal((t, h, p)).astype(np.float32),
            rng.standard_normal((t, g, n)).astype(np.float32),
            rng.standard_normal((t, g, n)).astype(np.float32),
            -np.abs(rng.standard_normal((t, h))).astype(np.float32) * 0.1)


def _recurrence(x, b, c, g, s0):
    """The three lines in float64, one token at a time: rows ``[T, ..]`` of
    ONE sequence from the state ``s0 [H, P, N]``: (y [T, H, P], the state
    after)."""
    s = s0.astype(np.float64).copy()
    per = x.shape[1] // b.shape[1]
    y = np.zeros(x.shape)
    for t in range(x.shape[0]):
        for h in range(x.shape[1]):
            s[h] = np.exp(np.float64(g[t, h])) * s[h] \
                + np.outer(x[t, h], b[t, h // per])
            y[t, h] = s[h] @ c[t, h // per].astype(np.float64)
    return y, s


@pytest.mark.parametrize("state_dtype", ["float32", "bfloat16"])
def test_the_one_step_kernel_is_the_recurrence_on_the_listed_slots_alone(
        state_dtype):
    """Three of five slots listed (one of them fresh), rows scattered over
    the flat batch: their states are the float64 recurrence's one step, in
    place, their rows of the output its read-out; a slot outside the list
    is neither read nor written (NaNs in it stay put and reach nothing),
    what lies behind ``count`` in the list is not looked at, and another
    layer's states stand."""
    rng = np.random.default_rng(0)
    layers, slots, h, p, n, g, t = 2, 5, 8, 8, 16, 2, 12
    dtype = jnp.dtype(state_dtype)
    s_all = rng.standard_normal((layers, slots, h, p, n)).astype(np.float32)
    s_all[1, 2] = np.nan                       # never listed
    s_all[1, 1] = np.nan                       # listed, and fresh
    s_in = pack_state(jnp.asarray(s_all), h // g).astype(dtype)
    # Stored: 4 heads of a group's 4 side by side, [2, 16, 32] a slot.
    assert s_in.shape == (layers, slots, 2, n, 4 * p)
    x, b, c, lg = _rows(rng, t, h, p, g, n)
    listed, fresh = np.array([3, 1, 4, 2, 2]), np.array([0, 1, 0, 0, 0])
    at = np.array([0, 7, 0, 2, 9])
    y, s_out = ssm_state_step(
        *(jnp.asarray(a) for a in (x, b, c, lg)), s_in, 1,
        jnp.asarray(listed), 3, jnp.asarray(fresh), jnp.asarray(at), pad=4,
        interpret=True)
    assert s_out.dtype == dtype and y.shape == (t + 4, h, p)
    s_out = np.asarray(unpack_state(s_out.astype(jnp.float32), p))
    stored = np.asarray(unpack_state(s_in.astype(jnp.float32), p))
    want_y = np.zeros((t + 4, h, p))
    tol = 1e-5 if state_dtype == "float32" else 2e-2
    for slot in listed[:3]:
        r = at[slot]
        s0 = np.zeros((h, p, n)) if fresh[slot] else stored[1, slot]
        want_y[r:r + 1], s1 = _recurrence(
            x[r:r + 1], b[r:r + 1], c[r:r + 1], lg[r:r + 1], s0)
        np.testing.assert_allclose(s_out[1, slot], s1, rtol=tol, atol=tol)
    # (The read-out is of the float32 state, before it is stored.)
    np.testing.assert_allclose(np.asarray(y), want_y, rtol=1e-5, atol=2e-5)
    assert np.isnan(s_out[1, 2]).all()
    assert np.array_equal(s_out[1, 0], stored[1, 0])
    assert np.array_equal(s_out[0], stored[0])


def test_an_empty_list_leaves_every_state_as_it_was():
    rng = np.random.default_rng(1)
    s_all = pack_state(jnp.asarray(rng.standard_normal((1, 3, 4, 8, 16)),
                                   jnp.float32), 2)
    x, b, c, lg = (jnp.asarray(a) for a in _rows(rng, 6, 4, 8, 2, 16))
    y, s_out = ssm_state_step(x, b, c, lg, s_all, 0, jnp.zeros(3, jnp.int32),
                              0, jnp.zeros(3, jnp.int32),
                              jnp.zeros(3, jnp.int32), interpret=True)
    assert np.array_equal(np.asarray(s_out), np.asarray(s_all))
    assert not np.asarray(y).any()


def test_the_chunked_scan_and_the_kernel_are_the_same_recurrence():
    """The same 70 rows of one lane, from the same state: one block after
    the other through ``_ssd_chunk`` (``_walk_blocks``: 64 + 6 rows), and
    row after row through the one-step kernel; both are the float64
    recurrence, outputs and the state they leave."""
    rng = np.random.default_rng(2)
    h, p, n, g, t = 8, 8, 16, 2, 70
    x, b, c, lg = _rows(rng, t, h, p, g, n)
    s0 = rng.standard_normal((1, 2, h, p, n)).astype(np.float32)
    want_y, want_s = _recurrence(x, b, c, lg, s0[0, 1])
    start, length = jnp.array([0, 0], jnp.int32), jnp.array([0, t], jnp.int32)
    none = jnp.zeros(2, bool)
    packed = pack_state(jnp.asarray(s0), h // g)
    y, s = tf._walk_blocks(
        tf._ssd_chunk, tuple(jnp.asarray(a) for a in (x, b, c, lg)),
        jnp.zeros((t + 64, h, p), jnp.float32), packed, 0, start, length,
        none, 64)
    np.testing.assert_allclose(np.asarray(y[:t]), want_y, rtol=1e-5,
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(unpack_state(s, p)[0, 1]), want_s,
                               rtol=1e-5, atol=1e-5)
    assert np.array_equal(np.asarray(s[0, 0]), np.asarray(packed[0, 0]))
    step = jax.jit(lambda s, r: ssm_state_step(
        *(jnp.asarray(a) for a in (x, b, c, lg)), s, 0,
        jnp.array([1, 0], jnp.int32), 1, none, jnp.stack([r, r]),
        interpret=True))
    s, ys = packed, []
    for r in range(t):
        out, s = step(s, jnp.int32(r))
        ys.append(np.asarray(out[r]))
    np.testing.assert_allclose(np.stack(ys), want_y, rtol=1e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(unpack_state(s, p)[0, 1]), want_s,
                               rtol=1e-5, atol=1e-5)


@pytest.fixture(scope="module")
def mixer():
    """ONE Mamba-2 mixer of ``tiny-ssm-moe`` on float32 rows: ``run(x, s,
    conv, start, length, fresh)`` over a flat batch of 80 rows and two
    slots -> (the mixer's output rows, the state, the carry)."""
    cfg = get_config("tiny-ssm-moe")
    lp = jax.tree.map(lambda a: a[0], tf.init_params(
        cfg, jax.random.PRNGKey(5), jnp.float32)["ssm_layers"])
    # (Seeded taps, bias, rate and skip: every term away from 0 and 1.)
    assert float(jnp.abs(lp["conv_b"]).min()) > 0

    @jax.jit
    def run(x, s_all, conv, start, length, fresh):
        z, xs, b, c, dt, g, conv = tf._ssm_in(x, lp, cfg, conv, start,
                                              length, fresh)
        y, s_all = tf._ssm_state(xs, b, c, dt, g, s_all, 0, start, length,
                                 fresh)
        return tf._ssm_out(y, xs, z, lp, cfg), s_all, conv

    return cfg, run


def test_a_prompt_cut_at_every_offset_of_a_block_leaves_the_same_state(
        mixer):
    """70 rows of one sequence in one pass, and cut in two at each of the
    69 offsets (inside the convolution's reach of the start and of the end,
    inside a block of the scan, at its edge, a last piece of ONE row, which
    the kernel takes): the state, the carry and every row's output are the
    one pass's."""
    cfg, run = mixer
    rng = np.random.default_rng(3)
    rows, t = 80, 70
    x = np.zeros((rows, cfg.hidden_size), np.float32)
    x[5:5 + t] = rng.standard_normal((t, cfg.hidden_size))
    s0 = pack_state(jnp.asarray(rng.standard_normal(
        (1, 2, cfg.ssm_num_heads, cfg.ssm_head_dim, cfg.ssm_state_size)),
        jnp.float32), cfg.ssm_num_heads // cfg.ssm_groups)   # dirty
    c0 = jnp.asarray(rng.standard_normal((2, cfg.ssm_conv - 1,
                                          cfg.ssm_conv_dim)), jnp.float32)

    def lane(first, n, fresh):
        return (jnp.array([0, first], jnp.int32), jnp.array([0, n], jnp.int32),
                jnp.array([False, fresh]))

    want, s_want, c_want = run(jnp.asarray(x), s0, c0, *lane(5, t, True))
    assert float(jnp.abs(s_want[0, 1] - s0[0, 1]).max()) > 0.1
    assert np.array_equal(np.asarray(s_want[0, 0]), np.asarray(s0[0, 0]))
    scale = float(jnp.abs(want[5:5 + t]).max())
    for cut in range(1, t):
        a, s, c = run(jnp.asarray(x), s0, c0, *lane(5, cut, True))
        b, s, c = run(jnp.asarray(x), s, c, *lane(5 + cut, t - cut, False))
        got = np.concatenate([np.asarray(a[5:5 + cut]),
                              np.asarray(b[5 + cut:5 + t])])
        assert np.abs(got - np.asarray(want[5:5 + t])).max() \
            < 1e-4 * scale, cut
        np.testing.assert_allclose(np.asarray(s), np.asarray(s_want),
                                   rtol=1e-4, atol=1e-6, err_msg=str(cut))
        np.testing.assert_allclose(np.asarray(c), np.asarray(c_want),
                                   rtol=1e-6, atol=1e-7, err_msg=str(cut))


def test_the_gate_goes_ahead_of_a_norm_over_each_group_of_channels():
    """``_ssm_out``: ``(y + D x) silu(z)``, then an RMS norm over each of
    the G groups ALONE (a group ten times as loud as the other is brought
    to the same size, which one norm over all channels would not do), times
    the learnt weight, then the output projection."""
    cfg = get_config("tiny-ssm-moe")
    rng = np.random.default_rng(4)
    t, h, p, e = 6, cfg.ssm_num_heads, cfg.ssm_head_dim, cfg.hidden_size
    y = rng.standard_normal((t, h, p)).astype(np.float32)
    y[:, : h // 2] *= 10.0                                   # group 0 loud
    xs = rng.standard_normal((t, h, p)).astype(np.float32)
    z = rng.standard_normal((t, h * p)).astype(np.float32)
    lp = {"d_skip": rng.standard_normal(h).astype(np.float32),
          "ssm_norm": rng.standard_normal(h * p).astype(np.float32),
          "w_out": rng.standard_normal((h * p, e)).astype(np.float32)}
    got = tf._ssm_out(*(jnp.asarray(a) for a in (y, xs, z)),
                      jax.tree.map(jnp.asarray, lp), cfg)
    v = ((y + lp["d_skip"][None, :, None] * xs).reshape(t, -1)
         * (z / (1 + np.exp(-z)))).astype(np.float64)
    one = v / np.sqrt((v * v).mean(-1, keepdims=True))
    v = v.reshape(t, cfg.ssm_groups, -1)
    v = v / np.sqrt((v * v).mean(-1, keepdims=True) + cfg.rms_norm_eps)
    want = (v.reshape(t, -1) * lp["ssm_norm"]) @ lp["w_out"]
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4, atol=2e-4)
    assert np.abs((one * lp["ssm_norm"]) @ lp["w_out"] - want).max() > 1.0


def test_the_walk_folds_runs_of_equal_periods_and_of_pairs():
    """``pattern_walk``: the published pattern is five equal periods (ONE
    traced body), a longer one and a tail with no attention layer; the
    layers it names, in order, are the pattern's; the counts follow it."""
    cfg = dataclasses.replace(get_config("tiny-ssm-moe"),
                              layer_pattern=PUBLISHED, num_layers=52)
    period = (("ME", 2), ("M", 1), ("*", 1), ("E", 1))
    assert cfg.pattern_walk() == (
        (period, 5),
        ((("ME", 3), ("M", 1), ("*", 1), ("E", 1)), 1),
        ((("ME", 4),), 1))
    assert (cfg.num_linear_layers, cfg.num_full_layers,
            cfg.num_routed_layers) == (23, 6, 23)
    for pattern in (PUBLISHED, "MEM*EMEM*EM*EME", "ME*EMEM*EM", "*MMEE*",
                    "EM*"):
        c = dataclasses.replace(cfg, layer_pattern=pattern,
                                num_layers=len(pattern))
        unfolded = "".join(
            "".join(run * n for run, n in items) * times
            for items, times in c.pattern_walk())
        assert unfolded == pattern
        assert c.layer_kinds() == tuple(
            {"M": "ssm", "E": "moe", "*": "full"}[k] for k in pattern)


def test_the_published_file_is_read_whole_and_counts_what_was_published():
    """The catalog row's ``config`` through the reader: the pattern whole,
    the mixer's sizes, a two-matrix relu^2 expert, an ungated shared expert
    of its own width, no rotation; 31.6 B parameters, the model's own
    figure, and this chip's eighth of the experts and the vocabulary."""
    d = harness.published("nemotron-3-nano-30b-ep8", n_routed_experts=128,
                          vocab_size=131072)
    cfg = ModelConfig.from_hf_config(d, name="m")
    assert cfg.layer_pattern == PUBLISHED and cfg.num_layers == 52
    assert (cfg.ssm_num_heads, cfg.ssm_head_dim, cfg.ssm_state_size,
            cfg.ssm_groups, cfg.ssm_conv) == (64, 64, 128, 8, 4)
    assert (cfg.ssm_dim, cfg.ssm_conv_dim) == (4096, 6144)
    assert cfg.expert_act == "relu2" and not cfg.use_rope
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (32, 2, 128)
    assert (cfg.num_experts, cfg.num_experts_per_tok,
            cfg.routed_scaling_factor, cfg.scoring_func) == (
                128, 6, 2.5, "sigmoid")
    assert cfg.moe_shared_expert_intermediate_size == 3712
    assert cfg.rms_norm_eps == 1e-5 and cfg.recurrent and not cfg.linear
    assert abs(cfg.num_params() / 1e9 - 31.58) < 0.01
    held = ModelConfig.from_hf_config(
        harness.published("nemotron-3-nano-30b-ep8"), name="m"
    ).with_expert_share(8, 0)
    assert (held.num_experts, held.router_width, held.vocab_size) == (
        16, 128, 16384)
    assert abs(held.num_params() / 1e9 - 5.26) < 0.01
    cache = jax.eval_shape(lambda: tf.init_paged_cache(
        held, 8, 256, jnp.bfloat16, quantized=True, pad_head=True,
        state_slots=2))
    # Two heads of 64 side by side a tile, the state's width down.
    assert cache.lin.s.shape == (23, 2, 32, 128, 128)
    assert cache.lin.s.dtype == jnp.float32
    assert cache.lin.conv.shape == (23, 2, 3, 6144)
    assert cache.k.shape == (6, 8, 2, 256, 128)
    slot = sum(np.prod(a.shape) * a.dtype.itemsize for a in cache.lin) // 2
    assert slot == 23 * (64 * 64 * 128 * 4 + 3 * 6144 * 2)      # 49.1 MB


def test_the_published_pattern_at_test_widths_is_the_references_forward():
    """All 52 layers of the published pattern, at ``tiny-ssm-moe``'s widths
    and under its share, through ``mixed_step`` on float32 activations: a
    prompt in two chunks (the second ends inside a block of the scan),
    then two decode steps, beside another lane; each step's logits are the
    reference family's one full forward."""
    over = dict(hybrid_override_pattern=PUBLISHED, num_hidden_layers=52)
    ref, config = harness.reference("tiny-ssm-moe", "ssm_moe", **over)
    cfg = ModelConfig.from_hf_config(
        harness.published("tiny-ssm-moe", **over), name="published-pattern"
    ).with_expert_share(2, 1)
    seed = 13
    params = jax.tree.map(
        lambda a: a.astype(jnp.float32) if a.dtype == jnp.bfloat16 else a,
        quant.init_params_quantized(cfg, jax.random.PRNGKey(seed),
                                    jnp.bfloat16, bits=8))
    assert params["ssm_layers"]["a_log"].shape[0] == 23
    assert params["layers"]["wo"]["q"].shape[0] == 6
    weights = ref.generate_weights(config, seed)
    slots, page, max_pages, rows = 2, 16, 8, 96
    tables = jnp.arange(slots * max_pages, dtype=jnp.int32).reshape(
        slots, max_pages)
    step = jax.jit(lambda c, *a: tf.mixed_step(params, cfg, c, tables, *a))
    cache = tf.init_paged_cache(cfg, slots * max_pages, page, jnp.float32,
                                state_slots=slots)
    rng = np.random.default_rng(7)
    ids = {0: rng.integers(2, 258, 92).astype(np.int32),
           1: rng.integers(2, 258, 30).astype(np.int32)}
    at, got = {0: 0, 1: 0}, {0: [], 1: []}
    for plan in ({0: 20}, {0: 70, 1: 1}, {0: 1, 1: 1}, {0: 1, 1: 28}):
        a = dict(tokens=np.zeros(rows, np.int32),
                 slot=np.full(rows, -1, np.int32),
                 pos=np.full(rows, page * max_pages, np.int32),
                 src=np.zeros(slots, np.int32), qs=np.zeros(slots, np.int32),
                 ql=np.zeros(slots, np.int32), ps=np.zeros(slots, np.int32))
        row = 1
        for slot, n in plan.items():
            p0 = at[slot]
            a["tokens"][row:row + n] = ids[slot][p0:p0 + n]
            a["slot"][row:row + n] = slot
            a["pos"][row:row + n] = np.arange(p0, p0 + n)
            a["qs"][slot], a["ql"][slot], a["ps"][slot] = row, n, p0
            a["src"][slot] = row + n - 1
            row, at[slot] = row + n, p0 + n
        logits, cache = step(cache, *(jnp.asarray(a[k]) for k in (
            "tokens", "slot", "pos", "src", "qs", "ql", "ps")))
        for slot in plan:
            got[slot].append((at[slot] - 1, np.asarray(logits[slot])))
    for slot, seen in got.items():
        want = ref.forward(config, weights, ids[slot][None],
                           np.asarray([[r for r, _ in seen]], np.int32))[0]
        for (r, lg), w in zip(seen, want):
            assert np.abs(lg - w).max() < 2e-4 * w.std() + 1e-6, (slot, r)
