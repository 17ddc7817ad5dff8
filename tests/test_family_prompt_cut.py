"""One family, every block that keeps a state a slot, and the block of two
latent sublayers a layer (ROADMAP D9): the step
program on float32 activations against the reference family's full forward,
a prompt cut at odd lengths and then decoded, decode and prefill lanes in
one flat batch, a slot another sequence just left; and the controls that
show a stale or a rounded state WOULD be seen.  A block joins by a row of
``BLOCKS``; what only one block has to say of its stepper (the readings a
published config leaves open) stands here too, behind the one fixture."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from arks_tpu.models import quant, transformer as tf
from arks_tpu.models.config import get_config

import harness

# preset: the reference family; whether the gated norms' weights (zeros as
# seeded: a scale of 1 under either reading of the norm) are redrawn at 0.5
# sigma in program and reference alike, so that the norm's form reaches the
# logits; how far a state rounded to bfloat16 after every token parts, in
# logit sigmas (None: the block keeps pages alone, no state to round).
BLOCKS = {
    "tiny-shortcut-mla-moe": dict(family="shortcut_mla_moe", norms=False,
                                  rounded=None),
    "tiny-linear-moe": dict(family="linear_moe", norms=False, rounded=1e-2),
    "tiny-latent-linear-moe": dict(family="latent_linear_moe", norms=True,
                                   rounded=5e-3),
    # (The selective scan's state is a few percent of what a mixer returns
    # under seeded weights, the skip ``D x`` the rest: a rounded state parts
    # by less than the delta rule's, and still by fifteen times the 2e-4.)
    "tiny-ssm-moe": dict(family="ssm_moe", norms=False, rounded=3e-3),
}


def _norm_leaf(name: str) -> bool:
    return name.endswith("_norm") and name != "o_norm"


@pytest.fixture(scope="module", params=sorted(BLOCKS))
def stepper(request):
    """The step program on float32 activations over the family's own
    weights (what is stored in bfloat16 widened, which is exact), sequences
    through 4 slots: (fresh cache, step, reference forward, the block's
    row)."""
    preset, block = request.param, BLOCKS[request.param]
    ref, config = harness.reference(preset, block["family"],
                                    n_routed_experts=8)
    seed = 11
    cfg = dataclasses.replace(get_config(preset), num_experts=8)
    cfg = cfg.with_expert_share(2, 1)
    params = jax.tree.map(
        lambda a: a.astype(jnp.float32) if a.dtype == jnp.bfloat16 else a,
        quant.init_params_quantized(cfg, jax.random.PRNGKey(seed),
                                    jnp.bfloat16, bits=8))
    weights = ref.generate_weights(config, seed)
    rng = np.random.default_rng(3)
    for path in sorted(weights) if block["norms"] else ():
        if _norm_leaf(path.rsplit("/", 1)[-1]):
            assert not weights[path].any()           # seeded: a scale of 1
            w = (rng.standard_normal(weights[path].shape) * 0.5).astype(
                np.float32)
            weights[path] = w
            tree, _, leaf = path.rpartition("/")
            (params[tree] if tree else params)[leaf] = jnp.asarray(w)
    step = jax.jit(lambda c, *a: tf.mixed_step(params, cfg, c, *a))
    slots, page, max_pages = 4, 16, 16
    tables = jnp.arange(slots * max_pages, dtype=jnp.int32).reshape(
        slots, max_pages)

    def fresh_cache():
        return tf.init_paged_cache(cfg, slots * max_pages, page, jnp.float32,
                                   state_slots=slots if cfg.recurrent else 0)

    def run(cache, lanes, rows=100):
        """One step over ``lanes``: {slot: (token ids, first position)}.
        Returns (logits at each lane's last row, cache)."""
        a = dict(tokens=np.zeros(rows, np.int32),
                 slot=np.full(rows, -1, np.int32),
                 pos=np.full(rows, page * max_pages, np.int32),
                 src=np.zeros(slots, np.int32), qs=np.zeros(slots, np.int32),
                 ql=np.zeros(slots, np.int32), ps=np.zeros(slots, np.int32))
        at = 1                                        # a padding row ahead
        for slot, (ids, p0) in lanes.items():
            n = len(ids)
            a["tokens"][at:at + n], a["slot"][at:at + n] = ids, slot
            a["pos"][at:at + n] = np.arange(p0, p0 + n)
            a["qs"][slot], a["ql"][slot], a["ps"][slot] = at, n, p0
            a["src"][slot] = at + n - 1
            at += n
        logits, cache = step(cache, tables, *(jnp.asarray(a[k]) for k in (
            "tokens", "slot", "pos", "src", "qs", "ql", "ps")))
        return {s: np.asarray(logits[s]) for s in lanes}, cache

    def want(ids, rows, **over):
        return ref.forward(dict(config, **over), weights,
                           np.asarray(ids, np.int32)[None],
                           np.asarray(rows, np.int32)[None])[0]

    return fresh_cache, run, want, dict(block, cfg=cfg)


def test_a_prompt_cut_at_odd_lengths_then_decoded_is_the_full_forward(
        stepper):
    """Chunks of 70, 63, 1, 1, 37 rows (ends inside blocks of the scan,
    inside pages of 16, single rows between chunks), then decode steps
    through the block's pages and state: each step's logits are the
    reference's one forward at that position.  Meanwhile ANOTHER sequence
    decodes and then prefills in the same flat batches, into a slot whose
    last sequence left its state there and reads zeros at position 0."""
    fresh_cache, run, want, block = stepper
    rng = np.random.default_rng(5)
    a_ids = rng.integers(2, 258, 180).astype(np.int32)
    b_ids = rng.integers(2, 258, 90).astype(np.int32)
    c_ids = rng.integers(2, 258, 40).astype(np.int32)
    cache = fresh_cache()
    cfg = block["cfg"]
    # The pool holds the attention layers (a latent pool one row a token and
    # no values), the state the linear ones.
    # (The shortcut block: two attention sublayers a layer, a pool row
    # each, and no state.)
    assert cache.k.shape[0] == cfg.num_attn_sublayers \
        == cfg.num_full_layers * (2 if cfg.shortcut else 1)
    assert (cache.v is None) == bool(cfg.latent)
    assert (cache.lin is None) == bool(cfg.shortcut)
    if cfg.recurrent:
        assert cache.lin.s.shape[0] == cfg.num_linear_layers == 6
    # Slot 0 is left dirty by sequence C, which then ends.
    _, cache = run(cache, {0: (c_ids, 0)})
    assert not cfg.recurrent or float(jnp.abs(cache.lin.s[:, 0]).max()) > 0
    got_a, got_b, pa, pb = [], [], 0, 0
    plan = [(70, 20), (63, 1), (1, 1), (1, 30), (37, 1), (1, 37), (1, 0),
            (1, 0), (5, 0)]
    for ta, tb in plan:
        lanes = {2: (a_ids[pa:pa + ta], pa)}
        if tb:
            lanes[0] = (b_ids[pb:pb + tb], pb)        # reuses C's slot
        out, cache = run(cache, lanes)
        pa, pb = pa + ta, pb + tb
        got_a.append((pa - 1, out[2]))
        if tb:
            got_b.append((pb - 1, out[0]))
    for ids, got in ((a_ids, got_a), (b_ids, got_b)):
        rows = [r for r, _ in got]
        ref_logits = want(ids, rows)
        for (r, lg), w in zip(got, ref_logits):
            # float32 on both sides: 1e-5 of a logit sigma as read, the
            # chunk form's triangular solve against the token recurrence.
            assert np.abs(lg - w).max() < 2e-4 * w.std() + 1e-6, r
    # Slot 1 and 3 were never touched.
    if cfg.recurrent:
        assert not float(jnp.abs(cache.lin.s[:, (1, 3)]).max())
    else:
        assert not float(jnp.abs(cache.k[:, 16:32]).max())
        assert not float(jnp.abs(cache.k[:, 48:]).max())


def test_a_stale_state_would_show(stepper):
    """The control of the test above: the same prompt into the dirty slot
    WITHOUT starting at position 0 reads what the last sequence left."""
    fresh_cache, run, want, _ = stepper
    rng = np.random.default_rng(6)
    ids = rng.integers(2, 258, 30).astype(np.int32)
    cache = fresh_cache()
    _, cache = run(cache, {0: (rng.integers(2, 258, 40).astype(np.int32), 0)})
    clean, _ = run(cache, {0: (ids, 0)})
    w = want(ids, [29])[0]
    assert np.abs(clean[0] - w).max() < 2e-4 * w.std() + 1e-6
    # Position 1 on: the program reads the slot's state (and pages that
    # hold another sequence's keys): far off.
    dirty, _ = run(cache, {0: (ids[1:], 1)})
    assert np.abs(dirty[0] - w).max() > 0.05 * w.std()


@pytest.mark.parametrize(
    "stepper", sorted(b for b, row in BLOCKS.items() if row["rounded"]),
    indirect=True)
def test_a_state_kept_in_bfloat16_shows_where_the_activations_are_float32(
        stepper):
    """The control the chip cannot read (PERF.md §2: there bfloat16
    activations put a floor under every position that a bfloat16 state
    does not rise above): on float32 activations the step program is the
    float32-state reference to 2e-4 of a logit sigma, and the reference
    whose state is rounded to bfloat16 after every token is fifty times
    that away from both."""
    fresh_cache, run, want, block = stepper
    rng = np.random.default_rng(8)
    ids = rng.integers(2, 258, 96).astype(np.int32)
    got, _ = run(fresh_cache(), {1: (ids, 0)})
    sound = want(ids, [95])[0]
    rounded = want(ids, [95], reference_state_dtype="bfloat16")[0]
    assert np.abs(got[1] - sound).max() < 2e-4 * sound.std() + 1e-6
    assert np.abs(got[1] - rounded).max() > block["rounded"] * sound.std()


@pytest.mark.parametrize("stepper", ["tiny-latent-linear-moe"], indirect=True)
@pytest.mark.parametrize("reading", [
    "norm_sigmoid", "post_norm", "gate_scale", "conv", "state", "mscale",
    "gate", "router_bias", "swiglu_limit"])
def test_program_and_reference_hold_the_same_reading_of_every_open_key(
        stepper, reading):
    """Each reading the published config leaves open (``deploy.json``'s
    ``assumed``), computed the OTHER way by the reference on the same
    weights, parts from the program by a hundred times and more what the
    shared reading does (read at this size: 0.04 of a logit sigma for the
    softmax scale's ``m^2``, 0.6 and more for every other)."""
    fresh_cache, run, want, _ = stepper
    rng = np.random.default_rng(9)
    ids = rng.integers(2, 258, 120).astype(np.int32)
    cache = fresh_cache()
    got1, cache = run(cache, {3: (ids[:90], 0)}, rows=100)
    got2, _ = run(cache, {3: (ids[90:], 90)}, rows=100)
    same, other = (want(ids, [89, 119], **over) for over in (
        {}, {"reference_without": [reading]}))
    for lg, s, o in zip((got1[3], got2[3]), same, other):
        assert np.abs(lg - s).max() < 2e-4 * s.std() + 1e-6
        assert np.abs(lg - o).max() > 0.02 * s.std(), reading


@pytest.mark.parametrize("stepper", ["tiny-ssm-moe"], indirect=True)
@pytest.mark.parametrize("chunk", [1, 17, 64, 100, 150])
def test_prefill_in_chunks_of_any_size_then_decode_is_the_full_forward(
        stepper, chunk):
    """A prompt of 150 tokens prefilled ``chunk`` rows a step (one row a
    step: the one-step kernel all the way; 17: ends inside a block of the
    scan and inside the convolution's reach; 64 and 150: whole blocks, one
    step), then three decode steps through the pages, the carry and the
    state: every step's logits are the reference's one full forward."""
    fresh_cache, run, want, _ = stepper
    rng = np.random.default_rng(12)
    ids = rng.integers(2, 258, 153).astype(np.int32)
    cache, got, at = fresh_cache(), [], 0
    for n in [chunk] * (150 // chunk) + [150 % chunk] * bool(150 % chunk) \
            + [1, 1, 1]:
        out, cache = run(cache, {3: (ids[at:at + n], at)}, rows=160)
        at += n
        got.append((at - 1, out[3]))
    got = got[-8:]
    for (r, lg), w in zip(got, want(ids, [r for r, _ in got])):
        assert np.abs(lg - w).max() < 2e-4 * w.std() + 1e-6, (chunk, r)


# What dropping one published term of a mixer or of a router does to the
# logits at this size, in logit sigmas, as read (the largest of two
# positions; tests/test_ssm_layers.py says why the state and the decay's
# rate read low at a width of 64): each at least a third of its reading, and
# ten times and more what program and reference agree to.
_SSM_TERMS = {"state": 0.1, "conv": 0.5, "conv_bias": 0.1, "a_log": 2e-3,
              "d_skip": 0.5, "gate": 1.0, "router_bias": 0.1}


@pytest.mark.parametrize("stepper", ["tiny-ssm-moe"], indirect=True)
@pytest.mark.parametrize("term", sorted(_SSM_TERMS))
def test_every_published_term_of_the_one_sublayer_block_is_live(stepper,
                                                                term):
    """The state, the convolution's older taps and its bias, the decay's
    rate ``A_log``, the skip ``D``, the gate ``silu(z)`` and the selection
    bias: the reference computed WITHOUT one of them on the same seeded
    weights parts from the program, which agrees with the whole one."""
    fresh_cache, run, want, _ = stepper
    rng = np.random.default_rng(9)
    ids = rng.integers(2, 258, 120).astype(np.int32)
    cache = fresh_cache()
    got1, cache = run(cache, {3: (ids[:90], 0)}, rows=100)
    got2, _ = run(cache, {3: (ids[90:], 90)}, rows=100)
    same, other = (want(ids, [89, 119], **over) for over in (
        {}, {"reference_without": [term]}))
    far = 0.0
    for lg, s, o in zip((got1[3], got2[3]), same, other):
        assert np.abs(lg - s).max() < 2e-4 * s.std() + 1e-6
        far = max(far, np.abs(lg - o).max() / s.std())
    assert far > _SSM_TERMS[term], (term, far)
