"""The lowered step programs of the configurations a change must NOT move
(PR 29's method): an engine a preset on the CPU, its sequential and
pipelined mixed programs lowered on their own operands, sha256 of the
StableHLO text (no source locations in it, so only the traced ops count).

The pins are the values of commit eaa1b59 (PR 32), taken with this file in
that tree.  PR 33 rewrote the dispatch of a routed layer held whole with
QUANTISED experts; ``tiny`` has no experts, and ``tiny-mla-moe`` (the
latent block) and ``tiny-swa-moe`` (window and full layers) under a share
run ``moe._batched_dispatch``'s loop as they did, int8 leaves included, so
all twelve stood.  A PR that means to change one of these
programs re-pins it and says so.

PR 36 re-pinned FOUR, the sequential programs of the two routed presets:
``moe._batch_pays`` now sends a SHARE's layer to the dense dispatch too
while an expert's batch (``_held_capacity``) would be every row, as it
sent a layer held whole since PR 33 (on the chip the batched form of such
a step copied every expert leaf out of its stack: 41.9 -> 26.3 ms for a
64-row decode step at Solar-Open2's widths, PERF.md §6).  At these
presets' 2 + 64 rows an expert's batch (whole tiles of 128) is every row,
so their 66-row steps are dense now.  The
benchmark's cells do not move: kimi's and laguna's sequential steps (1032
/ 1056 / 288 rows against batches of 128 / 256 / 128) stay batched and
their pipelined steps (8 / 32 rows) were dense already.  The eight other
pins are PR 32's values still, through ``_mixed_step_periods`` (PR 36's
one period scan for window and linear inner layers) too.

So that the share's BATCHED loop stays pinned inside a step program, the
two routed presets are lowered a second time at a shape where an expert's
batch is smaller than the step (``@wide``: 2 + 512 rows, top-4 of 32
scored, a batch of 384): these four pins are what commit c459215 (PR 33,
the parent of PR 36) lowers at the same shape, taken with this file in
that tree, so at such a shape PR 36's rule changes nothing.

PR 40 ADDED six pins and moved none: ``tiny-linear-moe`` (the
``solar_open2`` block) at both shapes, the values of commit 47905df (PR 39,
the parent of PR 40), taken with this file in that tree.  PR 40 made the
latent block the period scan's full-layer kind, let the head stack take
linear layers, and taught ``_linear_qkv`` / ``_linear_out`` a decay a head
and grouped key heads: every such branch is decided by the configuration
while the program is traced, and the three presets that share that code
lower the text they lowered before.

PR 41 RE-PINNED those six and moved none of the sixteen others: the lanes
of one row of ``transformer.py::_linear_state`` take their recurrence step
in a Pallas kernel now (``ops/linear_state.py``; on the CPU its interpreted
form is what these programs hold) where a ``jax.numpy`` pass over every
slot's state stood, so every program of a model WITH linear layers lowers
other text, as it should.  ``_linear_state`` has one caller, reached only
where ``cfg.linear``: ``tiny``, ``tiny-mla-moe``, ``tiny-swa-moe`` and their
``@wide`` shapes hold no such layer and stand at the values they had.  A
later edit of the kernel's body moves these six again, and re-pins them.

PR 44 RE-PINNED all eighteen of the three routed presets and moved none of
``tiny``'s four, for two reasons.  The six ``@wide`` programs moved because
the dispatch they exist to pin changed: a share's ``_SPARE_TILES`` overflow
tiles run unrolled, what a layer needs beyond them in a loop that takes its
expert out of the STACKED tree by (layer, expert) where the loop from tile 0
closed over the layer's slice (on the chip that slice was a buffer to make:
three int8 expert leaves copied whole a layer a step).  The twelve others
(2 + 64 rows: the dense dispatch, no tile at all) moved only by the counts:
a routed layer hands back int32 ``[3]`` (held pairs, overflow tiles needed,
those the loop ran) where a scalar stood, ``with_counts`` puts four entries
behind the ids where two stood, and the latent forward's two scans (its own
function until PR 46) hand a
layer its index in ITS stack (the pool's index is that plus the stack's
base).  NEW: ``whole-layer``, the branch mixtral runs and no step pin covers
(a layer held WHOLE, int8 leaves, 8 experts top-2 at 320 rows: the batched
dispatch with four unrolled tiles), ``moe.moe_ffn`` lowered on shapes alone;
its value is what commit 24dfdfe (PR 41, the parent of PR 44) lowers, taken
with ``whole_layer_hash`` in that tree, so that the tile's one reading
(``dynamic_slice`` with an empty lead) is shown to be the text
``dynamic_index_in_dim`` of the layer's leaf was.

PR 45 moved NONE of the twenty-two (ISSUE 45 expected all of them to move;
they stand, and here is why).  It rewrote the KV row write's Pallas kernel
(``ops/paged_attention.py::paged_kv_update`` / ``paged_kv_update_quant``: one
read-modify-write a touched block, the next block's read in flight).  Every
preset here holds a page pool, but an engine on the CPU that nobody steers
resolves to the XLA attention branch, whose row write is
``paged_update_xla``'s scatter: these programs never carried the kernel's
interpreted form, and no line of them is traced through the file's changed
half.  So the pins say only that the step programs AROUND the write are the
text they were.  What the kernel itself leaves in the pools is held byte
for byte against that scatter by ``tests/test_paged_attention.py::
test_paged_update_matches_oracle`` (pool kinds x batch layouts) and
``test_paged_update_waits_for_what_it_reads``; that the chip's compiler
takes it, in place, at the cells' row counts by ``tests/test_chip_compile.py``;
and that no cell's outputs move by the driver's cells on the chip
(``logprob_err`` on a pair's seed), not by pins.

PR 46 ADDED eight pins, then folded the three forwards of
``transformer.py::mixed_step`` into one period scan, and RE-PINNED the six of
``tiny-mla-moe`` for one op; the twenty-five others (the sixteen old ones of
``tiny``, ``tiny-swa-moe``, ``tiny-linear-moe``, ``whole-layer``, and the
eight new ones) stand.  NEW, taken BEFORE any edit of ``transformer.py``:
``tiny-mixtral`` (a dense block WITH routed layers, lowered as its cell
runs it: layers held WHOLE, int8 leaves, so this preset alone gets no
share here) and ``tiny-latent-linear-moe`` (the ``gigachat3_5`` block under
a share); their values are what commit fcc7e93 (PR 45, the parent of PR
46) lowers, taken with this file in that tree (a ``git archive`` of it).
The fold: a block without inner layers (``tiny``, ``tiny-mixtral``: no head
stack; ``tiny-mla-moe``: its dense prefix is the head stack) is the period
scan with NO inner layers a period, and the scan then traces no inner scan
and carries nothing for one; ``layer()`` took the mesh arguments of the
dense body; ``with_held`` False hands a routed layer neither the mask nor
its stack and the scans emit no counts, which is what a dense block's
program held (mixtral's routed layers are handed what they were: none of
its four moved).  Every such branch is decided by the configuration while
the program is traced.  The ONE op that differs, in all six programs of
``tiny-mla-moe``, is the index of a routed layer's page in the latent pool
inside the scan over the routed stack: ``stablehlo.add %at, %c1`` (the
deleted latent forward wrote ``at + base``) is ``stablehlo.add %c1, %at``
(the period scan writes ``full_base + i``, as ``tiny-swa-moe``'s and
``tiny-linear-moe``'s pinned programs always did); a ``diff`` of the two
texts shows that line and no other.  An integer add commutes: compiled for
the CPU, the parent's and the change's four programs of the preset are the
same HLO line for line once source locations are stripped (4,067 / 4,250 /
3,921 / 4,067 lines), and kimi's cell is among those run on the chip before
and after (PERF.md §6, PR 46).

PR 47 ADDED four pins and moved none of the thirty: ``tiny-swa-sink-moe``
(the ``mimo_v2`` block: the windowed block told a KV head count a kind, a
value width, a sink logit a head of the window layers, a value scale, a
rotary share in both kinds, a first period cut short, sigmoid routing and
no shared expert) under a share, the values of PR 47's own tree.  The block
rides ``tiny-swa-moe``'s code: ``_kind_qkv``, ``layer()`` of the period
scan, ``paged_mixed_update_and_attend`` and (on the chip) ``_ragged_launch``
gained branches that the configuration decides while the program is traced
(``cfg.v_head_dim``, ``cfg.window_kv_heads``, ``cfg.attn_sink``,
``cfg.attn_value_scale``, ``cfg.window_partial_rotary_factor``), and
``ops/attention.py::_softmax`` took a sink argument; with none of them set
every older preset lowers the text it lowered, ``tiny-swa-moe``'s eight
programs included.

PR 48 RE-PINNED the twenty-four programs of the five presets WITH a GQA
stack (``tiny``, ``tiny-mixtral``, ``tiny-swa-moe`` at both shapes,
``tiny-swa-sink-moe``, ``tiny-linear-moe`` at both shapes: its two GQA
layers) and moved none of the ten others (``tiny-mla-moe`` at both shapes,
``tiny-latent-linear-moe``: latent and linear projections only) nor the
whole-layer pin.  The q / k / v leaves of a GQA stack are stored ``[L, H,
D, E]`` (heads split, the contraction dimension minor: what the chip's
step programs read, ``tf.init_params``) where they were ``[L, E, H x D]``,
so ``_qkv`` contracts ``"...e,hde->...hd"`` and its callers' reshapes are
gone: every program that projects q / k / v takes other operands and lowers
another dot, as it should; the numbers are the parent's (the seeded leaves
are its leaves transposed, ``tests/test_quant.py``; the served
log-probabilities hold to every reference as before).

PR 49 RE-PINNED the six ``@wide`` programs and moved none of the
twenty-eight others nor the whole-layer pin.  The ``@wide`` programs exist
to pin a share's batched dispatch, and that dispatch's SHAPE changed: a
share's batch an expert is the fewest 128-row tiles that hold THREE times
its fair load (``moe._held_capacity``; it was four times), so at 2 + 512
rows, top-4 of 32 scored (fair 65), an expert's batch and every overflow
tile behind it are 256 rows where they were 384; the ops are the same ops
on other extents (at the benchmark's whole-budget steps the same rule
gives one tile of 128 rows where two stood: laguna, gigachat, mimo).
Every program of 2 + 64 rows stands because the dense dispatch runs there
(an expert's batch would be every row under either multiple), the
pipelined programs with them; ``tiny`` has no experts; ``tiny-mixtral`` and
the whole-layer pin are the branch of a layer held WHOLE (one and a half
times the fair load, ``(n x k - 1) // cap`` tiles), which PR 49 does not
touch.  The new counter ``moe_batch_rows_total`` is worked out on the host
from the step's shape and the ``extra`` the step already hands back
(``moe.share_rows``), so no program gained an output.

PR 53 RE-PINNED the six ``@wide`` programs and the whole-layer pin and
moved none of the twenty-eight others.  Both are the batched dispatch, and
its COMBINE changed: the batch's rows and the unrolled tiles' go back on
their tokens in one ``ns,se->ne`` contraction with a selection matrix
(``moe._combine``; a trip of a share's loop contracts its own tile) where
every part was scatter-added (values that are not finite count as 0 in
it: a contraction would hand them to every token), and the experts' sizes
are a compare and a sum where ``jnp.bincount`` was a second scatter-add; the sort, the
gathers, the three expert dots, ``held``, ``tiles`` and the three counts
are the ops they were (``tests/test_moe.py`` holds the result to the dense
float32 dispatch at every cell's step shape and counts the tiles by hand).
Every program of 2 + 64 rows stands (the dense dispatch), the pipelined
programs and ``tiny`` with them; ``tiny-mixtral``'s 66-row step is dense
too, which is why the branch its cell's 320-row step takes is the
whole-layer pin's.

PR 54 ADDED four pins and moved none of the thirty-four nor the whole-layer
pin: ``tiny-shortcut-mla-moe`` (the ``longcat_flash`` block: two latent
sublayers and two dense FFNs a layer, a routed layer with 8 identity experts
behind 16 real ones on a shortcut, softmax scores with a selection bias)
under a share, the values of PR 54's own tree.  The block is a third
full-layer kind of the period scan (``shortcut_layer``) and rides the latent
block's ``_mla_q`` / ``_mla_kv`` / ``_mla_out`` and ``moe.moe_ffn``; what
those gained is decided by the configuration while the program is traced
(``cfg.mla_q_scale`` / ``mla_kv_scale`` in ``_norm``, a ``bias`` handed to
the softmax rule of ``router_topk``, ``cfg.zero_experts`` in both dispatches
and in the width of the counts, ``cfg.attn_sublayers`` in the pool's leading
dimension), and ``router_weights`` was split into ``router_topk`` and
``_held_weights`` (the same ops in the same order), so with none of them
set every older preset lowers the text it lowered.  (No ``@wide`` shape of
the new preset: at 2 + 512 rows, top-4 of 24 scored, an expert's batch is
384 rows, not the 256 that shape asserts.)

PR 55 moved NONE of the thirty-eight nor the whole-layer pin (ISSUE 55
expected every program that holds a ragged launch to move; they stand, for
PR 45's reason).  It changed the LAYOUT of the ragged launch's query, output
and carried-state blocks (``ops/paged_attention.py::_ragged_launch``: a
block's ``G x block_q`` rows arrive merged, ``[.., G x block_q, D]``, where
the kernel merged a ``[.., G, block_q, D]`` block once a page).  An engine on
the CPU that nobody steers resolves to the XLA attention branch, so none of
these programs ever carried the launch or the gathers around it, and no
line of them is traced through the changed functions.  What holds the launch
itself: ``tests/test_ragged_grid.py::
test_merged_rows_block_is_the_parents_bytes`` (thirty cases against a dense
oracle and, byte for byte, against digests the parent commit a0b2fc9 gave
for the same seeded inputs), the older kernel suites of
``tests/test_paged_attention.py``, and ``tests/test_chip_compile.py`` (the
chip's compiler takes the merged block at every cell's shape; in longcat's
compiled step the launch's output is read through a bitcast where the
parent's step copied it out of ``T(2,128)`` tiles).

PR 57 RE-TOOK the fourteen pins of the three latent presets (``tiny-mla-moe``
at both shapes, ``tiny-latent-linear-moe``, ``tiny-shortcut-mla-moe``) and
moved none of the twenty-four others nor the whole-layer pin.  The latent
block's two up projections are stored head-split, contraction dimension
minor (``wq_b`` ``[L, H, nope + rope, q_lora]``, ``wkv_b`` ``[L, H, nope + v,
kv_lora]``; ``[L, 2, ..]`` by sublayer: ``tf.init_params``), so those
programs take operands of other shapes and ``_mla_q`` / ``_mla_out``
contract ``"...r,hdr->...hd"``, ``"bthn,hnc->bthc"`` and ``"thc,hvc->thv"``
with no reshape; the arithmetic is the drawn-order einsums'
(``tests/test_quant.py``), the seeded int8 values and scales the parent's
bit for bit.  No other preset has either leaf, and ``quant.contraction_axis``
answers as before for every other name.
"""

import hashlib
import types

import jax
import jax.numpy as jnp
import pytest

from arks_tpu.engine import EngineConfig, InferenceEngine
from arks_tpu.engine.tokenizer import ByteTokenizer
from arks_tpu.models import get_config, moe

PINS = {
    "tiny.seq": "0c278ada45cabbd2",
    "tiny.seq_lp": "491d011c67699524",
    "tiny.pipe": "4e1c1288c0815891",
    "tiny.pipe_lp": "51ca9446ab2834d8",
    "tiny-mla-moe.seq": "02b551d7c661a2b6",
    "tiny-mla-moe.seq_lp": "df47431e206ff0ff",
    "tiny-mla-moe.pipe": "1858476d570bed41",
    "tiny-mla-moe.pipe_lp": "e07d5d3f3064a040",
    "tiny-swa-moe.seq": "a74550b8ca32440a",
    "tiny-swa-moe.seq_lp": "dcd6bb16baa882d2",
    "tiny-swa-moe.pipe": "a5acbcd901fe6084",
    "tiny-swa-moe.pipe_lp": "fab7b8e5752c34a9",
    "tiny-mla-moe@wide.seq": "8ecbddb7cc314d43",
    "tiny-mla-moe@wide.seq_lp": "0204a6272d21c97c",
    "tiny-swa-moe@wide.seq": "d4d3888ce31c3b21",
    "tiny-swa-moe@wide.seq_lp": "2fba917a987e327a",
    "tiny-linear-moe.seq": "89d61e6062a43366",
    "tiny-linear-moe.seq_lp": "ff1f7609a933d0f2",
    "tiny-linear-moe.pipe": "751a7238e2d7bee6",
    "tiny-linear-moe.pipe_lp": "383c201b8f57bd2c",
    "tiny-linear-moe@wide.seq": "8dba54e083eabb7d",
    "tiny-linear-moe@wide.seq_lp": "01fe01ed2d2ba045",
    "tiny-mixtral.seq": "3c2050866b3daf71",
    "tiny-mixtral.seq_lp": "a7fd1b110fab0779",
    "tiny-mixtral.pipe": "5e7345d839d5dd71",
    "tiny-mixtral.pipe_lp": "76e8e6dcde5eb955",
    "tiny-latent-linear-moe.seq": "b478f06241fb7d00",
    "tiny-latent-linear-moe.seq_lp": "0e6ebb12f767c546",
    "tiny-latent-linear-moe.pipe": "86391cc2f4792e14",
    "tiny-latent-linear-moe.pipe_lp": "6f96aa001355e98b",
    "tiny-swa-sink-moe.seq": "cab321671b8a404a",
    "tiny-swa-sink-moe.seq_lp": "7db0abc59856f3c6",
    "tiny-swa-sink-moe.pipe": "ad254d3741195bb9",
    "tiny-swa-sink-moe.pipe_lp": "7616bbc242b77010",
    "tiny-shortcut-mla-moe.seq": "28aaa8e3f530f3d8",
    "tiny-shortcut-mla-moe.seq_lp": "1f3ca9ef4f701287",
    "tiny-shortcut-mla-moe.pipe": "2ebd0240d9c751ed",
    "tiny-shortcut-mla-moe.pipe_lp": "ff40a30bcdcf22d7",
}


def _lowered(eng, name):
    """One step program of ``eng`` lowered on its own operands: nothing is
    compiled (a hash of StableHLO text needs no executable, and the pipe
    programs' background warm-up is never kicked)."""
    if name.startswith("pipe"):
        return eng._pipe_jit_fn(name == "pipe_lp").lower(
            *eng._pipe_signature())
    operands, _ = eng._mixed_pack.host()
    fn = eng._mixed_lp_fn if name == "seq_lp" else eng._mixed_fn
    return fn.lower(eng.params, eng._cache, eng._sampling, operands,
                    eng._guide_dev)


def step_program_hashes(model: str, monkeypatch) -> dict:
    """``model`` is a preset's name, or ``<preset>@wide``: the same preset
    through a step of 2 + 512 rows, where a share's expert takes a batch
    of 256 (384 until PR 49) and the batched dispatch's loop is in the
    program."""
    monkeypatch.setenv("ARKS_PIPELINE_DEPTH", "2")
    monkeypatch.setenv("ARKS_MIXED_STEP", "auto")
    preset, _, wide = model.partition("@")
    cfg = get_config(preset)
    kw = dict(model=preset, num_slots=2, max_cache_len=128,
              prefill_buckets=(16,), prefill_chunk=16, kv_layout="paged")
    if cfg.num_experts:
        # A share, int8 leaves, and a step of 2 + 64 rows: the sequential
        # programs take the grouped rule (since PR 36: the dense dispatch,
        # an expert's batch being every row at these sizes).  Mixtral's
        # layers are held WHOLE, as its cell runs them: no share.
        if preset != "tiny-mixtral":
            cfg = cfg.with_expert_share(2, 0)
        kw.update(weight_dtype="int8", prefill_chunk=64)
    if cfg.windowed:
        kw.update(max_cache_len=256, kv_cache_dtype="bf16")
    if wide:
        kw.update(prefill_chunk=512, max_cache_len=1024)
        assert moe._held_capacity(2 + 512, cfg) == 256
    eng = InferenceEngine(cfg, EngineConfig(**kw), ByteTokenizer())
    try:
        return {pin: hashlib.sha256(_lowered(
            eng, pin.rsplit(".", 1)[1]).as_text().encode()).hexdigest()[:16]
            for pin in PINS if pin.rsplit(".", 1)[0] == model}
    finally:
        eng.stop()


@pytest.fixture(scope="module")
def hashes():
    """Every pinned program's hash, an engine a model, built once."""
    out = {}
    with pytest.MonkeyPatch.context() as mp:
        for model in sorted({k.rsplit(".", 1)[0] for k in PINS}):
            out.update(step_program_hashes(model, mp))
    return out


@pytest.mark.parametrize("program", sorted(PINS))
def test_step_program_hashes_equal_to_the_parents(hashes, program):
    assert hashes[program] == PINS[program]


def whole_layer_hash() -> str:
    """``moe.moe_ffn`` on a layer held WHOLE with int8 leaves, 8 experts
    top-2 of 64 x 96 at 320 rows (an expert's batch 128 rows, four overflow
    tiles, unrolled): mixtral's branch at test size, lowered on shapes."""
    x, e, f, rows = 8, 64, 96, 320
    cfg = types.SimpleNamespace(
        num_experts=x, num_experts_per_tok=2, router_width=x,
        expert_parallel_size=1, expert_parallel_rank=0,
        scoring_func="softmax", norm_topk_prob=True,
        routed_scaling_factor=1.0, swiglu_limit=0.0, zero_experts=0,
        expert_act="swiglu")
    assert moe._held_capacity(rows, cfg) == 128

    def leaf(k, n):
        return {"q": jax.ShapeDtypeStruct((x, k, n), jnp.int8),
                "s": jax.ShapeDtypeStruct((x, 1, n), jnp.float32)}

    lp = {"router": jax.ShapeDtypeStruct((e, x), jnp.bfloat16),
          "w_gate": leaf(e, f), "w_up": leaf(e, f), "w_down": leaf(f, e)}
    text = jax.jit(lambda lp, h: moe.moe_ffn(h, lp, cfg)).lower(
        lp, jax.ShapeDtypeStruct((1, rows, e), jnp.bfloat16)).as_text()
    # four tiles x three leaves x (values, scales), each its own slice
    assert text.count("stablehlo.dynamic_slice") >= 24
    return hashlib.sha256(text.encode()).hexdigest()[:16]


def test_the_whole_layers_batched_program_equals_the_parents():
    assert whole_layer_hash() == "b95ece34f63baefb"
