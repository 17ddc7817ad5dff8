"""Real-size kernel compiles against a DESCRIBED TPU v5e (no chip attached).

Interpret mode cannot see what the chip's compiler refuses: a slice that is
not aligned to the tiling, a vector shift Mosaic does not legalise, more
scoped VMEM than a kernel may use, a BlockSpec the TPU lowering rejects.
Four kernels that passed every interpret-mode test were refused exactly so
before this file existed.  Each case lowers one kernel of the serving path
at qwen2.5-7b widths (28 layers, 4 KV heads of 7 queries, head dim 128,
256-token pages, 64 slots, 1024 context) for a ``v5e:2x2`` topology
description and requires a Mosaic kernel in the compiled program.

Nothing runs and nothing is timed.  Skipped where the installation cannot
describe the topology (no libtpu).
"""

import math
import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or libtpu logs under /tmp

import jax
import jax.numpy as jnp
import pytest

from arks_tpu.ops import paged_attention as pa
from arks_tpu.ops import pallas_attention as sa

L, HKV, G, D, PAGE, SLOTS, CTX = 28, 4, 7, 128, 256, 64, 1024
MAX_PAGES = CTX // PAGE
N_PAGES = SLOTS * MAX_PAGES + 8
CHUNK = 256


@pytest.fixture(scope="module")
def chip():
    """A described v5e device to compile for; the persistent compilation
    cache is off around the module (entries compiled for a described chip
    cannot be read back without one, and the next run would warn)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu in this installation
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _pool(s, kv, hkv, page=PAGE, dk=D, dv=D):
    """K, V and the two scale pools; ``dk`` / ``dv``: the stored widths of
    keys and values where they differ."""
    if kv == "bf16":
        return (s((L, N_PAGES, hkv, page, D), jnp.bfloat16),) * 2 + (None,) * 2
    rows = page // 2 if kv == "int4" else page
    return (s((L, N_PAGES, hkv, rows, dk), jnp.int8),
            s((L, N_PAGES, hkv, rows, dv), jnp.int8)) \
        + (s((L, N_PAGES, hkv, page), jnp.float32),) * 2


def _mixed(s, kv, q, hkv=HKV, page=PAGE, **plan):
    kp, vp, ks, vs = _pool(s, kv, hkv, page)

    def fn(qq, kp, vp, tables, pos, qlen, ks, vs):
        return pa.paged_mixed_attention(qq, kp, vp, tables, pos, qlen, 3,
                                        ks, vs, **plan)

    return jax.jit(fn).lower(
        s((SLOTS, hkv, G, q, D), jnp.bfloat16), kp, vp,
        s((SLOTS, CTX // page), jnp.int32), s((SLOTS,), jnp.int32),
        s((SLOTS,), jnp.int32), ks, vs)


def _mixed_flat(s, kv, lanes, chunk, hkv=HKV, g=G, max_pages=MAX_PAGES,
                dk=D, dv=D, sink=False, **plan):
    """The flat batch through the block-compacted layout, as the mixed
    step calls it: ``lanes + chunk`` rows (chunk 0 = the pipelined step,
    one row a lane).  ``sink``: a logit a query head handed to the
    launch."""
    kp, vp, ks, vs = _pool(s, kv, hkv, dk=dk, dv=dv)

    def fn(qq, kp, vp, tables, tslot, qstart, qlen, pos, ks, vs, *snk):
        return pa.paged_mixed_attention_flat(
            qq, kp, vp, tables, tslot, qstart, qlen, pos, 3, ks, vs, **plan,
            **({"sink": snk[0]} if snk else {}))

    lane = s((lanes,), jnp.int32)
    return jax.jit(fn).lower(
        s((lanes + chunk, hkv, g, dk), jnp.bfloat16), kp, vp,
        s((lanes, max_pages), jnp.int32), s((lanes + chunk,), jnp.int32),
        lane, lane, lane, ks, vs,
        *((s((hkv, g), jnp.float32),) if sink else ()))


# Laguna-S-2.1 (benchmarks/configs/laguna-s-2.1-ep8): 8 KV heads under 48
# query heads in a full layer (g = 6) and 72 in a window layer (g = 9,
# window 512), int8 pages of 256, 32 slots x 16,384 tokens, a 1024-row
# chunk.
LAG = dict(hkv=8, g_full=6, g_win=9, window=512, slots=32, chunk=1024,
           max_pages=64)


def _laguna_flat(s, chunk, window: bool):
    return _mixed_flat(
        s, "int8", LAG["slots"], chunk, hkv=LAG["hkv"],
        g=LAG["g_win"] if window else LAG["g_full"],
        max_pages=LAG["max_pages"],
        **({"window": LAG["window"]} if window else {}))


def _row_write(pools, new, idx, tables):
    """The row write as a step program holds it: the pools donated, so
    that the compiled program shows whether they are rewritten in place."""
    pools = [p for p in pools if p is not None]

    def fn(*a):
        pools, (new, idx, tables) = a[:-3], a[-3:]
        if len(pools) == 4:
            return pa.paged_kv_update_quant(*pools, new, new, idx, tables, 3)
        if len(pools) == 2:
            return pa.paged_kv_update(*pools, new, new, idx, tables, 3)
        return pa.paged_kv_update(pools[0], None, new, None, idx, tables,
                                  3)[0]

    return jax.jit(fn, donate_argnums=tuple(range(len(pools)))).lower(
        *pools, new, idx, tables)


def _laguna_update(s):
    t = LAG["slots"] + LAG["chunk"]
    return _row_write(_pool(s, "int8", LAG["hkv"]),
                      s((t, LAG["hkv"], D), jnp.bfloat16), s((t,), jnp.int32),
                      s((t, LAG["max_pages"]), jnp.int32))


# MiMo-V2.5 (benchmarks/configs/mimo-v2.5-ep16-l13): 64 query heads over 8
# KV heads in a window layer (g = 8, window 128: half a page, a sink logit a
# head) and over 4 in a full layer (g = 16), keys 192 wide stored as 256
# lanes and values 128, int8 pages of 256, 64 slots x 8,192 tokens.
MIMO = dict(hkv_win=8, hkv_full=4, heads=64, window=128, slots=64,
            max_pages=32, dk=256, dv=128)


def _mimo_flat(s, chunk, window: bool):
    hkv = MIMO["hkv_win"] if window else MIMO["hkv_full"]
    return _mixed_flat(
        s, "int8", MIMO["slots"], chunk, hkv=hkv, g=MIMO["heads"] // hkv,
        max_pages=MIMO["max_pages"], dk=MIMO["dk"], dv=MIMO["dv"],
        **({"window": MIMO["window"], "sink": True} if window else {}))


# Kimi-K2.5 (benchmarks/configs/kimi-k2.5-ep32-l9): 64 heads over ONE latent
# row a token, 512 + 64 lanes stored as five 128-lane tiles, 9 layers, 16
# slots x 8192 tokens, a 1024-row chunk.
LAT = dict(layers=9, heads=64, row=640, value=512, slots=16, ctx=8192,
           chunk=1024)


def _latent_pool(s):
    pages = LAT["slots"] * LAT["ctx"] // PAGE // 4
    return s((LAT["layers"], pages, 1, PAGE, LAT["row"]), jnp.bfloat16)


def _latent_flat(s, chunk, **plan):
    """The latent kernel over the flat batch, as the latent block calls
    it: Hkv = 1, the 64 heads as the query group, values from the key
    tile's first 512 lanes (chunk 0 = the pipelined step)."""
    lanes, t = LAT["slots"], LAT["slots"] + chunk

    def fn(qq, pool, tables, tslot, qstart, qlen, pos):
        return pa.paged_mixed_attention_flat(
            qq, pool, None, tables, tslot, qstart, qlen, pos, 3,
            latent_v=LAT["value"], scale=0.1, **plan)

    lane = s((lanes,), jnp.int32)
    return jax.jit(fn).lower(
        s((t, 1, LAT["heads"], LAT["row"]), jnp.bfloat16), _latent_pool(s),
        s((lanes, LAT["ctx"] // PAGE), jnp.int32), s((t,), jnp.int32),
        lane, lane, lane)


def _latent_update(s, slots=LAT["slots"]):
    t = slots + LAT["chunk"]
    return _row_write([_latent_pool(s)], s((t, 1, LAT["row"]), jnp.bfloat16),
                      s((t,), jnp.int32), s((t, LAT["ctx"] // PAGE), jnp.int32))


def _paged_decode(s, kv):
    kp, vp, ks, vs = _pool(s, kv, HKV)
    return pa.paged_decode_attention.lower(
        s((SLOTS, HKV, G, D), jnp.bfloat16), kp, vp,
        s((SLOTS, MAX_PAGES), jnp.int32), s((SLOTS,), jnp.int32), 3, ks, vs)


def _update(s, kv, hkv=HKV, slots=SLOTS):
    t = slots + CHUNK           # the mixed step's flat token batch
    return _row_write(_pool(s, kv, hkv), s((t, hkv, D), jnp.bfloat16),
                      s((t,), jnp.int32), s((t, MAX_PAGES), jnp.int32))


def _slot_cache(s, kv):
    if kv == "bf16":
        return s((L, SLOTS, HKV, CTX, D), jnp.bfloat16), (None, None)
    return (s((L, SLOTS, HKV, CTX, D), jnp.int8),
            (s((L, SLOTS, HKV, CTX), jnp.float32),) * 2)


def _slot_decode(s, kv):
    kc, scales = _slot_cache(s, kv)
    return sa.ragged_decode_attention.lower(
        s((SLOTS, HKV, G, D), jnp.bfloat16), kc, kc, s((SLOTS,), jnp.int32),
        3, *scales)


def _slot_update(s, kv):
    kc, scales = _slot_cache(s, kv)
    new, idx = s((SLOTS, HKV, D), jnp.bfloat16), s((SLOTS,), jnp.int32)
    if kv == "bf16":
        return sa.kv_cache_update.lower(kc, kc, new, new, idx, 3)
    return sa.kv_cache_update_quant.lower(kc, kc, *scales, new, new, idx, 3)


CASES = {
    # The default path: one decode token per lane, and a chunk + 1.
    "mixed-int8-q1": lambda s: _mixed(s, "int8", 1),
    "mixed-int8-chunk": lambda s: _mixed(s, "int8", CHUNK + 1),
    "mixed-bf16-q1": lambda s: _mixed(s, "bf16", 1),
    "mixed-bf16-chunk": lambda s: _mixed(s, "bf16", CHUNK + 1),
    "update-bf16": lambda s: _update(s, "bf16"),
    "update-int8": lambda s: _update(s, "int8"),
    # The row write at the benchmark cells' row counts: qwen's 192 slots +
    # 256 (448 rows), kimi's 8 + 1024 (1032), laguna's 32 + 1024 (1056,
    # "update-int8-hkv8-64-pages" below).
    "update-int8-448-rows": lambda s: _update(s, "int8", slots=192),
    "latent-update-1032-rows": lambda s: _latent_update(s, slots=8),
    # The same through the flat batch's block-compacted query layout:
    # the sequential step (slots + chunk rows) at each block_q the plan
    # may choose, the pipelined step (blocks of one row), 192 slots (the
    # benchmark's qwen cell), grouped heads.
    "flat-int8-seq-bq8": lambda s: _mixed_flat(s, "int8", SLOTS, CHUNK,
                                               block_q=8),
    "flat-int8-seq-bq16": lambda s: _mixed_flat(s, "int8", SLOTS, CHUNK,
                                                block_q=16),
    "flat-int8-seq-bq32": lambda s: _mixed_flat(s, "int8", SLOTS, CHUNK,
                                                block_q=32),
    "flat-int8-seq-default-192": lambda s: _mixed_flat(s, "int8", 192,
                                                       CHUNK),
    "flat-int8-pipe": lambda s: _mixed_flat(s, "int8", SLOTS, 0),
    "flat-bf16-seq": lambda s: _mixed_flat(s, "bf16", SLOTS, CHUNK),
    "flat-int4-seq": lambda s: _mixed_flat(s, "int4", SLOTS, CHUNK),
    "flat-int8-seq-head-group-1": lambda s: _mixed_flat(
        s, "int8", SLOTS, CHUNK, head_group=1),
    "flat-int8-seq-hkv1": lambda s: _mixed_flat(s, "int8", SLOTS, CHUNK,
                                                hkv=1),
    # The latent page at Kimi-K2.5's widths: the sequential step at the
    # block the plan chooses (8: 512 query rows a work item) and at 16,
    # the pipelined step, and the one-pool row write.  (A 576-wide row is
    # refused: "Slice shape along dimension 4 must be aligned to tiling
    # (128)"; XLA lays such an array out 640 wide in HBM anyway.)
    "latent-seq": lambda s: _latent_flat(s, LAT["chunk"]),
    "latent-seq-bq16": lambda s: _latent_flat(s, LAT["chunk"], block_q=16),
    "latent-pipe": lambda s: _latent_flat(s, 0),
    "latent-update": lambda s: _latent_update(s),
    # Window and full layers at Laguna-S-2.1's widths: the window launch
    # (the same call told the bound; g = 9) and the full layers' (g = 6),
    # sequential and pipelined, and the row write over 64-page tables.
    "window-int8-seq": lambda s: _laguna_flat(s, LAG["chunk"], True),
    "window-int8-pipe": lambda s: _laguna_flat(s, 0, True),
    "full-g6-int8-seq": lambda s: _laguna_flat(s, LAG["chunk"], False),
    "full-g6-int8-pipe": lambda s: _laguna_flat(s, 0, False),
    "update-int8-hkv8-64-pages": lambda s: _laguna_update(s),
    # Window and full layers at MiMo-V2.5's widths, the pipelined step's
    # launches: the window launch with its sink (g = 8, a window of half a
    # page) and the full layers' (g = 16), keys stored 256 and values 128
    # lanes wide.  (A 192-lane key page is refused: "Slice shape along
    # dimension 4 must be aligned to tiling (128), but is 192".)  The
    # 1,088-row step's launches and its row writes of rows of two widths
    # compile inside the share's whole step below.
    "window-sink-k256-v128-int8-pipe": lambda s: _mimo_flat(s, 0, True),
    "full-g16-k256-v128-int8-pipe": lambda s: _mimo_flat(s, 0, False),
    # tp=4 leaves one KV head per chip.
    "mixed-int8-chunk-hkv1": lambda s: _mixed(s, "int8", CHUNK + 1, hkv=1),
    "update-int8-hkv1": lambda s: _update(s, "int8", hkv=1),
    # qwen2.5-0.5b: 2 KV heads, head dim 64 stored lane-padded at 128.
    "mixed-int8-chunk-d64-padded": lambda s: _mixed(s, "int8", CHUNK + 1,
                                                    hkv=2),
    # The rest of what serves somewhere: other page sizes, the
    # windowed-residency span (raw softmax state out), the legacy paged
    # decode step, the slot layout (dp engines).
    "mixed-int8-chunk-page128": lambda s: _mixed(s, "int8", CHUNK + 1,
                                                 page=128),
    "mixed-int8-chunk-page512": lambda s: _mixed(s, "int8", CHUNK + 1,
                                                 page=512),
    "mixed-int8-chunk-emit-state": lambda s: _mixed(s, "int8", CHUNK + 1,
                                                    emit_state=True),
    "paged-decode-int8": lambda s: _paged_decode(s, "int8"),
    "paged-decode-bf16": lambda s: _paged_decode(s, "bf16"),
    "slot-decode-int8-b64-s1024": lambda s: _slot_decode(s, "int8"),
    "slot-update-bf16": lambda s: _slot_update(s, "bf16"),
    "slot-update-int8": lambda s: _slot_update(s, "int8"),
    # Repaired in PR 21 (refused by the chip's compiler before it):
    # int4 nibble shifts on 8-bit vectors ...
    "mixed-int4-chunk": lambda s: _mixed(s, "int4", CHUNK + 1),
    "update-int4": lambda s: _update(s, "int4"),
    # ... a sub-tile head slice of the f32 scale pool ...
    "mixed-int8-chunk-head-group-1": lambda s: _mixed(
        s, "int8", CHUNK + 1, head_group=1),
    "mixed-int8-chunk-head-group-2": lambda s: _mixed(
        s, "int8", CHUNK + 1, head_group=2),
    # ... and 16.75 MB of scoped VMEM at the default blocks.
    "slot-decode-bf16-b64-s1024": lambda s: _slot_decode(s, "bf16"),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(chip, case):
    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    compiled = CASES[case](spec).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    if case.startswith("window-"):
        # The window launch has a name of its own in a profile.
        assert "paged_window_attention_ragged" in text
    if "update" in case and not case.startswith("slot-"):
        # The row write rewrites its pools IN PLACE: every pool is
        # aliased to its output, and nothing the size of the smallest
        # (a scale pool) stands among the program's temporaries.
        mem = compiled.memory_analysis()
        pools = [a for a in jax.tree.leaves(compiled.args_info)
                 if len(a.shape) >= 4]
        sizes = [math.prod(a.shape) * a.dtype.itemsize for a in pools]
        assert mem.alias_size_in_bytes >= sum(sizes)
        assert mem.temp_size_in_bytes < min(sizes)


@pytest.mark.parametrize("rows", [64, 320])
@pytest.mark.parametrize("kind", ["int8", "int4"])
def test_quantised_experts_compile_for_v5e_as_stored(chip, kind, rows):
    """One routed layer at Mixtral-8x7B's widths (8 experts top-2, 4096 x
    14336) on the mixtral cell's two step shapes, 64 rows (the dense
    dispatch) and 320 (the batched one): the compiled program writes no
    full-width copy of an int8 expert stack (940 MB a leaf; it did, three
    a layer, until PR 33), needs no Mosaic kernel and holds no scatter."""
    import types

    from arks_tpu.models import moe

    x, e, f = 8, 4096, 14336
    cfg = types.SimpleNamespace(
        num_experts=x, num_experts_per_tok=2, router_width=x,
        expert_parallel_size=1, expert_parallel_rank=0,
        scoring_func="softmax", norm_topk_prob=True,
        routed_scaling_factor=1.0, swiglu_limit=0.0, zero_experts=0,
        expert_act="swiglu")

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    def leaf(k, n):
        if kind == "int8":
            return {"q": spec((x, k, n), jnp.int8),
                    "s": spec((x, 1, n), jnp.float32)}
        return {"q": spec((x, k, n), jnp.int4),
                "gs": spec((x, k // 128, n), jnp.float32)}

    lp = {"router": spec((e, x), jnp.bfloat16), "w_gate": leaf(e, f),
          "w_up": leaf(e, f), "w_down": leaf(f, e)}
    compiled = jax.jit(lambda lp, h: moe.moe_ffn(h, lp, cfg)).lower(
        lp, spec((1, rows, e), jnp.bfloat16)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" not in text and "ragged-dot" not in text
    # The combine is a contraction and the experts' sizes a sum (PR 53).
    assert " scatter(" not in text
    if kind == "int8":
        assert f"bf16[{x},{e},{f}]" not in text
        assert f"bf16[{x},{f},{e}]" not in text
        assert compiled.memory_analysis().temp_size_in_bytes < 400e6


@pytest.mark.parametrize("decay,layers", [("a channel", 6), ("a head", 4)])
@pytest.mark.parametrize("rows", [0, 1024])
def test_the_delta_rules_state_update_compiles_for_v5e_in_place(
        chip, rows, decay, layers, monkeypatch):
    """A linear layer's state update at Solar-Open2-250B's widths (64 heads
    of 128, 64 slots: 268 MB of float32 state a layer, six layers, a decay
    a channel) and at GigaChat3.5-432B's (four layers, a decay a head) on
    the cells' two step shapes, 64 rows (the pipelined step: one recurrence
    step a lane, the kernel of ``ops/linear_state.py``) and 64 + 1024 (a
    chunk: the ``while`` over blocks of 64 rows with its triangular solve
    behind the kernel): the chip's compiler takes all four, and the layers'
    states (1.6 / 1.1 GB) are rewritten in place: the output aliases the
    argument, and the one-row step holds no copy of even one layer's
    states."""
    from arks_tpu.models import transformer as tf

    # ``_linear_state`` asks this whether to interpret its kernel.
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    h, d, slots = 64, 128, 64
    t = slots + rows

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    f32, i32 = jnp.float32, jnp.int32
    compiled = jax.jit(tf._linear_state, donate_argnums=(5,)).lower(
        spec((t, h, d), f32), spec((t, h, d), f32), spec((t, h, d), f32),
        spec((t, h, d if decay == "a channel" else 1), f32),
        spec((t, h), f32),
        spec((layers, slots, h, d, d), f32), spec((), i32),
        spec((slots,), i32), spec((slots,), i32), spec((slots,), jnp.bool_)
    ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "triangular" in text.lower() or "while" in text
    mem = compiled.memory_analysis()
    state = layers * slots * h * d * d * 4
    assert mem.alias_size_in_bytes >= state
    assert mem.temp_size_in_bytes < (state // layers + 300e6 if rows
                                     else 100e6)


@pytest.mark.parametrize("rows", [0, 1024])
def test_the_selective_scans_state_update_compiles_for_v5e_in_place(
        chip, rows, monkeypatch):
    """A Mamba-2 layer's state update at Nemotron-3-Nano's widths (64 heads
    of 64 over 8 groups, state 128, 64 slots: 134 MB of float32 state a
    layer, 23 layers: 3.1 GB) on the cell's two step shapes, 64 rows (the
    pipelined step: one recurrence step a lane, the kernel of
    ``ops/ssm_state.py``) and 64 + 1024 (a chunk: the ``while`` over blocks
    of 64 rows in the SSD form behind the kernel): the chip's compiler
    takes both, and the layers' states are rewritten in place: the output
    aliases the argument, and the one-row step holds no copy of even one
    layer's states."""
    from arks_tpu.models import transformer as tf

    # ``_ssm_state`` asks this whether to interpret its kernel.
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    h, p, n, g, slots, layers = 64, 64, 128, 8, 64, 23
    t = slots + rows

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    f32, i32 = jnp.float32, jnp.int32
    compiled = jax.jit(tf._ssm_state, donate_argnums=(5,)).lower(
        spec((t, h, p), f32), spec((t, g, n), f32), spec((t, g, n), f32),
        spec((t, h), f32), spec((t, h), f32),
        spec((layers, slots, h // 2, n, 2 * p), f32), spec((), i32),
        spec((slots,), i32), spec((slots,), i32), spec((slots,), jnp.bool_)
    ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "while" in text
    mem = compiled.memory_analysis()
    state = layers * slots * h * p * n * 4
    assert mem.alias_size_in_bytes >= state
    assert mem.temp_size_in_bytes < (state // layers + 200e6 if rows
                                     else 100e6)


# A share configuration of the benchmark: (chips a layer, slots, pages a
# slot, pages of the full pool, of the window pool, quantised pages).
SHARES = {
    "kimi-k2.5-ep32-l9": (32, 8, 32, 347, 0, False),
    "laguna-s-2.1-ep8": (8, 32, 64, 512, 32 * 7, True),
    "solar-open2-250b-ep8-l8": (8, 64, 20, 64 * 20, 0, True),
    "gigachat3.5-432b-ep8-l5": (8, 64, 80, 64 * 80, 0, False),
    "mimo-v2.5-ep16-l13": (16, 64, 32, 1280, 64 * 6, True),
    "longcat-flash-ep32-l6": (32, 64, 20, 64 * 20, 0, False),
    "nemotron-3-nano-30b-ep8": (8, 64, 20, 64 * 20, 0, True),
}
_STEPS: dict = {}


def _share_step(chip, monkeypatch, name: str, rows: int):
    """``(cfg, cache shapes, compiled)``: ``mixed_step`` of this chip's
    share of ``name`` (its own ``benchmarks/configs/*/config.json``, int8
    leaves) on its slots + ``rows`` rows, compiled for the described v5e;
    once a module for each shape."""
    if (name, rows) in _STEPS:
        return _STEPS[name, rows]
    import os
    from arks_tpu.models import quant, transformer as tf
    from arks_tpu.models.config import ModelConfig

    # The ops ask these two which branch to trace: the chip's.
    monkeypatch.setenv("ARKS_ATTN_IMPL", "pallas")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    share, slots, max_pages, pages, win_pages, quantized = SHARES[name]
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cfg = ModelConfig.from_hf_config(os.path.join(
        root, "benchmarks", "configs", name), name="m").with_expert_share(
            share, 0)
    t = slots + rows

    def on_chip(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip)

    def ints(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=chip)

    params = jax.tree.map(on_chip, jax.eval_shape(
        lambda: quant.init_params_quantized(
            cfg, jax.random.PRNGKey(0), jnp.bfloat16, bits=8)))
    cache = jax.tree.map(on_chip, jax.eval_shape(
        lambda: tf.init_paged_cache(
            cfg, pages, 256, jnp.bfloat16, quantized=quantized,
            pad_head=True, win_pages=win_pages,
            state_slots=slots if cfg.recurrent else 0)))
    kw = {"win_tables": ints(slots, max_pages)} if cfg.windowed else {}
    compiled = jax.jit(
        lambda p, c, *a, **k: tf.mixed_step(p, cfg, c, *a, with_held=True,
                                            **k),
        donate_argnums=(1,)).lower(
        params, cache, ints(slots, max_pages), ints(t), ints(t), ints(t),
        ints(slots), ints(slots), ints(slots), ints(slots), **kw).compile()
    _STEPS[name, rows] = cfg, cache, compiled
    return _STEPS[name, rows]


def _outside_fusions(text: str):
    """The lines of a compiled program that stand OUTSIDE its fused
    computations (those a ``fusion(... calls=%name)`` names): what they
    define is a buffer the step writes."""
    import re
    fused = set(re.findall(r"fusion\([^\n]*calls=%([\w.\-]+)", text))
    comp = None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) \(.*\{$", line)
        if head:
            comp = head.group(1)
        elif comp not in fused:
            yield line


def _unfused_s8(text: str) -> list:
    """``(dims, line)`` of every int8 array a compiled program defines as
    the result of a ``copy`` or a ``fusion`` outside its fused
    computations, a result that is a TUPLE of arrays included (a
    multi-output fusion: every int8 member counts)."""
    import re
    made = re.compile(r"= ([^%=]*?) (?:fusion|copy)\(")
    return [(tuple(map(int, dims.split(","))), line.strip()[:120])
            for line in _outside_fusions(text)
            for m in [made.search(line)] if m
            for dims in re.findall(r"\bs8\[([\d,]+)\]", m.group(1))]


def _writes_none_of(compiled, sizes: dict, temp_mb: float) -> None:
    """Outside its fusions ``compiled`` defines no int8 buffer with one of
    the element counts ``sizes`` names (a leaf in any shape or layout), and
    its temporaries stand under ``temp_mb``."""
    assert sizes
    found = [(sizes[math.prod(dims)], line)
             for dims, line in _unfused_s8(compiled.as_text())
             if math.prod(dims) in sizes]
    assert not found, found
    assert compiled.memory_analysis().temp_size_in_bytes < temp_mb * 1e6


@pytest.mark.parametrize("name,rows,temp_mb", [
    ("kimi-k2.5-ep32-l9", 1024, 260), ("laguna-s-2.1-ep8", 1024, 480),
    ("solar-open2-250b-ep8-l8", 256, 220),
    ("gigachat3.5-432b-ep8-l5", 1024, 360),
    ("mimo-v2.5-ep16-l13", 1024, 800),
    ("longcat-flash-ep32-l6", 1024, 700)])
def test_a_shares_chunk_step_copies_no_expert_leaf_out_of_its_stack(
        chip, monkeypatch, name, rows, temp_mb):
    """The chunk-carrying step of each share configuration at its published
    widths (kimi 1032 rows, laguna 1056, solar's tail shape 320, gigachat
    1088): outside its fusions the compiled program defines NO int8 buffer
    of a whole layer's expert leaf (``s8[X,E,F]`` / ``s8[X,F,E]`` as the
    result of a ``fusion`` or a ``copy``).  Until PR 44 the overflow loop
    closed over the layer's slice of the stacked tree, an operand of a
    ``while`` has to be a buffer, and every routed layer copied its three
    leaves whole first (kimi 3 x 176 MB a layer, laguna 3 x 101, solar 3 x
    210, gigachat 3 x 470: whole steps of 578 / 705 / 734 / 1618 MB of
    temporaries, 211 / 419 / 167 / 302 now; laguna's are a 340 MB copy of
    its window layers' fused qkv stack, once a step and not this test's)."""
    cfg, _, compiled = _share_step(chip, monkeypatch, name, rows)
    text = compiled.as_text()
    x, e, f = cfg.num_experts, cfg.hidden_size, cfg.moe_intermediate_size
    found = [line for dims, line in _unfused_s8(text)
             if dims[-3:] in ((x, e, f), (x, f, e))
             and dims[:-3] in ((), (1,))]
    assert not found, found
    assert "while" in text
    assert compiled.memory_analysis().temp_size_in_bytes < temp_mb * 1e6
    if cfg.shortcut:
        # Nor a layer's ``[2, ..]`` slice of a leaf stacked by sublayer
        # (PR 54: taken through the scan's slice, both sublayers' dots were
        # its users and every layer wrote 0.64 GB out before reading it;
        # `mixed_step`'s ``shortcut_layer`` takes (layer, sublayer) out of
        # the stacked tree a use).  The stacked ``wq_b`` / ``wkv_b`` are
        # read in place too since PR 57 (the test of the latent up
        # projections below).
        by_sublayer = [line for dims, line in _unfused_s8(text)
                       if dims[0] == 2 or dims[:2] == (1, 2)]
        assert not by_sublayer, by_sublayer


@pytest.mark.parametrize("name,rows", [
    ("kimi-k2.5-ep32-l9", 1024), ("laguna-s-2.1-ep8", 1024),
    ("solar-open2-250b-ep8-l8", 256), ("gigachat3.5-432b-ep8-l5", 1024),
    ("mimo-v2.5-ep16-l13", 1024)])
def test_a_shares_chunk_step_combines_by_one_contraction(chip, monkeypatch,
                                                         name, rows):
    """The same compiled chunk steps (the test above's programs): under
    ``arks.moe_route`` the chip's program holds NO ``scatter`` (until PR 53
    the combine was six or seven scatter-adds a routed layer, slot by slot,
    and the experts' sizes one more), and the combine is a convolution that
    emits ``bf16[n, E]`` from the ``[S, E]`` rows of the batch and the spare
    tiles, its selection matrix built inside the fusion: no ``[n, S]``
    buffer is defined outside one."""
    import re
    cfg, _, compiled = _share_step(chip, monkeypatch, name, rows)
    text = compiled.as_text()
    n = SHARES[name][1] + rows
    s = (cfg.num_experts + 4) * 128
    scatters = [line.strip()[:140] for line in text.splitlines()
                if re.search(r"= \S+ scatter\(", line)
                and "arks.moe_route" in line]
    assert not scatters, scatters
    combine = [line for line in text.splitlines()
               if "ns,se->ne/dot_general" in line and " convolution(" in line]
    assert combine and all(f"[{n},{cfg.hidden_size}]" in line
                           for line in combine)
    outside = [line.strip()[:140] for line in _outside_fusions(text)
               if "arks.moe_route" in line
               and re.search(rf"= bf16\[{n},{s}\]", line)]
    assert not outside, outside


@pytest.mark.parametrize("name", ["laguna-s-2.1-ep8", "mimo-v2.5-ep16-l13",
                                  "gigachat3.5-432b-ep8-l5"])
def test_a_whole_budget_steps_batch_is_one_tile_an_expert(chip, monkeypatch,
                                                          name):
    """The whole-budget step of the three share configurations whose fair
    load is 34-42 rows of ~1,060, compiled for the described v5e: the
    batched dispatch's three contractions run on ``bf16[X,128,F]`` /
    ``bf16[X,128,E]`` (one 128-row tile an expert: 3 x fair in tiles), not
    on the ``[X,256,*]`` that 4 x fair was rounded up to until PR 49, half
    of whose rows no pair ever landed on.  (That the tiles behind the batch
    still copy no expert leaf is the test above, on these same programs.)"""
    cfg, _, compiled = _share_step(chip, monkeypatch, name, 1024)
    text = compiled.as_text()
    x, e, f = cfg.num_experts, cfg.hidden_size, cfg.moe_intermediate_size
    assert f"bf16[{x},128,{f}]" in text and f"bf16[{x},128,{e}]" in text
    assert f"bf16[{x},256," not in text


@pytest.mark.parametrize("name,rows,temp_mb", [
    ("mimo-v2.5-ep16-l13", 0, 30), ("mimo-v2.5-ep16-l13", 1024, 260),
    ("laguna-s-2.1-ep8", 1024, 120),
    ("solar-open2-250b-ep8-l8", 256, 220)])
def test_a_step_writes_no_buffer_of_a_qkv_leaf(chip, monkeypatch, name, rows,
                                               temp_mb):
    """The step programs this module compiles of the configurations with
    a GQA stack, and mimo's 64-row one (laguna's 32-row program reads the
    same, 360 -> 11 MB of temporaries: PERF.md section 6, PR 48; left out
    for the gate's time), at their published widths: outside its fusions the compiled program defines NO
    int8 buffer of a whole stacked ``wq`` / ``wk`` / ``wv`` leaf and none of
    one layer's ``wq``, in any shape or layout (by element count: the
    compiler hands the same bytes on as ``s8[10,4096,12288]``, ``s8[1,
    4096,12288]`` or ``s8[64,192,4096]``).  The leaves are stored as the
    dots read them, ``[L, H, D, E]`` (``tf.init_params``).  Stored ``[L, E,
    H x D]`` (until PR 48) every program transposed the stacked leaves whole
    in ``main``, ahead of every loop (mimo ``copy s8[10,4096,12288]{1,2,0}``
    503 MB + 63 + 50, laguna 340 + 38, solar 2 x 34) and wrote a layer's
    slice out of the copy again inside the inner scan (mimo 50 MB x 10,
    laguna 28 x 12), or sliced and then transposed a layer (the full
    layers' scan; qwen's and mixtral's dense scan): 13.5 % of mimo's device
    time, temporaries of 675 / 751 MB (mimo) and 360 / 419 (laguna) where
    9 / 185 and 11 / 45 are left.  A layer's ``wk`` / ``wv`` (a few MB) is
    let be: none is written today either."""
    import math
    from arks_tpu.models import quant, transformer as tf
    cfg, _, compiled = _share_step(chip, monkeypatch, name, rows)
    shapes = jax.eval_shape(lambda k: tf.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    sizes = {}
    for stack, tree in shapes.items():
        if not isinstance(tree, dict):
            continue
        for leaf in sorted(quant.HEAD_SPLIT_KEYS & set(tree)):
            if tree[leaf].ndim != 4:        # a linear layer's: plain matmul
                continue
            sizes[math.prod(tree[leaf].shape)] = f"{stack}/{leaf}"
            if leaf == "wq":
                sizes[math.prod(tree[leaf].shape[1:])] = f"a layer of {stack}/wq"
    _writes_none_of(compiled, sizes, temp_mb)


@pytest.mark.parametrize("name,rows,temp_mb", [
    ("longcat-flash-ep32-l6", 0, 80), ("longcat-flash-ep32-l6", 1024, 350),
    ("kimi-k2.5-ep32-l9", 0, 30), ("kimi-k2.5-ep32-l9", 1024, 260),
    ("gigachat3.5-432b-ep8-l5", 0, 200)])
def test_a_step_writes_no_buffer_of_a_latent_up_projection(
        chip, monkeypatch, name, rows, temp_mb):
    """The step programs of the three configurations with a latent block,
    both shapes of longcat's and kimi's, at their published widths: outside
    its fusions the compiled program defines NO int8 buffer of a whole
    stacked ``wq_b`` / ``wkv_b``, of one layer's or of one sublayer's, in
    any shape or layout (by element count).  The leaves are stored as the
    dots read them, ``[L, H, nope + rope, q_lora]`` / ``[L, H, nope + v,
    kv_lora]`` (``tf.init_params``).  Stored ``[L, K, N]`` (until PR 57)
    longcat's programs transposed both stacks whole in ``main``, ahead of
    the layer loop (``copy s8[6,2,1536,12288]{2,3,1,0}`` 226 MB + ``copy
    s8[6,2,512,16384]`` 101 MB) and wrote a layer's two ``wq_b`` out of the
    copy again inside it (a fusion whose result is a TUPLE, ``(s8[1,1,1536,
    12288], s8[1,1,1536,12288])``), kimi's sliced AND transposed both
    leaves a layer, gigachat's transposed its one latent layer's: 7 % of
    longcat's device time, temporaries of 391 / 648 MB (longcat) where 45 /
    309 are left (kimi 2 / 211 and gigachat 51 as before: their copies
    were a layer's, live beside nothing)."""
    from arks_tpu.models import quant, transformer as tf
    cfg, _, compiled = _share_step(chip, monkeypatch, name, rows)
    shapes = jax.eval_shape(lambda k: tf.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    sizes = {}
    for stack, tree in shapes.items():
        if not isinstance(tree, dict):
            continue
        for leaf in sorted(quant.LATENT_SPLIT_KEYS & set(tree)):
            dims = tree[leaf].shape
            for lead in range(len(dims) - 2):   # whole, a layer, a sublayer
                sizes.setdefault(math.prod(dims[lead:]),
                                 f"{stack}/{leaf}{list(dims[lead:])}")
    _writes_none_of(compiled, sizes, temp_mb)


@pytest.mark.parametrize("name", ["mimo-v2.5-ep16-l13",
                                  "longcat-flash-ep32-l6"])
def test_a_one_row_steps_launch_is_read_back_through_no_copy(
        chip, monkeypatch, name):
    """The 64-row step (every lane one row, the pipelined program's shape)
    of mimo's share and of longcat's: the ragged launch hands back ``[nb,
    Hkv, G x block_q, Dv]`` in whole ``T(8,128)`` tiles (PR 55: the blocks'
    rows arrive merged), and what reads it back by (block, row) reads it
    through a bitcast.  Until then the launch wrote ``[nb, Hkv, G, 1, Dv]``
    in ``T(2,128)`` tiles, one row to a tile, and every launch of the step
    was followed by a ``copy`` (mimo) or a ``copy_bitcast_fusion``
    (longcat) of its whole output into the tiles the gather reads."""
    import re
    _, _, compiled = _share_step(chip, monkeypatch, name, 0)
    lines = list(_outside_fusions(compiled.as_text()))
    launches = [m.group(1, 2) for line in lines for m in [re.match(
        r"\s*%([\w.\-]*attention_ragged[\w.\-]*) = (\S+) custom-call\(",
        line)] if m]
    assert launches
    for out, shape in launches:
        assert "T(8,128)" in shape and shape.count(",") >= 3, shape
        users = [line.strip() for line in lines
                 if re.search(rf"%{re.escape(out)}\b", line)
                 and not re.match(rf"\s*%{re.escape(out)} =", line)]
        assert users and all(re.match(r"%bitcast[\w.\-]* =", u)
                             for u in users), users


def test_the_scan_for_written_buffers_sees_the_copies_it_is_there_for():
    """``_unfused_s8`` on lines of the parent's compiled mimo step (PR 47):
    the whole-leaf transpose in ``main`` and the slice in the inner scan's
    body are found, what a fused computation defines is not.  And on a line
    of longcat's (PR 56): a fusion whose result is a TUPLE of int8 arrays,
    a layer's two ``wq_b`` written out of the transposed copy, counts once
    a member (the scan missed such a line until PR 57)."""
    text = "\n".join([
        "%fused_computation.174 (p0: s8[10,4096,12288]) -> s8[1,4096,12288] {",
        "  %ds.1 = s8[1,4096,12288]{1,2,0:T(8,128)(4,1)} fusion(%p0), "
        "kind=kLoop, calls=%inner",
        "}",
        "%body (p: (s32[], s8[10,4096,12288])) -> (s32[]) {",
        "  %constant_dynamic-slice_fusion.28 = s8[1,4096,12288]{1,2,0:"
        "T(8,128)(4,1)} fusion(%gte.1, %sel), kind=kLoop, "
        "calls=%fused_computation.174",
        "  %bitcast.764 = s8[64,192,4096]{2,1,0} bitcast(%x)",
        "  %constant_dynamic-slice_fusion.18.remat2 = (s8[1,1,1536,12288]"
        "{2,3,1,0:T(8,128)(4,1)}, s8[1,1,1536,12288]{2,3,1,0:T(8,128)(4,1)}) "
        "fusion(%get-tuple-element.1643, %select_n.541), kind=kLoop, "
        "calls=%fused_computation.334.clone.clone.clone.clone, metadata={"
        "op_name=\"jit(<lambda>)/while/body/closed_call/dynamic_slice\"}",
        "  %dot.3 = bf16[64,64,192]{2,1,0} convolution(%a, %b), metadata={"
        "op_name=\"s8[9,9] fusion(\"}",
        "}",
        "ENTRY %main.98 (p0: s8[10,4096,12288]) -> f32[64,19072] {",
        "  %copy.534 = s8[10,4096,12288]{1,2,0:T(8,128)(4,1)} copy(%p0)",
        "  %copy-done.1 = s8[1,4,192,4096]{3,2,1,0} copy-done(%cs)",
        "}"])
    assert [dims for dims, _ in _unfused_s8(text)] == [
        (1, 4096, 12288), (1, 1, 1536, 12288), (1, 1, 1536, 12288),
        (10, 4096, 12288)]


@pytest.mark.parametrize("rows", [0, 1024])
def test_a_whole_latent_linear_step_compiles_for_v5e_in_place(
        chip, rows, monkeypatch):
    """``mixed_step`` of the ``gigachat3_5`` block at GigaChat3.5-432B's
    published widths, this chip's share (32 of 256 experts, 5 layers: 7.6
    GB of int8 weights), on the cell's two step shapes over 64 slots x 80
    pages: the latent kernel over a block table of 80 pages, the chunked
    scan with a decay a head, the period scan with a latent full layer and
    a linear head.  The chip's compiler takes both; the state and the
    latent pool (2.8 GB) are rewritten in place, and a step's temporaries
    leave room beside 10.4 GB of weights and caches on a 16 GB chip (the
    chunk's 302 MB: 1618 until PR 44, three copied expert leaves of 470)."""
    _, cache, compiled = _share_step(chip, monkeypatch,
                                     "gigachat3.5-432b-ep8-l5", rows)
    assert cache.k.shape == (1, 64 * 80, 1, 256, 640)
    assert compiled.as_text().count("tpu_custom_call") >= 2   # write, attend
    mem = compiled.memory_analysis()
    held = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(cache))
    assert mem.alias_size_in_bytes >= held
    assert mem.temp_size_in_bytes < (0.36e9 if rows else 0.2e9)


@pytest.mark.parametrize("rows", [0, 1024])
def test_a_whole_one_sublayer_step_compiles_for_v5e_in_place(
        chip, rows, monkeypatch):
    """``mixed_step`` of the ``nemotron_h`` block at Nemotron-3-Nano's
    published widths and WHOLE depth, this chip's share (16 of 128 experts,
    all 52 layers: 5.3 GB of int8 weights), on the cell's two step shapes
    over 64 slots x 20 pages: the walk over periods of unequal length (five
    equal ones one body), the selective scan's kernel and chunked scan, the
    ragged GQA launch at g = 16 over 2 KV heads, the dispatches of
    two-matrix experts.  The chip's compiler takes both; the state (3.1 GB)
    and the pages are rewritten in place, and a step's temporaries leave
    room beside 9.6 GB of weights and caches on a 16 GB chip."""
    cfg, cache, compiled = _share_step(chip, monkeypatch,
                                       "nemotron-3-nano-30b-ep8", rows)
    assert cache.k.shape == (6, 64 * 20, 2, 256, 128)
    assert cache.lin.s.shape == (23, 64, 32, 128, 128)
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 3     # write, attend, the scan
    mem = compiled.memory_analysis()
    held = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(cache))
    assert mem.alias_size_in_bytes >= held
    assert mem.temp_size_in_bytes < (0.6e9 if rows else 0.3e9)
