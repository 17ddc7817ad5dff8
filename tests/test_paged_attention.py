"""Paged-KV Pallas kernels vs XLA oracles (interpret mode on CPU).

The compiled-TPU counterpart is chip_smoke.py's kernel parity phases and
tests/test_chip_compile.py; here the same math runs in interpret mode so
CPU CI exercises the kernel bodies."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from arks_tpu.ops.attention import decode_attention_xla, _decode_attention_xla_quant
from arks_tpu.ops.paged_attention import (
    build_mixed_work_list,
    mixed_grid_plan,
    pack_int4,
    paged_decode_attention,
    paged_gather_kv,
    paged_kv_update,
    paged_kv_update_quant,
    paged_mixed_attention,
    paged_update_xla,
    unpack_int4,
)


def _setup(l=2, b=4, hkv=2, g=3, n=None, max_pages=4, page=16, d=32,
           quantized=False, seed=0):
    """Random pool + disjoint per-slot tables + ragged lengths."""
    n = n or b * max_pages + 2
    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, 8)
    if quantized:
        kp = jax.random.randint(ks[0], (l, n, hkv, page, d), -127, 128, jnp.int8)
        vp = jax.random.randint(ks[1], (l, n, hkv, page, d), -127, 128, jnp.int8)
        kps = jax.random.uniform(ks[4], (l, n, hkv, page), jnp.float32, 0.01, 0.03)
        vps = jax.random.uniform(ks[5], (l, n, hkv, page), jnp.float32, 0.01, 0.03)
    else:
        kp = jax.random.normal(ks[0], (l, n, hkv, page, d), jnp.float32)
        vp = jax.random.normal(ks[1], (l, n, hkv, page, d), jnp.float32)
        kps = vps = None
    q = jax.random.normal(ks[2], (b, hkv, g, d), jnp.float32)
    # Distinct pages per (slot, page-index): a permutation of pool indices.
    perm = jax.random.permutation(ks[3], n)[: b * max_pages]
    tables = perm.reshape(b, max_pages).astype(jnp.int32)
    lengths = jnp.asarray(
        [1 + (i * 7919) % (max_pages * page - 1) for i in range(b)], jnp.int32)
    return q, kp, vp, kps, vps, tables, lengths


@pytest.mark.parametrize("quantized", [False, True])
@pytest.mark.parametrize("block_b", [1, 2, 4])
def test_paged_attention_matches_oracle(quantized, block_b):
    page = 128 if quantized else 16
    q, kp, vp, kps, vps, tables, lengths = _setup(
        quantized=quantized, page=page)
    for layer in (0, 1):
        out = paged_decode_attention(
            q, kp, vp, tables, lengths, layer, k_scale=kps, v_scale=vps,
            block_b=block_b, interpret=True)
        kc = paged_gather_kv(kp, tables, layer)
        vc = paged_gather_kv(vp, tables, layer)
        if quantized:
            ksc = paged_gather_kv(kps, tables, layer)
            vsc = paged_gather_kv(vps, tables, layer)
            ref = _decode_attention_xla_quant(q, kc, vc, ksc, vsc, lengths)
        else:
            ref = decode_attention_xla(q, kc, vc, lengths)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-2 if quantized else 2e-5,
                                   rtol=2e-2 if quantized else 2e-5)


def test_paged_attention_shared_pages():
    """Two slots sharing prefix pages read identical prefixes (the whole
    point of paging: zero-copy sharing)."""
    q, kp, vp, _, _, tables, _ = _setup(b=2, max_pages=4, page=16)
    q = jnp.concatenate([q[:1], q[:1]])          # same query
    shared = tables.at[1, :2].set(tables[0, :2])  # share first 2 pages
    lengths = jnp.asarray([32, 32], jnp.int32)    # both end inside page 2
    out = paged_decode_attention(q, kp, vp, shared, lengths, 0,
                                 block_b=1, interpret=True)
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(out[1]),
                               atol=1e-6)


# The row write (``paged_kv_update`` / ``paged_kv_update_quant``): one
# oracle test over pool kinds x batch layouts.  Every layout is laid into the
# same 72 flat rows (the rest padding), so a kind compiles its kernel and
# its oracle once.
_UPD = dict(l=2, n=14, hkv=2, page=128, d=32, slots=4, max_pages=3, rows=72)
_PAD = None   # a padding row: its position is past the table's coverage


def _lone(t):
    """Flat row ``t`` of the ring-wrap layout: neighbours never share a
    page, and no two rows a position."""
    return (t % 4, (t // 4) * 34 + t % 4)


UPDATE_LAYOUTS = {
    # One decode row a slot, ragged positions (the lanes of a decode step).
    "a-row-a-slot": [(i, 1 + (i * 7919) % 383) for i in range(4)],
    # Nothing but padding: the pools come back untouched.
    "all-padding": [],
    # A chunk that starts and ends inside one block (of 16 and of 32).
    "chunk-inside-a-block": [(0, p) for p in range(35, 45)],
    # A chunk over blocks, a scale group and the page boundary at 128.
    "chunk-across-a-page": [(1, p) for p in range(90, 141)],
    # Two slots' runs side by side whose positions continue each other: the
    # same block index of two different pages.
    "two-slots-side-by-side": ([(0, p) for p in range(10, 20)]
                               + [(1, p) for p in range(20, 30)]),
    # Padding before, inside (mid-block and at a block's edge) and after.
    "padding-in-a-run": ([_PAD, _PAD] + [(2, p) for p in range(60, 64)]
                         + [_PAD] + [(2, p) for p in range(64, 71)]
                         + [_PAD, _PAD] + [(2, p) for p in range(71, 76)]
                         + [_PAD]),
    # Lone rows only, some of them neighbours in a page but not in a block.
    "lone-rows": [(0, 5), (0, 70), (1, 5), (2, 200), (2, 130), (3, 383),
                  (3, 0), (0, 140)],
    # 44 lone rows: the ring of scratch slots wraps eleven times, and rows
    # come back to blocks whose write is still on its way.
    "ring-wraps": [_lone(t) for t in range(44)],
    # Two int4 rows in one byte (6, 7), two in neighbouring bytes (9, 10).
    "rows-sharing-a-byte": [(3, 6), (3, 7), (3, 9), (3, 10)],
    # Two chunks' rows shuffled: rows that are NOT neighbours share a block,
    # so a block is read again right behind its own write.
    "shuffled": [(0, p) for p in range(40)] + [(1, p) for p in range(100, 131)],
}


@functools.cache
def _update_pools(kind: str):
    """``(pools, tables, kernel, oracle)``; pools = (k, v, k_scale,
    v_scale) with None where the kind has none."""
    u = _UPD
    ks = jax.random.split(jax.random.PRNGKey(5), 6)
    shape = (u["l"], u["n"], u["hkv"], u["page"], u["d"])
    tables = jax.random.permutation(ks[4], u["n"])[
        :u["slots"] * u["max_pages"]].reshape(
            u["slots"], u["max_pages"]).astype(jnp.int32)
    if kind in ("bf16", "latent"):
        kp = jax.random.normal(ks[0], shape, jnp.bfloat16)
        vp = None if kind == "latent" else jax.random.normal(
            ks[1], shape, jnp.bfloat16)

        def kernel(kp, vp, ksc, vsc, kn, vn, idx, tbl, layer, interpret):
            return paged_kv_update(kp, vp, kn, vn, idx, tbl, layer,
                                   interpret=interpret) + (None, None)

        def oracle(kp, vp, ksc, vsc, kn, vn, idx, tbl, layer):
            # The oracle writes pairs: a latent pool stands in for both.
            out = paged_update_xla(kp, kp if vp is None else vp, None, None,
                                   kn, kn if vn is None else vn, idx, tbl,
                                   layer)
            return (out[0], None if vp is None else out[1], None, None)

        return (kp, vp, None, None), tables, kernel, jax.jit(oracle)
    top = 8 if kind == "int4" else 128
    kp = jax.random.randint(ks[0], shape, 1 - top, top, jnp.int8)
    vp = jax.random.randint(ks[1], shape, 1 - top, top, jnp.int8)
    if kind == "int4":
        kp, vp = pack_int4(kp, axis=3), pack_int4(vp, axis=3)
    ksc = jax.random.uniform(ks[2], shape[:4], jnp.float32, 0.01, 0.03)
    vsc = jax.random.uniform(ks[3], shape[:4], jnp.float32, 0.01, 0.03)

    def kernel(kp, vp, ksc, vsc, kn, vn, idx, tbl, layer, interpret):
        return paged_kv_update_quant(kp, vp, ksc, vsc, kn, vn, idx, tbl,
                                     layer, interpret=interpret)

    return (kp, vp, ksc, vsc), tables, kernel, jax.jit(paged_update_xla)


def _update_batch(layout: str, tables, seed: int = 0):
    """The flat batch of a layout: ``(k_new, v_new, write_idx, per-row
    tables)``; ``shuffled`` permutes its rows."""
    u = _UPD
    rows = list(UPDATE_LAYOUTS[layout])
    if layout == "shuffled":
        rows = [rows[i] for i in np.random.default_rng(seed).permutation(
            len(rows))]
    rows += [_PAD] * (u["rows"] - len(rows))
    cover = u["max_pages"] * u["page"]
    idx = np.asarray([cover + 7 if r is _PAD else r[1] for r in rows],
                     np.int32)
    slot = np.asarray([0 if r is _PAD else r[0] for r in rows])
    key = jax.random.PRNGKey(17 + seed)
    kn = jax.random.normal(key, (u["rows"], u["hkv"], u["d"]), jnp.float32)
    vn = jax.random.normal(jax.random.fold_in(key, 1), kn.shape, jnp.float32)
    return kn, vn, jnp.asarray(idx), jnp.asarray(tables)[slot]


def _assert_same_bytes(got, ref):
    for g, r in zip(got, ref):
        assert (g is None) == (r is None)
        if g is not None:
            g, r = np.asarray(g), np.asarray(r)
            assert g.dtype == r.dtype and g.shape == r.shape
            np.testing.assert_array_equal(g.view(np.uint8), r.view(np.uint8))


@pytest.mark.parametrize("layout", sorted(UPDATE_LAYOUTS))
@pytest.mark.parametrize("kind", ["bf16", "latent", "int8", "int4"])
def test_paged_update_matches_oracle(kind, layout):
    """The block-wise row write leaves every pool BYTE FOR BYTE what
    ``paged_update_xla``'s scatter leaves: K and V pages, a latent pool, int8
    pages with their scale groups, int4 nibbles."""
    pools, tables, kernel, oracle = _update_pools(kind)
    kn, vn, idx, tbl = _update_batch(layout, tables)
    if kind == "latent":
        vn = None
    got = kernel(*pools, kn, vn, idx, tbl, 1, True)
    _assert_same_bytes(got, oracle(*pools, kn, vn, idx, tbl, 1))
    touched = any(r is not _PAD for r in UPDATE_LAYOUTS[layout])
    assert touched != all(
        np.array_equal(np.asarray(g), np.asarray(p))
        for g, p in zip(got, pools) if g is not None)
    # The other layer is nobody's to touch.
    _assert_same_bytes([g if g is None else g[0] for g in got],
                       [p if p is None else p[0] for p in pools])


@pytest.mark.parametrize("layout", ["ring-wraps", "shuffled",
                                    "chunk-across-a-page",
                                    "padding-in-a-run"])
@pytest.mark.parametrize("kind", ["latent", "int8", "int4"])
def test_paged_update_waits_for_what_it_reads(kind, layout):
    """The same under the TPU interpreter, whose DMAs move their bytes when
    they are WAITED for: a block read again before its write-back was
    waited for reads stale bytes there, and a copy nobody waits for never
    lands.  Three seeds of ``shuffled`` bring a block back at every distance
    the ring allows."""
    from jax.experimental.pallas import tpu as pltpu

    pools, tables, kernel, oracle = _update_pools(kind)
    mode = pltpu.InterpretParams(dma_execution_mode="on_wait")
    for seed in range(3 if layout == "shuffled" else 1):
        kn, vn, idx, tbl = _update_batch(layout, tables, seed)
        if kind == "latent":
            vn = None
        _assert_same_bytes(kernel(*pools, kn, vn, idx, tbl, 1, mode),
                           oracle(*pools, kn, vn, idx, tbl, 1))


# ---------------------------------------------------------------------------
# Ragged mixed-query kernel (prefill chunks + decode lanes in one grid)
# ---------------------------------------------------------------------------


def _mixed_ref(q, kp, vp, kps, vps, tables, pos_start, q_len, layer):
    """Oracle: per-(sequence, query) masked attention over gathered pages —
    query i of sequence s attends positions [0, pos_start[s]+i]."""
    kc = paged_gather_kv(kp, tables, layer)
    vc = paged_gather_kv(vp, tables, layer)
    out = np.zeros(np.asarray(q).shape, np.float32)
    for s in range(q.shape[0]):
        for i in range(int(q_len[s])):
            lens = jnp.asarray([int(pos_start[s]) + i + 1], jnp.int32)
            if kps is not None:
                ksc = paged_gather_kv(kps, tables, layer)
                vsc = paged_gather_kv(vps, tables, layer)
                ref = _decode_attention_xla_quant(
                    q[s:s + 1, :, :, i], kc[s:s + 1], vc[s:s + 1],
                    ksc[s:s + 1], vsc[s:s + 1], lens)
            else:
                ref = decode_attention_xla(q[s:s + 1, :, :, i],
                                           kc[s:s + 1], vc[s:s + 1], lens)
            out[s, :, :, i] = np.asarray(ref[0], np.float32)
    return out


def _assert_valid_rows_close(out, ref, q_len, tol):
    """Every valid (sequence, query) row of ``out`` against the oracle's."""
    for s in range(out.shape[0]):
        for i in range(int(q_len[s])):
            np.testing.assert_allclose(
                np.asarray(out[s, :, :, i], np.float32), ref[s, :, :, i],
                atol=tol, rtol=tol)


@pytest.mark.parametrize("quantized", [False, True])
@pytest.mark.parametrize("block_q", [2, 4, 8])
def test_paged_mixed_attention_matches_oracle(quantized, block_q):
    """Ragged q_len parity vs the XLA oracle: q_len = 1 (a decode lane),
    a partial chunk, a full chunk, and an inactive lane — the shapes the
    mixed scheduler actually dispatches — with SHARED prefix pages."""
    page = 128 if quantized else 16
    q, kp, vp, kps, vps, tables, _ = _setup(quantized=quantized, page=page)
    b, hkv, g, d = q.shape
    qmax = 8
    key = jax.random.PRNGKey(3)
    qm = jax.random.normal(key, (b, hkv, g, qmax, d), jnp.float32)
    # Slot 1 shares slot 0's first page (prefix reuse): its queries read
    # the shared prefix through its own table.
    tables = tables.at[1, 0].set(tables[0, 0])
    pos_start = jnp.asarray([5, page, 0, 3], jnp.int32)
    q_len = jnp.asarray([1, qmax, 3, 0], jnp.int32)
    for layer in (0, 1):
        out = paged_mixed_attention(qm, kp, vp, tables, pos_start, q_len,
                                    layer, k_scale=kps, v_scale=vps,
                                    block_q=block_q, interpret=True)
        ref = _mixed_ref(qm, kp, vp, kps, vps, tables, pos_start, q_len,
                         layer)
        _assert_valid_rows_close(out, ref, q_len,
                                 2e-2 if quantized else 2e-5)


@pytest.mark.parametrize("quantized", [False, True])
def test_paged_mixed_attention_verify_rows_match_oracle(quantized):
    """Speculative verify as ragged rows: mixed batches carrying q_len=K
    verify blocks ALONGSIDE q_len=1 decode lanes and chunk rows — the
    exact shape a spec-mixed dispatch sends — including a verify block
    that CROSSES a page boundary, on bf16 and int8-quantized pools."""
    page = 128 if quantized else 16
    q, kp, vp, kps, vps, tables, _ = _setup(quantized=quantized, page=page)
    b, hkv, g, d = q.shape
    K = 4
    qmax = 8
    qm = jax.random.normal(jax.random.PRNGKey(7), (b, hkv, g, qmax, d),
                           jnp.float32)
    # Lane 0: q_len=1 decode row.  Lane 1: q_len=K verify block CROSSING
    # the page boundary (starts K//2 before the page edge).  Lane 2:
    # q_len=K verify block inside page 0.  Lane 3: a chunk row span.
    pos_start = jnp.asarray([5, page - K // 2, 2, 0], jnp.int32)
    q_len = jnp.asarray([1, K, K, qmax], jnp.int32)
    for layer in (0, 1):
        out = paged_mixed_attention(qm, kp, vp, tables, pos_start, q_len,
                                    layer, k_scale=kps, v_scale=vps,
                                    block_q=4, interpret=True)
        ref = _mixed_ref(qm, kp, vp, kps, vps, tables, pos_start, q_len,
                         layer)
        _assert_valid_rows_close(out, ref, q_len,
                                 2e-2 if quantized else 2e-5)


@pytest.mark.parametrize("quantized", [False, True])
def test_mixed_step_verify_rows_match_verify_step(quantized):
    """Model-level closure: a spec-mixed flat batch's verify-block logits
    (tf.mixed_step with q_len=K rows) match tf.verify_step — the retired
    dedicated verify dispatch, kept as the oracle — on the same paged
    pool, with one block crossing a page boundary."""
    from arks_tpu.models import get_config, transformer as tf

    cfg = get_config("tiny")
    params = tf.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    B, K, PAGE, MAXP = 2, 4, 16, 4
    pool_a = tf.init_paged_cache(cfg, B * MAXP, PAGE, jnp.float32,
                                 quantized=quantized)
    pool_b = tf.init_paged_cache(cfg, B * MAXP, PAGE, jnp.float32,
                                 quantized=quantized)
    tables = jnp.arange(B * MAXP, dtype=jnp.int32).reshape(B, MAXP)
    # Slot 1's block crosses the page boundary (14 -> 18 with page 16).
    lengths = jnp.asarray([3, PAGE - 2], jnp.int32)
    key = jax.random.PRNGKey(2)
    for slot in range(B):
        plen = int(lengths[slot])
        pk = jax.random.normal(jax.random.fold_in(key, slot),
                               (cfg.num_layers, 1, plen, cfg.num_kv_heads,
                                cfg.head_dim), jnp.float32)
        pv = pk * 0.5 + 1.0
        n_pages = -(-plen // PAGE)
        pad = n_pages * PAGE - plen
        pkp = jnp.pad(pk, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
        pvp = jnp.pad(pv, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
        pool_a = tf.insert_pages(pool_a, pkp, pvp, tables[slot],
                                 jnp.asarray(n_pages))
        pool_b = tf.insert_pages(pool_b, pkp, pvp, tables[slot],
                                 jnp.asarray(n_pages))
    blocks = jax.random.randint(jax.random.PRNGKey(5), (B, K), 2, 200,
                                jnp.int32)
    ref, pool_a = tf.verify_step(params, cfg, pool_a, blocks, lengths,
                                 tables=tables)
    # The same blocks as a spec-mixed flat batch: lane b owns rows
    # [b*K, (b+1)*K); logits gathered at every row.
    flat_tokens = blocks.reshape(-1)
    flat_slot = jnp.repeat(jnp.arange(B, dtype=jnp.int32), K)
    flat_pos = (lengths[:, None]
                + jnp.arange(K, dtype=jnp.int32)[None, :]).reshape(-1)
    src = jnp.arange(B * K, dtype=jnp.int32)
    got, pool_b = tf.mixed_step(
        params, cfg, pool_b, tables, flat_tokens, flat_slot, flat_pos,
        src, jnp.arange(B, dtype=jnp.int32) * K,
        jnp.full((B,), K, jnp.int32), lengths)
    got = got.reshape(B, K, -1)
    tol = 2e-2 if quantized else 2e-4
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=tol, rtol=tol)
    # The written KV rows agree too (the next dispatch reads them).
    np.testing.assert_allclose(np.asarray(pool_b.k), np.asarray(pool_a.k),
                               atol=1e-5)


def _setup_int4(l=2, b=4, hkv=2, g=3, max_pages=4, page=128, d=32, seed=0):
    """int4 pool (packed token pairs) + the UNPACKED int8 twin for oracles."""
    n = b * max_pages + 2
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    k8 = jax.random.randint(ks[0], (l, n, hkv, page, d), -7, 8, jnp.int8)
    v8 = jax.random.randint(ks[1], (l, n, hkv, page, d), -7, 8, jnp.int8)
    kps = jax.random.uniform(ks[4], (l, n, hkv, page), jnp.float32, 0.01, 0.03)
    vps = jax.random.uniform(ks[5], (l, n, hkv, page), jnp.float32, 0.01, 0.03)
    kp = pack_int4(k8, axis=3)
    vp = pack_int4(v8, axis=3)
    q = jax.random.normal(ks[2], (b, hkv, g, d), jnp.float32)
    perm = jax.random.permutation(ks[3], n)[: b * max_pages]
    tables = perm.reshape(b, max_pages).astype(jnp.int32)
    return q, (kp, vp), (k8, v8), kps, vps, tables


def test_pack_unpack_int4_roundtrip():
    vals = jax.random.randint(jax.random.PRNGKey(0), (2, 3, 8, 5), -7, 8,
                              jnp.int8)
    packed = pack_int4(vals, axis=2)
    assert packed.shape == (2, 3, 4, 5)
    np.testing.assert_array_equal(np.asarray(unpack_int4(packed, axis=2)),
                                  np.asarray(vals))


@pytest.mark.parametrize("block_q", [2, 4, 8])
def test_paged_mixed_attention_int4_matches_oracle(block_q):
    """int4 cells of the oracle-parity matrix: the packed pool through the
    mixed kernel equals (a) the XLA oracle on the unpacked pool and (b)
    the mixed kernel fed the unpacked int8 pool BITWISE — dequant fused on
    the page stream changes no math.  Includes a verify block crossing a
    page boundary and an inactive lane."""
    page = 128
    q, (kp, vp), (k8, v8), kps, vps, tables = _setup_int4(page=page)
    b, hkv, g, d = q.shape
    qmax = 8
    qm = jax.random.normal(jax.random.PRNGKey(3), (b, hkv, g, qmax, d),
                           jnp.float32)
    # Lane 1's rows cross the page boundary; lane 3 is inactive.
    pos_start = jnp.asarray([5, page - 2, 0, 3], jnp.int32)
    q_len = jnp.asarray([1, qmax, 3, 0], jnp.int32)
    for layer in (0, 1):
        out = paged_mixed_attention(qm, kp, vp, tables, pos_start, q_len,
                                    layer, k_scale=kps, v_scale=vps,
                                    block_q=block_q, interpret=True)
        twin = paged_mixed_attention(qm, k8, v8, tables, pos_start, q_len,
                                     layer, k_scale=kps, v_scale=vps,
                                     block_q=block_q, interpret=True)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(twin))
        ref = _mixed_ref(qm, k8, v8, kps, vps, tables, pos_start, q_len,
                         layer)
        _assert_valid_rows_close(out, ref, q_len, 2e-2)


@pytest.mark.parametrize("kv", ["f32", "int8", "int4"])
def test_dma_depth_is_byte_identical_and_oracle_close(kv):
    """DMA depth is a pipelining knob, never a numerics knob: the ragged
    kernel at depth 2 and at depth 4 returns the same bytes for every pool
    dtype, and those bytes are the XLA gather oracle's values."""
    if kv == "int4":
        q, (kp, vp), (k8, v8), kps, vps, tables = _setup_int4()
    else:
        q, kp, vp, kps, vps, tables, _ = _setup(
            quantized=(kv == "int8"), page=128 if kv == "int8" else 16)
        k8, v8 = kp, vp
    b, hkv, g, d = q.shape
    qmax = 8
    qm = jax.random.normal(jax.random.PRNGKey(5), (b, hkv, g, qmax, d),
                           jnp.float32)
    page = kps.shape[3] if kps is not None else kp.shape[3]
    pos_start = jnp.asarray([5, page - 2, 0, 3], jnp.int32)
    q_len = jnp.asarray([1, qmax, 3, 0], jnp.int32)
    kwargs = dict(k_scale=kps, v_scale=vps, block_q=4, interpret=True)
    ragged = paged_mixed_attention(qm, kp, vp, tables, pos_start, q_len, 0,
                                   dma_depth=2, **kwargs)
    deep = paged_mixed_attention(qm, kp, vp, tables, pos_start, q_len, 0,
                                 dma_depth=4, **kwargs)
    np.testing.assert_array_equal(np.asarray(ragged), np.asarray(deep))
    ref = _mixed_ref(qm, k8, v8, kps, vps, tables, pos_start, q_len, 0)
    _assert_valid_rows_close(ragged, ref, q_len,
                             2e-5 if kv == "f32" else 2e-2)


@pytest.mark.parametrize("kv", ["f32", "int8", "int4"])
@pytest.mark.parametrize("dma_depth", [2, 4])
def test_gqa_head_grouped_kernel_byte_identical(kv, dma_depth):
    """GQA head grouping is a pure DMA-schedule change: every head_group
    divisor of hkv returns BITWISE the ungrouped ragged kernel's output,
    for every pool dtype and DMA depth, and stays oracle-close."""
    if kv == "int4":
        q, (kp, vp), (k8, v8), kps, vps, tables = _setup_int4()
    else:
        q, kp, vp, kps, vps, tables, _ = _setup(
            quantized=(kv == "int8"), page=128 if kv == "int8" else 16)
        k8, v8 = kp, vp
    b, hkv, g, d = q.shape
    qmax = 8
    qm = jax.random.normal(jax.random.PRNGKey(9), (b, hkv, g, qmax, d),
                           jnp.float32)
    page = kps.shape[3] if kps is not None else kp.shape[3]
    pos_start = jnp.asarray([5, page - 2, 0, 3], jnp.int32)
    q_len = jnp.asarray([1, qmax, 3, 0], jnp.int32)
    kwargs = dict(k_scale=kps, v_scale=vps, block_q=4, interpret=True,
                  dma_depth=dma_depth)
    base = paged_mixed_attention(qm, kp, vp, tables, pos_start, q_len, 0,
                                 head_group=hkv, **kwargs)
    for head_group in (1, 2):
        if hkv % head_group:
            continue
        grouped = paged_mixed_attention(qm, kp, vp, tables, pos_start,
                                        q_len, 0, head_group=head_group,
                                        **kwargs)
        np.testing.assert_array_equal(np.asarray(base),
                                      np.asarray(grouped))
    ref = _mixed_ref(qm, k8, v8, kps, vps, tables, pos_start, q_len, 0)
    _assert_valid_rows_close(base, ref, q_len,
                             2e-5 if kv == "f32" else 2e-2)


@pytest.mark.parametrize("kv", ["f32", "int8", "int4"])
def test_span_chained_state_matches_single_call(kv):
    """Windowed-residency building block: splitting the page loop into
    [0, split) + [split, end) spans with the f32 (m, l, acc) state carried
    between calls reproduces the single-call output BITWISE — the online
    softmax's per-page update sequence is unchanged and the final
    normalization happens exactly once, on the last span."""
    if kv == "int4":
        q, (kp, vp), _, kps, vps, tables = _setup_int4()
    else:
        q, kp, vp, kps, vps, tables, _ = _setup(
            quantized=(kv == "int8"), page=128 if kv == "int8" else 16)
    b, hkv, g, d = q.shape
    page = kps.shape[3] if kps is not None else kp.shape[3]
    # Decode-shaped lanes deep enough to span several pages each.
    qm = jax.random.normal(jax.random.PRNGKey(12), (b, hkv, g, 1, d),
                           jnp.float32)
    pos_start = jnp.asarray([3 * page + 5, 2 * page, page + 1, 3],
                            jnp.int32)
    q_len = jnp.ones((b,), jnp.int32)
    kwargs = dict(k_scale=kps, v_scale=vps, block_q=1, interpret=True)
    whole = paged_mixed_attention(qm, kp, vp, tables, pos_start, q_len, 0,
                                  **kwargs)
    split = jnp.full((b,), 2, jnp.int32)
    state = paged_mixed_attention(qm, kp, vp, tables, pos_start, q_len, 0,
                                  page_hi=split, emit_state=True, **kwargs)
    assert all(s.dtype == jnp.float32 for s in state)
    chained = paged_mixed_attention(qm, kp, vp, tables, pos_start, q_len,
                                    0, page_lo=split, carry_state=state,
                                    **kwargs)
    np.testing.assert_array_equal(np.asarray(whole), np.asarray(chained))


def test_mixed_all_lanes_inactive_returns_zeros():
    """q_len = 0 everywhere: the ragged work list is ALL padding (zero real
    page steps) and the output is defined — all zeros."""
    q, kp, vp, _, _, tables, _ = _setup(page=16)
    b, hkv, g, d = q.shape
    qm = jax.random.normal(jax.random.PRNGKey(1), (b, hkv, g, 4, d),
                           jnp.float32)
    zeros = jnp.zeros((b,), jnp.int32)
    out = paged_mixed_attention(qm, kp, vp, tables, jnp.zeros_like(zeros),
                                zeros, 0, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.zeros_like(out))


def test_mixed_single_item_work_list():
    """One active lane, one q block: the smallest possible ragged grid
    still matches the oracle."""
    q, kp, vp, _, _, tables, _ = _setup(b=1, page=16)
    _, hkv, g, d = q.shape
    qm = jax.random.normal(jax.random.PRNGKey(2), (1, hkv, g, 4, d),
                           jnp.float32)
    pos_start = jnp.asarray([7], jnp.int32)
    q_len = jnp.asarray([3], jnp.int32)
    out = paged_mixed_attention(qm, kp, vp, tables, pos_start, q_len, 0,
                                block_q=4, interpret=True)
    ref = _mixed_ref(qm, kp, vp, None, None, tables, pos_start, q_len, 0)
    for i in range(3):
        np.testing.assert_allclose(np.asarray(out[0, :, :, i], np.float32),
                                   ref[0, :, :, i], atol=2e-5, rtol=2e-5)


def test_build_mixed_work_list_compaction():
    """Real items are compacted to the grid front in (seq, qb) order with
    per-item causal page counts; padding items alias the LAST real item's
    output block (revisit semantics: no extra flush) with pages=0.  The
    (seq, qb, pages) columns are the PR 11 fixture values — the
    head-group / page-span refactor must not move them."""
    pos = jnp.asarray([5, 128, 0, 3], jnp.int32)
    qlen = jnp.asarray([1, 5, 3, 0], jnp.int32)
    seq, hg, qb, plo, pages, blk = build_mixed_work_list(
        pos, qlen, page=128, block_q=2, num_qb=3, max_pages=3)
    seq, hg, qb, plo, pages, blk = map(
        np.asarray, (seq, hg, qb, plo, pages, blk))
    assert seq.shape == (12,)
    # The sixth column: the pair's rank among the real (seq, qb) pairs;
    # padding items carry the last real item's.
    np.testing.assert_array_equal(blk, [0, 1, 2, 3, 4, 5] + [5] * 6)
    # Real: (0,0) 1 page; (1,0/1/2) 2 pages each; (2,0/1) 1 page each.
    np.testing.assert_array_equal(seq[:6], [0, 1, 1, 1, 2, 2])
    np.testing.assert_array_equal(qb[:6], [0, 0, 1, 2, 0, 1])
    np.testing.assert_array_equal(pages[:6], [1, 2, 2, 2, 1, 1])
    # Padding aliases the last real item, zero pages.
    np.testing.assert_array_equal(seq[6:], [2] * 6)
    np.testing.assert_array_equal(qb[6:], [1] * 6)
    np.testing.assert_array_equal(pages[6:], [0] * 6)
    # Ungrouped, unbounded defaults: hg and plo are identically zero.
    np.testing.assert_array_equal(hg, np.zeros(12, np.int32))
    np.testing.assert_array_equal(plo, np.zeros(12, np.int32))


def test_build_mixed_work_list_head_groups_and_spans():
    """head_groups replicates each real (seq, qb) item per KV head group
    (seq-major, hg, qb order) and page_lo/page_hi clamp each sequence's
    span — the windowed-residency hook.  Same PR 11 fixture inputs."""
    pos = jnp.asarray([5, 128, 0, 3], jnp.int32)
    qlen = jnp.asarray([1, 5, 3, 0], jnp.int32)
    seq, hg, qb, plo, pages, blk = build_mixed_work_list(
        pos, qlen, page=128, block_q=2, num_qb=3, max_pages=3,
        head_groups=2,
        page_lo=jnp.asarray([0, 1, 0, 0], jnp.int32),
        page_hi=jnp.asarray([3, 2, 1, 3], jnp.int32))
    seq, hg, qb, plo, pages, blk = map(
        np.asarray, (seq, hg, qb, plo, pages, blk))
    assert seq.shape == (24,)
    # Every head group of a (seq, qb) pair shares the pair's block.
    np.testing.assert_array_equal(blk[:12],
                                  [0, 0, 1, 2, 3, 1, 2, 3, 4, 5, 4, 5])
    # Each real item appears once per head group, hg-major inside a seq.
    np.testing.assert_array_equal(seq[:12],
                                  [0, 0, 1, 1, 1, 1, 1, 1, 2, 2, 2, 2])
    np.testing.assert_array_equal(hg[:12],
                                  [0, 1, 0, 0, 0, 1, 1, 1, 0, 0, 1, 1])
    np.testing.assert_array_equal(qb[:12],
                                  [0, 0, 0, 1, 2, 0, 1, 2, 0, 1, 0, 1])
    # seq 1's pages clamp to page_hi=2 (unchanged here) with plo=1; seq
    # 2's clamp to 1.  plo never exceeds the clamped page count.
    np.testing.assert_array_equal(pages[:12],
                                  [1, 1, 2, 2, 2, 2, 2, 2, 1, 1, 1, 1])
    np.testing.assert_array_equal(plo[:12],
                                  [0, 0, 1, 1, 1, 1, 1, 1, 0, 0, 0, 0])
    np.testing.assert_array_equal(pages[12:], np.zeros(12, np.int32))
    np.testing.assert_array_equal(plo[12:], np.zeros(12, np.int32))


def test_build_mixed_work_list_all_inactive():
    seq, hg, qb, plo, pages, blk = build_mixed_work_list(
        jnp.zeros((3,), jnp.int32), jnp.zeros((3,), jnp.int32),
        page=16, block_q=4, num_qb=2, max_pages=4)
    np.testing.assert_array_equal(np.asarray(pages), np.zeros(6, np.int32))
    np.testing.assert_array_equal(np.asarray(blk), np.zeros(6, np.int32))


def test_mixed_grid_plan_pads_awkward_qmax():
    """qmax=33 regression: the old fallback walked block_q down to the
    largest divisor (11 — a terrible tile); the plan now keeps the tuned
    block and pads the q axis instead."""
    plan = mixed_grid_plan(33, hkv=2, g=3, d=32, page=16, kv="float32")
    assert plan["block_q"] == 32
    assert plan["qpad"] == 64 and plan["num_qb"] == 2
    # And the padded grid still matches the oracle end to end.
    q, kp, vp, _, _, tables, _ = _setup(b=2, page=16)
    _, hkv, g, d = q.shape
    qm = jax.random.normal(jax.random.PRNGKey(6), (2, hkv, g, 33, d),
                           jnp.float32)
    pos_start = jnp.asarray([0, 3], jnp.int32)
    q_len = jnp.asarray([33, 1], jnp.int32)
    out = paged_mixed_attention(qm, kp, vp, tables, pos_start, q_len, 0,
                                interpret=True)
    ref = _mixed_ref(qm, kp, vp, None, None, tables, pos_start, q_len, 0)
    for s in range(2):
        for i in range(int(q_len[s])):
            np.testing.assert_allclose(
                np.asarray(out[s, :, :, i], np.float32), ref[s, :, :, i],
                atol=2e-5, rtol=2e-5)


def test_paged_mixed_attention_decode_lane_matches_decode_kernel():
    """A q_len=1 lane through the mixed kernel equals the dedicated decode
    kernel on the same pool/tables — the two paths must never diverge."""
    q, kp, vp, _, _, tables, lengths = _setup(page=16)
    b, hkv, g, d = q.shape
    qm = q[:, :, :, None, :]  # [B, Hkv, G, 1, D]
    pos_start = lengths - 1   # decode lane: query at position len-1
    q_len = jnp.ones((b,), jnp.int32)
    out = paged_mixed_attention(qm, kp, vp, tables, pos_start, q_len, 0,
                                interpret=True)
    ref = paged_decode_attention(q, kp, vp, tables, lengths, 0,
                                 block_b=1, interpret=True)
    np.testing.assert_allclose(np.asarray(out[:, :, :, 0]), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# The softmax state read lane-tiled (PR 51)
# ---------------------------------------------------------------------------


def _lane0_broadcast(x, n):
    """The oracle: a softmax-state value ``[.., 128]`` widened to ``n``
    lanes the way the block did before PR 51, lane 0 sliced out and
    broadcast (``scores - m_next[..., :1]``, ``acc * correction[..., :1]``,
    ``acc / (l[..., :1] + eps)``)."""
    return jnp.broadcast_to(x[..., :1], x.shape[:-1] + (n,))


@pytest.fixture
def lane0_broadcast(monkeypatch):
    """A context in which every ragged launch widens its softmax state by
    the oracle.  The jitted launches look ``_lanes`` up when they TRACE, so
    their caches go on the way in and on the way out."""
    import contextlib

    from arks_tpu.ops import paged_attention as pa

    def forget():
        pa._paged_mixed_call.clear_cache()
        pa._paged_mixed_flat_call.clear_cache()

    @contextlib.contextmanager
    def oracle():
        forget()
        with monkeypatch.context() as mp:
            mp.setattr(pa, "_lanes", _lane0_broadcast)
            yield
        forget()
    yield oracle
    forget()


# Whole lane tiles everywhere the state is widened: pages of 128 keys,
# heads of 128 lanes (a latent row of 256 with values of 128).
_TILED = dict(page=128, block_q=8, window=300, hkv=2, g=2, d=128,
              max_pages=6)

# Lanes (pos_start, q_len) of a batch, by what the span meets.
_TILED_SPANS = {
    # Every key of every lane in page 0.
    "one-page": [(24, 8), (0, 2), (72, 1)],
    # A two-block chunk over five pages and a decode lane over six.
    "many-pages": [(560, 16), (700, 1), (0, 0)],
    # Blocks whose queries sit on both sides of a page's end (124..131,
    # 250..257) and one that ends on it (248..255).
    "block-straddles-a-page": [(124, 8), (250, 8), (248, 8)],
    # The lowest key of the block's first query (pos - 299) is the first
    # key of page 1 (128) / of page 2 (256); the third lane's LAST query
    # (428) is the first that no longer holds page 1's first key.
    "window-edge-on-a-page-boundary": [(427, 8), (555, 16), (421, 8)],
    # ... and in the middle of one.
    "window-edge-inside-a-page": [(480, 8), (700, 1), (610, 12)],
    # Rows past q_len in the last block of a lane.
    "rows-past-q_len": [(320, 5), (130, 11), (720, 1)],
}


def _tiled_flat_batch(lanes):
    """The flat batch of ``lanes`` ((pos_start, q_len) a lane): the lanes'
    rows one after the other, two padding rows at the end."""
    q_len = np.asarray([n for _, n in lanes], np.int32)
    q_start = (np.cumsum(q_len) - q_len).astype(np.int32)
    token_slot = np.full((int(q_len.sum()) + 2,), -1, np.int32)
    for s, (start, n) in enumerate(zip(q_start, q_len)):
        token_slot[start:start + n] = s
    return (jnp.asarray(token_slot), jnp.asarray(q_start),
            jnp.asarray(q_len),
            jnp.asarray([p for p, _ in lanes], jnp.int32))


def _tiled_pools(kind, lanes: int):
    """Pools, tables, the query shape and the launch's keywords of a
    ``kind``."""
    c = _TILED
    ks = jax.random.split(jax.random.PRNGKey(51), 6)
    n = lanes * c["max_pages"] + 2
    tables = jax.random.permutation(ks[0], n)[:lanes * c["max_pages"]] \
        .reshape(lanes, c["max_pages"]).astype(jnp.int32)
    if kind == "latent":
        pools = (jax.random.normal(ks[1], (2, n, 1, c["page"], 256),
                                   jnp.float32), None, None, None)
        return pools, tables, (1, c["hkv"] * c["g"], 256), dict(
            latent_v=128, scale=256 ** -0.5)
    hkv, g, d = c["hkv"], c["g"], c["d"]
    shape = (2, n, hkv, c["page"], d)
    if kind == "gqa-int8":
        pools = (jax.random.randint(ks[1], shape, -127, 128, jnp.int8),
                 jax.random.randint(ks[2], shape, -127, 128, jnp.int8),
                 jax.random.uniform(ks[3], shape[:4], jnp.float32, .01, .03),
                 jax.random.uniform(ks[4], shape[:4], jnp.float32, .01, .03))
    else:
        pools = (jax.random.normal(ks[1], shape, jnp.float32),
                 jax.random.normal(ks[2], shape, jnp.float32), None, None)
    kw = dict(window=c["window"]) if kind.startswith("window") else {}
    if kind == "window+sink":
        kw["sink"] = jax.random.normal(ks[5], (hkv, g), jnp.float32)
    return pools, tables, (hkv, g, d), kw


@pytest.mark.parametrize("span", sorted(_TILED_SPANS))
@pytest.mark.parametrize("kind", ["gqa", "gqa-int8", "window", "window+sink",
                                  "latent"])
def test_lane_tiled_softmax_state_is_the_lane0_broadcast_bytes(
        kind, span, lane0_broadcast):
    """The launch that reads its running maximum, correction and sum
    lane-tiled is BIT FOR BIT the one that slices lane 0 out and
    broadcasts it, by kind of launch and by what the span meets."""
    from arks_tpu.ops.paged_attention import paged_mixed_attention_flat
    lanes = _TILED_SPANS[span]
    pools, tables, (hkv, g, d), kw = _tiled_pools(kind, len(lanes))
    token_slot, q_start, q_len, pos = _tiled_flat_batch(lanes)
    q = jax.random.normal(jax.random.PRNGKey(5),
                          (token_slot.shape[0], hkv, g, d), jnp.float32)

    def launch():
        return np.asarray(paged_mixed_attention_flat(
            q, pools[0], pools[1], tables, token_slot, q_start, q_len, pos,
            1, pools[2], pools[3], block_q=_TILED["block_q"], interpret=True,
            **kw))

    got = launch()
    with lane0_broadcast():
        want = launch()
    assert np.isfinite(got).all() and np.abs(got).max() > 0
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("kv", ["f32", "int8"])
def test_lane_tiled_softmax_state_carries_an_empty_span(kv, lane0_broadcast):
    """A ``carry`` call whose span is empty (every page of the lane fell in
    the earlier span) passes the state through and normalises it; the other
    lanes' spans run on both sides of the split.  Emitted state and final
    output are the lane-0-broadcast bytes, and the single call's."""
    q, kp, vp, kps, vps, tables, _ = _setup(quantized=kv == "int8",
                                            page=128, d=128)
    b, hkv, g, d = q.shape
    qm = jax.random.normal(jax.random.PRNGKey(12), (b, hkv, g, 4, d),
                           jnp.float32)
    pos_start = jnp.asarray([3 * 128 + 5, 2 * 128, 128 + 1, 3], jnp.int32)
    q_len = jnp.asarray([4, 1, 3, 2], jnp.int32)
    kwargs = dict(k_scale=kps, v_scale=vps, block_q=4, interpret=True)
    split = jnp.full((b,), 2, jnp.int32)

    def chained():
        state = paged_mixed_attention(
            qm, kp, vp, tables, pos_start, q_len, 0, page_hi=split,
            emit_state=True, **kwargs)
        out = paged_mixed_attention(
            qm, kp, vp, tables, pos_start, q_len, 0, page_lo=split,
            carry_state=state, **kwargs)
        return [np.asarray(x) for x in (*state, out)]

    got = chained()
    with lane0_broadcast():
        want = chained()
    for a, b_ in zip(got, want):
        np.testing.assert_array_equal(a, b_)
    whole = paged_mixed_attention(qm, kp, vp, tables, pos_start, q_len, 0,
                                  **kwargs)
    np.testing.assert_array_equal(np.asarray(whole), got[-1])
