"""Ragged work-list grid: engine-level stream identity against the XLA
gather oracle, the grid-plan counters, and the block-compacted query
layout.

The kernel-level parity lives in test_paged_attention.py; HERE the gate is
the serving stream: the same workload through the Pallas mixed path
(interpret mode on CPU) and through the XLA gather path (``impl="xla"``)
must emit the same token streams, at pipeline depths 0 and 2, for plain,
guided, and speculative traffic.
"""

import numpy as np
import pytest

from arks_tpu.engine import Request, SamplingParams

import harness


def _mk_engine(monkeypatch, *, depth=0, impl="pallas", spec=False, **kw):
    monkeypatch.setenv("ARKS_MIXED_STEP", "1")
    monkeypatch.setenv("ARKS_ATTN_IMPL", impl)
    monkeypatch.setenv("ARKS_PIPELINE_DEPTH", str(depth))
    defaults = dict(num_slots=2, max_cache_len=64,
                    prefill_buckets=(8, 16, 32), steps_per_dispatch=4,
                    prefill_chunk=16, kv_layout="paged", prefix_cache_mb=0)
    if spec:
        defaults.update(draft_model="tiny", draft_len=3)
    eng = harness.warmed("tiny", base=defaults, **kw)
    return eng.cfg, eng


_drive = harness.drive


def _collect(req):
    ids, fin = [], None
    while True:
        out = req.outputs.get(timeout=120)
        ids.extend(out.token_ids)
        if out.finished:
            fin = out
            break
    return ids, fin.finish_reason


def _run_workload(eng, cfg, guided=False, sampled=False):
    """Greedy (+ optionally guided) requests — chunked and one-shot prompt
    shapes, more requests than slots.  ``sampled`` makes the chunked one a
    fixed-seed sampled stream: the Pallas path and the oracle agree to a
    tolerance, not to the bit, so only greedy streams are compared across
    them; the sampled one is compared across depths of the Pallas path."""
    reqs = [
        Request("g0", [5, 6, 7], SamplingParams(
            max_tokens=5, temperature=0.0, ignore_eos=True)),
        Request("c0", [int(x) % cfg.vocab_size for x in range(3, 40)],
                SamplingParams(max_tokens=5, ignore_eos=True,
                               **(dict(temperature=0.8, top_p=0.9, seed=7)
                                  if sampled else dict(temperature=0.0)))),
        Request("g1", [9] * 20, SamplingParams(
            max_tokens=5, temperature=0.0, ignore_eos=True)),
    ]
    if guided:
        reqs.append(Request("j0", [4, 8, 2], SamplingParams(
            max_tokens=6, temperature=0.0, guide=("json", ""))))
    for r in reqs:
        eng.add_request(r)
    _drive(eng)
    return [_collect(r) for r in reqs]


@pytest.mark.parametrize("depth", [0, 2])
def test_stream_identity_ragged_vs_oracle(monkeypatch, depth):
    """Plain + guided traffic: the Pallas mixed path's token streams are
    those of the XLA gather oracle at this pipeline depth."""
    outs = {}
    for impl in ("pallas", "xla"):
        cfg, eng = _mk_engine(monkeypatch, impl=impl, depth=depth)
        assert eng.resolved_config["mixed_grid"] == "ragged"
        assert eng.resolved_config["decode_impl"] == impl
        outs[impl] = _run_workload(eng, cfg, guided=True)
    assert outs["pallas"] == outs["xla"]


@pytest.mark.parametrize("depth", [0, 2])
def test_stream_identity_spec_traffic(monkeypatch, depth):
    """Speculative traffic (draft+verify ride the mixed dispatch): the
    Pallas path and the XLA oracle emit identical accepted streams at this
    depth."""
    outs = {}
    for impl in ("pallas", "xla"):
        cfg, eng = _mk_engine(monkeypatch, impl=impl, depth=depth,
                              spec=True)
        outs[impl] = _run_workload(eng, cfg)
    assert outs["pallas"] == outs["xla"]


def test_pallas_sampled_streams_identical_across_depths(monkeypatch):
    """The sequential mixed step and the pipelined one run the SAME kernel:
    a fixed-seed sampled stream (with greedy and guided ones beside it)
    through the Pallas path is the same bytes at depth 0 and at depth 2."""
    outs = {}
    for depth in (0, 2):
        cfg, eng = _mk_engine(monkeypatch, depth=depth)
        outs[depth] = _run_workload(eng, cfg, guided=True, sampled=True)
    assert outs[0] == outs[2]


def test_sparse_batch_grid_steps_drop_to_ideal(monkeypatch):
    """3 active requests in a 64-slot engine: the counter equals
    ``mixed_grid_steps`` of the batches dispatched (each item's own causal
    page count) and sits far below S*num_qb*max_pages, what a grid over
    every lane's widest chunk would run.  Counters describe the grid
    PLAN, so this runs on the fast XLA oracle."""
    from arks_tpu.engine.paged import mixed_grid_steps
    cfg, eng = _mk_engine(monkeypatch, impl="xla", num_slots=64)
    batches = []
    count = eng._mixed_grid_counters

    def recording(pos_start, q_len, qmax):
        batches.append((pos_start.copy(), q_len.copy(), qmax))
        return count(pos_start, q_len, qmax)

    eng._mixed_grid_counters = recording
    for i in range(3):
        eng.add_request(Request(f"r{i}", [5 + i, 6, 7], SamplingParams(
            max_tokens=4, temperature=0.0, ignore_eos=True)))
    _drive(eng)
    steps = eng.metrics.mixed_grid_steps_total.total()
    want = sum(mixed_grid_steps(
        pos, ql, page=eng._page_size(),
        block_q=eng._grid_plans[qmax]["block_q"],
        num_qb=eng._grid_plans[qmax]["num_qb"], max_pages=eng._max_pages)
        for pos, ql, qmax in batches)
    assert steps == want > 0
    plan = next(iter(eng._grid_plans.values()))
    n_dispatches = sum(
        n for _, _, n in eng.metrics.mixed_batch_tokens._data.values())
    assert n_dispatches == len(batches)
    every_lane = 64 * plan["num_qb"] * eng._max_pages * n_dispatches
    assert steps < every_lane / 10, (steps, every_lane)


# GQA sweep shape: (hkv, d, page, max_pages, qmax).
_GQA_SHAPE = (8, 16, 16, 16, 64)
_GQA_VMEM_BUDGET = 18432  # f32 lanes; hg=1 affords block_q=qmax, hg=8 only 4


def _gqa_vmem_block_q(hg: int, g: int) -> int:
    """Largest q block the modeled VMEM budget affords one (hg-head,
    g-share) work item: double-buffered KV blocks (2 in flight) + q tile
    + f32 accumulator.  Grouping divides the whole footprint by
    hkv/head_group, which is the headroom the tuned plan re-invests in
    block_q."""
    hkv, d, page, _, qmax = _GQA_SHAPE
    comp = (_GQA_VMEM_BUDGET // hg - 4 * page * d) // (2 * g * d)
    if comp >= qmax:
        return qmax
    bq = 1
    while bq * 2 <= comp:
        bq *= 2
    return bq


def _gqa_bytes_sweep() -> dict:
    """GQA head-group sweep (g in {1, 4, 8}), plan-only — no kernel
    launches.  The head-grouped DMA schedule wins KV bytes THROUGH
    block_q: grouping shrinks a work item's VMEM footprint by
    hkv/head_group, the tuned plan re-invests that headroom in a larger q
    block, and fewer q blocks re-stream each causal page prefix fewer
    times.  The bytes-moved pair (mixed_kv_bytes actual vs
    fetch-each-block-once ideal) for the ungrouped baseline against the
    grouped tuned plan."""
    from arks_tpu.engine.paged import mixed_kv_bytes
    from arks_tpu.ops import paged_attention as pa

    hkv, d, page, maxp, qmax = _GQA_SHAPE
    # Decode-heavy lanes: a long causal prefix (the re-stream cost the
    # grouping exists to cut) plus a short second lane.
    pos = np.zeros(4, np.int32)
    ql = np.zeros(4, np.int32)
    pos[:2] = (maxp * page - qmax, page)
    ql[:2] = (qmax, 8)
    phb = page * d * 4 * 2  # f32 K + V bytes per (page, head) block
    out: dict = {}
    for g in (1, 4, 8):
        byt = {}
        for name, hg in (("base", hkv), ("grouped", 1)):
            plan = pa.mixed_grid_plan(
                qmax, hkv=hkv, g=g, d=d, page=page, kv="float32",
                block_q=_gqa_vmem_block_q(hg, g), head_group=hg)
            b_act, b_ideal = mixed_kv_bytes(
                pos, ql, page=page, block_q=plan["block_q"],
                num_qb=plan["num_qb"], max_pages=maxp, hkv=hkv,
                page_head_bytes=phb)
            byt[name] = b_act
            out[f"gqa_g{g}_{name}_kv_bytes"] = b_act
            out[f"gqa_g{g}_kv_bytes_ideal"] = b_ideal
        out[f"gqa_g{g}_bytes_ratio"] = byt["base"] / byt["grouped"]
    return out


def test_gqa_bytes_sweep_hits_group_factor():
    """At g=8 the grouped tuned plan moves >= g fewer KV bytes than the
    ungrouped baseline (the win arrives through the larger tuned block_q
    that head grouping's VMEM headroom affords), and the grouped plan
    reaches the fetch-each-block-once ideal.  Plan-only — no kernel
    launches; the bitwise identity of the grouped kernel lives in
    test_paged_attention.py."""
    r = _gqa_bytes_sweep()
    assert r["gqa_g8_bytes_ratio"] >= 8
    assert r["gqa_g8_grouped_kv_bytes"] == r["gqa_g8_kv_bytes_ideal"]
    # The win scales with the GQA share factor.
    assert (r["gqa_g1_bytes_ratio"] < r["gqa_g4_bytes_ratio"]
            < r["gqa_g8_bytes_ratio"])


def test_kv_bytes_moved_counter_pair(monkeypatch):
    """Every mixed dispatch accounts the KV bytes its grid plan moves
    (mixed_kv_bytes_total) against the fetch-each-block-once ideal
    (mixed_kv_bytes_ideal_total) — the waste ratio the head-grouped DMA
    restructure is gated on.  Counters describe the PLAN, so the fast
    XLA oracle drives them; actual >= ideal always, and with the
    head-group factor covering every kv head in one pass the pair
    converges for single-page decode dispatches."""
    cfg, eng = _mk_engine(monkeypatch, impl="xla", num_slots=4)
    for i in range(2):
        eng.add_request(Request(f"r{i}", [5 + i, 6, 7], SamplingParams(
            max_tokens=4, temperature=0.0, ignore_eos=True)))
    _drive(eng)
    actual = eng.metrics.mixed_kv_bytes_total.total()
    ideal = eng.metrics.mixed_kv_bytes_ideal_total.total()
    assert ideal > 0
    assert actual >= ideal
    # The tiny model's decode batches fit one q-block, so the ragged
    # plan fetches each (seq, page) block exactly once: no waste.
    plan = next(iter(eng._grid_plans.values()))
    if plan["num_qb"] == 1:
        assert actual == ideal


# ---------------------------------------------------------------------------
# Block-compacted query layout (the flat batch's way into the ragged kernel)
# ---------------------------------------------------------------------------
#
# The mixed step hands the kernel its queries in blocks of block_q rows, one
# per real (lane, q block) pair, filled by one gather from the flat batch and
# read back by one.  On every real row the bytes must be those of the
# per-lane call (paged_mixed_attention over the [S, Hkv, G, Q, D] block of the
# same batch), and the values those of the XLA gather oracle.

_LANES, _CHUNK, _BQ = 6, 12, 4      # t_flat = 18, qmax = 13, nb = 6 + 3


def _flat_batch(name):
    """(token_slot [T], q_start [S], q_len [S], pos_start [S]) of one of the
    batch shapes the engine packs: decode rows first, then chunk lanes."""
    t_flat = _LANES + _CHUNK
    lanes = {
        # one row a lane, one lane idle
        "decode_only": [(0, 1, 5), (1, 1, 17), (2, 1, 0), (4, 1, 30),
                        (5, 1, 9)],
        # decode rows and ONE chunk that takes the whole budget
        "full_chunk": [(0, 1, 5), (3, 1, 12), (1, _CHUNK, 3)],
        # the flood's even quota: many two- and three-token chunks
        "small_chunks": [(0, 1, 7), (1, 2, 0), (2, 3, 4), (3, 2, 15),
                         (4, 3, 1), (5, 2, 6)],
        # a chunk that ends exactly on a block edge ...
        "block_edge": [(2, 1, 3), (4, 2 * _BQ, 5)],
        # ... and one row past it
        "block_edge_plus_one": [(2, 1, 3), (4, 2 * _BQ + 1, 5)],
        "empty": [],
    }[name]
    token_slot = np.full((t_flat,), -1, np.int32)
    q_start = np.zeros((_LANES,), np.int32)
    q_len = np.zeros((_LANES,), np.int32)
    pos = np.zeros((_LANES,), np.int32)
    t = 0
    for lane, n, p0 in lanes:
        token_slot[t:t + n] = lane
        q_start[lane], q_len[lane], pos[lane] = t, n, p0
        t += n
    return token_slot, q_start, q_len, pos


_BATCHES = ["decode_only", "full_chunk", "small_chunks", "block_edge",
            "block_edge_plus_one", "empty"]


def _flat_pools(kv):
    import jax
    import jax.numpy as jnp
    page, hkv, d, max_pages = (128, 2, 32, 2) if kv == "int8" \
        else (16, 2, 32, 4)
    n = _LANES * max_pages + 2
    ks = jax.random.split(jax.random.PRNGKey(1), 6)
    if kv == "int8":
        kp = jax.random.randint(ks[0], (2, n, hkv, page, d), -127, 128,
                                jnp.int8)
        vp = jax.random.randint(ks[1], (2, n, hkv, page, d), -127, 128,
                                jnp.int8)
        kps = jax.random.uniform(ks[2], (2, n, hkv, page), jnp.float32,
                                 0.01, 0.03)
        vps = jax.random.uniform(ks[3], (2, n, hkv, page), jnp.float32,
                                 0.01, 0.03)
    else:
        kp = jax.random.normal(ks[0], (2, n, hkv, page, d), jnp.bfloat16)
        vp = jax.random.normal(ks[1], (2, n, hkv, page, d), jnp.bfloat16)
        kps = vps = None
    tables = jax.random.permutation(ks[4], n)[: _LANES * max_pages].reshape(
        _LANES, max_pages).astype(jnp.int32)
    q = jax.random.normal(ks[5], (_LANES + _CHUNK, hkv, 3, d), jnp.bfloat16)
    return q, kp, vp, kps, vps, tables


def _lane_block(q, q_start):
    """The per-lane ``[S, Hkv, G, qmax, D]`` block of a flat batch ``[T,
    Hkv, G, D]`` (``qmax = T - S + 1``, the flat call's own bound)."""
    import jax.numpy as jnp
    t_flat, lanes = q.shape[0], q_start.shape[0]
    qmax = t_flat - lanes + 1
    span = np.minimum(q_start[:, None] + np.arange(qmax)[None], t_flat - 1)
    return jnp.transpose(q[span.reshape(-1)].reshape(
        lanes, qmax, *q.shape[1:]), (0, 2, 3, 1, 4))


@pytest.mark.parametrize("head_group", [None, 1])
@pytest.mark.parametrize("kv", ["int8", "bf16"])
@pytest.mark.parametrize("batch", _BATCHES)
def test_compacted_layout_bytes_match_per_lane(batch, kv, head_group):
    import jax.numpy as jnp
    from arks_tpu.ops.paged_attention import (
        paged_mixed_attention, paged_mixed_attention_flat)
    q, kp, vp, kps, vps, tables = _flat_pools(kv)
    token_slot, q_start, q_len, pos = _flat_batch(batch)
    args = (jnp.asarray(token_slot), jnp.asarray(q_start),
            jnp.asarray(q_len), jnp.asarray(pos))
    kw = dict(k_scale=kps, v_scale=vps, block_q=_BQ, interpret=True)
    got = np.asarray(paged_mixed_attention_flat(
        q, kp, vp, tables, *args, 1, head_group=head_group,
        **kw).astype(jnp.float32))
    # The per-lane call over the [S, Hkv, G, qmax, D] block of the same
    # batch.
    lane = np.asarray(paged_mixed_attention(
        _lane_block(q, q_start), kp, vp, tables, args[3], args[2], 1,
        head_group=head_group, **kw).astype(jnp.float32))
    real = token_slot >= 0
    assert np.isfinite(got).all()
    # Padding rows come back as zeros, whatever their block held.
    np.testing.assert_array_equal(got[~real], 0.0)
    for t in np.flatnonzero(real):
        s = token_slot[t]
        np.testing.assert_array_equal(got[t], lane[s, :, :, t - q_start[s]])
    if batch != "empty":
        assert np.abs(got[real]).max() > 0


@pytest.mark.parametrize("kv", ["int8", "bf16"])
@pytest.mark.parametrize("batch", _BATCHES)
def test_compacted_layout_matches_xla_gather_oracle(batch, kv):
    """The flat call against the path ``impl="xla"`` serves: every flat
    row's own table gathered whole, masked attention over [0, its
    position].  The tuned-default block_q (no ``block_q`` argument), so the
    plan is the one the mixed step resolves."""
    import jax.numpy as jnp
    from arks_tpu.ops.attention import (
        _decode_attention_xla_quant, decode_attention_xla)
    from arks_tpu.ops.paged_attention import (
        paged_gather_kv, paged_mixed_attention_flat)
    q, kp, vp, kps, vps, tables = _flat_pools(kv)
    token_slot, q_start, q_len, pos = _flat_batch(batch)
    got = np.asarray(paged_mixed_attention_flat(
        q, kp, vp, tables, jnp.asarray(token_slot), jnp.asarray(q_start),
        jnp.asarray(q_len), jnp.asarray(pos), 1, k_scale=kps, v_scale=vps,
        interpret=True).astype(jnp.float32))
    real = token_slot >= 0
    lane = np.maximum(token_slot, 0)
    token_pos = pos[lane] + np.arange(token_slot.shape[0]) - q_start[lane]
    lens = jnp.asarray(np.where(real, token_pos + 1, 0), jnp.int32)
    tables_tok = tables[jnp.asarray(lane)]
    kc = paged_gather_kv(kp, tables_tok, 1)
    vc = paged_gather_kv(vp, tables_tok, 1)
    if kps is not None:
        want = _decode_attention_xla_quant(
            q, kc, vc, paged_gather_kv(kps, tables_tok, 1),
            paged_gather_kv(vps, tables_tok, 1), lens)
    else:
        want = decode_attention_xla(q, kc, vc, lens)
    want = np.asarray(want.astype(jnp.float32))
    np.testing.assert_array_equal(got[~real], 0.0)
    np.testing.assert_allclose(got[real], want[real], atol=3e-2, rtol=3e-2)
    if batch != "empty":
        assert np.abs(got[real]).max() > 0


@pytest.mark.parametrize("block_q", [1, 3, 4, 8])
@pytest.mark.parametrize("batch", _BATCHES)
def test_block_rank_arithmetic_matches_numpy_loop(batch, block_q):
    """base / block / row of mixed_block_layout and the work list's sixth
    column against a plain loop over the lanes."""
    import jax.numpy as jnp
    from arks_tpu.ops.paged_attention import (
        build_mixed_work_list, mixed_block_layout, mixed_grid_plan)
    token_slot, q_start, q_len, pos = _flat_batch(batch)
    t_flat = token_slot.shape[0]
    plan = mixed_grid_plan(t_flat - _LANES + 1, hkv=2, g=3, d=32, page=16,
                           kv="bfloat16", block_q=block_q, lanes=_LANES)
    nb = plan["nb"]
    assert nb == _LANES + -(-_CHUNK // block_q)
    base, src, out = map(np.asarray, mixed_block_layout(
        jnp.asarray(token_slot), jnp.asarray(q_start), jnp.asarray(q_len),
        block_q=block_q, nb=nb))
    # The loop: lanes in order, each lane's blocks in order.
    want_base, pairs = [], []
    for s in range(_LANES):
        want_base.append(len(pairs))
        pairs += [(s, qb) for qb in range(-(-int(q_len[s]) // block_q))]
    assert len(pairs) <= nb
    np.testing.assert_array_equal(base, want_base)
    src = src.reshape(nb, block_q)
    for j, (s, qb) in enumerate(pairs):
        for r in range(block_q):
            o = qb * block_q + r
            if o < q_len[s]:
                assert src[j, r] == q_start[s] + o
                assert out[q_start[s] + o] == j * block_q + r
    assert src.min() >= 0 and src.max() < t_flat
    assert out.min() >= 0 and out.max() < nb * block_q
    # The work list names the same blocks: item (s, hg, qb) -> rank of
    # (s, qb), for every head group; the launched front holds them all.
    for n_hg in (1, 2):
        seq, hg, qb, _, pages, blk = map(np.asarray, build_mixed_work_list(
            jnp.asarray(pos), jnp.asarray(q_len), page=16, block_q=block_q,
            num_qb=plan["num_qb"], max_pages=4, head_groups=n_hg,
            n_items=nb * n_hg))
        assert seq.shape == (nb * n_hg,)
        n_real = len(pairs) * n_hg
        got = sorted(zip(seq[:n_real], qb[:n_real], blk[:n_real]))
        assert got == sorted((s, b, j) for j, (s, b) in enumerate(pairs)
                             for _ in range(n_hg))
        assert (pages[:n_real] > 0).all() and (pages[n_real:] == 0).all()


@pytest.mark.parametrize("lanes", [1, 4, 192])
def test_pipelined_shape_lays_out_one_row_a_lane(lanes):
    """t_flat == b_lanes (the pipelined step): blocks of one row, as many
    as lanes, and a grid exactly as long as the per-lane launch's."""
    import jax
    import jax.numpy as jnp
    from arks_tpu.ops import paged_attention as pa
    plan = pa.mixed_grid_plan(1, hkv=2, g=3, d=32, page=16, kv="bfloat16",
                              lanes=lanes)
    assert (plan["block_q"], plan["nb"], plan["q_rows"]) == (1, lanes, lanes)

    def grid_of(fn, *args):
        eqns = jax.make_jaxpr(fn)(*args).jaxpr.eqns
        found = []

        def walk(eqns):
            for e in eqns:
                if e.primitive.name == "pallas_call":
                    found.append(tuple(e.params["grid_mapping"].grid))
                for v in e.params.values():
                    if hasattr(v, "jaxpr"):
                        walk(getattr(v.jaxpr, "eqns", None)
                             or v.jaxpr.jaxpr.eqns)
        walk(eqns)
        return found

    kp = jnp.zeros((1, lanes + 1, 2, 16, 32), jnp.bfloat16)
    tables = jnp.zeros((lanes, 1), jnp.int32)
    lane = jnp.arange(lanes, dtype=jnp.int32)
    ones = jnp.ones((lanes,), jnp.int32)
    flat = grid_of(lambda q: pa.paged_mixed_attention_flat(
        q, kp, kp, tables, lane, lane, ones, ones, 0, interpret=True),
        jnp.zeros((lanes, 2, 3, 32), jnp.bfloat16))
    per_lane = grid_of(lambda q: pa.paged_mixed_attention(
        q, kp, kp, tables, ones, ones, 0, interpret=True),
        jnp.zeros((lanes, 2, 3, 1, 32), jnp.bfloat16))
    assert flat == per_lane == [(lanes,)]


def test_q_layout_rows_counter_follows_the_plan(monkeypatch):
    """mixed_q_layout_rows_total rises by the plan's q_rows a dispatch
    (nb x block_q), whatever the batch holds: to be read against the real
    rows, the sum of mixed_batch_tokens.  mixed_kv_write_blocks_total
    rises by the distinct (slot, position // block rows) the dispatch's
    live rows touch (16 rows a block of this float32 pool): what the row
    write reads and writes back a layer."""
    cfg, eng = _mk_engine(monkeypatch, impl="xla", num_slots=8)
    seen = []
    inner = eng._mixed_grid_counters

    def spy(pos_start, q_len, qmax):
        seen.append((pos_start.copy(), q_len.copy()))
        inner(pos_start, q_len, qmax)

    monkeypatch.setattr(eng, "_mixed_grid_counters", spy)
    for i, n in enumerate((3, 40, 21)):
        eng.add_request(Request(f"r{i}", list(range(5, 5 + n)), SamplingParams(
            max_tokens=3, temperature=0.0, ignore_eos=True)))
    _drive(eng)
    plan = next(iter(eng._grid_plans.values()))
    n_dispatches = sum(
        n for _, _, n in eng.metrics.mixed_batch_tokens._data.values())
    rows = eng.metrics.mixed_q_layout_rows_total.total()
    assert n_dispatches > 0 and rows == n_dispatches * plan["q_rows"]
    assert plan["q_rows"] == plan["nb"] * plan["block_q"]
    assert plan["nb"] == 8 + -(-eng._mixed_budget // plan["block_q"])
    blk = eng._kv_write_block
    assert blk == 16 and len(seen) == n_dispatches
    want = sum(len({(s, p // blk) for s in range(len(ql))
                    for p in range(ps[s], ps[s] + ql[s])})
               for ps, ql in seen)
    live = sum(s for _, s, _ in eng.metrics.mixed_batch_tokens._data.values())
    got = eng.metrics.mixed_kv_write_blocks_total.total()
    assert got == want and n_dispatches <= got < live


# ---------------------------------------------------------------------------
# The launch's blocks arrive with their G x block_q rows merged (PR 55)
# ---------------------------------------------------------------------------
#
# The query, output and carried-state blocks of the ragged launch are
# [.., G x block_q, width], g-major, where they were [.., G, block_q, width]:
# a layout, not an algorithm, so every row's bytes are the parent commit's.
# Each case runs in interpret mode against a dense float64 masked softmax
# (what the XLA gather oracle computes) AND against the sha256 the parent
# commit a0b2fc9 gave for the same seeded inputs.  The digests were taken on
# this image's XLA CPU build; `_ORACLE_SHA` is the control that says the
# platform still rounds as it did then: the digest of a plain jax.numpy
# masked softmax over the case's own inputs, no kernel in it.  Where that
# one moves, the stored bytes say nothing about the kernel and only the
# oracle comparison is held.

_MERGED_SHAPES = [(64, 1), (64, 8), (7, 8), (6, 1), (16, 1)]
_MERGED_KINDS = ["latent", "int8", "window", "sink", "lane_int8", "chain"]


def _merged_inputs(g, block_q, kind):
    """Three lanes: a decode row deep in its third page, a chunk of two
    blocks and a row that starts inside its first page, an idle lane."""
    import jax
    import jax.numpy as jnp
    latent = kind == "latent"
    quant = kind in ("int8", "sink", "lane_int8", "chain")
    page = 128 if quant else 16
    hkv, d, dv = (1, 48, 32) if latent else (2, 32, 32)
    lanes, max_pages, chunk = 3, 3, 2 * block_q + 1
    n = lanes * max_pages + 1
    ks = jax.random.split(jax.random.PRNGKey(55 + 7 * g + block_q), 7)
    if quant:
        kp = jax.random.randint(ks[0], (2, n, hkv, page, d), -127, 128,
                                jnp.int8)
        vp = jax.random.randint(ks[1], (2, n, hkv, page, dv), -127, 128,
                                jnp.int8)
        kps = jax.random.uniform(ks[2], (2, n, hkv, page), jnp.float32,
                                 0.01, 0.03)
        vps = jax.random.uniform(ks[3], (2, n, hkv, page), jnp.float32,
                                 0.01, 0.03)
    else:
        kp = jax.random.normal(ks[0], (2, n, hkv, page, d), jnp.bfloat16)
        vp = None if latent else jax.random.normal(
            ks[1], (2, n, hkv, page, dv), jnp.bfloat16)
        kps = vps = None
    tables = jax.random.permutation(ks[4], n)[:lanes * max_pages].reshape(
        lanes, max_pages).astype(jnp.int32)
    t_flat = lanes + chunk
    q = jax.random.normal(ks[5], (t_flat, hkv, g, d), jnp.bfloat16)
    token_slot = np.full((t_flat,), -1, np.int32)
    q_start = np.zeros((lanes,), np.int32)
    q_len = np.zeros((lanes,), np.int32)
    pos = np.zeros((lanes,), np.int32)
    token_slot[0], q_start[0], q_len[0], pos[0] = 0, 0, 1, 2 * page + 5
    token_slot[1:1 + chunk] = 2
    q_start[2], q_len[2], pos[2] = 1, chunk, page - 3
    sink = jax.random.normal(ks[6], (hkv, g), jnp.float32) \
        if kind == "sink" else None
    return dict(q=q, kp=kp, vp=vp, kps=kps, vps=vps, tables=tables,
                token_slot=token_slot, q_start=q_start, q_len=q_len, pos=pos,
                window=(page + 9 if kind in ("window", "sink") else 0),
                sink=sink, latent_v=dv if latent else 0, page=page)


def _merged_run(pa, g, block_q, kind):
    """What module ``pa``'s launch hands back for a case, as float32 arrays
    (the chain: the final output, then the raw state as ``pa`` holds it)."""
    import jax.numpy as jnp
    c = _merged_inputs(g, block_q, kind)
    lane = tuple(jnp.asarray(c[k]) for k in ("pos", "q_len"))
    kw = dict(k_scale=c["kps"], v_scale=c["vps"], block_q=block_q,
              interpret=True)
    if kind in ("lane_int8", "chain"):
        block = _lane_block(c["q"], c["q_start"])
        if kind == "lane_int8":
            out = [pa.paged_mixed_attention(
                block, c["kp"], c["vp"], c["tables"], *lane, 1,
                head_group=1, **kw)]
        else:
            split = jnp.asarray([2, 0, 1], jnp.int32)
            state = pa.paged_mixed_attention(
                block, c["kp"], c["vp"], c["tables"], *lane, 1,
                page_hi=split, emit_state=True, **kw)
            out = pa.paged_mixed_attention(
                block, c["kp"], c["vp"], c["tables"], *lane, 1,
                page_lo=split, carry_state=state, **kw)
            out = [out, *state]
    else:
        out = [pa.paged_mixed_attention_flat(
            c["q"], c["kp"], c["vp"], c["tables"],
            *(jnp.asarray(c[k]) for k in ("token_slot", "q_start", "q_len",
                                          "pos")),
            1, latent_v=c["latent_v"],
            scale=0.2 if kind == "latent" else None, window=c["window"],
            sink=c["sink"], **kw)]
    return c, [np.asarray(x.astype(jnp.float32)) for x in out]


def _state_as_the_parent_held_it(x, g, block_q):
    """Raw state ``[S, Hkv, num_qb, G x block_q, W]`` as the ``[S, Hkv, G,
    qpad, W]`` the parent's launch handed out: the digests' layout."""
    s, hkv, num_qb, _, w = x.shape
    return np.transpose(x.reshape(s, hkv, num_qb, g, block_q, w),
                        (0, 1, 3, 2, 4, 5)).reshape(s, hkv, g, -1, w)


def _merged_oracle(c, kind, xp):
    """[T, Hkv, G, Dv]: every real flat row's masked softmax over its own
    lane's pages, dense; ``xp`` is numpy (float64: the oracle) or jax.numpy
    (float32: the platform control)."""
    f = np.float64 if xp is np else np.float32
    page, tables = c["page"], np.asarray(c["tables"])
    kp = xp.asarray(c["kp"][1]).astype(f)
    if c["kps"] is not None:
        kp = kp * xp.asarray(c["kps"][1]).astype(f)[..., None]
        vp = xp.asarray(c["vp"][1]).astype(f) * xp.asarray(
            c["vps"][1]).astype(f)[..., None]
    elif c["vp"] is None:
        vp = kp[..., :c["latent_v"]]
    else:
        vp = xp.asarray(c["vp"][1]).astype(f)
    q = xp.asarray(c["q"]).astype(f)
    scale = 0.2 if kind == "latent" else q.shape[-1] ** -0.5
    rows = []
    for t, s in enumerate(c["token_slot"]):
        if s < 0:
            rows.append(xp.zeros(q.shape[1:3] + (vp.shape[-1],), f))
            continue
        p = c["pos"][s] + t - c["q_start"][s]
        k = xp.concatenate([kp[i] for i in tables[s]], axis=1)   # [Hkv,S,D]
        v = xp.concatenate([vp[i] for i in tables[s]], axis=1)
        kv = np.arange(k.shape[1])
        keep = (kv <= p) & ((kv > p - c["window"]) if c["window"] else True)
        sc = xp.einsum("hgd,hsd->hgs", q[t], k) * scale
        sc = xp.where(xp.asarray(keep)[None, None], sc, -1e30)
        if c["sink"] is not None:
            sc = xp.concatenate(
                [sc, xp.asarray(c["sink"]).astype(f)[..., None]], axis=-1)
        w = xp.exp(sc - sc.max(axis=-1, keepdims=True))
        w = (w / w.sum(axis=-1, keepdims=True))[..., :k.shape[1]]
        rows.append(xp.einsum("hgs,hsv->hgv", w, v))
    return xp.stack(rows)


def _sha(arrays):
    import hashlib
    h = hashlib.sha256()
    for a in arrays:
        h.update(np.ascontiguousarray(a, np.float32).tobytes())
    return h.hexdigest()[:16]


_PARENT_SHA = {
    "latent-g64-bq1": "e7ef120ee874dc4e",
    "int8-g64-bq1": "1acf30d90d9117ec",
    "window-g64-bq1": "c34f417ccae7ccea",
    "sink-g64-bq1": "864b0a077db0e94f",
    "lane_int8-g64-bq1": "efb7f7725f791294",
    "chain-g64-bq1": "d6ad34b2008f8661",
    "latent-g64-bq8": "b338345920633c23",
    "int8-g64-bq8": "6956fd4525abe60c",
    "window-g64-bq8": "eefdfc8c8ff72a56",
    "sink-g64-bq8": "d045395c9d0688e9",
    "lane_int8-g64-bq8": "38ee247a2b7d5670",
    "chain-g64-bq8": "f64a939f87f9e139",
    "latent-g7-bq8": "9b035ce01a176ec3",
    "int8-g7-bq8": "9033c7787f94fea0",
    "window-g7-bq8": "b8864bbf081bc980",
    "sink-g7-bq8": "547086f11149c8ad",
    "lane_int8-g7-bq8": "c0a0ae523443b92e",
    "chain-g7-bq8": "1512eddf476545ac",
    "latent-g6-bq1": "e6377c1b2fe1ac3d",
    "int8-g6-bq1": "f3e52cc1fd415bb1",
    "window-g6-bq1": "62b0cab1f1958d27",
    "sink-g6-bq1": "e75ff3546ab267c5",
    "lane_int8-g6-bq1": "d4fdddd5485a5044",
    "chain-g6-bq1": "fc4c505b73619e5e",
    "latent-g16-bq1": "e6e86a2bd0414b3c",
    "int8-g16-bq1": "fc79576f0ddd7e38",
    "window-g16-bq1": "13a27055d96fd176",
    "sink-g16-bq1": "6c0940ec4e3e8c6b",
    "lane_int8-g16-bq1": "ee553174c265a4d6",
    "chain-g16-bq1": "93c1dc55f44dd789",
}
_ORACLE_SHA = {
    "latent-g64-bq1": "21adb8ed4f39390c",
    "int8-g64-bq1": "0154a3543e9b8b50",
    "window-g64-bq1": "382bb82a3cd477c1",
    "sink-g64-bq1": "cb6e2c908fbe3f70",
    "lane_int8-g64-bq1": "0154a3543e9b8b50",
    "chain-g64-bq1": "0154a3543e9b8b50",
    "latent-g64-bq8": "ed148bd43a0dc517",
    "int8-g64-bq8": "b3ff8bab7415cdb2",
    "window-g64-bq8": "2894fa50c63ccb17",
    "sink-g64-bq8": "9a439db619913028",
    "lane_int8-g64-bq8": "b3ff8bab7415cdb2",
    "chain-g64-bq8": "b3ff8bab7415cdb2",
    "latent-g7-bq8": "a2f058faf72f79e0",
    "int8-g7-bq8": "f736f58fad063d25",
    "window-g7-bq8": "b1ee5e183ff3c654",
    "sink-g7-bq8": "569b1621c63a6aa9",
    "lane_int8-g7-bq8": "f736f58fad063d25",
    "chain-g7-bq8": "f736f58fad063d25",
    "latent-g6-bq1": "68b8e0e53ed794e9",
    "int8-g6-bq1": "9c9a4f20289d4940",
    "window-g6-bq1": "b37ed30a4b26aa56",
    "sink-g6-bq1": "86a62f2448a4328b",
    "lane_int8-g6-bq1": "9c9a4f20289d4940",
    "chain-g6-bq1": "9c9a4f20289d4940",
    "latent-g16-bq1": "7b0deb1cb9784b71",
    "int8-g16-bq1": "9ddf2066a2eb3eee",
    "window-g16-bq1": "13fe67f5eace0bae",
    "sink-g16-bq1": "0de5c090a8d6da6c",
    "lane_int8-g16-bq1": "9ddf2066a2eb3eee",
    "chain-g16-bq1": "9ddf2066a2eb3eee",
}


@pytest.mark.parametrize("kind", _MERGED_KINDS)
@pytest.mark.parametrize("g,block_q", _MERGED_SHAPES)
def test_merged_rows_block_is_the_parents_bytes(g, block_q, kind):
    import jax.numpy as jnp
    from arks_tpu.ops import paged_attention as pa
    c, got = _merged_run(pa, g, block_q, kind)
    want = _merged_oracle(c, kind, np)
    out = got[0]
    real = c["token_slot"] >= 0
    if kind in ("lane_int8", "chain"):
        # [S, Hkv, G, qmax, Dv] per lane -> the flat rows.
        flat = np.zeros(want.shape, np.float32)
        for t in np.flatnonzero(real):
            s = c["token_slot"][t]
            flat[t] = out[s, :, :, t - c["q_start"][s]]
        rows = np.arange(out.shape[3])[None] < c["q_len"][:, None]
        np.testing.assert_array_equal(
            out[~np.broadcast_to(rows[:, None, None, :, None], out.shape)],
            0.0)
        out = flat
    assert np.isfinite(out).all() and np.abs(out[real]).max() > 0
    np.testing.assert_array_equal(out[~real], 0.0)
    np.testing.assert_allclose(out[real], want[real], atol=3e-2, rtol=3e-2)
    key = f"{kind}-g{g}-bq{block_q}"
    control = _sha([np.asarray(_merged_oracle(c, kind, jnp))])
    if control == _ORACLE_SHA[key]:
        got[1:] = [_state_as_the_parent_held_it(x, g, block_q)
                   for x in got[1:]]
        assert _sha(got) == _PARENT_SHA[key], key
