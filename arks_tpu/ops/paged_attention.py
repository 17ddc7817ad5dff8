"""Paged KV cache: block-table Pallas kernels for decode.

The reference's runtimes all serve from a paged KV cache (vLLM paged
attention / SGLang radix-tree pages — the reference only writes their
command lines, /root/reference/internal/controller/
arksapplication_controller.go:941-1014).  This is the TPU formulation:

- **Pool layout** ``[L, N_pages, Hkv, P, D]`` (+ ``[L, N, Hkv, P]`` f32
  scales for int8): a page is one (layer, kv-head)-major stripe of P
  tokens, so a page read is a dense DMA — the same property the
  slot-contiguous cache has, minus the fixed per-slot reservation.
- **Block tables** ``[B, MaxP] int32`` ride scalar prefetch (SMEM): page j
  of slot b holds positions [j*P, (j+1)*P).  Sharing = two slots' tables
  pointing at the same page (prefix reuse with ZERO copies — the
  slot-contiguous design paid a host round-trip per reuse).
- **Attention**: same flash-decoding structure as
  ``pallas_attention.ragged_decode_attention`` (groups of ``block_b``
  slots, online softmax across the page grid axis), but a group's pages
  are scattered in the pool, so KV tiles are fetched with **manual
  double-buffered async DMAs** instead of BlockSpec pipelining: while page
  j is computed, page j+1's copies are in flight.  Per-slot copies skip
  pages past that slot's length.
- **Update**: same aligned read-modify-write trick as the slot kernels,
  with the row address indirected through the table.

The XLA oracle (`paged_gather_kv` + the existing masked attention) doubles
as the CPU-test reference and the fallback for unsupported shapes.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_NEG_INF = -1e30


# ---------------------------------------------------------------------------
# int4 KV pools: packed nibble pairs along the token axis
# ---------------------------------------------------------------------------
#
# An int4 pool packs token pairs (2t, 2t+1) into one int8 byte along the
# PAGE (token) axis: pool [L, N, Hkv, P//2, D] int8, low nibble = token 2t,
# high nibble = token 2t+1.  Packing along P (not D) keeps the 128-lane D
# axis dense, so every page DMA stays a full-lane stripe.  The per-token
# scale stripes keep their int8 shape [L, N, Hkv, P] — which is also how
# int4-ness is detected everywhere: pool page != scale page.  Values are
# quantized to [-7, 7] (scale = amax/7); sign restoration is two arithmetic
# shifts, fused on the page stream inside the kernels.


def is_int4_pool(k_pool: jnp.ndarray, k_scale: jnp.ndarray | None) -> bool:
    return k_scale is not None and k_pool.shape[3] != k_scale.shape[3]


def pool_page_tokens(k_pool: jnp.ndarray,
                     k_scale: jnp.ndarray | None) -> int:
    """Tokens per page — the position-arithmetic page size (2x the packed
    byte rows for int4 pools)."""
    return k_scale.shape[3] if is_int4_pool(k_pool, k_scale) \
        else k_pool.shape[3]


def pack_int4(vals: jnp.ndarray, axis: int) -> jnp.ndarray:
    """Pack int8 values in [-7, 7] into nibble pairs along ``axis`` (its
    extent must be even): out[.., t, ..] = lo(2t) | hi(2t+1) << 4."""
    axis = axis % vals.ndim
    ns = vals.shape[:axis] + (vals.shape[axis] // 2, 2) + vals.shape[axis + 1:]
    pr = vals.reshape(ns)
    lo = jax.lax.index_in_dim(pr, 0, axis + 1, keepdims=False)
    hi = jax.lax.index_in_dim(pr, 1, axis + 1, keepdims=False)
    return jnp.bitwise_or(jnp.bitwise_and(lo, jnp.int8(15)),
                          jnp.left_shift(hi, 4)).astype(jnp.int8)


def unpack_int4(packed: jnp.ndarray, axis: int) -> jnp.ndarray:
    """Inverse of :func:`pack_int4`: int8 nibble pairs -> int8 values in
    [-7, 7], doubling ``axis``.  Sign-extension is two arithmetic shifts."""
    axis = axis % packed.ndim
    lo = jnp.right_shift(jnp.left_shift(packed, 4), 4)
    hi = jnp.right_shift(packed, 4)
    out = jnp.stack([lo, hi], axis=axis + 1)
    shape = list(packed.shape)
    shape[axis] *= 2
    return out.reshape(shape)


def unpack_int4_pool(pool: jnp.ndarray) -> jnp.ndarray:
    """[L, N, Hkv, P//2, D] packed -> [L, N, Hkv, P, D] int8 — the XLA
    oracle's view (every int8 oracle then applies unchanged)."""
    return unpack_int4(pool, axis=3)


# ---------------------------------------------------------------------------
# Mixed-grid planning: block sizes, q padding
# ---------------------------------------------------------------------------


def pool_kv_name(k_pool: jnp.ndarray, k_scale: jnp.ndarray | None) -> str:
    """The pool's KV width as the tune table's signatures spell it."""
    if k_scale is None:
        return str(k_pool.dtype)
    return "int4" if is_int4_pool(k_pool, k_scale) else "int8"


def mixed_grid_plan(qmax: int, *, hkv: int, g: int, d: int, page: int,
                    kv: str, block_q: int | None = None,
                    dma_depth: int | None = None,
                    head_group: int | None = None,
                    lanes: int | None = None) -> dict:
    """Resolve the mixed kernel's static launch parameters — ONE place, so
    the kernel wrapper and the engine's grid-step counters can never
    disagree on what actually launches.

    ``qmax`` is the widest query span one lane can have: the per-lane
    block's Q for :func:`paged_mixed_attention`, ``t_flat - lanes + 1``
    for the flat batch of :func:`paged_mixed_attention_flat`.

    block_q defaults to the autotune table entry for this shape signature
    (arks_tpu.ops.autotune, pure lookup — never sweeps here) and falls
    back to :func:`_default_block_q`, a rule over ``qmax`` and ``lanes``.  Non-divisible qmax is handled by
    PADDING the q axis to the block (qpad), not by shrinking block_q to a
    divisor — the old ``while qmax % block_q: block_q -= 1`` fallback
    degraded to tiny odd blocks (qmax=33 -> block_q=11).

    head_group is the number of KV heads each work item streams (a
    divisor of hkv; hkv = no grouping, the default).  Grouping shrinks a
    single item's KV and accumulator VMEM footprint by hkv/head_group,
    which is what lets a tuned entry raise block_q — fewer q-blocks means
    each causal page prefix is re-streamed fewer times, which is where
    the GQA bytes-moved win actually comes from.  Invalid divisors fall
    back to hkv rather than raising so stale tune tables can never break a
    launch.

    With ``lanes`` (the flat batch's lane count) the plan also carries the
    block-compacted query layout's size: ``nb``, the static bound on real
    (lane, q_block) pairs — every lane has at most ceil(q_len / block_q)
    blocks and the lanes share ``lanes + qmax - 1`` rows, so
    ``nb = lanes + ceil((qmax - 1) / block_q)`` — and ``q_rows``, the
    query rows one dispatch lays out for the kernel (``nb * block_q``)."""
    from arks_tpu.ops import autotune

    qmax = max(int(qmax), 1)
    tuned: dict = {}
    if block_q is None or dma_depth is None or head_group is None:
        tuned = autotune.lookup("paged_mixed", autotune.mixed_signature(
            hkv=hkv, g=g, d=d, page=page, qmax=qmax, kv=kv)) or {}
    if head_group is None:
        head_group = int(tuned.get("head_group", 0)) or hkv
    head_group = int(head_group)
    if head_group <= 0 or hkv % head_group:
        head_group = hkv
    if block_q is None:
        block_q = int(tuned.get("block_q", 0)) or _default_block_q(
            qmax, lanes, g)
    block_q = max(1, min(int(block_q), qmax))
    if dma_depth is None:
        dma_depth = int(tuned.get("dma_depth", 0)) or 2
    dma_depth = max(2, int(dma_depth))
    qpad = -(-qmax // block_q) * block_q
    plan = dict(block_q=block_q, qpad=qpad, num_qb=qpad // block_q,
                dma_depth=dma_depth, head_group=head_group)
    if lanes is not None:
        nb = int(lanes) + -(-(qmax - 1) // block_q)
        plan.update(nb=nb, q_rows=nb * block_q)
    return plan


def _default_block_q(qmax: int, lanes: int | None, g: int = 1) -> int:
    """Queries per work item where the tune table has no entry.

    A per-lane block (``lanes`` None) keeps 32.  The flat batch's blocks
    follow its rows per lane, ``(lanes + qmax - 1) / lanes``, in sublane
    tiles of 8 up to 32: every lane pays for one whole block, and a decode
    lane fills one row of it, so where the lanes outnumber the chunk's
    blocks (192 slots + 256 rows: 2.3 rows a lane) 8 halves the kernel's
    time and quarters the layout against 32; where one chunk is most of
    the batch (8 slots + 256 rows) 32 re-streams its causal prefix four
    times less often.  Measured on a v5e at qwen2.5-7b and mixtral widths
    (PERF.md, PR 25): 8 beat 4, 16 and 32 at 192 and 64 lanes, 32 beat 16
    and 8 at 8 lanes.

    A work item holds ``g x block_q`` query rows with their softmax state
    in VMEM, so the flat batch's block is also held to 512 such rows, never
    under one sublane tile of 8: it binds from 17 queries a KV head up (a
    latent pool's one row serves all 64 heads: blocks of 8)."""
    if lanes is None:
        return min(qmax, 32)
    rows_per_lane = -(-(lanes + qmax - 1) // lanes)
    return min(qmax, 32, -(-rows_per_lane // 8) * 8, max(8, 512 // g))


def build_mixed_work_list(pos_start: jnp.ndarray, q_len: jnp.ndarray, *,
                          page: int, block_q: int, num_qb: int,
                          max_pages: int, head_groups: int = 1,
                          page_lo: jnp.ndarray | None = None,
                          page_hi: jnp.ndarray | None = None,
                          n_items: int | None = None, window: int = 0):
    """Scalar-prefetch work list for the ragged mixed grid: one item per
    REAL (sequence, head_group, q_block), compacted to the front of a
    fixed-length [S*head_groups*num_qb] list (Pallas grids are static; the
    page axis is what actually scales with work).  Returns
    (seq, hg, qb, plo, pages, blk), each int32 [S*head_groups*num_qb]
    (or its first ``n_items`` entries: a caller whose static bound on the
    real items is shorter than the full list launches only that many):

    - real items: pages = ceil(causal kv end / page) clamped to the table
      width — that sequence's OWN page count, not the pool-wide max;
      plo is the first page the item streams (0 unless span-bounded);
    - padding items (q_len=0 lanes, blocks past a lane's q_len): pages = 0
      and (seq, hg, qb) aliased to the LAST real item, so their grid step
      re-flushes an already-written output block and computes nothing.

    - blk is the rank of the item's (seq, q_block) pair among the real
      pairs, lane-major then block (``base[seq] + qb`` with ``base`` the
      exclusive running sum of the lanes' block counts): the block the
      pair owns in the block-compacted query layout
      (:func:`mixed_block_layout`).  Every head group of a pair shares it;
      padding items carry the last real item's.

    head_groups replicates every (seq, q_block) item per KV head group so
    each grid step streams only its hkv/head_groups slice of the pool's
    head axis.  Item order is seq-major, then head group, then q_block —
    with head_groups=1 the (seq, qb, pages) columns are bit-for-bit the
    PR 11 layout (pinned by test_build_mixed_work_list_compaction).

    page_lo / page_hi ([S] int32, optional) bound each sequence's page
    span to [page_lo[s], min(pages, page_hi[s])) — the windowed-residency
    hook: a caller attending only the resident window clamps the span
    here and carries the online-softmax state across spans.

    ``window`` > 0 (a window layer: query at position p attends keys in
    ``(p - window, p]``) starts each item at the page that holds the
    lowest key its FIRST query attends, ``pos_start + qb * block_q -
    window + 1``: the pages before it are never streamed (the kernel masks
    the keys below each query's own bound inside the pages it does
    stream).

    Built from fixed-shape jnp ops only: the device-state pipelined
    dispatches derive q_len on device (zero-host-sync), so the list must
    be traceable — no host round trip."""
    s = q_len.shape[0]
    n = s * head_groups * num_qb if n_items is None else n_items
    qlen = q_len.astype(jnp.int32)
    # Real items, counted per lane and ranked without a sort: lane s owns
    # head_groups x nblk[s] consecutive entries, head group major.
    nblk, base = _lane_blocks(qlen, block_q, num_qb)
    ends = (base + nblk) * head_groups
    n_real = ends[-1]
    i = jnp.arange(n, dtype=jnp.int32)
    pad = i >= n_real
    # Padding entries alias the last real item (item 0 of lane 0 where
    # there is none).
    at = jnp.minimum(i, jnp.maximum(n_real - 1, 0))
    seq = jnp.where(n_real > 0, _owner(ends, at), 0)
    within = at - base[seq] * head_groups
    per = jnp.maximum(nblk[seq], 1)
    hg, qb = within // per, within % per
    kv_end = pos_start.astype(jnp.int32)[seq] + jnp.minimum(
        (qb + 1) * block_q, qlen[seq])
    pages = jnp.minimum(-(-kv_end // page), max_pages)
    if page_hi is not None:
        pages = jnp.minimum(pages, page_hi.astype(jnp.int32)[seq])
    plo = jnp.zeros_like(pages) if page_lo is None else jnp.minimum(
        page_lo.astype(jnp.int32)[seq], pages)
    if window:
        first_key = pos_start.astype(jnp.int32)[seq] + qb * block_q \
            - (window - 1)
        plo = jnp.maximum(plo, jnp.minimum(
            jnp.maximum(first_key, 0) // page, pages))
    pages = jnp.where(pad, 0, pages)
    plo = jnp.where(pad, 0, plo)
    return seq, hg, qb, plo, pages, base[seq] + qb


def _owner(ends: jnp.ndarray, rank: jnp.ndarray) -> jnp.ndarray:
    """Lane that owns each rank, where lane s owns ranks [ends[s-1],
    ends[s]): the number of lanes that end at or before it (a searchsorted
    over the S lanes as one compare), held inside the lanes for ranks past
    the last."""
    return jnp.minimum(
        jnp.sum((ends[None, :] <= rank[:, None]).astype(jnp.int32), axis=1),
        ends.shape[0] - 1)


def _lane_blocks(q_len: jnp.ndarray, block_q: int, num_qb: int | None = None):
    """(nblk, base), each [S] int32: the lanes' block counts
    ceil(q_len / block_q) (at most ``num_qb``) and their exclusive running
    sum, the rank of each lane's first block among the real blocks."""
    nblk = -(-q_len.astype(jnp.int32) // block_q)
    if num_qb is not None:
        nblk = jnp.minimum(nblk, num_qb)
    return nblk, jnp.cumsum(nblk) - nblk


def mixed_block_layout(token_slot: jnp.ndarray, q_start: jnp.ndarray,
                       q_len: jnp.ndarray, *, block_q: int, nb: int):
    """Row indices of the block-compacted query layout: the ``nb`` blocks
    of ``block_q`` rows the ragged kernel reads its queries from and
    writes its output to, one block per real (lane, q_block) pair in
    lane-major order.  Returns (base [S], src_rows [nb * block_q],
    out_rows [T]), all int32:

    - ``src_rows[j * block_q + r]`` is the flat row that fills row r of
      block j: ``q_start[lane] + qb * block_q + r`` for the pair of rank
      j, clipped into the flat batch (rows past the lane's q_len, and
      blocks past the real ones, read some other real row: the kernel
      computes on them and nobody reads the result);
    - ``out_rows[t]`` is where flat row t of lane ``token_slot[t]`` at
      offset ``o = t - q_start[lane]`` lives: block ``base[lane] +
      o // block_q``, row ``o % block_q``.  Padding rows (token_slot < 0)
      get some in-range row; the caller zeroes them.

    Fixed-shape jnp ops only, like the work list: the pipelined programs
    derive q_len on the device and must not sync."""
    t_flat = token_slot.shape[0]
    qs = q_start.astype(jnp.int32)
    nblk, base = _lane_blocks(q_len, block_q)
    ends = base + nblk
    j = jnp.arange(nb, dtype=jnp.int32)
    lane = _owner(ends, j)
    row0 = qs[lane] + (j - base[lane]) * block_q
    src = row0[:, None] + jnp.arange(block_q, dtype=jnp.int32)[None, :]
    src = jnp.clip(src, 0, t_flat - 1).reshape(-1)
    slot = jnp.maximum(token_slot.astype(jnp.int32), 0)
    off = jnp.arange(t_flat, dtype=jnp.int32) - qs[slot]
    out = (base[slot] + off // block_q) * block_q + off % block_q
    return base, src, jnp.clip(out, 0, nb * block_q - 1)


# ---------------------------------------------------------------------------
# XLA oracle / fallback
# ---------------------------------------------------------------------------


def paged_gather_kv(pool: jnp.ndarray, tables: jnp.ndarray,
                    layer) -> jnp.ndarray:
    """Materialize slot-contiguous [B, Hkv, S, D] (or [B, Hkv, S] for
    scales) from the paged pool — the oracle path; the Pallas kernel never
    does this."""
    pool_l = jax.lax.dynamic_index_in_dim(pool, layer, 0, keepdims=False)
    g = jnp.take(pool_l, tables, axis=0)  # [B, MaxP, Hkv, P, ...]
    if g.ndim == 5:
        b, mp, hkv, p, d = g.shape
        return jnp.transpose(g, (0, 2, 1, 3, 4)).reshape(b, hkv, mp * p, d)
    b, mp, hkv, p = g.shape
    return jnp.transpose(g, (0, 2, 1, 3)).reshape(b, hkv, mp * p)


def paged_update_xla(k_pool, v_pool, k_scale, v_scale, k_new, v_new,
                     write_idx, tables, layer):
    """Scatter one KV row per slot through the block table (oracle path —
    lowers to a full-pool rewrite in XLA, which is why the Pallas kernel
    exists).  int4 pools (detected by pool page != scale page) get a
    nibble merge at the target byte; all position math stays in TOKEN
    units."""
    int4 = is_int4_pool(k_pool, k_scale)
    p = pool_page_tokens(k_pool, k_scale)
    n = k_pool.shape[1]
    b, hkv, d = k_new.shape
    # write_idx beyond the table's coverage = inactive slot: route the
    # scatter to an out-of-bounds page so jit drops it (the Pallas kernel
    # guards the same way) — take_along_axis would otherwise CLAMP to the
    # last page and corrupt it.
    oob = write_idx >= tables.shape[1] * p
    safe_idx = jnp.where(oob, 0, write_idx)
    page = jnp.take_along_axis(
        tables, (safe_idx // p)[:, None], axis=1)[:, 0]    # [B]
    page = jnp.where(oob, n, page)
    off = safe_idx % p
    l_idx = jnp.full((b,), layer, jnp.int32)
    h_idx = jnp.arange(hkv)[None, :]
    quantized = k_scale is not None
    if quantized:
        from arks_tpu.ops.pallas_attention import quantize_kv
        kq, ks = quantize_kv(k_new, qmax=7 if int4 else 127)
        vq, vs = quantize_kv(v_new, qmax=7 if int4 else 127)
        if int4:
            # Two parity passes: positions 2t and 2t+1 share a byte, so a
            # single scatter of whole merged bytes would let pair-mates in
            # the same dispatch clobber each other's nibble.  Within one
            # parity every target byte is unique (distinct positions).
            boff = (off // 2)[:, None]
            for parity, vals_k, vals_v in ((0, kq, vq), (1, kq, vq)):
                sel = (off % 2) == parity
                pg_sel = jnp.where(sel, page, n)[:, None]
                oldk = k_pool[l_idx[:, None], page[:, None], h_idx, boff]
                oldv = v_pool[l_idx[:, None], page[:, None], h_idx, boff]
                if parity == 0:
                    mk = (oldk & -16) | (vals_k & 15)
                    mv = (oldv & -16) | (vals_v & 15)
                else:
                    mk = (oldk & 15) | (vals_k << 4)
                    mv = (oldv & 15) | (vals_v << 4)
                k_pool = k_pool.at[l_idx[:, None], pg_sel, h_idx, boff].set(mk)
                v_pool = v_pool.at[l_idx[:, None], pg_sel, h_idx, boff].set(mv)
        else:
            k_pool = k_pool.at[l_idx[:, None], page[:, None], h_idx,
                               off[:, None]].set(kq)
            v_pool = v_pool.at[l_idx[:, None], page[:, None], h_idx,
                               off[:, None]].set(vq)
        k_scale = k_scale.at[l_idx[:, None], page[:, None], h_idx,
                             off[:, None]].set(ks)
        v_scale = v_scale.at[l_idx[:, None], page[:, None], h_idx,
                             off[:, None]].set(vs)
    else:
        k_pool = k_pool.at[l_idx[:, None], page[:, None], h_idx,
                           off[:, None]].set(k_new.astype(k_pool.dtype))
        v_pool = v_pool.at[l_idx[:, None], page[:, None], h_idx,
                           off[:, None]].set(v_new.astype(v_pool.dtype))
    return k_pool, v_pool, k_scale, v_scale


def paged_update_block_xla(k_pool, v_pool, k_scale, v_scale, k_new, v_new,
                           positions, tables, layer):
    """Scatter a K-row KV BLOCK per slot (the speculative-verify write)
    through the block table in one gather+scatter.  ``k_new``/``v_new`` are
    [B, K, Hkv, D]; row k of slot b lands at position ``positions[b, k]``
    (which may cross a page boundary mid-block).  Positions at/past the
    table's coverage are dropped — the inactive-slot sentinel, same
    out-of-bounds-page guard as ``paged_update_xla``."""
    int4 = is_int4_pool(k_pool, k_scale)
    p = pool_page_tokens(k_pool, k_scale)
    n = k_pool.shape[1]
    b, kk, hkv, d = k_new.shape
    cover = tables.shape[1] * p
    oob = positions >= cover                              # [B, K]
    safe = jnp.where(oob, 0, positions)
    page = jnp.take_along_axis(tables, safe // p, axis=1)  # [B, K]
    page = jnp.where(oob, n, page)
    off = safe % p
    l_idx = jnp.full((b, kk, hkv), layer, jnp.int32)
    pg = page[:, :, None]
    of = off[:, :, None]
    h_idx = jnp.arange(hkv)[None, None, :]
    quantized = k_scale is not None
    if quantized:
        from arks_tpu.ops.pallas_attention import quantize_kv
        kq, ksn = quantize_kv(k_new, qmax=7 if int4 else 127)
        vq, vsn = quantize_kv(v_new, qmax=7 if int4 else 127)
        if int4:
            # Same two-parity nibble merge as paged_update_xla: a verify
            # block writes consecutive positions, so pair-mates (2t, 2t+1)
            # in one dispatch target the SAME byte.
            bof = (off // 2)[:, :, None]
            for parity in (0, 1):
                sel = (off % 2) == parity
                pg_sel = jnp.where(sel, page, n)[:, :, None]
                oldk = k_pool[l_idx, pg, h_idx, bof]
                oldv = v_pool[l_idx, pg, h_idx, bof]
                if parity == 0:
                    mk = (oldk & -16) | (kq & 15)
                    mv = (oldv & -16) | (vq & 15)
                else:
                    mk = (oldk & 15) | (kq << 4)
                    mv = (oldv & 15) | (vq << 4)
                k_pool = k_pool.at[l_idx, pg_sel, h_idx, bof].set(mk)
                v_pool = v_pool.at[l_idx, pg_sel, h_idx, bof].set(mv)
        else:
            k_pool = k_pool.at[l_idx, pg, h_idx, of].set(kq)
            v_pool = v_pool.at[l_idx, pg, h_idx, of].set(vq)
        k_scale = k_scale.at[l_idx, pg, h_idx, of].set(ksn)
        v_scale = v_scale.at[l_idx, pg, h_idx, of].set(vsn)
    else:
        k_pool = k_pool.at[l_idx, pg, h_idx, of].set(
            k_new.astype(k_pool.dtype))
        v_pool = v_pool.at[l_idx, pg, h_idx, of].set(
            v_new.astype(v_pool.dtype))
    return k_pool, v_pool, k_scale, v_scale


# ---------------------------------------------------------------------------
# Paged ragged decode attention (manual double-buffered DMA)
# ---------------------------------------------------------------------------


def _paged_attn_kernel(layer_ref, glens_ref, tables_ref, slens_ref, lens_ref,
                       q_ref, kpool, vpool, *rest,
                       block_b: int, page: int, scale: float,
                       quantized: bool):
    if quantized:
        kspool, vspool, o_ref, kbuf, vbuf, ksbuf, vsbuf, m_ref, l_ref, \
            acc_ref, sem = rest
    else:
        o_ref, kbuf, vbuf, m_ref, l_ref, acc_ref, sem = rest
        kspool = vspool = ksbuf = vsbuf = None
    bi = pl.program_id(0)
    si = pl.program_id(1)
    num_pages = pl.num_programs(1)
    lyr = layer_ref[0]

    def start_copies(page_i, buf):
        # One DMA per (slot, k/v[, scales]): the group's pages are scattered
        # in the pool, so there is no single dense tile to fetch.  Copies
        # for slots already past their length are skipped — but their
        # V-side buffer rows are ZEROED: uninitialized VMEM can hold NaN
        # bits, and the flash accumulation computes p@v where masked
        # positions contribute 0 * v — 0 * NaN would poison the output.
        # (K garbage is harmless: its scores are replaced after the dot.)
        for j in range(block_b):
            b = bi * block_b + j
            skip = page_i * page >= slens_ref[b]

            @pl.when(jnp.logical_not(skip))
            def _():
                pg = tables_ref[b, page_i]
                pltpu.make_async_copy(
                    kpool.at[lyr, pg], kbuf.at[buf, j],
                    sem.at[0, buf, j]).start()
                pltpu.make_async_copy(
                    vpool.at[lyr, pg], vbuf.at[buf, j],
                    sem.at[1, buf, j]).start()
                if quantized:
                    pltpu.make_async_copy(
                        kspool.at[lyr, pg], ksbuf.at[buf, j],
                        sem.at[2, buf, j]).start()
                    pltpu.make_async_copy(
                        vspool.at[lyr, pg], vsbuf.at[buf, j],
                        sem.at[3, buf, j]).start()

            @pl.when(skip)
            def _():
                vbuf[buf, j] = jnp.zeros_like(vbuf[buf, j])
                if quantized:
                    vsbuf[buf, j] = jnp.zeros_like(vsbuf[buf, j])

    def wait_copies(page_i, buf):
        for j in range(block_b):
            b = bi * block_b + j

            @pl.when(page_i * page < slens_ref[b])
            def _():
                pltpu.make_async_copy(kpool.at[lyr, 0], kbuf.at[buf, j],
                                      sem.at[0, buf, j]).wait()
                pltpu.make_async_copy(vpool.at[lyr, 0], vbuf.at[buf, j],
                                      sem.at[1, buf, j]).wait()
                if quantized:
                    pltpu.make_async_copy(
                        kspool.at[lyr, 0], ksbuf.at[buf, j],
                        sem.at[2, buf, j]).wait()
                    pltpu.make_async_copy(
                        vspool.at[lyr, 0], vsbuf.at[buf, j],
                        sem.at[3, buf, j]).wait()

    @pl.when(si == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)
        start_copies(0, 0)

    valid = si * page < glens_ref[bi]

    # Double buffering: kick page si+1's copies before computing page si.
    @pl.when(valid & ((si + 1) * page < glens_ref[bi]))
    def _prefetch():
        start_copies(si + 1, (si + 1) % 2)

    @pl.when(valid)
    def _block():
        buf = si % 2
        wait_copies(si, buf)
        bb, hkv, g, d = q_ref.shape
        q = q_ref[:].reshape(bb * hkv, g, d)
        k = kbuf[buf].reshape(bb * hkv, page, d).astype(q.dtype)
        v = vbuf[buf].reshape(bb * hkv, page, d).astype(q.dtype)
        scores = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale
        scores = scores.reshape(bb, hkv, g, page)
        if quantized:
            scores = scores * ksbuf[buf].reshape(bb, hkv, 1, page)
        pos = si * page + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 3)
        lens = lens_ref[0]  # [block_b, 1]
        scores = jnp.where(pos < lens[:, None, None, :], scores, _NEG_INF)

        m_prev = m_ref[:]
        l_prev = l_ref[:]
        m_curr = jnp.max(scores, axis=3, keepdims=True)
        m_next = jnp.maximum(m_prev, jnp.broadcast_to(m_curr, m_prev.shape))
        correction = jnp.exp(m_prev - m_next)
        p = jnp.exp(scores - m_next[..., :1])
        l_curr = jnp.sum(p, axis=3, keepdims=True)
        l_next = l_prev * correction + jnp.broadcast_to(l_curr, l_prev.shape)
        if quantized:
            p = p * vsbuf[buf].reshape(bb, hkv, 1, page)
        pv = jax.lax.dot_general(
            p.astype(v.dtype).reshape(bb * hkv, g, page), v,
            (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32).reshape(bb, hkv, g, d)
        acc_ref[:] = acc_ref[:] * correction[..., :1] + pv
        m_ref[:] = m_next
        l_ref[:] = l_next

    @pl.when(si == num_pages - 1)
    def _finish():
        out = acc_ref[:] / (l_ref[..., :1] + 1e-9)
        o_ref[:] = out.astype(o_ref.dtype)


def _pick_block_b(b: int, target: int) -> int:
    best = 1
    for cand in range(1, min(b, target) + 1):
        if b % cand == 0:
            best = cand
    return best


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def paged_decode_attention(
    q: jnp.ndarray,        # [B, Hkv, G, D] — one query token per slot
    k_pool: jnp.ndarray,   # [L, N, Hkv, P, D] page pool
    v_pool: jnp.ndarray,
    tables: jnp.ndarray,   # [B, MaxP] int32 block tables
    lengths: jnp.ndarray,  # [B] int32 valid positions per slot
    layer,                 # int32
    k_scale: jnp.ndarray | None = None,  # [L, N, Hkv, P] f32 (int8 pools)
    v_scale: jnp.ndarray | None = None,
    block_b: int | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """[B, Hkv, G, D] attention over each slot's block-table pages."""
    b, hkv, g, d = q.shape
    page = k_pool.shape[3]
    max_pages = tables.shape[1]
    quantized = k_scale is not None
    if is_int4_pool(k_pool, k_scale):
        raise ValueError(
            "int4 pools route through the mixed kernel (fused nibble "
            "dequant) or the XLA oracle; the standalone decode kernel is "
            "bf16/int8 only")
    if block_b is None:
        from arks_tpu.ops import autotune
        kvd = "int8" if quantized else str(k_pool.dtype)
        tuned = autotune.lookup("paged_decode", autotune.decode_signature(
            b=b, hkv=hkv, g=g, d=d, page=page, kv=kvd)) or {}
        # Heuristic fallback (VMEM budget: double-buffered k+v page tiles
        # must fit beside the accumulators; int8 pages are half the bytes
        # of bf16) — exactly the pre-autotune behavior when no table entry
        # exists for this signature.
        block_b = int(tuned.get("block_b", 0)) or (
            16 if k_pool.dtype == jnp.int8 else 8)
    block_b = _pick_block_b(b, block_b)
    num_groups = b // block_b
    scale = 1.0 / (d ** 0.5)
    lengths = lengths.astype(jnp.int32)
    group_lens = jnp.max(lengths.reshape(num_groups, block_b), axis=1)
    layer_arr = jnp.asarray(layer, jnp.int32).reshape(1)

    def q_map(bi, si, *prefetch):
        del si, prefetch
        return (bi, 0, 0, 0)

    def lens_map(bi, si, *prefetch):
        del si, prefetch
        return (bi, 0, 0)

    in_specs = [
        pl.BlockSpec((1, block_b, 1), lens_map),
        pl.BlockSpec((block_b, hkv, g, d), q_map),
        pl.BlockSpec(memory_space=pl.ANY),   # k pool (manual DMA)
        pl.BlockSpec(memory_space=pl.ANY),   # v pool
    ]
    inputs = [layer_arr, group_lens, tables.astype(jnp.int32),
              lengths, lengths.reshape(num_groups, block_b)[..., None],
              q, k_pool, v_pool]
    scratch = [
        pltpu.VMEM((2, block_b, hkv, page, d), k_pool.dtype),  # kbuf
        pltpu.VMEM((2, block_b, hkv, page, d), v_pool.dtype),  # vbuf
    ]
    n_sem = 2
    if quantized:
        in_specs += [pl.BlockSpec(memory_space=pl.ANY)] * 2
        inputs += [k_scale, v_scale]
        scratch += [pltpu.VMEM((2, block_b, hkv, page), jnp.float32),
                    pltpu.VMEM((2, block_b, hkv, page), jnp.float32)]
        n_sem = 4
    scratch += [
        pltpu.VMEM((block_b, hkv, g, 128), jnp.float32),  # m
        pltpu.VMEM((block_b, hkv, g, 128), jnp.float32),  # l
        pltpu.VMEM((block_b, hkv, g, d), jnp.float32),    # acc
        pltpu.SemaphoreType.DMA((n_sem, 2, block_b)),
    ]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,  # layer, group_lens, tables, slot lens
        grid=(num_groups, max_pages),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_b, hkv, g, d), q_map),
        scratch_shapes=scratch,
    )
    kernel = functools.partial(_paged_attn_kernel, block_b=block_b,
                               page=page, scale=scale, quantized=quantized)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="paged_decode_attention",
    )(*inputs)


# ---------------------------------------------------------------------------
# Ragged mixed-query paged attention (prefill chunks + decode in one grid)
# ---------------------------------------------------------------------------


def _unpack_int4_tile(w: jnp.ndarray) -> jnp.ndarray:
    """In-kernel nibble dequant, fused on the page stream: an int4 page
    tile [Hkv, page//2, D] of packed pairs -> [Hkv, page, D] int8 values.
    Sign extension is two arithmetic shifts; the interleave restores token
    order (low nibble = even token, high = odd).  The shifts run on int32:
    Mosaic legalises no 8-bit vector shift on a v5e (``arith.shli`` on
    ``vector<..xi8>`` is refused)."""
    w = w.astype(jnp.int32)
    lo = jnp.right_shift(jnp.left_shift(w, 28), 28)
    hi = jnp.right_shift(w, 4)
    hkv, p2, d = w.shape
    return jnp.stack([lo, hi], axis=2).reshape(hkv, p2 * 2, d)


def _group_heads(stripe: jnp.ndarray, h0, head_group: int) -> jnp.ndarray:
    """Rows [h0, h0 + head_group) of a [Hkv, P] scale stripe held in VMEM.
    ``h0`` is None (ungrouped: the whole stripe) or a traced multiple of
    ``head_group``.  A dynamic sublane slice narrower than the f32 tile is
    refused by Mosaic ("cannot statically prove that index ... is a
    multiple of 4"), so the group is picked by select over the Hkv /
    head_group static slices."""
    if h0 is None:
        return stripe
    out = stripe[:head_group]
    for lo in range(head_group, stripe.shape[0], head_group):
        out = jnp.where(h0 == lo, stripe[lo:lo + head_group], out)
    return out


def _lanes(x: jnp.ndarray, n: int) -> jnp.ndarray:
    """A softmax-state value ``[.., 128]``, the same number in every lane
    (the running maximum, the sum, the correction: that is how the kernel
    keeps them), as ``[.., n]``.  Tiled along the lanes where ``n`` is
    whole tiles: the value's own vregs read again.  Taking lane 0 and
    broadcasting it, the same numbers, is a cross-lane move a vreg a
    visit, and was a seventh of the latent launch's time and two fifths of
    the GQA launches' (PERF.md, PR 51)."""
    if n % x.shape[-1]:
        return jnp.broadcast_to(x[..., :1], x.shape[:-1] + (n,))
    return jnp.concatenate([x] * (n // x.shape[-1]), axis=-1)


def _mixed_softmax_block(q_ref, kbuf, vbuf, ksbuf, vsbuf, m_ref, l_ref,
                         acc_ref, buf, si, pos0, q_lo, *, page, block_q,
                         scale, quantized, int4, h0=None, window=0):
    """One page of online-softmax accumulation, the compute body of the
    ragged mixed kernel.  ``q_ref`` is the item's block as it arrived,
    ``[1, Hkv, G x block_q, D]`` with the rows already merged (g-major):
    nothing is laid out again here, once a page.  ``h0`` (grouped items
    only) is the first KV head of the item's group inside the scale
    buffers, which always hold the page's whole head stripe.  ``window`` >
    0 also masks the keys at or below ``qpos - window``."""
    hkv = q_ref.shape[1]
    q = q_ref[0]                           # [Hkv, G*BQ, D]
    kt = kbuf[buf]
    if vbuf is None:
        # A latent page: the values are the row's first lanes, as wide as
        # the accumulator (the same tile, read from HBM once, used twice).
        k = kt.astype(q.dtype)             # [1, page, R]
        v = k[..., :acc_ref.shape[-1]]
    else:
        vt = vbuf[buf]
        if int4:
            kt = _unpack_int4_tile(kt)
            vt = _unpack_int4_tile(vt)
        k = kt.astype(q.dtype)             # [Hkv, page, D]
        v = vt.astype(q.dtype)
    scores = jax.lax.dot_general(
        q, k, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32) * scale   # [Hkv, G*BQ, page]
    if quantized:
        scores = scores * _group_heads(ksbuf[buf], h0, hkv)[:, None, :]
    # Row r of the G*BQ axis is query index r % BQ (g-major layout).
    row = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    qpos = pos0 + q_lo + row % block_q
    kvpos = si * page + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 2)
    keep = kvpos <= qpos
    if window:
        keep = keep & (kvpos > qpos - window)
    scores = jnp.where(keep, scores, _NEG_INF)

    m_prev = m_ref[:]
    l_prev = l_ref[:]
    m_curr = jnp.max(scores, axis=2, keepdims=True)
    m_next = jnp.maximum(m_prev, jnp.broadcast_to(m_curr, m_prev.shape))
    correction = jnp.exp(m_prev - m_next)
    p = jnp.exp(scores - _lanes(m_next, scores.shape[-1]))
    l_curr = jnp.sum(p, axis=2, keepdims=True)
    l_next = l_prev * correction + jnp.broadcast_to(l_curr, l_prev.shape)
    if quantized:
        p = p * _group_heads(vsbuf[buf], h0, hkv)[:, None, :]
    pv = jax.lax.dot_general(
        p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)           # [Hkv, G*BQ, D]
    acc_ref[:] = acc_ref[:] * _lanes(correction, pv.shape[-1]) + pv
    m_ref[:] = m_next
    l_ref[:] = l_next


def _paged_mixed_ragged_kernel(layer_ref, tables_ref, pos_start_ref,
                               wl_seq_ref, wl_hg_ref, wl_qb_ref,
                               wl_plo_ref, wl_pages_ref, wl_blk_ref,
                               q_ref, kpool, *rest,
                               page: int, block_q: int, scale: float,
                               quantized: bool, int4: bool, depth: int,
                               head_group: int, carry: bool,
                               emit_state: bool, latent: bool = False,
                               window: int = 0, sink: bool = False):
    """RAGGED work-list grid: one grid step per (sequence, head_group,
    q_block) work item, the page loop INSIDE the kernel bounded by that
    item's own causal page span [wl_plo, wl_pages).  q_len=0 lanes and
    q-blocks past a lane's q_len never become items, so grid length
    tracks real work — a 3-active-of-64-slots batch costs 3 items'
    pages, not 64*num_qb*max_pages masked steps.  Items are compacted to
    the front of the fixed-length list by :func:`build_mixed_work_list`;
    padding items carry wl_pages=0 and alias the last real item's output
    block, so their only cost is re-flushing an already-written block.

    GQA head grouping: each item DMAs only its ``head_group``-head slice
    of the pool's head axis (wl_hg picks which), so per-item KV and
    accumulator VMEM shrink by hkv/head_group — the headroom a tuned
    entry spends on a larger block_q, which is what actually cuts the
    re-streamed causal-prefix bytes.  head_group == hkv with one group
    reduces exactly to the ungrouped kernel.

    Carried state: with ``carry`` the online-softmax state (m, l, acc)
    initializes from BlockSpec'd f32 inputs instead of (-inf, 0, 0); with
    ``emit_state`` the RAW state is written out instead of the
    normalized output.  Chaining spans through f32 state is bitwise
    exact — the per-page update sequence is identical and the final
    acc/(l+eps) division happens exactly once, on the last span.

    DMAs are ``depth``-way multi-buffered (depth=2 is double buffering;
    the accumulation order is identical for any depth, so tuned depths
    preserve byte identity).

    ``latent``: the pool is ONE array of latent rows (Hkv = 1, every head
    of the model a query row of the one group): there is no value pool,
    one copy a page, and the values are the leading lanes of the key tile
    (as many as the output is wide).

    ``window`` > 0: a window layer.  The work list's ``wl_plo`` already
    starts an item at the first page its window meets; the softmax block
    masks the keys below each query's bound.  A query's window always
    holds its own position, so no row of a real item is left without a
    key.

    ``sink``: one more input behind the scale pools, ``[Hkv, G x block_q,
    128]`` float32 held whole in VMEM: a learnt logit a query head, laid
    out as the running maximum is.  The softmax state starts from it (m =
    the logit, l = exp(0) = 1, nothing accumulated): the sink takes mass
    in the denominator and has no value."""
    del wl_blk_ref      # the index maps' column (compacted layout)
    rest = list(rest)
    vpool = None if latent else rest.pop(0)
    if quantized:
        kspool, vspool = rest[:2]
        rest = rest[2:]
    else:
        kspool = vspool = None
    sink_ref = rest.pop(0) if sink else None
    if carry:
        mi_ref, li_ref, ai_ref = rest[:3]
        rest = rest[3:]
    else:
        mi_ref = li_ref = ai_ref = None
    if emit_state:
        mo_ref, lo_ref, ao_ref = rest[:3]
        o_ref = None
        rest = rest[3:]
    else:
        o_ref = rest[0]
        mo_ref = lo_ref = ao_ref = None
        rest = rest[1:]
    if quantized:
        kbuf, vbuf, ksbuf, vsbuf, m_ref, l_ref, acc_ref, sem = rest
    elif latent:
        kbuf, m_ref, l_ref, acc_ref, sem = rest
        vbuf = ksbuf = vsbuf = None
    else:
        kbuf, vbuf, m_ref, l_ref, acc_ref, sem = rest
        ksbuf = vsbuf = None
    item = pl.program_id(0)
    lyr = layer_ref[0]
    s_i = wl_seq_ref[item]
    hg_i = wl_hg_ref[item]
    qb = wl_qb_ref[item]
    plo = wl_plo_ref[item]
    npages = wl_pages_ref[item]
    pos0 = pos_start_ref[s_i]
    q_lo = qb * block_q
    h0 = hg_i * head_group
    grouped = head_group != kpool.shape[2]

    def start_copies(page_i, buf):
        pg = tables_ref[s_i, page_i]
        pltpu.make_async_copy(kpool.at[lyr, pg, pl.ds(h0, head_group)],
                              kbuf.at[buf], sem.at[0, buf]).start()
        if vpool is not None:
            pltpu.make_async_copy(vpool.at[lyr, pg, pl.ds(h0, head_group)],
                                  vbuf.at[buf], sem.at[1, buf]).start()
        if quantized:
            # The f32 scale stripe [Hkv, P] is tiled (Hkv, 128) in HBM: a
            # sub-tile head slice cannot be DMA'd (Mosaic: "slice shape
            # must be aligned to tiling").  Fetch the page's whole stripe
            # (4 bytes/token/head) and pick the group's heads in VMEM.
            pltpu.make_async_copy(kspool.at[lyr, pg], ksbuf.at[buf],
                                  sem.at[2, buf]).start()
            pltpu.make_async_copy(vspool.at[lyr, pg], vsbuf.at[buf],
                                  sem.at[3, buf]).start()

    def wait_copies(buf):
        pltpu.make_async_copy(kpool.at[lyr, 0, pl.ds(0, head_group)],
                              kbuf.at[buf], sem.at[0, buf]).wait()
        if vpool is not None:
            pltpu.make_async_copy(vpool.at[lyr, 0, pl.ds(0, head_group)],
                                  vbuf.at[buf], sem.at[1, buf]).wait()
        if quantized:
            pltpu.make_async_copy(kspool.at[lyr, 0], ksbuf.at[buf],
                                  sem.at[2, buf]).wait()
            pltpu.make_async_copy(vspool.at[lyr, 0], vsbuf.at[buf],
                                  sem.at[3, buf]).wait()

    # Padding item (npages == 0 <= plo): compute nothing, write nothing —
    # the output window still holds the previous (aliased) item's block
    # and re-flushes it unchanged.  A carry call must still run REAL
    # items whose span is empty (all their pages fell in earlier spans:
    # plo == npages > 0) — the carried state still has to be passed
    # through / normalized into the output.
    run_gate = (npages > 0) if carry else (npages > plo)

    @pl.when(run_gate)
    def _run():
        if carry:
            m_ref[:] = mi_ref[0]
            l_ref[:] = li_ref[0]
            acc_ref[:] = ai_ref[0]
        elif sink:
            m_ref[:] = sink_ref[pl.ds(h0, head_group)]
            l_ref[:] = jnp.ones_like(l_ref)
            acc_ref[:] = jnp.zeros_like(acc_ref)
        else:
            m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
            l_ref[:] = jnp.zeros_like(l_ref)
            acc_ref[:] = jnp.zeros_like(acc_ref)
        for j in range(depth - 1):
            @pl.when(plo + j < npages)
            def _warm(j=j):
                start_copies(plo + j, (plo + j) % depth)

        def body(si, loop_c):
            nxt = si + depth - 1

            @pl.when(nxt < npages)
            def _prefetch():
                start_copies(nxt, nxt % depth)

            buf = si % depth
            wait_copies(buf)
            _mixed_softmax_block(q_ref, kbuf, vbuf, ksbuf, vsbuf, m_ref,
                                 l_ref, acc_ref, buf, si, pos0, q_lo,
                                 page=page, block_q=block_q, scale=scale,
                                 quantized=quantized, int4=int4,
                                 h0=h0 if grouped else None,
                                 window=window)
            return loop_c

        jax.lax.fori_loop(plo, npages, body, 0)
        # The state's scratch and the blocks it leaves through have one
        # layout, [head_group, G x block_q, .]: whole tiles, stored as held.
        if emit_state:
            mo_ref[0] = m_ref[:]
            lo_ref[0] = l_ref[:]
            ao_ref[0] = acc_ref[:]
        else:
            out = acc_ref[:] / (_lanes(l_ref[:], acc_ref.shape[-1]) + 1e-9)
            o_ref[0] = out.astype(o_ref.dtype)


def _mixed_scratch(k_pool, v_pool, *, nbuf: int, head_group: int,
                   hkv: int, g: int, d: int, page: int, block_q: int,
                   quantized: bool, dv: int | None = None):
    """VMEM scratch of one mixed-attention work item: ``nbuf`` page
    buffers (one set where ``v_pool`` is None: a latent pool), the
    online-softmax state (the accumulator ``dv`` wide, ``d`` by default),
    the DMA semaphores.  A value pool's buffers are as wide as the pool.
    The state is ``[head_group, G x block_q, .]``, the merged rows of the
    launch's query, output and carried-state blocks
    (:func:`_ragged_launch`): it is read from and stored to them as held."""
    kv_rows = k_pool.shape[3]            # page//2 byte rows for int4 pools
    scratch = [pltpu.VMEM((nbuf, head_group, kv_rows, d), k_pool.dtype)]
    if v_pool is not None:
        scratch.append(pltpu.VMEM(
            (nbuf, head_group, kv_rows, v_pool.shape[-1]), v_pool.dtype))
    n_sem = 2
    if quantized:
        scratch += [pltpu.VMEM((nbuf, hkv, page), jnp.float32),
                    pltpu.VMEM((nbuf, hkv, page), jnp.float32)]
        n_sem = 4
    scratch += [
        pltpu.VMEM((head_group, g * block_q, 128), jnp.float32),  # m
        pltpu.VMEM((head_group, g * block_q, 128), jnp.float32),  # l
        pltpu.VMEM((head_group, g * block_q, dv or d), jnp.float32),  # acc
        pltpu.SemaphoreType.DMA((n_sem, nbuf)),
    ]
    return scratch


def _ragged_launch(qp, k_pool, v_pool, tables32, pos32, work_list, layer,
                   k_scale, v_scale, carry_state=None, *, compact: bool,
                   block_q: int, dma_depth: int, interpret: bool,
                   head_group: int, emit_state: bool = False,
                   latent_v: int = 0, scale: float | None = None,
                   window: int = 0, sink: jnp.ndarray | None = None):
    """The ragged work-list ``pallas_call``, one grid step per entry of
    ``work_list`` (:func:`build_mixed_work_list`).  ``qp`` holds the
    queries in blocks of ``G x block_q`` MERGED rows, g-major (row ``r``
    of a block is query ``r % block_q`` of head ``r // block_q``: the
    order of the mask's ``row % block_q`` and of the sink's rows), so that
    the kernel's block ``[1, head_group, G x block_q, D]`` is whole
    sublane tiles whatever ``block_q`` is and the body lays nothing out
    again (a ``[.., G, block_q, D]`` block puts each head's ``block_q``
    rows in tiles of their own: 64 sixteenth-full tiles of a one-row
    latent item, gathered at every page, a third of that launch's time on
    a v5e; PERF.md, PR 55).  Two layouts that differ only in the index map
    that finds an item's block:

    - ``compact``: ``[NB, Hkv, G x block_q, D]``, block ``blk[i]`` — one
      block per real (lane, q_block) pair, the flat batch's layout;
    - per lane: ``[S, Hkv, num_qb, G x block_q, D]``, block ``(seq[i],
      qb[i])``, the q-block axis squeezed out of the kernel's view — what
      :func:`_paged_mixed_call` lays out of a caller's ``[S, Hkv, G, Q,
      D]``.

    The output (or, with ``emit_state``, the raw f32 m / l / acc) comes
    back in the layout ``qp`` has; ``carry_state`` is read through the
    same map.  Blocks no real item owns are never written.

    ``latent_v`` > 0 is the latent page (``v_pool`` None, ``k_pool``
    ``[L, N, 1, P, R]`` full width): scores over all R lanes, values the
    first ``latent_v`` lanes of the same tile, the output ``latent_v``
    wide; the call is named ``paged_latent_attention_ragged``.  ``scale``
    multiplies the scores (``1 / sqrt(d)`` by default).

    ``window`` > 0 is a window layer's launch (the work list built with
    the same ``window``): the same call, told the bound, named
    ``paged_window_attention_ragged`` under ``arks.attn_win_kernel``.

    A value pool narrower (or wider) than the key pool: the accumulator,
    the carried state's and the output are as wide as the VALUES.
    ``sink`` ``[Hkv, G]`` float32: a learnt logit a query head that every
    item's softmax state starts from (the kernel's docstring); it is
    handed over once, ``[Hkv, G x block_q, 128]``, a block that never
    moves."""
    hkv, rows, d = qp.shape[1], qp.shape[-2], qp.shape[-1]
    g = rows // block_q
    quantized = k_scale is not None
    page = pool_page_tokens(k_pool, k_scale)
    carry = carry_state is not None
    latent = latent_v > 0
    if latent and (v_pool is not None or quantized or carry or emit_state
                   or hkv != 1):
        raise ValueError("a latent page is one full-width pool of one row "
                         "a token: no value pool, no scales, no carried "
                         "softmax state")
    if window and (latent or carry or emit_state):
        raise ValueError("a window launch is one span of a K/V pool: no "
                         "latent page, no carried softmax state")
    if sink is not None and (latent or carry):
        raise ValueError("a sink logit starts the softmax state of a K/V "
                         "pool's one span: no latent page, no carried state")
    dv = latent_v or v_pool.shape[-1]

    if compact:
        def q_map(i, layer_p, tables_p, pos_p, seq_p, hg_p, qb_p, plo_p,
                  pages_p, blk_p):
            del layer_p, tables_p, pos_p, seq_p, qb_p, plo_p, pages_p
            return (blk_p[i], hg_p[i], 0, 0)
    else:
        def q_map(i, layer_p, tables_p, pos_p, seq_p, hg_p, qb_p, plo_p,
                  pages_p, blk_p):
            del layer_p, tables_p, pos_p, plo_p, pages_p, blk_p
            return (seq_p[i], hg_p[i], qb_p[i], 0, 0)

    # ONE block for the kernel, [1, head_group, G x block_q, width]: the
    # per-lane layout's q-block axis is squeezed.  (The accumulator's
    # blocks are ``o``'s, which is ``q``'s wherever the values are as wide
    # as the keys.)
    lead = (1, head_group) if compact else (1, head_group, None)
    blk = dict(q=lead + (rows, d), o=lead + (rows, dv),
               ml=lead + (rows, 128))
    carry_inputs, carry_specs = [], []
    if carry:
        # Carry arrays have the q layout's shape — exactly what a
        # previous emit_state call produced, so spans chain without
        # re-padding.
        carry_inputs = list(carry_state)
        carry_specs = [pl.BlockSpec(blk["ml"], q_map),
                       pl.BlockSpec(blk["ml"], q_map),
                       pl.BlockSpec(blk["o"], q_map)]
    if emit_state:
        out_specs = (pl.BlockSpec(blk["ml"], q_map),
                     pl.BlockSpec(blk["ml"], q_map),
                     pl.BlockSpec(blk["o"], q_map))
        out_shape = tuple(
            jax.ShapeDtypeStruct(qp.shape[:-1] + (w,), jnp.float32)
            for w in (128, 128, dv))
    else:
        out_specs = pl.BlockSpec(blk["o"], q_map)
        out_shape = jax.ShapeDtypeStruct(qp.shape[:-1] + (dv,), qp.dtype)

    pools = [k_pool] if latent else [k_pool, v_pool]
    pool_specs = [pl.BlockSpec(memory_space=pl.ANY)] * len(pools)  # manual DMA
    scale_inputs = [k_scale, v_scale] if quantized else []
    scale_specs = [pl.BlockSpec(memory_space=pl.ANY)] * 2 if quantized else []
    if sink is not None:
        # Row r of a head's G x block_q rows is query head r // block_q.
        scale_inputs.append(jnp.broadcast_to(
            sink.astype(jnp.float32)[:, :, None, None],
            (hkv, g, block_q, 128)).reshape(hkv, g * block_q, 128))
        scale_specs.append(pl.BlockSpec(
            (hkv, g * block_q, 128), lambda i, *prefetch: (0, 0, 0)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=9,  # layer, tables, pos_start, work list x6
        grid=(work_list[0].shape[0],),
        in_specs=[pl.BlockSpec(blk["q"], q_map)]
        + pool_specs + scale_specs + carry_specs,
        out_specs=out_specs,
        scratch_shapes=_mixed_scratch(
            k_pool, v_pool, nbuf=dma_depth, head_group=head_group, hkv=hkv,
            g=g, d=d, page=page, block_q=block_q, quantized=quantized,
            dv=dv),
    )
    kernel = functools.partial(
        _paged_mixed_ragged_kernel, page=page, block_q=block_q,
        scale=1.0 / (d ** 0.5) if scale is None else scale,
        quantized=quantized,
        int4=is_int4_pool(k_pool, k_scale), depth=dma_depth,
        head_group=head_group, carry=carry, emit_state=emit_state,
        **({"latent": True} if latent else {}),
        window=window, sink=sink is not None)
    # The call alone is the kernel in a profile; the layout work around it
    # stays with the caller's scope (arks.attn_layout in the mixed step).
    with jax.named_scope("arks.attn_win_kernel" if window
                         else "arks.attn_kernel"):
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=out_shape,
            # Consecutive items may alias one output block (padding
            # re-flush), so the item axis is "arbitrary", never "parallel".
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            interpret=interpret,
            name="paged_latent_attention_ragged" if latent
            else "paged_window_attention_ragged" if window
            else "paged_mixed_attention_ragged",
        )(jnp.asarray(layer, jnp.int32).reshape(1), tables32, pos32,
          *work_list, qp, *pools, *scale_inputs, *carry_inputs)


@functools.partial(jax.jit, static_argnames=("block_q", "interpret",
                                             "dma_depth", "head_group",
                                             "emit_state"))
def _paged_mixed_call(q, k_pool, v_pool, tables, pos_start, q_len, layer,
                      k_scale, v_scale, page_lo=None, page_hi=None,
                      carry_state=None, *, block_q: int, dma_depth: int,
                      interpret: bool, head_group: int, emit_state: bool):
    """Jitted mixed-attention launch over a per-lane ``[S, Hkv, G, Q, D]``
    query block, with FULLY RESOLVED statics — the public wrapper resolves
    the plan (autotune) per call so changing the tune table between calls
    can never hit a stale jit cache entry keyed on unresolved defaults.
    The q axis is padded to the plan's q blocks and laid out in the
    launch's merged rows here, ``[S, Hkv, num_qb, G x block_q, D]``
    (:func:`_ragged_launch`), and the output laid back and sliced; the
    grid visits the lanes' real blocks through the per-lane index map
    (the flat batch's block-compacted layout is
    :func:`_paged_mixed_flat_call`).  The raw state of ``emit_state`` is
    handed out, and ``carry_state`` taken, AS THE LAUNCH HOLDS IT (``[S,
    Hkv, num_qb, G x block_q, 128 | Dv]``): spans chain without a
    transpose between them."""
    s, hkv, g, qmax, d = q.shape
    page = pool_page_tokens(k_pool, k_scale)
    qpad = -(-qmax // block_q) * block_q
    num_qb = qpad // block_q
    qp = q if qpad == qmax else jnp.pad(
        q, ((0, 0), (0, 0), (0, 0), (0, qpad - qmax), (0, 0)))
    qp = jnp.transpose(qp.reshape(s, hkv, g, num_qb, block_q, d),
                       (0, 1, 3, 2, 4, 5)).reshape(
                           s, hkv, num_qb, g * block_q, d)
    tables32 = tables.astype(jnp.int32)
    pos32 = pos_start.astype(jnp.int32)
    qlen32 = q_len.astype(jnp.int32)
    work_list = build_mixed_work_list(
        pos32, qlen32, page=page, block_q=block_q, num_qb=num_qb,
        max_pages=tables.shape[1], head_groups=hkv // head_group,
        page_lo=page_lo, page_hi=page_hi)
    out = _ragged_launch(
        qp, k_pool, v_pool, tables32, pos32, work_list, layer, k_scale,
        v_scale, carry_state, compact=False, block_q=block_q,
        dma_depth=dma_depth, interpret=interpret, head_group=head_group,
        emit_state=emit_state)
    # Rows past q_len[s] are undefined (never-visited items) — zero them
    # so the call returns the same bytes everywhere, not just on the rows
    # callers keep.
    if emit_state:
        # Row r of block qb is query qb * block_q + r % block_q.
        query = (jnp.arange(num_qb, dtype=jnp.int32)[:, None] * block_q
                 + jnp.arange(g * block_q, dtype=jnp.int32)[None, :]
                 % block_q)
        validp = (query[None] < qlen32[:, None, None])[:, None, :, :, None]
        return tuple(jnp.where(validp, x, jnp.zeros_like(x)) for x in out)
    out = jnp.transpose(out.reshape(s, hkv, num_qb, g, block_q, -1),
                        (0, 1, 3, 2, 4, 5)).reshape(s, hkv, g, qpad, -1)
    if qpad != qmax:
        out = out[..., :qmax, :]
    valid = jnp.arange(qmax, dtype=jnp.int32)[None, :] < qlen32[:, None]
    return jnp.where(valid[:, None, None, :, None], out,
                     jnp.zeros_like(out))


@functools.partial(jax.jit, static_argnames=("block_q", "nb", "interpret",
                                             "dma_depth", "head_group",
                                             "latent_v", "scale", "window"))
def _paged_mixed_flat_call(q, k_pool, v_pool, tables, token_slot, q_start,
                           q_len, pos_start, layer, k_scale, v_scale,
                           sink=None, *,
                           block_q: int, nb: int, dma_depth: int,
                           interpret: bool, head_group: int,
                           latent_v: int = 0, scale: float | None = None,
                           window: int = 0):
    """Jitted ragged launch over the FLAT batch's queries ``[T, Hkv, G,
    D]`` in the block-compacted layout: ``nb`` blocks of ``block_q`` rows,
    one per real (lane, q_block) pair (``nb`` is the plan's static bound
    on them), handed to the launch with a block's ``G x block_q`` rows
    merged, ``[nb, Hkv, G x block_q, D]`` (:func:`_ragged_launch`: the
    merge is a free reshape of the array the transpose has just written),
    filled by ONE gather from the flat rows and read back by ONE
    gather of T rows (a per-lane layout gives every lane room for the
    widest chunk any lane could have: ``lanes x qmax`` rows for the same
    ``T``).  The grid is ``nb x head groups`` long: the
    compacted work list's front, which holds every real item.  Same
    kernel body, same work list columns, so each real row's arithmetic is
    the per-lane call's; padding rows (token_slot < 0) return zeros."""
    t_flat, hkv, g, d = q.shape
    page = pool_page_tokens(k_pool, k_scale)
    n_hg = hkv // head_group
    pos32 = pos_start.astype(jnp.int32)
    qlen32 = q_len.astype(jnp.int32)
    # A lane has at most the widest span's blocks; of the full list only
    # the first nb * n_hg entries (every real item) are launched.
    qmax = max(t_flat - q_len.shape[0] + 1, 1)
    work_list = build_mixed_work_list(
        pos32, qlen32, page=page, block_q=block_q,
        num_qb=-(-qmax // block_q), max_pages=tables.shape[1],
        head_groups=n_hg, n_items=nb * n_hg,
        window=window)
    _, src_rows, out_rows = mixed_block_layout(
        token_slot, q_start, qlen32, block_q=block_q, nb=nb)
    qb = jnp.take(q, src_rows, axis=0).reshape(nb, block_q, hkv, g, d)
    out = _ragged_launch(
        jnp.transpose(qb, (0, 2, 3, 1, 4)).reshape(
            nb, hkv, g * block_q, d), k_pool, v_pool,
        tables.astype(jnp.int32), pos32, work_list, layer, k_scale, v_scale,
        compact=True, block_q=block_q, dma_depth=dma_depth,
        interpret=interpret, head_group=head_group, latent_v=latent_v,
        scale=scale, window=window, sink=sink)
    # Straight out of the kernel's layout by (block, row): a transpose to
    # row-major first would copy the whole output once more.
    flat = out.reshape(nb, hkv, g, block_q, -1)[
        out_rows // block_q, :, :, out_rows % block_q]
    return jnp.where((token_slot >= 0)[:, None, None, None], flat,
                     jnp.zeros_like(flat))


def paged_mixed_attention(
    q: jnp.ndarray,        # [S, Hkv, G, Q, D] — Q query tokens per sequence
    k_pool: jnp.ndarray,   # [L, N, Hkv, P, D] page pool ([.., P//2, D] int4)
    v_pool: jnp.ndarray,
    tables: jnp.ndarray,   # [S, MaxP] int32 block tables
    pos_start: jnp.ndarray,  # [S] int32 — global position of query 0
    q_len: jnp.ndarray,      # [S] int32 — valid queries (0 = inactive lane)
    layer,                   # int32
    k_scale: jnp.ndarray | None = None,  # [L, N, Hkv, P] f32 (int8/int4)
    v_scale: jnp.ndarray | None = None,
    block_q: int | None = None,
    interpret: bool = False,
    dma_depth: int | None = None,
    head_group: int | None = None,  # KV heads per work item (None = tuned)
    page_lo: jnp.ndarray | None = None,   # [S] span start (pages)
    page_hi: jnp.ndarray | None = None,   # [S] span end bound (pages)
    carry_state: tuple | None = None,     # (m, l, acc) from emit_state
    emit_state: bool = False,
):
    """[S, Hkv, G, Q, D] ragged mixed attention: query i of sequence s
    attends its table pages over positions [0, pos_start[s]+i].  Rows past
    q_len[s] are zeroed — the ONE kernel serving decode lanes (q_len=1),
    prefill chunks, and spec verify rows (q_len=K) in a single dispatch.
    The plan (block_q via autotune, DMA depth, GQA head grouping) is
    resolved HERE, outside jit, then passed as statics.

    Span-bounded calls (page_lo/page_hi + carry_state/emit_state) chain
    the online-softmax state across page ranges — the windowed-residency
    building block.  With emit_state the return is the raw f32
    (m, l, acc) triple as the launch holds it (``[S, Hkv, num_qb, G x
    block_q, .]``, the q axis padded to the plan's blocks) instead of the
    normalized output; feeding it back as carry_state on the next span
    and finishing with emit_state=False reproduces the single-call
    result bitwise."""
    s, hkv, g, qmax, d = q.shape
    plan = mixed_grid_plan(qmax, hkv=hkv, g=g, d=d,
                           page=pool_page_tokens(k_pool, k_scale),
                           kv=pool_kv_name(k_pool, k_scale),
                           block_q=block_q, dma_depth=dma_depth,
                           head_group=head_group)
    return _paged_mixed_call(q, k_pool, v_pool, tables, pos_start, q_len,
                             layer, k_scale, v_scale, page_lo, page_hi,
                             carry_state,
                             block_q=plan["block_q"],
                             dma_depth=plan["dma_depth"],
                             interpret=interpret,
                             head_group=plan["head_group"],
                             emit_state=emit_state)


def paged_mixed_attention_flat(
    q: jnp.ndarray,          # [T, Hkv, G, D] — the flat mixed token batch
    k_pool: jnp.ndarray,     # [L, N, Hkv, P, D] page pool ([.., P//2, D] int4)
    v_pool: jnp.ndarray,
    tables: jnp.ndarray,     # [S, MaxP] int32 block tables, lane s == slot s
    token_slot: jnp.ndarray,  # [T] int32 — lane of each flat row (-1 = pad)
    q_start: jnp.ndarray,    # [S] int32 — lane's first flat row
    q_len: jnp.ndarray,      # [S] int32 — lane's row count (0 = inactive)
    pos_start: jnp.ndarray,  # [S] int32 — global position of the lane's row 0
    layer,                   # int32
    k_scale: jnp.ndarray | None = None,
    v_scale: jnp.ndarray | None = None,
    block_q: int | None = None,
    interpret: bool = False,
    dma_depth: int | None = None,
    head_group: int | None = None,
    latent_v: int = 0,
    scale: float | None = None,
    window: int = 0,
    sink: jnp.ndarray | None = None,   # [Hkv, G] f32
) -> jnp.ndarray:
    """[T, Hkv, G, D] ragged mixed attention straight over the flat batch:
    row t of lane s = token_slot[t] sits at global position
    ``pos_start[s] + t - q_start[s]`` and attends the lane's table pages
    over [0, that position]; padding rows return zeros.  A lane's rows are
    contiguous from ``q_start[s]``.  On every real row the bytes are those
    of :func:`paged_mixed_attention` over the per-lane block of the same
    batch.

    The plan is resolved HERE, outside jit, from the flat shape: ``qmax =
    T - S + 1`` is the widest span one lane can have, ``nb`` the bound on
    real q blocks (:func:`mixed_grid_plan`); the queries are laid out
    block-compacted (:func:`_paged_mixed_flat_call`).

    A LATENT pool (``latent_v`` > 0, ``v_pool`` None, ``k_pool`` ``[L, N,
    1, P, R]``): one row a token serves every head, so Hkv is 1 and the
    model's heads are the G query rows of the one group; scores are over
    all R lanes times ``scale``, values are the row's first ``latent_v``
    lanes, and the result is ``[T, 1, G, latent_v]``.  Same block layout,
    same work list.

    ``window`` > 0 (a window layer): a row at position p attends
    ``(p - window, p]``; the work list and the kernel are told the bound
    (:func:`build_mixed_work_list`, :func:`_ragged_launch`).

    A value pool of another width than the key pool: the result is ``[T,
    Hkv, G, Dv]``.  ``sink``: a learnt logit a query head in every row's
    softmax denominator (:func:`_ragged_launch`)."""
    t_flat, hkv, g, d = q.shape
    s = q_len.shape[0]
    # +1: with every lane a q_len = K block (t_flat == S * K, one lane)
    # t_flat - S would undershoot the lane's own width.
    qmax = max(t_flat - s + 1, 1)
    plan = mixed_grid_plan(qmax, hkv=hkv, g=g, d=d,
                           page=pool_page_tokens(k_pool, k_scale),
                           kv=pool_kv_name(k_pool, k_scale),
                           block_q=block_q, dma_depth=dma_depth,
                           head_group=head_group, lanes=s)
    return _paged_mixed_flat_call(
        q, k_pool, v_pool, tables, token_slot, q_start, q_len,
        pos_start, layer, k_scale, v_scale, sink, block_q=plan["block_q"],
        nb=plan["nb"], dma_depth=plan["dma_depth"],
        interpret=interpret, head_group=plan["head_group"],
        latent_v=latent_v, scale=scale,
        window=window)


# ---------------------------------------------------------------------------
# In-place paged KV row update
# ---------------------------------------------------------------------------

_UPDATE_CHUNK = 16        # bf16 sublane tile
_UPDATE_CHUNK_INT8 = 32   # int8 sublane tile
_SCALE_CHUNK = 128        # f32 lane tile
# Scratch slots a pool: one block merging, one being read for the next
# run, two whose write-backs are still on their way.
_UPDATE_RING = 4


def update_block_tokens(kv: str) -> int:
    """Token positions in one block of the row write, by the pool's KV
    width (:func:`pool_kv_name`)."""
    return {"int8": _UPDATE_CHUNK_INT8,
            "int4": 2 * _UPDATE_CHUNK_INT8}.get(kv, _UPDATE_CHUNK)


def _paged_update_kernel(layer_ref, idx_ref, pages_ref, starts_ref, nruns_ref,
                         *refs, page: int, cover: int, groups: tuple,
                         int4: bool):
    """One read-modify-write a touched BLOCK, the next block's read in
    flight while this one merges.

    ``groups``: ``(pools, rows, width)`` a group of pools that move the same
    block: ``rows`` token positions stored as ``width`` units of the pool's
    fourth axis (K and V pages: 16 bf16 rows, 32 int8 rows, 64 int4 tokens
    in 32 byte rows; the two scale pools: a 128-lane group).  The first
    group's block is the finest and every other group's holds it whole.
    ``refs``: per pool the new rows, the pool (aliased input), the pool
    (output) and a ring of ``_UPDATE_RING`` blocks of scratch, pools in
    group order; then the DMA semaphores ``[pool, ring slot]`` and the
    write-backs in flight (SMEM).

    Scalar prefetch (:func:`_update_runs`): a row's position
    (``>= cover``: a padding row) and the page id its table gives it, and
    the *runs*: ``starts_ref[r]`` the first flat row of run ``r`` of
    ``nruns_ref[0]``, ``starts_ref[nruns]`` the row count.  A run's live
    rows fall in ONE block of every group, and stand in front of its
    padding rows.  The kernel walks the runs: a group whose block changes
    with the next run has that block's read started before this run's rows
    are merged, row by row in flat order, into the copy in VMEM; the copy
    goes back once, when the group's block closes, and that write is
    waited for only when its ring slot is wanted again, or when a later
    run reads the same block (the packers never lay that out; the
    comparison of block keys keeps it right).  Two runs in a row over one
    block (a padding row between them) merge into one copy."""
    np_ = sum(n for n, _, _ in groups)
    news, outs, rings = (refs[:np_], refs[2 * np_:3 * np_],
                         refs[3 * np_:4 * np_])
    sem, going = refs[4 * np_:]
    first = [sum(n for n, _, _ in groups[:g]) for g in range(len(groups))]
    b = idx_ref.shape[0]
    nruns = nruns_ref[0]
    lyr = layer_ref[0]
    ring = _UPDATE_RING

    def place(i):
        """(page id, offset in the page) of live row ``i``."""
        return pages_ref[i], idx_ref[i] % page

    def key(g, pg, off):
        rows = groups[g][1]
        return pg * (page // rows) + off // rows

    def moves(g, pg, off, slot, back: bool):
        """The group's copies of block ``(pg, off // rows)``: pool to ring
        slot, or ``back``."""
        n, rows, width = groups[g]
        out = []
        for j in range(first[g], first[g] + n):
            blk = outs[j].at[pl.ds(lyr, 1), pl.ds(pg, 1), :,
                             pl.ds((off // rows) * width, width)]
            scr = rings[j].at[slot]
            out.append(pltpu.make_async_copy(
                *((scr, blk) if back else (blk, scr)), sem.at[j, slot]))
        return out

    def landed(g, k):
        """Wait for the write-back in flight from ring slot ``k`` (a wait
        needs the copy's shape, not its place)."""
        for c in moves(g, 0, 0, k, True):
            c.wait()
        going[g * ring + k] = -1

    for s in range(len(groups) * ring):
        going[s] = -1

    def merge(i, slots):
        """Row ``i`` into its blocks' copies: a select of one row of a
        page block, of one lane of a scale group."""
        off = idx_ref[i] % page
        for g, (n, rows, width) in enumerate(groups):
            unit = (off % rows) * width // rows
            for j in range(first[g], first[g] + n):
                scr = rings[j].at[slots[g]]
                old = scr[...]
                hit = jax.lax.broadcasted_iota(jnp.int32, old.shape, 3) == unit
                new = news[j][pl.ds(i, 1)]
                new = (new[None] if old.ndim == 5
                       else new.reshape(1, 1, old.shape[2], 1))
                if int4 and g == 0:
                    # Merge ONE nibble of the hit byte: low nibble = even
                    # token (keep 0xF0), high = odd (keep 0x0F); rows of a
                    # run that share a byte merge in flat order, the second
                    # over the first.  The bit math runs on int32 (Mosaic
                    # legalises no 8-bit vector shift on a v5e) and
                    # truncates back: the new value is in [-7, 7], so value
                    # << 4 and either merge stay inside int8's range.
                    old = old.astype(jnp.int32)
                    new = new.astype(jnp.int32)
                    new = jnp.where(
                        (off % 2) == 0,
                        jnp.bitwise_or(jnp.bitwise_and(old, -16),
                                       jnp.bitwise_and(new, 15)),
                        jnp.bitwise_or(jnp.bitwise_and(old, 15),
                                       jnp.left_shift(new, 4)))
                scr[...] = jnp.where(hit, new, old).astype(scr.dtype)

    # ``count`` a group: the blocks it has closed, so its open block stands
    # in ring slot ``count % ring``; ``opened``: this run is the open
    # block's first, its read not yet waited for.
    def run(r, carry):
        pg, off, count, opened = carry
        i0, i1 = starts_ref[r], starts_ref[r + 1]
        last = r + 1 == nruns
        npg, noff = place(jnp.minimum(i1, b - 1))
        slots = [c % ring for c in count]
        closes = []
        for g in range(len(groups)):
            nxt = jnp.where(last, -1, key(g, npg, noff))
            closes.append(nxt != key(g, pg, off))
            nslot = (count[g] + 1) % ring

            @pl.when(closes[g] & jnp.logical_not(last))
            def _():
                for k in range(ring):
                    held = going[g * ring + k]

                    @pl.when((held >= 0) & ((nslot == k) | (held == nxt)))
                    def _():
                        landed(g, k)
                for c in moves(g, npg, noff, nslot, False):
                    c.start()

            @pl.when(opened[g])
            def _():
                for c in moves(g, pg, off, slots[g], False):
                    c.wait()

        def row(i, _):
            @pl.when(idx_ref[i] < cover)
            def _():
                merge(i, slots)
            return 0

        jax.lax.fori_loop(i0, i1, row, 0)
        for g in range(len(groups)):
            @pl.when(closes[g])
            def _():
                for c in moves(g, pg, off, slots[g], True):
                    c.start()
                going[g * ring + slots[g]] = key(g, pg, off)
        return (npg, noff,
                tuple(c + x.astype(jnp.int32) for c, x in zip(count, closes)),
                tuple(closes))

    pg0, off0 = place(jnp.minimum(starts_ref[0], b - 1))

    @pl.when(nruns > 0)
    def _():
        for g in range(len(groups)):
            for c in moves(g, pg0, off0, 0, False):
                c.start()

    jax.lax.fori_loop(
        0, nruns, run,
        (pg0, off0, (jnp.int32(0),) * len(groups),
         (jnp.bool_(True),) * len(groups)))
    for g in range(len(groups)):
        for k in range(ring):
            @pl.when(going[g * ring + k] >= 0)
            def _():
                landed(g, k)


def _update_runs(write_idx, tables, page: int, rows: int):
    """What the row write's kernel walks, from the batch itself: ``(page id
    a row, first row a run [B + 1], run count [1])``.  A run opens at every
    live row (``write_idx`` inside the table's coverage) that does not
    continue the row before it in the same block of ``rows`` positions:
    "same block" is the page id and the block index actually read, so two
    slots' runs side by side, whose positions continue each other, and a
    window pool's stale table entries are told apart.  A handful of
    vector ops over ``[B]`` where the kernel's scalar core would walk the
    rows one by one (~4 us a call against ~45 us at 1056 rows on a v5e,
    PERF.md §6), and the kernel prefetches three ``[B]`` vectors where it
    prefetched a table row a flat row."""
    b = write_idx.shape[0]
    live = write_idx < tables.shape[1] * page
    safe = jnp.where(live, write_idx, 0)
    pages = jnp.take_along_axis(tables, (safe // page)[:, None], axis=1)[:, 0]
    key = jnp.where(live, pages * (page // rows) + safe % page // rows, -1)
    opens = live & (key != jnp.concatenate([jnp.full((1,), -1), key[:-1]]))
    starts = jnp.nonzero(opens, size=b + 1, fill_value=b)[0]
    return pages, starts.astype(jnp.int32), opens.sum(dtype=jnp.int32)[None]


def _paged_update_call(name: str, pools, news, page: int, groups, int4: bool,
                       write_idx, tables, layer, interpret: bool):
    """The row write over ``pools`` (in group order) of ``page`` tokens a
    page, each rewritten in place."""
    np_ = len(pools)
    widths = [w for n, _, w in groups for _ in range(n)]
    write_idx, tables = write_idx.astype(jnp.int32), tables.astype(jnp.int32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)] * np_
        + [pl.BlockSpec(memory_space=pl.ANY)] * np_,
        out_specs=tuple(pl.BlockSpec(memory_space=pl.ANY) for _ in pools),
        scratch_shapes=[
            pltpu.VMEM((_UPDATE_RING, 1, 1, p.shape[2], w) + p.shape[4:],
                       p.dtype)
            for p, w in zip(pools, widths)]
        + [pltpu.SemaphoreType.DMA((np_, _UPDATE_RING)),
           pltpu.SMEM((len(groups) * _UPDATE_RING,), jnp.int32)],
    )
    kernel = functools.partial(
        _paged_update_kernel, page=page, cover=tables.shape[1] * page,
        groups=tuple(groups), int4=int4)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=tuple(jax.ShapeDtypeStruct(p.shape, p.dtype)
                        for p in pools),
        # 0=layer, 1=idx, 2=page ids, 3=run starts, 4=run count, then the
        # new rows, then the pools.
        input_output_aliases={5 + np_ + j: j for j in range(np_)},
        interpret=interpret,
        name=name,
    )(jnp.asarray(layer, jnp.int32).reshape(1), write_idx,
      *_update_runs(write_idx, tables, page, groups[0][1]), *news, *pools)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_kv_update(
    k_pool: jnp.ndarray,   # [L, N, Hkv, P, D]
    v_pool: jnp.ndarray | None,   # None: a latent pool, one row a token
    k_new: jnp.ndarray,    # [B, Hkv, D]
    v_new: jnp.ndarray | None,
    write_idx: jnp.ndarray,  # [B] int32 position per slot
    tables: jnp.ndarray,     # [B, MaxP] int32
    layer,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray | None]:
    """Write one KV row per slot at its table-mapped page, in place."""
    page = k_pool.shape[3]
    if page % _UPDATE_CHUNK != 0:
        raise ValueError(f"page {page} must be a multiple of {_UPDATE_CHUNK}")
    pools = [k_pool] if v_pool is None else [k_pool, v_pool]
    news = [x.astype(p.dtype)[:, :, None, :]
            for x, p in zip((k_new, v_new), pools)]
    out = _paged_update_call(
        "paged_kv_update", pools, news, page,
        [(len(pools), _UPDATE_CHUNK, _UPDATE_CHUNK)], False,
        write_idx, tables, layer, interpret)
    return (out[0], None) if v_pool is None else (out[0], out[1])


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_kv_update_quant(
    k_pool: jnp.ndarray,   # [L, N, Hkv, P, D] int8
    v_pool: jnp.ndarray,
    k_scale: jnp.ndarray,  # [L, N, Hkv, P] f32
    v_scale: jnp.ndarray,
    k_new: jnp.ndarray,    # [B, Hkv, D]
    v_new: jnp.ndarray,
    write_idx: jnp.ndarray,
    tables: jnp.ndarray,
    layer,
    interpret: bool = False,
):
    """int8/int4 variant: quantize the new rows, write values + per-token
    scales in place through the table.  int4 pools (pool page rows !=
    scale page) store nibble pairs: a block of 32 BYTE rows holds 64
    tokens, and the kernel merges one nibble a row.  All position math
    stays in token units."""
    from arks_tpu.ops.pallas_attention import quantize_kv

    rows = k_pool.shape[3]
    page = k_scale.shape[3]
    int4 = rows != page
    if page % _SCALE_CHUNK != 0:
        raise ValueError(
            f"quantized page {page} must be a multiple of {_SCALE_CHUNK}")
    if int4 and rows % _UPDATE_CHUNK_INT8 != 0:
        raise ValueError(
            f"int4 packed page rows {rows} must be a multiple of "
            f"{_UPDATE_CHUNK_INT8}")
    kq, ks = quantize_kv(k_new, qmax=7 if int4 else 127)
    vq, vs = quantize_kv(v_new, qmax=7 if int4 else 127)
    return _paged_update_call(
        "paged_kv_update_quant", [k_pool, v_pool, k_scale, v_scale],
        [kq[:, :, None, :], vq[:, :, None, :], ks, vs], page,
        [(2, update_block_tokens("int4" if int4 else "int8"),
          _UPDATE_CHUNK_INT8),
         (2, _SCALE_CHUNK, _SCALE_CHUNK)], int4,
        write_idx, tables, layer, interpret)


# ---------------------------------------------------------------------------
# Host-tier spill/restore: whole-page pool gather / scatter
# ---------------------------------------------------------------------------
#
# The hierarchical prefix cache moves WHOLE pages between the device pool
# and host RAM: a spill gathers evicted pages into a contiguous staging
# block drained D2H with copy_to_host_async, and a restore scatters
# host-resident blocks back into freshly-allocated pool pages.  Unlike the
# per-row update kernels above, a page is already a dense (layer-major)
# stripe, so each transfer is one aligned whole-page DMA — XLA lowers
# take/dynamic_update_slice on the page axis to exactly that, and a Pallas
# formulation would buy nothing (no read-modify-write, no masking).  Both
# carry raw pool bytes (int8 + scales for quantized pools): spill->restore
# round-trips are bit-exact by construction.


def paged_pool_gather(pool: jnp.ndarray, pages: jnp.ndarray) -> jnp.ndarray:
    """Gather whole pool pages into a contiguous staging block:
    ``[L, N, Hkv, P, ...] x [G] int32 -> [L, G, Hkv, P, ...]``.  Duplicate
    page ids (host-side padding of a short spill group) are benign — the
    host drops the padded entries."""
    return jnp.take(pool, pages.astype(jnp.int32), axis=1)


def paged_pool_scatter(pool: jnp.ndarray, blocks: jnp.ndarray,
                       pages: jnp.ndarray, n_valid: jnp.ndarray) -> jnp.ndarray:
    """Write the first ``n_valid`` staged page blocks
    (``[L, G, Hkv, P, ...]``) into the pool pages listed in ``pages``
    ([G] int32, entries past n_valid ignored).  The counterpart of
    ``paged_pool_gather`` and the restore path's one device write; G is a
    fixed group size so the jitted program compiles ONCE (n_valid is the
    dynamic fill)."""

    def body(j, p):
        blk = jax.lax.dynamic_slice_in_dim(blocks, j, 1, axis=1)
        at = (0, pages[j].astype(jnp.int32)) + (0,) * (pool.ndim - 2)
        return jax.lax.dynamic_update_slice(p, blk.astype(p.dtype), at)

    return jax.lax.fori_loop(0, n_valid.astype(jnp.int32), body, pool)
