"""Attention ops for prefill and decode.

The reference delegates attention entirely to vLLM/SGLang CUDA kernels inside
runtime containers (/root/reference/internal/controller/
arksapplication_controller.go:941-1014 only builds their command lines).
Here attention is ours.  Two decode implementations behind one dispatcher:

- ``xla``: batched einsums that tile onto the MXU, masks as fused selects —
  the CPU tests' oracle, and what a shape the kernels cannot take runs
  under ``auto`` (``kernel_blockers``; the engine labels it so).  Reads
  the full cache.
- ``pallas``: ragged flash-decoding kernel (arks_tpu.ops.pallas_attention)
  that reads only each slot's valid KV prefix — the TPU default, since
  decode is HBM-bandwidth-bound.

Conventions:
- GQA everywhere: q heads H = G * Hkv.  q is reshaped to [.., Hkv, G, ..] so
  the kv head dim lines up for a single einsum (no repeat_kv materialization).
- Decode KV cache layout is ``[B, Hkv, S, D]`` — each (slot, head) sequence
  contiguous, which is what makes ragged block reads dense stripes.
- Inputs stay in their storage dtype (bf16 on TPU); matmuls accumulate in
  float32 via ``preferred_element_type`` — never materialize f32 casts of the
  KV cache (that would multiply decode HBM traffic by 2x).
- Softmax in float32 with max subtraction.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from arks_tpu.utils import knobs

_NEG_INF = -1e30


def _pad_last(x, d_store: int):
    """Zero-pad the trailing (head) dim to the cache's stored width —
    exact: padded K lanes add 0 to every q.k score, padded V lanes yield
    output columns the caller slices off."""
    if x is None or x.shape[-1] == d_store:
        return x
    width = [(0, 0)] * (x.ndim - 1) + [(0, d_store - x.shape[-1])]
    return jnp.pad(x, width)


def default_decode_impl() -> str:
    """'pallas' on real TPU, 'xla' elsewhere; override via ARKS_ATTN_IMPL."""
    impl = knobs.get_str("ARKS_ATTN_IMPL")
    if impl == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    return impl


def kernel_blockers(d_store: int, mesh=None, kv_sharded: bool = False,
                    model_axis: str = "model", *, int4_decode: bool = False,
                    pp: bool = False) -> list[str]:
    """Why the Pallas decode kernels cannot serve this shape (empty: they
    can).  The attention dispatchers below and the engine both decide from
    this one list, so ``engine_config_info{decode_impl}`` names the path
    that is traced: the engine raises at construction when the kernels were
    asked for by name (ARKS_ATTN_IMPL=pallas) and refuses the layouts that
    need them; under ``auto`` a blocked shape runs, and is labelled, xla.

    ``d_store`` is the STORED head dim (lane-padded caches store 128).
    ``int4_decode``: an int4 pool on the dedicated decode entry (the engine:
    int4 KV off the mixed scheduler).  ``pp``: a pipeline-parallel engine,
    whose per-stage bodies (parallel/pipeline.py) name ``impl="xla"``."""
    out = []
    if int4_decode:
        out.append("int4 KV off the mixed scheduler (there is no "
                   "standalone int4 decode kernel)")
    if pp:
        out.append("pipeline parallelism (per-stage decode runs the XLA "
                   "path)")
    # Mosaic tiles the last (lane) dim at 128.  Interpret mode has no such
    # constraint, so CPU kernel tests still run the kernels at small D.
    if d_store % 128 and jax.default_backend() == "tpu":
        out.append(f"stored head_dim {d_store} is not 128-lane aligned")
    # The kernels are embarrassingly parallel over (batch, kv head) and run
    # inside shard_map without collectives; replicated KV heads under a
    # non-trivial model axis need the XLA partitioner instead.
    if not (kv_sharded or mesh is None
            or mesh.shape.get(model_axis, 1) == 1):
        out.append("KV heads do not divide the tensor-parallel axis")
    return out


def _use_pallas(impl: str | None, d_store: int, mesh, kv_sharded: bool,
                model_axis: str, int4_decode: bool = False) -> bool:
    return ((impl or default_decode_impl()) == "pallas"
            and not kernel_blockers(d_store, mesh, kv_sharded, model_axis,
                                    int4_decode=int4_decode))


def _softmax(scores: jnp.ndarray, axis: int,
             sink: jnp.ndarray | None = None) -> jnp.ndarray:
    """``sink`` (broadcastable to ``scores`` with ``axis`` of size 1): one
    more logit in the denominator, which takes mass and has no value."""
    top = jnp.max(scores, axis=axis, keepdims=True)
    if sink is not None:
        top = jnp.maximum(top, sink)
    unnorm = jnp.exp(scores - top)
    total = jnp.sum(unnorm, axis=axis, keepdims=True)
    if sink is not None:
        total = total + jnp.exp(sink - top)
    return unnorm / (total + 1e-9)


def prefill_attention(
    q: jnp.ndarray,  # [B, T, H, D]
    k: jnp.ndarray,  # [B, T, Hkv, D]
    v: jnp.ndarray,  # [B, T, Hkv, D]
) -> jnp.ndarray:
    """Causal self-attention over a full (padded) prompt. Returns [B, T, H, D].

    Padded positions are handled by the caller: their outputs are garbage but
    never read (only the last valid token's logits are used), and their K/V
    entries are masked at decode time by the cache length.
    """
    b, t, h, d = q.shape
    hkv = k.shape[2]
    g = h // hkv
    qg = q.reshape(b, t, hkv, g, d)
    scale = 1.0 / (d ** 0.5)
    # [B, Hkv, G, Tq, Tk], f32 accumulation on the MXU.
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qg, k,
                        preferred_element_type=jnp.float32) * scale
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]  # [Tq, Tk]
    scores = jnp.where(causal[None, None, None], scores, _NEG_INF)
    probs = _softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, t, h, d).astype(q.dtype)


def decode_attention_xla(
    q: jnp.ndarray,        # [B, Hkv, G, D] — one new token per slot
    k_cache: jnp.ndarray,  # [B, Hkv, S, D]
    v_cache: jnp.ndarray,  # [B, Hkv, S, D]
    lengths: jnp.ndarray,  # [B] int32 — number of valid cache entries per slot
    lower: jnp.ndarray | None = None,  # [B] int32 — first index attended
    sink: jnp.ndarray | None = None,   # [Hkv, G] f32 — a sink logit a head
) -> jnp.ndarray:
    """Masked attention of one query token per slot against the slot KV cache.

    Cache index s is valid iff s < lengths[b] (the caller writes the current
    token's K/V into the cache *before* calling, so lengths includes it),
    and, with ``lower`` (a window layer), s >= lower[b].  The values may be
    narrower than the keys.  Returns [B, Hkv, G, Dv].
    """
    b, hkv, g, d = q.shape
    s = k_cache.shape[2]
    scale = 1.0 / (d ** 0.5)
    scores = jnp.einsum("bkgd,bksd->bkgs", q, k_cache,
                        preferred_element_type=jnp.float32) * scale
    valid = jnp.arange(s)[None] < lengths[:, None]  # [B, S]
    if lower is not None:
        valid = valid & (jnp.arange(s)[None] >= lower[:, None])
    scores = jnp.where(valid[:, None, None], scores, _NEG_INF)
    probs = _softmax(scores, -1, _sink_column(sink)).astype(v_cache.dtype)
    out = jnp.einsum("bkgs,bksd->bkgd", probs, v_cache,
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


def _sink_column(sink: jnp.ndarray | None) -> jnp.ndarray | None:
    """``[Hkv, G]`` sink logits as :func:`_softmax` takes them beside
    ``[B, Hkv, G, S]`` scores (None where there is no sink)."""
    return None if sink is None else sink.astype(
        jnp.float32)[None, :, :, None]


def _decode_attention_xla_quant(
    q: jnp.ndarray,        # [B, Hkv, G, D]
    k_cache: jnp.ndarray,  # [B, Hkv, S, D] int8
    v_cache: jnp.ndarray,
    k_scale: jnp.ndarray,  # [B, Hkv, S] f32
    v_scale: jnp.ndarray,
    lengths: jnp.ndarray,  # [B] int32
    lower: jnp.ndarray | None = None,  # [B] int32 — first index attended
    sink: jnp.ndarray | None = None,   # [Hkv, G] f32 — a sink logit a head
) -> jnp.ndarray:
    """int8 oracle/fallback: per-token scales applied to scores (K) and
    probabilities (V), mirroring the Pallas kernel's folding."""
    b, hkv, g, d = q.shape
    s = k_cache.shape[2]
    scale = 1.0 / (d ** 0.5)
    scores = jnp.einsum("bkgd,bksd->bkgs", q, k_cache.astype(q.dtype),
                        preferred_element_type=jnp.float32) * scale
    scores = scores * k_scale[:, :, None, :]
    valid = jnp.arange(s)[None] < lengths[:, None]  # [B, S]
    if lower is not None:
        valid = valid & (jnp.arange(s)[None] >= lower[:, None])
    scores = jnp.where(valid[:, None, None], scores, _NEG_INF)
    probs = _softmax(scores, -1, _sink_column(sink)) \
        * v_scale[:, :, None, :]
    out = jnp.einsum("bkgs,bksd->bkgd", probs.astype(q.dtype),
                     v_cache.astype(q.dtype),
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


def chunk_attention_xla(
    q: jnp.ndarray,        # [Hkv, G, C, D] — a chunk of queries for ONE slot
    k_cache: jnp.ndarray,  # [Hkv, S, D] — that slot's cache (chunk KV written)
    v_cache: jnp.ndarray,
    start: jnp.ndarray,    # () int32 — global position of the chunk's first query
    k_scale: jnp.ndarray | None = None,  # [Hkv, S] f32 — int8 caches
    v_scale: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Chunked-prefill attention: C queries against the slot's cache prefix.

    Query at chunk offset i (global position start+i) attends cache entries
    [0, start+i] — earlier chunks plus the causal prefix of this one.  The
    caller writes the chunk's KV into the cache *before* attending (same
    write-then-attend contract as decode_update_and_attend).  Cache entries
    beyond start+C (stale decode writes from interleaved dispatches, final-
    chunk padding) are masked out here and overwritten before any decode
    reads them.  Returns [Hkv, G, C, D].
    """
    hkv, g, c, d = q.shape
    s = k_cache.shape[1]
    scale = 1.0 / (d ** 0.5)
    scores = jnp.einsum("kgcd,ksd->kgcs", q, k_cache.astype(q.dtype),
                        preferred_element_type=jnp.float32) * scale
    if k_scale is not None:
        scores = scores * k_scale[:, None, None, :]
    qpos = start + jnp.arange(c)                    # [C] global positions
    valid = jnp.arange(s)[None] <= qpos[:, None]    # [C, S]
    scores = jnp.where(valid[None, None], scores, _NEG_INF)
    probs = _softmax(scores, axis=-1)
    if v_scale is not None:
        probs = probs * v_scale[:, None, None, :]
    out = jnp.einsum("kgcs,ksd->kgcd", probs.astype(q.dtype),
                     v_cache.astype(q.dtype),
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


def verify_update_and_attend(
    q: jnp.ndarray,        # [B, K, H, D] — K tokens per slot
    k_new: jnp.ndarray,    # [B, K, Hkv, D]
    v_new: jnp.ndarray,
    k_cache: jnp.ndarray,  # [L, B, Hkv, S, D] — FULL stacked cache
    v_cache: jnp.ndarray,
    positions: jnp.ndarray,  # [B, K] int32 — write positions per token
    lengths: jnp.ndarray,    # [B] int32 — valid prefix before this block
    layer,                   # int32
    mesh=None,
    batch_axis: str | None = None,
    kv_sharded: bool = False,
    model_axis: str = "model",
    k_scale: jnp.ndarray | None = None,
    v_scale: jnp.ndarray | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray,
           jnp.ndarray | None, jnp.ndarray | None]:
    """Speculative-verify attention: write K rows per slot at ``positions``,
    then attend each query over the cache prefix plus the causal part of its
    own block (index s valid iff s <= positions[b, k], which equals
    lengths[b]+k).  Returns ([B, K, H, D], kc, vc, k_scale, v_scale).

    XLA path only: K is small (draft lengths 2-8) and the scores tensor
    [B, Hkv, G, K, S] stays modest; under a mesh the partitioner reshards
    exactly as the non-pallas decode branch does."""
    del mesh, batch_axis, kv_sharded, model_axis, lengths
    b, kk, h, d_model = q.shape
    hkv = k_cache.shape[2]
    g = h // hkv
    # Lane padding (see decode_update_and_attend): pad to the stored head
    # dim, prescale q to keep the effective 1/sqrt(d_model) scale.
    d = k_cache.shape[-1]
    if d != d_model:
        q = _pad_last(q, d) * ((d / d_model) ** 0.5)
        k_new = _pad_last(k_new, d)
        v_new = _pad_last(v_new, d)
    quantized = k_scale is not None

    kc_l = jax.lax.dynamic_index_in_dim(k_cache, layer, 0, keepdims=False)
    vc_l = jax.lax.dynamic_index_in_dim(v_cache, layer, 0, keepdims=False)
    b_idx = jnp.arange(b)[:, None, None]
    h_idx = jnp.arange(hkv)[None, :, None]
    pos = positions[:, None, :]                       # [B, 1, K]
    kt = jnp.transpose(k_new, (0, 2, 1, 3))           # [B, Hkv, K, D]
    vt = jnp.transpose(v_new, (0, 2, 1, 3))
    if quantized:
        from arks_tpu.ops.pallas_attention import quantize_kv
        ktq, ktn = quantize_kv(kt)
        vtq, vtn = quantize_kv(vt)
        kc_l = kc_l.at[b_idx, h_idx, pos].set(ktq)
        vc_l = vc_l.at[b_idx, h_idx, pos].set(vtq)
        ks_l = jax.lax.dynamic_index_in_dim(k_scale, layer, 0, keepdims=False)
        vs_l = jax.lax.dynamic_index_in_dim(v_scale, layer, 0, keepdims=False)
        ks_l = ks_l.at[b_idx, h_idx, pos].set(ktn)
        vs_l = vs_l.at[b_idx, h_idx, pos].set(vtn)
    else:
        kc_l = kc_l.at[b_idx, h_idx, pos].set(kt.astype(kc_l.dtype))
        vc_l = vc_l.at[b_idx, h_idx, pos].set(vt.astype(vc_l.dtype))

    s = kc_l.shape[2]
    scale = 1.0 / (d ** 0.5)
    qg = jnp.transpose(q.reshape(b, kk, hkv, g, d), (0, 2, 3, 1, 4))  # [B,Hkv,G,K,D]
    scores = jnp.einsum("bkgqd,bksd->bkgqs", qg, kc_l.astype(q.dtype),
                        preferred_element_type=jnp.float32) * scale
    if quantized:
        scores = scores * ks_l[:, :, None, None, :]
    valid = jnp.arange(s)[None, None] <= positions[:, :, None]  # [B, K, S]
    scores = jnp.where(valid[:, None, None], scores, _NEG_INF)
    probs = _softmax(scores, axis=-1)
    if quantized:
        probs = probs * vs_l[:, :, None, None, :]
    out = jnp.einsum("bkgqs,bksd->bkgqd", probs.astype(q.dtype),
                     vc_l.astype(q.dtype),
                     preferred_element_type=jnp.float32)
    out = jnp.transpose(out, (0, 3, 1, 2, 4)).reshape(
        b, kk, h, d)[..., :d_model].astype(q.dtype)

    kc = jax.lax.dynamic_update_index_in_dim(k_cache, kc_l, layer, 0)
    vc = jax.lax.dynamic_update_index_in_dim(v_cache, vc_l, layer, 0)
    if quantized:
        ks = jax.lax.dynamic_update_index_in_dim(k_scale, ks_l, layer, 0)
        vs = jax.lax.dynamic_update_index_in_dim(v_scale, vs_l, layer, 0)
        return out, kc, vc, ks, vs
    return out, kc, vc, k_scale, v_scale


def paged_verify_update_and_attend(
    q: jnp.ndarray,        # [B, K, H, D] — K tokens per slot
    k_new: jnp.ndarray,    # [B, K, Hkv, D]
    v_new: jnp.ndarray,
    k_pool: jnp.ndarray,   # [L, N, Hkv, P, D] page pool
    v_pool: jnp.ndarray,
    tables: jnp.ndarray,   # [B, MaxP] int32 block tables
    positions: jnp.ndarray,  # [B, K] int32 — write positions per token
    layer,
    mesh=None,
    kv_sharded: bool = False,
    model_axis: str = "model",
    k_scale: jnp.ndarray | None = None,
    v_scale: jnp.ndarray | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray,
           jnp.ndarray | None, jnp.ndarray | None]:
    """Paged speculative-verify: write the K-row block through the block
    table (a block may cross a page boundary mid-dispatch), then attend
    each query over its table pages — index s valid iff s <=
    positions[b, k].  Positions at/past the table coverage are the
    inactive-slot sentinel: writes dropped, nothing attended.

    XLA path only, like the slot-layout ``verify_update_and_attend``: K is
    small (draft lengths 2-8), so the gather + [B, Hkv, G, K, S] scores
    stay modest; under a TP mesh the partitioner splits the Hkv axis the
    same way the paged XLA decode fallback does."""
    del mesh, kv_sharded, model_axis
    from arks_tpu.ops.paged_attention import (
        is_int4_pool, pool_page_tokens, unpack_int4_pool)
    b, kk, h, d_model = q.shape
    hkv = k_pool.shape[2]
    g = h // hkv
    int4 = is_int4_pool(k_pool, k_scale)
    page = pool_page_tokens(k_pool, k_scale)
    cover = tables.shape[1] * page
    # Lane padding (see decode_update_and_attend): pad to the pool's stored
    # head dim, prescale q to keep the effective 1/sqrt(d_model) scale.
    d = k_pool.shape[-1]
    if d != d_model:
        q = _pad_last(q, d) * ((d / d_model) ** 0.5)
        k_new = _pad_last(k_new, d)
        v_new = _pad_last(v_new, d)
    quantized = k_scale is not None

    from arks_tpu.ops.paged_attention import (
        paged_gather_kv, paged_update_block_xla)
    kp, vp, ks, vs = paged_update_block_xla(
        k_pool, v_pool, k_scale, v_scale, k_new, v_new, positions, tables,
        layer)
    # int4 pools gather through the nibble unpack so the attend math below
    # sees a plain per-token int8 view (scale math is unchanged).
    kp_r = unpack_int4_pool(kp) if int4 else kp
    vp_r = unpack_int4_pool(vp) if int4 else vp
    kc = paged_gather_kv(kp_r, tables, layer)  # [B, Hkv, cover, D]
    vc = paged_gather_kv(vp_r, tables, layer)

    scale = 1.0 / (d ** 0.5)
    qg = jnp.transpose(q.reshape(b, kk, hkv, g, d),
                       (0, 2, 3, 1, 4))        # [B, Hkv, G, K, D]
    scores = jnp.einsum("bkgqd,bksd->bkgqs", qg, kc.astype(q.dtype),
                        preferred_element_type=jnp.float32) * scale
    if quantized:
        ksc = paged_gather_kv(ks, tables, layer)   # [B, Hkv, cover]
        vsc = paged_gather_kv(vs, tables, layer)
        scores = scores * ksc[:, :, None, None, :]
    valid = (jnp.arange(cover)[None, None] <= positions[:, :, None]) \
        & (positions[:, :, None] < cover)          # [B, K, S]
    scores = jnp.where(valid[:, None, None], scores, _NEG_INF)
    probs = _softmax(scores, axis=-1)
    if quantized:
        probs = probs * vsc[:, :, None, None, :]
    out = jnp.einsum("bkgqs,bksd->bkgqd", probs.astype(q.dtype),
                     vc.astype(q.dtype), preferred_element_type=jnp.float32)
    out = jnp.transpose(out, (0, 3, 1, 2, 4)).reshape(
        b, kk, h, d)[..., :d_model].astype(q.dtype)
    return out, kp, vp, ks, vs


def paged_mixed_update_and_attend(
    q: jnp.ndarray,        # [T, H, D] — flat mixed token batch
    k_new: jnp.ndarray,    # [T, Hkv, D]
    v_new: jnp.ndarray,
    k_pool: jnp.ndarray,   # [L, N, Hkv, P, D] page pool
    v_pool: jnp.ndarray,
    tables: jnp.ndarray,   # [B, MaxP] int32 — lane b == slot b
    token_slot: jnp.ndarray,   # [T] int32 slot per token (-1 = padding)
    token_pos: jnp.ndarray,    # [T] int32 global position per token
    seq_q_start: jnp.ndarray,  # [B] int32 — lane's first flat-token index
    seq_q_len: jnp.ndarray,    # [B] int32 — lane's token count (0 inactive)
    seq_pos_start: jnp.ndarray,  # [B] int32 — lane's first global position
    layer,
    mesh=None,
    kv_sharded: bool = False,
    impl: str | None = None,
    model_axis: str = "model",
    k_scale: jnp.ndarray | None = None,
    v_scale: jnp.ndarray | None = None,
    window: int = 0,
    sink: jnp.ndarray | None = None,   # [H] — a sink logit a query head
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray,
           jnp.ndarray | None, jnp.ndarray | None]:
    """Mixed prefill+decode attention over one flat token batch: write every
    token's KV row through its slot's block table, then attend token t
    (slot b = token_slot[t], global position p = token_pos[t]) over that
    slot's pages at positions [0, p] — causal within a prefill chunk, the
    plain decode read for q_len-1 lanes, in ONE op.  Padding tokens
    (token_slot < 0) drop their writes and attend nothing.

    The per-token view (token_slot/token_pos) drives the KV write and the
    XLA oracle; the per-lane view (seq_q_start/q_len/pos_start) drives the
    ragged Pallas kernel's work list.  The kernel reads its queries in
    blocks of block_q rows, one block per real (lane, q block) pair
    (``paged_attention.paged_mixed_attention_flat``): the layout costs
    ``lanes + ceil(chunk budget / block_q)`` blocks whatever the batch
    holds, filled by one gather and read back by one.  A lane's rows are
    contiguous from ``seq_q_start``.  Returns
    (out [T, H, D], k_pool, v_pool, k_scale, v_scale).

    ``window`` > 0 (a window layer): token t attends positions
    ``(p - window, p]`` only.  The work list then starts at the page that
    holds ``p - window + 1`` of an item's first query and the kernel masks
    the keys below the bound inside it; table entries before that page are
    never read, so the caller may have released them.  The same
    ``pallas_call``, told the bound, under a name of its own
    (``paged_window_attention_ragged``); the scopes are ``arks.attn_win_*``
    (set by the caller's layer).

    The values may be narrower than the keys (``v_new [T, Hkv, Dv]`` and a
    value pool of its own stored width): the result is ``[T, H, Dv]``.
    ``sink``: one learnt logit a query head in the softmax denominator,
    which takes mass and has no value (both paths; the kernel's running
    maximum and sum start from it)."""
    from arks_tpu.ops.paged_attention import (
        is_int4_pool, pool_page_tokens, unpack_int4_pool)
    t_flat, h, d_model = q.shape
    dv_model = v_new.shape[-1]
    hkv = k_pool.shape[2]
    g = h // hkv
    int4 = is_int4_pool(k_pool, k_scale)
    page = pool_page_tokens(k_pool, k_scale)
    cover = tables.shape[1] * page
    d, dv = k_pool.shape[-1], v_pool.shape[-1]
    if d != d_model:
        # Lane padding (see decode_update_and_attend): pad to the stored
        # head dim, prescale q to keep the effective 1/sqrt(d_model) scale.
        q = _pad_last(q, d) * ((d / d_model) ** 0.5)
        k_new = _pad_last(k_new, d)
    v_new = _pad_last(v_new, dv)
    if sink is not None:
        sink = sink.astype(jnp.float32).reshape(hkv, g)
    quantized = k_scale is not None
    use_pallas = _use_pallas(impl, d, mesh, kv_sharded, model_axis)

    tables_tok = jnp.take(tables, jnp.maximum(token_slot, 0),
                          axis=0)                       # [T, MaxP]
    write_idx = jnp.where(token_slot < 0, cover, token_pos)

    if not use_pallas:
        from arks_tpu.ops.paged_attention import paged_gather_kv, paged_update_xla
        # The XLA oracle has no layout step of its own: all of it reads as
        # the kernel in a profile (scope names: docs/monitoring.md).
        with jax.named_scope("arks.attn_win_kernel" if window
                             else "arks.attn_kernel"):
            kp, vp, ks, vs = paged_update_xla(
                k_pool, v_pool, k_scale, v_scale, k_new, v_new, write_idx,
                tables_tok, layer)
            # int4 pools gather through the nibble unpack — the oracle
            # attend sees a plain per-token int8 view.
            kc = paged_gather_kv(unpack_int4_pool(kp) if int4 else kp,
                                 tables_tok, layer)     # [T, Hkv, cover, D]
            vc = paged_gather_kv(unpack_int4_pool(vp) if int4 else vp,
                                 tables_tok, layer)
            attend_lens = jnp.where(token_slot < 0, 0, token_pos + 1)
            lower = (attend_lens - window,) if window else ()
            if quantized:
                ksc = paged_gather_kv(ks, tables_tok, layer)
                vsc = paged_gather_kv(vs, tables_tok, layer)
                out = _decode_attention_xla_quant(
                    q.reshape(t_flat, hkv, g, d), kc, vc, ksc, vsc,
                    attend_lens, *lower, sink=sink)
            else:
                out = decode_attention_xla(q.reshape(t_flat, hkv, g, d), kc,
                                           vc, attend_lens, *lower,
                                           sink=sink)
        return out.reshape(t_flat, h, dv)[..., :dv_model], kp, vp, ks, vs

    from arks_tpu.ops.paged_attention import (
        paged_kv_update, paged_kv_update_quant, paged_mixed_attention_flat,
    )
    interpret = jax.default_backend() != "tpu"

    # A window layer's ops carry scopes of their own (arks.attn_win_*), so
    # that a profile tells the kinds apart; window is static, so the names
    # are too.
    sc_kernel, sc_layout = (("arks.attn_win_kernel", "arks.attn_win_layout")
                            if window else
                            ("arks.attn_kernel", "arks.attn_layout"))

    def local(qg, kn, vn, kp, vp, ks, vs, tbl, tok_tbl, widx, tslot,
              q_start, qlen, pos0, lyr, snk=None):
        with jax.named_scope(sc_kernel):
            if quantized:
                kp, vp, ks, vs = paged_kv_update_quant(
                    kp, vp, ks, vs, kn, vn, widx, tok_tbl, lyr,
                    interpret=interpret)
            else:
                kp, vp = paged_kv_update(kp, vp, kn, vn, widx, tok_tbl, lyr,
                                         interpret=interpret)
        # arks.attn_layout: what stands between the projections and the
        # Pallas call and back — ONE gather of the flat rows into the
        # kernel's block-compacted query layout (a block of block_q rows
        # per real (lane, q block) pair) and ONE gather of the T flat rows
        # back out of its output (the pallas_call alone carries
        # arks.attn_kernel: the innermost scope names an op).
        with jax.named_scope(sc_layout):
            out = paged_mixed_attention_flat(
                qg, kp, vp, tbl, tslot, q_start, qlen, pos0, lyr,
                k_scale=ks, v_scale=vs, interpret=interpret,
                window=window, sink=snk)
        return out, kp, vp, ks, vs

    qg = q.reshape(t_flat, hkv, g, d)
    if mesh is None or mesh.size == 1:
        out, kp, vp, ks, vs = local(qg, k_new, v_new, k_pool, v_pool,
                                    k_scale, v_scale, tables, tables_tok,
                                    write_idx, token_slot, seq_q_start,
                                    seq_q_len, seq_pos_start, layer, sink)
        return out.reshape(t_flat, h, dv)[..., :dv_model], kp, vp, ks, vs

    if sink is not None:
        raise NotImplementedError("a sink logit under a device mesh (the "
                                  "block has no sharding rules)")
    from jax.sharding import PartitionSpec as P
    model = model_axis if kv_sharded else None
    qspec = P(None, model, None, None)
    kvspec = P(None, model, None)
    pspec = P(None, None, model, None, None)
    sspec = P(None, None, model, None) if quantized else None
    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(qspec, kvspec, kvspec, pspec, pspec, sspec, sspec,
                  P(None, None), P(None, None), P(None), P(None), P(None),
                  P(None), P(None), P()),
        out_specs=(qspec, pspec, pspec, sspec, sspec),
        check_vma=False,
    )
    out, kp, vp, ks, vs = fn(qg, k_new, v_new, k_pool, v_pool,
                             k_scale, v_scale, tables, tables_tok,
                             write_idx, token_slot, seq_q_start, seq_q_len,
                             seq_pos_start, jnp.asarray(layer, jnp.int32))
    return out.reshape(t_flat, h, dv)[..., :dv_model], kp, vp, ks, vs


def latent_kernel_blockers(r_store: int, dv: int, mesh=None) -> list[str]:
    """Why the Pallas latent-page kernels cannot serve this shape (empty:
    they can); :func:`kernel_blockers`' counterpart for a latent pool.
    ``r_store`` is the STORED row width, ``dv`` the value lanes."""
    out = []
    if jax.default_backend() == "tpu" and (r_store % 128 or dv % 128):
        out.append(f"latent row {r_store} / value lanes {dv} not 128-lane "
                   "aligned")
    if mesh is not None and mesh.size > 1:
        out.append("a device mesh (the latent pool is not sharded)")
    return out


def paged_latent_update_and_attend(
    q: jnp.ndarray,          # [T, H, R] absorbed queries [q~ | q_rope]
    row_new: jnp.ndarray,    # [T, R] latent rows [c_kv | k_rope]
    pool: jnp.ndarray,       # [L, N, 1, P, R_store] the latent pool
    tables: jnp.ndarray,     # [B, MaxP] int32 — lane b == slot b
    token_slot: jnp.ndarray,   # [T] int32 slot per token (-1 = padding)
    token_pos: jnp.ndarray,    # [T] int32 global position per token
    seq_q_start: jnp.ndarray,  # [B] int32
    seq_q_len: jnp.ndarray,    # [B] int32
    seq_pos_start: jnp.ndarray,  # [B] int32
    layer,
    *,
    dv: int,
    scale: float,
    impl: str | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """:func:`paged_mixed_update_and_attend` over a LATENT pool: one row a
    token (the normed latent and the rotary key lanes) is key and value of
    every head.  Writes the rows through the block tables, then row t
    attends its slot's rows at positions [0, token_pos[t]]:
    ``softmax(scale * q . row) @ row[:dv]`` per head.  Returns
    (out [T, H, dv], pool).  The Pallas path is the ragged mixed kernel
    over the same block layout and work list with Hkv = 1 and the H heads
    as the query group; the XLA gather below is the CPU path and the
    tests' oracle.  Callers decide between them with
    :func:`latent_kernel_blockers`: there is no silent fallback here."""
    from arks_tpu.ops.paged_attention import paged_gather_kv
    t_flat, h, _ = q.shape
    page, r = pool.shape[3], pool.shape[4]
    cover = tables.shape[1] * page
    q = _pad_last(q, r)
    row_new = _pad_last(row_new, r)
    tables_tok = jnp.take(tables, jnp.maximum(token_slot, 0), axis=0)
    write_idx = jnp.where(token_slot < 0, cover, token_pos)

    if (impl or default_decode_impl()) != "pallas":
        with jax.named_scope("arks.attn_kernel"):
            # Padding rows go to a page past the pool, which jit drops.
            oob = write_idx >= cover
            safe = jnp.where(oob, 0, write_idx)
            pg = jnp.take_along_axis(tables_tok, (safe // page)[:, None],
                                     axis=1)[:, 0]
            pg = jnp.where(oob, pool.shape[1], pg)
            pool = pool.at[layer, pg, 0, safe % page].set(
                row_new.astype(pool.dtype))
            rows = paged_gather_kv(pool, tables_tok, layer)[:, 0]  # [T,S,R]
            scores = jnp.einsum("thr,tsr->ths", q, rows,
                                preferred_element_type=jnp.float32) * scale
            lens = jnp.where(token_slot < 0, 0, token_pos + 1)
            valid = jnp.arange(cover)[None] < lens[:, None]
            scores = jnp.where(valid[:, None], scores, _NEG_INF)
            probs = _softmax(scores, axis=-1).astype(rows.dtype)
            out = jnp.einsum("ths,tsv->thv", probs, rows[..., :dv],
                             preferred_element_type=jnp.float32)
        return out.astype(q.dtype), pool

    from arks_tpu.ops.paged_attention import (
        paged_kv_update, paged_mixed_attention_flat)
    interpret = jax.default_backend() != "tpu"
    with jax.named_scope("arks.mla_kv"):       # the page write
        pool, _ = paged_kv_update(pool, None, row_new[:, None, :], None,
                                  write_idx, tables_tok, layer,
                                  interpret=interpret)
    with jax.named_scope("arks.attn_layout"):
        out = paged_mixed_attention_flat(
            q[:, None], pool, None, tables, token_slot, seq_q_start,
            seq_q_len, seq_pos_start, layer, interpret=interpret,
            latent_v=dv, scale=scale)
    return out[:, 0], pool


def paged_decode_update_and_attend(
    q: jnp.ndarray,        # [B, H, D]
    k_new: jnp.ndarray,    # [B, Hkv, D]
    v_new: jnp.ndarray,
    k_pool: jnp.ndarray,   # [L, N, Hkv, P, D] page pool
    v_pool: jnp.ndarray,
    tables: jnp.ndarray,   # [B, MaxP] int32 block tables
    write_idx: jnp.ndarray,  # [B] int32 (>= MaxP*P = inactive: write dropped)
    layer,
    mesh=None,
    kv_sharded: bool = False,
    impl: str | None = None,
    model_axis: str = "model",
    k_scale: jnp.ndarray | None = None,
    v_scale: jnp.ndarray | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray,
           jnp.ndarray | None, jnp.ndarray | None]:
    """Paged counterpart of ``decode_update_and_attend``: the row lands in
    the slot's table-mapped page; attention reads only table pages.  A
    ``write_idx`` at/el beyond the table's coverage marks an INACTIVE slot:
    its write is dropped and it attends nothing (the engine parks freed
    slots there so their garbage dispatch rows cannot corrupt shared
    pages).

    dp meshes are not supported (tables index one global pool); the engine
    falls back to the slot-contiguous layout there.
    """
    from arks_tpu.ops.paged_attention import (
        is_int4_pool, pool_page_tokens, unpack_int4_pool)
    b, h, d_model = q.shape
    hkv = k_pool.shape[2]
    g = h // hkv
    int4 = is_int4_pool(k_pool, k_scale)
    page = pool_page_tokens(k_pool, k_scale)
    cover = tables.shape[1] * page
    # Lane padding (see the slot op): pad to the pool's stored head dim,
    # prescale q so the kernels' 1/sqrt(stored d) nets to 1/sqrt(d_model).
    d = k_pool.shape[-1]
    if d != d_model:
        q = _pad_last(q, d) * ((d / d_model) ** 0.5)
        k_new = _pad_last(k_new, d)
        v_new = _pad_last(v_new, d)
    quantized = k_scale is not None
    # int4 pools have no standalone decode kernel (decode traffic rides the
    # mixed kernel's fused dequant): this dedicated-decode entry takes the
    # XLA oracle, which kernel_blockers names for the engine's label too.
    use_pallas = _use_pallas(impl, d, mesh, kv_sharded, model_axis,
                             int4_decode=int4)
    # Inactive slots attend nothing (their stale tables may point at pages
    # other slots now own — reading them is wasted bandwidth at best).
    attend_lens = jnp.where(write_idx >= cover, 0, write_idx + 1)

    if not use_pallas:
        from arks_tpu.ops.paged_attention import paged_gather_kv, paged_update_xla
        kp, vp, ks, vs = paged_update_xla(
            k_pool, v_pool, k_scale, v_scale, k_new, v_new, write_idx,
            tables, layer)
        kc = paged_gather_kv(unpack_int4_pool(kp) if int4 else kp,
                             tables, layer)
        vc = paged_gather_kv(unpack_int4_pool(vp) if int4 else vp,
                             tables, layer)
        if quantized:
            ksc = paged_gather_kv(ks, tables, layer)
            vsc = paged_gather_kv(vs, tables, layer)
            out = _decode_attention_xla_quant(
                q.reshape(b, hkv, g, d), kc, vc, ksc, vsc, attend_lens)
        else:
            out = decode_attention_xla(q.reshape(b, hkv, g, d), kc, vc,
                                       attend_lens)
        return out.reshape(b, h, d)[..., :d_model], kp, vp, ks, vs

    from arks_tpu.ops.paged_attention import (
        paged_decode_attention, paged_kv_update, paged_kv_update_quant,
    )
    interpret = jax.default_backend() != "tpu"

    def local(qg, kn, vn, kp, vp, ks, vs, tbl, widx, alens, lyr):
        if quantized:
            kp, vp, ks, vs = paged_kv_update_quant(
                kp, vp, ks, vs, kn, vn, widx, tbl, lyr, interpret=interpret)
        else:
            kp, vp = paged_kv_update(kp, vp, kn, vn, widx, tbl, lyr,
                                     interpret=interpret)
        out = paged_decode_attention(qg, kp, vp, tbl, alens, lyr,
                                     k_scale=ks, v_scale=vs,
                                     interpret=interpret)
        return out, kp, vp, ks, vs

    qg = q.reshape(b, hkv, g, d)
    if mesh is None or mesh.size == 1:
        out, kp, vp, ks, vs = local(qg, k_new, v_new, k_pool, v_pool,
                                    k_scale, v_scale, tables, write_idx,
                                    attend_lens, layer)
        return out.reshape(b, h, d)[..., :d_model], kp, vp, ks, vs

    from jax.sharding import PartitionSpec as P
    model = model_axis if kv_sharded else None
    qspec = P(None, model, None, None)
    kvspec = P(None, model, None)
    pspec = P(None, None, model, None, None)
    sspec = P(None, None, model, None) if quantized else None
    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(qspec, kvspec, kvspec, pspec, pspec, sspec, sspec,
                  P(None, None), P(None), P(None), P()),
        out_specs=(qspec, pspec, pspec, sspec, sspec),
        check_vma=False,
    )
    out, kp, vp, ks, vs = fn(qg, k_new, v_new, k_pool, v_pool,
                             k_scale, v_scale, tables, write_idx,
                             attend_lens, jnp.asarray(layer, jnp.int32))
    return out.reshape(b, h, d)[..., :d_model], kp, vp, ks, vs


def decode_update_and_attend(
    q: jnp.ndarray,        # [B, H, D] — this step's query per slot
    k_new: jnp.ndarray,    # [B, Hkv, D] — this step's KV per slot
    v_new: jnp.ndarray,
    k_cache: jnp.ndarray,  # [L, B, Hkv, S, D] — FULL stacked cache
    v_cache: jnp.ndarray,
    write_idx: jnp.ndarray,  # [B] int32 — tokens already in cache per slot
    layer,                 # int32 — layer whose rows/blocks this step touches
    mesh=None,
    batch_axis: str | None = None,
    kv_sharded: bool = False,
    impl: str | None = None,
    model_axis: str = "model",
    k_scale: jnp.ndarray | None = None,  # [L, B, Hkv, S] f32 — int8 caches
    v_scale: jnp.ndarray | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray,
           jnp.ndarray | None, jnp.ndarray | None]:
    """Write this step's KV row at ``write_idx`` of ``layer``, then attend
    over the valid prefix (now ``write_idx + 1`` entries).  Returns
    (out [B, H, D], kc, vc, k_scale, v_scale).

    Takes the full stacked cache so the decode layer loop can carry it and
    the Pallas path (pallas_attention) can update/read it IN PLACE: both a
    row scatter and a per-layer slice/re-stack lower to whole-cache HBM
    traffic in XLA — each costs more than the rest of the model combined.

    With ``k_scale``/``v_scale`` the caches are int8 with per-token scales:
    the update quantizes this step's rows, attention dequantizes in VMEM —
    half the HBM read width where decode is bandwidth-bound.

    Under a mesh the op is embarrassingly parallel over (batch, kv-head), so
    the kernels run inside ``shard_map`` with no collectives; when kv heads
    don't divide the TP axis (replicated-KV regime) we stay on the XLA path,
    which the partitioner reshards automatically.
    """
    b, h, d_model = q.shape
    hkv = k_cache.shape[2]
    g = h // hkv
    # Lane padding: a cache stored wider than the model head dim (see
    # transformer.cache_head_dim) lets d<128 models ride the compiled
    # kernels; inputs pad up here and the output slices back down.  The
    # kernels scale scores by 1/sqrt(stored d); prescaling q by
    # sqrt(d_store/d_model) restores the true 1/sqrt(d_model).
    d = k_cache.shape[-1]
    if d != d_model:
        q = _pad_last(q, d) * ((d / d_model) ** 0.5)
        k_new = _pad_last(k_new, d)
        v_new = _pad_last(v_new, d)
    quantized = k_scale is not None
    # The kernels also serve dp-only meshes (trivial model axis): the op is
    # embarrassingly parallel over batch.  The engine pads the cache for
    # d<128 models (ARKS_PAD_HEAD_DIM=0 disables) so they take the kernels
    # too; what is left is in kernel_blockers.
    use_pallas = _use_pallas(impl, d, mesh, kv_sharded, model_axis)

    if not use_pallas:
        from arks_tpu.ops.pallas_attention import quantize_kv

        kc_l = jax.lax.dynamic_index_in_dim(k_cache, layer, 0, keepdims=False)
        vc_l = jax.lax.dynamic_index_in_dim(v_cache, layer, 0, keepdims=False)
        b_idx = jnp.arange(b)[:, None]
        h_idx = jnp.arange(hkv)[None, :]
        if quantized:
            kq, ksn = quantize_kv(k_new)
            vq, vsn = quantize_kv(v_new)
            kc_l = kc_l.at[b_idx, h_idx, write_idx[:, None]].set(kq)
            vc_l = vc_l.at[b_idx, h_idx, write_idx[:, None]].set(vq)
            ks_l = jax.lax.dynamic_index_in_dim(k_scale, layer, 0, keepdims=False)
            vs_l = jax.lax.dynamic_index_in_dim(v_scale, layer, 0, keepdims=False)
            ks_l = ks_l.at[b_idx, h_idx, write_idx[:, None]].set(ksn)
            vs_l = vs_l.at[b_idx, h_idx, write_idx[:, None]].set(vsn)
            # Scales fold into the score/prob stages (same trick as the
            # Pallas kernel) — never materialize a dequantized f32 cache.
            out = _decode_attention_xla_quant(
                q.reshape(b, hkv, g, d), kc_l, vc_l, ks_l, vs_l, write_idx + 1)
            ks = jax.lax.dynamic_update_index_in_dim(k_scale, ks_l, layer, 0)
            vs = jax.lax.dynamic_update_index_in_dim(v_scale, vs_l, layer, 0)
        else:
            kc_l = kc_l.at[b_idx, h_idx, write_idx[:, None]].set(
                k_new.astype(k_cache.dtype))
            vc_l = vc_l.at[b_idx, h_idx, write_idx[:, None]].set(
                v_new.astype(v_cache.dtype))
            out = decode_attention_xla(q.reshape(b, hkv, g, d), kc_l, vc_l,
                                       write_idx + 1)
            ks, vs = k_scale, v_scale
        kc = jax.lax.dynamic_update_index_in_dim(k_cache, kc_l, layer, 0)
        vc = jax.lax.dynamic_update_index_in_dim(v_cache, vc_l, layer, 0)
        return out.reshape(b, h, d)[..., :d_model], kc, vc, ks, vs

    from arks_tpu.ops.pallas_attention import (
        kv_cache_update, kv_cache_update_quant, ragged_decode_attention,
    )
    interpret = jax.default_backend() != "tpu"
    block_s = knobs.get_int("ARKS_ATTN_BLOCK_S")
    block_b = knobs.get_int("ARKS_ATTN_BLOCK_B")

    def local(qg, kn, vn, kc, vc, ks, vs, widx, lyr):
        if quantized:
            kc, vc, ks, vs = kv_cache_update_quant(
                kc, vc, ks, vs, kn, vn, widx, lyr, interpret=interpret)
        else:
            kc, vc = kv_cache_update(kc, vc, kn, vn, widx, lyr,
                                     interpret=interpret)
        out = ragged_decode_attention(qg, kc, vc, widx + 1, lyr,
                                      k_scale=ks, v_scale=vs,
                                      block_s=block_s, block_b=block_b,
                                      interpret=interpret)
        return out, kc, vc, ks, vs

    qg = q.reshape(b, hkv, g, d)
    if mesh is None or mesh.size == 1:
        out, kc, vc, ks, vs = local(qg, k_new, v_new, k_cache, v_cache,
                                    k_scale, v_scale, write_idx, layer)
        return out.reshape(b, h, d)[..., :d_model], kc, vc, ks, vs

    from jax.sharding import PartitionSpec as P
    model = model_axis if kv_sharded else None
    qspec = P(batch_axis, model, None, None)
    kvspec = P(batch_axis, model, None)
    cspec = P(None, batch_axis, model, None, None)
    sspec = P(None, batch_axis, model, None) if quantized else None
    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(qspec, kvspec, kvspec, cspec, cspec, sspec, sspec,
                  P(batch_axis), P()),
        out_specs=(qspec, cspec, cspec, sspec, sspec),
        check_vma=False,
    )
    out, kc, vc, ks, vs = fn(qg, k_new, v_new, k_cache, v_cache,
                             k_scale, v_scale, write_idx,
                             jnp.asarray(layer, jnp.int32))
    return out.reshape(b, h, d)[..., :d_model], kc, vc, ks, vs
