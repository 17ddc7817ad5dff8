"""Ragged decode attention + in-place KV update as Pallas TPU kernels.

The reference delegates its attention hot loop to vLLM/SGLang CUDA kernels
(paged attention) inside runtime containers; the TPU build owns it.  This is
the TPU formulation of the same idea: decode reads **only the valid prefix**
of each slot's KV cache instead of the full masked cache, which matters
because decode is HBM-bandwidth-bound — at long contexts the KV read *is*
the step time.

Both kernels operate on the FULL stacked cache ``[L, B, Hkv, S, D]`` with the
layer index as a scalar-prefetch argument.  That shape is load-bearing: the
decode layer loop carries the whole cache and each layer touches only its
rows/blocks.  Any formulation that materializes a per-layer slice (e.g.
scanning over the cache as scan xs/ys) makes XLA re-stack the entire cache
every step — measured ~20ms/step at [28, 32, 2, 4096, 128], more than the
rest of the model combined.

Design (flash-decoding / JetStream-ragged style):
- Cache layout ``[.., Hkv, S, D]``: each (slot, kv-head)'s sequence is
  contiguous, so a KV block DMA is one dense stripe.
- Attention grid ``(B / block_b, S / block_s)``: each program owns a *group*
  of slots and ALL kv heads — decode GQA matmuls are tiny ([G, D] x
  [D, block_s]), so per-program work must be batched or grid overhead
  dominates.  Scores for the whole group ride one batched dot_general.
- Per-slot ``lengths`` (and per-group maxima) ride scalar prefetch (SMEM) so
  both the kernel body and the BlockSpec index maps see them.  KV blocks past
  a group's max length are skipped two ways: the index map pins the block
  index (Mosaic issues no DMA for a revisited block) and ``pl.when`` skips
  the compute.  The engine packs similar-length slots into adjacent groups
  to make the skip effective under mixed lengths.
- Online softmax in f32 scratch (m/l/acc) across the KV-block grid axis;
  output written once on the final block.

The attention kernel is numerically identical (up to f32 accumulation order)
to ``arks_tpu.ops.attention.decode_attention_xla``, which stays as the XLA
fallback and the CPU test oracle.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _attn_kernel(layer_ref, glens_ref, lens_ref, q_ref, k_ref, v_ref, *rest,
                 block_b: int, block_s: int, scale: float, quantized: bool):
    del layer_ref  # consumed by the index maps
    if quantized:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    bi = pl.program_id(0)
    si = pl.program_id(1)
    num_blocks = pl.num_programs(1)

    @pl.when(si == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    block_start = si * block_s

    @pl.when(block_start < glens_ref[bi])
    def _block():
        bb, hkv, g, d = q_ref.shape
        # Mosaic matmul takes at most ONE batch dim: fold (slot-group, head)
        # into it for the dots; the leading-dim reshapes are layout no-ops.
        q = q_ref[:].reshape(bb * hkv, g, d)
        # int8 caches: convert WITHOUT scaling (one elementwise pass over
        # [block_s, D]); the per-token scales fold into the [G, block_s]
        # score/prob stage below, D/G times cheaper than row dequant.
        k = k_ref[0].reshape(bb * hkv, block_s, d).astype(q.dtype)
        v = v_ref[0].reshape(bb * hkv, block_s, d).astype(q.dtype)
        # [block_b*Hkv, G, block_s] — one batched MXU contraction for the
        # whole slot group.
        scores = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale
        scores = scores.reshape(bb, hkv, g, block_s)
        if quantized:
            # K scales: zero for never-written rows, but those are beyond
            # ``lens`` and masked to -inf right after (order matters: 0 * a
            # finite score is fine, 0 * -inf would be NaN).
            scores = scores * ks_ref[0].reshape(bb, hkv, 1, block_s)
        pos = block_start + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 3)
        lens = lens_ref[0]  # [block_b, 1]
        scores = jnp.where(pos < lens[:, None, None, :], scores, _NEG_INF)

        m_prev = m_ref[:]  # [block_b, Hkv, G, 128] lane-replicated
        l_prev = l_ref[:]
        m_curr = jnp.max(scores, axis=3, keepdims=True)
        m_next = jnp.maximum(m_prev, jnp.broadcast_to(m_curr, m_prev.shape))
        correction = jnp.exp(m_prev - m_next)
        p = jnp.exp(scores - m_next[..., :1])  # [block_b, Hkv, G, block_s]
        l_curr = jnp.sum(p, axis=3, keepdims=True)
        l_next = l_prev * correction + jnp.broadcast_to(l_curr, l_prev.shape)
        if quantized:
            # V scales fold into the probabilities (p >= 0, vs >= 0).
            p = p * vs_ref[0].reshape(bb, hkv, 1, block_s)
        # [block_b*Hkv, G, D] → [block_b, Hkv, G, D]
        pv = jax.lax.dot_general(
            p.astype(v.dtype).reshape(bb * hkv, g, block_s), v,
            (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32).reshape(bb, hkv, g, d)
        acc_ref[:] = acc_ref[:] * correction[..., :1] + pv
        m_ref[:] = m_next
        l_ref[:] = l_next

    @pl.when(si == num_blocks - 1)
    def _finish():
        # +eps keeps empty slots (length 0) finite; their output is unused.
        out = acc_ref[:] / (l_ref[..., :1] + 1e-9)
        o_ref[:] = out.astype(o_ref.dtype)


# Mosaic's default scoped-VMEM limit on a v5e is 16 MiB.  The BlockSpec
# pipeline double-buffers the K and V blocks, so they get half of it; the
# q/out blocks, scales and the f32 m/l/acc scratch live in the rest.
_KV_TILE_VMEM_BYTES = 8 * 2**20


def _pick_block_b(b: int, target: int) -> int:
    best = 1
    for cand in range(1, min(b, target) + 1):
        if b % cand == 0:
            best = cand
    return best


@functools.partial(jax.jit, static_argnames=("block_s", "block_b", "interpret"))
def ragged_decode_attention(
    q: jnp.ndarray,        # [B, Hkv, G, D] — one query token per slot
    k_cache: jnp.ndarray,  # [L, B, Hkv, S, D] — full stacked cache
    v_cache: jnp.ndarray,
    lengths: jnp.ndarray,  # [B] int32 — valid KV entries per slot
    layer,                 # int32 — which layer's blocks to read
    k_scale: jnp.ndarray | None = None,  # [L, B, Hkv, S] f32 (int8 caches)
    v_scale: jnp.ndarray | None = None,
    block_s: int = 256,
    block_b: int = 16,
    interpret: bool = False,
) -> jnp.ndarray:
    """Returns [B, Hkv, G, D] attention output, reading only valid KV blocks
    of layer ``layer``.  With ``k_scale``/``v_scale`` the caches are int8
    rows dequantized in VMEM (per-token scales)."""
    b, hkv, g, d = q.shape
    s = k_cache.shape[3]
    block_s = min(block_s, s)
    if s % block_s != 0:
        raise ValueError(f"cache len {s} not divisible by block_s {block_s}")
    quantized = k_scale is not None
    # 2 arrays (K, V) x 2 pipeline buffers of [block_b, Hkv, block_s, D]:
    # bf16 at the default block_b=16 / block_s=256 / Hkv=4 / D=128 is
    # exactly 16 MiB and the chip's compiler refuses it.
    slot_bytes = 4 * hkv * block_s * d * k_cache.dtype.itemsize
    block_b = _pick_block_b(
        b, max(1, min(block_b, _KV_TILE_VMEM_BYTES // slot_bytes)))
    num_groups = b // block_b
    num_blocks = s // block_s
    scale = 1.0 / (d ** 0.5)
    lengths = lengths.astype(jnp.int32)
    # Per-group max length: the index map's skip signal (a group's KV block is
    # read iff ANY slot in the group still needs it).
    group_lens = jnp.max(lengths.reshape(num_groups, block_b), axis=1)
    layer_arr = jnp.asarray(layer, jnp.int32).reshape(1)

    def q_map(bi, si, layer, glens):
        del si, layer, glens
        return (bi, 0, 0, 0)

    def lens_map(bi, si, layer, glens):
        del si, layer, glens
        return (bi, 0, 0)

    def _pin(bi, si, glens):
        # Pin out-of-range blocks to the group's LAST VALID block (the one
        # just visited): Mosaic skips the DMA for an unchanged block index,
        # so invalid KV is never read from HBM.
        last_valid = jnp.maximum(glens[bi] - 1, 0) // block_s
        valid = si * block_s < glens[bi]
        return jax.lax.select(valid, si, last_valid)

    def kv_map(bi, si, layer, glens):
        return (layer[0], bi, 0, _pin(bi, si, glens), 0)

    def scale_map(bi, si, layer, glens):
        return (layer[0], bi, 0, _pin(bi, si, glens))

    in_specs = [
        pl.BlockSpec((1, block_b, 1), lens_map),
        pl.BlockSpec((block_b, hkv, g, d), q_map),
        pl.BlockSpec((1, block_b, hkv, block_s, d), kv_map),
        pl.BlockSpec((1, block_b, hkv, block_s, d), kv_map),
    ]
    inputs = [layer_arr, group_lens,
              lengths.reshape(num_groups, block_b)[..., None], q,
              k_cache, v_cache]
    if quantized:
        in_specs += [pl.BlockSpec((1, block_b, hkv, block_s), scale_map),
                     pl.BlockSpec((1, block_b, hkv, block_s), scale_map)]
        inputs += [k_scale, v_scale]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(num_groups, num_blocks),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_b, hkv, g, d), q_map),
        scratch_shapes=[
            pltpu.VMEM((block_b, hkv, g, 128), jnp.float32),  # m (lane-replicated)
            pltpu.VMEM((block_b, hkv, g, 128), jnp.float32),  # l
            pltpu.VMEM((block_b, hkv, g, d), jnp.float32),    # acc
        ],
    )
    kernel = functools.partial(_attn_kernel, block_b=block_b, block_s=block_s,
                               scale=scale, quantized=quantized)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="ragged_decode_attention",
    )(*inputs)


# ---------------------------------------------------------------------------
# In-place KV cache row update
# ---------------------------------------------------------------------------
#
# XLA lowers the decode-step KV scatter (one [Hkv, D] row per slot at a
# data-dependent position) to a full-cache rewrite.  This kernel aliases the
# stacked cache in place and DMAs exactly the touched rows' aligned chunks:
# O(B * Hkv * D) bytes per step instead of the whole cache.

_UPDATE_CHUNK = 16  # bf16 sublane tile: DMA slices along S must be 16-aligned


def _update_kernel(layer_ref, idx_ref, kn_ref, vn_ref, kc_in, vc_in,
                   kc_out, vc_out, kscr, vscr, sem):
    del kc_in, vc_in  # aliased with the outputs; write through the out refs
    b, hkv, _, d = kn_ref.shape
    s = kc_out.shape[3]
    ch = _UPDATE_CHUNK
    lyr = layer_ref[0]

    def body(i, _):
        # Out-of-range writes (idx >= S) are dropped, matching JAX scatter
        # semantics on the XLA path — never corrupt a valid interior row.
        @pl.when(idx_ref[i] < s)
        def _():
            _write_row(i)
        return 0

    def _write_row(i):
        idx = idx_ref[i]
        base = (idx // ch) * ch
        # Read-modify-write of the aligned chunk containing row ``idx``:
        # single unaligned rows can't be DMA'd under bf16 sublane packing.
        dst_k = kc_out.at[pl.ds(lyr, 1), pl.ds(i, 1), :, pl.ds(base, ch)]
        dst_v = vc_out.at[pl.ds(lyr, 1), pl.ds(i, 1), :, pl.ds(base, ch)]
        rk = pltpu.make_async_copy(dst_k, kscr, sem.at[0])
        rv = pltpu.make_async_copy(dst_v, vscr, sem.at[1])
        rk.start()
        rv.start()
        rk.wait()
        rv.wait()
        row = jax.lax.broadcasted_iota(jnp.int32, (1, 1, hkv, ch, d), 3)
        hit = row == (idx - base)
        kscr[:] = jnp.where(hit, kn_ref[pl.ds(i, 1)][None], kscr[:])
        vscr[:] = jnp.where(hit, vn_ref[pl.ds(i, 1)][None], vscr[:])
        wk = pltpu.make_async_copy(kscr, dst_k, sem.at[0])
        wv = pltpu.make_async_copy(vscr, dst_v, sem.at[1])
        wk.start()
        wv.start()
        wk.wait()
        wv.wait()

    jax.lax.fori_loop(0, b, body, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def kv_cache_update(
    k_cache: jnp.ndarray,  # [L, B, Hkv, S, D] — full stacked cache
    v_cache: jnp.ndarray,
    k_new: jnp.ndarray,    # [B, Hkv, D]
    v_new: jnp.ndarray,
    write_idx: jnp.ndarray,  # [B] int32
    layer,                 # int32
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Write one KV row per slot at ``write_idx`` of layer ``layer``, in
    place. Returns the (aliased) updated caches."""
    _, b, hkv, s, d = k_cache.shape
    if s % _UPDATE_CHUNK != 0:
        raise ValueError(f"cache len {s} must be a multiple of {_UPDATE_CHUNK}")
    kn = k_new.astype(k_cache.dtype)[:, :, None, :]  # [B, Hkv, 1, D]
    vn = v_new.astype(v_cache.dtype)[:, :, None, :]
    layer_arr = jnp.asarray(layer, jnp.int32).reshape(1)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=(pl.BlockSpec(memory_space=pl.ANY),
                   pl.BlockSpec(memory_space=pl.ANY)),
        scratch_shapes=[
            pltpu.VMEM((1, 1, hkv, _UPDATE_CHUNK, d), k_cache.dtype),
            pltpu.VMEM((1, 1, hkv, _UPDATE_CHUNK, d), v_cache.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    return pl.pallas_call(
        _update_kernel,
        grid_spec=grid_spec,
        out_shape=(jax.ShapeDtypeStruct(k_cache.shape, k_cache.dtype),
                   jax.ShapeDtypeStruct(v_cache.shape, v_cache.dtype)),
        # Inputs indexed with scalar-prefetch args first: 0=layer, 1=idx,
        # 2=kn, 3=vn, 4=k_cache, 5=v_cache.
        input_output_aliases={4: 0, 5: 1},
        interpret=interpret,
        name="kv_cache_update",
    )(layer_arr, write_idx.astype(jnp.int32), kn, vn, k_cache, v_cache)


# ---------------------------------------------------------------------------
# int8 KV quantization
# ---------------------------------------------------------------------------

_SCALE_CHUNK = 128  # f32 lane tile: scale RMW slices along S are 128-aligned


def quantize_kv(x: jnp.ndarray, axis: int = -1,
                qmax: int = 127) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Symmetric per-token quantization: returns (q int8, scale f32) with
    the scale axis removed. ``axis`` is the reduced (feature) axis.
    ``qmax`` is the integer range: 127 for int8 pools, 7 for int4 pools
    (values in [-7, 7] so each fits a sign-extended nibble)."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=axis)
    scale = jnp.maximum(amax / float(qmax), 1e-8)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / jnp.expand_dims(scale, axis)),
                 -qmax, qmax).astype(jnp.int8)
    return q, scale


_UPDATE_CHUNK_INT8 = 32  # int8 sublane tile is (32, 128)


def _update_quant_kernel(layer_ref, idx_ref, kn_ref, vn_ref, ksn_ref, vsn_ref,
                         kc_in, vc_in, kss_in, vss_in,
                         kc_out, vc_out, kss_out, vss_out,
                         kscr, vscr, ksscr, vsscr, sem):
    del kc_in, vc_in, kss_in, vss_in  # aliased with outputs
    b, hkv, _, d = kn_ref.shape
    s = kc_out.shape[3]
    ch = _UPDATE_CHUNK_INT8
    sch = _SCALE_CHUNK
    lyr = layer_ref[0]

    def body(i, _):
        @pl.when(idx_ref[i] < s)
        def _():
            _write_row(i)
        return 0

    def _write_row(i):
        idx = idx_ref[i]
        base = (idx // ch) * ch
        sbase = (idx // sch) * sch
        dst_k = kc_out.at[pl.ds(lyr, 1), pl.ds(i, 1), :, pl.ds(base, ch)]
        dst_v = vc_out.at[pl.ds(lyr, 1), pl.ds(i, 1), :, pl.ds(base, ch)]
        dst_ks = kss_out.at[pl.ds(lyr, 1), pl.ds(i, 1), :, pl.ds(sbase, sch)]
        dst_vs = vss_out.at[pl.ds(lyr, 1), pl.ds(i, 1), :, pl.ds(sbase, sch)]
        copies = [pltpu.make_async_copy(dst_k, kscr, sem.at[0]),
                  pltpu.make_async_copy(dst_v, vscr, sem.at[1]),
                  pltpu.make_async_copy(dst_ks, ksscr, sem.at[2]),
                  pltpu.make_async_copy(dst_vs, vsscr, sem.at[3])]
        for c in copies:
            c.start()
        for c in copies:
            c.wait()
        row = jax.lax.broadcasted_iota(jnp.int32, (1, 1, hkv, ch, d), 3)
        hit = row == (idx - base)
        kscr[:] = jnp.where(hit, kn_ref[pl.ds(i, 1)][None], kscr[:])
        vscr[:] = jnp.where(hit, vn_ref[pl.ds(i, 1)][None], vscr[:])
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, 1, hkv, sch), 3)
        shit = lane == (idx - sbase)
        ksn = ksn_ref[pl.ds(i, 1)].reshape(1, 1, hkv, 1)
        vsn = vsn_ref[pl.ds(i, 1)].reshape(1, 1, hkv, 1)
        ksscr[:] = jnp.where(shit, ksn, ksscr[:])
        vsscr[:] = jnp.where(shit, vsn, vsscr[:])
        back = [pltpu.make_async_copy(kscr, dst_k, sem.at[0]),
                pltpu.make_async_copy(vscr, dst_v, sem.at[1]),
                pltpu.make_async_copy(ksscr, dst_ks, sem.at[2]),
                pltpu.make_async_copy(vsscr, dst_vs, sem.at[3])]
        for c in back:
            c.start()
        for c in back:
            c.wait()

    jax.lax.fori_loop(0, b, body, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def kv_cache_update_quant(
    k_cache: jnp.ndarray,  # [L, B, Hkv, S, D] int8
    v_cache: jnp.ndarray,
    k_scale: jnp.ndarray,  # [L, B, Hkv, S] f32
    v_scale: jnp.ndarray,
    k_new: jnp.ndarray,    # [B, Hkv, D] (bf16/f32 — quantized here)
    v_new: jnp.ndarray,
    write_idx: jnp.ndarray,  # [B] int32
    layer,                 # int32
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Quantize this step's KV rows to int8 + per-token scale and write both
    in place. Returns (kc, vc, k_scale, v_scale), all aliased."""
    _, b, hkv, s, d = k_cache.shape
    if s % _SCALE_CHUNK != 0:
        raise ValueError(f"int8 cache len {s} must be a multiple of {_SCALE_CHUNK}")
    kq, ks = quantize_kv(k_new)  # [B, Hkv, D] int8, [B, Hkv] f32
    vq, vs = quantize_kv(v_new)
    layer_arr = jnp.asarray(layer, jnp.int32).reshape(1)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)] * 4
        + [pl.BlockSpec(memory_space=pl.ANY)] * 4,
        out_specs=tuple([pl.BlockSpec(memory_space=pl.ANY)] * 4),
        scratch_shapes=[
            pltpu.VMEM((1, 1, hkv, _UPDATE_CHUNK_INT8, d), k_cache.dtype),
            pltpu.VMEM((1, 1, hkv, _UPDATE_CHUNK_INT8, d), v_cache.dtype),
            pltpu.VMEM((1, 1, hkv, _SCALE_CHUNK), jnp.float32),
            pltpu.VMEM((1, 1, hkv, _SCALE_CHUNK), jnp.float32),
            pltpu.SemaphoreType.DMA((4,)),
        ],
    )
    return pl.pallas_call(
        _update_quant_kernel,
        grid_spec=grid_spec,
        out_shape=(jax.ShapeDtypeStruct(k_cache.shape, k_cache.dtype),
                   jax.ShapeDtypeStruct(v_cache.shape, v_cache.dtype),
                   jax.ShapeDtypeStruct(k_scale.shape, jnp.float32),
                   jax.ShapeDtypeStruct(v_scale.shape, jnp.float32)),
        # 0=layer, 1=idx, 2=kq, 3=vq, 4=ks, 5=vs, 6=kc, 7=vc, 8=kss, 9=vss.
        input_output_aliases={6: 0, 7: 1, 8: 2, 9: 3},
        interpret=interpret,
        name="kv_cache_update_quant",
    )(layer_arr, write_idx.astype(jnp.int32),
      kq[:, :, None, :], vq[:, :, None, :], ks, vs,
      k_cache, v_cache, k_scale, v_scale)
