"""Pipeline parallelism: layer stages over a mesh axis, microbatch pipeline.

The reference has no pipeline-parallel code (its runtimes handle any model
parallelism internally; SURVEY.md §2.4); here PP is a first-class mesh axis
for training and offline forward passes over models deeper than one slice's
memory.

TPU-native formulation (collective-permute pipeline, scaling-book style):
- The stacked layer params [L, ...] shard their leading dim over the
  ``stage`` axis — no re-packing: each device simply holds L/S consecutive
  layers, and the per-stage body is the same ``lax.scan`` the unsharded
  model uses.
- The batch splits into M microbatches.  For M + S - 1 ticks, every stage
  runs its layers on its current microbatch and ``ppermute``s activations to
  the next stage over ICI.  Bubbles are computed-and-discarded (standard:
  utilization M / (M + S - 1)).
- The last stage accumulates outputs; a masked psum over the stage axis
  replicates them at the end.  Gradients flow backward through the
  ppermute/psum transposes automatically, so one ``jax.grad`` differentiates
  the whole pipeline.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from arks_tpu.models import transformer as tf
from arks_tpu.parallel.mesh import AXIS_STAGE


def shard_params_pp(params, mesh, stage_axis: str = AXIS_STAGE):
    """Shard the stacked layer dim over the stage axis; everything else
    (embed, final_norm, lm_head) replicated."""
    def put(x, spec):
        return jax.device_put(x, NamedSharding(mesh, spec))

    out = dict(params)
    out["layers"] = jax.tree.map(
        lambda x: put(x, P(stage_axis)), params["layers"])
    for k in ("embed", "final_norm", "lm_head"):
        if k in params:
            out[k] = put(params[k], P())
    return out


def pipeline_forward(
    params,
    cfg,
    tokens: jnp.ndarray,  # [B, T] int32
    mesh,
    num_microbatches: int,
    stage_axis: str = AXIS_STAGE,
) -> jnp.ndarray:
    """Hidden states [B, T, E] (pre-final-norm), replicated across stages."""
    num_stages = mesh.shape[stage_axis]
    if cfg.num_layers % num_stages != 0:
        raise ValueError(f"{cfg.num_layers} layers not divisible into "
                         f"{num_stages} stages")
    b, t = tokens.shape
    m = num_microbatches
    if b % m != 0:
        raise ValueError(f"batch {b} not divisible into {m} microbatches")
    mb = b // m
    x_mb = tokens.reshape(m, mb, t)

    def local(layers_local, embed, x_mb):
        s_ax = jax.lax.axis_size(stage_axis)
        s_id = jax.lax.axis_index(stage_axis)
        perm = [(i, (i + 1) % s_ax) for i in range(s_ax)]
        positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (mb, t))

        def run_stage(h):
            def body(h, lp):
                h, _, _ = tf.prefill_layer(h, lp, cfg, positions, None)
                return h, None
            h, _ = jax.lax.scan(body, h, layers_local)
            return h

        e = embed.shape[1]
        # Embed the whole microbatch stream ONCE (only stage 0's copy is
        # read, but hoisting it keeps the vocab-table gather out of the
        # per-tick loop on every stage).
        x_emb = jnp.take(embed, x_mb, axis=0)  # [M, mb, T, E]
        buf = jnp.zeros((mb, t, e), embed.dtype)
        outputs = jnp.zeros((m, mb, t, e), embed.dtype)

        def tick(carry, ti):
            buf, outputs = carry
            # Stage 0 feeds from the embedded microbatch stream; later
            # stages from the ring buffer.  Clamped indices during bubble
            # ticks write garbage that is overwritten before it's read
            # (microbatch i's real result lands at tick i + S - 1).
            x0 = jax.lax.dynamic_index_in_dim(
                x_emb, jnp.clip(ti, 0, m - 1), 0, keepdims=False)
            h_in = jnp.where(s_id == 0, x0, buf)
            h_out = run_stage(h_in)
            out_idx = jnp.clip(ti - (s_ax - 1), 0, m - 1)
            outputs = jax.lax.dynamic_update_slice(
                outputs, h_out[None].astype(outputs.dtype), (out_idx, 0, 0, 0))
            buf = jax.lax.ppermute(h_out, stage_axis, perm)
            return (buf, outputs), None

        (buf, outputs), _ = jax.lax.scan(
            tick, (buf, outputs), jnp.arange(m + s_ax - 1))
        mask = (s_id == s_ax - 1).astype(outputs.dtype)
        return jax.lax.psum(outputs * mask, stage_axis)

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(stage_axis), P(), P()),
        out_specs=P(),
        check_vma=False,
    )
    out = fn(params["layers"], params["embed"], x_mb)  # [M, mb, T, E]
    return out.reshape(b, t, -1)


def pp_loss_fn(params, cfg, tokens, targets, loss_mask, mesh,
               num_microbatches: int):
    from arks_tpu.train.sft import head_loss

    h = pipeline_forward(params, cfg, tokens, mesh, num_microbatches)
    return head_loss(params, cfg, h, targets, loss_mask)


def make_pp_train_step(cfg, optimizer, mesh, num_microbatches: int):
    """Jitted pipeline-parallel train step (same contract as
    arks_tpu.train.sft.make_train_step — shares its loss head and
    optimizer-step body)."""
    from arks_tpu.train.sft import make_step_fn

    step = make_step_fn(
        lambda params, tokens, targets, loss_mask: pp_loss_fn(
            params, cfg, tokens, targets, loss_mask, mesh, num_microbatches),
        optimizer)
    return jax.jit(step, donate_argnums=(0,))


def pp_train_init(cfg, key, optimizer, mesh, dtype=jnp.float32):
    from arks_tpu.train.sft import TrainState

    params = tf.init_params(cfg, key, dtype)
    params = shard_params_pp(params, mesh)
    opt_state = optimizer.init(params)
    return TrainState(params=params, opt_state=opt_state,
                      step=jnp.zeros((), jnp.int32))


# ---------------------------------------------------------------------------
# Serving: stage-sharded KV cache, pipelined decode, one-shot prefill
# ---------------------------------------------------------------------------


def shard_cache_pp(cache, mesh, stage_axis: str = AXIS_STAGE):
    """KV cache sharded over the STAGE axis on its layer dim: each stage
    holds only its own layers' KV — HBM capacity scales with stages, the
    lever serving PP exists for (models whose weights+KV exceed one chip)."""
    def put(x, spec):
        return jax.device_put(x, NamedSharding(mesh, spec))

    spec = P(stage_axis)
    return tf.KVCache(
        k=put(cache.k, spec), v=put(cache.v, spec),
        k_scale=put(cache.k_scale, spec) if cache.quantized else None,
        v_scale=put(cache.v_scale, spec) if cache.quantized else None)


def shard_paged_cache_pp(cache, mesh, stage_axis: str = AXIS_STAGE):
    """Paged pool sharded over the STAGE axis on its layer dim — the paged
    counterpart of ``shard_cache_pp``.  Pages (dim 1) stay whole: block
    tables index one global page id space and every stage holds its own
    layers' rows of each page."""
    def put(x, spec):
        return jax.device_put(x, NamedSharding(mesh, spec))

    spec = P(stage_axis)
    return tf.PagedKVCache(
        k=put(cache.k, spec), v=put(cache.v, spec),
        k_scale=put(cache.k_scale, spec) if cache.quantized else None,
        v_scale=put(cache.v_scale, spec) if cache.quantized else None)


def pp_decode_step_paged(
    params,
    cfg,
    cache,                 # PagedKVCache, pool sharded over ``stage`` on L
    tables: jnp.ndarray,   # [B, MaxP] int32 block tables
    tokens: jnp.ndarray,   # [B] int32
    lengths: jnp.ndarray,  # [B] int32
    mesh,
    num_microbatches: int,
    stage_axis: str = AXIS_STAGE,
):
    """One decode token for every slot against the PAGED pool, layers
    pipelined over stages — the paged counterpart of ``pp_decode_step``.

    The pool has no batch dim, so unlike the slot path there is no
    per-microbatch cache slice: the whole (stage-local) pool rides the
    tick carry and each microbatch writes through its rows of the block
    tables.  Bubble ticks skip via ``lax.cond`` (a bubble write through a
    clamped microbatch's tables would corrupt a REAL slot's pages); freed
    slots parked at the coverage sentinel are dropped inside the paged op,
    as on the single-stage path (transformer.decode_step).

    NOTE: the tick/bubble/clamp pipelining scaffolding here is the TWIN of
    ``pp_decode_step``'s — the two differ only in per-tick cache access
    (whole pool + table row here vs dynamic batch slice there).  A fix to
    the bubble-skip, out_idx clamp, or psum-collection logic in one almost
    certainly applies to the other.
    """
    num_stages = mesh.shape[stage_axis]
    if cfg.num_layers % num_stages != 0:
        raise ValueError(f"{cfg.num_layers} layers not divisible into "
                         f"{num_stages} stages")
    b = tokens.shape[0]
    m = num_microbatches
    if b % m != 0:
        raise ValueError(f"batch {b} not divisible into {m} microbatches")
    mbs = b // m
    quantized = cache.quantized
    compute_dtype = params["layers"]["attn_norm"].dtype
    page = cache.page
    cover = tables.shape[1] * page
    from arks_tpu.ops.attention import paged_decode_update_and_attend

    def local(layers_local, embed, kc, vc, ksc, vsc, tables, tokens, lengths):
        s_ax = jax.lax.axis_size(stage_axis)
        s_id = jax.lax.axis_index(stage_axis)
        perm = [(i, (i + 1) % s_ax) for i in range(s_ax)]
        toks_mb = tokens.reshape(m, mbs)
        lens_mb = lengths.reshape(m, mbs)
        tbl_mb = tables.reshape(m, mbs, -1)
        e = embed.shape[1]

        def run_stage(h, kc, vc, ksc, vsc, tbl, lens):
            write_idx = lens.astype(jnp.int32)
            # RoPE positions must be real for active slots; the sentinel
            # (>= coverage) only matters to the paged op, which drops it.
            rope_idx = jnp.minimum(write_idx, cover - 1)

            def body(carry, xs):
                h, kc, vc, ksc, vsc = carry
                lp, layer = xs
                x = tf.rms_norm(h, lp["attn_norm"], cfg.rms_norm_eps)
                q, k, v = tf._qkv(x, lp, cfg)
                q = tf.apply_rope(q, rope_idx, cfg.rope_theta)
                k = tf.apply_rope(k, rope_idx, cfg.rope_theta)
                # XLA impl for the same reason as the slot pp path: tiny
                # per-stage microbatches bind the kernels' batch tiling.
                attn, kc, vc, ksc, vsc = paged_decode_update_and_attend(
                    q, k, v, kc, vc, tbl, write_idx, layer, impl="xla",
                    k_scale=ksc, v_scale=vsc)
                attn = attn.reshape(mbs, cfg.q_dim)
                h = h + tf.qeinsum("bq,qe->be", attn, lp["wo"])
                h = h + tf._mlp(h, lp, cfg, None, None)
                return (h, kc, vc, ksc, vsc), None

            n_local = jax.tree.leaves(layers_local)[0].shape[0]
            (h, kc, vc, ksc, vsc), _ = jax.lax.scan(
                body, (h, kc, vc, ksc, vsc),
                (layers_local, jnp.arange(n_local, dtype=jnp.int32)))
            return h, kc, vc, ksc, vsc

        buf = jnp.zeros((mbs, e), compute_dtype)
        h_acc = jnp.zeros((m, mbs, e), compute_dtype)

        def tick(carry, ti):
            kc, vc, ksc, vsc, buf, h_acc = carry
            mi = ti - s_id
            valid = (mi >= 0) & (mi < m)
            mi_c = jnp.clip(mi, 0, m - 1)
            toks = jax.lax.dynamic_index_in_dim(toks_mb, mi_c, 0, keepdims=False)
            lens = jax.lax.dynamic_index_in_dim(lens_mb, mi_c, 0, keepdims=False)
            tbl = jax.lax.dynamic_index_in_dim(tbl_mb, mi_c, 0, keepdims=False)
            h0 = tf.embed_lookup(embed, toks, compute_dtype)
            h_in = jnp.where(s_id == 0, h0, buf)

            def do(h_in, kc, vc, ksc, vsc, tbl, lens):
                return run_stage(h_in, kc, vc, ksc, vsc, tbl, lens)

            def skip(h_in, kc, vc, ksc, vsc, tbl, lens):
                return jnp.zeros_like(h_in), kc, vc, ksc, vsc

            h_out, kc, vc, ksc, vsc = jax.lax.cond(
                valid, do, skip, h_in, kc, vc, ksc, vsc, tbl, lens)
            out_idx = jnp.clip(ti - (s_ax - 1), 0, m - 1)
            h_acc = jax.lax.dynamic_update_slice(
                h_acc, h_out[None].astype(h_acc.dtype), (out_idx, 0, 0))
            buf = jax.lax.ppermute(h_out, stage_axis, perm)
            return (kc, vc, ksc, vsc, buf, h_acc), None

        (kc, vc, ksc, vsc, buf, h_acc), _ = jax.lax.scan(
            tick, (kc, vc, ksc, vsc, buf, h_acc),
            jnp.arange(m + s_ax - 1))
        mask = (s_id == s_ax - 1).astype(h_acc.dtype)
        h_final = jax.lax.psum(h_acc * mask, stage_axis)
        return h_final, kc, vc, ksc, vsc

    cspec = P(stage_axis)
    sspec = cspec if quantized else None
    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(stage_axis), P(), cspec, cspec, sspec, sspec,
                  P(), P(), P()),
        out_specs=(P(), cspec, cspec, sspec, sspec),
        check_vma=False,
    )
    h, kc, vc, ksc, vsc = fn(params["layers"], params["embed"],
                             cache.k, cache.v, cache.k_scale, cache.v_scale,
                             tables, tokens, lengths)
    logits = tf._unembed(h.reshape(b, -1), params, cfg, None, None)
    return logits, tf.PagedKVCache(k=kc, v=vc, k_scale=ksc, v_scale=vsc)


def pp_decode_step(
    params,
    cfg,
    cache,
    tokens: jnp.ndarray,   # [B] int32
    lengths: jnp.ndarray,  # [B] int32
    mesh,
    num_microbatches: int,
    stage_axis: str = AXIS_STAGE,
):
    """One decode token for every slot, layers pipelined over stages.

    The batch splits into M microbatches of contiguous slots; for
    M + S - 1 ticks each stage advances one microbatch through its local
    layers (updating its local KV shard) and ``ppermute``s activations on.
    Bubble ticks run a ``lax.cond`` no-op branch: unlike activations
    (overwritten before read), a bubble CACHE write would corrupt a real
    slot's rows, so bubbles must genuinely skip.  The final hidden states
    are psum-collected from the last stage and unembedded OUTSIDE the
    shard_map — once, replicated, instead of S redundant vocab matmuls.

    The attention/update body runs the XLA path (impl="xla"): per-stage
    microbatches are small and kernel batch-tiling constraints would bind;
    PP's win is HBM capacity, not decode-kernel latency.

    NOTE: the tick/bubble/clamp pipelining scaffolding here is the TWIN of
    ``pp_decode_step_paged``'s (see its docstring) — keep fixes in sync.
    """
    num_stages = mesh.shape[stage_axis]
    if cfg.num_layers % num_stages != 0:
        raise ValueError(f"{cfg.num_layers} layers not divisible into "
                         f"{num_stages} stages")
    b = tokens.shape[0]
    m = num_microbatches
    if b % m != 0:
        raise ValueError(f"batch {b} not divisible into {m} microbatches")
    mbs = b // m
    quantized = cache.quantized
    compute_dtype = params["layers"]["attn_norm"].dtype
    from arks_tpu.ops.attention import decode_update_and_attend

    def local(layers_local, embed, kc, vc, ksc, vsc, tokens, lengths):
        s_ax = jax.lax.axis_size(stage_axis)
        s_id = jax.lax.axis_index(stage_axis)
        perm = [(i, (i + 1) % s_ax) for i in range(s_ax)]
        toks_mb = tokens.reshape(m, mbs)
        lens_mb = lengths.reshape(m, mbs)
        e = embed.shape[1]

        def run_stage(h, kc_mb, vc_mb, ks_mb, vs_mb, lens):
            write_idx = lens.astype(jnp.int32)

            def body(carry, xs):
                h, kc, vc, ks, vs = carry
                lp, layer = xs
                x = tf.rms_norm(h, lp["attn_norm"], cfg.rms_norm_eps)
                q, k, v = tf._qkv(x, lp, cfg)
                q = tf.apply_rope(q, write_idx, cfg.rope_theta)
                k = tf.apply_rope(k, write_idx, cfg.rope_theta)
                attn, kc, vc, ks, vs = decode_update_and_attend(
                    q, k, v, kc, vc, write_idx, layer, impl="xla",
                    k_scale=ks, v_scale=vs)
                attn = attn.reshape(mbs, cfg.q_dim)
                h = h + tf.qeinsum("bq,qe->be", attn, lp["wo"])
                h = h + tf._mlp(h, lp, cfg, None, None)
                return (h, kc, vc, ks, vs), None

            n_local = jax.tree.leaves(layers_local)[0].shape[0]
            (h, kc_mb, vc_mb, ks_mb, vs_mb), _ = jax.lax.scan(
                body, (h, kc_mb, vc_mb, ks_mb, vs_mb),
                (layers_local, jnp.arange(n_local, dtype=jnp.int32)))
            return h, kc_mb, vc_mb, ks_mb, vs_mb

        buf = jnp.zeros((mbs, e), compute_dtype)
        h_acc = jnp.zeros((m, mbs, e), compute_dtype)

        def tick(carry, ti):
            kc, vc, ksc, vsc, buf, h_acc = carry
            mi = ti - s_id
            valid = (mi >= 0) & (mi < m)
            mi_c = jnp.clip(mi, 0, m - 1)
            start = mi_c * mbs
            toks = jax.lax.dynamic_index_in_dim(toks_mb, mi_c, 0, keepdims=False)
            lens = jax.lax.dynamic_index_in_dim(lens_mb, mi_c, 0, keepdims=False)
            h0 = tf.embed_lookup(embed, toks, compute_dtype)
            h_in = jnp.where(s_id == 0, h0, buf)

            kc_mb = jax.lax.dynamic_slice_in_dim(kc, start, mbs, axis=1)
            vc_mb = jax.lax.dynamic_slice_in_dim(vc, start, mbs, axis=1)
            ks_mb = (jax.lax.dynamic_slice_in_dim(ksc, start, mbs, axis=1)
                     if quantized else None)
            vs_mb = (jax.lax.dynamic_slice_in_dim(vsc, start, mbs, axis=1)
                     if quantized else None)

            def do(h_in, kc_mb, vc_mb, ks_mb, vs_mb, lens):
                return run_stage(h_in, kc_mb, vc_mb, ks_mb, vs_mb, lens)

            def skip(h_in, kc_mb, vc_mb, ks_mb, vs_mb, lens):
                return jnp.zeros_like(h_in), kc_mb, vc_mb, ks_mb, vs_mb

            h_out, kc_mb, vc_mb, ks_mb, vs_mb = jax.lax.cond(
                valid, do, skip, h_in, kc_mb, vc_mb, ks_mb, vs_mb, lens)

            kc = jax.lax.dynamic_update_slice_in_dim(kc, kc_mb, start, 1)
            vc = jax.lax.dynamic_update_slice_in_dim(vc, vc_mb, start, 1)
            if quantized:
                ksc = jax.lax.dynamic_update_slice_in_dim(ksc, ks_mb, start, 1)
                vsc = jax.lax.dynamic_update_slice_in_dim(vsc, vs_mb, start, 1)
            # Last stage's h_out lands at its microbatch row (bubble-tick
            # garbage at clamped rows is overwritten before the psum reads
            # it — same trick as pipeline_forward).
            out_idx = jnp.clip(ti - (s_ax - 1), 0, m - 1)
            h_acc = jax.lax.dynamic_update_slice(
                h_acc, h_out[None].astype(h_acc.dtype), (out_idx, 0, 0))
            buf = jax.lax.ppermute(h_out, stage_axis, perm)
            return (kc, vc, ksc, vsc, buf, h_acc), None

        (kc, vc, ksc, vsc, buf, h_acc), _ = jax.lax.scan(
            tick, (kc, vc, ksc, vsc, buf, h_acc),
            jnp.arange(m + s_ax - 1))
        mask = (s_id == s_ax - 1).astype(h_acc.dtype)
        h_final = jax.lax.psum(h_acc * mask, stage_axis)
        return h_final, kc, vc, ksc, vsc

    cspec = P(stage_axis)
    sspec = cspec if quantized else None
    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(stage_axis), P(), cspec, cspec, sspec, sspec, P(), P()),
        out_specs=(P(), cspec, cspec, sspec, sspec),
        check_vma=False,
    )
    h, kc, vc, ksc, vsc = fn(params["layers"], params["embed"],
                             cache.k, cache.v, cache.k_scale, cache.v_scale,
                             tokens, lengths)
    logits = tf._unembed(h.reshape(b, -1), params, cfg, None, None)
    return logits, tf.KVCache(k=kc, v=vc, k_scale=ksc, v_scale=vsc)


def pp_prefill(
    params,
    cfg,
    tokens: jnp.ndarray,   # [B, T] int32, bucket-padded
    lengths: jnp.ndarray,  # [B] int32
    mesh,
    stage_axis: str = AXIS_STAGE,
):
    """One-shot serving prefill over stages.  Returns (last-token logits
    [B, V] f32 replicated, ks, vs time-major [L, B, T, Hkv, D] sharded over
    ``stage`` on L) — the same contract as transformer.prefill, so the
    engine's insert into a stage-sharded cache stays a local write.

    Single stream (serving prefills one prompt per dispatch), so no
    microbatch overlap: stages run in sequence, each contributing its
    layers; PP prefill trades bubbles for fitting the model at all.
    """
    num_stages = mesh.shape[stage_axis]
    if cfg.num_layers % num_stages != 0:
        raise ValueError(f"{cfg.num_layers} layers not divisible into "
                         f"{num_stages} stages")
    b, t = tokens.shape
    compute_dtype = params["layers"]["attn_norm"].dtype

    def local(layers_local, embed, tokens):
        s_ax = jax.lax.axis_size(stage_axis)
        s_id = jax.lax.axis_index(stage_axis)
        perm = [(i, (i + 1) % s_ax) for i in range(s_ax)]
        positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))

        def run_stage(h):
            def body(h, lp):
                h, k, v = tf.prefill_layer(h, lp, cfg, positions, None)
                return h, (k, v)
            return jax.lax.scan(body, h, layers_local)

        h = tf.embed_lookup(embed, tokens, compute_dtype)
        ks = vs = None
        # S sequential hops: stage s computes on hop s (earlier hops carry
        # zeros through it — cheap relative to fitting the model, and the
        # KV it produces on non-final hops is discarded by the where).
        for hop in range(num_stages):
            h_out, (k_hop, v_hop) = run_stage(h)
            keep = (s_id == hop)
            ks = k_hop if ks is None else jnp.where(keep, k_hop, ks)
            vs = v_hop if vs is None else jnp.where(keep, v_hop, vs)
            h = jax.lax.ppermute(h_out, stage_axis, perm)
        # After S hops the fully-processed h is back at stage 0; every
        # stage's ks/vs hold ITS layers' KV (the shard_map out_spec stacks
        # them into the global [L, ...]).
        mask = (s_id == 0).astype(h.dtype)
        h_final = jax.lax.psum(h * mask, stage_axis)
        return h_final, ks, vs

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(stage_axis), P(), P()),
        out_specs=(P(), P(stage_axis), P(stage_axis)),
        check_vma=False,
    )
    h, ks, vs = fn(params["layers"], params["embed"], tokens)
    h_last = jnp.take_along_axis(
        h, (lengths - 1)[:, None, None].astype(jnp.int32), axis=1)[:, 0]
    logits = tf._unembed(h_last, params, cfg, None, None)
    return logits, ks, vs
