"""North-star benchmark on real hardware: Qwen2.5-7B on one TPU chip.

Output contract: the LAST stdout line is the result JSON
({"metric", "value", "unit", "vs_baseline", ...extras}).  A raw-loop
checkpoint line precedes the final combined line so a run killed mid-
serving still leaves parsable evidence; consumers must take the last
line, not parse the whole stream.
Baseline: BASELINE.md north star — >=2,000 tok/s/chip decode throughput AND
p50 TTFT < 200 ms on Qwen2.5-7B (the reference publishes no numbers of its
own; these targets come from BASELINE.json).  ``vs_baseline`` is computed on
this 7B config — not on a smaller stand-in.

Configuration mirrors the production serving defaults on a 16GB v5e chip:
int8 weight-only quantization (w8a16 — bf16 weights alone are ~15GB and do
not fit next to a KV cache; see arks_tpu/models/quant.py) and int8 KV cache
(the engine's kv_cache_dtype=auto resolution on TPU).

Two measurements:
- Decode throughput: the fused multi-step decode loop (K decode steps +
  greedy sampling inside one jitted scan) — one dispatch per K tokens, host
  transfer limited to sampled ids.  The host fetch of the sampled ids is
  the completion barrier.
- TTFT: single-prompt prefill (bucketed length) + first-token argmax, host
  fetch of the sampled id as the completion barrier; p50 over trials.

Env knobs: ARKS_BENCH_MODEL (default qwen2.5-7b), ARKS_BENCH_BATCH,
ARKS_BENCH_CACHE_LEN, ARKS_BENCH_STEPS, ARKS_BENCH_TRIALS,
ARKS_BENCH_PROMPT_LEN (TTFT prompt length, default 1024),
ARKS_BENCH_KV_DTYPE (int8|bf16), ARKS_BENCH_WEIGHT_DTYPE (int8|bf16).
"""

from __future__ import annotations

import functools
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

BASELINE_TOK_S_CHIP = 2000.0
TARGET_TTFT_MS = 200.0


def pallas_parity_check(kv_quant: bool) -> float:
    """On-device parity: the Pallas decode path (cache update + ragged
    attention) vs the XLA oracle on the same random inputs — the compiled-TPU
    counterpart of the interpret-mode unit tests (tests/
    test_pallas_attention.py necessarily run interpret on CPU).  Returns the
    max |pallas - xla| over the attention output; the shapes satisfy the
    kernel tiling constraints (S % 256, B % 16)."""
    from arks_tpu.ops.attention import decode_update_and_attend

    L, B, Hkv, G, S, D = 2, 16, 4, 7, 512, 128
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 8)
    q = jax.random.normal(ks[0], (B, Hkv * G, D), jnp.bfloat16)
    k_new = jax.random.normal(ks[1], (B, Hkv, D), jnp.bfloat16)
    v_new = jax.random.normal(ks[2], (B, Hkv, D), jnp.bfloat16)
    if kv_quant:
        kc = jax.random.randint(ks[3], (L, B, Hkv, S, D), -127, 128, jnp.int8)
        vc = jax.random.randint(ks[4], (L, B, Hkv, S, D), -127, 128, jnp.int8)
        kscale = jax.random.uniform(ks[5], (L, B, Hkv, S), jnp.float32, 0.01, 0.03)
        vscale = jax.random.uniform(ks[6], (L, B, Hkv, S), jnp.float32, 0.01, 0.03)
    else:
        kc = jax.random.normal(ks[3], (L, B, Hkv, S, D), jnp.bfloat16)
        vc = jax.random.normal(ks[4], (L, B, Hkv, S, D), jnp.bfloat16)
        kscale = vscale = None
    widx = jnp.arange(B, dtype=jnp.int32) * 17 % (S - 1)
    layer = jnp.asarray(1, jnp.int32)

    def run(impl):
        out, *_ = jax.jit(functools.partial(
            decode_update_and_attend, impl=impl))(
            q, k_new, v_new, kc, vc, widx, layer,
            k_scale=kscale, v_scale=vscale)
        return np.asarray(out, np.float32)

    diff = float(np.max(np.abs(run("pallas") - run("xla"))))

    # Lane-padded small-head case (head_dim 64 stored at 128): the padded
    # kernel path must agree with the XLA oracle on device too.
    Dp = 64
    qs = jax.random.normal(ks[7], (B, Hkv * G, Dp), jnp.bfloat16)
    kns = jax.random.normal(ks[0], (B, Hkv, Dp), jnp.bfloat16)
    vns = jax.random.normal(ks[1], (B, Hkv, Dp), jnp.bfloat16)
    if kv_quant:
        kcp = jax.random.randint(ks[2], (L, B, Hkv, S, 128), -127, 128, jnp.int8)
        vcp = jax.random.randint(ks[3], (L, B, Hkv, S, 128), -127, 128, jnp.int8)
        # Padded lanes must be ZERO (real caches only ever write padded
        # rows) — random int8 there would differ from the oracle's view.
        lane = jnp.arange(128) < Dp
        kcp = jnp.where(lane, kcp, 0)
        vcp = jnp.where(lane, vcp, 0)
        kvargs = dict(k_scale=kscale, v_scale=vscale)
    else:
        kcp = jnp.zeros((L, B, Hkv, S, 128), jnp.bfloat16)
        vcp = jnp.zeros((L, B, Hkv, S, 128), jnp.bfloat16)
        kvargs = dict(k_scale=None, v_scale=None)

    def run_pad(impl):
        out, *_ = jax.jit(functools.partial(
            decode_update_and_attend, impl=impl))(
            qs, kns, vns, kcp, vcp, widx, layer, **kvargs)
        return np.asarray(out, np.float32)

    pad_diff = float(np.max(np.abs(run_pad("pallas") - run_pad("xla"))))
    return max(diff, pad_diff)


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


def measure_gqa_bytes_sweep() -> dict:
    """GQA head-group sweep (g in {1, 4, 8}), plan-only — no kernel
    launches, so tests can gate on it cheaply.  The head-grouped DMA
    restructure wins KV bytes THROUGH block_q: grouping shrinks a work
    item's VMEM footprint by hkv/head_group, the tuned plan re-invests
    that headroom in a larger q block, and fewer q blocks re-stream each
    causal page prefix fewer times.  Emits the bytes-moved counter pair
    (mixed_kv_bytes actual vs fetch-each-block-once ideal) for the
    ungrouped baseline vs the grouped tuned plan; the g=8 row is the
    acceptance shape (ratio >= g)."""
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
                block_q=_gqa_vmem_block_q(hg, g), grid="ragged",
                head_group=hg)
            b_act, b_ideal = mixed_kv_bytes(
                pos, ql, page=page, block_q=plan["block_q"],
                num_qb=plan["num_qb"], max_pages=maxp, hkv=hkv,
                page_head_bytes=phb)
            byt[name] = b_act
            out[f"gqa_g{g}_{name}_block_q"] = plan["block_q"]
            out[f"gqa_g{g}_{name}_kv_bytes"] = b_act
            out[f"gqa_g{g}_kv_bytes_ideal"] = b_ideal
        out[f"gqa_g{g}_bytes_ratio"] = round(byt["base"] / byt["grouped"],
                                             2)
    return out


def measure_kernel_microbench() -> dict:
    """Mixed-kernel microbench rung: dense vs ragged grid x int8 vs int4
    KV x default vs tuned block_q, on a SPARSE batch (3 active lanes of 8)
    — the shape the ragged work-list grid exists for.  Runs in interpret
    mode on CPU so the rung rides every bench round; interpret-mode
    timings order the work (grid steps executed), they are not TPU
    latencies — the grid_steps_* pair is the load-bearing number there.
    Under ARKS_KERNEL_TUNE=sweep the winning block_q is persisted to the
    autotune table, so a bench round doubles as the tuning pass."""
    from arks_tpu.engine.paged import mixed_grid_steps
    from arks_tpu.ops import autotune
    from arks_tpu.ops import paged_attention as pa
    from arks_tpu.ops.pallas_attention import quantize_kv

    on_tpu = jax.default_backend() == "tpu"
    interpret = not on_tpu
    s, hkv, g, maxp = 8, 2, 2, 4
    d = 128 if on_tpu else 32
    page = 128 if on_tpu else 16
    qmax = 8
    repeats = 3 if on_tpu else 2
    rng = np.random.default_rng(0)
    kf = jnp.asarray(rng.normal(size=(1, s * maxp, hkv, page, d)),
                     jnp.float32)
    vf = jnp.asarray(rng.normal(size=kf.shape), jnp.float32)
    k8, ks = quantize_kv(kf)
    v8, vs = quantize_kv(vf)
    k4q, k4s = quantize_kv(kf, qmax=7)
    v4q, v4s = quantize_kv(vf, qmax=7)
    pools = {
        "int8": (k8, v8, ks, vs),
        "int4": (pa.pack_int4(k4q, axis=3), pa.pack_int4(v4q, axis=3),
                 k4s, v4s),
    }
    tables = jnp.arange(s * maxp, dtype=jnp.int32).reshape(s, maxp)
    q = jnp.asarray(rng.normal(size=(s, hkv, g, qmax, d)), jnp.float32)
    # 3 active lanes (one full chunk, one mid-page decode burst, one
    # short), 5 idle — the padding the dense grid pays for.
    pos = np.zeros(s, np.int32)
    ql = np.zeros(s, np.int32)
    pos[:3], ql[:3] = (0, page + 3, 5), (qmax, qmax, 3)
    posj, qlj = jnp.asarray(pos), jnp.asarray(ql)

    def timeit(fn):
        fn()  # compile/warm outside the timed window
        t0 = time.perf_counter()
        for _ in range(repeats):
            fn()
        return round((time.perf_counter() - t0) / repeats * 1e3, 2)

    out: dict = {}
    for kv_name, (kp, vp, kss, vss) in pools.items():
        for grid in ("ragged", "dense"):
            def launch(block_q=None, dma_depth=None):
                r = pa.paged_mixed_attention(
                    q, kp, vp, tables, posj, qlj, 0, k_scale=kss,
                    v_scale=vss, block_q=block_q, interpret=interpret,
                    grid=grid, dma_depth=dma_depth)
                np.asarray(r)  # host fetch = completion barrier
            out[f"mixed_{grid}_{kv_name}_default_ms"] = timeit(launch)
            # Tuned: best block_q over the candidate set; a sweep-mode run
            # persists it under this shape's signature for serving reuse.
            cands = [{"block_q": b, "dma_depth": 2} for b in (2, qmax)]
            timed = {c["block_q"]: timeit(lambda c=c: launch(**c))
                     for c in cands}
            best_bq = min(timed, key=timed.get)
            out[f"mixed_{grid}_{kv_name}_tuned_ms"] = timed[best_bq]
            out[f"mixed_{grid}_{kv_name}_tuned_block_q"] = best_bq
            if grid == "ragged" and autotune.mode() == "sweep":
                autotune.record(
                    "paged_mixed",
                    autotune.mixed_signature(hkv=hkv, g=g, d=d, page=page,
                                             qmax=qmax, kv=kv_name),
                    {"block_q": best_bq, "dma_depth": 2})
    # The structural number (hardware-independent): page-compute steps the
    # ragged grid executes vs the dense grid's S*num_qb*max_pages padding.
    plan = pa.mixed_grid_plan(qmax, hkv=hkv, g=g, d=d, page=page, kv="int8")
    ideal, dense = mixed_grid_steps(pos, ql, page=page,
                                    block_q=plan["block_q"],
                                    num_qb=plan["num_qb"], max_pages=maxp)
    out["grid_steps_ideal"] = ideal
    out["grid_steps_dense"] = dense
    out.update(measure_gqa_bytes_sweep())

    # Kernel launches on the g=8 acceptance shape: all three schedules
    # (dense grid, ungrouped ragged, grouped ragged) must agree BITWISE,
    # and the grouped tuned plan times alongside.
    hkv8, d8, page8, maxp8, qmax8 = _GQA_SHAPE
    pos8 = np.zeros(4, np.int32)
    ql8 = np.zeros(4, np.int32)
    pos8[:2] = (maxp8 * page8 - qmax8, page8)
    ql8[:2] = (qmax8, 8)
    g8 = 8
    kf8 = jnp.asarray(rng.normal(size=(1, 2 * maxp8, hkv8, page8, d8)),
                      jnp.float32)
    vf8 = jnp.asarray(rng.normal(size=kf8.shape), jnp.float32)
    t8 = jnp.arange(2 * maxp8, dtype=jnp.int32).reshape(2, maxp8)
    q8 = jnp.asarray(rng.normal(size=(2, hkv8, g8, qmax8, d8)), jnp.float32)
    p8j, q8j = jnp.asarray(pos8[:2]), jnp.asarray(ql8[:2])

    def launch8(block_q, head_group, grid):
        r = pa.paged_mixed_attention(
            q8, kf8, vf8, t8, p8j, q8j, 0, block_q=block_q,
            interpret=interpret, grid=grid, head_group=head_group)
        return np.asarray(r)

    base_bq8, tuned_bq8 = _gqa_vmem_block_q(hkv8, g8), _gqa_vmem_block_q(1, g8)
    o_dense = launch8(base_bq8, hkv8, "dense")
    o_base = launch8(base_bq8, hkv8, "ragged")
    o_grp = launch8(tuned_bq8, 1, "ragged")
    out["gqa_g8_bitwise"] = bool(np.array_equal(o_dense, o_base)
                                 and np.array_equal(o_base, o_grp))
    out["gqa_g8_base_ms"] = timeit(
        lambda: launch8(base_bq8, hkv8, "ragged"))
    out["gqa_g8_grouped_ms"] = timeit(
        lambda: launch8(tuned_bq8, 1, "ragged"))
    return out


def measure_mixed_ttft_under_load() -> float:
    """p50 TTFT (ms) of chunk-length prompts admitted while EVERY decode
    slot is busy — the decode+prefill contention number the mixed scheduler
    (ARKS_MIXED_STEP) exists to bound: legacy chunking pays one extra full
    dispatch per chunk while all decode slots stall; the mixed step folds
    the chunk into the decode dispatch.

    Runs a real InferenceEngine (paged + mixed) at a small, fixed shape so
    the measurement rides every bench round without a second 7B init;
    ARKS_BENCH_MIXED_MODEL overrides (default qwen2.5-0.5b on TPU, tiny on
    CPU smoke runs)."""
    from arks_tpu.engine import EngineConfig, InferenceEngine
    from arks_tpu.engine.tokenizer import ByteTokenizer
    from arks_tpu.engine.types import Request, SamplingParams
    from arks_tpu.models import get_config

    on_tpu = jax.default_backend() == "tpu"
    model = os.environ.get("ARKS_BENCH_MIXED_MODEL",
                           "qwen2.5-0.5b" if on_tpu else "tiny")
    cfg = get_config(model)
    num_slots = int(os.environ.get("ARKS_BENCH_MIXED_SLOTS",
                                   "8" if on_tpu else "2"))
    chunk = 256 if on_tpu else 16
    ecfg = EngineConfig(model=model, num_slots=num_slots,
                        max_cache_len=1024 if on_tpu else 64,
                        prefill_buckets=(32, 64, 128, 256) if on_tpu
                        else (8, 16, 32),
                        steps_per_dispatch=4, prefill_chunk=chunk,
                        kv_layout="paged", prefix_cache_mb=0)
    eng = InferenceEngine(cfg, ecfg, ByteTokenizer())
    assert eng._mixed, "mixed step unexpectedly unsupported for the bench shape"
    eng.start()
    try:
        # Saturate all but one slot with long-running decodes (distinct
        # prompts so the prefix index never merges them); the probe takes
        # the last slot, its chunked prefill contending with the decodes.
        load = []
        for i in range(max(num_slots - 1, 1)):
            r = Request(f"load{i}", [3 + i, 7, 11],
                        SamplingParams(max_tokens=10_000, temperature=0.0,
                                       ignore_eos=True))
            load.append(r)
            eng.add_request(r)
        for r in load:
            r.outputs.get(timeout=300)  # first token = slot decoding
        # Chunk-length prompts admitted under full decode contention.
        plen = 3 * chunk + chunk // 2
        ttfts = []
        for i in range(int(os.environ.get("ARKS_BENCH_MIXED_TRIALS", "5"))):
            probe = Request(
                f"mixed{i}",
                [(7 + i + j) % cfg.vocab_size for j in range(plen)],
                SamplingParams(max_tokens=2, temperature=0.0,
                               ignore_eos=True))
            eng.add_request(probe)
            while True:
                out = probe.outputs.get(timeout=300)
                if out.ttft_s is not None:
                    ttfts.append(out.ttft_s * 1e3)
                if out.finished:
                    break
        for r in load:
            eng.abort(r.request_id)
        return float(np.percentile(ttfts, 50))
    finally:
        eng.stop()


def main() -> None:
    from arks_tpu.models import get_config
    from arks_tpu.models import quant
    from arks_tpu.models import transformer as tf

    model = os.environ.get("ARKS_BENCH_MODEL", "qwen2.5-7b")
    result: dict = {}
    # 192 beats 128 by ~9% and keeps ~2GB more HBM headroom than 256 on a
    # 16GB v5e (256 was only ~1% faster than 192 when measured).
    batch = int(os.environ.get("ARKS_BENCH_BATCH", "192"))
    cache_len = int(os.environ.get("ARKS_BENCH_CACHE_LEN", "1024"))
    # K sensitivity (b192, measured): 32 -> 6.44k, 64 -> 6.66k, 128 -> 6.78k
    # tok/s/chip.  32 stays the default: it matches a serving-realistic
    # scheduler granularity; bigger K trades admission latency for the
    # last ~5% by amortizing dispatch overhead further.
    steps = int(os.environ.get("ARKS_BENCH_STEPS", "32"))
    trials = int(os.environ.get("ARKS_BENCH_TRIALS", "3"))
    prompt_len = int(os.environ.get("ARKS_BENCH_PROMPT_LEN", "1024"))
    ttft_trials = int(os.environ.get("ARKS_BENCH_TTFT_TRIALS", "9"))
    kv_dtype = os.environ.get("ARKS_BENCH_KV_DTYPE", "int8")
    weight_dtype = os.environ.get("ARKS_BENCH_WEIGHT_DTYPE", "int8")
    kv_quant = kv_dtype == "int8"

    result["metric"] = (f"decode_throughput_{model}_b{batch}"
                        f"_w-{weight_dtype}_kv-{kv_dtype}")
    result["value"] = 0.0
    result["unit"] = "tok/s/chip"
    result["vs_baseline"] = 0.0

    from arks_tpu.utils import compile_cache
    compile_cache.configure()
    cfg = get_config(model)

    # Guided-decoding cold start: the host-side char-DFA + vocab-walk build
    # for JSON mode at this model's REAL vocab size — the latency the async
    # compile pipeline hides from the scheduler (it bounds added TTFT for
    # the first request per schema only; warm requests are a registry hit).
    # A byte-level vocab walks 1 byte per token where a merged-BPE vocab
    # walks ~word-length strings, so treat this as a floor, tracked across
    # BENCH rounds for regressions in the compile pipeline itself.
    try:
        from arks_tpu.engine.guides import GuideCompiler
        from arks_tpu.engine.tokenizer import ByteTokenizer
        gcomp = GuideCompiler(ByteTokenizer(), cfg.vocab_size, eos_ids=(0,))
        tg0 = time.perf_counter()
        gcomp.compile("json")
        result["guided_cold_start_s"] = round(time.perf_counter() - tg0, 3)
        del gcomp
    except Exception as e:
        result["guided_cold_start_error"] = f"{type(e).__name__}: {e}"

    n_chips = len(jax.devices())

    # ---- Raw-loop sections: fault-isolated so a failure here still leaves
    # a serving run + a parsable JSON line. ---------------------------------
    try:
        mesh = None
        if n_chips > 1:
            from arks_tpu.parallel.mesh import make_mesh
            mesh = make_mesh(tensor_parallel=n_chips)

        wbits = quant.weight_bits(weight_dtype)
        if wbits:
            params = quant.init_params_quantized(
                cfg, jax.random.PRNGKey(0), bits=wbits,
                shards=n_chips if n_chips > 1 else 1)
        else:
            params = tf.init_params(cfg, jax.random.PRNGKey(0))
        if mesh is not None:
            params = tf.shard_params(params, cfg, mesh)

        # -- TTFT: bucketed single-prompt prefill + first-token argmax
        # (UNLOADED — the loaded counterpart comes from the serving bench).
        def first_token(params, tokens, lengths):
            logits, ks, vs = tf.prefill(params, cfg, tokens, lengths, mesh)
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)

        prefill_fn = jax.jit(first_token)
        toks = jnp.zeros((1, prompt_len), jnp.int32)
        lens = jnp.asarray([prompt_len], jnp.int32)
        np.asarray(prefill_fn(params, toks, lens))  # warmup/compile
        ttft_ms = []
        for _ in range(ttft_trials):
            t0 = time.perf_counter()
            np.asarray(prefill_fn(params, toks, lens))  # host fetch = barrier
            ttft_ms.append((time.perf_counter() - t0) * 1e3)
        ttft_p50 = float(np.percentile(ttft_ms, 50))
        result["ttft_p50_ms"] = round(ttft_p50, 1)
        result["ttft_prompt_len"] = prompt_len
        result["ttft_vs_target"] = round(TARGET_TTFT_MS / ttft_p50, 3)

        # -- Decode throughput: fused multi-step loop
        cache = tf.init_cache(cfg, num_slots=batch, max_len=cache_len,
                              quantized=kv_quant)

        def multi_step(params, cache, tokens, lengths):
            def body(carry, _):
                cache, tokens, lengths = carry
                logits, cache = tf.decode_step(
                    params, cfg, cache, tokens, lengths, mesh)
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                return (cache, nxt, lengths + 1), nxt
            (cache, tokens, lengths), out = jax.lax.scan(
                body, (cache, tokens, lengths), None, length=steps)
            return cache, tokens, lengths, out

        fn = jax.jit(multi_step, donate_argnums=(1,))
        tokens = jnp.zeros((batch,), jnp.int32)
        # Mid-cache lengths: each decode step attends ~cache_len/2 of KV,
        # a representative steady-state working set.
        lengths = jnp.full((batch,), cache_len // 2, jnp.int32)

        cache, tokens, lengths, out = fn(params, cache, tokens, lengths)
        np.asarray(out[-1])  # warmup/compile

        best = float("inf")
        for _ in range(trials):
            lengths = jnp.full((batch,), cache_len // 2, jnp.int32)
            t0 = time.perf_counter()
            cache, tokens, lengths, out = fn(params, cache, tokens, lengths)
            np.asarray(out[-1])  # host fetch of ids = completion barrier
            best = min(best, time.perf_counter() - t0)

        tok_s_chip = batch * steps / best / max(n_chips, 1)
        result["value"] = round(tok_s_chip, 1)
        result["vs_baseline"] = round(tok_s_chip / BASELINE_TOK_S_CHIP, 3)
    except Exception as e:
        import traceback
        traceback.print_exc()
        result["raw_error"] = f"{type(e).__name__}: {e}"

    # TPU-side kernel parity rides every bench run: the Pallas decode path
    # must agree with the XLA oracle ON DEVICE, not just in CPU interpret
    # mode.  bf16 accumulation + (for int8) requantization of the new row
    # bound the tolerance.
    if jax.default_backend() == "tpu":  # interpret-mode parity is a unit test
        try:
            parity_diff = pallas_parity_check(kv_quant)
            result["pallas_parity_maxdiff"] = round(parity_diff, 5)
            result["pallas_parity_ok"] = \
                parity_diff < (0.075 if kv_quant else 0.05)
        except Exception as e:
            result["pallas_parity_error"] = f"{type(e).__name__}: {e}"

    # Kernel microbench rung: dense vs ragged mixed grid x int8/int4 KV x
    # default/tuned blocks on a sparse batch.  Fault-isolated;
    # ARKS_BENCH_KERNEL_MICRO=0 skips.
    if os.environ.get("ARKS_BENCH_KERNEL_MICRO", "1") != "0":
        try:
            result["kernel_microbench"] = measure_kernel_microbench()
        except Exception as e:
            import traceback
            traceback.print_exc()
            result["kernel_microbench_error"] = f"{type(e).__name__}: {e}"

    # Mixed-step TTFT under load: the decode+prefill-contention latency the
    # unified mixed dispatch (ARKS_MIXED_STEP) exists to bound.  Fault-
    # isolated like the raw loops; ARKS_BENCH_MIXED_TTFT=0 skips.
    if os.environ.get("ARKS_BENCH_MIXED_TTFT", "1") != "0":
        try:
            result["mixed_step_ttft_under_load_ms"] = round(
                measure_mixed_ttft_under_load(), 1)
        except Exception as e:
            import traceback
            traceback.print_exc()
            result["mixed_ttft_error"] = f"{type(e).__name__}: {e}"

    # Checkpoint line BEFORE the long serving phase: if the driver's
    # timeout kills this process mid-serving, the last printed JSON line
    # is still a parsed raw-loop result instead of nothing.  A completed
    # run prints the combined line after it, which then takes precedence
    # as the final line.
    print(json.dumps(result), flush=True)

    # Serving-path numbers (engine + OpenAI server + SSE under concurrent
    # load — bench_serving.py): the honest counterpart of the raw-loop
    # number above, and the number BASELINE.md actually specifies.
    # Raw-bench device buffers are dropped first so the serving engine's
    # params+cache fit HBM alongside nothing.
    if os.environ.get("ARKS_BENCH_SERVING", "1") != "0":
        import gc
        # Names are defined in this order; a mid-raw failure leaves a
        # prefix, and del stops at the first missing name — fine, the rest
        # were never created.
        try:
            del params, prefill_fn, cache, fn, tokens, lengths, out
        except NameError:
            pass
        gc.collect()
        try:
            from bench_serving import run_serving_bench
            result.update(run_serving_bench(model))
        except Exception as e:  # the raw-loop numbers must still print
            import traceback
            traceback.print_exc()
            result["serving_error"] = f"{type(e).__name__}: {e}"
        # Loaded TTFT vs the 200ms target rides the top-level pass/fail
        # fields next to the unloaded prefill number.
        lp50 = result.get("serving_ttft_p50_ms")
        if lp50:
            result["serving_ttft_vs_target"] = round(TARGET_TTFT_MS / lp50, 3)

    print(json.dumps(result))


if __name__ == "__main__":
    try:
        main()
    except BaseException as e:  # a failed run says so and exits non-zero
        import traceback
        traceback.print_exc()
        print(json.dumps({
            "metric": "bench_failed", "error": f"{type(e).__name__}: {e}"}))
        raise SystemExit(1)
