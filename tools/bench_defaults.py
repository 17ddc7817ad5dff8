"""One-shot on-chip measurement for the pending kernel defaults.

Three kernel paths shipped without hardware numbers; this script captures
ALL of them in one run so a single command settles the defaults:

1. paged vs slot-contiguous decode attention at production shapes
   (delegates to tools/bench_kernels.py — the existing gate).
2. lane-padded d<128 decode (qwen2.5-0.5b shapes, head_dim 64 stored at
   128 so the Pallas kernels apply) vs the unpadded XLA fallback those
   models would otherwise ride — decides ARKS_PAD_HEAD_DIM's default.
3. MoE block-sparse grouped-matmul Pallas kernel vs jax.lax.ragged_dot at
   Mixtral-8x7B prefill shapes — decides ARKS_MOE_KERNEL's default.

Prints one JSON line per section.  Usage:
  timeout 1200 python tools/bench_defaults.py
Meaningful numbers only on real TPU (CPU runs interpret-mode kernels).
"""

from __future__ import annotations

import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np


def _best(fn, trials: int) -> float:
    out = fn()
    jax.block_until_ready(out)  # compile
    best = float("inf")
    for _ in range(trials):
        t0 = time.perf_counter()
        out = fn()
        np.asarray(jax.tree_util.tree_leaves(out)[0][..., :1])  # host barrier
        best = min(best, time.perf_counter() - t0)
    return best


def bench_paged_vs_slot() -> None:
    """Section 1: the existing microbench (one JSON line), in THIS process:
    a chip belongs to one process, and the later sections take it here."""
    import bench_kernels
    bench_kernels.main()

def bench_lane_padding(trials: int = 5) -> None:
    """Section 2: d=64 decode — padded Pallas (stored at 128 lanes) vs the
    unpadded XLA fallback, fused K-step L-layer loop at qwen2.5-0.5b-ish
    shapes (L24, Hkv2, G7, d64), b192 s1024 int8 KV."""
    from arks_tpu.ops.attention import decode_update_and_attend

    L, B, Hkv, G, S, D, K = 24, 192, 2, 7, 1024, 64, 32
    if os.environ.get("BD_SMOKE") == "1":  # CPU plumbing check only
        L, B, S, K, trials = 2, 16, 256, 2, 1
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 6)
    q = jax.random.normal(ks[0], (B, Hkv * G, D), jnp.bfloat16)
    kn = jax.random.normal(ks[1], (B, Hkv, D), jnp.bfloat16)
    vn = jax.random.normal(ks[2], (B, Hkv, D), jnp.bfloat16)
    lengths = (jnp.arange(B, dtype=jnp.int32) * 37) % (S - K - 1) + 1

    def mk_cache(d_store):
        kc = jax.random.randint(ks[3], (L, B, Hkv, S, d_store), -127, 128,
                                jnp.int8)
        vc = jax.random.randint(ks[4], (L, B, Hkv, S, d_store), -127, 128,
                                jnp.int8)
        if d_store != D:  # padded lanes hold zeros in real caches
            lane = jnp.arange(d_store) < D
            kc = jnp.where(lane, kc, 0)
            vc = jnp.where(lane, vc, 0)
        sc = jax.random.uniform(ks[5], (L, B, Hkv, S), jnp.float32,
                                0.01, 0.03)
        return kc, vc, sc, sc

    def loop(impl, kc, vc, kscale, vscale, lens):
        def step(carry, _):
            kc, vc, ksc, vsc, lens = carry
            def layer(carry2, lyr):
                kc, vc, ksc, vsc = carry2
                out, kc, vc, ksc, vsc = decode_update_and_attend(
                    q, kn, vn, kc, vc, lens, lyr, impl=impl,
                    k_scale=ksc, v_scale=vsc)
                return (kc, vc, ksc, vsc), out[:, 0, 0]
            (kc, vc, ksc, vsc), outs = jax.lax.scan(
                layer, (kc, vc, ksc, vsc),
                jnp.arange(L, dtype=jnp.int32))
            return (kc, vc, ksc, vsc, lens + 1), outs[-1]
        (kc, vc, ksc, vsc, lens), outs = jax.lax.scan(
            step, (kc, vc, kscale, vscale, lens), None, length=K)
        return outs

    res = {}
    for name, impl, d_store in (("pallas_padded", "pallas", 128),
                                ("xla_unpadded", "xla", D)):
        kc, vc, ksc, vsc = mk_cache(d_store)
        fn = jax.jit(functools.partial(loop, impl))
        sec = _best(lambda: fn(kc, vc, ksc, vsc, lengths), trials)
        res[f"{name}_s"] = round(sec, 4)
    res.update({
        "metric": "lane_padding_decode_d64_L24_b192_s1024_int8",
        "unit": "s per 32-step loop",
        "padded_vs_xla": round(res["pallas_padded_s"]
                               / res["xla_unpadded_s"], 3),
        "backend": jax.default_backend(),
    })
    print(json.dumps(res), flush=True)


def bench_moe_kernel(trials: int = 5) -> None:
    """Section 3: the expert-sorted grouped FFN — Pallas block-sparse
    kernel vs ragged_dot — at Mixtral-8x7B prefill shapes (bf16 weights;
    the kernel's fused-int8-dequant edge would only widen the gap)."""
    from arks_tpu.models import get_config
    from arks_tpu.models.moe import router_topk
    from arks_tpu.ops.moe_kernel import grouped_ffn

    smoke = os.environ.get("BD_SMOKE") == "1"
    cfg = get_config("tiny-mixtral" if smoke else "mixtral-8x7b")
    E, I, X = cfg.hidden_size, cfg.intermediate_size, cfg.num_experts
    k = cfg.num_experts_per_tok
    T = int(os.environ.get("MB_TOKENS", "256" if smoke else "4096"))
    if smoke:
        trials = 1
    t_start = time.perf_counter()

    def stage(msg: str) -> None:
        # Stage evidence on stderr: a run that dies leaves a trail of
        # WHERE instead of a bare timeout.
        print(f"# moe: {msg} at {time.perf_counter() - t_start:.0f}s",
              file=sys.stderr, flush=True)

    key = jax.random.PRNGKey(1)
    ks = jax.random.split(key, 6)
    scale = 0.02
    # One jitted program materializes all ~2.8GB of weights.
    @jax.jit
    def init(ks):
        return (jax.random.normal(ks[0], (T, E), jnp.bfloat16) * scale,
                jax.random.normal(ks[1], (E, X), jnp.bfloat16) * scale,
                jax.random.normal(ks[2], (X, E, I), jnp.bfloat16) * scale,
                jax.random.normal(ks[3], (X, E, I), jnp.bfloat16) * scale,
                jax.random.normal(ks[4], (X, I, E), jnp.bfloat16) * scale)

    x, router, w_gate, w_up, w_down = init(ks)
    jax.block_until_ready(w_down)
    stage("weights ready")

    # Weights are jit ARGUMENTS, not closure captures: captured they bake
    # ~2.8GB of constants into the HLO of every program.
    def route(x, router):
        logits = jnp.einsum("te,ex->tx", x, router)
        vals, idx = router_topk(logits, cfg)
        flat = idx.reshape(-1)
        order = jnp.argsort(flat)
        xs = jnp.take(x, order // k, axis=0)
        return xs, jnp.take(flat, order), jnp.bincount(flat, length=X)

    def run_pallas(x, router, w_gate, w_up, w_down):
        xs, sorted_e, sizes = route(x, router)
        return grouped_ffn(xs, sorted_e, sizes, w_gate, w_up, w_down,
                           x.dtype)

    def run_ragged(x, router, w_gate, w_up, w_down):
        xs, sorted_e, sizes = route(x, router)
        gate = jax.lax.ragged_dot(xs, w_gate, sizes)
        up = jax.lax.ragged_dot(xs, w_up, sizes)
        act = jax.nn.silu(gate.astype(jnp.float32)).astype(gate.dtype) * up
        return jax.lax.ragged_dot(act, w_down, sizes)

    res = {}
    for name, fn in (("pallas", run_pallas), ("ragged_dot", run_ragged)):
        jf = jax.jit(fn)
        res[f"{name}_s"] = round(
            _best(lambda: jf(x, router, w_gate, w_up, w_down), trials), 4)
        stage(f"{name} measured")
    res.update({
        "metric": f"moe_grouped_ffn_mixtral8x7b_T{T}_bf16",
        "unit": "s per grouped FFN",
        "pallas_vs_ragged": round(res["pallas_s"] / res["ragged_dot_s"], 3),
        "backend": jax.default_backend(),
    })
    print(json.dumps(res), flush=True)


def main() -> None:
    only = os.environ.get("BD_ONLY", "")
    if only not in ("", "paged", "pad", "moe"):
        raise SystemExit(f"BD_ONLY={only!r}: expected paged|pad|moe (or "
                         "unset for all sections)")
    if not only or only == "paged":
        bench_paged_vs_slot()
    if not only or only == "pad":
        bench_lane_padding()
    if not only or only == "moe":
        bench_moe_kernel()


if __name__ == "__main__":
    main()
