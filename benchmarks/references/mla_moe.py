"""The ``mla_moe`` reference family: the DeepSeek-V3 block (``deepseek_v3``,
``kimi_k2``): latent attention, a dense prefix, sigmoid-routed experts with
a selection bias, an ungated shared expert.

The contract of a family is in ``benchmarks/README.md`` ("A reference
family"); what every family shares (which weights a seed means, the int8
storage rule, norms, the embedding and the vocabulary-blocked head) is in
``_common.py``.  Nothing here imports the program.

**What the model computes**, in the NON-absorbed, published form, on the
stored weights widened to float32, every matmul at the highest precision,
no cache, no kernels (``h = RMSNorm(x)``):

- attention: ``c_q = RMSNorm(h W_qa)``; ``[q_nope_i | q_rope_i] = c_q W_qb``
  per head; ``[c_kv | k_r] = h W_kva``; ``c_kv = RMSNorm(c_kv)``; RoPE on
  ``q_rope_i`` and on ``k_r`` (one key lane group shared by all heads), with
  YaRN's blended frequencies; ``[k_nope_i | v_i] = c_kv W_kvb`` per head;
  ``k_i = [k_nope_i | k_r]``; ``p_i = softmax_causal(s q_i . k_i)`` with
  ``s = (nope + rope)^-0.5 (0.1 mscale_all_dim ln factor + 1)^2``;
  ``y = concat_i(p_i v_i) W_o``.  Keys and values are materialised per
  head: nothing is absorbed and no latent row is kept;
- layers ``< first_k_dense_replace``: SwiGLU of ``intermediate_size``;
- routed layers: ``sigma = sigmoid(h W_g)``; chosen = the top-k of
  ``sigma + b``; ``g_e = scaling sigma_e / sum_chosen sigma`` (normalised
  where ``norm_topk_prob``); ``y = sum_{e chosen, e held} g_e SwiGLU_e(h)
  + SwiGLU_shared(h)``.  Every held expert is computed for every token and
  the unchosen ones weighted zero.

**The share.**  ``arch`` reads ``config["share"]`` (``manifest.with_share``):
``n_routed_experts`` experts are held, the experts ``[index x held, (index
+ 1) x held)`` of the published count, which is the router's width; what
the absent experts would add is left out.  A sliced vocabulary is a
smaller vocabulary.

**The routing margin** is in the units in which this family selects: the
biased score ``sigma + b`` of the last expert chosen less that of the first
left out.

**Departures from the published model**, all of them choices of the seeded
weights and not of the mathematics: rotate-half RoPE on the rotary lanes
(the published form interleaves pairs: with seeded random weights the two
differ by a fixed permutation of ``W_qb``'s and ``W_kva``'s rotary columns);
the selection bias is drawn from the seed like a weight (non-zero, so that
what selects and what weighs differ; the published one is learnt); no
vision tower; no dropout, no auxiliary loss, no multi-token prediction.

A sequence is run alone, trimmed to the last position asked for and padded
to a whole number of query blocks, and attention goes block of queries by
block, so that a probe of 8k tokens fits beside the serving pod.
"""

from __future__ import annotations

import functools
import math

import numpy as np

from benchmarks.references import _common
from benchmarks.references._common import rms as _rms, widen as _widen

Q_BLOCK = 512
ATTN = ("attn_norm", "wq_a", "q_norm", "wq_b", "wkv_a", "kv_norm", "wkv_b",
        "wo")
FFN = ("w_gate", "w_up", "w_down")
SHARED = ("shared_gate_proj", "shared_up", "shared_down")


def arch(config: dict) -> dict:
    """The sizes the reference needs, from a public ``config.json`` and the
    share ``deploy.json`` states (under ``config["share"]``)."""
    for k, want in (("scoring_func", "sigmoid"), ("topk_method", "noaux_tc"),
                    ("n_group", 1), ("topk_group", 1)):
        if config.get(k, want) != want:
            raise NotImplementedError(f"{k}={config[k]!r}: this family "
                                      f"computes {want!r} only")
    share = config.get("share") or {}
    held = config["n_routed_experts"]
    chips, index = share.get("chips_per_layer", 1), share.get("index", 0)
    experts = (share.get("published") or {}).get("n_routed_experts", held)
    if held * chips != experts:
        raise ValueError(f"{held} experts held on each of {chips} chips do "
                         f"not make the {experts} the router scores")
    rs = config.get("rope_scaling") or {}
    if rs and rs.get("type") != "yarn":
        raise NotImplementedError(f"rope_scaling {rs.get('type')!r}")
    return {
        "layers": config["num_hidden_layers"],
        "dense_layers": int(config.get("first_k_dense_replace", 0)),
        "hidden": config["hidden_size"],
        "ffn": config["intermediate_size"],
        "moe_ffn": config["moe_intermediate_size"],
        "heads": config["num_attention_heads"],
        "q_lora": config["q_lora_rank"], "kv_lora": config["kv_lora_rank"],
        "nope": config["qk_nope_head_dim"], "rope": config["qk_rope_head_dim"],
        "v": config["v_head_dim"],
        "held": held, "first": index * held, "experts": experts,
        "top_k": config["num_experts_per_tok"],
        "shared": int(config.get("n_shared_experts", 0)),
        "scaling": float(config.get("routed_scaling_factor", 1.0)),
        "norm_topk": bool(config.get("norm_topk_prob", True)),
        "vocab": config["vocab_size"],
        "eps": float(config.get("rms_norm_eps", 1e-6)),
        "rope_theta": float(config.get("rope_theta", 10000.0)),
        "yarn_factor": float(rs.get("factor", 1.0)),
        "yarn_original": float(rs.get("original_max_position_embeddings", 0)),
        "yarn_beta_fast": float(rs.get("beta_fast", 32)),
        "yarn_beta_slow": float(rs.get("beta_slow", 1)),
        "yarn_mscale": float(rs.get("mscale", 1)),
        "yarn_mscale_all_dim": float(rs.get("mscale_all_dim", 0)),
        "tied": bool(config.get("tie_word_embeddings", False)),
    }


def param_spec(a: dict) -> list[tuple[str, tuple[int, ...], str]]:
    """``(path, shape, kind)`` of every leaf in counter order: the trees
    ``dense_layers`` (the dense prefix) and ``layers`` (the routed layers),
    each stacked, the keys of every level sorted."""
    e, h, v = a["hidden"], a["heads"], a["vocab"]

    def attn(l: int) -> dict:
        return {
            "attn_norm": ((l, e), "ones"), "mlp_norm": ((l, e), "ones"),
            "wq_a": ((l, e, a["q_lora"]), "matmul"),
            "q_norm": ((l, a["q_lora"]), "ones"),
            "wq_b": ((l, a["q_lora"], h * (a["nope"] + a["rope"])), "matmul"),
            "wkv_a": ((l, e, a["kv_lora"] + a["rope"]), "matmul"),
            "kv_norm": ((l, a["kv_lora"]), "ones"),
            "wkv_b": ((l, a["kv_lora"], h * (a["nope"] + a["v"])), "matmul"),
            "wo": ((l, h * a["v"], e), "matmul"),
        }

    ld, lr = a["dense_layers"], a["layers"] - a["dense_layers"]
    x, fm, fs = a["held"], a["moe_ffn"], a["shared"] * a["moe_ffn"]
    routed = dict(attn(lr), **{
        "router": ((lr, e, a["experts"]), "full"),
        "router_bias": ((lr, a["experts"]), "full"),
        "w_gate": ((lr, x, e, fm), "matmul"),
        "w_up": ((lr, x, e, fm), "matmul"),
        "w_down": ((lr, x, fm, e), "matmul"),
    })
    if fs:
        routed.update({"shared_gate_proj": ((lr, e, fs), "matmul"),
                       "shared_up": ((lr, e, fs), "matmul"),
                       "shared_down": ((lr, fs, e), "matmul")})
    top = {"embed": ((v, e), "embed"), "final_norm": ((e,), "ones"),
           "layers": routed}
    if ld:
        f = a["ffn"]
        top["dense_layers"] = dict(attn(ld), **{
            "w_gate": ((ld, e, f), "matmul"), "w_up": ((ld, e, f), "matmul"),
            "w_down": ((ld, f, e), "matmul")})
    if not a["tied"]:
        top["lm_head"] = ((e, v), "matmul")
    out = []
    for name in sorted(top):
        if isinstance(top[name], dict):
            out += [(f"{name}/{k}", *top[name][k]) for k in sorted(top[name])]
        else:
            out.append((name, *top[name]))
    return out


def generate_weights(config: dict, seed: int, weight_bits: int = 8) -> dict:
    """The weights seed ``seed`` means for this configuration, parked in
    host memory (``_common.generate_weights``)."""
    return _common.generate_weights(param_spec(arch(config)), seed,
                                    weight_bits)


def kernel_shapes(a: dict) -> dict:
    """What ``benchmarks/kernels/paged_latent_attention.py`` needs."""
    return {"heads": a["heads"], "row": a["kv_lora"] + a["rope"],
            "value": a["kv_lora"], "layers": a["layers"]}


def rope_frequencies(a: dict) -> tuple[np.ndarray, float]:
    """(inverse frequencies [rope / 2], what multiplies cos and sin), as
    DeepSeek-V3's modelling file computes YaRN: a lane that turns more than
    ``beta_fast`` times inside the original context keeps its frequency,
    one that turns fewer than ``beta_slow`` times has it divided by the
    factor, a linear ramp between."""
    d, theta = a["rope"], a["rope_theta"]
    freqs = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float64) / d)
    factor = a["yarn_factor"]
    if factor <= 1:
        return freqs.astype(np.float32), 1.0

    def turn_dim(turns):
        return d * math.log(a["yarn_original"] / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(turn_dim(a["yarn_beta_fast"])), 0)
    high = min(math.ceil(turn_dim(a["yarn_beta_slow"])), d - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(d // 2) - low) / (high - low), 0.0, 1.0)
    blended = freqs / factor * ramp + freqs * (1.0 - ramp)

    def m(s):
        return 0.1 * s * math.log(factor) + 1.0

    return blended.astype(np.float32), \
        m(a["yarn_mscale"]) / m(a["yarn_mscale_all_dim"])


def softmax_scale(a: dict) -> float:
    scale = (a["nope"] + a["rope"]) ** -0.5
    if a["yarn_factor"] > 1 and a["yarn_mscale_all_dim"]:
        m = 0.1 * a["yarn_mscale_all_dim"] * math.log(a["yarn_factor"]) + 1.0
        scale *= m * m
    return scale


@functools.lru_cache(maxsize=None)
def _jits(akey: tuple):
    import jax
    import jax.numpy as jnp

    a = dict(akey)
    h_, nope, rope_d, v_ = a["heads"], a["nope"], a["rope"], a["v"]
    inv_freq, cos_scale = rope_frequencies(a)
    scale = softmax_scale(a)

    def rope(x):
        """x [T, H, rope]; rotate-half, position = index along T."""
        ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None, None] \
            * jnp.asarray(inv_freq)
        sin, cos = jnp.sin(ang) * cos_scale, jnp.cos(ang) * cos_scale
        x1, x2 = x[..., : rope_d // 2], x[..., rope_d // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    def qkv(x, lw):
        """x [T, E] -> q [T, H, nope + rope], k the same, v [T, H, v]."""
        t = x.shape[0]
        h = _rms(x, _widen(lw["attn_norm"]), a["eps"])
        cq = _rms(h @ _widen(lw["wq_a"]), _widen(lw["q_norm"]), a["eps"])
        q = (cq @ _widen(lw["wq_b"])).reshape(t, h_, nope + rope_d)
        kv = h @ _widen(lw["wkv_a"])
        c = _rms(kv[:, :a["kv_lora"]], _widen(lw["kv_norm"]), a["eps"])
        k_r = rope(kv[:, None, a["kv_lora"]:])
        kvb = (c @ _widen(lw["wkv_b"])).reshape(t, h_, nope + v_)
        q = jnp.concatenate([q[..., :nope], rope(q[..., nope:])], -1)
        k = jnp.concatenate(
            [kvb[..., :nope], jnp.broadcast_to(k_r, (t, h_, rope_d))], -1)
        return q, k, kvb[..., nope:]

    def attend(q_blk, k, v, start):
        """Queries ``start ..`` of one block against all keys, causal."""
        s = jnp.einsum("qhd,khd->hqk", q_blk, k) * scale
        qpos = start + jnp.arange(q_blk.shape[0])
        causal = qpos[:, None] >= jnp.arange(k.shape[0])[None]
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khv->qhv", p, v).reshape(q_blk.shape[0], -1)

    def out_proj(x, o, wo):
        return x + o @ _widen(wo)

    def ffn(hn, w_gate, w_up, w_down):
        gate = hn @ _widen(w_gate)
        return (jax.nn.silu(gate) * (hn @ _widen(w_up))) @ _widen(w_down)

    def norm2(x, w):
        return _rms(x, _widen(w), a["eps"])

    def biased(hn, router, bias):
        sigma = jax.nn.sigmoid(hn @ _widen(router))
        return sigma, sigma + _widen(bias)

    def route(hn, router, bias):
        """[T, X] combine weights over the router's whole width: sigmoid
        scores, the top k of score + bias chosen, the chosen ones' unbiased
        scores normalised and scaled, the rest zero."""
        sigma, sel = biased(hn, router, bias)
        kth = jax.lax.top_k(sel, a["top_k"])[0][..., -1:]
        g = jnp.where(sel >= kth, sigma, 0.0)
        if a["norm_topk"]:
            g = g / (jnp.sum(g, axis=-1, keepdims=True) + 1e-20)
        return g * a["scaling"]

    def margin(hn, router, bias, rows):
        """[R]: at positions ``rows``, the biased score of the last expert
        chosen less that of the first left out."""
        _, sel = biased(jnp.take(hn, rows, axis=0), router, bias)
        top = jax.lax.top_k(sel, a["top_k"] + 1)[0]
        return top[..., -2] - top[..., -1]

    return {k: jax.jit(f) for k, f in dict(
        qkv=qkv, attend=attend, out_proj=out_proj, ffn=ffn, norm2=norm2,
        route=route, margin=margin).items()}


def _layers(a: dict):
    """(tree, index in the tree, routed) of every layer in model order."""
    return [("dense_layers", l, False) for l in range(a["dense_layers"])] \
        + [("layers", l, True) for l in range(a["layers"] - a["dense_layers"])]


def _layer_weights(weights: dict, tree: str, l: int) -> dict:
    return {k.split("/", 1)[1]: _common.layer(v, l)
            for k, v in weights.items() if k.startswith(tree + "/")}


def _sequence(a, fn, weights, tokens, rows, margins):
    """One sequence ``tokens [T]`` (T a multiple of Q_BLOCK): the hidden
    state after the last layer, [1, T, E]; per routed layer the margin at
    ``rows`` is appended to ``margins``."""
    import jax.numpy as jnp

    put = _common.put
    t = tokens.shape[0]
    x = _common.embed(weights, tokens[None], a["eps"])[0]
    rows_d = jnp.asarray(rows, jnp.int32)
    for tree, l, routed in _layers(a):
        lw = _layer_weights(weights, tree, l)
        aw = put({k: lw[k] for k in ATTN})
        q, k, v = fn["qkv"](x, aw)
        o = jnp.concatenate([fn["attend"](q[s:s + Q_BLOCK], k, v, s)
                             for s in range(0, t, Q_BLOCK)])
        x = fn["out_proj"](x, o, aw["wo"])
        del q, k, v, o, aw
        hn = fn["norm2"](x, jnp.asarray(lw["mlp_norm"]))
        if not routed:
            x = x + fn["ffn"](hn, *(put(lw[k]) for k in FFN))
            continue
        router, bias = jnp.asarray(lw["router"]), jnp.asarray(lw["router_bias"])
        gates = fn["route"](hn, router, bias)
        margins.append(np.asarray(fn["margin"](hn, router, bias, rows_d)))
        for e in range(a["held"]):
            x = x + fn["ffn"](hn, *(put(_common.layer(lw[k], e))
                                    for k in FFN)) \
                * gates[:, a["first"] + e, None]
        if a["shared"]:
            x = x + fn["ffn"](hn, *(put(lw[k]) for k in SHARED))
    return x[None]


def forward(config: dict, weights: dict, tokens: np.ndarray,
            rows: np.ndarray, margins: list | None = None) -> np.ndarray:
    """Logits ``[B, R, V]`` (float32, host) at positions ``rows [B, R]`` of
    the right-padded sequences ``tokens [B, T]``; each routed layer's
    ``[B, R]`` routing margin at ``rows`` is appended to ``margins`` where a
    list is given.  Each sequence runs alone, cut after the last position
    asked for (causal attention keeps every kept position blind to what
    follows) and padded to whole query blocks."""
    import jax.numpy as jnp

    a = arch(config)
    if a["tied"]:
        raise NotImplementedError("tied output head: no cell uses one")
    fn = _jits(tuple(sorted(a.items())))
    logits, per_seq = [], []
    with _common.highest_precision():
        for b in range(tokens.shape[0]):
            n = int(rows[b].max()) + 1
            t = -(-n // Q_BLOCK) * Q_BLOCK
            seq = np.zeros((t,), np.int32)
            seq[:n] = tokens[b, :n]
            got: list = []
            x = _sequence(a, fn, weights, seq, rows[b], got)
            per_seq.append(got)
            logits.append(_common.head(
                weights, x, jnp.asarray(rows[b:b + 1], jnp.int32), a["eps"]))
    if margins is not None:
        for l in range(len(per_seq[0])):
            margins.append(np.stack([got[l] for got in per_seq]))
    return np.concatenate(logits, axis=0)
