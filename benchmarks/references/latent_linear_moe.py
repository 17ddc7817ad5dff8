"""The ``latent_linear_moe`` reference family: the ``gigachat3_5`` block
(ai-sage): Gated DeltaNet linear layers (arXiv:2412.06464; the config's
``linear_*`` keys are the Qwen3-Next layout's) beside gated latent-attention
layers at ``full_attention_layers`` (the DeepSeek-V3 block with an output
gate), a gated norm in sandwich placement, a clamped SwiGLU, sigmoid-routed
experts with a selection bias beside one ungated shared expert behind a
dense prefix of LINEAR layers.

The contract of a family is in ``benchmarks/README.md`` ("A reference
family"); what every family shares (which weights a seed means, the int8
storage rule, the embedding and the vocabulary-blocked head's matmul) is in
``_common.py``.  Nothing here imports the program.

**What the model computes**, on the stored weights widened to float32,
every matmul at the highest precision, no cache, no pages, no state kept
between calls, no chunks, no kernels.  ``N(x) = x / rms(x) * (g sigmoid(w))``
with g = ``layernorm_gating_weight`` (``norm_type``
``ZeroCenteredGatedNorm``; eps ``rms_norm_eps``) is the model's norm: the
four norms of a layer, the final norm, the two norms inside the latent
block.  A layer (``layernorm_type`` ``pre_post``): ``h = x + N2(Mixer(N1(x)))``,
``y = h + N4(FFN(N3(h)))``; ``u`` is a sublayer's normed input.

- a LINEAR layer (every layer not in ``full_attention_layers``), Hk =
  ``linear_num_key_heads`` key heads under H = ``linear_num_value_heads``
  value heads of d = ``linear_key_head_dim``: ``[q; k; v] = SiLU(conv(u
  W_qkv))``, ``conv`` a causal depthwise convolution over the last
  ``linear_conv_kernel_dim`` positions (``y_t = sum_i w[i] x_{t - K + 1 +
  i}``, positions before the sequence zeros, no bias) over the 2 Hk d + H d
  channels; q and k divided by their L2 norm a head (``/ sqrt(sum^2 +
  1e-6)``), q times ``d^-1/2``; key head j serves value heads ``j H / Hk ..
  (j + 1) H / Hk - 1``.  ONE log decay a head: ``g_t = -exp(A_log)
  softplus(u W_a + dt_bias)``; step size ``b_t = sigmoid(u W_b)``.  State
  ``S [d, d]`` a value head (keys down, values across), float32, zeros
  before the sequence, ONE TOKEN AT A TIME: ``S' = exp(g_t) S_{t-1}``;
  ``S_t = S' + b_t k_t (v_t - S'^T k_t)^T``; ``o_t = S_t^T q_t``.  Output
  ``(o_t / rms(o_t) * w_o[d]) * s sigmoid(u W_z)`` with s =
  ``linear_sigmoid_gate_scale`` and eps ``linear_attn_o_norm_eps``, then
  ``W_o``;
- a LATENT layer, in the NON-absorbed, published form: ``c_q = N(u W_qa)``;
  ``[q_nope_i | q_rope_i] = c_q W_qb`` per head; ``[c_kv | k_r] = u W_kva``;
  ``c_kv = N(c_kv)``; RoPE on ``q_rope_i`` and on ``k_r`` with YaRN's
  blended frequencies; ``[k_nope_i | v_i] = c_kv W_kvb``; ``p_i =
  softmax_causal(s q_i . k_i)`` with ``s = (nope + rope)^-1/2 (0.1
  mscale_all_dim ln factor + 1)^2`` (``use_mla_scaling_factor``); ``y =
  (concat_i(p_i v_i) * sigmoid(u W_g)) W_o`` (``gated_attention``);
- layers ``< first_k_dense_replace``: SwiGLU of ``intermediate_size``; the
  others: ``sigma = sigmoid(u W_r)`` over the router's whole width in
  float32; chosen = the top ``num_experts_per_tok`` of ``sigma + bias``;
  ``g_e = routed_scaling_factor sigma_e / sum_chosen sigma``; ``sum_{e
  chosen, e held} g_e SwiGLU_e(u) + SwiGLU_shared(u)``.  Every held expert
  is computed for every token and the unchosen ones weighted zero.  Every
  SwiGLU is ``SiLU(min(gate, c)) * clip(up, -c, c)``, c = ``swiglu_limit``.

**Assumed** (the published config does not settle them; the same list
stands in ``deploy.json``; ``config["reference_without"]``, tests only,
computes the same weights under the OTHER reading of each, so that a test
can show that program and reference hold the same one):

1. ``norm_type`` ``ZeroCenteredGatedNorm`` with ``layernorm_gating_weight``
   2 is ``x / rms(x) * 2 sigmoid(w)``: a scale of 1 at ``w`` = 0, the form
   ``linear_gating_type`` names for the linear layer's gate
   (``"norm_sigmoid"``: ``1 + w`` in its place, the other zero-centred
   reading; an elementwise function of a ``[hidden]`` leaf either way, the
   same cost on the chip).  The two norms inside the latent block are the
   model's norm too;
2. ``layernorm_type`` ``pre_post`` is sandwich norms, the post-norm before
   the residual add (``"post_norm"``: no post-norms);
3. the linear layer's output: an RMS norm over a head's d lanes with a
   learnt weight ``[d]`` shared by the heads, times ``2 sigmoid(z)`` from a
   FULL projection of the sublayer's normed input (``"gate_scale"``:
   ``sigmoid(z)``); SiLU behind the convolution, no convolution bias
   (``"conv"``: the older taps dropped); the state carried from token to
   token (``"state"``: forgotten at every token);
4. the latent layer's softmax scale carries YaRN's ``m^2``, DeepSeek-V3's
   rule at ``mscale_all_dim`` 1 (``"mscale"``: ``(nope + rope)^-1/2``
   alone); ``gated_attention`` is ``sigmoid(u W_g)`` elementwise over the H
   x v outputs before ``W_o``, no bias (``"gate"``: no gate);
5. the router is the sibling GigaChat3.1's (the 3.5 file carries no
   ``scoring_func`` / ``topk_method``): sigmoid scores, ``noaux_tc``'s
   selection bias, normalised, times 2.5 (``"router_bias"``: chosen by the
   unbiased scores); one ungated shared expert;
6. ``swiglu_limit`` is gpt-oss's clamp with this model's plain SiLU
   (``"swiglu_limit"``: no clamp).

**The share.**  ``arch`` reads ``config["share"]`` (``manifest.with_share``):
``n_routed_experts`` experts are held, the experts ``[index x held, (index
+ 1) x held)`` of the published count, which is the router's width; what
the absent experts would add is left out, and the post-norm takes what is
left.  A sliced vocabulary is a smaller vocabulary.

**The routing margin** is in the units in which this family selects: the
biased score ``sigma + bias`` of the last expert chosen less that of the
first left out.

**Departures from the published model**, choices of the seeded weights and
not of the mathematics: rotate-half RoPE on the rotary lanes
(``rope_interleave`` pairs them: with seeded random weights the two differ
by a fixed permutation of ``W_qb``'s and ``W_kva``'s rotary columns); the
one q | k | v projection and its one convolution are three leaves each, the
columns of the one (a layout); a gated norm's weight is zeros (its scale
1), the linear layers' per-head norm's ones; the selection bias is drawn
from the seed like a weight; ``dt_bias`` is the seeded leaf PLUS
``DT_BIAS_SHIFT`` = -4, rounded to bfloat16 again, so that a head decays by
about 0.98 a token (``linear_moe.py`` says why); the two multi-token
prediction modules (``num_nextn_predict_layers``) are not computed: they do
not enter the next-token logits.  ``config["reference_state_dtype"]``
(``"bfloat16"``; tests and the builder's control) rounds the state to that
type after every token, which is what a state KEPT in that type would be.

A sequence is run alone, trimmed to the last position asked for and padded
to a whole number of query blocks; attention goes block of queries by
block and the FFNs block of rows by block, so that a probe of 8k tokens
fits beside the serving pod.
"""

from __future__ import annotations

import functools
import math

import numpy as np

from benchmarks.references import _common
from benchmarks.references._common import widen as _widen

Q_BLOCK = 256
ROW_BLOCK = 2048
DT_BIAS_SHIFT = -4.0
NORMS = ("attn_norm", "attn_post_norm", "mlp_norm", "mlp_post_norm")
LATENT = ("wq_a", "q_norm", "wq_b", "wkv_a", "kv_norm", "wkv_b", "wg", "wo")
LINEAR = ("wq", "wk", "wv", "conv_q", "conv_k", "conv_v", "w_a", "dt_bias",
          "a_log", "w_b", "w_z", "o_norm", "wo")
FFN = ("w_gate", "w_up", "w_down")
SHARED = ("shared_gate_proj", "shared_up", "shared_down")
WITHOUT = ("conv", "gate", "gate_scale", "mscale", "norm_sigmoid",
           "post_norm", "router_bias", "state", "swiglu_limit")


def arch(config: dict) -> dict:
    """The sizes the reference needs, from a public ``config.json`` and the
    share ``deploy.json`` states (under ``config["share"]``)."""
    for k, want in (("scoring_func", "sigmoid"), ("topk_method", "noaux_tc"),
                    ("n_group", 1), ("topk_group", 1), ("hidden_act", "silu"),
                    ("linear_attention_type", "GigaChat35GatedDeltaNet"),
                    ("linear_gating_type",
                     "gated_rmsnorm_sigmoid_zero_centered"),
                    ("norm_type", "ZeroCenteredGatedNorm"),
                    ("layernorm_type", "pre_post"),
                    ("use_mla_scaling_factor", True),
                    ("use_shared_expert_sigmoid", False),
                    ("gated_attention", True), ("attention_bias", False)):
        if config.get(k, want) != want:
            raise NotImplementedError(f"{k}={config[k]!r}: this family "
                                      f"computes {want!r} only")
    if config["linear_value_head_dim"] != config["linear_key_head_dim"]:
        raise NotImplementedError("a state that is not square")
    layers = config["num_hidden_layers"]
    dense = int(config.get("first_k_dense_replace", 0))
    full = tuple(int(l) for l in config["full_attention_layers"])
    if any(l < dense for l in full):
        raise NotImplementedError("a latent layer inside the dense prefix")
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
    without = tuple(sorted(config.get("reference_without") or ()))
    if set(without) - set(WITHOUT):
        raise ValueError(f"reference_without {without}: one of {WITHOUT}")
    return {
        "layers": layers, "dense_layers": dense,
        "kinds": tuple("full" if l in full else "linear"
                       for l in range(layers)),
        "hidden": config["hidden_size"],
        "ffn": config["intermediate_size"],
        "moe_ffn": config["moe_intermediate_size"],
        "shared_ffn": int(config.get("n_shared_experts", 0) or 0)
        * config["moe_intermediate_size"],
        "heads": config["num_attention_heads"],
        "q_lora": config["q_lora_rank"], "kv_lora": config["kv_lora_rank"],
        "nope": config["qk_nope_head_dim"], "rope": config["qk_rope_head_dim"],
        "v": config["v_head_dim"],
        "lin_key_heads": int(config["linear_num_key_heads"]),
        "lin_heads": int(config["linear_num_value_heads"]),
        "lin_dim": int(config["linear_key_head_dim"]),
        "conv": int(config["linear_conv_kernel_dim"]),
        "gate_scale": float(config.get("linear_sigmoid_gate_scale", 1)),
        "o_eps": float(config.get("linear_attn_o_norm_eps", 1e-6)),
        "norm_gate": float(config.get("layernorm_gating_weight", 2)),
        "limit": float(config.get("swiglu_limit", 0) or 0),
        "held": held, "first": index * held, "experts": experts,
        "top_k": config["num_experts_per_tok"],
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
        "without": without,
        "state_dtype": str(config.get("reference_state_dtype", "float32")),
    }


def _layers(a: dict):
    """(tree, index in the tree, kind, routed) of every layer in model
    order: ``dense_layers`` (the dense prefix, linear layers), ``layers``
    (the latent layers), ``lin_layers`` (the routed linear layers)."""
    out, at = [], {"dense_layers": 0, "layers": 0, "lin_layers": 0}
    for l, kind in enumerate(a["kinds"]):
        tree = ("dense_layers" if l < a["dense_layers"]
                else "lin_layers" if kind == "linear" else "layers")
        out.append((tree, at[tree], kind, tree != "dense_layers"))
        at[tree] += 1
    return out


def param_spec(a: dict) -> list[tuple[str, tuple[int, ...], str]]:
    """``(path, shape, kind)`` of every leaf in counter order: a stacked
    tree a kind of layer, the keys of every level sorted."""
    e, v, h = a["hidden"], a["vocab"], a["heads"]
    count = {t: sum(1 for tt, *_ in _layers(a) if tt == t)
             for t in ("dense_layers", "layers", "lin_layers")}

    def norms(l: int) -> dict:
        return {n: ((l, e), "zeros") for n in NORMS}

    def routed(l: int) -> dict:
        x, fm, fs = a["held"], a["moe_ffn"], a["shared_ffn"]
        out = {"router": ((l, e, a["experts"]), "full"),
               "router_bias": ((l, a["experts"]), "full"),
               "w_gate": ((l, x, e, fm), "matmul"),
               "w_up": ((l, x, e, fm), "matmul"),
               "w_down": ((l, x, fm, e), "matmul")}
        if fs:
            out.update({"shared_gate_proj": ((l, e, fs), "matmul"),
                        "shared_up": ((l, e, fs), "matmul"),
                        "shared_down": ((l, fs, e), "matmul")})
        return out

    def dense(l: int) -> dict:
        f = a["ffn"]
        return {"w_gate": ((l, e, f), "matmul"), "w_up": ((l, e, f), "matmul"),
                "w_down": ((l, f, e), "matmul")}

    def linear(l: int) -> dict:
        lh, d, k = a["lin_heads"], a["lin_dim"], a["conv"]
        kd, ld = a["lin_key_heads"] * d, lh * d
        return dict(norms(l), **{
            "wq": ((l, e, kd), "matmul"), "wk": ((l, e, kd), "matmul"),
            "wv": ((l, e, ld), "matmul"),
            "conv_q": ((l, k, kd), "full"), "conv_k": ((l, k, kd), "full"),
            "conv_v": ((l, k, ld), "full"),
            "w_a": ((l, e, lh), "full"), "dt_bias": ((l, lh), "full"),
            "a_log": ((l, lh), "full"), "w_b": ((l, e, lh), "full"),
            "w_z": ((l, e, ld), "matmul"), "o_norm": ((l, d), "ones"),
            "wo": ((l, ld, e), "matmul")})

    def latent(l: int) -> dict:
        return dict(norms(l), **{
            "wq_a": ((l, e, a["q_lora"]), "matmul"),
            "q_norm": ((l, a["q_lora"]), "zeros"),
            "wq_b": ((l, a["q_lora"], h * (a["nope"] + a["rope"])), "matmul"),
            "wkv_a": ((l, e, a["kv_lora"] + a["rope"]), "matmul"),
            "kv_norm": ((l, a["kv_lora"]), "zeros"),
            "wkv_b": ((l, a["kv_lora"], h * (a["nope"] + a["v"])), "matmul"),
            "wg": ((l, e, h * a["v"]), "matmul"),
            "wo": ((l, h * a["v"], e), "matmul")})

    top = {"embed": ((v, e), "embed"), "final_norm": ((e,), "zeros"),
           "layers": dict(latent(count["layers"]),
                          **routed(count["layers"])),
           "lin_layers": dict(linear(count["lin_layers"]),
                              **routed(count["lin_layers"]))}
    if count["dense_layers"]:
        top["dense_layers"] = dict(linear(count["dense_layers"]),
                                   **dense(count["dense_layers"]))
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
    host memory (``_common.generate_weights``), ``dt_bias`` shifted (the
    module's head says why)."""
    import jax.numpy as jnp
    w = _common.generate_weights(param_spec(arch(config)), seed, weight_bits)
    for path in w:
        if path.endswith("/dt_bias"):
            w[path] = np.asarray((jnp.asarray(w[path]) + DT_BIAS_SHIFT)
                                 .astype(jnp.bfloat16).astype(jnp.float32))
    return w


def kernel_shapes(a: dict) -> dict:
    """What ``benchmarks/kernels/paged_latent_attention.py`` needs: the
    latent layers, the only ones that keep pages."""
    return {"heads": a["heads"], "row": a["kv_lora"] + a["rope"],
            "value": a["kv_lora"], "layers": a["kinds"].count("full")}


def linear_kernel_shapes(a: dict) -> dict:
    """What ``benchmarks/kernels/linear_state_update.py`` needs: the linear
    layers and the state a (value) head."""
    return {"heads": a["lin_heads"], "head_dim": a["lin_dim"],
            "layers": a["kinds"].count("linear"), "state_bytes": 4}


def rope_frequencies(a: dict) -> tuple[np.ndarray, float]:
    """(inverse frequencies [rope / 2], what multiplies cos and sin), as
    DeepSeek-V3's modelling file computes YaRN (``mla_moe.py``)."""
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
    if a["yarn_factor"] > 1 and a["yarn_mscale_all_dim"] \
            and "mscale" not in a["without"]:
        m = 0.1 * a["yarn_mscale_all_dim"] * math.log(a["yarn_factor"]) + 1.0
        scale *= m * m
    return scale


@functools.lru_cache(maxsize=None)
def _jits(akey: tuple):
    import jax
    import jax.numpy as jnp

    a = dict(akey)
    h_, nope, rope_d, v_ = a["heads"], a["nope"], a["rope"], a["v"]
    lh, lk, ld, kk = a["lin_heads"], a["lin_key_heads"], a["lin_dim"], a["conv"]
    without = a["without"]
    inv_freq, cos_scale = rope_frequencies(a)
    scale = softmax_scale(a)
    state_dtype = jnp.dtype(a["state_dtype"])

    def unit_rms(x, eps):
        return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)

    def norm(x, w):
        """The model's norm ``N``."""
        w = _widen(w)
        w = 1.0 + w if "norm_sigmoid" in without \
            else a["norm_gate"] * jax.nn.sigmoid(w)
        return unit_rms(x, a["eps"]) * w

    def post(x, y, w):
        """``x + N(y)``: a sublayer's output on its way to the residual."""
        return x + (y if "post_norm" in without else norm(y, w))

    def rope(x):
        """x [T, H, rope]; rotate-half, position = index along T."""
        ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None, None] \
            * jnp.asarray(inv_freq)
        sin, cos = jnp.sin(ang) * cos_scale, jnp.cos(ang) * cos_scale
        x1, x2 = x[..., : rope_d // 2], x[..., rope_d // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    def qkv(x, lw):
        """x [T, E] -> q [T, H, nope + rope], k the same, v [T, H, v], the
        gate [T, H v]."""
        t = x.shape[0]
        u = norm(x, lw["attn_norm"])
        cq = norm(u @ _widen(lw["wq_a"]), lw["q_norm"])
        q = (cq @ _widen(lw["wq_b"])).reshape(t, h_, nope + rope_d)
        kv = u @ _widen(lw["wkv_a"])
        c = norm(kv[:, :a["kv_lora"]], lw["kv_norm"])
        k_r = rope(kv[:, None, a["kv_lora"]:])
        kvb = (c @ _widen(lw["wkv_b"])).reshape(t, h_, nope + v_)
        q = jnp.concatenate([q[..., :nope], rope(q[..., nope:])], -1)
        k = jnp.concatenate(
            [kvb[..., :nope], jnp.broadcast_to(k_r, (t, h_, rope_d))], -1)
        gate = jnp.ones((t, h_ * v_), jnp.float32) if "gate" in without \
            else jax.nn.sigmoid(u @ _widen(lw["wg"]))
        return q, k, kvb[..., nope:], gate

    def attend(q_blk, k, v, start):
        """Queries ``start ..`` of one block against all keys, causal."""
        s = jnp.einsum("qhd,khd->hqk", q_blk, k) * scale
        qpos = start + jnp.arange(q_blk.shape[0])
        causal = qpos[:, None] >= jnp.arange(k.shape[0])[None]
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khv->qhv", p, v).reshape(q_blk.shape[0], -1)

    def latent_out(o, gate, wo):
        return (o * gate) @ _widen(wo)

    def conv(p, w):
        """p [T, C], w [K, C]: ``y_t = sum_i w[i] p_{t - K + 1 + i}``."""
        t = p.shape[0]
        pad = jnp.concatenate([jnp.zeros((kk - 1, p.shape[1])), p])
        taps = range(kk - 1, kk) if "conv" in without else range(kk)
        return sum(pad[i: i + t] * w[i] for i in taps)

    def linear(x, lw):
        """x [T, E] -> the linear layer's output [T, E], the delta rule one
        token at a time."""
        t = x.shape[0]
        u = norm(x, lw["attn_norm"])

        def head(name, cname, heads):
            return jax.nn.silu(conv(u @ _widen(lw[name]), _widen(lw[cname]))
                               ).reshape(t, heads, ld)

        def unit(z):
            return z / jnp.sqrt(jnp.sum(z * z, -1, keepdims=True) + 1e-6)

        # Key head j serves the value heads j H / Hk .. (j + 1) H / Hk - 1.
        q = jnp.repeat(unit(head("wq", "conv_q", lk)) * ld ** -0.5,
                       lh // lk, axis=1)
        k = jnp.repeat(unit(head("wk", "conv_k", lk)), lh // lk, axis=1)
        v = head("wv", "conv_v", lh)
        decay = jnp.exp(-jnp.exp(_widen(lw["a_log"]))[None] * jax.nn.softplus(
            u @ _widen(lw["w_a"]) + _widen(lw["dt_bias"])))       # [T, H]
        beta = jax.nn.sigmoid(u @ _widen(lw["w_b"]))              # [T, H]

        def step(s, xs):
            qt, kt, vt, at, bt = xs             # [H, d] x 3, [H] x 2
            if "state" in without:
                s = jnp.zeros_like(s)
            s = at[:, None, None] * s
            upd = bt[:, None] * (vt - jnp.einsum("hkv,hk->hv", s, kt))
            s = s + kt[..., None] * upd[:, None, :]
            s = s.astype(state_dtype).astype(jnp.float32)
            return s, jnp.einsum("hkv,hk->hv", s, qt)

        _, o = jax.lax.scan(step, jnp.zeros((lh, ld, ld), jnp.float32),
                            (q, k, v, decay, beta))        # [T, H, d]
        gate = jax.nn.sigmoid(u @ _widen(lw["w_z"])) * (
            1.0 if "gate_scale" in without else a["gate_scale"])
        y = (unit_rms(o, a["o_eps"]) * _widen(lw["o_norm"])
             ).reshape(t, lh * ld)
        return (y * gate) @ _widen(lw["wo"])

    def ffn(hn, w_gate, w_up, w_down):
        gate, up = hn @ _widen(w_gate), hn @ _widen(w_up)
        if a["limit"] and "swiglu_limit" not in without:
            gate = jnp.minimum(gate, a["limit"])
            up = jnp.clip(up, -a["limit"], a["limit"])
        return (jax.nn.silu(gate) * up) @ _widen(w_down)

    def biased(hn, router, bias):
        sigma = jax.nn.sigmoid(hn @ _widen(router))
        return sigma, sigma if "router_bias" in without \
            else sigma + _widen(bias)

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
        norm=norm, post=post, qkv=qkv, attend=attend, latent_out=latent_out,
        linear=linear, ffn=ffn, route=route, margin=margin).items()}


def _layer_weights(weights: dict, tree: str, l: int) -> dict:
    return {k.split("/", 1)[1]: _common.layer(v, l)
            for k, v in weights.items() if k.startswith(tree + "/")}


def _by_rows(fn, hn):
    """``fn`` over ``hn [T, E]`` a block of rows at a time."""
    import jax.numpy as jnp
    return jnp.concatenate([fn(hn[s:s + ROW_BLOCK])
                            for s in range(0, hn.shape[0], ROW_BLOCK)])


def _sequence(a, fn, weights, tokens, rows, margins):
    """One sequence ``tokens [T]`` (T a multiple of Q_BLOCK): the hidden
    state after the last layer, [1, T, E]; per routed layer the routing
    margin at ``rows`` is appended to ``margins``."""
    import jax.numpy as jnp

    put = _common.put
    t = tokens.shape[0]
    x = _common.embed(weights, tokens[None], a["eps"])[0]
    rows_d = jnp.asarray(rows, jnp.int32)
    for tree, l, kind, routed in _layers(a):
        lw = _layer_weights(weights, tree, l)
        if kind == "linear":
            y = fn["linear"](x, put({k: lw[k] for k in ("attn_norm",)
                                     + LINEAR}))
        else:
            aw = put({k: lw[k] for k in ("attn_norm",) + LATENT})
            q, k, v, gate = fn["qkv"](x, aw)
            o = jnp.concatenate([fn["attend"](q[s:s + Q_BLOCK], k, v, s)
                                 for s in range(0, t, Q_BLOCK)])
            y = fn["latent_out"](o, gate, aw["wo"])
            del q, k, v, o, gate, aw
        x = fn["post"](x, y, jnp.asarray(lw["attn_post_norm"]))
        hn = fn["norm"](x, jnp.asarray(lw["mlp_norm"]))
        if not routed:
            dense = [put(lw[k]) for k in FFN]
            y = _by_rows(lambda r: fn["ffn"](r, *dense), hn)
            del dense
        else:
            router, bias = (jnp.asarray(lw["router"]),
                            jnp.asarray(lw["router_bias"]))
            gates = fn["route"](hn, router, bias)
            margins.append(np.asarray(fn["margin"](hn, router, bias, rows_d)))
            y = jnp.zeros_like(x)
            for e in range(a["held"]):
                y = y + fn["ffn"](hn, *(put(_common.layer(lw[k], e))
                                        for k in FFN)) \
                    * gates[:, a["first"] + e, None]
            if a["shared_ffn"]:
                y = y + fn["ffn"](hn, *(put(lw[k]) for k in SHARED))
        x = fn["post"](x, y, jnp.asarray(lw["mlp_post_norm"]))
    return x[None]


def _head(a, fn, weights: dict, x, rows) -> np.ndarray:
    """The model's norm and the untied output head at positions ``rows [1,
    R]``, the vocabulary in blocks: logits ``[1, R, V]`` (float32, host)."""
    import jax.numpy as jnp
    h = fn["norm"](jnp.take_along_axis(x, rows[..., None], axis=1),
                   jnp.asarray(weights["final_norm"]))
    w = weights["lm_head"]
    vocab = (w["q"] if isinstance(w, dict) else w).shape[-1]
    out = []
    for c0 in range(0, vocab, _common.VOCAB_BLOCK):
        blk = {k: v[..., c0:c0 + _common.VOCAB_BLOCK] for k, v in w.items()} \
            if isinstance(w, dict) else w[:, c0:c0 + _common.VOCAB_BLOCK]
        out.append(np.asarray(h @ _widen(_common.put(blk))))
    return np.concatenate(out, axis=-1)


def forward(config: dict, weights: dict, tokens: np.ndarray,
            rows: np.ndarray, margins: list | None = None) -> np.ndarray:
    """Logits ``[B, R, V]`` (float32, host) at positions ``rows [B, R]`` of
    the right-padded sequences ``tokens [B, T]``; each routed layer's ``[B,
    R]`` routing margin at ``rows`` is appended to ``margins`` where a list
    is given.  Each sequence runs alone, cut after the last position asked
    for (causal attention, a causal convolution and a recurrence keep every
    kept position blind to what follows) and padded to whole query
    blocks."""
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
            logits.append(_head(a, fn, weights, x,
                                jnp.asarray(rows[b:b + 1], jnp.int32)))
    if margins is not None:
        for l in range(len(per_seq[0])):
            margins.append(np.stack([got[l] for got in per_seq]))
    return np.concatenate(logits, axis=0)
