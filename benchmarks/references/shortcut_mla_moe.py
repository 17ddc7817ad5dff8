"""The ``shortcut_mla_moe`` reference family: the ``longcat_flash`` block:
two latent-attention sublayers a layer, a dense FFN behind each, and one
routed layer on a shortcut beside the first dense FFN, whose router scores
real and identity (zero-compute) experts with one softmax.

The contract of a family is in ``benchmarks/README.md`` ("A reference
family"); what every family shares (which weights a seed means, the int8
storage rule, norms, the embedding and the vocabulary-blocked head) is in
``_common.py``.  Nothing here imports the program.

**What the model computes**, in the NON-absorbed, published form, on the
stored weights widened to float32, every matmul at the highest precision,
no cache, no kernels.  ``N(.)`` is RMSNorm with its own weight.  A layer
holds ``attn[0]``, ``attn[1]`` (latent attention), ``ffn[0]``, ``ffn[1]``
(SwiGLU of ``ffn_hidden_size``), four norms and one routed layer::

    a  = h  + MLA_0(N_in0(h))
    x  = N_post0(a)
    s  = MoE(x)                  # the shortcut: used only at the layer's end
    b  = a  + FFN_0(x)
    c  = b  + MLA_1(N_in1(b))
    d  = c  + FFN_1(N_post1(c))
    h' = d  + s

- attention (``u`` the sublayer's normed input): ``c_q = N(u W_qa)``;
  ``[q_nope_i | q_rope_i] = sq (c_q W_qb)`` per head, ``sq = (hidden /
  q_lora_rank)^0.5`` where ``mla_scale_q_lora``; ``[c_kv | k_r] = u W_kva``;
  ``c_kv = skv N(c_kv)``, ``skv = (hidden / kv_lora_rank)^0.5`` where
  ``mla_scale_kv_lora`` (it reaches the no-rope keys and the values through
  ``W_kvb``, and NOT ``k_r``); plain RoPE (``rope_theta``, no scaling) on
  ``q_rope_i`` and on ``k_r`` (one key lane group shared by all heads);
  ``[k_nope_i | v_i] = c_kv W_kvb``; ``p_i = softmax_causal((nope +
  rope)^-0.5 q_i . [k_nope_i | k_r])``; ``y = concat_i(p_i v_i) W_o``;
- the routed layer: ``p = softmax(x W_r)`` over the router's whole width
  (the published real experts and, behind them, ``zero_expert_num`` identity
  experts); chosen = the top ``moe_topk`` of ``p + b``; ``g_e = scaling p_e``
  for the chosen, nothing renormalised; ``MoE(x) = sum_{e chosen, e real and
  held} g_e SwiGLU_e(x) + (sum_{e chosen, e identity} g_e) x``.  Every held
  expert is computed for every token and the unchosen ones weighted zero.

**The share.**  ``arch`` reads ``config["share"]`` (``manifest.with_share``):
``n_routed_experts`` real experts are held, the experts ``[index x held,
(index + 1) x held)`` of the published count; the router scores the
published count AND the identity experts; what the absent real experts
would add is left out.  The identity part costs no exchange and is computed
where the token lives: every chip computes it alike, so it is in every
share's result and counted ONCE when shares are added up, as a shared
expert is.  A sliced vocabulary is a smaller vocabulary.

**The routing margin** is in the units in which this family selects: ``p +
b`` of the last expert chosen less that of the first left out, over the
router's whole width.

**Departures from the published model**, all of them choices of the seeded
weights and not of the mathematics: rotate-half RoPE on the rotary lanes
(the published form interleaves pairs: with seeded random weights the two
differ by a fixed permutation of ``W_qb``'s and ``W_kva``'s rotary columns);
the selection bias is the seeded leaf (normal x 0.02, as every leaf) times
``SELECT_BIAS_SCALE`` / the router's width: a deviation of half a uniform
probability, non-zero, so that what selects and what weighs differ, and
small enough that the token decides (the unscaled draw would pick the same
experts for every token; the published bias is learnt, on the scale of the
probabilities it balances).  What
``mla_scale_q_lora`` / ``mla_scale_kv_lora`` mean is the family's public
modelling file's reading, as remembered (``deploy.json``'s ``assumed``).

A sequence is run alone, trimmed to the last position asked for and padded
to a whole number of query blocks, and attention goes block of queries by
block.
"""

from __future__ import annotations

import functools

import numpy as np

from benchmarks.references import _common
from benchmarks.references._common import rms as _rms, widen as _widen

Q_BLOCK = 512
SELECT_BIAS_SCALE = 25.0
ATTN = ("attn_norm", "wq_a", "q_norm", "wq_b", "wkv_a", "kv_norm", "wkv_b",
        "wo")
FFN = ("ffn_gate", "ffn_up", "ffn_down")
EXPERT = ("w_gate", "w_up", "w_down")


def arch(config: dict) -> dict:
    """The sizes the reference needs, from a public ``config.json`` and the
    share ``deploy.json`` states (under ``config["share"]``)."""
    for k, want in (("zero_expert_type", "identity"), ("router_bias", False),
                    ("norm_topk_prob", False), ("rope_scaling", None)):
        if config.get(k, want) not in (want, None):
            raise NotImplementedError(f"{k}={config[k]!r}: this family "
                                      f"computes {want!r} only")
    share = config.get("share") or {}
    held = config["n_routed_experts"]
    chips, index = share.get("chips_per_layer", 1), share.get("index", 0)
    experts = (share.get("published") or {}).get("n_routed_experts", held)
    if held * chips != experts:
        raise ValueError(f"{held} experts held on each of {chips} chips do "
                         f"not make the {experts} real experts the router "
                         "scores")
    hidden = config["hidden_size"]
    return {
        "layers": config["num_layers"],
        "hidden": hidden,
        "ffn": config["ffn_hidden_size"],
        "moe_ffn": config["expert_ffn_hidden_size"],
        "heads": config["num_attention_heads"],
        "q_lora": config["q_lora_rank"], "kv_lora": config["kv_lora_rank"],
        "nope": config["qk_nope_head_dim"], "rope": config["qk_rope_head_dim"],
        "v": config["v_head_dim"],
        "q_scale": (hidden / config["q_lora_rank"]) ** 0.5
        if config.get("mla_scale_q_lora") else 1.0,
        "kv_scale": (hidden / config["kv_lora_rank"]) ** 0.5
        if config.get("mla_scale_kv_lora") else 1.0,
        "held": held, "first": index * held, "experts": experts,
        "zero": int(config.get("zero_expert_num", 0) or 0),
        "top_k": config["moe_topk"],
        "scaling": float(config.get("routed_scaling_factor", 1.0)),
        "vocab": config["vocab_size"],
        "eps": float(config.get("rms_norm_eps", 1e-6)),
        "rope_theta": float(config.get("rope_theta", 10000.0)),
        "tied": bool(config.get("tie_word_embeddings", False)),
    }


def param_spec(a: dict) -> list[tuple[str, tuple[int, ...], str]]:
    """``(path, shape, kind)`` of every leaf in counter order: one stacked
    tree ``layers``, the keys of every level sorted.  A layer's two
    attention sublayers and their dense FFNs are stacked once more, ``[L,
    2, ..]``; the routed layer's leaves are ``[L, ..]``."""
    e, h, v, l = a["hidden"], a["heads"], a["vocab"], a["layers"]
    x, fm, f = a["held"], a["moe_ffn"], a["ffn"]
    width = a["experts"] + a["zero"]
    layers = {
        "attn_norm": ((l, 2, e), "ones"), "mlp_norm": ((l, 2, e), "ones"),
        "wq_a": ((l, 2, e, a["q_lora"]), "matmul"),
        "q_norm": ((l, 2, a["q_lora"]), "ones"),
        "wq_b": ((l, 2, a["q_lora"], h * (a["nope"] + a["rope"])), "matmul"),
        "wkv_a": ((l, 2, e, a["kv_lora"] + a["rope"]), "matmul"),
        "kv_norm": ((l, 2, a["kv_lora"]), "ones"),
        "wkv_b": ((l, 2, a["kv_lora"], h * (a["nope"] + a["v"])), "matmul"),
        "wo": ((l, 2, h * a["v"], e), "matmul"),
        "ffn_gate": ((l, 2, e, f), "matmul"),
        "ffn_up": ((l, 2, e, f), "matmul"),
        "ffn_down": ((l, 2, f, e), "matmul"),
        "router": ((l, e, width), "full"),
        "router_bias": ((l, width), "full"),
        "w_gate": ((l, x, e, fm), "matmul"),
        "w_up": ((l, x, e, fm), "matmul"),
        "w_down": ((l, x, fm, e), "matmul"),
    }
    out = [("embed", (v, e), "embed"), ("final_norm", (e,), "ones")]
    out += [(f"layers/{k}", *layers[k]) for k in sorted(layers)]
    if not a["tied"]:
        out.append(("lm_head", (e, v), "matmul"))
    return out


def generate_weights(config: dict, seed: int, weight_bits: int = 8) -> dict:
    """The weights seed ``seed`` means for this configuration, parked in
    host memory (``_common.generate_weights``), the selection bias scaled
    to the router's probabilities (the module docstring) and rounded to
    bfloat16 again, as it is stored."""
    import jax.numpy as jnp
    w = _common.generate_weights(param_spec(arch(config)), seed, weight_bits)
    bias = jnp.asarray(w["layers/router_bias"])
    w["layers/router_bias"] = np.asarray(
        (bias * (SELECT_BIAS_SCALE / bias.shape[-1])).astype(jnp.bfloat16)
        .astype(jnp.float32))
    return w


def kernel_shapes(a: dict) -> dict:
    """What ``benchmarks/kernels/paged_latent_attention.py`` needs; its
    ``layers`` are calls of the kernel a token: the attention SUBLAYERS."""
    return {"heads": a["heads"], "row": a["kv_lora"] + a["rope"],
            "value": a["kv_lora"], "layers": 2 * a["layers"]}


@functools.lru_cache(maxsize=None)
def _jits(akey: tuple):
    import jax
    import jax.numpy as jnp

    a = dict(akey)
    h_, nope, rope_d, v_ = a["heads"], a["nope"], a["rope"], a["v"]
    inv_freq = (1.0 / a["rope_theta"] ** (
        np.arange(0, rope_d, 2, dtype=np.float64) / rope_d)).astype(np.float32)
    scale = (nope + rope_d) ** -0.5
    real = a["experts"]

    def rope(x):
        """x [T, H, rope]; rotate-half, position = index along T."""
        ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None, None] \
            * jnp.asarray(inv_freq)
        sin, cos = jnp.sin(ang), jnp.cos(ang)
        x1, x2 = x[..., : rope_d // 2], x[..., rope_d // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    def qkv(x, lw):
        """x [T, E] -> q [T, H, nope + rope], k the same, v [T, H, v]."""
        t = x.shape[0]
        u = _rms(x, _widen(lw["attn_norm"]), a["eps"])
        cq = _rms(u @ _widen(lw["wq_a"]), _widen(lw["q_norm"]), a["eps"])
        q = a["q_scale"] * (cq @ _widen(lw["wq_b"])).reshape(
            t, h_, nope + rope_d)
        kv = u @ _widen(lw["wkv_a"])
        c = a["kv_scale"] * _rms(kv[:, :a["kv_lora"]], _widen(lw["kv_norm"]),
                                 a["eps"])
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
        p = jax.nn.softmax(hn @ _widen(router), axis=-1)
        return p, p + _widen(bias)

    def route(hn, router, bias):
        """[T, W] combine weights over the router's whole width: softmax
        scores, the top k of score + bias chosen, the chosen ones' unbiased
        scores times the scaling factor, the rest zero; and [T, 1], the
        identity experts' share of them."""
        p, sel = biased(hn, router, bias)
        kth = jax.lax.top_k(sel, a["top_k"])[0][..., -1:]
        g = jnp.where(sel >= kth, p, 0.0) * a["scaling"]
        return g, jnp.sum(g[:, real:], axis=-1, keepdims=True)

    def margin(hn, router, bias, rows):
        """[R]: at positions ``rows``, ``p + b`` of the last expert chosen
        less that of the first left out."""
        _, sel = biased(jnp.take(hn, rows, axis=0), router, bias)
        top = jax.lax.top_k(sel, a["top_k"] + 1)[0]
        return top[..., -2] - top[..., -1]

    return {k: jax.jit(f) for k, f in dict(
        qkv=qkv, attend=attend, out_proj=out_proj, ffn=ffn, norm2=norm2,
        route=route, margin=margin).items()}


def _sequence(a, fn, weights, tokens, rows, margins):
    """One sequence ``tokens [T]`` (T a multiple of Q_BLOCK): the hidden
    state after the last layer, [1, T, E]; per layer the routing margin at
    ``rows`` is appended to ``margins``."""
    import jax.numpy as jnp

    put = _common.put
    t = tokens.shape[0]
    x = _common.embed(weights, tokens[None], a["eps"])[0]
    rows_d = jnp.asarray(rows, jnp.int32)
    for l in range(a["layers"]):
        lw = _common.layer_weights(weights, l)
        shortcut = None
        for j in range(2):
            aw = put({k: _common.layer(lw[k], j) for k in ATTN})
            q, k, v = fn["qkv"](x, aw)
            o = jnp.concatenate([fn["attend"](q[s:s + Q_BLOCK], k, v, s)
                                 for s in range(0, t, Q_BLOCK)])
            x = fn["out_proj"](x, o, aw["wo"])
            del q, k, v, o, aw
            hn = fn["norm2"](x, jnp.asarray(lw["mlp_norm"][j]))
            if j == 0:
                router = jnp.asarray(lw["router"])
                bias = jnp.asarray(lw["router_bias"])
                gates, identity = fn["route"](hn, router, bias)
                margins.append(np.asarray(
                    fn["margin"](hn, router, bias, rows_d)))
                shortcut = identity * hn
                for e in range(a["held"]):
                    shortcut = shortcut + fn["ffn"](
                        hn, *(put(_common.layer(lw[k], e)) for k in EXPERT)) \
                        * gates[:, a["first"] + e, None]
            x = x + fn["ffn"](hn, *(put(_common.layer(lw[k], j))
                                    for k in FFN))
        x = x + shortcut
    return x[None]


def forward(config: dict, weights: dict, tokens: np.ndarray,
            rows: np.ndarray, margins: list | None = None) -> np.ndarray:
    """Logits ``[B, R, V]`` (float32, host) at positions ``rows [B, R]`` of
    the right-padded sequences ``tokens [B, T]``; each layer's ``[B, R]``
    routing margin at ``rows`` is appended to ``margins`` where a list is
    given.  Each sequence runs alone, cut after the last position asked for
    (causal attention keeps every kept position blind to what follows) and
    padded to whole query blocks."""
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
