"""The ``decoder`` reference family: the Qwen2 / Mixtral block.

The contract of a family is in ``benchmarks/README.md`` ("A reference
family"); what every family shares (which weights a seed means, the int8
storage rule, norms, RoPE, the embedding and the vocabulary-blocked head)
is in ``_common.py``.  Nothing here imports the program.

**What the model computes**: the published Qwen2 / Mixtral decoder
(pre-norm blocks, rotate-half RoPE, grouped-query causal attention,
SwiGLU feed-forward or top-k routed SwiGLU experts with the weights of
the chosen experts renormalised, final norm, untied output head), on the
stored weights widened to float32, with every matmul at the highest
precision, no cache, no kernels, no batching tricks.  Every expert is
computed for every token and the unchosen ones weighted zero.

**The routing margin** is in router-logit units: the softmax is monotone,
so the experts with the largest logits are the ones chosen, and
``exp(margin)`` is the ratio of the two experts' probabilities.
"""

from __future__ import annotations

import functools

import numpy as np

from benchmarks.references import _common
from benchmarks.references._common import rms as _rms, rope as _rope, \
    widen as _widen


def arch(config: dict) -> dict:
    """The sizes the reference needs, from a public ``config.json``."""
    heads = config["num_attention_heads"]
    experts = int(config.get("num_local_experts") or 0)
    return {
        "layers": config["num_hidden_layers"],
        "hidden": config["hidden_size"],
        "ffn": config["intermediate_size"],
        "heads": heads,
        "kv_heads": config.get("num_key_value_heads", heads),
        "head_dim": config.get("head_dim") or config["hidden_size"] // heads,
        "vocab": config["vocab_size"],
        "rope_theta": float(config.get("rope_theta", 10000.0)),
        "eps": float(config.get("rms_norm_eps", 1e-6)),
        "tied": bool(config.get("tie_word_embeddings", False)),
        "qkv_bias": config.get("model_type") == "qwen2",
        "experts": experts,
        "top_k": int(config.get("num_experts_per_tok") or 0),
    }


def param_spec(a: dict) -> list[tuple[str, tuple[int, ...], str]]:
    """``(path, shape, kind)`` of every leaf in counter order.  ``kind`` is
    ``ones`` / ``zeros`` / ``matmul`` / ``embed`` / ``full``."""
    l, e, f, v = a["layers"], a["hidden"], a["ffn"], a["vocab"]
    qd, kvd = a["heads"] * a["head_dim"], a["kv_heads"] * a["head_dim"]
    layers = {
        "attn_norm": ((l, e), "ones"), "mlp_norm": ((l, e), "ones"),
        "wq": ((l, e, qd), "matmul"), "wk": ((l, e, kvd), "matmul"),
        "wv": ((l, e, kvd), "matmul"), "wo": ((l, qd, e), "matmul"),
    }
    if a["experts"]:
        x = a["experts"]
        layers.update({
            "router": ((l, e, x), "full"),
            "w_gate": ((l, x, e, f), "matmul"),
            "w_up": ((l, x, e, f), "matmul"),
            "w_down": ((l, x, f, e), "matmul"),
        })
    else:
        layers.update({
            "w_gate": ((l, e, f), "matmul"), "w_up": ((l, e, f), "matmul"),
            "w_down": ((l, f, e), "matmul"),
        })
    if a["qkv_bias"]:
        layers.update({"bq": ((l, qd), "zeros"), "bk": ((l, kvd), "zeros"),
                       "bv": ((l, kvd), "zeros")})
    top = {"embed": ((v, e), "embed"), "final_norm": ((e,), "ones"),
           "layers": layers}
    if not a["tied"]:
        top["lm_head"] = ((e, v), "matmul")
    out = []
    for name in sorted(top):
        if name == "layers":
            out += [(f"layers/{k}", *layers[k]) for k in sorted(layers)]
        else:
            out.append((name, *top[name]))
    return out


def generate_weights(config: dict, seed: int, weight_bits: int = 8) -> dict:
    """The weights seed ``seed`` means for this configuration, parked in
    host memory (``_common.generate_weights``)."""
    return _common.generate_weights(param_spec(arch(config)), seed,
                                    weight_bits)


def kernel_shapes(a: dict) -> dict:
    """What ``benchmarks/kernels/paged_mixed_attention.py`` needs."""
    return {k: a[k] for k in ("heads", "kv_heads", "head_dim", "layers")}


@functools.lru_cache(maxsize=None)
def _jits(akey: tuple):
    import jax
    import jax.numpy as jnp

    a = dict(akey)
    h_, kv_, d_ = a["heads"], a["kv_heads"], a["head_dim"]

    def attention(x, lw):
        b, t, _ = x.shape
        h = _rms(x, _widen(lw["attn_norm"]), a["eps"])
        q = h @ _widen(lw["wq"])
        k = h @ _widen(lw["wk"])
        v = h @ _widen(lw["wv"])
        if a["qkv_bias"]:
            q, k, v = (q + _widen(lw["bq"]), k + _widen(lw["bk"]),
                       v + _widen(lw["bv"]))
        q = _rope(q.reshape(b, t, h_, d_), a["rope_theta"])
        k = _rope(k.reshape(b, t, kv_, d_), a["rope_theta"])
        v = v.reshape(b, t, kv_, d_)
        g = h_ // kv_
        k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(d_)
        causal = jnp.tril(jnp.ones((t, t), bool))
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(b, t, h_ * d_)
        return x + o @ _widen(lw["wo"])

    def ffn(hn, w):
        gate = hn @ _widen(w["w_gate"])
        return (jax.nn.silu(gate) * (hn @ _widen(w["w_up"]))) \
            @ _widen(w["w_down"])

    def norm2(x, w):
        return _rms(x, _widen(w), a["eps"])

    def route(hn, router):
        """[B, T, X] weights: softmax over the experts, the top k kept and
        renormalised to sum to one, the rest zero."""
        probs = jax.nn.softmax(hn @ _widen(router), axis=-1)
        vals, idx = jax.lax.top_k(probs, a["top_k"])
        vals = vals / jnp.sum(vals, axis=-1, keepdims=True)
        hot = jax.nn.one_hot(idx, a["experts"], dtype=vals.dtype)
        return jnp.einsum("btk,btkx->btx", vals, hot)

    def margin(hn, router, rows):
        """[B, R]: at positions ``rows``, the router logit of the last
        expert chosen less that of the first one left out.  Where it is
        near zero a rounding anywhere upstream decides which expert the
        token takes, and no precision of the served side is at fault."""
        z = jnp.take_along_axis(hn, rows[..., None], axis=1) @ _widen(router)
        top = jax.lax.top_k(z, a["top_k"] + 1)[0]
        return top[..., -2] - top[..., -1]

    return {k: jax.jit(f) for k, f in dict(
        attention=attention, ffn=ffn, norm2=norm2, route=route,
        margin=margin).items()}


def forward(config: dict, weights: dict, tokens: np.ndarray,
            rows: np.ndarray, margins: list | None = None) -> np.ndarray:
    """Logits ``[B, R, V]`` (float32, host) at positions ``rows [B, R]`` of
    the right-padded sequences ``tokens [B, T]``.  Causal attention keeps a
    position blind to what follows it, padding included.  A routed model
    appends each layer's ``[B, R]`` routing margin at ``rows`` to
    ``margins`` where a list is given."""
    import jax.numpy as jnp

    a = arch(config)
    if a["tied"]:
        raise NotImplementedError("tied output head: no cell uses one")
    fn = _jits(tuple(sorted(a.items())))
    put = _common.put
    rows_d = jnp.asarray(rows, jnp.int32)
    with _common.highest_precision():
        x = _common.embed(weights, tokens, a["eps"])
        for l in range(a["layers"]):
            lw = _common.layer_weights(weights, l)
            attn = put({k: lw[k] for k in lw if k in (
                "attn_norm", "wq", "wk", "wv", "wo", "bq", "bk", "bv")})
            x = fn["attention"](x, attn)
            hn = fn["norm2"](x, jnp.asarray(lw["mlp_norm"]))
            if a["experts"]:
                gates = fn["route"](hn, jnp.asarray(lw["router"]))
                if margins is not None:
                    margins.append(np.asarray(fn["margin"](
                        hn, jnp.asarray(lw["router"]), rows_d)))
                for e in range(a["experts"]):
                    w = put({k: _common.layer(lw[k], e)
                             for k in ("w_gate", "w_up", "w_down")})
                    x = x + fn["ffn"](hn, w) * gates[..., e:e + 1]
            else:
                x = x + fn["ffn"](hn, put(
                    {k: lw[k] for k in ("w_gate", "w_up", "w_down")}))
        return _common.head(weights, x, rows_d, a["eps"])
