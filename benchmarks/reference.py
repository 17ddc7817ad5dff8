"""The plain reference: weights from the seed, one full forward in float32.

Nothing here imports the program.  Two things are written down:

1. **Which weights a seed means.**  The serving pod is started with
   ``--seed S`` and no checkpoint, so it serves seeded random weights.  The
   rule, stated here as a specification and checked against the program by
   ``benchmarks/tests/test_reference.py``:

   - the parameter tree is the one ``param_spec`` lists (stacked layers,
     leading ``[L]``), walked depth first with the keys of every level in
     sorted order; every leaf, norms and biases too, takes the next number
     of a counter that starts at 1;
   - leaf ``n`` is drawn with ``fold_in(PRNGKey(S), n)``: norms are ones,
     biases zeros, everything else ``normal * 0.02`` in float32 rounded to
     bfloat16;
   - with ``weight_bits=8`` (what every cell serves) a matmul weight
     ``[.., K, N]`` is stored as int8 with one float32 scale per output
     channel (``max|w| / 127`` over K), the embedding ``[V, E]`` with one
     scale per row; the router stays bfloat16.  ``weight_bits=0`` keeps
     every leaf bfloat16.

2. **What the model computes**: the published Qwen2 / Mixtral decoder
   (pre-norm blocks, rotate-half RoPE, grouped-query causal attention,
   SwiGLU feed-forward or top-k routed SwiGLU experts with the weights of
   the chosen experts renormalised, final norm, untied output head), on the
   stored weights widened to float32, with every matmul at the highest
   precision, no cache, no kernels, no batching tricks.  Every expert is
   computed for every token and the unchosen ones weighted zero.

The stored weights of a 7B model do not fit beside the serving engine, so
``generate_weights`` parks them in host memory and ``forward`` brings one
layer (one expert) at a time back to the device.
"""

from __future__ import annotations

import functools

import numpy as np


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


@functools.lru_cache(maxsize=None)
def _leaf_fn(shape: tuple[int, ...], kind: str, bits: int):
    import jax
    import jax.numpy as jnp

    def q8(w, axis):
        w = w.astype(jnp.float32)
        s = jnp.maximum(jnp.max(jnp.abs(w), axis=axis, keepdims=True),
                        1e-8) / 127.0
        return {"q": jnp.clip(jnp.round(w / s), -127, 127).astype(jnp.int8),
                "s": s}

    def gen(key):
        if kind == "ones":
            return jnp.ones(shape, jnp.bfloat16)
        if kind == "zeros":
            return jnp.zeros(shape, jnp.bfloat16)
        w = (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(
            jnp.bfloat16)
        if kind == "embed" and bits:
            return q8(w, -1)
        if kind == "matmul" and bits:
            return q8(w, -2)
        return w

    return jax.jit(gen)


def generate_weights(config: dict, seed: int, weight_bits: int = 8) -> dict:
    """``{path: leaf}`` in host memory; a leaf is a numpy array (bfloat16
    leaves are widened to float32, which is exact) or ``{"q", "s"}``."""
    if weight_bits not in (0, 8):
        raise ValueError(f"weight_bits={weight_bits}: the reference holds "
                         "the weights the cells state, int8 or bfloat16")
    import jax

    key = jax.random.PRNGKey(seed)
    out = {}
    for n, (path, shape, kind) in enumerate(param_spec(arch(config)), 1):
        leaf = _leaf_fn(shape, kind, weight_bits)(jax.random.fold_in(key, n))
        if isinstance(leaf, dict):
            out[path] = {k: np.asarray(v) for k, v in leaf.items()}
        else:
            out[path] = np.asarray(leaf.astype("float32"))
        del leaf
    return out


def _layer(leaf, l: int):
    if isinstance(leaf, dict):
        return {k: v[l] for k, v in leaf.items()}
    return leaf[l]


def _widen(w):
    """A stored leaf (already on the device) as float32."""
    import jax.numpy as jnp
    if not isinstance(w, dict):
        return w.astype(jnp.float32)
    return w["q"].astype(jnp.float32) * w["s"]


def _rms(x, w, eps):
    import jax.numpy as jnp
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x [B, T, H, D]; rotate-half form, position = index along T."""
    import jax.numpy as jnp
    d = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[None, :, None, None] \
        * freqs
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


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

    def logits(x, final_norm, head, rows):
        """Log-softmax inputs need the whole vocabulary; ``rows`` [B, R]
        picks the positions wanted."""
        h = _rms(jnp.take_along_axis(x, rows[..., None], axis=1),
                 _widen(final_norm), a["eps"])
        return h @ _widen(head)

    def embed(table, tokens):
        rows = jnp.take(table["q"], tokens, axis=0).astype(jnp.float32) \
            if isinstance(table, dict) else jnp.take(table, tokens, axis=0)
        if isinstance(table, dict):
            rows = rows * jnp.take(table["s"], tokens, axis=0)
        return rows

    return {k: jax.jit(f) for k, f in dict(
        attention=attention, ffn=ffn, norm2=norm2, route=route,
        margin=margin, logits=logits, embed=embed).items()}


def forward(config: dict, weights: dict, tokens: np.ndarray,
            rows: np.ndarray, vocab_block: int = 1 << 15,
            margins: list | None = None) -> np.ndarray:
    """Logits ``[B, R, V]`` (float32, host) at positions ``rows [B, R]`` of
    the right-padded sequences ``tokens [B, T]``.  Causal attention keeps a
    position blind to what follows it, padding included.  A routed model
    appends each layer's ``[B, R]`` routing margin at ``rows`` to
    ``margins`` where a list is given."""
    import jax
    import jax.numpy as jnp

    a = arch(config)
    fn = _jits(tuple(sorted(a.items())))
    put = functools.partial(jax.tree.map, jnp.asarray)
    rows_d = jnp.asarray(rows, jnp.int32)
    with jax.default_matmul_precision("highest"):
        x = fn["embed"](put(weights["embed"]), jnp.asarray(tokens, jnp.int32))
        for l in range(a["layers"]):
            lw = {k.split("/", 1)[1]: _layer(v, l)
                  for k, v in weights.items() if k.startswith("layers/")}
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
                    w = put({k: _layer(lw[k], e)
                             for k in ("w_gate", "w_up", "w_down")})
                    x = x + fn["ffn"](hn, w) * gates[..., e:e + 1]
            else:
                x = x + fn["ffn"](hn, put(
                    {k: lw[k] for k in ("w_gate", "w_up", "w_down")}))
        final = jnp.asarray(weights["final_norm"])
        if a["tied"]:
            raise NotImplementedError("tied output head: no cell uses one")
        head = weights["lm_head"]
        out = []
        for c0 in range(0, a["vocab"], vocab_block):
            blk = {k: v[..., c0:c0 + vocab_block] for k, v in head.items()} \
                if isinstance(head, dict) else head[:, c0:c0 + vocab_block]
            out.append(np.asarray(fn["logits"](x, final, put(blk), rows_d)))
    return np.concatenate(out, axis=-1)


def log_softmax(logits: np.ndarray) -> np.ndarray:
    z = logits - logits.max(axis=-1, keepdims=True)
    return z - np.log(np.exp(z).sum(axis=-1, keepdims=True))
