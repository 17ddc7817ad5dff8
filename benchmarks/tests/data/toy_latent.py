"""A reference family the ``decoder`` family cannot express, for the dry run
of ``test_manifest.py``: keys and values come out of one low-rank latent row
a token, the experts are scored by a sigmoid and this chip holds a share of
them, so the margin is in sigmoid-score units.  At toy sizes, un-jitted."""

import numpy as np

from benchmarks.references import _common


def arch(config):
    share = config.get("share") or {}
    held = config["n_routed_experts"]
    return {
        "layers": config["num_hidden_layers"], "hidden": config["hidden_size"],
        "heads": config["num_attention_heads"], "head_dim": config["head_dim"],
        "latent": config["kv_lora_rank"], "ffn": config["moe_intermediate_size"],
        "held": held, "first": share.get("index", 0) * held,
        "experts": (share.get("published") or {}).get("n_routed_experts", held),
        "top_k": config["num_experts_per_tok"], "vocab": config["vocab_size"],
        "eps": float(config["rms_norm_eps"]),
        "rope_theta": float(config["rope_theta"]),
    }


def param_spec(a):
    l, e, hd = a["layers"], a["hidden"], a["heads"] * a["head_dim"]
    layers = {
        "attn_norm": ((l, e), "ones"), "mlp_norm": ((l, e), "ones"),
        "wq": ((l, e, hd), "matmul"), "w_kv_a": ((l, e, a["latent"]), "matmul"),
        "w_kv_b": ((l, a["latent"], 2 * hd), "matmul"),
        "wo": ((l, hd, e), "matmul"),
        "router": ((l, e, a["experts"]), "full"),
        "w_up": ((l, a["held"], e, a["ffn"]), "matmul"),
        "w_down": ((l, a["held"], a["ffn"], e), "matmul"),
    }
    return [("embed", (a["vocab"], e), "embed"), ("final_norm", (e,), "ones")] \
        + [(f"layers/{k}", *layers[k]) for k in sorted(layers)] \
        + [("lm_head", (e, a["vocab"]), "matmul")]


def generate_weights(config, seed, weight_bits=8):
    return _common.generate_weights(param_spec(arch(config)), seed,
                                    weight_bits)


def forward(config, weights, tokens, rows, margins=None):
    import jax
    import jax.numpy as jnp

    a = arch(config)
    w_, rows_d = _common.widen, jnp.asarray(rows, jnp.int32)
    b, t = tokens.shape
    shape = (b, t, a["heads"], a["head_dim"])
    with _common.highest_precision():
        x = _common.embed(weights, tokens, a["eps"])
        for l in range(a["layers"]):
            lw = _common.put(_common.layer_weights(weights, l))
            h = _common.rms(x, w_(lw["attn_norm"]), a["eps"])
            k, v = jnp.split(h @ w_(lw["w_kv_a"]) @ w_(lw["w_kv_b"]), 2, -1)
            q = _common.rope((h @ w_(lw["wq"])).reshape(shape), a["rope_theta"])
            k = _common.rope(k.reshape(shape), a["rope_theta"])
            s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(a["head_dim"])
            p = jax.nn.softmax(jnp.where(jnp.tril(jnp.ones((t, t), bool)), s,
                                         -jnp.inf), axis=-1)
            o = jnp.einsum("bhqk,bkhd->bqhd", p, v.reshape(shape))
            x = x + o.reshape(b, t, -1) @ w_(lw["wo"])
            hn = _common.rms(x, w_(lw["mlp_norm"]), a["eps"])
            scores = jax.nn.sigmoid(hn @ w_(lw["router"]))
            top = jax.lax.top_k(scores, a["top_k"] + 1)[0]
            if margins is not None:
                margins.append(np.asarray(jnp.take_along_axis(
                    top[..., -2] - top[..., -1], rows_d, axis=1)))
            gates = jnp.where(scores >= top[..., -2:-1], scores, 0.0)
            gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
            for e in range(a["held"]):     # what the absent experts add is left out
                up = jax.nn.silu(hn @ w_(_common.layer(lw["w_up"], e)))
                x = x + (up @ w_(_common.layer(lw["w_down"], e))) \
                    * gates[..., a["first"] + e, None]
        return _common.head(weights, x, rows_d, a["eps"])
