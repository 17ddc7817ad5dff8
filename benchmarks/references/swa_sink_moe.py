"""The ``swa_sink_moe`` reference family: the ``mimo_v2`` block
(XiaomiMiMo; the language model of MiMo-V2-Flash / MiMo-V2.5): GQA layers
of two kinds in one model by ``hybrid_layer_pattern`` (0 full, 1 window),
each kind with a KV head count and a RoPE base of its own, keys and queries
``head_dim`` wide and values ``v_head_dim`` wide, a learnt sink logit a head
in the softmax of the kinds the config names, sigmoid-routed experts with a
selection bias and NO shared expert behind a dense prefix.

The contract of a family is in ``benchmarks/README.md`` ("A reference
family"); what every family shares (which weights a seed means, the int8
storage rule, norms, the embedding and the vocabulary-blocked head) is in
``_common.py``.  Nothing here imports the program.

**What the model computes**, on the stored weights widened to float32,
every matmul at the highest precision, no cache, no pages, no kernels
(``h = RMSNorm(x)`` at ``layernorm_epsilon``; H the kind's query heads, Hkv
its KV heads, D ``head_dim``, Dv ``v_head_dim``):

- attention of a layer of kind k: ``q = h Wq [H, D]``; ``k = h Wk [Hkv,
  D]``; ``v = attention_value_scale x (h Wv) [Hkv, Dv]``; rotate-half RoPE
  on the first ``int(D x partial_rotary_factor)`` lanes of every query and
  key head, at ``rope_theta`` in a full layer and ``swa_rope_theta`` in a
  window layer, no scaling, the other lanes pass through.  Scores ``q k /
  sqrt(D)``, query head i against KV head ``i // (H / Hkv)``, causal, and
  in a window layer only keys with ``pos_q - pos_k < sliding_window``.  A
  kind without a sink: ``p = softmax(s)``.  A kind with one, head i with
  the learnt logit ``b_i`` (``attn_sink [H]``): the softmax over the scores
  AND one extra column ``b_i``, that column dropped after it, ``p_j =
  exp(s_j - m) / (sum_j exp(s_j - m) + exp(b_i - m))``: the sink takes
  mass and has no value.  ``o_i = sum p v [Dv]``; then ``Wo [H Dv, E]``.  A
  masked dense softmax, computed a block of queries and a KV head at a
  time;
- layers ``moe_layer_freq`` marks 0: SwiGLU of ``intermediate_size``;
- routed layers: ``sigma = sigmoid(h Wr)`` over the router's whole width in
  float32; chosen = the top ``num_experts_per_tok`` of ``sigma + bias``
  (``noaux_tc``, one group); ``g_e = sigma_e / sum_chosen sigma``
  (``norm_topk_prob``) times ``routed_scaling_factor`` (null: 1); ``y =
  sum_{e chosen, e held} g_e SwiGLU_e(h)``.  No shared expert.  Every held
  expert is computed for every token and the unchosen ones weighted zero.

**Assumed** (the published config does not settle them; the same list
stands in ``deploy.json``):

1. the sink's form as written above (the published family's eager path:
   one extra column in the softmax, dropped after it);
2. the value scale multiplies ``v`` before anything else sees it (it
   commutes with the softmax and the sum, so the output is the same as
   scaling ``o``);
3. ``attention_chunk_size`` = ``sliding_window`` adds no mask to a window
   layer (the window's own span under another name);
4. ``attention_projection_layout: fused_qkv`` is how a checkpoint lays out
   q | k | v, not mathematics;
5. no norm on queries and keys (no key names one), no attention bias;
6. the draft (MTP) layers and the vision and audio towers are not in the
   config and are not built.

**The share.**  ``arch`` reads ``config["share"]`` (``manifest.with_share``):
``n_routed_experts`` experts are held, the experts ``[index x held, (index
+ 1) x held)`` of the published count, which is the router's width; what
the absent experts would add is left out.  A sliced vocabulary is a smaller
vocabulary.

**The routing margin** is in the units in which this family selects: the
BIASED sigmoid score of the last expert chosen less that of the first left
out.

**The kernels' work.**  ``kernel_shapes`` / ``window_kernel_shapes`` hand
``benchmarks/kernels/paged_mixed_attention.py`` and
``paged_window_attention.py`` ``head_dim`` = (D + Dv) / 2 (160 for keys 192
and values 128): those functions count ``4 x pairs x heads x head_dim``
operations and ``2 x keys x kv_heads x (head_dim x width + scale)`` bytes,
which is then exactly ``2 x pairs x heads x (D + Dv)`` and ``keys x kv_heads
x ((D + Dv) x width + 2 scales)``: the launch's useful work at the
PUBLISHED widths (what lane padding stores and streams beyond them is the
launch's cost, not its work).  Each kind hands its own ``kv_heads``,
``layers`` and ``window``.  The sink is one float a head and counts as
nothing.

**Departures from the published model**, choices of the seeded weights and
not of the mathematics: none beyond the list above.
``config["reference_without"]`` (tests only: ``"sink"``, ``"value_scale"``,
``"rotary"``, ``"window"``) computes the same weights with one mechanism
switched off (no sink column; values times 1; the WHOLE head rotated; no
window), so that a test can show the comparison sees it.

A sequence is run alone, trimmed to the last position asked for and padded
to a whole number of query blocks, so that a probe of 7k tokens fits beside
the serving pod.
"""

from __future__ import annotations

import functools

import numpy as np

from benchmarks.references import _common
from benchmarks.references._common import rms as _rms, widen as _widen

Q_BLOCK = 512
FFN = ("w_gate", "w_up", "w_down")
TREES = ("dense_layers", "layers", "win_layers")


def arch(config: dict) -> dict:
    """The sizes the reference needs, from a public ``config.json`` and the
    share ``deploy.json`` states (under ``config["share"]``)."""
    for k, want in (("scoring_func", "sigmoid"), ("topk_method", "noaux_tc"),
                    ("n_group", 1), ("topk_group", 1), ("hidden_act", "silu"),
                    ("attention_bias", False), ("hybrid_block_size", None),
                    ("tie_word_embeddings", False)):
        if (config.get(k, want) or want) != want:
            raise NotImplementedError(f"{k}={config[k]!r}: this family "
                                      f"computes {want!r} only")
    if config.get("n_shared_experts"):
        raise NotImplementedError("a shared expert")
    rs = config.get("rope_scaling") or {}
    if rs.get("rope_type", rs.get("type", "default")) != "default":
        raise NotImplementedError(f"rope_scaling {rs!r}")
    layers = config["num_hidden_layers"]
    kinds = tuple("window" if k else "full"
                  for k in config["hybrid_layer_pattern"])
    freq = tuple(config["moe_layer_freq"])
    if not len(kinds) == len(freq) == layers:
        raise ValueError("the per-layer lists do not have one entry a layer")
    dense = sum(not f for f in freq)
    if any(freq[:dense]) or "window" in kinds[:dense]:
        raise NotImplementedError("dense layers: a prefix of full layers")
    window = int(config["sliding_window"])
    for k in ("sliding_window_size", "attention_chunk_size"):
        if config.get(k) not in (None, window):
            raise NotImplementedError(f"{k}={config[k]} beside a window of "
                                      f"{window}")
    d = config["head_dim"]
    dv = config.get("v_head_dim") or d
    if config.get("swa_head_dim", d) != d \
            or config.get("swa_v_head_dim", dv) != dv:
        raise NotImplementedError("a head width a kind")
    share = config.get("share") or {}
    held = config["n_routed_experts"]
    chips, index = share.get("chips_per_layer", 1), share.get("index", 0)
    experts = (share.get("published") or {}).get("n_routed_experts", held)
    if held * chips != experts:
        raise ValueError(f"{held} experts held on each of {chips} chips do "
                         f"not make the {experts} the router scores")
    heads = config["num_attention_heads"]
    kv = config.get("num_key_value_heads") or heads
    without = tuple(sorted(config.get("reference_without") or ()))
    return {
        "layers": layers, "kinds": kinds, "dense_layers": dense,
        "hidden": config["hidden_size"], "ffn": config["intermediate_size"],
        "moe_ffn": config["moe_intermediate_size"],
        "heads_full": heads,
        "heads_window": config.get("swa_num_attention_heads") or heads,
        "kv_heads_full": kv,
        "kv_heads_window": config.get("swa_num_key_value_heads") or kv,
        "head_dim": d, "v_head_dim": dv, "window": window,
        "sink_full": bool(config.get("add_full_attention_sink_bias")),
        "sink_window": bool(config.get("add_swa_attention_sink_bias")),
        "value_scale": float(config.get("attention_value_scale") or 1.0),
        "rotary": float(config.get("partial_rotary_factor") or 1.0),
        "theta_full": float(config.get("rope_theta", 10000.0)),
        "theta_window": float(config.get("swa_rope_theta", 10000.0)),
        "held": held, "first": index * held, "experts": experts,
        "top_k": config["num_experts_per_tok"],
        "scaling": float(config.get("routed_scaling_factor") or 1.0),
        "norm_topk": bool(config.get("norm_topk_prob", True)),
        "vocab": config["vocab_size"],
        "eps": float(config.get("layernorm_epsilon", 1e-6)),
        "without": without,
    }


def _layers(a: dict):
    """(tree, index in the tree, kind, routed) of every layer in model
    order: ``dense_layers`` (the prefix), ``layers`` (the routed full
    layers), ``win_layers`` (the window layers)."""
    out, at = [], dict.fromkeys(TREES, 0)
    for l, kind in enumerate(a["kinds"]):
        tree = ("dense_layers" if l < a["dense_layers"]
                else "win_layers" if kind == "window" else "layers")
        out.append((tree, at[tree], kind, tree != "dense_layers"))
        at[tree] += 1
    return out


def param_spec(a: dict) -> list[tuple[str, tuple[int, ...], str]]:
    """``(path, shape, kind)`` of every leaf in counter order: a stacked
    tree a kind of layer, the keys of every level sorted."""
    e, v, d, dv = a["hidden"], a["vocab"], a["head_dim"], a["v_head_dim"]
    count = {t: sum(1 for tt, *_ in _layers(a) if tt == t) for t in TREES}

    def attn(l: int, kind: str) -> dict:
        h, kv = a["heads_" + kind], a["kv_heads_" + kind]
        out = {"attn_norm": ((l, e), "ones"), "mlp_norm": ((l, e), "ones"),
               "wq": ((l, e, h * d), "matmul"),
               "wk": ((l, e, kv * d), "matmul"),
               "wv": ((l, e, kv * dv), "matmul"),
               "wo": ((l, h * dv, e), "matmul")}
        if a["sink_" + kind]:
            out["attn_sink"] = ((l, h), "full")
        return out

    def routed(l: int, kind: str) -> dict:
        x, fm = a["held"], a["moe_ffn"]
        return dict(attn(l, kind), **{
            "router": ((l, e, a["experts"]), "full"),
            "router_bias": ((l, a["experts"]), "full"),
            "w_gate": ((l, x, e, fm), "matmul"),
            "w_up": ((l, x, e, fm), "matmul"),
            "w_down": ((l, x, fm, e), "matmul")})

    top = {"embed": ((v, e), "embed"), "final_norm": ((e,), "ones"),
           "lm_head": ((e, v), "matmul"),
           "layers": routed(count["layers"], "full"),
           "win_layers": routed(count["win_layers"], "window")}
    if count["dense_layers"]:
        ld, f = count["dense_layers"], a["ffn"]
        top["dense_layers"] = dict(attn(ld, "full"), **{
            "w_gate": ((ld, e, f), "matmul"), "w_up": ((ld, e, f), "matmul"),
            "w_down": ((ld, f, e), "matmul")})
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


def _work_shapes(a: dict, kind: str) -> dict:
    """Keys D and values Dv wide as the one ``head_dim`` the work functions
    take: their mean (the module docstring, "The kernels' work")."""
    return {"heads": a["heads_" + kind], "kv_heads": a["kv_heads_" + kind],
            "head_dim": (a["head_dim"] + a["v_head_dim"]) // 2,
            "layers": a["kinds"].count(kind)}


def kernel_shapes(a: dict) -> dict:
    """What ``benchmarks/kernels/paged_mixed_attention.py`` needs: the FULL
    layers, whose launch keeps the mixed kernel's name."""
    return _work_shapes(a, "full")


def window_kernel_shapes(a: dict) -> dict:
    """What ``benchmarks/kernels/paged_window_attention.py`` needs: the
    window layers, whose launch has a name of its own."""
    return dict(_work_shapes(a, "window"), window=a["window"])


@functools.lru_cache(maxsize=None)
def _jits(akey: tuple):
    import jax
    import jax.numpy as jnp

    a = dict(akey)
    d, dv = a["head_dim"], a["v_head_dim"]
    scale = d ** -0.5
    without = a["without"]
    rot = d if "rotary" in without else int(d * a["rotary"])
    value_scale = 1.0 if "value_scale" in without else a["value_scale"]

    def rope(x, kind):
        """x [T, H, D]; rotate-half over the first ``rot`` lanes, position
        = index along T."""
        inv_freq = 1.0 / a["theta_" + kind] ** (
            np.arange(0, rot, 2, dtype=np.float64) / rot)
        ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None, None] \
            * jnp.asarray(inv_freq.astype(np.float32))
        sin, cos = jnp.sin(ang), jnp.cos(ang)
        x1, x2 = x[..., : rot // 2], x[..., rot // 2: rot]
        return jnp.concatenate(
            [x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., rot:]], -1)

    def qkv(x, lw, kind):
        """x [T, E] -> q [T, H, D], k [T, Hkv, D], v [T, Hkv, Dv]."""
        t, hkv = x.shape[0], a["kv_heads_" + kind]
        h = _rms(x, _widen(lw["attn_norm"]), a["eps"])
        q = (h @ _widen(lw["wq"])).reshape(t, -1, d)
        k = (h @ _widen(lw["wk"])).reshape(t, hkv, d)
        v = (h @ _widen(lw["wv"])).reshape(t, hkv, dv) * value_scale
        return rope(q, kind), rope(k, kind), v

    def attend(q_blk, k, v, sink, start, kind):
        """Queries ``start ..`` of one block against all keys, a KV head at
        a time: causal, within the window in a window layer, and with the
        kind's sink ``[H]`` (None: none) as one extra column."""
        nq, h = q_blk.shape[:2]
        hkv = k.shape[1]
        qpos = start + jnp.arange(nq)
        kpos = jnp.arange(k.shape[0])
        keep = qpos[:, None] >= kpos[None]
        if kind == "window" and "window" not in without:
            keep = keep & (qpos[:, None] - kpos[None] < a["window"])

        def one(args):
            qh, kh, vh, bh = args       # [g, Q, D], [T, D], [T, Dv], [g]
            s = jnp.where(keep[None], jnp.einsum("gqd,kd->gqk", qh, kh)
                          * scale, -jnp.inf)
            if bh is not None:
                col = jnp.broadcast_to(bh[:, None, None], s.shape[:2] + (1,))
                s = jnp.concatenate([s, col], axis=-1)
            p = jax.nn.softmax(s, axis=-1)[..., : kh.shape[0]]
            return jnp.einsum("gqk,kd->gqd", p, vh)

        qg = jnp.transpose(q_blk.reshape(nq, hkv, h // hkv, d), (1, 2, 0, 3))
        bg = None if sink is None or "sink" in without \
            else sink.astype(jnp.float32).reshape(hkv, h // hkv)
        o = jax.lax.map(one, (qg, jnp.swapaxes(k, 0, 1),
                              jnp.swapaxes(v, 0, 1), bg))  # [Hkv, g, Q, Dv]
        return jnp.transpose(o, (2, 0, 1, 3)).reshape(nq, h, dv)

    def out_proj(x, o, wo):
        return x + o.reshape(x.shape[0], -1) @ _widen(wo)

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

    static = {"qkv": ("kind",), "attend": ("kind",)}
    return {k: jax.jit(f, static_argnames=static.get(k, ()))
            for k, f in dict(qkv=qkv, attend=attend, out_proj=out_proj,
                             ffn=ffn, norm2=norm2, route=route,
                             margin=margin).items()}


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
    for tree, l, kind, routed in _layers(a):
        lw = _layer_weights(weights, tree, l)
        aw = put({k: lw[k] for k in ("attn_norm", "wq", "wk", "wv", "wo")})
        sink = jnp.asarray(lw["attn_sink"]) if a["sink_" + kind] else None
        q, k, v = fn["qkv"](x, aw, kind=kind)
        o = jnp.concatenate([fn["attend"](q[s:s + Q_BLOCK], k, v, sink, s,
                                          kind=kind)
                             for s in range(0, t, Q_BLOCK)])
        x = fn["out_proj"](x, o, aw["wo"])
        del q, k, v, o, aw
        hn = fn["norm2"](x, jnp.asarray(lw["mlp_norm"]))
        if not routed:
            x = x + fn["ffn"](hn, *(put(lw[k]) for k in FFN))
            continue
        router, bias = (jnp.asarray(lw["router"]),
                        jnp.asarray(lw["router_bias"]))
        gates = fn["route"](hn, router, bias)
        margins.append(np.asarray(fn["margin"](hn, router, bias, rows_d)))
        for e in range(a["held"]):
            x = x + fn["ffn"](hn, *(put(_common.layer(lw[k], e))
                                    for k in FFN)) \
                * gates[:, a["first"] + e, None]
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
