"""The ``linear_moe`` reference family: the ``solar_open2`` block (upstage):
gated delta-rule linear-attention layers (Kimi Delta Attention,
arXiv:2510.26692, which the config's ``kda_*`` keys and
``linear_attn_config`` name) beside softmax GQA layers WITHOUT rotary
positions and with an output gate, every layer's FFN sigmoid-routed experts
with a selection bias beside one ungated shared expert.

The contract of a family is in ``benchmarks/README.md`` ("A reference
family"); what every family shares (which weights a seed means, the int8
storage rule, norms, the embedding and the vocabulary-blocked head) is in
``_common.py``.  Nothing here imports the program.

**What the model computes**, on the stored weights widened to float32,
every matmul at the highest precision, no cache, no pages, no state kept
between calls, no chunks, no kernels (``x = RMSNorm(h)``; GQA layers at
``gqa_layers``, every other layer linear):

- a LINEAR layer, H = ``linear_attn_config.num_heads`` heads of d =
  ``head_dim`` for queries, keys and values alike (``num_kv_heads`` null):
  ``q, k, v = SiLU(conv(x W_q)), SiLU(conv(x W_k)), SiLU(conv(x W_v))``,
  ``conv`` a causal depthwise convolution over the last
  ``short_conv_kernel_size`` positions of the sequence (``y_t = sum_i
  w[i] x_{t - K + 1 + i}``, positions before the sequence zeros, no bias);
  q and k divided by their L2 norm a head (``/ sqrt(sum^2 + 1e-6)``), q
  times ``d^-1/2``.  Decay a CHANNEL: ``a_t = exp(-exp(A_log[h]) softplus(x
  W_f1 W_f2 + dt_bias))`` in ``(0, 1)^[H, d]``.  Step size a head: ``b_t =
  2 sigmoid(x W_b)`` (``kda_allow_neg_eigval``; without it no factor 2).
  State ``S [d, d]`` a head (keys down, values across), zeros before the
  sequence, ONE TOKEN AT A TIME: ``S' = diag(a_t) S_{t-1}``; ``S_t = S' +
  b_t k_t (v_t - S'^T k_t)^T``; ``o_t = S_t^T q_t``.  Output ``y =
  (RMSNorm_head(o_t) sigmoid(x W_g1 W_g2)) W_o``;
- a GQA layer: ``q = x Wq [H, D]``, ``k, v = x Wk, x Wv [Hkv, D]``, NO
  rotation and no other position signal (``use_rope`` false); scores ``q k
  / sqrt(D)``, query head i against KV head ``i // (H / Hkv)``, causal
  softmax over all keys; ``attn sigmoid(x Wg)`` elementwise over the H x D
  outputs (``use_gqa_gate``); then ``Wo``.  A masked dense softmax, a block
  of queries and a KV head at a time;
- every layer's FFN: ``sigma = sigmoid(x' W_r)`` over the router's whole
  width in float32; chosen = the top ``num_experts_per_tok`` of ``sigma +
  bias``; ``g_e = routed_scaling_factor sigma_e / sum_chosen sigma``
  (``norm_topk_prob``); ``y = sum_{e chosen, e held} g_e SwiGLU_e(x') +
  SwiGLU_shared(x')``.  Every held expert is computed for every token and
  the unchosen ones weighted zero.

**Assumed** (the published config does not settle them; the same list
stands in ``deploy.json``):

1. the delta rule's equations and the meaning of ``kda_allow_neg_eigval``
   (the step size in (0, 2)) and ``kda_use_full_proj`` false (the decay and
   the output gate through LOW-RANK pairs) are Kimi Delta Attention's; the
   rank of both pairs is the head width d (the config carries no rank);
2. ``num_kv_heads`` null: keys and values have the queries' 64 heads;
3. SiLU behind each short convolution, no convolution bias; the L2 norm of
   q and k with eps 1e-6 and q's scale ``d^-1/2``; the output norm is an
   RMS norm over a head's d lanes with a learnt weight ``[d]`` shared by
   the heads, eps = ``rms_norm_eps``; the gate is a sigmoid;
4. the GQA gate is elementwise over the H x D outputs, from the sublayer's
   normed input, no bias; no norm on queries and keys; no attention bias;
   softmax scale ``1 / sqrt(D)``;
5. the router is the DeepSeek-V3 / Kimi family's, whose key names
   (``n_routed_experts``, ``n_shared_experts``, ``routed_scaling_factor``,
   ``norm_topk_prob``, ``first_k_dense_replace``) the config uses: sigmoid
   scores, a selection bias, no group limit; SwiGLU FFNs;
   ``intermediate_size`` is a dense layer's width and no layer is dense.

**The share.**  ``arch`` reads ``config["share"]`` (``manifest.with_share``):
``n_routed_experts`` experts are held, the experts ``[index x held, (index
+ 1) x held)`` of the published count, which is the router's width; what
the absent experts would add is left out.  A sliced vocabulary is a
smaller vocabulary.

**The routing margin** is in the units in which this family selects: the
biased score ``sigma + bias`` of the last expert chosen less that of the
first left out.

**Departures from the published model**, choices of the seeded weights and
not of the mathematics: the selection bias is drawn from the seed like a
weight (non-zero, so that what selects and what weighs differ; the
published one is learnt); ``dt_bias`` is the seeded leaf (normal x 0.02,
bfloat16) PLUS ``DT_BIAS_SHIFT`` = -4, rounded to bfloat16 again: a leaf
near zero would give softplus = 0.69, a state halved at every token that
forgets within a dozen, where a trained model of the family starts its
step sizes in [0.001, 0.1] and remembers over hundreds of tokens;
softplus(-4) = 0.018 gives a decay of about 0.98 a step, so what the state
holds (and the precision it is held in) reaches the logits.
``config["reference_without"]`` (tests only: ``"state"``, ``"conv"``,
``"gate"``) computes the same weights with one mechanism switched off (the
state forgotten at every token, the convolution's older taps dropped, the
GQA gate left out), so that a test can show the comparison sees it;
``config["reference_state_dtype"]`` (``"bfloat16"``; tests and the
builder's control) rounds the state to that type after every token, which
is what a state KEPT in that type would be.

A sequence is run alone, trimmed to the last position asked for and padded
to a whole number of query blocks, so that a probe of 4k tokens fits beside
the serving pod.
"""

from __future__ import annotations

import functools

import numpy as np

from benchmarks.references import _common
from benchmarks.references._common import rms as _rms, widen as _widen

Q_BLOCK = 512
DT_BIAS_SHIFT = -4.0
GQA = ("attn_norm", "wq", "wk", "wv", "wg", "wo")
LINEAR = ("attn_norm", "wq", "wk", "wv", "conv_q", "conv_k", "conv_v",
          "w_f1", "w_f2", "dt_bias", "a_log", "w_b", "o_norm", "w_g1",
          "w_g2", "wo")
FFN = ("w_gate", "w_up", "w_down")
SHARED = ("shared_gate_proj", "shared_up", "shared_down")


def arch(config: dict) -> dict:
    """The sizes the reference needs, from a public ``config.json`` and the
    share ``deploy.json`` states (under ``config["share"]``)."""
    for k, want in (("scoring_func", "sigmoid"), ("n_group", 1),
                    ("topk_group", 1), ("first_k_dense_replace", 0),
                    ("kda_use_full_proj", False), ("use_rope", False)):
        if (config.get(k, want) or want) != want:
            raise NotImplementedError(f"{k}={config[k]!r}: this family "
                                      f"computes {want!r} only")
    layers = config["num_hidden_layers"]
    lin = config["linear_attn_config"]
    if lin.get("num_kv_heads") not in (None, lin["num_heads"]):
        raise NotImplementedError("linear keys and values of fewer heads")
    per = int(config["gqa_interval"])
    gqa = tuple(int(l) for l in config["gqa_layers"])
    if gqa != tuple(range(0, layers, per + 1)):
        raise NotImplementedError(f"gqa_layers {gqa}: a GQA layer every "
                                  f"{per + 1} layers from layer 0")
    share = config.get("share") or {}
    held = config["n_routed_experts"]
    chips, index = share.get("chips_per_layer", 1), share.get("index", 0)
    experts = (share.get("published") or {}).get("n_routed_experts", held)
    if held * chips != experts:
        raise ValueError(f"{held} experts held on each of {chips} chips do "
                         f"not make the {experts} the router scores")
    return {
        "layers": layers,
        "kinds": tuple("full" if l in gqa else "linear"
                       for l in range(layers)),
        "hidden": config["hidden_size"],
        "moe_ffn": config["moe_intermediate_size"],
        "shared_ffn": int(config.get("n_shared_experts", 0) or 0)
        * config["moe_intermediate_size"],
        "heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"],
        "gate": bool(config.get("use_gqa_gate", False)),
        "lin_heads": int(lin["num_heads"]),
        "lin_dim": int(lin["head_dim"]),
        "conv": int(lin["short_conv_kernel_size"]),
        "neg_eigval": bool(config.get("kda_allow_neg_eigval", False)),
        "held": held, "first": index * held, "experts": experts,
        "top_k": config["num_experts_per_tok"],
        "scaling": float(config.get("routed_scaling_factor", 1.0)),
        "norm_topk": bool(config.get("norm_topk_prob", True)),
        "vocab": config["vocab_size"],
        "eps": float(config.get("rms_norm_eps", 1e-6)),
        "tied": bool(config.get("tie_word_embeddings", False)),
        "without": tuple(sorted(config.get("reference_without") or ())),
        "state_dtype": str(config.get("reference_state_dtype", "float32")),
    }


def _layers(a: dict):
    """(tree, index in the tree, kind) of every layer in model order:
    ``head_layers`` (layer 0), ``layers`` (the GQA layer of each period),
    ``lin_layers`` (the linear layers)."""
    out, at = [], {"head_layers": 0, "layers": 0, "lin_layers": 0}
    for l, kind in enumerate(a["kinds"]):
        tree = ("head_layers" if l == 0
                else "lin_layers" if kind == "linear" else "layers")
        out.append((tree, at[tree], kind))
        at[tree] += 1
    return out


def param_spec(a: dict) -> list[tuple[str, tuple[int, ...], str]]:
    """``(path, shape, kind)`` of every leaf in counter order: a stacked
    tree a kind of layer, the keys of every level sorted."""
    e, v = a["hidden"], a["vocab"]
    count = {t: sum(1 for tt, *_ in _layers(a) if tt == t)
             for t in ("head_layers", "layers", "lin_layers")}

    def ffn(l: int) -> dict:
        x, fm, fs = a["held"], a["moe_ffn"], a["shared_ffn"]
        out = {"mlp_norm": ((l, e), "ones"),
               "router": ((l, e, a["experts"]), "full"),
               "router_bias": ((l, a["experts"]), "full"),
               "w_gate": ((l, x, e, fm), "matmul"),
               "w_up": ((l, x, e, fm), "matmul"),
               "w_down": ((l, x, fm, e), "matmul")}
        if fs:
            out.update({"shared_gate_proj": ((l, e, fs), "matmul"),
                        "shared_up": ((l, e, fs), "matmul"),
                        "shared_down": ((l, fs, e), "matmul")})
        return out

    def gqa(l: int) -> dict:
        qd, kvd = a["heads"] * a["head_dim"], a["kv_heads"] * a["head_dim"]
        out = dict(ffn(l), **{
            "attn_norm": ((l, e), "ones"),
            "wq": ((l, e, qd), "matmul"), "wk": ((l, e, kvd), "matmul"),
            "wv": ((l, e, kvd), "matmul"), "wo": ((l, qd, e), "matmul")})
        if a["gate"]:
            out["wg"] = ((l, e, qd), "matmul")
        return out

    def linear(l: int) -> dict:
        h, d, k = a["lin_heads"], a["lin_dim"], a["conv"]
        ld = h * d
        return dict(ffn(l), **{
            "attn_norm": ((l, e), "ones"),
            "wq": ((l, e, ld), "matmul"), "wk": ((l, e, ld), "matmul"),
            "wv": ((l, e, ld), "matmul"),
            "conv_q": ((l, k, ld), "full"), "conv_k": ((l, k, ld), "full"),
            "conv_v": ((l, k, ld), "full"),
            "w_f1": ((l, e, d), "matmul"), "w_f2": ((l, d, ld), "matmul"),
            "dt_bias": ((l, ld), "full"), "a_log": ((l, h), "full"),
            "w_b": ((l, e, h), "full"), "o_norm": ((l, d), "ones"),
            "w_g1": ((l, e, d), "matmul"), "w_g2": ((l, d, ld), "matmul"),
            "wo": ((l, ld, e), "matmul")})

    top = {"embed": ((v, e), "embed"), "final_norm": ((e,), "ones"),
           "head_layers": gqa(count["head_layers"]),
           "layers": gqa(count["layers"]),
           "lin_layers": linear(count["lin_layers"])}
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
    w["lin_layers/dt_bias"] = np.asarray(
        (jnp.asarray(w["lin_layers/dt_bias"]) + DT_BIAS_SHIFT)
        .astype(jnp.bfloat16).astype(jnp.float32))
    return w


def kernel_shapes(a: dict) -> dict:
    """What ``benchmarks/kernels/paged_mixed_attention.py`` needs: the GQA
    layers, the only ones that keep pages."""
    return {"heads": a["heads"], "kv_heads": a["kv_heads"],
            "head_dim": a["head_dim"], "layers": a["kinds"].count("full")}


def linear_kernel_shapes(a: dict) -> dict:
    """What ``benchmarks/kernels/linear_state_update.py`` needs: the linear
    layers and the state a head."""
    return {"heads": a["lin_heads"], "head_dim": a["lin_dim"],
            "layers": a["kinds"].count("linear"), "state_bytes": 4}


@functools.lru_cache(maxsize=None)
def _jits(akey: tuple):
    import jax
    import jax.numpy as jnp

    a = dict(akey)
    d, hkv = a["head_dim"], a["kv_heads"]
    lh, ld, kk = a["lin_heads"], a["lin_dim"], a["conv"]
    no_state, no_conv, no_gate = (w in a["without"]
                                  for w in ("state", "conv", "gate"))
    state_dtype = jnp.dtype(a["state_dtype"])

    def qkv(x, lw):
        """x [T, E] -> q [T, H, D], k and v [T, Hkv, D], gate [T, H D]."""
        t = x.shape[0]
        h = _rms(x, _widen(lw["attn_norm"]), a["eps"])
        q = (h @ _widen(lw["wq"])).reshape(t, -1, d)
        k = (h @ _widen(lw["wk"])).reshape(t, hkv, d)
        v = (h @ _widen(lw["wv"])).reshape(t, hkv, d)
        gate = jnp.ones((t, q.shape[1] * d), jnp.float32)
        if a["gate"] and not no_gate:
            gate = jax.nn.sigmoid(h @ _widen(lw["wg"]))
        return q, k, v, gate

    def attend(q_blk, k, v, start):
        """Queries ``start ..`` of one block against all keys, a KV head at
        a time, causal; no position enters but through the mask."""
        nq, h = q_blk.shape[:2]
        keep = (start + jnp.arange(nq))[:, None] >= jnp.arange(k.shape[0])

        def one(args):
            qh, kh, vh = args                   # [g, Q, D], [T, D], [T, D]
            s = jnp.einsum("gqd,kd->gqk", qh, kh) * d ** -0.5
            p = jax.nn.softmax(jnp.where(keep[None], s, -jnp.inf), axis=-1)
            return jnp.einsum("gqk,kd->gqd", p, vh)

        qg = jnp.transpose(q_blk.reshape(nq, hkv, h // hkv, d), (1, 2, 0, 3))
        o = jax.lax.map(one, (qg, jnp.swapaxes(k, 0, 1),
                              jnp.swapaxes(v, 0, 1)))   # [Hkv, g, Q, D]
        return jnp.transpose(o, (2, 0, 1, 3)).reshape(nq, h * d)

    def out_proj(x, o, gate, wo):
        return x + (o * gate) @ _widen(wo)

    def conv(p, w):
        """p [T, C], w [K, C]: ``y_t = sum_i w[i] p_{t - K + 1 + i}``."""
        t = p.shape[0]
        pad = jnp.concatenate([jnp.zeros((kk - 1, p.shape[1])), p])
        taps = range(kk - 1, kk) if no_conv else range(kk)
        return sum(pad[i: i + t] * w[i] for i in taps)

    def linear(x, lw):
        """x [T, E] -> x + the linear layer's output, the delta rule one
        token at a time."""
        t = x.shape[0]
        h = _rms(x, _widen(lw["attn_norm"]), a["eps"])

        def head(name, cname):
            return jax.nn.silu(conv(h @ _widen(lw[name]), _widen(lw[cname]))
                               ).reshape(t, lh, ld)

        def unit(z):
            return z / jnp.sqrt(jnp.sum(z * z, -1, keepdims=True) + 1e-6)

        q = unit(head("wq", "conv_q")) * ld ** -0.5
        k, v = unit(head("wk", "conv_k")), head("wv", "conv_v")
        f = (h @ _widen(lw["w_f1"])) @ _widen(lw["w_f2"]) \
            + _widen(lw["dt_bias"])
        decay = jnp.exp(-jnp.exp(_widen(lw["a_log"]))[None, :, None]
                        * jax.nn.softplus(f).reshape(t, lh, ld))
        beta = jax.nn.sigmoid(h @ _widen(lw["w_b"])) \
            * (2.0 if a["neg_eigval"] else 1.0)            # [T, H]

        def step(s, xs):
            qt, kt, vt, at, bt = xs             # [H, d] x 4, [H]
            if no_state:
                s = jnp.zeros_like(s)
            s = at[..., None] * s
            u = bt[:, None] * (vt - jnp.einsum("hkv,hk->hv", s, kt))
            s = s + kt[..., None] * u[:, None, :]
            s = s.astype(state_dtype).astype(jnp.float32)
            return s, jnp.einsum("hkv,hk->hv", s, qt)

        _, o = jax.lax.scan(step, jnp.zeros((lh, ld, ld), jnp.float32),
                            (q, k, v, decay, beta))        # [T, H, d]
        gate = jax.nn.sigmoid((h @ _widen(lw["w_g1"])) @ _widen(lw["w_g2"]))
        y = _rms(o, _widen(lw["o_norm"]), a["eps"]).reshape(t, lh * ld)
        return x + (y * gate) @ _widen(lw["wo"])

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
        qkv=qkv, attend=attend, out_proj=out_proj, linear=linear, ffn=ffn,
        norm2=norm2, route=route, margin=margin).items()}


def _layer_weights(weights: dict, tree: str, l: int) -> dict:
    return {k.split("/", 1)[1]: _common.layer(v, l)
            for k, v in weights.items() if k.startswith(tree + "/")}


def _sequence(a, fn, weights, tokens, rows, margins):
    """One sequence ``tokens [T]`` (T a multiple of Q_BLOCK): the hidden
    state after the last layer, [1, T, E]; per layer the routing margin at
    ``rows`` is appended to ``margins``."""
    import jax.numpy as jnp

    put = _common.put
    t = tokens.shape[0]
    x = _common.embed(weights, tokens[None], a["eps"])[0]
    rows_d = jnp.asarray(rows, jnp.int32)
    for tree, l, kind in _layers(a):
        lw = _layer_weights(weights, tree, l)
        if kind == "linear":
            x = fn["linear"](x, put({k: lw[k] for k in LINEAR}))
        else:
            aw = put({k: lw[k] for k in GQA if k in lw})
            q, k, v, gate = fn["qkv"](x, aw)
            o = jnp.concatenate([fn["attend"](q[s:s + Q_BLOCK], k, v, s)
                                 for s in range(0, t, Q_BLOCK)])
            x = fn["out_proj"](x, o, gate, aw["wo"])
            del q, k, v, o, gate, aw
        hn = fn["norm2"](x, jnp.asarray(lw["mlp_norm"]))
        router, bias = (jnp.asarray(lw["router"]),
                        jnp.asarray(lw["router_bias"]))
        gates = fn["route"](hn, router, bias)
        margins.append(np.asarray(fn["margin"](hn, router, bias, rows_d)))
        for e in range(a["held"]):
            x = x + fn["ffn"](hn, *(put(_common.layer(lw[k], e))
                                    for k in FFN)) \
                * gates[:, a["first"] + e, None]
        if a["shared_ffn"]:
            x = x + fn["ffn"](hn, *(put(lw[k]) for k in SHARED))
    return x[None]


def forward(config: dict, weights: dict, tokens: np.ndarray,
            rows: np.ndarray, margins: list | None = None) -> np.ndarray:
    """Logits ``[B, R, V]`` (float32, host) at positions ``rows [B, R]`` of
    the right-padded sequences ``tokens [B, T]``; each layer's ``[B, R]``
    routing margin at ``rows`` is appended to ``margins`` where a list is
    given.  Each sequence runs alone, cut after the last position asked for
    (causal attention, a causal convolution and a recurrence keep every
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
            logits.append(_common.head(
                weights, x, jnp.asarray(rows[b:b + 1], jnp.int32), a["eps"]))
    if margins is not None:
        for l in range(len(per_seq[0])):
            margins.append(np.stack([got[l] for got in per_seq]))
    return np.concatenate(logits, axis=0)
