"""The ``swa_moe`` reference family: the ``laguna`` block (poolside): GQA
layers of two kinds in one model, window and full, each kind with a head
count and a RoPE of its own, a per-head output gate, softmax-routed experts
with a scaling factor behind a dense prefix, a sigmoid-gated shared expert.

The contract of a family is in ``benchmarks/README.md`` ("A reference
family"); what every family shares (which weights a seed means, the int8
storage rule, norms, the embedding and the vocabulary-blocked head) is in
``_common.py``.  Nothing here imports the program.

**What the model computes**, on the stored weights widened to float32,
every matmul at the highest precision, no cache, no pages, no kernels
(``h = RMSNorm(x)``, H the layer kind's head count, D ``head_dim``):

- attention of a layer of kind k: ``q = h Wq [H, D]``; ``k, v = h Wk,
  h Wv [Hkv, D]``; RoPE of the kind on q and k: a FULL layer rotates the
  first ``partial_rotary_factor x D`` lanes of a head, with HF's YaRN
  (``inv_freq`` blended between ``theta^(-2i/rot)`` and that over
  ``factor`` by the linear ramp between the lanes that turn ``beta_fast``
  and ``beta_slow`` times in ``original_max_position_embeddings``; cos and
  sin times ``attention_factor``), the other lanes pass through; a WINDOW
  layer rotates the whole head, plain RoPE at its own theta.  Scores
  ``q k / sqrt(D)``, query head i against KV head ``i // (H / Hkv)``,
  causal, and in a window layer only keys with ``pos_q - pos_k <
  sliding_window``; softmax; ``o_i = sum p v``; the gate ``g = sigmoid(h
  Wg) [H]``, one scalar a head from the sublayer's normed input, ``o_i <-
  g_i o_i``; then ``Wo [H D, E]``.  A masked dense softmax, computed a
  block of queries and a KV head at a time;
- layers listed ``dense``: SwiGLU of ``intermediate_size``;
- routed layers: ``p = softmax(h Wr)`` over the router's whole width in
  float32; chosen = the ``num_experts_per_tok`` largest; ``w_e = p_e /
  sum_chosen p`` where ``norm_topk_prob``, times
  ``moe_routed_scaling_factor``; ``y = sum_{e chosen, e held} w_e
  SwiGLU_e(h) + sigmoid(h w_s) SwiGLU_shared(h)``.  Every held expert is
  computed for every token and the unchosen ones weighted zero.

**Assumed** (the published config does not settle them; set by the lineage
of its key names, ``num_experts`` / ``norm_topk_prob`` / ``mlp_only_layers``
/ ``decoder_sparse_step`` / ``shared_expert_intermediate_size`` being
Qwen2-MoE's; the same list stands in ``deploy.json``):

1. SiLU-gated FFNs (SwiGLU), dense, routed and shared alike;
2. softmax over the experts BEFORE the top-k (not sigmoid scores, no
   selection bias);
3. the shared expert's output times ``sigmoid(h w_s)``, ``w_s [E]``;
4. no norm on queries and keys;
5. the per-head gate as written above: a linear map of the sublayer's
   normed input to one logit a head, a sigmoid, on the head's attention
   output before the output projection; no bias;
6. the softmax scale is ``1 / sqrt(D)`` in both kinds (YaRN's
   ``attention_factor`` enters through cos and sin only, as HF's rotary
   embedding applies it, so only the rotated lanes' share of a score
   carries its square);
7. no attention bias, no sink tokens.

**The share.**  ``arch`` reads ``config["share"]`` (``manifest.with_share``):
``num_experts`` experts are held, the experts ``[index x held, (index + 1)
x held)`` of the published count, which is the router's width; what the
absent experts would add is left out.  A sliced vocabulary is a smaller
vocabulary.

**The routing margin** is in the units in which this family selects: the
router LOGIT of the last expert chosen less that of the first left out (a
softmax keeps the order of its logits, so the ten largest probabilities
are the ten largest logits; ``exp(margin)`` is the ratio of the two
probabilities, as in the ``decoder`` family, where a difference of
probabilities over 256 experts would read in the fifth decimal).

**Departures from the published model**, choices of the seeded weights and
not of the mathematics: none beyond the list above (rotate-half RoPE is
what HF's ``laguna`` applies).  ``config["reference_without"]`` (tests
only: ``"window"``, ``"gate"``) computes the same weights with one
mechanism switched off, so that a test can show the comparison sees it.

A sequence is run alone, trimmed to the last position asked for and padded
to a whole number of query blocks, so that a probe of 9k tokens fits beside
the serving pod.
"""

from __future__ import annotations

import functools
import math

import numpy as np

from benchmarks.references import _common
from benchmarks.references._common import rms as _rms, widen as _widen

Q_BLOCK = 512
ATTN = ("attn_norm", "wq", "wk", "wv", "wo")
FFN = ("w_gate", "w_up", "w_down")
SHARED = ("shared_gate_proj", "shared_up", "shared_down")
_KIND = {"full_attention": "full", "sliding_attention": "window"}


def arch(config: dict) -> dict:
    """The sizes the reference needs, from a public ``config.json`` and the
    share ``deploy.json`` states (under ``config["share"]``)."""
    if float(config.get("moe_router_logit_softcapping", 0) or 0):
        raise NotImplementedError("a router logit soft-cap")
    if config.get("moe_apply_router_weight_on_input"):
        raise NotImplementedError("moe_apply_router_weight_on_input")
    layers = config["num_hidden_layers"]
    kinds = tuple(_KIND[k] for k in config["layer_types"])
    heads = tuple(config["num_attention_heads_per_layer"])
    mlp = tuple(config.get("mlp_layer_types") or (
        "dense" if l in (config.get("mlp_only_layers") or ()) else "sparse"
        for l in range(layers)))
    if not len(kinds) == len(heads) == len(mlp) == layers:
        raise ValueError("the per-layer lists do not have one entry a layer")
    by_kind = {k: {h for h, kk in zip(heads, kinds) if kk == k}
               for k in ("full", "window")}
    if any(len(v) != 1 for v in by_kind.values()):
        raise NotImplementedError(f"head counts {by_kind}: one a kind")
    dense = sum(m == "dense" for m in mlp)
    if mlp[:dense] != ("dense",) * dense or "window" in kinds[:dense]:
        raise NotImplementedError("dense layers: a prefix of full layers")
    share = config.get("share") or {}
    held = config["num_experts"]
    chips, index = share.get("chips_per_layer", 1), share.get("index", 0)
    experts = (share.get("published") or {}).get("num_experts", held)
    if held * chips != experts:
        raise ValueError(f"{held} experts held on each of {chips} chips do "
                         f"not make the {experts} the router scores")
    rp = config["rope_parameters"]
    full, win = rp["full_attention"], rp["sliding_attention"]
    if win.get("rope_type", "default") != "default" \
            or float(win.get("partial_rotary_factor", 1)) != 1:
        raise NotImplementedError(f"sliding_attention rope {win!r}")
    if full.get("rope_type", "default") not in ("default", "yarn"):
        raise NotImplementedError(f"full_attention rope {full!r}")
    yarn = full.get("rope_type") == "yarn"
    factor = float(full.get("factor", 1.0)) if yarn else 1.0
    gating = config.get("gating", False)
    if gating not in (False, True, "per-head", "per_head"):
        raise NotImplementedError(f"gating={gating!r}")
    without = tuple(sorted(config.get("reference_without") or ()))
    return {
        "layers": layers, "kinds": kinds, "dense_layers": dense,
        "hidden": config["hidden_size"], "ffn": config["intermediate_size"],
        "moe_ffn": config["moe_intermediate_size"],
        "shared_ffn": int(config.get("shared_expert_intermediate_size", 0)),
        "heads_full": by_kind["full"].pop(),
        "heads_window": by_kind["window"].pop(),
        "kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"],
        "window": int(config["sliding_window"]),
        "gate": bool(gating),
        "held": held, "first": index * held, "experts": experts,
        "top_k": config["num_experts_per_tok"],
        "scaling": float(config.get("moe_routed_scaling_factor", 1.0)),
        "norm_topk": bool(config.get("norm_topk_prob", False)),
        "vocab": config["vocab_size"],
        "eps": float(config.get("rms_norm_eps", 1e-6)),
        "theta_full": float(full.get("rope_theta", 10000.0)),
        "theta_window": float(win.get("rope_theta", 10000.0)),
        "rotary_full": float(full.get("partial_rotary_factor", 1.0)),
        "yarn_factor": factor,
        "yarn_original": float(full.get(
            "original_max_position_embeddings", 0)) if yarn else 0.0,
        "yarn_beta_fast": float(full.get("beta_fast", 32)),
        "yarn_beta_slow": float(full.get("beta_slow", 1)),
        "attention_factor": float(
            full.get("attention_factor") or 0.1 * math.log(factor) + 1.0)
        if yarn else 1.0,
        "tied": bool(config.get("tie_word_embeddings", False)),
        "without": without,
    }


def _layers(a: dict):
    """(tree, index in the tree, kind, routed) of every layer in model
    order: ``dense_layers`` (the prefix), ``layers`` (the full layer of
    each period), ``win_layers`` (the window layers)."""
    out, at = [], {"dense_layers": 0, "layers": 0, "win_layers": 0}
    for l, kind in enumerate(a["kinds"]):
        tree = ("dense_layers" if l < a["dense_layers"]
                else "win_layers" if kind == "window" else "layers")
        out.append((tree, at[tree], kind, tree != "dense_layers"))
        at[tree] += 1
    return out


def param_spec(a: dict) -> list[tuple[str, tuple[int, ...], str]]:
    """``(path, shape, kind)`` of every leaf in counter order: a stacked
    tree a kind of layer, the keys of every level sorted."""
    e, v, d = a["hidden"], a["vocab"], a["head_dim"]
    kvd = a["kv_heads"] * d
    count = {t: sum(1 for tt, *_ in _layers(a) if tt == t)
             for t in ("dense_layers", "layers", "win_layers")}

    def attn(l: int, h: int) -> dict:
        out = {"attn_norm": ((l, e), "ones"), "mlp_norm": ((l, e), "ones"),
               "wq": ((l, e, h * d), "matmul"),
               "wk": ((l, e, kvd), "matmul"), "wv": ((l, e, kvd), "matmul"),
               "wo": ((l, h * d, e), "matmul")}
        if a["gate"]:
            out["attn_gate"] = ((l, e, h), "full")
        return out

    def routed(l: int, h: int) -> dict:
        x, fm, fs = a["held"], a["moe_ffn"], a["shared_ffn"]
        out = dict(attn(l, h), **{
            "router": ((l, e, a["experts"]), "full"),
            "w_gate": ((l, x, e, fm), "matmul"),
            "w_up": ((l, x, e, fm), "matmul"),
            "w_down": ((l, x, fm, e), "matmul")})
        if fs:
            out.update({"shared_gate_proj": ((l, e, fs), "matmul"),
                        "shared_up": ((l, e, fs), "matmul"),
                        "shared_down": ((l, fs, e), "matmul"),
                        "shared_gate": ((l, e), "full")})
        return out

    top = {"embed": ((v, e), "embed"), "final_norm": ((e,), "ones"),
           "layers": routed(count["layers"], a["heads_full"]),
           "win_layers": routed(count["win_layers"], a["heads_window"])}
    if count["dense_layers"]:
        ld, f = count["dense_layers"], a["ffn"]
        top["dense_layers"] = dict(attn(ld, a["heads_full"]), **{
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
    """What ``benchmarks/kernels/paged_mixed_attention.py`` needs: the FULL
    layers, whose launch keeps the mixed kernel's name."""
    return {"heads": a["heads_full"], "kv_heads": a["kv_heads"],
            "head_dim": a["head_dim"], "layers": a["kinds"].count("full")}


def window_kernel_shapes(a: dict) -> dict:
    """What ``benchmarks/kernels/paged_window_attention.py`` needs: the
    window layers, whose launch has a name of its own."""
    return {"heads": a["heads_window"], "kv_heads": a["kv_heads"],
            "head_dim": a["head_dim"], "layers": a["kinds"].count("window"),
            "window": a["window"]}


def rope_frequencies(a: dict, kind: str) -> tuple[np.ndarray, float]:
    """(inverse frequencies [rot / 2], what multiplies cos and sin) of a
    layer kind; rot = the lanes of a head that rotate.  The full kind under
    YaRN as HF's ``_compute_yarn_parameters`` computes it."""
    d = a["head_dim"]
    if kind == "window":
        return (1.0 / a["theta_window"] ** (
            np.arange(0, d, 2, dtype=np.float64) / d)).astype(np.float32), 1.0
    rot, theta = int(d * a["rotary_full"]), a["theta_full"]
    freqs = 1.0 / theta ** (np.arange(0, rot, 2, dtype=np.float64) / rot)
    factor = a["yarn_factor"]
    if factor <= 1:
        return freqs.astype(np.float32), 1.0

    def turn_dim(turns):
        return rot * math.log(a["yarn_original"] / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(turn_dim(a["yarn_beta_fast"])), 0)
    high = min(math.ceil(turn_dim(a["yarn_beta_slow"])), rot - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(rot // 2) - low) / (high - low), 0.0, 1.0)
    blended = freqs / factor * ramp + freqs * (1.0 - ramp)
    return blended.astype(np.float32), a["attention_factor"]


@functools.lru_cache(maxsize=None)
def _jits(akey: tuple):
    import jax
    import jax.numpy as jnp

    a = dict(akey)
    d, hkv = a["head_dim"], a["kv_heads"]
    scale = d ** -0.5
    no_window, no_gate = "window" in a["without"], "gate" in a["without"]

    def rope(x, kind):
        """x [T, H, D]; rotate-half over the kind's rotary lanes, position
        = index along T."""
        inv_freq, factor = rope_frequencies(a, kind)
        rot = 2 * inv_freq.shape[0]
        ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None, None] \
            * jnp.asarray(inv_freq)
        sin, cos = jnp.sin(ang) * factor, jnp.cos(ang) * factor
        x1, x2 = x[..., : rot // 2], x[..., rot // 2: rot]
        return jnp.concatenate(
            [x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., rot:]], -1)

    def qkv(x, lw, kind):
        """x [T, E] -> q [T, H, D], k and v [T, Hkv, D], gate [T, H]."""
        t = x.shape[0]
        h = _rms(x, _widen(lw["attn_norm"]), a["eps"])
        q = (h @ _widen(lw["wq"])).reshape(t, -1, d)
        k = (h @ _widen(lw["wk"])).reshape(t, hkv, d)
        v = (h @ _widen(lw["wv"])).reshape(t, hkv, d)
        gate = jnp.ones((t, q.shape[1]), jnp.float32)
        if a["gate"] and not no_gate:
            gate = jax.nn.sigmoid(h @ _widen(lw["attn_gate"]))
        return rope(q, kind), rope(k, kind), v, gate

    def attend(q_blk, k, v, start, kind):
        """Queries ``start ..`` of one block against all keys, a KV head at
        a time: causal, and within the window in a window layer."""
        nq, h = q_blk.shape[:2]
        qpos = start + jnp.arange(nq)
        kpos = jnp.arange(k.shape[0])
        keep = qpos[:, None] >= kpos[None]
        if kind == "window" and not no_window:
            keep = keep & (qpos[:, None] - kpos[None] < a["window"])

        def one(args):
            qh, kh, vh = args                   # [g, Q, D], [T, D], [T, D]
            s = jnp.einsum("gqd,kd->gqk", qh, kh) * scale
            p = jax.nn.softmax(jnp.where(keep[None], s, -jnp.inf), axis=-1)
            return jnp.einsum("gqk,kd->gqd", p, vh)

        qg = jnp.transpose(q_blk.reshape(nq, hkv, h // hkv, d), (1, 2, 0, 3))
        o = jax.lax.map(one, (qg, jnp.swapaxes(k, 0, 1),
                              jnp.swapaxes(v, 0, 1)))   # [Hkv, g, Q, D]
        return jnp.transpose(o, (2, 0, 1, 3)).reshape(nq, h, d)

    def out_proj(x, o, gate, wo):
        return x + (o * gate[..., None]).reshape(x.shape[0], -1) @ _widen(wo)

    def ffn(hn, w_gate, w_up, w_down):
        gate = hn @ _widen(w_gate)
        return (jax.nn.silu(gate) * (hn @ _widen(w_up))) @ _widen(w_down)

    def norm2(x, w):
        return _rms(x, _widen(w), a["eps"])

    def probs(hn, router):
        return jax.nn.softmax(hn @ _widen(router), axis=-1)

    def route(hn, router):
        """[T, X] combine weights over the router's whole width: softmax,
        the top k chosen, normalised over the chosen and scaled, the rest
        zero."""
        p = probs(hn, router)
        kth = jax.lax.top_k(p, a["top_k"])[0][..., -1:]
        g = jnp.where(p >= kth, p, 0.0)
        if a["norm_topk"]:
            g = g / (jnp.sum(g, axis=-1, keepdims=True) + 1e-20)
        return g * a["scaling"]

    def margin(hn, router, rows):
        """[R]: at positions ``rows``, the router logit of the last expert
        chosen less that of the first left out."""
        top = jax.lax.top_k(jnp.take(hn, rows, axis=0) @ _widen(router),
                            a["top_k"] + 1)[0]
        return top[..., -2] - top[..., -1]

    def shared(hn, w_gate, w_up, w_down, w_s):
        return ffn(hn, w_gate, w_up, w_down) \
            * jax.nn.sigmoid(hn @ _widen(w_s))[:, None]

    static = {"qkv": ("kind",), "attend": ("kind",)}
    return {k: jax.jit(f, static_argnames=static.get(k, ()))
            for k, f in dict(qkv=qkv, attend=attend, out_proj=out_proj,
                             ffn=ffn, norm2=norm2, route=route,
                             margin=margin, shared=shared).items()}


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
        aw = put({k: lw[k] for k in ATTN + (("attn_gate",) if a["gate"]
                                            else ())})
        q, k, v, gate = fn["qkv"](x, aw, kind=kind)
        o = jnp.concatenate([fn["attend"](q[s:s + Q_BLOCK], k, v, s,
                                          kind=kind)
                             for s in range(0, t, Q_BLOCK)])
        x = fn["out_proj"](x, o, gate, aw["wo"])
        del q, k, v, o, gate, aw
        hn = fn["norm2"](x, jnp.asarray(lw["mlp_norm"]))
        if not routed:
            x = x + fn["ffn"](hn, *(put(lw[k]) for k in FFN))
            continue
        router = jnp.asarray(lw["router"])
        gates = fn["route"](hn, router)
        margins.append(np.asarray(fn["margin"](hn, router, rows_d)))
        for e in range(a["held"]):
            x = x + fn["ffn"](hn, *(put(_common.layer(lw[k], e))
                                    for k in FFN)) \
                * gates[:, a["first"] + e, None]
        if a["shared_ffn"]:
            x = x + fn["shared"](hn, *(put(lw[k]) for k in SHARED),
                                 jnp.asarray(lw["shared_gate"]))
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
