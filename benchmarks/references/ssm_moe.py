"""The ``ssm_moe`` reference family: the ``nemotron_h`` block (nvidia;
Nemotron-3-Nano): layers of ONE sublayer each, chosen by a character of
``hybrid_override_pattern``: a Mamba-2 state-space mixer (``M``,
arXiv:2405.21060), a GQA layer without any position signal (``*``) or a
routed FFN of two-matrix relu^2 experts (``E``), alone under one norm and
one residual.

The contract of a family is in ``benchmarks/README.md`` ("A reference
family"); what every family shares (which weights a seed means, the int8
storage rule, norms, the embedding and the vocabulary-blocked head) is in
``_common.py``.  Nothing here imports the program.

**What the model computes**, on the stored weights widened to float32,
every matmul at the highest precision, no cache, no pages, no state kept
between calls, no chunks, no kernels.  ``N_l`` is an RMS norm (eps
``layer_norm_epsilon``) with layer l's own weight; layer l is

    h' = h + Mixer_l(N_l(h))

with ``Mixer_l`` by character l of the pattern, then a final norm and an
untied head; no bias anywhere but the convolution's:

- ``M``, Mamba-2 (H = ``mamba_num_heads`` heads of P = ``mamba_head_dim``,
  G = ``n_groups`` groups, state N = ``ssm_state_size``, K =
  ``conv_kernel`` taps), u the normed input:
  ``[z | xBC | dt] = u W_in`` (H P | H P + 2 G N | H);
  ``xBC = silu(conv(xBC) + b)``, ``conv`` a causal depthwise convolution
  over the last K positions of the sequence (``y_t = sum_i w[i] x_{t - K + 1
  + i}``, positions before the sequence zeros); ``[x | B | C] = xBC`` with
  x [H, P], B and C [G, N], head h reading group ``h // (H / G)``;
  ``dt_t = softplus(dt_t + dt_bias)`` [H]; ``a_t = exp(-exp(A_log) dt_t)``,
  ONE decay a head; state ``S [H, P, N]``, zeros before the sequence, ONE
  TOKEN AT A TIME: ``S_t = a_t S_{t-1} + dt_t x_t (x) B_t``; ``y_t = S_t C_t
  + D x_t``; ``y = GroupRMSNorm_G(y silu(z)) w_norm`` (the gate BEFORE the
  norm, each of G groups of H P / G channels normed alone); ``out = y
  W_out``;
- ``*``, attention: ``q = u Wq [H, D]``, ``k, v = u Wk, u Wv [Hkv, D]``, NO
  rotation and no other position signal; scores ``q k / sqrt(D)``, query
  head i against KV head ``i // (H / Hkv)``, causal softmax over all keys;
  then ``Wo``.  A masked dense softmax, a block of queries and a KV head at
  a time;
- ``E``, the routed FFN: ``s = sigmoid(u W_r)`` over the router's whole
  width in float32; chosen = the top ``num_experts_per_tok`` of ``s + b``
  (one group: no group limit); ``g_e = routed_scaling_factor s_e /
  sum_chosen s`` (``norm_topk_prob``); ``y = sum_{e chosen, e held} g_e
  relu(u W_up,e)^2 W_down,e + relu(u W_up,s)^2 W_down,s``: two matrices an
  expert, no gate matrix, the one shared expert
  ``moe_shared_expert_intermediate_size`` wide and ungated.  Every held
  expert is computed for every token and the unchosen ones weighted zero.

**Assumed** (the published config does not settle them; the same list
stands in ``deploy.json``): the equations are the family's public modelling
file's (``transformers``, ``modeling_nemotron_h.py``) and Mamba-2's; the
mixer's width is ``mamba_num_heads x mamba_head_dim`` (``expand`` is
unread); the grouped norm's eps is ``layer_norm_epsilon``; the attention
module applies no rotation (``rope_theta`` and ``partial_rotary_factor``
are in the file and unread); ``chunk_size`` is the published kernels' block
and ``time_step_*`` the initialiser's, neither enters the mathematics.

**The share.**  ``arch`` reads ``config["share"]`` (``manifest.with_share``):
``n_routed_experts`` experts are held, the experts ``[index x held, (index
+ 1) x held)`` of the published count, which is the router's width; what
the absent experts would add is left out.  A sliced vocabulary is a
smaller vocabulary.

**The routing margin** is in the units in which this family selects: the
biased score ``s + b`` of the last expert chosen less that of the first
left out.

**Departures from the published model**, choices of the seeded weights and
not of the mathematics: ``dt_bias`` is the seeded leaf (normal x 0.02,
bfloat16) PLUS ``DT_BIAS_SHIFT`` = -4, rounded to bfloat16 again (softplus
reads ~0.018, inside the file's own ``time_step_min`` 0.001 ..
``time_step_max`` 0.1: a decay of ~0.98 a token; a leaf near zero would
halve the state at every token); the convolution's taps are the seeded
leaf TIMES ``CONV_SCALE`` = 25, rounded to bfloat16 again (std 0.5, the
order of a trained mixer's taps: at 0.02 x, B and C leave the convolution at
a few hundredths, the state they drive is their third power, nine tenths
and more of a mixer's output is the skip ``D x``, and neither the state nor
its precision would reach the logits); ``A_log``, ``D``, the convolution's bias
and the selection bias are drawn from the seed like a weight (non-zero, so
that every published term is live and dropping one shows).
``config["reference_without"]`` (tests only: ``"state"``, ``"conv"``,
``"conv_bias"``, ``"a_log"``, ``"d_skip"``, ``"gate"``, ``"group_norm"``,
``"router_bias"``) computes the same weights with one mechanism switched
off (the state forgotten at every token, the convolution's older taps
dropped, its bias zero, a decay of ``exp(-dt)``, no skip, no ``silu(z)``,
one norm over all channels, selection without the bias), so that a test can
show the comparison sees it; ``config["reference_state_dtype"]``
(``"bfloat16"``; tests and the builder's control) rounds the state to that
type after every token, which is what a state KEPT in that type would be.

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
CONV_SCALE = 25.0
TREE = {"M": "ssm_layers", "*": "layers", "E": "moe_layers"}
SSM = ("attn_norm", "w_in", "conv_w", "conv_b", "dt_bias", "a_log", "d_skip",
       "ssm_norm", "w_out")
GQA = ("attn_norm", "wq", "wk", "wv", "wo")
FFN = ("w_upt", "w_down")
SHARED = ("shared_up", "shared_down")


def arch(config: dict) -> dict:
    """The sizes the reference needs, from a public ``config.json`` and the
    share ``deploy.json`` states (under ``config["share"]``)."""
    for k, want in (("n_group", 1), ("topk_group", 1),
                    ("mlp_hidden_act", "relu2"), ("mamba_hidden_act", "silu"),
                    ("use_conv_bias", True), ("norm_topk_prob", True)):
        if config.get(k, want) not in (want, None):
            raise NotImplementedError(f"{k}={config[k]!r}: this family "
                                      f"computes {want!r} only")
    for k in ("attention_bias", "mamba_proj_bias", "mlp_bias", "use_bias",
              "tie_word_embeddings"):
        if config.get(k):
            raise NotImplementedError(f"{k}: this family computes none")
    pattern = str(config["hybrid_override_pattern"])
    if set(pattern) - set(TREE) or len(pattern) != config["num_hidden_layers"]:
        raise NotImplementedError(f"hybrid_override_pattern {pattern!r}: one "
                                  "of M, E, * a layer")
    share = config.get("share") or {}
    held = config["n_routed_experts"]
    chips, index = share.get("chips_per_layer", 1), share.get("index", 0)
    experts = (share.get("published") or {}).get("n_routed_experts", held)
    if held * chips != experts:
        raise ValueError(f"{held} experts held on each of {chips} chips do "
                         f"not make the {experts} the router scores")
    return {
        "pattern": pattern,
        "hidden": config["hidden_size"],
        "moe_ffn": config["moe_intermediate_size"],
        "shared_ffn": int(config.get("moe_shared_expert_intermediate_size")
                          or 0) if config.get("n_shared_experts", 1) else 0,
        "heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"],
        "ssm_heads": int(config["mamba_num_heads"]),
        "ssm_head_dim": int(config["mamba_head_dim"]),
        "ssm_state": int(config["ssm_state_size"]),
        "ssm_groups": int(config["n_groups"]),
        "conv": int(config["conv_kernel"]),
        "held": held, "first": index * held, "experts": experts,
        "top_k": config["num_experts_per_tok"],
        "scaling": float(config.get("routed_scaling_factor", 1.0)),
        "vocab": config["vocab_size"],
        "eps": float(config.get("layer_norm_epsilon", 1e-5)),
        "without": tuple(sorted(config.get("reference_without") or ())),
        "state_dtype": str(config.get("reference_state_dtype", "float32")),
    }


def _layers(a: dict):
    """(tree, index in the tree, character) of every layer in model order:
    ``ssm_layers`` (the M layers), ``layers`` (the * layers),
    ``moe_layers`` (the E layers)."""
    out, at = [], dict.fromkeys(TREE.values(), 0)
    for kind in a["pattern"]:
        out.append((TREE[kind], at[TREE[kind]], kind))
        at[TREE[kind]] += 1
    return out


def param_spec(a: dict) -> list[tuple[str, tuple[int, ...], str]]:
    """``(path, shape, kind)`` of every leaf in counter order: a stacked
    tree a kind of sublayer, the keys of every level sorted.  A leaf's
    shape is the one it is DRAWN and quantised in, ``[.., K, N]``; the two
    the serving program stores transposed (``ssm_layers/w_in``,
    ``moe_layers/w_upt``: an expert's ``W_up``, under the name the program
    gives it) hold the same numbers."""
    e, v = a["hidden"], a["vocab"]
    lm, la, le = (a["pattern"].count(k) for k in "M*E")
    h, p, n, g = (a["ssm_heads"], a["ssm_head_dim"], a["ssm_state"],
                  a["ssm_groups"])
    d_in, c = h * p, h * p + 2 * g * n
    qd, kvd = a["heads"] * a["head_dim"], a["kv_heads"] * a["head_dim"]
    x, fm, fs = a["held"], a["moe_ffn"], a["shared_ffn"]
    moe = {"mlp_norm": ((le, e), "ones"),
           "router": ((le, e, a["experts"]), "full"),
           "router_bias": ((le, a["experts"]), "full"),
           "w_upt": ((le, x, e, fm), "matmul"),
           "w_down": ((le, x, fm, e), "matmul")}
    if fs:
        moe.update({"shared_up": ((le, e, fs), "matmul"),
                    "shared_down": ((le, fs, e), "matmul")})
    top = {
        "embed": ((v, e), "embed"), "final_norm": ((e,), "ones"),
        "lm_head": ((e, v), "matmul"),
        "ssm_layers": {
            "attn_norm": ((lm, e), "ones"),
            "w_in": ((lm, e, d_in + c + h), "matmul"),
            "conv_w": ((lm, a["conv"], c), "full"),
            "conv_b": ((lm, c), "full"), "dt_bias": ((lm, h), "full"),
            "a_log": ((lm, h), "full"), "d_skip": ((lm, h), "full"),
            "ssm_norm": ((lm, d_in), "ones"),
            "w_out": ((lm, d_in, e), "matmul")},
        "layers": {
            "attn_norm": ((la, e), "ones"),
            "wq": ((la, e, qd), "matmul"), "wk": ((la, e, kvd), "matmul"),
            "wv": ((la, e, kvd), "matmul"), "wo": ((la, qd, e), "matmul")},
        "moe_layers": moe}
    out = []
    for name in sorted(top):
        if isinstance(top[name], dict):
            out += [(f"{name}/{k}", *top[name][k]) for k in sorted(top[name])]
        else:
            out.append((name, *top[name]))
    return out


def generate_weights(config: dict, seed: int, weight_bits: int = 8) -> dict:
    """The weights seed ``seed`` means for this configuration, parked in
    host memory (``_common.generate_weights``), ``dt_bias`` shifted and the
    convolution's taps scaled (the module's head says why)."""
    import jax.numpy as jnp
    w = _common.generate_weights(param_spec(arch(config)), seed, weight_bits)
    for path, fn in (("dt_bias", lambda x: x + DT_BIAS_SHIFT),
                     ("conv_w", lambda x: x * CONV_SCALE)):
        path = "ssm_layers/" + path
        w[path] = np.asarray(fn(jnp.asarray(w[path])).astype(jnp.bfloat16)
                             .astype(jnp.float32))
    return w


def kernel_shapes(a: dict) -> dict:
    """What ``benchmarks/kernels/paged_mixed_attention.py`` needs: the GQA
    layers, the only ones that keep pages."""
    return {"heads": a["heads"], "kv_heads": a["kv_heads"],
            "head_dim": a["head_dim"], "layers": a["pattern"].count("*")}


def ssm_kernel_shapes(a: dict) -> dict:
    """What ``benchmarks/kernels/ssm_state_update.py`` needs: the Mamba-2
    layers and the state a head."""
    return {"heads": a["ssm_heads"], "head_dim": a["ssm_head_dim"],
            "state": a["ssm_state"], "groups": a["ssm_groups"],
            "layers": a["pattern"].count("M"), "state_bytes": 4}


@functools.lru_cache(maxsize=None)
def _jits(akey: tuple):
    import jax
    import jax.numpy as jnp

    a = dict(akey)
    d, hkv = a["head_dim"], a["kv_heads"]
    mh, mp, mn, mg, kk = (a["ssm_heads"], a["ssm_head_dim"], a["ssm_state"],
                          a["ssm_groups"], a["conv"])
    without = set(a["without"])
    state_dtype = jnp.dtype(a["state_dtype"])

    def qkv(x, lw):
        """x [T, E] -> q [T, H, D], k and v [T, Hkv, D]."""
        t = x.shape[0]
        h = _rms(x, _widen(lw["attn_norm"]), a["eps"])
        return ((h @ _widen(lw["wq"])).reshape(t, -1, d),
                (h @ _widen(lw["wk"])).reshape(t, hkv, d),
                (h @ _widen(lw["wv"])).reshape(t, hkv, d))

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

    def out_proj(x, o, wo):
        return x + o @ _widen(wo)

    def conv(p, w, bias):
        """p [T, C], w [K, C]: ``y_t = sum_i w[i] p_{t - K + 1 + i} + b``."""
        t = p.shape[0]
        pad = jnp.concatenate([jnp.zeros((kk - 1, p.shape[1])), p])
        taps = range(kk - 1, kk) if "conv" in without else range(kk)
        y = sum(pad[i: i + t] * w[i] for i in taps)
        return y if "conv_bias" in without else y + bias

    def mamba(x, lw):
        """x [T, E] -> x + the Mamba-2 mixer's output, the selective scan
        one token at a time."""
        t = x.shape[0]
        d_in, gn = mh * mp, mg * mn
        u = _rms(x, _widen(lw["attn_norm"]), a["eps"])
        zxbcdt = u @ _widen(lw["w_in"])
        z, xbc, dt = (zxbcdt[:, :d_in], zxbcdt[:, d_in: 2 * d_in + 2 * gn],
                      zxbcdt[:, 2 * d_in + 2 * gn:])
        xbc = jax.nn.silu(conv(xbc, _widen(lw["conv_w"]),
                               _widen(lw["conv_b"])))
        xs = xbc[:, :d_in].reshape(t, mh, mp)
        # Head h reads group h // (H / G).
        b = jnp.repeat(xbc[:, d_in: d_in + gn].reshape(t, mg, mn),
                       mh // mg, axis=1)                        # [T, H, N]
        c = jnp.repeat(xbc[:, d_in + gn:].reshape(t, mg, mn),
                       mh // mg, axis=1)
        dt = jax.nn.softplus(dt + _widen(lw["dt_bias"]))        # [T, H]
        rate = 1.0 if "a_log" in without else jnp.exp(_widen(lw["a_log"]))
        decay = jnp.exp(-rate * dt)

        def step(s, row):
            xt, bt, ct, at, dtt = row           # [H,P] [H,N] [H,N] [H] [H]
            if "state" in without:
                s = jnp.zeros_like(s)
            s = at[:, None, None] * s \
                + (dtt[:, None] * xt)[:, :, None] * bt[:, None, :]
            s = s.astype(state_dtype).astype(jnp.float32)
            return s, jnp.einsum("hpn,hn->hp", s, ct)

        _, y = jax.lax.scan(step, jnp.zeros((mh, mp, mn), jnp.float32),
                            (xs, b, c, decay, dt))              # [T, H, P]
        if "d_skip" not in without:
            y = y + _widen(lw["d_skip"])[None, :, None] * xs
        y = y.reshape(t, d_in)
        if "gate" not in without:
            y = y * jax.nn.silu(z)
        groups = 1 if "group_norm" in without else mg
        y = y.reshape(t, groups, -1)
        y = y / jnp.sqrt(jnp.mean(y * y, -1, keepdims=True) + a["eps"])
        y = y.reshape(t, d_in) * _widen(lw["ssm_norm"])
        return x + y @ _widen(lw["w_out"])

    def ffn(hn, w_up, w_down):
        return jnp.square(jax.nn.relu(hn @ _widen(w_up))) @ _widen(w_down)

    def norm2(x, w):
        return _rms(x, _widen(w), a["eps"])

    def biased(hn, router, bias):
        sigma = jax.nn.sigmoid(hn @ _widen(router))
        if "router_bias" in without:
            return sigma, sigma
        return sigma, sigma + _widen(bias)

    def route(hn, router, bias):
        """[T, X] combine weights over the router's whole width: sigmoid
        scores, the top k of score + bias chosen, the chosen ones' unbiased
        scores normalised and scaled, the rest zero."""
        sigma, sel = biased(hn, router, bias)
        kth = jax.lax.top_k(sel, a["top_k"])[0][..., -1:]
        g = jnp.where(sel >= kth, sigma, 0.0)
        g = g / (jnp.sum(g, axis=-1, keepdims=True) + 1e-20)
        return g * a["scaling"]

    def margin(hn, router, bias, rows):
        """[R]: at positions ``rows``, the biased score of the last expert
        chosen less that of the first left out."""
        _, sel = biased(jnp.take(hn, rows, axis=0), router, bias)
        top = jax.lax.top_k(sel, a["top_k"] + 1)[0]
        return top[..., -2] - top[..., -1]

    return {k: jax.jit(f) for k, f in dict(
        qkv=qkv, attend=attend, out_proj=out_proj, mamba=mamba, ffn=ffn,
        norm2=norm2, route=route, margin=margin).items()}


def _layer_weights(weights: dict, tree: str, l: int) -> dict:
    return {k.split("/", 1)[1]: _common.layer(v, l)
            for k, v in weights.items() if k.startswith(tree + "/")}


def _sequence(a, fn, weights, tokens, rows, margins):
    """One sequence ``tokens [T]`` (T a multiple of Q_BLOCK): the hidden
    state after the last layer, [1, T, E]; per routed layer the routing
    margin at ``rows`` is appended to ``margins``."""
    import jax.numpy as jnp

    put = _common.put
    t = tokens.shape[0]
    x = _common.embed(weights, tokens[None], a["eps"])[0]
    rows_d = jnp.asarray(rows, jnp.int32)
    for tree, l, kind in _layers(a):
        lw = _layer_weights(weights, tree, l)
        if kind == "M":
            x = fn["mamba"](x, put({k: lw[k] for k in SSM}))
        elif kind == "*":
            aw = put({k: lw[k] for k in GQA})
            q, k, v = fn["qkv"](x, aw)
            o = jnp.concatenate([fn["attend"](q[s:s + Q_BLOCK], k, v, s)
                                 for s in range(0, t, Q_BLOCK)])
            x = fn["out_proj"](x, o, aw["wo"])
            del q, k, v, o, aw
        else:
            hn = fn["norm2"](x, jnp.asarray(lw["mlp_norm"]))
            router, bias = (jnp.asarray(lw["router"]),
                            jnp.asarray(lw["router_bias"]))
            gates = fn["route"](hn, router, bias)
            margins.append(np.asarray(fn["margin"](hn, router, bias,
                                                   rows_d)))
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
    the right-padded sequences ``tokens [B, T]``; each routed layer's ``[B,
    R]`` routing margin at ``rows`` is appended to ``margins`` where a list
    is given.  Each sequence runs alone, cut after the last position asked
    for (causal attention, a causal convolution and a recurrence keep every
    kept position blind to what follows) and padded to whole query
    blocks."""
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
