"""What every reference family shares.  Nothing here imports the program.

1. **Which weights a seed means.**  The serving pod is started with
   ``--seed S`` and no checkpoint, so it serves seeded random weights.  The
   rule, stated here as a specification and checked against the program by
   ``benchmarks/tests/test_reference.py``:

   - the parameter tree is the one the family's ``param_spec`` lists
     (stacked layers, leading ``[L]``), walked depth first with the keys of
     every level in sorted order; every leaf, norms and biases too, takes
     the next number of a counter that starts at 1;
   - leaf ``n`` is drawn with ``fold_in(PRNGKey(S), n)``: by ``kind``,
     ``ones`` (norms) are ones, ``zeros`` (biases) zeros, everything else
     ``normal * 0.02`` in float32 rounded to bfloat16;
   - with ``weight_bits=8`` (what every cell serves) a ``matmul`` weight
     ``[.., K, N]`` is stored as int8 with one float32 scale per output
     channel (``max|w| / 127`` over K), the ``embed`` table ``[V, E]`` with
     one scale per row; a ``full`` leaf (the router) stays bfloat16.
     ``weight_bits=0`` keeps every leaf bfloat16.

2. **The pieces of a forward that do not depend on the block**: stored
   weights widened to float32, RMS norm, rotate-half RoPE, the embedding
   lookup, the final norm and the output head over the vocabulary in
   blocks, ``log_softmax``.  A family's ``forward`` runs under
   ``highest_precision()``.

The stored weights of a 7B model do not fit beside the serving engine, so
``generate_weights`` parks them in host memory and a family's ``forward``
brings one layer (one expert) at a time back to the device with ``put``.
"""

from __future__ import annotations

import functools

import numpy as np

VOCAB_BLOCK = 1 << 15


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


def generate_weights(spec: list[tuple[str, tuple[int, ...], str]], seed: int,
                     weight_bits: int = 8) -> dict:
    """``{path: leaf}`` in host memory for a family's ``param_spec``; a
    leaf is a numpy array (bfloat16 leaves are widened to float32, which is
    exact) or ``{"q", "s"}``."""
    if weight_bits not in (0, 8):
        raise ValueError(f"weight_bits={weight_bits}: the reference holds "
                         "the weights the cells state, int8 or bfloat16")
    import jax

    key = jax.random.PRNGKey(seed)
    out = {}
    for n, (path, shape, kind) in enumerate(spec, 1):
        leaf = _leaf_fn(shape, kind, weight_bits)(jax.random.fold_in(key, n))
        if isinstance(leaf, dict):
            out[path] = {k: np.asarray(v) for k, v in leaf.items()}
        else:
            out[path] = np.asarray(leaf.astype("float32"))
        del leaf
    return out


def layer(leaf, l: int):
    """Entry ``l`` of a stacked leaf (a layer of ``[L, ..]``, an expert of
    ``[X, ..]``)."""
    if isinstance(leaf, dict):
        return {k: v[l] for k, v in leaf.items()}
    return leaf[l]


def layer_weights(weights: dict, l: int) -> dict:
    """``{name: leaf}`` of layer ``l``, still on the host."""
    return {k.split("/", 1)[1]: layer(v, l)
            for k, v in weights.items() if k.startswith("layers/")}


def put(tree):
    """Parked leaves back on the device."""
    import jax
    import jax.numpy as jnp
    return jax.tree.map(jnp.asarray, tree)


def highest_precision():
    """On a TPU a float32 matmul runs in lower precision unless this is
    set; every family's ``forward`` runs under it."""
    import jax
    return jax.default_matmul_precision("highest")


def widen(w):
    """A stored leaf (already on the device) as float32."""
    import jax.numpy as jnp
    if not isinstance(w, dict):
        return w.astype(jnp.float32)
    return w["q"].astype(jnp.float32) * w["s"]


def rms(x, w, eps):
    import jax.numpy as jnp
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(x, theta):
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
def _ends(eps: float):
    import jax
    import jax.numpy as jnp

    def embed(table, tokens):
        rows = jnp.take(table["q"], tokens, axis=0).astype(jnp.float32) \
            if isinstance(table, dict) else jnp.take(table, tokens, axis=0)
        if isinstance(table, dict):
            rows = rows * jnp.take(table["s"], tokens, axis=0)
        return rows

    def logits(x, final_norm, head, rows):
        """Log-softmax inputs need the whole vocabulary; ``rows`` [B, R]
        picks the positions wanted."""
        h = rms(jnp.take_along_axis(x, rows[..., None], axis=1),
                widen(final_norm), eps)
        return h @ widen(head)

    return jax.jit(embed), jax.jit(logits)


def embed(weights: dict, tokens: np.ndarray, eps: float):
    """``[B, T, E]`` float32 on the device: the rows of the stored table."""
    import jax.numpy as jnp
    embed_fn, _ = _ends(eps)
    return embed_fn(put(weights["embed"]), jnp.asarray(tokens, jnp.int32))


def head(weights: dict, x, rows, eps: float) -> np.ndarray:
    """Final norm and the untied output head at positions ``rows [B, R]``,
    the vocabulary in blocks: logits ``[B, R, V]`` (float32, host)."""
    import jax.numpy as jnp
    _, logits_fn = _ends(eps)
    final = jnp.asarray(weights["final_norm"])
    w = weights["lm_head"]
    vocab = (w["q"] if isinstance(w, dict) else w).shape[-1]
    out = []
    for c0 in range(0, vocab, VOCAB_BLOCK):
        blk = {k: v[..., c0:c0 + VOCAB_BLOCK] for k, v in w.items()} \
            if isinstance(w, dict) else w[:, c0:c0 + VOCAB_BLOCK]
        out.append(np.asarray(logits_fn(x, final, put(blk), rows)))
    return np.concatenate(out, axis=-1)


def log_softmax(logits: np.ndarray) -> np.ndarray:
    z = logits - logits.max(axis=-1, keepdims=True)
    return z - np.log(np.exp(z).sum(axis=-1, keepdims=True))
