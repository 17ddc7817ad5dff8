"""Rotary position embeddings (NeoX/Llama interleaving: rotate_half).

Position-indexed on the fly (no precomputed table) so the same code path
serves prefill ([B, T] positions) and decode ([B] positions) — XLA fuses the
sin/cos into the surrounding elementwise work, which beats gathering from an
HBM-resident table for decode-sized batches.
"""

from __future__ import annotations

import math

import jax.numpy as jnp


def rope_freqs(head_dim: int, theta: float,
               yarn: tuple[float, ...] = ()) -> jnp.ndarray:
    """Inverse frequencies, shape [head_dim // 2], float32.  ``yarn``
    (``ModelConfig.rope_yarn``: factor, original context, beta_fast,
    beta_slow, mscale, mscale_all_dim) blends each frequency between its
    own value and that value over ``factor``, as DeepSeek-V3's modelling
    file does: lanes that turn more than ``beta_fast`` times inside the
    original context keep theirs, lanes that turn fewer than ``beta_slow``
    times are interpolated, a linear ramp between."""
    exponent = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    freqs = 1.0 / (theta ** exponent)
    if not yarn or yarn[0] <= 1:
        return freqs
    factor, original, beta_fast, beta_slow = yarn[:4]

    def turn_dim(turns: float) -> float:
        return (head_dim * math.log(original / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(turn_dim(beta_fast)), 0)
    high = min(math.ceil(turn_dim(beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return freqs / factor * ramp + freqs * (1.0 - ramp)


def yarn_mscale(yarn: tuple[float, ...]) -> float:
    """What YaRN multiplies cos and sin by: ``m(mscale) / m(mscale_all_dim)``
    with ``m(s) = 0.1 s ln(factor) + 1`` (1.0 where the two are equal)."""
    if not yarn or yarn[0] <= 1:
        return 1.0

    def m(s: float) -> float:
        return 0.1 * s * math.log(yarn[0]) + 1.0

    return m(yarn[4]) / m(yarn[5])


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float,
               yarn: tuple[float, ...] = (), *, rotary_dim: int | None = None,
               attention_factor: float | None = None) -> jnp.ndarray:
    """Apply rotary embedding.

    x: [..., H, D] with leading dims matching ``positions`` (e.g. x [B, T, H, D]
    with positions [B, T], or x [B, H, D] with positions [B]).

    ``rotary_dim`` (HF ``partial_rotary_factor`` x D): only the first
    ``rotary_dim`` lanes of a head rotate (rotate-half within them), the
    rest pass through.  ``attention_factor`` is HF's YaRN: ``yarn`` then
    gives the blend alone (its first four entries) and cos and sin are
    multiplied by the factor as stated, so that the rotated lanes' share of
    a score carries its square and the lanes that pass through do not.
    """
    d = x.shape[-1] if rotary_dim is None else rotary_dim
    if rotary_dim is not None:
        x, rest = x[..., :d], x[..., d:]
    freqs = rope_freqs(d, theta, yarn)  # [D/2]
    angles = positions.astype(jnp.float32)[..., None, None] * freqs  # [..., 1, D/2]
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    mscale = yarn_mscale(yarn) if attention_factor is None \
        else attention_factor
    if mscale != 1.0:
        sin, cos = sin * mscale, cos * mscale
    x1, x2 = x[..., : d // 2], x[..., d // 2 :]
    x1f, x2f = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate([x1f * cos - x2f * sin, x2f * cos + x1f * sin], axis=-1)
    if rotary_dim is not None:
        return jnp.concatenate([out.astype(x.dtype), rest], axis=-1)
    return out.astype(x.dtype)
