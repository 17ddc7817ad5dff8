"""Block-sparse grouped matmul for MoE prefill (Pallas TPU kernel).

``jax.lax.ragged_dot`` serves the grouped path today, but for quantized
(w8a16 / w4a16) experts it forces a DEQUANTIZED materialization of every
routed expert's weights before the matmul (models/moe.py) — doubling (or
4x for int4) expert weight HBM traffic exactly where MoE prefill is
weight-bound.  This kernel is the megablocks-style alternative with the
dequant FUSED: quantized weight tiles are read raw; int8 per-channel
scales fold into the f32 accumulator, int4 group scales dequant the tile
in-register before the MXU dot.

Layout contract (prepared by ``pad_groups``):
- Rows are sorted by expert and each expert's group is padded to a
  ``block_t`` multiple with zero rows, so every [block_t, K] tile belongs
  to exactly ONE expert — ``block_expert`` (scalar prefetch) maps tile row
  index -> expert id, and the weight BlockSpec indexes expert tiles
  data-dependently (same trick as the paged-attention tables).
- Zero padding rows produce zero outputs regardless of expert/scales, so
  out-of-range tiles can point at any expert.

Opt-in for now (``ARKS_MOE_KERNEL=pallas``): the ragged_dot path remains
the default until the kernel is measured on hardware (ROADMAP.md S5).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from arks_tpu.utils import knobs


def moe_impl() -> str:
    impl = knobs.get_str("ARKS_MOE_KERNEL")
    # auto currently resolves to the ragged_dot path; flips to the kernel
    # once measured faster on hardware.
    return "xla" if impl == "auto" else impl


def pad_groups(xs: jnp.ndarray, sorted_expert: jnp.ndarray,
               group_sizes: jnp.ndarray, block_t: int
               ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Scatter expert-sorted rows into block-aligned group slots.

    Returns (xs_padded [Tp, K] with zero fill, dest [T] row positions —
    also the gather map for outputs — and block_expert [Tp/block_t]).
    Tp = T + E*block_t is static (worst-case padding)."""
    t, k = xs.shape
    nx = group_sizes.shape[0]
    # Worst-case padded total, itself block-aligned (static shape).
    tp = (-(-t // block_t) + nx) * block_t
    padded_sizes = -(-group_sizes // block_t) * block_t        # [E]
    pad_starts = jnp.cumsum(padded_sizes) - padded_sizes       # exclusive
    starts = jnp.cumsum(group_sizes) - group_sizes
    dest = (pad_starts[sorted_expert]
            + (jnp.arange(t) - starts[sorted_expert])).astype(jnp.int32)
    xs_padded = jnp.zeros((tp, k), xs.dtype).at[dest].set(xs)
    # Tile -> expert: tile i (rows [i*bt, (i+1)*bt)) belongs to the expert
    # whose padded range contains it; beyond the last group any expert
    # works (all-zero rows), clamp to E-1.
    tile_starts = jnp.arange(tp // block_t, dtype=jnp.int32) * block_t
    ends = jnp.cumsum(padded_sizes)
    block_expert = jnp.minimum(
        jnp.searchsorted(ends, tile_starts, side="right"),
        nx - 1).astype(jnp.int32)
    return xs_padded, dest, block_expert


def _gm_kernel(bexp_ref, x_ref, w_ref, *rest, quantized: bool,
               group: int = 0):
    if quantized:
        ws_ref, o_ref = rest
    else:
        (o_ref,) = rest
    x = x_ref[...]
    w = w_ref[0]
    if quantized and group:
        # int4 groupwise: scales vary ALONG the contraction dim, so they
        # cannot fold into the accumulator like int8's per-channel scales
        # — dequant the tile in-register (same bf16 math as the XLA
        # producer fusion in models/quant._dequant_int4) and feed the MXU.
        gs = ws_ref[0]                                   # [K/G, bn] f32
        kk, bn = w.shape
        wdq = (w.astype(x.dtype).reshape(kk // group, group, bn)
               * gs[:, None, :].astype(x.dtype)).reshape(kk, bn)
        o_ref[...] = jax.lax.dot(
            x, wdq, preferred_element_type=jnp.float32).astype(o_ref.dtype)
        return
    acc = jax.lax.dot(x, w.astype(x.dtype),
                      preferred_element_type=jnp.float32)
    if quantized:
        acc = acc * ws_ref[0]                            # [1, bn] f32
    o_ref[...] = acc.astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("block_t", "block_n", "interpret"))
def grouped_matmul(
    xs: jnp.ndarray,           # [Tp, K] expert-sorted, block-aligned groups
    w: jnp.ndarray,            # [E, K, N] (int8/int4 when scales given)
    block_expert: jnp.ndarray,  # [Tp/block_t] int32 tile -> expert
    w_scale: jnp.ndarray | None = None,  # int8: [E, 1, N] per-channel scales
    w_group_scale: jnp.ndarray | None = None,  # int4: [E, K/G, N] scales
    block_t: int = 128,
    block_n: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """[Tp, N] = per-tile xs @ w[block_expert[tile]] (scales fused)."""
    tp, k = xs.shape
    nx, _, n = w.shape
    if tp % block_t:
        raise ValueError(f"rows {tp} not a multiple of block_t {block_t}")
    block_n = min(block_n, n)
    if n % block_n:
        raise ValueError(f"N {n} not a multiple of block_n {block_n}")
    group = 0
    if w_group_scale is not None:
        if w_scale is not None:
            raise ValueError("w_scale and w_group_scale are exclusive")
        group = k // w_group_scale.shape[1]
    quantized = w_scale is not None or w_group_scale is not None

    def x_map(ti, ni, bexp):
        del ni, bexp
        return (ti, 0)

    def w_map(ti, ni, bexp):
        return (bexp[ti], 0, ni)

    def ws_map(ti, ni, bexp):
        return (bexp[ti], 0, ni)

    def o_map(ti, ni, bexp):
        del bexp
        return (ti, ni)

    def gs_map(ti, ni, bexp):
        return (bexp[ti], 0, ni)

    in_specs = [
        pl.BlockSpec((block_t, k), x_map),
        pl.BlockSpec((1, k, block_n), w_map),
    ]
    inputs = [block_expert.astype(jnp.int32), xs, w]
    if group:
        in_specs.append(pl.BlockSpec((1, k // group, block_n), gs_map))
        inputs.append(w_group_scale)
    elif quantized:
        # [E, 1, N] with a (1, 1, block_n) block: the TPU lowering wants a
        # block's last two dims (8, 128)-divisible or equal to the array's
        # own, which a (1, block_n) block of an [E, N] array is not.
        in_specs.append(pl.BlockSpec((1, 1, block_n), ws_map))
        inputs.append(w_scale)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(tp // block_t, n // block_n),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_t, block_n), o_map),
    )
    return pl.pallas_call(
        functools.partial(_gm_kernel, quantized=quantized, group=group),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((tp, n), xs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="moe_grouped_matmul",
    )(*inputs)


def grouped_ffn(xs: jnp.ndarray, sorted_expert: jnp.ndarray,
                group_sizes: jnp.ndarray, w_gate, w_up, w_down,
                act_dtype, block_t: int = 128,
                interpret: bool | None = None) -> jnp.ndarray:
    """The full gate/up/silu/down expert FFN over expert-sorted rows via
    the block-sparse kernel (int8 dequant fused when the weights carry
    scales).  Returns rows in the SAME sorted order as ``xs``."""
    from arks_tpu.models.quant import is_quantized

    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    def wv(wq):
        """(raw weight, kwargs for grouped_matmul's scale argument)."""
        if is_quantized(wq):
            if "gs" in wq:    # int4 groupwise [E, K/G, N]
                return wq["q"], {"w_group_scale":
                                 wq["gs"].astype(jnp.float32)}
            # [E, 1, N] per-output-channel, as quantize_tensor stores it.
            return wq["q"], {"w_scale": wq["s"].astype(jnp.float32)}
        return wq, {}

    wg, sg = wv(w_gate)
    wu, su = wv(w_up)
    wd, sd = wv(w_down)

    xs_p, dest, bexp = pad_groups(xs, sorted_expert, group_sizes, block_t)
    gate = grouped_matmul(xs_p, wg, bexp, block_t=block_t,
                          interpret=interpret, **sg)
    up = grouped_matmul(xs_p, wu, bexp, block_t=block_t,
                        interpret=interpret, **su)
    act = (jax.nn.silu(gate.astype(jnp.float32)).astype(act_dtype)
           * up.astype(act_dtype))
    down = grouped_matmul(act, wd, bexp, block_t=block_t,
                          interpret=interpret, **sd)
    return down[dest]
