"""The Mamba-2 mixers' one recurrence step as one pass over the live lanes.

A state-space layer keeps a float32 state ``S [H, P, N]`` a slot (P the
head's width, N the state's).  A lane of ONE row (a decode lane, a prompt's
last token) takes one step of the selective scan::

    S = a S + (dt x) (x) B      (S: zeros where the slot starts a sequence)
    y = S C

with ONE decay ``a`` a head and ``B``, ``C`` ``[N]`` shared by the heads of a
group.  :func:`ssm_state_step` does that for a list of slots in one
``pallas_call``, the sibling of ``ops/linear_state.py::linear_state_step``
whose list-of-slots grid, in-place aliasing and ``fresh`` flag it shares: a
block of a slot's heads is fetched from HBM once, held in VMEM across the
two lines above, and written once, in place; a slot outside the list is
neither read nor written.  Everything is float32 on the VPU, so no bfloat16
pass goes over the state.

**The stored layout** (:func:`pack_state`): ``[H / r, N, r P]``, the state's
width N down the sublanes and the widths of ``r`` heads of one group side by
side along the lanes (r = 128 / P where the group has as many: two heads of
64 a tile).  So the rows of the step's flat batch are the kernel's rows as
they lie: ``dt x``, the decay and the output ``y`` index the lanes (a head's
P channels beside its neighbour's) and broadcast down the sublanes; only
``B`` and ``C``, which index the state's width, are turned into columns, a
group's 2 x ``[N]`` a grid step; and the read-out ``S C`` sums down the
sublanes, vreg onto vreg.  Stored ``[H, P, N]``, the state's width along the
lanes, every head's read-out was eight cross-lane reductions and its ``dt
x`` and ``y`` went through two transposes: 382 GB/s of state at 64 lanes on
a v5e where the delta rule's kernel streams 610 (PERF.md section 6, PR 56).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from arks_tpu.ops.paged_attention import _pick_block_b

# Heads a grid step, in whole groups: 16 x [64, 128] float32 is 512 KB, 2 MB
# with the input's and the output's double buffers (linear_state.py's
# reading: a step is the DMA's, whatever it computes).
_HEAD_BLOCK = 16
_TILE = 128


def heads_a_tile(head_dim: int, per_group: int) -> int:
    """Heads of one group whose widths share a tile's 128 lanes."""
    r = 1
    while 2 * r * head_dim <= _TILE and per_group % (2 * r) == 0:
        r *= 2
    return r


def pack_state(s: jnp.ndarray, per_group: int) -> jnp.ndarray:
    """``[.., H, P, N]`` as it is stored, ``[.., H / r, N, r P]``."""
    *lead, h, p, n = s.shape
    r = heads_a_tile(p, per_group)
    s = s.reshape(*lead, h // r, r, p, n)
    return jnp.moveaxis(s, -1, -3).reshape(*lead, h // r, n, r * p)


def unpack_state(s: jnp.ndarray, head_dim: int) -> jnp.ndarray:
    """:func:`pack_state`'s inverse: ``[.., H / r, N, r P]`` -> ``[.., H, P,
    N]``."""
    *lead, tiles, n, rp = s.shape
    r = rp // head_dim
    s = s.reshape(*lead, tiles, n, r, head_dim)
    return jnp.moveaxis(s, -3, -1).reshape(*lead, tiles * r, head_dim, n)


def _step_kernel(layer_ref, slots_ref, n_ref, fresh_ref, at_ref, x_ref,
                 a_ref, b_ref, c_ref, o_zero, s_ref, s_out, o_ref, stage,
                 cols):
    """One block of ``tb`` tiles (``gb`` whole groups) of one listed slot:
    the slot's row of ``dt x`` and of the decay ``[tb, r P]``, of ``B`` and
    ``C [gb, 1, N]``, the state ``[tb, N, r P]`` in and out, the output row
    ``[tb, r P]``; ``stage`` and ``cols`` ``[>= 2 gb, >= N]`` tiles are
    scratch."""
    del layer_ref, at_ref, o_zero
    i, j = pl.program_id(0), pl.program_id(1)
    n = n_ref[0]
    tb, width = s_ref.shape[0], s_ref.shape[1]
    gb = b_ref.shape[0]
    f32 = jnp.float32

    @pl.when(i < n)
    def _():
        # Columns g and gb + g of ``cols``: group g's B and C.  (What the
        # tiles hold outside these is never read.)
        for g in range(gb):
            stage[g:g + 1, 0:width] = b_ref[g].astype(f32)
            stage[gb + g:gb + g + 1, 0:width] = c_ref[g].astype(f32)
        cols[...] = stage[...].T
        keep = fresh_ref[slots_ref[i]] == 0
        for k in range(tb):
            g = k * gb // tb
            row = slice(k, k + 1)
            s = jnp.where(keep, s_ref[k].astype(f32), 0.0) * a_ref[row, :] \
                + cols[0:width, g:g + 1] * x_ref[row, :]
            s_out[k] = s.astype(s_out.dtype)
            o_ref[row, :] = jnp.sum(s * cols[0:width, gb + g:gb + g + 1],
                                    axis=0, keepdims=True)

    # A step behind the list's end maps to the list's last block (see
    # ``lane`` below) and does nothing: the block's new state and output
    # stay in their buffers until the grid ends.  With an empty list every
    # step maps to one block, which goes back as it came.
    @pl.when((n == 0) & (i == 0) & (j == 0))
    def _():
        s_out[...] = s_ref[...]
        o_ref[...] = jnp.zeros_like(o_ref)


@functools.partial(jax.jit, static_argnames=("pad", "interpret"))
def ssm_state_step(x, b, c, g, s_all, layer, slots, count, fresh, at,
                   pad: int = 0, interpret: bool = False):
    """One recurrence step for the slots ``slots[:count]`` (distinct; what
    lies behind ``count`` is padding and is not looked at) of layer
    ``layer`` of ``s_all [Lm, B, H / r, N, r P]`` (:func:`pack_state`),
    rewritten in place.  Slot b's row is row ``at[b]`` of the flat ``x [T,
    H, P]`` (the head's input times its step size), of ``b, c [T, G, N]``
    (head h reads group ``h // (H / G)``) and of the log decay ``g [T, H]``;
    ``fresh [B]``: the slot's old state reads as zeros.  Returns (``y [T +
    pad, H, P]`` float32: the listed slots' rows, zeros elsewhere;
    ``s_all``)."""
    _, nb, tiles, n, rp = s_all.shape
    t, h, p = x.shape
    groups = b.shape[1]
    gb = _pick_block_b(groups, max(1, _HEAD_BLOCK * groups // h))
    tb = tiles // groups * gb
    nj = groups // gb
    f32 = jnp.float32
    tile = -(-max(2 * gb, n) // _TILE) * _TILE

    def lane(i, j, n_ref, slots_ref):
        """The (slot, block) of grid step (i, j); behind the list's end, the
        list's last block again, so that nothing more is fetched or
        written."""
        n = n_ref[0]
        slot = slots_ref[jnp.maximum(jnp.minimum(i, n - 1), 0)]
        return slot, jnp.where(i < n, j, nj - 1)

    def row_map(i, j, layer_ref, slots_ref, n_ref, fresh_ref, at_ref):
        del layer_ref, fresh_ref
        slot, jj = lane(i, j, n_ref, slots_ref)
        return at_ref[slot], jj, 0

    def group_map(*a):
        return (*row_map(*a), 0)

    def state_map(i, j, layer_ref, slots_ref, n_ref, fresh_ref, at_ref):
        del fresh_ref, at_ref
        return (layer_ref[0], *lane(i, j, n_ref, slots_ref), 0, 0)

    row_spec = pl.BlockSpec((None, tb, rp), row_map)
    group_spec = pl.BlockSpec((None, gb, 1, n), group_map)
    state_spec = pl.BlockSpec((None, None, tb, n, rp), state_map)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,  # layer, the list, its length, fresh, rows
        grid=(nb, nj),
        in_specs=[row_spec, row_spec, group_spec, group_spec,
                  pl.BlockSpec(memory_space=pl.ANY), state_spec],
        out_specs=(state_spec, row_spec),
        scratch_shapes=[pltpu.VMEM((tile, tile), f32),
                        pltpu.VMEM((tile, tile), f32)],
    )
    # A head's one decay over the head's lanes, as its ``dt x`` lies there.
    decay = jnp.repeat(jnp.exp(g.astype(f32)), p, axis=1)
    s_all, y = pl.pallas_call(
        _step_kernel,
        grid_spec=grid_spec,
        out_shape=(jax.ShapeDtypeStruct(s_all.shape, s_all.dtype),
                   jax.ShapeDtypeStruct((t + pad, tiles, rp), f32)),
        # 0-4 the scalars, 5-8 the rows, 9 the output's zeros, 10 the states.
        input_output_aliases={9: 1, 10: 0},
        # In list order: a step behind the list's end rests on the step
        # before it having been the list's last.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="ssm_state_step",
    )(jnp.asarray(layer, jnp.int32).reshape(1), slots.astype(jnp.int32),
      jnp.asarray(count, jnp.int32).reshape(1), fresh.astype(jnp.int32),
      at.astype(jnp.int32), x.astype(f32).reshape(t, tiles, rp),
      decay.reshape(t, tiles, rp), b.astype(f32)[:, :, None],
      c.astype(f32)[:, :, None], jnp.zeros((t + pad, tiles, rp), f32), s_all)
    return y.reshape(t + pad, h, p), s_all
