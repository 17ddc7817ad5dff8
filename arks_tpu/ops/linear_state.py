"""The linear layers' one recurrence step as one pass over the live lanes.

A linear-attention layer keeps a ``[H, d, d]`` float32 state a slot
(``models/transformer.py::LinearState``).  A lane of ONE row (a decode lane,
a prompt's last token) takes one step of the gated delta rule::

    S' = diag(a) S        (zeros where the slot starts a sequence)
    u  = beta (v - S'^T k)
    S  = S' + k u^T
    o  = S^T q

:func:`linear_state_step` does that for a list of slots in one
``pallas_call``: a block of a slot's heads is fetched from HBM once, held in
VMEM across the four lines above, and written once, in place.  A slot outside
the list is neither read nor written.  Everything is float32 on the VPU: the
two contractions over the key channel are a multiply and a sum down the
sublanes, so no bfloat16 pass goes over the state.  The lanes' rows come
straight out of the step's flat batch and their outputs go straight into
its rows (the block index maps read the row of a slot), so no gather, no
transpose and no scatter stands around the call.

The key channel lies along the state block's sublanes and the value channel
along its lanes.  ``a``, ``k`` and ``q`` index the key channel: their rows
are stacked and transposed in VMEM, a block of heads a grid step, into
columns that broadcast along the lanes.  ``v``, ``u`` and ``o`` index the
value channel and stay rows.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from arks_tpu.ops.paged_attention import _pick_block_b

# Heads a grid step: 16 x [128, 128] float32 is 1 MB, 4 MB with the input's
# and the output's double buffers; their k, q and a rows, 48 of them, fill
# one [128, 128] transpose with room to spare.  (On the chip a step is the
# DMA's, whatever it computes: 8, 16 and 32 heads a step read 0.93, 0.88
# and 0.86 ms a layer at 64 lanes, PERF.md §6, PR 41.)
_HEAD_BLOCK = 16
_TILE = 128


def _step_kernel(layer_ref, slots_ref, n_ref, fresh_ref, at_ref, q_ref,
                 k_ref, v_ref, g_ref, b_ref, o_zero, s_ref, s_out, o_ref,
                 cols):
    """One block of ``hb`` heads of one listed slot: the slot's row of ``q,
    k, v [hb, d]``, of the log decay ``g [hb, d | 1]`` and of the step size
    ``b [hb, 1]``, the state ``[hb, d, d]`` in and out, the output row ``[hb,
    d]``; ``cols [d, >= 3 hb]`` is scratch."""
    del layer_ref, at_ref, o_zero
    i, j = pl.program_id(0), pl.program_id(1)
    n = n_ref[0]
    hb, d = v_ref.shape
    f32 = jnp.float32

    @pl.when(i < n)
    def _():
        # Columns h, hb + h and 2 hb + h: head h's k, q and decay.  (One
        # decay a head is that head's value down the whole column.)
        rows = [k_ref[...].astype(f32), q_ref[...].astype(f32),
                jnp.exp(jnp.broadcast_to(g_ref[...].astype(f32), (hb, d)))]
        if cols.shape[1] > 3 * hb:
            rows.append(jnp.zeros((cols.shape[1] - 3 * hb, d), f32))
        cols[...] = jnp.concatenate(rows, axis=0).T
        keep = fresh_ref[slots_ref[i]] == 0
        for h in range(hb):
            row = slice(h, h + 1)
            k, q, a = (cols[:, x * hb + h: x * hb + h + 1] for x in range(3))
            s = jnp.where(keep, s_ref[h].astype(f32), 0.0) * a
            u = b_ref[row, :] * (v_ref[row, :].astype(f32)
                                 - jnp.sum(s * k, axis=0, keepdims=True))
            s = s + k * u
            s_out[h] = s.astype(s_out.dtype)
            o_ref[row, :] = jnp.sum(s * q, axis=0, keepdims=True)

    # A step behind the list's end maps to the list's last block (see
    # ``lane`` below) and does nothing: the block's new state and output
    # stay in their buffers until the grid ends.  With an empty list every
    # step maps to one block, which goes back as it came.
    @pl.when((n == 0) & (i == 0) & (j == 0))
    def _():
        s_out[...] = s_ref[...]
        o_ref[...] = jnp.zeros_like(o_ref)


@functools.partial(jax.jit, static_argnames=("pad", "interpret"))
def linear_state_step(q, k, v, g, beta, s_all, layer, slots, count, fresh,
                      at, pad: int = 0, interpret: bool = False):
    """One recurrence step for the slots ``slots[:count]`` (distinct; what
    lies behind ``count`` is padding and is not looked at) of layer
    ``layer`` of ``s_all [Ll, B, H, d, d]`` float32, rewritten in place.
    Slot b's row is row ``at[b]`` of the flat ``q, k, v [T, H, d]``, of the
    log decay ``g [T, H, d]`` or ``[T, H, 1]`` (one a head, broadcast over
    the head's channels) and of the step size ``beta [T, H]``; ``fresh
    [B]``: the slot's old state reads as zeros.  Returns (``o [T + pad, H,
    d]`` float32: the listed slots' rows, zeros elsewhere; ``s_all``)."""
    _, b, h, d, _ = s_all.shape
    t = q.shape[0]
    hb = _pick_block_b(h, _HEAD_BLOCK)
    nj = h // hb
    f32 = jnp.float32

    def lane(i, j, n_ref, slots_ref):
        """The (slot, head block) of grid step (i, j); behind the list's end,
        the list's last block again, so that nothing more is fetched or
        written."""
        n = n_ref[0]
        slot = slots_ref[jnp.maximum(jnp.minimum(i, n - 1), 0)]
        return slot, jnp.where(i < n, j, nj - 1)

    def row_map(i, j, layer_ref, slots_ref, n_ref, fresh_ref, at_ref):
        del layer_ref, fresh_ref
        slot, jj = lane(i, j, n_ref, slots_ref)
        return at_ref[slot], jj, 0

    def state_map(i, j, layer_ref, slots_ref, n_ref, fresh_ref, at_ref):
        del fresh_ref, at_ref
        return (layer_ref[0], *lane(i, j, n_ref, slots_ref), 0, 0)

    row_spec = pl.BlockSpec((None, hb, d), row_map)
    state_spec = pl.BlockSpec((None, None, hb, d, d), state_map)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,  # layer, the list, its length, fresh, rows
        grid=(b, nj),
        in_specs=[row_spec, row_spec, row_spec,
                  pl.BlockSpec((None, hb, g.shape[-1]), row_map),
                  pl.BlockSpec((None, hb, 1), row_map),
                  pl.BlockSpec(memory_space=pl.ANY), state_spec],
        out_specs=(state_spec, row_spec),
        scratch_shapes=[pltpu.VMEM((d, -(-3 * hb // _TILE) * _TILE), f32)],
    )
    s_all, o = pl.pallas_call(
        _step_kernel,
        grid_spec=grid_spec,
        out_shape=(jax.ShapeDtypeStruct(s_all.shape, s_all.dtype),
                   jax.ShapeDtypeStruct((t + pad, h, d), f32)),
        # 0-4 the scalars, 5-9 the rows, 10 the output's zeros, 11 the states.
        input_output_aliases={10: 1, 11: 0},
        # In list order: a step behind the list's end rests on the step
        # before it having been the list's last.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="linear_state_step",
    )(jnp.asarray(layer, jnp.int32).reshape(1), slots.astype(jnp.int32),
      jnp.asarray(count, jnp.int32).reshape(1), fresh.astype(jnp.int32),
      at.astype(jnp.int32), q, k, v, g, beta.astype(f32)[..., None],
      jnp.zeros((t + pad, h, d), f32), s_all)
    return o, s_all
