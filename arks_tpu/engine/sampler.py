"""On-device token sampling for the fused decode loop.

Sampling lives inside the jitted multi-step loop so only sampled ids ever
cross the host boundary.

Per-slot params come in as arrays so one compiled program serves any mix of
greedy/temperature/top-k/top-p requests.  Top-k/top-p work on a static
``TOP_K_MAX``-wide window of the vocab, so no step sorts the full vocab.  The
window itself is found in two stages where the vocabulary is large
(``_top_window``): the maximum of every ``WINDOW_BLOCK``-column block, the
``k`` best blocks by that maximum, and ``lax.top_k`` over those blocks'
columns alone; the same values and ids as one ``lax.top_k`` over the row,
ties included, for a sixteenth of the sorting at 152,064 columns.  A small
vocabulary (a chip's share of one, a test model) takes the one call.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

TOP_K_MAX = 64
TOP_LOGPROBS_MAX = 8

_NP_KEY_OK: bool | None = None


# Everything the sampler does inside a step program reads as ``arks.sampler``
# in a profile (docs/monitoring.md; metadata only, the program is unchanged).
_in_profile = jax.named_scope("arks.sampler")

# The two-stage window (``_top_window``).  WINDOW_BLOCK: one lane tile of the
# chip, so a block's maximum is a reduction along the minor axis of whole
# tiles and a chosen block is gathered as one aligned row (32 / 64 columns a
# block sort fewer values and cost more in layout at 152,064 columns; 256
# and up sort more).  _MIN_VALUES:
# the one call costs ~0.3 ns a value of the [lanes, vocab] logits on a v5e,
# the two stages ~0.25 ms whatever the lanes up to 128 (their second top_k
# runs with the lanes along the chip's lanes), so under ~a million values
# the one call wins.  The timed table: PERF.md §6, PR 37.
WINDOW_BLOCK = 128
_MIN_VALUES = 1 << 20


def window_blocks(lanes: int, vocab: int, k: int = TOP_K_MAX) -> int:
    """How many blocks ``_top_window`` cuts the rows of a [lanes, vocab]
    array into for their ``k`` best entries; 0 = it makes the one
    ``lax.top_k`` call.  THE rule, from the static shape alone: two stages
    sort ``blocks + k x WINDOW_BLOCK`` values a lane where the one call sorts
    ``vocab``, so they need the row to be at least twice the gathered
    blocks, and enough values in all to pay for their fixed cost."""
    blocks = -(-vocab // WINDOW_BLOCK)
    return blocks if (blocks >= 2 * k
                      and lanes * vocab >= _MIN_VALUES) else 0


@_in_profile
def _top_window(x: jnp.ndarray, k: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """``jax.lax.top_k(x, k)`` over the last axis of ``x`` [B, V], bit for
    bit (values descending, equal values by ascending id), sorting fewer
    values where V is large.

    Every member of the true top-k lies in one of the k best BLOCKS (by
    maximum descending, then block id ascending): were its block outranked
    by k others, each of those would hold an entry that precedes it.  The
    chosen blocks are gathered in ascending block order, so a candidate's
    position is monotone in its vocab id and the second ``top_k`` breaks
    ties as the one call does.  Columns that pad the last block are -inf
    behind every real column, so none is ever chosen over one."""
    b, v = x.shape
    nb = window_blocks(b, v, k)
    if not nb:
        return jax.lax.top_k(x, k)
    pad = nb * WINDOW_BLOCK - v
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad)), constant_values=-jnp.inf)
    blocks = x.reshape(b, nb, WINDOW_BLOCK)
    _, bid = jax.lax.top_k(blocks.max(axis=-1), k)              # [B, k]
    bid = jnp.sort(bid, axis=-1)
    cand = jnp.take_along_axis(blocks, bid[:, :, None], axis=1)  # [B, k, W]
    vals, pos = jax.lax.top_k(cand.reshape(b, k * WINDOW_BLOCK), k)
    ids = (jnp.take_along_axis(bid, pos // WINDOW_BLOCK, axis=-1)
           * WINDOW_BLOCK + pos % WINDOW_BLOCK)
    return vals, ids


def np_prng_key(seed: int) -> np.ndarray:
    """Host-side ``jax.random.PRNGKey`` for the default threefry impl —
    byte-identical key data with ZERO device dispatches.  PRNGKey costs a
    traced jit + device round-trip (~0.7ms); at tens of admissions per
    scheduler cycle that is real engine-thread time (profiled: ~5% of the
    host-side loop).  Self-checks against jax once (covering x32/x64 and
    impl differences) and falls back to the real thing on mismatch.

    Used by BOTH the leader's admission batching and the follower's
    dispatch replay — the two must produce identical keys or gang
    sampling diverges.  Unlike ``jax.random.PRNGKey``, seeds outside the
    int64 range are MASKED rather than rejected: every key site (leader
    and follower) goes through this helper, so an absurd client-supplied
    seed yields a consistent key everywhere instead of an OverflowError
    on one side of a gang collective."""
    global _NP_KEY_OK
    if _NP_KEY_OK is None:
        probe = (1 << 35) + 7  # high bits exercise the truncation rule
        _NP_KEY_OK = bool(
            np.array_equal(np.array([0, probe & 0xFFFFFFFF], np.uint32),
                           np.asarray(jax.random.PRNGKey(probe)))
            and np.array_equal(np.array([0, (-1) & 0xFFFFFFFF], np.uint32),
                               np.asarray(jax.random.PRNGKey(-1))))
    if not _NP_KEY_OK:
        return np.asarray(jax.random.PRNGKey(seed))
    return np.array([0, seed & 0xFFFFFFFF], np.uint32)


@_in_profile
def top_logprobs(logits: jnp.ndarray, chosen: jnp.ndarray
                 ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Logprob data for OpenAI ``logprobs`` responses: (chosen token's
    logprob [B], top-``TOP_LOGPROBS_MAX`` logprobs [B, L], their vocab ids
    [B, L]).  Computed over the RAW model distribution (full-vocab
    log-softmax) — the conventional reading of the API field, independent
    of temperature/penalty shaping."""
    lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    vals, ids = _top_window(lp, min(TOP_LOGPROBS_MAX, lp.shape[-1]))
    chosen_lp = jnp.take_along_axis(lp, chosen[:, None], -1)[:, 0]
    return chosen_lp, vals, ids.astype(jnp.int32)


LOGIT_BIAS_MAX = 300  # full OpenAI logit_bias key budget; the bias pass
                      # is lax.cond-gated so unbiased batches pay nothing.
SUPPRESS_MAX = 8      # eos + stop_token_ids suppressed under min_tokens.
STOP_IDS_MAX = 32     # per-slot stop set (eos + stop_token_ids) mirrored
                      # onto the device so the pipelined decode path can
                      # compute liveness without a host round-trip.  A
                      # request whose stop set exceeds this rides the
                      # sequential path instead (never truncated).


def np_stop_col(stop_ids) -> np.ndarray | None:
    """Host-side [STOP_IDS_MAX] stop column for device-side liveness
    (pipelined decoding); ids < 0 pad.  Returns None on overflow — the
    caller must then keep the slot on the host-resolved sequential path
    (silently dropping a stop id would let the device keep a slot alive
    past its stop token and emit overshoot the host never discards)."""
    ids = list(dict.fromkeys(int(t) for t in stop_ids))
    if len(ids) > STOP_IDS_MAX:
        return None
    col = np.full((STOP_IDS_MAX,), -1, np.int32)
    col[: len(ids)] = ids
    return col


@_in_profile
def advance_liveness(toks: jnp.ndarray, alive: jnp.ndarray,
                     lengths: jnp.ndarray, stop_ids: jnp.ndarray,
                     dead_len: jnp.ndarray) -> jnp.ndarray:
    """End-of-dispatch device liveness for the pipelined decode path.

    ``toks`` [K, B] are the dispatch's sampled tokens, ``lengths`` [B] the
    POST-dispatch absolute lengths, ``stop_ids`` [B, S] the per-slot stop
    sets (< 0 pad), ``dead_len`` [B] the absolute length at which the host
    would retire the slot (min of the max_tokens cutoff and the cache-cap
    margin).  A slot stays alive iff none of its K tokens is a stop token
    AND its new length sits below dead_len — EXACTLY the host's retire
    condition in _resolve_decode, which is what lets in-flight dispatches
    self-mask dead slots before the host has seen the death."""
    valid = stop_ids >= 0                                   # [B, S]
    hit = jnp.any((toks[:, :, None] == stop_ids[None, :, :])
                  & valid[None, :, :], axis=(0, 2))         # [B]
    return alive & ~hit & (lengths < dead_len)


class SamplingState(NamedTuple):
    """Per-slot sampling params, stacked into arrays (all [B])."""

    temperature: jnp.ndarray  # f32; <=0 means greedy
    top_p: jnp.ndarray        # f32 in (0, 1]
    top_k: jnp.ndarray        # i32; 0 = disabled (use TOP_K_MAX window)
    key: jnp.ndarray          # uint32 [B, 2] per-slot PRNG keys
    # OpenAI presence/frequency penalties over OUTPUT tokens (vLLM
    # semantics): logits -= presence*1[count>0] + frequency*count.
    presence: jnp.ndarray     # f32 [B]
    frequency: jnp.ndarray    # f32 [B]
    counts: jnp.ndarray       # i32 [B, V] per-slot generated-token counts
    # OpenAI logit_bias: up to LOGIT_BIAS_MAX (id, bias) pairs per slot;
    # id < 0 = empty entry.  Applied before greedy/filtering, like the
    # penalties (lax.cond-gated so unbiased batches pay nothing).
    bias_ids: jnp.ndarray     # i32 [B, NB]
    bias_vals: jnp.ndarray    # f32 [B, NB]
    # min_tokens: ids in suppress_ids (< 0 = empty) are masked to -inf
    # while the slot's sequence length is below min_until (0 = off).
    suppress_ids: jnp.ndarray  # i32 [B, NS]
    min_until: jnp.ndarray     # i32 [B]
    # Guided decoding (guides.py): guide = packed guide id (-1 = none),
    # guide_row = ABSOLUTE row in the trans table (the slot's DFA state).
    # shaped() masks tokens whose transition is dead; sample() advances
    # the row.  Both need the (class_ids, trans) tables passed alongside —
    # they live on the ENGINE (fixed budget shapes), not in this state.
    guide: jnp.ndarray        # i32 [B]
    guide_row: jnp.ndarray    # i32 [B]


def init_sampling_state(batch: int, seed: int = 0,
                        vocab_size: int = 1) -> SamplingState:
    keys = jax.random.split(jax.random.PRNGKey(seed), batch)
    return SamplingState(
        temperature=jnp.zeros((batch,), jnp.float32),
        top_p=jnp.ones((batch,), jnp.float32),
        top_k=jnp.zeros((batch,), jnp.int32),
        key=jnp.asarray(keys),
        presence=jnp.zeros((batch,), jnp.float32),
        frequency=jnp.zeros((batch,), jnp.float32),
        counts=jnp.zeros((batch, vocab_size), jnp.int32),
        bias_ids=jnp.full((batch, LOGIT_BIAS_MAX), -1, jnp.int32),
        bias_vals=jnp.zeros((batch, LOGIT_BIAS_MAX), jnp.float32),
        suppress_ids=jnp.full((batch, SUPPRESS_MAX), -1, jnp.int32),
        min_until=jnp.zeros((batch,), jnp.int32),
        guide=jnp.full((batch,), -1, jnp.int32),
        guide_row=jnp.zeros((batch,), jnp.int32),
    )


def np_bias_cols(params, vocab_size: int):
    """Host-side [NB] bias columns (ids, vals) for one request's
    ``logit_bias``; ids < 0 pad empty entries."""
    ids = np.full((LOGIT_BIAS_MAX,), -1, np.int32)
    vals = np.zeros((LOGIT_BIAS_MAX,), np.float32)
    for i, (tid, b) in enumerate(params.logit_bias[:LOGIT_BIAS_MAX]):
        if 0 <= tid < vocab_size:
            ids[i] = tid
            vals[i] = b
    return ids, vals


def np_suppress_col(stop_ids) -> np.ndarray:
    """Host-side [NS] suppress column for min_tokens; ids < 0 pad.

    Overflow raises instead of truncating: a silently-dropped id would let
    that token end the stream before min_tokens (the HTTP layer 400s the
    same condition; direct engine callers must fail just as loudly)."""
    ids = list(dict.fromkeys(stop_ids))
    if len(ids) > SUPPRESS_MAX:
        raise ValueError(
            f"min_tokens suppress set has {len(ids)} ids; at most "
            f"{SUPPRESS_MAX} eos/stop token ids are supported")
    col = np.full((SUPPRESS_MAX,), -1, np.int32)
    for i, tid in enumerate(ids):
        col[i] = tid
    return col


def transient_state(temperature, top_p, top_k, key,
                    vocab_size: int, bias_ids=None, bias_vals=None,
                    suppress_ids=None, min_first=None, guide=None,
                    guide_row=None) -> SamplingState:
    """One-row state for first-token sampling (prefill paths): penalties
    are identity there — the output is empty, so counts are all zero.
    ``min_first`` (i32 scalar, 1 when min_tokens >= 1): the first token
    must already respect suppression (sample's lengths=None reading of
    min_until)."""
    return SamplingState(
        temperature=temperature[None], top_p=top_p[None], top_k=top_k[None],
        key=key[None],
        presence=jnp.zeros((1,), jnp.float32),
        frequency=jnp.zeros((1,), jnp.float32),
        counts=jnp.zeros((1, vocab_size), jnp.int32),
        bias_ids=(jnp.full((1, LOGIT_BIAS_MAX), -1, jnp.int32)
                  if bias_ids is None else bias_ids[None]),
        bias_vals=(jnp.zeros((1, LOGIT_BIAS_MAX), jnp.float32)
                   if bias_vals is None else bias_vals[None]),
        suppress_ids=(jnp.full((1, SUPPRESS_MAX), -1, jnp.int32)
                      if suppress_ids is None else suppress_ids[None]),
        min_until=(jnp.zeros((1,), jnp.int32)
                   if min_first is None else min_first[None]),
        guide=(jnp.full((1,), -1, jnp.int32)
               if guide is None else guide[None]),
        guide_row=(jnp.zeros((1,), jnp.int32)
                   if guide_row is None else guide_row[None]),
    )


def transient_state_batch(temperature, top_p, top_k, keys,
                          vocab_size: int, bias_ids=None, bias_vals=None,
                          suppress_ids=None, min_first=None, guide=None,
                          guide_row=None) -> SamplingState:
    """M-row transient state for BATCHED first-token sampling (fused
    multi-prompt admissions): all params already [M]-shaped."""
    m = temperature.shape[0]
    return SamplingState(
        temperature=temperature, top_p=top_p, top_k=top_k, key=keys,
        presence=jnp.zeros((m,), jnp.float32),
        frequency=jnp.zeros((m,), jnp.float32),
        counts=jnp.zeros((m, vocab_size), jnp.int32),
        bias_ids=(jnp.full((m, LOGIT_BIAS_MAX), -1, jnp.int32)
                  if bias_ids is None else bias_ids),
        bias_vals=(jnp.zeros((m, LOGIT_BIAS_MAX), jnp.float32)
                   if bias_vals is None else bias_vals),
        suppress_ids=(jnp.full((m, SUPPRESS_MAX), -1, jnp.int32)
                      if suppress_ids is None else suppress_ids),
        min_until=(jnp.zeros((m,), jnp.int32)
                   if min_first is None else min_first),
        guide=(jnp.full((m,), -1, jnp.int32) if guide is None else guide),
        guide_row=(jnp.zeros((m,), jnp.int32)
                   if guide_row is None else guide_row),
    )


def set_slots(state: SamplingState, slots: jnp.ndarray, temperature,
              top_p, top_k, keys, presence, frequency,
              bias_ids=None, bias_vals=None, suppress_ids=None,
              min_until=None, guide=None, guide_row=None) -> SamplingState:
    """Batched set_slot: write M slots' sampling params in one scatter
    (one compiled program per batch size M)."""
    m = temperature.shape[0]
    return SamplingState(
        temperature=state.temperature.at[slots].set(temperature),
        top_p=state.top_p.at[slots].set(top_p),
        top_k=state.top_k.at[slots].set(top_k),
        key=state.key.at[slots].set(keys),
        presence=state.presence.at[slots].set(presence),
        frequency=state.frequency.at[slots].set(frequency),
        counts=state.counts.at[slots].set(0),
        bias_ids=state.bias_ids.at[slots].set(
            jnp.full((m, state.bias_ids.shape[1]), -1, jnp.int32)
            if bias_ids is None else bias_ids),
        bias_vals=state.bias_vals.at[slots].set(
            jnp.zeros((m, state.bias_vals.shape[1]), jnp.float32)
            if bias_vals is None else bias_vals),
        suppress_ids=state.suppress_ids.at[slots].set(
            jnp.full((m, state.suppress_ids.shape[1]), -1, jnp.int32)
            if suppress_ids is None else suppress_ids),
        min_until=state.min_until.at[slots].set(
            jnp.zeros((m,), jnp.int32) if min_until is None else min_until),
        guide=state.guide.at[slots].set(
            jnp.full((m,), -1, jnp.int32) if guide is None else guide),
        guide_row=state.guide_row.at[slots].set(
            jnp.zeros((m,), jnp.int32) if guide_row is None else guide_row),
    )


def promote_slots(state: SamplingState, slots, scalars_f, scalars_i, keys,
                  fold, bias_ids, bias_vals, suppress_ids) -> SamplingState:
    """The step loop's slot registration: write M slots' sampling rows in
    one program whose operands are HOST arrays (the jit's own argument
    path transfers them; nothing runs eagerly).  ``scalars_f`` [M, 4] =
    (temperature, top_p, presence, frequency), ``scalars_i`` [M, 4] =
    (top_k, min_until, guide, guide_row).  ``fold`` [M] marks the rows
    whose key is the request's BASE key and is advanced here, bit for bit
    as ``jax.random.fold_in(key, 1)`` (a prompt that just sampled its
    first token); the others carry a key that is written as it is (a
    swapped-out slot's snapshot).  Rows whose slot is out of range (the
    padding up to the compiled size M) write nothing."""
    folded = jax.vmap(lambda k: jax.random.fold_in(k, 1))(keys)
    return set_slots(
        state, slots, scalars_f[:, 0], scalars_f[:, 1], scalars_i[:, 0],
        jnp.where(fold[:, None], folded, keys), scalars_f[:, 2],
        scalars_f[:, 3], bias_ids, bias_vals, suppress_ids,
        min_until=scalars_i[:, 1], guide=scalars_i[:, 2],
        guide_row=scalars_i[:, 3])


def clear_slot_penalties(state: SamplingState,
                         slot: jnp.ndarray) -> SamplingState:
    """Zero a freed slot's penalties, bias, and suppression so the
    shaping fast-path gates (jnp.any over ALL rows) re-arm once no live
    slot needs them."""
    return state._replace(
        presence=state.presence.at[slot].set(0.0),
        frequency=state.frequency.at[slot].set(0.0),
        bias_ids=state.bias_ids.at[slot].set(-1),
        bias_vals=state.bias_vals.at[slot].set(0.0),
        suppress_ids=state.suppress_ids.at[slot].set(-1),
        min_until=state.min_until.at[slot].set(0),
        guide=state.guide.at[slot].set(-1),
        guide_row=state.guide_row.at[slot].set(0))


@_in_profile
def count_tokens(state: SamplingState, tokens: jnp.ndarray,
                 active: jnp.ndarray | None = None) -> SamplingState:
    """Record one emitted token per slot (called on the tokens FED to a
    decode step — every generated token is fed exactly once, so feed-time
    counting covers the one-shot, chunked, and disagg admission paths
    uniformly; free slots' garbage rows are reset at set_slot).

    ``active`` (bool [B]) masks the update to live slots: with deferred
    admissions a slot's set_slots (in the admit program) may precede
    intervening decode dispatches, and counting its garbage feed rows
    there would poison the new request's penalties."""
    b = tokens.shape[0]
    inc = 1 if active is None else active.astype(jnp.int32)
    return state._replace(
        counts=state.counts.at[jnp.arange(b), tokens].add(inc))


def penalized(logits: jnp.ndarray, state: SamplingState) -> jnp.ndarray:
    """Apply presence/frequency penalties (identity when both are 0).

    Runtime-gated with ``lax.cond``: the un-penalized common case skips the
    two [B, V] reads entirely instead of multiplying by zero."""
    def apply(logits):
        cnt = state.counts.astype(jnp.float32)
        return (logits - state.presence[:, None] * (cnt > 0)
                - state.frequency[:, None] * cnt)

    active = jnp.any((state.presence != 0.0) | (state.frequency != 0.0))
    return jax.lax.cond(active, apply, lambda x: x, logits)


def guide_mask(logits: jnp.ndarray, state: SamplingState,
               guide_tables) -> jnp.ndarray:
    """Mask tokens with dead guide transitions to -inf.  guide_tables =
    (class_ids [G, V] i32, trans [R, C] i32).  lax.cond-gated: unguided
    batches skip the [B, V] class gather entirely."""
    class_ids, trans = guide_tables

    def apply(lg):
        b = lg.shape[0]
        cls = class_ids[jnp.maximum(state.guide, 0)]          # [B, V]
        row = trans[jnp.maximum(state.guide_row, 0)]          # [B, C]
        nxt = jnp.take_along_axis(row, cls, axis=1)           # [B, V]
        bad = (nxt < 0) & (state.guide >= 0)[:, None]
        return jnp.where(bad, jnp.float32(-1e30), lg)

    return jax.lax.cond(jnp.any(state.guide >= 0), apply,
                        lambda x: x, logits)


def guide_advance(state: SamplingState, ids: jnp.ndarray, guide_tables,
                  active: jnp.ndarray | None = None) -> SamplingState:
    """Advance each guided slot's DFA row by its sampled token.  A dead
    transition (only reachable when every token was masked — degenerate
    grammar) holds the row instead of corrupting it."""
    class_ids, trans = guide_tables
    b = ids.shape[0]
    cls = class_ids[jnp.maximum(state.guide, 0), ids]         # [B]
    nxt = trans[jnp.maximum(state.guide_row, 0), cls]         # [B]
    upd = state.guide >= 0
    if active is not None:
        upd = upd & active
    upd = upd & (nxt >= 0)
    return state._replace(
        guide_row=jnp.where(upd, nxt, state.guide_row))


def shaped(logits: jnp.ndarray, state: SamplingState,
           lengths: jnp.ndarray | None = None,
           guide_tables=None) -> jnp.ndarray:
    """Penalties + OpenAI logit_bias + min_tokens suppression + guided-
    decoding masks, each lax.cond-gated so the plain batch pays none of it.

    min_tokens: suppress_ids are masked to -inf while the slot's current
    sequence length sits below min_until.  Without ``lengths`` (first-token
    prefill paths), min_until > 0 itself means "still under the minimum"
    (the engine sets it to 1 only when min_tokens >= 1 there)."""
    logits = penalized(logits, state)
    b = logits.shape[0]

    def apply_bias(lg):
        valid = state.bias_ids >= 0
        ids = jnp.maximum(state.bias_ids, 0)
        return lg.at[jnp.arange(b)[:, None], ids].add(
            jnp.where(valid, state.bias_vals, 0.0))

    logits = jax.lax.cond(jnp.any(state.bias_ids >= 0), apply_bias,
                          lambda x: x, logits)

    def apply_min(lg):
        if lengths is None:
            hold = state.min_until > 0
        else:
            hold = lengths < state.min_until
        valid = (state.suppress_ids >= 0) & hold[:, None]
        ids = jnp.maximum(state.suppress_ids, 0)
        return lg.at[jnp.arange(b)[:, None], ids].add(
            jnp.where(valid, jnp.float32(-1e30), 0.0))

    logits = jax.lax.cond(jnp.any(state.min_until > 0), apply_min,
                          lambda x: x, logits)
    # Guide mask LAST: a +100 logit_bias must not resurrect a token the
    # grammar forbids.
    if guide_tables is not None:
        logits = guide_mask(logits, state, guide_tables)
    return logits


def _filtered_scaled(logits: jnp.ndarray, state: SamplingState
                     ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The effective per-slot sampling distribution in window form:
    (scaled logits [B, W] with filtered entries at -inf, vocab ids
    [B, W]) after temperature + top-k + top-p over the TOP_K_MAX window."""
    b, v = logits.shape
    window = min(TOP_K_MAX, v)
    top_logits, top_idx = _top_window(logits, window)  # [B, W], descending
    temp = jnp.maximum(state.temperature, 1e-6)[:, None]
    scaled = top_logits / temp

    # top-k mask within the window (0 = keep whole window).
    k = jnp.where(state.top_k <= 0, window, jnp.minimum(state.top_k, window))
    rank = jnp.arange(window)[None, :]
    scaled = jnp.where(rank < k[:, None], scaled, -jnp.inf)

    # top-p (nucleus) over the kept candidates: keep the smallest prefix with
    # cumulative prob >= top_p; candidates are already sorted descending.
    probs = jax.nn.softmax(scaled, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep = (cum - probs) < state.top_p[:, None]  # first candidate always kept
    return jnp.where(keep, scaled, -jnp.inf), top_idx


def filtered_probs(logits: jnp.ndarray, state: SamplingState
                   ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """(probs [B, W], vocab ids [B, W], scaled logits [B, W]) — the exact
    distribution ``sample`` draws from, exposed for speculative decoding's
    acceptance ratios and residual distributions."""
    scaled, idx = _filtered_scaled(logits, state)
    return jax.nn.softmax(scaled, axis=-1), idx, scaled


@_in_profile
def sample(logits: jnp.ndarray, state: SamplingState,
           active: jnp.ndarray | None = None,
           lengths: jnp.ndarray | None = None,
           guide_tables=None,
           ) -> tuple[jnp.ndarray, SamplingState]:
    """Sample one token per slot. logits [B, V] float32 -> ids [B] int32.

    Greedy where temperature <= 0; otherwise temperature + top-k + top-p over
    the TOP_K_MAX highest-logit candidates.  Penalties, logit_bias, and
    min_tokens suppression apply BEFORE greedy/filtering (identity at the
    defaults — see ``shaped``).

    ``active`` (bool [B]) freezes INACTIVE slots' PRNG keys: with deferred
    admissions, decode dispatches can land between a slot's set_slots (in
    the admit program) and its registration — advancing its fresh key
    stream there would make seeded sampling depend on scheduler timing.
    """
    logits = shaped(logits, state, lengths, guide_tables)
    greedy_ids = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled, top_idx = _filtered_scaled(logits, state)

    new_keys = jax.vmap(lambda k: jax.random.split(k, 2))(state.key)
    step_keys, carry_keys = new_keys[:, 0], new_keys[:, 1]
    choice = jax.vmap(lambda key, s: jax.random.categorical(key, s))(step_keys, scaled)
    sampled_ids = jnp.take_along_axis(top_idx, choice[:, None], axis=-1)[:, 0].astype(jnp.int32)

    ids = jnp.where(state.temperature <= 0.0, greedy_ids, sampled_ids)
    if active is not None:
        carry_keys = jnp.where(active[:, None], carry_keys, state.key)
    state = state._replace(key=carry_keys)
    if guide_tables is not None:
        state = guide_advance(state, ids, guide_tables, active)
    return ids, state


@_in_profile
def draft_sample(logits: jnp.ndarray, state: SamplingState, keys: jnp.ndarray
                 ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray,
                            jnp.ndarray, jnp.ndarray]:
    """One draft proposal per slot for speculative decoding.

    Returns (token [B], q(token) [B], q probs [B, W], window ids [B, W],
    advanced keys [B, 2]).  Greedy slots propose argmax with q=1 (the
    temperature->0 limit of the acceptance rule reduces to exact-match)."""
    probs, idx, scaled = filtered_probs(logits, state)
    new_keys = jax.vmap(lambda k: jax.random.split(k, 2))(keys)
    step_keys, carry_keys = new_keys[:, 0], new_keys[:, 1]
    choice = jax.vmap(lambda key, s: jax.random.categorical(key, s))(step_keys, scaled)
    samp_tok = jnp.take_along_axis(idx, choice[:, None], -1)[:, 0].astype(jnp.int32)
    samp_q = jnp.take_along_axis(probs, choice[:, None], -1)[:, 0]
    greedy = state.temperature <= 0.0
    tok = jnp.where(greedy, jnp.argmax(logits, -1).astype(jnp.int32), samp_tok)
    q = jnp.where(greedy, 1.0, samp_q)
    return tok, q, probs, idx, carry_keys


@_in_profile
def speculative_accept(
    drafts: jnp.ndarray,        # [B, K-1] draft proposals
    q_sel: jnp.ndarray,         # [B, K-1] q(draft) under the draft dist
    q_probs: jnp.ndarray,       # [B, K-1, W] draft window probs
    q_idx: jnp.ndarray,         # [B, K-1, W] draft window vocab ids
    target_logits: jnp.ndarray,  # [B, K, V] verifier logits per position
    state: SamplingState,
    keys: jnp.ndarray,          # [B, 2]
    enable: jnp.ndarray | None = None,  # [B] bool; False = no speculation
    lengths: jnp.ndarray | None = None,  # [B] — min_tokens gating for the
                                         # disabled slots' plain sample
    guide_tables=None,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Rejection-sampled acceptance (Leviathan et al.): accept draft i with
    prob min(1, p_i(d_i)/q_i(d_i)); at the first rejection sample from the
    residual norm(max(p - q, 0)); after a fully-accepted block sample the
    bonus token from p_{K-1}.  The emitted tokens are distributed EXACTLY
    as the engine's own effective sampling distribution (the windowed
    temperature/top-k/top-p dist ``sample`` uses) — the draft only changes
    how many land per dispatch.  Greedy slots reduce to exact argmax
    matching + the argmax bonus token.

    ``enable`` gates speculation PER SLOT: a disabled slot (penalized /
    logprob-bearing / stale draft mirror) advances exactly ONE token,
    sampled from the target's position-0 logits through the NORMAL path —
    penalties included — so one such request no longer drops the whole
    batch off the speculative path.

    Guided slots SPECULATE (``guide_tables``): the DFA is threaded through
    the draft prefix — position i's candidate row is the current row
    advanced by drafts[0..i-1] — and each position's TARGET logits are
    masked with that row's dead transitions before the acceptance
    distribution is formed.  A draft token the grammar forbids has p = 0
    at its own position, so it is always rejected and the residual (masked
    target) distribution resamples a legal one — exactness is untouched
    because only the target side defines the emitted distribution.  The
    returned rows are rolled back to the ACCEPTED prefix: row after the
    accepted drafts, advanced once more by the bonus/residual token.
    Draft proposals themselves stay unmasked (the draft model has no DFA),
    costing only acceptance rate, never correctness.

    Returns (tokens [B, K] — first counts[b] are valid, counts [B] in
    1..K, advanced keys, advanced guide rows [B])."""
    b, km1 = drafts.shape
    kk = km1 + 1
    greedy = state.temperature <= 0.0

    # Guided lanes: candidate DFA rows per position + per-position target
    # masks.  The [B, V] class gathers are cond-gated like guide_mask so
    # unguided batches skip them.
    rows_arr = None
    if guide_tables is not None:
        class_ids, trans = guide_tables
        guided = state.guide >= 0

        def _row_next(row, toks):
            cls = class_ids[jnp.maximum(state.guide, 0), toks]    # [B]
            nxt = trans[jnp.maximum(row, 0), cls]                 # [B]
            # Dead transition holds the row (degenerate grammar), exactly
            # like guide_advance.
            return jnp.where(guided & (nxt >= 0), nxt, row)

        rows = [state.guide_row]
        for i in range(km1):
            rows.append(_row_next(rows[-1], drafts[:, i]))
        rows_arr = jnp.stack(rows, axis=1)                        # [B, K]

        def _with_guides(tl):
            cls_all = class_ids[jnp.maximum(state.guide, 0)]      # [B, V]

            def mask_pos(lg, row):
                r = trans[jnp.maximum(row, 0)]                    # [B, C]
                nxt = jnp.take_along_axis(r, cls_all, axis=1)     # [B, V]
                bad = (nxt < 0) & guided[:, None]
                return jnp.where(bad, jnp.float32(-1e30), lg)

            return jnp.stack([mask_pos(tl[:, i], rows_arr[:, i])
                              for i in range(kk)], axis=1)

        target_eff = jax.lax.cond(jnp.any(guided), _with_guides,
                                  lambda tl: tl, target_logits)
    else:
        target_eff = target_logits

    # Target filtered dist per position: [B, K, W].
    def per_pos(logits_i):
        return filtered_probs(logits_i, state)

    p_probs, p_idx, _ = jax.vmap(per_pos, in_axes=1, out_axes=1)(target_eff)
    g_t = jnp.argmax(target_eff, axis=-1).astype(jnp.int32)  # [B, K]

    new_keys = jax.vmap(lambda k: jax.random.split(k, 3))(keys)
    u_keys, r_keys, carry_keys = new_keys[:, 0], new_keys[:, 1], new_keys[:, 2]
    u = jax.vmap(lambda key: jax.random.uniform(key, (km1,)))(u_keys)

    # p_i(d_i): the draft token's prob under the target window (0 when the
    # token fell outside the target's filtered support).
    p_at_d = jnp.sum(p_probs[:, :km1]
                     * (p_idx[:, :km1] == drafts[..., None]), axis=-1)
    accept_samp = u < p_at_d / jnp.maximum(q_sel, 1e-20)
    accept_greedy = g_t[:, :km1] == drafts
    accept = jnp.where(greedy[:, None], accept_greedy, accept_samp)
    j = jnp.cumprod(accept.astype(jnp.int32), axis=1).sum(axis=1)  # [B] 0..K-1
    counts = 1 + j

    # Residual/bonus token at position j.
    pj = jnp.take_along_axis(p_probs, j[:, None, None], axis=1)[:, 0]   # [B, W]
    pidxj = jnp.take_along_axis(p_idx, j[:, None, None], axis=1)[:, 0]
    jq = jnp.minimum(j, km1 - 1)
    qj = jnp.take_along_axis(q_probs, jq[:, None, None], axis=1)[:, 0]
    qidxj = jnp.take_along_axis(q_idx, jq[:, None, None], axis=1)[:, 0]
    # Map q onto the target window's index set.
    q_on_p = jnp.sum(qj[:, None, :] * (qidxj[:, None, :] == pidxj[:, :, None]),
                     axis=-1)                                           # [B, W]
    rejected = (j < km1)[:, None]
    res = jnp.maximum(pj - jnp.where(rejected, q_on_p, 0.0), 0.0)
    norm = res.sum(-1, keepdims=True)
    res = jnp.where(norm > 1e-20, res / jnp.maximum(norm, 1e-20), pj)
    rchoice = jax.vmap(lambda key, pr: jax.random.categorical(
        key, jnp.log(pr + 1e-30)))(r_keys, res)
    y_samp = jnp.take_along_axis(pidxj, rchoice[:, None], -1)[:, 0].astype(jnp.int32)
    y = jnp.where(greedy, jnp.take_along_axis(g_t, j[:, None], 1)[:, 0], y_samp)

    out = jnp.concatenate([drafts, jnp.zeros((b, 1), jnp.int32)], axis=1)
    out = out.at[jnp.arange(b), j].set(y)

    guide_row = state.guide_row
    if rows_arr is not None:
        # Roll back to the accepted prefix's row, then advance by the
        # bonus/residual token — the state the NEXT dispatch's position-0
        # mask (and the engine's persistent guide_row) must carry.
        row_j = jnp.take_along_axis(rows_arr, j[:, None], axis=1)[:, 0]
        guide_row = _row_next(row_j, y)
    if enable is not None:
        # Disabled slots: one token via the regular sampler (which applies
        # penalties / logit_bias / min_tokens / guide shaping) from the
        # position-0 target logits.
        plain, pstate = sample(target_logits[:, 0],
                               state._replace(key=r_keys),
                               lengths=lengths, guide_tables=guide_tables)
        out = jnp.where(enable[:, None], out, out.at[:, 0].set(plain))
        counts = jnp.where(enable, counts, 1)
        guide_row = jnp.where(enable, guide_row, pstate.guide_row)
    return out, counts, carry_keys, guide_row
