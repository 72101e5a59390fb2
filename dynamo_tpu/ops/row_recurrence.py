"""What the stateful families' mixers share over a flat axis of token slots
(models/hybrid.py's gated delta rule, models/nemotron_h.py's state-space
layers; docs/hybrid_models.md): R rows share M slots (row r: slots
row_starts[r] ... + row_lens[r]), each row goes on from its own lane's
convolution tail and state and leaves its own behind.

`flat_conv` is the causal depthwise convolution of a few taps; `rows_
recurrence` takes a recurrence over the rows: a row of one token that goes
on from its lane's state over the store in place, by the family's decode
step; the other rows gathered, through the step form and the chunked form.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .paged_attention import rows_at

f32 = jnp.float32
#: gathered rows a group of `rows_recurrence`: the file's one tuned number.
#: The chunked form's temporaries grow with the group and a pack mostly
#: holds ONE prompt, so a group of the whole prefill batch (8) worked on
#: eight rows' worth of HBM for one row's tokens. One layer's recurrence of
#: a 256-slot pack (31 one-token rows + prompts) alone on a v5e, ms at
#: groups of 1, 2, 4, 8: the hybrid cell's sizes 0.52, 0.58, 0.89, 1.45
#: (one prompt of 160 tokens), 0.60, 0.51, 0.72, 1.11 (two of 110), 1.00,
#: 0.79, 0.79, 0.78 (eight of 28); the state-space cell's 0.84, 1.06, 1.33,
#: 2.14; 0.88, 0.90, 1.09, 1.51; 1.45, 1.47, 1.46, 1.55 (PERF.md, PR 52)
GROUP_ROWS = 2


def flat_conv(mixed, w, tails, row_ids, row_starts, row_lens):
    """mixed [M, C] convolved along each row with w [C, taps] (float32; tap
    taps - 1 meets the token itself): the taps - 1 inputs before a row's
    first token are its lane's tail, tails [R, taps - 1, C] (zero for a
    sequence's first chunk: the caller's). -> (y [M, C] float32, the tail
    each row leaves [R, taps - 1, C]: the last taps - 1 inputs of tail ++
    row)."""
    M, taps = mixed.shape[0], w.shape[1]
    slot = jnp.arange(M, dtype=jnp.int32)
    t = slot - row_starts[row_ids]  # offset in the slot's row
    y = mixed.astype(f32) * w[:, taps - 1]
    for back in range(1, taps):
        before = jnp.where(
            (t >= back)[:, None],
            rows_at(mixed, jnp.maximum(slot - back, 0)),
            tails[row_ids, jnp.clip(taps - 1 + t - back, 0, taps - 2)],
        )
        y = y + before.astype(f32) * w[:, taps - 1 - back]
    end = row_lens[:, None] - (taps - 1) + jnp.arange(taps - 1)  # [R, taps - 1]
    new_tails = jnp.where(
        (end >= 0)[..., None],
        rows_at(mixed, row_starts[:, None] + jnp.maximum(end, 0)),
        jnp.take_along_axis(
            tails, jnp.clip(end + taps - 1, 0, taps - 2)[..., None], axis=1),
    )
    return y, new_tails


def step_in_store(step, state, layer, *inputs, live):
    """`rows_recurrence`'s `in_place` in plain XLA, and a decode step's
    recurrence: one token a lane through `step(S, *inputs) -> (S, o)` over
    the first n slots of `layer` in the store `state` [state layers, lanes +
    1, ...] (inputs [n, ...]; row b IS lane b), written back where they
    were read; a lane that is not `live` [n] keeps its state bit for bit.
    -> (state, o [n, ...])."""
    n = live.shape[0]
    S = jax.lax.dynamic_index_in_dim(state, layer, 0, False)[:n]
    S_new, o = step(S.astype(f32), *inputs)
    S_new = jnp.where(
        live.reshape(-1, *[1] * (S.ndim - 1)), S_new.astype(S.dtype), S)
    return jax.lax.dynamic_update_slice(
        state, S_new[None], (layer, *[0] * (state.ndim - 1))), o


def rows_recurrence(state, layer, lanes, ctx_lens, inputs, out_shape, in_place,
                    step, chunk, chunk_len: int, row_starts, row_lens,
                    long_rows: int):
    """A recurrence over the rows, each from its lane's state in the store
    `state` [state layers, lanes + 1, ...] at `layer` (row r: lane
    lanes[r]; from zero where ctx_lens[r] is 0, a sequence's first chunk).
    `inputs`: what a token feeds it, arrays [M, ...]; a token whose inputs
    are all 0 (slot M: `rows_at`'s zero row) must leave a state as it was.
    `in_place(state, layer, *inputs a lane [B, ...], live [B]) -> (state, o
    [B, *out_shape])`: one token a lane over the store, the family's decode
    step; `step(S, *inputs at one slot a row) -> (S, o [n, *out_shape])` and
    `chunk(S, *inputs [n, chunk_len, ...]) -> (S, o [n, chunk_len,
    *out_shape])` over gathered states S [n, ...] float32. -> (the store,
    each row's lane behind its last token; o [M, *out_shape]).

    Which road a row takes is what the operands say. A row of ONE token
    that goes on from its lane's state (a decode lane, a prompt's last
    chunk of one token) is stepped over the lane, in the store, by
    `in_place`: its inputs are laid out by lane, no state leaves the
    store. Every other row of a token or more is GATHERED: its state is
    read out of the store (or starts from zero), its first token goes
    through `step`, what is left of it through `chunk`, `chunk_len` tokens
    an iteration, as many iterations as the longest row needs, and the
    state is scattered back. `long_rows`: how many gathered rows the caller
    expects at most (a mixed step's prefill batch). The gather, both forms
    and the scatter run over a GROUP of the longest such rows, `GROUP_ROWS`
    of them (`long_rows` where that is fewer), and again over the next
    group while rows are left that need it: a pack of one prompt pays for
    one small group, a full prefill batch for a few, a pack that breaks
    the caller's promise for as many as it takes, and a group's
    temporaries are those of `GROUP_ROWS` rows whatever R is. `long_rows >=
    R` (a batched prefill: every row may be long) gathers every row at
    once and asks `in_place` for nothing.

    The two roads may run in either order inside a layer because no lane
    holds a row of each kind in one pack: a lane has one row a pack (the
    engine refuses speculation for a family with a state)."""
    M, R = inputs[0].shape[0], row_lens.shape[0]
    fresh = ctx_lens == 0
    o = jnp.zeros((M, *out_shape), f32)

    def at_slots(at):
        return tuple(rows_at(a, at) for a in inputs)

    def gathered(rows, real, state, o):
        """`rows` [n] (indices of rows) from their lanes' states to behind
        their last tokens; a row that is not `real` [n] feeds zero rows and
        writes nowhere."""
        row = jnp.minimum(rows, R - 1)
        # one gather on the stored arrays (a layer's slots sliced out first
        # are copied whole before the rows are read out of the copy)
        S = jnp.where(fresh[row].reshape(-1, *[1] * (state.ndim - 2)), 0,
                      state[layer, lanes[row]]).astype(f32)
        first = jnp.where(real, row_starts[row], M)
        S, o_first = step(S, *at_slots(first))
        o = o.at[first].set(o_first, mode="drop")
        starts = row_starts[row] + 1
        left = jnp.where(real, row_lens[row] - 1, 0)

        def one(j, carry):
            S, o = carry
            offset = j * chunk_len + jnp.arange(chunk_len)
            at = jnp.where(offset[None, :] < left[:, None],
                           starts[:, None] + offset, M)  # [n, chunk_len]
            S, oc = chunk(S, *at_slots(at))
            return S, o.at[at].set(oc, mode="drop")

        S, o = jax.lax.fori_loop(
            0, -(-jnp.max(left) // chunk_len), one, (S, o))
        lane = jnp.where(real, lanes[row], state.shape[1])
        return state.at[layer, lane].set(S.astype(state.dtype), mode="drop"), o

    if long_rows >= R:
        return gathered(jnp.arange(R, dtype=jnp.int32), row_lens > 0, state, o)

    # one token from a lane's state: the decode step's function over the
    # store, the row's inputs at its lane (slot B: dropped)
    B = state.shape[1] - 1
    single = (row_lens == 1) & ~fresh
    lane = jnp.where(single, lanes, B)
    state, o_lane = in_place(
        state, layer,
        *(jnp.zeros((B, *a.shape[1:]), a.dtype).at[lane].set(
            rows_at(a, row_starts), mode="drop") for a in inputs),
        jnp.zeros((B,), bool).at[lane].set(True, mode="drop"))
    o = o.at[jnp.where(single, row_starts, M)].set(
        rows_at(o_lane, lane), mode="drop")

    # the gathered rows, longest first, a few a group, as many groups as
    # hold one (none where a pack is decode rows alone)
    need = jnp.where(single, 0, row_lens)
    n = min(long_rows, GROUP_ROWS)
    order = jnp.pad(jnp.argsort(-need).astype(jnp.int32),
                    (0, -R % n), constant_values=R)

    def group(g, carry):
        rows = jax.lax.dynamic_slice(order, (g * n,), (n,))
        real = rows_at(need, rows) > 0
        return gathered(rows, real, *carry)

    return jax.lax.fori_loop(
        0, -(-jnp.sum(need > 0) // n), group, (state, o))
