"""What the stateful families' mixers share over a flat axis of token slots
(models/hybrid.py's gated delta rule, models/nemotron_h.py's state-space
layers; docs/hybrid_models.md): R rows share M slots (row r: slots
row_starts[r] ... + row_lens[r]), each row goes on from its own lane's
convolution tail and state and leaves its own behind.

`flat_conv` is the causal depthwise convolution of a few taps; `rows_
recurrence` takes a recurrence, given in its step form and its chunked
form, over the rows in two passes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .paged_attention import rows_at

f32 = jnp.float32


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


def rows_recurrence(S, inputs, out_shape, step, chunk, chunk_len: int,
                    row_starts, row_lens, long_rows: int):
    """A recurrence over the rows, each from its state S[r] (float32).
    `inputs`: what a token feeds it, arrays [M, ...]; a token whose inputs
    are all 0 (slot M: `rows_at`'s zero row) must leave a state as it was.
    `step(S, *inputs at one slot a row) -> (S, o [R, *out_shape])`;
    `chunk(S_rows, *inputs [n, chunk_len, ...]) -> (S_rows, o [n, chunk_len,
    *out_shape])`. -> (S behind each row's last token, o [M, *out_shape]).

    Two passes. Every row's FIRST token goes through the step form, all
    rows at once: a decode row is done with that. What is left of the rows
    of more tokens goes through the chunked form, `chunk_len` tokens an
    iteration, as many iterations as the longest of them needs.
    `long_rows`: how many rows of more than one token the caller expects
    at most (a mixed step's prefill batch; every row of a batched prefill):
    the chunked pass runs over the `long_rows` longest rows alone, so that
    the decode rows of a mixed step cost it nothing, and again over the
    next `long_rows` while more rows turn out to be long."""
    M, R = inputs[0].shape[0], row_lens.shape[0]

    def at_slots(at):
        return tuple(rows_at(a, at) for a in inputs)

    # pass one: every row's first token, the step form
    first = jnp.where(row_lens > 0, row_starts, M)
    S, o_first = step(S, *at_slots(first))
    o = jnp.zeros((M, *out_shape), f32)
    o = o.at[first].set(o_first, mode="drop")

    def chunks(rows, S, o):
        """Pass two over `rows` [n] (indices of rows; R: no row): their
        tokens from the second on, `chunk_len` an iteration."""
        row = jnp.minimum(rows, R - 1)
        starts = row_starts[row] + 1
        left = jnp.where(rows < R, row_lens[row] - 1, 0)

        def one(j, carry):
            S_rows, o = carry
            offset = j * chunk_len + jnp.arange(chunk_len)
            at = jnp.where(offset[None, :] < left[:, None],
                           starts[:, None] + offset, M)  # [n, chunk_len]
            S_rows, oc = chunk(S_rows, *at_slots(at))
            return S_rows, o.at[at].set(oc, mode="drop")

        S_rows, o = jax.lax.fori_loop(
            0, -(-jnp.max(left) // chunk_len), one, (S[row], o))
        return S.at[rows].set(S_rows, mode="drop"), o

    if long_rows >= R:
        return chunks(jnp.arange(R, dtype=jnp.int32), S, o)
    # the longest rows first, `long_rows` a group, as many groups as hold a
    # row of more than one token: one, unless a caller packs more such rows
    # than it said (no group then costs a row of one token anything, and a
    # group's temporaries are those of `long_rows` rows whatever R is)
    groups = -(-R // long_rows)
    order = jnp.pad(jnp.argsort(-row_lens).astype(jnp.int32),
                    (0, groups * long_rows - R), constant_values=R)

    def group(g, carry):
        rows = jax.lax.dynamic_slice(order, (g * long_rows,), (long_rows,))
        return chunks(rows, *carry)

    return jax.lax.fori_loop(
        0, -(-jnp.sum(row_lens > 1) // long_rows), group, (S, o))
