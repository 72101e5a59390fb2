"""ops/row_recurrence.py `rows_recurrence` alone, on the CPU at small sizes:
a pack's rows of one token that go on from a lane's state take the family's
decode step over the store in place, every other row is gathered, stepped,
chunked and scattered back. Each case against the plain reference "every
row stepped token by token from its lane's state"."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.models import hybrid, nemotron_h
from dynamo_tpu.ops.row_recurrence import rows_recurrence

f32 = jnp.float32
LAYERS, LANES, LAYER = 3, 6, 1  # the store's layers and lanes; the one stepped
R, M = 8, 64  # the pack's row bucket and token slots
CHUNK = 4


def delta_family(key):
    """The gated delta rule at 2 heads of 8 x 16: (state shape a lane, out
    shape, in_place, step, chunk, inputs [M, ...])."""
    nv, dk, dv = 2, 8, 16
    ks = jax.random.split(key, 5)
    q = jax.random.normal(ks[0], (M, nv, dk), f32) * dk ** -0.5
    k = jax.random.normal(ks[1], (M, nv, dk), f32)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (M, nv, dv), f32)
    g = -jax.nn.softplus(jax.random.normal(ks[3], (M, nv), f32))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (M, nv), f32))
    return ((nv, dk, dv), (nv, dv),
            functools.partial(hybrid.lanes_step, impl="xla"), hybrid.delta_step,
            hybrid.delta_chunk, (q, k, v, g, beta))


def ssm_family(key):
    """The state-space recurrence at 4 heads of 8 x 16 in 2 groups."""
    heads, hd, N, groups = 4, 8, 16, 2
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (M, heads, hd), f32)
    Bm = jax.random.normal(ks[1], (M, groups, N), f32) * 0.5
    Cm = jax.random.normal(ks[2], (M, groups, N), f32) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[3], (M, heads), f32))
    A = -jnp.exp(jax.random.normal(ks[4], (heads,), f32) * 0.3)
    return ((heads, hd, N), (heads, hd),
            functools.partial(nemotron_h.lanes_step, A=A),
            functools.partial(nemotron_h.ssm_step, A=A),
            functools.partial(nemotron_h.ssd_chunk, A=A), (x, Bm, Cm, dt))


FAMILIES = {"delta": delta_family, "ssm": ssm_family}

#: (lane, tokens, context before them) of each row, and `long_rows`
PACKS = {
    "decode_rows_and_one_prompt": (
        [(0, 1, 7), (2, 1, 30), (3, 1, 1), (5, 11, 0)], 2),
    "two_prompts_and_a_one_token_last_chunk": (
        [(4, 9, 0), (1, 6, 12), (0, 1, 20), (3, 1, 3)], 2),
    "a_first_chunk_of_one_token": (
        [(2, 1, 0), (0, 1, 5), (5, 1, 9)], 2),
    "more_long_rows_than_said": (
        [(0, 5, 0), (1, 1, 4), (2, 9, 3), (3, 2, 8), (4, 1, 0), (5, 6, 2)], 2),
    "a_prefill_batch_of_four_in_groups": (
        [(5, 3, 0), (0, 1, 9), (1, 12, 5), (2, 7, 0), (4, 2, 2)], 4),
    "decode_rows_alone": (
        [(1, 1, 2), (3, 1, 40), (4, 1, 7)], 2),
    "every_row_may_be_long": (
        [(0, 1, 7), (2, 5, 0), (3, 1, 1), (5, 11, 4)], R),
}


def pack_of(rows):
    """(lanes, row_starts, row_lens, ctx_lens) [R] of `rows`, laid end to
    end from slot 0; the rows past them: the scratch lane, no token."""
    lanes = np.full(R, LANES, np.int32)
    starts, lens, ctx = (np.zeros(R, np.int32) for _ in range(3))
    at = 0
    for r, (lane, n, before) in enumerate(rows):
        lanes[r], starts[r], lens[r], ctx[r] = lane, at, n, before
        at += n
    starts[len(rows):] = at
    return tuple(jnp.asarray(a) for a in (lanes, starts, lens, ctx))


def token_by_token(state, rows, step, inputs):
    """Every row stepped a token at a time from its lane's state (zero for a
    first chunk): (the store after, o [M, ...] with zeros where no token)."""
    state = np.array(state)
    o = None
    at = 0
    for lane, n, before in rows:
        S = jnp.asarray(state[LAYER, lane] if before else
                        np.zeros_like(state[LAYER, lane]))[None]
        for t in range(at, at + n):
            S, ot = step(S, *(a[t][None] for a in inputs))
            if o is None:
                o = np.zeros((M, *ot.shape[1:]), np.float32)
            o[t] = ot[0]
        state[LAYER, lane] = S[0]
        at += n
    return state, o


@pytest.mark.parametrize("pack", sorted(PACKS))
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_a_packs_rows_leave_what_token_by_token_leaves(family, pack):
    key = jax.random.PRNGKey(sorted(PACKS).index(pack))
    lane_shape, out_shape, in_place, step, chunk, inputs = FAMILIES[family](key)
    rows, long_rows = PACKS[pack]
    store = jax.random.normal(
        jax.random.fold_in(key, 7), (LAYERS, LANES + 1, *lane_shape), f32)
    lanes, starts, lens, ctx = pack_of(rows)
    got_state, got_o = jax.jit(
        lambda s: rows_recurrence(
            s, jnp.int32(LAYER), lanes, ctx, inputs, out_shape, in_place,
            step, chunk, CHUNK, starts, lens, long_rows))(store)
    want_state, want_o = token_by_token(store, rows, step, inputs)
    got_state, got_o = np.asarray(got_state), np.asarray(got_o)
    packed = [lane for lane, _, _ in rows]
    np.testing.assert_allclose(
        got_state[LAYER, packed], want_state[LAYER, packed], rtol=1e-5, atol=1e-5)
    tokens = sum(n for _, n, _ in rows)
    np.testing.assert_allclose(got_o[:tokens], want_o[:tokens], rtol=1e-5, atol=1e-5)
    # what no row of the pack owns keeps its bits: the other layers, the
    # idle lanes and the scratch lane
    store = np.asarray(store)
    others = [l for l in range(LAYERS) if l != LAYER]
    np.testing.assert_array_equal(got_state[others], store[others])
    idle = [l for l in range(LANES) if l not in packed]
    np.testing.assert_array_equal(got_state[LAYER, idle], store[LAYER, idle])
    np.testing.assert_array_equal(got_state[LAYER, LANES], store[LAYER, LANES])


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_a_one_token_row_is_the_decode_steps_own_call(family):
    """A pack's one-token rows that go on from a state reach `in_place`
    with their inputs at their lanes and `live` true for exactly those
    lanes, once; the gathered rows never do."""
    key = jax.random.PRNGKey(3)
    lane_shape, out_shape, in_place, step, chunk, inputs = FAMILIES[family](key)
    rows, long_rows = PACKS["decode_rows_and_one_prompt"]
    store = jnp.zeros((LAYERS, LANES + 1, *lane_shape), f32)
    lanes, starts, lens, ctx = pack_of(rows)
    seen = []

    def spy(state, layer, *rest):
        seen.append(rest)
        return in_place(state, layer, *rest)

    rows_recurrence(store, jnp.int32(LAYER), lanes, ctx, inputs, out_shape,
                    spy, step, chunk, CHUNK, starts, lens, long_rows)
    (*at_lanes, live), = seen
    assert np.asarray(live).tolist() == [True, False, True, True, False, False]
    for a, at_lane in zip(inputs, at_lanes):
        assert at_lane.shape == (LANES, *a.shape[1:])
        for r, (lane, n, before) in enumerate(rows[:3]):
            np.testing.assert_array_equal(at_lane[lane], a[r])
        np.testing.assert_array_equal(at_lane[jnp.asarray([1, 4, 5])], 0)

    seen.clear()
    rows_recurrence(store, jnp.int32(LAYER), lanes, ctx, inputs, out_shape,
                    spy, step, chunk, CHUNK, starts, lens, R)
    assert not seen  # a batched prefill gathers every row
