"""Attention under a sliding window, over a ring of keys and values a lane
(models/exaone_moe.py; docs/hybrid_models.md, "A ring beside the pages").

A window layer's position t attends to positions j with t - W < j <= t, so
a lane needs the K and V of its last W positions and no more, whatever its
context: they live in a ring `[W, KH*D]` a lane and layer (the `state` and
`conv` leaves of ops/state_cache.StateCache), position p at slot p % W,
keys stored rotated, so the order within the ring is nothing to softmax.
Which position a slot holds follows from the lane's context alone
(`ring_positions`), so a ring is never cleared: a sequence that starts in a
lane (context 0) finds every slot masked.

Plain XLA: a decode step's ring is 128 slots a lane, and a prompt's q block
of W rows needs its own and the previous key block alone.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .paged_attention import NEG_INF, rows_at

f32 = jnp.float32


def ring_positions(ctx_lens: jax.Array, window: int) -> jax.Array:
    """[..., W]: the position each slot of a ring holds once `ctx_lens`
    tokens of its sequence are written, the largest p < ctx with p % W ==
    slot; negative where the sequence has none yet."""
    last = ctx_lens[..., None] - 1
    return last - (last - jnp.arange(window)) % window


def _partial_softmax(scores, ok, v, spec: str):
    """One part of a softmax whose keys come in two parts: scores [..., q,
    s] float32, ok the same shape, v the part's values. -> (row maximum,
    sum of exp(score - maximum), the same weights times v), a part with no
    key allowed reading (NEG_INF, 0, 0)."""
    scores = jnp.where(ok, scores, NEG_INF)
    m = scores.max(axis=-1)
    p = jnp.where(ok, jnp.exp(scores - m[..., None]), 0.0)
    return m, p.sum(axis=-1), jnp.einsum(
        spec, p.astype(v.dtype), v, preferred_element_type=f32)


def decode_window_attention(q, ring_k, ring_v, positions):
    """One token a lane: q [B, H, D] at `positions` [B] over the lanes'
    rings [B, W, KH*D], the token's own key and value already in them.
    -> [B, H, D]. Every slot is read, the W of them; those of positions the
    sequence does not have yet (slot > position) are masked."""
    B, H, D = q.shape
    W = ring_k.shape[1]
    KH = ring_k.shape[2] // D
    k, v = (r.reshape(B, W, KH, D) for r in (ring_k, ring_v))
    scores = jnp.einsum(
        "bkgd,bwkd->bkgw", q.reshape(B, KH, H // KH, D), k,
        preferred_element_type=f32) / jnp.sqrt(f32(D))
    ok = jnp.arange(W)[None, :] <= positions[:, None]
    probs = jax.nn.softmax(
        jnp.where(ok[:, None, None, :], scores, NEG_INF), axis=-1)
    out = jnp.einsum("bkgw,bwkd->bkgd", probs.astype(v.dtype), v)
    return out.reshape(B, H, D)


def flat_window_attention(q, k, v, ring_k, ring_v, row_ids, row_starts,
                          row_lens, ctx_lens, long_rows: int):
    """Rows of any length over a flat axis of M token slots (row r: slots
    row_starts[r] ... + row_lens[r], ctx_lens[r] tokens of its sequence
    before it): q [M, H, D], the step's own k and v [M, KH, D] (rotated),
    and each row's lane's ring [R, W, KH*D] AS IT STOOD BEFORE the step.
    -> [M, H, D]; slots of no row return finite garbage.

    Two parts of one softmax. The step's own keys: a q block of W slots
    meets its own and the previous key block under the band, the row and
    the causal order ([blocks, heads, W, 2W] scores). The ring: only a
    row's first W tokens reach back past the row's start; every row's
    first token meets its ring (a decode row is done with that), and the
    `long_rows` longest rows' first W tokens meet theirs (`long_rows`: how
    many rows of more than one token a pack holds at most; a pack that
    breaks it loses the ring's part of the rows past it)."""
    M, H, D = q.shape
    R, W = ring_k.shape[:2]
    KH = k.shape[1]
    G = H // KH
    scale = 1.0 / jnp.sqrt(f32(D))
    nb = -(-M // W)
    Mp = nb * W

    slot = jnp.arange(Mp, dtype=jnp.int32)
    row = jnp.pad(row_ids, (0, Mp - M), constant_values=R - 1)
    local = slot - row_starts[row]
    real = (local >= 0) & (local < row_lens[row]) & (slot < M)

    def blocks(x):
        return jnp.pad(x, ((0, Mp - M),) + ((0, 0),) * (x.ndim - 1)).reshape(
            nb, W, *x.shape[1:])

    def with_previous(x):  # [nb, W, ...] -> [nb, 2W, ...]
        return jnp.concatenate(
            [jnp.concatenate([jnp.zeros_like(x[:1]), x[:-1]]), x], axis=1)

    # the step's own keys, banded
    qb = blocks(q).reshape(nb, W, KH, G, D)
    k2, v2 = with_previous(blocks(k)), with_previous(blocks(v))
    slot_q = slot.reshape(nb, W)[:, :, None]
    slot_k = (slot.reshape(nb, W)[:, :1] - W + jnp.arange(2 * W))[:, None, :]
    row_k = with_previous(row.reshape(nb, W))[:, None, :]
    ok = ((slot_k >= 0) & (slot_k <= slot_q) & (slot_q - slot_k < W)
          & (row_k == row.reshape(nb, W)[:, :, None])
          & with_previous(real.reshape(nb, W))[:, None, :])  # [nb, W, 2W]
    scores = jnp.einsum(
        "nqkgd,nskd->nkgqs", qb, k2, preferred_element_type=f32) * scale
    m_own, l_own, acc_own = _partial_softmax(
        scores, ok[:, None, None], v2, "nkgqs,nskd->nqkgd")
    # [Mp, KH, G(, D)]
    m_own = jnp.moveaxis(m_own, 3, 1).reshape(Mp, KH, G)
    l_own = jnp.moveaxis(l_own, 3, 1).reshape(Mp, KH, G)
    acc_own = acc_own.reshape(Mp, KH, G, D)

    # the rings
    ring_pos = ring_positions(ctx_lens, W)  # [R, W]

    def against_ring(rows, at, q_pos):
        """Rows `rows` [n], their tokens at slots `at` [n, T] (Mp: none),
        at positions q_pos [n, T]."""
        n, T = at.shape
        qh = rows_at(q, at.reshape(-1)).reshape(n, T, KH, G, D)
        rk, rv = (r[rows].reshape(n, W, KH, D) for r in (ring_k, ring_v))
        pos = ring_pos[rows][:, None, :]  # [n, 1, W]
        ok = (pos >= 0) & (pos > q_pos[:, :, None] - W) & (at < Mp)[:, :, None]
        scores = jnp.einsum(
            "ntkgd,nwkd->nkgtw", qh, rk, preferred_element_type=f32) * scale
        m, l, acc = _partial_softmax(
            scores, ok[:, None, None], rv, "nkgtw,nwkd->ntkgd")
        return (jnp.moveaxis(m, 3, 1).reshape(n * T, KH, G),
                jnp.moveaxis(l, 3, 1).reshape(n * T, KH, G),
                acc.reshape(n * T, KH, G, D), at.reshape(-1))

    every = jnp.arange(R, dtype=jnp.int32)
    first = jnp.where(row_lens > 0, row_starts, Mp)[:, None]
    parts = [against_ring(every, first, ctx_lens[:, None])]
    n_long = min(long_rows, R)
    longest = every if n_long == R else jax.lax.top_k(row_lens, n_long)[1]
    offs = jnp.arange(W, dtype=jnp.int32)[None, :]
    parts.append(against_ring(
        longest,
        jnp.where(offs < row_lens[longest][:, None],
                  row_starts[longest][:, None] + offs, Mp),
        ctx_lens[longest][:, None] + offs))
    m_ring = jnp.full((Mp, KH, G), NEG_INF, f32)
    l_ring = jnp.zeros((Mp, KH, G), f32)
    acc_ring = jnp.zeros((Mp, KH, G, D), f32)
    for m, l, acc, at in parts:  # a long row's first token: the same twice
        m_ring = m_ring.at[at].set(m, mode="drop")
        l_ring = l_ring.at[at].set(l, mode="drop")
        acc_ring = acc_ring.at[at].set(acc, mode="drop")

    m = jnp.maximum(m_own, m_ring)
    w_own, w_ring = jnp.exp(m_own - m), jnp.exp(m_ring - m)
    total = l_own * w_own + l_ring * w_ring
    out = (acc_own * w_own[..., None] + acc_ring * w_ring[..., None]) \
        / jnp.where(total > 0, total, 1.0)[..., None]
    return out.reshape(Mp, H, D)[:M].astype(q.dtype)


def rings_after(ring, new, row_starts, row_lens, ctx_lens):
    """Each row's ring [R, W, C] behind the row's last token: slot s holds
    the last position p ≡ s (mod W) of ctx_lens + row_lens tokens, taken
    from the step's own rows `new` [M, C] where the step wrote it and left
    as it was where not (every slot of a row of no tokens)."""
    M, W = new.shape[0], ring.shape[1]
    pos = ring_positions(ctx_lens + row_lens, W)
    ours = pos >= ctx_lens[:, None]
    at = jnp.where(ours, row_starts[:, None] + pos - ctx_lens[:, None], M)
    return jnp.where(ours[..., None], rows_at(new, at), ring)
