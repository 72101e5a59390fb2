"""Latent attention (MLA) over the paged latent cache (docs/latent_cache.md).

A latent layer keeps ONE row a token: the normed latent `c` (`rank` values)
and the rotated key `k_r` that every head shares (`rope` values), side by
side, `[c | k_r | 0...]`, in a row of `width` lanes, the next multiple of
128 (ops/kv_quant.latent_row_width: 640 for 512 + 64; the module's text
there says what a row of 576 lanes costs on a TPU): a pool `[L, pages,
rows, width]`, which is ops/kv_quant's lane-dense layout with one "head" of
`width` and no V store. Three walks over it, all plain XLA that streams a
bounded block of pages a step and keeps a running softmax, so none holds a
context-sized temporary and none cares how wide the page table is (a walk
ends at the longest context of what it serves, not at the table's end).
Which side of the score `W_kvb` stands on is read off a row's COST:

  * `absorbed_attention`: one query token a row, in the LATENT space, every
    row a lane of one walk. The query arrives with `W_kvb`'s key half folded
    in (`q~ = q_n W^K`, `rank` wide, beside `q_r`), so a cached row is read
    as it lies: the score is `q~ . c + q_r . k_r`, the value is `c`. An MQA
    of group `heads`: the bytes of a context are read once for all heads.
  * `absorbed_rows_attention`: a SHORT row of `1 < n <= T*` tokens (a
    request's tail behind a cached prefix, a split prompt's last chunk), in
    the latent space too, a row at a time over the row's OWN pages up to its
    own last position. The row's tokens are folded into the head axis: a
    tile of them gives `[tile x heads, width]` query rows against one
    gathered block, each token under its own causal limit, and `u W^V` on
    the way out. An MQA of group `n x heads`: the context's bytes are read
    once for all the row's tokens and heads.
  * `expanded_attention`: a row of MORE than `T*` tokens, in the EXPANDED
    space. A block of the row's cached latents goes through `W_kvb` (a
    head's `nope` key values and `vdim` values), and the row's tokens attend
    at `heads` heads of `nope + rope` / `vdim`, causally, a row at a time.

`T*` is `absorbed_row_limit`: a CACHED position costs the expanded form
`rank x heads x (nope + vdim)` multiply-adds whatever the row holds, and a
cached position and QUERY token costs it `heads x (nope + rope + vdim)`
where the absorbed form pays `heads x (width + rank)`; the two cross at
`rank x (nope + vdim) / (width + rank - nope - rope - vdim)` query tokens,
whatever the context's length (358 at 512 / 192 / 64 / 256 in a row of 640).

Two forms of the short row's walk that look right and are not: its tokens as
so many more lanes of `absorbed_attention` (every lane gathers its own copy
of the context: 13 copies of 21 MB a layer for a tail of 13 behind 16k), and
a token axis on every lane of the lanes' walk (lanes x tile times the flops
for one row's sake).

A configuration that SELECTS the positions a token attends (a learned
indexer's `index_topk` highest: models/mla_moe.py) adds two functions at the
end of this module and no walk of the three changes for it: `select_rows`
scores a lane's context from the index-key store and takes the exact top-k,
`selected_attention` gathers THOSE latent rows by position and attends them
absorbed; every token that selects is a lane of both (docs/latent_cache.md,
"A learned selection"). The by-row walks take `only`, the rows to serve.

No Pallas kernel reads the latent row yet (the decode kernel's values are
as wide as its keys, and the prefill kernels read K and V of equal heads);
ROADMAP.md M4 has what one needs.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .kv_quant import KVLayer

f32 = jnp.float32
NEG_INF = -1e30
#: pages of every row that one step of the absorbed walk gathers
ABSORBED_PAGES = 16
#: cached positions of ONE row that one step of the expanded walk expands
EXPANDED_POSITIONS = 1024
#: query tokens of one row that the expanded walk holds at a time
EXPANDED_QUERIES = 1024
#: query tokens of one SHORT row that its absorbed walk holds at a time, each
#: at every head: `ABSORBED_ROW_QUERIES x heads` query rows a gathered block.
#: On a v5e, one row behind 16,384 positions at the published widths, a layer
#: (PERF.md section 5, PR 55): tiles of 16 / 32 / 64 take 0.39 / 0.43 / 0.59
#: ms at 13 tokens and 3.90 / 2.67 / 2.48 at 352, where expanding takes 1.97
#: and 2.95: at 32 the chip's crossing is not under the rule's 358
ABSORBED_ROW_QUERIES = 32


def absorbed_row_limit(rank: int, heads: int, nope: int, rope: int, vdim: int,
                       width: int) -> int:
    """`T*`, the most tokens a row may hold and still attend absorbed: the
    query tokens at which the two forms cost the same multiply-adds a cached
    position (the module's text), rounded down. Expanded: `rank x heads x
    (nope + vdim)` for the position + `heads x (nope + rope + vdim)` a
    query token. Absorbed: `heads x (width + rank)` a query token, the
    row's zeros among them. Widths whose absorbed form is the cheaper one a
    query token have no crossing: every row attends absorbed."""
    a_token = heads * (width + rank)
    e_token = heads * (nope + rope + vdim)
    if a_token <= e_token:
        return 2**31 - 1
    return rank * heads * (nope + vdim) // (a_token - e_token)


def _blocked_tables(page_tables, pages: int):
    """(tables padded with the scratch page 0 to a whole number of blocks
    of `pages` pages, pages a block)."""
    P = page_tables.shape[-1]
    pb = min(pages, P)
    pad = [(0, 0)] * (page_tables.ndim - 1) + [(0, -P % pb)]
    return jnp.pad(page_tables, pad), pb


def _online(carry, s, mask, values, spec):
    """One block of a running softmax: scores `s` (f32, `mask`ed) against
    `values` by the einsum `spec`."""
    m, l, acc = carry
    s = jnp.where(mask, s, NEG_INF)
    m_new = jnp.maximum(m, s.max(-1))
    alpha = jnp.exp(m - m_new)
    p = jnp.where(mask, jnp.exp(s - m_new[..., None]), 0.0)
    acc = acc * alpha[..., None] + jnp.einsum(
        spec, p.astype(values.dtype), values, preferred_element_type=f32)
    return m_new, l * alpha + p.sum(-1), acc


def absorbed_attention(
    q: jax.Array,  # [B, H, rank + rope]: (q_n W^K | q_r) of one token a row;
    # padded here with zeros to the pool's row
    latent: KVLayer,  # the whole latent pool + the layer's index
    page_tables: jax.Array,  # [B, max_pages]
    seq_lens: jax.Array,  # [B] positions to attend, the new one among them
    rank: int,
    scale: float,
) -> jax.Array:
    """sum_s softmax_s(scale x q . [c_s | k_r_s]) c_s over each row's own
    pages -> [B, H, rank]; a row of length 0 reads nothing that counts and
    returns zeros."""
    pool, li = latent
    B, H, _ = q.shape
    W = pool.shape[3]
    q = jnp.pad(q, ((0, 0), (0, 0), (0, W - q.shape[2])))
    tables, pb = _blocked_tables(page_tables, ABSORBED_PAGES)
    S = pb * pool.shape[2]

    def block(j, carry):
        tb = jax.lax.dynamic_slice_in_dim(tables, j * pb, pb, axis=1)
        rows = pool[li, tb].reshape(B, S, W)
        s = jnp.einsum("bhw,bsw->bhs", q, rows, preferred_element_type=f32)
        mask = (j * S + jnp.arange(S))[None, :] < seq_lens[:, None]
        return _online(carry, s * scale, mask[:, None, :], rows[..., :rank],
                       "bhs,bsr->bhr")

    init = (jnp.full((B, H), NEG_INF, f32), jnp.zeros((B, H), f32),
            jnp.zeros((B, H, rank), f32))
    _, l, acc = jax.lax.fori_loop(0, -(-jnp.max(seq_lens) // S), block, init)
    return (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)


def _one_function_a_program(*static):
    """jax.jit with the keywords `static`: a program calls a by-row walk
    once a layer, and as a jitted function of the layer's index (data, with
    the pool) all its layers share ONE traced and lowered function, which
    the compiler inlines. A start pays for a program's text before its
    cache is asked (on a v5e at the published widths, a mixed step of 2,048
    slots: 4.2 s from the cache with both walks written out a layer, 3.7 s
    so, 3.4 s before there was a second walk; to compile, 42 / 39 / 36 s:
    PERF.md section 6, PR 55). The block and tile sizes are this module's
    constants where a caller reads them, static arguments in here."""
    return functools.partial(jax.jit, static_argnames=static)


def _served(serve, only):
    """`serve` [R] held to the rows `only` marks (None: as it is, and no
    operation more in the program)."""
    return serve if only is None else serve & only


def _by_row(q, out, serve, Tq, tables, row_starts, row_lens, ctx_lens,
            attend):
    """`out` [M, H, vdim] with the slots of the rows that `serve` [R] marks
    filled: a walk by row (as many trips as it marks: none, and `out` comes
    back as it went), then by tile of `Tq` of the row's tokens, `attend(qt,
    table, pos_q, real, last) -> [Tq, H, vdim]` for a tile's queries
    `qt` [Tq, H, D] at positions `pos_q` (the `real` ones the row's own)
    over the row's blocked `table` up to position `last`."""
    M = q.shape[0]
    order = jnp.argsort(~serve, stable=True)  # the rows to serve come first
    qp = jnp.pad(q, ((0, Tq), (0, 0), (0, 0)))
    steps = jnp.arange(Tq)

    def row(i, out):
        r = order[i]
        start, n, ctx = row_starts[r], row_lens[r], ctx_lens[r]
        table = tables[r]

        def tile(t, out):  # the row's tokens t * Tq ...
            at = start + t * Tq
            qt = jax.lax.dynamic_slice_in_dim(qp, at, Tq, axis=0)
            real = t * Tq + steps < n
            last = jnp.minimum(ctx + (t + 1) * Tq, ctx + n)  # keys a tile sees
            o = attend(qt, table, ctx + t * Tq + steps, real, last)
            old = jax.lax.dynamic_slice_in_dim(out, at, Tq, axis=0)
            return jax.lax.dynamic_update_slice_in_dim(
                out, jnp.where(real[:, None, None], o, old), at, axis=0)

        return jax.lax.fori_loop(0, -(-n // Tq), tile, out)

    out = jnp.pad(out, ((0, Tq), (0, 0), (0, 0)))
    return jax.lax.fori_loop(0, serve.sum(), row, out)[:M]


def expanded_attention(
    q: jax.Array,  # [M, H, nope + rope] on a flat axis that rows share
    latent: KVLayer,
    w_kvb: jax.Array,  # [rank, H * (nope + vdim)]
    page_tables: jax.Array,  # [R, max_pages]
    row_starts: jax.Array,  # [R] slot of each row's first token
    row_lens: jax.Array,  # [R] the row's tokens
    ctx_lens: jax.Array,  # [R] positions of the row's sequence before it
    rank: int,
    nope: int,
    scale: float,
    longer_than: int = 1,  # rows of this many tokens and fewer are skipped
    only=None,  # [R] bool: of those rows, the ones to serve (None: all)
) -> jax.Array:
    """Causal attention of every row of MORE than `longer_than` tokens over
    its own pages (its history and itself: the row's latents are written
    already), expanded through `w_kvb` a block at a time -> [M, H, vdim];
    slots of the other rows return zeros."""
    return _expanded_rows(
        q, *latent, w_kvb, page_tables, row_starts, row_lens, ctx_lens, only,
        rank=rank, nope=nope, scale=scale, longer_than=longer_than,
        positions=EXPANDED_POSITIONS, queries=EXPANDED_QUERIES)


@_one_function_a_program(
    "rank", "nope", "scale", "longer_than", "positions", "queries")
def _expanded_rows(q, pool, li, w_kvb, page_tables, row_starts, row_lens,
                   ctx_lens, only, *, rank, nope, scale, longer_than,
                   positions, queries):
    M, H, D = q.shape
    W, rope = pool.shape[3], D - nope
    vdim = w_kvb.shape[1] // H - nope
    Tq = min(M, queries)
    tables, pb = _blocked_tables(
        page_tables, max(positions // pool.shape[2], 1))
    S = pb * pool.shape[2]

    def attend(qt, table, pos_q, real, last):
        def block(j, carry):
            tb = jax.lax.dynamic_slice_in_dim(table, j * pb, pb)
            rows = pool[li, tb].reshape(S, W)
            kv = jnp.dot(rows[:, :rank], w_kvb).astype(q.dtype)
            kv = kv.reshape(S, H, nope + vdim)
            s = jnp.einsum("thn,shn->hts", qt[..., :nope], kv[..., :nope],
                           preferred_element_type=f32)
            s += jnp.einsum("thr,sr->hts", qt[..., nope:],
                            rows[:, rank:rank + rope],
                            preferred_element_type=f32)
            pos_k = j * S + jnp.arange(S)
            mask = (pos_k[None, :] <= pos_q[:, None]) & real[:, None]
            return _online(carry, s * scale, mask[None], kv[..., nope:],
                           "hts,shv->htv")

        init = (jnp.full((H, Tq), NEG_INF, f32), jnp.zeros((H, Tq), f32),
                jnp.zeros((H, Tq, vdim), f32))
        _, l, acc = jax.lax.fori_loop(0, -(-last // S), block, init)
        o = (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)
        return jnp.moveaxis(o, 0, 1)  # [Tq, H, vdim]

    return _by_row(
        q, jnp.zeros((M, H, vdim), q.dtype),
        _served(row_lens > longer_than, only), Tq, tables, row_starts,
        row_lens, ctx_lens, attend)


def absorbed_rows_attention(
    q: jax.Array,  # [M, H, nope + rope] on a flat axis that rows share
    latent: KVLayer,
    w_kvb: jax.Array,  # [rank, H * (nope + vdim)]
    page_tables: jax.Array,  # [R, max_pages]
    row_starts: jax.Array,  # [R] slot of each row's first token
    row_lens: jax.Array,  # [R] the row's tokens
    ctx_lens: jax.Array,  # [R] positions of the row's sequence before it
    rank: int,
    nope: int,
    scale: float,
    upto: int,  # rows of 2 ... `upto` tokens are served
    out: jax.Array,  # [M, H, vdim]: what the other rows' slots keep
    only=None,  # [R] bool: of those rows, the ones to serve (None: all)
) -> jax.Array:
    """Causal attention of every row of 2 to `upto` tokens over its own
    pages (its history and itself), in the latent space -> `out` with those
    rows' slots filled. By row, by tile of the row's tokens, by block of the
    row's own pages up to the tile's last position: `q~ = q_n W^K` of the
    tile's tokens at every head against the gathered block as it lies, each
    token under its own causal limit, then `u W^V`. `q~` and `u` are rounded
    to q's dtype, as the lanes' walk rounds them."""
    return _absorbed_rows(
        q, *latent, w_kvb, page_tables, row_starts, row_lens, ctx_lens, out,
        only, rank=rank, nope=nope, scale=scale, upto=upto, pages=ABSORBED_PAGES,
        queries=ABSORBED_ROW_QUERIES)


@_one_function_a_program(
    "rank", "nope", "scale", "upto", "pages", "queries")
def _absorbed_rows(q, pool, li, w_kvb, page_tables, row_starts, row_lens,
                   ctx_lens, out, only, *, rank, nope, scale, upto, pages,
                   queries):
    M, H, D = q.shape
    W, rope = pool.shape[3], D - nope
    w = w_kvb.reshape(rank, H, -1)
    Tq = min(M, queries)
    tables, pb = _blocked_tables(page_tables, pages)
    S = pb * pool.shape[2]

    def attend(qt, table, pos_q, real, last):
        q_lat = jnp.einsum("thn,rhn->thr", qt[..., :nope], w[..., :nope],
                           preferred_element_type=f32).astype(q.dtype)
        qw = jnp.concatenate(  # [Tq, H, W]: (q~ | q_r | 0...), the row's
            [q_lat, qt[..., nope:],
             jnp.zeros((Tq, H, W - rank - rope), q.dtype)], axis=-1)

        def block(j, carry):
            tb = jax.lax.dynamic_slice_in_dim(table, j * pb, pb)
            rows = pool[li, tb].reshape(S, W)
            s = jnp.einsum("thw,sw->ths", qw, rows,
                           preferred_element_type=f32)
            pos_k = j * S + jnp.arange(S)
            mask = (pos_k[None, :] <= pos_q[:, None]) & real[:, None]
            return _online(carry, s * scale, mask[:, None, :],
                           rows[:, :rank], "ths,sr->thr")

        init = (jnp.full((Tq, H), NEG_INF, f32), jnp.zeros((Tq, H), f32),
                jnp.zeros((Tq, H, rank), f32))
        _, l, acc = jax.lax.fori_loop(0, -(-last // S), block, init)
        u = (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)
        return jnp.einsum("thr,rhv->thv", u, w[..., nope:],
                          preferred_element_type=f32).astype(q.dtype)

    return _by_row(
        q, out, _served((row_lens > 1) & (row_lens <= upto), only), Tq,
        tables, row_starts, row_lens, ctx_lens, attend)


# ---------------------------------------------------------------------- #
# a learned selection of the context (the lightning indexer's picks)
# ---------------------------------------------------------------------- #

#: lanes that one step of `select_rows` / `selected_attention` holds: each
#: lane gathers its OWN `k` latent rows (32 lanes x 2,048 rows x 640 lanes of
#: bfloat16 are 84 MB) and, while it scores, its own block of index keys
SELECTED_LANES = 32
#: pages of every lane that one step of the index scoring gathers
INDEX_PAGES = 16


def _in_tiles(serve, out, fn):
    """`out` [N, ...] with the slots `serve` [N] marks filled by `fn(ids,
    live) -> [tile, ...]` for a tile of lanes `ids` (the `live` ones real).
    The lanes to serve are walked `SELECTED_LANES` at a time, those first:
    a step's padding and the tokens another walk serves cost no trip. A
    batch of one tile's lanes or fewer is one call and no loop."""
    N, tile = serve.shape[0], SELECTED_LANES
    if N <= tile:
        ids = jnp.arange(N, dtype=jnp.int32)
        keep = serve.reshape(N, *[1] * (out.ndim - 1))
        return jnp.where(keep, fn(ids, serve), out)
    order = jnp.pad(jnp.argsort(~serve, stable=True).astype(jnp.int32),
                    (0, tile))
    count = serve.sum()
    steps = jnp.arange(tile)

    def one(t, out):
        ids = jax.lax.dynamic_slice_in_dim(order, t * tile, tile)
        live = t * tile + steps < count
        return out.at[jnp.where(live, ids, N)].set(fn(ids, live), mode="drop")

    return jax.lax.fori_loop(0, -(-count // tile), one, out)


def select_rows(
    q_i: jax.Array,  # [N, J, D] index queries, one token a lane
    w: jax.Array,  # [N, J] float32: a token's weight of each index head
    index: KVLayer,  # the index-key store [full layers, pages, rows, D] + fi
    tables: jax.Array,  # [N, max_pages] each lane's own page table
    seq_lens: jax.Array,  # [N] positions the lane may pick from (s < that)
    k: int,
    serve: jax.Array,  # [N] bool: the lanes that pick
) -> jax.Array:
    """picks [N, k] i32: the `k` positions of largest index score `I[s] =
    sum_j w_j relu(q_j . k_s)` among each lane's first `seq_lens` positions,
    EXACT (`jax.lax.top_k` of float32 scores: the set is the model's), in no
    order that means anything; -1 in the places a context shorter than `k`
    leaves empty, and everywhere in a lane that is not served. A block of
    `INDEX_PAGES` pages of keys a step: the scores of a lane are a vector
    `[positions]`, never a head axis times the context."""
    return _select_rows(q_i, w, *index, tables, seq_lens, serve, k=k,
                        pages=INDEX_PAGES)


@_one_function_a_program("k", "pages")
def _select_rows(q_i, w, store, fi, tables, seq_lens, serve, *, k, pages):
    N = q_i.shape[0]
    tables, pb = _blocked_tables(tables, pages)
    R = store.shape[2]
    S, span = pb * R, tables.shape[1] * R
    assert span > k, "a table of k positions or fewer selects nothing"

    def pick(ids, live):
        qt, wt, tb = q_i[ids], w[ids], tables[ids]
        lens = jnp.where(live, seq_lens[ids], 0)

        def block(j, scores):
            t = jax.lax.dynamic_slice_in_dim(tb, j * pb, pb, axis=1)
            keys = store[fi, t].reshape(ids.shape[0], S, -1)
            s = jnp.einsum("njd,nsd->njs", qt, keys,
                           preferred_element_type=f32)
            s = jnp.einsum("njs,nj->ns", jax.nn.relu(s), wt)
            return jax.lax.dynamic_update_slice_in_dim(scores, s, j * S, 1)

        scores = jax.lax.fori_loop(
            0, -(-jnp.max(lens) // S), block,
            jnp.zeros((ids.shape[0], span), f32))
        scores = jnp.where(jnp.arange(span)[None, :] < lens[:, None], scores,
                           NEG_INF)
        top, at = jax.lax.top_k(scores, k)
        return jnp.where(top > NEG_INF / 2, at.astype(jnp.int32), -1)

    return _in_tiles(serve, jnp.full((N, k), -1, jnp.int32), pick)


def selected_attention(
    q: jax.Array,  # [N, H, rank + rope]: (q_n W^K | q_r) of one token a lane
    latent: KVLayer,
    tables: jax.Array,  # [N, max_pages] each lane's own page table
    picks: jax.Array,  # [N, k] the positions a lane attends; -1: none
    rank: int,
    scale: float,
    serve: jax.Array,  # [N] bool: the lanes that attend
) -> jax.Array:
    """sum_{s in picks} softmax(scale x q . [c_s | k_r_s]) c_s -> [N, H,
    rank], absorbed: each lane READS its picked rows and no other (a gather
    by position through the lane's page table, `k` rows of the pool's width
    a lane and layer whatever the context holds); zeros for a lane that is
    not served or picks nothing."""
    return _selected(q, *latent, tables, picks, serve, rank=rank, scale=scale)


@_one_function_a_program("rank", "scale")
def _selected(q, pool, li, tables, picks, serve, *, rank, scale):
    N, H, _ = q.shape
    R, W = pool.shape[2], pool.shape[3]
    flat = pool.reshape(-1, W)
    q = jnp.pad(q, ((0, 0), (0, 0), (0, W - q.shape[2])))

    def attend(ids, live):
        at = picks[ids]
        real = (at >= 0) & live[:, None]
        at = jnp.maximum(at, 0)
        page = jnp.take_along_axis(tables[ids], at // R, axis=1)
        # one index a row into the pool as `[layers x pages x rows, W]` (a
        # view: the row axis stays whole tiles): on a v5e 1.65 ms for 32 x
        # 2,048 rows of 640 where `pool[li, page, row]` takes 1.88
        # (PERF.md section 5, PR 58)
        rows = jnp.take(flat, (li * pool.shape[1] + page) * R + at % R, axis=0)
        s = jnp.einsum("nhw,nsw->nhs", q[ids], rows,
                       preferred_element_type=f32) * scale
        s = jnp.where(real[:, None, :], s, NEG_INF)
        p = jnp.where(real[:, None, :],
                      jnp.exp(s - s.max(-1, keepdims=True)), 0.0)
        u = jnp.einsum("nhs,nsr->nhr", p.astype(rows.dtype), rows[..., :rank],
                       preferred_element_type=f32)
        return (u / jnp.maximum(p.sum(-1), 1e-30)[..., None]).astype(q.dtype)

    return _in_tiles(serve, jnp.zeros((N, H, rank), q.dtype), attend)
