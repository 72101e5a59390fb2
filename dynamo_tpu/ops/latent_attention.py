"""Latent attention (MLA) over the paged latent cache (docs/latent_cache.md).

A latent layer keeps ONE row a token: the normed latent `c` (`rank` values)
and the rotated key `k_r` that every head shares (`rope` values), side by
side, `[c | k_r | 0...]`, in a row of `width` lanes, the next multiple of
128 (ops/kv_quant.latent_row_width: 640 for 512 + 64; the module's text
there says what a row of 576 lanes costs on a TPU): a pool `[L, pages,
rows, width]`, which is ops/kv_quant's lane-dense layout with one "head" of
`width` and no V store. Two walks over it, both plain XLA that streams a
bounded block of pages a step and keeps a running softmax, so neither holds
a context-sized temporary and neither cares how wide the page table is (a
walk ends at the longest context of the call, not at the table's end):

  * `absorbed_attention`: one query token a row, in the LATENT space. The
    query arrives with `W_kvb`'s key half folded in (`q~ = q_n W^K`, `rank`
    wide, beside `q_r`), so a cached row is read as it lies: the score is
    `q~ . c + q_r . k_r`, the value is `c`. An MQA of group `heads`: the
    bytes of a context are read once for all heads.
  * `expanded_attention`: rows of many query tokens, in the EXPANDED space.
    A block of the row's cached latents goes through `W_kvb` (a head's
    `nope` key values and `vdim` values), and the row's tokens attend at
    `heads` heads of `nope + rope` / `vdim`, causally, a row at a time.

No Pallas kernel reads the latent row yet (the decode kernel's values are
as wide as its keys, and the prefill kernels read K and V of equal heads);
ROADMAP.md M4 has what one needs.
"""

from __future__ import annotations

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


def expanded_attention(
    q: jax.Array,  # [M, H, nope + rope] on a flat axis that rows share
    latent: KVLayer,
    w_kvb: jax.Array,  # [rank, H * (nope + vdim)]
    page_tables: jax.Array,  # [R, max_pages]
    row_starts: jax.Array,  # [R] slot of each row's first token
    row_lens: jax.Array,  # [R] the row's tokens; rows of 0 or 1 are skipped
    ctx_lens: jax.Array,  # [R] positions of the row's sequence before it
    rank: int,
    nope: int,
    scale: float,
) -> jax.Array:
    """Causal attention of every row of MORE than one token over its own
    pages (its history and itself: the row's latents are written already),
    expanded through `w_kvb` a block at a time -> [M, H, vdim]; slots of
    the other rows return zeros."""
    pool, li = latent
    M, H, D = q.shape
    W, rope = pool.shape[3], D - nope
    vdim = w_kvb.shape[1] // H - nope
    Tq = min(M, EXPANDED_QUERIES)
    tables, pb = _blocked_tables(
        page_tables, max(EXPANDED_POSITIONS // pool.shape[2], 1))
    S = pb * pool.shape[2]
    long = row_lens > 1
    order = jnp.argsort(~long, stable=True)  # the rows to serve come first
    qp = jnp.pad(q, ((0, Tq), (0, 0), (0, 0)))
    steps = jnp.arange(Tq)

    def row(i, out):
        r = order[i]
        start, n, ctx = row_starts[r], row_lens[r], ctx_lens[r]
        table = tables[r]

        def tile(t, out):  # the row's tokens t * Tq ...
            at = start + t * Tq
            qt = jax.lax.dynamic_slice_in_dim(qp, at, Tq, axis=0)
            pos_q = ctx + t * Tq + steps
            real = t * Tq + steps < n
            last = jnp.minimum(ctx + (t + 1) * Tq, ctx + n)  # keys a tile sees

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
            o = jnp.moveaxis(o, 0, 1)  # [Tq, H, vdim]
            old = jax.lax.dynamic_slice_in_dim(out, at, Tq, axis=0)
            return jax.lax.dynamic_update_slice_in_dim(
                out, jnp.where(real[:, None, None], o, old), at, axis=0)

        return jax.lax.fori_loop(0, -(-n // Tq), tile, out)

    out = jnp.zeros((M + Tq, H, vdim), q.dtype)
    return jax.lax.fori_loop(0, long.sum(), row, out)[:M]
