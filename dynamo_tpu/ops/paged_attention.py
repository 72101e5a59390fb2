"""Paged attention ops: XLA reference implementations.

The role of the reference's engine attention kernels + block_copy.cu, done
the TPU way: static-shaped gathers + einsums that XLA fuses well on the MXU,
with the Pallas kernels (ops/pallas_*.py) swapped in on TPU behind ONE gate
(`_pallas_eligible`); what layout a kernel wants of its operands is built
here, behind that gate, not in the models.

Layouts:
  kv_k_layer / kv_v_layer: ops/kv_quant.KVLayer — the WHOLE lane-dense
      pool [L, num_pages, page_size, kv_heads*head_dim] (or QuantKV) plus
      the layer index; never a slice of the pool
  page_table: logical page index -> physical page id
"""

from __future__ import annotations

import contextlib
import functools
import contextvars
import os
from typing import Optional

import jax
import jax.numpy as jnp

from .kv_quant import KVLayer, gather_dequant, is_quant_kv, layer_dims

NEG_INF = -1e30


def prefill_attention(
    q: jax.Array,  # [T, H, D] (current chunk, rope applied)
    k_chunk: jax.Array,  # [T, KH, D] (unused: already written to pages)
    v_chunk: jax.Array,
    kv_k_layer: KVLayer,  # whole pool + layer index (kv_quant.kv_layer)
    kv_v_layer: KVLayer,
    positions: jax.Array,  # [T] absolute positions of the chunk
    page_table: jax.Array,  # [max_pages]
    context_len: jax.Array,  # scalar (history before this chunk)
    total_len: Optional[jax.Array] = None,  # scalar: history + real chunk len
) -> jax.Array:
    """Chunk attends to all earlier positions (history pages + itself,
    causal). Returns [T, H, D].

    Dispatch: on TPU the Pallas flash kernel
    (ops/pallas_prefill_attention.py) streams only the pages that hold real
    context; elsewhere the XLA reference path below gathers the page table
    (the engine bounds the table length to the context bucket, so the
    gather is context-sized, not max-context-sized).
    """
    if total_len is not None and _pallas_eligible(
        q.shape[-1], is_quant_kv(kv_k_layer.pool)
    ):
        from .pallas_prefill_attention import paged_prefill_attention_pallas

        return paged_prefill_attention_pallas(
            q, kv_k_layer, kv_v_layer, page_table, context_len, total_len
        )
    T, H, D = q.shape
    page_size, KH_l = layer_dims(kv_k_layer, D)
    S = page_table.shape[0] * page_size
    ctx_k = gather_dequant(kv_k_layer, page_table, D, q.dtype).reshape(S, KH_l, D)
    ctx_v = gather_dequant(kv_v_layer, page_table, D, q.dtype).reshape(S, KH_l, D)

    KH = ctx_k.shape[1]
    G = H // KH
    qg = q.reshape(T, KH, G, D)
    scores = jnp.einsum(
        "tkgd,skd->tkgs", qg, ctx_k, preferred_element_type=jnp.float32
    ) / jnp.sqrt(jnp.asarray(D, jnp.float32))
    # causal over absolute positions: key j valid iff j <= pos_t
    key_pos = jnp.arange(S)
    mask = key_pos[None, :] <= positions[:, None]  # [T, S]
    scores = jnp.where(mask[:, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("tkgs,skd->tkgd", probs.astype(ctx_v.dtype), ctx_v)
    return out.reshape(T, H, D)


def prefill_attention_batched(
    q: jax.Array,  # [B, T, H, D] (chunks, rope applied)
    kv_k_layer: KVLayer,  # whole pool + layer index (kv_quant.kv_layer)
    kv_v_layer: KVLayer,
    positions: jax.Array,  # [B, T] absolute positions
    page_tables: jax.Array,  # [B, max_pages]
    total_lens: jax.Array,  # [B] valid context per seq (history + real chunk)
    starts: jax.Array,  # [B] absolute position of each chunk's row 0
) -> jax.Array:
    """Batched chunked prefill: each sequence's chunk attends to its own
    history pages + itself (causal). Returns [B, T, H, D].

    Dispatch: on TPU the batched Pallas flash kernel streams only real
    context pages; elsewhere the XLA path gathers each (engine-bounded)
    page table.
    """
    if _pallas_eligible(q.shape[-1], is_quant_kv(kv_k_layer.pool)):
        from .pallas_prefill_attention import paged_prefill_attention_pallas_batched

        return paged_prefill_attention_pallas_batched(
            q, kv_k_layer, kv_v_layer, page_tables, starts, total_lens
        )
    B, T, H, D = q.shape
    page_size, KH = layer_dims(kv_k_layer, D)
    S = page_tables.shape[1] * page_size
    ctx_k = gather_dequant(kv_k_layer, page_tables, D, q.dtype).reshape(B, S, KH, D)
    ctx_v = gather_dequant(kv_v_layer, page_tables, D, q.dtype).reshape(B, S, KH, D)
    G = H // KH
    qg = q.reshape(B, T, KH, G, D)
    scores = jnp.einsum(
        "btkgd,bskd->btkgs", qg, ctx_k, preferred_element_type=jnp.float32
    ) / jnp.sqrt(jnp.asarray(D, jnp.float32))
    key_pos = jnp.arange(S)
    mask = (key_pos[None, None, :] <= positions[:, :, None]) & (
        key_pos[None, None, :] < total_lens[:, None, None]
    )  # [B, T, S]
    scores = jnp.where(mask[:, :, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("btkgs,bskd->btkgd", probs.astype(ctx_v.dtype), ctx_v)
    return out.reshape(B, T, H, D)


# Set by an engine around its model calls (engine._ScopedModel): False
# when the engine runs over a multi-device mesh. None = no engine scope
# (bare op calls, profiler): kernels wherever the backend is a TPU.
_MESH_ALLOWS_KERNELS: contextvars.ContextVar = contextvars.ContextVar(
    "attention_mesh_allows_kernels", default=None
)


def mesh_allows_kernels(mesh) -> bool:
    """The mesh half of the gate. No mesh or a one-device mesh: the
    operands live whole on one chip and the Pallas kernels apply. A
    multi-device mesh (tp/ep/pp/sp > 1) shards the KV cache over heads or
    pages and a bare pallas_call has no partitioning rule, so those
    engines take the XLA path — by this rule, not by device count: a
    one-chip engine in a process that sees four devices keeps its kernels.
    (The kernels are not yet wrapped in a shard_map over KV heads.)"""
    return mesh is None or mesh.devices.size == 1


@contextlib.contextmanager
def attention_scope(allows_kernels: bool):
    """Trace-time scope: every attention op called inside resolves its
    implementation for an engine whose mesh `allows_kernels`."""
    tok = _MESH_ALLOWS_KERNELS.set(bool(allows_kernels))
    try:
        yield
    finally:
        _MESH_ALLOWS_KERNELS.reset(tok)


def scope_allows_kernels() -> bool:
    """False inside the scope of an engine whose mesh spans devices; True
    in a one-device engine's scope and outside any (bare op calls)."""
    return _MESH_ALLOWS_KERNELS.get() is not False


def _use_pallas_decode() -> bool:
    mode = os.environ.get("DYNAMO_TPU_PAGED_ATTN", "auto")
    if mode == "pallas":
        return True
    if mode == "xla":
        return False
    if mode != "auto":
        raise ValueError(
            f"DYNAMO_TPU_PAGED_ATTN={mode!r}: expected auto, pallas or xla"
        )
    if not scope_allows_kernels():
        return False
    # a backend that cannot be asked is an error, never "use the reference"
    return jax.default_backend() == "tpu"


def _pallas_eligible(lane_dim: int, quantized: bool = False) -> bool:
    """THE Pallas dispatch gate, shared by every attention op in this
    module. Kernels run when all of these hold:
      * DYNAMO_TPU_PAGED_ATTN allows it (auto = the backend is a TPU and
        the calling engine's mesh is one device, see mesh_allows_kernels);
      * the kernel's lane dimension is 128-aligned (Mosaic DMA). `lane_dim`
        is whatever the kernel's page DMA slices — head_dim for the
        per-head-column prefill/ragged kernels, KH*D for the whole-page
        decode kernels; smaller (tiny/test) models take the bounded XLA
        reference paths;
      * the pool is not quantized. The in-kernel int8/int4 dequant has
        never compiled for a TPU (per-page scales overflow scalar-prefetch
        SMEM at real pool sizes; the int4 unpack shifts i8 vectors, which
        Mosaic cannot legalize — tests/test_tpu_compile.py pins both), so
        quantized pools take the XLA gather+dequant path on every op until
        it does."""
    return lane_dim % 128 == 0 and not quantized and _use_pallas_decode()


def resolved_attention(head_dim: int, kv_heads: int, quantized: bool) -> dict:
    """Which implementation each attention op resolves to for a model of
    these widths under the current scope: what the engine logs at start
    and publishes in stats()."""
    def name(lane_dim):
        return "pallas" if _pallas_eligible(lane_dim, quantized) else "xla"

    return {
        "decode": name(kv_heads * head_dim),
        "prefill": name(head_dim),
        "ragged": name(head_dim),
    }


def paged_attention_decode(
    q: jax.Array,  # [B, H, D]
    kv_k_layer: KVLayer,  # whole pool + layer index (kv_quant.kv_layer)
    kv_v_layer: KVLayer,
    page_tables: jax.Array,  # [B, max_pages]
    seq_lens: jax.Array,  # [B] (including current token)
) -> jax.Array:
    """One-token decode attention over paged KV. Returns [B, H, D].

    Dispatch: on TPU (or DYNAMO_TPU_PAGED_ATTN=pallas) the Pallas flash
    kernel (ops/pallas_paged_attention.py) streams pages HBM→VMEM without
    materializing the gather; elsewhere the XLA reference path below runs.
    """
    B, H, D = q.shape
    page_size, KH = layer_dims(kv_k_layer, D)
    # the decode kernel's page window has lane dim KH*D (whole-page
    # copies), so that is what must be 128-aligned here (int4 packs along
    # the page_size/sublane axis, so the lane dim is unchanged)
    if _pallas_eligible(KH * D, is_quant_kv(kv_k_layer.pool)):
        from .pallas_paged_attention import paged_attention_decode_pallas

        return paged_attention_decode_pallas(
            q, kv_k_layer, kv_v_layer, page_tables, seq_lens
        )
    S = page_tables.shape[1] * page_size
    ctx_k = gather_dequant(kv_k_layer, page_tables, D, q.dtype).reshape(B, S, KH, D)
    ctx_v = gather_dequant(kv_v_layer, page_tables, D, q.dtype).reshape(B, S, KH, D)

    G = H // KH
    qg = q.reshape(B, KH, G, D)
    scores = jnp.einsum(
        "bkgd,bskd->bkgs", qg, ctx_k, preferred_element_type=jnp.float32
    ) / jnp.sqrt(jnp.asarray(D, jnp.float32))
    key_pos = jnp.arange(S)
    mask = key_pos[None, :] < seq_lens[:, None]  # [B, S]
    scores = jnp.where(mask[:, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", probs.astype(ctx_v.dtype), ctx_v)
    return out.reshape(B, H, D)


def _owning_rows(slots: jax.Array, row_starts: jax.Array) -> jax.Array:
    """The owning row of each flat slot: the last row whose start is at or
    before it (starts ascending; padding slots fold into the nearest
    preceding row, where the callers mask them to nothing)."""
    return jnp.clip(
        jnp.sum(slots[:, None] >= row_starts[None, :], axis=1) - 1,
        0, row_starts.shape[0] - 1,
    )


def ragged_attention_reference(
    q: jax.Array,  # [N, H, D] flat packed tokens (rope applied)
    kv_k_layer: KVLayer,  # whole pool + layer index (kv_quant.kv_layer)
    kv_v_layer: KVLayer,
    page_tables: jax.Array,  # [R, max_pages]
    row_starts: jax.Array,  # [R] flat index of row r's token 0 (ascending;
    # padding rows sit at N)
    row_lens: jax.Array,  # [R] real tokens per row (0 for padding rows)
    ctx_lens: jax.Array,  # [R] history length before each row's chunk
) -> jax.Array:
    """XLA reference for the ragged mixed prefill+decode attention: every
    flat token attends to its OWN row's pages (history + chunk, causal).
    Returns [N, H, D]. The CPU/non-aligned fallback of the Pallas ragged
    kernel (ops/pallas_ragged_attention.py) and the fuzz-parity oracle
    (tests/test_ragged_attention.py). Tokens outside every row span
    (alignment/tail padding) return finite garbage — callers only read
    real rows."""
    N, H, D = q.shape
    R, P = page_tables.shape
    page_size, KH = layer_dims(kv_k_layer, D)
    S = P * page_size
    idx = jnp.arange(N)
    row_ids = _owning_rows(idx, row_starts)
    local = idx - row_starts[row_ids]
    positions = ctx_lens[row_ids] + local
    totals = ctx_lens[row_ids] + row_lens[row_ids]
    ctx_k = gather_dequant(
        kv_k_layer, page_tables, D, q.dtype
    ).reshape(R, S, KH, D)[row_ids]  # [N, S, KH, D]
    ctx_v = gather_dequant(
        kv_v_layer, page_tables, D, q.dtype
    ).reshape(R, S, KH, D)[row_ids]
    G = H // KH
    qg = q.reshape(N, KH, G, D)
    scores = jnp.einsum(
        "nkgd,nskd->nkgs", qg, ctx_k, preferred_element_type=jnp.float32
    ) / jnp.sqrt(jnp.asarray(D, jnp.float32))
    key_pos = jnp.arange(S)
    mask = (
        (key_pos[None, :] <= positions[:, None])
        & (key_pos[None, :] < totals[:, None])
        & (local < row_lens[row_ids])[:, None]
    )  # [N, S]
    scores = jnp.where(mask[:, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("nkgs,nskd->nkgd", probs.astype(ctx_v.dtype), ctx_v)
    return out.reshape(N, H, D)


def ragged_tiles(tokens: int, rows: int, tile: int, long_rows=None) -> int:
    """Q tiles of the ragged kernel's grid for a pack of `tokens` compact
    slots over `rows` rows of which at most `long_rows` (every row when
    None) hold more than one token: each such row loses at most tile - 1
    slots to its alignment, so any pack of the bucket fits. Static: the
    engine counts its launched tiles by this, tests/test_tpu_compile.py
    the grid."""
    long_rows = rows if long_rows is None else min(long_rows, rows)
    return -(-(tokens + (tile - 1) * long_rows) // tile)


def rows_at(x: jax.Array, idx: jax.Array) -> jax.Array:
    """x[idx] along axis 0, a zero row where idx is out of range."""
    return jnp.take(x, idx, axis=0, mode="fill", fill_value=0)


def _tiled_layout(tile: int, tiles: int, slots: int, row_starts, row_lens):
    """The flat axis the ragged kernel wants, beside the compact one the
    step is packed on (`slots` wide): every row of `row_lens` > 0 starts on
    a multiple of `tile`, rows in the same order, rows of no length where
    the next row starts (they own no tile, and the kernel's tail tiles
    belong to the last row, of no length unless the pack is full).
    `row_lens` are the KERNEL's: the caller has zeroed the rows it serves
    elsewhere. Returns (starts [R] on the tiled axis, to_tiled [slots]:
    each compact slot's tiled slot, tiles * tile where it has none;
    from_tiled [tiles * tile]: each tiled slot's compact slot, `slots`
    where it holds no token)."""
    N = tiles * tile
    spans = -(-row_lens // tile) * tile
    starts = (jnp.cumsum(spans) - spans).astype(jnp.int32)
    slot = jnp.arange(slots, dtype=jnp.int32)
    row_ids = _owning_rows(slot, row_starts)
    local = slot - row_starts[row_ids]
    to_tiled = jnp.where(
        (local >= 0) & (local < row_lens[row_ids]), starts[row_ids] + local, N
    )
    from_tiled = jnp.full((N,), slots, jnp.int32).at[to_tiled].set(
        slot, mode="drop"
    )
    return starts, to_tiled, from_tiled


def ragged_attention(
    q: jax.Array,  # [M, H, D] on the step's compact axis
    kv_k_layer: KVLayer,  # whole pool + layer index (kv_quant.kv_layer)
    kv_v_layer: KVLayer,
    page_tables: jax.Array,  # [R, max_pages]
    row_starts: jax.Array,  # [R] ascending; padding rows sit at M
    row_lens: jax.Array,  # [R]
    ctx_lens: jax.Array,  # [R]
    long_rows: Optional[int] = None,
) -> jax.Array:
    """Ragged mixed prefill+decode attention over paged KV: one call for a
    flat buffer packing prefill chunks (T>1) and decode slots (T=1), rows
    back to back. Every row's K and V are in its pages already. Returns
    [M, H, D]; slots of no row return finite garbage or zeros.

    Dispatch, by `ragged_tile` (the module's one gate): where it is 1 the
    XLA reference gathers the (engine-bounded) tables and takes the flat
    axis as it is. Where the Pallas kernels run, the call is two kernels,
    chosen by what the operands say, a row's length:

      * rows of ONE token (decode lanes, spec verify rows, a prompt's
        one-token chunk) are lanes of the paged decode kernel: q gathered
        at `row_starts`, the pack's tables, `seq_lens = ctx_lens + 1` and
        0 for every other row (an empty lane copies and multiplies
        nothing);
      * rows of more tokens alone own q tiles of the ragged kernel
        (ops/pallas_ragged_attention.py), whose tiles may not straddle
        rows: q is laid out tile-aligned for the call (`_tiled_layout`) on
        an axis of `ragged_tiles(M, R, tile, long_rows)` tiles, and a tile
        that holds no row returns before its first copy.

    `long_rows`: how many rows of more than one token a pack can hold at
    most (the engine's `max_prefill_batch`; every row when None). It is
    static and sizes the tiled axis: a pack that breaks it loses rows.
    Both results come back to the compact axis in one gather."""
    tile = ragged_tile(q.dtype, q.shape[-1], is_quant_kv(kv_k_layer.pool))
    if tile == 1:
        return ragged_attention_reference(
            q, kv_k_layer, kv_v_layer, page_tables, row_starts, row_lens,
            ctx_lens,
        )
    return ragged_attention_kernels(
        q, kv_k_layer, kv_v_layer, page_tables, row_starts, row_lens,
        ctx_lens, tile=tile, long_rows=long_rows,
    )


@functools.partial(jax.jit, static_argnames=("tile", "long_rows"))
def ragged_attention_kernels(
    q, kv_k_layer, kv_v_layer, page_tables, row_starts, row_lens, ctx_lens,
    *, tile: int, long_rows: Optional[int],
):
    """`ragged_attention` where the gate has resolved to the Pallas kernels
    (see there). A jit of its own, so that a step's layers, which call it
    with the same shapes, are traced and lowered once a program: the
    layout's index arithmetic and the two kernels' Mosaic bodies are no
    small part of a start (PERF.md, PR 45). Inlined by XLA, where the
    layers' copies of the layout fold into one."""
    from .pallas_paged_attention import paged_attention_decode_pallas
    from .pallas_ragged_attention import ragged_paged_attention_pallas

    M, R = q.shape[0], row_lens.shape[0]
    one = row_lens == 1
    tiled_lens = jnp.where(one, 0, row_lens)
    tiles = ragged_tiles(M, R, tile, long_rows)
    starts, to_tiled, from_tiled = _tiled_layout(
        tile, tiles, M, row_starts, tiled_lens
    )
    N = tiles * tile
    lane_slots = jnp.where(one, row_starts, M).astype(jnp.int32)
    # one gather out of the compact axis and one back into it
    q_both = rows_at(q, jnp.concatenate([from_tiled, lane_slots]))
    tiled = ragged_paged_attention_pallas(
        q_both[:N], kv_k_layer, kv_v_layer, page_tables, starts, tiled_lens,
        ctx_lens,
    )
    lanes = paged_attention_decode_pallas(
        q_both[N:], kv_k_layer, kv_v_layer, page_tables,
        jnp.where(one, ctx_lens + 1, 0),
    )
    back = to_tiled.at[lane_slots].set(
        N + jnp.arange(R, dtype=jnp.int32), mode="drop"
    )
    return rows_at(jnp.concatenate([tiled, lanes]), back)


def ragged_tile(dtype, head_dim: int, quantized: bool = False) -> int:
    """The gate of `ragged_attention`, decided at trace time: the Pallas
    ragged kernel's q tile (what it lays every row of more than one token
    out to: tiles may not straddle rows) where the kernels will run, else
    1 (the XLA reference takes rows back to back). The engine reckons its
    `mixed_attn_*` counts in it."""
    if _pallas_eligible(head_dim, quantized):
        from .pallas_ragged_attention import ragged_tile_q

        return ragged_tile_q(dtype)
    return 1
