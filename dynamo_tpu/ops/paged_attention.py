"""Paged attention ops: XLA reference implementations.

The role of the reference's engine attention kernels + block_copy.cu, done
the TPU way: static-shaped gathers + einsums that XLA fuses well on the MXU,
with a Pallas decode kernel (ops/pallas_paged_attention.py) swapped in on
TPU for the HBM-bound gather.

Layouts:
  kv_k_layer / kv_v_layer: ops/kv_quant.KVLayer — the WHOLE lane-dense
      pool [L, num_pages, page_size, kv_heads*head_dim] (or QuantKV) plus
      the layer index; never a slice of the pool
  page_table: logical page index -> physical page id
"""

from __future__ import annotations

import contextlib
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
    # owning row per token: the last row whose start <= idx (padding
    # tokens fold into the nearest preceding row and mask to nothing)
    row_ids = jnp.clip(
        jnp.sum(idx[:, None] >= row_starts[None, :], axis=1) - 1, 0, R - 1
    )
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


def ragged_attention(
    q: jax.Array,  # [N, H, D]
    kv_k_layer: KVLayer,  # whole pool + layer index (kv_quant.kv_layer)
    kv_v_layer: KVLayer,
    page_tables: jax.Array,  # [R, max_pages]
    row_starts: jax.Array,  # [R]
    row_lens: jax.Array,  # [R]
    ctx_lens: jax.Array,  # [R]
) -> jax.Array:
    """Ragged mixed prefill+decode attention over paged KV: one call for a
    flat buffer packing prefill chunks (T>1) and decode slots (T=1).
    Returns [N, H, D].

    Dispatch: on TPU the Pallas ragged kernel streams only each row's real
    context pages; elsewhere the XLA reference path gathers the (engine-
    bounded) tables. The Pallas path additionally requires row starts
    aligned to `ragged_tile(...)` below — the model's ragged forward lays
    q out so, between the q projection and this call alone, exactly when
    this gate says the kernel will run (models/llama.py:ragged_forward)."""
    if _pallas_eligible(q.shape[-1], is_quant_kv(kv_k_layer.pool)):
        from .pallas_ragged_attention import ragged_paged_attention_pallas

        return ragged_paged_attention_pallas(
            q, kv_k_layer, kv_v_layer, page_tables,
            row_starts, row_lens, ctx_lens,
        )
    return ragged_attention_reference(
        q, kv_k_layer, kv_v_layer, page_tables, row_starts, row_lens, ctx_lens
    )


def ragged_tile(dtype, head_dim: int, quantized: bool = False) -> int:
    """What `ragged_attention` needs every row of its flat axis to start
    on a multiple of, decided at trace time by the same gate: the Pallas
    ragged kernel's q tile (its tiles may not straddle rows) where the
    kernel will run, else 1 (the XLA reference takes rows back to back)."""
    if _pallas_eligible(head_dim, quantized):
        from .pallas_ragged_attention import ragged_tile_q

        return ragged_tile_q(dtype)
    return 1
