"""Pallas TPU kernel: batched chunked-prefill flash attention over paged KV.

The prefill-side counterpart of ops/pallas_paged_attention.py (decode).
Role of the reference engines' prefill attention kernels (vLLM flash-attn
over paged KV), done the TPU way: each sequence's chunk KV has already been
scattered into HBM pages by the model; this kernel streams ONLY the pages
that hold real context (history + the chunk itself) through a
double-buffered VMEM window and flash-accumulates — instead of the XLA
fallback's materialized full max-context gather, which reads
`max_pages * page_size` positions per layer regardless of actual context
(the round-1 TTFT killer: 493 ms at isl 128 came almost entirely from that
gather traffic).

Batching: the engine packs prefill chunks from SEVERAL sequences into one
dispatch (grid dim 0), so concurrent short prompts prefill together instead
of serializing one chunk per engine-loop iteration.

Layouts (match ops/paged_attention.py and engine/kv_cache.py):
    q:           [B, T, H, D]     (chunks, rope applied; T = bucket)
    kv_{k,v}:    [L, num_pages, page_size, KH*D]  (the WHOLE lane-dense pool,
                 as it lies in HBM, + the layer index as scalar prefetch)
    page_tables: [B, max_pages] int32 (per-seq logical -> physical)
    starts:      [B] int32 — absolute position of each seq's q row 0
    total_lens:  [B] int32 — valid context = start + real chunk len

Design notes:
  * grid = (B, KH, T // TQ): one kv-head per middle step so each DMA
    fetches only that head's D-wide column slice of a page — total HBM
    bytes equal one pass over the real context, never duplicated across
    heads.
  * q is pre-arranged [B, KH, T, G*D] by the wrapper (XLA transpose);
    inside the kernel the G query heads of the group are static column
    slices, so every matmul is a clean 2D [TQ, D] x [D, C] MXU op (no
    Mosaic reshapes of minor dims — unsupported shape casts).
  * causal masking by absolute position: tile t's rows are positions
    start + t*TQ + i, keys are ci*C + j; a tile only loops over chunks up
    to its own causal limit, so early tiles do less work.
  * tail chunks may DMA a stale/garbage page (clamped ids); additive NEG
    masking keeps them out of the softmax.
  * REQUIRES head_dim % 128 == 0: the per-head DMA slices the flattened
    KH*D minor (lane) dim in head_dim-wide columns, and Mosaic rejects
    lane slices not aligned to the 128-lane tiling. The dispatcher
    (ops/paged_attention.py) falls back to the bounded XLA path for
    smaller head dims (tiny/test models); flagship llama-family configs
    all use head_dim 128.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def _prefill_kernel(
    # scalar prefetch
    li_ref,  # [1] int32 (SMEM): the layer whose pages this call reads
    pt_ref,  # [B, max_pages] int32 (SMEM)
    start_ref,  # [B] int32 (SMEM)
    total_ref,  # [B] int32 (SMEM)
    # inputs
    q_ref,  # [1, 1, TQ, G*D] VMEM block (one seq, one kv-head's query group)
    kv_k_hbm,  # [L, num_pages, page_size, KH*D] (ANY/HBM; the whole pool)
    kv_v_hbm,
    # outputs
    out_ref,  # [1, 1, TQ, G*D] VMEM block
    # scratch
    k_buf,  # [2, C, D] VMEM — this head's column slice of the chunk pages
    v_buf,
    k_sem,  # DMA sems [2, chunk_pages]
    v_sem,
    *,
    page_size: int,
    chunk_pages: int,
    max_pages: int,
    group: int,
    head_dim: int,
    tile_q: int,
):
    b = pl.program_id(0)
    k0 = pl.program_id(1)
    t = pl.program_id(2)
    g, d, tq = group, head_dim, tile_q
    chunk = chunk_pages * page_size
    li = li_ref[0]
    num_phys = kv_k_hbm.shape[1]

    start = start_ref[b]
    total_len = total_ref[b]
    # causal limit for this q tile: its last row is position start+(t+1)*TQ-1
    limit = jnp.minimum(total_len, start + (t + 1) * tq)
    n_chunks = pl.cdiv(jnp.maximum(limit, 1), chunk)

    def start_chunk(ci, slot):
        for p in range(chunk_pages):
            lp = jnp.minimum(ci * chunk_pages + p, max_pages - 1)
            phys = jnp.minimum(pt_ref[b, lp], num_phys - 1)
            pltpu.make_async_copy(
                kv_k_hbm.at[li, phys, :, pl.ds(k0 * d, d)],
                k_buf.at[slot, pl.ds(p * page_size, page_size)],
                k_sem.at[slot, p],
            ).start()
            pltpu.make_async_copy(
                kv_v_hbm.at[li, phys, :, pl.ds(k0 * d, d)],
                v_buf.at[slot, pl.ds(p * page_size, page_size)],
                v_sem.at[slot, p],
            ).start()

    def wait_chunk(ci, slot):
        for p in range(chunk_pages):
            lp = jnp.minimum(ci * chunk_pages + p, max_pages - 1)
            phys = jnp.minimum(pt_ref[b, lp], num_phys - 1)
            pltpu.make_async_copy(
                kv_k_hbm.at[li, phys, :, pl.ds(k0 * d, d)],
                k_buf.at[slot, pl.ds(p * page_size, page_size)],
                k_sem.at[slot, p],
            ).wait()
            pltpu.make_async_copy(
                kv_v_hbm.at[li, phys, :, pl.ds(k0 * d, d)],
                v_buf.at[slot, pl.ds(p * page_size, page_size)],
                v_sem.at[slot, p],
            ).wait()

    start_chunk(0, 0)

    q_tile = q_ref[0, 0]  # [TQ, G*D], pre-scaled by 1/sqrt(D)
    q_pos = start + t * tq + jax.lax.broadcasted_iota(jnp.int32, (tq, 1), 0)

    m0 = tuple(jnp.full((tq, 1), NEG, jnp.float32) for _ in range(g))
    l0 = tuple(jnp.zeros((tq, 1), jnp.float32) for _ in range(g))
    acc0 = tuple(jnp.zeros((tq, d), jnp.float32) for _ in range(g))

    def body(ci, carry):
        m, l, acc = carry
        slot = jax.lax.rem(ci, 2)

        @pl.when(ci + 1 < n_chunks)
        def _():
            start_chunk(ci + 1, jax.lax.rem(ci + 1, 2))

        wait_chunk(ci, slot)
        k = k_buf[slot]  # [C, D]
        v = v_buf[slot]

        key_pos = ci * chunk + jax.lax.broadcasted_iota(jnp.int32, (1, chunk), 1)
        valid = (key_pos <= q_pos) & (key_pos < total_len)  # [TQ, C]

        m_n, l_n, acc_n = [], [], []
        for gi in range(g):
            qg = q_tile[:, gi * d : (gi + 1) * d]  # [TQ, D] static slice
            s = jax.lax.dot_general(
                qg.astype(k.dtype),
                k,
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [TQ, C]
            s = jnp.where(valid, s, NEG)
            mg = jnp.maximum(m[gi], jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m[gi] - mg)
            p = jnp.exp(s - mg)
            lg = l[gi] * alpha + jnp.sum(p, axis=-1, keepdims=True)
            pv = jax.lax.dot_general(
                p.astype(v.dtype),
                v,
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [TQ, D]
            m_n.append(mg)
            l_n.append(lg)
            acc_n.append(acc[gi] * alpha + pv)
        return tuple(m_n), tuple(l_n), tuple(acc_n)

    m, l, acc = jax.lax.fori_loop(0, n_chunks, body, (m0, l0, acc0))
    for gi in range(g):
        out = acc[gi] / jnp.maximum(l[gi], 1e-30)
        out_ref[0, 0, :, gi * d : (gi + 1) * d] = out.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_prefill_attention_pallas_batched(
    q: jax.Array,  # [B, T, H, D] (rope applied)
    kv_k_layer,  # kv_quant.KVLayer: whole pool + layer index
    kv_v_layer,
    page_tables: jax.Array,  # [B, max_pages] int32
    starts: jax.Array,  # [B] int32
    total_lens: jax.Array,  # [B] int32
    *,
    interpret: bool = False,
) -> jax.Array:
    """Batched flash chunked-prefill over paged KV; returns [B, T, H, D].
    fp pools only: the dispatch gate sends quantized pools to XLA."""
    from .kv_quant import kernel_operands

    B, T, H, D = q.shape
    kv_k_pool, kv_v_pool, li, KH, _, page_size, kv_bits, _ = kernel_operands(
        kv_k_layer, kv_v_layer, D
    )
    assert kv_bits == 0, "the prefill kernel has no in-kernel dequant"
    G = H // KH
    max_pages = page_tables.shape[1]
    tile_q = min(256, T)
    assert T % tile_q == 0, f"chunk bucket {T} must be a multiple of {tile_q}"
    num_tiles = T // tile_q
    # KV streamed in ~512-position chunks: full 128-lane score tiles, and
    # 2 slots x (K+V) x [C, D] comfortably inside VMEM
    chunk_pages = max(1, 512 // page_size)
    chunk_pages = min(chunk_pages, max_pages)

    scale = 1.0 / (D**0.5)
    # [B, T, H, D] -> [B, KH, T, G*D]: group g of kv-head k0 in column block g
    q_g = (
        (q * scale)
        .reshape(B, T, KH, G, D)
        .transpose(0, 2, 1, 3, 4)
        .reshape(B, KH, T, G * D)
    )

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B, KH, num_tiles),
        in_specs=[
            pl.BlockSpec((1, 1, tile_q, G * D), lambda b, k0, t, *_: (b, k0, t, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, tile_q, G * D), lambda b, k0, t, *_: (b, k0, t, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((2, chunk_pages * page_size, D), kv_k_pool.dtype),
            pltpu.VMEM((2, chunk_pages * page_size, D), kv_v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, chunk_pages)),
            pltpu.SemaphoreType.DMA((2, chunk_pages)),
        ],
    )
    kernel = functools.partial(
        _prefill_kernel,
        page_size=page_size,
        chunk_pages=chunk_pages,
        max_pages=max_pages,
        group=G,
        head_dim=D,
        tile_q=tile_q,
    )
    cost = pl.CostEstimate(
        flops=4 * B * T * H * D * max_pages * page_size // 2,
        bytes_accessed=2 * B * max_pages * page_size * KH * D * 2,
        transcendentals=B * T * H * max_pages * page_size // 2,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KH, T, G * D), q.dtype),
        cost_estimate=cost,
        interpret=interpret,
    )(
        li,
        page_tables.astype(jnp.int32),
        starts.astype(jnp.int32),
        total_lens.astype(jnp.int32),
        q_g,
        kv_k_pool,
        kv_v_pool,
    )
    # [B, KH, T, G*D] -> [B, T, H, D]
    return out.reshape(B, KH, T, G, D).transpose(0, 2, 1, 3, 4).reshape(B, T, H, D)


def paged_prefill_attention_pallas(
    q: jax.Array,  # [T, H, D]
    kv_k_layer,  # kv_quant.KVLayer
    kv_v_layer,
    page_table: jax.Array,  # [max_pages]
    start: jax.Array,  # scalar
    total_len: jax.Array,  # scalar
    *,
    interpret: bool = False,
) -> jax.Array:
    """Single-sequence wrapper over the batched kernel; returns [T, H, D]."""
    out = paged_prefill_attention_pallas_batched(
        q[None],
        kv_k_layer,
        kv_v_layer,
        page_table[None],
        jnp.asarray(start, jnp.int32)[None],
        jnp.asarray(total_len, jnp.int32)[None],
        interpret=interpret,
    )
    return out[0]
