"""Pallas TPU kernel: ragged paged-attention over a mixed step's prompt rows.

The engine's mixed step (engine/engine.py:_dispatch_mixed) packs the
StepPlanner's chosen prefill chunks (T > 1) and the active decode lanes
(T = 1) into ONE flat token buffer, and one call a layer,
ops/paged_attention.py:ragged_attention, runs attention for every row of it
(the "Ragged Paged Attention" shape, PAPERS.md). Behind that call's gate
this kernel owns the rows of MORE than one token: its grid is their q
tiles. A one-token row (a decode lane, a spec verify row, a prompt's
one-token chunk) is a lane of the paged decode kernel
(ops/pallas_paged_attention.py), which does such a row in one grid step
over all KV heads with the next lane's pages already in flight, where a q
tile here costs a grid step a KV head, each with its own exposed first
copy. The caller passes such rows with a length of 0: they own no tile.
The kernel itself takes any row length, 1 included (the tests run it so).

Layouts (match ops/paged_attention.py and engine/kv_cache.py):
    q:           [N, H, D]  flat packed tokens (rope applied, chunk KV
                            already written into pages by the model)
    kv_{k,v}:    [L, num_pages, page_size, KH*D]  (the WHOLE lane-dense pool,
                 as it lies in HBM, + the layer index as scalar prefetch)
    page_tables: [R, max_pages] int32 (per-row logical -> physical)
    row_starts:  [R] int32 — flat index of row r's first token, ascending,
                 ALIGNED to the q tile (ragged_tile_q); a row of no length
                 sits where the next row starts, or at the end of the last
                 row's tiles (it owns no tile)
    row_lens:    [R] int32 — real tokens in row r (0 for padding rows and
                 for rows served elsewhere)
    ctx_lens:    [R] int32 — history length before the row's chunk (the
                 absolute position of its token 0)

Design notes:
  * grid = (num_tiles, KH): the flat buffer is cut into TQ-token q tiles
    and a scalar-prefetched `tile_rows` map (built by the wrapper from
    row_starts) names each tile's owning row — tiles never straddle rows
    because the packer aligns row starts to TQ. Per (tile, kv-head) step
    the kernel streams ONLY that row's real context pages (history +
    chunk, causally bounded per tile) through a double-buffered VMEM
    window and flash-accumulates, exactly like the prefill kernel.
  * the tiled axis is static (paged_attention.ragged_tiles: the token
    bucket and TQ - 1 slots for each row of a prefill batch), so its tail
    holds tiles of no row. The map gives them to the last row whose start
    they follow, and such a tile (its first in-row offset is not under the
    row's length, both in SMEM) returns before its first copy: no DMA, no
    multiply, and an out block that is never written and never gathered.
    The grid's cost follows the prompt rows.
  * per-head DMA: each step fetches only kv-head k0's D-wide column slice
    of a page, so total HBM bytes equal one pass over the real context.
  * q tiles are pre-arranged [num_tiles, KH, TQ, G*D] by the wrapper; the
    G query heads of the group are static column slices (no Mosaic
    reshapes of minor dims).
  * masking: a q row is real iff its in-row offset < row_len; keys are
    valid iff key_pos <= q_pos and key_pos < ctx + row_len. The padding
    behind a row's last token in its last tile comes out as finite
    garbage, a tile of no row as whatever its out block held: the caller
    reads real slots only.
  * REQUIRES head_dim % 128 == 0 (the per-head DMA slices the flattened
    KH*D lane dim in head_dim-wide columns) — the dispatcher
    (ops/paged_attention.py:_pallas_eligible) falls back to
    ragged_attention_reference otherwise, and on CPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def ragged_tile_q(dtype) -> int:
    """Q-tile height (and the row-start alignment the packer must honor):
    the Mosaic second-minor register tile — 16 for bf16, 8 for f32."""
    return 16 if jnp.dtype(dtype).itemsize < 4 else 8


def _ragged_kernel(
    # positional refs — scalar prefetch first: layer index [1],
    # tile_rows [num_tiles], row_starts [R], row_lens [R], ctx_lens [R],
    # page_tables [R, max_pages] (all int32 SMEM) and, under kv_bits > 0,
    # this layer's per-page-per-head K and V scales [num_pages, KH] f32
    # riding the SAME scalar-prefetch channel beside the page tables; then
    # q [1, 1, TQ, G*D] VMEM, kv_k/kv_v [L, num_pages, rows, KH*D] ANY/HBM
    # (the whole pool; rows = page_size, or page_size//2 int4-packed along
    # the sublane axis), the output block, and the double-buffered VMEM
    # window + DMA semaphores.
    *refs,
    page_size: int,
    chunk_pages: int,
    max_pages: int,
    group: int,
    head_dim: int,
    tile_q: int,
    kv_bits: int = 0,
):
    if kv_bits:
        (li_ref, tr_ref, rs_ref, rl_ref, ctx_ref, pt_ref, ks_ref, vs_ref,
         q_ref, kv_k_hbm, kv_v_hbm, out_ref, k_buf, v_buf, k_sem,
         v_sem) = refs
    else:
        (li_ref, tr_ref, rs_ref, rl_ref, ctx_ref, pt_ref,
         q_ref, kv_k_hbm, kv_v_hbm, out_ref, k_buf, v_buf, k_sem,
         v_sem) = refs
        ks_ref = vs_ref = None
    t = pl.program_id(0)
    k0 = pl.program_id(1)
    g, d, tq = group, head_dim, tile_q
    chunk = chunk_pages * page_size
    li = li_ref[0]
    num_phys = kv_k_hbm.shape[1]
    # rows each page occupies in HBM/VMEM (int4 packs 2 tokens per byte
    # along this axis; positions unpack back in order, so the causal
    # key_pos math below is untouched)
    page_rows = kv_k_hbm.shape[2]

    r = tr_ref[t]
    ctx = ctx_ref[r]
    row_len = rl_ref[r]
    local0 = t * tq - rs_ref[r]  # this tile's first in-row offset
    total_len = ctx + row_len
    # causal limit for this tile: its last row is position ctx+local0+tq-1
    limit = jnp.minimum(total_len, ctx + local0 + tq)
    n_chunks = pl.cdiv(jnp.maximum(limit, 1), chunk)

    def chunk_copies(ci, slot, wait: bool):
        """Start (or wait for) the K and V copies of chunk ci's pages into
        buffer `slot`: a loop over the pages, not its unrolling, because a
        start lowers this body for every program that holds the kernel
        (eight pages a chunk, three call sites: PERF.md, PR 45)."""
        def page(p, carry):
            lp = jnp.minimum(ci * chunk_pages + p, max_pages - 1)
            phys = jnp.minimum(pt_ref[r, lp], num_phys - 1)
            rows = pl.ds(pl.multiple_of(p * page_rows, page_rows), page_rows)
            for hbm, buf, sem in (
                (kv_k_hbm, k_buf, k_sem), (kv_v_hbm, v_buf, v_sem)
            ):
                copy = pltpu.make_async_copy(
                    hbm.at[li, phys, :, pl.ds(k0 * d, d)],
                    buf.at[slot, rows],
                    sem.at[slot, p],
                )
                copy.wait() if wait else copy.start()
            return carry

        jax.lax.fori_loop(0, chunk_pages, page, 0)

    def start_chunk(ci, slot):
        chunk_copies(ci, slot, wait=False)

    def wait_chunk(ci, slot):
        chunk_copies(ci, slot, wait=True)

    def dequant_window(ci, slot, compute_dtype):
        """Quantized window -> [chunk, D] full-precision K and V: per page,
        unpack (int4) and multiply by that page's per-head scale read from
        the scalar-prefetched scales — the in-kernel dequant the DMA
        overlap pays for (RTP-LLM shape, PAPERS.md)."""
        from ..models.quant import unpack_int4

        k_segs, v_segs = [], []
        for p in range(chunk_pages):
            lp = jnp.minimum(ci * chunk_pages + p, max_pages - 1)
            phys = jnp.minimum(pt_ref[r, lp], num_phys - 1)
            kseg = k_buf[slot, pl.ds(p * page_rows, page_rows)]  # int8 [rows, D]
            vseg = v_buf[slot, pl.ds(p * page_rows, page_rows)]
            if kv_bits == 4:
                kseg = unpack_int4(kseg, axis=0)  # [page_size, D]
                vseg = unpack_int4(vseg, axis=0)
            ks = ks_ref[phys, k0]
            vs = vs_ref[phys, k0]
            k_segs.append((kseg.astype(jnp.float32) * ks).astype(compute_dtype))
            v_segs.append((vseg.astype(jnp.float32) * vs).astype(compute_dtype))
        return (
            jnp.concatenate(k_segs, axis=0),
            jnp.concatenate(v_segs, axis=0),
        )

    # a tile that holds no real q row (the tail of the tiled axis, which
    # belongs to the last row: local0 >= its length) returns before its
    # first copy: no DMA, no multiply, and an out block nobody gathers
    @pl.when(local0 < row_len)
    def _():
        start_chunk(0, 0)

        q_tile = q_ref[0, 0]  # [TQ, G*D], pre-scaled by 1/sqrt(D)
        local = local0 + jax.lax.broadcasted_iota(jnp.int32, (tq, 1), 0)
        q_pos = ctx + local
        q_real = local < row_len  # [TQ, 1]

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
            if kv_bits:
                k, v = dequant_window(ci, slot, q_ref.dtype)  # [C, D]
            else:
                k = k_buf[slot]  # [C, D]
                v = v_buf[slot]

            key_pos = ci * chunk + jax.lax.broadcasted_iota(jnp.int32, (1, chunk), 1)
            valid = q_real & (key_pos <= q_pos) & (key_pos < total_len)  # [TQ, C]

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
def ragged_paged_attention_pallas(
    q: jax.Array,  # [N, H, D] flat packed tokens (rope applied)
    kv_k_layer,  # kv_quant.KVLayer: whole pool + layer index
    kv_v_layer,
    page_tables: jax.Array,  # [R, max_pages] int32
    row_starts: jax.Array,  # [R] int32, ascending, TQ-aligned
    row_lens: jax.Array,  # [R] int32
    ctx_lens: jax.Array,  # [R] int32
    *,
    interpret: bool = False,
) -> jax.Array:
    """Ragged flash attention over paged KV; returns [N, H, D] (q.dtype).
    Slots outside every [row_start, row_start+row_len) span are undefined
    (finite garbage in a row's last tile, unwritten in a tile of no row) —
    the caller only reads real rows. The pools may be QuantKV
    stores (ops/kv_quant.py): the int8/int4 pages
    DMA at their packed width and dequantize inside the VMEM window, with
    the per-page-per-head scales scalar-prefetched beside the page
    tables."""
    from .kv_quant import kernel_operands

    N, H, D = q.shape
    kv_k_pool, kv_v_pool, li, KH, rows, page_size, kv_bits, scale_prefetch = (
        kernel_operands(kv_k_layer, kv_v_layer, D)
    )
    G = H // KH
    max_pages = page_tables.shape[1]
    tile_q = ragged_tile_q(q.dtype)
    assert N % tile_q == 0, (
        f"flat buffer {N} must be a multiple of the q tile {tile_q} "
        "(the mixed packer pads to ragged_tile_q)"
    )
    num_tiles = N // tile_q
    # KV streamed in ~512-position chunks: full 128-lane score tiles, and
    # 2 slots x (K+V) x [C, D] comfortably inside VMEM
    chunk_pages = max(1, 512 // page_size)
    chunk_pages = min(chunk_pages, max_pages)

    # each tile's owning row: rows are TQ-aligned and packed ascending, so
    # the owner of tile t is the last row whose start <= t*TQ (tail-padding
    # tiles fold into the last real row and mask to nothing)
    t0s = jnp.arange(num_tiles, dtype=jnp.int32) * tile_q
    tile_rows = jnp.maximum(
        jnp.sum(
            t0s[:, None] >= row_starts.astype(jnp.int32)[None, :], axis=1
        ).astype(jnp.int32)
        - 1,
        0,
    )

    scale = 1.0 / (D**0.5)
    # [N, H, D] -> [num_tiles, KH, TQ, G*D]: group g of kv-head k0 in
    # column block g (same pre-arrangement as the prefill kernel)
    q_g = (
        (q * scale)
        .reshape(num_tiles, tile_q, KH, G, D)
        .transpose(0, 2, 1, 3, 4)
        .reshape(num_tiles, KH, tile_q, G * D)
    )
    # the pool goes in as it lies (lane-dense: Mosaic cannot merge minor
    # dims in-register, and an XLA reshape of it is a pool-sized copy).
    # Quantized stores DMA their PACKED q bytes (int4: half the sublane
    # rows); the f32 scales join the scalar prefetch operands right after
    # the page tables (kernel_operands is the one spelling of this
    # contract across all four kernels).
    prefetch = [
        li,
        tile_rows,
        row_starts.astype(jnp.int32),
        row_lens.astype(jnp.int32),
        ctx_lens.astype(jnp.int32),
        page_tables.astype(jnp.int32),
        *scale_prefetch,
    ]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(num_tiles, KH),
        in_specs=[
            pl.BlockSpec((1, 1, tile_q, G * D), lambda t, k0, *_: (t, k0, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, tile_q, G * D), lambda t, k0, *_: (t, k0, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((2, chunk_pages * rows, D), kv_k_pool.dtype),
            pltpu.VMEM((2, chunk_pages * rows, D), kv_v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, chunk_pages)),
            pltpu.SemaphoreType.DMA((2, chunk_pages)),
        ],
    )
    kernel = functools.partial(
        _ragged_kernel,
        page_size=page_size,
        chunk_pages=chunk_pages,
        max_pages=max_pages,
        group=G,
        head_dim=D,
        tile_q=tile_q,
        kv_bits=kv_bits,
    )
    cost = pl.CostEstimate(
        flops=4 * N * H * D * max_pages * page_size // 2,
        bytes_accessed=2 * num_tiles * max_pages * page_size * KH * D * 2,
        transcendentals=N * H * max_pages * page_size // 2,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((num_tiles, KH, tile_q, G * D), q.dtype),
        cost_estimate=cost,
        interpret=interpret,
    )(
        *prefetch,
        q_g,
        kv_k_pool,
        kv_v_pool,
    )
    # [num_tiles, KH, TQ, G*D] -> [N, H, D]
    return (
        out.reshape(num_tiles, KH, tile_q, G, D)
        .transpose(0, 2, 1, 3, 4)
        .reshape(N, H, D)
    )
