"""Pallas TPU kernel: ragged paged-attention for single-token decode.

Role of the reference's paged-attention CUDA kernels (inside vLLM) and of
`block_copy.cu` (lib/llm/src/kernels/block_copy.cu:41) — done the TPU way:
the KV cache stays in HBM, each grid step streams ONE lane's pages through
a double-buffered VMEM window with async DMA, and a flash-style running
softmax accumulates the output. This avoids the XLA fallback's materialized
[B, S, KH, D] gather (which costs an extra HBM round-trip for the whole
context).

Layouts (match ops/paged_attention.py and engine/kv_cache.py):
    q:           [B, H, D]
    kv_{k,v}:    [L, num_pages, page_size, KH*D]  (the WHOLE lane-dense pool,
                 as it lies in HBM, + the layer index as scalar prefetch)
    page_tables: [B, max_pages] int32  (logical -> physical page)
    seq_lens:    [B] int32             (valid positions incl. current token)

Design notes:
  * grid = (B,), SEQUENTIAL ("arbitrary"): the window, its semaphores and
    the slot counter live across grid steps. The layer index and
    page_tables/seq_lens ride scalar-prefetch (SMEM) so DMA source indices
    (`pool[li, page]`) are known ahead of the body. No per-layer slice or
    reshape of the pool exists outside the kernel: on the TPU either is a
    pool-sized copy.
  * the page stream follows the lane's length: page `lp` of lane `b` is
    copied only where `lp * page_size < seq_lens[b]`, and a copy is waited
    for under the same predicate, so every semaphore waited on was
    signalled. An empty lane (`seq_lens[b]` 0) copies nothing, multiplies
    nothing and returns zeros. No page id past a lane's length is read:
    the clamp to the pool's last page guards a copied page alone.
  * the stream runs one chunk ahead ACROSS lanes: the copy of chunk ci + 1
    is started before chunk ci is waited for, and where ci is the lane's
    last chunk the copy started is chunk 0 of lane b + 1, into the buffer
    slot this lane is not computing from. Grid step 0 starts its own first
    chunk and the last step starts none. `slot_ref` (SMEM) carries the slot
    of a lane's first chunk from one grid step to the next (the schedule of
    jax.experimental.pallas.ops.tpu.paged_attention).
  * pages are streamed in chunks of `_chunk_positions` positions (512 at
    KH*D of 1,024): the grain at which copy and multiply alternate; most
    lanes of a few hundred positions are one chunk, and overlap with their
    neighbours.
  * rows of the window that no copy of this lane wrote hold what an earlier
    lane left there. Their scores are masked (a select, so whatever K holds
    is dropped) and their `p` is an exact 0, but 0 x NaN is not 0: the V
    window is ZEROED ONCE, at grid step 0, and from then on holds only what
    was copied from pages a table names below its lane's length, which the
    engine wrote and are finite.
  * all softmax state is f32; QK^T and PV ride the MXU in bf16 with f32
    accumulation (preferred_element_type).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def _window_dequant(b, ci, slot, k_buf, v_buf, pt_ref, ks_ref, vs_ref,
                    compute_dtype, *, chunk_pages, page_rows, max_pages,
                    num_phys, num_kv_heads, head_dim, kv_bits):
    """Quantized decode window -> full-precision ([chunk, KH*D] K, V):
    per page, unpack (int4 packs two tokens per byte along the sublane
    axis) and multiply each kv head's D-wide column block by that page's
    scalar-prefetched per-head scale."""
    from ..models.quant import unpack_int4

    k_segs, v_segs = [], []
    for p in range(chunk_pages):
        lp_safe = jnp.minimum(ci * chunk_pages + p, max_pages - 1)
        phys = jnp.minimum(pt_ref[b, lp_safe], num_phys - 1)
        kseg = k_buf[slot, pl.ds(p * page_rows, page_rows)]  # int8 [rows, KH*D]
        vseg = v_buf[slot, pl.ds(p * page_rows, page_rows)]
        if kv_bits == 4:
            kseg = unpack_int4(kseg, axis=0)  # [page_size, KH*D]
            vseg = unpack_int4(vseg, axis=0)
        # per-head scale over the head's D-wide column block
        ks_row = jnp.concatenate(
            [jnp.full((1, head_dim), ks_ref[phys, h], jnp.float32)
             for h in range(num_kv_heads)], axis=1,
        )  # [1, KH*D]
        vs_row = jnp.concatenate(
            [jnp.full((1, head_dim), vs_ref[phys, h], jnp.float32)
             for h in range(num_kv_heads)], axis=1,
        )
        k_segs.append((kseg.astype(jnp.float32) * ks_row).astype(compute_dtype))
        v_segs.append((vseg.astype(jnp.float32) * vs_row).astype(compute_dtype))
    return jnp.concatenate(k_segs, axis=0), jnp.concatenate(v_segs, axis=0)


def _decode_kernel(
    # positional refs: layer index [1] + page_tables [B, max_pages] +
    # seq_lens [B] int32 scalar prefetch (+ this layer's per-page-per-head
    # K/V scales [num_pages, KH] f32 when kv_bits > 0), then q [1, H, D]
    # VMEM, kv_k/kv_v [L, num_pages, rows, KH*D] ANY/HBM (the whole pool;
    # rows = page_size, or page_size//2 int4-packed along the sublane
    # axis), the out block, the double-buffered VMEM window + DMA
    # semaphores, and the SMEM slot counter.
    *refs,
    page_size: int,
    chunk_pages: int,
    max_pages: int,
    num_heads: int,
    num_kv_heads: int,
    head_dim: int,
    kv_bits: int = 0,
):
    if kv_bits:
        (li_ref, pt_ref, sl_ref, ks_ref, vs_ref, q_ref, kv_k_hbm, kv_v_hbm,
         out_ref, k_buf, v_buf, k_sem, v_sem, slot_ref) = refs
    else:
        (li_ref, pt_ref, sl_ref, q_ref, kv_k_hbm, kv_v_hbm,
         out_ref, k_buf, v_buf, k_sem, v_sem, slot_ref) = refs
        ks_ref = vs_ref = None
    b = pl.program_id(0)
    num_lanes = pl.num_programs(0)
    li = li_ref[0]
    chunk = chunk_pages * page_size
    num_phys = kv_k_hbm.shape[1]
    page_rows = kv_k_hbm.shape[2]
    kh, g, d = num_kv_heads, num_heads // num_kv_heads, head_dim

    seq_len = sl_ref[b]
    n_chunks = pl.cdiv(seq_len, chunk)

    def chunk_copies(lane, ci, slot, wait=False):
        """(predicate, K copy, V copy) of each page of chunk ci of `lane`
        into buffer `slot`; a page is copied where it holds a position. A
        wait takes its byte count from the window's rows, so it names no
        page."""
        lane_len = sl_ref[lane]
        for p in range(chunk_pages):
            lp = ci * chunk_pages + p
            # lp < max_pages wherever the predicate holds
            phys = 0 if wait else jnp.minimum(
                pt_ref[lane, jnp.minimum(lp, max_pages - 1)], num_phys - 1
            )
            rows = pl.ds(p * page_rows, page_rows)
            yield (
                lp * page_size < lane_len,
                pltpu.make_async_copy(
                    kv_k_hbm.at[li, phys], k_buf.at[slot, rows], k_sem.at[slot, p]
                ),
                pltpu.make_async_copy(
                    kv_v_hbm.at[li, phys], v_buf.at[slot, rows], v_sem.at[slot, p]
                ),
            )

    def start_chunk(lane, ci, slot):
        for holds, k_copy, v_copy in chunk_copies(lane, ci, slot):
            @pl.when(holds)
            def _():
                k_copy.start()
                v_copy.start()

    def wait_chunk(lane, ci, slot):
        for holds, k_copy, v_copy in chunk_copies(lane, ci, slot, wait=True):
            @pl.when(holds)
            def _():
                k_copy.wait()
                v_copy.wait()

    @pl.when(b == 0)
    def _():
        # what no copy wrote must stay finite under p == 0 (module docstring)
        v_buf[...] = jnp.zeros(v_buf.shape, v_buf.dtype)
        slot_ref[0] = 0
        start_chunk(0, 0, 0)

    slot0 = slot_ref[0]  # where this lane's first chunk is, or is arriving
    succ = jnp.minimum(b + 1, num_lanes - 1)

    @pl.when((n_chunks == 0) & (b + 1 < num_lanes))
    def _():
        start_chunk(succ, 0, slot0)  # an empty lane hands the slot on

    # GQA as ONE matmul pair per chunk: q arrives pre-packed block-diagonal
    # [KH*G, KH*D] (head h's G queries in column block h, built by XLA in
    # the wrapper) so s = q_bd @ k_flat^T and pv = p @ v_flat each hit the
    # MXU once instead of KH tiny per-head matmuls. acc accumulates the full
    # [HG, KH*D] pv; the diagonal blocks are extracted once after the loop.
    hg = kh * g
    q_bd = q_ref[0]  # [HG, KH*D]

    m0 = jnp.full((hg, 1), NEG, jnp.float32)
    l0 = jnp.zeros((hg, 1), jnp.float32)
    acc0 = jnp.zeros((hg, kh * d), jnp.float32)

    def body(ci, carry):
        m, l, acc = carry
        slot = jax.lax.rem(slot0 + ci, 2)

        # one chunk ahead: this lane's next chunk, or the next lane's first
        last = ci + 1 == n_chunks

        @pl.when(jnp.logical_not(last) | (b + 1 < num_lanes))
        def _():
            start_chunk(
                jnp.where(last, succ, b), jnp.where(last, 0, ci + 1), 1 - slot
            )

        wait_chunk(b, ci, slot)
        if kv_bits:
            k, v = _window_dequant(
                b, ci, slot, k_buf, v_buf, pt_ref, ks_ref, vs_ref,
                q_ref.dtype, chunk_pages=chunk_pages, page_rows=page_rows,
                max_pages=max_pages, num_phys=num_phys,
                num_kv_heads=kh, head_dim=d, kv_bits=kv_bits,
            )
        else:
            k = k_buf[slot]  # [CHUNK, KH*D]
            v = v_buf[slot]

        pos = ci * chunk + jax.lax.broadcasted_iota(jnp.int32, (1, chunk), 1)
        valid = pos < seq_len  # [1, CHUNK]

        s = jax.lax.dot_general(
            q_bd.astype(k.dtype),
            k,
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [HG, CHUNK]
        s = jnp.where(valid, s, NEG)
        m_n = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_n)  # [HG, 1]
        p = jnp.exp(s - m_n)  # [HG, CHUNK]
        l_n = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        pv_all = jax.lax.dot_general(
            p.astype(v.dtype),
            v,
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [HG, KH*D]
        return m_n, l_n, acc * alpha + pv_all

    m, l, acc = jax.lax.fori_loop(0, n_chunks, body, (m0, l0, acc0))
    slot_ref[0] = jax.lax.rem(slot0 + n_chunks, 2)  # the successor's first
    # extract head h's D-block from row block h of acc: static slices per kv
    # head (no [HG,KH*D]->[HG,KH,D] reshape — unsupported Mosaic shape cast)
    row_head = jax.lax.broadcasted_iota(jnp.int32, (hg, 1), 0) // g
    out = jnp.zeros((hg, d), jnp.float32)
    for k0 in range(kh):
        blk = jax.lax.slice(acc, (0, k0 * d), (hg, (k0 + 1) * d))
        out = out + jnp.where(row_head == k0, blk, 0.0)
    out = out / jnp.maximum(l, 1e-30)
    out_ref[0] = out.astype(out_ref.dtype)


def _block_diagonal_q(q: jax.Array, num_kv_heads: int) -> jax.Array:
    """[B, H, D] -> [B, H, KH*D], scaled by 1/sqrt(D): head h's query in
    the D-wide lane block of its kv head (q_bd[b, k*G+g, k*D:(k+1)*D] = q),
    zeros elsewhere. Built lane-dense, a concatenation along the lanes and
    a mask: a reshape that merges (KH, D) is a relayout on the TPU."""
    _, H, D = q.shape
    lanes = (H, num_kv_heads * D)
    lane_head = jax.lax.broadcasted_iota(jnp.int32, lanes, 1) // D
    row_head = jax.lax.broadcasted_iota(jnp.int32, lanes, 0) // (H // num_kv_heads)
    tiled = jnp.concatenate([q * (1.0 / (D**0.5))] * num_kv_heads, axis=-1)
    return jnp.where(lane_head == row_head, tiled, jnp.zeros((), q.dtype))


def _chunk_positions(page_size: int, kv_width: int) -> int:
    """Positions a chunk of the window holds, from the page's size and
    KH*D alone: 512 where the window (K and V, two slots each) stays within
    4 MiB of VMEM, which is KH*D up to 1,024 in bf16; half of that for each
    doubling of the width, never under 128 (a full lane register of scores)
    nor under a page. Read on a v5e at Mistral-7B's widths, 32 lanes of 146
    to 794 positions, 16 layers a step (PERF.md, PR 42): 1.42 ms at 128,
    1.30 at 256, 1.22 at 512, 1.32 and more at 1,024. The copies follow the
    length whatever the chunk, and at 512 they run at 600 GB/s, which is
    what this chip's HBM gives a plain pass; a smaller chunk pays 0.17 us
    an iteration more often, a larger one multiplies 1,024 masked positions
    for a lane of 300."""
    return max(min(512, max(128, (1 << 19) // kv_width)), page_size)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention_decode_pallas(
    q: jax.Array,  # [B, H, D]
    kv_k_layer,  # kv_quant.KVLayer: whole pool + layer index
    kv_v_layer,
    page_tables: jax.Array,  # [B, max_pages] int32
    seq_lens: jax.Array,  # [B] int32
    *,
    interpret: bool = False,
) -> jax.Array:
    """Flash decode attention over paged KV; returns [B, H, D] (q.dtype).
    The pool may be a QuantKV: packed pages dequantize in the VMEM window."""
    from .kv_quant import kernel_operands

    B, H, D = q.shape
    kv_k_pool, kv_v_pool, li, KH, rows, page_size, kv_bits, scale_prefetch = (
        kernel_operands(kv_k_layer, kv_v_layer, D)
    )
    max_pages = page_tables.shape[1]
    chunk_pages = min(_chunk_positions(page_size, KH * D) // page_size, max_pages)

    KHG = KH * (H // KH)
    q_bd = _block_diagonal_q(q, KH)

    prefetch = [
        li,
        page_tables.astype(jnp.int32),
        seq_lens.astype(jnp.int32),
        *scale_prefetch,
    ]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, KHG, KH * D), lambda b, *_: (b, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, H, D), lambda b, *_: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, chunk_pages * rows, KH * D), kv_k_pool.dtype),
            pltpu.VMEM((2, chunk_pages * rows, KH * D), kv_v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, chunk_pages)),
            pltpu.SemaphoreType.DMA((2, chunk_pages)),
            pltpu.SMEM((1,), jnp.int32),
        ],
    )
    kernel = functools.partial(
        _decode_kernel,
        page_size=page_size,
        chunk_pages=chunk_pages,
        max_pages=max_pages,
        num_heads=H,
        num_kv_heads=KH,
        head_dim=D,
        kv_bits=kv_bits,
    )
    # What XLA's scheduler takes the custom call to cost when it orders the
    # step around it. The kernel reads what the lanes hold, which only the
    # run knows: reckon with tables half full (max_pages is the bucket the
    # longest lane needs, so most lanes hold less).
    ctx = B * max_pages * page_size // 2
    cost = pl.CostEstimate(
        flops=4 * H * KH * D * ctx,  # the block-diagonal pair: [H, KH*D] wide
        bytes_accessed=2 * ctx * KH * D * kv_k_pool.dtype.itemsize
        + B * H * (KH + 1) * D * q.dtype.itemsize,
        transcendentals=H * ctx,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, D), q.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        cost_estimate=cost,
        interpret=interpret,
    )(*prefetch, q_bd, kv_k_pool, kv_v_pool)
