"""Ragged unified attention: interpreter-mode fuzz parity vs the XLA
reference, plus cross-checks against the pre-existing prefill/decode ops.

The ragged kernel (ops/pallas_ragged_attention.py) runs one grid over a
flat token buffer packing prefill chunks (T>1) and decode slots (T=1);
`ragged_attention_reference` (ops/paged_attention.py) is its oracle and
the engine's CPU/non-aligned fallback. Runs in Pallas interpreter mode on
the CPU test mesh (conftest pins JAX_PLATFORMS=cpu); on real TPU the same
kernel compiles via Mosaic.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.ops import paged_attention as ref_ops
from dynamo_tpu.ops.pallas_ragged_attention import (
    ragged_paged_attention_pallas,
    ragged_tile_q,
)

from .utils import kv_layer_case as L


def _pack_rows(rows, tile_q, R_pad=None):
    """rows = [(row_len, ctx_len)] -> (row_starts, row_lens, ctx_lens, N)
    with starts tile-aligned (the engine packer's layout)."""
    starts, lens, ctxs = [], [], []
    off = 0
    for (length, ctx) in rows:
        starts.append(off)
        lens.append(length)
        ctxs.append(ctx)
        off += -(-length // tile_q) * tile_q
    N = -(-max(off, tile_q) // tile_q) * tile_q
    R_pad = R_pad or len(rows)
    pad = R_pad - len(rows)
    return (
        np.array(starts + [N] * pad, np.int32),
        np.array(lens + [0] * pad, np.int32),
        np.array(ctxs + [0] * pad, np.int32),
        N,
    )


def _mk_ragged_case(rows, H=8, KH=4, D=32, page_size=8, seed=0, R_pad=None,
                    dtype=jnp.float32):
    rng = np.random.RandomState(seed)
    tile_q = ragged_tile_q(dtype)
    row_starts, row_lens, ctx_lens, N = _pack_rows(rows, tile_q, R_pad)
    R = len(row_starts)
    max_pages = max(
        (int(c) + int(l) + page_size - 1) // page_size + 1
        for l, c in rows
    ) + 1
    pages = R * max_pages + 4
    q = jnp.asarray(rng.randn(N, H, D), dtype)
    kv_k = jnp.asarray(rng.randn(pages, page_size, KH, D), dtype)
    kv_v = jnp.asarray(rng.randn(pages, page_size, KH, D), dtype)
    pt = jnp.asarray(
        rng.choice(pages, size=(R, max_pages), replace=False).astype(np.int32)
    )
    return (
        q, kv_k, kv_v, pt,
        jnp.asarray(row_starts), jnp.asarray(row_lens), jnp.asarray(ctx_lens),
        row_starts, row_lens, N,
    )


def _assert_real_rows_close(got, want, row_starts, row_lens, rtol, atol):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert got.shape == want.shape  # bit-identical shapes
    assert got.dtype == want.dtype
    for s, l in zip(row_starts, row_lens):
        if l:
            np.testing.assert_allclose(
                got[s : s + l], want[s : s + l], rtol=rtol, atol=atol
            )


MIX = [(24, 7), (1, 13), (1, 40), (9, 0), (1, 1), (17, 31)]


@pytest.mark.parametrize(
    "rows,name",
    [
        (MIX, "mixed"),
        ([(1, 5), (1, 17), (1, 64), (1, 1)], "all_decode"),
        ([(32, 0), (16, 8), (40, 24)], "all_prefill"),
        # context lengths straddling page boundaries (page_size=8): ctx at
        # page_size-1 / page_size / page_size+1, and chunk ends mid-page
        ([(1, 7), (1, 8), (1, 9), (5, 15), (11, 16), (3, 17)], "page_straddle"),
    ],
)
def test_ragged_kernel_matches_reference(rows, name):
    (q, kv_k, kv_v, pt, rs, rl, cl, starts, lens, _N) = _mk_ragged_case(
        rows, seed=len(rows)
    )
    want = ref_ops.ragged_attention_reference(q, L(kv_k), L(kv_v), pt, rs, rl, cl)
    got = ragged_paged_attention_pallas(
        q, L(kv_k), L(kv_v), pt, rs, rl, cl, interpret=True
    )
    _assert_real_rows_close(got, want, starts, lens, rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("gqa", [(8, 8), (8, 2), (4, 1)])
def test_ragged_kernel_gqa_group_sizes(gqa):
    H, KH = gqa
    (q, kv_k, kv_v, pt, rs, rl, cl, starts, lens, _N) = _mk_ragged_case(
        MIX, H=H, KH=KH, seed=H * 7 + KH
    )
    want = ref_ops.ragged_attention_reference(q, L(kv_k), L(kv_v), pt, rs, rl, cl)
    got = ragged_paged_attention_pallas(
        q, L(kv_k), L(kv_v), pt, rs, rl, cl, interpret=True
    )
    _assert_real_rows_close(got, want, starts, lens, rtol=2e-3, atol=2e-3)


def test_ragged_kernel_bf16_and_padding_rows():
    """bf16 (the production KV dtype, 16-row tiles) + padded row bucket:
    trailing zero-length rows must not disturb real rows."""
    (q, kv_k, kv_v, pt, rs, rl, cl, starts, lens, _N) = _mk_ragged_case(
        [(20, 5), (1, 33), (3, 0)], seed=9, R_pad=8, dtype=jnp.bfloat16
    )
    want = ref_ops.ragged_attention_reference(q, L(kv_k), L(kv_v), pt, rs, rl, cl)
    got = ragged_paged_attention_pallas(
        q, L(kv_k), L(kv_v), pt, rs, rl, cl, interpret=True
    )
    _assert_real_rows_close(got, want, starts, lens, rtol=5e-2, atol=5e-2)


@pytest.mark.parametrize("seed", range(6))
def test_ragged_fuzz_parity(seed):
    """Random mixes of prefill chunks and decode slots with page-boundary-
    straddling context lengths — the kernel and the XLA oracle must agree
    on every real row."""
    rng = np.random.RandomState(100 + seed)
    page_size = int(rng.choice([8, 16]))
    n_rows = rng.randint(2, 7)
    rows = []
    for _ in range(n_rows):
        if rng.rand() < 0.5:
            rows.append((1, int(rng.randint(1, 70))))  # decode slot
        else:
            rows.append(
                (int(rng.randint(2, 40)), int(rng.randint(0, 40)))
            )  # prefill chunk
    KH = int(rng.choice([1, 2, 4]))
    H = KH * int(rng.choice([1, 2, 4]))
    (q, kv_k, kv_v, pt, rs, rl, cl, starts, lens, _N) = _mk_ragged_case(
        rows, H=H, KH=KH, page_size=page_size, seed=seed, R_pad=n_rows + 2
    )
    want = ref_ops.ragged_attention_reference(q, L(kv_k), L(kv_v), pt, rs, rl, cl)
    got = ragged_paged_attention_pallas(
        q, L(kv_k), L(kv_v), pt, rs, rl, cl, interpret=True
    )
    _assert_real_rows_close(got, want, starts, lens, rtol=2e-3, atol=2e-3)


# --------------------------------------------------------------------- #
# the reference itself vs the pre-existing split-path ops: a ragged row
# must equal the same computation done the split way
# --------------------------------------------------------------------- #


def test_reference_prefill_row_equals_batched_prefill_op():
    rows = [(24, 7), (1, 13)]
    (q, kv_k, kv_v, pt, rs, rl, cl, starts, lens, _N) = _mk_ragged_case(
        rows, seed=3
    )
    ref = ref_ops.ragged_attention_reference(q, L(kv_k), L(kv_v), pt, rs, rl, cl)
    T, ctx = rows[0]
    qb = q[starts[0] : starts[0] + T][None]
    positions = jnp.asarray(np.arange(ctx, ctx + T))[None]
    want = ref_ops.prefill_attention_batched(
        qb, L(kv_k), L(kv_v), positions, pt[0:1],
        jnp.asarray([ctx + T]), jnp.asarray([ctx]),
    )
    np.testing.assert_allclose(
        np.asarray(ref)[starts[0] : starts[0] + T], np.asarray(want)[0],
        rtol=2e-3, atol=2e-3,
    )


def test_reference_decode_row_equals_decode_op():
    rows = [(24, 7), (1, 13)]
    (q, kv_k, kv_v, pt, rs, rl, cl, starts, lens, _N) = _mk_ragged_case(
        rows, seed=3
    )
    ref = ref_ops.ragged_attention_reference(q, L(kv_k), L(kv_v), pt, rs, rl, cl)
    # decode row: ctx=13, len=1 == classic decode with seq_len 14 over a
    # pool already holding the current token's KV
    qd = q[starts[1] : starts[1] + 1]
    want = ref_ops.paged_attention_decode(
        qd, L(kv_k), L(kv_v), pt[1:2], jnp.asarray([14])
    )
    np.testing.assert_allclose(
        np.asarray(ref)[starts[1] : starts[1] + 1], np.asarray(want),
        rtol=2e-3, atol=2e-3,
    )


# --------------------------------------------------------------------- #
# quantized KV (DYN_KV_QUANT, ops/kv_quant.py): the kernel must agree
# with the quantized XLA reference EXACTLY (same ints, same scales,
# rtol 2e-3 like the fp arms) and with the FP oracle within quantization
# tolerance — the acceptance contract (docs/ragged_attention.md
# "Quantized pages": int8 degrades outputs by ~a half step of the
# per-page-per-head scale; int4 by ~1/14 of the page amax).
# --------------------------------------------------------------------- #

# absolute tolerance vs the FP oracle, in units of the per-page amax
# (values here are N(0,1): page amax ~3-4). K-error shifts softmax
# weights on top of direct V-error, hence the factor over a half step.
_QUANT_FP_ATOL = {"int8": 0.08, "int4": 0.8}


def _quantize_case(kv, page_size, mode):
    """FP per-layer case KV [pages, ps, KH, D] -> per-layer QuantKV via
    the production write path (kv_write, one call covering every page)."""
    from dynamo_tpu.ops.kv_quant import alloc_kv_store, kv_layer, kv_write

    pages, ps, KH, D = kv.shape
    st = alloc_kv_store(1, pages, ps, KH, D, kv.dtype, mode)
    phys = jnp.asarray(np.repeat(np.arange(pages, dtype=np.int32), ps))
    offs = jnp.asarray(np.tile(np.arange(ps, dtype=np.int32), pages))
    st = kv_write(st, 0, phys, offs, kv.reshape(pages * ps, KH, D))
    return kv_layer(st, 0)


@pytest.mark.parametrize("mode", ["int8", "int4"])
@pytest.mark.parametrize(
    "rows,name",
    [
        (MIX, "mixed"),
        ([(1, 5), (1, 17), (1, 64), (1, 1)], "all_decode"),
        ([(1, 7), (1, 8), (1, 9), (5, 15), (11, 16), (3, 17)], "page_straddle"),
    ],
)
def test_ragged_kernel_quantized_matches_oracles(mode, rows, name):
    (q, kv_k, kv_v, pt, rs, rl, cl, starts, lens, _N) = _mk_ragged_case(
        rows, seed=len(rows)
    )
    qk = _quantize_case(kv_k, kv_k.shape[1], mode)
    qv = _quantize_case(kv_v, kv_v.shape[1], mode)
    fp_oracle = ref_ops.ragged_attention_reference(q, L(kv_k), L(kv_v), pt, rs, rl, cl)
    want = ref_ops.ragged_attention_reference(q, qk, qv, pt, rs, rl, cl)
    got = ragged_paged_attention_pallas(
        q, qk, qv, pt, rs, rl, cl, interpret=True
    )
    # kernel == quantized reference (same ints dequantized the same way)
    _assert_real_rows_close(got, want, starts, lens, rtol=2e-3, atol=2e-3)
    # kernel == FP oracle within quantization tolerance
    _assert_real_rows_close(
        got, fp_oracle, starts, lens, rtol=0.0, atol=_QUANT_FP_ATOL[mode]
    )


@pytest.mark.parametrize("mode", ["int8", "int4"])
@pytest.mark.parametrize("gqa", [(8, 2), (4, 1)])
def test_ragged_kernel_quantized_gqa(mode, gqa):
    H, KH = gqa
    (q, kv_k, kv_v, pt, rs, rl, cl, starts, lens, _N) = _mk_ragged_case(
        MIX, H=H, KH=KH, seed=H * 7 + KH
    )
    qk = _quantize_case(kv_k, kv_k.shape[1], mode)
    qv = _quantize_case(kv_v, kv_v.shape[1], mode)
    want = ref_ops.ragged_attention_reference(q, qk, qv, pt, rs, rl, cl)
    got = ragged_paged_attention_pallas(
        q, qk, qv, pt, rs, rl, cl, interpret=True
    )
    _assert_real_rows_close(got, want, starts, lens, rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("mode", ["int8", "int4"])
@pytest.mark.parametrize("seed", range(4))
def test_ragged_quantized_fuzz_parity(mode, seed):
    """Random mixed/decode traffic over quantized pages: kernel vs the
    quantized reference (exact) and vs the FP oracle (quant tolerance)."""
    rng = np.random.RandomState(700 + seed)
    page_size = int(rng.choice([8, 16]))
    rows = []
    for _ in range(rng.randint(2, 6)):
        if rng.rand() < 0.5:
            rows.append((1, int(rng.randint(1, 70))))
        else:
            rows.append((int(rng.randint(2, 40)), int(rng.randint(0, 40))))
    KH = int(rng.choice([1, 2, 4]))
    H = KH * int(rng.choice([1, 2, 4]))
    (q, kv_k, kv_v, pt, rs, rl, cl, starts, lens, _N) = _mk_ragged_case(
        rows, H=H, KH=KH, page_size=page_size, seed=seed, R_pad=len(rows) + 2
    )
    qk = _quantize_case(kv_k, page_size, mode)
    qv = _quantize_case(kv_v, page_size, mode)
    fp_oracle = ref_ops.ragged_attention_reference(q, L(kv_k), L(kv_v), pt, rs, rl, cl)
    want = ref_ops.ragged_attention_reference(q, qk, qv, pt, rs, rl, cl)
    got = ragged_paged_attention_pallas(
        q, qk, qv, pt, rs, rl, cl, interpret=True
    )
    _assert_real_rows_close(got, want, starts, lens, rtol=2e-3, atol=2e-3)
    _assert_real_rows_close(
        got, fp_oracle, starts, lens, rtol=0.0, atol=_QUANT_FP_ATOL[mode]
    )


# --------------------------------------------------------------------- #
# speculative verify geometry: 1+d one-token rows per lane, sibling rows
# share ONE page-table row with a ctx staircase (engine spec fusion packs
# lane token + d drafts as adjacent rows; row j attends ctx L-1+j over
# the SAME kv pages, the later positions written earlier in the dispatch)
# --------------------------------------------------------------------- #


def _spec_staircase(L, d, lanes):
    """rows + sibling groups for `lanes` spec lanes of 1+d verify rows:
    lane k rows carry ctx_lens (Lk-1, Lk, ..., Lk-1+d), row_len 1."""
    rows, groups = [], []
    for k in range(lanes):
        base = L + 3 * k
        g = list(range(len(rows), len(rows) + d + 1))
        for j in range(d + 1):
            rows.append((1, base - 1 + j))
        groups.append(g)
    return rows, groups


def _share_sibling_tables(pt, groups):
    """Point every sibling row's page-table row at the group leader's —
    the engine layout (one lane = one kv page list, 1+d flat rows)."""
    pt = np.array(pt)
    for g in groups:
        for r in g[1:]:
            pt[r] = pt[g[0]]
    return jnp.asarray(pt)


@pytest.mark.parametrize("d", [1, 3])
def test_ragged_kernel_spec_staircase_shared_tables(d):
    rows, groups = _spec_staircase(L=18, d=d, lanes=3)
    (q, kv_k, kv_v, pt, rs, rl, cl, starts, lens, _N) = _mk_ragged_case(
        rows, seed=41 + d
    )
    pt = _share_sibling_tables(pt, groups)
    want = ref_ops.ragged_attention_reference(q, L(kv_k), L(kv_v), pt, rs, rl, cl)
    got = ragged_paged_attention_pallas(
        q, L(kv_k), L(kv_v), pt, rs, rl, cl, interpret=True
    )
    _assert_real_rows_close(got, want, starts, lens, rtol=2e-3, atol=2e-3)
    # the staircase is real: each later sibling sees strictly more ctx,
    # so sibling outputs must differ (guards against a broken ctx clamp
    # silently giving every sibling the leader's window)
    got = np.asarray(got, np.float32)
    for g in groups:
        for a, b in zip(g, g[1:]):
            assert not np.allclose(got[starts[a]], got[starts[b]])


def test_ragged_kernel_spec_rows_blend_with_prefill_and_decode():
    """Spec staircases packed beside prefill chunks and plain decode rows
    in one flat buffer — the fused mixed step's worst-case row blend."""
    stair, groups = _spec_staircase(L=12, d=2, lanes=2)
    off = 3  # staircase rows sit after a chunk, a decode row, a chunk
    rows = [(24, 7), (1, 33), (13, 5)] + stair + [(1, 9)]
    groups = [[r + off for r in g] for g in groups]
    (q, kv_k, kv_v, pt, rs, rl, cl, starts, lens, _N) = _mk_ragged_case(
        rows, seed=77, R_pad=len(rows) + 2
    )
    pt = _share_sibling_tables(pt, groups)
    want = ref_ops.ragged_attention_reference(q, L(kv_k), L(kv_v), pt, rs, rl, cl)
    got = ragged_paged_attention_pallas(
        q, L(kv_k), L(kv_v), pt, rs, rl, cl, interpret=True
    )
    _assert_real_rows_close(got, want, starts, lens, rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("mode", ["int8", "int4"])
def test_ragged_kernel_spec_staircase_quantized(mode):
    rows, groups = _spec_staircase(L=18, d=3, lanes=2)
    (q, kv_k, kv_v, pt, rs, rl, cl, starts, lens, _N) = _mk_ragged_case(
        rows, seed=53
    )
    pt = _share_sibling_tables(pt, groups)
    qk = _quantize_case(kv_k, kv_k.shape[1], mode)
    qv = _quantize_case(kv_v, kv_v.shape[1], mode)
    fp_oracle = ref_ops.ragged_attention_reference(q, L(kv_k), L(kv_v), pt, rs, rl, cl)
    want = ref_ops.ragged_attention_reference(q, qk, qv, pt, rs, rl, cl)
    got = ragged_paged_attention_pallas(
        q, qk, qv, pt, rs, rl, cl, interpret=True
    )
    _assert_real_rows_close(got, want, starts, lens, rtol=2e-3, atol=2e-3)
    _assert_real_rows_close(
        got, fp_oracle, starts, lens, rtol=0.0, atol=_QUANT_FP_ATOL[mode]
    )


@pytest.mark.parametrize("mode", ["int8", "int4"])
def test_decode_kernels_quantized_match_oracles(mode):
    """The decode kernel under quantized pools: exact vs the quantized
    XLA reference, quant-tolerance vs the FP oracle."""
    import os

    from dynamo_tpu.ops.pallas_paged_attention import paged_attention_decode_pallas

    rng = np.random.RandomState(41)
    pages, ps, KH, D, H, B = 12, 8, 2, 32, 4, 3
    kv_k = jnp.asarray(rng.randn(pages, ps, KH, D), jnp.float32)
    kv_v = jnp.asarray(rng.randn(pages, ps, KH, D), jnp.float32)
    qk = _quantize_case(kv_k, ps, mode)
    qv = _quantize_case(kv_v, ps, mode)
    tables = jnp.asarray(
        rng.choice(pages, size=(B, 4), replace=False).astype(np.int32)
    )
    seq_lens = jnp.asarray([13, 5, 20], jnp.int32)
    q = jnp.asarray(rng.randn(B, H, D), jnp.float32)
    os.environ["DYNAMO_TPU_PAGED_ATTN"] = "xla"
    try:
        ref_q = ref_ops.paged_attention_decode(q, qk, qv, tables, seq_lens)
        ref_fp = ref_ops.paged_attention_decode(q, L(kv_k), L(kv_v), tables, seq_lens)
    finally:
        os.environ.pop("DYNAMO_TPU_PAGED_ATTN", None)
    got = paged_attention_decode_pallas(
        q, qk, qv, tables, seq_lens, interpret=True
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref_q),
                               rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref_fp),
                               rtol=0.0, atol=_QUANT_FP_ATOL[mode])


def test_quantized_page_write_tracks_scale_growth():
    """Incremental decode-style writes that GROW a page's scale must keep
    earlier tokens dequantizable (the requantize pass), and a write at
    in-page offset 0 must reset a stale scale (page reuse)."""
    from dynamo_tpu.ops.kv_quant import (
        alloc_kv_store, gather_dequant, kv_layer, kv_write,
    )

    rng = np.random.RandomState(5)
    ps, KH, D = 8, 2, 4
    st = alloc_kv_store(1, 4, ps, KH, D, jnp.float32, "int8")
    ref = np.zeros((ps, KH, D), np.float32)
    # small tokens first, then a 10x outlier -> scale grows 10x
    for t in range(4):
        scale = 10.0 if t == 3 else 1.0
        vals = (rng.randn(1, KH, D) * scale).astype(np.float32)
        ref[t] = vals[0]
        st = kv_write(st, 0, jnp.asarray([1]), jnp.asarray([t]),
                      jnp.asarray(vals))
    deq = np.asarray(gather_dequant(kv_layer(st, 0), jnp.asarray([1]), D))[0]
    page_amax = np.abs(ref[:4]).max(axis=(0, 2))  # [KH]
    # a couple of half-steps of the FINAL scale (requantize accumulation)
    tol = page_amax / 127 * 2.6 + 1e-6
    assert np.all(np.abs(deq[:4] - ref[:4]) <= tol[None, :, None])
    # page reuse: rewrite from offset 0 with small values — the stale 10x
    # scale must reset, keeping the new page tightly quantized
    tiny = (rng.randn(ps, KH, D) * 0.01).astype(np.float32)
    st = kv_write(st, 0, jnp.asarray(np.full(ps, 1, np.int32)),
                  jnp.asarray(np.arange(ps, dtype=np.int32)),
                  jnp.asarray(tiny))
    deq = np.asarray(gather_dequant(kv_layer(st, 0), jnp.asarray([1]), D))[0]
    tiny_amax = np.abs(tiny).max(axis=(0, 2))
    assert np.all(
        np.abs(deq - tiny) <= (tiny_amax / 127 * 0.51 + 1e-8)[None, :, None]
    )


def test_pallas_eligible_gate_is_shared():
    """The centralized gate: env knob + 128-lane alignment, one spelling
    for prefill/decode/ragged dispatch."""
    import os

    os.environ["DYNAMO_TPU_PAGED_ATTN"] = "pallas"
    try:
        assert ref_ops._pallas_eligible(128)
        assert ref_ops._pallas_eligible(256)
        assert not ref_ops._pallas_eligible(64)
    finally:
        os.environ.pop("DYNAMO_TPU_PAGED_ATTN", None)
    os.environ["DYNAMO_TPU_PAGED_ATTN"] = "xla"
    try:
        assert not ref_ops._pallas_eligible(128)
    finally:
        os.environ.pop("DYNAMO_TPU_PAGED_ATTN", None)


# --------------------------------------------------------------------- #
# the model's ragged forward: the flat axis is compact, and the q-tile
# layout lives between the q projection and the attention call alone
# --------------------------------------------------------------------- #

FWD_PAGE = 8


def _compact_pack(cfg, rows, M, R_pad, shared=(), seed=0):
    """The engine packer's operands for `ragged_forward`: rows =
    [(row_len, ctx_len)] back to back from slot 0 of an [M] buffer, the
    rest of it padding (scratch-tail position, owned by the last row),
    R_pad - len(rows) empty rows starting past the buffer, tables with
    the scratch column last, and a pool whose pages hold random history.
    `shared` = groups of rows that are one lane's verify staircase: they
    share the leader's table row, as the engine packs spec lanes."""
    rng = np.random.RandomState(seed)
    P = max(-(-(ctx + n) // FWD_PAGE) for n, ctx in rows) + 1
    pages = 1 + len(rows) * P  # page 0 = scratch
    pool = (cfg.num_layers, pages, FWD_PAGE, cfg.num_kv_heads * cfg.head_dim)
    kv_k = jnp.asarray(rng.randn(*pool), cfg.dtype)
    kv_v = jnp.asarray(rng.randn(*pool), cfg.dtype)
    tables = np.zeros((R_pad, P + 1), np.int32)
    tables[: len(rows), :P] = np.arange(1, pages).reshape(len(rows), P)
    for group in shared:
        tables[group[1:]] = tables[group[0]]
    tokens = np.zeros(M, np.int32)
    positions = np.full(M, (P + 1) * FWD_PAGE - 1, np.int32)
    row_ids = np.full(M, R_pad - 1, np.int32)
    row_starts = np.full(R_pad, M, np.int32)
    row_lens = np.zeros(R_pad, np.int32)
    ctx_lens = np.zeros(R_pad, np.int32)
    last_flat = np.zeros(R_pad, np.int32)
    off = 0
    for r, (n, ctx) in enumerate(rows):
        tokens[off : off + n] = rng.randint(5, cfg.vocab_size - 1, size=n)
        positions[off : off + n] = np.arange(ctx, ctx + n)
        row_ids[off : off + n] = r
        row_starts[r], row_lens[r], ctx_lens[r] = off, n, ctx
        last_flat[r] = off + n - 1
        off += n
    assert off <= M
    ops = (tokens, positions, row_ids, kv_k, kv_v, tables, row_starts,
           row_lens, ctx_lens, last_flat)
    return tuple(jnp.asarray(a) for a in ops)


def _fuzzed_rows(seed):
    rng = np.random.RandomState(seed)
    return [
        (1, int(rng.randint(1, 60))) if rng.rand() < 0.6
        else (int(rng.randint(2, 45)), int(rng.randint(0, 30)))
        for _ in range(rng.randint(2, 9))
    ]


# name -> (rows, M, R_pad, shared): chunk lengths off both tiles, one-token
# rows, a spec staircase on one table row, empty rows, a tail of padding
FWD_PACKS = {
    "chunks_off_the_tile": ([(21, 0), (9, 16), (1, 13), (17, 3)], 64, 4, ()),
    "one_token_rows": ([(1, 5), (1, 17), (1, 63), (1, 1), (1, 8)], 8, 8, ()),
    "spec_staircase": (
        [(13, 2), (1, 20), (1, 21), (1, 22), (1, 9), (1, 30), (1, 31)],
        32, 8, ([1, 2, 3], [5, 6]),
    ),
    "empty_rows_and_tail": ([(3, 0), (1, 40)], 128, 16, ()),
    "full_bucket": ([(40, 0), (23, 8), (1, 7)], 64, 4, ()),
    # the grouped expert path (moe.GROUPED_MIN_TOKENS slots and more)
    "grouped_bucket": ([(33, 4), (1, 12), (1, 50), (70, 0)], 256, 8, ()),
    "fuzz0": (_fuzzed_rows(0), 256, 16, ()),
    "fuzz1": (_fuzzed_rows(1), 256, 16, ()),
    "fuzz2": (_fuzzed_rows(2), 256, 16, ()),
}


@pytest.fixture
def kernels_as(monkeypatch):
    """Put `ragged` and `decode` in the two Pallas kernels' places behind
    `ragged_attention`'s gate. The split path is a jit of its own, whose
    cache knows nothing of what a test patched: cleared on both sides."""
    from dynamo_tpu.ops import pallas_paged_attention as decode_mod
    from dynamo_tpu.ops import pallas_ragged_attention as ragged_mod

    def patch(ragged, decode):
        monkeypatch.setattr(ragged_mod, "ragged_paged_attention_pallas", ragged)
        monkeypatch.setattr(decode_mod, "paged_attention_decode_pallas", decode)
        ref_ops.ragged_attention_kernels.clear_cache()

    yield patch
    ref_ops.ragged_attention_kernels.clear_cache()


@pytest.fixture(scope="module")
def fwd_families():
    import jax

    from dynamo_tpu.models import llama, moe

    out = {}
    for name, mod, cfg in (
        ("llama", llama, llama.LlamaConfig.tiny(dtype=jnp.float32)),
        ("tiny-moe", moe, moe.MoeConfig.tiny_moe(dtype=jnp.float32)),
    ):
        out[name] = (mod, cfg, mod.init_params(cfg, jax.random.PRNGKey(3)))
    return out


@pytest.mark.parametrize("pack", sorted(FWD_PACKS))
@pytest.mark.parametrize("tile", [8, 16])
@pytest.mark.parametrize("family", ["llama", "tiny-moe"])
def test_ragged_forward_tiled_q_matches_compact(
    fwd_families, monkeypatch, kernels_as, family, tile, pack
):
    """`ragged_forward` with the attention tile forced to 8 and to 16 (the
    split path of `ragged_attention`: the XLA ragged reference on the
    tile-aligned starts it derives for the rows of more than one token, as
    the Pallas kernel would, and the decode op for the one-token rows)
    against tile 1, where attention takes the compact axis as it is: the
    same last-token logits on every real row, and the same pool bytes
    outside the scratch page."""
    mod, cfg, params = fwd_families[family]
    rows, M, R_pad, shared = FWD_PACKS[pack]
    ops = _compact_pack(cfg, rows, M, R_pad, shared, seed=len(rows))
    # the kernels' places taken by their XLA references: the layout, the
    # split by row length and the merge are what runs here
    kernels_as(ref_ops.ragged_attention_reference,
               ref_ops.paged_attention_decode)

    def run(t):
        monkeypatch.setattr(ref_ops, "ragged_tile", lambda *a, **kw: t)
        return mod.ragged_forward(params, cfg, *ops)

    want, got = run(1), run(tile)
    n = len(rows)
    np.testing.assert_allclose(
        np.asarray(got[0])[:n], np.asarray(want[0])[:n], rtol=1e-5, atol=1e-5
    )
    for g, w, before in zip(got[1:], want[1:], ops[3:5]):
        np.testing.assert_allclose(
            np.asarray(g)[:, 1:], np.asarray(w)[:, 1:], rtol=1e-5, atol=1e-5
        )
        # the step wrote its rows' K and V: the pool is not what it was
        assert not np.array_equal(np.asarray(g)[:, 1:], np.asarray(before)[:, 1:])


def test_tiled_layout_is_the_inverse_pair_the_kernel_contract_wants():
    """Starts on multiples of the tile in row order, rows of no length
    (empty ones, and the one-token rows the caller zeroed) where the next
    row starts, a static length that holds any pack of the bucket, and two
    indices that are each other's inverse on the slots of the rows that
    keep tiles and out of range elsewhere."""
    from dynamo_tpu.ops.paged_attention import _tiled_layout, ragged_tiles

    cfg_rows = [(21, 0), (1, 13), (16, 3), (1, 1)]
    ops = _compact_pack(
        type("C", (), dict(num_layers=1, num_kv_heads=1, head_dim=8,
                           vocab_size=64, dtype=jnp.float32)),
        cfg_rows, 64, 8,
    )
    row_starts, row_lens = ops[6], ops[7]
    # every row may be long: M + (tile - 1) * R = 184 slots, whole tiles
    assert ragged_tiles(64, 8, 16) == 12
    # two rows may: 64 + 15 * 2 = 94 slots, 6 tiles
    tiles = ragged_tiles(64, 8, 16, long_rows=2)
    assert tiles == 6
    tiled_lens = jnp.where(row_lens == 1, 0, row_lens)
    starts, to_tiled, from_tiled = map(
        np.asarray, _tiled_layout(16, tiles, 64, row_starts, tiled_lens))
    assert list(starts) == [0, 32, 32, 48, 48, 48, 48, 48]
    assert from_tiled.shape == (96,)
    kept = [*range(21), *range(22, 38)]  # compact slots of rows 0 and 2
    assert list(from_tiled[to_tiled[kept]]) == kept
    others = sorted(set(range(64)) - set(kept))
    assert (to_tiled[others] == len(from_tiled)).all()
    assert (from_tiled == 64).sum() == len(from_tiled) - len(kept)


# --------------------------------------------------------------------- #
# the split path: one-token rows through the paged decode kernel, the
# rest through the ragged kernel, both Pallas in interpret mode
# --------------------------------------------------------------------- #


def _split_case(rows, H, KH, D, dtype=jnp.float32, M=None, R_pad=None,
                shared=(), seed=0):
    """A compact pack for `ragged_attention`: rows = [(row_len, ctx_len)],
    a row of no length starting where the next row does (ascending starts,
    padding rows at M). -> (q [M, H, D], kv_k, kv_v, tables, row_starts,
    row_lens, ctx_lens)."""
    rng = np.random.RandomState(seed)
    P = max(-(-(ctx + n) // FWD_PAGE) for n, ctx in rows) + 1
    R = len(rows)
    R_pad = R_pad or R
    real = sum(n for n, _ in rows)
    M = M or -(-real // 8) * 8
    pages = 1 + R * P
    kv_k = jnp.asarray(rng.randn(pages, FWD_PAGE, KH, D), dtype)
    kv_v = jnp.asarray(rng.randn(pages, FWD_PAGE, KH, D), dtype)
    tables = np.zeros((R_pad, P + 1), np.int32)
    tables[:R, :P] = rng.permutation(np.arange(1, pages)).reshape(R, P)
    for group in shared:
        tables[group[1:]] = tables[group[0]]
    row_starts = np.full(R_pad, M, np.int32)
    row_lens = np.zeros(R_pad, np.int32)
    ctx_lens = np.zeros(R_pad, np.int32)
    off = 0
    for r, (n, ctx) in enumerate(rows):
        row_starts[r], row_lens[r], ctx_lens[r] = off, n, ctx
        off += n
    q = jnp.asarray(rng.randn(M, H, D), dtype)
    return (q, kv_k, kv_v, jnp.asarray(tables), jnp.asarray(row_starts),
            jnp.asarray(row_lens), jnp.asarray(ctx_lens))


# name -> (rows, kwargs of _split_case, long_rows)
SPLIT_PACKS = {
    "decode_rows_only": (
        [(1, 5), (1, 17), (1, 64), (1, 1), (1, 8)], dict(R_pad=8), 2),
    "one_prompt_beside_31_rows": (
        [(45, 0)] + [(1, 3 + 5 * i) for i in range(31)],
        dict(M=128, R_pad=40), 8),
    "a_chunk_of_one_token": ([(1, 0), (19, 8), (1, 40)], dict(R_pad=4), 2),
    "verify_rows_on_one_table": (
        [(13, 2), (1, 20), (1, 21), (1, 22), (1, 23), (1, 9)],
        dict(R_pad=8, shared=([1, 2, 3, 4],)), 1),
    "empty_rows_in_the_middle": (
        [(1, 12), (0, 0), (21, 4), (0, 0), (0, 0), (1, 30), (7, 0)],
        dict(R_pad=8), 3),
    "a_full_prefill_batch_off_the_tile": (
        [(17, 0), (3, 9), (33, 16), (9, 1), (21, 40), (2, 7), (31, 3),
         (5, 0), (1, 11), (1, 50)],
        dict(M=128, R_pad=16), 8),
    "no_one_token_row": ([(23, 0), (9, 16)], dict(M=64, R_pad=8), 2),
}


@pytest.fixture
def interpreted_kernels(monkeypatch, kernels_as):
    """`ragged_attention` resolved to its Pallas kernels, both in interpret
    mode: the gate says yes, and the two jitted kernels are asked to
    interpret."""
    import functools

    from dynamo_tpu.ops.pallas_paged_attention import (
        paged_attention_decode_pallas,
    )

    monkeypatch.setattr(ref_ops, "_pallas_eligible", lambda *a, **kw: True)
    kernels_as(
        functools.partial(ragged_paged_attention_pallas, interpret=True),
        functools.partial(paged_attention_decode_pallas, interpret=True),
    )
    return kernels_as


# the cells' widths (heads of 128 over 8 KV heads, of 256 over 2) on the
# small packs, where interpret mode is quick; every pack at D = 32
SPLIT_CASES = [
    pytest.param(pack, heads, id=f"{pack}-{name}")
    for pack in sorted(SPLIT_PACKS)
    for name, heads in (("128x8kv", (32, 8, 128)), ("256x2kv", (16, 2, 256)),
                        ("32x4kv", (8, 4, 32)))
    if heads[2] == 32 or len(SPLIT_PACKS[pack][0]) <= 8
]


@pytest.mark.parametrize("pack,heads", SPLIT_CASES)
def test_split_path_matches_reference(interpreted_kernels, pack, heads):
    """One-token rows through the decode kernel and the rest through the
    ragged kernel give, on every real row of the compact axis, what the
    XLA reference gives for the whole pack."""
    H, KH, D = heads
    rows, kw, long_rows = SPLIT_PACKS[pack]
    q, kv_k, kv_v, pt, rs, rl, cl = _split_case(
        rows, H, KH, D, seed=len(rows), **kw)
    want = ref_ops.ragged_attention_reference(
        q, L(kv_k), L(kv_v), pt, rs, rl, cl)
    got = ref_ops.ragged_attention(
        q, L(kv_k), L(kv_v), pt, rs, rl, cl, long_rows=long_rows)
    assert np.isfinite(np.asarray(got)).all()
    _assert_real_rows_close(
        got, want, np.asarray(rs), np.asarray(rl), rtol=2e-3, atol=2e-3)


def test_split_path_bf16_at_the_cells_tile(interpreted_kernels):
    """bf16 packs at the 16-row tile, as the cells run them."""
    rows, kw, long_rows = SPLIT_PACKS["a_chunk_of_one_token"]
    q, kv_k, kv_v, pt, rs, rl, cl = _split_case(
        rows, 8, 2, 128, dtype=jnp.bfloat16, seed=5, **kw)
    want = ref_ops.ragged_attention_reference(
        q, L(kv_k), L(kv_v), pt, rs, rl, cl)
    got = ref_ops.ragged_attention(
        q, L(kv_k), L(kv_v), pt, rs, rl, cl, long_rows=long_rows)
    _assert_real_rows_close(
        got, want, np.asarray(rs), np.asarray(rl), rtol=3e-2, atol=3e-2)


def test_skipped_tail_tile_is_never_read(interpreted_kernels):
    """A tile that holds no real q row returns before its first copy and
    writes nothing: whatever its out block holds reaches no slot of the
    compact axis. The ragged kernel's result is poisoned outside the
    tiles of real rows before the merge reads it."""
    import functools

    from dynamo_tpu.ops.pallas_paged_attention import (
        paged_attention_decode_pallas,
    )

    seen = {}

    def poisoned(q, kv_k, kv_v, pt, starts, lens, ctx):
        out = ragged_paged_attention_pallas(
            q, kv_k, kv_v, pt, starts, lens, ctx, interpret=True)
        slot = jnp.arange(q.shape[0])
        tile = ragged_tile_q(q.dtype)
        spans = -(-lens // tile) * tile
        held = ((slot[:, None] >= starts[None, :])
                & (slot[:, None] < (starts + spans)[None, :])).any(axis=1)
        seen["tiles"] = q.shape[0] // tile
        return jnp.where(held[:, None, None], out, jnp.nan)

    interpreted_kernels(
        poisoned,
        functools.partial(paged_attention_decode_pallas, interpret=True))
    rows, kw, long_rows = SPLIT_PACKS["empty_rows_in_the_middle"]
    q, kv_k, kv_v, pt, rs, rl, cl = _split_case(rows, 8, 4, 32, seed=3, **kw)
    want = ref_ops.ragged_attention_reference(
        q, L(kv_k), L(kv_v), pt, rs, rl, cl)
    got = ref_ops.ragged_attention(
        q, L(kv_k), L(kv_v), pt, rs, rl, cl, long_rows=long_rows)
    assert seen["tiles"] == 7  # for 4 tiles of real rows (21 and 7 tokens)
    assert np.isfinite(np.asarray(got)).all()
    _assert_real_rows_close(
        got, want, np.asarray(rs), np.asarray(rl), rtol=2e-3, atol=2e-3)
