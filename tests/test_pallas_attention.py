"""Pallas decode paged-attention kernel vs the XLA reference path.

Runs the kernel in interpreter mode on the CPU test mesh (conftest pins
JAX_PLATFORMS=cpu); on real TPU the same code compiles via Mosaic.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.ops import paged_attention as ref_ops
from dynamo_tpu.ops.pallas_paged_attention import paged_attention_decode_pallas

from .utils import kv_layer_case as L


def _mk_case(B=4, H=8, KH=4, D=32, pages=16, page_size=8, max_pages=6, seed=0):
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(B, H, D), jnp.float32)
    kv_k = jnp.asarray(rng.randn(pages, page_size, KH, D), jnp.float32)
    kv_v = jnp.asarray(rng.randn(pages, page_size, KH, D), jnp.float32)
    pt = jnp.asarray(
        rng.choice(pages, size=(B, max_pages), replace=False).astype(np.int32)
        if pages >= B * max_pages
        else rng.randint(0, pages, size=(B, max_pages)).astype(np.int32)
    )
    seq_lens = jnp.asarray(rng.randint(1, max_pages * page_size + 1, size=(B,)), jnp.int32)
    return q, kv_k, kv_v, pt, seq_lens


@pytest.mark.parametrize("seed", [0, 1])
def test_pallas_matches_xla(seed):
    q, kv_k, kv_v, pt, seq_lens = _mk_case(seed=seed)
    import os

    os.environ["DYNAMO_TPU_PAGED_ATTN"] = "xla"
    try:
        want = ref_ops.paged_attention_decode(q, L(kv_k), L(kv_v), pt, seq_lens)
    finally:
        os.environ.pop("DYNAMO_TPU_PAGED_ATTN", None)
    got = paged_attention_decode_pallas(q, L(kv_k), L(kv_v), pt, seq_lens, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-3, atol=2e-3)


def test_pallas_partial_page_and_len1():
    q, kv_k, kv_v, pt, _ = _mk_case(B=3, seed=2)
    seq_lens = jnp.asarray([1, 5, 13], jnp.int32)  # len 1, partial page, cross-page
    import os

    os.environ["DYNAMO_TPU_PAGED_ATTN"] = "xla"
    try:
        want = ref_ops.paged_attention_decode(q, L(kv_k), L(kv_v), pt, seq_lens)
    finally:
        os.environ.pop("DYNAMO_TPU_PAGED_ATTN", None)
    got = paged_attention_decode_pallas(q, L(kv_k), L(kv_v), pt, seq_lens, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-3, atol=2e-3)


def _mk_prefill_case(T=128, H=8, KH=4, D=32, page_size=8, start=0, real=None, seed=0):
    """Random paged cache + a page table big enough to cover the context
    (as the engine guarantees), matching the write-then-attend order."""
    rng = np.random.RandomState(seed)
    real = real if real is not None else T
    max_pages = (start + T + page_size - 1) // page_size + 2
    pages = max_pages + 8
    q = jnp.asarray(rng.randn(T, H, D), jnp.float32)
    kv_k = jnp.asarray(rng.randn(pages, page_size, KH, D), jnp.float32)
    kv_v = jnp.asarray(rng.randn(pages, page_size, KH, D), jnp.float32)
    pt = jnp.asarray(rng.choice(pages, size=(max_pages,), replace=False).astype(np.int32))
    return q, kv_k, kv_v, pt, start, start + real


@pytest.mark.parametrize(
    "T,start,real",
    [(128, 0, 128), (128, 64, 128), (256, 0, 200), (512, 128, 512), (128, 0, 1)],
)
def test_pallas_prefill_matches_xla(T, start, real):
    from dynamo_tpu.ops.pallas_prefill_attention import paged_prefill_attention_pallas

    q, kv_k, kv_v, pt, s, total = _mk_prefill_case(T=T, start=start, real=real, seed=T + start)
    positions = jnp.asarray(np.arange(s, s + T), jnp.int32)
    want = ref_ops.prefill_attention(
        q, None, None, L(kv_k), L(kv_v), positions, pt, jnp.asarray(s, jnp.int32)
    )
    got = paged_prefill_attention_pallas(
        q, L(kv_k), L(kv_v), pt, jnp.asarray(s, jnp.int32), jnp.asarray(total, jnp.int32),
        interpret=True,
    )
    # only the real (unpadded) rows must match; padded rows are discarded.
    # the XLA reference attends to ALL table positions <= q_pos (stale pages
    # included), the kernel only to positions < total_len — identical for
    # real rows since their q_pos < total_len.
    np.testing.assert_allclose(
        np.asarray(got)[:real], np.asarray(want)[:real], rtol=2e-3, atol=2e-3
    )


def test_pallas_prefill_bf16_gqa():
    from dynamo_tpu.ops.pallas_prefill_attention import paged_prefill_attention_pallas

    rng = np.random.RandomState(9)
    T, H, KH, D, pages, page_size, max_pages = 128, 8, 2, 64, 40, 16, 32
    q = jnp.asarray(rng.randn(T, H, D), jnp.bfloat16)
    kv_k = jnp.asarray(rng.randn(pages, page_size, KH, D), jnp.bfloat16)
    kv_v = jnp.asarray(rng.randn(pages, page_size, KH, D), jnp.bfloat16)
    pt = jnp.asarray(rng.choice(pages, size=(max_pages,), replace=False).astype(np.int32))
    start = 32
    positions = jnp.asarray(np.arange(start, start + T), jnp.int32)
    want = ref_ops.prefill_attention(
        q, None, None, L(kv_k), L(kv_v), positions, pt, jnp.asarray(start, jnp.int32)
    )
    got = paged_prefill_attention_pallas(
        q, L(kv_k), L(kv_v), pt, jnp.asarray(start, jnp.int32),
        jnp.asarray(start + T, jnp.int32), interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), rtol=5e-2, atol=5e-2
    )


def test_pallas_bf16_gqa():
    rng = np.random.RandomState(3)
    B, H, KH, D, pages, page_size, max_pages = 2, 8, 2, 64, 12, 16, 4
    q = jnp.asarray(rng.randn(B, H, D), jnp.bfloat16)
    kv_k = jnp.asarray(rng.randn(pages, page_size, KH, D), jnp.bfloat16)
    kv_v = jnp.asarray(rng.randn(pages, page_size, KH, D), jnp.bfloat16)
    pt = jnp.asarray(rng.randint(0, pages, size=(B, max_pages)), jnp.int32)
    seq_lens = jnp.asarray([17, 64], jnp.int32)
    import os

    os.environ["DYNAMO_TPU_PAGED_ATTN"] = "xla"
    try:
        want = ref_ops.paged_attention_decode(q, L(kv_k), L(kv_v), pt, seq_lens)
    finally:
        os.environ.pop("DYNAMO_TPU_PAGED_ATTN", None)
    got = paged_attention_decode_pallas(q, L(kv_k), L(kv_v), pt, seq_lens, interpret=True)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), rtol=5e-2, atol=5e-2
    )


# --------------------------------------------------------------------- #
# the page stream (PR 42): copies follow the lane's length, and a lane's
# first chunk is started by the lane before it
# --------------------------------------------------------------------- #

# lane lengths of a batch, from the page size and the chunk's positions
_STREAM_CASES = {
    "empty": lambda ps, c: [0],
    "one-position": lambda ps, c: [1],
    "one-page": lambda ps, c: [ps],
    "one-page-and-one": lambda ps, c: [ps + 1],
    "one-chunk": lambda ps, c: [c],
    "one-chunk-and-one": lambda ps, c: [c + 1],
    "three-chunks": lambda ps, c: [3 * c],
    "long-before-short": lambda ps, c: [2 * c + ps + 3, 5, c + 9],
    "empty-between-live": lambda ps, c: [ps + 7, 0, c - 3],
    "empty-first-and-last": lambda ps, c: [0, c + 2, 0, 0],
    "every-edge": lambda ps, c: [0, 1, ps, ps + 1, c, c + 1, 3 * c, 0, 2],
}


@pytest.mark.parametrize("heads", [(4, 2), (24, 8)], ids=["G2", "G3"])
@pytest.mark.parametrize("case", list(_STREAM_CASES))
def test_decode_streams_only_the_pages_a_lane_holds(case, heads):
    """Every pool page that no lane's first ceil(len / page_size) table
    entries name is NaN, and the table's entries past a lane's length
    point at such pages: the kernel copies none of them, nothing it did
    not copy reaches the sums, and live lanes agree with the XLA path
    (which gathers the whole table, so it is given the pool with zeros
    where the NaN is). An empty lane returns zeros."""
    from dynamo_tpu.ops.pallas_paged_attention import _chunk_positions

    H, KH = heads
    D, ps = 16, 64
    chunk = _chunk_positions(ps, KH * D)
    lens = _STREAM_CASES[case](ps, chunk)
    B = len(lens)
    held = [-(-n // ps) for n in lens]
    max_pages = max(max(held) + 2, chunk // ps + 1)  # never clamps the chunk
    poison = 3
    pages = sum(held) + poison
    rng = np.random.RandomState(len(case) + H)
    order = rng.permutation(pages)
    bad, good = order[:poison], list(order[poison:])
    pt = np.empty((B, max_pages), np.int32)
    for b in range(B):
        pt[b] = bad[rng.randint(0, poison, size=max_pages)]
        for lp in range(held[b]):
            pt[b, lp] = good.pop()
    clean_k = rng.randn(pages, ps, KH, D).astype(np.float32)
    clean_v = rng.randn(pages, ps, KH, D).astype(np.float32)
    clean_k[bad] = 0.0
    clean_v[bad] = 0.0
    nan_k, nan_v = clean_k.copy(), clean_v.copy()
    nan_k[bad] = np.nan
    nan_v[bad] = np.nan
    q = jnp.asarray(rng.randn(B, H, D), jnp.float32)
    pt, seq_lens = jnp.asarray(pt), jnp.asarray(lens, jnp.int32)

    want = _xla(
        ref_ops.paged_attention_decode, q, L(jnp.asarray(clean_k)),
        L(jnp.asarray(clean_v)), pt, seq_lens,
    )
    got = np.asarray(paged_attention_decode_pallas(
        q, L(jnp.asarray(nan_k)), L(jnp.asarray(nan_v)), pt, seq_lens,
        interpret=True,
    ))
    assert np.isfinite(got).all()
    live = np.asarray(lens) > 0
    np.testing.assert_allclose(
        got[live], np.asarray(want)[live], rtol=2e-3, atol=2e-3
    )
    assert not got[~live].any()


# --------------------------------------------------------------------- #
# whole pool + layer index (PR 26): the kernels are handed the pool as it
# lies in HBM, [L, pages, rows, KH*D], and DMA pool[li, page]
# --------------------------------------------------------------------- #


def _by_layer_decode(li, num_layers):
    q, kv_k, kv_v, pt, seq_lens = _mk_case(seed=21)
    want = _xla(ref_ops.paged_attention_decode, q, L(kv_k), L(kv_v), pt, seq_lens)
    got = paged_attention_decode_pallas(
        q, L(kv_k, li, num_layers), L(kv_v, li, num_layers, seed=1), pt,
        seq_lens, interpret=True,
    )
    return got, want, slice(None)


def _by_layer_prefill(li, num_layers):
    from dynamo_tpu.ops.pallas_prefill_attention import paged_prefill_attention_pallas

    q, kv_k, kv_v, pt, s, total = _mk_prefill_case(T=128, start=64, real=100, seed=24)
    positions = jnp.asarray(np.arange(s, s + 128), jnp.int32)
    want = ref_ops.prefill_attention(
        q, None, None, L(kv_k), L(kv_v), positions, pt, jnp.asarray(s, jnp.int32)
    )
    got = paged_prefill_attention_pallas(
        q, L(kv_k, li, num_layers), L(kv_v, li, num_layers, seed=1), pt,
        jnp.asarray(s, jnp.int32), jnp.asarray(total, jnp.int32), interpret=True,
    )
    return got, want, slice(0, 100)


def _by_layer_ragged(li, num_layers):
    from dynamo_tpu.ops.pallas_ragged_attention import ragged_paged_attention_pallas

    from .test_ragged_attention import MIX, _mk_ragged_case

    (q, kv_k, kv_v, pt, rs, rl, cl, starts, lens, _N) = _mk_ragged_case(MIX, seed=25)
    want = ref_ops.ragged_attention_reference(q, L(kv_k), L(kv_v), pt, rs, rl, cl)
    got = ragged_paged_attention_pallas(
        q, L(kv_k, li, num_layers), L(kv_v, li, num_layers, seed=1), pt,
        rs, rl, cl, interpret=True,
    )
    real = np.concatenate([np.arange(s, s + n) for s, n in zip(starts, lens)])
    return got, want, real


def _xla(fn, *args):
    import os

    os.environ["DYNAMO_TPU_PAGED_ATTN"] = "xla"
    try:
        return fn(*args)
    finally:
        os.environ.pop("DYNAMO_TPU_PAGED_ATTN", None)


@pytest.mark.parametrize("li", [0, 2, 4], ids=["first", "middle", "last"])
@pytest.mark.parametrize(
    "case",
    [_by_layer_decode, _by_layer_prefill, _by_layer_ragged],
    ids=["decode", "prefill", "ragged"],
)
def test_kernels_read_the_whole_pool_by_layer(case, li):
    """Every kernel, handed a five-layer pool whose other layers hold
    noise, agrees with the XLA reference of that layer alone."""
    got, want, rows = case(li, 5)
    np.testing.assert_allclose(
        np.asarray(got)[rows], np.asarray(want)[rows], rtol=2e-3, atol=2e-3
    )
