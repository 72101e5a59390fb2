"""KVBM tier tests: storage units + engine-integrated offload/onboard.

Oracle for the e2e case: greedy tokens after a G1 eviction + KVBM onboard
must equal the tokens from the original (fully computed) run.
"""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine import EngineConfig, JaxEngine
from dynamo_tpu.kvbm import DiskTier, HostTier, KvBlockManager, KvbmConfig
from dynamo_tpu.llm.protocols import PreprocessedRequest
from dynamo_tpu.models import llama
from dynamo_tpu.runtime.engine import Context

CFG = llama.LlamaConfig.tiny(dtype=jnp.float32)
PAGE = 8
BLOCK_SHAPE = (2, 4, 2, 4)  # layers, page, heads, dim


def _blk(seed):
    r = np.random.RandomState(seed)
    return (
        r.randn(*BLOCK_SHAPE).astype(np.float32),
        r.randn(*BLOCK_SHAPE).astype(np.float32),
    )


def test_host_tier_lru_eviction():
    tier = HostTier(2, BLOCK_SHAPE, np.float32)
    k1, v1 = _blk(1)
    k2, v2 = _blk(2)
    k3, v3 = _blk(3)
    assert tier.put(100, k1, v1) is None
    assert tier.put(200, k2, v2) is None
    tier.get(100)  # touch: 200 becomes LRU
    evicted = tier.put(300, k3, v3)
    assert evicted is not None and evicted[0] == 200
    np.testing.assert_array_equal(evicted[1], k2)
    assert tier.has(100) and tier.has(300) and not tier.has(200)
    got = tier.get(100)
    np.testing.assert_array_equal(got[0], k1)
    np.testing.assert_array_equal(got[1], v1)


def test_disk_tier_roundtrip(tmp_path):
    tier = DiskTier(2, BLOCK_SHAPE, np.float32, str(tmp_path / "g3"))
    k1, v1 = _blk(1)
    assert tier.put(7, k1, v1) is None
    got = tier.get(7)
    np.testing.assert_array_equal(got[0], k1)
    np.testing.assert_array_equal(got[1], v1)
    # capacity 2: third insert drops LRU
    tier.put(8, *_blk(2))
    tier.get(7)  # 8 becomes LRU
    dropped = tier.put(9, *_blk(3))
    assert dropped == 8
    tier.flush()
    assert (tmp_path / "g3" / "index.json").exists()


def test_disk_tier_warm_restart(tmp_path):
    """flush() + re-open must restore the index and block contents
    (reference: G3 tiers persist KV blocks for reuse, offload.rs)."""
    path = str(tmp_path / "g3")
    tier = DiskTier(4, BLOCK_SHAPE, np.float32, path)
    k1, v1 = _blk(11)
    k2, v2 = _blk(12)
    tier.put(111, k1, v1)
    tier.put(222, k2, v2)
    tier.flush()
    reopened = DiskTier(4, BLOCK_SHAPE, np.float32, path)
    assert reopened.has(111) and reopened.has(222)
    got = reopened.get(111)
    np.testing.assert_array_equal(got[0], k1)
    np.testing.assert_array_equal(got[1], v1)
    # capacity/shape mismatch -> cold start, no crash
    cold = DiskTier(8, BLOCK_SHAPE, np.float32, path)
    assert len(cold) == 0


def test_manager_cascade_host_to_disk(tmp_path):
    mgr = KvBlockManager(
        KvbmConfig(host_blocks=2, disk_blocks=4, disk_path=str(tmp_path / "g3")),
        BLOCK_SHAPE,
        np.float32,
    )
    blocks = {h: _blk(h) for h in (1, 2, 3, 4)}
    for h, (k, v) in blocks.items():
        mgr.store(h, k, v)
    # host holds the 2 most recent; older ones cascaded to disk
    assert len(mgr.host) == 2
    assert len(mgr.disk) == 2
    assert mgr.disk_evictions == 2
    assert mgr.match_prefix([1, 2, 3, 4]) == [1, 2, 3, 4]
    assert mgr.match_prefix([1, 99, 3]) == [1]
    # load from disk promotes back to host and keeps contents intact
    k_np, v_np = mgr.load_blocks([1, 2])
    np.testing.assert_array_equal(k_np[0], blocks[1][0])
    np.testing.assert_array_equal(v_np[1], blocks[2][1])
    assert mgr.onboarded_blocks == 2


# --------------------------------------------------------------------- #
# Quantized KV blocks (DYN_KV_QUANT, docs/kvbm.md "Quantized KV format"):
# tiers store PACKED uint8 rows (q bytes + per-page-per-head scales)
# natively, so G2/G3 roundtrips must be byte-exact — dequantization
# happens exactly once, on the device, never on a tier hop.
# --------------------------------------------------------------------- #


def _quant_block(seed, mode="int8"):
    """One packed quantized block's (k, v) rows [L, PAGE_BYTES] uint8,
    produced by the SAME host layout the engine's offload gather uses."""
    from dynamo_tpu.ops.kv_quant import (
        alloc_kv_store, extract_pages, host_pack_pages, kv_write,
    )

    L, ps, KH, D = BLOCK_SHAPE
    r = np.random.RandomState(seed)
    st_k = alloc_kv_store(L, 2, ps, KH, D, jnp.float32, mode)
    st_v = alloc_kv_store(L, 2, ps, KH, D, jnp.float32, mode)
    phys = jnp.asarray(np.full(ps, 1, np.int32))
    offs = jnp.asarray(np.arange(ps, dtype=np.int32))
    for li in range(L):
        st_k = kv_write(st_k, li, phys, offs,
                        jnp.asarray(r.randn(ps, KH, D).astype(np.float32)))
        st_v = kv_write(st_v, li, phys, offs,
                        jnp.asarray(r.randn(ps, KH, D).astype(np.float32)))
    ids = jnp.asarray([1])
    ex_k = extract_pages(st_k, ids, KH)
    ex_v = extract_pages(st_v, ids, KH)
    return host_pack_pages(ex_k)[:, 0], host_pack_pages(ex_v)[:, 0]


# --------------------------------------------------------------------- #
# The lane-dense pool (PR 26): [L, pages, rows, KH*D] in HBM, while every
# tier and wire hop still carries pages as [L, n, rows, KH, D] — the same
# bytes in the same order. A page written by the model's scatter must come
# back identical by every way out of the pool and back in.
# --------------------------------------------------------------------- #


def _via_accessor(k_pages, v_pages, tmp_path):
    return k_pages, v_pages


def _via_kvbm_offload_onboard(k_pages, v_pages, tmp_path):
    """G2 store -> eviction to the G3 disk tier -> load (onboard)."""
    mgr = KvBlockManager(
        KvbmConfig(host_blocks=1, disk_blocks=4, disk_path=str(tmp_path / "g3")),
        k_pages.shape[:1] + k_pages.shape[2:], np.float32,
    )
    for i in range(k_pages.shape[1]):
        mgr.store(100 + i, k_pages[:, i], v_pages[:, i])
    assert len(mgr.disk) == k_pages.shape[1] - 1
    k_np, v_np = mgr.load_blocks([100 + i for i in range(k_pages.shape[1])])
    return np.stack(k_np, axis=1), np.stack(v_np, axis=1)


def _via_kv_transfer_payload(k_pages, v_pages, tmp_path):
    """The disagg wire: header + raw bytes, through msgpack."""
    import msgpack

    from dynamo_tpu.llm.disagg import pack_kv_payload, unpack_kv_payload

    payload = pack_kv_payload(k_pages, v_pages, 3 * 4, 4)
    assert payload["shape"] == [2, 3, 4, 2, 4]  # [L, n, page, KH, D] as ever
    wire = msgpack.unpackb(msgpack.packb(payload, use_bin_type=True), raw=False)
    k, v, _ = unpack_kv_payload(wire)
    return k, v


@pytest.mark.parametrize(
    "hop", [_via_accessor, _via_kvbm_offload_onboard, _via_kv_transfer_payload],
    ids=["accessor", "kvbm_offload_onboard", "kv_transfer_payload"],
)
def test_page_written_by_the_scatter_reads_back_identically(hop, tmp_path):
    from dynamo_tpu.ops.kv_quant import (
        alloc_kv_store, extract_pages, gather_dequant, inject_pages,
        kv_layer, kv_write,
    )

    L, ps, KH, D = BLOCK_SHAPE
    r = np.random.RandomState(7)
    vals = r.randn(2, L, 3 * ps, KH, D).astype(np.float32)  # K/V, 3 pages
    pools = [alloc_kv_store(L, 8, ps, KH, D, jnp.float32, "none") for _ in "kv"]
    assert pools[0].shape == (L, 8, ps, KH * D)
    pages = np.array([5, 2, 6], np.int32)
    phys = jnp.asarray(np.repeat(pages, ps))
    offs = jnp.asarray(np.tile(np.arange(ps, dtype=np.int32), 3))
    for li in range(L):  # the model's scatter, layer by layer
        pools = [
            kv_write(pool, li, phys, offs, jnp.asarray(vals[i, li]))
            for i, pool in enumerate(pools)
        ]
    want = vals.reshape(2, L, 3, ps, KH, D)
    # the XLA reference path's accessor, per layer
    for li in range(L):
        got = gather_dequant(kv_layer(pools[0], li), jnp.asarray(pages), D)
        np.testing.assert_array_equal(np.asarray(got), want[0, li])
    # out of the pool: pages as the tiers and the wire carry them
    k_pages = np.asarray(extract_pages(pools[0], jnp.asarray(pages), KH))
    v_pages = np.asarray(extract_pages(pools[1], jnp.asarray(pages), KH))
    assert k_pages.shape == (L, 3, ps, KH, D)
    assert k_pages.tobytes() == want[0].tobytes()
    k_back, v_back = hop(k_pages, v_pages, tmp_path)
    # and back into another pool, at other pages
    dest = jnp.asarray([1, 7, 3])
    fresh = [alloc_kv_store(L, 8, ps, KH, D, jnp.float32, "none") for _ in "kv"]
    k_new = inject_pages(fresh[0], dest, jnp.asarray(k_back))
    v_new = inject_pages(fresh[1], dest, jnp.asarray(v_back))
    np.testing.assert_array_equal(np.asarray(k_new[:, dest]), np.asarray(pools[0][:, pages]))
    np.testing.assert_array_equal(np.asarray(v_new[:, dest]), np.asarray(pools[1][:, pages]))
    np.testing.assert_array_equal(
        np.asarray(extract_pages(v_new, dest, KH)), want[1]
    )


@pytest.mark.parametrize("mode", ["int8", "int4"])
def test_quantized_blocks_roundtrip_g2_g3_byte_exact(mode, tmp_path):
    """store -> host-tier eviction -> disk cascade -> load: every packed
    byte (ints AND scales) must survive unchanged."""
    from dynamo_tpu.ops.kv_quant import kv_page_bytes

    L, ps, KH, D = BLOCK_SHAPE
    pb = kv_page_bytes(ps, KH, D, jnp.float32, mode)
    shape = (L, pb)
    mgr = KvBlockManager(
        KvbmConfig(host_blocks=2, disk_blocks=4,
                   disk_path=str(tmp_path / "g3")),
        shape, np.uint8, kv_format=mode,
    )
    assert mgr.kv_format == mode
    blocks = {h: _quant_block(h, mode) for h in (1, 2, 3, 4)}
    for h, (k, v) in blocks.items():
        assert k.shape == shape and k.dtype == np.uint8
        mgr.store(h, k, v)
    # 1 and 2 cascaded to disk; all four must load back byte-exact
    assert len(mgr.disk) == 2
    k_np, v_np = mgr.load_blocks([1, 2, 3, 4])
    for i, h in enumerate([1, 2, 3, 4]):
        np.testing.assert_array_equal(k_np[i], blocks[h][0])
        np.testing.assert_array_equal(v_np[i], blocks[h][1])
    # and the packed rows decode to the same ints/scales they encoded
    from dynamo_tpu.ops.kv_quant import host_unpack_pages

    q1, s1 = host_unpack_pages(k_np[0], mode, ps, KH, D)
    q2, s2 = host_unpack_pages(blocks[1][0], mode, ps, KH, D)
    np.testing.assert_array_equal(q1, q2)
    np.testing.assert_array_equal(s1, s2)


@pytest.mark.parametrize("mode", ["int8", "int4"])
def test_quantized_disk_warm_restart_byte_exact(mode, tmp_path):
    from dynamo_tpu.ops.kv_quant import kv_page_bytes

    L, ps, KH, D = BLOCK_SHAPE
    shape = (L, kv_page_bytes(ps, KH, D, jnp.float32, mode))
    path = str(tmp_path / "g3")
    tier = DiskTier(4, shape, np.uint8, path)
    k1, v1 = _quant_block(31, mode)
    tier.put(111, k1, v1)
    tier.flush()
    reopened = DiskTier(4, shape, np.uint8, path)
    got = reopened.get(111)
    np.testing.assert_array_equal(got[0], k1)
    np.testing.assert_array_equal(got[1], v1)


@pytest.fixture(scope="module")
def params():
    return llama.init_params(CFG, jax.random.PRNGKey(0))


def _engine(params, tmp_path=None, host_blocks=0, disk_blocks=0, num_pages=16):
    cfg = EngineConfig(
        model="tiny",
        max_num_seqs=2,
        page_size=PAGE,
        num_pages=num_pages,
        max_model_len=128,
        prefill_buckets=(16, 32),
        max_prefill_chunk=32,
        kvbm_host_blocks=host_blocks,
        kvbm_disk_blocks=disk_blocks,
        kvbm_disk_path=str(tmp_path / "g3") if tmp_path else None,
    )
    return JaxEngine(cfg, model_config=CFG, params=params)


async def _gen(eng, prompt, n, rid):
    req = PreprocessedRequest(
        token_ids=prompt, stop_conditions={"max_tokens": n}, request_id=rid
    ).to_dict()
    toks = []
    async for item in eng.generate(req, Context()):
        if item.get("data"):
            toks.extend(item["data"]["token_ids"])
    return toks


def test_engine_offload_and_onboard(params):
    """Fill G1, evict via competing traffic, re-issue the first prompt:
    the prefix must come back from the host tier (onboard), and greedy
    tokens must match the original run exactly."""

    async def main():
        eng = _engine(params, host_blocks=32, num_pages=8)
        base = list(range(10, 10 + 3 * PAGE))  # 3 full pages
        first = await _gen(eng, base, 4, "a")
        await _drain_offloads(eng)
        assert eng.kvbm.manager.offloaded_blocks >= 3

        # competing traffic evicts base's pages from the 8-page device pool
        for i in range(4):
            await _gen(eng, list(range(300 + 40 * i, 300 + 40 * i + 3 * PAGE)), 2, f"f{i}")
        await _drain_offloads(eng)
        assert len(eng.allocator.cached_prefix([h for h in _hashes(base)])) < 3, (
            "device cache should have evicted at least part of the base prefix"
        )

        onboarded_before = eng.kvbm.manager.onboarded_blocks
        again = await _gen(eng, base, 4, "b")
        assert again == first
        assert eng.kvbm.manager.onboarded_blocks > onboarded_before, (
            "re-issued prompt must onboard from the host tier"
        )
        await eng.close()

    asyncio.run(main())


def test_engine_onboard_from_disk(params, tmp_path):
    """Host tier of 2 blocks + disk tier: blocks cascade to disk and still
    onboard correctly."""

    async def main():
        eng = _engine(params, tmp_path, host_blocks=2, disk_blocks=32, num_pages=8)
        base = list(range(10, 10 + 3 * PAGE))
        first = await _gen(eng, base, 4, "a")
        await _drain_offloads(eng)
        for i in range(4):
            await _gen(eng, list(range(300 + 40 * i, 300 + 40 * i + 3 * PAGE)), 2, f"f{i}")
        await _drain_offloads(eng)
        assert len(eng.kvbm.manager.disk) > 0, "cascade to disk expected"
        again = await _gen(eng, base, 4, "b")
        assert again == first
        assert eng.kvbm.manager.onboarded_blocks >= 3
        await eng.close()

    asyncio.run(main())


@pytest.mark.parametrize("mode", ["int8", "int4"])
def test_engine_quantized_offload_onboard_roundtrip(params, mode):
    """The e2e density path: a quantized engine offloads packed blocks,
    competing traffic evicts G1, and the re-issued prompt onboards the
    SAME packed bytes — greedy tokens must match the original quantized
    run exactly (the onboard injects identical ints+scales)."""

    async def main():
        cfg = EngineConfig(
            model="tiny", max_num_seqs=2, page_size=PAGE, num_pages=8,
            max_model_len=128, prefill_buckets=(16, 32),
            max_prefill_chunk=32, kvbm_host_blocks=32, kv_quant=mode,
        )
        eng = JaxEngine(cfg, model_config=CFG, params=params)
        assert eng.kvbm.manager.kv_format == mode
        assert eng.kvbm.manager.dtype == np.dtype(np.uint8)
        base = list(range(10, 10 + 3 * PAGE))
        first = await _gen(eng, base, 4, "a")
        await _drain_offloads(eng)
        assert eng.kvbm.manager.offloaded_blocks >= 3
        for i in range(4):
            await _gen(eng, list(range(300 + 40 * i, 300 + 40 * i + 3 * PAGE)),
                       2, f"f{i}")
        await _drain_offloads(eng)
        onboarded_before = eng.kvbm.manager.onboarded_blocks
        again = await _gen(eng, base, 4, "b")
        assert again == first
        assert eng.kvbm.manager.onboarded_blocks > onboarded_before
        await eng.close()

    asyncio.run(main())


def test_kv_quant_none_arm_is_byte_identical(params):
    """Quant off == exact seed behavior: kv_quant="none" (and the
    DYN_KV_QUANT-unset default) must produce byte-identical token streams
    — the fp path compiles the very same scatter/gather programs."""

    async def run(kv_quant):
        cfg = EngineConfig(
            model="tiny", max_num_seqs=2, page_size=PAGE, num_pages=16,
            max_model_len=128, prefill_buckets=(16, 32),
            max_prefill_chunk=32, kv_quant=kv_quant,
        )
        eng = JaxEngine(cfg, model_config=CFG, params=params)
        toks = await _gen(eng, list(range(10, 10 + 2 * PAGE + 3)), 6, "n")
        await eng.close()
        return toks

    assert asyncio.run(run("none")) == asyncio.run(run(None))


def test_kvbm_disabled_by_default(params):
    async def main():
        eng = _engine(params)
        assert eng.kvbm is None
        toks = await _gen(eng, list(range(10, 26)), 2, "x")
        assert len(toks) == 2
        await eng.close()

    asyncio.run(main())


def _hashes(prompt):
    from dynamo_tpu.llm.tokens import TokenBlockSequence

    return TokenBlockSequence(prompt, PAGE).block_hashes()


async def _drain_offloads(eng):
    """Flush + wait out the offload pipeline (staged pairs and queued
    batches)."""
    if eng.kvbm is None:
        return
    eng.kvbm.flush_step()
    for _ in range(300):
        if eng.kvbm.pending_offloads() == 0:
            return
        await asyncio.sleep(0.01)
    raise TimeoutError("offloads did not drain")


# ------------------------------------------------------------------ #
# eviction policies (storage seam; DYN_KVBM_EVICTION)
# ------------------------------------------------------------------ #


def test_lfu_eviction_prefers_cold_blocks():
    tier = HostTier(2, BLOCK_SHAPE, np.float32, policy="lfu")
    tier.put(1, *_blk(1))
    tier.put(2, *_blk(2))
    tier.get(1)
    tier.get(1)  # 1 is hot (freq 3), 2 cold (freq 1)
    evicted = tier.put(3, *_blk(3))
    assert evicted is not None and evicted[0] == 2
    assert tier.has(1) and tier.has(3)


def test_prefix_aware_protects_interior_blocks():
    tier = HostTier(2, BLOCK_SHAPE, np.float32, policy="prefix-aware")
    tier.put(1, *_blk(1))
    tier.put(2, *_blk(2), parent=1)
    # 1 is LRU-oldest but has live descendant 2 in-pool: the leaf goes
    evicted = tier.put(3, *_blk(3))
    assert evicted is not None and evicted[0] == 2
    assert tier.has(1) and tier.has(3)
    # with 2 gone, 1 is a leaf again and evictable
    evicted = tier.put(4, *_blk(4))
    assert evicted[0] == 1


def test_lfu_heap_compacts_on_hit_heavy_workload():
    """The lazy LFU heap grows one entry per touch and only eviction
    pops: without compaction a hit-heavy tier whose working set fits in
    capacity leaks heap entries forever."""
    tier = HostTier(4, BLOCK_SHAPE, np.float32, policy="lfu")
    tier.put(1, *_blk(1))
    tier.put(2, *_blk(2))
    for _ in range(5000):
        tier.get(1)
    assert len(tier._heap) <= max(4 * tier.capacity, 64) + 1
    # compaction kept the live ordering: 2 is still the coldest victim
    tier.put(3, *_blk(3))
    tier.put(4, *_blk(4))
    evicted = tier.put(5, *_blk(5))
    assert evicted is not None and evicted[0] == 2


def test_eviction_spec_parsing():
    from dynamo_tpu.kvbm.manager import _parse_eviction

    assert _parse_eviction("lfu") == ("lfu", "lfu")
    assert _parse_eviction("host=lfu,disk=prefix-aware") == ("lfu", "prefix-aware")
    assert _parse_eviction("bogus") == ("lru", "lru")  # typo never fatal
    assert _parse_eviction("host=bogus") == ("lru", "lru")


@pytest.mark.parametrize("policy", ["lru", "lfu", "prefix-aware"])
def test_eviction_policy_invariants_fuzz(policy):
    """All policies preserve the pool invariants under random
    put/get/clear sequences: capacity respected, slots partition exactly,
    recency tracks membership, retrievals return exact bytes."""
    rng = np.random.RandomState(7)
    cap = 4
    tier = HostTier(cap, BLOCK_SHAPE, np.float32, policy=policy)
    for _ in range(400):
        op = rng.rand()
        h = int(rng.randint(1, 12))
        if op < 0.62:
            parent = h - 1 if h > 1 and rng.rand() < 0.5 else None
            tier.put(h, *_blk(h), parent=parent)
        elif op < 0.94:
            got = tier.get(h)
            if got is not None:
                np.testing.assert_array_equal(got[0], _blk(h)[0])
                np.testing.assert_array_equal(got[1], _blk(h)[1])
        else:
            tier.clear()
        assert len(tier) <= cap
        used = set(tier._by_hash.values())
        assert len(used) == len(tier._by_hash), "slot aliasing"
        assert used.isdisjoint(tier._free)
        assert len(used) + len(tier._free) == cap, "slot leak"
        assert set(tier._lru) == set(tier._by_hash), "recency drift"
        # leaf index tracks exactly the in-pool childless blocks
        assert set(tier._leaves) == {
            h for h in tier._by_hash if not tier._children.get(h)
        }, "leaf-index drift"
    for h in list(tier._by_hash):
        got = tier.get(h)
        np.testing.assert_array_equal(got[0], _blk(h)[0])


# ------------------------------------------------------------------ #
# crash-consistent G3 index (temp file + atomic rename)
# ------------------------------------------------------------------ #


def test_disk_flush_crash_mid_write_keeps_old_index(tmp_path, monkeypatch):
    """A crash mid-flush must leave the PREVIOUS index intact: the new
    index lands via temp-file + atomic os.replace, never a partial
    overwrite of index.json."""
    import os as _os

    path = str(tmp_path / "g3")
    tier = DiskTier(4, BLOCK_SHAPE, np.float32, path)
    tier.put(1, *_blk(1))
    tier.flush()
    tier.put(2, *_blk(2))

    def boom(src, dst):
        raise OSError("killed mid-flush")

    monkeypatch.setattr(_os, "replace", boom)
    with pytest.raises(OSError):
        tier.flush()
    monkeypatch.undo()
    # the torn flush left index.json.tmp behind but index.json is the
    # pre-crash version: warm restart sees block 1, not a corrupt file
    reopened = DiskTier(4, BLOCK_SHAPE, np.float32, path)
    assert reopened.has(1)
    got = reopened.get(1)
    np.testing.assert_array_equal(got[0], _blk(1)[0])
    # and the next clean flush supersedes the leftover temp file
    reopened.put(3, *_blk(3))
    reopened.flush()
    again = DiskTier(4, BLOCK_SHAPE, np.float32, path)
    assert again.has(1) and again.has(3)


# ------------------------------------------------------------------ #
# offload pipeline: batched gather -> bounded queue -> tier thread
# ------------------------------------------------------------------ #


class _FakeEngine:
    """Minimal engine surface KvbmConnector needs: jitted-gather stand-in,
    the serial device executor, and the _timed wrapper."""

    def __init__(self, n_pages=64):
        import concurrent.futures

        r = np.random.RandomState(3)
        # [layers, pages, page, heads, dim]
        self.kv_k = r.randn(2, n_pages, 4, 2, 4).astype(np.float32)
        self.kv_v = r.randn(2, n_pages, 4, 2, 4).astype(np.float32)
        self._device_exec = concurrent.futures.ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="fake-jax-step"
        )
        self.dev_calls = 0

    def _extract_pages(self, k, v, ids):
        ids = np.asarray(ids)
        self.dev_calls += 1
        return k[:, ids], v[:, ids]

    def _timed(self, fn, tag, shape=None):
        return fn


def _mk_connector(tmp_path=None, host_blocks=16, queue_env=None, monkeypatch=None):
    from dynamo_tpu.kvbm import KvBlockManager, KvbmConfig, KvbmConnector

    if queue_env is not None:
        monkeypatch.setenv("DYN_KVBM_OFFLOAD_QUEUE", str(queue_env))
    eng = _FakeEngine()
    mgr = KvBlockManager(
        KvbmConfig(host_blocks=host_blocks), (2, 4, 2, 4), np.float32
    )
    return eng, KvbmConnector(eng, mgr)


def test_pipeline_coalesces_stages_into_one_gather(monkeypatch):
    """Multiple offload_commit calls in one step become ONE device gather
    at flush_step, and the stored bytes match the gathered pages."""
    eng, conn = _mk_connector(monkeypatch=monkeypatch)
    conn.offload_commit([101, 102], [3, 4])
    conn.offload_commit([103], [5], parent=102)
    assert eng.dev_calls == 0  # nothing hits the device until the flush
    conn.flush_step()
    assert conn.drain(5.0)
    assert eng.dev_calls == 1
    assert conn.offload_gathers == 1
    assert conn.offload_commit_calls == 2
    assert conn.manager.has(101) and conn.manager.has(103)
    got_k, _ = conn.manager.load_blocks([102])
    np.testing.assert_array_equal(got_k[0], eng.kv_k[:, 4])
    # chain parents reached the tier (prefix-aware bookkeeping)
    assert conn.manager.host._parent.get(102) == 101
    assert conn.manager.host._parent.get(103) == 102
    conn.shutdown()


@pytest.mark.parametrize("steps", [(3, 1, 0, 2), (1, 1, 1)])
def test_one_gather_a_step_however_many_commits(steps, monkeypatch):
    """`offload_gathers` (and the device's gathers) rise by one per
    `flush_step()` that has something staged, not per `offload_commit`;
    a step that committed nothing gathers nothing."""
    eng, conn = _mk_connector(host_blocks=32, monkeypatch=monkeypatch)
    h = 700
    for n_step, commits in enumerate(steps):
        before = conn.offload_gathers
        for _ in range(commits):
            conn.offload_commit([h], [2 + h % 32])
            h += 1
        conn.flush_step()
        assert conn.offload_gathers - before == (1 if commits else 0), n_step
    assert conn.drain(5.0)
    assert conn.offload_commit_calls == sum(steps)
    assert eng.dev_calls == conn.offload_gathers == sum(1 for c in steps if c)
    assert len(conn.manager.host) == sum(steps)
    conn.shutdown()


@pytest.mark.parametrize("held_here", [0, 2], ids=["all_remote", "local_head"])
def test_remote_onboard_promotes_through_the_tier_queue(held_here, monkeypatch):
    """Blocks pulled from a peer at admission are handed to
    `stage_promotion` (a READY batch on the kvbm-tier thread, parents
    chained); the onboard itself stores nothing: `run` carries only the
    local tier's read."""
    eng, conn = _mk_connector(monkeypatch=monkeypatch)
    hashes = [901, 902, 903, 904]
    blocks = {h: _blk(h) for h in hashes}
    for i, h in enumerate(hashes[:held_here]):
        conn.manager.store(h, *blocks[h], parent=hashes[i - 1] if i else None)
    pulled, promoted, ran = [], [], []

    class _Dist:
        checkpointer = None

        async def pull_blocks(self, remote, hint_instance=None):
            pulled.append(list(remote))
            return (np.stack([blocks[h][0] for h in remote]),
                    np.stack([blocks[h][1] for h in remote]))

        def announce_threadsafe(self, *a, **k):
            pass

    conn.distributed = _Dist()
    stage = conn.stage_promotion

    def spy(hs, parents, k, v):
        promoted.append((list(hs), list(parents)))
        stage(hs, parents, k, v)

    monkeypatch.setattr(conn, "stage_promotion", spy)

    async def run(fn, *a):
        ran.append(fn)
        return fn(*a)

    ks, vs = asyncio.run(conn.load_async(hashes, run))
    remote = hashes[held_here:]
    assert pulled == [remote]
    assert promoted == [(remote, [None, *hashes][held_here:held_here + len(remote)])]
    assert ran == ([conn.manager.load_blocks] if held_here else [])
    np.testing.assert_array_equal(ks, np.stack([blocks[h][0] for h in hashes]))
    np.testing.assert_array_equal(vs, np.stack([blocks[h][1] for h in hashes]))
    assert conn.drain(5.0)
    assert all(conn.manager.has(h) for h in hashes)
    assert conn.offload_gathers == 0 and eng.dev_calls == 0
    conn.shutdown()


def test_engine_gathers_once_a_step_under_concurrent_commits(params):
    """Through JaxEngine: three prompts admitted together commit their
    blocks in the same steps, and every `_step_once` ends in one
    `flush_step()`: gathers never outnumber flushes, and commits
    outnumber gathers."""
    async def main():
        eng = _engine(params, host_blocks=64, num_pages=64)
        flushes = []
        flush = eng.kvbm.flush_step

        def counted():
            before = eng.kvbm.offload_gathers
            flush()
            flushes.append(eng.kvbm.offload_gathers - before)

        eng.kvbm.flush_step = counted
        prompts = [list(range(20 + 50 * i, 20 + 50 * i + 3 * PAGE)) for i in range(3)]
        await asyncio.gather(*[
            _gen(eng, p, 10, f"c{i}") for i, p in enumerate(prompts)])
        await _drain_offloads(eng)
        st = eng.stats()
        await eng.close()
        return flushes, st

    flushes, st = asyncio.run(main())
    assert set(flushes) <= {0, 1}
    assert st["kvbm_offload_gathers"] == sum(flushes) >= 1
    assert st["kvbm_offload_commit_calls"] > st["kvbm_offload_gathers"]
    assert st["kvbm_offloaded_blocks"] >= 3 * 3
    assert st["kvbm_offload_blocks_dropped"] == 0


def test_pipeline_backpressure_drops_oldest(monkeypatch):
    """With the in-flight queue capped at 1 and a slow tier thread, newer
    flushes evict the OLDEST queued batch — counted, never blocking."""
    from dynamo_tpu.runtime import faults

    eng, conn = _mk_connector(queue_env=1, monkeypatch=monkeypatch)
    faults.configure("kvbm.offload:delay,times=50")
    try:
        for i in range(5):
            conn.offload_commit([500 + i], [2 + i])
            conn.flush_step()
        assert conn.drain(10.0)
    finally:
        faults.reset()
    stats = conn.stats()
    assert stats["kvbm_offload_batches_dropped"] >= 1
    assert stats["kvbm_offload_blocks_dropped"] >= 1
    # accounting is clean after the dust settles: nothing stuck in flight
    assert conn.pending_offloads() == 0
    with conn._offload_cv:
        assert not conn._inflight_hashes
    # dropped + stored partition the 5 staged blocks
    assert len(conn.manager.host) + stats["kvbm_offload_blocks_dropped"] == 5
    conn.shutdown()


def test_chaos_offload_error_drops_batch_never_stream(params):
    """dynochaos kvbm.offload error: every offload batch dies on the tier
    thread, yet generation streams are untouched — offload is strictly a
    cache write (ISSUE 10 / ROADMAP 3 chaos coverage)."""
    from dynamo_tpu.runtime import faults

    async def main():
        eng = _engine(params, host_blocks=32, num_pages=16)
        faults.configure("kvbm.offload:error,times=100")
        try:
            base = list(range(10, 10 + 3 * PAGE))
            first = await _gen(eng, base, 4, "a")
            assert len(first) == 4
            await _drain_offloads(eng)
            st = eng.kvbm.stats()
            assert st["kvbm_offload_failures"] >= 1
            assert len(eng.kvbm.manager.host) == 0  # every batch dropped
            # the engine keeps serving; once the plan exhausts, offloads heal
            second = await _gen(eng, base, 4, "b")
            assert second == first
        finally:
            faults.reset()
        await eng.close()

    asyncio.run(main())


def test_chaos_onboard_error_falls_back_to_full_prefill(params):
    """dynochaos kvbm.onboard error: the tier load fails at admission and
    the engine prefills the span instead — tokens identical, no hang."""
    from dynamo_tpu.runtime import faults

    async def main():
        eng = _engine(params, host_blocks=32, num_pages=8)
        base = list(range(10, 10 + 3 * PAGE))
        first = await _gen(eng, base, 4, "a")
        await _drain_offloads(eng)
        for i in range(4):
            await _gen(eng, list(range(300 + 40 * i, 300 + 40 * i + 3 * PAGE)), 2, f"f{i}")
        await _drain_offloads(eng)
        onboarded_before = eng.kvbm.manager.onboarded_blocks
        faults.configure("kvbm.onboard:error,times=1")
        try:
            again = await _gen(eng, base, 4, "b")
        finally:
            faults.reset()
        assert again == first
        assert eng.kvbm.manager.onboarded_blocks == onboarded_before, (
            "fallback must recompute, not load tiers"
        )
        await eng.close()

    asyncio.run(main())


def test_kvbm_on_off_token_parity(params):
    """KVBM is a latency optimization, never a semantics change: fifo
    token streams are byte-identical with tiers on vs off, including
    after G1 eviction forces tier onboarding."""

    async def run_suite(eng):
        out = []
        base = list(range(10, 10 + 3 * PAGE))
        out.append(await _gen(eng, base, 4, "a"))
        for i in range(4):
            out.append(
                await _gen(eng, list(range(300 + 40 * i, 300 + 40 * i + 3 * PAGE)), 2, f"f{i}")
            )
        out.append(await _gen(eng, base, 4, "b"))  # onboard vs recompute
        await eng.close()
        return out

    async def main():
        with_kvbm = await run_suite(_engine(params, host_blocks=32, num_pages=8))
        without = await run_suite(_engine(params, num_pages=8))
        assert with_kvbm == without

    asyncio.run(main())


def test_onboard_budget_falls_back_to_recompute(params):
    """Under DYN_SCHED_POLICY=sla, an onboard whose projected tier-load
    latency exceeds the slot's TTFT headroom is skipped in favor of
    recompute (docs/kvbm.md onboard budget); tokens stay identical."""

    async def main():
        cfg = EngineConfig(
            model="tiny", max_num_seqs=2, page_size=PAGE, num_pages=8,
            max_model_len=128, prefill_buckets=(16, 32), max_prefill_chunk=32,
            kvbm_host_blocks=32,
            sched_policy="sla", ttft_target_ms=1.0,
        )
        eng = JaxEngine(cfg, model_config=CFG, params=params)
        base = list(range(10, 10 + 3 * PAGE))
        first = await _gen(eng, base, 4, "a")
        await _drain_offloads(eng)
        for i in range(4):
            await _gen(eng, list(range(300 + 40 * i, 300 + 40 * i + 3 * PAGE)), 2, f"f{i}")
        await _drain_offloads(eng)
        # a (synthetically) slow host tier: any onboard estimate now dwarfs
        # the ~1ms TTFT headroom
        with eng.kvbm.manager._lock:
            eng.kvbm.manager._load_ms["host"] = 1000.0
        onboarded_before = eng.kvbm.manager.onboarded_blocks
        again = await _gen(eng, base, 4, "b")
        assert again == first
        assert eng.kvbm.stats()["kvbm_onboard_recompute_fallbacks"] >= 1
        assert eng.kvbm.manager.onboarded_blocks == onboarded_before
        await eng.close()

    asyncio.run(main())


def test_engine_stats_expose_tier_pipeline(params):
    async def main():
        eng = _engine(params, host_blocks=32, num_pages=8)
        base = list(range(10, 10 + 3 * PAGE))
        await _gen(eng, base, 4, "a")
        await _drain_offloads(eng)
        st = eng.stats()
        for key in (
            "kvbm_g1_hit_blocks", "kvbm_g1_miss_blocks", "kvbm_host_hits",
            "kvbm_host_misses", "kvbm_offload_gathers",
            "kvbm_offload_queue_depth", "kvbm_offload_blocks_dropped",
            "kvbm_onboard_hist", "kvbm_onboard_count",
        ):
            assert key in st, key
        assert st["kvbm_offload_gathers"] >= 1
        assert st["kvbm_g1_miss_blocks"] >= 3  # cold start prefilled the base
        await eng.close()

    asyncio.run(main())


class TestDistributedKvbm:
    def test_cross_worker_onboard_via_data_plane(self):
        """Worker A offloads committed blocks to its host tier and announces
        them; worker B's admission probes the mesh, pulls A's blocks over
        the data plane, onboards, and produces EXACTLY the greedy tokens A
        produced (reference distributed KVBM role, block_manager/
        distributed/leader.rs:126, worker.rs:137)."""
        from dynamo_tpu.kvbm import KvbmDistributed
        from dynamo_tpu.llm.kv_transfer import KvDataPlaneServer
        from dynamo_tpu.runtime import (
            DiscoveryServer,
            DistributedRuntime,
            RuntimeConfig,
        )

        params = llama.init_params(CFG, jax.random.PRNGKey(0))
        prompt = list(range(5, 45))  # 40 tokens = 5 full pages of 8

        def make_engine():
            return JaxEngine(
                EngineConfig(
                    model="tiny", max_num_seqs=4, page_size=PAGE, num_pages=64,
                    max_model_len=128, prefill_buckets=(16, 32),
                    max_prefill_chunk=32, kvbm_host_blocks=32,
                ),
                model_config=CFG, params=params,
            )

        async def run_one(engine, n_steps=6):
            req = PreprocessedRequest(
                token_ids=prompt, stop_conditions={"max_tokens": n_steps},
            ).to_dict()
            toks = []
            async for item in engine.generate(req, Context()):
                data = item.get("data")
                if data:
                    toks.extend(data["token_ids"])
            return toks

        async def main():
            server = DiscoveryServer(port=0)
            _, port = await server.start()
            cfg = RuntimeConfig(discovery_endpoint=f"127.0.0.1:{port}")
            drt_a = await DistributedRuntime.create(cfg)
            drt_b = await DistributedRuntime.create(cfg)

            eng_a, eng_b = make_engine(), make_engine()
            dists, planes = [], []
            for eng, drt in [(eng_a, drt_a), (eng_b, drt_b)]:
                dp = KvDataPlaneServer()
                await dp.start()
                await dp.register(drt)
                dist = KvbmDistributed(
                    drt, eng.kvbm, dp, "ns", "kvbm", drt.instance_id
                )
                await dist.start()
                dists.append(dist)
                planes.append(dp)
            dist_a, dist_b = dists
            dp_a, dp_b = planes

            want = await run_one(eng_a)  # A computes; offloads + announces
            for _ in range(200):
                await asyncio.sleep(0.02)
                if len(dist_b._owners) >= 5 and dist_b._addrs:
                    break
            assert len(dist_b._owners) >= 5, "announcements never mirrored"

            got = await run_one(eng_b)  # B onboards A's blocks remotely
            assert got == want
            assert dist_b.remote_blocks_pulled >= 5, dist_b.stats()
            assert dp_a.transfers_served >= 1
            # promotion: a THIRD run on a fresh engine sharing B's tiers
            # would hit locally — check B's tier now holds the blocks
            assert eng_b.kvbm.manager.match_prefix(
                list(dist_b._owners.keys())[:1]
            ) or len(eng_b.kvbm.manager.host) >= 5

            await eng_a.close()
            await eng_b.close()
            for d in dists:
                await d.close()
            for p in planes:
                await p.close()
            await drt_a.close()
            await drt_b.close()
            await server.stop()

        asyncio.run(main())
