"""Multi-LoRA serving: delta math, PEFT loading, per-lane engine
correctness, and adapter-salted KV separation.

Reference contract: lora_id in the block-hash protocol
(lib/llm/src/kv_router/protocols.rs:110-115) — two adapters sharing a
text prefix must never share KV; adapter execution itself is native to
the JAX engine here (models/lora.py stacked A/B deltas).
"""

import asyncio
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine import EngineConfig, JaxEngine
from dynamo_tpu.llm.protocols import PreprocessedRequest
from dynamo_tpu.models import llama, lora
from dynamo_tpu.runtime.engine import Context

CFG = llama.LlamaConfig.tiny(dtype=jnp.float32)
PAGE = 8


@pytest.fixture(scope="module")
def params():
    return llama.init_params(CFG, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def adapters():
    return [
        lora.init_adapter(CFG, "ad1", jax.random.PRNGKey(101), rank=4),
        lora.init_adapter(CFG, "ad2", jax.random.PRNGKey(202), rank=4),
    ]


def test_lora_delta_matches_dense():
    rng = np.random.RandomState(0)
    B, din, dout, r, N = 3, 16, 24, 4, 3
    h = jnp.asarray(rng.randn(B, din).astype(np.float32))
    A = jnp.asarray(rng.randn(N, din, r).astype(np.float32))
    Bm = jnp.asarray(rng.randn(N, r, dout).astype(np.float32))
    scale = jnp.asarray([1.0, 2.0, 0.5], jnp.float32)
    idx = jnp.asarray([2, 0, 1], jnp.int32)
    got = np.asarray(lora.lora_delta(h, A, Bm, idx, scale))
    for b in range(B):
        i = int(idx[b])
        want = float(scale[i]) * (
            np.asarray(h[b]) @ np.asarray(A[i]) @ np.asarray(Bm[i])
        )
        np.testing.assert_allclose(got[b], want, atol=1e-4)
    # 3D (prefill) path
    h3 = jnp.asarray(rng.randn(B, 5, din).astype(np.float32))
    got3 = np.asarray(lora.lora_delta(h3, A, Bm, idx, scale))
    for b in range(B):
        i = int(idx[b])
        want = float(scale[i]) * (
            np.asarray(h3[b]) @ np.asarray(A[i]) @ np.asarray(Bm[i])
        )
        np.testing.assert_allclose(got3[b], want, atol=1e-4)


def test_stack_adapters_zero_slot(adapters):
    stack = lora.stack_adapters(CFG, adapters)
    assert stack["names"] == {"ad1": 1, "ad2": 2}
    for t, arr in stack["a"].items():
        assert np.asarray(arr[0]).max() == 0.0  # slot 0 = base no-op


def _mk_ragged_pack(rows, page_size=PAGE, seed=9):
    """Flat ragged pack for llama.ragged_forward: rows = [(row_len, ctx)],
    tile-aligned starts, per-row disjoint page tables, random pool KV for
    the decode rows' pre-existing context."""
    rng = np.random.RandomState(seed)
    c = CFG
    align = 8
    starts, lens, ctxs = [], [], []
    off = 0
    for (length, ctx) in rows:
        starts.append(off)
        lens.append(length)
        ctxs.append(ctx)
        off += -(-length // align) * align
    N = max(off, align)
    R = len(rows)
    max_pages = max(
        (ctx + length + page_size - 1) // page_size for length, ctx in rows
    ) + 1
    pages = 1 + R * max_pages  # page 0 = scratch
    kv_k = jnp.asarray(
        rng.randn(c.num_layers, pages, page_size,
                  c.num_kv_heads * c.head_dim).astype(np.float32))
    kv_v = jnp.asarray(
        rng.randn(c.num_layers, pages, page_size,
                  c.num_kv_heads * c.head_dim).astype(np.float32))
    pt = np.arange(1, pages, dtype=np.int32).reshape(R, max_pages)
    BIG = pt.shape[1] * page_size  # pad positions -> scratch page route
    tokens = np.zeros(N, np.int32)
    positions = np.full(N, BIG, np.int32)
    row_ids = np.zeros(N, np.int32)
    last_flat = np.zeros(R, np.int32)
    for r, (s, l, ctx) in enumerate(zip(starts, lens, ctxs)):
        tokens[s:s + l] = rng.randint(5, c.vocab_size - 1, size=l)
        positions[s:s + l] = np.arange(ctx, ctx + l)
        row_ids[s:s + l] = r
        last_flat[r] = s + l - 1
    return (
        jnp.asarray(tokens), jnp.asarray(positions), jnp.asarray(row_ids),
        kv_k, kv_v, jnp.asarray(pt),
        jnp.asarray(np.array(starts, np.int32)),
        jnp.asarray(np.array(lens, np.int32)),
        jnp.asarray(np.array(ctxs, np.int32)),
        jnp.asarray(last_flat),
    )


def test_ragged_forward_per_row_adapter_routing(params, adapters):
    """The fused mixed step's multi-LoRA contract at the model layer:
    per-row idx 0 rows are byte-identical to the lora=None forward (slot
    0 = exact no-op), and every idx>0 row matches the forward where ALL
    rows carry that adapter (row outputs depend only on their own idx —
    disjoint pages, no cross-row leak)."""
    rows = [(8, 0), (1, 5), (1, 9), (5, 0)]  # chunks + decode singletons
    pack = _mk_ragged_pack(rows)
    stack = lora.stack_adapters(CFG, adapters)

    def run(idx):
        ld = None if idx is None else dict(
            stack, idx=jnp.asarray(np.array(idx, np.int32)))
        logits, _, _ = llama.ragged_forward(params, CFG, *pack, lora=ld)
        return np.asarray(logits)

    base = run(None)
    np.testing.assert_array_equal(run([0, 0, 0, 0]), base)
    mix = run([1, 0, 2, 1])
    all1, all2 = run([1, 1, 1, 1]), run([2, 2, 2, 2])
    np.testing.assert_array_equal(mix[1], base[1])
    np.testing.assert_array_equal(mix[0], all1[0])
    np.testing.assert_array_equal(mix[3], all1[3])
    np.testing.assert_array_equal(mix[2], all2[2])
    # the adapters are not accidental no-ops
    assert not np.array_equal(all1, base)
    assert not np.array_equal(all2, base)


def test_peft_roundtrip(tmp_path):
    """Write a PEFT-format export, load it, and check the delta numbers."""
    r, alpha = 4, 8.0
    dims = lora.target_dims(CFG)
    state = {}
    rng = np.random.RandomState(7)
    for li in range(CFG.num_layers):
        for peft_t, t in (("q_proj", "wq"), ("v_proj", "wv")):
            din, dout = dims[t]
            state[
                f"base_model.model.model.layers.{li}.self_attn.{peft_t}.lora_A.weight"
            ] = rng.randn(r, din).astype(np.float32)
            state[
                f"base_model.model.model.layers.{li}.self_attn.{peft_t}.lora_B.weight"
            ] = rng.randn(dout, r).astype(np.float32)
    from safetensors.numpy import save_file

    d = tmp_path / "peft_ad"
    d.mkdir()
    save_file(state, str(d / "adapter_model.safetensors"))
    (d / "adapter_config.json").write_text(
        json.dumps({"r": r, "lora_alpha": alpha})
    )
    ad = lora.load_peft_adapter(str(d), CFG, name="mine")
    assert ad.scale == alpha / r
    assert set(ad.a) == {"wq", "wv"}
    # PEFT A [r, in] -> ours [in, r]
    want = state[
        "base_model.model.model.layers.0.self_attn.q_proj.lora_A.weight"
    ].T
    np.testing.assert_allclose(np.asarray(ad.a["wq"][0]), want, atol=1e-6)


def _engine(params, adapters=None, **kw):
    cfg = EngineConfig(
        model="tiny", max_num_seqs=4, page_size=PAGE, num_pages=64,
        max_model_len=256, prefill_buckets=(16, 32), max_prefill_chunk=32,
        **kw,
    )
    events = []
    eng = JaxEngine(cfg, model_config=CFG, params=params,
                    event_sink=events.append)
    if adapters:
        eng.register_adapters(adapters)
    return eng, events


async def _run_one(eng, prompt, rid, lora_name=None, n=8, guided=None):
    req = PreprocessedRequest(
        token_ids=list(prompt),
        stop_conditions={"max_tokens": n,
                         **({} if guided else {"ignore_eos": True})},
        sampling_options={"temperature": 1.0} if guided else {},
        eos_token_ids=[2] if guided else [],  # ByteTokenizer.EOS
        lora_name=lora_name,
        guided=guided,
        request_id=rid,
    ).to_dict()
    toks = []
    async for item in eng.generate(req, Context()):
        data = item.get("data")
        if data:
            toks.extend(data["token_ids"])
        if item.get("event") == "error":
            raise RuntimeError(item.get("comment"))
    return toks


PROMPT = [5, 9, 17, 33, 101, 7, 250, 3]


def test_adapter_changes_output_and_base_unchanged(params, adapters):
    async def main():
        base_eng, _ = _engine(params)
        base = await _run_one(base_eng, PROMPT, "b")
        await base_eng.close()

        eng, _ = _engine(params, adapters)
        still_base = await _run_one(eng, PROMPT, "b2")
        with_ad = await _run_one(eng, PROMPT, "a1", lora_name="ad1")
        await eng.close()
        assert still_base == base, "registered-but-unselected stack must be a no-op"
        assert with_ad != base, "adapter must change greedy output"

    asyncio.run(main())


def test_two_adapters_concurrent_match_solo(params, adapters):
    """The per-lane contract: each adapter's output in a MIXED batch equals
    its solo run — lanes never leak deltas into each other."""

    async def main():
        eng, _ = _engine(params, adapters)
        solo1 = await _run_one(eng, PROMPT, "s1", lora_name="ad1")
        solo2 = await _run_one(eng, PROMPT, "s2", lora_name="ad2")
        solo0 = await _run_one(eng, PROMPT, "s0")
        both = await asyncio.gather(
            _run_one(eng, PROMPT, "c1", lora_name="ad1"),
            _run_one(eng, PROMPT, "c2", lora_name="ad2"),
            _run_one(eng, PROMPT, "c0"),
        )
        await eng.close()
        assert both[0] == solo1
        assert both[1] == solo2
        assert both[2] == solo0
        assert len({tuple(solo0), tuple(solo1), tuple(solo2)}) == 3

    asyncio.run(main())


def test_adapter_kv_never_cross_pollinates(params, adapters):
    """Same prompt under two adapters: the engine's KV events must carry
    DISJOINT block hashes (the router/prefix-cache key), and each run's
    output must be independent of cache state the other left behind."""

    async def main():
        eng, events = _engine(params, adapters, enable_prefix_caching=True)
        prompt = list(range(5, 5 + 3 * PAGE))  # 3 full blocks
        a_first = await _run_one(eng, prompt, "a", lora_name="ad1")
        hashes_a = {
            h for ev in events for h in getattr(ev, "block_hashes", [])
        }
        events.clear()
        b_after_a = await _run_one(eng, prompt, "b", lora_name="ad2")
        hashes_b = {
            h for ev in events for h in getattr(ev, "block_hashes", [])
        }
        await eng.close()

        # fresh engine: ad2 with a cold cache must match ad2 after ad1
        eng2, _ = _engine(params, adapters, enable_prefix_caching=True)
        b_cold = await _run_one(eng2, prompt, "bc", lora_name="ad2")
        await eng2.close()

        assert hashes_a and hashes_b
        assert hashes_a.isdisjoint(hashes_b), "adapters shared block hashes"
        assert b_after_a == b_cold, "adapter KV cross-pollinated via cache"

    asyncio.run(main())


def test_lora_lane_correct_while_guided_inflight(params, adapters):
    """A guided request and a LoRA request decoding CONCURRENTLY: the LoRA
    lane must still produce its solo output (the guided single-step path
    must carry the adapter deltas, not fall back to base weights)."""

    async def main():
        eng, _ = _engine(params, adapters)
        solo = await _run_one(eng, PROMPT, "s", lora_name="ad1", n=12)
        mixed = await asyncio.gather(
            _run_one(eng, PROMPT, "m1", lora_name="ad1", n=12),
            _run_one(eng, [8, 8, 8], "mg", lora_name=None, n=24,
                     guided={"kind": "choice", "choices": ["yes", "no"]}),
        )
        await eng.close()
        assert mixed[0] == solo, "guided in-flight perturbed the LoRA lane"
        from dynamo_tpu.llm.tokenizers import ByteTokenizer

        assert ByteTokenizer(CFG.vocab_size).decode(mixed[1]) in ("yes", "no")

    asyncio.run(main())


def test_unknown_adapter_rejected(params, adapters):
    async def main():
        eng, _ = _engine(params, adapters)
        with pytest.raises(RuntimeError, match="unknown LoRA adapter"):
            await _run_one(eng, PROMPT, "x", lora_name="nope")
        await eng.close()

    asyncio.run(main())
