"""JAX engine correctness tests (CPU, tiny model).

The key oracle: the paged-KV chunked/decode path must produce exactly the
same greedy tokens as a naive full-recompute forward pass.
"""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine import EngineConfig, JaxEngine
from dynamo_tpu.engine.kv_cache import PageAllocator, alloc_kv_arrays
from dynamo_tpu.engine.sampling import SamplingParams, sample
from dynamo_tpu.llm.protocols import PreprocessedRequest
from dynamo_tpu.models import llama
from dynamo_tpu.runtime.engine import Context

CFG = llama.LlamaConfig.tiny(dtype=jnp.float32)
PAGE = 8


@pytest.fixture(scope="module")
def params():
    return llama.init_params(CFG, jax.random.PRNGKey(0))


def naive_next_token(params, tokens):
    """Full recompute: forward the whole sequence in one un-paged pass."""
    n = len(tokens)
    pages = (n + PAGE - 1) // PAGE + 1
    kv_k, kv_v = alloc_kv_arrays(
        CFG.num_layers, pages, PAGE, CFG.num_kv_heads, CFG.head_dim, CFG.dtype
    )
    table = jnp.arange(pages, dtype=jnp.int32)
    logits, _, _ = llama.prefill_forward(
        params,
        CFG,
        jnp.asarray(tokens, jnp.int32),
        jnp.arange(n, dtype=jnp.int32),
        kv_k,
        kv_v,
        table,
        jnp.asarray(0, jnp.int32),
    )
    return int(jnp.argmax(logits))


def naive_logits(params, tokens):
    """Full-recompute logits at the last position (logprob oracle)."""
    n = len(tokens)
    pages = (n + PAGE - 1) // PAGE + 1
    kv_k, kv_v = alloc_kv_arrays(
        CFG.num_layers, pages, PAGE, CFG.num_kv_heads, CFG.head_dim, CFG.dtype
    )
    table = jnp.arange(pages, dtype=jnp.int32)
    logits, _, _ = llama.prefill_forward(
        params,
        CFG,
        jnp.asarray(tokens, jnp.int32),
        jnp.arange(n, dtype=jnp.int32),
        kv_k,
        kv_v,
        table,
        jnp.asarray(0, jnp.int32),
    )
    return logits


def test_greedy_decode_matches_full_recompute(params):
    """Engine (prefill once + paged decode steps) == naive recompute."""
    prompt = [5, 9, 17, 33, 101, 7, 250, 3]
    n_steps = 8

    # naive: extend one token at a time, full recompute each time
    naive_tokens = list(prompt)
    for _ in range(n_steps):
        naive_tokens.append(naive_next_token(params, naive_tokens))
    expected = naive_tokens[len(prompt) :]

    async def engine_run():
        cfg = EngineConfig(
            model="tiny",
            max_num_seqs=4,
            page_size=PAGE,
            num_pages=64,
            max_model_len=128,
            prefill_buckets=(16, 32),
            max_prefill_chunk=32,
        )
        eng = JaxEngine(cfg, model_config=CFG, params=params)
        req = PreprocessedRequest(
            token_ids=prompt,
            stop_conditions={"max_tokens": n_steps},
            request_id="parity",
        ).to_dict()
        toks = []
        async for item in eng.generate(req, Context()):
            data = item.get("data")
            if data:
                toks.extend(data["token_ids"])
        await eng.close()
        return toks

    got = asyncio.run(engine_run())
    assert got == expected, f"paged {got} != naive {expected}"


def test_chunked_prefill_matches_single_shot(params):
    """Chunked prefill (several small buckets) must give the same first
    token as processing the whole prompt in one chunk."""
    prompt = list(np.random.RandomState(7).randint(3, 500, size=50))
    expected_first = naive_next_token(params, prompt)

    async def run_with(bucket):
        cfg = EngineConfig(
            model="tiny",
            max_num_seqs=2,
            page_size=PAGE,
            num_pages=64,
            max_model_len=256,
            prefill_buckets=(bucket,),
            max_prefill_chunk=bucket,
        )
        eng = JaxEngine(cfg, model_config=CFG, params=params)
        req = PreprocessedRequest(
            token_ids=prompt, stop_conditions={"max_tokens": 1}, request_id="c"
        ).to_dict()
        toks = []
        async for item in eng.generate(req, Context()):
            if item.get("data"):
                toks.extend(item["data"]["token_ids"])
        await eng.close()
        return toks[0]

    assert asyncio.run(run_with(64)) == expected_first
    assert asyncio.run(run_with(16)) == expected_first  # 4 chunks


def test_concurrent_requests_and_prefix_cache(params):
    async def main():
        events = []
        cfg = EngineConfig(
            model="tiny",
            max_num_seqs=4,
            page_size=PAGE,
            num_pages=128,
            max_model_len=128,
            prefill_buckets=(16, 32),
        )
        eng = JaxEngine(cfg, model_config=CFG, params=params, event_sink=events.append)

        async def one(rid, prompt, n):
            req = PreprocessedRequest(
                token_ids=prompt, stop_conditions={"max_tokens": n}, request_id=rid
            ).to_dict()
            toks = []
            async for item in eng.generate(req, Context()):
                if item.get("data"):
                    toks.extend(item["data"]["token_ids"])
            return toks

        base = list(range(10, 10 + 24))  # 3 full pages
        r1, r2, r3 = await asyncio.gather(
            one("a", base, 4),
            one("b", base, 4),  # same prompt -> same greedy tokens
            one("c", list(range(200, 230)), 4),
        )
        assert r1 == r2
        assert len(r3) == 4
        stored = [e for e in events if e.event_type == "stored"]
        assert stored, "prefill must emit stored KV events"
        # identical prompts: the 3 prompt blocks stored only once
        all_stored = [h for e in stored for h in e.block_hashes]
        assert len(all_stored) == len(set(all_stored)), "duplicate stored hashes"

        # a fourth identical request should hit the prefix cache
        free_before = eng.allocator.free_pages
        r4 = await one("d", base, 2)
        assert r4 == r1[:2]
        await eng.close()

    asyncio.run(main())


def test_burst_same_prefix_reuses_inflight_blocks(params):
    """Concurrent same-prefix requests admitted BEFORE the first finishes
    must still reuse its prompt blocks: chunks commit incrementally at
    fetch time and waiting slots skip ahead over newly cached pages —
    with identical greedy output to independent runs."""

    async def main():
        cfg = EngineConfig(
            model="tiny",
            max_num_seqs=4,
            page_size=PAGE,
            num_pages=128,
            max_model_len=256,
            prefill_buckets=(16,),  # small chunks: many incremental commits
            max_prefill_chunk=16,
        )
        eng = JaxEngine(cfg, model_config=CFG, params=params)

        async def one(rid, prompt, n):
            req = PreprocessedRequest(
                token_ids=prompt,
                stop_conditions={"max_tokens": n, "ignore_eos": True},
                request_id=rid,
            ).to_dict()
            toks = []
            async for item in eng.generate(req, Context()):
                if item.get("data"):
                    toks.extend(item["data"]["token_ids"])
            return toks

        shared = list(range(10, 10 + 12 * PAGE))  # 12 pages of shared prefix
        p1 = shared + [301, 302, 303]
        p2 = shared + [401, 402, 403]

        solo1 = await one("s1", p1, 4)
        eng.allocator.clear_cache()
        hits_before = eng.allocator.prefix_hit_blocks_total
        t1 = asyncio.create_task(one("a", p1, 4))
        # stagger: B arrives while A is mid-prefill — after SOME of A's
        # chunks committed (incrementally, at fetch) but before A finished
        for _ in range(400):
            await asyncio.sleep(0.01)
            if eng.allocator._by_hash:
                break
        assert eng.allocator._by_hash, "no incremental chunk commits landed"
        slot_a = next(s for s in eng.slots if s is not None)
        assert slot_a.prefill_pos < len(p1), "A already finished; no overlap"
        t2 = asyncio.create_task(one("b", p2, 4))
        r1, r2 = await asyncio.gather(t1, t2)
        hits = eng.allocator.prefix_hit_blocks_total - hits_before
        await eng.close()
        assert r1 == solo1, "reuse changed greedy output"
        # B was admitted with only part of the prefix cached; the rest
        # must have been picked up mid-flight (skip-ahead over blocks A
        # committed after B's admission)
        assert hits > 0, "no in-flight prefix reuse in a same-prefix burst"

    asyncio.run(main())


def test_greedy_logprobs_match_full_recompute(params):
    """sampling_options.logprobs: every emitted token carries its
    raw-model logprob, equal to log_softmax of a naive full-recompute
    forward at that position (prefill first token AND fused-block decode
    steps)."""
    prompt = [5, 9, 17, 33, 101, 7, 250, 3]
    n_steps = 6

    async def main():
        cfg = EngineConfig(
            model="tiny", max_num_seqs=4, page_size=PAGE, num_pages=64,
            max_model_len=128, prefill_buckets=(16, 32),
        )
        eng = JaxEngine(cfg, model_config=CFG, params=params)
        req = PreprocessedRequest(
            token_ids=prompt,
            stop_conditions={"max_tokens": n_steps, "ignore_eos": True},
            sampling_options={"logprobs": True, "top_logprobs": 3},
            request_id="lp",
        ).to_dict()
        toks, lps, tops = [], [], []
        async for item in eng.generate(req, Context()):
            data = item.get("data")
            if data:
                toks.extend(data["token_ids"])
                lps.extend(data.get("log_probs") or [])
                tops.extend(data.get("top_logprobs") or [])
        await eng.close()
        return toks, lps, tops

    toks, lps, tops = asyncio.run(main())
    assert len(lps) == len(toks) == len(tops) == n_steps
    seq = list(prompt)
    for tok, lp, top in zip(toks, lps, tops):
        logits = naive_logits(params, seq)
        lsm = jax.nn.log_softmax(jnp.asarray(logits, jnp.float32))
        want = float(lsm[tok])
        assert abs(lp - want) < 2e-3, (tok, lp, want)
        # top-3 alternatives match the oracle's top-3 (greedy: top1 == tok)
        assert len(top["ids"]) == 3
        oracle_top = np.asarray(jnp.argsort(-lsm)[:3])
        assert top["ids"] == [int(x) for x in oracle_top], (
            top["ids"], oracle_top,
        )
        assert top["ids"][0] == tok
        for tid, tlp in zip(top["ids"], top["logprobs"]):
            assert abs(tlp - float(lsm[tid])) < 2e-3
        seq.append(tok)

    # without the flag: no log_probs on the wire
    async def plain():
        cfg = EngineConfig(
            model="tiny", max_num_seqs=4, page_size=PAGE, num_pages=64,
            max_model_len=128, prefill_buckets=(16, 32),
        )
        eng = JaxEngine(cfg, model_config=CFG, params=params)
        req = PreprocessedRequest(
            token_ids=prompt,
            stop_conditions={"max_tokens": 2, "ignore_eos": True},
            request_id="nolp",
        ).to_dict()
        outs = []
        async for item in eng.generate(req, Context()):
            if item.get("data"):
                outs.append(item["data"])
        await eng.close()
        return outs

    assert all("log_probs" not in o for o in asyncio.run(plain()))


def test_a_sampled_token_is_among_its_top_entries(params):
    """A top entry names the served token: where a hot sampler takes one
    outside the n most likely, the last of them gives way to it and to its
    own log-probability (first token and decode blocks alike)."""
    async def main():
        cfg = EngineConfig(
            model="tiny", max_num_seqs=4, page_size=PAGE, num_pages=64,
            max_model_len=128, prefill_buckets=(16, 32),
        )
        eng = JaxEngine(cfg, model_config=CFG, params=params)
        req = PreprocessedRequest(
            token_ids=[5, 9, 17, 33, 101, 7, 250, 3],
            stop_conditions={"max_tokens": 24, "ignore_eos": True},
            sampling_options={"logprobs": True, "top_logprobs": 2,
                              "temperature": 8.0, "seed": 11},
            request_id="hot",
        ).to_dict()
        toks, lps, tops = [], [], []
        async for item in eng.generate(req, Context()):
            data = item.get("data")
            if data:
                toks.extend(data["token_ids"])
                lps.extend(data.get("log_probs") or [])
                tops.extend(data.get("top_logprobs") or [])
        await eng.close()
        return toks, lps, tops

    toks, lps, tops = asyncio.run(main())
    assert len(toks) == len(lps) == len(tops) == 24
    gave_way = 0
    for tok, lp, top in zip(toks, lps, tops):
        assert len(set(top["ids"])) == 2 and tok in top["ids"], (tok, top)
        assert top["logprobs"][top["ids"].index(tok)] == pytest.approx(lp, abs=1e-5)
        gave_way += top["ids"][-1] == tok and top["logprobs"][0] > lp
    assert gave_way, "the sampler never left the two most likely: raise the heat"


def test_penalties_match_naive_oracle(params):
    """Greedy + penalties through the engine == naive full-recompute with
    apply_logit_penalties at every step (the penalties actually bite:
    outputs must differ from the unpenalized run)."""
    from dynamo_tpu.engine.sampling import apply_logit_penalties

    prompt = [5, 9, 17, 33, 101, 7, 250, 3]
    n_steps = 8
    pen = {"presence_penalty": 0.8, "frequency_penalty": 0.6,
           "repetition_penalty": 1.4}
    W = 64

    # oracle: naive recompute + penalty window over prompt+generated
    seq = list(prompt)
    expected = []
    for _ in range(n_steps):
        logits = np.asarray(naive_logits(params, seq), np.float32)
        recent = np.full((1, W), -1, np.int32)
        toks = np.asarray(seq[-W:], np.int32)
        ps = np.arange(len(seq) - len(toks), len(seq))
        recent[0, ps % W] = toks
        pl = np.asarray(apply_logit_penalties(
            jnp.asarray(logits[None]), jnp.asarray(recent),
            jnp.full((1,), pen["presence_penalty"], jnp.float32),
            jnp.full((1,), pen["frequency_penalty"], jnp.float32),
            jnp.full((1,), pen["repetition_penalty"], jnp.float32),
        ))[0]
        tok = int(np.argmax(pl))
        expected.append(tok)
        seq.append(tok)

    async def run(sampling):
        cfg = EngineConfig(
            model="tiny", max_num_seqs=4, page_size=PAGE, num_pages=64,
            max_model_len=128, prefill_buckets=(16, 32), penalty_window=W,
        )
        eng = JaxEngine(cfg, model_config=CFG, params=params)
        req = PreprocessedRequest(
            token_ids=prompt,
            stop_conditions={"max_tokens": n_steps, "ignore_eos": True},
            sampling_options=sampling,
            request_id="p",
        ).to_dict()
        toks = []
        async for item in eng.generate(req, Context()):
            if item.get("data"):
                toks.extend(item["data"]["token_ids"])
        await eng.close()
        return toks

    got = asyncio.run(run(dict(pen)))
    plain = asyncio.run(run({}))
    assert got == expected, f"penalized {got} != oracle {expected}"
    assert got != plain, "penalties had no effect on a repetitive prompt"

    # logprobs stay RAW-model even when penalties shaped the sampling
    # distribution (the documented guarantee)
    async def run_lp():
        cfg = EngineConfig(
            model="tiny", max_num_seqs=4, page_size=PAGE, num_pages=64,
            max_model_len=128, prefill_buckets=(16, 32), penalty_window=W,
        )
        eng = JaxEngine(cfg, model_config=CFG, params=params)
        req = PreprocessedRequest(
            token_ids=prompt,
            stop_conditions={"max_tokens": 4, "ignore_eos": True},
            sampling_options={**pen, "logprobs": True},
            request_id="plp",
        ).to_dict()
        toks, lps = [], []
        async for item in eng.generate(req, Context()):
            if item.get("data"):
                toks.extend(item["data"]["token_ids"])
                lps.extend(item["data"].get("log_probs") or [])
        await eng.close()
        return toks, lps

    toks, lps = asyncio.run(run_lp())
    seq = list(prompt)
    for tok, lp in zip(toks, lps):
        raw = jax.nn.log_softmax(
            jnp.asarray(naive_logits(params, seq), jnp.float32)
        )
        assert abs(lp - float(raw[tok])) < 2e-3, (tok, lp, float(raw[tok]))
        seq.append(tok)


def test_seeded_sampling_batch_independent(params):
    """A seeded request reproduces its output EXACTLY regardless of what
    it was co-batched with (counter-based per-lane draws keyed on
    (seed, position) — sampling.py SamplingParams.seed). Unseeded
    concurrent identical requests must still diverge."""

    prompt = [5, 9, 17, 33, 101, 7]

    def mk():
        return JaxEngine(EngineConfig(
            model="tiny", max_num_seqs=4, page_size=PAGE, num_pages=128,
            max_model_len=256, prefill_buckets=(16, 32),
        ), model_config=CFG, params=params)

    async def run(eng, rid, seed, with_noise=False, prompt_=None):
        async def one(r, p, s):
            req = PreprocessedRequest(
                token_ids=p,
                stop_conditions={"max_tokens": 10, "ignore_eos": True},
                sampling_options={"temperature": 1.0,
                                  **({"seed": s} if s is not None else {})},
                request_id=r,
            ).to_dict()
            toks = []
            async for item in eng.generate(req, Context()):
                if item.get("data"):
                    toks.extend(item["data"]["token_ids"])
            return toks

        tasks = [one(rid, prompt_ or prompt, seed)]
        if with_noise:
            tasks += [one(f"noise{i}", list(range(40 + i, 70 + i)), None)
                      for i in range(2)]
        return (await asyncio.gather(*tasks))[0]

    async def main():
        e1 = mk()
        alone = await run(e1, "a", 1234)
        await e1.close()
        e2 = mk()
        batched = await run(e2, "b", 1234, with_noise=True)
        other_seed = await run(e2, "c", 99)
        unseeded = await asyncio.gather(
            run(e2, "u1", None), run(e2, "u2", None)
        )
        await e2.close()
        assert alone == batched, "seeded output changed under co-batching"
        assert alone != other_seed, "different seeds gave identical output"
        assert unseeded[0] != unseeded[1], (
            "unseeded concurrent identical requests must diverge (n>1)"
        )

    asyncio.run(main())


def test_cancellation_releases_pages(params):
    async def main():
        cfg = EngineConfig(
            model="tiny",
            max_num_seqs=2,
            page_size=PAGE,
            num_pages=64,
            max_model_len=128,
            prefill_buckets=(16,),
        )
        eng = JaxEngine(cfg, model_config=CFG, params=params)
        ctx = Context()
        req = PreprocessedRequest(
            token_ids=list(range(12)),
            stop_conditions={"max_tokens": 1000},
            request_id="cancel",
        ).to_dict()
        got = 0
        async for item in eng.generate(req, ctx):
            if item.get("data"):
                got += 1
                if got == 3:
                    ctx.stop_generating()
        assert 3 <= got < 1000
        await asyncio.sleep(0.05)
        assert eng.allocator.active_pages == 0
        assert all(s is None for s in eng.slots)
        await eng.close()

    asyncio.run(main())


def test_model_len_boundary_with_fused_blocks(params):
    """A request with prompt+max_tokens == max_model_len must complete
    cleanly: fused-block speculation past the bound routes writes to the
    scratch page instead of overflowing the page table (regression: the
    K-step lookahead raised IndexError in _grow_pages_for_block and
    _fail_all errored every live request)."""

    async def main():
        cfg = EngineConfig(
            model="tiny", max_num_seqs=2, page_size=8, num_pages=16,
            max_model_len=32, prefill_buckets=(16,), decode_block_steps=4,
        )
        eng = JaxEngine(cfg, model_config=CFG, params=params)
        req = PreprocessedRequest(
            token_ids=list(range(10, 26)),  # 16 tokens, max_tokens -> 16
            stop_conditions={"max_tokens": 16, "ignore_eos": True},
            request_id="edge",
        ).to_dict()
        toks = []
        finish = None
        async for item in eng.generate(req, Context()):
            data = item.get("data")
            assert item.get("error") is None, item
            if data:
                toks.extend(data["token_ids"])
                finish = data.get("finish_reason") or finish
        await eng.close()
        return toks, finish

    toks, finish = asyncio.run(main())
    assert len(toks) == 16
    assert finish == "length"


def test_preemption_requeue_completes_all(params):
    """Over-subscribe the page pool: the engine must preempt (not truncate)
    and every request must still produce its full, correct output.
    Reference semantics: mocker scheduler watermark eviction + requeue
    (lib/llm/src/mocker/scheduler.rs:240)."""
    prompts = [
        list(range(10, 26)),
        list(range(60, 76)),
        list(range(120, 136)),
    ]
    n_gen = 24

    # oracle: run each request alone with ample pages
    async def alone(prompt):
        cfg = EngineConfig(
            model="tiny", max_num_seqs=1, page_size=PAGE, num_pages=64,
            max_model_len=128, prefill_buckets=(16,), decode_block_steps=4,
            enable_prefix_caching=False,
        )
        eng = JaxEngine(cfg, model_config=CFG, params=params)
        req = PreprocessedRequest(
            token_ids=prompt,
            stop_conditions={"max_tokens": n_gen, "ignore_eos": True},
            request_id="solo",
        ).to_dict()
        toks = []
        async for item in eng.generate(req, Context()):
            if item.get("data"):
                toks.extend(item["data"]["token_ids"])
        await eng.close()
        return toks

    expected = [asyncio.run(alone(p)) for p in prompts]
    assert all(len(e) == n_gen for e in expected)

    async def contended():
        # each seq needs (16 prompt + 24 gen + pending) / 8 ≈ 6 pages
        # -> 3 seqs need ~18; give 13 so at least one preemption must happen
        cfg = EngineConfig(
            model="tiny", max_num_seqs=4, page_size=PAGE, num_pages=13,
            max_model_len=128, prefill_buckets=(16,), decode_block_steps=4,
            enable_prefix_caching=False,
        )
        eng = JaxEngine(cfg, model_config=CFG, params=params)

        async def one(rid, prompt):
            req = PreprocessedRequest(
                token_ids=prompt,
                stop_conditions={"max_tokens": n_gen, "ignore_eos": True},
                request_id=rid,
            ).to_dict()
            toks = []
            async for item in eng.generate(req, Context()):
                if item.get("data"):
                    toks.extend(item["data"]["token_ids"])
            return toks

        results = await asyncio.gather(*[one(f"r{i}", p) for i, p in enumerate(prompts)])
        n_preempt = eng.num_preemptions
        await eng.close()
        return results, n_preempt

    got, n_preempt = asyncio.run(contended())
    assert n_preempt > 0, "test must actually exercise preemption"
    for i, (g, e) in enumerate(zip(got, expected)):
        assert g == e, f"req {i}: preempted run {g} != solo run {e}"


def test_sampling_determinism_and_topk():
    logits = jnp.asarray(np.random.RandomState(0).randn(2, 100).astype(np.float32))
    key = jax.random.PRNGKey(0)
    # greedy
    samp = SamplingParams.full(2, temperature=0.0)
    toks = sample(logits, samp, key)
    assert (np.asarray(toks) == np.asarray(jnp.argmax(logits, -1))).all()
    # top_k=1 == greedy even with temperature
    samp = SamplingParams.full(2, temperature=1.0, top_k=1)
    toks = sample(logits, samp, key)
    assert (np.asarray(toks) == np.asarray(jnp.argmax(logits, -1))).all()
    # temperature sampling stays within top-k set
    samp = SamplingParams.full(2, temperature=2.0, top_k=5)
    top5 = np.asarray(jax.lax.top_k(logits, 5)[1])
    for i in range(50):
        t = np.asarray(sample(logits, samp, jax.random.PRNGKey(i)))
        assert t[0] in top5[0] and t[1] in top5[1]


def test_moe_family_greedy_parity():
    """The engine serves the MoE (mixtral) family: paged decode must match
    the naive full-recompute forward, same oracle as the dense test."""
    from dynamo_tpu.models import moe

    mcfg = moe.MoeConfig.tiny_moe(dtype=jnp.float32, capacity_factor=8.0)
    mparams = moe.init_params(mcfg, jax.random.PRNGKey(3))
    prompt = [4, 8, 15, 16, 23, 42, 99, 7]
    n_steps = 4

    def naive_next(tokens):
        n = len(tokens)
        pages = (n + PAGE - 1) // PAGE + 1
        kv_k, kv_v = alloc_kv_arrays(
            mcfg.num_layers, pages, PAGE, mcfg.num_kv_heads, mcfg.head_dim, mcfg.dtype
        )
        table = jnp.arange(pages, dtype=jnp.int32)
        logits, _, _ = moe.prefill_forward(
            mparams, mcfg,
            jnp.asarray(tokens, jnp.int32), jnp.arange(n, dtype=jnp.int32),
            kv_k, kv_v, table, jnp.asarray(0, jnp.int32),
        )
        return int(jnp.argmax(logits))

    naive_tokens = list(prompt)
    for _ in range(n_steps):
        naive_tokens.append(naive_next(naive_tokens))
    expected = naive_tokens[len(prompt):]

    async def engine_run():
        cfg = EngineConfig(
            model="tiny-moe",
            max_num_seqs=4,
            page_size=PAGE,
            num_pages=64,
            max_model_len=128,
            prefill_buckets=(16,),
            max_prefill_chunk=16,
        )
        eng = JaxEngine(cfg, model_config=mcfg, params=mparams)
        assert eng._model._module is moe
        req = PreprocessedRequest(
            token_ids=prompt,
            stop_conditions={"max_tokens": n_steps},
            request_id="moe-parity",
        ).to_dict()
        toks = []
        async for item in eng.generate(req, Context()):
            data = item.get("data")
            if data:
                toks.extend(data["token_ids"])
        await eng.close()
        return toks

    got = asyncio.run(engine_run())
    assert got == expected, f"moe paged {got} != naive {expected}"


def test_moe_resolve_registry():
    from dynamo_tpu.engine.engine import _resolve_model
    from dynamo_tpu.models import moe

    assert isinstance(_resolve_model("tiny-moe"), moe.MoeConfig)
    assert isinstance(_resolve_model("mixtral-8x7b"), moe.MoeConfig)


def test_gptoss_shaped_registry_resolves_and_steps():
    """The gpt-oss-120b-shaped wide-MoE config (BASELINE config 5) resolves
    from the registry and one decode step runs at reduced layer count."""
    from dynamo_tpu.engine.engine import _resolve_model
    from dynamo_tpu.models import moe

    cfg = _resolve_model("gptoss-120b")
    assert isinstance(cfg, moe.MoeConfig)
    assert cfg.num_experts == 128 and cfg.num_experts_per_tok == 4

    import jax
    import jax.numpy as jnp

    small = moe.MoeConfig.gptoss_120b(
        num_layers=1, hidden_size=64, intermediate_size=64, num_heads=4,
        num_kv_heads=2, head_dim=16, vocab_size=512, num_experts=8,
        num_experts_per_tok=2, dtype=jnp.float32,
    )
    p = moe.init_params(small, jax.random.PRNGKey(0))
    kv_k = jnp.zeros((1, 8, 8, 2 * 16), jnp.float32)
    kv_v = jnp.zeros_like(kv_k)
    logits, _, _ = moe.decode_forward(
        p, small, jnp.zeros((2,), jnp.int32), jnp.zeros((2,), jnp.int32),
        kv_k, kv_v, jnp.ones((2, 4), jnp.int32), jnp.ones((2,), jnp.int32),
    )
    assert logits.shape == (2, 512)


def test_kv_headwise_shard_guard():
    """The per-shard multi-host KV transfer can only reassemble pools
    host-sharded on the lane axis (whole kv heads); any other host-sharded
    axis must be detected so the engine falls back to the inline allgather
    transfer instead of silently corrupting KV (advisor r3 finding)."""
    from types import SimpleNamespace

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from dynamo_tpu.engine.engine import JaxEngine

    devs = np.array(jax.devices()[:4]).reshape(2, 2)
    mesh = Mesh(devs, ("dp", "tp"))
    pool = jnp.zeros((2, 8, 4, 4 * 8), jnp.float32)  # [L, pages, page, KH*D]

    def check(spec):
        arr = jax.device_put(pool, NamedSharding(mesh, spec))
        return JaxEngine._kv_headwise_shards_ok(SimpleNamespace(kv_k=arr))

    assert check(P(None, None, None, "tp"))  # kv-head blocks sharded: ok
    assert check(P(None, None, None, ("dp", "tp")))  # both axes on KH*D: ok
    assert check(P())  # fully replicated: ok
    assert not check(P(None, "dp", None, "tp"))  # pages sharded: reject
    assert not check(P("tp", None, None, None))  # layers sharded: reject
