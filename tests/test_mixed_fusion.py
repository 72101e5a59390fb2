"""Fused blended traffic on the ragged unified dispatch (ISSUE 19).

The tentpole contract: guided, speculative, and multi-LoRA rows pack into
the SAME flat token buffer as plain prefill chunks and decode lanes, and
the streams stay byte-identical to the split path per kind (the PR 8
parity discipline). The split reference differs per kind:

  * guided / lora on a non-spec engine: `mixed_dispatch=False` runs the
    dedicated guided/lora split programs — fused must match bit-for-bit;
  * speculative: the fused verify rows must reproduce the plain seeded
    decode stream exactly (acceptance reorders WHEN tokens are computed,
    never WHAT comes out), so the reference is the non-spec plain engine;
  * guided / lora UNDER spec_mode: inadmissible pre-PR (the split spec
    lane can't serve them), so the reference is again the plain non-spec
    engine — fusion is what makes the combination servable at all.

Also here: the eligibility collapse (mm excludes only its OWN rows, with
starvation aging), and the adapter-tier chaos arm (`lora.onboard`).
"""

import asyncio

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


def _engine(params, adapters=None, mixed=True, spec=False, **over):
    kw = dict(
        model="tiny", max_num_seqs=4, page_size=PAGE, num_pages=128,
        max_model_len=256, prefill_buckets=(16, 32), max_prefill_chunk=32,
        mixed_dispatch=mixed,
    )
    if spec:
        kw.update(spec_mode="ngram", spec_rounds=2, spec_draft_len=3,
                  spec_ngram=2, spec_hist=128)
    kw.update(over)
    eng = JaxEngine(EngineConfig(**kw), model_config=CFG, params=params)
    if adapters:
        eng.register_adapters(adapters)
    return eng


async def _stream(eng, prompt, rid, n=12, ctx=None, stop=None, sampling=None,
                  eos=(), **req_kw):
    req = PreprocessedRequest(
        token_ids=list(prompt),
        stop_conditions={"max_tokens": n, "ignore_eos": not (eos or stop),
                         **(stop or {})},
        sampling_options=dict(sampling or {"temperature": 0.0}),
        eos_token_ids=list(eos), request_id=rid, **req_kw,
    ).to_dict()
    toks, finish = [], None
    async for item in eng.generate(req, ctx or Context()):
        data = item.get("data")
        if data:
            toks.extend(data["token_ids"])
            finish = data.get("finish_reason") or finish
    return toks, finish


async def _one(eng, prompt, rid, lora_name=None, guided=None, n=12,
               temperature=0.0, seed=None):
    sampling = {"temperature": temperature}
    if seed is not None:
        sampling["seed"] = seed
    toks, _ = await _stream(
        eng, prompt, rid, n=n, sampling=sampling,
        eos=[2] if guided else (),  # ByteTokenizer.EOS
        lora_name=lora_name, guided=guided,
    )
    return toks


def _blend_prompts():
    rng = np.random.RandomState(11)
    base = rng.randint(5, 200, size=7).tolist()
    return (
        (base * 5)[:30],                       # spec-friendly repetitive
        rng.randint(5, 200, size=24).tolist(),
        rng.randint(5, 200, size=20).tolist(),
    )


async def _staggered_blend(eng, with_spec_prompt=True):
    """plain + lora + guided arrive staggered so prefill chunks overlap
    live decode lanes — the shape that exercises the fused packer."""
    p1, p2, p3 = _blend_prompts()
    t1 = asyncio.create_task(_one(eng, p1, "plain", n=20))
    await asyncio.sleep(0.3)
    t2 = asyncio.create_task(_one(eng, p2, "lora", lora_name="ad1", n=16))
    await asyncio.sleep(0.3)
    t3 = asyncio.create_task(_one(
        eng, p3, "guided", n=18,
        guided={"kind": "choice", "choices": ["yes", "no"]},
    ))
    return await asyncio.gather(t1, t2, t3)


# --------------------------------------------------------------------- #
# per-kind byte-identical parity, fused vs split
# --------------------------------------------------------------------- #


def test_blended_guided_lora_fused_vs_split_byte_identical(params, adapters):
    """Non-spec engine: guided + lora + plain staggered traffic through
    the fused variant program == the split guided/lora programs, byte for
    byte, with mixed_steps > 0 and every kind counted on the fused path."""
    eng = _engine(params, adapters, mixed=True)
    fused = asyncio.run(_staggered_blend(eng))
    st = eng.stats()
    asyncio.run(eng.close())

    eng2 = _engine(params, adapters, mixed=False)
    split = asyncio.run(_staggered_blend(eng2))
    st2 = eng2.stats()
    asyncio.run(eng2.close())

    assert fused == split
    assert all(len(t) > 0 for t in fused)
    assert st["mixed_steps"] > 0
    assert st2["mixed_steps"] == 0
    assert st["mixed_rows_guided"] > 0
    assert st["mixed_rows_lora"] > 0
    assert st["mixed_coverage_frac"] > 0.0
    assert st["lora_pool_hits"] + st["lora_pool_misses"] > 0


async def _blend_beside_a_decoding_lane(eng, rounds=2):
    """A round: a plain stream decodes; once it has emitted, a LoRA request
    and then a guided one arrive and run to their ends beside it. Arrivals
    wait on an event, so each one's prefill chunk meets a live decode lane
    whatever the host's load."""
    rng = np.random.RandomState(5)
    out = []
    for r in range(rounds):
        started = asyncio.Event()

        async def plain():
            req = PreprocessedRequest(
                token_ids=rng.randint(5, 200, size=24).tolist(),
                stop_conditions={"max_tokens": 64, "ignore_eos": True},
                sampling_options={"temperature": 0.0}, request_id=f"p{r}",
            ).to_dict()
            toks = []
            async for item in eng.generate(req, Context()):
                toks.extend((item.get("data") or {}).get("token_ids", ()))
                if toks:
                    started.set()
            return toks

        lane = asyncio.create_task(plain())
        await started.wait()
        out.append(await _one(eng, rng.randint(5, 200, size=20).tolist(),
                              f"l{r}", lora_name="ad1", n=6))
        out.append(await _one(eng, rng.randint(5, 200, size=20).tolist(),
                              f"g{r}", n=6, guided={"kind": "choice",
                                                    "choices": ["yes", "no"]}))
        out.append(await lane)
    return out


def test_every_blended_arrival_rides_one_fused_step(params, adapters):
    """Tokens per dispatch on blended traffic, as counts: each guided or
    LoRA arrival beside a decoding lane is ONE mixed step and no split
    prefill + decode pair; with `mixed_dispatch=False` the same trace is
    that many split pairs, and the streams are the same."""
    stats, streams = {}, {}
    for mixed in (True, False):
        eng = _engine(params, adapters, mixed=mixed)
        streams[mixed] = asyncio.run(_blend_beside_a_decoding_lane(eng))
        stats[mixed] = eng.stats()
        asyncio.run(eng.close())
    fused, split = stats[True], stats[False]
    assert streams[True] == streams[False] and all(streams[True])
    assert (fused["mixed_steps"], fused["split_steps"]) == (4, 0)
    assert (split["mixed_steps"], split["split_steps"]) == (0, 4)
    assert fused["mixed_rows_lora"] == fused["mixed_rows_guided"] == 2
    assert fused["mixed_rows_plain"] == 4  # the lane's row in each step
    assert fused["mixed_coverage_frac"] == 1.0
    assert split["mixed_coverage_frac"] == 0.0


def test_spec_fused_verify_rows_vs_split_spec_and_plain(params):
    """Spec engine, plain traffic: the fused path packs 1+d verify rows
    per lane and must reproduce BOTH the split spec lane and the plain
    non-spec stream exactly (greedy — the lossless spec property)."""
    rng = np.random.RandomState(7)
    base = rng.randint(5, 500, size=8).tolist()
    p1 = (base * 6)[:44]
    p2 = rng.randint(5, 500, size=40).tolist()

    async def staggered(eng):
        t1 = asyncio.create_task(_one(eng, p1, "a", n=24))
        await asyncio.sleep(0.3)
        t2 = asyncio.create_task(_one(eng, p2, "b", n=24))
        return await asyncio.gather(t1, t2)

    eng = _engine(params, mixed=True, spec=True)
    fused = asyncio.run(staggered(eng))
    st = eng.stats()
    asyncio.run(eng.close())

    eng2 = _engine(params, mixed=False, spec=True)
    split = asyncio.run(staggered(eng2))
    asyncio.run(eng2.close())

    eng3 = _engine(params, mixed=False, spec=False)
    plain = asyncio.run(staggered(eng3))
    asyncio.run(eng3.close())

    assert fused == split
    assert fused == plain
    assert st["mixed_steps"] > 0
    assert st["mixed_rows_spec"] > 0  # verify rows actually packed
    assert st["spec_num_drafts"] > 0


def test_full_blend_under_spec_matches_plain_reference(params, adapters):
    """Spec engine serving guided + lora + plain at once: every stream
    must equal the plain non-spec engine's bit-for-bit (guided/lora were
    inadmissible under spec pre-PR, so the plain engine IS the split
    reference), with all four row kinds packed fused."""
    eng = _engine(params, adapters, mixed=True, spec=True)
    fused = asyncio.run(_staggered_blend(eng))
    st = eng.stats()
    asyncio.run(eng.close())

    ref = _engine(params, adapters, mixed=False, spec=False)
    want = asyncio.run(_staggered_blend(ref))
    asyncio.run(ref.close())

    assert fused == want
    assert all(len(t) > 0 for t in fused)
    assert st["mixed_steps"] > 0
    assert st["mixed_rows_spec"] > 0
    assert st["mixed_rows_guided"] > 0
    assert st["mixed_rows_lora"] > 0


def test_guided_lora_rejected_under_spec_without_fusion(params, adapters):
    """The admission relaxation is scoped exactly to fusion: with the
    fused path disabled, a spec engine still refuses guided and lora
    requests typed (the split spec lane cannot serve them)."""
    eng = _engine(params, adapters, mixed=False, spec=True)

    async def run():
        g = await _one(eng, [5, 6, 7], "g",
                       guided={"kind": "choice", "choices": ["yes", "no"]})
        l = await _one(eng, [5, 6, 7], "l", lora_name="ad1")
        return g, l

    g, l = asyncio.run(run())
    asyncio.run(eng.close())
    assert g == [] and l == []


# --------------------------------------------------------------------- #
# eligibility collapse: mm excludes only its own rows
# --------------------------------------------------------------------- #


def test_mm_stream_neither_starves_nor_blocks_fusion(params):
    """A steady multimodal stream (split-only kind) must not stop plain
    traffic from fusing — and the mm requests themselves must all finish
    (the sched_skips aging credit hands them to the split path's
    starvation override instead of starving behind fused steps)."""
    from dynamo_tpu.llm.multimodal import (
        MockVisionEncoder, encode_parts, splice_placeholders,
    )

    enc = MockVisionEncoder(hidden_size=CFG.hidden_size, n_tokens=4)
    [encoded] = encode_parts(
        [{"type": "image_url", "url": "http://x/cat.png"}], enc
    )
    token_ids, [stamped] = splice_placeholders(
        list(range(5, 13)), [encoded], 4, 256
    )

    import dataclasses

    eng = _engine(params, mixed=True)
    # tighten the starvation guard so the hand-off to the split path's
    # override happens within the test's traffic window
    eng.scheduler.sla = dataclasses.replace(
        eng.scheduler.sla, starve_dispatches=4
    )

    async def mm_one(rid):
        req = {
            "request_id": rid,
            "token_ids": list(token_ids),
            "multimodal": [stamped],
            "stop_conditions": {"max_tokens": 6, "ignore_eos": True},
            "sampling_options": {"temperature": 0.0},
        }
        toks = []
        async for item in eng.generate(req, Context()):
            data = item.get("data") or {}
            toks.extend(data.get("token_ids") or [])
        return toks

    async def main():
        rng = np.random.RandomState(3)
        plain_tasks = [
            asyncio.create_task(_one(
                eng, rng.randint(5, 200, size=24).tolist(), f"p{k}", n=20
            ))
            for k in range(2)
        ]
        await asyncio.sleep(0.3)
        mm_tasks = [asyncio.create_task(mm_one(f"mm{k}")) for k in range(3)]
        # second plain wave: these prefills arrive while wave-one decodes
        # AND mm candidates sit in the queue -- they must still fuse
        await asyncio.sleep(0.1)
        plain_tasks += [
            asyncio.create_task(_one(
                eng, rng.randint(5, 200, size=24).tolist(), f"q{k}", n=20
            ))
            for k in range(2)
        ]
        plains = await asyncio.gather(*plain_tasks)
        mms = await asyncio.gather(*mm_tasks)
        return plains, mms

    plains, mms = asyncio.run(main())
    st = eng.stats()
    asyncio.run(eng.close())
    assert all(len(t) == 20 for t in plains)
    assert all(len(t) == 6 for t in mms)  # mm never starves
    assert st["mixed_steps"] > 0  # plain traffic kept fusing


# --------------------------------------------------------------------- #
# adapter-tier chaos: lora.onboard faults never corrupt a stream
# --------------------------------------------------------------------- #


def test_lora_onboard_fault_refuses_typed_never_corrupts(params, adapters):
    """An injected `lora.onboard:error` at admission refuses exactly the
    cold-acquiring request (counted in lora_pool_refusals); a healthy
    retry then serves the SAME stream the un-faulted engine produces."""
    from dynamo_tpu.runtime import faults

    prompt = list(range(5, 25))
    ref_eng = _engine(params, adapters, mixed=True)
    want = asyncio.run(_one(ref_eng, prompt, "ref", lora_name="ad1", n=8))
    asyncio.run(ref_eng.close())

    # arm the fault AFTER construction: register() eagerly onboards ad1
    # into the single slot, and that healthy onboard must not eat times=1
    eng = _engine(params, adapters, mixed=True, lora_pool_slots=1)
    faults.configure("lora.onboard:error,times=1")
    try:

        async def run():
            # ad1 onboarded eagerly at register; ad2's cold acquire (slot
            # evict + onboard) eats the injected fault -> typed refusal
            bad = await _one(eng, prompt, "bad", lora_name="ad2", n=8)
            good = await _one(eng, prompt, "good", lora_name="ad1", n=8)
            return bad, good

        bad, good = asyncio.run(run())
        st = eng.stats()
        asyncio.run(eng.close())
    finally:
        faults.reset()

    assert bad == []  # refused up front, no partial stream
    assert good == want  # the fault never leaked into a served stream
    assert st["lora_pool_refusals"] >= 1


def test_lora_pool_pinned_full_refuses_and_releases(params, adapters):
    """All slots pinned by live streams -> a cold acquire refuses typed;
    after the pinning stream finishes, the same adapter serves fine and
    the eviction is counted."""
    eng = _engine(params, adapters, mixed=True, lora_pool_slots=1)

    async def main():
        hold = asyncio.create_task(
            _one(eng, list(range(5, 25)), "hold", lora_name="ad1", n=24)
        )
        await asyncio.sleep(0.4)  # ad1 decoding, pin held
        blocked = await _one(eng, [5, 6, 7], "blocked", lora_name="ad2", n=4)
        held = await hold
        after = await _one(eng, [5, 6, 7], "after", lora_name="ad2", n=4)
        return blocked, held, after

    blocked, held, after = asyncio.run(main())
    st = eng.stats()
    asyncio.run(eng.close())
    assert blocked == []  # pool full + pinned -> typed refusal
    assert len(held) == 24  # the pinned stream was never disturbed
    assert len(after) == 4  # pin released at finish -> evict + onboard
    assert st["lora_pool_refusals"] >= 1
    assert st["lora_pool_evictions"] >= 1


# --------------------------------------------------------------------- #
# the mixed step as a closed family of programs (PR 32): at most three
# token buckets, one table width under the Pallas ragged kernel, and every
# member compiled at the first mixed step
# --------------------------------------------------------------------- #


@pytest.mark.parametrize("spec", [False, True], ids=["plain", "spec"])
@pytest.mark.parametrize("fill", ["one_token", "half", "budget"])
@pytest.mark.parametrize("max_tokens", [48, 256, 2048, 2050, 8192])
@pytest.mark.parametrize("seqs", [1, 8, 32, 256])
def test_token_buckets_are_few_and_hold_what_the_planner_packs(
    seqs, max_tokens, fill, spec
):
    """Over a grid of EngineConfigs: at most four buckets, ascending, in
    REAL tokens (no alignment in them: the flat buffer is compact), the
    last one plan_mixed's budget (mixed_max_tokens), the first one the
    weight stream's floor unless the budget is smaller, powers of two
    between; and a pack of `fill` tokens lands in the smallest that
    holds it, never over twice its size above the floor."""
    from dynamo_tpu.engine.bucketing import (
        MIXED_TOKEN_BUCKET_FLOOR,
        MIXED_TOKEN_BUCKETS_MAX,
        bucket_for,
        mixed_token_buckets,
    )

    kw = dict(spec_mode="ngram", spec_draft_len=3) if spec else {}
    cfg = EngineConfig(max_num_seqs=seqs, mixed_max_tokens=max_tokens, **kw)
    buckets = mixed_token_buckets(cfg)
    assert 1 <= len(buckets) <= MIXED_TOKEN_BUCKETS_MAX == 4
    assert list(buckets) == sorted(set(buckets))
    assert buckets[-1] == max_tokens
    assert buckets[0] >= min(max_tokens, MIXED_TOKEN_BUCKET_FLOOR)
    assert all(b & (b - 1) == 0 for b in buckets[:-1])
    total = {"one_token": 1, "half": max_tokens // 2, "budget": max_tokens}[fill]
    bucket = bucket_for(total, buckets)
    assert bucket >= total and bucket in buckets
    assert all(b < total for b in buckets if b < bucket)
    assert bucket == buckets[0] or bucket < 2 * total


@pytest.mark.parametrize("max_tokens,ladder", [
    (2048, (256, 512, 1024, 2048)),  # the benchmark's cells: the default
    (4096, (512, 1024, 2048, 4096)),  # a floor raised to keep four
    (1024, (256, 512, 1024)),
    (300, (256, 300)),
    (256, (256,)),
    (100, (100,)),
])
def test_the_ladder_counts_real_tokens(max_tokens, ladder):
    """The cells' family (32 lanes, bf16, the default mixed_max_tokens) is
    four token buckets from the weight stream's floor; the q tile is not
    in them (it was: (1024, 2048) while rows were packed 16 apart)."""
    from dynamo_tpu.engine.bucketing import mixed_token_buckets

    cfg = EngineConfig(max_num_seqs=32, max_model_len=4096,
                       mixed_max_tokens=max_tokens)
    assert mixed_token_buckets(cfg) == ladder


FAMILY_KW = dict(max_num_seqs=4, max_model_len=128, num_pages=96,
                 max_prefill_batch=2, mixed_max_tokens=300)


def _one_width(eng):
    """Steer the engine onto the ONE table width it takes under the Pallas
    ragged kernel, which the CPU cannot run: the XLA reference serves the
    same packs at any width."""
    assert eng._mixed_primed == set()
    eng._mixed_table_rungs = (eng.config.max_pages_per_seq,)
    return eng


async def _arrivals_beside(eng, rng, lanes, arrivals, isl):
    """`lanes` requests decoding, then `arrivals` prompts of `isl` tokens
    at once: their chunks share mixed steps with the decode lanes."""
    started = [asyncio.Event() for _ in range(lanes)]

    async def anchor(i):
        req = PreprocessedRequest(
            token_ids=rng.randint(5, 200, size=6).tolist(),
            stop_conditions={"max_tokens": 60, "ignore_eos": True},
            sampling_options={"temperature": 0.0}, request_id=f"a{i}-{isl}",
        ).to_dict()
        async for _ in eng.generate(req, Context()):
            started[i].set()

    anchors = [asyncio.create_task(anchor(i)) for i in range(lanes)]
    await asyncio.gather(*(e.wait() for e in started))
    await asyncio.gather(*(
        _one(eng, rng.randint(5, 200, size=isl).tolist(), f"r{j}-{lanes}-{isl}", n=4)
        for j in range(arrivals)
    ))
    await asyncio.gather(*anchors)


@pytest.mark.parametrize("family", ["dense", "moe"])
def test_first_mixed_step_compiles_the_whole_family(family, params):
    """After the first mixed step no pack the scheduler can build meets a
    mixed_step shape the jit cache lacks: 1 to max_prefill_batch arrivals,
    contexts from one page to max_model_len, 1 to max_num_seqs - 1 lanes
    decoding beside them. The priming calls leave no observation in the
    cost model, and `_rng` and the pool (scratch page apart) as they were."""
    if family == "moe":
        from dynamo_tpu.models import moe

        mcfg = moe.MoeConfig.tiny_moe(dtype=jnp.float32, capacity_factor=2.0)
        eng = JaxEngine(
            EngineConfig(model="tiny-moe", page_size=PAGE, prefill_buckets=(16, 32),
                         max_prefill_chunk=32, mixed_dispatch=True, **FAMILY_KW),
            model_config=mcfg, params=moe.init_params(mcfg, jax.random.PRNGKey(3)),
        )
    else:
        eng = _engine(params, **FAMILY_KW)
    _one_width(eng)

    async def main():
        rng = np.random.RandomState(5)
        st0 = eng.stats()
        assert st0["mixed_family_size"] == len(eng._mixed_token_buckets) >= 2
        assert st0["mixed_family_compiled"] == 0
        rng_before = np.asarray(eng._rng)
        pool_before = np.asarray(eng.kv_k)[:, 1:].copy()
        await eng._prime_mixed_family(False, eng.config.max_pages_per_seq)
        np.testing.assert_array_equal(np.asarray(eng._rng), rng_before)
        np.testing.assert_array_equal(np.asarray(eng.kv_k)[:, 1:], pool_before)
        eng._mixed_primed.clear()  # the first pack meets the family itself
        await _arrivals_beside(eng, rng, lanes=1, arrivals=1, isl=6)
        first = eng.stats()
        for lanes, arrivals, isl in [(1, 2, 40), (3, 1, 100), (2, 2, 70),
                                     (3, 1, 7), (1, 1, 118)]:
            await _arrivals_beside(eng, rng, lanes, arrivals, isl)
        last = eng.stats()
        await eng.close()
        return first, last

    first, last = asyncio.run(main())
    assert first["mixed_steps"] >= 1
    assert first["mixed_family_compiled"] == first["mixed_family_size"]
    assert last["mixed_steps"] > first["mixed_steps"] + 5
    assert last["compile_surfaces"]["mixed_step"] == first["mixed_family_size"]
    assert last["mixed_family_compiled"] == last["mixed_family_size"]
    # one observation a real step, none from priming
    assert last["dispatch_mixed_count"] == last["mixed_steps"]
    assert last["dispatch_mixed_prime_count"] >= first["mixed_family_size"]
    observed = sum(
        n for (kind, _, _), (_, n) in eng.scheduler.cost._ewma.items()
        if kind == "mixed"
    )
    assert observed == last["mixed_steps"]
    if family == "moe":
        assert 0 < last["expert_rows_routed"] < last["expert_rows_computed"]
    else:
        assert last["expert_rows_computed"] == 0


def test_xla_reference_keeps_its_rungs_and_primes_a_rung_at_a_time(params):
    """Where the table's width costs (the XLA reference: this CPU, tp > 1,
    head sizes that are not multiples of 128) the pow2 rungs stay, and a
    rung's token buckets are compiled together when it is first reached."""
    eng = _engine(params, **FAMILY_KW)
    assert eng.attention_impl["ragged"] == "xla"
    assert eng._mixed_table_rungs == (1, 2, 4, 8, 16)
    n_buckets = len(eng._mixed_token_buckets)

    async def main():
        rng = np.random.RandomState(9)
        await _arrivals_beside(eng, rng, lanes=1, arrivals=1, isl=6)
        st = eng.stats()
        await eng.close()
        return st

    st = asyncio.run(main())
    assert st["mixed_family_size"] == 5 * n_buckets
    assert st["mixed_steps"] >= 1
    assert st["compile_surfaces"]["mixed_step"] % n_buckets == 0
    assert n_buckets <= st["mixed_family_compiled"] < st["mixed_family_size"]


# --------------------------------------------------------------------- #
# the mixed step as an entry of the two-deep decode pipeline (ISSUE 37)
# --------------------------------------------------------------------- #

PIPE_KW = dict(max_num_seqs=4, num_pages=128, max_model_len=256,
               decode_block_steps=4)
SAMPLINGS = {
    "greedy": {"temperature": 0.0},
    "seeded": {"temperature": 0.7, "seed": 11},
    "penalties": {"temperature": 0.7, "seed": 11, "repetition_penalty": 1.3,
                  "presence_penalty": 0.5, "frequency_penalty": 0.2},
}


def _family_engine(family, params, mixed=True, **over):
    kw = {**PIPE_KW, **over}
    if family == "dense":
        return _engine(params, mixed=mixed, **kw)
    from dynamo_tpu.models import moe

    mcfg = moe.MoeConfig.tiny_moe(dtype=jnp.float32, capacity_factor=2.0)
    return JaxEngine(
        EngineConfig(model="tiny-moe", page_size=PAGE, prefill_buckets=(16, 32),
                     max_prefill_chunk=32, mixed_dispatch=mixed, **kw),
        model_config=mcfg, params=moe.init_params(mcfg, jax.random.PRNGKey(3)),
    )


def _drained(eng):
    """The same engine with every pack on the old road: the test's way to
    the drain for a lean pack, since no option of the program leads there."""
    eng._pack_pipes = lambda chosen, lanes: False
    return eng


class _Stepped:
    """An engine whose step loop the test drives: every `_step_once` is the
    test's own call, so what is in flight between two steps is known, and
    an arrival lands exactly where the case wants it."""

    def __init__(self, eng):
        self.eng = eng
        self.fetches = []  # kinds in flight at every fetch of an entry
        self.dispatched = []  # (kind, kinds in flight before it), in order
        fetch, mixed = eng._fetch_and_process, eng._dev_mixed

        async def fetch_logged(fetch_block):
            kinds = [e["kind"] for e in eng._inflight]
            assert len(kinds) <= 2, kinds
            if fetch_block and kinds:
                self.fetches.append(kinds)
            return await fetch(fetch_block)

        def mixed_logged(p):
            if "prime" not in p:
                self.dispatched.append((
                    "piped" if "row_lane" in p else "drained",
                    [e["kind"] for e in eng._inflight],
                ))
            return mixed(p)

        eng._fetch_and_process = fetch_logged
        eng._dev_mixed = mixed_logged

    async def __aenter__(self):
        # generate() starts the step loop unless a task is there already
        self.eng._step_task = asyncio.create_task(asyncio.sleep(3600))
        return self

    async def __aexit__(self, *exc):
        await self.eng.close()

    async def submit(self, prompt, rid, **kw):
        task = asyncio.create_task(_stream(self.eng, prompt, rid, **kw))
        for _ in range(50):
            if any(s.request_id == rid for s in self.eng._waiting):
                return task
            await asyncio.sleep(0)
        raise AssertionError(f"{rid} never reached the waiting list")

    async def step(self, n=1):
        for _ in range(n):
            await self.eng._step_once()
            await asyncio.sleep(0)

    async def until(self, cond, limit=400):
        for _ in range(limit):
            if cond():
                return
            await self.step()
        raise AssertionError("the engine never got there")

    async def finish(self, *tasks):
        await self.until(lambda: all(t.done() for t in tasks))
        return [t.result() for t in tasks]

    def slot(self, rid):
        return next((s for s in self.eng.slots
                     if s is not None and s.request_id == rid), None)

    def generated(self, rid):
        slot = self.slot(rid)
        return slot.generated if slot is not None else 0


def _prompts(n, seed=3, lo=9, hi=30):
    """Prompts of one chunk each (max_prefill_chunk is 32) unless `hi` says
    otherwise: an arrival's prompt then completes in its first mixed step."""
    rng = np.random.RandomState(seed)
    return [rng.randint(5, 200, size=rng.randint(lo, hi)).tolist()
            for _ in range(n)]


async def _staggered_plain(eng, sampling, ns=(20, 27, 34, 41)):
    """Four plain requests, each arriving once the one before it decodes:
    every arrival's prompt shares mixed steps with live decode lanes."""
    tasks = []
    for k, (p, n) in enumerate(zip(_prompts(len(ns), hi=60), ns)):
        samp = dict(sampling)
        if "seed" in samp:
            samp["seed"] += k
        tasks.append(asyncio.create_task(
            _stream(eng, p, f"r{k}", n=n, sampling=samp)))
        for _ in range(200):
            await asyncio.sleep(0.005)
            if any(s is not None and s.request_id == f"r{k}" and s.generated
                   for s in eng.slots) or tasks[-1].done():
                break
    return [t for t, _ in await asyncio.gather(*tasks)]


@pytest.mark.parametrize("sampling", list(SAMPLINGS))
@pytest.mark.parametrize("family", ["dense", "moe"])
def test_piped_drained_and_split_streams_are_byte_identical(
    family, sampling, params
):
    """A lean pack as an entry of the pipeline (decode rows read the device
    carry, samples written back into it) against the same packs drained
    and against the split pair: the same streams, greedy and seeded, with
    and without penalties (whose window a piped row takes from the device
    ring, a drained one from `_fill_recent`)."""
    out, stats = {}, {}
    for road in ("piped", "drained", "split"):
        eng = _family_engine(family, params, mixed=road != "split")
        if road == "drained":
            _drained(eng)
        out[road] = asyncio.run(_staggered_plain(eng, SAMPLINGS[sampling]))
        stats[road] = eng.stats()
        asyncio.run(eng.close())
    assert out["piped"] == out["split"]
    assert out["drained"] == out["split"]
    assert [len(t) for t in out["split"]] == [20, 27, 34, 41]
    assert stats["piped"]["mixed_steps"] > 0
    assert stats["piped"]["mixed_steps_piped"] > 0
    assert stats["drained"]["mixed_steps"] > 0
    assert stats["drained"]["mixed_steps_piped"] == 0
    assert stats["split"]["mixed_steps"] == 0
    if sampling == "penalties":
        plain = _family_engine(family, params)
        unpenalized = asyncio.run(_staggered_plain(plain, SAMPLINGS["seeded"]))
        asyncio.run(plain.close())
        assert unpenalized != out["piped"]  # the penalties do act


def test_the_device_ring_after_a_piped_step_is_fill_recents(params):
    """After every piped mixed step the carry's penalty ring, token,
    position and length of each lane the step wrote are what the host
    would have built from the finished streams: `_fill_recent`'s window
    (ring-indexed by absolute position) up to the sampled token."""
    eng = _family_engine("dense", params, penalty_window=16)
    W = eng.config.penalty_window
    seen = []  # (request id, position, ring row, token, length) per write
    dev_mixed = eng._dev_mixed

    def spy(p):
        first = dev_mixed(p)
        if "row_lane" in p:
            tok, pos, sl = (np.asarray(x) for x in eng._carry)
            pen = np.asarray(eng._pen_dev)
            for lane, at in zip(p["w_lane"], p["w_pos"]):
                if lane >= 0:
                    seen.append((eng.slots[lane].request_id, int(at),
                                 pen[lane].copy(), int(tok[lane]),
                                 int(pos[lane]), int(sl[lane])))
        return first

    eng._dev_mixed = spy
    prompts = _prompts(4, hi=60)
    outs = asyncio.run(_staggered_plain(eng, SAMPLINGS["penalties"]))
    asyncio.run(eng.close())
    assert len(seen) > 4  # completions and decode rows both
    for rid, at, ring, tok, pos, sl in seen:
        k = int(rid[1:])
        full = np.asarray(prompts[k] + outs[k], np.int32)
        if at >= len(full):
            continue  # a row sampled past the request's end: dropped
        want = np.full((W,), -1, np.int32)
        ps = np.arange(max(0, at + 1 - W), at + 1)
        want[ps % W] = full[ps]
        np.testing.assert_array_equal(ring, want)
        assert (tok, pos, sl) == (full[at], at, at + 1)


@pytest.mark.parametrize("blocks_in_flight", [1, 2])
def test_an_arrival_joins_the_pipeline_behind_what_is_in_flight(
    blocks_in_flight, params
):
    """An arrival while one block is in flight, and while two are: its
    mixed step is dispatched behind them with no drain, the next entry is
    queued behind it before it is fetched, and the streams are the split
    path's."""
    prompts = _prompts(2, seed=8)

    async def run(eng, piped):
        async with _Stepped(eng) as st:
            a = await st.submit(prompts[0], "a", n=40)
            await st.until(lambda: len(eng._inflight) == 1
                           and eng._inflight[0]["kind"] == "block")
            if blocks_in_flight == 2 and piped:
                # the arrival lands while the step loop waits for a fetch
                # with two blocks in flight, and is admitted at that wake
                fetch = eng._fetch
                armed = [True]

                async def fetch_with_arrival(tree):
                    if armed[0] and len(eng._inflight) == 2:
                        armed[0] = False
                        st.b = await st.submit(prompts[1], "b", n=9)
                    return await fetch(tree)

                eng._fetch = fetch_with_arrival
                await st.until(lambda: not armed[0])
                b = st.b
            else:
                b = await st.submit(prompts[1], "b", n=9)
            await st.until(lambda: bool(st.dispatched) or not piped, 40)
            (ta, _), (tb, _) = await st.finish(a, b)
            return ta, tb, list(st.dispatched), list(st.fetches), eng.stats()

    ta, tb, dispatched, fetches, stats = asyncio.run(
        run(_family_engine("dense", params), True))
    ref = _family_engine("dense", params, mixed=False)
    ra, rb, *_ = asyncio.run(run(ref, False))
    assert (ta, tb) == (ra, rb) and len(tb) == 9
    # one block ran ahead of the mixed step, and it was not drained for it
    assert dispatched == [("piped", ["block"])]
    # fetched with its successor queued behind it
    assert ["mixed", "block"] in fetches
    assert stats["mixed_steps"] == stats["mixed_steps_piped"] == 1


def test_a_second_arrival_gets_its_own_mixed_step_next(params):
    """Two arrivals a few steps apart: the second, admitted while the
    first's mixed step is still in flight, gets the next entry to itself
    as a mixed step, not a place behind a block chained eagerly; the
    queue never holds more than two entries (asserted at every fetch)."""
    prompts = _prompts(3, seed=21)

    async def run(eng, piped):
        async with _Stepped(eng) as st:
            a = await st.submit(prompts[0], "a", n=48)
            await st.until(lambda: len(eng._inflight) == 1
                           and eng._inflight[0]["kind"] == "block")
            b = await st.submit(prompts[1], "b", n=20)
            if piped:
                await st.until(lambda: len(st.dispatched) == 1, 20)
                # b's mixed step is queued or running; nothing behind it
                assert [e["kind"] for e in eng._inflight] == ["mixed"]
            else:
                await st.step(2)
            c = await st.submit(prompts[2], "c", n=16)
            outs = await st.finish(a, b, c)
            return [t for t, _ in outs], list(st.dispatched), eng.stats()

    outs, dispatched, stats = asyncio.run(
        run(_family_engine("dense", params), True))
    ref, _, _ = asyncio.run(
        run(_family_engine("dense", params, mixed=False), False))
    assert outs == ref
    # c's pack went out right behind b's mixed step
    assert dispatched[:2] == [("piped", ["block"]), ("piped", ["mixed"])]
    assert stats["mixed_steps_piped"] == stats["mixed_steps"] == len(dispatched)


@pytest.mark.parametrize("how", ["eos", "stop", "max_tokens_1", "decode_lane_stop"])
def test_a_lane_that_ends_on_the_mixed_steps_token(how, params):
    """A request whose last token is the mixed step's own (its first
    token, by EOS, by a stop token or by max_tokens 1; or a decoding
    lane's, by a stop token): nothing past the end is emitted, the entry
    queued behind drops its rows, and the pages it gives back serve the
    next request as they do on the split path."""
    prompts = _prompts(3, seed=34)

    async def run(eng, b_kw, a_kw, piped):
        async with _Stepped(eng) as st:
            a = await st.submit(prompts[0], "a", n=40, **a_kw)
            await st.until(lambda: len(eng._inflight) == 1
                           and eng._inflight[0]["kind"] == "block")
            a_before = st.slot("a").generated
            b = await st.submit(prompts[1], "b", **b_kw)
            if piped:
                await st.until(lambda: bool(st.dispatched), 20)
                await st.until(lambda: ["mixed", "block"] in st.fetches
                               or ["mixed", "mixed"] in st.fetches, 20)
            else:
                await st.step(3)
            c = await st.submit(prompts[2], "c", n=12)
            outs = await st.finish(a, b, c)
            assert all(s is None for s in eng.slots)
            return outs, a_before, list(st.dispatched), eng.stats()

    ref_eng = _family_engine("dense", params, mixed=False)
    (ra, rb, rc), _, _, _ = asyncio.run(
        run(ref_eng, {"n": 12}, {}, False))
    assert len(ra[0]) == 40 and len(rb[0]) == 12
    a_kw, want_a = {}, ra[0]
    if how == "eos":
        b_kw, want_b = {"n": 12, "eos": [rb[0][0]]}, rb[0][:1]
    elif how == "stop":
        b_kw = {"n": 12, "stop": {"stop_token_ids": [rb[0][0]]}}
        want_b = rb[0][:1]
    elif how == "max_tokens_1":
        b_kw, want_b = {"n": 1}, rb[0][:1]
    else:
        b_kw, want_b = {"n": 12}, rb[0]
    if how == "decode_lane_stop":
        # the token lane a samples IN the mixed step: what it had when b
        # arrived (one dry piped run tells; the stepping is
        # deterministic) and the block of 4 that was in flight
        _, n_before, _, _ = asyncio.run(run(
            _family_engine("dense", params), b_kw, {}, True))
        idx = n_before + 4
        assert ra[0][idx] not in ra[0][:idx], "pick another seed"
        a_kw = {"stop": {"stop_token_ids": [ra[0][idx]]}}
        want_a = ra[0][: idx + 1]
    (ta, tb, tc), _, dispatched, stats = asyncio.run(run(
        _family_engine("dense", params), b_kw, a_kw, True))
    assert ta[0] == want_a and tb[0] == want_b
    assert tb[1] == ("length" if how in ("max_tokens_1", "decode_lane_stop")
                     else "eos")
    assert ta[1] == ("eos" if how == "decode_lane_stop" else "length")
    assert dispatched[0] == ("piped", ["block"])
    assert stats["mixed_steps_piped"] >= 1
    # c took its pages from what the ended lanes gave back
    c_ref = rc[0] if how != "decode_lane_stop" else None
    if c_ref is not None:
        assert tc[0] == c_ref
    assert len(tc[0]) == 12


def test_a_cancel_while_its_mixed_entry_is_queued(params):
    """The arrival's caller goes away after its mixed step was dispatched
    and before it was fetched: the request ends cancelled with no token,
    the block queued behind drops its lane, and the lane beside it reads
    what it reads on the split path."""
    prompts = _prompts(2, seed=55)

    async def run(eng, piped):
        async with _Stepped(eng) as st:
            a = await st.submit(prompts[0], "a", n=40)
            await st.until(lambda: len(eng._inflight) == 1
                           and eng._inflight[0]["kind"] == "block")
            ctx = Context()
            b = await st.submit(prompts[1], "b", n=20, ctx=ctx)
            if piped:
                await st.until(lambda: bool(st.dispatched), 20)
                assert eng._inflight[-1]["kind"] == "mixed"
                assert st.slot("b").first_pending
            ctx.stop_generating()
            (ta, _), (tb, fb) = await st.finish(a, b)
            assert all(s is None for s in eng.slots)
            return ta, tb, fb

    ta, tb, fb = asyncio.run(run(_family_engine("dense", params), True))
    ra, _, _ = asyncio.run(
        run(_family_engine("dense", params, mixed=False), False))
    assert ta == ra and len(ta) == 40
    assert tb == [] and fb == "cancelled"


@pytest.mark.parametrize("what", ["preemption", "invalid_carry"])
def test_the_pipeline_drains_for_a_host_lane_and_pipes_again(what, params):
    """Between two entries a lane is preempted (its resume packs a
    `resume_token`, whose sample is discarded for the host's token), or
    the carry is invalidated (what a failed step leaves): the next pack
    waits for the drain and packs host-authoritative lanes, the one after
    it pipes again, and every stream is the split path's."""
    prompts = _prompts(4, seed=89)

    async def run(eng, piped):
        async with _Stepped(eng) as st:
            a = await st.submit(prompts[0], "a", n=60)
            await st.until(lambda: len(eng._inflight) == 1
                           and eng._inflight[0]["kind"] == "block")
            b = await st.submit(prompts[1], "b", n=40)
            await st.until(lambda: st.generated("b") > 0)
            await st.until(lambda: len(eng._inflight) == 1)
            if what == "preemption":
                # the newest decoding lane (b) loses its pages with an
                # entry in flight and comes back through the waiting list
                assert eng._preempt_one(exclude_idx=-1)
                assert eng._waiting[0].resume_token is not None
            elif piped:
                eng._carry_valid = False
            c = await st.submit(prompts[2], "c", n=16)
            await st.until(lambda: st.generated("c") > 0 or c.done())
            d = await st.submit(prompts[3], "d", n=12)
            outs = await st.finish(a, b, c, d)
            return [t for t, _ in outs], list(st.dispatched), eng.stats()

    outs, dispatched, stats = asyncio.run(
        run(_family_engine("dense", params), True))
    ref, _, _ = asyncio.run(
        run(_family_engine("dense", params, mixed=False), False))
    assert outs == ref
    roads = [road for road, _ in dispatched]
    assert roads[0] == "piped" and roads[-1] == "piped"
    if what == "preemption":
        assert "drained" in roads  # the resume's pack
        assert all(ahead == [] for road, ahead in dispatched
                   if road == "drained")
        assert stats["mixed_steps_piped"] <= roads.count("piped")
    else:
        # an invalid carry is the engine's state, not the pack's: the pack
        # waits for the drain, the carry is uploaded whole, and the pack
        # reads it; it is not counted as piped, the next one is
        assert stats["mixed_steps_piped"] == len(roads) - 1
        assert dispatched[1][1] == []
    assert stats["mixed_steps"] == len(roads)


@pytest.mark.parametrize("kind", ["guided", "lora", "spec", "return_kv"])
def test_packs_that_need_a_host_value_take_the_drain(kind, params, adapters):
    """A pack with a guided row, a LoRA row, a spec verify row or the
    disagg prefill role's completion drains as before this PR: dispatched
    with nothing in flight, fetched in its own step, never counted as
    piped. (The preempted resume is in the case above.)"""
    prompts = _prompts(2, seed=144)
    eng = _family_engine("dense", params, spec=kind == "spec")
    if kind in ("lora", "guided"):
        eng.register_adapters(adapters)
    a_kw = {"guided": {"kind": "choice", "choices": ["yes yes yes", "no"]}} \
        if kind == "guided" else {}
    if kind == "lora":
        a_kw = {"lora_name": "ad1"}
    b_kw = {"disagg_params": {"return_kv": True}} if kind == "return_kv" else {}

    async def run():
        async with _Stepped(eng) as st:
            a = await st.submit(
                prompts[0], "a", n=24,
                **({"eos": [2], **a_kw} if kind == "guided" else a_kw))
            await st.until(lambda: any(
                s is not None and s.generated > 0 for s in eng.slots))
            b = await st.submit(prompts[1], "b", n=6, **b_kw)
            await st.finish(a, b)
            return list(st.dispatched), eng.stats()

    dispatched, stats = asyncio.run(run())
    assert dispatched and all(d == ("drained", []) for d in dispatched)
    assert stats["mixed_steps"] == len(dispatched)
    assert stats["mixed_steps_piped"] == 0


@pytest.mark.parametrize("family", ["dense", "moe"])
def test_plain_traffic_pipes_every_mixed_step_and_compiles_nothing_new(
    family, params
):
    """On plain traffic with the pipeline running every mixed step is an
    entry of it (`mixed_steps_piped` equals `mixed_steps`), and after the
    family is primed no program is compiled: the write-back is compiled
    with the family, and the patch before the first block."""
    eng = _one_width(_family_engine(family, params))
    prompts = _prompts(6, seed=233)

    async def run():
        async with _Stepped(eng) as st:
            a = await st.submit(prompts[0], "a", n=80)
            await st.until(lambda: len(eng._inflight) == 1
                           and eng._inflight[0]["kind"] == "block")
            first = await st.submit(prompts[1], "b", n=10)
            await st.until(lambda: bool(st.dispatched), 20)
            primed = eng._surface_cache_sizes()
            # (the write-back takes its two maps out of the step's own
            # buffer, so it is a program a member of the family)
            assert primed["carry_write"] == len(eng._mixed_token_buckets)
            assert primed["mixed_step"] == len(eng._mixed_token_buckets)
            tasks = [a, first]
            for k, p in enumerate(prompts[2:]):
                await st.step(3 + k)
                tasks.append(await st.submit(p, f"r{k}", n=8 + 3 * k))
            outs = await st.finish(*tasks)
            assert [len(t) for t, _ in outs] == [80, 10, 8, 11, 14, 17]
            return primed, eng._surface_cache_sizes(), eng.stats()

    primed, after, stats = asyncio.run(run())
    assert after == primed
    assert stats["mixed_steps"] >= 5
    assert stats["mixed_steps_piped"] == stats["mixed_steps"]


@pytest.mark.parametrize("tile", [1, 16])
@pytest.mark.parametrize("family", ["dense", "moe"])
def test_the_attention_counters_follow_the_packs(family, tile, params):
    """`mixed_attn_tiles`, `mixed_attn_tiles_real` and
    `mixed_rows_decode_kernel` over a scripted run, against the same
    arithmetic on the packs the device calls were handed: q tiles of the
    ragged grid a step (the token bucket and tile - 1 slots for each row of
    a prefill batch), whole tiles over the rows of more than one token, and
    the one-token rows. All host arithmetic, in the kernel's tile (forced
    here: the CPU's engine resolves the XLA path, whose tile is 1 and
    whose counts stay 0)."""
    from dynamo_tpu.ops.paged_attention import ragged_tiles

    eng = _one_width(_family_engine(family, params))
    assert eng._ragged_tile == 1 and eng.attention_impl["ragged"] == "xla"
    eng._ragged_tile = tile
    # one arrival of one chunk, one of two chunks whose second is ONE token
    # (33 = 32 + 1), and a short one, each beside the lanes decoding
    prompts = _prompts(2, seed=77) + [list(range(5, 38))]
    packs = []

    async def run():
        async with _Stepped(eng) as st:
            dev_mixed = eng._dev_mixed

            def kept(p):
                if "prime" not in p:
                    packs.append((len(p["toks"]), np.array(p["row_lens"])))
                return dev_mixed(p)

            eng._dev_mixed = kept
            a = await st.submit(prompts[0], "a", n=40)
            await st.until(lambda: any(
                s is not None and s.generated > 0 for s in eng.slots))
            tasks = [a]
            for k, prompt in enumerate(prompts[1:]):
                tasks.append(await st.submit(prompt, f"r{k}", n=6))
                await st.step(4)
            await st.finish(*tasks)
            return eng.stats()

    stats = asyncio.run(run())
    assert len(packs) == stats["mixed_steps"] >= 3
    if tile == 1:
        want = (0, 0, 0)
    else:
        batch = eng.config.max_prefill_batch
        want = (
            sum(ragged_tiles(M, len(lens), tile, batch) for M, lens in packs),
            sum(int(-(-n // tile)) for _, lens in packs for n in lens if n > 1),
            sum(int((lens == 1).sum()) for _, lens in packs),
        )
        assert 0 < want[1] < want[0] and want[2] >= len(packs)
    assert (stats["mixed_attn_tiles"], stats["mixed_attn_tiles_real"],
            stats["mixed_rows_decode_kernel"]) == want

