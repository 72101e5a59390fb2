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


async def _one(eng, prompt, rid, lora_name=None, guided=None, n=12,
               temperature=0.0, seed=None):
    sampling = {"temperature": temperature}
    if seed is not None:
        sampling["seed"] = seed
    req = PreprocessedRequest(
        token_ids=list(prompt),
        stop_conditions={"max_tokens": n,
                         **({} if guided else {"ignore_eos": True})},
        sampling_options=sampling,
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
@pytest.mark.parametrize("align", [1, 8, 16])
@pytest.mark.parametrize("max_tokens", [48, 256, 2048, 2050, 8192])
@pytest.mark.parametrize("seqs", [1, 8, 32, 256])
def test_token_buckets_are_few_and_hold_what_the_planner_packs(
    seqs, max_tokens, align, spec
):
    """Over a grid of EngineConfigs: at most three buckets, ascending,
    every one a multiple of the packer's alignment, the last one
    plan_mixed's budget (mixed_max_tokens floored to the alignment), the
    first one holding a full decode batch twice over unless the budget is
    smaller."""
    from dynamo_tpu.engine.bucketing import (
        MIXED_TOKEN_BUCKETS_MAX,
        mixed_token_buckets,
        next_pow2,
    )

    kw = dict(spec_mode="ngram", spec_draft_len=3) if spec else {}
    cfg = EngineConfig(max_num_seqs=seqs, mixed_max_tokens=max_tokens, **kw)
    buckets = mixed_token_buckets(cfg, align)
    budget = max_tokens - max_tokens % align
    rows = seqs * (4 if spec else 1)
    assert 1 <= len(buckets) <= MIXED_TOKEN_BUCKETS_MAX <= 4
    assert list(buckets) == sorted(set(buckets))
    assert all(b % align == 0 for b in buckets)
    assert buckets[-1] == budget
    assert buckets[0] >= min(budget, 2 * next_pow2(rows * align))


def test_the_cells_family_is_two_token_buckets():
    """The benchmark's cell: 32 lanes, bf16 (rows aligned to 16), the
    default mixed_max_tokens."""
    from dynamo_tpu.engine.bucketing import mixed_token_buckets

    cfg = EngineConfig(max_num_seqs=32, max_model_len=4096)
    assert mixed_token_buckets(cfg, 16) == (1024, 2048)


FAMILY_KW = dict(max_num_seqs=4, max_model_len=128, num_pages=96,
                 max_prefill_batch=2, mixed_max_tokens=256)


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
