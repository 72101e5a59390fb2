"""Nothing compiles after `JaxEngine.warmup()` (docs/compilation.md): the
worker's own warmup, then per prefill bucket a lone arrival, a burst of
three and a staggered pair whose second request is admitted while the first
decodes. `stats()["post_warmup_compiles"]` is what the chip reports as
`engine.post_warmup_compiles`; every assertion here is a count.

The mixed step's tables have ONE width, as under the Pallas ragged kernel on
a TPU, so its family is whole after warmup's first mixed step. On the XLA
path's pow2 rungs warmup primes only the rungs its own traffic meets.
"""

import asyncio

import numpy as np
import pytest

from dynamo_tpu.engine import EngineConfig, JaxEngine
from dynamo_tpu.llm.protocols import PreprocessedRequest
from dynamo_tpu.runtime.engine import Context


def _engine(model, page, **over):
    kw = dict(
        model=model, max_num_seqs=2, page_size=page, num_pages=64,
        max_model_len=96, prefill_buckets=(16,), max_prefill_chunk=16,
        decode_block_steps=4, mixed_max_tokens=64,
    )
    kw.update(over)
    return JaxEngine(EngineConfig(**kw))


async def _one(eng, rng, isl, osl, started=None):
    req = PreprocessedRequest(
        token_ids=rng.randint(5, 200, size=isl).tolist(),
        stop_conditions={"max_tokens": osl, "ignore_eos": True},
        sampling_options={"temperature": 1.0},
    ).to_dict()
    n = 0
    async for item in eng.generate(req, Context()):
        n += len((item.get("data") or {}).get("token_ids", ()))
        if n and started is not None:
            started.set()
    return n


async def _staggered(eng, rng, isl_a, isl_b):
    """The second request arrives once the first has emitted: its prefill
    chunk meets a decoding lane (an event, not a sleep: after warmup a step
    is faster than any fixed wait)."""
    started = asyncio.Event()
    first = asyncio.create_task(_one(eng, rng, isl_a, 8, started))
    await started.wait()
    return await asyncio.gather(first, _one(eng, rng, isl_b, 4))


# a dense family and a stateful one (a state per lane beside the pages);
# `tiny-moe` has its case in tests/test_moe_family.py, and `tiny-hybrid`,
# which takes the stateful family's road through the engine, compiles for
# over a minute here
@pytest.mark.parametrize("model,page", [("tiny", 8), ("tiny-nemotron-h", 16)])
def test_nothing_compiles_after_warmup(model, page):
    async def main():
        eng = _engine(model, page)
        eng._mixed_table_rungs = (eng.config.max_pages_per_seq,)
        served = await eng.warmup()
        warm = eng.stats()
        rng = np.random.RandomState(0xC0DE)
        tokens = 0
        for b in eng.config.prefill_buckets:
            lengths = [b - 8, b // 2, b - 1]
            tokens += await _one(eng, rng, lengths[0], 6)
            tokens += sum(await asyncio.gather(
                *[_one(eng, rng, n, 4) for n in lengths]))
            tokens += sum(await _staggered(eng, rng, lengths[1], lengths[2]))
        st = eng.stats()
        await eng.close()
        return served, warm, tokens, st

    served, warm, tokens, st = asyncio.run(main())
    assert served > 0 and tokens == 6 + 3 * 4 + 8 + 4
    assert st["mixed_steps"] > warm["mixed_steps"], "no arrival met a decoding lane"
    assert st["post_warmup_compiles"] == 0, st["compile_surfaces"]
    assert st["compiled_variants"] == warm["compiled_variants"]
    assert st["compile_surfaces"] == warm["compile_surfaces"]


def test_a_filtered_pack_runs_a_primed_member_of_the_family():
    """On the XLA path's pow2 table rungs: a lane the host knows will be
    done is left out of the pack after the plan, and what remains may fit a
    narrower table. The step keeps the plan's width, the one that was
    primed: every mixed_step program the jit holds is a primed member."""
    async def main():
        eng = _engine("tiny", 8, decode_block_steps=8)
        rng = np.random.RandomState(0xC0DE)
        for _ in range(3):
            await _staggered(eng, rng, 8, 15)
        st = eng.stats()
        primed = sorted(eng._mixed_primed)
        await eng.close()
        return st, primed

    st, primed = asyncio.run(main())
    lean = [pages for variant, pages in primed if not variant]
    assert st["mixed_steps"] > 0 and lean
    assert st["compile_surfaces"]["mixed_step"] == len(lean)
