"""models/moe.py beside models/llama.py: the engine calls one family
module or the other through the same seven entry points with the same
keywords (engine._ScopedModel), so moe's wrappers must take everything
their llama twins take. Until PR 31 three of them lacked `lora`, which
the engine passes on every variant ragged step, also when it is None: a
guided row on a MoE model raised TypeError, and so did the worker's own
warm-up.
"""

import asyncio
import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine import EngineConfig, JaxEngine
from dynamo_tpu.models import llama, moe

from .test_mixed_fusion import _one

FORWARDS = (
    "prefill_forward",
    "prefill_forward_batched",
    "ragged_forward",
    "prefill_forward_ring",
    "decode_forward",
    "decode_forward_pp",
    "prefill_forward_pp",
)


@pytest.mark.parametrize("name", FORWARDS)
def test_moe_forward_takes_what_llama_takes(name):
    """Same parameters, same order, same defaults, `mlp_fn` apart (the
    seam moe fills in). A keyword added to llama's forward and not to
    moe's fails here, not under the first MoE request that needs it."""
    def params_of(module):
        sig = inspect.signature(getattr(module, name))
        return [
            (p.name, p.kind, p.default)
            for p in sig.parameters.values() if p.name != "mlp_fn"
        ]

    assert "mlp_fn" in inspect.signature(getattr(llama, name)).parameters
    assert "mlp_fn" not in inspect.signature(getattr(moe, name)).parameters
    assert params_of(moe) == params_of(llama)


def test_family_modules_export_the_same_forwards():
    """Neither module has a forward entry point the other lacks."""
    def forwards(module):
        return {
            n for n, f in vars(module).items()
            if inspect.isfunction(f) and f.__module__ == module.__name__
            and (n.startswith(("prefill_forward", "decode_forward"))
                 or n == "ragged_forward")
        }

    assert forwards(llama) == forwards(moe) == set(FORWARDS)


# --------------------------------------------------------------------- #
# the engine on tiny-moe: variant rows and the worker's warm-up
# --------------------------------------------------------------------- #

PAGE = 8
# experts / experts per token: the least capacity factor that drops no
# token, so a row's output does not depend on its neighbours in the step
# and the fused and split dispatch orders can be compared byte for byte
MCFG = moe.MoeConfig.tiny_moe(dtype=jnp.float32, capacity_factor=2.0)


@pytest.fixture(scope="module")
def mparams():
    return moe.init_params(MCFG, jax.random.PRNGKey(3))


def _engine(mparams, mixed, **over):
    kw = dict(
        model="tiny-moe", max_num_seqs=4, page_size=PAGE, num_pages=128,
        max_model_len=256, prefill_buckets=(16, 32), max_prefill_chunk=32,
        mixed_dispatch=mixed,
    )
    kw.update(over)
    eng = JaxEngine(EngineConfig(**kw), model_config=MCFG, params=mparams)
    assert eng._model._module is moe
    return eng


GUIDED = {"kind": "choice", "choices": ["yes", "no"]}


async def _plain_then_guided(eng):
    """A plain request decoding while the guided row arrives: its prefill
    chunk and the plain decode lane share a mixed step."""
    rng = np.random.RandomState(11)
    t1 = asyncio.create_task(
        _one(eng, rng.randint(5, 200, size=24).tolist(), "plain", n=20))
    await asyncio.sleep(0.3)
    t2 = asyncio.create_task(
        _one(eng, rng.randint(5, 200, size=20).tolist(), "guided", n=16,
             guided=GUIDED))
    return await asyncio.gather(t1, t2)


def test_moe_guided_row_fused_matches_split(mparams):
    """A guided row on tiny-moe through mixed_step_variant gives the split
    dispatch's stream byte for byte."""
    eng = _engine(mparams, mixed=True)
    fused = asyncio.run(_plain_then_guided(eng))
    st = eng.stats()
    asyncio.run(eng.close())

    ref = _engine(mparams, mixed=False)
    split = asyncio.run(_plain_then_guided(ref))
    st_ref = ref.stats()
    asyncio.run(ref.close())

    assert fused == split
    assert all(len(t) > 0 for t in fused)
    assert st["mixed_steps"] > 0 and st["mixed_rows_guided"] > 0
    assert st_ref["mixed_steps"] == 0


def test_engine_warmup_reaches_its_end_on_tiny_moe(mparams):
    """JaxEngine.warmup, the worker's own: plain, guided and fused-variant
    arrivals, on the family whose wrappers had fallen behind the engine's
    calls. Nothing compiles under the guided request that follows."""
    async def main():
        eng = _engine(
            mparams, mixed=True, max_num_seqs=2, num_pages=64,
            max_model_len=96, prefill_buckets=(16,), max_prefill_chunk=16,
            decode_block_steps=4,
        )
        n = await eng.warmup()
        warm = eng.stats()
        toks = await _one(eng, [5, 9, 17, 33, 101, 7], "after", n=6,
                          guided=GUIDED)
        st = eng.stats()
        await eng.close()
        return n, warm, toks, st

    n, warm, toks, st = asyncio.run(main())
    assert n > 0 and toks
    assert warm["compiled_variants"] > 0
    assert st["compiled_variants"] == warm["compiled_variants"]


# --------------------------------------------------------------------- #
# what went with the second ("local") decode block
# --------------------------------------------------------------------- #


@pytest.mark.parametrize("flag", ["--decode-pool-mode", "--decode-block-unroll"])
def test_worker_rejects_the_deleted_flags(flag, capsys):
    """There is one decode block: the worker has no option to pick one."""
    from dynamo_tpu.jax_worker.__main__ import parse_args

    with pytest.raises(SystemExit) as e:
        parse_args(["--model", "tiny", flag, "1"])
    assert e.value.code == 2
    assert "unrecognized arguments" in capsys.readouterr().err
