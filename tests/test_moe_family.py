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
# the two expert blocks of moe_mlp (PR 32): grouped matmuls over the routed
# rows at and above GROUPED_MIN_TOKENS on one device, the capacity einsum
# below it (the decode block), under a mesh and for quantized stacks
# --------------------------------------------------------------------- #


def _parent_moe_mlp(layer, x, c):
    """moe_mlp as it stood before the grouped path (commit 8338d8b), kept
    verbatim as the reference the decode block's program is held to."""
    from dynamo_tpu.models.llama import rms_norm
    from dynamo_tpu.models.quant import qeinsum

    T, H = x.shape
    E, K = c.num_experts, c.num_experts_per_tok
    C = moe.expert_capacity(T, c)

    h = rms_norm(x, layer["mlp_norm"], c.rms_norm_eps)
    logits = jnp.dot(h.astype(jnp.float32), layer["router"])  # [T, E]
    topv, topi = jax.lax.top_k(logits, K)  # [T, K]
    probs = jax.nn.softmax(topv, axis=-1)

    combine = jnp.zeros((T, E), jnp.float32)
    combine = combine.at[jnp.arange(T)[:, None], topi].add(probs)
    routed = combine > 0.0  # [T, E]

    pos = jnp.cumsum(routed.astype(jnp.int32), axis=0) - 1  # [T, E]
    keep = routed & (pos < C)
    dispatch = (
        jax.nn.one_hot(jnp.where(keep, pos, C), C, dtype=h.dtype)
        * keep[..., None]
    )  # [T, E, C]

    expert_in = moe._constrain_ep(jnp.einsum("tec,th->ech", dispatch, h))
    gate = qeinsum("ech,ehi->eci", expert_in, layer["w_gate"])
    up = qeinsum("ech,ehi->eci", expert_in, layer["w_up"])
    act = (jax.nn.silu(gate) * up).astype(c.dtype)
    expert_out = moe._constrain_ep(
        qeinsum("eci,eih->ech", act, layer["w_down"])
    )

    out = jnp.einsum(
        "ech,tec->th", expert_out, dispatch.astype(jnp.float32) * combine[..., None]
    )
    return x + out.astype(c.dtype)


def _reference_block():
    """benchmark/references/moe.py's float32 block (dropless, no capacity,
    nothing from dynamo_tpu): `moe_mlp(x, w, cfg) -> (x + out, margin)`."""
    import os
    import sys

    bench = os.path.join(os.path.dirname(os.path.dirname(__file__)), "benchmark")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    from references import moe as ref

    return ref.moe_mlp


def _layer_and_x(dtype, T, seed=0):
    cfg = moe.MoeConfig.tiny_moe(dtype=dtype, capacity_factor=2.0)  # E / K
    params = moe.init_params(cfg, jax.random.PRNGKey(7))
    layer = jax.tree.map(lambda p: p[1], params["layers"])
    x = jax.random.normal(jax.random.PRNGKey(seed + T), (T, cfg.hidden_size))
    return cfg, params, layer, x.astype(dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("real_share", [0.1, 0.5, 1.0])
@pytest.mark.parametrize("T", [64, 1024])
def test_grouped_experts_match_the_einsum_and_the_reference(
    T, real_share, dtype, monkeypatch
):
    """The grouped path over a flat buffer whose padding is poisoned with
    NaN, against the capacity einsum over the real rows alone at
    capacity_factor E / K (which drops nothing) and, in float32, against
    the benchmark's plain float32 block: same routing, outputs within the
    dtype's tolerance, and padding reaches no expert (a NaN row in a
    group would spread through nothing here, but a padding row counted
    into a group would come back finite)."""
    cfg, _, layer, x = _layer_and_x(dtype, T)
    real = max(1, int(T * real_share))
    valid = jnp.arange(T) < real
    poisoned = jnp.where(valid[:, None], x, jnp.nan)

    monkeypatch.setattr(moe, "GROUPED_MIN_TOKENS", 1)
    grouped = moe.moe_mlp(layer, poisoned, cfg, valid=valid)
    monkeypatch.setattr(moe, "GROUPED_MIN_TOKENS", 10**9)
    einsum = moe.moe_mlp(layer, x[:real], cfg)

    assert bool(jnp.isfinite(grouped[:real]).all())
    assert bool(jnp.isnan(grouped[real:]).all())
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    scale = float(jnp.abs(einsum.astype(jnp.float32)).max())
    np.testing.assert_allclose(
        np.asarray(grouped[:real], np.float32), np.asarray(einsum, np.float32),
        atol=tol * scale, rtol=0,
    )
    if dtype == jnp.float32:
        ref, _ = _reference_block()(x[:real], layer, cfg)
        np.testing.assert_allclose(
            np.asarray(grouped[:real]), np.asarray(ref), atol=tol * scale, rtol=0
        )


GROUPED_OP = "ragged_dot"  # _grouped_matmul's primitive off the TPU


@pytest.mark.parametrize("case", ["one_device", "below_threshold", "mesh", "quantized"])
def test_moe_mlp_picks_its_expert_block_from_what_it_sees(case):
    """T, the mesh and the weights' format decide, at trace time: no
    option. Only a large T on one device with plain weights is grouped."""
    from dynamo_tpu.models.quant import quantize_tree
    from dynamo_tpu.ops.paged_attention import attention_scope

    T = 32 if case == "below_threshold" else moe.GROUPED_MIN_TOKENS
    cfg, params, layer, x = _layer_and_x(jnp.float32, T)
    if case == "quantized":
        layer = jax.tree.map(lambda p: p[1], quantize_tree(params)["layers"])
    with attention_scope(case != "mesh"):
        text = str(jax.make_jaxpr(lambda l, x: moe.moe_mlp(l, x, cfg))(layer, x))
    assert (GROUPED_OP in text) == (case == "one_device")


@pytest.mark.parametrize("T", [4, 32, 255])
def test_decode_block_program_is_the_parents(T):
    """Below the threshold moe_mlp lowers to the text the parent's moe_mlp
    lowers to, and so does the decode forward around it: the decode block
    is bound by the weight stream and keeps the einsum, byte for byte."""
    cfg, params, layer, x = _layer_and_x(jnp.float32, T)

    def lowered(mlp):
        return jax.jit(lambda l, x: mlp(l, x, cfg)).lower(layer, x).as_text()

    assert lowered(moe.moe_mlp) == lowered(_parent_moe_mlp)

    from dynamo_tpu.ops.kv_quant import alloc_kv_store

    kv = alloc_kv_store(cfg.num_layers, 9, 8, cfg.num_kv_heads, cfg.head_dim,
                        cfg.dtype, "none")
    i32 = jnp.int32
    args = (jnp.zeros((T,), i32), jnp.zeros((T,), i32), kv, kv,
            jnp.ones((T, 3), i32), jnp.ones((T,), i32))

    def lowered_decode(fn, **kw):
        return jax.jit(
            lambda p, *a: fn(p, cfg, *a, **kw), donate_argnums=(3, 4)
        ).lower(params, *args).as_text()

    assert lowered_decode(moe.decode_forward) == lowered_decode(
        llama.decode_forward, mlp_fn=_parent_moe_mlp)


def test_whole_expert_stack_gives_what_the_slice_gives():
    """ragged_forward hands moe_mlp the stacked weights whole (ExpertStack:
    a slice is a copy under a Pallas call); layer li's experts are groups
    li*E .. li*E+E-1 of the stack, the other layers' groups empty."""
    cfg, params, layer, x = _layer_and_x(jnp.float32, moe.GROUPED_MIN_TOKENS)
    valid = jnp.arange(x.shape[0]) < 100
    whole = jax.tree.map(
        lambda p: p[1], moe._whole_expert_stacks(params)["layers"])
    assert isinstance(whole["w_gate"], moe.ExpertStack) and whole["w_gate"].li == 1
    a = moe.moe_mlp(whole, x, cfg, valid=valid)
    b = moe.moe_mlp(layer, x, cfg, valid=valid)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6, rtol=0)


@pytest.mark.parametrize("T,real,grouped", [(32, 20, False), (1024, 190, True),
                                            (1024, 0, True), (2048, 2048, True)])
def test_expert_rows_is_host_arithmetic(T, real, grouped):
    """(routed, computed) as the engine counts them per dispatch: routed =
    real x K; computed = E x C on the einsum path, whole 256-row tiles
    plus one for every expert that can straddle on the grouped path."""
    cfg = moe.MoeConfig.mixtral_8x7b(capacity_factor=4.0)
    routed, computed = moe.expert_rows(cfg, T, real, quantized=False)
    assert routed == 2 * real
    if not grouped:
        assert computed == 8 * moe.expert_capacity(T, cfg) == 8 * T
    else:
        assert computed % 256 == 0 and routed <= computed <= routed + 8 * 256
        assert computed < 8 * T


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
