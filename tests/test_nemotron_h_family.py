"""models/nemotron_h.py against benchmark/references/nemotron_h.py, and the
engine's state store beside the pages for a second stateful family
(docs/hybrid_models.md).

CPU, tiny sizes, float32 weights and activations, seeded random weights,
the matmul precision "highest" on both sides. The tolerance is 1e-3
deviations of the reference's logits at a position, the one
tests/test_hybrid_family.py holds its family to: in float32 the program and
the reference differ only by the order of their sums (a chunk's closed form
against a step at a time, a grouped matmul against a scan over experts),
which reads 1e-6 to 1e-5; a state one token off, a convolution tap or its
bias out of place, a head paired with the wrong group of B and C, or an
expert dropped reads 1e-1 and more. The invariant everything here rests on:
after any forward, a lane's state stands at exactly the tokens whose keys
and values were written for it.
"""

import asyncio
import dataclasses
import functools
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine import EngineConfig, JaxEngine
from dynamo_tpu.llm.protocols import PreprocessedRequest
from dynamo_tpu.models import nemotron_h
from dynamo_tpu.ops.state_cache import alloc_state_cache, state_bytes_per_lane
from dynamo_tpu.runtime.engine import Context

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

from references import nemotron_h as ref  # noqa: E402

from . import test_hybrid_family as hybrid_tests  # noqa: E402
from .test_hybrid_family import (  # noqa: E402
    off, sequence, stream, table_of, with_lanes,
)

PAGE = 16
TOL = 1e-3  # deviations of the reference's logits (see the module's text)
CFG = nemotron_h.NemotronHConfig.tiny_nemotron_h(dtype=jnp.float32)
LM, LE, LA = nemotron_h.kinds(CFG)
CONFIG_FILE = os.path.join(
    ROOT, "benchmark", "configs", "nemotron-3-super-120b-a12b-ep4-d11.json")


@pytest.fixture(scope="module")
def params():
    return nemotron_h.init_params(CFG, jax.random.PRNGKey(0))


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


@functools.lru_cache(maxsize=None)
def REFERENCE(cfg, padded):
    return jax.jit(lambda p, t: ref.logits(p, cfg, t, n_last=padded))


def reference_logits(params, cfg, tokens):
    """The reference's logits at every position of `tokens`, and the experts
    it chose [routed layers, T, K]."""
    T = len(tokens)
    padded = -(-T // 64) * 64
    toks = np.zeros((padded,), np.int32)
    toks[:T] = tokens
    logits, _, chosen, _ = REFERENCE(cfg, padded)(params, jnp.asarray(toks))
    return np.asarray(logits)[:T], np.asarray(chosen)[:, :T]


PREFILL = jax.jit(lambda *a: nemotron_h.prefill_forward_batched(a[0], CFG, *a[1:]))
DECODE = jax.jit(lambda *a: nemotron_h.decode_forward(a[0], CFG, *a[1:]))
RAGGED = jax.jit(lambda *a: nemotron_h.ragged_forward(a[0], CFG, *a[1:]))
# one dispatch of each kind, as the sibling family's tests pack it
prefill = functools.partial(hybrid_tests.prefill, fn=PREFILL)
decode = functools.partial(hybrid_tests.decode, fn=DECODE)


def test_the_state_store_takes_its_shapes_from_the_family():
    """Three counts of layers, none of them `num_layers`: the state store
    over the state-space layers, the pools over the layers that attend, the
    recorded choices over the routed ones."""
    cache, kv_v = alloc_state_cache(CFG, 40, PAGE, 4, 128, 8)
    assert (LM, LE, LA) == (3, 3, 2) and CFG.num_layers == 8
    assert cache.state.shape == (LM, 5, 8, 16, 16) and cache.state.dtype == jnp.float32
    assert cache.conv.shape == (LM, 5, 3, 8 * 16 + 2 * 2 * 16)
    assert cache.pages.shape[0] == kv_v.shape[0] == LA
    assert cache.routed_ring.shape[1:] == (LE, 4, 3)
    assert cache.routed_flat.shape == (LE, 128, 3)
    assert state_bytes_per_lane(CFG) == LM * (8 * 16 * 16 * 4 + 3 * 192 * 4)


def test_chunks_then_decode_steps_equal_the_full_forward(params):
    """(i) One prefill chunk (several chunks of the recurrence's 16), a
    second chunk from the state the first left, then decode steps through
    pages and state: the reference's full forward at every position judged;
    and the experts the program says it chose are the reference's."""
    seq = sequence(1, 120)
    want, chosen = reference_logits(params, CFG, seq)
    cache, kv_v = alloc_state_cache(CFG, 40, PAGE, 4, 128, 8)
    tab = table_of(2)
    got, cache, kv_v = prefill(params, cache, kv_v, [(2, seq[:50], 0, tab)], 64)
    assert off(got[0], want[49]) < TOL
    assert (np.sort(np.asarray(cache.routed_flat)[:, :50], -1)
            == np.sort(chosen[:, :50], -1)).all()
    got, cache, kv_v = prefill(params, cache, kv_v, [(2, seq[50:90], 50, tab)], 64)
    assert off(got[0], want[89]) < TOL
    for t in range(90, 120):
        got, cache, kv_v = decode(params, cache, kv_v, {2: (seq[t], t)})
        assert off(got[2], want[t]) < TOL, t
        ring = np.asarray(cache.routed_ring)[t % cache.routed_ring.shape[0], :, 2]
        assert (np.sort(ring, -1) == np.sort(chosen[:, t], -1)).all()
    # the lanes that did not decode kept their (zero) state
    assert not np.asarray(cache.state)[:, [0, 1, 3]].any()


@pytest.mark.parametrize("n", [1, 2])
def test_a_mixed_step_of_prefill_rows_and_decode_rows(params, n):
    """(ii) Two prefill rows (a sequence's first chunk in a lane that holds
    another's stale state, and a second chunk) and three decode rows in
    one flat buffer: each row starts from its own lane's state and leaves
    its own behind. With n = 1 the decode rows take the decode step's
    recurrence over their lanes and the two prompts are gathered; with n =
    2 tokens a "decode" row, five rows are gathered where the forward
    expects three: three groups of two."""
    seqs = {lane: sequence(10 + lane, 70) for lane in range(4)}
    fresh = sequence(20, 33)
    want = {lane: reference_logits(params, CFG, s)[0] for lane, s in seqs.items()}
    want_fresh = reference_logits(params, CFG, fresh)[0]
    cache, kv_v = alloc_state_cache(CFG, 48, PAGE, 5, 256, 8)
    _, cache, kv_v = prefill(params, cache, kv_v, [
        (lane, seqs[lane][:40], 0, table_of(lane)) for lane in range(3)], 64)
    _, cache, kv_v = prefill(params, cache, kv_v, [
        (3, seqs[3][:24], 0, table_of(3)), (4, seqs[0][:30], 0, table_of(4))], 32)
    rows = [  # (lane, tokens, context)
        (4, fresh, 0), (3, seqs[3][24:61], 24),
        (0, seqs[0][40:40 + n], 40), (1, seqs[1][40:40 + n], 40),
        (2, seqs[2][40:40 + n], 40)]
    R, M = 8, 96
    toks, pos = np.zeros((M,), np.int32), np.full((M,), 8 * PAGE - 1, np.int32)
    row_ids = np.full((M,), R - 1, np.int32)
    starts, lens, ctx, last = (np.zeros((R,), np.int32) for _ in range(4))
    starts[:] = M
    tables = np.zeros((R, 8), np.int32)
    at = 0
    for r, (lane, tk, c0) in enumerate(rows):
        m = len(tk)
        toks[at: at + m], pos[at: at + m], row_ids[at: at + m] = tk, c0 + np.arange(m), r
        starts[r], lens[r], ctx[r], last[r], tables[r] = at, m, c0, at + m - 1, table_of(lane)
        at += m
    logits, cache, kv_v = RAGGED(
        params, *(jnp.asarray(a) for a in (toks, pos, row_ids)),
        with_lanes(cache, [r[0] for r in rows]), kv_v,
        *(jnp.asarray(a) for a in (tables, starts, lens, ctx, last)))
    logits = np.asarray(logits)
    assert off(logits[0], want_fresh[32]) < TOL  # the stale state was not read
    assert off(logits[1], want[3][60]) < TOL
    for r, lane in ((2, 0), (3, 1), (4, 2)):
        assert off(logits[r], want[lane][39 + n]) < TOL
    # ... and every lane goes on from the state the mixed step left
    got, cache, kv_v = decode(params, cache, kv_v, {
        0: (seqs[0][40 + n], 40 + n), 1: (seqs[1][40 + n], 40 + n),
        2: (seqs[2][40 + n], 40 + n), 3: (seqs[3][61], 61)})
    for lane, t in ((0, 40 + n), (1, 40 + n), (2, 40 + n), (3, 61)):
        assert off(got[lane], want[lane][t]) < TOL


def test_a_mixed_steps_one_token_rows_are_the_decode_steps(params):
    """A decode step and a mixed step share `lanes_step`: fed the same
    token, they leave a lane the same state and the same logits (the
    sibling family's test has the shape of the pack)."""
    stepped, packed_, by_step, by_pack = hybrid_tests.one_token_either_way(
        params, CFG, prefill, decode, RAGGED)
    scale = np.abs(stepped[:, :3]).max()
    assert scale > 0 and np.abs(packed_[:, :3] - stepped[:, :3]).max() < 1e-5 * scale
    assert off(by_pack, by_step) < 1e-4
    assert packed_[:, 3].any() and not stepped[:, 3].any()
    assert not packed_[:, 4:].any()


def test_the_chunked_and_the_step_form_of_the_recurrence_agree():
    """(iii) ssd_chunk over a chunk of tokens against ssm_step a token at a
    time, from a state that is not zero, with more heads than groups; a
    tail of tokens whose inputs are 0 (padding) leaves the state as it was.
    1e-4 of the largest value: float32 sums in another order."""
    R, L, nh, hd, G, N = 3, 32, 8, 4, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(5), 6)
    real = jnp.arange(L)[None, :, None] < jnp.asarray([L, 17, 1])[:, None, None]
    x = jax.random.normal(ks[0], (R, L, nh, hd)) * real[..., None]
    B = jax.random.normal(ks[1], (R, L, G, N)) * real[..., None]
    C = jax.random.normal(ks[2], (R, L, G, N)) * real[..., None]
    dt = jax.random.uniform(ks[3], (R, L, nh), minval=0.001, maxval=0.5) * real
    A = -jax.random.uniform(ks[4], (nh,), minval=1.0, maxval=16.0)
    S0 = jax.random.normal(ks[5], (R, nh, hd, N))
    S, want = S0, []
    for t in range(L):
        S, y = nemotron_h.ssm_step(S, x[:, t], B[:, t], C[:, t], dt[:, t], A=A)
        want.append(y)
    got_S, got = nemotron_h.ssd_chunk(S0, x, B, C, dt, A=A)
    want = jnp.stack(want, axis=1)
    assert float(jnp.abs(got_S - S).max()) < 1e-4 * float(jnp.abs(S).max())
    assert float(jnp.abs(jnp.where(real[..., None], got - want, 0)).max()) \
        < 1e-4 * float(jnp.abs(want).max())
    # the padded tail of row 2 (one real token) changed nothing behind it
    S1, _ = nemotron_h.ssm_step(S0[2], x[2, 0], B[2, 0], C[2, 0], dt[2, 0], A=A)
    assert float(jnp.abs(got_S[2] - S1).max()) < 1e-5 * float(jnp.abs(S1).max())


def routed_parts(params_of, cfg_of, x, shares):
    """Each share's routed part of one layer's block over x (its output
    less x and what every chip computes alike), and the experts chosen."""
    parts = []
    for first in shares:
        cfg = cfg_of(first)
        p = params_of(cfg)["layers"]["experts"]
        stacks = {k: p[k] for k in ("w1", "w2")}
        layer = {k: v[0] for k, v in p.items() if k not in stacks}
        block = jax.jit(nemotron_h.routed_block, static_argnums=(2, 4))
        whole, idx = block(layer, stacks, 0, x, cfg)
        alone, _ = block(layer, jax.tree.map(jnp.zeros_like, stacks), 0, x, cfg)
        parts.append((np.asarray(whole - alone), np.asarray(idx), np.asarray(alone - x)))
    return parts


def test_the_four_shares_add_up_to_the_uncut_layer(params):
    """(iv) The guide's section 4: each of four chips routes over the
    router's full width and computes its own experts' part (the latent
    projections around it, as the model states them: the up projection is
    linear, so the parts add up behind it); the four parts, with the shared
    expert counted once, are the uncut reference's layer; a token none of
    whose experts a chip holds gets the shared expert's part alone there.
    It also holds `init_params` to the share: an expert's weights are the
    uncut model's, whichever share holds them."""
    held = 2  # of a router 8 wide: four shares
    key = jax.random.PRNGKey(3)

    def cfg_of(first):
        return dataclasses.replace(CFG, num_experts=held, first_expert_held=first)

    def params_of(cfg):
        return nemotron_h.init_params(cfg, key)

    uncut = dataclasses.replace(CFG, num_experts=CFG.router_width, first_expert_held=0)
    x = jax.random.normal(jax.random.PRNGKey(4), (24, CFG.hidden_size), jnp.float32)
    w = jax.tree.map(lambda a: a[0], params_of(uncut)["layers"]["experts"])
    free = jnp.full((24, CFG.num_experts_per_tok), -1, jnp.int32)
    want, (_, chosen, _) = ref.latent_moe(x, w, uncut, free)
    want, chosen = np.asarray(want - x), np.asarray(chosen)
    parts = routed_parts(params_of, cfg_of, x, range(0, CFG.router_width, held))
    for first, (part, idx, _) in zip(range(0, CFG.router_width, held), parts):
        assert (np.sort(idx, -1) == np.sort(chosen, -1)).all()  # the full width
        assert idx.max() >= held  # ids of experts held elsewhere among them
        none_here = ~((chosen >= first) & (chosen < first + held)).any(-1)
        assert none_here.any() and not part[none_here].any()
        assert np.abs(part[~none_here]).max() > 0
    shared = parts[0][2]
    total = sum(p for p, _, _ in parts) + shared
    assert np.abs(total - want).max() / np.abs(want).max() < TOL


def test_no_token_is_dropped_whatever_the_batch(params):
    """(v) 32 tokens that all choose the same experts (a capacity of
    tokens x k / experts x 1.25 would hold 15 of them): the reference's
    result, to the tolerance."""
    x = jnp.tile(jax.random.normal(jax.random.PRNGKey(6), (1, CFG.hidden_size)), (32, 1))
    x = x + 1e-4 * jax.random.normal(jax.random.PRNGKey(7), x.shape)
    p = params["layers"]["experts"]
    stacks = {k: p[k] for k in ("w1", "w2")}
    layer = {k: v[2] for k, v in p.items() if k not in stacks}
    got, idx = jax.jit(nemotron_h.routed_block, static_argnums=(2, 4))(
        layer, stacks, 2, x, CFG)
    assert (np.sort(np.asarray(idx), -1) == np.sort(np.asarray(idx[0]))).all()
    assert (np.asarray(idx[0]) < CFG.num_experts).any(), "no held expert chosen: reseed"
    w = jax.tree.map(lambda a: a[2], p)
    want, _ = ref.latent_moe(x, w, CFG, jnp.full((32, CFG.num_experts_per_tok), -1, jnp.int32))
    assert off(np.asarray(got - x), np.asarray(want - x)) < TOL


def test_the_choice_follows_the_biased_score_and_the_weights_the_score(params):
    """A choice bias large enough to decide: the experts chosen are the k
    with the largest bias, whatever they score; their weights are the
    SCORES there over their sum times the scaling factor, in which the bias
    has no part; with the bias at zero the k best scores are chosen; and
    the program's choice is the reference's either way."""
    K, W = CFG.num_experts_per_tok, CFG.router_width
    layer = {k: v[1] for k, v in params["layers"]["experts"].items()
             if k not in ("w1", "w2")}
    x = jax.random.normal(jax.random.PRNGKey(8), (16, CFG.hidden_size), jnp.float32)
    h = nemotron_h.norm(x, layer["norm"], CFG.rms_norm_eps)
    scores = np.asarray(jax.nn.sigmoid(h @ layer["router"]))
    favoured = np.array([6, 1, 4])
    bias = np.zeros((W,), np.float32)
    bias[favoured] = 10.0
    idx, weight = nemotron_h.route(h, dict(layer, router_bias=jnp.asarray(bias)), CFG)
    assert (np.sort(np.asarray(idx), -1) == np.sort(favoured)).all()
    at = np.take_along_axis(scores, np.asarray(idx), -1)
    want = CFG.routed_scaling_factor * at / at.sum(-1, keepdims=True)
    assert np.abs(np.asarray(weight) - want).max() < 1e-6
    assert abs(float(weight.sum(-1)[0]) - CFG.routed_scaling_factor) < 1e-5
    idx0, _ = nemotron_h.route(h, dict(layer, router_bias=jnp.zeros((W,))), CFG)
    assert (np.sort(np.asarray(idx0), -1) == np.sort(np.argsort(-scores, -1)[:, :K], -1)).all()
    # the seeded bias matters: some token's choice differs from the unbiased one
    idx1, _ = nemotron_h.route(h, layer, CFG)
    w = jax.tree.map(lambda a: a[1], params["layers"]["experts"])
    _, (_, chosen, deficit) = ref.latent_moe(x, w, CFG, jnp.full((16, K), -1, jnp.int32))
    assert (np.sort(np.asarray(idx1), -1) == np.sort(np.asarray(chosen), -1)).all()
    assert not np.asarray(deficit).any()


# ---------------------------------------------------------------------- #
# (vi) through JaxEngine
# ---------------------------------------------------------------------- #


def engine(params, **over):
    # one mixed-step program: one token bucket, one table width
    kw = dict(model="tiny-nemotron-h", max_num_seqs=4, page_size=PAGE, num_pages=128,
              max_model_len=256, prefill_buckets=(32,), max_prefill_chunk=32,
              mixed_max_tokens=64)
    kw.update(over)
    eng = JaxEngine(EngineConfig(**kw), model_config=CFG, params=params)
    eng._mixed_table_rungs = (eng.config.max_pages_per_seq,)
    return eng


def reference_greedy(params, prompt, n):
    seq = list(prompt)
    for _ in range(n):
        seq.append(int(reference_logits(params, CFG, seq)[0][-1].argmax()))
    return seq[len(prompt):]


def test_the_engine_serves_the_references_tokens_and_says_what_it_routed(params):
    """Three requests that arrive apart, so that prefill chunks share mixed
    steps with decode lanes: greedy tokens are the reference's; an annotated
    request's frames carry one row [3 routed layers][k] of ids under the
    router's width for each input position of prompt + served[:-1], the
    prompt's with the first frame, and the rows are the reference's choices;
    an unannotated request's frames carry none; the family's counters are
    exported, the held experts' bytes a part of the step's."""
    prompts = [sequence(30, 40), sequence(31, 70), sequence(32, 21)]

    async def run():
        eng = engine(params)
        assert eng.stateful and "Nemotron-H" in eng.STATE_FAMILY
        out = await asyncio.gather(
            stream(eng, prompts[0], "a", 30, ["routed_experts"]),
            stream(eng, prompts[1], "b", 20, ["routed_experts"], delay=0.3),
            stream(eng, prompts[2], "c", 25, delay=0.6))
        stats = eng.stats()
        await eng.close()
        return out, stats

    out, stats = asyncio.run(run())
    for prompt, (toks, rows, frames), n in zip(prompts, out, (30, 20, 25)):
        assert toks == reference_greedy(params, prompt, n)
    for prompt, (toks, rows, frames) in zip(prompts[:2], out[:2]):
        assert len(rows) == len(prompt) + len(toks) - 1
        assert len(frames[0]["routed_experts"]) == len(prompt)
        got = np.asarray(rows)
        assert got.shape[1:] == (LE, CFG.num_experts_per_tok)
        assert 0 <= got.min() and CFG.num_experts <= got.max() < CFG.router_width
        chosen = reference_logits(params, CFG, prompt + toks[:-1])[1]
        assert (np.sort(got, -1) == np.sort(chosen.transpose(1, 0, 2), -1)).all()
    assert not out[2][1] and all("routed_experts" not in f for f in out[2][2])
    assert stats["routed_rows_emitted"] == len(out[0][1]) + len(out[1][1])
    assert stats["state_lanes_reset"] == 3 and stats["mixed_steps"] > 0
    assert stats["state_bytes"] == 5 * state_bytes_per_lane(CFG)
    # every row a mixed step packed took one of the recurrence's two roads
    assert stats["state_rows_in_place"] + stats["state_rows_gathered"] == (
        stats["mixed_rows_plain"])
    assert stats["state_rows_in_place"] > stats["state_rows_gathered"] > 0
    assert 0 < stats["step_state_bytes"] < stats["step_min_bytes"]
    assert 0 < stats["step_expert_bytes"] < stats["step_min_bytes"]
    assert 0 < stats["expert_rows_routed"] <= stats["expert_rows_computed"]
    assert stats["step_model_flops"] > 0
    # a stateful family with no kernel for its recurrence says so
    assert stats["attention_impl"] == dict.fromkeys(
        ("decode", "prefill", "ragged", "recurrence"), "xla")


def test_a_lane_reused_and_a_sequence_resumed_give_a_fresh_engines_tokens(params):
    """One lane: the second request takes the lane the first one left its
    state in. Then a pool too small for three sequences: one is preempted,
    comes back with its prompt recomputed from a zero state, and every
    request still reads the reference's tokens."""
    prompts = [sequence(40 + i, 16) for i in range(3)]
    want = [reference_greedy(params, p, 24) for p in prompts]

    async def one_lane():
        eng = engine(params, max_num_seqs=1)
        first = await stream(eng, prompts[0], "first", 24)
        second = await stream(eng, prompts[1], "second", 24)
        resets = eng.stats()["state_lanes_reset"]
        await eng.close()
        return first[0], second[0], resets

    first, second, resets = asyncio.run(one_lane())
    assert (first, second, resets) == (want[0], want[1], 2)

    async def contended():
        # each needs (16 + 24 + 1) / 16 = 3 pages: 3 sequences, 7 pages
        eng = engine(params, num_pages=7, max_model_len=64, prefill_buckets=(16,),
                     max_prefill_chunk=16, decode_block_steps=4,
                     enable_prefix_caching=False)
        got = await asyncio.gather(*(
            stream(eng, p, f"r{i}", 24) for i, p in enumerate(prompts)))
        n = eng.num_preemptions
        await eng.close()
        return [g[0] for g in got], n

    got, preemptions = asyncio.run(contended())
    assert preemptions > 0, "the pool held all three: nothing was preempted"
    assert got == want


def test_the_prefix_index_hands_a_stateful_sequence_no_cached_pages(params):
    """A second request with the first one's prompt: its blocks are in the
    prefix index, nobody kept the state that stood at their end, so it gets
    none of them, recomputes, reads the same tokens, and the counter says
    how many blocks were declined."""
    prompt = sequence(50, 48)

    async def run():
        eng = engine(params)
        first = await stream(eng, prompt, "p1", 8)
        before = eng.stats()
        second = await stream(eng, prompt, "p2", 8)
        after = eng.stats()
        await eng.close()
        return first[0], second[0], before, after

    first, second, before, after = asyncio.run(run())
    assert first == second == reference_greedy(params, prompt, 8)
    assert before["state_prefix_hits_declined"] == 0
    assert after["state_prefix_hits_declined"] == 48 // PAGE
    assert after["kv_prefix_hit_blocks_total"] == 0


@pytest.mark.parametrize("over, what", [
    (dict(kvbm_host_blocks=8), "KVBM"),
    (dict(spec_mode="ngram"), "speculative"),
    (dict(role="prefill"), "disaggregated"),
    (dict(kv_quant="int8"), "--kv-quant"),
    (dict(tp_size=2), "mesh"),
])
def test_what_cannot_follow_a_state_is_refused_at_start_by_name(params, over, what):
    with pytest.raises(ValueError) as e:
        engine(params, **over)
    assert "Nemotron-H family" in str(e.value) and what in str(e.value)


def test_the_disaggregated_entries_refuse_a_stateful_family(params):
    """The disaggregated hand-off arrives by request and is refused there,
    by the same code as the hybrid family and in this family's name."""
    async def run():
        eng = engine(params)
        req = PreprocessedRequest(
            token_ids=sequence(60, 20), stop_conditions={"max_tokens": 4},
            request_id="d", disagg_params={"return_kv": True}).to_dict()
        items = [i async for i in eng.generate(req, Context())]
        slot, err = await eng._decode_entry_slot(req, Context(), None)
        pull = eng.begin_streamed_pull(req, Context(), {})
        await eng.close()
        return items, slot, err, pull

    items, slot, err, pull = asyncio.run(run())
    assert items[0].get("event") == "error" and "Nemotron-H family" in str(items[0])
    assert slot is None and "Nemotron-H family" in err and pull is None


def test_the_configuration_loads_into_the_dataclass():
    """The benchmark's file, plain and under `rehearsal`, fills
    NemotronHConfig field by field; the cut is what it says (the first 11
    layers of the published pattern: 5 state-space, 5 routed, 1 that
    attends; 128 of 512 experts from 0; a quarter of the vocabulary), no
    width differs from the published row, the routed layers are what the
    harness reckons from the file (`num_hidden_layers` less
    `num_dense_layers`), and the bytes are the arithmetic's."""
    import files_check
    from worker_entry import build_model_config, load_config, lookup

    for rehearsal in (False, True):
        cfg = load_config(CONFIG_FILE, rehearsal)
        built = build_model_config(cfg)
        assert type(built) is nemotron_h.NemotronHConfig
        for field, key in cfg["dataclass_fields"].items():
            assert getattr(built, field) == lookup(cfg, key), field
        Lm, Le, La = nemotron_h.kinds(built)
        assert built.router_width > built.num_experts
        width, per_token, routed = files_check.routed_geometry("nemotron", cfg)
        assert (width, per_token, routed) == (
            built.router_width, built.num_experts_per_tok, Le)
        assert cfg["num_dense_layers"] == Lm + La
    cfg = load_config(CONFIG_FILE, False)
    built = build_model_config(cfg)
    assert nemotron_h.kinds(built) == (5, 5, 1) and built.pattern == "MEMEMEM*EME"
    assert (built.num_experts, built.router_width, built.first_expert_held,
            built.num_experts_per_tok, built.vocab_size) == (128, 512, 0, 22, 32768)
    assert cfg["published"]["hybrid_override_pattern"].startswith(built.pattern)
    # the catalog's row, where the guides are installed beside the checkout
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "NVIDIA-Nemotron-3-Super-120B-A12B-BF16")
        assert cfg["source"] == row["source_url"]
        for key, value in row["config"].items():
            published = cfg["published"][key] if key in cfg["reduced"] else cfg[key]
            assert published == value, key
    assert state_bytes_per_lane(built) == 5 * (4_194_304 + 61_440)
    shapes = jax.eval_shape(lambda: nemotron_h.init_params(built, jax.random.PRNGKey(0)))
    nbytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(shapes))
    assert 9.2e9 < nbytes < 9.4e9  # the issue's 9.3 GB
    # what a decode step of 32 lanes at a context of 256 asks for: the
    # issue's reckoning (8.6 GB, experts 62%, the state and the mixers'
    # weights most of the rest)
    _, nbytes, state, experts = nemotron_h.step_work(
        built, 32, 32 * 256, 1, rows=32)
    assert 8.0e9 < nbytes < 9.2e9 and 0.55 < experts / nbytes < 0.68
    assert state == 2 * 32 * state_bytes_per_lane(built)
