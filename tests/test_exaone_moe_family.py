"""models/exaone_moe.py against benchmark/references/exaone_moe.py, and the
engine's rings beside the pages for a family whose window layers forget
(docs/hybrid_models.md, "A ring beside the pages").

CPU, tiny sizes (a window of 8 under contexts of 10 windows and more),
float32 weights and activations, seeded random weights, the matmul precision
"highest" on both sides. The tolerance is 1e-3 deviations of the reference's
logits at a position, the one tests/test_hybrid_family.py holds its family
to: in float32 the program and the reference differ only by the order of
their sums (a softmax in two parts, the ring's and the step's own keys,
against one over the whole sequence; a grouped matmul against a scan over
experts), which reads 1e-6 to 1e-5; a ring one slot off, a key rotated at the
wrong position, a position past the window let in or an expert dropped reads
1e-2 and more. The invariant everything here rests on: after any forward, a
lane's rings stand at exactly the tokens whose full-layer keys and values
were written for it. The reference has no ring: it masks one band over the
whole sequence.
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
from dynamo_tpu.models import exaone_moe
from dynamo_tpu.ops.state_cache import alloc_state_cache, state_bytes_per_lane
from dynamo_tpu.ops.window_attention import ring_positions
from dynamo_tpu.runtime.engine import Context

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

from references import exaone_moe as ref  # noqa: E402

from . import test_hybrid_family as hybrid_tests  # noqa: E402
from .test_hybrid_family import off, packed, sequence, stream, table_of  # noqa: E402

PAGE = 16
TOL = 1e-3  # deviations of the reference's logits (see the module's text)
CFG = exaone_moe.ExaoneMoeConfig.tiny_exaone_moe(dtype=jnp.float32)
LW, LF, LD, LE = exaone_moe.kinds(CFG)
W = CFG.sliding_window
CONFIG_FILE = os.path.join(
    ROOT, "benchmark", "configs", "k-exaone-236b-a23b-ep8-d8.json")


@pytest.fixture(scope="module")
def params():
    return exaone_moe.init_params(CFG, jax.random.PRNGKey(0))


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


@functools.lru_cache(maxsize=None)
def REFERENCE(cfg, padded):
    return jax.jit(lambda p, t: ref.logits(p, cfg, t, n_last=padded))


def reference_logits(params, cfg, tokens):
    """The reference's logits at every position of `tokens`, and the experts
    it chose [sparse layers, T, K]."""
    T = len(tokens)
    padded = -(-T // 64) * 64
    toks = np.zeros((padded,), np.int32)
    toks[:T] = tokens
    logits, _, chosen, _ = REFERENCE(cfg, padded)(params, jnp.asarray(toks))
    return np.asarray(logits)[:T], np.asarray(chosen)[:, :T]


PREFILL = jax.jit(lambda *a: exaone_moe.prefill_forward_batched(a[0], CFG, *a[1:]))
DECODE = jax.jit(lambda *a: exaone_moe.decode_forward(a[0], CFG, *a[1:]))
RAGGED = jax.jit(lambda *a: exaone_moe.ragged_forward(a[0], CFG, *a[1:]))
# one dispatch of each kind, as the sibling families' tests pack it
prefill = functools.partial(hybrid_tests.prefill, fn=PREFILL)
decode = functools.partial(hybrid_tests.decode, fn=DECODE)


def test_the_rings_take_their_shapes_from_the_family():
    """Four counts of layers: the rings over the window layers, the pools
    over the full ones, the recorded choices over the sparse ones; a lane's
    rings are W positions of K and of V a window layer, whatever the
    longest context."""
    cache, kv_v = alloc_state_cache(CFG, 40, PAGE, 4, 128, 8)
    assert (LW, LF, LD, LE) == (6, 2, 1, 7) and CFG.num_layers == 8
    assert [exaone_moe.is_window(CFG, li) for li in range(8)] == [
        True, True, True, False] * 2
    assert cache.state.shape == cache.conv.shape == (LW, 5, W, 2 * 16)
    assert cache.state.dtype == cache.conv.dtype == CFG.dtype
    assert cache.pages.shape[0] == kv_v.shape[0] == LF
    assert cache.routed_ring.shape[1:] == (LE, 4, 3)
    assert cache.routed_flat.shape == (LE, 128, 3)
    assert state_bytes_per_lane(CFG) == LW * 2 * (W * 2 * 16 * 4)


def test_a_slot_holds_the_last_position_of_its_residue():
    """ring_positions: slot s holds the largest p < context with p % W == s,
    and a negative number where the sequence has none yet (an empty ring at
    context 0: nothing is ever cleared)."""
    got = np.asarray(ring_positions(jnp.asarray([0, 5, 8, 21]), 8))
    assert (got[0] < 0).all()
    assert got[1].tolist()[:5] == [0, 1, 2, 3, 4] and (got[1][5:] < 0).all()
    assert got[2].tolist() == list(range(8))
    assert got[3].tolist() == [16, 17, 18, 19, 20, 13, 14, 15]


def test_chunks_then_decode_steps_equal_the_full_forward(params):
    """(i) One prefill chunk of 6 windows, a second chunk from the rings the
    first left (its first tokens reach back into them), then decode steps
    through pages and rings that have wrapped many times, to a context of 15
    windows: the reference's full forward, which has no ring, at every
    position judged; and the experts the program says it chose are the
    reference's."""
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
    # the lanes that did not decode kept their (zero) rings
    assert not np.asarray(cache.state)[:, [0, 1, 3]].any()
    assert not np.asarray(cache.conv)[:, [0, 1, 3]].any()


def test_a_chunk_shorter_than_the_window_keeps_the_rings_older_slots(params):
    """Chunks of 3 and 5 tokens behind a chunk of 20: the ring keeps the
    slots a short chunk did not reach, and the next token still reads the
    reference's window."""
    seq = sequence(2, 40)
    want, _ = reference_logits(params, CFG, seq)
    cache, kv_v = alloc_state_cache(CFG, 40, PAGE, 4, 128, 8)
    tab = table_of(1)
    at = 0
    for n in (20, 3, 5, 1, 11):
        got, cache, kv_v = prefill(
            params, cache, kv_v, [(1, seq[at: at + n], at, tab)], 32)
        at += n
        assert off(got[0], want[at - 1]) < TOL, at


@pytest.mark.parametrize("n", [1, 2])
def test_a_mixed_step_of_prefill_rows_and_decode_rows(params, n):
    """(ii) Two prefill rows (a sequence's first chunk in a lane that holds
    another's stale rings, and a second chunk) and three decode rows in one
    flat buffer: each row reads its own lane's rings and leaves its own
    behind. `long_rows` says how many rows of more than one token the pack
    holds at most: two where a decode row is one token, five where it is
    two (n = 2), and only those rows' later tokens meet their rings."""
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
    long_rows = 2 if n == 1 else 5
    ragged = jax.jit(lambda *a: exaone_moe.ragged_forward(
        a[0], CFG, *a[1:], long_rows=long_rows))
    logits, cache, kv_v = ragged(params, *packed(rows, cache, kv_v, 8, 96))
    logits = np.asarray(logits)
    assert off(logits[0], want_fresh[32]) < TOL  # the stale rings were not read
    assert off(logits[1], want[3][60]) < TOL
    for r, lane in ((2, 0), (3, 1), (4, 2)):
        assert off(logits[r], want[lane][39 + n]) < TOL
    # ... and every lane goes on from the rings the mixed step left
    got, cache, kv_v = decode(params, cache, kv_v, {
        0: (seqs[0][40 + n], 40 + n), 1: (seqs[1][40 + n], 40 + n),
        2: (seqs[2][40 + n], 40 + n), 3: (seqs[3][61], 61)})
    for lane, t in ((0, 40 + n), (1, 40 + n), (2, 40 + n), (3, 61)):
        assert off(got[lane], want[lane][t]) < TOL


def test_the_mixed_step_without_a_count_of_long_rows_takes_the_engines(params):
    """`long_rows` None: the rows past the lanes (R - lanes, the engine's
    prefill batch) are the long ones."""
    seqs = {lane: sequence(60 + lane, 40) for lane in range(2)}
    want = {lane: reference_logits(params, CFG, s)[0] for lane, s in seqs.items()}
    cache, kv_v = alloc_state_cache(CFG, 48, PAGE, 4, 256, 8)
    _, cache, kv_v = prefill(params, cache, kv_v, [
        (0, seqs[0][:30], 0, table_of(0)), (1, seqs[1][:10], 0, table_of(1))], 32)
    rows = [(1, seqs[1][10:35], 10), (0, seqs[0][30:31], 30)]
    logits, cache, kv_v = RAGGED(params, *packed(rows, cache, kv_v, 8, 64))
    assert off(np.asarray(logits)[0], want[1][34]) < TOL
    assert off(np.asarray(logits)[1], want[0][30]) < TOL


def one_layer(pattern):
    """One layer of the kind `pattern` names (and a sparse feed-forward, a
    token's own), so that what a position can see is the layer's own mask
    and no more (two stacked window layers see 2 (W - 1) positions back)."""
    return dataclasses.replace(
        CFG, num_layers=1, sliding_window_pattern=pattern,
        first_k_dense_replace=0)


@pytest.mark.parametrize("side", ["program", "reference"])
def test_the_window_is_really_there(side):
    """A token at position t - W altered: the window layer's logits at t do
    not move at all, its full-attention twin's do; the token at t - W + 1,
    the window's oldest, moves both. On the program (a chunk, then decode
    steps through the ring) and on the reference (one banded mask)."""
    t = 5 * W + 3
    seq = sequence(70, t + 1)

    def last_logits(cfg, p, tokens):
        if side == "reference":
            return reference_logits(p, cfg, tokens)[0][t]
        cache, kv_v = alloc_state_cache(cfg, 40, PAGE, 4, 128, 8)
        fwd = functools.partial(exaone_moe.prefill_forward_batched, p, cfg)
        _, cache, kv_v = hybrid_tests.prefill(
            p, cache, kv_v, [(0, tokens[: t - 2], 0, table_of(0))], 64,
            fn=lambda _, *a: fwd(*a))
        for at in range(t - 2, t + 1):
            got, cache, kv_v = hybrid_tests.decode(
                p, cache, kv_v, {0: (tokens[at], at)},
                fn=lambda _, *a: exaone_moe.decode_forward(p, cfg, *a))
        return np.asarray(got)[0]

    moved = {}
    for pattern in "LG":
        cfg = one_layer(pattern)
        p = exaone_moe.init_params(cfg, jax.random.PRNGKey(1))
        base = last_logits(cfg, p, seq)
        for back in (W, W - 1):
            other = list(seq)
            other[t - back] = (other[t - back] + 7) % CFG.vocab_size
            moved[pattern, back] = float(
                np.abs(last_logits(cfg, p, other) - base).max() / base.std())
    assert moved["L", W] == 0.0, moved
    assert moved["G", W] > 1e-3 and moved["L", W - 1] > 1e-3 and moved["G", W - 1] > 1e-3


def routed_parts(params_of, cfg_of, x, shares):
    """Each share's routed part of one sparse layer's block over x (its
    output less x and what every chip computes alike), and the experts
    chosen."""
    parts = []
    for first in shares:
        cfg = cfg_of(first)
        p = params_of(cfg)["layers"]["experts"]
        stacks = {k: p[k] for k in ("w_gate", "w_up", "w_down")}
        layer = {k: v[0] for k, v in p.items() if k not in stacks}
        block = jax.jit(exaone_moe.routed_block, static_argnums=(2, 4))
        whole, idx = block(layer, stacks, 0, x, cfg)
        alone, _ = block(layer, jax.tree.map(jnp.zeros_like, stacks), 0, x, cfg)
        parts.append((np.asarray(whole - alone), np.asarray(idx), np.asarray(alone - x)))
    return parts


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """(iv) The guide's section 4: each of eight chips routes over the
    router's full width and computes its own expert's part; the eight parts,
    with the shared expert counted once, are the uncut reference's layer; a
    token none of whose experts a chip holds gets the shared expert's part
    alone there. It also holds `init_params` to the share: an expert's
    weights are the uncut model's, whichever share holds them."""
    held = 1  # of a router 8 wide: eight shares
    key = jax.random.PRNGKey(3)

    def cfg_of(first):
        return dataclasses.replace(CFG, num_experts=held, first_expert_held=first)

    def params_of(cfg):
        return exaone_moe.init_params(cfg, key)

    uncut = dataclasses.replace(CFG, num_experts=CFG.router_width, first_expert_held=0)
    x = jax.random.normal(jax.random.PRNGKey(4), (24, CFG.hidden_size), jnp.float32)
    w = jax.tree.map(lambda a: a[0], params_of(uncut)["layers"]["experts"])
    free = jnp.full((24, CFG.num_experts_per_tok), -1, jnp.int32)
    h = ref.rms(x, w["norm"], CFG.rms_norm_eps)
    want, (_, chosen, _) = ref.sparse_ffn(h, w, uncut, free)
    want, chosen = np.asarray(want), np.asarray(chosen)
    shares = range(0, CFG.router_width, held)
    parts = routed_parts(params_of, cfg_of, x, shares)
    for first, (part, idx, _) in zip(shares, parts):
        assert (np.sort(idx, -1) == np.sort(chosen, -1)).all()  # the full width
        none_here = ~((chosen >= first) & (chosen < first + held)).any(-1)
        assert none_here.any() and not part[none_here].any()
        assert np.abs(part[~none_here]).max() > 0
    shared = parts[0][2]
    total = sum(p for p, _, _ in parts) + shared
    assert np.abs(total - want).max() / np.abs(want).max() < TOL


def test_no_token_is_dropped_whatever_the_batch(params):
    """(v) 32 tokens that all choose the same experts (a capacity of tokens
    x k / experts x 1.25 would hold 15 of them): the reference's result, to
    the tolerance."""
    x = jnp.tile(jax.random.normal(jax.random.PRNGKey(6), (1, CFG.hidden_size)), (32, 1))
    x = x + 1e-4 * jax.random.normal(jax.random.PRNGKey(7), x.shape)
    p = params["layers"]["experts"]
    stacks = {k: p[k] for k in ("w_gate", "w_up", "w_down")}
    layer = {k: v[2] for k, v in p.items() if k not in stacks}
    got, idx = jax.jit(exaone_moe.routed_block, static_argnums=(2, 4))(
        layer, stacks, 2, x, CFG)
    assert (np.sort(np.asarray(idx), -1) == np.sort(np.asarray(idx[0]))).all()
    assert (np.asarray(idx[0]) < CFG.num_experts).any(), "no held expert chosen: reseed"
    w = jax.tree.map(lambda a: a[2], p)
    want, _ = ref.sparse_ffn(ref.rms(x, w["norm"], CFG.rms_norm_eps), w, CFG,
                             jnp.full((32, CFG.num_experts_per_tok), -1, jnp.int32))
    assert off(np.asarray(got - x), np.asarray(want)) < TOL


def test_the_router_is_the_one_the_nemotron_family_routes_by(params):
    """The router moved to where both families import it
    (moe.sigmoid_route): the choice follows score + bias, the weights the
    score, renormalised and scaled, and the program's choice is the
    reference's."""
    from dynamo_tpu.models import moe, nemotron_h

    K, width = CFG.num_experts_per_tok, CFG.router_width
    layer = {k: v[1] for k, v in params["layers"]["experts"].items()
             if k not in ("w_gate", "w_up", "w_down")}
    x = jax.random.normal(jax.random.PRNGKey(8), (16, CFG.hidden_size), jnp.float32)
    h = exaone_moe.norm(x, layer["norm"], CFG.rms_norm_eps)
    scores = np.asarray(jax.nn.sigmoid(h @ layer["router"]))
    favoured = np.array([6, 1, 4])
    bias = np.zeros((width,), np.float32)
    bias[favoured] = 10.0
    idx, weight = exaone_moe.route(h, dict(layer, router_bias=jnp.asarray(bias)), CFG)
    assert (np.sort(np.asarray(idx), -1) == np.sort(favoured)).all()
    at = np.take_along_axis(scores, np.asarray(idx), -1)
    want = CFG.routed_scaling_factor * at / at.sum(-1, keepdims=True)
    assert np.abs(np.asarray(weight) - want).max() < 1e-6
    # one function behind both families' routers
    ncfg = nemotron_h.NemotronHConfig.tiny_nemotron_h(
        num_experts_per_tok=K, routed_scaling_factor=CFG.routed_scaling_factor)
    for got, same in zip(nemotron_h.route(h, layer, ncfg), moe.sigmoid_route(
            h, layer["router"], layer["router_bias"], K, True,
            CFG.routed_scaling_factor)):
        assert (np.asarray(got) == np.asarray(same)).all()
    idx1, _ = exaone_moe.route(h, layer, CFG)
    w = jax.tree.map(lambda a: a[1], params["layers"]["experts"])
    _, (_, chosen, deficit) = ref.sparse_ffn(h, w, CFG, jnp.full((16, K), -1, jnp.int32))
    assert (np.sort(np.asarray(idx1), -1) == np.sort(np.asarray(chosen), -1)).all()
    assert not np.asarray(deficit).any()


def test_what_a_step_asks_for_does_not_grow_with_a_lanes_context():
    """The host's count of a decode step (step_work): the window layers'
    K and V bytes are W positions a lane at a context of 4 windows and of 40
    alike, where layers that keep pages would read ten times as much at the
    second; the share falls with the context; the full layers' bytes grow."""
    def at(context):
        return exaone_moe.step_work(CFG, 4, 4 * context, 1, rows=4)

    short, long = at(4 * W), at(40 * W)
    assert short[4] == long[4] == LW * 4 * W * (2 * 2 * 16 * 4)
    assert long[5] == 10 * short[5] and short[4] * 4 == short[5]
    assert short[4] / short[5] > long[4] / long[5]
    assert short[2] == long[2] and short[3] == long[3]  # rings, experts
    assert long[1] - short[1] == LF * (2 * 2 * 16 * 4) * 4 * 36 * W
    # under a window every position is read: the share is 100%
    under = exaone_moe.step_work(CFG, 4, 4 * 5, 1, rows=4)
    assert under[4] == under[5]


# ---------------------------------------------------------------------- #
# (vi) through JaxEngine
# ---------------------------------------------------------------------- #


def engine(params, **over):
    # one mixed-step program: one token bucket, one table width
    kw = dict(model="tiny-exaone-moe", max_num_seqs=4, page_size=PAGE, num_pages=128,
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
    """Three requests that arrive apart, so that prefill chunks (of several
    windows, in chunks of 32) share mixed steps with decode lanes: greedy
    tokens are the reference's; an annotated request's frames carry one row
    [7 sparse layers][k] of ids under the router's width for each input
    position of prompt + served[:-1], the prompt's with the first frame, and
    the rows are the reference's choices; an unannotated request's frames
    carry none; the family's counters are exported, the window's among
    them."""
    prompts = [sequence(30, 40), sequence(31, 70), sequence(32, 21)]

    async def run():
        eng = engine(params)
        assert eng.stateful and "EXAONE-MoE" in eng.STATE_FAMILY
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
    assert 0 < stats["step_state_bytes"] < stats["step_min_bytes"]
    assert 0 < stats["step_expert_bytes"] < stats["step_min_bytes"]
    # contexts of 3 to 11 windows: the window layers read a part of what
    # layers that keep pages would
    assert 0 < stats["step_window_kv_bytes"] < stats["step_window_kv_whole_bytes"]
    assert 0 < stats["expert_rows_routed"] <= stats["expert_rows_computed"]
    assert stats["step_model_flops"] > 0
    assert stats["attention_impl"] == dict.fromkeys(
        ("decode", "prefill", "ragged", "recurrence"), "xla")


def test_the_rings_bytes_do_not_grow_with_the_longest_context(params):
    """`--max-model-len` 64 or 1,024: the same bytes of rings (W positions a
    lane and window layer), where the pages a lane may take grow sixteen
    times."""
    sizes = {}
    for longest in (64, 1024):
        eng = engine(params, max_model_len=longest, num_pages=300)
        sizes[longest] = (eng.stats()["state_bytes"], eng.config.max_pages_per_seq)
        asyncio.run(eng.close())
    assert sizes[64][0] == sizes[1024][0] == 5 * LW * 2 * W * 2 * 16 * 4
    assert sizes[1024][1] == 16 * sizes[64][1]


def test_a_lane_reused_and_a_sequence_resumed_give_a_fresh_engines_tokens(params):
    """One lane: the second request takes the lane the first one left its
    rings in. Then a pool too small for three sequences: one is preempted,
    comes back with its prompt recomputed into an empty ring, and every
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


def test_the_prefix_index_hands_a_sequence_with_rings_no_cached_pages(params):
    """A second request with the first one's prompt: its blocks are in the
    prefix index, nobody kept the ring's tail that stood at their end, so it
    gets none of them, recomputes, reads the same tokens, and the counter
    says how many blocks were declined."""
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
    (dict(quantize="int8"), "--quantize"),
    (dict(kv_quant="int8"), "--kv-quant"),
    (dict(tp_size=2), "mesh"),
])
def test_what_cannot_follow_a_ring_is_refused_at_start_by_name(params, over, what):
    with pytest.raises(ValueError) as e:
        engine(params, **over)
    assert "EXAONE-MoE family" in str(e.value) and what in str(e.value)


def test_the_disaggregated_entries_refuse_a_family_with_rings(params):
    """The disaggregated hand-off arrives by request and is refused there,
    by the same code as the two other stateful families and in this
    family's name."""
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
    assert items[0].get("event") == "error" and "EXAONE-MoE family" in str(items[0])
    assert slot is None and "EXAONE-MoE family" in err and pull is None


def test_the_configuration_loads_into_the_dataclass():
    """The benchmark's file, plain and under `rehearsal`, fills
    ExaoneMoeConfig field by field; the cut is what it says (the first 8
    layers of the published pattern: LLLG twice, one dense layer and seven
    sparse; 16 of 128 experts from 0; an eighth of the vocabulary), every
    key of the catalog's row that is not `reduced` stands at its published
    value (the three per-layer lists whole: the program runs their first 8
    entries), the sparse layers are what the harness reckons from the file
    (`num_hidden_layers` less `first_k_dense_replace`), and the bytes are
    the arithmetic's."""
    import files_check
    from worker_entry import build_model_config, load_config, lookup

    for rehearsal in (False, True):
        cfg = load_config(CONFIG_FILE, rehearsal)
        built = build_model_config(cfg)
        assert type(built) is exaone_moe.ExaoneMoeConfig
        for field, key in cfg["dataclass_fields"].items():
            assert getattr(built, field) == lookup(cfg, key), field
        Lw, Lf, Ld, Le = exaone_moe.kinds(built)
        assert built.router_width > built.num_experts
        width, per_token, routed = files_check.routed_geometry("exaone", cfg)
        assert (width, per_token, routed) == (
            built.router_width, built.num_experts_per_tok, Le)
        # the per-layer lists of the file say what the program derives from
        # the pattern, the window and the count of dense layers
        n = built.num_layers
        assert cfg["layer_types"][:n] == [
            "sliding_attention" if exaone_moe.is_window(built, li) else "full_attention"
            for li in range(n)]
        assert [w > 0 for w in cfg["sliding_windows"][:n]] == [
            exaone_moe.is_window(built, li) for li in range(n)]
        assert cfg["mlp_layer_types"][:n] == ["dense"] * Ld + ["sparse"] * Le
    cfg = load_config(CONFIG_FILE, False)
    built = build_model_config(cfg)
    assert exaone_moe.kinds(built) == (6, 2, 1, 7)
    assert set(cfg["sliding_windows"]) == {0, built.sliding_window}
    assert (built.num_experts, built.router_width, built.first_expert_held,
            built.num_experts_per_tok, built.vocab_size, built.sliding_window) == (
        16, 128, 0, 8, 19200, 128)
    assert sorted(cfg["reduced"]) == ["num_experts", "num_hidden_layers", "vocab_size"]
    assert {"norm_place", "qk_norm", "rotary", "router_bias", "mtp"} <= set(cfg["assumed"])
    # the catalog's row, where the guides are installed beside the checkout
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "K-EXAONE-236B-A23B")
        assert cfg["source"] == row["source_url"]
        for key, value in row["config"].items():
            published = cfg["published"][key] if key in cfg["reduced"] else cfg[key]
            assert published == value, key
    assert state_bytes_per_lane(built) == 6 * 2 * 128 * 1024 * 2
    shapes = jax.eval_shape(lambda: exaone_moe.init_params(built, jax.random.PRNGKey(0)))
    nbytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(shapes))
    assert 11.9e9 < nbytes < 12.05e9  # the issue's 11.96 GB
    # what a decode step of 32 lanes at a context of 450 asks for: the
    # issue's reckoning (10.85 GB, the routed experts 68% of it)
    _, nbytes, rings, experts, read, whole = exaone_moe.step_work(
        built, 32, 32 * 450, 1, rows=32)
    assert 10.3e9 < nbytes < 11.3e9 and 0.62 < experts / nbytes < 0.72
    assert read == 6 * 32 * 128 * 4096 and 0.25 < read / whole < 0.32
