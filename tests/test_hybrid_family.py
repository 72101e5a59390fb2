"""models/hybrid.py against benchmark/references/hybrid.py, and the engine's
state store beside the pages (docs/hybrid_models.md).

CPU, tiny sizes, float32 weights and activations, seeded random weights,
the matmul precision "highest" on both sides. The tolerance is 1e-3
deviations of the reference's logits at a position, the one
`benchmark/selftest.py:test_references_against_the_program` holds the two
other families to: in float32 the program and the reference differ only by
the order of their sums (a chunk's closed form against a step at a time, a
grouped matmul against a scan over experts), which reads 1e-6 to 1e-5; a
state one token off, a convolution tap out of place or an expert dropped
reads 1e-1 and more. The invariant everything here rests on: after any
forward, a lane's state stands at exactly the tokens whose keys and values
were written for it.
"""

import asyncio
import dataclasses
import functools
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine import EngineConfig, JaxEngine
from dynamo_tpu.llm.protocols import PreprocessedRequest
from dynamo_tpu.models import hybrid
from dynamo_tpu.ops.state_cache import alloc_state_cache
from dynamo_tpu.runtime.engine import Context

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

from references import hybrid as ref  # noqa: E402

PAGE = 16
TOL = 1e-3  # deviations of the reference's logits (see the module's text)
CFG = hybrid.HybridConfig.tiny_hybrid(dtype=jnp.float32)


@pytest.fixture(scope="module")
def params():
    return hybrid.init_params(CFG, jax.random.PRNGKey(0))


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


@functools.lru_cache(maxsize=None)
def REFERENCE(cfg, padded):
    return jax.jit(lambda p, t: ref.logits(p, cfg, t, n_last=padded))


def reference_logits(params, cfg, tokens):
    """The reference's logits at every position of `tokens`, and the experts
    it chose [layers, T, K]."""
    T = len(tokens)
    padded = -(-T // 64) * 64
    toks = np.zeros((padded,), np.int32)
    toks[:T] = tokens
    logits, _, chosen, _ = REFERENCE(cfg, padded)(params, jnp.asarray(toks))
    return np.asarray(logits)[:T], np.asarray(chosen)[:, :T]


def off(got, want):
    return float(np.abs(np.asarray(got) - want).max() / want.std())


def sequence(seed, n):
    return np.random.RandomState(seed).randint(5, CFG.vocab_size, size=n).tolist()


PREFILL = jax.jit(lambda *a: hybrid.prefill_forward_batched(a[0], CFG, *a[1:]))
DECODE = jax.jit(lambda *a: hybrid.decode_forward(a[0], CFG, *a[1:]))
RAGGED = jax.jit(lambda *a: hybrid.ragged_forward(a[0], CFG, *a[1:]))


def with_lanes(cache, lanes):
    """The cache for a dispatch whose row r belongs to lane `lanes[r]`, as
    the engine's programs make it from their buffer."""
    return cache.replace(lanes=jnp.asarray(cache.row_lanes(lanes)))


def prefill(params, cache, kv_v, rows, width, fn=None):
    """One batched prefill dispatch: rows of (lane, tokens, start, table)
    (`fn`: another family's jitted forward; tests/test_nemotron_h_family.py)."""
    B = len(rows)
    toks = np.zeros((B, width), np.int32)
    pos = np.zeros((B, width), np.int32)
    tables = np.stack([r[3] for r in rows])
    for b, (_, tk, start, _) in enumerate(rows):
        toks[b, : len(tk)] = tk
        pos[b] = start + np.arange(width)
    logits, cache, kv_v = (fn or PREFILL)(
        params, jnp.asarray(toks), jnp.asarray(pos),
        with_lanes(cache, [r[0] for r in rows]), kv_v, jnp.asarray(tables),
        jnp.asarray([r[2] for r in rows], jnp.int32),
        jnp.asarray([len(r[1]) - 1 for r in rows], jnp.int32))
    return np.asarray(logits), cache, kv_v


def table_of(lane, pages=8):
    """Pages of a lane's own (page 0 is the engine's scratch page)."""
    return np.arange(1 + lane * pages, 1 + (lane + 1) * pages, dtype=np.int32)


def decode(params, cache, kv_v, lanes, fn=None):
    """One decode step over 4 lanes: lanes {lane: (token, position)}."""
    tok, pos, sl = (np.zeros((4,), np.int32) for _ in range(3))
    tables = np.zeros((4, 8), np.int32)
    for lane, (t, p) in lanes.items():
        tok[lane], pos[lane], sl[lane], tables[lane] = t, p, p + 1, table_of(lane)
    logits, cache, kv_v = (fn or DECODE)(
        params, jnp.asarray(tok), jnp.asarray(pos), cache, kv_v,
        jnp.asarray(tables), jnp.asarray(sl))
    return np.asarray(logits), cache, kv_v


def packed(rows, cache, kv_v, R, M):
    """A mixed step's operands after the weights: rows of (lane, tokens,
    context) packed into a flat buffer of M slots and R rows, the slots
    past the pack on the last row's scratch position."""
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
    return (
        *(jnp.asarray(a) for a in (toks, pos, row_ids)),
        with_lanes(cache, [r[0] for r in rows]), kv_v,
        *(jnp.asarray(a) for a in (tables, starts, lens, ctx, last)))


def test_chunks_then_decode_steps_equal_the_full_forward(params):
    """(i) One prefill chunk, a second chunk from the state the first left,
    then decode steps through pages and state: the reference's full forward
    at every position judged; and the experts the program says it chose are
    the reference's."""
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
    expects three (8 rows less 5 lanes): three groups of two."""
    seqs = {lane: sequence(10 + lane, 70) for lane in range(4)}
    fresh = sequence(20, 33)
    want = {lane: reference_logits(params, CFG, s)[0] for lane, s in seqs.items()}
    want_fresh = reference_logits(params, CFG, fresh)[0]
    cache, kv_v = alloc_state_cache(CFG, 48, PAGE, 5, 256, 8)
    # lanes 0-2 hold 40 tokens each and decode; lane 3 holds a first chunk
    # of 24; lane 4 holds a finished sequence's state (stale)
    _, cache, kv_v = prefill(params, cache, kv_v, [
        (lane, seqs[lane][:40], 0, table_of(lane)) for lane in range(3)], 64)
    _, cache, kv_v = prefill(params, cache, kv_v, [
        (3, seqs[3][:24], 0, table_of(3)), (4, seqs[0][:30], 0, table_of(4))], 32)
    rows = [  # (lane, tokens, context)
        (4, fresh, 0), (3, seqs[3][24:61], 24),
        (0, seqs[0][40:40 + n], 40), (1, seqs[1][40:40 + n], 40),
        (2, seqs[2][40:40 + n], 40)]
    logits, cache, kv_v = RAGGED(params, *packed(rows, cache, kv_v, 8, 96))
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


def one_token_either_way(params, cfg, prefill, decode, ragged):
    """(the state store a decode step leaves, the one a mixed step leaves,
    their logits [3, vocab] each) when both feed lanes 0 to 2 the same
    token from the same cache; the mixed step packs a first chunk on lane
    3 beside them (tests/test_nemotron_h_family.py runs its family
    through this too)."""
    seqs = {lane: sequence(50 + lane, 41) for lane in range(3)}
    cache, kv_v = alloc_state_cache(cfg, 48, PAGE, 5, 256, 8)
    _, cache, kv_v = prefill(params, cache, kv_v, [
        (lane, seqs[lane][:40], 0, table_of(lane)) for lane in range(3)], 64)
    by_step, stepped, _ = decode(
        params, cache, kv_v, {lane: (seqs[lane][40], 40) for lane in range(3)})
    rows = [(3, sequence(60, 21), 0),
            *((lane, seqs[lane][40:], 40) for lane in range(3))]
    by_pack, packed_, _ = ragged(params, *packed(rows, cache, kv_v, 8, 96))
    return (np.asarray(stepped.state), np.asarray(packed_.state),
            by_step[:3], np.asarray(by_pack)[1:4])


def test_a_mixed_steps_one_token_rows_are_the_decode_steps(params):
    """A decode step and a mixed step share `lanes_step`: fed the same
    token, they leave a lane the same state (what differs is how the
    token's q, k and v were multiplied: a batch of 4 against 96 slots),
    and the same logits. The mixed step's prompt lane holds a state; the
    lane nobody packed and the scratch lane hold none."""
    stepped, packed_, by_step, by_pack = one_token_either_way(
        params, CFG, prefill, decode, RAGGED)
    scale = np.abs(stepped[:, :3]).max()
    assert scale > 0 and np.abs(packed_[:, :3] - stepped[:, :3]).max() < 1e-5 * scale
    assert off(by_pack, by_step) < 1e-4
    assert packed_[:, 3].any() and not stepped[:, 3].any()
    assert not packed_[:, 4:].any()


FORWARDS = ("decode_forward", "ragged_forward", "prefill_forward_batched",
            "prefill_forward")


def forward_case(name, params):
    """The operands of one call of the named forward after the weights, on
    lanes 0 to 2 that hold 24 tokens' state and pages each and a lane 3
    that holds a finished sequence's."""
    seqs = {lane: sequence(30 + lane, 40) for lane in range(4)}
    cache, kv_v = alloc_state_cache(CFG, 40, PAGE, 4, 128, 8)
    _, cache, kv_v = prefill(params, cache, kv_v, [
        (lane, seq[:24], 0, table_of(lane)) for lane, seq in seqs.items()], 32)
    if name == "decode_forward":  # lane 3 sits out
        tok, pos = np.zeros((4,), np.int32), np.zeros((4,), np.int32)
        tables = np.zeros((4, 8), np.int32)
        for lane in range(3):
            tok[lane], pos[lane], tables[lane] = seqs[lane][24], 24, table_of(lane)
        return (jnp.asarray(tok), jnp.asarray(pos), cache, kv_v,
                jnp.asarray(tables), jnp.asarray(np.where(pos > 0, pos + 1, 0)))
    if name == "ragged_forward":
        return packed([(3, sequence(40, 20), 0), (0, seqs[0][24:40], 24),
                       (1, seqs[1][24:25], 24), (2, seqs[2][24:25], 24)],
                      cache, kv_v, 8, 64)
    toks, pos = np.zeros((2, 32), np.int32), np.zeros((2, 32), np.int32)
    toks[0, :16], toks[1, :20] = seqs[0][24:40], sequence(40, 20)
    pos[0], pos[1] = 24 + np.arange(32), np.arange(32)
    if name == "prefill_forward_batched":
        return (jnp.asarray(toks), jnp.asarray(pos), with_lanes(cache, [0, 3]),
                kv_v, jnp.asarray(np.stack([table_of(0), table_of(3)])),
                jnp.asarray([24, 0], jnp.int32), jnp.asarray([15, 19], jnp.int32))
    return (jnp.asarray(toks[0]), jnp.asarray(pos[0]), with_lanes(cache, [0]),
            kv_v, jnp.asarray(table_of(0)), jnp.int32(24), jnp.int32(15))


def plain_stack(params, c, x, cache, kv_v, linear_fn, full_fn, valid=None):
    """The layers one after another, each kind counted as it comes and its
    leaves taken as `a[i]` from the stored stacks: what
    `hybrid._layer_stack` has to compute."""
    stored = params["layers"]
    stacks = {k: stored["moe"][k] for k in ("w_gate", "w_up", "w_down")}
    pages, state, conv = cache.pages, cache.state, cache.conv
    linear = full = 0
    chosen = []
    for i in range(c.num_layers):
        if (i + 1) % c.full_attention_interval:
            layer = {k: a[linear] for k, a in stored["linear"].items()}
            h = hybrid.norm(x, layer["norm"], c.rms_norm_eps)
            out, state, conv = linear_fn(layer, h, state, conv, linear)
            linear += 1
        else:
            layer = {k: a[full] for k, a in stored["full"].items()}
            h = hybrid.norm(x, layer["norm"], c.rms_norm_eps)
            out, pages, kv_v = full_fn(layer, h, pages, kv_v, full)
            full += 1
        x = x + out
        routed = {k: a[i] for k, a in stored["moe"].items() if k not in stacks}
        y, idx = hybrid.routed_block(
            routed, stacks, i, x.reshape(-1, x.shape[-1]), c, valid)
        x = y.reshape(x.shape)
        chosen.append(idx)
    cache = cache.replace(pages=pages, state=state, conv=conv)
    return x, cache, kv_v, jnp.stack(chosen)


@pytest.mark.parametrize("name", FORWARDS)
def test_a_forward_is_a_plain_loop_over_the_stored_layers(params, name, monkeypatch):
    """The named forward's logits and every leaf of the cache it leaves,
    bit for bit those of the same forward over `plain_stack` (ISSUE 48: the
    layers' order, the index of each kind's stack and of the state store
    are all that `_layer_stack` decides)."""
    args = forward_case(name, params)

    def run():
        fn = getattr(hybrid, name)
        return jax.jit(lambda p, *a: fn(p, CFG, *a))(params, *args)

    got = run()
    monkeypatch.setattr(hybrid, "_layer_stack", plain_stack)
    want = run()
    assert np.abs(np.asarray(got[0])).max() > 0
    for x, y in zip(jax.tree.leaves(got), jax.tree.leaves(want), strict=True):
        assert (np.asarray(x) == np.asarray(y)).all()
    before = next(a for a in args if isinstance(a, hybrid.StateCache))
    assert (np.asarray(got[1].state) != np.asarray(before.state)).any()


@pytest.mark.parametrize("name", FORWARDS)
def test_a_forward_slices_each_stored_leaf_once_a_layer(params, name):
    """The lowered StableHLO of the named forward (no TPU compiler needed):
    a stacked leaf of `params["layers"]` is never the operand of a
    `reshape` (a stack reshaped by period and indexed twice is a period's
    weights copied every step on the chip: PERF.md, PR 48), and is sliced
    exactly once for each layer that uses it. The three expert stacks go
    whole to the grouped matmul: their one use a layer merges layers and
    experts into groups."""
    fn = getattr(hybrid, name)
    text = jax.jit(lambda p, *a: fn(p, CFG, *a), keep_unused=True).lower(
        params, *forward_case(name, params)).as_text()
    main = text[text.index("func.func public @main("):]
    main = main[:main.index("\n  }\n") + 1]
    _, linear, full = hybrid.periods(CFG)
    users = {"linear": linear, "full": full, "moe": CFG.num_layers}
    seen = 0
    for i, (path, _) in enumerate(jax.tree_util.tree_flatten_with_path(params)[0]):
        keys = [k.key for k in path]
        if keys[0] != "layers":
            continue
        seen += 1
        ops = re.findall(
            rf"= \"?([\w.]+)\"? [^\n]*%arg{i}\b(?!:)", main)
        if keys[2] in ("w_gate", "w_up", "w_down"):
            assert ops == ["stablehlo.reshape"] * CFG.num_layers, (keys, ops)
            assert f"tensor<{CFG.num_layers * CFG.num_experts}x" in main
        else:
            assert ops == ["stablehlo.slice"] * users[keys[1]], (name, keys, ops)
    assert seen == sum(len(kind) for kind in params["layers"].values())


def test_the_chunked_and_the_step_form_of_the_recurrence_agree():
    """(iii) delta_chunk over CHUNK tokens against delta_step a token at a
    time, from a state that is not zero; a tail of tokens whose beta and g
    are 0 (padding) leaves the state as it was."""
    R, C, nv, dk, dv = 3, hybrid.CHUNK, 4, 16, 8
    ks = jax.random.split(jax.random.PRNGKey(5), 6)
    q = jax.random.normal(ks[0], (R, C, nv, dk)) * dk ** -0.5
    k = jax.random.normal(ks[1], (R, C, nv, dk))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (R, C, nv, dv))
    g = -jax.random.uniform(ks[3], (R, C, nv), minval=0.0, maxval=2.0)
    beta = jax.random.uniform(ks[4], (R, C, nv))
    real = jnp.arange(C)[None, :, None] < jnp.asarray([C, 17, 1])[:, None, None]
    g, beta = jnp.where(real, g, 0.0), jnp.where(real, beta, 0.0)
    S0 = jax.random.normal(ks[5], (R, nv, dk, dv))
    S, want = S0, []
    for t in range(C):
        S, o = hybrid.delta_step(S, q[:, t], k[:, t], v[:, t], g[:, t], beta[:, t])
        want.append(o)
    got_S, got = hybrid.delta_chunk(S0, q, k, v, g, beta)
    want = jnp.stack(want, axis=1)
    assert float(jnp.abs(got_S - S).max()) < 1e-4 * float(jnp.abs(S).max())
    assert float(jnp.abs(jnp.where(real[..., None], got - want, 0)).max()) \
        < 1e-4 * float(jnp.abs(want).max())


def test_a_decode_step_through_the_kernel_is_the_xla_steps(monkeypatch):
    """`decode_forward` with ops/pallas_delta_step.py forced on
    (interpreted; value heads of 128 x 128, which the kernel takes) gives
    the logits and the cache of the path through `delta_step`: lanes that
    decode from a prompt's state, one that does not and keeps its own."""
    cfg = hybrid.HybridConfig.tiny_hybrid(
        dtype=jnp.float32, linear_key_head_dim=128, linear_value_head_dim=128)
    params = hybrid.init_params(cfg, jax.random.PRNGKey(3))
    cache, kv_v = alloc_state_cache(cfg, 40, PAGE, 4, 128, 8)
    fill = jax.jit(lambda *a: hybrid.prefill_forward_batched(a[0], cfg, *a[1:]))
    seqs = {lane: sequence(10 + lane, 30 + lane) for lane in (0, 1, 3)}
    _, cache, kv_v = prefill(
        params, cache, kv_v,
        [(lane, seq, 0, table_of(lane)) for lane, seq in seqs.items()], 64,
        fn=fill)
    assert hybrid.recurrence_impl(cfg) == "xla"  # the CPU's own answer
    xla = jax.jit(lambda *a: hybrid.decode_forward(a[0], cfg, *a[1:]))
    monkeypatch.setattr(hybrid, "recurrence_impl", lambda c: "pallas")
    monkeypatch.setattr(hybrid, "delta_step_pallas", functools.partial(
        hybrid.delta_step_pallas, interpret=True))
    kernel = jax.jit(lambda *a: hybrid.decode_forward(a[0], cfg, *a[1:]))
    assert "pallas_call" in str(jax.make_jaxpr(kernel)(
        params, *(jnp.zeros((4,), jnp.int32),) * 2, cache, kv_v,
        jnp.zeros((4, 8), jnp.int32), jnp.zeros((4,), jnp.int32)))
    a = b = (cache, kv_v)
    for t in range(4):  # lane 1 sits out: between two chunks of its prompt
        lanes = {lane: (7 + t + lane, len(seqs[lane]) + t) for lane in (0, 3)}
        want, *a = decode(params, *a, lanes, fn=xla)
        got, *b = decode(params, *b, lanes, fn=kernel)
        assert off(got, want) < TOL, t
    for name in hybrid.StateCache.FIELDS:
        x, y = np.asarray(getattr(a[0], name)), np.asarray(getattr(b[0], name))
        assert np.abs(x - y).max() <= 1e-5 * max(np.abs(x).max(), 1), name
    assert np.abs(np.asarray(a[1]) - np.asarray(b[1])).max() < 1e-5
    state = np.asarray(b[0].state)
    assert (state[:, [1, 2, 4]] == np.asarray(cache.state)[:, [1, 2, 4]]).all()
    assert np.abs(state[:, [0, 3]] - np.asarray(cache.state)[:, [0, 3]]).max() > 0


def routed_parts(params_of, cfg_of, x, shares):
    """Each share's routed part of one layer's block over x (its output
    less x and the shared expert's part), and the experts chosen."""
    parts = []
    for first in shares:
        cfg = cfg_of(first)
        p = params_of(cfg)["layers"]["moe"]
        stacks = {k: p[k] for k in ("w_gate", "w_up", "w_down")}
        layer = {k: v[0] for k, v in p.items() if k not in stacks}
        block = jax.jit(hybrid.routed_block, static_argnums=(2, 4))
        whole, idx = block(layer, stacks, 0, x, cfg)
        alone, _ = block(layer, jax.tree.map(jnp.zeros_like, stacks), 0, x, cfg)
        parts.append((np.asarray(whole - alone), np.asarray(idx), np.asarray(alone - x)))
    return parts


def test_the_four_shares_add_up_to_the_uncut_layer(params):
    """(iv) The guide's section 4: each of four chips routes over the
    router's full width and computes its own experts' part; the four
    parts, with the shared expert counted once, are the uncut reference's
    layer; a token none of whose experts a chip holds gets the shared
    expert's part alone there. (The one test that ties the share to the
    model; it also holds `init_params` to it: a share's experts are the
    uncut model's, whichever share holds them.)"""
    held = 2  # of a router 8 wide: four shares
    key = jax.random.PRNGKey(3)

    def cfg_of(first):
        return dataclasses.replace(CFG, num_experts=held, first_expert_held=first)

    def params_of(cfg):
        return hybrid.init_params(cfg, key)

    uncut = dataclasses.replace(CFG, num_experts=CFG.router_width, first_expert_held=0)
    x = jax.random.normal(jax.random.PRNGKey(4), (24, CFG.hidden_size), jnp.float32)
    w = jax.tree.map(lambda a: a[0], params_of(uncut)["layers"]["moe"])
    free = jnp.full((24, CFG.num_experts_per_tok), -1, jnp.int32)
    want, (_, chosen, _) = ref.routed_mlp(x, w, uncut, free)
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
    tokens x k / experts x 1.25 would hold 10 of them): the reference's
    result, to the tolerance."""
    x = jnp.tile(jax.random.normal(jax.random.PRNGKey(6), (1, CFG.hidden_size)), (32, 1))
    x = x + 1e-4 * jax.random.normal(jax.random.PRNGKey(7), x.shape)
    p = params["layers"]["moe"]
    stacks = {k: p[k] for k in ("w_gate", "w_up", "w_down")}
    layer = {k: v[3] for k, v in p.items() if k not in stacks}
    got, idx = jax.jit(hybrid.routed_block, static_argnums=(2, 4))(
        layer, stacks, 3, x, CFG)
    assert (np.sort(np.asarray(idx), -1) == np.sort(np.asarray(idx[0]))).all()
    assert (np.asarray(idx[0]) < CFG.num_experts).any(), "no held expert chosen: reseed"
    w = jax.tree.map(lambda a: a[3], p)
    want, _ = ref.routed_mlp(x, w, CFG, jnp.full((32, CFG.num_experts_per_tok), -1, jnp.int32))
    assert off(np.asarray(got - x), np.asarray(want - x)) < TOL


# ---------------------------------------------------------------------- #
# (vi) through JaxEngine
# ---------------------------------------------------------------------- #


def engine(params, **over):
    # one mixed-step program: one token bucket, one table width
    kw = dict(model="tiny-hybrid", max_num_seqs=4, page_size=PAGE, num_pages=128,
              max_model_len=256, prefill_buckets=(32,), max_prefill_chunk=32,
              mixed_max_tokens=64)
    kw.update(over)
    eng = JaxEngine(EngineConfig(**kw), model_config=CFG, params=params)
    eng._mixed_table_rungs = (eng.config.max_pages_per_seq,)
    return eng


async def stream(eng, prompt, rid, n, annotations=(), delay=0.0):
    await asyncio.sleep(delay)
    req = PreprocessedRequest(
        token_ids=list(prompt), stop_conditions={"max_tokens": n, "ignore_eos": True},
        sampling_options={"temperature": 0.0}, request_id=rid,
        annotations=list(annotations)).to_dict()
    toks, rows, frames = [], [], []
    async for item in eng.generate(req, Context()):
        assert item.get("event") != "error", item
        data = item.get("data") or {}
        toks += data.get("token_ids") or []
        rows += data.get("routed_experts") or []
        frames.append(data)
    return toks, rows, frames


def reference_greedy(params, prompt, n):
    seq = list(prompt)
    for _ in range(n):
        seq.append(int(reference_logits(params, CFG, seq)[0][-1].argmax()))
    return seq[len(prompt):]


def test_the_engine_serves_the_references_tokens_and_says_what_it_routed(params):
    """Three requests that arrive apart, so that prefill chunks share mixed
    steps with decode lanes: greedy tokens are the reference's; an annotated
    request's frames carry one row [8 routed layers][k] of ids under the
    router's width for each input position of prompt + served[:-1], the
    prompt's with the first frame, and the rows are the reference's choices;
    an unannotated request's frames carry none."""
    prompts = [sequence(30, 40), sequence(31, 70), sequence(32, 21)]

    async def run():
        eng = engine(params)
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
        assert got.shape[1:] == (CFG.num_layers, CFG.num_experts_per_tok)
        assert 0 <= got.min() and CFG.num_experts <= got.max() < CFG.router_width
        chosen = reference_logits(params, CFG, prompt + toks[:-1])[1]
        assert (np.sort(got, -1) == np.sort(chosen.transpose(1, 0, 2), -1)).all()
    assert not out[2][1] and all("routed_experts" not in f for f in out[2][2])
    assert stats["routed_rows_emitted"] == len(out[0][1]) + len(out[1][1])
    assert stats["state_lanes_reset"] == 3 and stats["mixed_steps"] > 0
    assert stats["step_state_bytes"] > 0 and stats["state_bytes"] > 0
    assert stats["expert_rows_routed"] > 0
    assert stats["attention_impl"] == dict.fromkeys(
        ("decode", "prefill", "ragged", "recurrence"), "xla")


def test_the_mixed_steps_attention_counters_follow_the_packs(params):
    """The three counts of a mixed step's attention (q tiles the ragged
    grid launched, those that hold a real q row, one-token rows served by
    the decode kernel) for this family's packs, against the same arithmetic
    on the operands its device calls were handed. Host arithmetic in the
    kernel's tile, forced here: the CPU's engine resolves the XLA path,
    whose tile is 1 and whose counts stay 0."""
    from dynamo_tpu.ops.paged_attention import ragged_tiles

    from .test_mixed_fusion import _Stepped

    # a prompt of two chunks whose second is ONE token (33 = 32 + 1), and
    # two of one chunk, each arriving beside the lanes that decode
    prompts = [sequence(40, 20), sequence(41, 33), sequence(42, 21),
               sequence(43, 30)]
    packs, going_on = [], []

    async def run():
        eng = engine(params)
        assert eng._ragged_tile == 1
        eng._ragged_tile = 16
        async with _Stepped(eng) as st:
            dev_mixed = eng._dev_mixed

            def kept(p):
                if "prime" not in p:
                    packs.append((len(p["toks"]), np.array(p["row_lens"])))
                    going_on.append(int(((np.array(p["row_lens"]) == 1)
                                         & (np.array(p["ctx_lens"]) > 0)).sum()))
                return dev_mixed(p)

            eng._dev_mixed = kept
            tasks = [await st.submit(prompts[0], "a", n=40)]
            await st.until(lambda: any(
                s is not None and s.generated > 0 for s in eng.slots))
            for k, prompt in enumerate(prompts[1:]):
                tasks.append(await st.submit(prompt, f"r{k}", n=6))
                await st.step(4)
            await st.finish(*tasks)
            return eng.stats()

    stats = asyncio.run(run())
    assert len(packs) == stats["mixed_steps"] > 0
    batch = EngineConfig(model="tiny-hybrid").max_prefill_batch
    assert stats["mixed_attn_tiles"] == sum(
        ragged_tiles(M, len(lens), 16, batch) for M, lens in packs)
    assert stats["mixed_attn_tiles_real"] == sum(
        int(-(-n // 16)) for _, lens in packs for n in lens if n > 1)
    assert stats["mixed_rows_decode_kernel"] == sum(
        int((lens == 1).sum()) for _, lens in packs)
    assert 0 < stats["mixed_attn_tiles_real"] < stats["mixed_attn_tiles"]
    # ... and the two roads of the recurrence (ops/row_recurrence.py): a
    # row of one token that goes on from its lane's state, every other row
    assert stats["state_rows_in_place"] == sum(going_on)
    assert stats["state_rows_in_place"] + stats["state_rows_gathered"] == sum(
        int((lens > 0).sum()) for _, lens in packs)
    # (the one-token chunk goes on from a state; every first chunk does not)
    assert stats["mixed_rows_decode_kernel"] == stats["state_rows_in_place"]
    assert stats["state_rows_gathered"] > 0


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
    assert "hybrid family" in str(e.value) and what in str(e.value)


def test_the_disaggregated_entries_refuse_a_stateful_family(params):
    """KVBM offload, migration checkpoints (KVBM's tiers) and speculation
    refuse at start; the disaggregated hand-off arrives by request and is
    refused there: a request that asks for its pages, and the decode
    role's entries."""
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
    assert items[0].get("event") == "error" and "hybrid family" in str(items[0])
    assert slot is None and "hybrid family" in err and pull is None


def test_the_configuration_loads_into_the_dataclass():
    """The benchmark's file, plain and under `rehearsal`, fills HybridConfig
    field by field; the cut is what it says (two periods, 128 of 512
    experts from 0, a quarter of the vocabulary), no width differs from
    the published row, and the state's bytes are the arithmetic's."""
    from worker_entry import build_model_config, load_config, lookup

    from dynamo_tpu.ops.state_cache import state_bytes_per_lane

    path = os.path.join(ROOT, "benchmark", "configs", "qwen3-next-80b-a3b-ep4-d8.json")
    for rehearsal in (False, True):
        cfg = load_config(path, rehearsal)
        built = build_model_config(cfg)
        assert type(built) is hybrid.HybridConfig
        for field, key in cfg["dataclass_fields"].items():
            assert getattr(built, field) == lookup(cfg, key), field
        assert built.num_layers == 8 and hybrid.periods(built) == (2, 6, 2)
        assert built.router_width > built.num_experts
        assert built.linear_num_value_heads == 2 * built.linear_num_key_heads
    cfg = load_config(path, False)
    built = build_model_config(cfg)
    assert (built.num_experts, built.router_width, built.first_expert_held,
            built.vocab_size) == (128, 512, 0, 37984)
    # the catalog's row, where the guides are installed beside the checkout
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Qwen3-Next-80B-A3B-Instruct")
        for key, value in row["config"].items():
            published = cfg["published"][key] if key in cfg["reduced"] else cfg[key]
            assert published == value, key
    assert state_bytes_per_lane(built) == 6 * (2_097_152 + 49_152)
    shapes = jax.eval_shape(lambda: hybrid.init_params(built, jax.random.PRNGKey(0)))
    nbytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(shapes))
    assert 7.3e9 < nbytes < 7.4e9  # the issue's 7.35 GB


# ---------------------------------------------------------------------- #
# the benchmark's files, as files_check.py holds them (ISSUEs 34 and 36)
# ---------------------------------------------------------------------- #


def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", [c["name"] for c in bench()["configs"]])
def test_files_check_holds_each_configuration_of_the_benchmark(name):
    """One case a configuration of BENCHMARK.json: `files_check.check_loaded`
    on the benchmark cut down to that configuration and its cells."""
    import files_check

    whole = bench()
    config = next(c for c in whole["configs"] if c["name"] == name)
    cells = [w for w in whole["workloads"] if w["config"] == name]
    names = {w["name"] for w in cells}
    assert cells
    cut = dict(whole, configs=[config], workloads=cells, per_layer=[
        dict(m, workloads=[w for w in m["workloads"] if w in names])
        if "workloads" in m else m for m in whole["per_layer"]],
        end_to_end=[
        dict(m, workloads=[w for w in m["workloads"] if w in names])
        if "workloads" in m else m for m in whole["end_to_end"]])
    cut["end_to_end"] = [m for m in cut["end_to_end"] if m.get("workloads", True)]
    reported = {m["name"] for m in cut["end_to_end"]}
    cut["per_layer"] = [m for m in cut["per_layer"]
                        if m.get("workloads", True) and m["moves"] in reported]
    with open(os.path.join(ROOT, config["file"])) as f:
        files_check.check_loaded(cut, {name: json.load(f)}, ROOT)


JUDGED_FILES = sorted(
    os.path.join("benchmark", "fixtures", f)
    for f in os.listdir(os.path.join(ROOT, "benchmark", "fixtures"))
    if f.startswith("many-experts") and f.endswith(".json") and "readings" not in f
) + [os.path.join("benchmark", "configs", name + ".json") for name in (
    "qwen3-next-80b-a3b-ep4-d8", "nemotron-3-super-120b-a12b-ep4-d11")]


@pytest.mark.parametrize("path", JUDGED_FILES)
def test_files_check_holds_each_forced_familys_limits_to_its_readings(path):
    """One case a file whose routing is judged forced: `check_judge` (every
    limit above its highest sound reading, past the geometric mean, at most
    0.8 of the int8 control's lowest; one upper reading), and that a limit
    moved under the highest sound reading is refused."""
    import files_check

    with open(os.path.join(ROOT, path)) as f:
        cfg = json.load(f)
    assert cfg["judge_routing"] == "forced"
    files_check.check_judge(path, cfg)
    number = "logprob_gap_pooled_mean_sigmas"
    low = cfg["judge_readings"][number]["sound"]["highest"] / 2
    with pytest.raises(files_check.BenchmarkFilesError):
        files_check.check_judge(path, dict(cfg, judge=dict(cfg["judge"], **{number: low})))
