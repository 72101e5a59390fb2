"""models/mla_moe.py WITH the learned selection (`index_topk`) against
benchmark/references/mla_dsa_moe.py, and the engine's second store of pages
(docs/latent_cache.md, "A learned selection").

CPU, `tiny-mla-dsa`: `tiny-mla-moe`'s widths, 16 picks a token, an indexer in
layers 0 and 2 whose picks layers 1 and 3 borrow, pages of 8 and contexts of
40 to 200 positions, so that the selection bites everywhere it is judged;
float32 weights and activations, seeded random weights, the matmul precision
"highest" on both sides. TOL = 1e-3 deviations of the reference's logits at
a position, the sibling families' own: in float32 the program (picked rows
gathered by position, absorbed, tokens as lanes) and the reference (every
(t, s) scored, a mask, expanded) differ by the order of their sums, which
reads 1e-6 to 1e-5, and the picks are the same SETS; attention over the
whole context in place of the picks reads 0.3 and more, a shared layer with
a pick of its own making 0.1 and more: both fail, as they have to.
"""

import asyncio
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
from dynamo_tpu.engine import engine as engine_module
from dynamo_tpu.engine.recorder import Work
from dynamo_tpu.models import exaone_moe, mla_moe
from dynamo_tpu.ops.state_cache import alloc_state_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

from references import mla_dsa_moe as ref  # noqa: E402

from .test_hybrid_family import off, sequence, stream  # noqa: E402

PAGE, PAGES, TABLE = 8, 160, 32  # a table of 256 positions, 16 times the picks
TOL = 1e-3
CFG = mla_moe.MlaMoeConfig.tiny_mla_dsa(dtype=jnp.float32)
K = CFG.index_topk
CONFIG_FILE = os.path.join(ROOT, "benchmark", "configs", "glm-5.2-ep16-d7.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL = "glm-5.2-ep16-d7.sharedprefix-closed"


@pytest.fixture(scope="module")
def params():
    return mla_moe.init_params(CFG, jax.random.PRNGKey(0))


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


@functools.lru_cache(maxsize=None)
def REFERENCE(cfg, padded):
    return jax.jit(lambda p, t: ref.logits(p, cfg, t, n_last=padded, picks=True))


def reference(params, cfg, tokens):
    """(logits [T, vocab], the picks of each full layer [full layers, T, k])
    of the reference at every position of `tokens`."""
    T = len(tokens)
    padded = -(-T // 64) * 64
    toks = np.zeros((padded,), np.int32)
    toks[:T] = tokens
    logits, _, _, _, picks = REFERENCE(cfg, padded)(params, jnp.asarray(toks))
    return np.asarray(logits)[:T], np.asarray(picks)[:, :T]


def table_of(lane):
    return np.arange(1 + lane * TABLE, 1 + (lane + 1) * TABLE, dtype=np.int32)


def pools(cfg=CFG):
    """(the latent store, the index-key store) as the engine allocates them."""
    cache, index = alloc_state_cache(cfg, PAGES, PAGE, 4, 256, 8)
    return cache, index


PREFILL = jax.jit(lambda *a: mla_moe.prefill_forward_batched(a[0], CFG, *a[1:]))
DECODE = jax.jit(lambda *a: mla_moe.decode_forward(a[0], CFG, *a[1:]))
RAGGED = jax.jit(lambda *a: mla_moe.ragged_forward(a[0], CFG, *a[1:]))


def prefill(params, kv, rows, width, fn=PREFILL):
    """One batched prefill: rows of (tokens, start, table)."""
    toks = np.zeros((len(rows), width), np.int32)
    pos = np.zeros((len(rows), width), np.int32)
    for b, (tk, start, _) in enumerate(rows):
        toks[b, : len(tk)] = tk
        pos[b] = start + np.arange(width)
    logits, *kv = fn(
        params, jnp.asarray(toks), jnp.asarray(pos), *kv,
        jnp.asarray(np.stack([r[2] for r in rows])),
        jnp.asarray([r[1] for r in rows], jnp.int32),
        jnp.asarray([len(r[0]) - 1 for r in rows], jnp.int32))
    return np.asarray(logits), kv


def decode(params, kv, lanes, fn=DECODE):
    """One decode step over 4 lanes: {lane: (token, position, table)}."""
    tok, pos, sl = (np.zeros((4,), np.int32) for _ in range(3))
    tables = np.zeros((4, TABLE), np.int32)
    for lane, (t, p, tab) in lanes.items():
        tok[lane], pos[lane], sl[lane], tables[lane] = t, p, p + 1, tab
    logits, *kv = fn(params, jnp.asarray(tok), jnp.asarray(pos), *kv,
                     jnp.asarray(tables), jnp.asarray(sl))
    return np.asarray(logits), kv


def packed(rows, kv, R, M):
    """A mixed step's operands: rows of (tokens, context, table)."""
    toks, pos = np.zeros((M,), np.int32), np.zeros((M,), np.int32)
    row_ids = np.full((M,), R - 1, np.int32)
    starts, lens, ctx, last = (np.zeros((R,), np.int32) for _ in range(4))
    starts[:] = M
    tables = np.zeros((R, TABLE), np.int32)
    at = 0
    for r, (tk, c0, tab) in enumerate(rows):
        m = len(tk)
        toks[at: at + m], pos[at: at + m], row_ids[at: at + m] = tk, c0 + np.arange(m), r
        starts[r], lens[r], ctx[r], last[r], tables[r] = at, m, c0, at + m - 1, tab
        at += m
    return (*(jnp.asarray(a) for a in (toks, pos, row_ids)), *kv,
            *(jnp.asarray(a) for a in (tables, starts, lens, ctx, last)))


def _ops(text):
    return len(re.findall(r"= (?:stablehlo|func|chlo)\.", text))


def _picks(text):
    """Whether a lowered program takes the `index_topk` largest of anything
    (the router's own `top_k` takes 2 or 3)."""
    return re.search(rf"top_k\(.*k = {K}\b", text) is not None


# ---------------------------------------------------------------------- #
# the second store
# ---------------------------------------------------------------------- #


def test_the_index_keys_are_a_second_store_under_the_latent_stores_page_ids():
    """`[full layers, pages, rows, index_head_dim]` in the V pool's place, as
    many pages as the latent store; a configuration that does not select
    keeps one page of one value there; a page's bytes count both."""
    cache, index = pools()
    assert CFG.full_layers == (0, 2)
    assert cache.pages.shape == (CFG.num_layers, PAGES, PAGE, CFG.head_dim)
    assert index.shape == (2, PAGES, PAGE, CFG.index_head_dim)
    plain = mla_moe.MlaMoeConfig.tiny_mla_moe(dtype=jnp.float32)
    assert alloc_state_cache(plain, PAGES, PAGE, 4, 256, 8)[1].shape == (
        plain.num_layers, 1, PAGE, 1)
    assert plain.full_layers == () and plain.state_spec().index_layers == 0


def test_the_auto_pool_counts_the_index_keys_in_a_pages_bytes(monkeypatch):
    """At the published widths a page of 64 tokens is 7 x 81,920 B of latent
    rows and 2 x 16,384 B of index keys: the issue's 606,208 B."""
    with open(CONFIG_FILE) as f:
        built = _built(json.load(f))

    class Device:
        platform, device_kind = "tpu", "described"

        def memory_stats(self):
            return {"bytes_limit": 16_909_336_064, "bytes_in_use": 11_020_000_000}

    monkeypatch.setattr(engine_module.jax, "local_devices", lambda: [Device()])
    pages = engine_module._auto_num_pages(
        None, built, EngineConfig(model="x", max_num_seqs=32, page_size=64))
    free = int(16_909_336_064 * 0.85) - 11_020_000_000 - 512 * 2**20
    assert pages == free // 606_208 and 4000 < pages < 5000


# ---------------------------------------------------------------------- #
# the forwards against the reference, the selection biting
# ---------------------------------------------------------------------- #


def test_chunks_then_decode_steps_equal_the_full_forward(params):
    """Two prefill chunks (the second behind 60 positions: its tokens pick
    16 of 61 and more), then decode steps at contexts of 100 to 200, through
    the two stores: the reference's full forward at every position judged,
    and the index keys are written."""
    seq = sequence(1, 200)
    want, _ = reference(params, CFG, seq)
    kv, tab = list(pools()), table_of(2)
    got, kv = prefill(params, kv, [(seq[:60], 0, tab)], 64)
    assert off(got[0], want[59]) < TOL
    got, kv = prefill(params, kv, [(seq[60:100], 60, tab)], 64)
    assert off(got[0], want[99]) < TOL
    for t in range(100, 200):
        got, kv = decode(params, kv, {2: (seq[t], t, tab)})
        assert off(got[2], want[t]) < TOL, t
    assert np.asarray(kv[1])[:, tab[0]].any()


@pytest.mark.parametrize("chunks", [(20, 3, 5, 1, 11, 60), (1, 1, 98), (100,)])
def test_a_prompt_in_chunks_of_any_length(params, chunks):
    """Chunks that the selection leaves whole (the walks that stand, by
    row), chunks behind more than 16 positions (every token a lane with a
    pick of its own) and chunks of one token: each chunk's last position
    reads the reference's logits."""
    seq = sequence(2, 100)
    want, _ = reference(params, CFG, seq)
    kv, at = list(pools()), 0
    for n in chunks:
        got, kv = prefill(params, kv, [(seq[at: at + n], at, table_of(1))], 128)
        at += n
        assert off(got[0], want[at - 1]) < TOL, at


def test_a_mixed_step_with_the_three_kinds_of_row(params):
    """One flat buffer: a fresh prompt of 12 tokens (the selection leaves it
    whole: by row), a tail of 9 tokens behind 150 positions (a pick a
    token), a fresh prompt of 50 (its later tokens pick), and decode rows at
    contexts of 8 (whole) and 120 (picks): each row reads the reference's
    logits, and every sequence goes on from the pages the step left."""
    seqs = {lane: sequence(10 + lane, 170) for lane in range(3)}
    short, long_ = sequence(20, 12), sequence(21, 50)
    want = {lane: reference(params, CFG, s)[0] for lane, s in seqs.items()}
    kv = list(pools())
    for lane, n in ((0, 150), (1, 8), (2, 120)):
        _, kv = prefill(params, kv, [(seqs[lane][:n], 0, table_of(lane))], 160)
    rows = [(short, 0, table_of(3)), (seqs[0][150:159], 150, table_of(0)),
            (long_, 0, table_of(4)), (seqs[1][8:9], 8, table_of(1)),
            (seqs[2][120:121], 120, table_of(2))]
    logits, *kv = RAGGED(params, *packed(rows, kv, 8, 96))
    logits = np.asarray(logits)
    assert off(logits[0], reference(params, CFG, short)[0][-1]) < TOL
    assert off(logits[1], want[0][158]) < TOL
    assert off(logits[2], reference(params, CFG, long_)[0][-1]) < TOL
    assert off(logits[3], want[1][8]) < TOL
    assert off(logits[4], want[2][120]) < TOL
    got, kv = decode(params, kv, {
        0: (seqs[0][159], 159, table_of(0)), 1: (seqs[1][9], 9, table_of(1)),
        2: (seqs[2][121], 121, table_of(2))})
    for lane, t in ((0, 159), (1, 9), (2, 121)):
        assert off(got[lane], want[lane][t]) < TOL


def test_a_table_of_no_more_positions_than_the_picks_takes_the_walks_that_stand(params):
    """A program whose tables hold `index_topk` positions or fewer scores
    nothing (no `top_k` in it) and still writes the index keys; its logits
    are the reference's, for which every position is picked."""
    seq = sequence(5, 16)
    want, _ = reference(params, CFG, seq)
    narrow = lambda tab: tab[:2]  # noqa: E731 — 16 positions
    cache, index = pools()
    toks = np.zeros((1, 16), np.int32)
    toks[0, :15] = seq[:15]
    args = (params, jnp.asarray(toks), jnp.arange(16)[None], cache, index,
            jnp.asarray(narrow(table_of(0)))[None], jnp.zeros((1,), jnp.int32),
            jnp.asarray([14]))
    assert not _picks(PREFILL.lower(*args).as_text())
    got, cache, index = PREFILL(*args)
    assert off(np.asarray(got)[0], want[14]) < TOL
    assert np.asarray(index)[:, table_of(0)[0]].any()


def test_attention_without_the_selection_fails_the_comparison(params):
    """The same weights read WITHOUT the selection (the reference at an
    `index_topk` past every context) are hundreds of tolerances from the
    served path at a context of 100: the tolerance tells the two apart."""
    seq = sequence(1, 120)
    whole = mla_moe.MlaMoeConfig.tiny_mla_dsa(dtype=jnp.float32, index_topk=4096)
    unselected, _ = reference(params, whole, seq)
    want, _ = reference(params, CFG, seq)
    kv, tab = list(pools()), table_of(0)
    _, kv = prefill(params, kv, [(seq[:100], 0, tab)], 128)
    got, kv = decode(params, kv, {0: (seq[100], 100, tab)})
    assert off(got[0], want[100]) < TOL
    assert off(got[0], unselected[100]) > 100 * TOL
    # ... and a context the picks cover whole reads the same either way
    assert off(want[K - 1], unselected[K - 1]) < TOL


def test_a_shared_layer_attends_the_picks_of_the_full_layer_below(params, monkeypatch):
    """Broken on purpose: a shared layer that makes a pick of its own (the
    last 16 positions) moves the logits a hundred tolerances and more."""
    seq = sequence(3, 101)
    want, _ = reference(params, CFG, seq)
    kv, tab = list(pools()), table_of(0)
    _, kv = prefill(params, kv, [(seq[:100], 0, tab)], 128)
    sound, _ = decode(params, kv, {0: (seq[100], 100, tab)},
                      fn=lambda *a: mla_moe.decode_forward(a[0], CFG, *a[1:]))
    assert off(sound[0], want[100]) < TOL
    attend = mla_moe._attend_selecting

    def own_pick(params, c, layer, h, sel, li, positions, *rest, **kw):
        if li not in c.full_layers:
            recent = positions[:, None] - jnp.arange(K)[None, :]
            sel = mla_moe._Selecting(sel.pool, sel.index,
                                     jnp.maximum(recent, -1).astype(jnp.int32))
        return attend(params, c, layer, h, sel, li, positions, *rest, **kw)

    monkeypatch.setattr(mla_moe, "_attend_selecting", own_pick)
    broken, _ = decode(params, kv, {0: (seq[100], 100, tab)},
                       fn=lambda *a: mla_moe.decode_forward(a[0], CFG, *a[1:]))
    assert off(broken[0], want[100]) > 100 * TOL


@pytest.mark.parametrize("dtype, least", [(jnp.float32, 1.0), (jnp.bfloat16, 0.9)])
def test_the_served_picks_are_the_references_as_sets(params, dtype, least):
    """The positions each full layer picks for a decode lane at a context of
    150 and for the 9 tokens of a tail behind it, against the reference's
    S_t, as SETS: all of them in float32; in bfloat16 (keys cached rounded,
    scores float32) nine in ten and more, the overlap printed: the picks
    that differ lie at the margin of the sixteenth score."""
    cfg = mla_moe.MlaMoeConfig.tiny_mla_dsa(dtype=dtype)
    weights = jax.tree.map(lambda a: a.astype(dtype) if a.dtype == jnp.float32
                           and a.ndim > 2 else a, params)
    seq = sequence(4, 160)
    _, want = reference(params, CFG, seq)
    kv, tab = list(pools(cfg)), table_of(0)
    toks = np.zeros((1, 160), np.int32)
    toks[0, :150] = seq[:150]
    _, *kv = mla_moe.prefill_forward_batched(
        weights, cfg, jnp.asarray(toks), jnp.arange(160)[None], *kv,
        jnp.asarray(tab)[None], jnp.zeros((1,), jnp.int32), jnp.asarray([149]))
    toks = np.zeros((1, 16), np.int32)
    toks[0, :9] = seq[150:159]
    *_, seen = mla_moe.prefill_picks(
        weights, cfg, jnp.asarray(toks), 150 + jnp.arange(16)[None], *kv,
        jnp.asarray(tab)[None], jnp.asarray([150]), jnp.asarray([8]))
    seen = np.asarray(seen)
    assert seen.shape == (len(cfg.full_layers), 16, K)
    shares = []
    for fi, picks in enumerate(seen):
        for j in range(9):
            mine, theirs = set(picks[j].tolist()), set(want[fi, 150 + j].tolist())
            assert len(mine) == K and -1 not in mine
            shares.append(len(mine & theirs) / K)
    print(f"overlap of picks, {jnp.dtype(dtype).name}: mean "
          f"{np.mean(shares):.4f}, least {min(shares):.4f}")
    assert min(shares) >= least - 0.2 and np.mean(shares) >= least


# ---------------------------------------------------------------------- #
# the prefix cache brings the index keys with the pages
# ---------------------------------------------------------------------- #


def test_a_second_sequence_over_the_firsts_pages_reads_the_same_logits(params):
    """A second lane whose table begins with the first's 18 pages (144
    positions: what the prefix index hands over) prefills its tail alone and
    decodes: the index keys came with the pages, and the logits are those
    of the sequence served cold."""
    prefix, tails = sequence(6, 144), [sequence(7, 12), sequence(8, 12)]
    kv = list(pools())
    first = table_of(0)
    _, kv = prefill(params, kv, [(prefix, 0, first)], 160)
    for lane, tail in enumerate(tails, start=1):
        seq = prefix + tail
        want, _ = reference(params, CFG, seq)
        tab = np.concatenate([first[:18], table_of(lane)[18:]])
        got, kv = prefill(params, kv, [(tail[:8], 144, tab)], 8)
        assert off(got[0], want[151]) < TOL
        for t in range(152, 156):
            got, kv = decode(params, kv, {lane: (seq[t], t, tab)})
            assert off(got[lane], want[t]) < TOL


def engine(params, **over):
    kw = dict(model="tiny-mla-dsa", max_num_seqs=4, page_size=PAGE, num_pages=160,
              max_model_len=256, prefill_buckets=(32,), max_prefill_chunk=32,
              mixed_max_tokens=64)
    kw.update(over)
    return JaxEngine(EngineConfig(**kw), model_config=CFG, params=params)


def reference_greedy(params, prompt, n):
    seq = list(prompt)
    for _ in range(n):
        seq.append(int(reference(params, CFG, seq)[0][-1].argmax()))
    return seq[len(prompt):]


def test_a_prefixed_request_reads_the_same_tokens_cold_and_from_the_cache(params):
    """A prompt of 15 pages and 5 tokens served cold through split chunks,
    then again, beside a decoding request, from the prefix index through a
    mixed step (its tail's 5 tokens each pick 16 of 120 and more, over index
    keys that another request wrote): the same greedy tokens, the
    reference's; the counters say that the selection was on the path."""
    prompt, other = sequence(60, 125), sequence(61, 30)

    async def run():
        eng = engine(params)
        cold = await stream(eng, prompt, "cold", 10)
        _, cached = await asyncio.gather(
            stream(eng, other, "bg", 220),
            stream(eng, prompt, "cached", 10, delay=0.2))
        stats = eng.stats()
        await eng.close()
        return cold[0], cached[0], stats

    cold, cached, stats = asyncio.run(run())
    assert cold == cached == reference_greedy(params, prompt, 10)
    assert stats["kv_prefix_hit_blocks_total"] == 125 // PAGE
    assert stats["mixed_steps"] > 0
    assert stats["attention_impl"] == {
        "decode": "xla-latent-selected-top16-gather",
        "prefill": "xla-latent-selected-top16-gather+by-row",
        "ragged": "xla-latent-selected-top16-gather+by-row"}
    assert 0 < stats["dsa_selected_rows"] < 0.5 * stats["dsa_context_rows"]
    assert 0 < stats["step_index_kv_bytes"] < stats["step_latent_kv_bytes"] * 8
    assert stats["step_latent_kv_bytes"] < stats["step_min_bytes"]
    # two stores: latent rows of 7 layers... here 4 x 128 lanes, and index
    # keys of 2 layers x 16 values, under one count of pages
    stores = (CFG.num_layers * CFG.head_dim + 2 * CFG.index_head_dim) * 161 * PAGE * 4
    assert stores < stats["kv_pool_bytes"] < 1.05 * stores


def test_concurrent_requests_of_one_prefix_skip_ahead_over_both_stores(params):
    """Four requests with one long prefix arrive together: those behind the
    first splice its committed pages into their tables, and each reads the
    reference's tokens: the spliced pages hold the index keys too."""
    prefix = sequence(70, 128)
    prompts = [prefix + sequence(71 + i, 9) for i in range(4)]

    async def run():
        eng = engine(params, max_prefill_batch=1)
        out = await asyncio.gather(*(
            stream(eng, p, f"s{i}", 6) for i, p in enumerate(prompts)))
        stats = eng.stats()
        await eng.close()
        return [o[0] for o in out], stats

    got, stats = asyncio.run(run())
    assert got == [reference_greedy(params, p, 6) for p in prompts]
    assert stats["kv_skip_ahead_blocks"] + stats["kv_prefix_hit_blocks_total"] > 0


# ---------------------------------------------------------------------- #
# the held share, the sibling's programs, the counters
# ---------------------------------------------------------------------- #


def test_the_held_shares_of_a_sparse_layer_add_up_to_the_uncut_layer(params):
    """A sparse layer's routed part over all 8 experts is the sum of the
    parts of two chips that hold experts [0, 4) and [4, 8) under the same
    router of 8, the shared expert counted ONCE; the reference's held share
    is the served one."""
    layers = params["layers"]["experts"]
    names = ("w_gate", "w_up", "w_down")
    small = {k: v[0] for k, v in layers.items() if k not in names}
    x = jax.random.normal(jax.random.PRNGKey(3), (24, CFG.hidden_size), jnp.float32)
    whole_cfg = mla_moe.MlaMoeConfig.tiny_mla_dsa(dtype=jnp.float32, num_experts=8)
    whole, _ = exaone_moe.routed_block(
        small, {k: layers[k] for k in names}, 0, x, whole_cfg, None)
    shared = ref.gated_silu(
        ref.rms(x, small["norm"], CFG.rms_norm_eps), small["ws_gate"],
        small["ws_up"], small["ws_down"])
    parts = []
    for first in (0, 4):
        held = mla_moe.MlaMoeConfig.tiny_mla_dsa(
            dtype=jnp.float32, num_experts=4, router_width=8, first_expert_held=first)
        stacks = {k: layers[k][:, first: first + 4] for k in names}
        out, _ = exaone_moe.routed_block(small, stacks, 0, x, held, None)
        parts.append(out - x)
        theirs, _ = ref.sparse_ffn(
            ref.rms(x, small["norm"], CFG.rms_norm_eps),
            {**small, **stacks}, held, jnp.full((24, 2), -1), 0)
        assert float(jnp.abs(theirs - (out - x)).max()) < 1e-5
    assert float(jnp.abs(parts[0] + parts[1] - shared - (whole - x)).max()) < 1e-5


def test_a_configuration_that_does_not_select_keeps_its_programs(params):
    """`tiny-mla-moe`'s lowered decode step and mixed step hold the
    operations they held before the selection was written (the counts of PR
    57's tree, read there with this function): no leaf, no argument and no
    operation more; the selecting configuration's hold `top_k`."""
    plain = mla_moe.MlaMoeConfig.tiny_mla_moe(dtype=jnp.float32)
    shapes = jax.eval_shape(lambda: mla_moe.init_params(plain, jax.random.PRNGKey(0)))
    assert "indexer" not in shapes["layers"]
    cache, kv_v = alloc_state_cache(plain, 40, 16, 4, 128, 8)
    z = lambda *s: jnp.zeros(s, jnp.int32)  # noqa: E731
    step = jax.jit(lambda p, *a: mla_moe.decode_forward(p, plain, *a)).lower(
        shapes, z(4), z(4), cache, kv_v, z(4, 8), z(4)).as_text()
    mixed = jax.jit(lambda p, *a: mla_moe.ragged_forward(p, plain, *a)).lower(
        shapes, z(96), z(96), z(96), cache, kv_v, z(8, 8), z(8), z(8), z(8),
        z(8)).as_text()
    assert (_ops(step), _ops(mixed)) == (1829, 2412)
    assert not _picks(step) and not _picks(mixed)
    mine = jax.eval_shape(lambda: mla_moe.init_params(CFG, jax.random.PRNGKey(0)))
    cache, index = pools()
    step = jax.jit(lambda p, *a: mla_moe.decode_forward(p, CFG, *a)).lower(
        mine, z(4), z(4), cache, index, z(4, TABLE), z(4)).as_text()
    assert _picks(step)


def _built(cfg):
    from worker_entry import build_model_config

    return build_model_config(cfg)


def test_what_a_step_asks_for_at_the_published_widths():
    """A decode step of 28 lanes behind 16,500 positions and 4 behind 300:
    the least bytes count 2,048 latent rows a long lane and layer, not
    16,500; the index keys are read whole in the two full layers; the share
    selected is the issue's 13%; the indexer's multiply-adds are counted."""
    with open(CONFIG_FILE) as f:
        c = _built(json.load(f))
    work = Work(c.index_topk)
    for n in [16_500] * 28 + [300] * 4:
        work.decode(n)
    flops, nbytes, named = work.of(functools.partial(
        mla_moe.step_work, c, weight_bytes=2, kv_bytes=1280))
    rows = 28 * 2048 + 4 * 300
    assert named["dsa_selected_rows"] == rows
    assert named["dsa_context_rows"] == 28 * 16_500 + 4 * 300
    assert 0.12 < rows / named["dsa_context_rows"] < 0.13
    assert named["latent_kv_bytes"] == 7 * 1280 * (rows + 32)
    assert named["index_kv_bytes"] == 2 * 256 * (named["dsa_context_rows"] + 32)
    whole = mla_moe.step_work(c, 32, work.context, 1, kv_tokens=work.kv_tokens,
                              weight_bytes=2, kv_bytes=1280)
    assert whole[2]["latent_kv_bytes"] == 7 * 1280 * (named["dsa_context_rows"] + 32)
    assert whole[1] - nbytes == whole[2]["latent_kv_bytes"] - named["latent_kv_bytes"]
    # the issue's reckoning: about 8 GB of weights, 0.75 GB of cache
    cache = named["latent_kv_bytes"] + named["index_kv_bytes"]
    assert 0.7e9 < cache < 0.8e9 and 7.5e9 < nbytes - cache < 8.8e9
    scoring = 2 * 2 * 32 * 128 * work.context
    assert 0.03 * flops < scoring < 0.1 * flops
    # a chunk of 13 tokens behind 16,384: each token attends 2,048
    tail = Work(c.index_topk)
    tail.chunk(16_384, 13, True)
    assert (tail.attended, tail.selected) == (13 * 2048, 2048)
    short = Work(c.index_topk)
    short.chunk(2040, 13, True)
    assert short.attended == sum(min(2040 + j + 1, 2048) for j in range(13))


# ---------------------------------------------------------------------- #
# the benchmark's files
# ---------------------------------------------------------------------- #


def catalog_row():
    if not os.path.exists(CATALOG):
        pytest.skip("the guides are not installed beside this checkout")
    with open(CATALOG) as f:
        return next(r for r in map(json.loads, f) if r["name"] == "GLM-5.2")


def test_the_configuration_loads_into_the_dataclass():
    """The benchmark's file, plain and under `rehearsal`, fills MlaMoeConfig
    field by field; the cut is the issue's; the bytes are its arithmetic; the
    harness's own checks pass on it."""
    import files_check
    from worker_entry import load_config, lookup

    for rehearsal in (False, True):
        cfg = load_config(CONFIG_FILE, rehearsal)
        built = _built(cfg)
        assert type(built) is mla_moe.MlaMoeConfig
        for field, key in cfg["dataclass_fields"].items():
            want = lookup(cfg, key)
            assert getattr(built, field) == (tuple(want) if isinstance(want, list) else want)
        assert built.index_topk and built.full_layers == (0, 4)
        assert built.ffn_kinds == ("dense",) + ("sparse",) * 6
        assert built.rope_interleave and built.indexer_rope_interleave
    cfg = load_config(CONFIG_FILE, False)
    built = _built(cfg)
    assert (built.num_layers, built.num_heads, built.latent_dim, built.head_dim,
            built.num_experts, built.router_width, built.first_expert_held,
            built.num_experts_per_tok, built.vocab_size, built.index_topk,
            built.index_n_heads, built.index_head_dim, built.rope_theta) == (
        7, 64, 576, 640, 16, 256, 0, 8, 19_360, 2048, 32, 128, 8_000_000)
    assert files_check.routed_geometry("glm", cfg) == (256, 8, 6)
    assert cfg["judge_routing"] == "forced" and cfg["family"] == "mla_dsa_moe"
    shapes = jax.eval_shape(lambda: mla_moe.init_params(built, jax.random.PRNGKey(0)))
    nbytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(shapes))
    assert abs(nbytes - 11.02e9) < 0.02e9  # the issue's 11.02 GB
    ix = shapes["layers"]["indexer"]
    assert sum(x.size for x in jax.tree.leaves(ix)) == 2 * 9_371_904
    files_check.check(ROOT)
    files_check.check_judge("glm-5.2-ep16-d7", cfg)


def test_every_key_of_the_catalogs_row_stands_in_the_file_at_its_value():
    """All keys of the row's `config`, numbers, strings, booleans, NULLS,
    lists and the nested `rope_parameters` alike, under the same key with
    the same value and type; the six in `reduced` alone differ, and their
    published values stand under `published`; `head_dim` 192,
    `num_key_value_heads` 64 and `ep_size` 1 stand though no field reads
    them."""
    row = catalog_row()
    with open(CONFIG_FILE) as f:
        cfg = json.load(f)
    assert cfg["source"] == row["source_url"] and len(row["config"]) == 45
    differs = []
    for key, value in row["config"].items():
        assert key in cfg, key
        if cfg[key] != value or type(cfg[key]) is not type(value):
            differs.append(key)
    assert sorted(differs) == sorted(cfg["reduced"]) and len(differs) == 6
    assert cfg["published"] == {k: row["config"][k] for k in cfg["reduced"]}
    assert cfg["index_topk_pattern"] is None
    assert cfg["rope_parameters"] == {"rope_theta": 8000000, "rope_type": "default"}
    assert (cfg["head_dim"], cfg["num_key_value_heads"], cfg["ep_size"]) == (192, 64, 1)
    # the held stack is published layers 2 to 8
    assert cfg["indexer_types"] == row["config"]["indexer_types"][2:9]
    assert cfg["mlp_layer_types"] == row["config"]["mlp_layer_types"][2:9]


@pytest.mark.parametrize("key", ["configs", "workloads", "per_layer"])
def test_the_benchmark_names_the_configuration_its_cell_and_its_metrics(key):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    if key == "per_layer":
        mine = [m for m in bench[key] if m.get("workloads") == [CELL]]
        assert sorted(m["name"] for m in mine) == [
            "dsa.index_kv_bytes_share", "dsa.latent_kv_bytes_share",
            "dsa.selected_rows_share", "dsa.step_hbm_roofline_share",
            "dsa.step_mfu", "dsa_moe.expert_bytes_share", "dsa_moe.held_rows_share"]
        assert all(CELL not in m.get("workloads", []) for m in bench[key] if m not in mine)
        return
    mine = [e for e in bench[key] if "glm-5.2-ep16-d7" in e["name"]]
    assert len(mine) == 1
    if key == "workloads":
        assert mine[0] == dict(mine[0], name=CELL, config="glm-5.2-ep16-d7",
                               traffic="sharedprefix-closed", chips=1)
