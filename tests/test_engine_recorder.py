"""The engine's span-and-counter recorder (dynamo_tpu/engine/recorder.py):
phases of an iteration, every pipeline entry's kind, interval and work,
the waits ahead of a first token, and the benchmark's readers of them
(benchmark/layer_metrics/*.json added with it). CPU, tiny models."""

import asyncio
import glob
import importlib.util
import json
import logging
import os
import sys
import time

import jax
import numpy as np
import pytest

from dynamo_tpu.engine import EngineConfig, JaxEngine
from dynamo_tpu.engine import recorder as recorder_mod
from dynamo_tpu.engine.recorder import PHASES, STEP_KINDS, Recorder
from dynamo_tpu.llm.protocols import PreprocessedRequest
from dynamo_tpu.models import llama, moe
from dynamo_tpu.runtime.engine import Context
from dynamo_tpu.runtime.metrics import METRICS

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PAGE = 8
K = 4  # decode_block_steps of the engines below
DENSE = llama.LlamaConfig.tiny()
ROUTED = moe.MoeConfig.tiny_moe(capacity_factor=2.0)


# -- the recorder alone --------------------------------------------------- #

def test_a_span_costs_under_three_microseconds_with_no_profiler_open():
    rec = Recorder()
    n = 50_000
    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        for _ in range(n):
            with rec.span("pack"):
                pass
        best = min(best, (time.perf_counter() - t0) / n)
    assert rec.phases["pack"][0] == 5 * n
    assert best < 3e-6, f"{best * 1e6:.2f} us a span"


def test_a_span_counts_once_and_its_continuation_only_adds_time():
    rec = Recorder()
    with rec.span("pack"):
        time.sleep(0.002)
    with rec.span("pack", more=True):
        time.sleep(0.002)
    count, seconds, slow = rec.phases["pack"]
    assert count == 1 and 0.004 <= seconds < 0.2 and slow == 0
    out = rec.stats()
    assert out["phase_pack_count"] == 1 and out["phase_pack_s"] == round(seconds, 6)
    assert all(out[f"phase_{p}_s"] == 0 for p in PHASES if p != "pack")


@pytest.mark.parametrize("phase", ["put", "wait"])
def test_a_planted_sleep_raises_slow_and_logs_the_phase(phase, caplog, monkeypatch):
    """0.6 s inside one phase: its `_slow` rises by one and, unless the
    phase is `wait` (an idle engine is no stall), one WARNING line names
    it with the entry's kind and the engine's load."""
    assert recorder_mod.SLOW_SPAN_S == 0.5
    rec = Recorder(lambda: "1 in flight, 3 running, 2 waiting")
    rec.entry_kind = "mixed"
    with caplog.at_level(logging.WARNING, logger=recorder_mod.__name__):
        with rec.span(phase):
            time.sleep(0.6)
        with rec.span(phase):
            pass
    assert rec.phases[phase][2] == 1 and rec.phases[phase][0] == 2
    lines = [r.getMessage() for r in caplog.records]
    if phase == "wait":
        assert lines == []
    else:
        assert len(lines) == 1
        assert lines[0].startswith("engine phase put took 0.6")
        assert lines[0].endswith(
            " s: entry mixed, 1 in flight, 3 running, 2 waiting")


def test_an_entrys_interval_runs_from_the_later_of_last_ready_and_dispatch():
    rec = Recorder()
    a, b, c, d = {}, {}, {}, {}
    rec.dispatched(a, "block", (10, 100))
    rec.dispatched(b, "mixed", (1, 1))
    t0 = a["t_dispatch"]
    # a idle pipeline: a's interval starts at its dispatch
    rec.fetched([a], t0 + 0.100)
    # b was queued behind a: ready to ready
    rec.fetched([b], t0 + 0.130)
    assert rec.steps["block"] == [1, pytest.approx(0.100)]
    assert rec.steps["mixed"][0] == 1
    assert rec.steps["mixed"][1] == pytest.approx(0.130 - 0.100, abs=1e-4)
    # the engine sat idle, then one fetch brings a prefill and a block back
    # together: from the earlier dispatch, in equal parts
    rec.dispatched(c, "prefill", (0, 0))
    rec.dispatched(d, "block", (0, 0))
    c["t_dispatch"], d["t_dispatch"] = t0 + 5.0, t0 + 5.01
    rec.fetched([c, d], t0 + 5.2)
    assert rec.steps["prefill"] == [1, pytest.approx(0.1)]
    assert rec.steps["block"] == [2, pytest.approx(0.2)]
    # an interval of half a second or more is a stall, not a step: it is
    # kept out of its kind's mean (the profiler's stop, a compile)
    e = {}
    rec.dispatched(e, "block", (0, 0))
    e["t_dispatch"] = t0 + 6.0
    rec.fetched([e], t0 + 8.5)
    assert rec.steps["block"] == [2, pytest.approx(0.2)]
    out = rec.stats()
    assert out["step_stalled_count"] == 1 and out["step_stalled_s"] == 2.5
    assert out["step_model_flops"] == 11 and out["step_min_bytes"] == 101
    assert sum(out[f"step_{k}_count"] for k in STEP_KINDS) == 4


# -- the work of an entry, by hand ---------------------------------------- #

def test_step_work_dense_by_hand():
    c = DENSE  # hidden 64, mlp 128, 2 layers, 4 heads / 2 kv heads of 16
    attn = 64 * 64 + 2 * 64 * 32 + 64 * 64  # q, k and v, o
    layer = attn + 3 * 64 * 128
    head = 64 * 512
    wb = 2  # bf16
    # a block of 4 passes over 3 lanes at contexts 10, 20, 30: 12 tokens
    ctx = sum(4 * L + 6 for L in (10, 20, 30))
    flops, nbytes = llama.step_work(c, 12, ctx, 4)
    assert flops == 2 * (2 * layer * 12 + head * 12) + 4 * 2 * 4 * 16 * ctx
    kv = 2 * 2 * 16 * wb  # one position's K and V in one layer
    assert nbytes == 4 * (2 * layer + head) * wb + 2 * kv * (ctx + 12)
    # a prompt's chunk of 8 tokens behind 5: one sample, 13 positions read
    flops, nbytes = llama.step_work(c, 8, 8 * 5 + 36, 1, sampled=1, kv_tokens=13)
    assert flops == 2 * (2 * layer * 8 + head) + 4 * 2 * 4 * 16 * 76
    assert nbytes == (2 * layer + head) * wb + 2 * kv * (13 + 8)
    # int8 weights and a quantized pool at their own bytes
    _, q = llama.step_work(c, 1, 1, 1, weight_bytes=1, kv_bytes=40.0)
    assert q == (2 * layer + head) + 2 * 40 * 2
    assert llama.step_work(c, 0, 0, 0) == (0, 0)


def test_step_work_routed_by_hand():
    c = ROUTED  # 4 experts of width 96, 2 a token
    attn = 64 * 64 + 2 * 64 * 32 + 64 * 64
    expert, router, head, wb = 3 * 64 * 96, 64 * 4, 64 * 512, 2
    per_token = attn + router + 2 * expert  # the experts it is sent to
    kv = 2 * 2 * 16 * wb
    # one row a pass reaches at most 2 of the 4 experts
    flops, nbytes = moe.step_work(c, 3, 30, 3)
    assert flops == 2 * (2 * per_token * 3 + head * 3) + 4 * 2 * 4 * 16 * 30
    one_pass = 2 * (attn * wb + router * 4 + 2 * expert * wb) + head * wb
    assert nbytes == 3 * one_pass + 2 * kv * (30 + 3)
    # 5 rows a pass could reach 10: capped at the 4 there are
    _, nbytes = moe.step_work(c, 5, 5, 1)
    assert nbytes == 2 * (attn * wb + router * 4 + 4 * expert * wb) \
        + head * wb + 2 * kv * 10


# -- a tiny engine -------------------------------------------------------- #

def _engine(family="dense", **over):
    cfg, mod = (DENSE, llama) if family == "dense" else (ROUTED, moe)
    kw = dict(
        model="tiny", max_num_seqs=4, page_size=PAGE, num_pages=128,
        max_model_len=256, prefill_buckets=(16, 32), max_prefill_chunk=32,
        decode_block_steps=K, mixed_dispatch=True,
    )
    kw.update(over)
    eng = JaxEngine(EngineConfig(**kw), model_config=cfg,
                    params=mod.init_params(cfg, jax.random.PRNGKey(0)))
    # one table width, as under the Pallas ragged kernel: the first mixed
    # step then primes one family (tests/test_mixed_fusion.py:_one_width)
    eng._mixed_table_rungs = (eng.config.max_pages_per_seq,)
    eng.dispatched = []  # (kind, work) of every entry, in order
    stamp = eng._rec.dispatched

    def logged(entry, kind, work, **more):
        eng.dispatched.append((kind, work))
        return stamp(entry, kind, work, **more)

    eng._rec.dispatched = logged
    return eng


async def _stream(eng, prompt, rid, n):
    req = PreprocessedRequest(
        token_ids=list(prompt),
        stop_conditions={"max_tokens": n, "ignore_eos": True},
        sampling_options={"temperature": 0.0}, request_id=rid,
    ).to_dict()
    return [t async for out in eng.generate(req, Context())
            for t in (out.get("data") or {}).get("token_ids", [])]


async def _settled(eng):
    """Close the engine once its loop has fetched what it dispatched (a
    block queued behind a request's last one outlives the stream)."""
    for _ in range(500):
        if not (eng._inflight or eng._pending_prefill):
            break
        await asyncio.sleep(0.01)
    await eng.close()


class _Stepped:
    """The test makes every `_step_once` itself, so what is in flight when
    an arrival lands is known."""

    def __init__(self, eng):
        self.eng = eng

    async def __aenter__(self):
        # generate() starts the step loop unless a task is there already
        self.eng._step_task = asyncio.create_task(asyncio.sleep(3600))
        return self

    async def __aexit__(self, *exc):
        await self.eng.close()

    async def submit(self, prompt, rid, n):
        task = asyncio.create_task(_stream(self.eng, prompt, rid, n))
        for _ in range(50):
            if any(s.request_id == rid for s in self.eng._waiting):
                return task
            await asyncio.sleep(0)
        raise AssertionError(f"{rid} never reached the waiting list")

    async def until(self, cond, limit=400):
        for _ in range(limit):
            if cond():
                return
            await self.eng._step_once()
            await asyncio.sleep(0)
        raise AssertionError("the engine never got there")


def _prompt(n, seed):
    return np.random.RandomState(seed).randint(5, 200, size=n).tolist()


def _by_hand(family, entries):
    """The formula of models/<family>.step_work for a sequence of entries
    given as (real, context, passes, sampled, kv_tokens)."""
    cfg, mod = (DENSE, llama) if family == "dense" else (ROUTED, moe)
    flops = nbytes = 0
    for real, ctx, passes, sampled, kv_tokens in entries:
        f, b = mod.step_work(cfg, real, ctx, passes, sampled=sampled,
                             kv_tokens=kv_tokens)
        flops, nbytes = flops + f, nbytes + b
    return flops, nbytes


@pytest.mark.parametrize("family", ["dense", "routed"])
def test_a_prefill_two_blocks_and_a_mixed_step_by_hand(family):
    """Request a (20 tokens, 9 to make) alone: a split prefill of one chunk,
    then two blocks of K = 4 that its 8 tokens left fill exactly. Request b
    (12 tokens, 1 to make) beside c's decode lane: one mixed step."""
    eng = _engine(family)

    async def run():
        async with _Stepped(eng) as st:
            a = await st.submit(_prompt(20, 1), "a", 9)
            await st.until(a.done)
            kinds = [k for k, _ in eng.dispatched]
            assert kinds[:3] == ["prefill", "block", "block"], kinds
            # a block queued after a's last useful one asks for nothing
            assert all(w == (0, 0) for _, w in eng.dispatched[3:])
            n_a = len(eng.dispatched)
            c = await st.submit(_prompt(10, 2), "c", 40)
            await st.until(lambda: any(
                s is not None and s.request_id == "c" and s.generated > 0
                for s in eng.slots))
            await st.until(lambda: not eng._inflight)
            lane = next(s for s in eng.slots if s is not None)
            seq_len = int(eng.seq_lens[lane.slot_idx])
            n_c = len(eng.dispatched)
            b = await st.submit(_prompt(12, 3), "b", 1)
            await st.until(b.done)
            mixed = [w for k, w in eng.dispatched[n_c:] if k == "mixed"]
            assert len(mixed) == 1
            c.cancel()
            return n_a, mixed[0], seq_len

    n_a, mixed, seq_len = asyncio.run(run())
    want = _by_hand(family, [
        (20, 20 * 21 // 2, 1, 1, 20),  # the prompt: token j attends j + 1
        (4, 4 * 21 + 6, 4, None, None),  # tokens 2 to 5, from a context of 21
        (4, 4 * 25 + 6, 4, None, None),  # tokens 6 to 9, queued behind it
    ])
    got = tuple(map(sum, zip(*(w for _, w in eng.dispatched[:n_a]))))
    assert got == want
    # b's whole prompt and c's one decode row at its context, in one pass
    assert mixed == _by_hand(family, [
        (12 + 1, 12 * 13 // 2 + seq_len, 1, 2, 12 + seq_len)])
    out = eng.stats()
    assert out["step_model_flops"] == sum(w[0] for _, w in eng.dispatched)
    assert out["step_min_bytes"] == sum(w[1] for _, w in eng.dispatched)


def test_served_requests_leave_the_counters_whole():
    """A handful of requests: every entry dispatched is counted once it is
    fetched, every request admitted once and given a first token once, both
    waits non-negative, and nothing runs backwards between two stats()."""
    eng = _engine("dense")
    # the first block's launch holds the device thread as a program that
    # compiles inside it does: how long a compile takes is the CPU's (and
    # the compilation cache's) business, so the test plants one
    block, planted = eng._dev_block, []

    def compiling():
        if not planted:
            planted.append(time.sleep(recorder_mod.SLOW_SPAN_S))
        return block()

    eng._dev_block = compiling

    async def run():
        first = eng.stats()
        outs = await asyncio.gather(*(
            _stream(eng, _prompt(9 + 5 * i, 10 + i), f"r{i}", 6 + i)
            for i in range(5)))
        mid = eng.stats()
        await _stream(eng, _prompt(11, 99), "last", 5)
        await _settled(eng)
        return first, mid, eng.stats(), outs

    first, mid, last, outs = asyncio.run(run())
    assert [len(o) for o in outs] == [6 + i for i in range(5)]
    # an entry that met a compile is counted as stalled and not as a step:
    # every entry is in one of the two counts, the planted one among the
    # stalled, and a stalled entry brings its whole interval with it
    assert sum(last[f"step_{k}_count"] for k in STEP_KINDS) \
        + last["step_stalled_count"] == len(eng.dispatched)
    assert last["step_stalled_count"] >= len(planted) == 1
    assert last["step_stalled_s"] \
        >= recorder_mod.SLOW_SPAN_S * last["step_stalled_count"]
    assert last["req_admitted"] == last["req_first_tokens"] == 6
    assert mid["req_admitted"] == 5
    assert last["req_queue_wait_s"] >= 0 and last["req_admit_to_first_s"] > 0
    keys = ["engine_clock_s", "step_model_flops", "step_min_bytes",
            "step_stalled_count", "step_stalled_s",
            *(f"phase_{p}_{x}" for p in PHASES for x in ("count", "s", "slow")),
            *(f"step_{k}_{x}" for k in STEP_KINDS for x in ("count", "interval_s"))]
    for key in keys:
        assert first[key] <= mid[key] <= last[key], key
    assert mid["engine_clock_s"] > first["engine_clock_s"]
    for p in ("admit", "pack", "put", "launch", "fetch", "emit"):
        assert last[f"phase_{p}_count"] > 0 and last[f"phase_{p}_s"] > 0, p
    # nothing counted twice: the phases fit into the clock that held them
    phases = sum(last[f"phase_{p}_s"] - first[f"phase_{p}_s"] for p in PHASES)
    assert phases <= last["engine_clock_s"] - first["engine_clock_s"]
    # _timed's table moved into the recorder and reads as before
    assert last["dispatch_fetch_count"] == last["phase_fetch_count"]
    assert last["dispatch_block_count"] >= last["step_block_count"] > 0
    for gone in ("mixed_padding_frac", "split_padding_frac", "sched_last_decision"):
        assert gone not in last and gone not in METRICS
    assert last["split_real_tokens"] <= last["split_padded_tokens"]


def test_a_preempted_resume_is_not_admitted_twice():
    """Two requests whose contexts outgrow the pool: one is preempted and
    resumes. It keeps its first admission and its first token."""
    eng = _engine("dense", num_pages=10, max_num_seqs=2,
                  enable_prefix_caching=False)
    preempted = []
    preempt = eng._preempt_one

    def logged(exclude_idx):
        done = preempt(exclude_idx)
        preempted.append(done)
        return done

    eng._preempt_one = logged

    async def run():
        outs = await asyncio.gather(
            _stream(eng, _prompt(20, 1), "p0", 36),
            _stream(eng, _prompt(20, 2), "p1", 36))
        await _settled(eng)
        return outs

    outs = asyncio.run(run())
    assert [len(o) for o in outs] == [36, 36]
    assert any(preempted), "the pool was to run out: make the case tighter"
    out = eng.stats()
    assert out["req_admitted"] == out["req_first_tokens"] == 2


def test_the_entries_ahead_of_a_first_token_are_those_fetched_since_the_arrival():
    """Request b (12 tokens, 1 to make) arrives beside c's decode lane: what
    is fetched between its arrival and its first token is its own mixed
    step and the blocks that were in the pipeline ahead of it."""
    eng = _engine("dense")

    async def run():
        async with _Stepped(eng) as st:
            c = await st.submit(_prompt(10, 2), "c", 40)
            await st.until(lambda: any(
                s is not None and s.request_id == "c" and s.generated > 0
                for s in eng.slots))
            s0 = eng.stats()
            b = await st.submit(_prompt(12, 3), "b", 1)
            await st.until(b.done)
            s1 = eng.stats()
            c.cancel()
            return s0, s1

    s0, s1 = asyncio.run(run())
    grew = {k: s1[k] - s0[k] for k in (
        "req_first_tokens", "req_mixed_ahead", "req_blocks_ahead",
        "step_mixed_count", "step_block_count", "step_stalled_count")}
    assert grew["req_first_tokens"] == 1
    # (an entry that met a compile counts as stalled, and still stood ahead;
    # the loop may fetch another block before b's task has seen its end)
    assert grew["req_mixed_ahead"] == 1
    assert grew["step_mixed_count"] + grew["step_stalled_count"] >= 1
    assert 0 <= grew["req_blocks_ahead"] \
        <= grew["step_block_count"] + grew["step_stalled_count"]


@pytest.mark.parametrize("family", ["dense", "routed"])
def test_a_dispatch_hands_the_runtime_one_array(family):
    """A few served requests: every mixed step and lane patch hands the
    runtime ONE host array (its operands laid end to end in one buffer:
    engine._put_words); a carry reset, which is rare and has no program to
    slice a buffer, hands over its twelve in one call, and a split prefill
    its thirteen. Each inside ONE counted `put` span."""
    eng = _engine(family)
    seen = {}  # helper -> [(arrays, spans) of each call]

    def counted(name):
        fn = getattr(eng, name)

        def call(*a):
            before = eng._rec.put_arrays, eng._rec.phases["put"][0]
            out = fn(*a)
            seen.setdefault(name, []).append(
                (eng._rec.put_arrays - before[0],
                 eng._rec.phases["put"][0] - before[1]))
            return out

        setattr(eng, name, call)

    want = {"_dev_mixed": (1, 1), "_dev_patch": (1, 1), "_dev_reset": (12, 1),
            "_dev_prefill": (13, 1)}
    for name in want:
        counted(name)

    async def run():
        outs = await asyncio.gather(*(
            _stream(eng, _prompt(9 + 5 * i, 10 + i), f"r{i}", 6 + i)
            for i in range(5)))
        await _settled(eng)
        return outs

    outs = asyncio.run(run())
    assert [len(o) for o in outs] == [6 + i for i in range(5)]
    assert set(seen) == set(want)
    for name, calls in seen.items():
        assert set(calls) == {want[name]}, (name, calls)
    out = eng.stats()
    # (what is left: the mixed family's packs, put as the family compiles)
    left = [out[k] - sum(c[i] for calls in seen.values() for c in calls)
            for i, k in enumerate(("put_arrays", "phase_put_count"))]
    assert left[0] == left[1] == len(eng._mixed_token_buckets)


#: every helper that takes a dispatch's operands from the host to the device
PUT_HELPERS = (
    "_prefill_operands", "_mixed_operands", "_dev_mixed", "_dev_reset",
    "_dev_patch", "_dev_block_guided", "_dev_block_lora", "_lora_operand",
    "_dev_prefill_single",
)


@pytest.mark.parametrize("helper", PUT_HELPERS)
def test_a_helpers_transfers_all_go_through_put(helper):
    """`put_arrays` counts what `JaxEngine._put` hands over and cannot see
    a transfer made round it, so no helper makes one: no `jnp.asarray`,
    `jnp.array` or `jax.device_put` of its own."""
    import inspect

    src = inspect.getsource(getattr(JaxEngine, helper))
    for call in ("jnp.asarray(", "jnp.array(", "device_put("):
        assert call not in src, (helper, call)


def test_a_dispatchs_buffer_comes_apart_to_the_bit():
    """pack_words / riders: seeds stay uint32 and temperatures float32 by
    their bits (a NaN's payload and a negative zero survive: a bitcast,
    never a cast), masks come back bool, shapes as they were; an array of
    another element size is refused, not reinterpreted."""
    from dynamo_tpu.engine.engine import pack_words, riders

    nan = np.array([0x7FC00123, 0x80000000, 0x3F800000], np.uint32)
    arrays = [
        np.array([0, 2**31, 2**32 - 1], np.uint32),
        nan.view(np.float32),
        np.array([True, False, True, True]),
        np.arange(-6, 6, dtype=np.int32).reshape(3, 4),
        np.asarray(7, np.int32),
        np.zeros((0,), np.int32),
        np.arange(12, dtype=np.float32).reshape(4, 3)[:, 1],  # a column
    ]
    names = tuple(f"a{i}" for i in range(len(arrays)))
    buf, layout = pack_words(arrays)
    assert buf.dtype == np.int32 and buf.ndim == 1
    assert buf.size == sum(a.size for a in arrays)
    got = jax.jit(riders, static_argnums=(0, 2))(names, buf, layout)
    assert tuple(got) == names
    for g, a in zip(got.values(), arrays):
        assert g.dtype == a.dtype and g.shape == a.shape
        bits = lambda x: np.asarray(x).astype(np.uint8) \
            if a.dtype == np.bool_ else np.ascontiguousarray(x).view(np.uint32)
        assert np.array_equal(bits(g), bits(a))
    for odd in (np.zeros(3, np.int64), np.zeros(3, np.uint8)):
        with pytest.raises(AssertionError):
            pack_words([odd])


def test_every_stats_key_of_the_recorder_is_registered():
    out = _engine("dense")._rec.stats()
    missing = [k for k in out if k not in METRICS and not k.startswith("dispatch_")]
    assert missing == []
    for key in out:
        if key in METRICS:
            assert METRICS[key]["kind"] == "counter" and METRICS[key]["export"]
    with open(os.path.join(ROOT, "docs", "observability.md")) as f:
        doc = f.read()
    assert all(f"`{k}`" in doc for k in out if k in METRICS)


def test_the_spans_lie_in_the_profilers_own_trace(tmp_path):
    """A profiler session on the CPU around a few iterations: the recorder's
    spans are events of the host plane, on the profiler's clock."""
    from jax.profiler import ProfileData

    eng = _engine("dense")

    async def run():
        await _stream(eng, _prompt(12, 5), "warm", 6)  # compile outside it
        jax.profiler.start_trace(str(tmp_path))
        try:
            await asyncio.gather(_stream(eng, _prompt(12, 6), "t0", 9),
                                 _stream(eng, _prompt(17, 7), "t1", 9))
        finally:
            jax.profiler.stop_trace()
        await eng.close()

    asyncio.run(run())
    found = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    assert found
    pd = ProfileData.from_file(found[-1])
    seen = {}
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("engine."):
                    seen.setdefault(ev.name, []).append(
                        (line.name, ev.start_ns, ev.duration_ns))
    # (engine.ingest: the making of the two requests' slots, beside the phases)
    for name in ("engine.pack", "engine.put", "engine.launch", "engine.fetch",
                 "engine.emit", "engine.ingest"):
        assert name in seen, sorted(seen)
        assert all(d > 0 for _, _, d in seen[name])
    # spans of one thread do not nest: the device thread's put and launch,
    # the loop's pack and emit
    for names in (("engine.put", "engine.launch"),
                  ("engine.pack", "engine.emit", "engine.ingest")):
        spans = sorted((s, s + d) for n in names for _, s, d in seen[n])
        assert all(a[1] <= b[0] for a, b in zip(spans, spans[1:])), names


# -- the benchmark's readers ---------------------------------------------- #

def _layer_metrics():
    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    try:
        spec = importlib.util.spec_from_file_location(
            "bench_layer_metrics", os.path.join(ROOT, "benchmark", "layer_metrics.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod
    finally:
        sys.path.pop(0)


S0 = {"engine_clock_s": 100.0, "phase_admit_s": 0.5, "phase_pack_s": 1.0,
      "phase_put_s": 1.0, "phase_launch_s": 0.5, "phase_emit_s": 1.0,
      "phase_fetch_s": 30.0, "phase_wait_s": 2.0,
      "phase_pack_count": 100, "phase_put_count": 200, "phase_emit_count": 100,
      "put_arrays": 250, "step_block_count": 10, "step_block_interval_s": 1.0,
      "step_mixed_count": 5, "step_mixed_interval_s": 0.1,
      "step_model_flops": 1e12, "step_min_bytes": 1e12,
      "req_admitted": 10, "req_queue_wait_s": 0.2,
      "req_first_tokens": 9, "req_admit_to_first_s": 0.5,
      **{f"phase_{p}_slow": 0 for p in PHASES}}
S1 = {"engine_clock_s": 150.0, "phase_admit_s": 1.5, "phase_pack_s": 4.0,
      "phase_put_s": 3.0, "phase_launch_s": 1.5, "phase_emit_s": 3.0,
      "phase_fetch_s": 65.0, "phase_wait_s": 4.0,
      "phase_pack_count": 600, "phase_put_count": 1200, "phase_emit_count": 500,
      "put_arrays": 1500, "step_block_count": 410, "step_block_interval_s": 41.4,
      "step_mixed_count": 205, "step_mixed_interval_s": 3.3,
      "step_model_flops": 1e12 + 0.12 * 50 * 197e12,
      "step_min_bytes": 1e12 + 0.75 * 50 * 819e9,
      "req_admitted": 210, "req_queue_wait_s": 6.2,
      "req_first_tokens": 209, "req_admit_to_first_s": 14.5,
      **{f"phase_{p}_slow": 0 for p in PHASES},
      "phase_fetch_slow": 2, "phase_put_slow": 1, "phase_wait_slow": 7}
# the request's path (PR 56): 200 requests arrived in the window, all with a
# timeline from this host
for _s0, _s1, _rows in (
        (S0, S1, {"http": (10, 0.01, 210, 0.21), "preprocess": (10, 0.1, 210, 2.1),
                  "route": (10, 0.0, 210, 0.02), "send": (10, 0.0, 210, 0.04),
                  "hop": (10, 0.01, 210, 0.07), "ingest": (10, 0.05, 210, 0.85),
                  "first_frame": (9, 0.009, 209, 0.109)}),):
    for _stage, (_c0, _t0, _c1, _t1) in _rows.items():
        _s0[f"req_stage_{_stage}_count"], _s0[f"req_stage_{_stage}_s"] = _c0, _t0
        _s1[f"req_stage_{_stage}_count"], _s1[f"req_stage_{_stage}_s"] = _c1, _t1
S0.update({"req_blocks_ahead": 12, "req_mixed_ahead": 10})
S1.update({"req_blocks_ahead": 232, "req_mixed_ahead": 290})
TPU = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
WANT = {
    "engine.host_busy_share": 100 * (1.0 + 3.0 + 2.0 + 1.0 + 2.0) / 50,
    "engine.pack_ms": 1000 * 3.0 / 500,
    "engine.put_ms": 1000 * 2.0 / 1000,
    "engine.put_arrays_per_span": 1250 / 1000,
    "engine.emit_ms": 1000 * 2.0 / 400,
    "engine.slow_spans": 3.0,
    "step.decode_block_ms": 1000 * 40.4 / 400,
    "step.mixed_ms": 1000 * 3.2 / 200,
    "step_mfu": 12.0,
    "step.hbm_roofline_share": 75.0,
    "sched.queue_wait_ms": 1000 * 6.0 / 200,
    "engine.admit_to_first_token_ms": 1000 * 14.0 / 200,
}
#: the six of PR 56, which list no cells: a metric that moves `ttft_p95_ms`
#: is the Mixtral cell's by run.py:load_cell's rule, another every cell's
WANT_EVERY_CELL = {
    "frontend.preprocess_ms": 1000 * 2.0 / 200,
    "frontend.inbound_ms": 1000 * (0.2 + 2.0 + 0.02 + 0.04 + 0.06) / 200,
    "plane.request_hop_ms": 1000 * 0.06 / 200,
    "plane.first_frame_ms": 1000 * 0.1 / 200,
    "engine.ingest_ms": 1000 * 0.8 / 200,
    "sched.entries_before_first_token": (220 + 280) / 200,
}
WANT.update(WANT_EVERY_CELL)


@pytest.mark.parametrize("name", sorted(WANT))
def test_a_new_layer_metric_reads_the_tables_arithmetic(name):
    lm = _layer_metrics()
    ctx = {"stats0": S0, "stats1": S1, "stats2": S1, "trace": None,
           "seconds": 50.0, "device": TPU, "client": {}, "end_to_end": {}}
    assert lm.read(name, ctx) == pytest.approx(WANT[name], rel=1e-9)
    # a program without the spans and counters (the parent of the PR that
    # brought them; benchmark/selftest.py's context, which has no `device`):
    # nothing to read, and nothing raised
    old = {"mixed_steps": 4, "emit_tokens": 9, "compiled_variants": 38}
    assert lm.read(name, {"stats0": old, "stats1": old, "stats2": old,
                          "trace": None, "seconds": 50.0}) is None
    assert lm.read(name, dict(ctx, stats0=old, stats1=old, stats2=old)) is None
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(m for m in json.load(f)["per_layer"] if m["name"] == name)
    spec = lm.load(name)
    assert {k: entry[k] for k in ("unit", "better", "source", "layer", "moves")} \
        == {k: spec[k] for k in ("unit", "better", "source", "layer", "moves")}
    assert ("workloads" not in entry) == (name in WANT_EVERY_CELL)
    if name in WANT_EVERY_CELL:
        assert spec["better"] == "lower" and "expr" in spec


def test_a_share_of_a_peak_needs_a_chip_the_table_knows():
    lm = _layer_metrics()
    ctx = {"stats0": S0, "stats1": S1, "stats2": S1, "trace": None}
    for name in ("step_mfu", "step.hbm_roofline_share"):
        # a CPU run has no share of a chip's peak
        cpu = {"platform": "cpu", "kind": "cpu", "count": 1}
        assert lm.read(name, dict(ctx, device=cpu)) is None
        with pytest.raises(KeyError):  # an error, not a default
            lm.read(name, dict(ctx, device=dict(TPU, kind="TPU v9 mega")))
    with open(os.path.join(ROOT, "benchmark", "peaks.json")) as f:
        peaks = json.load(f)
    assert peaks["TPU v5 lite"]["bf16_flops"] == 197e12
    assert peaks["TPU v5 lite"]["hbm_bytes_s"] == 819e9
