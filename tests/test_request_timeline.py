"""A request's timeline (docs/observability.md, "A request's path"): stage
times stamped where the work happens, from HttpService's first line through
the request plane to the engine's slot and back on the frame of the first
token. In-process frontend, request plane and a tiny engine; CPU."""

import asyncio
import logging
import time

import pytest

from dynamo_tpu.engine.recorder import STAGES as WORKER_ROWS
from dynamo_tpu.llm.backend import Backend
from dynamo_tpu.llm.discovery import ModelManager
from dynamo_tpu.llm.http import metrics as http_metrics
from dynamo_tpu.llm.http.service import HttpService
from dynamo_tpu.llm.migration import Migration
from dynamo_tpu.llm.model_card import ModelDeploymentCard
from dynamo_tpu.llm.protocols import PreprocessedRequest
from dynamo_tpu.llm.service import ModelPipeline, ServiceBackend
from dynamo_tpu.llm.tokenizers import load_tokenizer
from dynamo_tpu.runtime import codec, request_plane
from dynamo_tpu.runtime.engine import BOOT_ID, Context
from dynamo_tpu.runtime.pipeline import compose
from dynamo_tpu.runtime.request_plane import RequestPlaneClient, RequestPlaneServer

from .test_engine_recorder import _engine as _recorder_engine, _prompt, _settled

SUBJECT = "ns.backend.generate"
FRONTEND = ("http", "preprocess", "route", "send")


def _engine(**over):
    return _recorder_engine("dense", **over)


def _request(n_prompt, n, rid, seed=0):
    return PreprocessedRequest(
        token_ids=_prompt(n_prompt, seed),
        stop_conditions={"max_tokens": n, "ignore_eos": True},
        sampling_options={"temperature": 0.0}, request_id=rid,
    ).to_dict()


class _Direct:
    """The router's place: every request to the one worker there is."""

    def __init__(self, client, address):
        self.client, self.address = client, address

    async def generate(self, request, context):
        return await self.client.call(self.address, SUBJECT, request, context)


class _Stack:
    """HttpService -> preprocessor -> backend -> request plane -> engine, in
    one process and on one event loop."""

    async def __aenter__(self):
        self.eng = _engine()
        self.server = RequestPlaneServer()
        self.server.register(SUBJECT, self.eng.generate)
        host, port = await self.server.start()
        self.client = RequestPlaneClient()
        card = ModelDeploymentCard(name="tiny", tokenizer="byte", context_length=256)
        tok = load_tokenizer("byte")
        sink = ServiceBackend(_Direct(self.client, f"{host}:{port}"))
        pipeline = ModelPipeline(
            card, tok, compose([Backend(tokenizer=tok), Migration(migration_limit=0)], sink))
        manager = ModelManager()

        class _NoClient:
            def instance_ids(self):
                return []

        manager.add("tiny", pipeline, _NoClient())
        self.service = HttpService(manager, host="127.0.0.1", port=0)
        self.base = f"http://127.0.0.1:{await self.service.start()}"
        # every first token, as the frontend saw it: (stages, time to it)
        self.seen = []
        first_token = self.service.metrics.first_token

        def logged(model, ctx, t0, now):
            first_token(model, ctx, t0, now)
            self.seen.append((dict(ctx.stages), now - t0))

        self.service.metrics.first_token = logged
        return self

    async def __aexit__(self, *exc):
        await self.service.stop()
        await self.client.close()
        await self.server.stop()
        await _settled(self.eng)

    async def complete(self, session, prompt, n, stream):
        body = {"model": "tiny", "prompt": prompt, "max_tokens": n, "stream": stream,
                "ignore_eos": True, "temperature": 0.0}
        async with session.post(f"{self.base}/v1/completions", json=body) as r:
            assert r.status == 200, await r.text()
            return await r.text()

    def histogram_counts(self):
        out = {}
        for line in self.service.metrics.render().decode().splitlines():
            if line.startswith("dynamo_frontend_stage_seconds_count{"):
                out[line.split('stage="')[1].split('"')[0]] = float(line.rsplit(" ", 1)[1])
        return out


def test_a_served_request_leaves_every_stage_counted_once_and_no_stamp_a_token():
    """Three requests over HTTP, streamed and not. The worker's recorder
    holds every stage once a request, the frontend's histogram every stage
    of the whole path, and each request's stages add up to no more than the
    frontend's own time to its first token: they are differences of
    monotonic stamps that follow one another. How many tokens a request
    makes changes nothing about how often it is stamped."""
    import aiohttp

    stamps = []
    stamp = Context.stamp

    def counted(self, stage, now=None):
        stamps.append(stage)
        return stamp(self, stage, now)

    async def run():
        async with _Stack() as st, aiohttp.ClientSession() as session:
            await st.complete(session, "warm the programs", 4, False)
            before = st.eng.stats()
            Context.stamp = counted
            try:
                per_request = []
                for prompt, n, stream in (("hello there", 3, True),
                                          ("a longer prompt, and more tokens", 30, True),
                                          ("unary", 5, False)):
                    del stamps[:]
                    text = await st.complete(session, prompt, n, stream)
                    assert ("[DONE]" in text) == stream
                    per_request.append(list(stamps))
            finally:
                Context.stamp = stamp
            return before, st.eng.stats(), st.seen[1:], st.histogram_counts(), per_request

    before, after, seen, hist, per_request = asyncio.run(run())
    for stage in WORKER_ROWS:
        assert after[f"req_stage_{stage}_count"] - before[f"req_stage_{stage}_count"] == 3, stage
        assert after[f"req_stage_{stage}_s"] >= before[f"req_stage_{stage}_s"] >= 0, stage
    assert after["req_hop_unmeasured"] == 0
    assert after["req_first_tokens"] - before["req_first_tokens"] == 3
    assert hist == {stage: 4.0 for stage in http_metrics.STAGES}
    assert len(seen) == 3
    for stages, ttft in seen:
        assert set(stages) == set(http_metrics.STAGES)
        assert all(v >= 0 for v in stages.values())
        assert sum(stages.values()) <= ttft
    # the same stamps whatever the request makes: 3 tokens, 30, or 5 unary
    want = ["http", "route", "preprocess", "route", "send", "ingest", "queue",
            "first", "first_frame", "sse"]
    assert per_request == [want, want, want]


def _raw_request(timeline):
    """One request written onto the request plane by hand, as a caller of
    any age might: (the frames' control headers, the engine's stats)."""

    async def run():
        eng = _engine()
        server = RequestPlaneServer()
        server.register(SUBJECT, eng.generate)
        host, port = await server.start()
        reader, writer = await asyncio.open_connection(host, port)
        control = {"t": codec.T_REQ, "stream": 1, "subject": SUBJECT}
        if timeline is not None:
            control["timeline"] = timeline
        await codec.write_frame(writer, control, codec.pack(_request(12, 6, "raw")))
        frames, tokens = [], 0
        while True:
            control, payload = await codec.read_frame(reader)
            frames.append(control)
            if control["t"] != codec.T_DATA:
                break
            items = codec.unpack(payload) if control.get("n") else [codec.unpack(payload)]
            tokens += sum(len(i["data"].get("token_ids") or []) for i in items)
        writer.close()
        await server.stop()
        await _settled(eng)
        return frames, tokens, eng.stats()

    return asyncio.run(run())


def test_another_hosts_timeline_leaves_the_hop_out():
    sent = {"http": 0.001, "preprocess": 0.002, "route": 0.0005, "send": 0.0001}
    frames, tokens, out = _raw_request(
        {"s": sent, "at": 12345.0, "boot": "another-host's"})
    assert frames[-1]["t"] == codec.T_DONE and tokens == 6
    assert out["req_hop_unmeasured"] == 1 and out["req_stage_hop_count"] == 0
    for stage, seconds in sent.items():
        assert out[f"req_stage_{stage}_count"] == 1
        assert out[f"req_stage_{stage}_s"] == pytest.approx(seconds)
    assert out["req_stage_ingest_count"] == out["req_stage_first_frame_count"] == 1
    # the worker's stages ride ONE frame, the first token's, and name no hop
    carrying = [f["stages"] for f in frames if "stages" in f]
    assert len(carrying) == 1
    assert set(carrying[0]) == {"ingest", "queue", "first", "first_frame"}
    # the same host's: the hop is the difference of the two stamps
    at = time.monotonic()
    frames, _, out = _raw_request({"s": sent, "at": at, "boot": BOOT_ID})
    assert out["req_hop_unmeasured"] == 0 and out["req_stage_hop_count"] == 1
    assert 0 <= out["req_stage_hop_s"] < time.monotonic() - at
    assert "hop" in next(f["stages"] for f in frames if "stages" in f)


@pytest.mark.parametrize("bad", [float("nan"), float("inf"), -0.001, "0.1", None])
def test_what_is_no_count_of_seconds_never_reaches_a_counter(bad):
    """The stage rows only grow: a NaN, an infinity or a negative number off
    the wire is left out, in the table and as the sender's clock."""
    sent = {"http": 0.001, "preprocess": bad, "send": 0.0001}
    frames, tokens, out = _raw_request({"s": sent, "at": bad, "boot": BOOT_ID})
    assert frames[-1]["t"] == codec.T_DONE and tokens == 6
    assert out["req_stage_http_count"] == out["req_stage_send_count"] == 1
    assert out["req_stage_preprocess_count"] == out["req_stage_hop_count"] == 0
    assert out["req_hop_unmeasured"] == 1
    total = sum(out[f"req_stage_{s}_s"] for s in WORKER_ROWS)
    assert 0 <= total < float("inf")


@pytest.mark.parametrize("timeline", [None, "not a table", {"s": "nor this"}])
def test_a_caller_that_sends_no_timeline_is_served_and_counts_nothing(timeline):
    frames, tokens, out = _raw_request(timeline)
    assert frames[-1]["t"] == codec.T_DONE and tokens == 6
    # (a table with nothing usable in it still began a timeline: the
    # worker's own stages are kept, the caller's are not made up)
    began = isinstance(timeline, dict)
    assert sum("stages" in f for f in frames) == int(began)
    assert out["req_stage_ingest_count"] == int(began)
    assert all(out[f"req_stage_{s}_count"] == 0 for s in (*FRONTEND, "hop"))
    assert out["req_hop_unmeasured"] == 0
    assert out["req_admitted"] == out["req_first_tokens"] == 1


def test_a_worker_that_ignores_the_timeline_serves_as_before(caplog):
    """An older worker: it reads `req`, knows no `timeline`, answers with
    frames that carry no `stages`. The caller's own stages stand, `sse` is
    not closed (nothing came back to close it from), and a first token later
    than SLOW_FIRST_TOKEN_S is logged with what there is."""

    async def old_worker(reader, writer):
        control, payload = await codec.read_frame(reader)
        assert "timeline" in control  # sent, and ignored
        for tok in (7, 8):
            await codec.write_frame(
                writer, {"t": codec.T_DATA, "stream": control["stream"]},
                codec.pack({"data": {"token_ids": [tok]}}))
        await codec.write_frame(writer, {"t": codec.T_DONE, "stream": control["stream"]})

    async def run():
        server = await asyncio.start_server(old_worker, "127.0.0.1", 0)
        port = server.sockets[0].getsockname()[1]
        client = RequestPlaneClient()
        t0 = time.monotonic() - http_metrics.SLOW_FIRST_TOKEN_S
        ctx = Context(id="slow-one").begin(t0)
        ctx.stamp("http")
        stream = await client.call(f"127.0.0.1:{port}", SUBJECT, {"token_ids": [1]}, ctx)
        items = [item async for item in stream]
        metrics = http_metrics.HttpMetrics()
        metrics.first_token("m", ctx, t0, time.monotonic())
        await client.close()
        server.close()
        return items, ctx

    with caplog.at_level(logging.WARNING, logger=http_metrics.__name__):
        items, ctx = asyncio.run(run())
    assert [i["data"]["token_ids"] for i in items] == [[7], [8]]
    assert set(ctx.stages) == {"http", "route", "send"}
    lines = [r.getMessage() for r in caplog.records]
    assert len(lines) == 1
    assert lines[0].startswith("request slow-one first token after 2.")
    assert " s: http 2." in lines[0] and ", send 0.0" in lines[0]
    assert "sse" not in lines[0] and ", other 0.0" in lines[0]


def _timed(rid):
    """A context whose timeline began as the request plane's server begins
    one: at the arrival."""
    return Context(id=rid).begin(time.monotonic())


async def _drain(stream):
    return [t async for out in stream
            for t in (out.get("data") or {}).get("token_ids", [])]


def test_the_ingest_of_a_slow_slot_shows_in_ingest_and_not_in_the_queues_wait():
    eng = _engine()
    new_slot = eng._new_slot

    def slow(req, context, suffix=""):
        time.sleep(0.2)  # a long prompt's hashing
        return new_slot(req, context, suffix)

    eng._new_slot = slow

    async def run():
        toks = await _drain(eng.generate(_request(12, 5, "slow"), _timed("slow")))
        await _settled(eng)
        return toks

    assert len(asyncio.run(run())) == 5
    out = eng.stats()
    assert out["req_stage_ingest_count"] == 1 and out["req_stage_ingest_s"] >= 0.2
    assert out["req_queue_wait_s"] < 0.2


def test_a_preempted_resume_counts_its_stages_once():
    eng = _engine(num_pages=10, max_num_seqs=2, enable_prefix_caching=False)
    preempted = []
    preempt = eng._preempt_one

    def logged(exclude_idx):
        preempted.append(preempt(exclude_idx))
        return preempted[-1]

    eng._preempt_one = logged
    contexts = [_timed("p0"), _timed("p1")]

    async def run():
        outs = await asyncio.gather(*(
            _drain(eng.generate(_request(20, 36, c.id, seed=i), c))
            for i, c in enumerate(contexts)))
        await _settled(eng)
        return outs

    assert [len(o) for o in asyncio.run(run())] == [36, 36]
    assert any(preempted), "the pool was to run out: make the case tighter"
    out = eng.stats()
    assert out["req_stage_ingest_count"] == out["req_first_tokens"] == 2
    for ctx in contexts:
        assert set(ctx.stages) == {"ingest", "queue", "first"}


def test_a_disaggregated_decode_entry_counts_its_stages_once():
    """The decode worker's entry makes a slot of its own (`-d`); one that
    falls back to a local prefill makes a second for the same request: the
    context has been counted, and is not counted again."""
    eng = _engine()
    ctx = _timed("d0")
    ctx.stages.update({"http": 0.001, "send": 0.0001, "hop": 0.0002})

    async def run():
        req = _request(12, 6, "d0")
        first = await _drain(eng.generate_decode_resume(req, ctx, first_token=17))
        again = await _drain(eng.generate(req, ctx))
        await _settled(eng)
        return first, again

    first, again = asyncio.run(run())
    assert len(first) == 5 and len(again) == 6  # the entry's first token came from elsewhere
    out = eng.stats()
    for stage in ("http", "send", "hop", "ingest"):
        assert out[f"req_stage_{stage}_count"] == 1, stage
    assert out["req_hop_unmeasured"] == 0
    assert out["req_admitted"] == 2  # two slots, as before


def test_a_requests_stamps_cost_under_twenty_microseconds_with_no_profiler_open():
    """Everything one request adds between the accept and the first SSE
    write, end to end and in one thread: the frontend's stamps, the table
    onto the header and off it, the recorder's arrival, admission and first
    token, the stages onto the first frame and off it (the span
    `engine.ingest` with no profiler open is in it; the histogram's ten
    observations, which a scrape reads and no request waits on, are not)."""
    from dynamo_tpu.engine.recorder import Recorder

    class Slot:
        admit_s = first_token_s = arrival_s = 0.0

    rec = Recorder(lambda: (0, 1, 0))
    # the two contexts are no cost of the timeline's: a request had them
    ctx, worker = Context(), Context()

    def one_request():
        for c in (ctx, worker):
            c.stages.clear()
            c.stamp_s, c.on_stamp = 0.0, None
        t0 = time.monotonic()
        ctx.begin(t0)
        for stage in ("http", "route", "preprocess", "route"):
            ctx.stamp(stage)
        control = {"timeline": {"s": dict(ctx.stages), "at": ctx.stamp("send"),
                                "boot": BOOT_ID}}
        request_plane._take_timeline(worker, control["timeline"], time.monotonic())
        slot = Slot()
        slot.context = worker
        with rec.ingest():
            pass
        rec.arrived(slot)
        rec.admitted(slot)
        rec.first_token(slot)
        frame = {"stages": request_plane._worker_stages(worker, time.monotonic()),
                 "read_s": time.monotonic()}
        worker.stamp("first_frame")
        request_plane._merge_stages(ctx, frame)
        ctx.stamp("sse")
        return ctx

    assert set(one_request().stages) == set(http_metrics.STAGES)
    n, best = 2_000, float("inf")
    for _ in range(15):  # the best of many short rounds: the other workers' noise aside
        t0 = time.perf_counter()
        for _ in range(n):
            one_request()
        best = min(best, (time.perf_counter() - t0) / n)
    assert rec.stages["first_frame"][0] == 15 * n + 1
    assert best < 20e-6, f"{best * 1e6:.2f} us a request"
