"""ISSUE 4 token-path batching tests: multi-item request-plane frames,
batched incremental detokenization, preserialized SSE chunks, warmup
registration ordering, and stream-semantics preservation end to end.

The contract under test: batching changes CHUNK BOUNDARIES ONLY —
concatenated text, finish reasons, token counts and ordering are identical
to the singleton path, and coalesced streams stay contiguous and
duplicate-free under request_plane.frame faults."""

import asyncio
import json
import random
import time

import httpx
import pytest

from dynamo_tpu.llm.backend import Backend, Decoder, merge_token_deltas
from dynamo_tpu.llm.migration import Migration
from dynamo_tpu.llm.preprocessor import ChatDeltaGenerator, CompletionDeltaGenerator
from dynamo_tpu.llm.protocols import Annotated, LLMEngineOutput, PreprocessedRequest
from dynamo_tpu.llm.tokenizers import ByteTokenizer
from dynamo_tpu.runtime import faults
from dynamo_tpu.runtime.component import DistributedRuntime
from dynamo_tpu.runtime.config import RuntimeConfig
from dynamo_tpu.runtime.discovery import DiscoveryServer
from dynamo_tpu.runtime.engine import Context
from dynamo_tpu.runtime.push_router import PushRouter, RouterMode
from dynamo_tpu.runtime.request_plane import (
    RequestPlaneClient,
    RequestPlaneServer,
)

from .utils import ManagedProcess, free_port


# --------------------------------------------------------------------------- #
# request plane: multi-item frames
# --------------------------------------------------------------------------- #


def test_multi_item_frames_preserve_order_and_coalesce(monkeypatch):
    """A same-tick burst coalesces into fewer frames; item order and the
    full item set are exactly preserved across the wire."""
    monkeypatch.setenv("DYN_STREAM_COALESCE_MS", "0")

    async def main():
        srv = RequestPlaneServer()

        async def handler(req, ctx):
            for i in range(32):
                yield {"i": i}
            await asyncio.sleep(0.03)  # writer drains the burst first
            yield {"i": 32}

        stats = srv.register("t.gen", handler)
        host, port = await srv.start()
        cli = RequestPlaneClient()
        try:
            stream = await cli.call(f"{host}:{port}", "t.gen", {})
            got = [item["i"] async for item in stream]
            assert got == list(range(33))
            assert stats.items_total == 33
            # the 32-item burst was enqueued in one tick: frames << items
            assert stats.frames_total < 33
        finally:
            await cli.close()
            await srv.stop()

    asyncio.run(main())


def test_coalesce_max_items_caps_frame_size(monkeypatch):
    monkeypatch.setenv("DYN_STREAM_COALESCE_MS", "5")
    monkeypatch.setenv("DYN_STREAM_COALESCE_MAX_ITEMS", "4")

    async def main():
        srv = RequestPlaneServer()
        assert srv.coalesce_max == 4

        async def handler(req, ctx):
            for i in range(12):
                yield i

        srv.register("t.gen", handler)
        host, port = await srv.start()
        cli = RequestPlaneClient()
        try:
            stream = await cli.call(f"{host}:{port}", "t.gen", {})
            got = [item async for item in stream]
            assert got == list(range(12))
            stats = srv.stats("t.gen")
            assert stats.frames_total >= 3  # 12 items / cap 4
        finally:
            await cli.close()
            await srv.stop()

    asyncio.run(main())


def test_cancel_and_kill_arriving_mid_batch(monkeypatch):
    """kill mid-stream while the writer is coalescing: the stream ends
    promptly (no hang, no post-kill items trickling out)."""
    monkeypatch.setenv("DYN_STREAM_COALESCE_MS", "2")

    async def main():
        srv = RequestPlaneServer()

        async def handler(req, ctx):
            i = 0
            while True:
                yield {"i": i}
                i += 1
                await asyncio.sleep(0.001)

        srv.register("t.gen", handler)
        host, port = await srv.start()
        cli = RequestPlaneClient()
        try:
            ctx = Context()
            stream = await cli.call(f"{host}:{port}", "t.gen", {}, ctx)
            seen = []
            async for item in stream:
                seen.append(item["i"])
                if len(seen) == 5:
                    ctx.kill()
            assert seen[:5] == list(range(5))
            # the server must release the stream (kill propagated)
            deadline = time.monotonic() + 5.0
            while srv.active_streams and time.monotonic() < deadline:
                await asyncio.sleep(0.01)
            assert srv.active_streams == 0
        finally:
            await cli.close()
            await srv.stop()

    asyncio.run(main())


# --------------------------------------------------------------------------- #
# batched incremental detokenization
# --------------------------------------------------------------------------- #


def _random_token_stream(rng, tok, n):
    """Token ids exercising multi-byte UTF-8 splits and padded-vocab
    placeholders (the decode edge cases)."""
    ids = []
    for _ in range(n):
        kind = rng.random()
        if kind < 0.6:
            ids.extend(tok.encode(rng.choice("abc xyz,.")))
        elif kind < 0.9:
            ids.extend(tok.encode(rng.choice("é漢🎉ü")))  # 2-4 byte chars
        else:
            ids.append(300 + rng.randrange(100))  # padded-vocab placeholder
    return ids


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_step_batch_equals_repeated_step(seed):
    rng = random.Random(seed)
    tok = ByteTokenizer(512)
    ids = _random_token_stream(rng, tok, 80)

    ref = tok.decode_stream()
    ref_text = "".join(d for i in ids if (d := ref.step(i)))

    batched = tok.decode_stream()
    out, i = [], 0
    while i < len(ids):
        k = rng.randrange(1, 9)
        d = batched.step_batch(ids[i : i + k])
        if d:
            out.append(d)
        i += k
    assert "".join(out) == ref_text


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_decoder_step_batch_equivalence_with_stop_strings(seed):
    """step_batch == repeated step through stop-string holdback, including
    a stop string split across a batch boundary; n_consumed matches the
    per-token hit index."""
    rng = random.Random(100 + seed)
    tok = ByteTokenizer(512)
    stop = ["STOP!", "##"]
    body = _random_token_stream(rng, tok, 30)
    # plant a stop string at a random point so batches straddle it
    ids = body + tok.encode("abcST") + tok.encode("OP!tail-never-seen")

    ref = Decoder(tok, list(stop))
    ref_parts, ref_consumed, ref_hit = [], 0, False
    for t in ids:
        d, hit = ref.step(t)
        ref_consumed += 1
        if d:
            ref_parts.append(d)
        if hit:
            ref_hit = True
            break

    bat = Decoder(tok, list(stop))
    parts, consumed, got_hit = [], 0, False
    i = 0
    while i < len(ids) and not got_hit:
        k = rng.randrange(1, 7)
        d, n, hit = bat.step_batch(ids[i : i + k])
        if d:
            parts.append(d)
        consumed += n
        got_hit = hit
        i += k
    assert got_hit == ref_hit
    assert "".join(parts) == "".join(ref_parts)
    if ref_hit:
        assert consumed == ref_consumed
        assert "STOP!" not in "".join(parts) and "##" not in "".join(parts)


def test_backend_batch_vs_singleton_stream_semantics():
    """The Backend produces identical concatenated text, finish reason and
    token counts whether the engine emitted singletons or one batch."""
    tok = ByteTokenizer(512)
    text = "hello wörld, this is a STOP!never-shown"
    ids = tok.encode(text)

    async def run(items):
        async def stream():
            for it in items:
                yield it
            yield Annotated(
                data=LLMEngineOutput(token_ids=[], finish_reason="length").to_dict()
            ).to_dict()

        req = PreprocessedRequest(
            token_ids=[1], stop_conditions={"stop": ["STOP!"]}
        )
        backend = Backend(tokenizer=tok)
        texts, n_tok, finish = [], 0, None
        async for ann in backend.backward(stream(), req, Context()):
            out = ann.data
            n_tok += len(out.token_ids)
            if out.text:
                texts.append(out.text)
            if out.finish_reason:
                finish = out.finish_reason
        return "".join(texts), n_tok, finish

    singles = [
        Annotated(data=LLMEngineOutput(token_ids=[t]).to_dict()).to_dict()
        for t in ids
    ]
    one_batch = [Annotated(data=LLMEngineOutput(token_ids=list(ids)).to_dict()).to_dict()]

    s_text, s_n, s_fin = asyncio.run(run(singles))
    b_text, b_n, b_fin = asyncio.run(run(one_batch))
    assert s_text == b_text == "hello wörld, this is a "
    assert s_fin == b_fin == "stop"
    assert s_n == b_n  # usage counts stop at the hit token either way


def test_merge_token_deltas_respects_boundaries():
    """Ready token items merge; annotation events, finish chunks and
    logprob-carrying items are never folded in, and order is preserved."""

    async def main():
        items = [
            Annotated(event="worker_instance_id", comment=["ab"]).to_dict(),
            Annotated(data=LLMEngineOutput(token_ids=[1]).to_dict()).to_dict(),
            Annotated(data=LLMEngineOutput(token_ids=[2]).to_dict()).to_dict(),
            Annotated(
                data=LLMEngineOutput(token_ids=[3], log_probs=[-0.5]).to_dict()
            ).to_dict(),
            Annotated(data=LLMEngineOutput(token_ids=[4]).to_dict()).to_dict(),
            Annotated(
                data=LLMEngineOutput(token_ids=[], finish_reason="length").to_dict()
            ).to_dict(),
        ]

        async def stream():
            for it in items:
                yield it

        got = [ann async for ann in merge_token_deltas(stream())]
        assert got[0].event == "worker_instance_id"
        assert got[1].data == {"token_ids": [1, 2]}  # merged pair
        assert got[2].data["log_probs"] == [-0.5]  # logprob item kept alone
        assert got[3].data == {"token_ids": [4]}
        assert got[4].data["finish_reason"] == "length"

    asyncio.run(main())


# --------------------------------------------------------------------------- #
# preserialized SSE chunks
# --------------------------------------------------------------------------- #


def test_chat_chunk_json_matches_pydantic_path():
    a = ChatDeltaGenerator("m odel\"x", "rid", index=2)
    b = ChatDeltaGenerator("m odel\"x", "rid", index=2)
    b.created = a.created
    fast = json.loads(a.text_chunk_json("héllo \"wörld\"\n", 3))
    slow = json.loads(
        b.text_chunk("héllo \"wörld\"\n", 3).model_dump_json(exclude_none=True)
    )
    assert fast == slow
    assert a.completion_tokens == b.completion_tokens == 3
    # second chunk: no role field anymore
    fast2 = json.loads(a.text_chunk_json("x", 1))
    slow2 = json.loads(b.text_chunk("x", 1).model_dump_json(exclude_none=True))
    assert fast2 == slow2
    assert json.loads(a.finish_chunk_json("eos")) == json.loads(
        b.finish_chunk("eos").model_dump_json(exclude_none=True)
    )


def test_completion_chunk_json_matches_pydantic_path():
    a = CompletionDeltaGenerator("model", "rid")
    b = CompletionDeltaGenerator("model", "rid")
    b.created = a.created
    assert json.loads(a.text_chunk_json("sn\"ippet", 2)) == json.loads(
        b.text_chunk("sn\"ippet", 2).model_dump_json(exclude_none=True)
    )
    assert a.completion_tokens == b.completion_tokens
    assert a._chars_sent == b._chars_sent
    assert json.loads(a.finish_chunk_json("length")) == json.loads(
        b.finish_chunk("length").model_dump_json(exclude_none=True)
    )


# --------------------------------------------------------------------------- #
# coalesced streams under request_plane.frame faults (chaos tie-in)
# --------------------------------------------------------------------------- #


def _counting_handler(calls):
    async def handler(request, context):
        calls.append(1)
        toks = request["token_ids"]
        n = int(request["stop_conditions"]["max_tokens"])
        start = len(toks)
        for i in range(n):
            out = LLMEngineOutput(
                token_ids=[start + i],
                finish_reason="length" if i == n - 1 else None,
            ).to_dict()
            yield Annotated(data=out).to_dict()
            await asyncio.sleep(0.001)

    return handler


@pytest.mark.parametrize("plan", [
    "request_plane.frame:sever,after=3,times=2",
    "request_plane.frame:delay,delay=0.05,times=3",
])
def test_coalesced_streams_contiguous_under_frame_faults(monkeypatch, plan):
    """With coalescing ON, frame sever/delay plans must still produce
    contiguous duplicate-free streams (frames commit atomically; migration
    resumes at the batch boundary)."""
    monkeypatch.setenv("DYN_STREAM_COALESCE_MS", "2")

    async def main():
        disc = DiscoveryServer(port=0)
        host, port = await disc.start()
        cfg = RuntimeConfig()
        cfg.discovery_endpoint = f"tcp://{host}:{port}"
        cfg.graceful_shutdown_timeout = 2.0

        calls = []
        workers = []
        for _ in range(2):
            w = await DistributedRuntime.create(cfg)
            await w.namespace("sb").component("bk").endpoint("gen").serve_endpoint(
                _counting_handler(calls)
            )
            workers.append(w)
        fe = await DistributedRuntime.create(cfg)
        client = await fe.namespace("sb").component("bk").endpoint("gen").client()
        await client.wait_for_instances()
        router = PushRouter(client, RouterMode.ROUND_ROBIN)

        class Eng:
            async def generate(self, request, context):
                stream = await router.generate(request.to_dict(), context)
                async for item in stream:
                    yield item

        inj = faults.configure(plan, seed=7)
        try:
            async def run_one(i):
                req = PreprocessedRequest(
                    token_ids=list(range(4 + i)),
                    stop_conditions={"max_tokens": 10},
                    request_id=f"sb-{i}",
                )
                toks, err = [], None
                async for ann in Migration(Eng(), migration_limit=4).generate(
                    req, Context()
                ):
                    if ann.is_error():
                        err = (ann.comment or ["err"])[0]
                    elif ann.data:
                        toks.extend(ann.data.get("token_ids", []))
                return i, toks, err

            results = await asyncio.gather(*(run_one(i) for i in range(6)))
            assert inj.fired_log, "fault plan never fired"
            for i, toks, err in results:
                assert err is None, err
                start = 4 + i
                assert toks == list(range(start, start + 10)), (
                    f"req {i}: stream not contiguous/duplicate-free: {toks}"
                )
        finally:
            faults.reset()
            await client.close()
            for drt in (fe, *workers):
                await drt.close()
            await disc.stop()

    asyncio.run(main())


# --------------------------------------------------------------------------- #
# warmup-before-registration ordering (mocker regression test)
# --------------------------------------------------------------------------- #


def test_mocker_not_routable_until_warmup_done():
    """A mocker with a slow warmup must not appear in the frontend's model
    list (i.e. not be routable) until warmup reports done."""
    http_port = free_port()
    disc = f"tcp://127.0.0.1:{free_port()}"
    fe = ManagedProcess(
        ["-m", "dynamo_tpu.frontend", "--http-port", str(http_port),
         "--embed-discovery", "--discovery", disc],
        name="warmup_fe",
    ).start("/tmp/warmup_fe.log")
    worker = None
    try:
        fe.wait_port(http_port)
        worker = ManagedProcess(
            ["-m", "dynamo_tpu.mocker", "--model-name", "warm-model",
             "--discovery", disc, "--warmup-delay", "3.0"],
            name="warmup_mocker",
        ).start("/tmp/warmup_mocker.log")
        base = f"http://127.0.0.1:{http_port}"
        with httpx.Client(timeout=10) as client:
            # while warmup is running (3s window), the model must be absent
            deadline = time.time() + 2.0
            while time.time() < deadline:
                r = client.get(base + "/v1/models")
                assert r.status_code == 200
                assert r.json()["data"] == [], (
                    "worker routable before warmup completed"
                )
                time.sleep(0.25)
            # after warmup, it registers and serves
            deadline = time.time() + 20.0
            ready = False
            while time.time() < deadline:
                if client.get(base + "/v1/models").json()["data"]:
                    ready = True
                    break
                time.sleep(0.25)
            assert ready, "worker never registered after warmup"
            r = client.post(
                base + "/v1/chat/completions",
                json={"model": "warm-model",
                      "messages": [{"role": "user", "content": "hi"}],
                      "max_tokens": 4},
            )
            assert r.status_code == 200, r.text
        log = open("/tmp/warmup_mocker.log").read()
        assert log.index("warmup done") < log.index("mocker worker up")
    finally:
        fe.stop()
        if worker:
            worker.stop()


# --------------------------------------------------------------------------- #
# ENC_TOK binary token wire path (ISSUE 13, docs/wire_protocol.md)
# --------------------------------------------------------------------------- #


def test_enc_tok_codec_roundtrip_shapes():
    from dynamo_tpu.runtime import codec

    bare = [{"token_ids": [1, 2, 3]}, {"token_ids": [4]}]
    wrapped = [{"data": {"token_ids": [7]}}, {"data": {"token_ids": [8, 9]}}]
    # boundary-exact roundtrip (merge=False)
    assert codec.unpack_token_items(codec.pack_token_items(bare)) == bare
    assert codec.unpack_token_items(
        codec.pack_token_items(wrapped, wrapped=True)
    ) == wrapped
    # merged decode: one item, same ids in order, wrapper preserved
    assert codec.unpack_token_items(
        codec.pack_token_items(bare), merge=True
    ) == [{"token_ids": [1, 2, 3, 4]}]
    assert codec.unpack_token_items(
        codec.pack_token_items(wrapped, wrapped=True), merge=True
    ) == [{"data": {"token_ids": [7, 8, 9]}}]
    # u32 boundary ids survive
    big = [{"token_ids": [0, (1 << 32) - 1]}]
    assert codec.unpack_token_items(codec.pack_token_items(big)) == big

    # shape classifier: only PURE deltas are eligible
    assert codec.token_delta_kind(bare[0]) == 1
    assert codec.token_delta_kind(wrapped[0]) == 2
    assert codec.token_delta_kind({"token_ids": []}) == 0
    assert codec.token_delta_kind(
        {"data": {"token_ids": [1], "finish_reason": "stop"}}
    ) == 0
    assert codec.token_delta_kind({"event": "x", "comment": ["y"]}) == 0
    assert codec.token_delta_kind("nope") == 0

    # unknown flags / inconsistent payloads are rejected, not misread
    payload = codec.pack_token_items(bare)
    broken = payload[:4] + (255).to_bytes(4, "little") + payload[8:]
    with pytest.raises(ValueError):
        codec.unpack_token_items(broken)
    with pytest.raises(ValueError):
        codec.unpack_token_items(payload[:-4])  # lens sum != ids


def test_try_pack_token_run_boundaries():
    from dynamo_tpu.runtime import codec

    # leading run stops at the first non-delta (the finish item)
    items = [{"token_ids": [1]}, {"token_ids": [2]},
             {"token_ids": [3], "finish_reason": "stop"}]
    payload, n = codec.try_pack_token_run(items)
    assert n == 2
    assert codec.unpack_token_items(payload, merge=True) == [
        {"token_ids": [1, 2]}
    ]
    # a wrapper-shape change also ends the run (one shape per frame)
    mixed = [{"token_ids": [1]}, {"data": {"token_ids": [2]}}]
    _, n = codec.try_pack_token_run(mixed)
    assert n == 1
    # non-delta head: the whole batch rides msgpack
    assert codec.try_pack_token_run([{"finish_reason": "x"}]) is None
    # ids the u32 array cannot carry degrade to msgpack, never corrupt
    assert codec.try_pack_token_run([{"token_ids": [-1]}]) is None
    assert codec.try_pack_token_run([{"token_ids": [1 << 33]}]) is None


def test_binary_token_frames_end_to_end(monkeypatch):
    """Engine-shaped token deltas ride ENC_TOK frames (counted), the
    trailing finish item falls back to msgpack, and the client's merged
    decode preserves token order/count exactly."""
    monkeypatch.setenv("DYN_WIRE_BINARY_TOKENS", "1")
    monkeypatch.setenv("DYN_STREAM_COALESCE_MS", "5")

    async def main():
        srv = RequestPlaneServer()

        async def handler(req, ctx):
            for i in range(24):
                yield {"data": {"token_ids": [100 + i]}}
            yield {"data": {"token_ids": [], "finish_reason": "stop"}}

        stats = srv.register("t.gen", handler)
        host, port = await srv.start()
        cli = RequestPlaneClient()
        assert cli.binary_tokens
        try:
            stream = await cli.call(f"{host}:{port}", "t.gen", {})
            got = [it async for it in stream]
            ids = [t for it in got if "token_ids" in it.get("data", {})
                   for t in it["data"]["token_ids"]]
            assert ids == [100 + i for i in range(24)]
            assert got[-1]["data"]["finish_reason"] == "stop"
            assert stats.frames_binary >= 1
            assert stats.items_total == 25
        finally:
            await cli.close()
            await srv.stop()

    asyncio.run(main())


def test_binary_negotiation_client_opt_out(monkeypatch):
    """DYN_WIRE_BINARY_TOKENS=0: the client never advertises ENC_TOK and
    the server answers pure msgpack — the A/B baseline arm."""
    monkeypatch.setenv("DYN_WIRE_BINARY_TOKENS", "0")

    async def main():
        srv = RequestPlaneServer()

        async def handler(req, ctx):
            for i in range(8):
                yield {"data": {"token_ids": [i]}}

        stats = srv.register("t.gen", handler)
        host, port = await srv.start()
        cli = RequestPlaneClient()
        assert not cli.binary_tokens
        try:
            stream = await cli.call(f"{host}:{port}", "t.gen", {})
            got = [it async for it in stream]
            total = sum(len(it["data"]["token_ids"]) for it in got)
            assert total == 8
            assert stats.frames_binary == 0
        finally:
            await cli.close()
            await srv.stop()

    asyncio.run(main())


def test_unknown_payload_encoding_is_typed_error():
    """A frame with an enc this client doesn't speak must raise a typed
    EngineError (version skew), never silently misread the payload."""
    from dynamo_tpu.runtime import codec as _codec
    from dynamo_tpu.runtime.request_plane import EngineError

    async def main():
        async def serve(reader, writer):
            try:
                frame = await _codec.read_frame(reader)
                control, _ = frame
                sid = control["stream"]
                await _codec.write_frame(
                    writer, {"t": "data", "stream": sid, "n": 1, "enc": "zzz"},
                    b"\x00" * 8,
                )
                await reader.read()  # until the client goes away
            finally:
                writer.close()  # 3.12 wait_closed() waits for open transports

        server = await asyncio.start_server(serve, "127.0.0.1", 0)
        port = server.sockets[0].getsockname()[1]
        cli = RequestPlaneClient()
        try:
            stream = await cli.call(f"127.0.0.1:{port}", "t.gen", {})
            with pytest.raises(EngineError, match="unknown payload encoding"):
                async for _ in stream:
                    pass
        finally:
            await cli.close()
            server.close()
            await server.wait_closed()

    asyncio.run(main())


# --------------------------------------------------------------------------- #
# detok compute-pool offload (DYN_DETOK_POOL, docs/frontend_scaleout.md)
# --------------------------------------------------------------------------- #


def _run_backend(items, stop, pool_env):
    import os

    os.environ["DYN_DETOK_POOL"] = pool_env
    os.environ["DYN_DETOK_POOL_MIN_TOKENS"] = "4"
    try:
        async def main():
            async def stream():
                for it in items:
                    yield it
                yield Annotated(data=LLMEngineOutput(
                    token_ids=[], finish_reason="length").to_dict()).to_dict()

            req = PreprocessedRequest(
                token_ids=[1],
                stop_conditions={"stop": stop} if stop else {},
            )
            backend = Backend(tokenizer=ByteTokenizer(512))
            out_texts, n_tok, finish = [], 0, None
            async for ann in backend.backward(stream(), req, Context()):
                out = ann.data
                n_tok += len(out.token_ids)
                if out.text:
                    out_texts.append(out.text)
                if out.finish_reason:
                    finish = out.finish_reason
            return "".join(out_texts), n_tok, finish

        return asyncio.run(main())
    finally:
        import os

        os.environ.pop("DYN_DETOK_POOL", None)
        os.environ.pop("DYN_DETOK_POOL_MIN_TOKENS", None)


@pytest.mark.parametrize("stop", [[], ["STOP!"]])
def test_detok_pool_matches_inline(stop):
    """Pool on/off is byte-identical — same text, token counts, finish —
    for big batches (pool path) and singletons (inline path), with and
    without stop strings."""
    tok = ByteTokenizer(512)
    ids = tok.encode("pooled detök batch, then a STOP!never-seen tail")
    batch = [Annotated(data=LLMEngineOutput(
        token_ids=list(ids)).to_dict()).to_dict()]
    singles = [Annotated(data=LLMEngineOutput(
        token_ids=[t]).to_dict()).to_dict() for t in ids]

    ref = _run_backend(batch, stop, "0")
    for items in (batch, singles):
        got = _run_backend(items, stop, "1")
        # singleton emission differs from one batch only in chunking; the
        # reference tuple (text, tokens, finish) must match everywhere
        assert got == ref


def test_detok_pool_actually_engages():
    """A batch >= DYN_DETOK_POOL_MIN_TOKENS runs on the compute pool (the
    stall-isolation contract is meaningless if the offload silently never
    happens)."""
    from dynamo_tpu.runtime.compute import ComputePool

    tok = ByteTokenizer(512)
    ids = tok.encode("long enough batch to cross the pool threshold")
    batch = [Annotated(data=LLMEngineOutput(
        token_ids=list(ids)).to_dict()).to_dict()]
    before = ComputePool.get().tasks_run
    _run_backend(batch, [], "1")
    assert ComputePool.get().tasks_run > before
