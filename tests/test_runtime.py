"""Tests for the distributed runtime: codec, discovery, components, routing.

Mirrors the reference's runtime unit-test strategy (SURVEY.md §4): in-process
servers, echo engines, lease-expiry and cancellation behaviors.
"""

import asyncio
import time

import pytest

from dynamo_tpu.runtime import (
    Context,
    DiscoveryClient,
    DiscoveryServer,
    DistributedRuntime,
    PushRouter,
    RouterMode,
    RuntimeConfig,
    StreamLost,
    codec,
    parse_traceparent,
)
from dynamo_tpu.runtime.codec import decode_frame, encode_frame


def test_codec_roundtrip():
    control = {"t": "req", "stream": 7, "subject": "ns.comp.ep"}
    payload = codec.pack({"token_ids": list(range(100)), "text": "héllo"})
    frame = encode_frame(control, payload)
    c2, p2 = decode_frame(frame)
    assert c2 == control
    assert codec.unpack(p2)["text"] == "héllo"


@pytest.mark.parametrize("check", ["close_completes", "loop_idles"])
def test_write_frame_bodyless_frame_leaves_transport_empty(check):
    """Every control frame has no payload. write_frame must not hand the
    transport a zero-length segment: CPython 3.12 never pops it off the
    write buffer, so the loop spins on sendmsg and close() never ends."""

    async def main():
        got = asyncio.Queue()

        async def handler(reader, writer):
            await got.put(await codec.read_frame(reader))
            await reader.read()  # until the peer goes away
            writer.close()

        server = await asyncio.start_server(handler, "127.0.0.1", 0)
        port = server.sockets[0].getsockname()[1]
        _, writer = await asyncio.open_connection("127.0.0.1", port)
        try:
            control = {"t": codec.T_DONE, "stream": 1}
            await codec.write_frame(writer, control)
            assert await asyncio.wait_for(got.get(), 2) == (control, b"")
            if check == "close_completes":
                writer.close()
                await asyncio.wait_for(writer.wait_closed(), 2)
            else:
                # the loop runs in this thread: its CPU time is the spin's
                # (the process's would count other tests' leftover threads)
                cpu = time.thread_time()
                await asyncio.sleep(1.0)
                assert time.thread_time() - cpu < 0.2
        finally:
            writer.transport.abort()  # drops whatever a failure left buffered
            server.close()
            await server.wait_closed()

    asyncio.run(main())


def test_traceparent():
    ctx = parse_traceparent("00-0af7651916cd43dd8448eb211c80319c-b7ad6b7169203331-01")
    assert ctx is not None and ctx.trace_id.startswith("0af76519")
    assert parse_traceparent("garbage") is None
    assert parse_traceparent("00-short-b7ad6b7169203331-01") is None


def test_discovery_kv_and_watch():
    async def main():
        server = DiscoveryServer(port=0)
        host, port = await server.start()
        client = await DiscoveryClient.connect(host, port)

        await client.put("v1/a/one", b"1")
        assert await client.get("v1/a/one") == b"1"
        assert await client.get("v1/a/missing") is None

        # atomic create
        assert await client.create("v1/a/two", b"2") is True
        assert await client.create("v1/a/two", b"x") is False

        watch = await client.watch_prefix("v1/a/")
        assert {i["key"] for i in watch.snapshot} == {"v1/a/one", "v1/a/two"}

        await client.put("v1/a/three", b"3")
        ev = await watch.get(timeout=2)
        assert ev.type == "put" and ev.key == "v1/a/three" and ev.value == b"3"

        await client.delete("v1/a/one")
        ev = await watch.get(timeout=2)
        assert ev.type == "delete" and ev.key == "v1/a/one"

        items = await client.get_prefix("v1/a/")
        assert {i["key"] for i in items} == {"v1/a/two", "v1/a/three"}

        await watch.cancel()
        await client.close()
        await server.stop()

    asyncio.run(main())


def test_discovery_lease_expiry_deletes_keys():
    async def main():
        server = DiscoveryServer(port=0)
        host, port = await server.start()
        client = await DiscoveryClient.connect(host, port)
        lease = await client.grant_lease(ttl=0.6, keepalive=False)
        await client.put("v1/leased/k", b"v", lease)
        assert await client.get("v1/leased/k") == b"v"

        watch = await client.watch_prefix("v1/leased/")
        ev = await watch.get(timeout=3)
        assert ev is not None and ev.type == "delete"  # lease expired
        assert await client.get("v1/leased/k") is None
        await client.close()
        await server.stop()

    asyncio.run(main())


def _drt_config(port: int) -> RuntimeConfig:
    cfg = RuntimeConfig()
    cfg.discovery_endpoint = f"tcp://127.0.0.1:{port}"
    return cfg


async def _echo_handler(request, context: Context):
    for tok in request["tokens"]:
        yield {"tok": tok}


async def _slow_handler(request, context: Context):
    for i in range(1000):
        if context.is_stopped():
            yield {"cancelled": True}
            return
        yield {"i": i}
        await asyncio.sleep(0.01)


def test_endpoint_serve_and_client_roundtrip():
    async def main():
        server = DiscoveryServer(port=0)
        host, port = await server.start()
        cfg = _drt_config(port)

        worker = await DistributedRuntime.create(cfg)
        ep = worker.namespace("test").component("echo").endpoint("generate")
        served = await ep.serve_endpoint(_echo_handler)

        frontend = await DistributedRuntime.create(cfg)
        client = await frontend.namespace("test").component("echo").endpoint("generate").client()
        ids = await client.wait_for_instances(timeout=5)
        assert ids == [worker.instance_id]

        stream = await client.direct({"tokens": [1, 2, 3]}, worker.instance_id)
        out = [item async for item in stream]
        assert out == [{"tok": 1}, {"tok": 2}, {"tok": 3}]
        assert served.stats.requests_total == 1

        # instance disappears when the worker closes (lease revoke)
        await worker.close()
        await asyncio.sleep(0.2)
        assert client.instance_ids() == []

        await frontend.close()
        await server.stop()

    asyncio.run(main())


def test_push_router_round_robin_and_failover():
    async def main():
        server = DiscoveryServer(port=0)
        host, port = await server.start()
        cfg = _drt_config(port)

        async def tagged(tag):
            async def handler(request, context):
                yield {"worker": tag}

            return handler

        w1 = await DistributedRuntime.create(cfg)
        await w1.namespace("t").component("c").endpoint("e").serve_endpoint(await tagged("w1"))
        w2 = await DistributedRuntime.create(cfg)
        await w2.namespace("t").component("c").endpoint("e").serve_endpoint(await tagged("w2"))

        fe = await DistributedRuntime.create(cfg)
        client = await fe.namespace("t").component("c").endpoint("e").client()
        await client.wait_for_instances()
        router = PushRouter(client, RouterMode.ROUND_ROBIN)

        seen = set()
        for _ in range(4):
            stream = await router.generate({})
            async for item in stream:
                seen.add(item["worker"])
        assert seen == {"w1", "w2"}

        # kill w1 hard (no graceful close) — router should fail over
        w1.server._server.close()
        for conn in list(fe.client._conns.values()):
            conn.writer.close()
        fe.client._conns.clear()
        results = set()
        for _ in range(4):
            stream = await router.generate({})
            async for item in stream:
                results.add(item["worker"])
        assert results == {"w2"}

        for drt in (w1, w2, fe):
            await drt.close()
        await server.stop()

    asyncio.run(main())


def test_cancellation_propagates_to_worker():
    async def main():
        server = DiscoveryServer(port=0)
        host, port = await server.start()
        cfg = _drt_config(port)

        worker = await DistributedRuntime.create(cfg)
        await worker.namespace("t").component("slow").endpoint("e").serve_endpoint(_slow_handler)

        fe = await DistributedRuntime.create(cfg)
        client = await fe.namespace("t").component("slow").endpoint("e").client()
        await client.wait_for_instances()

        ctx = Context()
        stream = await client.direct({}, worker.instance_id, ctx)
        got = []
        async for item in stream:
            got.append(item)
            if len(got) == 3:
                ctx.stop_generating()
            if item.get("cancelled"):
                break
        assert {"cancelled": True} in got
        assert len(got) < 1000

        await worker.close()
        await fe.close()
        await server.stop()

    asyncio.run(main())


def test_stream_lost_on_worker_death():
    async def main():
        server = DiscoveryServer(port=0)
        host, port = await server.start()
        cfg = _drt_config(port)

        worker = await DistributedRuntime.create(cfg)
        await worker.namespace("t").component("dying").endpoint("e").serve_endpoint(_slow_handler)

        fe = await DistributedRuntime.create(cfg)
        client = await fe.namespace("t").component("dying").endpoint("e").client()
        await client.wait_for_instances()

        stream = await client.direct({}, worker.instance_id)
        got = 0
        with pytest.raises(StreamLost):
            async for _item in stream:
                got += 1
                if got == 2:
                    # simulate SIGKILL: close the worker's sockets abruptly
                    await worker.server.stop()
        assert got >= 2

        await fe.close()
        await server.stop()

    asyncio.run(main())


class TestOperatorPipeline:
    """Generic operator graph (runtime/pipeline.py — reference
    lib/runtime/src/pipeline.rs node model)."""

    def test_forward_backward_order_and_around(self):
        from dynamo_tpu.runtime.engine import Context
        from dynamo_tpu.runtime.pipeline import Operator, compose

        calls = []

        class Sink:
            async def generate(self, request, context):
                calls.append(("sink", request))
                yield {"v": request}
                yield {"v": request + "!"}

        class Tag(Operator):
            def __init__(self, label):
                self.label = label

            async def forward(self, request, context):
                calls.append((f"fwd-{self.label}", request))
                return request + self.label

            async def backward(self, stream, request, context):
                async for item in stream:
                    item["v"] += f"<{self.label}"
                    yield item

        class Retry(Operator):
            """around(): owns the sink call — retries once on failure."""

            def __init__(self):
                self.attempts = 0

            def around(self, next_engine, request, context):
                return self._run(next_engine, request, context)

            async def _run(self, next_engine, request, context):
                self.attempts += 1
                async for item in next_engine.generate(request, context):
                    yield item

        retry = Retry()
        pipe = compose([Tag("A"), retry, Tag("B")], Sink())

        async def run():
            return [i async for i in pipe.generate("req", Context())]

        items = asyncio.run(run())
        # forward order A (retry owns the tail, which runs B), sink once
        assert calls == [("fwd-A", "req"), ("fwd-B", "reqA"), ("sink", "reqAB")]
        # backward order: B wraps first (inner), then A
        assert [i["v"] for i in items] == ["reqAB<B<A", "reqAB!<B<A"]
        assert retry.attempts == 1


def test_conn_locks_pruned_with_connections():
    """Regression: `RequestPlaneClient._conn_locks` grew one lock per
    address ever dialed, forever (setdefault, never pruned). Under worker
    churn every replacement instance brings a fresh host:port, so the
    dict must shrink when a connection dies — and a failed dial must not
    leave a lock behind either."""
    from dynamo_tpu.runtime.request_plane import (
        RequestPlaneClient,
        RequestPlaneServer,
    )

    async def main():
        srv = RequestPlaneServer()
        host, port = await srv.start()
        addr = f"{host}:{port}"
        cli = RequestPlaneClient(connect_timeout=0.5)
        try:
            await cli.ping(addr)
            assert addr in cli._conns and addr in cli._conn_locks

            # server dies -> recv loop ends -> both pool and lock pruned
            await srv.stop()
            for _ in range(100):
                if addr not in cli._conn_locks and addr not in cli._conns:
                    break
                await asyncio.sleep(0.02)
            assert addr not in cli._conns
            assert addr not in cli._conn_locks

            # refused dial: no connection, and no lock kept for it
            with pytest.raises(StreamLost):
                await cli.ping(addr, timeout=0.5)
            assert addr not in cli._conn_locks

            # close() leaves nothing behind even with a live entry
            srv2 = RequestPlaneServer()
            host2, port2 = await srv2.start()
            addr2 = f"{host2}:{port2}"
            await cli.ping(addr2)
            assert addr2 in cli._conn_locks
            await cli.close()
            assert cli._conn_locks == {} and cli._conns == {}
            await srv2.stop()
        finally:
            await cli.close()

    asyncio.run(main())


def test_request_plane_ping_pong_roundtrip():
    """Transport liveness probe: ping answers pong with the stream id
    echoed (the flow-frame-protocol symmetry contract), and a dead peer
    surfaces StreamLost within the timeout instead of hanging."""
    from dynamo_tpu.runtime.request_plane import (
        RequestPlaneClient,
        RequestPlaneServer,
    )

    async def main():
        srv = RequestPlaneServer()
        host, port = await srv.start()
        cli = RequestPlaneClient()
        try:
            rtt = await cli.ping(f"{host}:{port}")
            assert 0.0 <= rtt < 5.0
            # repeatable on the same pooled connection
            assert await cli.ping(f"{host}:{port}") >= 0.0
        finally:
            await cli.close()
            await srv.stop()

        # dead peer: refused dial -> StreamLost, not a hang
        dead = RequestPlaneClient(connect_timeout=0.5)
        try:
            with pytest.raises(StreamLost):
                await dead.ping(f"{host}:{port}", timeout=0.5)
        finally:
            await dead.close()

    asyncio.run(main())
