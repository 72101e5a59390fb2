"""Router extras: sharded indexer, snapshots, event recorder/replay
(reference indexer.rs:992, kv_cache_routing.md snapshots, recorder.rs)."""

import asyncio
import json

import pytest

from dynamo_tpu.llm.kv_router.indexer import (
    KvIndexer,
    KvIndexerSharded,
    RadixTree,
    ROUTER_SNAPSHOT_KEY_FMT,
)
from dynamo_tpu.llm.kv_router.recorder import (
    KvRecorder,
    load_recording,
    replay_into_tree,
    replay_to_topic,
)
from dynamo_tpu.llm.kv_router.publisher import EVENT_TOPIC_FMT
from dynamo_tpu.runtime import (
    DiscoveryServer,
    DistributedRuntime,
    RuntimeConfig,
    codec,
)


def _drt_config(port: int) -> RuntimeConfig:
    cfg = RuntimeConfig()
    cfg.discovery_endpoint = f"tcp://127.0.0.1:{port}"
    return cfg


class TestShardedIndexer:
    def test_matches_merge_across_shards(self):
        idx = KvIndexerSharded(num_shards=4)
        # workers land on different shards (0..3 mod 4)
        idx.apply_stored(0, [1, 2, 3])
        idx.apply_stored(1, [1, 2])
        idx.apply_stored(2, [1])
        scores = idx.find_matches([1, 2, 3])
        assert scores.scores == {0: 3, 1: 2, 2: 1}

    def test_remove_and_dump_load(self):
        idx = KvIndexerSharded(num_shards=3)
        idx.apply_stored(5, [10, 11])
        idx.apply_stored(7, [10])
        idx.remove_worker(5)
        assert idx.find_matches([10]).scores == {7: 1}
        snap = idx.dump()
        idx2 = KvIndexerSharded(num_shards=2)  # shard count can differ
        idx2.load(snap)
        assert idx2.find_matches([10]).scores == {7: 1}

    def test_same_result_as_single_tree(self):
        single = RadixTree()
        sharded = KvIndexerSharded(num_shards=4)
        for w in range(8):
            hashes = list(range(w + 1))
            single.apply_stored(w, hashes)
            sharded.apply_stored(w, hashes)
        q = [0, 1, 2, 3]
        assert sharded.find_matches(q).scores == single.find_matches(q).scores


class TestSnapshots:
    def test_snapshot_persist_and_restore(self):
        async def main():
            server = DiscoveryServer(port=0)
            _, port = await server.start()
            cfg = _drt_config(port)
            drt = await DistributedRuntime.create(cfg)

            topic = EVENT_TOPIC_FMT.format(namespace="ns", component="c")
            idx = KvIndexer(drt, "ns", "c", snapshot_threshold=2)
            await idx.start()
            await drt.discovery.publish(
                topic,
                codec.pack(
                    {
                        "worker_id": 1,
                        "events": [
                            {"event_type": "stored", "block_hashes": [1, 2, 3]},
                            {"event_type": "stored", "block_hashes": [4]},
                        ],
                    }
                ),
            )
            for _ in range(100):
                await asyncio.sleep(0.02)
                if idx.events_applied >= 2:
                    break
            await asyncio.sleep(0.1)  # let the snapshot write land
            key = ROUTER_SNAPSHOT_KEY_FMT.format(namespace="ns", component="c")
            raw = await drt.discovery.get(key)
            assert raw is not None
            assert json.loads(raw)["1"] == [1, 2, 3, 4]
            await idx.close()

            # a fresh replica restores from the snapshot before any events
            idx2 = KvIndexer(drt, "ns", "c", snapshot_threshold=2)
            await idx2.start()
            assert idx2.tree.find_matches([1, 2]).scores == {1: 2}
            await idx2.close()

            # reset_states drops it
            idx3 = KvIndexer(drt, "ns", "c", snapshot_threshold=2, reset_states=True)
            await idx3.start()
            assert await drt.discovery.get(key) is None
            assert idx3.tree.find_matches([1, 2]).scores == {}
            await idx3.close()

            await drt.close()
            await server.stop()

        asyncio.run(main())


class TestRecorder:
    def test_record_and_replay(self, tmp_path):
        async def main():
            server = DiscoveryServer(port=0)
            _, port = await server.start()
            cfg = _drt_config(port)
            drt = await DistributedRuntime.create(cfg)
            topic = EVENT_TOPIC_FMT.format(namespace="ns", component="rec")

            path = tmp_path / "events.jsonl"
            rec = KvRecorder(drt, topic, path)
            await rec.start()
            await asyncio.sleep(0.05)
            for i in range(3):
                await drt.discovery.publish(
                    topic,
                    codec.pack(
                        {
                            "worker_id": i % 2,
                            "events": [
                                {"event_type": "stored", "block_hashes": [i, i + 10]}
                            ],
                        }
                    ),
                )
            for _ in range(100):
                await asyncio.sleep(0.02)
                if rec.events_recorded >= 3:
                    break
            await rec.close()

            records = load_recording(path)
            assert len(records) == 3
            tree = RadixTree()
            n = replay_into_tree(records, tree)
            assert n == 3
            assert tree.find_matches([0, 10]).scores[0] == 2

            # replay back to a live topic feeds a live indexer
            idx = KvIndexer(drt, "ns", "rec2", block_size=64)
            await idx.start()
            await replay_to_topic(
                drt, EVENT_TOPIC_FMT.format(namespace="ns", component="rec2"), records
            )
            for _ in range(100):
                await asyncio.sleep(0.02)
                if idx.events_applied >= 3:
                    break
            assert idx.tree.find_matches([0, 10]).scores[0] == 2
            await idx.close()
            await drt.close()
            await server.stop()

        asyncio.run(main())


class TestReplicaSync:
    def test_two_routers_mirror_routing_decisions(self):
        """Two KV-mode frontends with replica_sync share active-block
        accounting: a decision made by router A appears in router B's
        scheduler (and its approx indexer), and frees propagate too
        (reference kv_router/subscriber.rs role)."""
        from dynamo_tpu.llm.kv_router import KvPushRouter, KvRouterConfig

        class _Comp:
            namespace = "ns"
            name = "sync"

        class _Ep:
            component = _Comp()

        class _FakeClient:
            endpoint = _Ep()

            def instance_ids(self):
                return [1, 2]

        async def main():
            server = DiscoveryServer(port=0)
            _, port = await server.start()
            drt_a = await DistributedRuntime.create(_drt_config(port))
            drt_b = await DistributedRuntime.create(_drt_config(port))
            cfg = KvRouterConfig(
                use_kv_events=False, replica_sync=True, block_size=4
            )
            ra = KvPushRouter(drt_a, _FakeClient(), cfg, block_size=4)
            rb = KvPushRouter(drt_b, _FakeClient(), cfg, block_size=4)
            await ra.start()
            await rb.start()

            tokens = list(range(16))  # 4 blocks
            ra.scheduler.add_request("req-1", 1, 4)
            ra.indexer.process_routing_decision_for_request(tokens, 1)
            ra._publish_sync(
                {"op": "route", "request_id": "req-1", "worker": 1,
                 "blocks": 4, "token_ids": tokens}
            )
            for _ in range(100):
                await asyncio.sleep(0.02)
                if "req-1" in rb.scheduler._active:
                    break
            assert rb.scheduler._active["req-1"].worker_id == 1
            # approx indexer mirrored the prefix -> same overlap scores
            assert rb.indexer.find_matches_for_tokens(tokens).scores.get(1)

            ra._publish_sync({"op": "free", "request_id": "req-1"})
            for _ in range(100):
                await asyncio.sleep(0.02)
                if "req-1" not in rb.scheduler._active:
                    break
            assert "req-1" not in rb.scheduler._active
            # A ignores its own sync events: its local state is whatever it
            # set directly (req-1 still active — B's mirror free and A's own
            # broadcast free were both skipped as self-echo)
            assert "req-1" in ra.scheduler._active

            await ra.close()
            await rb.close()
            await drt_a.close()
            await drt_b.close()
            await server.stop()

        asyncio.run(main())
