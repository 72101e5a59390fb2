"""Metrics registry, system status server, canary health checks,
ForwardPassMetrics (reference metrics.rs, system_status_server.rs,
health_check.rs, _core.pyi ForwardPassMetrics)."""

import asyncio

import aiohttp
import pytest

from dynamo_tpu.llm.protocols.metrics import (
    ForwardPassMetrics,
    KvMetricsAggregator,
)
from dynamo_tpu.runtime import (
    Context,
    DiscoveryServer,
    DistributedRuntime,
    RuntimeConfig,
)
from dynamo_tpu.runtime.health_check import HealthCheckManager
from dynamo_tpu.runtime.metrics import MetricsRegistry
from dynamo_tpu.runtime.system_status import SystemHealth, SystemStatusServer


def _drt_config(port: int) -> RuntimeConfig:
    cfg = RuntimeConfig()
    cfg.discovery_endpoint = f"tcp://127.0.0.1:{port}"
    return cfg


class TestMetricsRegistry:
    def test_hierarchy_labels(self):
        root = MetricsRegistry()
        ep = (
            root.for_namespace("ns1").for_component("comp1").for_endpoint("gen")
        )
        c = ep.counter("requests_total", "requests")
        c.inc(3)
        text = root.render().decode()
        assert 'dynamo_namespace="ns1"' in text
        assert 'dynamo_component="comp1"' in text
        assert 'dynamo_endpoint="gen"' in text
        assert "dynamo_requests_total" in text

    def test_root_level_metric_no_labels(self):
        root = MetricsRegistry()
        root.counter("uptime_total", "uptime").inc()
        assert "dynamo_uptime_total" in root.render().decode()

    def test_same_name_at_different_depths(self):
        root = MetricsRegistry()
        root.for_namespace("ns").counter("requests_total").inc()
        root.for_namespace("ns").for_component("c").for_endpoint("e").counter(
            "requests_total"
        ).inc(2)
        text = root.render().decode()
        assert 'dynamo_component=""' in text
        assert 'dynamo_component="c"' in text

    def test_same_metric_multiple_children(self):
        root = MetricsRegistry()
        a = root.for_namespace("ns").for_component("a").for_endpoint("e")
        b = root.for_namespace("ns").for_component("b").for_endpoint("e")
        a.counter("reqs_total").inc()
        b.counter("reqs_total").inc(2)
        text = root.render().decode()
        assert 'dynamo_component="a"' in text
        assert 'dynamo_component="b"' in text

    def test_callback_gauge_evaluated_at_render(self):
        root = MetricsRegistry()
        val = {"x": 1.0}
        root.for_namespace("n").callback_gauge("depth", "queue depth", lambda: val["x"])

        def value() -> str:
            line = next(
                l for l in root.render().decode().splitlines()
                if l.startswith("dynamo_depth{")
            )
            return line.rsplit(" ", 1)[1]

        assert value() == "1.0"
        val["x"] = 7.0
        assert value() == "7.0"

    def test_extra_labels(self):
        root = MetricsRegistry()
        h = root.for_namespace("n").histogram(
            "lat_seconds", "latency", extra_labels=("op",), buckets=(0.1, 1)
        )
        h.labels("prefill").observe(0.05)
        text = root.render().decode()
        assert 'op="prefill"' in text


class TestSystemHealth:
    def test_endpoint_states_drive_health(self):
        h = SystemHealth()
        assert h.healthy  # no endpoints yet: live process is healthy
        h.set_endpoint_health("ns/c/e1", True)
        h.set_endpoint_health("ns/c/e2", False)
        assert not h.healthy
        h.set_endpoint_health("ns/c/e2", True)
        assert h.healthy
        h.remove_endpoint("ns/c/e1")
        assert h.healthy


class TestSystemStatusServer:
    def test_routes(self):
        async def main():
            health = SystemHealth()
            metrics = MetricsRegistry()
            metrics.for_namespace("ns").counter("up_total").inc()
            srv = SystemStatusServer(health, metrics, host="127.0.0.1")
            host, port = await srv.start()
            base = f"http://127.0.0.1:{port}"
            async with aiohttp.ClientSession() as s:
                async with s.get(base + "/live") as r:
                    assert r.status == 200
                async with s.get(base + "/health") as r:
                    assert r.status == 200
                health.set_endpoint_health("ns/c/e", False)
                async with s.get(base + "/health") as r:
                    assert r.status == 503
                    body = await r.json()
                    assert body["status"] == "unhealthy"
                async with s.get(base + "/metrics") as r:
                    assert "dynamo_up_total" in await r.text()
            await srv.stop()

        asyncio.run(main())


class TestHealthCheck:
    def test_canary_marks_unhealthy_then_recovers(self):
        async def main():
            server = DiscoveryServer(port=0)
            _, port = await server.start()
            cfg = _drt_config(port)
            # the wedged canary stream is still in flight at close(): do not
            # sit out the default 30 s drain for a handler wedged on purpose
            cfg.graceful_shutdown_timeout = 0.5

            healthy_mode = {"on": True}

            async def handler(request, context: Context):
                if not healthy_mode["on"]:
                    await asyncio.sleep(60)  # wedged engine
                yield {"ok": True}

            drt = await DistributedRuntime.create(cfg)
            served = await (
                drt.namespace("ns").component("c").endpoint("gen").serve_endpoint(handler)
            )
            hc = HealthCheckManager(
                drt, drt.system_health,
                idle_timeout=0.05, request_timeout=0.3, check_interval=0.05,
            )
            hc.register(served, {"canary": True})
            assert drt.system_health.healthy
            hc.start()
            await asyncio.sleep(0.3)
            assert drt.system_health.healthy  # canaries succeed

            healthy_mode["on"] = False
            await asyncio.sleep(0.8)
            assert not drt.system_health.healthy  # canary timed out

            healthy_mode["on"] = True
            await asyncio.sleep(0.5)
            assert drt.system_health.healthy  # recovered

            await hc.stop()
            await drt.close()
            await server.stop()

        asyncio.run(main())


class TestForwardPassMetrics:
    def test_from_engine_stats(self):
        m = ForwardPassMetrics.from_stats_dict(
            {
                "num_running_reqs": 3,
                "num_waiting_reqs": 2,
                "request_total_slots": 8,
                "kv_active_blocks": 100,
                "kv_total_blocks": 400,
                "gpu_cache_usage_perc": 0.25,
            }
        )
        assert m.worker_stats.request_active_slots == 3
        assert m.worker_stats.num_requests_waiting == 2
        assert m.kv_stats.kv_active_blocks == 100
        assert m.kv_stats.gpu_cache_usage_perc == 0.25

    def test_aggregator_totals(self):
        agg = KvMetricsAggregator()
        agg.update(1, {"num_running_reqs": 2, "kv_active_blocks": 10,
                       "kv_total_blocks": 100, "request_total_slots": 4})
        agg.update(2, {"num_running_reqs": 1, "kv_active_blocks": 30,
                       "kv_total_blocks": 100, "request_total_slots": 4})
        t = agg.totals()
        assert t["num_workers"] == 2
        assert t["active_slots"] == 3
        assert t["kv_active_blocks"] == 40
        agg.remove_worker(2)
        assert agg.totals()["num_workers"] == 1
