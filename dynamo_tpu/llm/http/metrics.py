"""HTTP frontend Prometheus metrics.

Mirrors reference lib/llm/src/http/service/metrics.rs: request counters,
in-flight gauge, duration + TTFT + output-token histograms, disconnects —
labeled by model and endpoint type, exported at /metrics.
"""

from __future__ import annotations

import logging
import time

from prometheus_client import (
    CollectorRegistry,
    Counter,
    Gauge,
    Histogram,
    generate_latest,
)

from ...runtime.engine import STAGES

logger = logging.getLogger(__name__)

#: a first token this late logs one WARNING line that names every stage:
#: the frontend's counterpart of engine/recorder.py's SLOW_SPAN_S, no knob
SLOW_FIRST_TOKEN_S = 2.0


class HttpMetrics:
    def __init__(self, registry: CollectorRegistry | None = None):
        self.registry = registry or CollectorRegistry()
        ns = "dynamo_frontend"
        self.requests_total = Counter(
            f"{ns}_requests_total",
            "Total HTTP LLM requests",
            ["model", "endpoint", "status"],
            registry=self.registry,
        )
        self.inflight = Gauge(
            f"{ns}_inflight_requests",
            "Requests currently being processed",
            ["model", "endpoint"],
            registry=self.registry,
        )
        self.request_duration = Histogram(
            f"{ns}_request_duration_seconds",
            "End-to-end request duration",
            ["model", "endpoint"],
            registry=self.registry,
            buckets=(0.05, 0.1, 0.25, 0.5, 1, 2, 4, 8, 16, 32, 64, 128),
        )
        self.ttft = Histogram(
            f"{ns}_time_to_first_token_seconds",
            "Time to first token",
            ["model"],
            registry=self.registry,
            buckets=(0.01, 0.025, 0.05, 0.1, 0.2, 0.4, 0.8, 1.6, 3.2, 6.4, 12.8),
        )
        stage = Histogram(
            f"{ns}_stage_seconds",
            "Seconds of a request in one stage of its path to the first token",
            ["stage"],
            registry=self.registry,
            buckets=(0.0001, 0.0003, 0.001, 0.003, 0.01, 0.03, 0.1, 0.3, 1, 3, 10),
        )
        self._stage = {s: stage.labels(s) for s in STAGES}
        self.output_tokens = Counter(
            f"{ns}_output_tokens_total",
            "Total generated tokens",
            ["model"],
            registry=self.registry,
        )
        self.input_tokens = Counter(
            f"{ns}_input_tokens_total",
            "Total prompt tokens",
            ["model"],
            registry=self.registry,
        )
        self.itl = Histogram(
            f"{ns}_inter_token_latency_seconds",
            "Mean inter-token latency per request",
            ["model"],
            registry=self.registry,
            buckets=(0.002, 0.005, 0.01, 0.02, 0.04, 0.08, 0.16, 0.32, 0.64, 1.28),
        )
        self.disconnects = Counter(
            f"{ns}_client_disconnects_total",
            "Client disconnects mid-stream",
            ["model"],
            registry=self.registry,
        )
        # token-path batching visibility: tokens per streamed delta batch
        # (= per SSE event). Mean > 1 in steady decode means the batched
        # emit/coalesce path is active end-to-end; mean == 1 flags a
        # serving plane paying per-token overhead again.
        self.tokens_per_frame = Histogram(
            f"{ns}_tokens_per_frame",
            "Generated tokens carried by each streamed delta batch",
            ["model"],
            registry=self.registry,
            buckets=(1, 2, 4, 8, 16, 32, 64, 128),
        )

    def request_start(self, model: str, endpoint: str):
        self.inflight.labels(model, endpoint).inc()

    def request_end(
        self,
        model: str,
        endpoint: str,
        t0: float,
        error: bool = False,
        output_tokens: int = 0,
        input_tokens: int = 0,
        first_token_at: float | None = None,
        last_token_at: float | None = None,
    ):
        self.inflight.labels(model, endpoint).dec()
        self.requests_total.labels(model, endpoint, "error" if error else "success").inc()
        now = time.monotonic()
        self.request_duration.labels(model, endpoint).observe(now - t0)
        if output_tokens:
            self.output_tokens.labels(model).inc(output_tokens)
        if input_tokens:
            self.input_tokens.labels(model).inc(input_tokens)
        # ITL over first→last token, not request end (post-stream work such
        # as [DONE]/usage frames must not inflate the planner's signal)
        if first_token_at is not None and last_token_at is not None and output_tokens > 1:
            self.itl.labels(model).observe(
                max(last_token_at - first_token_at, 0.0) / (output_tokens - 1)
            )

    def observe_ttft(self, model: str, seconds: float):
        self.ttft.labels(model).observe(seconds)

    def first_token(self, model: str, ctx, t0: float, now: float):
        """A request's first token has reached its handler at `now`: the
        time to first token and, of a request with a timeline, every stage
        it holds (`sse` is closed here, where the worker's stages came back
        on the frame that carried the token). A first token later than
        SLOW_FIRST_TOKEN_S is logged with its whole path."""
        ttft = now - t0
        self.observe_ttft(model, ttft)
        if "first_frame" in ctx.stages:
            ctx.stamp("sse", now)
        for name, seconds in ctx.stages.items():
            child = self._stage.get(name)
            if child is not None:
                child.observe(seconds)
        if ttft >= SLOW_FIRST_TOKEN_S:
            known = {s: ctx.stages[s] for s in STAGES if s in ctx.stages}
            logger.warning(
                "request %s first token after %.3f s: %s, other %.3f",
                ctx.id, ttft,
                ", ".join(f"{s} {v:.3f}" for s, v in known.items()),
                ttft - sum(known.values()),
            )

    def observe_tokens_per_frame(self, model: str, n_tokens: int):
        self.tokens_per_frame.labels(model).observe(n_tokens)

    def client_disconnect(self, model: str):
        self.disconnects.labels(model).inc()

    def render(self) -> bytes:
        return generate_latest(self.registry)
