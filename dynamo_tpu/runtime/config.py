"""Layered runtime configuration.

Mirrors the reference's figment-based config (lib/runtime/src/config.rs:72):
defaults <- optional config file (TOML/JSON/YAML) <- `DYN_*` environment
variables. Env takes precedence, like figment's profile layering.

Recognised env prefixes (parity with reference config.rs:214-260):
  DYN_RUNTIME_*   — runtime knobs (worker threads, shutdown timeouts)
  DYN_SYSTEM_*    — system status server (enabled, port)
  DYN_COMPUTE_*   — compute pool sizing
  DYN_HEALTH_CHECK_* — canary health checks
  DYN_DISCOVERY_* — built-in discovery service address
"""

from __future__ import annotations

import dataclasses
import json
import os
from pathlib import Path
from typing import Any, Optional


def _env(name: str, default: Any = None, cast=str):
    raw = os.environ.get(name)
    if raw is None:
        return default
    if cast is bool:
        return raw.lower() in ("1", "true", "yes", "on")
    return cast(raw)


def env_bool(name: str, default: bool = False) -> bool:
    """Canonical bool parsing for registry-typed env vars: truthy spellings
    are exactly 1/true/yes/on (case-insensitive); anything else is False.
    Every `bool`-typed ENV_REGISTRY read must go through this (or _env) so
    the accepted spellings cannot drift between modules."""
    return bool(_env(name, default, bool))


def env_float(name: str, default: float) -> float:
    """Canonical lenient float parsing for registry-typed env vars: unset,
    empty, or unparseable values fall back to the default with a warning
    (a typo'd knob must degrade, not take the process down). One spelling
    shared by every module (the SLA/sched and gate knob surfaces)."""
    raw = os.environ.get(name)
    if raw in (None, ""):
        return default
    try:
        return float(raw)
    except ValueError:
        import logging

        logging.getLogger(__name__).warning(
            "%s=%r is not a number; using %s", name, raw, default)
        return default


def env_int(name: str, default: int) -> int:
    """Lenient int parsing, same contract as env_float."""
    raw = os.environ.get(name)
    if raw in (None, ""):
        return default
    try:
        return int(raw)
    except ValueError:
        import logging

        logging.getLogger(__name__).warning(
            "%s=%r is not an integer; using %s", name, raw, default)
        return default


@dataclasses.dataclass(frozen=True)
class EnvVar:
    """One registered environment variable: the discoverability contract.

    Every `DYN_*` / `DYNAMO_TPU_*` read anywhere in the package must have
    an entry here — enforced by the `env-registry` dynolint rule
    (dynamo_tpu/analysis). `python -m dynamo_tpu.analysis --emit-env-docs`
    renders the table to docs/configuration.md."""

    name: str
    type: str  # "str" | "int" | "float" | "bool" | "path" | "enum"
    default: Optional[str]
    description: str
    module: str  # primary consuming module (repo-relative)


ENV_REGISTRY: tuple = (
    # -- logging ------------------------------------------------------- #
    EnvVar("DYN_LOG", "str", "info",
           "Log filter, RUST_LOG-style: a level (`debug`) or "
           "`target=level` pairs (`dynamo_tpu.engine=debug,info`).",
           "runtime/logging.py"),
    EnvVar("DYN_LOGGING_JSONL", "bool", "0",
           "Switch log output to JSON lines (one object per record).",
           "runtime/logging.py"),
    # -- runtime / event loop ------------------------------------------ #
    EnvVar("DYN_RUNTIME_CONFIG", "path", None,
           "Optional TOML/JSON/YAML config file layered under the env.",
           "runtime/config.py"),
    EnvVar("DYN_RUNTIME_NUM_WORKER_THREADS", "int", "0",
           "Worker thread count hint; 0 = library default.",
           "runtime/config.py"),
    EnvVar("DYN_RUNTIME_MAX_BLOCKING_THREADS", "int", "4",
           "Cap on blocking-offload threads.",
           "runtime/config.py"),
    EnvVar("DYN_RUNTIME_GRACEFUL_SHUTDOWN_TIMEOUT", "float", "30.0",
           "Seconds to wait for in-flight streams on shutdown.",
           "runtime/config.py"),
    EnvVar("DYN_COMPUTE_THREADS", "int", "min(4, cpus)",
           "Compute-pool size for CPU-bound offload (tokenize/template).",
           "runtime/compute.py"),
    # -- system status / health ---------------------------------------- #
    EnvVar("DYN_SYSTEM_ENABLED", "bool", "0",
           "Enable the system-status HTTP server (health + metrics).",
           "runtime/system_status.py"),
    EnvVar("DYN_SYSTEM_HOST", "str", "0.0.0.0",
           "Bind host for the system-status server.",
           "runtime/system_status.py"),
    EnvVar("DYN_SYSTEM_PORT", "int", "0",
           "Bind port for the system-status server; 0 = ephemeral. An "
           "explicit port implies DYN_SYSTEM_ENABLED=1.",
           "runtime/system_status.py"),
    EnvVar("DYN_HEALTH_CHECK_ENABLED", "bool", "0",
           "Enable canary health checks against served endpoints.",
           "runtime/health_check.py"),
    EnvVar("DYN_HEALTH_CHECK_IDLE_TIMEOUT", "float", "60.0",
           "Seconds of endpoint idleness before a canary probe fires.",
           "runtime/health_check.py"),
    EnvVar("DYN_HEALTH_CHECK_REQUEST_TIMEOUT", "float", "10.0",
           "Canary probe request timeout in seconds.",
           "runtime/health_check.py"),
    # -- discovery / request plane ------------------------------------- #
    EnvVar("DYN_DISCOVERY_ENDPOINT", "str", "tcp://127.0.0.1:2379",
           "Discovery-service address (etcd role).",
           "runtime/discovery.py"),
    EnvVar("DYN_LEASE_TTL_S", "float", "10.0",
           "Instance-lease TTL: missed keepalives past this drop the "
           "worker from discovery.",
           "runtime/discovery.py"),
    EnvVar("DYN_REQUEST_PLANE_HOST", "str", "127.0.0.1",
           "Bind host for the TCP request-plane server.",
           "runtime/request_plane.py"),
    EnvVar("DYN_REQUEST_PLANE_CONNECT_TIMEOUT", "float", "5.0",
           "Connect budget for dialing a worker's request-plane server; "
           "a black-holed address raises StreamLost (retryable) instead "
           "of hanging the caller.",
           "runtime/request_plane.py"),
    EnvVar("DYN_STREAM_COALESCE_MS", "float", "0",
           "Extra milliseconds the worker-side response writer may wait "
           "after the first ready stream item to gather more into one "
           "multi-item request-plane frame. 0 (default) coalesces only "
           "items already queued in the same event-loop tick, adding no "
           "latency; raising it trades TTFT/ITL for fewer, fuller frames.",
           "runtime/request_plane.py"),
    EnvVar("DYN_STREAM_COALESCE_MAX_ITEMS", "int", "64",
           "Cap on stream items packed into one multi-item request-plane "
           "frame (and on token deltas merged per detokenizer batch on "
           "the frontend). Bounds frame size and per-batch latency.",
           "runtime/request_plane.py"),
    EnvVar("DYN_WIRE_BINARY_TOKENS", "bool", "1",
           "Zero-copy token wire path: the request-plane client "
           "advertises ENC_TOK on every stream, and workers answer pure "
           "token-delta batches as packed little-endian u32 payloads "
           "instead of msgpack dicts (per-frame msgpack fallback for "
           "anything the encoding cannot carry). 0 = msgpack everywhere "
           "(the pre-PR-13 wire, and the codec A/B baseline arm).",
           "runtime/request_plane.py"),
    EnvVar("DYN_DETOK_POOL", "bool", "1",
           "Run frontend detokenization batches on the bounded compute "
           "pool instead of the event loop when they are big enough to "
           "amortize the hop (DYN_DETOK_POOL_MIN_TOKENS) or carry a "
           "stop-string scan — one slow stream's scan must not stall "
           "every other stream's SSE writer. 0 = always inline.",
           "llm/backend.py"),
    EnvVar("DYN_DETOK_POOL_MIN_TOKENS", "int", "8",
           "Smallest token-delta batch worth offloading to the compute "
           "pool under DYN_DETOK_POOL (stop-string batches always "
           "offload); smaller batches detokenize inline — the executor "
           "hop would cost more than it frees.",
           "llm/backend.py"),
    # -- fault injection (dynochaos) ----------------------------------- #
    EnvVar("DYN_FAULT_PLAN", "str", None,
           "dynochaos fault plan: `;`-separated `point[:spec,...]` rules "
           "(e.g. `request_plane.frame:sever,after=3;discovery.lease:"
           "drop@t=2.0`). Unset = injection compiled out to a no-op "
           "pass-through. See docs/fault_tolerance.md.",
           "runtime/faults.py"),
    EnvVar("DYN_FAULT_SEED", "int", "0",
           "Seed for probabilistic (`p=`) fault rules — same plan + seed "
           "+ hit sequence fires identically.",
           "runtime/faults.py"),
    EnvVar("DYN_FAULT_DISABLE", "bool", "0",
           "Global dynochaos kill-switch: force the no-op injector even "
           "when DYN_FAULT_PLAN is set.",
           "runtime/faults.py"),
    # -- engine scheduling / SLA (engine/scheduler/, docs/scheduler.md) - #
    EnvVar("DYN_SCHED_POLICY", "enum", "fifo",
           "Engine step-scheduling policy: `fifo` preserves the legacy "
           "admit-order prefill dispatch bit-for-bit (modulo the "
           "batch-kind anti-starvation fairness fix, active under both "
           "policies); `sla` enables the EDF + ITL-budget StepPlanner "
           "(also honored by the CPU mocker's scheduler).",
           "engine/scheduler/sla.py"),
    EnvVar("DYN_SLA_TTFT_MS", "float", "2000",
           "Per-request TTFT target under DYN_SCHED_POLICY=sla: prefill "
           "deadlines are arrival + target, halved per +1 of the "
           "request's nvext.priority. Drives EDF ordering and the disagg "
           "router's local-vs-remote prefill decision.",
           "engine/scheduler/sla.py"),
    EnvVar("DYN_SLA_ITL_MS", "float", "0",
           "Decode ITL budget (ms/token) under DYN_SCHED_POLICY=sla: "
           "prefill dispatches are shrunk or deferred so the projected "
           "per-token latency of decode-block + prefill stays under it. "
           "0 (default) disables the ITL budget.",
           "engine/scheduler/sla.py"),
    # -- SLA planner loop (planner/, docs/autoscaling.md) ---------------- #
    EnvVar("DYN_PLANNER_SCRAPE_TIMEOUT", "float", "5.0",
           "Per-attempt timeout for the planner's frontend /metrics "
           "scrape; a hung endpoint costs one bounded attempt, never the "
           "whole planner loop.",
           "planner/planner_core.py"),
    EnvVar("DYN_PLANNER_SCRAPE_RETRIES", "int", "3",
           "Scrape attempts per adjustment interval (backoff between); "
           "when all fail the planner holds its last decision instead of "
           "feeding NaN/stale averages into the scaling math.",
           "planner/planner_core.py"),
    EnvVar("DYN_PLANNER_METRICS_MAX_AGE_S", "float", "0",
           "Observations older than this never reach a scaling decision "
           "(the planner holds). 0 = 2.5 × the adjustment interval.",
           "planner/planner_core.py"),
    EnvVar("DYN_PLANNER_COOLDOWN_INTERVALS", "int", "1",
           "Intervals the planner holds after an applied replica change "
           "before it may change again — structurally rules out A→B→A "
           "flapping inside the window.",
           "planner/planner_core.py"),
    EnvVar("DYN_PLANNER_MAX_STEP", "int", "2",
           "Bound on the replica delta per decision per role: one noisy "
           "interval can move the fleet at most this far.",
           "planner/planner_core.py"),
    EnvVar("DYN_PLANNER_SCALE_DOWN_STABLE_INTERVALS", "int", "2",
           "Consecutive intervals the model must ask for below-current "
           "capacity before the planner steps down (scale-up is never "
           "hysteresis-gated: restoring SLA outranks fleet stability).",
           "planner/planner_core.py"),
    EnvVar("DYN_PLANNER_WORKERS_PER_FRONTEND", "int", "0",
           "Frontend-role scaling: with N > 0 the planner sizes the "
           "frontend tier to ceil(total workers / N) replicas alongside "
           "every applied worker target (frontends are stateless over "
           "shared discovery, docs/frontend_scaleout.md). 0 = frontends "
           "are not planner-managed (the pre-PR-13 behavior).",
           "planner/planner_core.py"),
    # -- planner role morphing (docs/autoscaling.md "Role morphing") ---- #
    EnvVar("DYN_PLANNER_MORPH", "bool", "1",
           "Re-role arm: under load skew (one role over, the other "
           "under) convert a live worker via morph instead of "
           "cold-spawning, when the priced morph beats spawn on "
           "time-to-SLA-recovery. Effective only when the connector "
           "exposes morph_replicas; 0 = spawn-only (the pre-morph "
           "behavior).",
           "planner/planner_core.py"),
    EnvVar("DYN_PLANNER_MORPH_COST_S", "float", "3.0",
           "Seed estimate of one live morph's wall-clock (drain the "
           "outgoing role + flip + re-warm cached compile surfaces); "
           "refined by the connector's measured morph durations when "
           "available. Compared against DYN_PLANNER_SPAWN_COST_S to "
           "price re-role vs spawn.",
           "planner/planner_core.py"),
    EnvVar("DYN_PLANNER_SPAWN_COST_S", "float", "30.0",
           "Seed estimate of a cold replica spawn's wall-clock (process "
           "start + weight load + full warmup compile drive) for the "
           "re-role pricing; refined by measured spawn-to-ready times "
           "when the connector reports them.",
           "planner/planner_core.py"),
    EnvVar("DYN_PLANNER_COLOCATE", "bool", "0",
           "Colocated-mode arm: at low traffic (both roles' raw asks at "
           "the 1-replica floor for the scale-down-stable window) morph "
           "the decode worker to role `both` and retire the dedicated "
           "prefill replica — small fleets stop paying a dedicated "
           "prefill tax. Scale-up later adds dedicated replicas "
           "normally.",
           "planner/planner_core.py"),
    EnvVar("DYN_MORPH_DRAIN_TIMEOUT_S", "float", "10.0",
           "Engine role-morph drain budget: in-flight outgoing-role "
           "sessions are severed to peers (StreamSevered -> migration) "
           "and must clear the lanes within this window before the flip "
           "proceeds; expiry fails the morph and rolls the role back.",
           "engine/engine.py"),
    # -- frontend admission gate (gate/, docs/overload.md) -------------- #
    EnvVar("DYN_GATE", "bool", "1",
           "dynogate master switch: frontend admission control, per-"
           "tenant fairness and load shedding (docs/overload.md). 0 "
           "compiles the gate out of the frontend — no admission checks, "
           "no metrics subscription, no router watermark preference; "
           "streams are byte-identical to a build without the package.",
           "gate/config.py"),
    EnvVar("DYN_GATE_TTFT_MS", "float", "0",
           "Base TTFT target (ms) for admission-class math; each +1 of "
           "nvext.priority halves it (the SlaConfig.deadline math). 0 "
           "(default) inherits DYN_SLA_TTFT_MS so the edge and the "
           "worker scheduler agree on what on-time means.",
           "gate/config.py"),
    EnvVar("DYN_GATE_TTFT_HEADROOM", "float", "1.5",
           "Admission ceiling multiplier: a request is rejected (429 + "
           "Retry-After, before tokenization) when the fleet's projected "
           "TTFT exceeds headroom x its class target — serving it would "
           "blow its SLA anyway.",
           "gate/config.py"),
    EnvVar("DYN_GATE_QUEUE_WATERMARK", "int", "16",
           "Per-instance queue-depth watermark: PushRouter prefers "
           "instances below it for new streams, and admission projects "
           "TTFT from depth/watermark for workers that publish no "
           "sched_est_ttft_ms estimate (fifo-policy fleets).",
           "gate/signals.py"),
    EnvVar("DYN_GATE_MAX_QUEUE", "int", "64",
           "Gate queue bound: past it waiting admissions are SHED, "
           "lowest SLA class first (newest first within a class). 0 "
           "disables the bound (shedding then happens only on the "
           "per-request wait cap).",
           "gate/gate.py"),
    EnvVar("DYN_GATE_MAX_WAIT_MS", "float", "1000",
           "Cap (ms) on how long a request may park in the gate queue "
           "awaiting capacity; the effective bound is min(this, class "
           "headroom) — waiting past either would blow the SLA it was "
           "queued to protect.",
           "gate/gate.py"),
    EnvVar("DYN_GATE_TENANT_HEADER", "str", "x-dynamo-tenant",
           "HTTP header carrying the tenant key for fairness accounting "
           "(rides PreprocessedRequest.tenant to the worker scheduler's "
           "fairness tiebreak). Absent header = tenant 'default'.",
           "gate/config.py"),
    EnvVar("DYN_GATE_TENANT_RATE", "float", "0",
           "Per-tenant token-bucket rate limit (requests/s) enforced at "
           "admission; a tenant past its bucket gets 429 with "
           "Retry-After = its exact refill time. 0 = unlimited.",
           "gate/config.py"),
    EnvVar("DYN_GATE_TENANT_BURST", "float", "0",
           "Token-bucket burst size per tenant; 0 = max(2 x rate, 1).",
           "gate/config.py"),
    EnvVar("DYN_GATE_TENANT_WEIGHTS", "str", None,
           "WFQ weights per tenant (`gold=4,free=1`): under contention a "
           "tenant drains the gate queue at weight-proportional share. "
           "Unlisted tenants weigh 1.",
           "gate/config.py"),
    EnvVar("DYN_GATE_SIGNAL_TTL_S", "float", "5.0",
           "Load-signal staleness bound: samples older than this are "
           "invisible to admission (a stale fleet view must admit, "
           "never reject on ghosts — the disagg queue_depth_ttl_s rule).",
           "gate/config.py"),
    EnvVar("DYN_GATE_RETRY_AFTER_FLOOR_S", "float", "1.0",
           "Minimum Retry-After (s) on any gate 429.",
           "gate/config.py"),
    # -- engine / memory sizing ---------------------------------------- #
    EnvVar("DYN_HBM_UTILIZATION", "float", "0.85",
           "Fraction of device memory the KV pool auto-sizer may plan "
           "for (the gpu_memory_utilization role).",
           "engine/engine.py"),
    EnvVar("DYN_HBM_BYTES", "int", None,
           "Device memory size in bytes for a device that reports no "
           "memory_stats; an accelerator without either is an error.",
           "engine/engine.py"),
    EnvVar("DYN_HBM_RESERVE_MB", "float", "512",
           "Memory held back for compile/activation workspace the "
           "post-weights snapshot cannot see.",
           "engine/engine.py"),
    # -- workers / models / native ------------------------------------- #
    EnvVar("DYN_WORKER_INDEX", "int", None,
           "Set by the planner for each spawned worker: its index within "
           "its role's replica set.",
           "planner/connector.py"),
    EnvVar("DYN_HF_ALLOW_DOWNLOAD", "bool", "0",
           "Allow model loads to hit the HuggingFace hub; default is "
           "cache-only (serving environments are often airgapped).",
           "models/loader.py"),
    EnvVar("DYN_NATIVE", "bool", "1",
           "Set to 0 to disable the optional native (C) extension and "
           "force the pure-Python paths.",
           "native/__init__.py"),
    EnvVar("DYNAMO_TPU_PAGED_ATTN", "enum", "auto",
           "Paged-attention kernel selection: auto / pallas / xla "
           "reference. One gate (`_pallas_eligible`) covers the prefill, "
           "decode, and ragged mixed-step kernels; auto = kernels on a TPU "
           "for an engine on one device, XLA for a multi-device mesh; "
           "quantized KV pools always take XLA.",
           "ops/paged_attention.py"),
    EnvVar("DYN_LORA_POOL_SLOTS", "int", "8",
           "Device slots in the LoRA adapter tier (models/lora_pool.py): "
           "the fixed-size HBM adapter stack pages against the host "
           "roster, LRU-evicting unpinned adapters on a cold acquire "
           "(docs/multi_lora.md). Fixed N keeps adapter churn from ever "
           "recompiling a dispatch variant.",
           "engine/engine.py"),
    EnvVar("DYN_KV_QUANT", "enum", "none",
           "Quantized KV cache page format: `none` (fp, the seed's exact "
           "byte-identical path), `int8`, or `int4` (two tokens per byte "
           "along the page axis). Pages quantize ON WRITE with "
           "per-page-per-head f32 scales and dequantize inside the "
           "attention kernels' VMEM window (scales ride scalar prefetch "
           "beside the page tables); the auto-sized HBM pool, the KVBM "
           "G2/G3 tiers and every peer-pull/disagg payload shrink "
           "~2x/4x, roughly doubling resident sessions at fixed HBM. "
           "Every worker of a fleet must run the SAME format — "
           "mismatches fail typed (KvFormatError), counted in "
           "kv_format_mismatches. EngineConfig.kv_quant overrides. "
           "Requires tp/pp/sp == 1.",
           "ops/kv_quant.py"),
    # -- KVBM tier pipeline (kvbm/, docs/kvbm.md) ----------------------- #
    EnvVar("DYN_KVBM_OFFLOAD_QUEUE", "int", "8",
           "Max in-flight offload batches between the per-step gather "
           "and the kvbm-tier thread's stores. When the tier thread "
           "falls behind, the OLDEST queued batch is dropped (counted "
           "in kvbm_offload_blocks_dropped) instead of stalling the "
           "step loop — offloads are cache copies, never correctness.",
           "kvbm/manager.py"),
    EnvVar("DYN_KV_INCREMENTAL_COMMIT", "bool", "1",
           "Durable decode sessions: commit newly-full generated KV "
           "blocks DURING the step loop (prefix cache + KVBM offload + "
           "announcement mesh + session checkpointing see a live "
           "session's prefix as it grows) instead of only at slot "
           "release. Commits are byte-identical either way; 0 restores "
           "the release-only arm.",
           "engine/engine.py"),
    EnvVar("DYN_KV_CHECKPOINT", "str", "off",
           "Session KV checkpointing (kvbm/checkpoint.py): replicate "
           "committed session blocks to a peer worker's G2 over the KV "
           "data plane so a worker death loses only the un-checkpointed "
           "tail — the survivor onboards the replicated prefix and "
           "recomputes the rest. Value = max staged blocks (bounded "
           "queue refusing the newest on overflow — the replicated "
           "prefix stays contiguous; same never-stall discipline as "
           "DYN_KVBM_OFFLOAD_QUEUE); 'off' (default) compiles the path "
           "out entirely.",
           "kvbm/checkpoint.py"),
    EnvVar("DYN_KVBM_PEER_PULL", "bool", "1",
           "Cluster KV fabric: let admission onboard blocks from a PEER "
           "worker's G2/G3 tiers over the KV data plane (announcement "
           "mesh owner, or the router's kv_holder hint), arbitrated by "
           "the three-arm onboard budget — per-peer transfer-rate EWMA "
           "vs local-tier load vs recompute. 0 = local tiers only "
           "(pre-fabric behavior).",
           "kvbm/manager.py"),
    EnvVar("DYN_DISAGG_STREAM", "bool", "1",
           "Streamed disagg prefill→decode handoff: the prefill worker "
           "stages the transfer at ADMISSION and publishes KV chunks as "
           "prefill commits pages, so the decode worker's pull overlaps "
           "prefill compute and its first decode step dispatches as soon "
           "as the last chunk + first token land. 0 = serial handoff "
           "(descriptor ships only after prefill completes).",
           "jax_worker/disagg_handler.py"),
    EnvVar("DYN_KVBM_EVICTION", "enum", "lru",
           "KVBM tier eviction policy: `lru`, `lfu`, or `prefix-aware` "
           "(protects blocks with live chained descendants in the same "
           "tier — the RTP-LLM/Mooncake heuristic). One value applies "
           "to both tiers; `host=lfu,disk=lru` sets them independently.",
           "kvbm/manager.py"),
    # -- KV router index bound (llm/kv_router/, docs/kv_cache_routing.md) #
    EnvVar("DYN_ROUTER_INDEX_MAX_BLOCKS", "int", "0",
           "Block-count cap per KV-router index (KvIndexer tree; "
           "KvIndexerSharded ceil-splits it statically across shards, "
           "so with fewer workers than shards the effective cap is "
           "proportionally lower — the memory bound always holds, the "
           "hit-rate errs conservative). Past the cap, leaves are "
           "evicted least-recently-matched first, so the index degrades "
           "from the deep cold end of each prefix chain instead of "
           "OOMing the frontend. 0 = unbounded (seed behavior; keeps "
           "the native C++ index eligible).",
           "llm/kv_router/indexer.py"),
)


@dataclasses.dataclass
class RuntimeConfig:
    """Process-local runtime configuration (reference: RuntimeConfig config.rs:72)."""

    # asyncio / compute pool
    num_worker_threads: int = 0  # 0 = library default
    max_blocking_threads: int = 4
    # graceful shutdown
    graceful_shutdown_timeout: float = 30.0
    # system status server (reference: DYN_SYSTEM_ENABLED/DYN_SYSTEM_PORT)
    system_enabled: bool = False
    system_host: str = "0.0.0.0"
    system_port: int = 0  # 0 = ephemeral
    # health checks (reference: config.rs:155-167)
    health_check_enabled: bool = False
    health_check_idle_timeout: float = 60.0
    health_check_request_timeout: float = 10.0
    # built-in discovery service ("etcd" role)
    discovery_endpoint: str = "tcp://127.0.0.1:2379"
    # instance-lease TTL: how long after missed keepalives a worker drops
    # out of discovery (reference etcd lease, transports/etcd.rs:43). Raise
    # on heavily-contended hosts where event loops can starve past 10s.
    lease_ttl_s: float = 10.0
    # request-plane bind host for TCP response/request streams
    request_plane_host: str = "127.0.0.1"
    # connect budget for dialing a worker (black-holed address -> StreamLost)
    request_plane_connect_timeout: float = 5.0

    @classmethod
    def from_settings(cls, config_path: Optional[str] = None) -> "RuntimeConfig":
        """Layered load: defaults <- file <- env (reference figment() config.rs:214)."""
        cfg = cls()
        path = config_path or os.environ.get("DYN_RUNTIME_CONFIG")
        if path and Path(path).exists():
            text = Path(path).read_text()
            data: dict
            if path.endswith((".yaml", ".yml")):
                import yaml

                data = yaml.safe_load(text) or {}
            else:
                data = json.loads(text)
            for field in dataclasses.fields(cls):
                if field.name in data:
                    setattr(cfg, field.name, data[field.name])
        # env layer
        cfg.num_worker_threads = _env(
            "DYN_RUNTIME_NUM_WORKER_THREADS", cfg.num_worker_threads, int
        )
        cfg.max_blocking_threads = _env(
            "DYN_RUNTIME_MAX_BLOCKING_THREADS", cfg.max_blocking_threads, int
        )
        cfg.graceful_shutdown_timeout = _env(
            "DYN_RUNTIME_GRACEFUL_SHUTDOWN_TIMEOUT", cfg.graceful_shutdown_timeout, float
        )
        cfg.system_enabled = _env("DYN_SYSTEM_ENABLED", cfg.system_enabled, bool)
        cfg.system_host = _env("DYN_SYSTEM_HOST", cfg.system_host)
        cfg.system_port = _env("DYN_SYSTEM_PORT", cfg.system_port, int)
        if cfg.system_port > 0 and "DYN_SYSTEM_ENABLED" not in os.environ:
            # an explicit port IS the ask (the deploy/metrics prometheus
            # scrape targets it); requiring a second flag to turn the
            # server on makes the gauges silently absent. An explicit
            # DYN_SYSTEM_ENABLED=0 still wins.
            cfg.system_enabled = True
        cfg.health_check_enabled = _env(
            "DYN_HEALTH_CHECK_ENABLED", cfg.health_check_enabled, bool
        )
        cfg.health_check_idle_timeout = _env(
            "DYN_HEALTH_CHECK_IDLE_TIMEOUT", cfg.health_check_idle_timeout, float
        )
        cfg.health_check_request_timeout = _env(
            "DYN_HEALTH_CHECK_REQUEST_TIMEOUT", cfg.health_check_request_timeout, float
        )
        cfg.discovery_endpoint = _env("DYN_DISCOVERY_ENDPOINT", cfg.discovery_endpoint)
        cfg.lease_ttl_s = _env("DYN_LEASE_TTL_S", cfg.lease_ttl_s, float)
        cfg.request_plane_host = _env("DYN_REQUEST_PLANE_HOST", cfg.request_plane_host)
        cfg.request_plane_connect_timeout = _env(
            "DYN_REQUEST_PLANE_CONNECT_TIMEOUT", cfg.request_plane_connect_timeout, float
        )
        return cfg


def discovery_address(cfg: Optional[RuntimeConfig] = None) -> tuple[str, int]:
    """Parse the discovery endpoint into (host, port)."""
    cfg = cfg or RuntimeConfig.from_settings()
    ep = cfg.discovery_endpoint
    if "://" in ep:
        ep = ep.split("://", 1)[1]
    host, _, port = ep.rpartition(":")
    return host or "127.0.0.1", int(port)
