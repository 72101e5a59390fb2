"""JAX engine worker: `python -m dynamo_tpu.jax_worker`.

Mirrors the reference vLLM worker wiring (components/backends/vllm main.py:
64,209 — create service, build engine, publish KV events + metrics,
register_llm, serve_endpoint) with the native JAX engine underneath.
"""

import argparse
import asyncio
import json
import logging
import time

from dynamo_tpu.engine import EngineConfig, JaxEngine
from dynamo_tpu.llm.kv_router.publisher import KvEventPublisher, WorkerMetricsPublisher
from dynamo_tpu.llm.model_card import ModelDeploymentCard, register_llm
from dynamo_tpu.runtime import DistributedRuntime, RuntimeConfig, init_logging
from dynamo_tpu.runtime.metrics import (
    NUM_RUNNING_REQS,
    NUM_WAITING_REQS,
    worker_exported_stats,
)

logger = logging.getLogger("dynamo_tpu.jax_worker")


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description="dynamo-tpu JAX engine worker")
    ap.add_argument("--model", default="tiny", help="model registry key (tiny/llama3-8b/llama3-70b)")
    ap.add_argument("--model-name", default=None, help="served model name (defaults to --model)")
    ap.add_argument("--model-path", default=None,
                    help="HF safetensors checkpoint dir; random init if omitted")
    ap.add_argument("--namespace", default="dynamo")
    ap.add_argument("--component", default="backend")
    ap.add_argument("--endpoint", default="generate")
    ap.add_argument("--discovery", default=None)
    ap.add_argument("--page-size", type=int, default=64)
    ap.add_argument("--num-pages", type=int, default=0,
                    help="KV page pool; 0 = auto-size from free device HBM "
                    "(DYN_HBM_UTILIZATION; the CPU gets a fixed 2048)")
    ap.add_argument("--max-num-seqs", type=int, default=64)
    ap.add_argument("--max-model-len", type=int, default=8192)
    ap.add_argument("--lora", action="append", default=[],
                    metavar="NAME=PATH",
                    help="serve a LoRA adapter (HF PEFT export dir); "
                         "repeatable. NAME=random:<seed> makes a random "
                         "adapter (tests/demos). Select per request via "
                         "nvext.lora_name.")
    ap.add_argument("--spec", choices=["ngram"], default=None,
                    help="speculative decoding: self-drafting prompt-lookup "
                    "verified in one pass (engine/spec.py)")
    ap.add_argument("--spec-draft-len", type=int, default=4)
    ap.add_argument("--spec-ngram", type=int, default=2)
    ap.add_argument("--spec-rounds", type=int, default=4)
    ap.add_argument("--kv-quant", choices=["none", "int8", "int4"],
                    default=None,
                    help="quantized KV cache page format (default: resolve "
                    "from DYN_KV_QUANT, none): pages quantize on write and "
                    "dequantize on the XLA attention path; ~2x/4x resident "
                    "sessions at fixed HBM and the same shrink on every KVBM/peer/"
                    "disagg transfer. All workers of a fleet must match "
                    "(mismatches fail typed).")
    ap.add_argument("--quantize", choices=["int8"], default=None,
                    help="weight-only quantization (models/quant.py): int8 "
                    "projections/embed/head, per-channel scales")
    ap.add_argument("--tp-size", type=int, default=1)
    ap.add_argument("--ep-size", type=int, default=1,
                    help="expert-parallel axis size (MoE models)")
    ap.add_argument("--pp-size", type=int, default=1,
                    help="pipeline stages (layers over the pp mesh axis)")
    ap.add_argument("--sp-size", type=int, default=1,
                    help="sequence-parallel axis (ring-attention prefill)")
    ap.add_argument("--ring-prefill-threshold", type=int, default=512,
                    help="fresh prompts at least this long ride the sp ring")
    ap.add_argument("--dp-attention", action="store_true",
                    help="MoE: attention/batch data-parallel over the ep axis "
                    "(DeepSeek-style wide-EP layout)")
    ap.add_argument("--kv-events", action="store_true")
    # KVBM tiers (kvbm/): host-RAM + disk KV block offload
    ap.add_argument("--kvbm-host-blocks", type=int, default=0)
    ap.add_argument("--kvbm-disk-blocks", type=int, default=0)
    ap.add_argument("--kvbm-disk-path", default=None)
    ap.add_argument("--migration-limit", type=int, default=3)
    # SLA-aware step scheduling (engine/scheduler/, docs/scheduler.md);
    # defaults resolve from DYN_SCHED_POLICY / DYN_SLA_TTFT_MS /
    # DYN_SLA_ITL_MS so fleet-wide rollout needs no CLI change
    ap.add_argument("--sched-policy", choices=["fifo", "sla"], default=None,
                    help="step-scheduling policy: fifo = legacy admit-order "
                    "dispatch (bit-for-bit, modulo the batch-kind "
                    "anti-starvation fix), sla = EDF + ITL-budget planner "
                    "(default: DYN_SCHED_POLICY, fifo)")
    ap.add_argument("--ttft-target-ms", type=float, default=None,
                    help="TTFT target under sla policy (default: "
                    "DYN_SLA_TTFT_MS)")
    ap.add_argument("--itl-target-ms", type=float, default=None,
                    help="decode ITL budget under sla policy; 0 disables "
                    "(default: DYN_SLA_ITL_MS)")
    ap.add_argument("--warmup", choices=["auto", "full", "none"],
                    default="auto",
                    help="compile all engine dispatch variants before "
                    "joining the control plane (auto: on for accelerators, "
                    "off for CPU test runs)")
    ap.add_argument("--context-length", type=int, default=None)
    # disaggregation (reference: --disaggregation-mode prefill|decode)
    ap.add_argument(
        "--role", choices=["aggregated", "prefill", "decode"], default="aggregated"
    )
    ap.add_argument("--prefill-component", default="prefill")
    ap.add_argument("--disagg-threshold", type=int, default=64,
                    help="remote prefill iff uncached prompt tokens exceed this")
    # multi-host slice (reference: vLLM node orchestration, main.py:64-296).
    # All hosts run this same module; host 0 owns the control plane and
    # broadcasts step descriptors; hosts >0 replay them (SPMD).
    ap.add_argument("--num-hosts", type=int, default=1)
    ap.add_argument("--host-id", type=int, default=0)
    ap.add_argument("--coordinator", default=None,
                    help="jax.distributed coordinator host:port (required for --num-hosts > 1)")
    ap.add_argument("--spmd-port", type=int, default=17300,
                    help="host-0 step-descriptor fan-out port")
    # KV data plane (llm/kv_transfer.py — the NIXL-replacement fast path):
    # prefill-capable workers stage finished prompts here for pulling
    ap.add_argument("--kv-data-plane-port", type=int, default=0,
                    help="KV data plane listen port (0 = ephemeral)")
    ap.add_argument("--kv-data-plane-host", default=None,
                    help="advertised data plane host (defaults to local)")
    ap.add_argument("--no-kv-data-plane", action="store_true",
                    help="disable the pull data plane (inline KV payloads)")
    return ap.parse_args(argv)


async def main():
    init_logging()
    args = parse_args()

    multihost = args.num_hosts > 1
    spmd = None
    if multihost:
        if not args.coordinator:
            raise SystemExit("--coordinator is required with --num-hosts > 1")
        from dynamo_tpu.parallel.multihost import (
            StepBroadcaster,
            StepReceiver,
            init_multihost,
        )

        # must run before ANY other jax call on every host
        init_multihost(args.coordinator, args.num_hosts, args.host_id)
        if args.host_id == 0:
            spmd = StepBroadcaster("0.0.0.0", args.spmd_port, args.num_hosts - 1)
            await spmd.start()

    # the device this process holds, before any weight is built: a launcher
    # that needs an accelerator reads this line and stops here if it got
    # the CPU (chip_smoke.py)
    from dynamo_tpu.engine.engine import device_info

    logger.info("worker device %s", json.dumps(device_info()))

    engine_cfg = EngineConfig(
        model=args.model,
        page_size=args.page_size,
        num_pages=args.num_pages,
        max_num_seqs=args.max_num_seqs,
        max_model_len=args.max_model_len,
        quantize=args.quantize,
        kv_quant=args.kv_quant,
        spec_mode=args.spec,
        spec_draft_len=args.spec_draft_len,
        spec_ngram=args.spec_ngram,
        spec_rounds=args.spec_rounds,
        tp_size=args.tp_size,
        pp_size=args.pp_size,
        sp_size=args.sp_size,
        ring_prefill_threshold=args.ring_prefill_threshold,
        kvbm_host_blocks=args.kvbm_host_blocks,
        kvbm_disk_blocks=args.kvbm_disk_blocks,
        kvbm_disk_path=args.kvbm_disk_path,
        sched_policy=args.sched_policy,
        ttft_target_ms=args.ttft_target_ms,
        itl_target_ms=args.itl_target_ms,
        # aggregated serving warms both surfaces, same as decode
        role=args.role if args.role in ("prefill", "decode") else "decode",
    )

    kv_sharding = None
    params = None
    model_config = None
    gguf_path = None
    mesh = None
    any_parallel = (
        args.tp_size > 1 or args.ep_size > 1 or args.pp_size > 1
        or args.sp_size > 1
    )
    if any_parallel or args.model_path or multihost:
        from dynamo_tpu.models import llama, moe
        from dynamo_tpu.parallel.mesh import (
            DpAttentionShardings,
            LlamaShardings,
            MoeShardings,
            ParallelConfig,
            build_mesh,
            shard_params,
        )
        import jax

        from dynamo_tpu.engine.engine import _resolve_model

        from dynamo_tpu.models.loader import _find_gguf, config_from_gguf

        gguf_path = _find_gguf(args.model_path) if args.model_path else None
        if gguf_path is not None:
            # the checkpoint is authoritative: shapes come from the .gguf
            # metadata/tensors, no registry entry needed
            model_config = config_from_gguf(gguf_path)
        else:
            model_config = _resolve_model(args.model)
        is_moe = isinstance(model_config, moe.MoeConfig)
        model_mod = moe if is_moe else llama
        shardings = None
        if any_parallel or multihost:
            mesh = build_mesh(
                ParallelConfig(
                    tp_size=args.tp_size, ep_size=args.ep_size,
                    pp_size=args.pp_size, sp_size=args.sp_size,
                )
            )
            if is_moe and args.dp_attention:
                shardings = DpAttentionShardings(mesh)
            elif is_moe:
                shardings = MoeShardings(mesh)
            else:
                shardings = LlamaShardings(mesh)
            kv_sharding = shardings.kv_sharding()
        if args.model_path:
            from dynamo_tpu.models.loader import load_llama_params, load_moe_params

            load = load_moe_params if is_moe else load_llama_params
            params = load(
                args.model_path,
                model_config,
                shardings.param_shardings() if shardings else None,
                quantize=args.quantize,
            )
        else:
            params = model_mod.init_params(
                model_config, jax.random.PRNGKey(engine_cfg.seed)
            )
            if args.quantize == "int8":
                from dynamo_tpu.models.quant import quantize_tree

                params = quantize_tree(params, consume=True)
            if shardings is not None:
                params = shard_params(params, shardings)

    # build the engine BEFORE joining the control plane: param init can take
    # tens of seconds and must not eat into the discovery lease
    pending_events = []
    engine = JaxEngine(
        engine_cfg,
        model_config=model_config,
        params=params,
        kv_sharding=kv_sharding,
        event_sink=pending_events.append if args.host_id == 0 else None,
        mesh=mesh,
        spmd=spmd,
        multihost=multihost,
    )
    if args.role != "aggregated" and engine.stateful:
        # (--role prefill is the engine's own refusal; the decode role's
        # requests would each be refused at its entries)
        raise SystemExit(
            f"{engine.STATE_FAMILY} cannot run the disaggregated hand-off "
            f"(--role {args.role}): injected pages bring no state for the lane"
        )
    # guided decoding compiles token FSMs against the SERVED vocabulary:
    # GGUF checkpoints carry their own; everything else uses the byte
    # tokenizer the model card advertises (llm/guided.py)
    from dynamo_tpu.llm.tokenizers import load_tokenizer

    engine.tokenizer = load_tokenizer(
        f"gguf:{gguf_path}" if gguf_path is not None
        else f"byte:{engine.model_config.vocab_size}"
    )
    if args.lora:
        import jax as _jax_lora

        from dynamo_tpu.models import lora as lora_mod

        adapters = []
        for spec in args.lora:
            name, _, src = spec.partition("=")
            if not src:
                raise SystemExit(f"--lora expects NAME=PATH, got {spec!r}")
            if src.startswith("random:"):
                adapters.append(lora_mod.init_adapter(
                    engine.model_config, name,
                    _jax_lora.random.PRNGKey(int(src.split(":", 1)[1])),
                ))
            else:
                adapters.append(lora_mod.load_peft_adapter(
                    src, engine.model_config, name=name
                ))
        engine.register_adapters(adapters)
        logger.info("LoRA adapters registered: %s", engine.lora_names())

    # KV data plane: prefill-capable workers stage finished prompts here;
    # under multi-host EVERY host (followers too) runs one, serving only its
    # own KV shard — the per-shard point-to-point transfer path
    data_plane = None
    kvbm_enabled = args.kvbm_host_blocks > 0 or args.kvbm_disk_blocks > 0
    if not args.no_kv_data_plane and (
        multihost or kvbm_enabled or args.role in ("prefill", "aggregated")
    ):
        # kvbm_enabled: decode-role workers join the distributed KVBM mesh
        # too — they both pull peers' offloaded blocks and serve their own
        from dynamo_tpu.llm.kv_transfer import KvDataPlaneServer

        data_plane = KvDataPlaneServer(
            advertise_host=args.kv_data_plane_host, port=args.kv_data_plane_port
        )
        await data_plane.start()
        engine.data_plane = data_plane
        engine.host_id = args.host_id
        logger.info("kv data plane listening on %s", data_plane.addr)

    if multihost and args.host_id != 0:
        # follower host: no discovery, no endpoint, no KV events (host-0
        # ownership) — replay the leader's dispatch stream until shutdown
        leader_host = args.coordinator.rsplit(":", 1)[0]
        receiver = StepReceiver(
            leader_host, args.spmd_port,
            host_id=args.host_id,
            data_plane_addr=data_plane.addr if data_plane is not None else "",
        )
        await receiver.connect()
        logger.info(
            "jax follower host %d/%d connected to leader %s:%d",
            args.host_id, args.num_hosts, leader_host, args.spmd_port,
        )
        await engine.run_follower(receiver)
        return

    if spmd is not None:
        logger.info("waiting for %d follower host(s)", args.num_hosts - 1)
        await spmd.wait_for_followers()
        follower_planes = spmd.follower_data_planes
        if data_plane is not None and len(follower_planes) == args.num_hosts - 1 \
                and all(follower_planes.get(h) for h in range(1, args.num_hosts)):
            engine.shard_addrs = [data_plane.addr] + [
                follower_planes[h] for h in range(1, args.num_hosts)
            ]
            logger.info("kv shard rendezvous: %s", engine.shard_addrs)
        # a dead follower wedges every future collective: fail all in-flight
        # requests (so callers migrate, llm/migration.py) and shut the
        # worker down — the lease expires and the frontend drops us
        # (reference analogue: engine-death watchdog -> runtime shutdown,
        # vllm handlers.py:268-273)
        loop = asyncio.get_running_loop()
        shutdown_holder = {}

        def _follower_lost(host_id, why):
            logger.error(
                "follower %d lost (%s): failing active requests and shutting down",
                host_id, why,
            )
            engine._fail_all(f"follower host {host_id} lost: {why}")
            if "shutdown" in shutdown_holder:
                shutdown_holder["shutdown"]()
            # the device thread may be wedged inside a dead collective and
            # block interpreter exit: force it after a drain grace period
            import os
            import threading

            threading.Timer(5.0, lambda: os._exit(1)).start()

        spmd.on_follower_lost = lambda hid, why: loop.call_soon(_follower_lost, hid, why)

    # compile every engine program variant BEFORE joining the control
    # plane: a first-request compile (tens of seconds per full-depth
    # program) after registration starves lease renewal and the frontend
    # drops the worker mid-stream (round-4 e2e failure mode)
    import jax as _jax

    do_warmup = args.warmup == "full" or (
        args.warmup == "auto" and _jax.local_devices()[0].platform != "cpu"
    )
    if do_warmup:
        t0 = time.monotonic()
        n_warm = await engine.warmup()
        logger.info(
            "engine warmup: %d requests, %d programs compiled in %.1fs",
            n_warm, engine.stats()["warmup_compiles"], time.monotonic() - t0,
        )

    cfg = RuntimeConfig.from_settings()
    if args.discovery:
        cfg.discovery_endpoint = args.discovery
    drt = await DistributedRuntime.create(cfg)
    # SIGTERM (planner scale-down) walks the graceful drain, not a hard exit
    drt.install_signal_handlers()
    if spmd is not None:
        shutdown_holder["shutdown"] = drt.shutdown
    if data_plane is not None:
        await data_plane.register(drt)

    kvbm_dist = None
    if engine.kvbm is not None and data_plane is not None:
        # distributed KVBM (reference KvbmLeader/Worker role): announce our
        # tiered blocks namespace-wide so ANY worker (prefill or decode
        # pool) can onboard blocks we offloaded, via the data plane
        from dynamo_tpu.kvbm.distributed import KvbmDistributed

        kvbm_dist = KvbmDistributed(
            drt, engine.kvbm, data_plane, args.namespace, "kvbm",
            drt.instance_id,
        )
        await kvbm_dist.start()
        logger.info("distributed KVBM mesh joined (namespace %s)", args.namespace)
    def role_component(role: str) -> str:
        return args.prefill_component if role == "prefill" else args.component

    # live role state: `morph` (below) re-roles the worker without a
    # restart, so everything role-dependent reads this box, not args.role
    state = {"role": args.role, "card_key": None}
    component = role_component(args.role)
    endpoint = drt.namespace(args.namespace).component(component).endpoint(args.endpoint)

    publisher = None
    if args.kv_events:
        publisher = KvEventPublisher(drt, endpoint, drt.instance_id)
        await publisher.start()
        for ev in pending_events:
            publisher.publish(ev)
        engine.allocator.event_sink = publisher.publish
    else:
        engine.allocator.event_sink = None
    pending_events.clear()

    metrics_pub = WorkerMetricsPublisher(drt, endpoint, drt.instance_id, engine.stats)
    await metrics_pub.start()

    # prometheus surface for the engine counters (system-status /metrics
    # when DYN_SYSTEM_PORT is set — the deploy/metrics grafana dashboard
    # reads these; the discovery metrics topic above feeds router/planner)
    _stats_snap = {"t": 0.0, "v": {}}

    def _snap_stat(k):
        # one engine.stats() per scrape, shared across the gauges (each
        # gauge callback fires within the same render pass)
        now = time.monotonic()
        if now - _stats_snap["t"] > 0.5:
            _stats_snap["v"] = engine.stats()
            _stats_snap["t"] = now
        return float(_stats_snap["v"].get(k, 0) or 0)

    # registry-driven export (runtime/metrics.py METRICS export=True):
    # a stat added to the registry with export=True becomes a
    # dynamo_worker_<name> gauge here without touching this file, and
    # the met-registry dynolint rule retires the 'published on the
    # metrics topic but never exported to prometheus' drift class
    for _stat in worker_exported_stats():
        # registry prepends the "dynamo" prefix -> dynamo_worker_<stat>
        drt.metrics.callback_gauge(
            f"worker_{_stat}", f"engine stat {_stat}",
            (lambda k=_stat: _snap_stat(k)),
        )

    model_name = args.model_name or args.model

    def make_card() -> ModelDeploymentCard:
        # only decode/aggregated workers front the model (reference: the
        # prefill pool is internal, reached by decode orchestration).
        # Publication is deferred until AFTER serve_endpoint below: the
        # card is what makes frontends build a pipeline, so the instance
        # must already be live (and warmup done) when it appears.
        return ModelDeploymentCard(
            name=model_name,
            # the card's tokenizer is the SERVING contract: frontend
            # tokenization and the engine's guided-decoding FSM must agree
            # on the id↔text mapping, so GGUF checkpoints advertise their
            # embedded vocab
            tokenizer=f"gguf:{gguf_path}" if gguf_path is not None else "byte",
            kv_cache_block_size=args.page_size,
            context_length=args.context_length or args.max_model_len,
            migration_limit=args.migration_limit,
            lora_adapters=engine.lora_names(),
        )

    prefill_client = None
    disagg_router = None
    _queue_watch_task = None
    _set_watch_task = None
    if args.role in ("prefill", "decode"):
        # built for BOTH disagg roles: a prefill worker can be morphed
        # into a decode worker at runtime, and then needs the conditional-
        # disagg wiring live (the handler gates on state["role"])
        from dynamo_tpu.llm.disagg import DisaggConfig, DisaggregatedRouter

        prefill_ep = (
            drt.namespace(args.namespace)
            .component(args.prefill_component)
            .endpoint(args.endpoint)
        )
        prefill_client = await prefill_ep.client()
        disagg_router = DisaggregatedRouter(
            DisaggConfig(remote_prefill_threshold_tokens=args.disagg_threshold)
        )

        # conditional-disagg queue guard (reference disagg_router.rs:230
        # "prefill queue below limit"): watch the prefill pool's published
        # engine stats and feed the LEAST-loaded live worker's queue depth
        # into the router — remote prefill stops when the whole pool is
        # backed up
        async def _watch_prefill_queue():
            from dynamo_tpu.llm.kv_router.publisher import METRICS_TOPIC_FMT
            from dynamo_tpu.runtime import codec

            if drt.discovery is None:
                return
            sub = await drt.discovery.subscribe(
                METRICS_TOPIC_FMT.format(
                    namespace=args.namespace, component=args.prefill_component
                )
            )
            depths: dict[int, int] = {}
            announced = False
            async for payload in sub:
                try:
                    msg = codec.unpack(payload)
                    stats = msg.get("stats", {})
                    depths[int(msg["worker_id"])] = int(
                        stats.get(NUM_WAITING_REQS, 0)
                    ) + int(stats.get(NUM_RUNNING_REQS, 0))
                    live = set(prefill_client.instance_ids())
                    for w in list(depths):
                        if w not in live:
                            del depths[w]
                    if depths:
                        disagg_router.update_queue_depth(
                            min(depths[w] for w in depths)
                        )
                    else:
                        # no live publisher left: UNKNOWN, not "empty" —
                        # a fresh depth=0 would green-light remote prefill
                        # into a pool that just vanished
                        disagg_router.invalidate("no live prefill publishers")
                    if not announced:
                        announced = True
                        logger.info(
                            "prefill queue watcher active (%d worker(s), depth=%d)",
                            len(depths), disagg_router.prefill_queue_depth,
                        )
                except Exception:  # noqa: BLE001 — stats are advisory
                    logger.debug("bad prefill metrics message", exc_info=True)

        async def _watch_prefill_set():
            # role-flip staleness guard (docs/disagg_serving.md "Role
            # morphing"): the metrics loop above only wakes on PUBLISHED
            # messages, so when the prefill instance set changes shape —
            # a worker drained, died, or role-morphed in or out — the
            # last depth would otherwise hold sway until the TTL aged it
            # out. Watch the set itself and invalidate immediately.
            prev = set(prefill_client.instance_ids())
            while True:
                await asyncio.sleep(0.25)
                live = set(prefill_client.instance_ids())
                if live != prev:
                    disagg_router.invalidate(
                        f"prefill set changed {len(prev)}->{len(live)}"
                    )
                prev = live

        # owned by main(): strong refs (the event loop keeps only weak
        # refs), cancelled after wait_for_shutdown
        _queue_watch_task = asyncio.get_running_loop().create_task(
            _watch_prefill_queue()
        )
        _set_watch_task = asyncio.get_running_loop().create_task(
            _watch_prefill_set()
        )

    async def handler(request, context):
        if "worker_instance_id" in (request.get("annotations") or []):
            yield {"event": "worker_instance_id", "comment": [f"{drt.instance_id:x}"]}
        if "clear_kv_blocks" in (request.get("annotations") or []):
            # admin flush (reference service_v2.rs:319-339 clear-kv-blocks):
            # drop every unreferenced prefix-cache page (+ KVBM tiers)
            cleared = engine.clear_kv_blocks()
            yield {"event": "clear_kv_blocks", "comment": [str(cleared)]}
            return
        if state["role"] == "decode" and disagg_router is not None:
            from dynamo_tpu.jax_worker.disagg_handler import maybe_remote_prefill

            stream = maybe_remote_prefill(
                engine, prefill_client, disagg_router, request, context
            )
            async for item in stream:
                yield item
            return
        async for item in engine.generate(request, context):
            yield item

    # ---------------------------------------------------------------- #
    # live role morphing (docs/autoscaling.md "Role morphing"): a
    # `morph` control endpoint rides beside `generate`; the planner's
    # re-role arm calls it to convert this worker prefill<->decode
    # in-place — drain via StreamSevered tail-migration, flip the
    # discovery component + model card atomically with the drain, then
    # re-warm the incoming role's compile surfaces.
    # ---------------------------------------------------------------- #
    lanes: dict = {"component": component, "generate": None, "morph": None}

    async def _drop_card():
        if state["card_key"] is None:
            return
        drt._leased_keys.pop(state["card_key"], None)
        if drt.discovery is not None:
            await drt.discovery.delete(state["card_key"])
        state["card_key"] = None

    async def _apply_lanes(role: str):
        """Reconcile discovery registrations to `role`: move generate +
        morph endpoints to the role's component (new lanes born
        `morphing` until the morph commits), move the model card and the
        metrics/KV-events topics with them. Runs as the engine morph's
        on_flip hook — atomic with drain completion — and again (toward
        the OLD role) on rollback."""
        nonlocal metrics_pub, publisher
        from dynamo_tpu.runtime.component import STATE_MORPHING

        new_comp = role_component(role)
        if new_comp != lanes["component"]:
            gen_ep = (drt.namespace(args.namespace)
                      .component(new_comp).endpoint(args.endpoint))
            morph_ep = (drt.namespace(args.namespace)
                        .component(new_comp).endpoint("morph"))
            for name in ("generate", "morph"):
                if lanes[name] is not None:
                    await lanes[name].remove()
            lanes["generate"] = await gen_ep.serve_endpoint(handler)
            await lanes["generate"].set_state(STATE_MORPHING)
            lanes["morph"] = await morph_ep.serve_endpoint(morph_handler)
            await lanes["morph"].set_state(STATE_MORPHING)
            lanes["component"] = new_comp
            # load-signal + KV-event topics are per-component: re-home
            await metrics_pub.close()
            metrics_pub = WorkerMetricsPublisher(
                drt, gen_ep, drt.instance_id, engine.stats)
            await metrics_pub.start()
            if publisher is not None:
                await publisher.close()
                publisher = KvEventPublisher(drt, gen_ep, drt.instance_id)
                await publisher.start()
                engine.allocator.event_sink = publisher.publish
        if role != "prefill" and state["card_key"] is None:
            state["card_key"] = await register_llm(
                (drt.namespace(args.namespace)
                 .component(lanes["component"]).endpoint(args.endpoint)),
                make_card())
        elif role == "prefill":
            await _drop_card()

    async def _set_lane_states(st: str):
        for name in ("generate", "morph"):
            if lanes[name] is not None:
                await lanes[name].set_state(st)

    async def morph_handler(request, context):
        from dynamo_tpu.runtime import faults
        from dynamo_tpu.runtime.component import STATE_MORPHING, STATE_READY

        target = (request or {}).get("target_role", "")
        if target not in ("prefill", "decode"):
            yield {"error": f"bad target_role {target!r}"}
            return
        if args.role == "aggregated":
            yield {"error": "aggregated worker has no role to morph"}
            return
        if state["role"] == target:
            yield {"ok": True, "noop": True, "role": target}
            return
        old_role = state["role"]
        await _set_lane_states(STATE_MORPHING)
        try:
            summary = await engine.morph(
                target, on_flip=lambda: _apply_lanes(target))
        except faults.MorphCrash:
            raise
        except asyncio.CancelledError:
            raise
        except Exception as e:  # noqa: BLE001 — typed result for the planner
            # engine rolled back to old_role (drained sessions already
            # migrating to peers); restore the old lane set routable
            await _apply_lanes(old_role)
            await _set_lane_states(STATE_READY)
            yield {"error": f"morph rolled back: {type(e).__name__}: {e}"}
            return
        state["role"] = target
        await _set_lane_states(STATE_READY)
        yield {"ok": True, **summary}

    lanes["generate"] = await endpoint.serve_endpoint(handler)
    if args.role in ("prefill", "decode"):
        morph_ep = (drt.namespace(args.namespace)
                    .component(component).endpoint("morph"))
        lanes["morph"] = await morph_ep.serve_endpoint(morph_handler)
    if args.role != "prefill":
        state["card_key"] = await register_llm(endpoint, make_card())
    logger.info(
        "jax worker up: model=%s tp=%d role=%s instance=%x",
        model_name,
        args.tp_size,
        state["role"],
        drt.instance_id,
    )
    await drt.wait_for_shutdown()
    for t in (_queue_watch_task, _set_watch_task):
        if t is not None:
            t.cancel()
    # graceful drain: lease revoked first (routers stop picking us), then
    # in-flight streams finish within DYN_RUNTIME_GRACEFUL_SHUTDOWN_TIMEOUT,
    # then survivors are force-cancelled (runtime/component.py close())
    await drt.close()


if __name__ == "__main__":
    asyncio.run(main())
