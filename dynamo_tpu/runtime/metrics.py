"""Hierarchical runtime metrics registry.

Role of the reference's MetricsRegistry (lib/runtime/src/metrics.rs,
MetricsRegistryEntry lib.rs:92): every level of the
DRT → namespace → component → endpoint hierarchy can mint Prometheus
counters/gauges/histograms that are automatically labeled with their
position in the hierarchy (dynamo_namespace / dynamo_component /
dynamo_endpoint), all collected into one process-wide registry that the
system status server exports at /metrics. Callback gauges mirror the
reference's metrics callbacks (scrape-time evaluation).
"""

from __future__ import annotations

import threading
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from prometheus_client import (
    CollectorRegistry,
    Counter,
    Gauge,
    Histogram,
    generate_latest,
)

HIERARCHY_LABELS = ("dynamo_namespace", "dynamo_component", "dynamo_endpoint")

# --------------------------------------------------------------------- #
# the metrics contract registry (dynomet)
# --------------------------------------------------------------------- #
# Cross-process metric KEY constants. These keys are spelled at a
# publisher in one process (engine/mocker stats() on the metrics topic)
# and re-spelled at consumers in OTHER processes (gate LoadSignals, the
# disagg router's prefill-queue watcher, the KV router's scheduler) — a
# rename at one end fails silently into fail-open admission, so both
# ends import the spelling from here and the `met-consume-symmetry`
# dynolint rule enforces that every wire-crossing key keeps at least one
# producer and one consumer.

NUM_WAITING_REQS = "num_waiting_reqs"
NUM_RUNNING_REQS = "num_running_reqs"
KV_ACTIVE_BLOCKS = "kv_active_blocks"
KV_TOTAL_BLOCKS = "kv_total_blocks"
SCHED_EST_TTFT_MS = "sched_est_ttft_ms"
SCHED_EST_REQ_MS = "sched_est_req_ms"
SCHED_EST_PREFILL_TOK_S = "sched_est_prefill_tok_s"
SCHED_EST_DECODE_TOK_S = "sched_est_decode_tok_s"

#: The observability contract: every metric key this package emits —
#: stats()-dict keys published on the metrics topic, prometheus names
#: minted by the frontend, and the hand-assembled exposition families.
#: The `met` dynolint pack parses this dict from the AST (never imports
#: this module) and cross-checks every emission and consumption site in
#: the tree against it; `--emit-metrics-docs` renders it into
#: docs/observability.md.
#:
#: Value fields (all literal — the registry must stay literal_eval-able):
#:   kind     counter | gauge | histogram | info ("info" = a string or
#:            structured value that must never be exported as a number)
#:   layer    engine | worker | frontend | kvbm | router | sched |
#:            planner | gate
#:   unit     human unit ("" for plain counts)
#:   help     one-line description (the docs table / HELP text)
#:   labels   bounded label names for labeled exposition families
#:   wire     True when the key crosses a process boundary and the
#:            symmetry rule requires >=1 producer AND >=1 consumer
#:   export   True when jax_worker republishes the stat as a
#:            dynamo_worker_<name> prometheus gauge (worker_exported_
#:            stats() drives that loop, so export drift is structural)
#:   dynamic  True when the key is emitted through an f-string or
#:            comprehension the analyzer cannot resolve (tier names,
#:            merged sub-dicts) — exempts the entry from the
#:            never-emitted check
#:   buckets  histogram bucket upper bounds (exposition + registry must
#:            agree; the kind rule compares ctor buckets against these)
METRICS = {
    # ---- engine core (published on the kv_metrics topic) -------------
    NUM_WAITING_REQS: {"kind": "gauge", "layer": "engine", "unit": "requests", "help": "Requests queued for prefill admission.", "wire": True, "export": True},
    NUM_RUNNING_REQS: {"kind": "gauge", "layer": "engine", "unit": "requests", "help": "Requests occupying decode slots.", "wire": True, "export": True},
    "gpu_cache_usage_perc": {"kind": "gauge", "layer": "engine", "unit": "fraction", "help": "Active KV pages / total pages.", "wire": True, "export": True},
    "request_total_slots": {"kind": "gauge", "layer": "engine", "unit": "slots", "help": "Configured max concurrent sequences.", "wire": True, "export": True},
    "kv_quant": {"kind": "info", "layer": "engine", "help": "KV cache quantization format (bf16/int8/int4)."},
    "kv_pool_bytes": {"kind": "gauge", "layer": "engine", "unit": "bytes", "help": "Resident KV pool bytes including scales.", "export": True},
    # bring-up surface: what the engine runs on and which paths it resolved
    # (chip_smoke.py reads these off the worker metrics topic)
    "device": {"kind": "info", "layer": "engine", "help": "Device as JAX reports it (platform, device_kind, device_count) plus jax/jaxlib/libtpu versions."},
    "attention_impl": {"kind": "info", "layer": "engine", "help": "Implementation (pallas/xla) the decode, prefill and ragged attention ops resolved to, and a stateful family's decode recurrence."},
    "decode_pool_mode": {"kind": "info", "layer": "engine", "help": "KV-write strategy of the fused decode block: always scatter (kept for the readers of stats())."},
    "native_core": {"kind": "info", "layer": "engine", "help": "True when the C++ core (csrc/) is loaded, False on the pure-Python twin."},
    "device_memory": {"kind": "info", "layer": "engine", "help": "Per local device: bytes_limit, bytes_in_use, peak_bytes_in_use from memory_stats()."},
    "weight_bytes_per_device": {"kind": "info", "layer": "engine", "help": "Model weight bytes resident on each local device."},
    "kv_bytes_per_device": {"kind": "info", "layer": "engine", "help": "KV pool bytes resident on each local device."},
    "warmup_s": {"kind": "gauge", "layer": "engine", "unit": "seconds", "help": "Wall-clock of the boot warmup (compiles included)."},
    "warmup_compiles": {"kind": "gauge", "layer": "engine", "unit": "programs", "help": "XLA executables across staged surfaces when warmup finished."},
    "kv_format_mismatches": {"kind": "counter", "layer": "engine", "help": "Typed mixed-precision KV transfer rejections.", "export": True},
    KV_ACTIVE_BLOCKS: {"kind": "gauge", "layer": "engine", "unit": "blocks", "help": "KV blocks referenced by live sequences.", "wire": True, "export": True},
    KV_TOTAL_BLOCKS: {"kind": "gauge", "layer": "engine", "unit": "blocks", "help": "Total KV blocks in the device pool.", "wire": True, "export": True},
    "kv_cached_blocks": {"kind": "gauge", "layer": "engine", "unit": "blocks", "help": "Unreferenced blocks held for prefix reuse.", "export": True},
    "kv_prefix_hit_blocks_total": {"kind": "counter", "layer": "engine", "unit": "blocks", "help": "Prefix-cache block hits at admission.", "export": True},
    "kv_transfers_served": {"kind": "counter", "layer": "engine", "help": "Data-plane KV transfers served to peers.", "export": True},
    "kv_bytes_served": {"kind": "counter", "layer": "engine", "unit": "bytes", "help": "Data-plane KV bytes served to peers.", "export": True},
    "kv_checkpoint_pushes": {"kind": "counter", "layer": "engine", "help": "Session-checkpoint pushes accepted into local tiers.", "export": True},
    "kv_checkpoint_blocks_received": {"kind": "counter", "layer": "engine", "unit": "blocks", "help": "Checkpoint blocks received from peers.", "export": True},
    "kv_pulls_completed": {"kind": "counter", "layer": "engine", "help": "Remote KV pulls completed (disagg onboarding).", "export": True},
    "kv_pages_pulled": {"kind": "counter", "layer": "engine", "unit": "blocks", "help": "KV pages pulled from remote workers.", "export": True},
    "disagg_streamed_handoffs": {"kind": "counter", "layer": "engine", "help": "Streamed prefill->decode handoffs started.", "export": True},
    "disagg_chunks_before_first_token": {"kind": "counter", "layer": "engine", "help": "KV chunks landed before the first decode token.", "export": True},
    "disagg_first_token_before_last_chunk": {"kind": "counter", "layer": "engine", "help": "First tokens emitted while KV chunks were in flight.", "export": True},
    "disagg_streamed_handoff_ratio": {"kind": "gauge", "layer": "engine", "unit": "fraction", "help": "Overlapped handoffs / streamed handoffs.", "export": True},
    "kv_streamed_stages": {"kind": "counter", "layer": "engine", "help": "Prefill-side streamed KV stages shipped.", "export": True},
    "kv_streamed_fallbacks": {"kind": "counter", "layer": "engine", "help": "Streamed handoffs that fell back to blocking pulls.", "export": True},
    "migrations_resumed": {"kind": "counter", "layer": "engine", "help": "Decode streams resumed here after a worker death.", "export": True},
    "migration_replayed_tokens": {"kind": "counter", "layer": "engine", "unit": "tokens", "help": "Tokens re-prefilled to resume migrated streams.", "export": True},
    "resume_source_checkpoint": {"kind": "counter", "layer": "engine", "help": "Migration resumes seeded from a peer checkpoint.", "export": True},
    "resume_source_peer": {"kind": "counter", "layer": "engine", "help": "Migration resumes seeded from live peer KV.", "export": True},
    "resume_source_local": {"kind": "counter", "layer": "engine", "help": "Migration resumes seeded from local tiers.", "export": True},
    "resume_source_recompute": {"kind": "counter", "layer": "engine", "help": "Migration resumes that fully re-prefilled.", "export": True},
    # role morphing (docs/autoscaling.md "Role morphing"): the live
    # prefill<->decode re-role state machine's outcome counters
    "engine_role": {"kind": "info", "layer": "engine", "help": "Current serving role (prefill/decode/both/aggregated)."},
    "morph_state": {"kind": "info", "layer": "engine", "help": "Role-morph state machine position (serving/draining-role/flipped/warm)."},
    "morphs_completed": {"kind": "counter", "layer": "engine", "help": "Live role morphs that reached the new role's warm state.", "export": True},
    "morphs_rolled_back": {"kind": "counter", "layer": "engine", "help": "Role morphs that failed mid-flight and restored the original role.", "export": True},
    "morph_drained_sessions": {"kind": "counter", "layer": "engine", "help": "In-flight sessions severed to peers by morph drains (resumed via migration).", "export": True},
    "morph_last_duration_s": {"kind": "gauge", "layer": "engine", "unit": "seconds", "help": "Wall-clock of the last completed morph (drain + flip + re-warm).", "export": True},
    "kv_skip_ahead_blocks": {"kind": "counter", "layer": "engine", "unit": "blocks", "help": "Prefill blocks skipped via prefix skip-ahead.", "export": True},
    "emit_batches": {"kind": "counter", "layer": "engine", "help": "Token delta batches emitted to streams.", "export": True},
    "emit_tokens": {"kind": "counter", "layer": "engine", "unit": "tokens", "help": "Tokens emitted to streams.", "export": True},
    "mixed_steps": {"kind": "counter", "layer": "engine", "help": "Fused mixed prefill+decode dispatch steps.", "export": True},
    "mixed_steps_piped": {"kind": "counter", "layer": "engine", "help": "Mixed steps that ran as entries of the decode pipeline: dispatched without a drain before them and with their successor queued before their fetch.", "export": True},
    "mixed_real_tokens": {"kind": "counter", "layer": "engine", "unit": "tokens", "help": "Real tokens (prefill chunks and one-token decode rows) that mixed steps packed.", "export": True},
    "mixed_padded_tokens": {"kind": "counter", "layer": "engine", "unit": "slots", "help": "Flat slots of the token buckets that mixed steps ran in: what every dense layer of a mixed step multiplies.", "export": True},
    "mixed_attn_tiles": {"kind": "counter", "layer": "engine", "unit": "tiles", "help": "Q tiles of the ragged attention kernel's grid that mixed steps launched (0 where attention is the XLA reference).", "export": True},
    "mixed_attn_tiles_real": {"kind": "counter", "layer": "engine", "unit": "tiles", "help": "Of those, the tiles that hold a real q row: whole tiles over the packs' rows of more than one token.", "export": True},
    "mixed_rows_decode_kernel": {"kind": "counter", "layer": "engine", "unit": "rows", "help": "One-token rows of mixed steps (decode lanes, verify rows, one-token chunks) that the paged decode kernel served.", "export": True},
    "split_steps": {"kind": "counter", "layer": "engine", "help": "Split prefill/decode dispatch steps.", "export": True},
    "mixed_family_size": {"kind": "gauge", "layer": "engine", "unit": "programs", "help": "Programs in the lean mixed_step family (token buckets x table widths).", "export": True},
    "mixed_family_compiled": {"kind": "gauge", "layer": "engine", "unit": "programs", "help": "Lean mixed_step programs the jit cache holds (the whole family after the first mixed step).", "export": True},
    "expert_rows_routed": {"kind": "counter", "layer": "engine", "unit": "rows", "help": "Expert rows routed per layer: real tokens x experts per token, summed over dispatches (MoE).", "export": True},
    "expert_rows_computed": {"kind": "counter", "layer": "engine", "unit": "rows", "help": "Expert rows the expert matmuls multiply per layer: E x capacity, or the grouped matmul's tile-padded rows (MoE).", "export": True},
    # a family with a recurrent state per lane beside the pages
    # (models/hybrid.py, models/nemotron_h.py, models/exaone_moe.py;
    # docs/hybrid_models.md): exported for those alone
    "state_bytes": {"kind": "gauge", "layer": "engine", "unit": "bytes", "help": "Bytes of the recurrent-state store beside the pages (lanes + 1 slots over the layers that keep a state; the stateful families).", "export": True},
    "state_lanes_reset": {"kind": "counter", "layer": "engine", "unit": "rows", "help": "First chunks dispatched, each of which starts its lane's recurrent state from zero (the stateful families).", "export": True},
    "state_rows_in_place": {"kind": "counter", "layer": "engine", "unit": "rows", "help": "Rows of mixed steps whose recurrence was stepped over the lane in the state store: one token that goes on from the lane's state (the stateful families).", "export": True},
    "state_rows_gathered": {"kind": "counter", "layer": "engine", "unit": "rows", "help": "Rows of mixed steps whose state was gathered out of the store, stepped and scattered back: more than one token, or a sequence's first (the stateful families).", "export": True},
    "mla_rows_absorbed_tokens": {"kind": "counter", "layer": "engine", "unit": "tokens", "help": "Tokens of mixed steps' chunks of more than one token that attended absorbed, in the latent space: chunks of at most ops/latent_attention.absorbed_row_limit tokens, the family's own rule (the latent-attention family).", "export": True},
    "mla_rows_expanded_tokens": {"kind": "counter", "layer": "engine", "unit": "tokens", "help": "Tokens of mixed steps' chunks that attended expanded, their context's cached latents through W_kvb: chunks of more tokens than the rule's limit (the latent-attention family).", "export": True},
    "state_prefix_hits_declined": {"kind": "counter", "layer": "engine", "unit": "blocks", "help": "Cached blocks the prefix index was not allowed to hand a sequence because nobody kept the state that stood at their end (the stateful families).", "export": True},
    "routed_rows_emitted": {"kind": "counter", "layer": "engine", "unit": "rows", "help": "Rows of chosen expert ids sent to requests annotated routed_experts (the stateful families).", "export": True},
    "step_state_bytes": {"kind": "counter", "layer": "engine", "unit": "bytes", "help": "Of step_min_bytes, the recurrent state's: read and written once for each (row, pass) of the entries dispatched (exported once it is not 0: the stateful families).", "export": True},
    "step_expert_bytes": {"kind": "counter", "layer": "engine", "unit": "bytes", "help": "Of step_min_bytes, the weights of the held experts that the entries dispatched touch, in expectation under an even router (exported once it is not 0: the Nemotron-H, EXAONE-MoE and latent-attention families).", "export": True},
    "step_latent_kv_bytes": {"kind": "counter", "layer": "engine", "unit": "bytes", "help": "Of step_min_bytes, the latent cache's: every cached row the entries dispatched read and every new one written, at the row's width in HBM (640 lanes for 512 + 64 values; where the configuration selects, the rows a step MUST read: at most index_topk a row; exported once it is not 0: the latent-attention family).", "export": True},
    "step_latent_kv_expanded_bytes": {"kind": "counter", "layer": "engine", "unit": "bytes", "help": "What the same positions would cost as heads of K and V, the expanded form the latent cache stands for (exported once it is not 0: the latent-attention family).", "export": True},
    "step_index_kv_bytes": {"kind": "counter", "layer": "engine", "unit": "bytes", "help": "Of step_min_bytes, the index keys': in the layers that hold an indexer every row of the entries dispatched reads its context's keys whole and writes its own, index_head_dim values a token (exported once dsa_context_rows is not 0: a latent-attention configuration that selects).", "export": True},
    "dsa_context_rows": {"kind": "counter", "layer": "engine", "unit": "rows", "help": "Cached positions the rows of the entries dispatched had behind them, a layer: what a step without the learned selection would read of the latent cache (exported once it is not 0: a latent-attention configuration that selects).", "export": True},
    "dsa_selected_rows": {"kind": "counter", "layer": "engine", "unit": "rows", "help": "Of dsa_context_rows, the latent rows the same rows had to read: at most index_topk a row, which is what step_latent_kv_bytes and step_min_bytes count (exported once dsa_context_rows is not 0).", "export": True},
    "step_window_kv_bytes": {"kind": "counter", "layer": "engine", "unit": "bytes", "help": "K and V bytes the window layers of the entries dispatched read: at most a window of positions for each (row, pass) (exported once it is not 0: the EXAONE-MoE family).", "export": True},
    "step_window_kv_whole_bytes": {"kind": "counter", "layer": "engine", "unit": "bytes", "help": "K and V bytes the same window layers would read at every row's whole context, as layers that keep pages do (exported once it is not 0: the EXAONE-MoE family).", "export": True},
    # compile telemetry (engine/compile_registry.py, docs/compilation.md):
    # XLA cache growth per staged surface. post_warmup_compiles is THE
    # steady-state contract number — the compile smoke gates on 0
    "compile_surfaces": {"kind": "info", "layer": "engine", "help": "Per-surface XLA executable counts (COMPILE_SURFACES keys).", "dynamic": True},
    "compiled_variants": {"kind": "gauge", "layer": "engine", "unit": "programs", "help": "Total XLA executables across staged surfaces.", "export": True},
    "post_warmup_compiles": {"kind": "counter", "layer": "engine", "unit": "programs", "help": "XLA programs compiled after the warmup baseline (steady-state debt; 0 is the contract).", "export": True},
    "split_real_tokens": {"kind": "counter", "layer": "engine", "unit": "tokens", "help": "Real tokens of the split prefill+decode pairs that served a mixed-shaped step.", "export": True},
    "split_padded_tokens": {"kind": "counter", "layer": "engine", "unit": "slots", "help": "Token slots those split pairs ran in.", "export": True},
    # ---- the engine loop's recorder (engine/recorder.py, docs/observability.md
    # "The engine's iteration"): seconds by phase of an iteration, every pipeline
    # entry's kind, interval and work, the waits ahead of a first token. All
    # monotonic: a share over a window is two differences, the second of
    # engine_clock_s. Emitted by loops over PHASES and STEP_KINDS (dynamic)
    "engine_clock_s": {"kind": "counter", "layer": "engine", "unit": "seconds", "help": "time.monotonic() when the stats were taken: the denominator of every share formed from two of the engine's counters.", "export": True},
    "phase_admit_count": {"kind": "counter", "layer": "engine", "unit": "spans", "help": "Spans of the engine loop's `admit` phase: ordering, page allocation and prefix-cache lookup of waiting requests.", "dynamic": True, "export": True},
    "phase_admit_s": {"kind": "counter", "layer": "engine", "unit": "seconds", "help": "Seconds inside the `admit` phase (profiler span engine.admit).", "dynamic": True, "export": True},
    "phase_admit_slow": {"kind": "counter", "layer": "engine", "unit": "spans", "help": "Spans of the `admit` phase that took 0.5 s or more (each logs one WARNING line naming the phase).", "dynamic": True, "export": True},
    "phase_pack_count": {"kind": "counter", "layer": "engine", "unit": "spans", "help": "Spans of the engine loop's `pack` phase: the host's planning and packing of one entry up to the device call.", "dynamic": True, "export": True},
    "phase_pack_s": {"kind": "counter", "layer": "engine", "unit": "seconds", "help": "Seconds inside the `pack` phase (profiler span engine.pack).", "dynamic": True, "export": True},
    "phase_pack_slow": {"kind": "counter", "layer": "engine", "unit": "spans", "help": "Spans of the `pack` phase that took 0.5 s or more (each logs one WARNING line naming the phase).", "dynamic": True, "export": True},
    "phase_put_count": {"kind": "counter", "layer": "engine", "unit": "spans", "help": "Spans of the engine loop's `put` phase: host-to-device operands of one entry.", "dynamic": True, "export": True},
    "phase_put_s": {"kind": "counter", "layer": "engine", "unit": "seconds", "help": "Seconds inside the `put` phase (profiler span engine.put).", "dynamic": True, "export": True},
    "phase_put_slow": {"kind": "counter", "layer": "engine", "unit": "spans", "help": "Spans of the `put` phase that took 0.5 s or more (each logs one WARNING line naming the phase).", "dynamic": True, "export": True},
    "phase_launch_count": {"kind": "counter", "layer": "engine", "unit": "spans", "help": "Spans of the engine loop's `launch` phase: the jitted calls themselves (they return at once unless the runtime's queue is full or a program compiles).", "dynamic": True, "export": True},
    "phase_launch_s": {"kind": "counter", "layer": "engine", "unit": "seconds", "help": "Seconds inside the `launch` phase (profiler span engine.launch).", "dynamic": True, "export": True},
    "phase_launch_slow": {"kind": "counter", "layer": "engine", "unit": "spans", "help": "Spans of the `launch` phase that took 0.5 s or more (each logs one WARNING line naming the phase).", "dynamic": True, "export": True},
    "phase_fetch_count": {"kind": "counter", "layer": "engine", "unit": "spans", "help": "Spans of the engine loop's `fetch` phase: jax.device_get of an entry's result: the host waiting for the device.", "dynamic": True, "export": True},
    "phase_fetch_s": {"kind": "counter", "layer": "engine", "unit": "seconds", "help": "Seconds inside the `fetch` phase (profiler span engine.fetch).", "dynamic": True, "export": True},
    "phase_fetch_slow": {"kind": "counter", "layer": "engine", "unit": "spans", "help": "Spans of the `fetch` phase that took 0.5 s or more (each logs one WARNING line naming the phase).", "dynamic": True, "export": True},
    "phase_emit_count": {"kind": "counter", "layer": "engine", "unit": "spans", "help": "Spans of the engine loop's `emit` phase: bookkeeping and the streams' frames of a fetched entry.", "dynamic": True, "export": True},
    "phase_emit_s": {"kind": "counter", "layer": "engine", "unit": "seconds", "help": "Seconds inside the `emit` phase (profiler span engine.emit).", "dynamic": True, "export": True},
    "phase_emit_slow": {"kind": "counter", "layer": "engine", "unit": "spans", "help": "Spans of the `emit` phase that took 0.5 s or more (each logs one WARNING line naming the phase).", "dynamic": True, "export": True},
    "phase_wait_count": {"kind": "counter", "layer": "engine", "unit": "spans", "help": "Spans of the engine loop's `wait` phase: the loop idle or yielding to the event loop's other tasks.", "dynamic": True, "export": True},
    "phase_wait_s": {"kind": "counter", "layer": "engine", "unit": "seconds", "help": "Seconds inside the `wait` phase (profiler span engine.wait).", "dynamic": True, "export": True},
    "phase_wait_slow": {"kind": "counter", "layer": "engine", "unit": "spans", "help": "Spans of the `wait` phase that took 0.5 s or more (an idle engine: not logged).", "dynamic": True, "export": True},
    "put_arrays": {"kind": "counter", "layer": "engine", "unit": "arrays", "help": "Host arrays handed to the runtime inside `put` spans, as engine._put counts them: over phase_put_count, the transfers a dispatch makes. A transfer made some other way goes uncounted.", "export": True},
    "step_block_count": {"kind": "counter", "layer": "engine", "unit": "entries", "help": "Pipeline entries fetched: decode blocks (plain, guided, LoRA, spec).", "dynamic": True, "export": True},
    "step_block_interval_s": {"kind": "counter", "layer": "engine", "unit": "seconds", "help": "Ready-to-ready seconds of those entries: the device's time for each plus whatever the device waited for the host inside it.", "dynamic": True, "export": True},
    "step_mixed_count": {"kind": "counter", "layer": "engine", "unit": "entries", "help": "Pipeline entries fetched: mixed steps.", "dynamic": True, "export": True},
    "step_mixed_interval_s": {"kind": "counter", "layer": "engine", "unit": "seconds", "help": "Ready-to-ready seconds of those entries: the device's time for each plus whatever the device waited for the host inside it.", "dynamic": True, "export": True},
    "step_prefill_count": {"kind": "counter", "layer": "engine", "unit": "entries", "help": "Pipeline entries fetched: split prefill dispatches.", "dynamic": True, "export": True},
    "step_prefill_interval_s": {"kind": "counter", "layer": "engine", "unit": "seconds", "help": "Ready-to-ready seconds of those entries: the device's time for each plus whatever the device waited for the host inside it.", "dynamic": True, "export": True},
    "step_stalled_count": {"kind": "counter", "layer": "engine", "unit": "entries", "help": "Pipeline entries whose ready-to-ready interval was 0.5 s or more: a stall of the pipeline (a program compiling inside the launch, the profiler's stop, a paused guest), kept out of their kind's count and seconds.", "export": True},
    "step_stalled_s": {"kind": "counter", "layer": "engine", "unit": "seconds", "help": "Ready-to-ready seconds of those stalled entries.", "export": True},
    "step_model_flops": {"kind": "counter", "layer": "engine", "unit": "flop", "help": "Useful operations of the entries dispatched (models/<family>.step_work): 2 x matmul parameters a real token passes through + attention over its context; no padding, no recomputation.", "export": True},
    "step_min_bytes": {"kind": "counter", "layer": "engine", "unit": "bytes", "help": "Least HBM bytes the entries dispatched must move: per forward pass the weights once (a routed model: the experts its rows can reach), the live context's K and V read once, the new tokens' written.", "export": True},
    "req_admitted": {"kind": "counter", "layer": "engine", "unit": "requests", "help": "Requests admitted to a slot for the first time (a preempted resume is not counted again).", "export": True},
    "req_queue_wait_s": {"kind": "counter", "layer": "engine", "unit": "seconds", "help": "Summed arrival-to-first-admission wait of those requests.", "export": True},
    "req_first_tokens": {"kind": "counter", "layer": "engine", "unit": "requests", "help": "Requests whose first token was handed to their stream.", "export": True},
    "req_admit_to_first_s": {"kind": "counter", "layer": "engine", "unit": "seconds", "help": "Summed first-admission-to-first-token time of those requests.", "export": True},
    "req_stage_http_count": {"kind": "counter", "layer": "engine", "unit": "requests", "help": "Requests that came with a timeline and closed the frontend's `http`: the handler's first line to a validated body (HttpService._accepted).", "dynamic": True, "export": True},
    "req_stage_http_s": {"kind": "counter", "layer": "engine", "unit": "seconds", "help": "Summed seconds of those requests in that stage.", "dynamic": True, "export": True},
    "req_stage_preprocess_count": {"kind": "counter", "layer": "engine", "unit": "requests", "help": "Requests that came with a timeline and closed the frontend's `preprocess`: the chat template and the tokenizer (OpenAIPreprocessor.preprocess_*, stamped by the handler as it returns).", "dynamic": True, "export": True},
    "req_stage_preprocess_s": {"kind": "counter", "layer": "engine", "unit": "seconds", "help": "Summed seconds of those requests in that stage.", "dynamic": True, "export": True},
    "req_stage_route_count": {"kind": "counter", "layer": "engine", "unit": "requests", "help": "Requests that came with a timeline and closed the frontend's `route`: the gate's admission and the router's pick, up to the dial (RequestPlaneClient.call's first line).", "dynamic": True, "export": True},
    "req_stage_route_s": {"kind": "counter", "layer": "engine", "unit": "seconds", "help": "Summed seconds of those requests in that stage.", "dynamic": True, "export": True},
    "req_stage_send_count": {"kind": "counter", "layer": "engine", "unit": "requests", "help": "Requests that came with a timeline and closed the frontend's `send`: the dial where the pool holds no connection, and codec.pack of the request, up to write_frame.", "dynamic": True, "export": True},
    "req_stage_send_s": {"kind": "counter", "layer": "engine", "unit": "seconds", "help": "Summed seconds of those requests in that stage.", "dynamic": True, "export": True},
    "req_stage_hop_count": {"kind": "counter", "layer": "engine", "unit": "requests", "help": "Requests that came with a timeline and closed `hop`: the sender's stamp before write_frame to the server's arrival stamp before codec.unpack (RequestPlaneServer._run_stream), where both clocks are one host's (the boot ids match).", "dynamic": True, "export": True},
    "req_stage_hop_s": {"kind": "counter", "layer": "engine", "unit": "seconds", "help": "Summed seconds of those requests in that stage.", "dynamic": True, "export": True},
    "req_stage_ingest_count": {"kind": "counter", "layer": "engine", "unit": "requests", "help": "Requests that came with a timeline and closed `ingest`: the server's arrival to the slot's (slot.arrival_s): unpack, from_dict, the checks, the prompt's hashing in _new_slot; it runs on the engine's event loop whenever the engine's loop yields (profiler span engine.ingest round the slot's making).", "dynamic": True, "export": True},
    "req_stage_ingest_s": {"kind": "counter", "layer": "engine", "unit": "seconds", "help": "Summed seconds of those requests in that stage.", "dynamic": True, "export": True},
    "req_stage_first_frame_count": {"kind": "counter", "layer": "engine", "unit": "requests", "help": "Requests that came with a timeline and closed `first_frame`: the first token handed to the stream (Recorder.first_token) to its frame's write_frame returning in RequestPlaneServer._run_stream.", "dynamic": True, "export": True},
    "req_stage_first_frame_s": {"kind": "counter", "layer": "engine", "unit": "seconds", "help": "Summed seconds of those requests in that stage.", "dynamic": True, "export": True},
    "req_hop_unmeasured": {"kind": "counter", "layer": "engine", "unit": "requests", "help": "Requests whose timeline came from another host (another boot id): monotonic clocks do not compare, so `hop` was left out.", "export": True},
    "req_blocks_ahead": {"kind": "counter", "layer": "engine", "unit": "entries", "help": "Decode blocks fetched between a request's arrival and its first token, summed over the requests counted in req_first_tokens.", "export": True},
    "req_mixed_ahead": {"kind": "counter", "layer": "engine", "unit": "entries", "help": "Mixed steps fetched between a request's arrival and its first token, its own among them, summed over the requests counted in req_first_tokens.", "export": True},
    "successor_waits": {"kind": "counter", "layer": "engine", "unit": "waits", "help": "Times the step loop held the second entry of the pipeline back until the running entry was about to end (engine._await_successor): one entry in flight, its program's length known, depth 2 allowed.", "export": True},
    "successor_woken": {"kind": "counter", "layer": "engine", "unit": "waits", "help": "Of successor_waits, those inside which a request was admitted: its prompt rides the entry queued at the wait's end.", "export": True},
    "successor_late": {"kind": "counter", "layer": "engine", "unit": "waits", "help": "Of successor_waits, those whose running entry came back (its fetch returned) before the launch of its successor had: the estimate was too long or the margin too short, and the device stood idle between.", "export": True},
    "step_starved_s": {"kind": "counter", "layer": "engine", "unit": "seconds", "help": "Seconds between an entry's end (its fetch's return) and the return of the next entry's launch, summed wherever the launch came second and the engine had not gone idle between: the device waited for the host with work at hand.", "export": True},
    # per-kind fused coverage (docs/ragged_attention.md "Row classes"):
    # proves blended guided/spec/lora traffic actually rides the fused
    # path; the blended-trace CI smoke gates mixed_coverage_frac >= 0.9
    "mixed_rows_plain": {"kind": "counter", "layer": "engine", "unit": "rows", "help": "Plain prefill/decode rows packed into fused mixed steps.", "export": True},
    "mixed_rows_guided": {"kind": "counter", "layer": "engine", "unit": "rows", "help": "Guided (FSM-masked) rows packed into fused mixed steps.", "export": True},
    "mixed_rows_spec": {"kind": "counter", "layer": "engine", "unit": "rows", "help": "Speculative verify rows packed into fused mixed steps.", "export": True},
    "mixed_rows_lora": {"kind": "counter", "layer": "engine", "unit": "rows", "help": "LoRA-adapter rows packed into fused mixed steps.", "export": True},
    "mixed_coverage_frac": {"kind": "gauge", "layer": "engine", "unit": "fraction", "help": "Fused steps / (fused + split) dispatch steps (1.0 before any step).", "export": True},
    # LoRA adapter tier (models/lora_pool.py, docs/multi_lora.md):
    # fixed-slot device stack paging adapters HBM<->host, KVBM-priced
    "lora_pool_slots": {"kind": "gauge", "layer": "engine", "unit": "slots", "help": "Configured device adapter slots (DYN_LORA_POOL_SLOTS).", "export": True},
    "lora_pool_resident": {"kind": "gauge", "layer": "engine", "unit": "adapters", "help": "Adapters currently resident in device slots.", "export": True},
    "lora_pool_known": {"kind": "gauge", "layer": "engine", "unit": "adapters", "help": "Adapters registered in the host roster.", "export": True},
    "lora_pool_hits": {"kind": "counter", "layer": "engine", "help": "Adapter acquires served from a resident slot (hot switch).", "export": True},
    "lora_pool_misses": {"kind": "counter", "layer": "engine", "help": "Adapter acquires that paid a cold onboard.", "export": True},
    "lora_pool_evictions": {"kind": "counter", "layer": "engine", "help": "Unpinned adapters evicted from device slots (LRU).", "export": True},
    "lora_pool_refusals": {"kind": "counter", "layer": "engine", "help": "Typed adapter-tier refusals (pinned-full pool or injected onboard fault).", "export": True},
    "lora_pool_onboard_ms": {"kind": "counter", "layer": "engine", "unit": "ms", "help": "Cumulative adapter onboard latency (mean = sum/count).", "export": True},
    "lora_pool_onboard_count": {"kind": "counter", "layer": "engine", "help": "Adapter onboard operations.", "export": True},
    "lora_pool_onboard_ewma_ms": {"kind": "gauge", "layer": "engine", "unit": "ms", "help": "EWMA adapter onboard latency (cold-switch price).", "dynamic": True, "export": True},
    "guided_requests": {"kind": "counter", "layer": "engine", "help": "Requests decoded under a guided-decoding FSM.", "export": True},
    "lora_requests": {"kind": "counter", "layer": "engine", "help": "Requests served through a LoRA adapter.", "export": True},
    "spec_num_drafts": {"kind": "counter", "layer": "engine", "help": "Speculative draft batches proposed.", "export": True},
    "spec_num_draft_tokens": {"kind": "counter", "layer": "engine", "unit": "tokens", "help": "Speculative tokens proposed by the draft model.", "export": True},
    "spec_num_accepted_tokens": {"kind": "counter", "layer": "engine", "unit": "tokens", "help": "Speculative tokens accepted by verification.", "export": True},
    "spec_mean_accepted_len": {"kind": "gauge", "layer": "engine", "unit": "tokens", "help": "Mean accepted length per draft (incl. bonus token).", "export": True},
    # ---- dynosched (engine/scheduler/policy.py) ----------------------
    "sched_policy": {"kind": "info", "layer": "sched", "help": "Active scheduling policy name."},
    "sched_ttft_target_ms": {"kind": "gauge", "layer": "sched", "unit": "ms", "help": "Configured TTFT SLA target.", "export": True},
    "sched_itl_target_ms": {"kind": "gauge", "layer": "sched", "unit": "ms", "help": "Configured ITL SLA target.", "export": True},
    "sched_granted_chunks": {"kind": "counter", "layer": "sched", "help": "Prefill chunks granted by the budgeter.", "export": True},
    "sched_granted_tokens": {"kind": "counter", "layer": "sched", "unit": "tokens", "help": "Prefill tokens granted by the budgeter.", "export": True},
    "sched_deferred_steps": {"kind": "counter", "layer": "sched", "help": "Steps where prefill was deferred for ITL.", "export": True},
    "sched_itl_shrunk_steps": {"kind": "counter", "layer": "sched", "help": "Steps where the chunk budget was shrunk for ITL.", "export": True},
    "sched_deadline_overrides": {"kind": "counter", "layer": "sched", "help": "Deadline-driven priority overrides.", "export": True},
    "sched_starvation_overrides": {"kind": "counter", "layer": "sched", "help": "Starvation-guard priority overrides.", "export": True},
    "sched_pending_deadlines": {"kind": "gauge", "layer": "sched", "help": "Requests with an armed TTFT deadline.", "export": True},
    "sched_cost_observations": {"kind": "counter", "layer": "sched", "help": "Cost-model samples observed.", "export": True},
    "sched_tenants_served": {"kind": "gauge", "layer": "sched", "help": "Distinct tenants the fairness tiebreak has served.", "export": True},
    "sched_last_budget_tokens": {"kind": "gauge", "layer": "sched", "unit": "tokens", "help": "Last step's granted token budget."},
    "sched_last_slack_ms": {"kind": "gauge", "layer": "sched", "unit": "ms", "help": "Last step's tightest deadline slack."},
    SCHED_EST_TTFT_MS: {"kind": "gauge", "layer": "sched", "unit": "ms", "help": "Projected TTFT for one more admitted request — the gate's admission ceiling and the disagg router's routing signal.", "wire": True, "export": True},
    SCHED_EST_REQ_MS: {"kind": "gauge", "layer": "sched", "unit": "ms", "help": "Marginal TTFT cost of one more admitted request (the gate's optimism debt between publishes).", "wire": True, "export": True},
    SCHED_EST_PREFILL_TOK_S: {"kind": "gauge", "layer": "sched", "unit": "tok/s", "help": "Per-worker marginal prefill throughput estimate from the cost-model EWMAs — prices the planner's re-role (morph vs spawn) decision.", "wire": True, "export": True},
    SCHED_EST_DECODE_TOK_S: {"kind": "gauge", "layer": "sched", "unit": "tok/s", "help": "Per-worker marginal decode throughput estimate from the cost-model EWMAs — prices the planner's re-role (morph vs spawn) decision.", "wire": True, "export": True},
    # ---- KVBM tiers / offload / checkpoint (kvbm/) -------------------
    "kvbm_g1_hit_blocks": {"kind": "counter", "layer": "kvbm", "unit": "blocks", "help": "Device prefix-cache hits at admission (G1).", "export": True},
    "kvbm_g1_miss_blocks": {"kind": "counter", "layer": "kvbm", "unit": "blocks", "help": "Device prefix-cache misses at admission (G1).", "export": True},
    "kvbm_onboard_count": {"kind": "counter", "layer": "kvbm", "help": "Tier onboard operations.", "export": True},
    "kvbm_onboard_ms_sum": {"kind": "counter", "layer": "kvbm", "unit": "ms", "help": "Cumulative onboard latency (mean = sum/count).", "export": True},
    "kvbm_onboard_hist": {"kind": "histogram", "layer": "kvbm", "unit": "ms", "help": "Onboard latency histogram (stats-dict blob).", "buckets": (1.0, 5.0, 20.0, 100.0, 500.0)},
    "kvbm_offloaded_blocks": {"kind": "counter", "layer": "kvbm", "unit": "blocks", "help": "Blocks offloaded device->host.", "export": True},
    "kvbm_onboarded_blocks": {"kind": "counter", "layer": "kvbm", "unit": "blocks", "help": "Blocks onboarded back to device.", "export": True},
    "kvbm_disk_evictions": {"kind": "counter", "layer": "kvbm", "help": "Disk-tier evictions.", "dynamic": True, "export": True},
    "kvbm_dropped_blocks": {"kind": "counter", "layer": "kvbm", "unit": "blocks", "help": "Blocks dropped out of the tier chain.", "export": True},
    "kvbm_host_eviction_policy": {"kind": "info", "layer": "kvbm", "help": "Host tier eviction policy name."},
    "kvbm_disk_eviction_policy": {"kind": "info", "layer": "kvbm", "help": "Disk tier eviction policy name."},
    "kvbm_host_blocks": {"kind": "gauge", "layer": "kvbm", "unit": "blocks", "help": "Blocks resident in the host tier (G2).", "dynamic": True, "export": True},
    "kvbm_host_capacity": {"kind": "gauge", "layer": "kvbm", "unit": "blocks", "help": "Host tier capacity.", "dynamic": True},
    "kvbm_host_hits": {"kind": "counter", "layer": "kvbm", "help": "Host tier lookup hits.", "dynamic": True, "export": True},
    "kvbm_host_misses": {"kind": "counter", "layer": "kvbm", "help": "Host tier lookup misses.", "dynamic": True, "export": True},
    "kvbm_host_evictions": {"kind": "counter", "layer": "kvbm", "help": "Host tier evictions.", "dynamic": True, "export": True},
    "kvbm_disk_blocks": {"kind": "gauge", "layer": "kvbm", "unit": "blocks", "help": "Blocks resident in the disk tier (G3).", "dynamic": True, "export": True},
    "kvbm_disk_capacity": {"kind": "gauge", "layer": "kvbm", "unit": "blocks", "help": "Disk tier capacity.", "dynamic": True},
    "kvbm_disk_hits": {"kind": "counter", "layer": "kvbm", "help": "Disk tier lookup hits.", "dynamic": True, "export": True},
    "kvbm_disk_misses": {"kind": "counter", "layer": "kvbm", "help": "Disk tier lookup misses.", "dynamic": True, "export": True},
    "kvbm_host_load_ms_per_block": {"kind": "gauge", "layer": "kvbm", "unit": "ms", "help": "Observed host-tier load cost per block.", "dynamic": True},
    "kvbm_disk_load_ms_per_block": {"kind": "gauge", "layer": "kvbm", "unit": "ms", "help": "Observed disk-tier load cost per block.", "dynamic": True},
    "kvbm_offload_commit_calls": {"kind": "counter", "layer": "kvbm", "help": "Offload commit batches entered.", "export": True},
    "kvbm_offload_gathers": {"kind": "counter", "layer": "kvbm", "help": "Device gathers staged for offload.", "export": True},
    "kvbm_offload_queue_depth": {"kind": "gauge", "layer": "kvbm", "help": "Offload batches waiting in the pipeline.", "export": True},
    "kvbm_offload_staged_blocks": {"kind": "counter", "layer": "kvbm", "unit": "blocks", "help": "Blocks staged for offload.", "export": True},
    "kvbm_offload_batches_dropped": {"kind": "counter", "layer": "kvbm", "help": "Offload batches dropped under backpressure.", "export": True},
    "kvbm_offload_blocks_dropped": {"kind": "counter", "layer": "kvbm", "unit": "blocks", "help": "Blocks dropped under offload backpressure.", "export": True},
    "kvbm_offload_failures": {"kind": "counter", "layer": "kvbm", "help": "Offload batches that failed.", "export": True},
    "kvbm_onboard_recompute_fallbacks": {"kind": "counter", "layer": "kvbm", "help": "Onboards that fell back to recompute.", "export": True},
    "kvbm_onboard_src_local_blocks": {"kind": "counter", "layer": "kvbm", "unit": "blocks", "help": "Onboarded blocks sourced from local tiers.", "export": True},
    "kvbm_onboard_src_peer_blocks": {"kind": "counter", "layer": "kvbm", "unit": "blocks", "help": "Onboarded blocks pulled from peers.", "export": True},
    "kvbm_onboard_src_recompute_blocks": {"kind": "counter", "layer": "kvbm", "unit": "blocks", "help": "Onboard blocks recomputed.", "export": True},
    "kvbm_pending_offloads": {"kind": "gauge", "layer": "kvbm", "help": "Offload futures not yet committed.", "export": True},
    "kvbm_ckpt_blocks_staged": {"kind": "counter", "layer": "kvbm", "unit": "blocks", "help": "Checkpoint blocks staged for replication.", "export": True},
    "kvbm_ckpt_blocks_pushed": {"kind": "counter", "layer": "kvbm", "unit": "blocks", "help": "Checkpoint blocks pushed to replica holders.", "export": True},
    "kvbm_ckpt_bytes_pushed": {"kind": "counter", "layer": "kvbm", "unit": "bytes", "help": "Checkpoint bytes pushed to replica holders.", "export": True},
    "kvbm_ckpt_blocks_dropped": {"kind": "counter", "layer": "kvbm", "unit": "blocks", "help": "Checkpoint blocks dropped (refuse-newest backpressure).", "export": True},
    "kvbm_ckpt_push_failures": {"kind": "counter", "layer": "kvbm", "help": "Checkpoint pushes that failed.", "export": True},
    "kvbm_ckpt_format_refusals": {"kind": "counter", "layer": "kvbm", "help": "Checkpoint pushes refused on KV-format mismatch.", "export": True},
    "kvbm_ckpt_queue_depth": {"kind": "gauge", "layer": "kvbm", "help": "Checkpoint batches waiting to push.", "export": True},
    "kvbm_ckpt_last_peer": {"kind": "info", "layer": "kvbm", "help": "Last checkpoint replica peer address."},
    "kvbm_remote_onboards": {"kind": "counter", "layer": "kvbm", "help": "Onboards served from remote peers.", "export": True},
    "kvbm_remote_blocks_pulled": {"kind": "counter", "layer": "kvbm", "unit": "blocks", "help": "Blocks pulled over the cluster KV fabric.", "export": True},
    "kvbm_peer_bytes_pulled": {"kind": "counter", "layer": "kvbm", "unit": "bytes", "help": "Bytes pulled over the cluster KV fabric.", "export": True},
    "kvbm_peer_pull_failures": {"kind": "counter", "layer": "kvbm", "help": "Peer pulls that failed (quarantine feed).", "export": True},
    "kvbm_peer_pull_ms_sum": {"kind": "counter", "layer": "kvbm", "unit": "ms", "help": "Cumulative peer-pull latency (mean = sum/onboards).", "export": True},
    "kvbm_peer_pull_hist": {"kind": "histogram", "layer": "kvbm", "unit": "ms", "help": "Peer-pull latency histogram (stats-dict blob).", "buckets": (5.0, 20.0, 50.0, 100.0, 250.0, 1000.0)},
    "kvbm_known_remote_blocks": {"kind": "gauge", "layer": "kvbm", "unit": "blocks", "help": "Remote blocks known to the fabric index.", "export": True},
    "kvbm_quarantined_peers": {"kind": "gauge", "layer": "kvbm", "help": "Peers currently quarantined after pull failures.", "export": True},
    "kvbm_known_checkpoint_blocks": {"kind": "gauge", "layer": "kvbm", "unit": "blocks", "help": "Checkpoint blocks known cluster-wide.", "export": True},
    "kvbm_ckpt_ineligible_peers": {"kind": "gauge", "layer": "kvbm", "help": "Peers refused as checkpoint targets (format skew).", "export": True},
    "kvbm_peer_ms_per_block": {"kind": "info", "layer": "kvbm", "unit": "ms", "help": "Per-peer observed pull cost map (addr -> ms/block)."},
    # ---- dynogate (gate/, frontend process) --------------------------
    "gate_enabled": {"kind": "gauge", "layer": "gate", "help": "1 when the admission gate is active."},
    "gate_admitted_total": {"kind": "counter", "layer": "gate", "help": "Requests admitted by the gate."},
    "gate_rejected_total": {"kind": "counter", "layer": "gate", "help": "Requests rejected (429) by the gate."},
    "gate_shed_total": {"kind": "counter", "layer": "gate", "help": "Parked requests shed before admission."},
    "gate_parked_total": {"kind": "counter", "layer": "gate", "help": "Requests parked in the admission queue."},
    "gate_queue_depth": {"kind": "gauge", "layer": "gate", "help": "Requests currently parked at the gate."},
    "gate_rejected_by_reason": {"kind": "info", "layer": "gate", "help": "Rejection counts keyed by reason (stats-dict map)."},
    "gate_retry_after_hist": {"kind": "histogram", "layer": "gate", "unit": "seconds", "help": "Retry-After values handed out (stats-dict blob).", "buckets": (1.0, 2.0, 5.0, 10.0)},
    "gate_per_tenant": {"kind": "info", "layer": "gate", "help": "Bounded per-tenant admit/reject map."},
    "gate_signal_samples": {"kind": "counter", "layer": "gate", "help": "Worker metric samples folded into gate signals."},
    # ---- frontend prometheus exposition (llm/http, llm/migration) ----
    "dynamo_frontend_requests_total": {"kind": "counter", "layer": "frontend", "unit": "requests", "help": "HTTP LLM requests completed.", "labels": ("model", "endpoint", "status"), "wire": True},
    "dynamo_frontend_inflight_requests": {"kind": "gauge", "layer": "frontend", "unit": "requests", "help": "Requests currently being processed.", "labels": ("model", "endpoint")},
    "dynamo_frontend_request_duration_seconds": {"kind": "histogram", "layer": "frontend", "unit": "seconds", "help": "End-to-end request duration.", "labels": ("model", "endpoint"), "wire": True, "buckets": (0.05, 0.1, 0.25, 0.5, 1, 2, 4, 8, 16, 32, 64, 128)},
    "dynamo_frontend_time_to_first_token_seconds": {"kind": "histogram", "layer": "frontend", "unit": "seconds", "help": "Time to first token.", "labels": ("model",), "wire": True, "buckets": (0.01, 0.025, 0.05, 0.1, 0.2, 0.4, 0.8, 1.6, 3.2, 6.4, 12.8)},
    "dynamo_frontend_output_tokens_total": {"kind": "counter", "layer": "frontend", "unit": "tokens", "help": "Generated tokens delivered to clients.", "labels": ("model",), "wire": True},
    "dynamo_frontend_input_tokens_total": {"kind": "counter", "layer": "frontend", "unit": "tokens", "help": "Prompt tokens accepted.", "labels": ("model",), "wire": True},
    "dynamo_frontend_inter_token_latency_seconds": {"kind": "histogram", "layer": "frontend", "unit": "seconds", "help": "Mean inter-token latency per request.", "labels": ("model",), "wire": True, "buckets": (0.002, 0.005, 0.01, 0.02, 0.04, 0.08, 0.16, 0.32, 0.64, 1.28)},
    "dynamo_frontend_client_disconnects_total": {"kind": "counter", "layer": "frontend", "help": "Client disconnects mid-stream.", "labels": ("model",)},
    "dynamo_frontend_stage_seconds": {"kind": "histogram", "layer": "frontend", "unit": "seconds", "help": "Seconds of a request in one stage of its path from the accept to the first token (http, preprocess, route, send, hop, ingest, queue, first, first_frame, sse), observed at its first token.", "labels": ("stage",), "buckets": (0.0001, 0.0003, 0.001, 0.003, 0.01, 0.03, 0.1, 0.3, 1, 3, 10)},
    "dynamo_frontend_tokens_per_frame": {"kind": "histogram", "layer": "frontend", "unit": "tokens", "help": "Generated tokens per streamed delta batch.", "labels": ("model",), "buckets": (1, 2, 4, 8, 16, 32, 64, 128)},
    "dynamo_frontend_migrations_total": {"kind": "counter", "layer": "frontend", "help": "Stream migrations started after worker loss."},
    "dynamo_frontend_migration_replayed_tokens_total": {"kind": "counter", "layer": "frontend", "unit": "tokens", "help": "Tokens replayed into migration retry prompts."},
    "dynamo_frontend_migrations_exhausted_total": {"kind": "counter", "layer": "frontend", "help": "Streams that ran out of migration budget."},
    "dynamo_frontend_gate_admitted_total": {"kind": "counter", "layer": "gate", "help": "Gate admissions (exposition view)."},
    "dynamo_frontend_gate_rejected_total": {"kind": "counter", "layer": "gate", "help": "Gate rejections (exposition view)."},
    "dynamo_frontend_gate_shed_total": {"kind": "counter", "layer": "gate", "help": "Parked requests shed (exposition view)."},
    "dynamo_frontend_gate_queue_depth": {"kind": "gauge", "layer": "gate", "help": "Parked requests right now (exposition view)."},
    "dynamo_frontend_gate_rejected_by_reason_total": {"kind": "counter", "layer": "gate", "help": "Gate rejections by bounded reason.", "labels": ("reason",)},
    "dynamo_frontend_gate_tenant_requests_total": {"kind": "counter", "layer": "gate", "help": "Per-tenant admit/reject counts (bounded tenant set).", "labels": ("tenant", "outcome")},
    "dynamo_frontend_gate_retry_after_seconds": {"kind": "histogram", "layer": "gate", "unit": "seconds", "help": "Retry-After values handed out.", "buckets": (1.0, 2.0, 5.0, 10.0)},
    # ---- KV router / indexer (frontend process) ----------------------
    "index_blocks": {"kind": "gauge", "layer": "router", "unit": "blocks", "help": "Blocks tracked by the KV event index."},
    "index_max_blocks": {"kind": "gauge", "layer": "router", "unit": "blocks", "help": "Index capacity (0 = unbounded)."},
    "index_evicted_blocks": {"kind": "counter", "layer": "router", "unit": "blocks", "help": "Index entries evicted at capacity."},
    "index_mappings": {"kind": "gauge", "layer": "router", "help": "hash->worker mappings held."},
    "index_memory_bytes_estimate": {"kind": "gauge", "layer": "router", "unit": "bytes", "help": "Estimated index memory footprint."},
    "events_applied": {"kind": "counter", "layer": "router", "help": "KV events applied to the index."},
    # ---- vLLM-dialect aliases (read-if-present by protocols) ---------
    "request_active_slots": {"kind": "gauge", "layer": "router", "unit": "slots", "help": "vLLM-dialect alias of num_running_reqs (read if present)."},
    "num_requests_waiting": {"kind": "gauge", "layer": "router", "unit": "requests", "help": "vLLM-dialect alias of num_waiting_reqs (read if present)."},
    "data_parallel_rank": {"kind": "gauge", "layer": "router", "help": "Publisher's data-parallel rank (read if present)."},
    "gpu_prefix_cache_hit_rate": {"kind": "gauge", "layer": "router", "unit": "fraction", "help": "vLLM-dialect prefix hit rate (read if present)."},
    "spec_decode": {"kind": "info", "layer": "router", "help": "Nested speculative-decode stats blob (read if present)."},
    # ---- runtime plumbing (worker process) ---------------------------
    "frames_total": {"kind": "counter", "layer": "worker", "help": "Request-plane frames handled by the endpoint."},
    "items_total": {"kind": "counter", "layer": "worker", "help": "Stream items delivered by the endpoint."},
    "frames_binary": {"kind": "counter", "layer": "worker", "help": "Zero-copy binary frames on the token wire path."},
    "compute_threads": {"kind": "gauge", "layer": "worker", "help": "Compute-pool worker threads."},
    "compute_tasks_run": {"kind": "counter", "layer": "worker", "help": "Tasks run on the compute pool."},
}


def worker_exported_stats() -> Tuple[str, ...]:
    """Stats keys jax_worker republishes as dynamo_worker_<name> prometheus
    gauges (system-status /metrics). Driven by the registry so a key added
    to METRICS with export=True is exported without touching the worker —
    the 'published but never exported' drift class is gone structurally.
    Only scalar kinds are exportable; the registry seeds keep info/
    histogram entries unexported and the met-kind-discipline rule enforces
    it."""
    return tuple(
        name for name, spec in METRICS.items() if spec.get("export")
    )


def metric_spec(name: str) -> Optional[dict]:
    """Registry entry for `name`, or None. Exposition helpers use this to
    keep HELP/TYPE lines consistent with the contract."""
    return METRICS.get(name)


class MetricsRegistry:
    """One node in the metrics hierarchy. The root owns the
    prometheus-client CollectorRegistry; children share it and add labels."""

    def __init__(
        self,
        prefix: str = "dynamo",
        _registry: Optional[CollectorRegistry] = None,
        _labels: Optional[Dict[str, str]] = None,
        _root: Optional["MetricsRegistry"] = None,
    ):
        self.prefix = prefix
        self.registry = _registry or CollectorRegistry()
        self.labels = dict(_labels or {})
        self._root = _root or self
        if _root is None:
            self._metrics: Dict[str, object] = {}
            self._lock = threading.Lock()
            self._callbacks: List[Callable[[], None]] = []

    # -- hierarchy ----------------------------------------------------------
    def child(self, level: str, name: str) -> "MetricsRegistry":
        labels = dict(self.labels)
        labels[level] = name
        return MetricsRegistry(
            self.prefix, _registry=self.registry, _labels=labels, _root=self._root
        )

    def for_namespace(self, name: str) -> "MetricsRegistry":
        return self.child("dynamo_namespace", name)

    def for_component(self, name: str) -> "MetricsRegistry":
        return self.child("dynamo_component", name)

    def for_endpoint(self, name: str) -> "MetricsRegistry":
        return self.child("dynamo_endpoint", name)

    # -- metric constructors -------------------------------------------------
    # every metric carries ALL hierarchy labels ("" when minted above that
    # level): one prometheus collector can then serve the same metric name
    # from any depth, and label arity never conflicts
    def _label_names(self, extra: Sequence[str]) -> Tuple[str, ...]:
        return HIERARCHY_LABELS + tuple(extra)

    def _label_values(self) -> Tuple[str, ...]:
        return tuple(self.labels.get(k, "") for k in HIERARCHY_LABELS)

    def _get_or_create(self, cls, name: str, doc: str, extra_labels: Sequence[str], **kw):
        root = self._root
        full = f"{self.prefix}_{name}"
        names = self._label_names(extra_labels)
        with root._lock:
            cached = root._metrics.get(full)
            if cached is None:
                metric = cls(full, doc, names, registry=self.registry, **kw)
                root._metrics[full] = (metric, names, kw)
                return metric
            metric, cached_names, cached_kw = cached
            if cached_names != names:
                raise ValueError(
                    f"metric {full} already registered with labels "
                    f"{cached_names}, requested {names}"
                )
            if cached_kw != kw:
                raise ValueError(
                    f"metric {full} already registered with options "
                    f"{cached_kw}, requested {kw} (e.g. differing buckets)"
                )
        return metric

    def counter(self, name: str, doc: str = "", extra_labels: Sequence[str] = ()):
        m = self._get_or_create(Counter, name, doc or name, extra_labels)
        return m.labels(*self._label_values()) if not extra_labels else _Partial(m, self._label_values())

    def gauge(self, name: str, doc: str = "", extra_labels: Sequence[str] = ()):
        m = self._get_or_create(Gauge, name, doc or name, extra_labels)
        return m.labels(*self._label_values()) if not extra_labels else _Partial(m, self._label_values())

    def histogram(
        self,
        name: str,
        doc: str = "",
        extra_labels: Sequence[str] = (),
        buckets: Optional[Sequence[float]] = None,
    ):
        kw = {"buckets": tuple(buckets)} if buckets else {}
        m = self._get_or_create(Histogram, name, doc or name, extra_labels, **kw)
        return m.labels(*self._label_values()) if not extra_labels else _Partial(m, self._label_values())

    def callback_gauge(self, name: str, doc: str, fn: Callable[[], float]):
        """Gauge whose value is computed at scrape time (reference metrics
        callbacks): re-evaluated by render()."""
        g = self.gauge(name, doc)
        root = self._root

        def update():
            try:
                g.set(fn())
            except Exception:  # noqa: BLE001 — scrape must not die
                pass

        root._callbacks.append(update)
        return g

    # -- export ---------------------------------------------------------------
    def render(self) -> bytes:
        for cb in self._root._callbacks:
            cb()
        return generate_latest(self.registry)


class _Partial:
    """Metric bound to the hierarchy labels, awaiting the extra labels."""

    def __init__(self, metric, hier_values: Tuple[str, ...]):
        self._metric = metric
        self._hier = hier_values

    def labels(self, *values: str):
        return self._metric.labels(*self._hier, *values)
