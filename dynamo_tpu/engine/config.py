"""JAX engine configuration (vLLM-engine-args role for the TPU engine)."""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional


@dataclass
class EngineConfig:
    model: str = "tiny"  # models/registry key or path
    max_num_seqs: int = 64  # decode slot batch
    page_size: int = 64  # tokens per KV page == router block size
    num_pages: int = 2048  # HBM page pool size; 0 = auto-size from free
    # device memory after weights load (engine._auto_num_pages, vLLM's
    # gpu_memory_utilization role; DYN_HBM_UTILIZATION / DYN_HBM_BYTES)
    max_model_len: int = 8192
    max_prefill_chunk: int = 1024  # chunked-prefill bucket cap
    prefill_buckets: tuple = (128, 256, 512, 1024)
    enable_prefix_caching: bool = True
    # fused decode: K steps per dispatch (one host read per K*B tokens);
    # speculated tokens past a stop condition are discarded (bounded waste)
    decode_block_steps: int = 8
    # batched prefill: token budget per dispatch; lanes = budget // bucket
    prefill_batch_tokens: int = 1024
    max_prefill_batch: int = 8
    # weight-only quantization ("int8" | None): halves weight HBM traffic
    # and makes llama3-8b fit a single v5e chip beside a KV pool
    # (models/quant.py; reference analogue: FP8 recipes)
    quantize: Optional[str] = None
    # quantized KV cache ("none" | "int8" | "int4"; None = resolve from
    # DYN_KV_QUANT, default none): pages quantize on write with
    # per-page-per-head scales and dequantize in the XLA gather path (the
    # in-kernel dequant does not compile for a TPU yet: the dispatch gate
    # routes quantized pools to XLA; ops/kv_quant.py, docs/kvbm.md). int8
    # halves / int4 quarters KV bytes per page, so the auto-sized pool
    # holds ~2x/4x the pages — roughly 2x resident sessions at fixed HBM —
    # and every
    # KVBM tier/peer-fabric/disagg transfer shrinks the same way. "none"
    # is the seed's exact fp path (byte-identical streams). Requires
    # tp_size == pp_size == sp_size == 1 (scale sharding is the
    # multi-chip follow-up).
    kv_quant: Optional[str] = None
    # speculative decoding (engine/spec.py; reference SpecDecodeStats
    # contract _core.pyi:269-301). "ngram" = self-drafting prompt-lookup:
    # draft spec_draft_len tokens from the most recent spec_ngram-gram
    # match in a device-resident history ring, verify them all in ONE
    # batched-prefill pass (one weight stream for up to 1+d tokens/lane).
    # Each fused block runs spec_rounds draft-verify rounds.
    spec_mode: Optional[str] = None
    spec_draft_len: int = 4
    spec_ngram: int = 2
    spec_hist: int = 512  # history ring size (tokens) per lane
    spec_rounds: int = 4
    # sampling defaults
    default_temperature: float = 0.0
    seed: int = 0
    # OpenAI penalties window: recent tokens tracked per lane ON DEVICE
    # (static shape; vLLM penalizes the full context — a bounded window
    # is the TPU-shaped approximation, covering the repetition loops
    # penalties exist to break)
    penalty_window: int = 256
    # parallelism (parallel/mesh.py)
    tp_size: int = 1
    dp_size: int = 1
    pp_size: int = 1  # pipeline stages (layers over the pp axis; decode and
    # prefill stream microbatches through parallel/pipeline.py)
    sp_size: int = 1  # sequence-parallel axis (ring-attention prefill)
    # route a fresh prompt through the ring-prefill path when it has at
    # least this many uncached tokens (and sp_size > 1)
    ring_prefill_threshold: int = 512
    # scheduling
    max_queue: int = 4096
    decode_batch_wait_s: float = 0.0  # wait to fill decode batch (0 = greedy)
    # SLA-aware step scheduling (engine/scheduler/, docs/scheduler.md).
    # None = resolve from the DYN_SCHED_POLICY / DYN_SLA_TTFT_MS /
    # DYN_SLA_ITL_MS env knobs; "fifo" preserves the legacy admit-order
    # dispatch bit-for-bit (sole exception: the batch-kind anti-starvation
    # guard, a fairness bug fix active under both policies), "sla" enables
    # the EDF + ITL-budget StepPlanner.
    sched_policy: Optional[str] = None
    ttft_target_ms: Optional[float] = None
    itl_target_ms: Optional[float] = None
    # ragged unified mixed dispatch (ops/pallas_ragged_attention.py,
    # docs/ragged_attention.md): when the planner has BOTH runnable prefill
    # chunks and active decode lanes, pack them into ONE flat ragged token
    # buffer and ONE device call per layer stack (ragged_forward) instead
    # of a prefill dispatch followed by a decode dispatch. Guided rows
    # (packed FSM-mask operand), multi-LoRA rows (adapter-index operand)
    # and speculative verify rows (1+d one-token rows per lane) fuse too;
    # only mm and pp/sp layouts ride their split variants.
    mixed_dispatch: bool = True
    # LoRA adapter tier (models/lora_pool.py, docs/multi_lora.md): device
    # slots in the fixed-size HBM adapter stack; adapters beyond this
    # page in from the host roster on acquire (LRU eviction of unpinned
    # residents). None = resolve from DYN_LORA_POOL_SLOTS (default 8).
    lora_pool_slots: Optional[int] = None
    # flat-token budget of one mixed dispatch, in real tokens: decode rows
    # + granted prefill chunks, in at most four pow2 buckets up to this cap
    # (bucketing.mixed_token_buckets). Bounds the mixed compile-variant
    # space exactly like prefill_buckets bounds prefill's (token bucket x
    # table width, compiled together at first use; the row axis is a
    # single fixed bucket, see engine._mixed_row_bucket).
    mixed_max_tokens: int = 2048
    # KVBM tiers (kvbm/manager.py); 0 disables a tier
    kvbm_host_blocks: int = 0
    kvbm_disk_blocks: int = 0
    kvbm_disk_path: Optional[str] = None
    # durable decode sessions (docs/fault_tolerance.md): commit newly-full
    # generated blocks DURING the step loop (prefix cache + KVBM offload +
    # announcement mesh + session checkpointing see a live session's KV as
    # it grows) instead of only at slot release. None = resolve from
    # DYN_KV_INCREMENTAL_COMMIT (default on). The commit content is
    # byte-identical either way; off restores the release-only arm.
    incremental_commit: Optional[bool] = None
    # serving role (docs/autoscaling.md "Role morphing"): which discovery
    # component this engine's worker registers under — "prefill",
    # "decode", or "both" (colocated). Flipped live by JaxEngine.morph();
    # the worker harness moves the discovery record on the flip.
    role: str = "decode"

    @property
    def max_pages_per_seq(self) -> int:
        return (self.max_model_len + self.page_size - 1) // self.page_size

    @property
    def block_advance(self) -> int:
        """Max tokens one fused block advances a lane: K plain decode
        steps, or spec_rounds draft-verify rounds of up to 1+d tokens."""
        if self.spec_mode:
            return self.spec_rounds * (self.spec_draft_len + 1)
        return self.decode_block_steps
