"""COMPILE_SURFACES: the compile contract, one entry per staged surface.

Every jit/pjit/shard_map/pallas_call-staged computation in engine/, ops/,
models/, llm/, and planner/ is named here with the contract the
`comp-*` dynolint rules enforce:

  module   repo-relative file the staged callsite lives in
  kind     "jit" | "pjit" | "shard_map" | "pallas_call"
  donate   donate_argnums the callsite must declare, () for none.
           Donation is the TPU memory-headroom lever (a decode block
           donates the KV pool so XLA aliases instead of copying ~GBs),
           and also the sharp edge comp-donation-safety guards: reading
           a donated buffer in the caller after the call returns is
           silent wrong data.
  static   static_argnames/static_argnums the callsite must declare.
  axes     operand-shape dimensions that select the compile variant,
           mapped to the bound that keeps the variant space finite.
           Purely documentary (rendered into docs/compilation.md); the
           enforcement lives in comp-shape-bucketing's taint analysis
           against bucketing.BUCKETING_HELPERS.
  warmup   True when the surface serves the request path and must be
           reachable from JaxEngine.warmup's compile drive — a
           serving-reachable variant missing from warmup is a 20-40s
           cold-compile TTFT spike on a live fleet (comp-warmup-coverage).
           False for offline tools (planner profiler) and surfaces only
           reached by KV-transfer RPCs, which compile on first use by
           design.
  dispatch optional alternate caller-side names (the engine stores
           `spec_block` as `self._spec_block_fn`); `_<key>` is always
           accepted without being spelled.
  help     one line for the generated docs table.

Parsed from the AST, NEVER imported (the ENV_REGISTRY / KNOWN_FAULT_POINTS
/ GUARDED_STATE / METRICS discipline: the checker runs on hosts without
jax importable), so every value must stay a pure literal. The runtime
reads its own copy of the surface names through
`JaxEngine._compiled_surfaces` (engine.py) — the comp-surface-registry
rule is what keeps this table and the code from drifting apart.
"""

COMPILE_SURFACES = {
    # ----------------------------------------------------------------- #
    # engine/engine.py — the serving dispatch closures built in _compile()
    # ----------------------------------------------------------------- #
    "decode_block": {
        "module": "dynamo_tpu/engine/engine.py",
        "kind": "jit",
        "donate": (1, 2, 8, 9),
        "static": (),
        "axes": {
            "B": "config.max_num_seqs (fixed lane count)",
            "K": "config.decode_block_steps (fused steps)",
        },
        "warmup": True,
        "help": "K fused decode steps over all lanes, each scattering its "
                "K/V rows into the donated pool; one variant total",
    },
    "spec_block": {
        "module": "dynamo_tpu/engine/engine.py",
        "kind": "jit",
        "donate": (1, 2, 8, 9),
        "static": (),
        "axes": {
            "B": "config.max_num_seqs",
            "S": "config.spec_rounds (draft-verify rounds)",
        },
        "warmup": True,
        "dispatch": ("_spec_block_fn",),
        "help": "speculative decode: S n-gram draft-verify rounds per "
                "dispatch",
    },
    "prefill_batch": {
        "module": "dynamo_tpu/engine/engine.py",
        "kind": "jit",
        "donate": (1, 2, 9),
        "static": (),
        "axes": {
            "lanes": "plan_prefill (1 or per-bucket lane cap)",
            "bucket": "plan_prefill (config.prefill_buckets ladder)",
            "P": "min(next_pow2(pages), config.max_pages_per_seq) + 1",
        },
        "warmup": True,
        "help": "chunked batched prefill; variant per (bucket, lanes, "
                "page-table bucket)",
    },
    "mixed_step": {
        "module": "dynamo_tpu/engine/engine.py",
        "kind": "jit",
        "donate": (1, 2, 4),
        "static": (7,),
        "axes": {
            "N": "bucket_for(tokens, mixed_token_buckets(config)): real "
                 "tokens, at most 4 powers of two from "
                 "MIXED_TOKEN_BUCKET_FLOOR up to config.mixed_max_tokens",
            "R": "mixed_row_bucket(config): config.max_num_seqs * (1 + "
                 "spec_draft_len if spec_mode else 1) + "
                 "config.max_prefill_batch, rounded up to 8 — spec verify "
                 "rows share the lane row budget",
            "P": "config.max_pages_per_seq + 1, the ONE width, where the "
                 "ragged kernel is Pallas and R * P * 4 B <= "
                 "MIXED_TABLE_SMEM_BYTES; else table_rungs: "
                 "min(next_pow2(pages), config.max_pages_per_seq) + 1",
        },
        "warmup": True,
        "help": "ragged prefill+decode fusion over the token dimension "
                "(plain and pure-spec packs; spec lanes pack 1+d verify "
                "rows; decode rows of a piped pack read the decode carry "
                "by lane); the N x P family of a table width is compiled "
                "together at its first use (engine._prime_mixed_family)",
    },
    "mixed_step_variant": {
        "module": "dynamo_tpu/engine/engine.py",
        "kind": "jit",
        "donate": (1, 2, 4),
        "static": (7,),
        "axes": {
            "N": "bucket_for(tokens, mixed_token_buckets(config)): real "
                 "tokens, at most 4 powers of two from "
                 "MIXED_TOKEN_BUCKET_FLOOR up to config.mixed_max_tokens",
            "R": "mixed_row_bucket(config), as mixed_step",
            "P": "as mixed_step: one width under the Pallas ragged "
                 "kernel, else table_rungs",
            "V8": "(vocab_size + 7) // 8 (packed per-row grammar mask; "
                  "all-ones rows are exact no-ops)",
            "rank": "pool r_max (fixed device adapter stack; operand "
                    "present only when adapters are registered)",
        },
        "warmup": True,
        "help": "fused mixed step with per-row FSM mask and adapter-index "
                "operands — guided/lora rows ride the same flat buffer",
    },
    "prefill_batch_mm": {
        "module": "dynamo_tpu/engine/engine.py",
        "kind": "jit",
        "donate": (1, 2, 9),
        "static": (),
        "axes": {
            "lanes": "plan_prefill",
            "bucket": "plan_prefill",
            "E": "vit config.n_patches (fixed embed count)",
        },
        "warmup": True,
        "help": "prefill with multimodal embedding scatter into the token "
                "stream",
    },
    "decode_step_guided": {
        "module": "dynamo_tpu/engine/engine.py",
        "kind": "jit",
        "donate": (1, 2, 8, 10),
        "static": (),
        "axes": {
            "B": "config.max_num_seqs",
            "V8": "(vocab_size + 7) // 8 (packed grammar mask)",
        },
        "warmup": True,
        "help": "single guided-decoding step with grammar-mask logit "
                "filtering",
    },
    "decode_step_guided_lora": {
        "module": "dynamo_tpu/engine/engine.py",
        "kind": "jit",
        "donate": (1, 2, 8, 10),
        "static": (),
        "axes": {
            "B": "config.max_num_seqs",
            "V8": "(vocab_size + 7) // 8",
            "rank": "config.lora_rank (fixed)",
        },
        "warmup": True,
        "help": "guided step through per-lane LoRA deltas",
    },
    "prefill_batch_guided": {
        "module": "dynamo_tpu/engine/engine.py",
        "kind": "jit",
        "donate": (1, 2, 9),
        "static": (),
        "axes": {
            "lanes": "plan_prefill",
            "bucket": "plan_prefill",
            "V8": "(vocab_size + 7) // 8",
        },
        "warmup": True,
        "help": "batched prefill whose last-token logits pass the grammar "
                "mask",
    },
    "decode_block_lora": {
        "module": "dynamo_tpu/engine/engine.py",
        "kind": "jit",
        "donate": (1, 2, 8, 9),
        "static": (),
        "axes": {
            "B": "config.max_num_seqs",
            "K": "config.decode_block_steps",
            "rank": "config.lora_rank (fixed)",
        },
        "warmup": True,
        "help": "K fused decode steps through per-lane LoRA deltas",
    },
    "prefill_batch_lora": {
        "module": "dynamo_tpu/engine/engine.py",
        "kind": "jit",
        "donate": (1, 2, 9),
        "static": (),
        "axes": {
            "lanes": "plan_prefill",
            "bucket": "plan_prefill",
            "rank": "config.lora_rank (fixed)",
        },
        "warmup": True,
        "help": "batched prefill through per-lane LoRA deltas",
    },
    "prefill_single": {
        "module": "dynamo_tpu/engine/engine.py",
        "kind": "jit",
        "donate": (1, 2, 7),
        "static": (),
        "axes": {
            "T": "next_pow2(chunk) rounded to sp/pp unit "
                 "(admission-bounded prompt)",
            "P": "min(next_pow2(pages), config.max_pages_per_seq) + 1",
        },
        "warmup": True,
        "help": "whole-prompt single-sequence prefill through the ring/"
                "pipeline parallel path (compiled only when sp/pp > 1)",
    },
    "patch_lanes": {
        "module": "dynamo_tpu/engine/engine.py",
        "kind": "jit",
        "donate": (),
        "static": (13,),
        "axes": {
            "B": "config.max_num_seqs",
            "layout": "the one layout of a patch's buffer (engine.PATCH_KEYS)",
        },
        "warmup": True,
        "help": "masked on-device swap of per-lane decode state at slot "
                "turnover (no donation: old carry is the fallback for "
                "unmasked lanes)",
    },
    "carry_write": {
        "module": "dynamo_tpu/engine/engine.py",
        "kind": "jit",
        "donate": (),
        "static": (6,),
        "axes": {
            "B": "config.max_num_seqs",
            "R": "the mixed step's row bucket (engine._mixed_row_bucket)",
            "layout": "the step's own buffer (engine.MIXED_KEYS + CARRY_KEYS): "
                      "one a member of the mixed family",
        },
        "warmup": True,
        "dispatch": ("_carry_write",),
        "help": "a piped mixed step's samples into the decode carry by "
                "lane, behind it on the same stream; compiled with the "
                "mixed family (engine._prime_mixed_family)",
    },
    "extract_pages": {
        "module": "dynamo_tpu/engine/engine.py",
        "kind": "jit",
        "donate": (),
        "static": (),
        "axes": {"n": "gather width = len(page_ids) (pow2-bucketed by "
                      "the KV-transfer batcher)"},
        "warmup": False,
        "help": "KV page gather for migration/offload export; reached "
                "only by KV-transfer RPCs, compiles on first transfer",
    },
    "inject_pages": {
        "module": "dynamo_tpu/engine/engine.py",
        "kind": "jit",
        "donate": (0, 1),
        "static": (),
        "axes": {"n": "scatter width = len(page_ids)"},
        "warmup": False,
        "help": "KV page scatter for migration/onboard import; donates "
                "the pool (aliased in-place update)",
    },
    "zero_pool": {
        "module": "dynamo_tpu/ops/kv_quant.py",
        "kind": "jit",
        "donate": (),
        "static": (),
        "axes": {"pool": "one program per engine: the sharded pool's shape"},
        "warmup": False,
        "help": "allocates a mesh-sharded KV pool shard by shard at engine "
                "construction (the whole pool does not fit one device)",
    },
    "dense_leaf": {
        "module": "dynamo_tpu/models/hybrid.py",
        "kind": "jit",
        "donate": (),
        "static": (1, 2),
        "axes": {"shape": "one program per distinct stacked leaf shape "
                          "and dtype (a dozen per model)"},
        "warmup": False,
        "help": "one stacked leaf of seeded random weights at model "
                "construction (hybrid.init_params, nemotron_h."
                "init_params), built in its own dtype with no float32 copy "
                "beside it",
    },
    "expert_stack_leaf": {
        "module": "dynamo_tpu/models/hybrid.py",
        "kind": "jit",
        "donate": (),
        "static": (1, 2, 3, 4),
        "axes": {"shape": "two programs per model: the shapes of an "
                          "expert's matrices into and out of its width"},
        "warmup": False,
        "help": "a [layers, held experts, ...] stack of seeded weights "
                "keyed by each expert's global id, at model construction "
                "(hybrid.init_params, nemotron_h.init_params)",
    },
    # ----------------------------------------------------------------- #
    # ops/ — attention kernels (jit wrappers staging pallas_call bodies)
    # ----------------------------------------------------------------- #
    "paged_attention_decode_pallas": {
        "module": "dynamo_tpu/ops/pallas_paged_attention.py",
        "kind": "jit",
        "donate": (),
        "static": ("interpret",),
        "axes": {
            "B": "caller lane count: decode_block's lanes, or a mixed "
                 "step's row bucket (its one-token rows; every other row "
                 "an empty lane)",
            "pages": "caller page-table bucket",
        },
        "warmup": True,
        "help": "paged flash decode attention over the scattered pool",
    },
    "ragged_attention_kernels": {
        "module": "dynamo_tpu/ops/paged_attention.py",
        "kind": "jit",
        "donate": (),
        "static": ("tile", "long_rows"),
        "axes": {
            "M": "mixed_step's token bucket",
            "R": "mixed_row_bucket(config)",
            "tile": "ragged_tile_q(dtype)",
            "long_rows": "config.max_prefill_batch",
        },
        "warmup": True,
        "help": "a mixed step's attention call where the gate resolves to "
                "the Pallas kernels: one-token rows to "
                "paged_attention_decode_pallas, the rest to "
                "ragged_paged_attention_pallas on the q-tile layout; a jit "
                "of its own so that a step's layers trace and lower it "
                "once",
    },
    "ragged_paged_attention_pallas": {
        "module": "dynamo_tpu/ops/pallas_ragged_attention.py",
        "kind": "jit",
        "donate": (),
        "static": ("interpret",),
        "axes": {
            "N": "tiles * ragged_tile_q(dtype): mixed_step's token bucket "
                 "M with the rows of more than one token laid out to the "
                 "q tile (paged_attention._tiled_layout)",
            "tiles": "paged_attention.ragged_tiles: (M + "
                     "(ragged_tile_q(dtype) - 1) * config.max_prefill_batch)"
                     " / ragged_tile_q(dtype), rounded up",
        },
        "warmup": True,
        "help": "ragged paged attention over a mixed step's rows of more "
                "than one token (its one-token rows are lanes of "
                "paged_attention_decode_pallas, which mixed_step holds "
                "beside it)",
    },
    "paged_prefill_attention_pallas_batched": {
        "module": "dynamo_tpu/ops/pallas_prefill_attention.py",
        "kind": "jit",
        "donate": (),
        "static": ("interpret",),
        "axes": {
            "B": "caller lane count",
            "T": "caller chunk bucket",
        },
        "warmup": True,
        "help": "batched causal prefill attention against the paged pool",
    },
    "delta_step_pallas": {
        "module": "dynamo_tpu/ops/pallas_delta_step.py",
        "kind": "jit",
        "donate": (),
        "static": ("heads", "interpret"),
        "axes": {
            "B": "decode_block's lanes (the hybrid family's decode step "
                 "alone calls it, once a linear layer)",
        },
        "warmup": True,
        "help": "one token of the gated delta rule a lane, the state store "
                "updated in place (aliased to the result, not donated: the "
                "caller's step owns the store)",
    },
    "ring_attention_local": {
        "module": "dynamo_tpu/ops/ring_attention.py",
        "kind": "shard_map",
        "donate": (),
        "static": (),
        "axes": {
            "T/sp": "sequence shard = caller T / config.sp_size",
        },
        "warmup": True,
        "dispatch": ("_ring_attention_local",),
        "help": "sequence-parallel ring attention shard program "
                "(prefill_single path, sp > 1)",
    },
    # ----------------------------------------------------------------- #
    # llm/ — multimodal encoder
    # ----------------------------------------------------------------- #
    "vit_encode": {
        "module": "dynamo_tpu/llm/multimodal.py",
        "kind": "jit",
        "donate": (),
        "static": (),
        "axes": {
            "px": "(num_channels, image_size, image_size) — config-fixed, "
                  "one variant",
        },
        "warmup": True,
        "dispatch": ("_fwd",),
        "help": "ViT image-to-embedding forward; single config-fixed "
                "pixel shape",
    },
    # ----------------------------------------------------------------- #
    # planner/ — offline profiler (not serving-path; no warmup claim)
    # ----------------------------------------------------------------- #
    "profiler_prefill": {
        "module": "dynamo_tpu/planner/profiler.py",
        "kind": "jit",
        "donate": (1, 2),
        "static": (),
        "axes": {"isl": "isl_grid sweep points (offline, one compile per "
                        "grid point by design)"},
        "warmup": False,
        "dispatch": ("prefill",),
        "help": "offline prefill timing probe for the planner's "
                "interpolation tables",
    },
    "profiler_decode_step": {
        "module": "dynamo_tpu/planner/profiler.py",
        "kind": "jit",
        "donate": (1, 2),
        "static": (),
        "axes": {"B": "derived batch per (context, kv_usage) grid point "
                      "(offline sweep)"},
        "warmup": False,
        "dispatch": ("decode_step",),
        "help": "offline batched-decode timing probe",
    },
}
