"""Shape bucketing: the one module allowed to mint dispatch-shape sizes.

Every integer that becomes a jit-dispatch operand dimension must be
bounded — XLA compiles one program per distinct operand shape, so an
unbounded (request-derived) dimension turns steady-state serving into a
recompile storm: tens of seconds per full-depth program, during which the
step loop is frozen and discovery leases lapse.
The engine's defense is a closed bucket algebra: round UP to the next
power of two, then clamp to a config-derived cap, so the variant space
per surface is O(log(cap)) and warmup can precompile all of it.

`next_pow2` used to be spelled twice (engine/engine.py and
engine/scheduler/policy.py); this module is now the single spelling, and
`BUCKETING_HELPERS` below is the machine-readable registry of every
helper the `comp-shape-bucketing` dynolint rule accepts as a bounded
shape source. The registry is parsed from the AST (never imported) by
`analysis/comp/registry.py` — same contract as ENV_REGISTRY /
KNOWN_FAULT_POINTS / GUARDED_STATE / METRICS — so every value must stay
a pure literal. Registering a helper here is a claim that its RETURN
VALUE is bounded by configuration regardless of its argument; the
comp pack trusts this table, so additions belong in the same review as
the helper's bound proof.
"""

from __future__ import annotations

from typing import Sequence


def next_pow2(n: int) -> int:
    """Smallest power of two >= n (1 for n <= 1).

    Collapses arbitrary integers onto the pow2 ladder, so the variant
    count is logarithmic in the largest value that can reach a dispatch
    site (admission-bounded lengths); page/row dimensions additionally
    clamp with `min(next_pow2(x), cap)` to a config ceiling.
    """
    return 1 << max(n - 1, 0).bit_length()


def bucket_for(n: int, buckets: Sequence[int]) -> int:
    """Smallest configured bucket that holds n (the largest if none do).

    The clamped ladder lookup used for prefill chunk sizing: `buckets`
    comes from config (`prefill_buckets`), so the return value is always
    a member of a config-fixed set — bounded by construction.
    """
    for b in buckets:
        if n <= b:
            return b
    return buckets[-1]


# The mixed step's token axis has at most this many buckets, whatever the
# configuration: its family of programs is compiled together at the first
# mixed step (engine._prime_mixed_family), lean and variant, so each bucket
# is two full-depth programs of start-up (25-30 s apiece cold at 16 layers
# on a v5e, 4-5 s from the cache; a family's members side by side).
# A dense layer multiplies every slot of the bucket, so a bucket twice too
# large doubles the step where the step is over the ridge: at 16 layers of
# Mistral-7B, 36 ms for 1,024 slots beside a weight stream of 9 (PERF.md,
# PR 40). Four buckets reach from the floor to the default
# mixed_max_tokens.
MIXED_TOKEN_BUCKETS_MAX = 4
# Under about this many rows a step is bound by the weights it streams, not
# by what it multiplies (a v5e's ridge is 197 TFLOP/s / 819 GB/s = 240
# rows), and moe._takes_grouped switches to the capacity einsum under
# moe.GROUPED_MIN_TOKENS, the same number: nothing smaller is wanted.
MIXED_TOKEN_BUCKET_FLOOR = 256


def mixed_token_buckets(config) -> tuple:
    """The flat-token buckets of the mixed step, from the configuration
    alone, counted in REAL tokens (the flat buffer is compact: rows back
    to back, models/llama.py:ragged_forward): powers of two from
    MIXED_TOKEN_BUCKET_FLOOR up to `mixed_max_tokens`, which is
    plan_mixed's budget and the last bucket whatever it is; the floor is
    raised where needed so that at most MIXED_TOKEN_BUCKETS_MAX buckets
    are left. A smaller bucket would buy nothing: under the floor the
    weight stream bounds the step."""
    cap = config.mixed_max_tokens
    floor = max(
        MIXED_TOKEN_BUCKET_FLOOR,
        next_pow2(cap) >> (MIXED_TOKEN_BUCKETS_MAX - 1),
    )
    buckets = []
    while floor < cap:
        buckets.append(floor)
        floor *= 2
    return tuple(buckets) + (cap,)


def mixed_row_bucket(config) -> int:
    """The mixed step's ONE row bucket, from the configuration alone: every
    decode lane's rows (1 + spec_draft_len verify rows under spec) and a
    prefill batch of chunks, rounded up to whole sublanes. One bucket, so
    the row axis adds no program; and no power of two, because where
    attention is the Pallas kernels every row of the bucket, packed or
    not, is a grid step of the decode kernel (an empty one copies and
    multiplies nothing) and a row of the tables both kernels prefetch. The
    ragged kernel's q tiles follow the prefill batch alone
    (ops/paged_attention.ragged_tiles)."""
    rows = config.max_num_seqs * (
        1 + (config.spec_draft_len if config.spec_mode else 0)
    ) + config.max_prefill_batch
    return -(-rows // 8) * 8


def table_rungs(max_pages: int) -> tuple:
    """Page-table widths (context pages, without the scratch column) on
    the pow2 ladder clamped to `max_pages`: 1, 2, 4, ..., max_pages."""
    rungs = []
    p = 1
    while p < max_pages:
        rungs.append(p)
        p *= 2
    return tuple(rungs) + (max_pages,)


#: Bounded shape sources the comp-shape-bucketing rule resolves against.
#: Keyed by bare helper name (callsites match with leading underscores
#: stripped, so `self._bucket_for(...)` and `planner.plan_prefill(...)`
#: both resolve). `bound`: what clamps the result. `returns`: what the
#: bounded value is used for at dispatch sites.
BUCKETING_HELPERS = {
    "next_pow2": {
        "module": "dynamo_tpu/engine/bucketing.py",
        "bound": "pow2 ladder over admission-bounded lengths; page/row "
                 "dims additionally clamp min(next_pow2(x), config cap)",
        "returns": "pow2 rounding for token/page/row dimensions",
    },
    "bucket_for": {
        "module": "dynamo_tpu/engine/bucketing.py",
        "bound": "config.prefill_buckets membership",
        "returns": "prefill chunk bucket",
    },
    "plan_prefill": {
        "module": "dynamo_tpu/engine/scheduler/policy.py",
        "bound": "bucket/lanes drawn from the engine's compile-variant "
                 "space (prefill_buckets x {1, lane cap})",
        "returns": "PrefillPlan with .bucket and .lanes dispatch dims",
    },
    "plan_mixed": {
        "module": "dynamo_tpu/engine/scheduler/policy.py",
        "bound": "bucket_for(total, mixed_token_buckets(config))",
        "returns": "MixedPlan with .bucket token dim",
    },
    "mixed_token_buckets": {
        "module": "dynamo_tpu/engine/bucketing.py",
        "bound": "at most MIXED_TOKEN_BUCKETS_MAX powers of two from "
                 "MIXED_TOKEN_BUCKET_FLOOR up to config.mixed_max_tokens",
        "returns": "the mixed step's token buckets",
    },
    "mixed_row_bucket": {
        "module": "dynamo_tpu/engine/bucketing.py",
        "bound": "config.max_num_seqs * (1 + spec_draft_len) + "
                 "config.max_prefill_batch, rounded up to 8",
        "returns": "the mixed step's one row bucket",
    },
    "table_rungs": {
        "module": "dynamo_tpu/engine/bucketing.py",
        "bound": "pow2 ladder clamped to config.max_pages_per_seq",
        "returns": "page-table widths of the mixed step",
    },
}
