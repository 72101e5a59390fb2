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
# is two full-depth compiles of start-up (8 to 25 s apiece cold on a v5e).
# A fourth would serve only packs under an eighth of mixed_max_tokens, at
# most a few ms a step on a dense model and nothing on a MoE one.
MIXED_TOKEN_BUCKETS_MAX = 3


def mixed_token_buckets(config, align: int) -> tuple:
    """The flat-token buckets of the mixed step, from the configuration
    alone: powers of two from a floor up to `mixed_max_tokens` (floored to
    the packer's alignment, as plan_mixed's budget is). The floor is the
    smallest power of two that holds a full decode batch (every lane's
    rows, each padded to `align`) beside as much again of prompt, raised
    where needed so that at most MIXED_TOKEN_BUCKETS_MAX buckets are left.
    A step's cost hardly grows with its bucket where attention is ragged
    and the experts multiply routed rows only, so small buckets would buy
    nothing but programs to compile."""
    cap = config.mixed_max_tokens - config.mixed_max_tokens % align
    rows = config.max_num_seqs * (
        1 + (config.spec_draft_len if config.spec_mode else 0)
    )
    floor = max(
        2 * next_pow2(rows * align),
        next_pow2(cap) >> (MIXED_TOKEN_BUCKETS_MAX - 1),
    )
    buckets = []
    while floor < cap:
        buckets.append(floor)
        floor *= 2
    return tuple(buckets) + (cap,)


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
        "bound": "bucket_for(total, mixed_token_buckets(config, align))",
        "returns": "MixedPlan with .bucket token dim",
    },
    "mixed_token_buckets": {
        "module": "dynamo_tpu/engine/bucketing.py",
        "bound": "at most MIXED_TOKEN_BUCKETS_MAX powers of two up to "
                 "config.mixed_max_tokens",
        "returns": "the mixed step's token buckets",
    },
    "table_rungs": {
        "module": "dynamo_tpu/engine/bucketing.py",
        "bound": "pow2 ladder clamped to config.max_pages_per_seq",
        "returns": "page-table widths of the mixed step",
    },
    "ragged_tile_q": {
        "module": "dynamo_tpu/ops/pallas_ragged_attention.py",
        "bound": "dtype-keyed kernel tile constant (8/16/32)",
        "returns": "mixed-dispatch row alignment unit",
    },
}
