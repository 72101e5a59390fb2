#!/usr/bin/env python3
"""The comparison that decides `correct`, in a child of its own: the worker
has exited, so the chip is free for it.

    python benchmark/reference.py <cases.json>

(`run.py` writes the file: a run's served prompts and tokens, or, under
`--controls-only`, seeded prompts and continuations with no program behind
them.) Teacher-forced, as `chip_smoke.py` does it (PR 21): the plain reference of
the configuration's family (references/<family>.py) reads the SERVED tokens
and is asked, at each generated position, how good the served next token
was and what its log-probability should have been. Both in deviations of
that position's reference logits over the vocabulary. Prints one JSON object.
"""

from __future__ import annotations

import functools
import importlib
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
PAGE_SIZE = 64

# The served token's reference logit must be within this many deviations of
# the reference maximum: the engine computes in bf16, the reference in
# float32, so near-ties at a 32k vocabulary flip and token equality is not
# demanded. A token from a wrong page lands whole deviations below the
# maximum (4.5, PR 21's sabotaged run); a tie lands within a small fraction.
LOGIT_TOLERANCE_SIGMAS = 0.3
# Served log-probability against the reference's log-softmax at the served
# token: the largest difference over all positions, and the mean difference
# of the worst request. chip_smoke.py's values, set for 28 layers of
# llama3-3b; 16 and 2 layers here accumulate less rounding, so they are kept.
LOGPROB_TOLERANCE_MAX_SIGMAS = 0.25
LOGPROB_TOLERANCE_MEAN_SIGMAS = 0.06
# What they stand between, for the dense family at its cell's size (21 runs
# on 15 seeds of mistral-7b-d16, 16 layers; the int8 control on the same 21
# sets of prompts and tokens; my chip runs, PR 34): token 0.083-0.128 against
# 0.477-0.802, log-probability 0.094-0.151 against 0.465-0.604, its mean in
# the worst request 0.0241-0.0269 (0.0287 in one of seven later runs) against
# 0.1133-0.1305, positions outside 0 against 113-157.
# Mixture of experts: a bf16 hidden state may send a token to another expert
# than float32 does, and with two layers and random weights the other expert
# changes the logits wholesale. Positions where, in some layer, the
# reference's margin between its last chosen and first rejected router logit
# is under this many deviations of the token's router logits are left out and
# counted. On the chip the flips seen at such margins moved a position by up
# to 5.8 deviations; above it nearly every position agreed within 0.15 (token)
# and 0.19 (log-probability) (25 runs of about 1,300 positions, my chip runs,
# PR 23). At most the stated
# share may be left out (29-34% measured at this epsilon).
# A flip also happens, rarely, at a larger margin: in 6 of those 25 runs ONE
# kept position of about 900 lay outside the per-position tolerances (by 0.4
# to 2.2 deviations), in none two. So for a routed family at most this many
# kept positions may lie outside, whatever the run's length; how far outside
# is not held, because a flip's size does not depend on its margin (5.8 among
# the positions left out). A fault of the served path is not one position: a
# wrong page puts every later position of the request outside and moves the
# means (4.5 deviations and a mean of 2.4, PR 21; PERF.md has this cell's
# own sabotaged run).
ROUTER_MARGIN_EPSILON = 0.1
ROUTER_SKIPPED_SHARE_MAX = 0.45
ROUTED_OUTLIERS_MAX = 3
# Since PR 34 a routed family's two means are POOLED over all the run's
# positions (some 1,200 to 1,500 of four requests) and carry names of their
# own (`logprob_gap_pooled_mean*`); the worst REQUEST's means are the dense
# family's alone. One flip of 4.51 deviations among the 76 positions of a
# run's shortest request put that request's mean over all positions at 0.109
# in a sound run (0.1 allowed; seed 1616161613, the same reading on a second
# run on another machine: the re-send is greedy), and a flip of 2.2 at a kept
# position would do the same to the kept mean. A flip's size does not depend
# on the request's length, so a short request's mean is one flip over few
# positions: noise of the number, not of the path. Pooled, 25 sound runs on
# the chip (18 seeds) read 0.0123 to 0.0162 (kept) and 0.0202 to 0.0380 (all);
# the int8 control (`run`, below) on the same 25 sets of prompts and tokens
# 0.0588 to 0.0739 (3.6 times the lower reading) and 0.1015 to 0.1460 (2.7
# times), with 4 to 20 kept positions outside where sound runs have 0 or 1 (my
# chip runs, PR 34). Each limit stands between its two readings, nearer the
# lower.
ROUTED_POOLED_MEAN_SIGMAS = 0.035
ROUTED_POOLED_MEAN_ALL_SIGMAS = 0.07
# What pooling would let through is one request of the four computed a little
# wrong at every position (0.2 deviations: under the per-position tolerance,
# 0.01 on the pooled mean of a request of 60 positions). So a routed family's
# requests are also held one by one, by a number that a flip does not move:
# the MEDIAN log-probability difference over a request's kept positions, for
# requests of at least this many served tokens (a shorter one's median swings:
# 27 such requests read up to 0.0215 where the int8 control reads down to
# 0.0280). The worst such request of a run read 0.0102 to 0.0159 in those 25
# sound runs (73 requests; 0.0169 in one of three later runs), the control's
# 0.0424 to 0.0564 (single requests down to 0.0391): 2.5 times, not the three
# that would make the control this number's upper reading, because two layers
# of int8 at one scale a column are only some 3.5 times bf16's own rounding at
# every position and a median narrows neither side. The control fails by the
# pooled means; this number's upper reading is the fault it is there for, 0.2
# deviations at every position of one request, which reads 0.19 or more
# (selftest.py plants it).
ROUTED_REQUEST_MEDIAN_SIGMAS = 0.03
ROUTED_REQUEST_MEDIAN_MIN_TOKENS = 128
# All of the above are ONE geometry's readings: 8 experts, 2 a token, 2 routed
# layers. The share of positions with a margin under an epsilon is order
# statistics, not a property of a program (`order_statistics_share`, below:
# 0.32 at 8 / 2 / 2 and an epsilon of 0.1, 0.95 at 64 / 4 / 4, 0.998 at 64 / 4
# / 8), so they are the DEFAULTS, under the names `compared` prints, and a
# configuration whose geometry they cannot hold brings its own in its file's
# `judge` key, set from its own readings by the rule files_check.py holds it
# to. With `router_margin_epsilon` 0 every position is kept: the two pooled
# means coincide, the median is over all of a request's positions and
# `router_left_out_share` reads 0; a flip is then judged, not left out, which
# is the only way where the 4th and 5th of 64 router logits lie 0.12
# deviations apart and every position has a near-tie in some layer. Every
# number stays compared, under every geometry: a limit is a number.
ROUTED_LIMITS = {
    "router_margin_epsilon": ROUTER_MARGIN_EPSILON,
    "router_left_out_share": ROUTER_SKIPPED_SHARE_MAX,
    "positions_outside": ROUTED_OUTLIERS_MAX,
    "logprob_gap_pooled_mean_sigmas": ROUTED_POOLED_MEAN_SIGMAS,
    "logprob_gap_pooled_mean_all_sigmas": ROUTED_POOLED_MEAN_ALL_SIGMAS,
    "logprob_gap_request_median_sigmas": ROUTED_REQUEST_MEDIAN_SIGMAS,
}
# A family of many small experts has a near-tie at nearly every position (the
# table above), and judged with its flips in, four requests' numbers swing too
# far to part a bf16 program from an int8 one (PERF.md section 6, PR 36). Its
# configuration says `judge_routing: "forced"`: the served path (or a control)
# hands over the experts it chose, per input position and routed layer, the
# reference uses THOSE (references/<family>.py, `forced`), so no flip is left
# on either side and the four numbers above read rounding alone; and each
# choice is held to the reference's own scores, through its DEFICIT: the
# reference's k-th best router score less the lowest-scored forced expert's,
# in deviations of the token's router logits; 0 where the forced set is the
# reference's own. One limit more, which has no default, since a geometry's
# deficits are its own:
#   router_choice_deficit_max_sigmas  the largest deficit of the run. A number
#                          that precision moves, like the four above, and held
#                          to the same rule: it reads how far the router's
#                          INPUT is off, at every layer and position, whatever
#                          token the head puts first (at 64 experts the int8
#                          control reads 0.09-0.15 where the bf16 control reads
#                          0.014-0.028, on every seed; the log-probability
#                          numbers swing by which token a request settles on:
#                          PERF.md section 6, PR 38). It is the validity check
#                          too: a WRONGLY routed token reads 0.9 or more.
FORCED_LIMITS = ("router_choice_deficit_max_sigmas",)
# A forced family's head is judged over the TOP_TOKENS tokens that whoever is
# judged puts first at a position, not over the first alone: a position's
# number is the mean over them of |its log-probability of the token - the
# reference's|, in deviations of the position's reference logits, and the two
# pooled means and the worst request's median are read from THOSE under the
# names they have. Over the first token alone a request's int8 error is set by
# the token its positions settle on (each int8 head column is off by its own
# fixed amount, and a seeded random model puts few distinct tokens first), so
# four requests left the controls 1.5 to 1.7 times apart at 8 experts a token
# where the rule takes 1.5625 (PR 38; more requests a run: 1.47 to 1.78, PR 39);
# several columns a position average that out (PERF.md section 6, PR 43, has
# the readings at 5 and at 8 that settled the count). One constant of the
# harness, not a key of a configuration; five is what the wire carries
# (README.md, "The top-token contract"). `positions_outside` stays on the
# first token and the largest deficit on the router. A family judged free is
# judged over the served token alone, as ever.
TOP_TOKENS = 5
# what `--control` takes, in the order they are computed: the reference
# itself in the program's place at the program's own precision (bf16: has to
# come out agreeing) and one step down (int8 matrices: NOT agreeing). See
# `control_pass`.
CONTROLS = ("bf16", "int8")


def log_normalizer(rows):
    """log of the sum of exp over the vocabulary, per position."""
    import numpy as np

    top = rows.max(axis=-1)
    return np.log(np.exp(rows - top[:, None]).sum(-1)) + top


def router_margins(router_logits, per_token: int):
    """[layers, tokens, experts] -> per token the smallest margin over the
    layers between the last chosen and the first rejected logit, in deviations
    of the token's router logits: references/moe.py's arithmetic in numpy."""
    import numpy as np

    top = -np.sort(-router_logits, axis=-1)
    margin = (top[..., per_token - 1] - top[..., per_token]) / router_logits.std(axis=-1)
    return margin.min(axis=0)


def order_statistics_share(experts: int, per_token: int, layers: int, epsilon: float,
                           tokens: int = 20000, seed: int = 0) -> float:
    """The share of positions with a margin under `epsilon` in some layer
    that independent normal router logits give: what a sound program reads as
    `router_left_out_share`, whatever it computes (the Mixtral cell's 0.283 to
    0.328 on the chip against 0.32 here)."""
    import numpy as np

    logits = np.random.default_rng(seed).normal(size=(layers, tokens, experts))
    return float((router_margins(logits, per_token) < epsilon).mean())


def judge(cases: dict, rows_of: dict, margins_of: dict, served_of: dict,
          overrides: dict | None = None, deficits_of: dict | None = None) -> dict:
    """The numbers `correct` rests on, each against its limit. `rows_of[name]`
    are the reference's logits at the positions that predict the served
    tokens, `served_of[name]` the (token ids, log-probabilities) judged: the
    program's, or a control's (see `run`). `overrides` is the configuration's
    `judge` key: a routed family's own limits (ROUTED_LIMITS). `deficits_of`,
    under forced routing alone: name -> [layers, input positions], how far
    each forced choice lies under the reference's own; `overrides` then sets
    FORCED_LIMITS too, and `served_of[name]` has a third member, (token ids
    [positions, TOP_TOKENS], their log-probabilities): the tokens the judged
    side puts first at each position, over which the head is judged."""
    import numpy as np

    out, skipped, positions, outliers = {}, 0, 0, 0
    routed = any(m is not None for m in margins_of.values())
    forced = deficits_of is not None
    allowed = set(ROUTED_LIMITS) | set(FORCED_LIMITS if forced else ())
    if forced and not (routed and overrides and set(FORCED_LIMITS) <= set(overrides)
                       and not overrides.get("router_margin_epsilon", 1)
                       and not overrides.get("router_left_out_share", 1)):
        raise ValueError(f"forced routing is for a routed family whose `judge` sets "
                         f"{FORCED_LIMITS} and keeps every position (router_margin_epsilon "
                         f"0, router_left_out_share 0); it sets {overrides}")
    if overrides and not (routed and set(overrides) <= allowed and all(
            isinstance(v, (int, float)) and not isinstance(v, bool)
            for v in overrides.values())):
        raise ValueError(f"`judge` sets a routed family's limits {sorted(allowed)}, "
                         f"each to a number; it sets {overrides}, and the family is "
                         f"{'routed' if routed else 'dense'}")
    limits = {**ROUTED_LIMITS, **(overrides or {})}
    epsilon = limits["router_margin_epsilon"]
    dlp_sum_kept = dlp_sum_all = 0.0  # over all the run's positions
    for name, c in cases.items():
        prompt, rows = c["prompt_ids"], rows_of[name]
        served, served_lp, *tops = served_of[name]
        served = np.asarray(served)
        keep = np.ones(len(served), bool)
        if margins_of[name] is not None:
            keep = margins_of[name] >= epsilon
        std = rows.std(axis=-1)
        top = rows.max(axis=-1)
        got = rows[np.arange(len(served)), served]
        gap = (top - got) / std  # in deviations
        logz = log_normalizer(rows)
        served_lp = np.asarray(served_lp, np.float64)
        finite = bool(np.isfinite(rows).all() and np.isfinite(served_lp).all())
        dlp_first = dlp = np.abs(served_lp - (got - logz)) / std
        if forced:  # the head over the top tokens: a position's mean over them
            if not tops:
                raise ValueError(f"forced routing, and no top {TOP_TOKENS} tokens for {name}")
            top_ids, top_lp = (np.asarray(x) for x in tops[0])
            top_lp = top_lp.astype(np.float64)
            if top_ids.shape != (len(served), TOP_TOKENS) or top_lp.shape != top_ids.shape:
                raise ValueError(f"{name}: top tokens of shape {top_ids.shape} and "
                                 f"{top_lp.shape}, not {(len(served), TOP_TOKENS)}")
            finite = finite and bool(np.isfinite(top_lp).all())
            theirs = np.take_along_axis(rows, top_ids, axis=-1) - logz[:, None]
            dlp = (np.abs(top_lp - theirs) / std[:, None]).mean(axis=-1)
        pos = len(prompt) + np.arange(len(served))  # position written
        at_page = gap[(pos % PAGE_SIZE == 0) & keep]
        skipped += int((~keep).sum())
        outside = int((keep & ((gap > LOGIT_TOLERANCE_SIGMAS)
                               | (dlp_first > LOGPROB_TOLERANCE_MAX_SIGMAS))).sum())
        outliers += outside
        positions += len(served)
        dlp_sum_kept += float(dlp[keep].sum())
        dlp_sum_all += float(dlp.sum())
        out[name] = {
            "prompt_tokens": len(prompt),
            "tokens": len(served),
            "finite": finite,
            "exact_matches": int((rows.argmax(-1) == served).sum()),
            "router_near_ties_left_out": int((~keep).sum()),
            "positions_outside": outside,
            "worst_gap_sigmas": float(gap[keep].max()) if keep.any() else 0.0,
            "worst_gap_sigmas_left_out": float(gap[~keep].max()) if (~keep).any() else None,
            "worst_gap_sigmas_after_page_boundary":
                float(at_page.max()) if at_page.size else None,
            "page_boundaries_crossed": int(at_page.size),
            "logprob_diff_sigmas_max": float(dlp_first[keep].max()) if keep.any() else 0.0,
            "logprob_diff_sigmas_mean": float(dlp[keep].mean()) if keep.any() else 0.0,
            "logprob_diff_sigmas_median": float(np.median(dlp[keep])) if keep.any() else 0.0,
            "logprob_diff_sigmas_mean_all_positions": float(dlp.mean()),
            "reference_logit_std": float(rows.std()),
        }
        if forced:  # the request's own largest deficit (sample_sizes.py reads it)
            out[name]["router_choice_deficit_max_sigmas"] = float(deficits_of[name].max())
            # the means and the median above are over the top tokens; what the
            # first token alone reads (the finding of PRs 38 and 39) beside them
            out[name].update(top_tokens=TOP_TOKENS,
                             logprob_diff_sigmas_first_token_mean=float(dlp_first.mean()),
                             logprob_diff_sigmas_first_token_median=float(np.median(dlp_first)))
    # name -> [value, limit]: every number `correct` rests on (run.py prints
    # them and adds the client's own count of wrong lengths)
    if routed:  # a flip is counted, not sized: no worst token, no worst position
        long_enough = [r["logprob_diff_sigmas_median"] for r in out.values()
                       if r["tokens"] >= ROUTED_REQUEST_MEDIAN_MIN_TOKENS]
        values = {
            "positions_outside": outliers,
            "logprob_gap_pooled_mean_sigmas": dlp_sum_kept / max(positions - skipped, 1),
            "logprob_gap_pooled_mean_all_sigmas": dlp_sum_all / max(positions, 1),
            "logprob_gap_request_median_sigmas": max(long_enough, default=0.0),
            "router_left_out_share": skipped / max(positions, 1),
        }
        if forced:
            values["router_choice_deficit_max_sigmas"] = max(
                r["router_choice_deficit_max_sigmas"] for r in out.values())
        compared = {name: [value, limits[name]] for name, value in values.items()}
    else:  # the worst position, and the worst request's mean
        compared = {
            "positions_outside": [outliers, 0],
            "token_gap_sigmas": [max(r["worst_gap_sigmas"] for r in out.values()),
                                 LOGIT_TOLERANCE_SIGMAS],
            "logprob_gap_sigmas": [max(r["logprob_diff_sigmas_max"] for r in out.values()),
                                   LOGPROB_TOLERANCE_MAX_SIGMAS],
            "logprob_gap_mean_sigmas": [
                max(r["logprob_diff_sigmas_mean"] for r in out.values()),
                LOGPROB_TOLERANCE_MEAN_SIGMAS],
        }
    why = [f"{name} reads {value:.4g}, over its limit {limit}"
           for name, (value, limit) in compared.items() if not value <= limit]
    if not all(r["finite"] for r in out.values()):
        why.append("reference logits or served log-probabilities are not finite")
    return {"agrees": not why, "why_not": why, "compared": compared, "cases": out,
            "routed": routed, "positions": positions,
            "router_near_ties_left_out": skipped,
            "router_margin_epsilon": epsilon}


def int8_weights(params: dict):
    """The control's weights: every matrix (projections, experts, embedding,
    head: the leaves the program's own `--quantize int8` takes) rounded to
    int8 with one scale per output channel and put back in its own type, leaf
    by leaf and in place, so that a second copy of the model never exists.
    Norms and the router stay as they are."""
    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, donate_argnums=0)
    def rounded(w):
        f = w.astype(jnp.float32)
        scale = jnp.max(jnp.abs(f), axis=-2, keepdims=True) / 127.0
        scale = jnp.where(scale > 0, scale, 1.0)
        return (jnp.clip(jnp.round(f / scale), -127, 127) * scale).astype(w.dtype)

    def walk(node, name=""):
        if isinstance(node, dict):
            return {k: walk(v, k) for k, v in node.items()}
        is_matrix = name in ("embed", "lm_head") or name.startswith("w")
        return rounded(node) if node is not None and is_matrix else node

    return walk(params)


def load_model(config_file: str, rehearsal: bool) -> tuple:
    """(configuration, a function that builds the weights, the family's plain
    reference, the file's `judge` key or None, whether its routing is judged
    forced)."""
    import jax

    from worker_entry import build_model_config, load_config

    # the cache directory is the harness's (JAX_COMPILATION_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    cfg_file = load_config(config_file, rehearsal)
    cfg = build_model_config(cfg_file)
    model_mod = importlib.import_module(cfg_file["dataclass"].partition(":")[0])
    reference = importlib.import_module(f"references.{cfg_file['family']}")

    def weights():
        # the same seeded weights the worker built (JaxEngine: init_params
        # from PRNGKey(EngineConfig.seed))
        return model_mod.init_params(cfg, jax.random.PRNGKey(cfg_file["weight_seed"]))

    return (cfg, weights, reference, cfg_file.get("judge"),
            cfg_file.get("judge_routing") == "forced")


def padded_length(cases: dict) -> int:
    """One padded shape for all of `cases`: causal, so the tail is inert."""
    longest = max(len(c["prompt_ids"]) + len(c["served_ids"]) for c in cases.values())
    return -(-longest // 64) * 64


@functools.lru_cache(maxsize=None)
def jitted_logits(reference, cfg, padded: int):
    """One jitted function a reference, configuration and length, however
    many passes call it (a matmul precision in force is part of its key)."""
    import jax

    return jax.jit(functools.partial(reference.logits, cfg=cfg, n_last=padded))


def forward(reference, cfg, params, cases: dict, forced_of: dict | None = None,
            padded: int | None = None) -> tuple:
    """name -> the logits [tokens, vocab] that predict the served tokens,
    teacher-forced on them; the routing margins there (or None); and, where
    the family's reference gives them, the experts it used at every INPUT
    position (prompt + served[:-1]) and their deficits: {"chosen": [layers,
    inputs, k], "deficits": [layers, inputs]}, else None. `forced_of`: name ->
    the experts to force, [inputs][layers][k] (a case's `routed_experts`).
    `padded`: the length every case is padded to (`padded_length` of a larger
    set, so that its parts share one program)."""
    import jax.numpy as jnp
    import numpy as np

    T = padded or padded_length(cases)
    rows_of, margins_of, routing_of = {}, {}, {}
    fwd = jitted_logits(reference, cfg, T)
    for name, c in cases.items():
        prompt, served = c["prompt_ids"], c["served_ids"]
        seq = prompt + served[:-1]
        toks = np.zeros((T,), np.int32)
        toks[: len(seq)] = seq
        more = {}
        if forced_of is not None:
            given = np.asarray(forced_of[name], np.int32).transpose(1, 0, 2)
            forced = np.full((given.shape[0], T, given.shape[2]), -1, np.int32)
            forced[:, : len(seq)] = given  # the padded tail routes freely
            more["forced"] = jnp.asarray(forced)
        logits, margins, *routing = fwd(params, tokens=jnp.asarray(toks), **more)
        # a copy of the rows judged, so that the padded [T, vocab] can go
        rows_of[name] = np.array(np.asarray(logits)[len(prompt) - 1: len(seq)])
        margins_of[name] = (None if margins is None else
                            np.asarray(margins)[len(prompt) - 1: len(seq)])
        routing_of[name] = None if not routing else {
            "chosen": np.array(np.asarray(routing[0])[:, : len(seq)]),
            "deficits": np.array(np.asarray(routing[1])[:, : len(seq)])}
    return rows_of, margins_of, routing_of


def control_choice(low_rows: dict, top: int = 0) -> dict:
    """What a control is judged by: at each position the token it puts
    first and the log-probability it gives that token; with `top` (a forced
    family: TOP_TOKENS) also the `top` tokens it puts first, best first, and
    its log-probabilities of them."""
    import numpy as np

    out = {}
    for name, low in low_rows.items():
        logz = log_normalizer(low)
        out[name] = (low.argmax(-1), low.max(-1) - logz)
        if top:
            part = np.argpartition(low, -top, axis=-1)[:, -top:]
            order = np.argsort(-np.take_along_axis(low, part, -1), axis=-1, kind="stable")
            ids = np.take_along_axis(part, order, -1)
            out[name] += ((ids, np.take_along_axis(low, ids, -1) - logz[:, None]),)
    return out


def control_pass(precision: str, reference, cfg, params, cases: dict, padded: int,
                 top: int = 0):
    """A control: the reference itself in the program's place, at each
    position of the same prompts and tokens, routing by its own scores. It
    does not decode: it is judged by the token it puts first and the
    log-probability it gives that token, a forced family's over the `top`
    tokens it puts first (`control_choice`). Returns (name -> (token ids,
    log-probabilities[, its top tokens]), name -> the experts it used
    [inputs][layers][k] or None): all that is kept of the pass, so its rows go at once.

    bf16: the TPU's default matmul precision, one bf16 pass of the operands
    with float32 accumulation: what the configuration states and a program
    computes in. It has to come out AGREEING: a limit that it breaks is not a
    limit a bf16 program can keep. (On a CPU the default is float32 already,
    and `run` judges nothing.)
    int8: `params` are `int8_weights`' (every matrix rounded to int8, the
    nearest precision below bf16, the step that would tempt a later PR), at
    float32 otherwise. It has to come out NOT agreeing, or the limits let it
    through."""
    import contextlib

    import jax

    lowered = (contextlib.nullcontext() if precision == "bf16"
               else jax.default_matmul_precision("highest"))
    choice, chosen = {}, {}
    for name, c in cases.items():  # case by case: a case's rows are freed before the next
        with lowered:
            rows, _, routing = forward(reference, cfg, params, {name: c}, None, padded)
        choice.update(control_choice(rows, top))
        chosen[name] = (None if routing[name] is None
                        else routing[name]["chosen"].transpose(1, 0, 2))
    return choice, chosen


NOT_ON_A_CPU = ("the reference's device is a CPU, whose default matmul precision is "
                "float32 already: nothing to judge")


def run(case_file: str) -> dict:
    """`cases`: name -> prompt and served tokens of ONE run, judged together;
    under `controls_only` seed -> such a set, each judged on its own, with no
    program's tokens to judge.

    Free routing: one float32 pass a set, which every verdict is read against.
    Forced routing: whoever is judged (the program, a control) brings the
    experts it chose and the TOP_TOKENS tokens it puts first at each position
    with its log-probabilities of them (a case's `served_top_ids` and
    `served_top_logprobs`; a control's `control_choice`), and the float32
    reference is run with THOSE experts forced: one
    pass for the program's `routed_experts`, one more for each control's own
    choices, so a control is judged against the rows its routing gives, as a
    program is.

    Set by set, and a set's rows are freed before the next set's are made: a
    judged position is a row of the vocabulary in float32 (393 KB at 98,304),
    some 2 GB a pass of sixteen requests. The int8 control rounds the weights
    IN PLACE (a second copy of the model does not fit), so its pass over every
    set comes first and keeps tokens, log-probabilities and choices alone;
    then the weights are built anew for everything else."""
    import time

    import jax
    import numpy as np

    with open(case_file) as f:
        spec = json.load(f)
    cfg, weights, reference, overrides, forced = load_model(
        spec["config_file"], spec["rehearsal"])
    only = spec.get("controls_only", False)
    sets = spec["cases"] if only else {"run": spec["cases"]}
    padded = max(padded_length(cases) for cases in sets.values())  # one program for all
    asked = [c for c in CONTROLS if c in (spec.get("controls") or [])]
    top = TOP_TOKENS if forced else 0
    on_cpu = jax.devices()[0].platform == "cpu"
    seconds = {s: {} for s in sets}  # set -> what each part of its judging took

    def timed(s: str, part: str, fn, *args):
        t0 = time.monotonic()
        out = fn(*args)
        seconds[s][part] = seconds[s].get(part, 0.0) + time.monotonic() - t0
        return out

    t0 = time.monotonic()
    params = weights()
    jax.block_until_ready(params)
    weights_s = time.monotonic() - t0
    int8_pass = {}
    if "int8" in asked:
        rounded, params = int8_weights(params), None
        for s, cases in sets.items():
            int8_pass[s] = timed(s, "int8_pass_s", control_pass, "int8", reference, cfg,
                                 rounded, cases, padded, top)
        del rounded
        params = weights()

    def float32(cases: dict, forced_of=None) -> tuple:
        with jax.default_matmul_precision("highest"):
            return forward(reference, cfg, params, cases, forced_of, padded)

    def judged(cases: dict, served_of: dict, against: tuple) -> dict:
        rows_of, margins_of, routing_of = against
        deficits = ({name: r["deficits"] for name, r in routing_of.items()}
                    if forced else None)
        return judge(cases, rows_of, margins_of, served_of, overrides, deficits)

    result = {"controls_only": True, "sets": {}} if only else {}
    for s, cases in sets.items():
        against = None if forced else timed(s, "float32_pass_s", float32, cases)
        one = {"positions": sum(len(c["served_ids"]) for c in cases.values())}
        if not only:
            if forced:
                for key in ("routed_experts", "served_top_ids", "served_top_logprobs"):
                    missing = [name for name, c in cases.items() if not c.get(key)]
                    if missing:  # never judged free, or over one token, for want of them
                        raise ValueError(f"forced routing, and no `{key}` for {missing}")
                against = timed(s, "float32_pass_s", float32, cases,
                                {name: c["routed_experts"] for name, c in cases.items()})
            one = judged(cases, {name: (c["served_ids"], c["served_logprobs"]) + (
                ((c["served_top_ids"], c["served_top_logprobs"]),) if forced else ())
                for name, c in cases.items()}, against)
        verdicts = {}
        for precision in asked:
            if precision == "bf16" and on_cpu:
                verdicts[precision] = {"skipped": NOT_ON_A_CPU}
                continue
            choice, chosen = int8_pass[s] if precision == "int8" else timed(
                s, "bf16_pass_s", control_pass, "bf16", reference, cfg, params, cases, padded,
                top)
            if forced:
                against = timed(s, "float32_pass_s", float32, cases, chosen)
            verdicts[precision] = judged(cases, choice, against)
        if only:
            # whatever epsilon the configuration sets: what its geometry gives,
            # of the last float32 pass made (forced, and no control ran: none)
            margins = None if against is None else list(against[1].values())
            one["margin_under_default_epsilon_share"] = (
                None if margins is None or any(m is None for m in margins)
                else float((np.concatenate(margins) < ROUTER_MARGIN_EPSILON).mean()))
            one.update(verdicts)
        elif verdicts:
            one["controls"] = verdicts
        one["seconds"] = dict(seconds[s], weights_s=weights_s, requests=len(cases))
        if only:
            result["sets"][s] = one
        else:
            result = one
        del against
    dev = jax.devices()[0]
    result["reference_device"] = {"platform": dev.platform, "kind": dev.device_kind}
    return result


if __name__ == "__main__":
    sys.path.insert(0, HERE)
    print(json.dumps(run(sys.argv[1])), flush=True)
