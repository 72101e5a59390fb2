#!/usr/bin/env python3
"""BENCHMARK.json against the files it names. Every run of run.py makes
these checks before it starts a process (they read JSON only, and for a
routed family draw some normals: milliseconds), so the driver's own runs
guard the files: a configuration whose width no longer is the published one,
a cut not stated, a cell whose files are missing, a bound outside the
contract's range or a routed family's limits that its geometry or its
readings do not bear ends the run with exit code 2 and no result.
selftest.py makes them too, with what needs the program (the dataclass each
configuration loads into).

    python3 benchmark/files_check.py [configuration file ...]

A file named there (one that no cell uses yet, or a fixture) is held to the
rule for a routed family's own limits, `check_judge`.
"""

from __future__ import annotations

import ast
import json
import math
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from reference import (  # noqa: E402  (no JAX until it runs)
    FORCED_LIMITS, ROUTED_LIMITS, order_statistics_share)

# Each configuration's published sizes, written here from its source and
# not read from its file: a width that differs is a fault of the file.
# `reduced` keys carry the PUBLISHED value; the file must name it too.
PUBLISHED = {
    "mixtral-8x7b-d2": {  # mistralai/Mixtral-8x7B-Instruct-v0.1 config.json
        "hidden_size": 4096, "intermediate_size": 14336, "num_attention_heads": 32,
        "num_key_value_heads": 8, "head_dim": 128, "vocab_size": 32000,
        "rope_theta": 1e6, "rms_norm_eps": 1e-5, "max_position_embeddings": 32768,
        "tie_word_embeddings": False, "num_local_experts": 8,
        "num_experts_per_tok": 2, "num_hidden_layers": 32},
    "mistral-7b-d16": {  # mistralai/Mistral-7B-Instruct-v0.3 config.json
        "hidden_size": 4096, "intermediate_size": 14336, "num_attention_heads": 32,
        "num_key_value_heads": 8, "head_dim": 128, "vocab_size": 32768,
        "rope_theta": 1e6, "rms_norm_eps": 1e-5, "max_position_embeddings": 32768,
        "tie_word_embeddings": False, "sliding_window": None,
        "num_hidden_layers": 32},
}


class BenchmarkFilesError(Exception):
    pass


def need(ok, *what):
    if not ok:
        raise BenchmarkFilesError(" ".join(str(w) for w in what))


# of reference.py:ROUTED_LIMITS two say which positions are judged at all; the
# others are the numbers that a precision moves
WHICH_POSITIONS = ("router_margin_epsilon", "router_left_out_share")
JUDGED_NUMBERS = tuple(k for k in ROUTED_LIMITS if k not in WHICH_POSITIONS)
SOUND_KINDS = ("served", "bf16_control")
RUNS_MIN = 6  # of each side, and the control's no fewer than the sound side's
UPPER_READING_TIMES = 3.0
# a limit is at most this share of the int8 control's lowest reading: fresh
# seeds read the control lower than a dozen did (PERF.md section 6, PR 36:
# 0.0345 over twelve seeds, 0.0205 over twenty-four)
CONTROL_SIDE_SHARE = 0.8
SHARE_TOLERANCE = 0.015  # of a stated order-statistics share (20,000 tokens)
# `judge_routing`: "free" (or absent) judges a routed family with its flips
# in or left out; "forced" hands the served path's expert choices to the
# reference (reference.py:FORCED_LIMITS; README.md "A forced family's limits")
JUDGE_ROUTINGS = ("free", "forced")
# a routed family's geometry, under the keys most public configurations use
# (the catalog beside the `model-configs` guide); a file whose source names
# them otherwise states them under these too
EXPERTS_KEYS = ("num_local_experts", "num_experts", "n_routed_experts")
PER_TOKEN_KEYS = ("num_experts_per_tok",)
DENSE_LAYERS_KEYS = ("num_dense_layers", "first_k_dense_replace")
# where a chip holds a SHARE of every layer's experts (the experts key listed
# in `reduced`): the id of the first one it holds; it holds [first, first + the
# key's count) of the router's published width (README.md, "The reference's
# protocol"); 0 where absent
FIRST_HELD_KEY = "first_expert_held"


def number(x) -> bool:
    return isinstance(x, (int, float)) and not isinstance(x, bool) and math.isfinite(x)


def routed_geometry(name: str, cfg: dict):
    """(the router's width, experts a token, routed layers) from the
    configuration's own keys, or None where it has no experts. The width is
    the experts key's count, but where the file lists that key in `reduced`:
    the key then counts the experts HELD here, a share of every layer's, and
    the router keeps the PUBLISHED count (`published.<key>`) of outputs, which
    is what a reply's expert ids lie under and what order statistics are drawn
    at; the share held is [first, first + held) of them (FIRST_HELD_KEY)."""
    def first(keys, default=None):
        return next((cfg[k] for k in keys if k in cfg), default)

    key = next((k for k in EXPERTS_KEYS if k in cfg), None)
    if key is None:
        return None
    experts = held = cfg[key]
    if key in (cfg.get("reduced") or []):
        experts, at = (cfg.get("published") or {}).get(key), cfg.get(FIRST_HELD_KEY, 0)
        need(all(isinstance(x, int) and not isinstance(x, bool) for x in (experts, held, at))
             and 0 <= at and 0 < held and at + held <= experts,
             name, key, "is listed as reduced: it counts the experts held,", held, "from",
             FIRST_HELD_KEY, at, ", of the router's published width published." + key, experts)
    else:
        need(FIRST_HELD_KEY not in cfg, name, "states", FIRST_HELD_KEY,
             "and does not list", key, "in `reduced`")
    per_token = first(PER_TOKEN_KEYS)
    layers = cfg.get("num_hidden_layers", 0) - first(DENSE_LAYERS_KEYS, 0)
    need(all(isinstance(x, int) and x > 0 for x in (experts, per_token, layers))
         and per_token < experts, name, "experts, experts a token, routed layers:",
         (experts, per_token, layers), "from", EXPERTS_KEYS, PER_TOKEN_KEYS,
         "num_hidden_layers less", DENSE_LAYERS_KEYS)
    return experts, per_token, layers


def told_why(name: str, told: dict, key: str) -> dict:
    """`judge_readings[key]`, which has to give one line of `reason`."""
    entry = told.get(key)
    need(isinstance(entry, dict), name, "judge_readings has no entry for", key)
    why = entry.get("reason")
    need(isinstance(why, str) and why.strip() and "\n" not in why,
         name, "judge_readings", key, "needs one line of `reason`")
    return entry


def told_readings(name: str, told: dict, key: str) -> tuple:
    """(`sound`, `control_int8`) of `judge_readings[key]`: each lowest, highest
    and at least RUNS_MIN runs, the sound side's kind, and no fewer runs of the
    control than sound ones."""
    entry = told_why(name, told, key)
    sound, low = entry.get("sound"), entry.get("control_int8")
    for side in (sound, low):
        need(isinstance(side, dict) and number(side.get("lowest"))
             and number(side.get("highest")) and 0 <= side["lowest"] <= side["highest"]
             and isinstance(side.get("runs"), int) and side["runs"] >= RUNS_MIN,
             name, "judge_readings", key, "needs `sound` and `control_int8`, each with "
             "lowest <= highest and at least", RUNS_MIN, "runs; has", side)
    need(sound.get("kind") in SOUND_KINDS, name, key, "sound.kind is one of", SOUND_KINDS)
    need(low["runs"] >= sound["runs"], name, key, "has", low["runs"],
         "runs of the int8 control for", sound["runs"], "sound ones")
    return sound, low


def reference_takes_forced(family: str) -> bool:
    """Whether references/<family>.py:logits has a `forced` parameter, read
    from its source: importing it would start JAX."""
    try:
        with open(os.path.join(HERE, "references", f"{family}.py")) as f:
            tree = ast.parse(f.read())
    except (OSError, SyntaxError):
        return False
    return any(isinstance(node, ast.FunctionDef) and node.name == "logits"
               and "forced" in [a.arg for a in node.args.args + node.args.kwonlyargs]
               for node in tree.body)


def check_forced(name: str, cfg: dict, limits, geometry) -> None:
    """`judge_routing`: "forced" is for a routed family that keeps every
    position, sets FORCED_LIMITS (judged numbers, which `check_judge`'s rule
    holds to their readings like the rest) and whose reference takes `forced`;
    a family judged free sets none of them."""
    routing = cfg.get("judge_routing", "free")
    need(routing in JUDGE_ROUTINGS, name, "judge_routing is one of", JUDGE_ROUTINGS,
         "and says", routing)
    set_here = [k for k in FORCED_LIMITS if k in (limits or {})]
    if routing == "free":
        need(not set_here, name, "sets", set_here, "and its routing is judged free")
        return
    need(geometry is not None and isinstance(limits, dict)
         and limits.get("router_margin_epsilon") == 0
         and limits.get("router_left_out_share") == 0 and len(set_here) == len(FORCED_LIMITS),
         name, "judge_routing forced is for a routed family whose `judge` keeps every "
         "position (router_margin_epsilon 0, router_left_out_share 0) and sets",
         FORCED_LIMITS, "; judge is", limits)
    need(reference_takes_forced(cfg.get("family", "")), name, "judge_routing forced, and "
         f"references/{cfg.get('family')}.py:logits takes no `forced`")


def check_judge(name: str, cfg: dict) -> None:
    """A routed family's limits, the defaults (reference.py:ROUTED_LIMITS) or
    its own (`judge`), against its geometry and against what they were set from
    (`judge_readings`). Each limit in force stands where PERF.md section 4 says
    a limit stands:

    - the share of positions that order statistics leave out at the epsilon
      in force, for the experts, experts a token and routed layers of the
      configuration's OWN keys (`reference.order_statistics_share`), is no
      higher than the share's limit, or a sound program fails by arithmetic;
      this holds a file without `judge` too. An override of the epsilon or of
      the share gives a reason and states that share;
    - a file with `judge` has, for every number that is compared, its `sound`
      readings (lowest, highest, runs, and their kind: served runs, or the
      bf16 control's where no program can serve the geometry yet), the
      `control_int8` readings on the same prompts and tokens, of no fewer runs,
      and one line of `reason`;
    - the limit is a number (none is left out), above the highest sound
      reading and at most CONTROL_SIDE_SHARE of the lowest control reading:
      room on both sides, and the more of it above the sound one, so at or
      past their geometric mean;
    - for at least one number the control's lowest reading is three times
      the highest sound one or more: an upper reading.

    Every fault of the last two kinds is told, not the first alone. Under
    `judge_routing: "forced"` the same rule holds the same numbers (read with
    no flip left on either side) and FORCED_LIMITS, judged numbers too.
    """
    geometry, limits = routed_geometry(name, cfg), cfg.get("judge")
    if geometry is None:
        need(limits is None, name, "has `judge` and none of", EXPERTS_KEYS)
        need(cfg.get("judge_routing", "free") == "free", name,
             "has `judge_routing` and none of", EXPERTS_KEYS)
        return
    may_set = sorted([*ROUTED_LIMITS, *FORCED_LIMITS])
    need(limits is None or (isinstance(limits, dict) and limits
                            and set(limits) <= set(may_set)
                            and all(number(v) for v in limits.values())),
         name, "judge may set", may_set, "each to a number, and sets", limits)
    check_forced(name, cfg, limits, geometry)
    in_force = {**ROUTED_LIMITS, **(limits or {})}
    epsilon, share_max = in_force["router_margin_epsilon"], in_force["router_left_out_share"]
    need(epsilon >= 0 and 0 <= share_max <= 1,
         name, "router_margin_epsilon", epsilon, "router_left_out_share", share_max)
    # (no margin lies under 0: nothing to draw)
    share = order_statistics_share(*geometry, epsilon) if epsilon else 0.0
    need(share <= share_max, name, "router_left_out_share is held to", share_max,
         "where order statistics leave out", share, "of a sound program's positions, at",
         geometry, "experts, experts a token, routed layers and an epsilon of", epsilon)
    if limits is None:
        return
    told = cfg.get("judge_readings")
    need(isinstance(told, dict), name, "has `judge` and no `judge_readings`")

    for key in WHICH_POSITIONS:
        if key in limits:
            stated = told_why(name, told, key).get("order_statistics_share")
            need(number(stated) and abs(stated - share) <= SHARE_TOLERANCE,
                 name, "judge_readings", key, "order_statistics_share is", stated,
                 "where", geometry, "at an epsilon of", epsilon, "give", share)
    faults, upper_readings = [], 0
    forced = cfg.get("judge_routing") == "forced"
    for key in JUDGED_NUMBERS + (FORCED_LIMITS if forced else ()):
        limit = in_force[key]
        sound, low = told_readings(name, told, key)
        count = key == "positions_outside"  # whole numbers: a run passes at its limit
        middle = math.sqrt(sound["highest"] * low["lowest"])
        for ok, what in (
                (sound["highest"] <= limit if count else sound["highest"] < limit,
                 f"is not above the highest sound reading {sound['highest']}"),
                (limit >= (math.floor(middle) if count else middle),
                 "leaves less room above the sound reading than below the control's: the "
                 f"geometric mean of {sound['highest']} and {low['lowest']} is {middle}"),
                (limit <= CONTROL_SIDE_SHARE * low["lowest"],
                 f"is over {CONTROL_SIDE_SHARE} of the int8 control's lowest reading "
                 f"{low['lowest']}")):
            if not ok:
                faults.append(f"{key}: limit {limit} {what}")
        if low["lowest"] >= UPPER_READING_TIMES * max(sound["highest"], 1 if count else 0):
            upper_readings += 1
    if not upper_readings:
        faults.append(f"in no number does the int8 control read {UPPER_READING_TIMES} times "
                      "the highest sound reading")
    need(not faults, name, "judge_readings: " + "; ".join(faults))


def check_loaded(bench: dict, cfgs: dict, root: str) -> None:
    """`cfgs`: configuration name -> its file's content."""
    for c in bench["configs"]:
        cfg = cfgs[c["name"]]
        check_judge(c["name"], cfg)
        need(cfg["source"] == c["source"] and cfg["reduced"] == c["reduced"],
             c["name"], "source or reduced differ between BENCHMARK.json and", c["file"])
        need(os.path.exists(os.path.join(HERE, "references", cfg["family"] + ".py")),
             c["name"], "has no plain reference references/", cfg["family"])
        need(set(cfg["published"]) == set(c["reduced"]),
             c["name"], "published", sorted(cfg["published"]), "reduced", c["reduced"])
        # a later PR's configuration is not in the table (it may not edit
        # this file): it is held to everything but the table
        for key, value in PUBLISHED.get(c["name"], {}).items():
            if key in c["reduced"]:
                need(cfg["published"][key] == value and cfg[key] != value,
                     c["name"], key, "is listed as reduced from", value)
            else:
                need(cfg[key] == value, c["name"], key, "is", cfg[key], "published", value)
    configs = {c["name"] for c in bench["configs"]}
    cells = {w["name"] for w in bench["workloads"]}
    for w in bench["workloads"]:
        need(w["config"] in configs and w["name"] == f"{w['config']}.{w['traffic']}", w)
        need(os.path.exists(os.path.join(HERE, "traffic", w["traffic"] + ".json")),
             w["name"], "has no traffic file")
        need(w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200, w)
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench["end_to_end"]:
        need(0.01 <= m["bound"] <= 0.1, m["name"], "bound", m["bound"], "outside [0.01, 0.1]")
    reports = {w: {m["name"] for m in bench["end_to_end"] if w in m.get("workloads", cells)}
               for w in cells}
    need(all(len(r - {"setup_s"}) >= 1 and "setup_s" in r for r in reports.values()),
         "every cell reports setup_s and another end-to-end metric:", reports)
    for m in bench["per_layer"]:
        need(m["moves"] in e2e and set(m.get("workloads", [])) <= cells, m)
        # a cell that a metric lists reports the end-to-end metric it moves
        need(all(m["moves"] in reports[w] for w in m.get("workloads", [])), m["name"],
             "lists a cell that does not report", m["moves"])
        need(os.path.exists(os.path.join(HERE, "layer_metrics", m["name"] + ".json")),
             m["name"], "has no reader")


def check(root: str) -> None:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cfgs = {}
    for c in bench["configs"]:
        with open(os.path.join(root, c["file"])) as f:
            cfgs[c["name"]] = json.load(f)
    check_loaded(bench, cfgs, root)


def main(config_files: list) -> int:
    try:
        check(os.path.dirname(HERE))
        for path in config_files:
            with open(path) as f:
                check_judge(path, json.load(f))
    except BenchmarkFilesError as e:
        print(f"BENCHMARK.json or a file it names is at fault: {e}", file=sys.stderr)
        return 2
    print("ok")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
