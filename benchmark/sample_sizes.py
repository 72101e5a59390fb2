#!/usr/bin/env python3
"""What more judged requests a run would read, from ONE sizing run.

    python3 benchmark/run.py --controls-only --requests 16 --config-file <file> --traffic <mix> --seed ...
    python3 benchmark/sample_sizes.py [--counts 4 8 12 16] <reference_result.json> ...

`run.py:pick_checked`'s picks are nested (the first M of a seed's N picks are
its picks at M) and `reference_result.json` keeps every request's own numbers,
so the judged numbers of both controls at every smaller count follow on the
CPU from the first N requests of each seed: per count and number the lowest
and highest reading of each control over all the seeds of all the files given,
and how many times the bf16 control's highest the int8 control's lowest reads
(what files_check.py:check_judge needs to be 1.5625 or more for every judged
number, 3.0 for one). A run judges four (run.py:CHECKED_REQUESTS): this is the
tool that found more of them to be no help at 8 experts a token (PERF.md
section 6, PR 39; fixtures/many-experts-k8.requests-readings.json), kept so
that the finding can be read again. Reads JSON and does arithmetic: no JAX.
"""

from __future__ import annotations

import argparse
import json
import sys

from reference import CONTROLS, ROUTED_REQUEST_MEDIAN_MIN_TOKENS


def numbers_at(cases: dict, count: int) -> dict:
    """reference.py:judge's routed numbers over the first `count` requests,
    from each request's own (its `cases`)."""
    first = [cases[name] for name in sorted(cases, key=lambda n: int(n.partition(".")[0]))]
    assert len(first) >= count, f"{len(first)} requests a seed, {count} asked"
    first = first[:count]
    tokens = sum(r["tokens"] for r in first)
    left_out = sum(r["router_near_ties_left_out"] for r in first)
    out = {
        "positions_outside": sum(r["positions_outside"] for r in first),
        "logprob_gap_pooled_mean_sigmas": sum(
            r["logprob_diff_sigmas_mean"] * (r["tokens"] - r["router_near_ties_left_out"])
            for r in first) / max(tokens - left_out, 1),
        "logprob_gap_pooled_mean_all_sigmas": sum(
            r["logprob_diff_sigmas_mean_all_positions"] * r["tokens"] for r in first) / tokens,
        "logprob_gap_request_median_sigmas": max(
            (r["logprob_diff_sigmas_median"] for r in first
             if r["tokens"] >= ROUTED_REQUEST_MEDIAN_MIN_TOKENS), default=0.0),
        "router_left_out_share": left_out / tokens,
    }
    if all("router_choice_deficit_max_sigmas" in r for r in first):
        out["router_choice_deficit_max_sigmas"] = max(
            r["router_choice_deficit_max_sigmas"] for r in first)
    return out


def readings(result_files: list, count: int) -> dict:
    """control -> number -> every seed's reading at `count` requests a run."""
    out = {c: {} for c in CONTROLS}
    for path in result_files:
        with open(path) as f:
            sets = json.load(f)["sets"]
        for one in sets.values():
            for c in CONTROLS:
                if "skipped" in one[c]:  # the bf16 control on a CPU
                    continue
                for number, value in numbers_at(one[c]["cases"], count).items():
                    out[c].setdefault(number, []).append(value)
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("result_files", nargs="+")
    ap.add_argument("--counts", type=int, nargs="+", default=[4, 8, 12, 16])
    args = ap.parse_args()
    for count in args.counts:
        got = readings(args.result_files, count)
        for number, low in got["int8"].items():
            sound = got["bf16"].get(number)
            print(json.dumps({
                "requests_a_run": count, "number": number, "seeds": len(low),
                "bf16": sound and [min(sound), max(sound)], "int8": [min(low), max(low)],
                "int8_lowest_over_bf16_highest":
                    min(low) / max(sound) if sound and max(sound) else None}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
