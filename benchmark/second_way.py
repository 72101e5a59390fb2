#!/usr/bin/env python3
"""An experiment, and NO judged number of the harness: what the head judged
over the top few tokens would read (PERF.md section 7, item 2: the second way).

    python3 benchmark/second_way.py --config-file <file> --traffic <mix> --seed n [--seed m ...]
                  [--requests 16] [--swap a b] [--rehearsal]          (on the chip)
    python3 benchmark/second_way.py --read <requests.json> [...]      (on the CPU: the tables)

The first form runs both controls of reference.py over the cases that `run.py
--controls-only --requests N` judges for those seeds (`run.py:control_cases`),
with the control's own expert choices forced into the float32 reference, as
there. At each position a control gives the log-probabilities of the TOP
tokens it puts first (what a served reply's `logprobs` option can give); each
is compared with the reference's log-probability of that token, in deviations
of the position's reference logits, and the differences are averaged: a
position's number. Over the first token alone (`d1`) that is the judge's own
`logprob_diff_sigmas`. Every request's mean and median of both go to
`chiprun_out/benchmark/second_way.<file>.<mix>/requests.json`.

`--swap a b`: seed a's cases once more with their prompts' first six ids (a
request's id opens its prompt with the seed's last six digits: traffic.py)
replaced by seed b's, and the other way round, under the int8 control alone:
whether what a seed's requests share is those characters.

The second form reads such files and prints, at 4, 8, 12 and 16 requests a run
(the picks are nested), what the judge's two numbers would read over the top
tokens and over the first alone: the pooled mean over a run's positions and
the worst request's median, each control's lowest and highest over the seeds,
and how many times the bf16 control's highest the int8 control's lowest reads.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(1, ROOT)

TOP = 8  # tokens a position is judged over
DIGITS = 6  # of the seed, which open a request's id and so its prompt


def low_pass(R, model, params, precision: str, cases: dict, padded: int) -> dict:
    """name -> (the control's top tokens, best first; its log-probabilities of
    them; the experts it chose [inputs][layers][k])."""
    import jax
    import numpy as np

    cfg, reference = model
    lowered = (contextlib.nullcontext() if precision == "bf16"
               else jax.default_matmul_precision("highest"))
    out = {}
    for name, c in cases.items():
        with lowered:
            rows, _, routing = R.forward(reference, cfg, params, {name: c}, None, padded)
        low = rows[name]
        lp = low - R.log_normalizer(low)[:, None]
        part = np.argpartition(low, -TOP, axis=-1)[:, -TOP:]
        order = np.argsort(-np.take_along_axis(low, part, -1), axis=-1)
        ids = np.take_along_axis(part, order, -1)
        out[name] = (ids, np.take_along_axis(lp, ids, -1),
                     routing[name]["chosen"].transpose(1, 0, 2).copy())
    return out


def against(R, model, params, cases: dict, low: dict, padded: int) -> dict:
    """name -> the request's numbers: the control's log-probabilities of its
    top tokens against the float32 reference's, its choices forced."""
    import jax
    import numpy as np

    cfg, reference = model
    out = {}
    with jax.default_matmul_precision("highest"):
        for name, c in cases.items():
            ids, lp_low, chosen = low[name]
            rows, _, _ = R.forward(reference, cfg, params, {name: c}, {name: chosen}, padded)
            r = rows[name]
            lp = r - R.log_normalizer(r)[:, None]
            d = np.abs(lp_low - np.take_along_axis(lp, ids, -1)) / r.std(-1)[:, None]
            out[name] = {
                "tokens": int(len(r)),
                "d1_mean": float(d[:, 0].mean()), "d1_median": float(np.median(d[:, 0])),
                f"d{TOP}_mean": float(d.mean()), f"d{TOP}_median": float(np.median(d.mean(-1))),
                "distinct_top1": int(len(set(ids[:, 0].tolist()))),
                "top1_most_common_share": float(np.bincount(ids[:, 0]).max() / len(r)),
            }
    return out


def measure(args) -> int:
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    if args.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import reference as R
    import run
    from traffic import load_mix
    from worker_entry import load_config

    file_cfg = load_config(args.config_file, args.rehearsal)
    mix = load_mix(args.traffic, args.rehearsal)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        seconds = float(json.load(f)["run_seconds"])
    sets = run.control_cases(file_cfg, mix, args.seed, args.requests, seconds)
    for a, b in (args.swap, args.swap[::-1]) if args.swap else ():
        digits = next(iter(sets[b].values()))["prompt_ids"][:DIGITS]
        sets[f"{a}>{b}"] = {name: dict(c, prompt_ids=digits + c["prompt_ids"][DIGITS:])
                            for name, c in sets[a].items()}
    cfg, weights, reference, _, forced = R.load_model(
        os.path.abspath(args.config_file), args.rehearsal)
    assert forced, "the second way is for a family whose routing is judged forced"
    model = (cfg, reference)
    padded = max(R.padded_length(cases) for cases in sets.values())
    # as reference.py:run: the int8 control rounds the weights in place, so its
    # pass over every set comes first and the weights are built anew after it
    rounded = R.int8_weights(weights())
    low8 = {s: low_pass(R, model, rounded, "int8", cases, padded) for s, cases in sets.items()}
    del rounded
    params = weights()
    out = {}
    for s, cases in sets.items():
        out[s] = {"int8": against(R, model, params, cases, low8.pop(s), padded)}
        if ">" not in s:
            out[s]["bf16"] = against(R, model, params, cases,
                                     low_pass(R, model, params, "bf16", cases, padded), padded)
        print(json.dumps({"set": s, "requests": len(cases)}), flush=True)
    name = os.path.splitext(os.path.basename(args.config_file))[0]
    out_dir = os.path.join(ROOT, "chiprun_out", "benchmark",
                           f"second_way.{name}.{args.traffic}"
                           + (".rehearsal" if args.rehearsal else ""))
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "requests.json"), "w") as f:
        json.dump(out, f)
    return 0


def first(requests: dict, count: int) -> list:
    names = sorted(requests, key=lambda n: int(n.partition(".")[0]))
    assert len(names) >= count, f"{len(names)} requests a seed, {count} asked"
    return [requests[n] for n in names[:count]]


def pooled_mean(requests: list, over: str) -> float:
    return (sum(r[f"{over}_mean"] * r["tokens"] for r in requests)
            / sum(r["tokens"] for r in requests))


def read(files: list, counts: list) -> int:
    from reference import ROUTED_REQUEST_MEDIAN_MIN_TOKENS

    sets = {}
    for path in files:
        with open(path) as f:
            sets.update(json.load(f))
    seeds = {s: one for s, one in sets.items() if ">" not in s}
    for over in (f"d{TOP}", "d1"):
        for count in counts:
            numbers = {"pooled_mean": lambda rs: pooled_mean(rs, over),
                       "worst_request_median": lambda rs: max(
                           (r[f"{over}_median"] for r in rs
                            if r["tokens"] >= ROUTED_REQUEST_MEDIAN_MIN_TOKENS), default=0.0)}
            for number, of in numbers.items():
                got = {c: [of(first(one[c], count)) for one in seeds.values()]
                       for c in ("bf16", "int8")}
                print(json.dumps({
                    "over": over, "requests_a_run": count, "number": number,
                    "seeds": len(seeds), "bf16": [min(got["bf16"]), max(got["bf16"])],
                    "int8": [min(got["int8"]), max(got["int8"])],
                    "int8_lowest_over_bf16_highest":
                        min(got["int8"]) / max(got["bf16"]) if max(got["bf16"]) else None}))
    for s, one in sets.items():  # a swap: the int8 control's pooled mean, first token alone
        if ">" in s:
            a, b = s.split(">")
            print(json.dumps({"swap": s, "requests": len(one["int8"]), **{
                who: pooled_mean(list(sets[x]["int8"].values()), "d1")
                for who, x in (("own", a), ("with_the_others_digits", s), ("the_other", b))}}))
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--read", nargs="+", metavar="requests.json")
    ap.add_argument("--counts", type=int, nargs="+", default=[4, 8, 12, 16])
    ap.add_argument("--config-file")
    ap.add_argument("--traffic")
    ap.add_argument("--seed", action="append", default=[], type=int)
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--swap", nargs=2, metavar=("a", "b"), help="two of the seeds given")
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args()
    if args.read:
        return read(args.read, args.counts)
    if not (args.config_file and args.traffic and args.seed):
        ap.error("either --read, or --config-file, --traffic and --seed")
    return measure(args)


if __name__ == "__main__":
    sys.exit(main())
