#!/usr/bin/env python3
"""One cell of BENCHMARK.json, once, on the served path.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Starts discovery, the worker (through worker_entry.py, which hands the
unchanged `dynamo_tpu.jax_worker` main a configuration read from a file) and
the OpenAI frontend; waits for ready; warms with the cell's own traffic until
nothing compiles; measures for `--seconds` from a client over HTTP; re-sends
four of the window's requests greedily (asking, for a configuration whose
routing is judged forced, for the experts the worker chose and for the top
tokens of each position) and checks them
against the plain reference in a child of its own, after the worker has
exited. The last line of standard output is the result: one JSON object with
`correct`, `attempted`, `failed`, `metrics` and `device` (and `breakdown`
when traced). Earlier lines are JSON objects too, one per phase.

    --rehearsal   tiny sizes from the configuration's own file, on the CPU:
                  finds wrong paths and arguments at no chip time. Its line
                  names the CPU, never says `correct: true`, carries no
                  device metric, and its exit code is 4 when all passed.
    --sweep       one process, a ladder of rates of `--seconds` each (the
                  mix's `sweep_rates_rps`): the table from which a mix's
                  fixed rate is set, once. Prints no result line.
    --controls-only --config-file <file> --traffic <mix> --seed n [--seed m ...]
                  no discovery, worker or frontend: for each seed four cases
                  drawn from the mix's own generator (`--requests N`: a sizing
                  run of N a seed, which sample_sizes.py reads at every
                  smaller count), and the reference's two controls
                  (reference.py) judged on them by the file's limits.
                  The table from which a routed family's limits are set, for
                  any configuration file whose `dataclass` the program can
                  build. Under `judge_routing: "forced"` each control is
                  judged against a float32 pass with its OWN expert choices
                  forced, over the top tokens IT puts first, as a program
                  is. Prints no result line.

One process per chip: this parent never imports JAX. Process supervision is
copied from chip_smoke.py (PR 21). A run that finds no TPU fails.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import random
import re
import shutil
import signal
import socket
import subprocess
import sys
import time

T_START = time.monotonic()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(1, ROOT)

import files_check  # noqa: E402
import layer_metrics  # noqa: E402
import metrics as e2e  # noqa: E402
from client import Load, StatsWatch, resend_greedy  # noqa: E402
from reference import (  # noqa: E402  (imports no JAX until it runs)
    CONTROLS, ROUTED_REQUEST_MEDIAN_MIN_TOKENS)
from traffic import Generator, load_mix  # noqa: E402
from worker_entry import load_config  # noqa: E402

REHEARSAL_PASSED = 4
DEADLINE_DEVICE_S = 180
DEADLINE_READY_S = 1100
TRACE_SECONDS = 4.0
WARM_BUDGET_S = 1000  # a first start compiles every program its traffic reaches
REFERENCE_POSITIONS = 2048
# (CONTROLS, from reference.py, which computes both: every configuration here
# states bf16, so int8, the nearest precision below, has to come out not
# correct and bf16 correct; the program's own `--quantize int8` cannot start
# at these sizes: PERF.md, Open questions.)

# how many of a window's finished requests a run re-sends and judges; and the
# most that a sizing run (`--controls-only --requests N`) judges a seed
CHECKED_REQUESTS = 4
SIZING_REQUESTS_MAX = 16
# how many requests of the mix a --controls-only run picks its cases from: a
# closed loop's first requests of each client, about what a window finishes
CONTROLS_ONLY_PER_CLIENT = 16
BYTE_TOKENIZER_OFFSET = 3  # dynamo_tpu's byte tokenizer: id = byte + 3 specials


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


class BenchFailure(Exception):
    pass


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Children:
    """Every process this run starts, stopped on every way out."""

    def __init__(self, out_dir: str):
        self.out_dir = out_dir
        self.procs: list = []

    def start(self, name: str, argv: list, env: dict) -> subprocess.Popen:
        log = open(os.path.join(self.out_dir, f"{name}.log"), "wb")
        p = subprocess.Popen(argv, cwd=ROOT, env=env, stdout=log,
                             stderr=subprocess.STDOUT, start_new_session=True)
        self.procs.append((name, p, log))
        return p

    def stop(self, name: str, grace: float = 20.0) -> None:
        for n, p, log in self.procs:
            if n != name:
                continue
            if p.poll() is None:
                try:
                    os.killpg(p.pid, signal.SIGTERM)
                    p.wait(timeout=grace)
                except subprocess.TimeoutExpired:
                    os.killpg(p.pid, signal.SIGKILL)
                    p.wait()
                except ProcessLookupError:
                    pass
            log.close()

    def stop_all(self) -> None:
        for n, _, _ in reversed(self.procs):
            self.stop(n, grace=10.0)

    def log_tail(self, name: str, n: int = 30) -> str:
        try:
            with open(os.path.join(self.out_dir, f"{name}.log"), "rb") as f:
                return b"\n".join(f.read().splitlines()[-n:]).decode("utf-8", "replace")
        except OSError:
            return ""

    def wait_for_log(self, name: str, proc, pattern: str, deadline_s: float):
        rx = re.compile(pattern)
        t_end = time.monotonic() + deadline_s
        path = os.path.join(self.out_dir, f"{name}.log")
        while time.monotonic() < t_end:
            with open(path, "r", errors="replace") as f:
                for line in f:
                    m = rx.search(line)
                    if m:
                        return m
            if proc.poll() is not None:
                raise BenchFailure(
                    f"{name} exited with code {proc.returncode} before logging "
                    f"/{pattern}/:\n{self.log_tail(name)}")
            time.sleep(0.5)
        raise BenchFailure(f"{name}: no /{pattern}/ within {deadline_s}s")


def child_env(rehearsal: bool) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = ROOT + (os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    env["PYTHONUNBUFFERED"] = "1"
    # the compile cache lives inside the checkout, at a fixed path, whatever
    # the machine's environment names: the engine takes the directory it is
    # given (engine.py:_enable_compile_cache), and a cache the machine caps
    # or shares would hit for one side of a comparison and not the other
    env["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    env.pop("JAX_COMPILATION_CACHE_MAX_SIZE", None)
    if rehearsal:
        env["JAX_PLATFORMS"] = "cpu"
    return env


def run_child(argv: list, env: dict, log_path: str, timeout: float) -> dict:
    """A helper of the harness that needs JAX (the reference, the trace
    reduction): its last line of output is one JSON object."""
    with open(log_path, "wb") as errs:
        p = subprocess.run([sys.executable, *argv], cwd=ROOT, env=env,
                           stdout=subprocess.PIPE, stderr=errs, timeout=timeout)
    if p.returncode != 0:
        with open(log_path, "rb") as f:
            tail = b"\n".join(f.read().splitlines()[-25:]).decode("utf-8", "replace")
        raise BenchFailure(f"{argv[0]} exited {p.returncode}:\n{tail}")
    return json.loads(p.stdout.decode().strip().splitlines()[-1])


# ---------------------------------------------------------------------- #
# the cell
# ---------------------------------------------------------------------- #


def load_cell(workload: str) -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json; known: {sorted(cells)}")
    cell = cells[workload]
    config = {c["name"]: c for c in bench["configs"]}[cell["config"]]

    def here(m):
        return "workloads" not in m or workload in m["workloads"]

    end_to_end = [m for m in bench["end_to_end"] if here(m)]
    reported = {m["name"] for m in end_to_end}
    return {
        "name": workload, "config": cell["config"], "traffic": cell["traffic"],
        "chips": cell["chips"], "config_file": os.path.join(ROOT, config["file"]),
        "run_seconds": float(bench["run_seconds"]),
        "end_to_end": end_to_end,
        # a per-layer metric belongs to the cells that report what it moves
        "per_layer": [m for m in bench["per_layer"] if here(m) and m["moves"] in reported],
    }


def pick_checked(finished: list, seed: int, count: int = CHECKED_REQUESTS) -> list:
    """Four of the window's own finished requests for the reference: the
    shortest, a median one, the longest that decodes across a page boundary,
    and one at random from the seed; each of at most REFERENCE_POSITIONS
    positions. A sizing run's `count` over four: every further one is drawn
    by the same seeded generator from the requests not picked yet, of
    ROUTED_REQUEST_MEDIAN_MIN_TOKENS served tokens or more while there are
    such: the ones whose median the judge holds. A draw depends on the draws
    before it alone, so the picks are nested: the first M of a seed's N are
    its picks at M, and the first four are a run's."""
    fit = [r for r in finished
           if len(r.prompt) + r.max_tokens <= REFERENCE_POSITIONS]
    if not fit:
        raise BenchFailure("no finished request of the window fits the reference")
    by_len = sorted(fit, key=lambda r: (len(r.prompt) + r.max_tokens, r.rid))
    crossing = [r for r in by_len if len(r.prompt) % 64 + r.max_tokens > 64]
    rnd = random.Random(f"{seed}:checked")
    picks = [
        ("shortest", by_len[0]),
        ("median", by_len[len(by_len) // 2]),
        ("crosses_page", (crossing or by_len)[-1]),
        ("random", rnd.choice(by_len)),
    ]
    while len(picks) < count:
        left = [r for r in by_len if all(r is not p for _, p in picks)]
        if not left:
            raise BenchFailure(f"{len(by_len)} finished requests of the window fit the "
                               f"reference, and {count} are to be judged")
        long_enough = [r for r in left if r.max_tokens >= ROUTED_REQUEST_MEDIAN_MIN_TOKENS]
        picks.append(("further", rnd.choice(long_enough or left)))
    return [{"why": why, "prompt": r.prompt, "max_tokens": r.max_tokens}
            for why, r in picks]


def compared(ref: dict, counts: dict) -> dict:
    """name -> [value, limit] of each number `correct` rests on: the
    reference's own (reference.py names them and their limits) and the
    client's count of requests that returned another number of tokens than
    asked."""
    return dict(ref["compared"], wrong_length_requests=[len(counts["wrong_length"]), 0])


async def own_pulse(t_end: float, period: float = 0.1) -> tuple:
    """The longest overshoot of a loop that only sleeps `period`, and when."""
    worst, at, last = 0.0, time.monotonic(), time.monotonic()
    while last < t_end:
        await asyncio.sleep(period)
        now = time.monotonic()
        if now - last - period > worst:
            worst, at = now - last - period, last
        last = now
    return worst, at


async def measure(args, cell: dict, mix: dict, cfg: dict, discovery_addr: str,
                  http_port: int, worker_pid: int) -> dict:
    """Warm, window, drain, re-sends. Everything the client sees."""
    import aiohttp

    base = f"http://127.0.0.1:{http_port}"
    watch = StatsWatch(discovery_addr)
    await watch.start()
    await watch.fresh()
    timeout = aiohttp.ClientTimeout(total=None, sock_connect=30, sock_read=180)
    conn = aiohttp.TCPConnector(limit=0)
    async with aiohttp.ClientSession(timeout=timeout, connector=conn) as session:
        load = Load(session, base, cell["config"], float(mix.get("temperature", 0.0)))
        gen = Generator(mix, args.seed)
        closed = mix["loop"] == "closed"
        warm_s = float(mix.get("warm_seconds", 10))
        if closed:
            load.start_clients(gen.client_streams())
        # -- warm: the cell's own traffic, block after block, until one passes
        # in which nothing compiled. The worker starts with `--warmup none`
        # (the configuration's file says why), so this compiles the shapes
        # this traffic uses and no others; after a first start they load
        # from the cache. Counted as set-up.
        t0 = t_warm = time.monotonic()
        warm_compiles = []
        while True:
            s0 = await watch.fresh()
            if not closed:
                await load.open_block(gen.block(warm_s, "warm"), t0)
            elif len(warm_compiles) == 1:  # the slots are full: see Load.bursts
                await load.bursts(mix.get("warm_bursts") or [])
            await asyncio.sleep(max(t0 + warm_s - time.monotonic(), 0))
            # stats published after the block's end: a program counts as
            # compiled only when its compile has ended, and a cold compile of
            # a deep model outlasts a block (mistral-7b-d16's first start, PR
            # 34: the window opened under it and held no request). So a block
            # passes only if tokens also flowed in it
            s1 = await watch.fresh(timeout=WARM_BUDGET_S)
            grew = s1["compiled_variants"] - s0["compiled_variants"]
            warm_compiles.append(grew)
            if not grew and s1["emit_tokens"] > s0["emit_tokens"]:
                t0 += warm_s  # the window follows without a pause
                break
            if time.monotonic() - t_warm > WARM_BUDGET_S:
                raise BenchFailure(f"programs still compile after {WARM_BUDGET_S} s "
                                   f"of the cell's traffic: {warm_compiles}")
            if not closed:  # a compile stalled the engine: let the queue clear
                await load.drain(WARM_BUDGET_S)
            t0 = time.monotonic()
        # -- the window
        t_w0, t_w1 = t0, t0 + args.seconds
        load.phase = "window"
        # this process's own pulse: a stall that also stops a loop that only
        # sleeps is the machine's (a paused guest), not the program's
        pulse = asyncio.create_task(own_pulse(t_w1))
        stats0 = watch.latest
        tracer = None
        if args.trace:
            async def start_trace():
                await asyncio.sleep(max(
                    t_w0 + (args.seconds - TRACE_SECONDS) / 2 - time.monotonic(), 0))
                os.kill(worker_pid, signal.SIGUSR1)
            tracer = asyncio.create_task(start_trace())
        if not closed:
            await load.open_block(gen.block(args.seconds, "window"), t_w0)
        await asyncio.sleep(max(t_w1 - time.monotonic(), 0))
        stats1 = watch.latest
        harness_pause_s, harness_pause_at = await pulse
        in_flight_at_end = load.in_flight()
        load.stop_offering()
        unfinished = await load.drain(float(mix.get("drain_seconds", 60)))
        if tracer:
            await tracer
        stats2 = await watch.fresh()
    window = [r for r in load.sent if t_w0 <= r.t_due < t_w1]
    penalty_ms = (args.seconds + float(mix.get("drain_seconds", 60))) * 1e3
    result = e2e.end_to_end(load.sent, t_w0, t_w1, penalty_ms)
    frames = sorted(t for r in load.sent for t in r.frame_at if t_w0 - 5 <= t < t_w1)
    span, span_at = max(((b - a, a) for a, b in zip(frames, frames[1:])),
                        default=(args.seconds, t_w0))
    result["counts"].update({
        # over 0.5 s is a stall (PERF.md); it may begin up to 5 s before the
        # window. Beside it the longest pause of this process's own pulse
        "no_frame_s_max": span, "no_frame_at_s": span_at - t_w0,
        "harness_pause_s_max": harness_pause_s,
        "harness_pause_at_s": harness_pause_at - t_w0,
        "requests_sent_all_phases": len(load.sent),
        "warm_blocks_compiles": warm_compiles,
        "compiles_in_window": stats1["compiled_variants"] - stats0["compiled_variants"],
        "in_flight_at_window_end": in_flight_at_end,
        "unfinished_at_drain_deadline": unfinished,
    })
    result["setup_s"] = t_w0 - T_START
    # every request that touched the window, with its frames, seconds from
    # the window's start: what the metrics were taken from
    result["records"] = [
        {"rid": r.rid, "ok": r.ok, "error": r.error, "tokens": r.tokens,
         "max_tokens": r.max_tokens, "prompt_chars": len(r.prompt),
         "due": r.t_due - t_w0, "send": r.t_send - t_w0,
         "end": None if r.t_end is None else r.t_end - t_w0,
         "frame_at": [t - t_w0 for t in r.frame_at], "frame_chars": r.frame_chars}
        for r in load.sent
        if r.t_due < t_w1 and (r.t_end is None or r.t_end >= t_w0)]
    result["stats"] = (stats0, stats1, stats2)
    picks = pick_checked([r for r in window if r.ok], args.seed)
    t_resend = time.monotonic()
    result["served"] = await resend_greedy(
        discovery_addr, cell["config"], cfg["vocab_size"],
        int(cfg["worker_args"][cfg["worker_args"].index("--max-model-len") + 1]),
        picks, BenchFailure,
        routed=(files_check.routed_geometry(cell["config"], cfg)
                if cfg.get("judge_routing") == "forced" else None))
    # after the window and in no metric; README.md has what they cost a run
    result["counts"]["resend_s"] = time.monotonic() - t_resend
    await watch.close()
    return result


async def sweep(args, cell: dict, mix: dict, http_port: int) -> None:
    """A ladder of rates, `--seconds` each, drained between steps. A rate is
    sustained when the requests in flight do not grow across its step."""
    import aiohttp

    base = f"http://127.0.0.1:{http_port}"
    timeout = aiohttp.ClientTimeout(total=None, sock_connect=30, sock_read=180)
    async with aiohttp.ClientSession(
        timeout=timeout, connector=aiohttp.TCPConnector(limit=0)
    ) as session:
        for rate in mix["sweep_rates_rps"]:
            step = dict(mix, rate_rps=rate)
            load = Load(session, base, cell["config"], float(mix.get("temperature", 0.0)))
            load.phase = "window"
            gen = Generator(step, args.seed)
            t0 = time.monotonic()
            samples = []

            async def sample():
                while True:
                    samples.append((time.monotonic() - t0, load.in_flight()))
                    await asyncio.sleep(0.5)

            sampler = asyncio.create_task(sample())
            await load.open_block(gen.block(args.seconds, "window"), t0)
            await asyncio.sleep(max(t0 + args.seconds - time.monotonic(), 0))
            sampler.cancel()
            t_drain = time.monotonic()
            unfinished = await load.drain(120)
            res = e2e.end_to_end(load.sent, t0, t0 + args.seconds, 180e3)
            third = args.seconds / 3

            def mean_in_flight(lo, hi):
                xs = [n for t, n in samples if lo <= t < hi]
                return sum(xs) / max(len(xs), 1)

            emit({"phase": "sweep", "rate_rps": rate,
                  "in_flight_middle_third": mean_in_flight(third, 2 * third),
                  "in_flight_last_third": mean_in_flight(2 * third, args.seconds),
                  "drain_s": time.monotonic() - t_drain, "unfinished": unfinished,
                  **res["metrics"], **{k: res["counts"][k] for k in (
                      "requests_due_in_window", "requests_failed",
                      "generator_late_ms_max")}})


def control_cases(cfg: dict, mix: dict, seeds: list, count: int, seconds: float) -> dict:
    """seed -> the cases a control is judged on: `count` requests picked as a
    run picks them, from the mix's own generator; the continuation is seeded
    random ids, since a control is judged by the token IT puts first at each
    position."""
    sets = {}
    for seed in seeds:
        gen = Generator(mix, seed)
        pool = ([r for stream in gen.client_streams()
                 for r in stream[:CONTROLS_ONLY_PER_CLIENT]]
                if mix["loop"] == "closed" else gen.block(seconds, "window"))
        rnd = random.Random(f"{seed}:continuation")
        sets[str(seed)] = {f"{i}.{p['why']}": {
            "prompt_ids": [b + BYTE_TOKENIZER_OFFSET for b in p["prompt"].encode()],
            "served_ids": [rnd.randrange(BYTE_TOKENIZER_OFFSET, cfg["vocab_size"])
                           for _ in range(p["max_tokens"])],
        } for i, p in enumerate(pick_checked(pool, seed, count))}
    return sets


def controls_only(args, seeds: list) -> int:
    """Both controls with no program behind them (reference.py:control_pass),
    for any configuration file: per seed four cases (`--requests`: a sizing
    run's count; `control_cases`). One child builds the weights and judges
    seed by seed."""
    cfg = load_config(args.config_file, args.rehearsal)
    count = args.requests or CHECKED_REQUESTS
    mix = load_mix(args.traffic, args.rehearsal)
    if args.seconds is None:
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            args.seconds = float(json.load(f)["run_seconds"])
    sets = control_cases(cfg, mix, seeds, count, args.seconds)
    name = os.path.splitext(os.path.basename(args.config_file))[0]
    out_dir = os.path.join(ROOT, "chiprun_out", "benchmark",
                           f"controls.{name}.{args.traffic}"
                           + (".rehearsal" if args.rehearsal else ""))
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)
    cases = os.path.join(out_dir, "reference_cases.json")
    with open(cases, "w") as f:
        json.dump({"config_file": os.path.abspath(args.config_file),
                   "rehearsal": args.rehearsal, "controls_only": True,
                   "controls": list(CONTROLS), "cases": sets}, f)
    ref = run_child([os.path.join(HERE, "reference.py"), cases],
                    child_env(args.rehearsal), os.path.join(out_dir, "reference.log"), 3000)
    with open(os.path.join(out_dir, "reference_result.json"), "w") as f:
        json.dump(ref, f)  # every request's own numbers too
    readings = {c: {} for c in CONTROLS}  # control -> number -> every seed's reading
    judged = {c: [] for c in CONTROLS}  # control -> every judged seed's verdict
    shares = []
    for seed, one in ref["sets"].items():
        shares.append(one["margin_under_default_epsilon_share"])
        line = {"phase": "controls", "seed": int(seed), "requests": count,
                "positions": one["positions"], "seconds": one["seconds"],
                "margin_under_default_epsilon_share": shares[-1]}
        for c in CONTROLS:
            low = one[c]
            if "skipped" in low:
                line[c] = low
                continue
            judged[c].append(low["agrees"])
            line[c] = {"agrees": low["agrees"], "why_not": low["why_not"],
                       "checked": low["compared"]}
            for number, (value, limit) in low["compared"].items():
                readings[c].setdefault(number, []).append(value)
                print(f"checked {seed} {c} {number}: {value} (limit {limit})",
                      file=sys.stderr)
        emit(line)
    # lowest and highest over the seeds: what `judge_readings` is written from
    emit({"phase": "controls_summary", "config_file": args.config_file,
          "traffic": args.traffic, "seeds": seeds, "requests": count,
          "device": ref["reference_device"],
          "judge": cfg.get("judge"),
          "margin_under_default_epsilon_share":
              None if None in shares else [min(shares), max(shares)],
          **{c: {"agreed": f"{sum(judged[c])} of {len(judged[c])}",
                 **{n: [min(v), max(v)] for n, v in readings[c].items()}}
             for c in CONTROLS}})
    # the readings come before the rule can hold: a file whose `judge` has no
    # sound `judge_readings` yet is judged by it all the same, and told so
    # (held at the file's own geometry: a rehearsal's tiny one is not what the
    # limits were set for)
    if args.requests:
        # a sizing run: its verdicts were read under limits set at four
        # requests a run, and sample_sizes.py reads its reference_result.json
        print(f"--requests {count}: a sizing run, nothing is judged by its exit code",
              file=sys.stderr)
        return REHEARSAL_PASSED if args.rehearsal else 0
    files_check.check_judge(args.config_file, load_config(args.config_file, False))
    if args.rehearsal:
        return REHEARSAL_PASSED
    skipped = [c for c in CONTROLS if len(judged[c]) < len(seeds)]
    if skipped:
        print(f"not judged: {skipped} ({ref['reference_device']})", file=sys.stderr)
        return 1
    # bf16 has to agree on every seed, int8 on none
    return 0 if all(judged["bf16"]) and not any(judged["int8"]) else 1


# ---------------------------------------------------------------------- #


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, action="append",
                    help="once; --controls-only takes it several times")
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--sweep", action="store_true")
    ap.add_argument("--control", choices=CONTROLS, action="append", default=[],
                    help="also judge the reference itself in the program's place, on "
                         "the same prompts and tokens: int8, the nearest precision below "
                         "the configuration's, has to come out NOT correct; bf16, the "
                         "configuration's own, correct (exit code 0 then, if the program "
                         "is correct). May be given twice")
    ap.add_argument("--controls-only", action="store_true")
    ap.add_argument("--config-file", help="--controls-only: any configuration file")
    ap.add_argument("--traffic", help="--controls-only: a mix under traffic/")
    ap.add_argument("--requests", type=int, default=None,
                    choices=range(CHECKED_REQUESTS, SIZING_REQUESTS_MAX + 1), metavar="N",
                    help="--controls-only: N requests a seed for a run's four: the one run "
                         "from which sample_sizes.py reads every count up to N (the picks "
                         "are nested)")
    ap.add_argument("--break", dest="broken", choices=["token"], default=None,
                    help="a fault planted under the timed path (worker_entry.py): "
                         "the run has to come out NOT correct")
    args = ap.parse_args()
    seeds = args.seed or [0]
    args.seed = seeds[-1]
    if args.controls_only != bool(args.config_file and args.traffic) or (
            args.controls_only == bool(args.workload)):
        ap.error("either --workload, or --controls-only with --config-file and --traffic")
    if args.requests and not args.controls_only:
        ap.error("--requests is for --controls-only: a run judges four")
    try:  # every run guards the files it is driven by
        files_check.check(ROOT)
        if args.controls_only:
            return controls_only(args, seeds)
    except files_check.BenchmarkFilesError as e:
        print(f"BENCHMARK.json or a file it names is at fault: {e}", file=sys.stderr)
        return 2
    except BenchFailure as e:
        emit({"phase": "failed", "error": str(e)[-3000:]})
        return 1
    cell = load_cell(args.workload)
    if args.seconds is None:
        args.seconds = cell["run_seconds"]
    cfg = load_config(cell["config_file"], args.rehearsal)
    mix = load_mix(cell["traffic"], args.rehearsal)
    out_dir = os.path.join(
        ROOT, "chiprun_out", "benchmark",
        f"{cell['name']}.seed{args.seed}.trace{args.trace}"
        + (".sweep" if args.sweep else "") + (".rehearsal" if args.rehearsal else "")
        + "".join(f".control-{c}" for c in sorted(args.control))
        + (f".break-{args.broken}" if args.broken else ""))
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)
    trace_dir = os.path.join(out_dir, "trace")
    children = Children(out_dir)
    env = child_env(args.rehearsal)
    device = {"platform": None, "kind": None, "count": 0}
    line, checked = None, {}
    try:
        disc_port, http_port = free_port(), free_port()
        discovery_addr = f"127.0.0.1:{disc_port}"
        env["DYN_DISCOVERY_ENDPOINT"] = discovery_addr
        py = sys.executable
        children.start("discovery", [
            py, "-m", "dynamo_tpu.runtime.discovery",
            "--host", "127.0.0.1", "--port", str(disc_port)], env)
        worker_argv = [
            py, os.path.join(HERE, "worker_entry.py"),
            "--bench-config", cell["config_file"], "--bench-name", cell["config"]]
        if args.rehearsal:
            worker_argv.append("--bench-rehearsal")
        if args.trace:
            worker_argv += ["--bench-trace-dir", trace_dir,
                            "--bench-trace-seconds", str(TRACE_SECONDS)]
        if args.broken:
            worker_argv += ["--bench-break", args.broken]
        worker = children.start("worker", worker_argv + list(cfg["worker_args"]), env)
        # -- the device, from the worker, before any weight is built
        m = children.wait_for_log("worker", worker, r"worker device (\{.*\})",
                                  DEADLINE_DEVICE_S)
        dev = json.loads(m.group(1))
        device = {"platform": dev["platform"], "kind": dev["device_kind"],
                  "count": dev["device_count"]}
        emit({"phase": "device", **dev})
        if not args.rehearsal and dev["platform"] != "tpu":
            raise BenchFailure(f"the worker's device is {dev['platform']!r}, not a TPU")
        if not args.rehearsal and dev["device_count"] < cell["chips"]:
            raise BenchFailure(
                f"{dev['device_count']} devices, the cell asks for {cell['chips']}")
        children.start("frontend", [
            py, "-m", "dynamo_tpu.frontend", "--http-host", "127.0.0.1",
            "--http-port", str(http_port)], dict(env, **cfg.get("frontend_env", {})))
        import urllib.request

        t_end = time.monotonic() + DEADLINE_READY_S
        while True:
            if worker.poll() is not None:
                raise BenchFailure(f"worker exited with code {worker.returncode}:\n"
                                   f"{children.log_tail('worker')}")
            try:
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{http_port}/v1/models", timeout=5) as r:
                    if cell["config"] in r.read().decode():
                        break
            except OSError:
                pass
            if time.monotonic() > t_end:
                raise BenchFailure(f"model not served within {DEADLINE_READY_S}s:\n"
                                   f"{children.log_tail('worker')}")
            time.sleep(0.5)
        emit({"phase": "ready", "seconds_from_start": time.monotonic() - T_START})

        if args.sweep:
            asyncio.run(sweep(args, cell, mix, http_port))
            return 0
        res = asyncio.run(measure(args, cell, mix, cfg, discovery_addr,
                                  http_port, worker.pid))
        stats0, stats1, stats2 = res.pop("stats")
        with open(os.path.join(out_dir, "requests.json"), "w") as f:
            json.dump({"seconds": args.seconds, "requests": res.pop("records")}, f)
        emit({"phase": "window", "setup_s": res["setup_s"], **res["metrics"],
              **res["counts"]})
        emit({"phase": "worker", "warmup_s": stats2["warmup_s"],
              "warmup_compiles": stats2["warmup_compiles"],
              "compiled_variants": stats2["compiled_variants"],
              "compile_surfaces": stats2["compile_surfaces"],
              "post_warmup_compiles": stats2["post_warmup_compiles"],
              "attention_impl": stats2["attention_impl"],
              "decode_pool_mode": stats2["decode_pool_mode"],
              "num_pages": stats2["kv_total_blocks"],
              "kv_pool_bytes": stats2["kv_pool_bytes"],
              "weight_bytes_per_device": stats2["weight_bytes_per_device"],
              "device_memory": stats2["device_memory"]})
        if args.trace:
            marker = os.path.join(trace_dir, "trace_done.json")
            t_end = time.monotonic() + 120
            while not os.path.exists(marker) and time.monotonic() < t_end:
                time.sleep(0.5)
        # -- free the chip, then the children that need JAX
        children.stop("frontend")
        children.stop("worker", grace=30.0)
        children.stop("discovery")
        cases = os.path.join(out_dir, "reference_cases.json")
        with open(cases, "w") as f:
            json.dump({"config_file": cell["config_file"], "rehearsal": args.rehearsal,
                       "controls": args.control, "cases": res["served"]}, f)
        t_ref = time.monotonic()
        ref = run_child([os.path.join(HERE, "reference.py"), cases], env,
                        os.path.join(out_dir, "reference.log"), 600)
        emit({"phase": "reference", "reference_s": time.monotonic() - t_ref, **ref})
        counts = res["counts"]
        why_not = list(ref["why_not"])
        if counts["wrong_length"]:
            why_not.append(f"requests returned another token count than asked: "
                           f"{counts['wrong_length']}")
        trace = None
        if args.trace:
            cpu_env = dict(env, JAX_PLATFORMS="cpu")
            trace = run_child([os.path.join(HERE, "reduce_trace.py"), trace_dir],
                              cpu_env, os.path.join(out_dir, "reduce_trace.log"), 600)
            with open(os.path.join(out_dir, "trace_reduced.json"), "w") as f:
                json.dump(trace, f)
            shutil.rmtree(trace_dir, ignore_errors=True)  # large; the numbers stay
            emit({"phase": "trace", **{k: v for k, v in trace.items()
                                       if k not in ("device_ops", "idle_gaps")}})
            if "error" in trace:
                if not args.rehearsal:
                    raise BenchFailure(f"trace: {trace['error']}")
                trace = None

        # what serving holds: bytes in use on the fullest chip after the
        # window and the drain (weights and page pool). The allocator's own
        # peak is a transient of building the weights (moe.init_params holds
        # the layers twice while it stacks them), says nothing about the
        # cell's size, and stays on the "worker" line above.
        held = [m["bytes_in_use"] for m in stats2["device_memory"]
                if m.get("bytes_in_use") is not None]
        device["memory_peak_bytes"] = max(held) if held else None
        measured = dict(res["metrics"], setup_s=res["setup_s"])
        if args.trace:
            on_tpu = device["platform"] == "tpu"
            ctx = {"stats0": stats0, "stats1": stats1, "stats2": stats2,
                   "client": counts, "end_to_end": measured, "seconds": args.seconds,
                   "trace": trace if on_tpu else None, "device": device}
            values = layer_metrics.read_all(cell["per_layer"], ctx)
            if trace and on_tpu:
                device["busy_s"], device["window_s"] = trace["busy_s"], trace["window_s"]
        else:
            values = {m["name"]: measured.get(m["name"]) for m in cell["end_to_end"]}
        units = {m["name"]: m["unit"] for m in cell["end_to_end"] + cell["per_layer"]}
        line = {
            "correct": (not why_not) and not args.rehearsal,
            "attempted": counts["requests_due_in_window"],
            "failed": counts["requests_failed"],
            "metrics": {k: {"value": v, "unit": units[k]}
                        for k, v in values.items() if v is not None},
            "device": device,
        }
        if why_not:
            line["why_not_correct"] = why_not
        # every number compared, beside its limit: the result's last key
        # (moved to the end below) and the last lines of standard error
        checked = compared(ref, counts)
        for precision, low in ref.get("controls", {}).items():
            line.setdefault("controls", {})[precision] = low if "skipped" in low else {
                "correct": low["agrees"], "why_not_correct": low["why_not"],
                "checked": compared(low, counts)}
        if args.rehearsal:
            # counts and CPU times, under a name no device metric has
            line["cpu_rehearsal_values"] = line.pop("metrics")
            line["metrics"] = {}
            line["rehearsal"] = True
            line["reference_agrees"] = not why_not
        if args.trace and trace:
            line["breakdown"] = {"device_ops": trace["device_ops"],
                                 "idle_gaps": trace["idle_gaps"]}
        missing = [m["name"] for m in cell["end_to_end"]
                   if not (args.trace or args.rehearsal)
                   and m["name"] not in line["metrics"]]
        if missing:
            raise BenchFailure(f"no value for {missing}")
    except BenchFailure as e:
        emit({"phase": "failed", "error": str(e)[-3000:]})
        line = None
    except Exception:  # noqa: BLE001 — any fault is a failed run, reported
        import traceback

        emit({"phase": "failed", "error": traceback.format_exc()[-3000:]})
        line = None
    finally:
        children.stop_all()
    if line is None:
        return 1
    line["checked"] = checked
    for name, (value, limit) in checked.items():
        print(f"checked {name}: {value} (limit {limit})", file=sys.stderr)
    print(json.dumps(line), flush=True)
    if args.rehearsal:
        return REHEARSAL_PASSED if line["reference_agrees"] else 1
    for precision, low in line.get("controls", {}).items():
        # the limits let a lower precision through, or refuse the program's own
        if low.get("correct") == (precision == "int8"):
            return 1
    return 0 if line["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
