#!/usr/bin/env python3
"""chip_smoke.py: does the served path start and answer correctly on the chip?

Drives the system the way the README's "Running" section does — discovery,
`python -m dynamo_tpu.jax_worker --model llama3-3b`, the OpenAI frontend —
at the full width and depth of llama3-3b (bf16, seeded random weights, byte
tokenizer, default engine options), sends a few requests over HTTP and over
the request plane, and checks the served greedy tokens and the
log-probabilities served with them against a plain `jax.numpy` reference
kept at the bottom of this file.

    python chip_smoke.py             one TPU chip (what the driver runs)
    python chip_smoke.py --chips 4   the same model, one worker, --tp-size 4
    python chip_smoke.py --rehearsal tiny model on the CPU: finds wrong
                                     paths and arguments at no chip time;
                                     never reports ok

One process per chip: this parent never imports JAX. The worker owns the
device and says what it is; the reference runs in a child of its own after
the worker has exited. Every line of standard output is one JSON object;
the last one is exactly {"ok": ..., "device": {...}}. Exit code 0 only when
ok is true: the device is a TPU and every phase passed.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import random
import re
import signal
import socket
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(HERE, "chiprun_out", "chip_smoke")

# Shape space of the smoke's worker, bounded with the worker's own arguments
# so that a cold-cache warmup (one full-depth compile per token bucket x
# page-table rung x program kind, 12-40 s each on the chip) fits the run's
# time limit. Everything else is the worker's default: auto pool, mixed
# dispatch, full warmup. 1088 = the largest prefill chunk
# (1024) plus one page: the smallest context in which a prompt takes two
# prefill chunks.
MAX_MODEL_LEN = 1088
MAX_NUM_SEQS = 8
WEIGHT_SEED = 0  # EngineConfig.seed: what the worker's init_params uses
PAGE_SIZE = 64  # the worker's default --page-size
DECODE_BLOCK = 8  # EngineConfig.decode_block_steps

# At every generated position the served token's reference logit must be
# within this many standard deviations (of that position's reference logits
# over the vocabulary) of the reference maximum. The engine computes in bf16
# (8 mantissa bits) through 28 layers, the reference in float32, so near-ties
# at a 128k vocabulary flip and token equality is not demanded. The maximum
# of 128k logits sits about 4.5 deviations above their mean: a token picked
# from a wrong page, a misplaced KV write or a wrong mask lands whole
# deviations below it; a tie lands within a small fraction of one.
LOGIT_TOLERANCE_SIGMAS = 0.3
# The engine also reports the log-probability of every token it serves. It
# must agree with the reference's log-softmax at that token: the continuous
# check, which sees an error too small to change a token (one misplaced KV
# slot among hundreds, under the diffuse attention of random weights).
# In the same unit (deviations of that position's reference logits): the
# largest difference over all positions, and the mean difference of the
# worst request.
LOGPROB_TOLERANCE_MAX_SIGMAS = 0.25
LOGPROB_TOLERANCE_MEAN_SIGMAS = 0.06

REHEARSAL_PASSED = 4  # exit code of a --rehearsal whose phases all passed

DEADLINE_DEVICE_S = 180
DEADLINE_READY_S = 1000
DEADLINE_REQUEST_S = 180


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


class SmokeFailure(Exception):
    pass


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# ---------------------------------------------------------------------- #
# children
# ---------------------------------------------------------------------- #


class Children:
    """Every process this script starts, stopped on every way out."""

    def __init__(self):
        self.procs: list = []

    def start(self, name: str, argv: list, env: dict) -> subprocess.Popen:
        log = open(os.path.join(OUT_DIR, f"{name}.log"), "wb")
        p = subprocess.Popen(
            argv, cwd=HERE, env=env, stdout=log, stderr=subprocess.STDOUT,
            start_new_session=True,
        )
        self.procs.append((name, p, log))
        return p

    def stop(self, name: str, grace: float = 20.0) -> None:
        for n, p, log in self.procs:
            if n != name or p.poll() is not None:
                continue
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


def child_env() -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = HERE + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else ""
    )
    env["PYTHONUNBUFFERED"] = "1"
    return env


def log_tail(name: str, n: int = 30) -> str:
    try:
        with open(os.path.join(OUT_DIR, f"{name}.log"), "rb") as f:
            return b"\n".join(f.read().splitlines()[-n:]).decode("utf-8", "replace")
    except OSError:
        return ""


def wait_for_log(name: str, proc: subprocess.Popen, pattern: str,
                 deadline_s: float) -> re.Match:
    rx = re.compile(pattern)
    t_end = time.monotonic() + deadline_s
    path = os.path.join(OUT_DIR, f"{name}.log")
    while time.monotonic() < t_end:
        with open(path, "r", errors="replace") as f:
            for line in f:
                m = rx.search(line)
                if m:
                    return m
        if proc.poll() is not None:
            raise SmokeFailure(
                f"{name} exited with code {proc.returncode} before logging "
                f"/{pattern}/:\n{log_tail(name)}"
            )
        time.sleep(0.5)
    raise SmokeFailure(f"{name}: no /{pattern}/ within {deadline_s}s")


# ---------------------------------------------------------------------- #
# prompts (from the seed; byte tokenizer: one character is one token)
# ---------------------------------------------------------------------- #


def make_groups(seed: int) -> list:
    """Groups of requests; a group's members run together, groups one after
    another. Lengths follow the shapes the worker's warmup compiles
    (JaxEngine.warmup): a lone prompt per prefill bucket, one long prompt
    that walks the chunked path, and arrivals of chunk-bucket length that
    fuse, one at a time, beside an anchor that is decoding at a given
    page-table rung."""
    rnd = random.Random(seed)
    words = ["tpu", "page", "token", "cache", "router", "prefill", "decode",
             "mesh", "kernel", "stream", "block", "query", "serve", "chip"]

    def text(n_chars: int) -> str:
        # a distinct first word per prompt: no two share a first KV block,
        # so the prefix cache hands none of them a shorter chunk
        out = f"{rnd.randrange(10**6):06d} "
        while len(out) < n_chars:
            out += rnd.choice(words) + rnd.choice([" ", " ", ", ", ". "])
        return out[:n_chars]

    def case(name, kind, n_chars, max_tokens, stream=False):
        return {"name": name, "kind": kind, "prompt": text(n_chars),
                "max_tokens": max_tokens, "stream": stream}

    K, page = DECODE_BLOCK, PAGE_SIZE
    return [
        # through the chat template, streamed (SSE)
        [case("chat", "chat", 40, 3 * K, stream=True)],
        # two prefill chunks (1024 + 52 tokens) and 17 KV pages
        [case("long", "completion", MAX_MODEL_LEN - K - 4, K + 2)],
        # anchor: streamed; decodes eight blocks and crosses a KV page
        # boundary while decoding (124 -> 188 passes 128). The arrival
        # lands beside its decode: one mixed (prefill + decode) step, then
        # two lanes of different lengths decode together.
        [case("anchor_2p", "completion", 2 * page - 4, 8 * K, stream=True),
         case("arrival_128", "completion", 128 - 8, K + 4)],
        # the same at the eight-page rung (508 -> 572 passes 512) with a
        # 16-page arrival
        [case("anchor_8p", "completion", 8 * page - 4, 8 * K, stream=True),
         case("arrival_1024", "completion", 1024 - 8, K + 4)],
    ]


def openai_body(case: dict, model: str, stream: bool) -> dict:
    body = {
        "model": model,
        "max_tokens": case["max_tokens"],
        "temperature": 0,
        "stream": stream,
        # random weights may emit EOS at once: a fixed length is the point
        "nvext": {"ignore_eos": True},
    }
    if stream:
        body["stream_options"] = {"include_usage": True}
    if case["kind"] == "chat":
        body["messages"] = [{"role": "user", "content": case["prompt"]}]
    else:
        body["prompt"] = case["prompt"]
    return body


# ---------------------------------------------------------------------- #
# phases (parent side; asyncio, no JAX)
# ---------------------------------------------------------------------- #


async def http_request(session, base: str, case: dict, model: str,
                       first_token: asyncio.Event = None) -> dict:
    """One OpenAI request; checks status, SSE framing and usage."""
    path = "/v1/chat/completions" if case["kind"] == "chat" else "/v1/completions"
    stream = case["stream"]
    body = openai_body(case, model, stream)
    async with session.post(base + path, json=body) as resp:
        if resp.status != 200:
            raise SmokeFailure(
                f"{case['name']}: HTTP {resp.status}: {(await resp.text())[:300]}"
            )
        if not stream:
            data = await resp.json()
            if first_token is not None:
                first_token.set()
            usage = data.get("usage") or {}
            text = data["choices"][0].get("text")
            if text is None:
                text = data["choices"][0]["message"]["content"]
            finish = data["choices"][0].get("finish_reason")
        else:
            if not resp.headers.get("Content-Type", "").startswith("text/event-stream"):
                raise SmokeFailure(f"{case['name']}: stream is not SSE")
            usage, text, finish, done = {}, "", None, False
            async for raw in resp.content:
                line = raw.decode("utf-8").rstrip("\r\n")
                if not line:
                    continue
                if not line.startswith("data: "):
                    raise SmokeFailure(f"{case['name']}: bad SSE line {line[:80]!r}")
                payload = line[len("data: "):]
                if payload == "[DONE]":
                    done = True
                    continue
                if done:
                    raise SmokeFailure(f"{case['name']}: data after [DONE]")
                chunk = json.loads(payload)
                if chunk.get("usage"):
                    usage = chunk["usage"]
                for ch in chunk.get("choices") or []:
                    piece = ch.get("text")
                    if piece is None:
                        piece = (ch.get("delta") or {}).get("content")
                    text += piece or ""
                    if first_token is not None:
                        first_token.set()
                    finish = ch.get("finish_reason") or finish
            if not done:
                raise SmokeFailure(f"{case['name']}: stream ended without [DONE]")
    if usage.get("completion_tokens") != case["max_tokens"]:
        raise SmokeFailure(
            f"{case['name']}: usage {usage} != {case['max_tokens']} tokens asked"
        )
    if finish != "length":
        raise SmokeFailure(f"{case['name']}: finish_reason {finish!r}, not length")
    return {"text": text, "prompt_tokens": usage.get("prompt_tokens")}


async def run_groups(groups: list, run_one) -> dict:
    """The traffic shape of both passes. In a group of two the first is the
    anchor: it starts alone, and once its first token is out the second
    arrives beside its decode."""
    results = {}
    for group in groups:
        started = asyncio.Event()
        first = asyncio.create_task(run_one(group[0], started))
        if len(group) > 1:
            await asyncio.wait(
                [first, asyncio.create_task(started.wait())],
                return_when=asyncio.FIRST_COMPLETED,
            )
        rest = await asyncio.gather(*[run_one(c, None) for c in group[1:]])
        for c, r in zip(group, [await first, *rest]):
            results[c["name"]] = r
    return results


async def read_stats(discovery_addr: str, component: str = "backend") -> dict:
    """The worker's own JaxEngine.stats(), off the metrics topic it already
    publishes for the router and planner."""
    from dynamo_tpu.runtime import codec
    from dynamo_tpu.runtime.discovery import DiscoveryClient

    host, port = discovery_addr.rsplit(":", 1)
    cli = await DiscoveryClient.connect(host, int(port))
    sub = await cli.subscribe(f"kv_metrics/dynamo/{component}")
    try:
        async def first():
            async for payload in sub:
                return codec.unpack(payload).get("stats", {})

        return await asyncio.wait_for(first(), timeout=15)
    finally:
        await sub.cancel()
        await cli.close()


async def drive(args, discovery_addr: str, http_port: int, groups: list) -> dict:
    """HTTP pass, prefix-cache flush, request-plane pass. Returns the
    served token ids per case for the reference."""
    import aiohttp

    from dynamo_tpu.llm.model_card import ModelDeploymentCard
    from dynamo_tpu.llm.preprocessor import OpenAIPreprocessor
    from dynamo_tpu.llm.protocols import ChatCompletionRequest, CompletionRequest
    from dynamo_tpu.llm.tokenizers import load_tokenizer
    from dynamo_tpu.runtime import DistributedRuntime, RuntimeConfig

    base = f"http://127.0.0.1:{http_port}"
    model = args.model
    n_cases = sum(len(g) for g in groups)
    stats0 = await read_stats(discovery_addr)

    # -- pass 1: the OpenAI surface ------------------------------------- #
    timeout = aiohttp.ClientTimeout(total=DEADLINE_REQUEST_S)
    async with aiohttp.ClientSession(timeout=timeout) as session:
        async def over_http(case, started):
            return await http_request(session, base, case, model, started)

        http = await run_groups(groups, over_http)
        stats1 = await read_stats(discovery_addr)
        emit({
            "phase": "http", "requests_sent": n_cases,
            "requests_succeeded": len(http),
            "streamed": [c["name"] for g in groups for c in g if c["stream"]],
            "mixed_steps": stats1["mixed_steps"] - stats0["mixed_steps"],
            "prompt_tokens": {k: v["prompt_tokens"] for k, v in http.items()},
        })
        # same prompts again below: flush the prefix cache (the frontend's
        # admin route) so the compared pass prefills from nothing too
        async with session.post(base + "/clear-kv-blocks") as resp:
            if resp.status != 200:
                raise SmokeFailure(f"/clear-kv-blocks: HTTP {resp.status}")

    # -- pass 2: the same requests on the worker's generate endpoint ---- #
    # token ids, not text: the byte tokenizer's decode does not round-trip.
    # The preprocessor is the frontend's own, so the wire request is what
    # the frontend sent in pass 1.
    tok = load_tokenizer(f"byte:{args.vocab_size}")
    pre = OpenAIPreprocessor(
        ModelDeploymentCard(name=model, tokenizer="byte",
                            kv_cache_block_size=PAGE_SIZE,
                            context_length=MAX_MODEL_LEN),
        tok,
    )
    cfg = RuntimeConfig()
    cfg.discovery_endpoint = discovery_addr
    drt = await DistributedRuntime.create(cfg)
    try:
        ep = drt.namespace("dynamo").component("backend").endpoint("generate")
        client = await ep.client()
        (instance,) = await client.wait_for_instances(timeout=30)

        async def over_wire(case, started):
            body = openai_body(case, model, stream=False)
            # the served log-probability of each served token rides the
            # existing logprobs option (computed on the device either way)
            body["logprobs"] = True if case["kind"] == "chat" else 0
            req = (
                pre.preprocess_chat(ChatCompletionRequest(**body))
                if case["kind"] == "chat"
                else pre.preprocess_completion(CompletionRequest(**body))
            )
            out, lps = [], []
            stream = await client.direct(req.to_dict(), instance)
            async for item in stream:
                if item.get("event") == "error":
                    raise SmokeFailure(f"{case['name']}: {item.get('comment')}")
                data = item.get("data") or {}
                out.extend(data.get("token_ids") or [])
                lps.extend(data.get("log_probs") or [])
                if out and started is not None:
                    started.set()
            if not len(out) == len(lps) == case["max_tokens"]:
                raise SmokeFailure(
                    f"{case['name']}: {len(out)} token ids and {len(lps)} "
                    f"logprobs over the request plane, {case['max_tokens']} asked"
                )
            return {"prompt_ids": list(req.token_ids), "served_ids": out,
                    "served_logprobs": lps}

        wire = await asyncio.wait_for(
            run_groups(groups, over_wire), timeout=DEADLINE_REQUEST_S
        )
    finally:
        await drt.close()
    stats2 = await read_stats(discovery_addr)
    mixed = {"http": stats1["mixed_steps"] - stats0["mixed_steps"],
             "request_plane": stats2["mixed_steps"] - stats1["mixed_steps"]}
    emit({
        "phase": "request_plane", "requests_sent": n_cases,
        "requests_succeeded": len(wire),
        "mixed_steps": mixed["request_plane"],
        # informational: batch composition differs between the passes, so a
        # bf16 near-tie may legitimately flip
        "text_equals_http_pass": {
            k: tok.decode(v["served_ids"]) == http[k]["text"]
            for k, v in wire.items()
        },
    })
    for name, delta in mixed.items():
        if delta < 1:
            raise SmokeFailure(f"{name} pass: no mixed (prefill+decode) step ran")
    for k, v in wire.items():
        if len(v["prompt_ids"]) != http[k]["prompt_tokens"]:
            raise SmokeFailure(
                f"{k}: {len(v['prompt_ids'])} prompt ids on the wire, "
                f"{http[k]['prompt_tokens']} prompt_tokens over HTTP"
            )
    # which surfaces, if any, compiled under the requests (expected: none)
    grew = {
        k: n - stats0["compile_surfaces"].get(k, 0)
        for k, n in stats2["compile_surfaces"].items()
        if n != stats0["compile_surfaces"].get(k, 0)
    }
    return {"cases": wire, "stats": stats2, "compiled_under_requests": grew}


def report_worker(served: dict, tp: int, rehearsal: bool) -> list:
    """The worker's account of itself, and the checks that need no
    reference: kernels resolved, nothing compiled after warmup, the model
    really sharded. Returns what failed, so that the reference still runs
    and one chip call shows every fault."""
    failed = []
    stats = served["stats"]
    dev = stats["device"]
    mem = stats["device_memory"]
    emit({"phase": "worker", "jax": dev["jax"], "jaxlib": dev["jaxlib"],
          "libtpu": dev["libtpu"], "attention_impl": stats["attention_impl"],
          "decode_pool_mode": stats["decode_pool_mode"],
          "num_pages": stats["kv_total_blocks"],
          "kv_pool_bytes": stats["kv_pool_bytes"],
          "native_core": stats["native_core"]})
    emit({"phase": "memory", "device_memory": mem,
          "weight_bytes_per_device": stats["weight_bytes_per_device"],
          "kv_bytes_per_device": stats["kv_bytes_per_device"]})
    emit({"phase": "compile", "warmup_compiles": stats["warmup_compiles"],
          "warmup_s": stats["warmup_s"],
          "compile_surfaces": stats["compile_surfaces"],
          "post_warmup_compiles": stats["post_warmup_compiles"],
          "compiled_under_requests": served["compiled_under_requests"]})
    if stats["warmup_compiles"] < 1:
        failed.append("the worker served without its warmup")
    if stats["post_warmup_compiles"] != 0:
        failed.append(
            f"{stats['post_warmup_compiles']} programs compiled after warmup: "
            f"{served['compiled_under_requests']}"
        )
    if tp > 1:
        # the model is really sharded: each device of the tp axis holds
        # 1/tp of the weight bytes and of the KV bytes, none holds it all
        for what in ("weight_bytes_per_device", "kv_bytes_per_device"):
            per = [b for b in stats[what] if b]
            total = sum(per)
            if len(per) != tp or any(
                abs(b / total - 1 / tp) > 0.05 for b in per
            ):
                failed.append(f"{what} not 1/{tp} on each device: {stats[what]}")
        used = [m["bytes_in_use"] for m in mem]
        if None not in used and max(used) > 1.5 * min(used):
            failed.append(f"device bytes_in_use uneven: {used}")
    if rehearsal:
        return failed
    want = "pallas" if tp == 1 else "xla"  # tp>1: the gate's mesh rule
    if set(stats["attention_impl"].values()) != {want}:
        failed.append(
            f"attention resolved to {stats['attention_impl']}, expected {want}"
        )
    return failed


def run_reference(args, served: dict) -> dict:
    """The plain reference, in a child of its own: the worker has exited,
    so the chip is free for it."""
    case_file = os.path.join(OUT_DIR, "reference_cases.json")
    with open(case_file, "w") as f:
        json.dump({"model": args.model, "seed": WEIGHT_SEED,
                   "cases": served}, f)
    log = os.path.join(OUT_DIR, "reference.log")
    with open(log, "wb") as errs:
        p = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--reference", case_file],
            cwd=HERE, env=child_env(), stdout=subprocess.PIPE, stderr=errs,
            timeout=600,
        )
    if p.returncode != 0:
        raise SmokeFailure(
            f"reference child exited {p.returncode}:\n{log_tail('reference')}"
        )
    return json.loads(p.stdout.decode().strip().splitlines()[-1])


def check_reference(result: dict) -> None:
    worst = dlp_max = dlp_mean = 0.0
    for name, r in result["cases"].items():
        emit({"phase": "reference", "case": name, **r})
        worst = max(worst, r["worst_gap_sigmas"])
        dlp_max = max(dlp_max, r["logprob_diff_sigmas_max"])
        dlp_mean = max(dlp_mean, r["logprob_diff_sigmas_mean"])
        if not r["finite"]:
            raise SmokeFailure(f"{name}: reference logits are not finite")
    emit({"phase": "reference", "worst_gap_sigmas": worst,
          "tolerance_sigmas": LOGIT_TOLERANCE_SIGMAS,
          "logprob_diff_sigmas_max": dlp_max,
          "tolerance_logprob_max_sigmas": LOGPROB_TOLERANCE_MAX_SIGMAS,
          "logprob_diff_sigmas_mean_worst_case": dlp_mean,
          "tolerance_logprob_mean_sigmas": LOGPROB_TOLERANCE_MEAN_SIGMAS})
    if worst > LOGIT_TOLERANCE_SIGMAS:
        raise SmokeFailure(
            f"a served token's reference logit is {worst:.2f} standard "
            f"deviations below the reference maximum (tolerance "
            f"{LOGIT_TOLERANCE_SIGMAS})"
        )
    if (dlp_max > LOGPROB_TOLERANCE_MAX_SIGMAS
            or dlp_mean > LOGPROB_TOLERANCE_MEAN_SIGMAS):
        raise SmokeFailure(
            f"served and reference log-probabilities of the served tokens "
            f"differ by up to {dlp_max:.3f} deviations (tolerance "
            f"{LOGPROB_TOLERANCE_MAX_SIGMAS}), {dlp_mean:.3f} on average in "
            f"the worst request (tolerance {LOGPROB_TOLERANCE_MEAN_SIGMAS})"
        )


def main_parent(args) -> int:
    os.makedirs(OUT_DIR, exist_ok=True)
    device = {"platform": None, "kind": None, "count": 0}
    children = Children()
    ok = rehearsed = False
    try:
        try:
            import dynamo_tpu  # noqa: F401 — the program under test
        except ImportError as e:
            raise SmokeFailure(f"dynamo_tpu is not beside this script: {e}")
        env = child_env()
        disc_port, http_port = free_port(), free_port()
        discovery_addr = f"127.0.0.1:{disc_port}"
        env["DYN_DISCOVERY_ENDPOINT"] = discovery_addr
        if args.rehearsal:
            env["JAX_PLATFORMS"] = "cpu"
            if args.chips > 1:
                env["XLA_FLAGS"] = (
                    f"--xla_force_host_platform_device_count={args.chips}"
                )
        py = sys.executable
        children.start("discovery", [
            py, "-m", "dynamo_tpu.runtime.discovery",
            "--host", "127.0.0.1", "--port", str(disc_port),
        ], env)
        worker_argv = [
            py, "-m", "dynamo_tpu.jax_worker", "--model", args.model,
            "--max-model-len", str(MAX_MODEL_LEN),
            "--max-num-seqs", str(MAX_NUM_SEQS),
        ]
        if args.tp > 1:
            worker_argv += ["--tp-size", str(args.tp)]
        if args.rehearsal:
            worker_argv += ["--warmup", "full"]  # auto skips it on the CPU
        t0 = time.monotonic()
        worker = children.start("worker", worker_argv, env)

        # -- the device, from the worker, before any weight is built ---- #
        m = wait_for_log("worker", worker, r"worker device (\{.*\})",
                         DEADLINE_DEVICE_S)
        dev = json.loads(m.group(1))
        device = {"platform": dev["platform"], "kind": dev["device_kind"],
                  "count": dev["device_count"]}
        emit({"phase": "device", **dev})
        if not args.rehearsal and dev["platform"] != "tpu":
            raise SmokeFailure(
                f"the worker's device is {dev['platform']!r}, not a TPU"
            )
        if dev["device_count"] != args.chips:
            raise SmokeFailure(
                f"{dev['device_count']} devices, --chips {args.chips}"
            )

        children.start("frontend", [
            py, "-m", "dynamo_tpu.frontend", "--http-host", "127.0.0.1",
            "--http-port", str(http_port),
        ], env)
        # the model appears on the frontend once the worker has warmed up
        # and registered
        import urllib.request

        # the four-chip run is the builder's own and may take its time; the
        # one-chip run is held to the contract's limit
        deadline_ready = DEADLINE_READY_S * (2 if args.chips > 1 else 1)
        t_end = time.monotonic() + deadline_ready
        while True:
            if worker.poll() is not None:
                raise SmokeFailure(
                    f"worker exited with code {worker.returncode}:\n"
                    f"{log_tail('worker')}"
                )
            try:
                with urllib.request.urlopen(
                    f"http://127.0.0.1:{http_port}/v1/models", timeout=5
                ) as r:
                    if args.model in r.read().decode():
                        break
            except OSError:
                pass
            if time.monotonic() > t_end:
                raise SmokeFailure(
                    f"model not served within {deadline_ready}s:\n"
                    f"{log_tail('worker')}"
                )
            time.sleep(1.0)
        emit({"phase": "ready", "seconds_to_ready": round(time.monotonic() - t0, 1)})

        groups = make_groups(args.seed)
        served = asyncio.run(drive(args, discovery_addr, http_port, groups))
        failed = report_worker(served, args.tp, args.rehearsal)

        # -- free the chip, then the reference --------------------------- #
        children.stop("frontend")
        children.stop("worker", grace=30.0)
        children.stop("discovery")
        check_reference(run_reference(args, served["cases"]))
        if failed:
            raise SmokeFailure("; ".join(failed))
        # a rehearsal is never ok: that word is kept for the chip
        ok, rehearsed = not args.rehearsal, args.rehearsal
        if rehearsed:
            emit({"phase": "rehearsal", "passed": True})
    except SmokeFailure as e:
        emit({"phase": "failed", "error": str(e)[-3000:]})
    except Exception as e:  # noqa: BLE001 — any fault is a failed smoke
        import traceback

        emit({"phase": "failed", "error": traceback.format_exc()[-3000:]})
        del e
    finally:
        children.stop_all()
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    if ok:
        return 0
    return REHEARSAL_PASSED if rehearsed else 1


# ---------------------------------------------------------------------- #
# the plain reference (child process; the only JAX in this file)
# ---------------------------------------------------------------------- #


def reference_logits(params, cfg, tokens, n_last: int):
    """Full-sequence causal forward of the Llama architecture in jax.numpy:
    float32 activations over the model's own (bf16) weights, one softmax
    over the whole sequence, no paging, no kernels, nothing from ops/ or the
    serving forwards. Returns logits [n_last, vocab] for the last n_last
    positions."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    T = tokens.shape[0]
    H, KH, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim

    def norm(x, w):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + cfg.rms_norm_eps) * w.astype(f32)

    inv_freq = 1.0 / (cfg.rope_theta ** (jnp.arange(0, D, 2, dtype=f32) / D))
    ang = jnp.arange(T, dtype=f32)[:, None] * inv_freq[None, :]  # [T, D/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]

    def rope(x):  # [T, heads, D], rotate-half convention
        a, b = x[..., : D // 2], x[..., D // 2:]
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)

    causal = jnp.tril(jnp.ones((T, T), bool))

    def layer(x, w):
        h = norm(x, w["attn_norm"])
        q = rope((h @ w["wq"].astype(f32)).reshape(T, H, D))
        k = rope((h @ w["wk"].astype(f32)).reshape(T, KH, D))
        v = (h @ w["wv"].astype(f32)).reshape(T, KH, D)
        k = jnp.repeat(k, H // KH, axis=1)
        v = jnp.repeat(v, H // KH, axis=1)
        s = jnp.einsum("thd,shd->hts", q, k) / jnp.sqrt(f32(D))
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        a = jnp.einsum("hts,shd->thd", p, v).reshape(T, H * D)
        x = x + a @ w["wo"].astype(f32)
        h = norm(x, w["mlp_norm"])
        gate = jax.nn.silu(h @ w["w_gate"].astype(f32)) * (h @ w["w_up"].astype(f32))
        return x + gate @ w["w_down"].astype(f32), None

    x = params["embed"][tokens].astype(f32)
    x, _ = jax.lax.scan(layer, x, params["layers"])
    x = norm(x[T - n_last:], params["final_norm"])
    head = params["lm_head"] if params.get("lm_head") is not None else params["embed"].T
    return x @ head.astype(f32)


def main_reference(case_file: str) -> int:
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.engine.engine import _enable_compile_cache, _resolve_model
    from dynamo_tpu.models import llama

    _enable_compile_cache()
    with open(case_file) as f:
        spec = json.load(f)
    cfg = _resolve_model(spec["model"])
    # the same seeded weights the worker built (engine.py / jax_worker)
    params = llama.init_params(cfg, jax.random.PRNGKey(spec["seed"]))
    cases = spec["cases"]
    T = max(len(c["prompt_ids"]) + len(c["served_ids"]) for c in cases.values())
    T = -(-T // 64) * 64  # one padded shape: causal, so the tail is inert

    with jax.default_matmul_precision("highest"):
        fwd = jax.jit(functools.partial(reference_logits, cfg=cfg, n_last=T))
        out = {}
        for name, c in cases.items():
            prompt, served = c["prompt_ids"], c["served_ids"]
            # teacher forcing: the reference reads the SERVED tokens and is
            # asked, at each position, how good the served next token was
            seq = prompt + served[:-1]
            toks = np.zeros((T,), np.int32)
            toks[: len(seq)] = seq
            logits = np.asarray(fwd(params, tokens=jnp.asarray(toks)))
            rows = logits[len(prompt) - 1: len(seq)]  # predicts served[i]
            top = rows.max(axis=-1)
            got = rows[np.arange(len(served)), served]
            gap = (top - got) / rows.std(axis=-1)  # in deviations
            # log-softmax of the reference at the served token, against
            # the log-probability the engine reported for it
            logz = np.log(np.exp(rows - top[:, None]).sum(-1)) + top
            dlp = np.abs(
                np.asarray(c["served_logprobs"]) - (got - logz)
            ) / rows.std(axis=-1)
            pos = len(prompt) + np.arange(len(served))  # position written
            at_page = gap[pos % PAGE_SIZE == 0]
            at_block = gap[1::DECODE_BLOCK]  # token 0 is the prefill's
            out[name] = {
                "tokens": len(served),
                "finite": bool(np.isfinite(rows).all()),
                "exact_matches": int((rows.argmax(-1) == np.asarray(served)).sum()),
                "worst_gap_sigmas": float(gap.max()),
                "worst_gap_sigmas_after_page_boundary":
                    float(at_page.max()) if at_page.size else None,
                "page_boundaries_crossed": int(at_page.size),
                "worst_gap_sigmas_at_block_start": float(at_block.max()),
                "worst_gap_logit": float((top - got).max()),
                "logprob_diff_sigmas_max": float(dlp.max()),
                "logprob_diff_sigmas_mean": float(dlp.mean()),
                "reference_top_logit_mean": float(top.mean()),
                "reference_logit_std": float(rows.std()),
            }
    print(json.dumps({"cases": out}), flush=True)
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=[1, 4], default=1,
                    help="4: only the --tp-size 4 path and its reference")
    ap.add_argument("--seed", type=int, default=0,
                    help="prompts (the weights come from the worker's own "
                    "default seed, EngineConfig.seed)")
    ap.add_argument("--rehearsal", action="store_true",
                    help="tiny model on the CPU; exit code 4 when every "
                    "phase passed, 1 when one failed, never 0")
    ap.add_argument("--reference", metavar="CASES_JSON", default=None,
                    help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.reference:
        return main_reference(args.reference)
    args.model = "tiny" if args.rehearsal else "llama3-3b"
    args.vocab_size = 512 if args.rehearsal else 128256
    # tensor-parallel width: all the chips; the rehearsal's tiny model has
    # two KV heads, so it shards two ways over two of its virtual devices
    args.tp = min(args.chips, 2) if args.rehearsal else args.chips
    return main_parent(args)


if __name__ == "__main__":
    sys.exit(main())
