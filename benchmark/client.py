"""The load generator and the other clients of the served path: streaming
`/v1/completions` over HTTP, the worker's stats off the metrics topic, and
greedy re-sends with log-probabilities over the request plane.

One process, one asyncio loop, no JAX. SSE handling, `read_stats` and the
request-plane client are copied from `chip_smoke.py` (proven on the chip,
PR 21); times are taken from when a request was DUE, not from when it was
sent, so a stalled generator cannot hide queueing.
"""

from __future__ import annotations

import asyncio
import json
import math
import time
from typing import Callable, List, Optional

from reference import TOP_TOKENS  # (no JAX until it runs)
from traffic import Request

PAGE_SIZE = 64  # the worker's default --page-size


def completion_body(req: Request, model: str, temperature: float) -> dict:
    return {
        "model": model,
        "prompt": req.prompt,
        "max_tokens": req.max_tokens,
        "temperature": temperature,
        "stream": True,
        "stream_options": {"include_usage": True},
        # random weights may emit EOS at once: exact lengths are the point
        "nvext": {"ignore_eos": True},
    }


async def send(session, base: str, model: str, temperature: float,
               req: Request) -> Request:
    """One streamed completion. Fills the request's times (time.monotonic),
    frame and token counts; never raises for a fault of the server: a failed
    request is a result."""
    req.t_send = time.monotonic()
    try:
        async with session.post(
            base + "/v1/completions",
            json=completion_body(req, model, temperature),
        ) as resp:
            if resp.status != 200:
                req.error = f"HTTP {resp.status}: {(await resp.text())[:200]}"
                return req
            done = False
            async for raw in resp.content:
                line = raw.decode("utf-8").rstrip("\r\n")
                if not line.startswith("data: "):
                    continue  # blank separators and ": event" comments
                payload = line[len("data: "):]
                if payload == "[DONE]":
                    done = True
                    continue
                chunk = json.loads(payload)
                if chunk.get("error"):
                    req.error = str(chunk["error"])[:200]
                    continue
                if chunk.get("usage"):
                    req.tokens = chunk["usage"].get("completion_tokens", 0)
                for ch in chunk.get("choices") or []:
                    if ch.get("finish_reason"):
                        req.extra["finish_reason"] = ch["finish_reason"]
                        continue
                    now = time.monotonic()
                    if req.t_first is None:
                        req.t_first = now
                    req.t_last = now
                    req.frames += 1
                    # when each frame came and how much it carried: the byte
                    # tokenizer decodes a token to one character (all but
                    # the 259 lowest ids of the vocabulary), and `usage`
                    # gives the exact total at the end (metrics.py)
                    req.frame_at.append(now)
                    req.frame_chars.append(len(ch.get("text") or ""))
            req.ok = done and req.error is None and req.t_first is not None
            if req.error is None and not req.ok:
                req.error = ("no frame carried text" if done
                             else "stream ended without [DONE]")
    except asyncio.CancelledError:
        req.error = "unfinished at the drain deadline"
        raise
    except Exception as e:  # noqa: BLE001 — any fault of a request is a result
        req.error = f"{type(e).__name__}: {e}"[:200]
    finally:
        req.t_end = time.monotonic()
    return req


class Load:
    """Offers a cell's traffic and keeps every request it sent."""

    def __init__(self, session, base: str, model: str, temperature: float):
        self.session, self.base, self.model = session, base, model
        self.temperature = temperature
        self.sent: List[Request] = []
        self.tasks: List[asyncio.Task] = []
        self._clients: List[asyncio.Task] = []
        self._stop = False
        self._gate: Optional[dict] = None
        self.phase = "warm"

    def _launch(self, req: Request, t_due: float) -> None:
        req.t_due = t_due
        self.sent.append(req)
        self.tasks.append(asyncio.create_task(self._run(req)))

    async def _run(self, req: Request) -> None:
        await send(self.session, self.base, self.model, self.temperature, req)
        nxt = req.next_turn
        if nxt is not None and not self._stop:
            # a session's next turn: due `think_s` after this one ended
            t_due = req.t_end + req.think_s
            await asyncio.sleep(max(t_due - time.monotonic(), 0))
            if not self._stop:
                nxt.phase = self.phase
                self._launch(nxt, t_due)

    async def open_block(self, schedule: List[Request], t0: float) -> None:
        """Send each request when it is due (t0 + due), whatever the server
        is doing; returns when the last of the block is sent."""
        for req in schedule:
            t_due = t0 + req.due
            delay = t_due - time.monotonic()
            if delay > 0:
                await asyncio.sleep(delay)
            self._launch(req, t_due)

    def start_clients(self, streams: List[List[Request]]) -> None:
        """Closed loop: each client sends its next request when the last
        ends; a request is due the moment the one before it ended."""

        async def client(stream: List[Request]) -> None:
            i = 0
            while not self._stop:
                gate = self._gate
                if gate is not None and gate["waiting"] < gate["need"]:
                    gate["waiting"] += 1
                    if gate["waiting"] == gate["need"]:
                        gate["open"].set()
                    await gate["open"].wait()
                src = stream[i % len(stream)]
                req = Request(rid=f"{src.rid}r{i // len(stream)}",
                              prompt=src.prompt, max_tokens=src.max_tokens,
                              phase=self.phase)
                req.t_due = time.monotonic()
                self.sent.append(req)
                await send(self.session, self.base, self.model,
                           self.temperature, req)
                i += 1

        self._clients = [asyncio.create_task(client(s)) for s in streams]

    async def bursts(self, sizes: List[int]) -> None:
        """Closed loop, warm phase only: for each k, hold the next k clients
        that finish and release them together, so that k prompts arrive in
        one step. A closed loop's arrivals bunch like this by chance, rarely
        for large k, and each bunch size is a token bucket of the engine's
        mixed step: a program that would otherwise compile inside a window."""
        for k in sizes:
            self._gate = {"need": k, "waiting": 0, "open": asyncio.Event()}
            await self._gate["open"].wait()
            await asyncio.sleep(0.5)
        self._gate = None

    def stop_offering(self) -> None:
        self._stop = True

    async def drain(self, deadline_s: float) -> int:
        """Wait for every request in flight; what is unfinished at the
        deadline is cancelled and counts as failed. Returns how many."""
        pending = [t for t in self.tasks + self._clients if not t.done()]
        if not pending:
            return 0
        _, late = await asyncio.wait(pending, timeout=deadline_s)
        for t in late:
            t.cancel()
        if late:
            await asyncio.wait(late, timeout=10)
        return len(late)

    def in_flight(self) -> int:
        return sum(1 for r in self.sent if r.t_end is None)


# ---------------------------------------------------------------------- #
# the worker's stats, off the metrics topic it already publishes
# ---------------------------------------------------------------------- #


class StatsWatch:
    """Keeps the newest `JaxEngine.stats()` the worker published (every
    0.25 s, for the router and planner)."""

    def __init__(self, discovery_addr: str, component: str = "backend"):
        self.addr, self.component = discovery_addr, component
        self.latest: Optional[dict] = None
        self.seen = 0
        self._task: Optional[asyncio.Task] = None

    async def start(self) -> None:
        from dynamo_tpu.runtime.discovery import DiscoveryClient

        host, port = self.addr.rsplit(":", 1)
        self._cli = await DiscoveryClient.connect(host, int(port))
        self._sub = await self._cli.subscribe(f"kv_metrics/dynamo/{self.component}")
        self._task = asyncio.create_task(self._loop())

    async def _loop(self) -> None:
        from dynamo_tpu.runtime import codec

        async for payload in self._sub:
            self.latest = codec.unpack(payload).get("stats", {})
            self.seen += 1

    async def fresh(self, timeout: float = 15.0) -> dict:
        """Stats published after this call."""
        n, t_end = self.seen, time.monotonic() + timeout
        while self.seen == n:
            if time.monotonic() > t_end:
                raise TimeoutError("no stats on the worker's metrics topic")
            await asyncio.sleep(0.05)
        return self.latest

    async def close(self) -> None:
        if self._task:
            self._task.cancel()
            await asyncio.gather(self._task, return_exceptions=True)
            await self._sub.cancel()
            await self._cli.close()


# ---------------------------------------------------------------------- #
# greedy re-sends over the request plane: token ids and log-probabilities
# ---------------------------------------------------------------------- #


ROUTED_EXPERTS = "routed_experts"  # the annotation asked, and the reply's key


def distinct_ids(ids, count: int, under: int) -> bool:
    """`count` distinct whole numbers in [0, under), as a list."""
    return (isinstance(ids, list) and len(ids) == count == len(set(ids)) and all(
        isinstance(i, int) and not isinstance(i, bool) and 0 <= i < under for i in ids))


def routed_rows(rows: list, inputs: int, geometry: tuple) -> list:
    """A reply's `routed_experts` held to the wire contract (README.md): one
    row for each input position of prompt + served[:-1], in order (a row for
    the last served token, which nothing reads, is dropped), each row [routed
    layers][experts a token] of distinct expert ids in range. Raises
    ValueError with the reason; what passes goes to the reference as it is."""
    experts, per_token, layers = geometry
    if len(rows) == inputs + 1:
        rows = rows[:-1]
    if len(rows) != inputs:
        raise ValueError(f"{len(rows)} rows of `{ROUTED_EXPERTS}` for {inputs} input "
                         f"positions (prompt + served[:-1])")
    for at, row in enumerate(rows):
        if not (isinstance(row, list) and len(row) == layers):
            raise ValueError(f"row {at} of `{ROUTED_EXPERTS}` holds "
                             f"{len(row) if isinstance(row, list) else row!r} layers, the "
                             f"configuration has {layers} routed ones")
        for layer, ids in enumerate(row):
            if not distinct_ids(ids, per_token, experts):
                raise ValueError(f"row {at}, layer {layer} of `{ROUTED_EXPERTS}` is {ids!r}: "
                                 f"not {per_token} distinct expert ids under {experts}")
    return rows


TOP_LOGPROBS = "top_logprobs"  # the reply's key, one entry a served token


def top_tokens(entries: list, served_ids: list, vocab_size: int) -> tuple:
    """A reply's `top_logprobs` held to the top-token contract (README.md):
    one entry for each served token, in order, each {"ids": [...], "logprobs":
    [...]} of TOP_TOKENS distinct token ids in range, the served token among
    them, and as many finite log-probabilities. Raises ValueError with the
    reason; what passes goes to the reference as (ids, log-probabilities), each
    [served tokens][TOP_TOKENS]."""
    if len(entries) != len(served_ids):
        raise ValueError(f"{len(entries)} entries of `{TOP_LOGPROBS}` for "
                         f"{len(served_ids)} served tokens")
    for at, (entry, served) in enumerate(zip(entries, served_ids)):
        ids, lps = ((entry.get("ids"), entry.get("logprobs")) if isinstance(entry, dict)
                    else (None, None))
        if not (distinct_ids(ids, TOP_TOKENS, vocab_size) and served in ids):
            raise ValueError(f"entry {at} of `{TOP_LOGPROBS}` has the ids {ids!r}: not "
                             f"{TOP_TOKENS} distinct token ids under {vocab_size} with the "
                             f"served token {served} among them")
        if not (isinstance(lps, list) and len(lps) == TOP_TOKENS and all(
                isinstance(v, (int, float)) and not isinstance(v, bool) and math.isfinite(v)
                for v in lps)):
            raise ValueError(f"entry {at} of `{TOP_LOGPROBS}` has the log-probabilities "
                             f"{lps!r}: not {TOP_TOKENS} finite numbers")
    return ([e["ids"] for e in entries], [e["logprobs"] for e in entries])


def resend_body(model: str, pick: dict, forced: bool) -> dict:
    """The OpenAI body of a re-sent request. A family judged free sends what
    it always sent (`logprobs` 0: the served token's log-probability alone);
    one judged forced asks for the TOP_TOKENS tokens a position puts first,
    over the same option."""
    return {
        "model": model, "prompt": pick["prompt"],
        "max_tokens": pick["max_tokens"], "temperature": 0,
        "stream": False, "nvext": {"ignore_eos": True},
        # the served log-probability of each served token rides the
        # existing logprobs option, and so do a forced family's top tokens
        "logprobs": TOP_TOKENS if forced else 0,
    }


def resend_wire(pre, model: str, pick: dict, forced: bool) -> tuple:
    """(the preprocessed request, the dictionary that goes over the request
    plane): the frontend's own preprocessing of `resend_body`, and for a
    family judged forced the annotation that asks for the experts chosen."""
    from dynamo_tpu.llm.protocols import CompletionRequest

    req = pre.preprocess_completion(CompletionRequest(**resend_body(model, pick, forced)))
    wire = req.to_dict()
    if forced:
        wire["annotations"] = [*(wire.get("annotations") or []), ROUTED_EXPERTS]
    return req, wire


async def resend_greedy(discovery_addr: str, model: str, vocab_size: int,
                        context_length: int, picks: List[dict],
                        fail: Callable[[str], Exception],
                        routed: Optional[tuple] = None) -> dict:
    """The picked requests again, at temperature 0, on the worker's generate
    endpoint, all at once. The preprocessor is the frontend's own, so the
    wire request is what the frontend sends. Token ids and not text: the
    byte tokenizer's decode does not round-trip. `routed`: the geometry
    (experts, experts a token, routed layers) of a configuration whose routing
    is judged forced; the worker is then asked for the experts it chose and
    the reply is held to them (`routed_rows`): a reply without them fails the
    run, it is never judged free. Such a family is also asked for the tokens
    each position puts first and held to them (`top_tokens`): never judged
    over one token instead."""
    from dynamo_tpu.llm.model_card import ModelDeploymentCard
    from dynamo_tpu.llm.preprocessor import OpenAIPreprocessor
    from dynamo_tpu.llm.tokenizers import load_tokenizer
    from dynamo_tpu.runtime import DistributedRuntime, RuntimeConfig

    tok = load_tokenizer(f"byte:{vocab_size}")
    pre = OpenAIPreprocessor(
        ModelDeploymentCard(name=model, tokenizer="byte",
                            kv_cache_block_size=PAGE_SIZE,
                            context_length=context_length),
        tok,
    )
    cfg = RuntimeConfig()
    cfg.discovery_endpoint = discovery_addr
    drt = await DistributedRuntime.create(cfg)
    try:
        ep = drt.namespace("dynamo").component("backend").endpoint("generate")
        client = await ep.client()
        (instance,) = await client.wait_for_instances(timeout=30)

        async def one(pick: dict) -> dict:
            req, wire = resend_wire(pre, model, pick, bool(routed))
            out, lps, rows, tops = [], [], [], []
            stream = await client.direct(wire, instance)
            async for item in stream:
                if item.get("event") == "error":
                    raise fail(f"{pick['why']}: {item.get('comment')}")
                data = item.get("data") or {}
                out.extend(data.get("token_ids") or [])
                lps.extend(data.get("log_probs") or [])
                rows.extend(data.get(ROUTED_EXPERTS) or [])
                tops.extend(data.get(TOP_LOGPROBS) or [])
            if not len(out) == len(lps) == pick["max_tokens"]:
                raise fail(
                    f"{pick['why']}: {len(out)} token ids and {len(lps)} "
                    f"logprobs over the request plane, {pick['max_tokens']} asked"
                )
            served = {"prompt_ids": list(req.token_ids), "served_ids": out,
                      "served_logprobs": lps, "why": pick["why"]}
            if routed:
                try:
                    served["served_top_ids"], served["served_top_logprobs"] = top_tokens(
                        tops, out, vocab_size)
                    served[ROUTED_EXPERTS] = routed_rows(
                        rows, len(req.token_ids) + len(out) - 1, routed)
                except ValueError as e:
                    raise fail(f"{pick['why']}: forced routing: {e}") from e
            return served

        served = await asyncio.wait_for(
            asyncio.gather(*[one(p) for p in picks]), timeout=180
        )
    finally:
        await drt.close()
    return {f"{i}.{p['why']}": s for i, (p, s) in enumerate(zip(picks, served))}
