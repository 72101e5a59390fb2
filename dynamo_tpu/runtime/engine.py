"""Streaming engine abstraction + cancellation contexts.

Mirrors reference lib/runtime/src/engine.rs: `AsyncEngine` (:201) is the
universal request→response-stream interface every layer speaks;
`AsyncEngineContext` (:112) carries id + cancellation ("kill switch")
down the pipeline; `ResponseStream` (:213) pairs a stream with its context.

In dynamo-tpu an engine is any object with
    async def generate(request, context) -> AsyncIterator[response]
Operators (preprocessor, backend, migration, router) wrap engines; the
outermost stream is consumed by the HTTP frontend.
"""

from __future__ import annotations

import asyncio
import secrets
import time
from typing import Any, AsyncIterator, Awaitable, Callable, Dict, Optional, Protocol, runtime_checkable


def _boot_id() -> str:
    """What two processes share exactly when their `time.monotonic()`
    clocks compare: the kernel's id of this boot. Where the kernel gives
    none, a token of this process alone (its clock compares with itself)."""
    try:
        with open("/proc/sys/kernel/random/boot_id") as f:
            return f.read().strip()
    except OSError:
        return secrets.token_hex(8)


#: a request's path from the HTTP accept to the first SSE write, in order
#: (docs/observability.md, "A request's path"): the frontend's four, the
#: hop, the worker's four, and the frontend's last
STAGES = ("http", "preprocess", "route", "send", "hop", "ingest", "queue",
          "first", "first_frame", "sse")

#: rides the request plane beside a request's stage times, so that the
#: receiver knows whether the sender's monotonic stamps mean anything to it
BOOT_ID = _boot_id()


class Context:
    """Cancellation context propagated through the pipeline
    (reference AsyncEngineContext engine.rs:112).

    `stop_generating` = graceful: finish the current token, emit a final
    usage chunk. `kill` = hard: stop streaming immediately. Child contexts
    form a cancellation tree like the reference's token hierarchy.

    A context may carry a `deadline` (absolute `time.monotonic()` value):
    the end-to-end budget for the request. Connect attempts, retry loops
    (migration) and backoff waits clip to it — past the deadline they stop
    retrying and surface a clean error instead of spinning. Children
    inherit the tightest deadline on the parent chain; the deadline also
    crosses the request plane (`deadline_ms` on the wire) so worker-side
    contexts see the same budget.

    A context also carries the request's timeline (docs/observability.md,
    "A request's path"): `stages`, seconds by stage name, and `stamp_s`,
    the `time.monotonic()` of the last stamp. `stamp(stage)` closes a stage
    where its work ends: the time since the last stamp is the stage's. A
    context nobody began (`begin`; `stamp_s` is 0) keeps no times, and a
    child begins none.
    """

    def __init__(
        self,
        id: Optional[str] = None,
        parent: Optional["Context"] = None,
        deadline: Optional[float] = None,
    ):
        self._id = id or secrets.token_hex(8)
        self._stopped = asyncio.Event()
        self._killed = asyncio.Event()
        self._parent = parent
        self._deadline = deadline
        self._children: list[Context] = []
        # the worker instance the last routed dial targeted (set by
        # Client.direct): when the stream dies, migration reads this to
        # exclude the dead instance from the retry's re-route
        # (docs/fault_tolerance.md "Request migration")
        self.routed_instance: Optional[int] = None
        self.stages: Dict[str, float] = {}
        self.stamp_s = 0.0
        # who else keeps the stages stamped from here on (the engine's
        # recorder, once the request has reached it)
        self.on_stamp: Optional[Callable[[str, float], None]] = None
        # when the handler handed the first token to its stream, where it
        # says so: the request plane's server then closes `first_frame`
        self.first_token_s = 0.0
        if parent is not None:
            parent._children.append(self)

    def begin(self, at: float) -> "Context":
        """Start the timeline at `at`, a `time.monotonic()` taken where the
        request was accepted."""
        self.stamp_s = at
        return self

    def stamp(self, stage: str, now: Optional[float] = None) -> float:
        """Close `stage` at `now` (this moment where none is given): what
        has passed since the last stamp accrues to it. Returns `now`, or 0
        on a context whose timeline nobody began, which keeps no times."""
        if not self.stamp_s:
            return 0.0
        if now is None:
            now = time.monotonic()
        spent = now - self.stamp_s
        self.stages[stage] = self.stages.get(stage, 0.0) + spent
        self.stamp_s = now
        if self.on_stamp is not None:
            self.on_stamp(stage, spent)
        return now

    @property
    def id(self) -> str:
        return self._id

    @property
    def deadline(self) -> Optional[float]:
        """Effective deadline: the tightest on the parent chain."""
        own = self._deadline
        if self._parent is not None:
            inherited = self._parent.deadline
            if inherited is not None and (own is None or inherited < own):
                return inherited
        return own

    def set_deadline(self, seconds_from_now: float) -> "Context":
        self._deadline = time.monotonic() + seconds_from_now
        return self

    def time_remaining(self) -> Optional[float]:
        """Seconds until the deadline (>= 0), or None when unbounded."""
        dl = self.deadline
        return None if dl is None else max(0.0, dl - time.monotonic())

    def deadline_exceeded(self) -> bool:
        dl = self.deadline
        return dl is not None and time.monotonic() >= dl

    def is_stopped(self) -> bool:
        return self._stopped.is_set() or (self._parent is not None and self._parent.is_stopped())

    def is_killed(self) -> bool:
        return self._killed.is_set() or (self._parent is not None and self._parent.is_killed())

    def stop_generating(self):
        self._stopped.set()
        for child in self._children:
            child.stop_generating()

    def kill(self):
        self._killed.set()
        self._stopped.set()
        for child in self._children:
            child.kill()

    async def stopped(self):
        """Wait until stop is requested."""
        await self._wait_event(lambda c: c._stopped)

    async def killed(self):
        """Wait until hard kill is requested."""
        await self._wait_event(lambda c: c._killed)

    async def _wait_event(self, get_event):
        if self._parent is None:
            await get_event(self).wait()
            return
        parent_task = asyncio.create_task(self._parent._wait_event(get_event))
        own_task = asyncio.create_task(get_event(self).wait())
        done, pending = await asyncio.wait(
            [parent_task, own_task], return_when=asyncio.FIRST_COMPLETED
        )
        for task in pending:
            task.cancel()

    def child(self, id: Optional[str] = None) -> "Context":
        return Context(id=id or self._id, parent=self)


@runtime_checkable
class AsyncEngine(Protocol):
    """The universal streaming engine interface (reference engine.rs:201)."""

    def generate(self, request: Any, context: Context) -> AsyncIterator[Any]:
        ...


class FnEngine:
    """Adapt a plain async-generator function into an AsyncEngine."""

    def __init__(self, fn: Callable[[Any, Context], AsyncIterator[Any]], name: str = "fn"):
        self._fn = fn
        self.name = name

    def generate(self, request: Any, context: Context) -> AsyncIterator[Any]:
        return self._fn(request, context)


class ResponseStream:
    """An async response stream bound to its engine context
    (reference ResponseStream engine.rs:213)."""

    def __init__(self, stream: AsyncIterator[Any], context: Context):
        self._stream = stream
        self.context = context

    def __aiter__(self):
        return self

    async def __anext__(self):
        if self.context.is_killed():
            raise StopAsyncIteration
        return await self._stream.__anext__()


async def collect(stream: AsyncIterator[Any]) -> list:
    """Drain a stream into a list (test helper)."""
    return [item async for item in stream]
