"""The engine's span-and-counter recorder (docs/observability.md).

One table for the engine's loop, exported through `JaxEngine.stats()`:

* PHASES of an iteration. `with rec.span("pack"):` around the leaf work
  accrues `phase_pack_count`, `phase_pack_s` and, for a span of
  SLOW_SPAN_S or more, `phase_pack_slow`, and is a
  `jax.profiler.TraceAnnotation("engine.pack")`, which records only
  while a profiler session is open: the spans then lie in the
  profiler's own trace, on its clock, beside the device's operations.
  Spans do not nest and none is held across an `await` but `wait`, so
  the seven sums add up to no more than the clock. Beside the `put`
  spans, `put_arrays`: the host arrays handed to the runtime inside them
  (engine._put counts its own; a transfer made some other way it cannot
  see), so that `put_arrays / phase_put_count` says how many transfers a
  dispatch makes.
* STEP_KINDS of pipeline entries. An entry is stamped when it is
  dispatched and timed when its fetch returns: `step_<kind>_count`,
  `step_<kind>_interval_s` (ready to ready: the device's time for the
  entry plus whatever the device waited for the host inside it; an
  interval of SLOW_SPAN_S or more is a stall of the pipeline and goes to
  `step_stalled_count`, `step_stalled_s` instead), and the work it was
  asked for, `step_model_flops` and `step_min_bytes`
  (models/<family>.step_work), and of the bytes a recurrent state's,
  `step_state_bytes`, and the held experts', `step_expert_bytes`; beside
  them what the window layers' K and V cost and would cost without the
  window, `step_window_kv_bytes` and `step_window_kv_whole_bytes` (each
  exported once it is not 0: models/hybrid.py, models/nemotron_h.py,
  models/exaone_moe.py). A family may instead end its count with a dict
  that names its shares (models/mla_moe.py: `step_latent_kv_bytes`,
  `step_latent_kv_expanded_bytes`, `step_expert_bytes`, and for a
  configuration that selects the rows a token attends
  `step_index_kv_bytes`, `dsa_context_rows`, `dsa_selected_rows`). An entry also
  names its PROGRAM (a block of K steps, a mixed step of a token bucket):
  the intervals of a program's last ESTIMATE_RUNS runs stay, and the
  shortest of them is the engine's estimate of its next run
  (`estimate`), from which the loop times the running entry's successor
  (engine._await_successor).
* the successor's wait (docs/observability.md, "The engine's iteration"):
  `successor_waits` (the loop held the second entry of the pipeline back
  until the running one was about to end), `successor_woken` (a request
  was admitted inside the wait), `successor_late` (the running entry's
  fetch returned before its successor's launch had), and
  `step_starved_s`: the seconds between an entry's end (its fetch's
  return: asked for at the start of the wait, so the entry's own) and
  the launch of the next entry, summed wherever the second came after
  the first: the device stood idle with work at hand.
* the waits ahead of a first token: `req_admitted`, `req_queue_wait_s`,
  `req_first_tokens`, `req_admit_to_first_s`, and the pipeline entries
  fetched between a request's arrival and its first token, its own among
  them: `req_blocks_ahead`, `req_mixed_ahead`.
* STAGES of a request's path (docs/observability.md, "A request's path"):
  `req_stage_<stage>_count`, `req_stage_<stage>_s`. A request that came
  with a timeline on its context (runtime/engine.Context: the request
  plane's server began it from the caller's header) brings the caller's
  stages with it, `ingest` is closed here (the server's arrival to the
  slot's: unpack, checks, the prompt's hashing; a
  `TraceAnnotation("engine.ingest")` round the slot's making), and
  `first_frame` by the request plane's server through the context's
  `on_stamp`. A request that came with none counts nothing.
* the device calls' host clock by tag (`dispatch_<tag>_count`, `_s`:
  engine._timed).

Counters are always on; there is no flag. With no profiler open a span
costs about a microsecond (tests/test_engine_recorder.py holds it under
three).
"""

from __future__ import annotations

import logging
import time
from collections import deque
from typing import Callable, Dict, List, Optional

from jax.profiler import TraceAnnotation

from ..runtime.engine import STAGES as PATH_STAGES

logger = logging.getLogger(__name__)

PHASES = ("admit", "pack", "put", "launch", "fetch", "emit", "wait")
STEP_KINDS = ("block", "mixed", "prefill")
#: the stages of a request's path with a row here: the caller's four, the
#: hop, and the worker's two (`queue` and `first` are the two waits' rows,
#: `sse` is the frontend's alone)
STAGES = tuple(s for s in PATH_STAGES if s not in ("queue", "first", "sse"))
#: a span this long is a stall of the loop: counted, and logged unless
#: the phase is `wait` (an idle engine is no stall). An entry's interval
#: this long is one too (a program compiling inside its launch, the
#: profiler's stop, a paused guest: no step of a served model takes it)
SLOW_SPAN_S = 0.5
#: a program's estimate is the shortest of this many of its last runs: it
#: errs early, and one run that a pause stretched cannot poison it
ESTIMATE_RUNS = 8


class _Span:
    """One use of one phase: a context manager, made anew for each use so
    that the threads that share a phase share no state but its row."""

    __slots__ = ("_rec", "_name", "_more", "_ann", "_t0")

    def __init__(self, rec: "Recorder", name: str, more: bool):
        self._rec, self._name, self._more = rec, name, more

    def __enter__(self):
        self._ann = TraceAnnotation(self._rec._labels[self._name])
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self._t0
        self._ann.__exit__(*exc)
        row = self._rec.phases[self._name]
        row[1] += dt
        if not self._more:
            row[0] += 1
        if dt >= SLOW_SPAN_S:
            self._rec._slow(self._name, row, dt)
        return False


class Work:
    """What one forward pass over an entry's rows asks for, summed row by
    row as models/<family>.step_work takes it: real tokens, positions
    attended, positions whose K and V are read, tokens sampled."""

    __slots__ = ("real", "context", "kv_tokens", "sampled", "rows", "cap",
                 "attended", "selected")

    def __init__(self, cap: int = 0):
        self.real = self.context = self.kv_tokens = self.sampled = 0
        self.rows = 0
        # a family that selects at most `cap` positions of a context a
        # token (models/mla_moe.py: index_topk; 0: none) is also told the
        # positions its tokens ATTEND, each at most `cap`, and the rows of
        # the cache its rows must READ, at most `cap` a row
        self.cap = cap
        self.attended = self.selected = 0

    def chunk(self, start: int, n: int, completes: bool):
        """A prompt's chunk of `n` tokens behind `start`: token j attends
        start + j + 1 positions, the row reads start + n once, and samples
        if the prompt ends in it."""
        self.real += n
        self.context += n * start + n * (n + 1) // 2
        self.kv_tokens += start + n
        self.sampled += bool(completes)
        self.rows += 1
        if self.cap:
            # the first m tokens see fewer than `cap` positions
            m = min(max(self.cap - start, 0), n)
            self.attended += m * start + m * (m + 1) // 2 + (n - m) * self.cap
            self.selected += min(start + n, self.cap)

    def decode(self, seq_len: int):
        """A decode row at a context of `seq_len`, its own token in it."""
        self.real += 1
        self.context += seq_len
        self.kv_tokens += seq_len
        self.sampled += 1
        self.rows += 1
        if self.cap:
            self.attended += min(seq_len, self.cap)
            self.selected += min(seq_len, self.cap)

    def of(self, step_work) -> tuple:
        more = {"attended": self.attended, "kv_selected": self.selected} \
            if self.cap else {}
        return step_work(self.real, self.context, 1,
                         kv_tokens=self.kv_tokens, sampled=self.sampled,
                         rows=self.rows, **more)


class Recorder:
    def __init__(self, describe: Optional[Callable[[], str]] = None):
        # [count, seconds, slow] by phase; one writer thread a phase (the
        # loop's: admit, pack, emit, wait; the device thread's: put,
        # launch; the fetch thread's: fetch), so no lock
        self.phases: Dict[str, list] = {p: [0, 0.0, 0] for p in PHASES}
        self.put_arrays = 0  # host arrays handed over inside `put` spans
        self._labels = {p: f"engine.{p}" for p in PHASES}
        # [entries, seconds ready to ready] by kind
        self.steps: Dict[str, list] = {k: [0, 0.0] for k in STEP_KINDS}
        self.stalled = [0, 0.0]  # the same of entries whose interval stalled
        self.model_flops = 0
        self.min_bytes = 0
        self.state_bytes = 0  # of min_bytes, a recurrent state's (hybrid)
        # of min_bytes, a latent cache's rows, and what the same positions
        # would cost as heads of K and V (mla_moe)
        self.latent_kv_bytes = 0
        self.latent_kv_expanded_bytes = 0
        # ... and where the family selects the rows a token attends: the
        # index keys' bytes, the positions its rows had behind them and
        # the rows of those they had to read
        self.index_kv_bytes = 0
        self.dsa_context_rows = self.dsa_selected_rows = 0
        self.expert_bytes = 0  # of min_bytes, the held experts' (nemotron_h)
        # K and V bytes the window layers read, and would at the whole
        # context (exaone_moe)
        self.window_kv_bytes = self.window_kv_whole_bytes = 0
        # (count, seconds) of the host's clock around a device call, by tag
        self.dev_time: Dict[str, tuple] = {}
        self.req_admitted = 0
        self.req_queue_wait_s = 0.0
        self.req_first_tokens = 0
        self.req_admit_to_first_s = 0.0
        self.blocks_ahead = self.mixed_ahead = 0
        # entries fetched so far by kind, the stalled ones among them
        self.fetched_n: Dict[str, int] = {k: 0 for k in STEP_KINDS}
        # [requests, seconds] by stage; one writer, the event loop's thread
        self.stages: Dict[str, list] = {s: [0, 0.0] for s in STAGES}
        self.hop_unmeasured = 0
        # what the loop is working on, for a slow span's log line
        self.entry_kind = "none"
        self._describe = describe
        self._last_ready = 0.0
        # the host's clock of every entry's stamps (a test drives its own)
        self.clock = time.perf_counter
        # ready-to-ready intervals of each program's last runs
        self.runs: Dict[tuple, deque] = {}
        self.successor_waits = self.successor_woken = 0
        self.successor_late = 0
        self.starved_s = 0.0
        # when the last entry in flight came back with none behind it:
        # the device stands idle until the next launch
        self._unfollowed: Optional[float] = None

    # -- phases ---------------------------------------------------------- #

    def span(self, name: str, more: bool = False) -> _Span:
        """`more`: the rest of a span that an `await` cut in two: its time
        counts, and it is not counted again."""
        return _Span(self, name, more)

    def _slow(self, name: str, row: list, dt: float):
        row[2] += 1
        if name != "wait":
            logger.warning(
                "engine phase %s took %.3f s: entry %s, %s", name, dt,
                self.entry_kind,
                self._describe() if self._describe else "no engine",
            )

    def timed(self, tag: str, dt: float):
        cnt, tot = self.dev_time.get(tag, (0, 0.0))
        self.dev_time[tag] = (cnt + 1, tot + dt)

    # -- pipeline entries ------------------------------------------------ #

    def dispatched(self, entry: dict, kind: str, work: tuple,
                   program: Optional[tuple] = None):
        """Stamp `entry` as it goes to the device: its kind, its program
        (what `estimate` keys a run's length by; None: never estimated),
        the host's clock, and the (useful operations, least bytes) it was
        asked for; a
        family with a recurrent state says third how many of the bytes are
        the state's (models/hybrid.step_work), may say fourth how many
        are the held experts' (models/nemotron_h.step_work), and fifth and
        sixth the window layers' K and V bytes read and what they would be
        at the whole context (models/exaone_moe.step_work); or, last, a
        dict that names its shares (models/mla_moe.step_work)."""
        entry["step_kind"] = kind
        entry["program"] = program
        entry["t_dispatch"] = self.clock()
        if isinstance(work[-1], dict):
            # a family that names its shares of the bytes (models/mla_moe.
            # step_work) in place of counting on their order
            *work, named = work
            self.expert_bytes += named["expert_bytes"]
            self.latent_kv_bytes += named["latent_kv_bytes"]
            self.latent_kv_expanded_bytes += named["latent_kv_expanded_bytes"]
            self.index_kv_bytes += named.get("index_kv_bytes", 0)
            self.dsa_context_rows += named.get("dsa_context_rows", 0)
            self.dsa_selected_rows += named.get("dsa_selected_rows", 0)
        self.model_flops += work[0]
        self.min_bytes += work[1]
        if len(work) > 2:
            self.state_bytes += work[2]
        if len(work) > 3:
            self.expert_bytes += work[3]
        if len(work) > 5:
            self.window_kv_bytes += work[4]
            self.window_kv_whole_bytes += work[5]

    def launched(self, entry: dict):
        """The launch of `entry` has returned. If the entry before it had
        come back already, the device stood idle from then to now."""
        entry["t_launched"] = now = self.clock()
        if self._unfollowed is not None:
            self.starved_s += max(now - self._unfollowed, 0.0)
            self._unfollowed = None

    def idle(self):
        """Nothing is in flight and nothing is asked for: the device's
        idleness from here on is nobody's wait."""
        self._unfollowed = None

    def began(self, entry: dict) -> float:
        """When the oldest entry in flight began to run: at the return of
        the fetch before it, or at its own dispatch if that came later."""
        return max(self._last_ready, entry["t_dispatch"])

    def estimate(self, entry: dict) -> Optional[float]:
        """The engine's own reading of how long `entry`'s program runs:
        the shortest of its last runs, or None before its first."""
        runs = self.runs.get(entry["program"])
        return min(runs) if runs else None

    def fetched(self, entries: List[dict], t_ready: float,
                next_launched: Optional[float] = None,
                waited: bool = False):
        """The fetch that brought these entries back returned at `t_ready`:
        their interval runs from the later of the fetch before it and
        their dispatch. Entries that one fetch brings back together (a
        split prefill beside a block) share it in equal parts: the host
        cannot tell them apart. A stalled interval is kept out of its
        kind's sum, which a window's mean is taken from: one profiler's
        stop of 2.5 s would move 600 blocks' mean by 4 ms, and out of its
        program's runs. `next_launched`: when the launch of the oldest
        entry still in flight returned, None with nothing in flight: if
        that is after `t_ready` the device stood idle between, and where
        the loop had `waited` with the successor (the fetch was asked for
        before the successor was queued) the successor came late."""
        if not entries:
            return
        since = max(self._last_ready, min(e["t_dispatch"] for e in entries))
        share = max(t_ready - since, 0.0) / len(entries)
        for e in entries:
            self.fetched_n[e["step_kind"]] += 1
            row = self.stalled if share >= SLOW_SPAN_S \
                else self.steps[e["step_kind"]]
            row[0] += 1
            row[1] += share
        program = entries[0]["program"] if len(entries) == 1 else None
        if program is not None and share < SLOW_SPAN_S:
            # an entry that came back alone: the interval is its program's
            self.runs.setdefault(
                program, deque(maxlen=ESTIMATE_RUNS)).append(share)
        self._last_ready = t_ready
        if next_launched is None:
            self._unfollowed = t_ready
        else:
            self.starved_s += max(next_launched - t_ready, 0.0)
        if waited and (next_launched is None or next_launched > t_ready):
            self.successor_late += 1

    # -- a request's path ------------------------------------------------ #

    def ingest(self) -> TraceAnnotation:
        """Round the making of a request's slot: the span `engine.ingest`
        of a profiler's trace, beside the seven phases."""
        return TraceAnnotation("engine.ingest")

    def stage(self, name: str, seconds: float):
        """One request's `seconds` in stage `name`; a stage with no row
        here (`queue`, `first`: the waits' rows have them) is not kept."""
        row = self.stages.get(name)
        if row is not None:
            row[0] += 1
            row[1] += seconds

    def arrived(self, slot) -> None:
        """A request's slot is made: its arrival, and how many entries had
        been fetched by then. Where the request came with a timeline, the
        caller's stages are counted, `ingest` is closed, and what is
        stamped on its context from here on (`first_frame`, by the request
        plane's server) comes here too. Once a context: a decode entry
        that falls back to a local prefill makes a second slot."""
        ctx = slot.context
        slot.arrival_s = now = time.monotonic()
        slot.fetched_at_arrival = (self.fetched_n["block"], self.fetched_n["mixed"])
        if not ctx.stamp_s or ctx.on_stamp is not None:
            return
        for name, seconds in ctx.stages.items():
            self.stage(name, seconds)
        if "send" in ctx.stages and "hop" not in ctx.stages:
            self.hop_unmeasured += 1  # the caller's clock is another host's
        ctx.on_stamp = self.stage
        ctx.stamp("ingest", now)

    # -- the waits ahead of a first token -------------------------------- #

    def admitted(self, slot) -> None:
        """The first admission of a request (a preempted resume keeps its
        first one)."""
        if slot.admit_s:
            return
        slot.admit_s = time.monotonic()
        self.req_admitted += 1
        self.req_queue_wait_s += max(slot.admit_s - slot.arrival_s, 0.0)
        slot.context.stamp("queue", slot.admit_s)

    def first_token(self, slot) -> None:
        """The first token of a request handed to its stream."""
        if slot.first_token_s or not slot.admit_s:
            return
        slot.first_token_s = time.monotonic()
        self.req_first_tokens += 1
        self.req_admit_to_first_s += slot.first_token_s - slot.admit_s
        blocks, mixed = slot.fetched_at_arrival
        self.blocks_ahead += self.fetched_n["block"] - blocks
        self.mixed_ahead += self.fetched_n["mixed"] - mixed
        ctx = slot.context
        ctx.first_token_s = ctx.stamp("first", slot.first_token_s)

    # -- export ---------------------------------------------------------- #

    def stats(self) -> dict:
        out = {
            # the denominator of every share formed from these counters
            "engine_clock_s": round(time.monotonic(), 6),
            # floats on the wire: a busy worker's operations pass 2**63
            # within days (the sums themselves stay exact integers)
            "step_model_flops": float(self.model_flops),
            "step_min_bytes": float(self.min_bytes),
            "step_stalled_count": self.stalled[0],
            "step_stalled_s": round(self.stalled[1], 6),
            "put_arrays": self.put_arrays,
            "req_admitted": self.req_admitted,
            "req_queue_wait_s": round(self.req_queue_wait_s, 6),
            "req_first_tokens": self.req_first_tokens,
            "req_admit_to_first_s": round(self.req_admit_to_first_s, 6),
            "req_blocks_ahead": self.blocks_ahead,
            "req_mixed_ahead": self.mixed_ahead,
            "req_hop_unmeasured": self.hop_unmeasured,
            "successor_waits": self.successor_waits,
            "successor_woken": self.successor_woken,
            "successor_late": self.successor_late,
            "step_starved_s": round(self.starved_s, 6),
        }
        for name, (cnt, tot) in self.stages.items():
            out[f"req_stage_{name}_count"] = cnt
            out[f"req_stage_{name}_s"] = round(tot, 6)
        for name, (cnt, tot, slow) in self.phases.items():
            out[f"phase_{name}_count"] = cnt
            out[f"phase_{name}_s"] = round(tot, 6)
            out[f"phase_{name}_slow"] = slow
        for kind, (cnt, tot) in self.steps.items():
            out[f"step_{kind}_count"] = cnt
            out[f"step_{kind}_interval_s"] = round(tot, 6)
        # list() is one atomic C-level snapshot: the device and fetch
        # threads keep inserting while we iterate
        for tag, (cnt, tot) in list(self.dev_time.items()):
            out[f"dispatch_{tag}_count"] = cnt
            out[f"dispatch_{tag}_s"] = round(tot, 3)
        if self.state_bytes:
            out["step_state_bytes"] = float(self.state_bytes)
        if self.expert_bytes:
            out["step_expert_bytes"] = float(self.expert_bytes)
        if self.latent_kv_expanded_bytes:
            out["step_latent_kv_bytes"] = float(self.latent_kv_bytes)
            out["step_latent_kv_expanded_bytes"] = float(
                self.latent_kv_expanded_bytes)
        if self.dsa_context_rows:
            out["step_index_kv_bytes"] = float(self.index_kv_bytes)
            out["dsa_context_rows"] = self.dsa_context_rows
            out["dsa_selected_rows"] = self.dsa_selected_rows
        if self.window_kv_whole_bytes:
            out["step_window_kv_bytes"] = float(self.window_kv_bytes)
            out["step_window_kv_whole_bytes"] = float(
                self.window_kv_whole_bytes)
        return out
