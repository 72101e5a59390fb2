"""The second entry of the decode pipeline is queued LATE
(engine._await_successor, docs/observability.md "The engine's iteration"):
with one entry running and its program's length known, the step loop asks
for that entry's fetch, waits until the entry is about to end, admits who
arrives meanwhile, and only then dispatches.

Most cases run a tiny engine over a device whose entries take a SET time on
a clock the test drives (`Rig`): no thread and no real timer takes part, the
step loop is the test's own calls, and the clock moves only when the step
waits on it, so what is in flight when an arrival lands is known. The
parity cases run the engine as it is served, on the real clock."""

import asyncio
import heapq
import itertools

import jax
import numpy as np
import pytest

from dynamo_tpu.engine import EngineConfig, JaxEngine
from dynamo_tpu.engine import engine as engine_mod
from dynamo_tpu.engine.recorder import ESTIMATE_RUNS, SLOW_SPAN_S, Recorder
from dynamo_tpu.llm.protocols import PreprocessedRequest
from dynamo_tpu.models import llama, moe
from dynamo_tpu.runtime.engine import Context

PAGE = 8
K = 4  # decode_block_steps of the engines below
DENSE = llama.LlamaConfig.tiny()
ROUTED = moe.MoeConfig.tiny_moe(capacity_factor=2.0)
#: what an entry takes on the rig's device, and what every call into the
#: runtime costs the loop, in the rig's seconds
LENGTHS = {"block": 0.064, "mixed": 0.012, "prefill": 0.020}
LAUNCH_S = 0.001


def _engine(family="dense", **over):
    cfg, mod = (DENSE, llama) if family == "dense" else (ROUTED, moe)
    kw = dict(
        model="tiny", max_num_seqs=4, page_size=PAGE, num_pages=128,
        max_model_len=256, prefill_buckets=(16, 32), max_prefill_chunk=32,
        decode_block_steps=K, mixed_dispatch=True,
    )
    kw.update(over)
    eng = JaxEngine(EngineConfig(**kw), model_config=cfg,
                    params=mod.init_params(cfg, jax.random.PRNGKey(0)))
    # one table width, as under the Pallas ragged kernel
    eng._mixed_table_rungs = (eng.config.max_pages_per_seq,)
    return eng


async def _stream(eng, prompt, rid, n, **req_kw):
    req = PreprocessedRequest(
        token_ids=list(prompt),
        stop_conditions={"max_tokens": n, "ignore_eos": not req_kw},
        sampling_options={"temperature": 0.0}, request_id=rid,
        eos_token_ids=[2] if req_kw else [], **req_kw,
    ).to_dict()
    return [t async for out in eng.generate(req, Context())
            for t in (out.get("data") or {}).get("token_ids", [])]


def _prompt(seed, n=20):
    return np.random.RandomState(seed).randint(5, 200, size=n).tolist()


class Rig:
    """A device whose entries take LENGTHS on a clock the test drives, under
    an engine whose steps are the test's calls. An entry begins when the
    one before it ends or when its launch returns, whichever is later; its
    fetch returns when it ends; a call into the runtime moves the clock by
    LAUNCH_S; and the clock otherwise moves only in `advance`."""

    def __init__(self, eng, lengths=LENGTHS):
        self.eng, self.lengths = eng, dict(lengths)
        self.now, self.busy_until = 1000.0, 0.0
        self.launched = []  # every entry, in the order of its launch
        self.step = None
        self._due, self._n = [], itertools.count()
        rec = eng._rec
        rec.clock = lambda: self.now
        eng._sleep = lambda dt: self.at(self.now + dt)
        launched, tree_of, timed = rec.launched, eng._fetch_tree, eng._timed

        def stamped(entry):
            launched(entry)
            entry["ends"] = self.busy_until = (
                max(self.busy_until, self.now)
                + self.lengths[entry["step_kind"]])
            self.launched.append(entry)

        def tree(prefills, want):
            self._asked = [*prefills, *([] if want is None else [want])]
            return tree_of(prefills, want)

        async def fetch(tree):
            ends = max(e["ends"] for e in self._asked)
            out = jax.device_get(tree)
            await self.at(ends)
            return out, ends

        async def on_device(fn, *args, tag=None, shape=None):
            self.now += LAUNCH_S
            return (timed(fn, tag, shape) if tag else fn)(*args)

        rec.launched, eng._fetch_tree = stamped, tree
        eng._fetch, eng._run_on_device = fetch, on_device

    async def __aenter__(self):
        # generate() starts the step loop unless a task is there already
        self.eng._step_task = asyncio.create_task(asyncio.sleep(3600))
        return self

    async def __aexit__(self, *exc):
        if self.step is not None:
            self.step.cancel()
        await self.eng.close()

    def at(self, due):
        fut = asyncio.get_running_loop().create_future()
        if due <= self.now:
            fut.set_result(None)
        else:
            heapq.heappush(self._due, (due, next(self._n), fut))
        return fut

    @staticmethod
    async def settle():
        """Every task runs as far as it can: nothing here waits on a thread
        or on a real timer, so a bounded count of turns of the loop does."""
        for _ in range(40):
            await asyncio.sleep(0)

    async def advance(self, to=None):
        """Move the clock to `to` (default: to the next moment something
        waits for), waking what falls due on the way, each at its moment."""
        await self.settle()
        first = True
        while self._due and (self._due[0][0] <= to if to is not None else first):
            due, _, fut = heapq.heappop(self._due)
            if fut.done():
                continue
            first = False
            self.now = max(self.now, due)
            fut.set_result(None)
            await self.settle()
        if to is not None:
            self.now = max(self.now, to)

    def in_wait(self):
        """The step stands in the wait for its successor's moment (asked
        after `settle`: the fetch is asked for and not yet taken up)."""
        return self.eng._early_fetch is not None

    async def pump(self, cond, limit=3000):
        """Steps, and the clock whenever the step waits on it, until `cond`."""
        for _ in range(limit):
            await self.settle()
            if cond():
                return
            if self.step is None or self.step.done():
                if self.step is not None:
                    self.step.result()
                self.step = asyncio.create_task(self.eng._step_once())
            else:
                assert self._due, "the step waits on nothing the rig holds"
                await self.advance()
        raise AssertionError("the engine never got there")

    async def submit(self, prompt, rid, n, **kw):
        task = asyncio.create_task(_stream(self.eng, prompt, rid, n, **kw))
        await self.settle()
        return task

    async def steady(self, lanes=2, n=400):
        """`lanes` requests decoding, the pipeline two deep, every program's
        length known, and the step in a wait behind a running block."""
        tasks = [await self.submit(_prompt(i), f"lane{i}", n)
                 for i in range(lanes)]
        rec = self.eng._rec
        await self.pump(lambda: rec.successor_waits >= 3 and self.in_wait()
                        and self.eng._inflight[0]["kind"] == "block")
        return tasks


def _grew(eng, before):
    after = eng._rec.stats()
    return {k: after[k] - before[k] for k in before
            if isinstance(before[k], (int, float))}


# -- the recorder's part, alone ------------------------------------------- #

def _entry(rec, clock, at, program=("block", K), kind="block"):
    e = {}
    clock[0] = at
    rec.dispatched(e, kind, (0, 0), program=program)
    rec.launched(e)
    return e


@pytest.mark.parametrize("runs, expect", [
    ([], None),
    ([0.070], 0.070),
    ([0.070, 0.066, 0.068], 0.066),
    # a stalled run (the profiler's stop) never enters
    ([0.070, 2.0, 0.068], 0.068),
    # only the last ESTIMATE_RUNS count: the short one has aged out
    ([0.050] + [0.070] * ESTIMATE_RUNS, 0.070),
])
def test_a_programs_estimate_is_the_shortest_of_its_last_runs(runs, expect):
    rec, clock = Recorder(), [0.0]
    rec.clock = lambda: clock[0]
    t = 10.0
    for length in runs:
        e = _entry(rec, clock, t)
        t += length
        rec.fetched([e], t, None)
    probe = {"program": ("block", K)}
    assert rec.estimate(probe) == (None if expect is None
                                   else pytest.approx(expect))
    assert rec.estimate({"program": ("mixed", 256)}) is None
    assert SLOW_SPAN_S < 2.0


@pytest.mark.parametrize("case", ["followed", "late", "drained", "idle"])
def test_starved_seconds_lie_between_an_end_and_the_next_launch(case):
    """followed: the successor's launch had returned before the entry ended,
    nothing is counted. late: it returned 3 ms after. drained: nothing was
    behind the entry, and the next launch comes 5 ms later. idle: the same,
    but the engine went idle between, which is nobody's wait."""
    rec, clock = Recorder(), [0.0]
    rec.clock = lambda: clock[0]
    a = _entry(rec, clock, 10.000)
    if case in ("followed", "late"):
        b = _entry(rec, clock, 10.050 if case == "followed" else 10.071)
        rec.fetched([a], 10.068, b["t_launched"], waited=True)
        assert rec.successor_late == (case == "late")
    else:
        rec.fetched([a], 10.068, None)
        assert rec.successor_late == 0  # nobody had waited with a successor
        if case == "idle":
            rec.idle()
        _entry(rec, clock, 10.073)
    expect = {"followed": 0.0, "late": 0.003, "drained": 0.005, "idle": 0.0}
    assert rec.stats()["step_starved_s"] == pytest.approx(expect[case])


# -- on the rig ----------------------------------------------------------- #

@pytest.mark.parametrize("arrivals", [1, 2])
def test_arrivals_of_one_wait_ride_the_next_entry_in_one_pack(arrivals):
    """Requests that arrive while a block runs and its successor is not yet
    queued are admitted at their wake and ride ONE mixed step queued at the
    deadline, the next entry behind the running block: two entries lie
    between an arrival and its first token, the running block and its own
    step, where a block queued at once would have stood between as well."""
    async def main():
        eng = _engine()
        async with Rig(eng) as rig:
            await rig.steady()
            rec = eng._rec
            before, launched = rec.stats(), len(rig.launched)
            deadline = eng._successor_deadline()
            running = eng._inflight[0]
            assert rig.now < deadline < running["ends"]
            new = [await rig.submit(_prompt(10 + i), f"new{i}", 6)
                   for i in range(arrivals)]
            # admitted at the wake; nothing is queued before the deadline
            assert _grew(eng, before)["req_admitted"] == arrivals
            assert rig.in_wait() and len(rig.launched) == launched
            await rig.advance(deadline)
            step = rig.launched[launched]
            assert step["step_kind"] == "mixed" and len(step["done"]) == arrivals
            assert deadline <= step["t_launched"] < running["ends"]
            assert list(eng._inflight) == [running, step]
            await rig.pump(lambda: all(t.done() for t in new))
            assert all(len(t.result()) == 6 for t in new)
            grew = _grew(eng, before)
            assert grew["req_first_tokens"] == arrivals
            assert grew["req_blocks_ahead"] == arrivals  # the running block
            assert grew["req_mixed_ahead"] == arrivals  # its own step
            assert grew["successor_woken"] >= 1
            assert grew["successor_late"] == 0
            assert grew["step_starved_s"] == 0
            assert eng.mixed_steps_piped == eng.mixed_steps
    asyncio.run(main())


def test_with_no_arrival_the_block_is_queued_at_the_deadline():
    """The wait ends at the deadline and the block that would have been
    queued at once is queued then: a quarter of the running block's length
    before its end, nothing late and the device never left idle."""
    async def main():
        eng = _engine()
        async with Rig(eng) as rig:
            await rig.steady()
            rec = eng._rec
            for _ in range(6):
                before, launched = rec.stats(), len(rig.launched)
                running = eng._inflight[0]
                deadline = eng._successor_deadline()
                estimate = rec.estimate(running)
                assert estimate == pytest.approx(LENGTHS["block"], abs=0.004)
                assert deadline == pytest.approx(
                    rec.began(running)
                    + (1 - engine_mod.SUCCESSOR_MARGIN) * estimate)
                await rig.advance(deadline - 0.001)
                assert rig.in_wait() and len(rig.launched) == launched
                await rig.advance(deadline)
                block = rig.launched[launched]
                assert block["step_kind"] == "block"
                assert deadline <= block["t_launched"] < running["ends"]
                await rig.pump(lambda: rec.successor_waits
                               > before["successor_waits"] and rig.in_wait())
                grew = _grew(eng, before)
                assert grew["successor_late"] == grew["successor_woken"] == 0
                assert grew["step_starved_s"] == 0
            # what the margin must cover was measured at every deadline
            assert 0 < max(eng._successor_costs) < 0.25 * LENGTHS["block"]
    asyncio.run(main())


def test_an_estimate_twice_too_long_ends_the_wait_at_the_fetch_and_is_late_once():
    """The running block takes half of what its program's last runs did:
    the wait ends the moment the block comes back (its fetch was asked for
    at the wait's start), the successor is launched then, the seconds
    between are the device's starved time, and the block's own length is
    the next estimate, so the next wait is on time."""
    async def main():
        eng = _engine()
        async with Rig(eng) as rig:
            await rig.steady()
            rec = eng._rec
            # from here on a block takes half of what its last runs did
            old = eng._inflight[0]
            rig.lengths["block"] = LENGTHS["block"] / 2
            await rig.pump(lambda: rig.in_wait() and eng._inflight[0] is not old)
            before, launched = rec.stats(), len(rig.launched)
            running = eng._inflight[0]
            assert eng._successor_deadline() > running["ends"]
            await rig.advance(running["ends"])
            block = rig.launched[launched]
            assert block["step_kind"] == "block"
            assert block["t_launched"] > running["ends"]
            grew = _grew(eng, before)
            assert grew["successor_late"] == 1
            assert grew["step_starved_s"] == pytest.approx(
                block["t_launched"] - running["ends"])
            assert 0 < grew["step_starved_s"] < 0.01
            # the run that came back is the program's estimate now
            for _ in range(3):
                await rig.pump(lambda: not rig.in_wait())
                await rig.pump(rig.in_wait)
            assert rec.estimate(running) == pytest.approx(LENGTHS["block"] / 2, abs=0.004)
            assert _grew(eng, before)["successor_late"] == 1
    asyncio.run(main())


@pytest.mark.parametrize("case", [
    "no_estimate", "guided_lane", "pending_drain", "carry_invalid", "spec_mode",
])
def test_what_keeps_depth_one_or_knows_no_length_never_waits(case):
    """No reading of the running program yet, a guided lane decoding, a pack
    that waits for the drain, a carry to be uploaded, spec mode: the loop
    dispatches (or drains) at once, as it always did, and counts no wait."""
    async def main():
        over = dict(spec_mode="ngram", spec_rounds=2, spec_draft_len=3,
                    spec_ngram=2, spec_hist=128) if case == "spec_mode" else {}
        eng = _engine(**over)
        rec = eng._rec
        async with Rig(eng) as rig:
            if case == "spec_mode":
                tasks = [await rig.submit(_prompt(i), f"lane{i}", 24)
                         for i in range(2)]
                await rig.pump(lambda: all(t.done() for t in tasks))
                assert rec.successor_waits == 0
                assert rec.stats()["step_block_count"] > 2
                return
            if case == "guided_lane":
                guided = await rig.submit(
                    _prompt(7), "guided", 40,
                    guided={"kind": "choice", "choices": ["yes" * 12, "no" * 12]})
                plain = await rig.submit(_prompt(8), "plain", 40)
                await rig.pump(lambda: eng._guided_decoding())
                waits = rec.successor_waits
                await rig.pump(lambda: guided.done())
                assert rec.successor_waits == waits
                await rig.pump(lambda: plain.done())
                return
            await rig.steady()
            await rig.pump(lambda: not rig.in_wait() and len(eng._inflight) == 1
                           and rig.step.done())
            waits, launched, now = rec.successor_waits, len(rig.launched), rig.now
            if case == "no_estimate":
                rec.runs.clear()
            elif case == "pending_drain":
                eng._mixed_wait_drain = True
            else:
                eng._carry_valid = False
            assert eng._successor_deadline() is None
            rig.step = asyncio.create_task(eng._step_once())
            await rig.settle()
            assert rec.successor_waits == waits and not rig.in_wait()
            if case == "carry_invalid":
                # drains first: the entry in flight owns the carry
                assert len(rig.launched) == launched
            else:
                assert rig.launched[launched]["t_launched"] < now + 0.01
    asyncio.run(main())


def test_a_margin_that_outgrew_the_estimate_shrinks_again():
    """A dispatch that compiled inside its launch is no reading of the
    margin, and a margin that came to exceed the estimate (so that no wait
    begins) is measured on at every dispatch of a second entry and comes
    down again: the wait cannot switch itself off for good."""
    async def main():
        eng = _engine()
        async with Rig(eng) as rig:
            await rig.steady()
            rec = eng._rec
            await rig.pump(lambda: not rig.in_wait() and rig.step.done())
            eng._successor_costs.extend([1.0] * ESTIMATE_RUNS)
            assert eng._successor_deadline() is None
            waits = rec.successor_waits
            launched = len(rig.launched)
            await rig.pump(lambda: len(rig.launched) >= launched + ESTIMATE_RUNS
                           and rig.step.done())
            assert max(eng._successor_costs) < 0.01
            await rig.pump(rig.in_wait)
            assert rec.successor_waits > waits
            # a launch that took a compile's time is left out
            before = list(eng._successor_costs)
            on_device = eng._run_on_device

            async def compiling(fn, *args, **kw):
                rig.now += 0.6
                return await on_device(fn, *args, **kw)

            eng._run_on_device = compiling
            launched = len(rig.launched)
            await rig.pump(lambda: len(rig.launched) > launched and rig.step.done())
            eng._run_on_device = on_device
            assert max(eng._successor_costs) < 0.01
            assert len(before) == ESTIMATE_RUNS
    asyncio.run(main())


def test_an_arrival_in_the_turn_that_ends_the_wait_is_admitted_before_the_dispatch():
    """A request that reaches the waiting list as the deadline falls (its
    wake comes too late to be seen inside the wait) is admitted at the
    wait's end and rides the entry queued there. Left waiting, it would
    hold the block back as prefill work that joins no pipeline does, and
    the running entry would end with nothing behind it."""
    async def main():
        eng = _engine()
        async with Rig(eng) as rig:
            await rig.steady()
            launched, deadline = len(rig.launched), eng._successor_deadline()
            running = eng._inflight[0]
            wake, eng._wake = eng._wake, asyncio.Event()  # nobody hears it
            new = await rig.submit(_prompt(21), "new", 6)
            eng._wake = wake
            assert [s.request_id for s in eng._waiting] == ["new"]
            assert rig.in_wait() and len(rig.launched) == launched
            await rig.advance(deadline)
            step = rig.launched[launched]
            assert step["step_kind"] == "mixed" and len(step["done"]) == 1
            assert list(eng._inflight) == [running, step]
            await rig.pump(new.done)
            assert len(new.result()) == 6
            assert eng.mixed_steps_piped == eng.mixed_steps
            assert eng._rec.successor_late == 0
    asyncio.run(main())


def test_a_finished_request_wakes_the_loop_and_the_wait_goes_on():
    """A stream that ends sets the loop's wake as an arrival does; with
    nobody to admit the wait goes on to its deadline."""
    async def main():
        eng = _engine()
        async with Rig(eng) as rig:
            await rig.steady()
            launched, waits = len(rig.launched), eng._rec.successor_waits
            deadline = eng._successor_deadline()
            for _ in range(3):
                eng._wake.set()
                await rig.settle()
                assert rig.in_wait() and len(rig.launched) == launched
            await rig.advance(deadline)
            assert rig.launched[launched]["step_kind"] == "block"
            assert eng._rec.successor_waits == waits
            assert eng._rec.successor_woken == 0
    asyncio.run(main())


# -- as it is served: the same tokens ------------------------------------- #

async def _served(eng, wait: bool):
    if not wait:
        eng._successor_deadline = lambda: None
    prompts = [_prompt(40 + i, 12 + 5 * i) for i in range(6)]
    tasks = []
    for i, p in enumerate(prompts):
        tasks.append(asyncio.create_task(_stream(eng, p, f"r{i}", 24 + 8 * (i % 3))))
        await asyncio.sleep(0.05 if i % 2 else 0.0)
    out = await asyncio.gather(*tasks)
    stats = eng.stats()
    await eng.close()
    return out, stats


@pytest.mark.parametrize("family", ["dense", "routed"])
def test_greedy_tokens_are_the_same_with_and_without_the_wait(family):
    """Order alone changes: six staggered greedy requests over four lanes
    give the tokens they gave when every successor was queued at once."""
    held, stats = asyncio.run(_served(_engine(family), wait=True))
    at_once, stats0 = asyncio.run(_served(_engine(family), wait=False))
    assert held == at_once
    assert stats["successor_waits"] > 0 and stats0["successor_waits"] == 0
    assert stats["successor_late"] <= stats["successor_waits"]
    assert stats["mixed_steps"] > 0
