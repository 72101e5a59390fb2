"""dynorace (analysis/race/) fixture tests.

Mirrors tests/test_flow_analysis.py: every rule gets a shape it FIRES
on, a shape it stays QUIET on, and a suppression check — plus the
seeded-bug reconstructions the acceptance criteria demand, each
producing EXACTLY ONE violation at the anchor a maintainer would fix:

  * race-await-atomicity: HealthCheckManager.stop()'s take-then-act bug
    (test `self._task`, await it, then null it — a concurrent stop()
    passing the None-check during the await reaps the task twice), and
    the discovery server's DELETE_PREFIX sweep deleting keys a
    concurrent op already removed during an earlier notification await;
  * race-guarded-state: KvBlockManager.stats() reading the offload
    counters without `self._lock` while the device-exec thread stores;
  * race-iter-mutation: StepBroadcaster.drain() iterating the live
    follower list while `_lose`/`_on_connect` mutate it from other
    tasks.

Plus the red test proving removal of any GUARDED_STATE guard at one
REAL access site fails race-guarded-state, the waivers-are-visible
check (same contract as shard's pipeline forward-edge test), the
generated docs/concurrency.md freshness gate, SARIF 2.1.0 schema
validation for --format=sarif, and the CLI-surface tests (--rules
all / pack aliases / unknown-rule exit / --list-rules sync).
"""

import json
import shutil
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from dynamo_tpu.analysis import Project, run
from dynamo_tpu.analysis.race import (
    RACE_RULES,
    RaceAwaitAtomicityRule,
    RaceGuardedStateRule,
    RaceIterMutationRule,
    RaceLockOrderRule,
)

REPO = Path(__file__).resolve().parents[1]

_repo_project = None


def repo_project() -> Project:
    """The real tree, parsed once per test session (several tests below
    only read it)."""
    global _repo_project
    if _repo_project is None:
        _repo_project = Project.load(REPO)
    return _repo_project


def make_project(tmp_path: Path, files: dict) -> Project:
    for rel, text in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(text))
    return Project.load(tmp_path)


def rule_hits(project: Project, rule) -> list:
    return run(project, [rule])


# --------------------------------------------------------------------- #
# race-await-atomicity
# --------------------------------------------------------------------- #


def test_await_atomicity_canonical_tear_fires(tmp_path):
    """The canonical `if slot.free: await ...; slot.free = False` tear,
    anchored at the stale test."""
    project = make_project(tmp_path, {
        "dynamo_tpu/engine/slots.py": """
            class Engine:
                async def admit(self, slot):
                    if slot.free:
                        await self.kv.allocate(slot)
                        slot.free = False
        """,
    })
    hits = rule_hits(project, RaceAwaitAtomicityRule())
    assert len(hits) == 1
    assert hits[0].line == 4 and "slot.free" in hits[0].message


def test_await_atomicity_quiet_on_lock_recheck_and_while(tmp_path):
    """The three sanctioned shapes: a lock spanning test and act, a
    re-check after the suspension, and the while-retest idiom."""
    project = make_project(tmp_path, {
        "dynamo_tpu/engine/slots_ok.py": """
            import asyncio

            class Engine:
                def __init__(self):
                    self._lock = asyncio.Lock()

                async def admit_locked(self, slot):
                    async with self._lock:
                        if slot.free:
                            await self.kv.allocate(slot)
                            slot.free = False

                async def admit_recheck(self, slot):
                    if slot.free:
                        await self.kv.allocate(slot)
                        if not slot.free:
                            return
                        slot.free = False

                async def wait_ready(self):
                    while not self.ready:
                        await asyncio.sleep(0)
                    self.ready = False
        """,
    })
    assert rule_hits(project, RaceAwaitAtomicityRule()) == []


def test_await_atomicity_awaited_callee_write_is_the_act(tmp_path):
    """An awaited same-class coroutine that mutates `self.<attr>` after
    its own suspension is folded in as the act at the call site — the
    tear does not hide one call deep."""
    project = make_project(tmp_path, {
        "dynamo_tpu/engine/fold.py": """
            import asyncio

            class Engine:
                async def admit(self):
                    if self._draining:
                        await self._finish()

                async def _finish(self):
                    await asyncio.sleep(0)
                    self._draining = False
        """,
    })
    hits = rule_hits(project, RaceAwaitAtomicityRule())
    assert len(hits) == 1
    assert "self._draining" in hits[0].message


def test_await_atomicity_awaitless_callee_runs_inline_quiet(tmp_path):
    """Awaiting a same-class coroutine with no internal await never
    yields to the event loop — no suspension, no tear."""
    project = make_project(tmp_path, {
        "dynamo_tpu/engine/inline.py": """
            class Engine:
                async def admit(self, slot):
                    if slot.free:
                        await self.mark(slot)
                        slot.free = False

                async def mark(self, slot):
                    slot.owner = self
        """,
    })
    assert rule_hits(project, RaceAwaitAtomicityRule()) == []


def test_await_atomicity_guarded_state_entry_exempts(tmp_path):
    """An attribute whose confinement is registered in GUARDED_STATE is
    race-guarded-state's job: the owner task is the only writer, so the
    check cannot go stale — atomicity stays quiet and the sibling rule
    accepts the in-owner mutation."""
    files = {
        "dynamo_tpu/runtime/sync.py": """
            GUARDED_STATE = {
                "Engine._inflight": "single-task:_step_loop",
            }
        """,
        "dynamo_tpu/engine/exempt.py": """
            import asyncio

            class Engine:
                async def _step_loop(self):
                    if self._inflight:
                        await asyncio.sleep(0)
                        self._inflight = []
        """,
    }
    project = make_project(tmp_path, files)
    assert rule_hits(project, RaceAwaitAtomicityRule()) == []
    assert rule_hits(project, RaceGuardedStateRule()) == []


def test_await_atomicity_suppression_with_reason(tmp_path):
    project = make_project(tmp_path, {
        "dynamo_tpu/engine/slots.py": """
            class Engine:
                async def admit(self, slot):
                    if slot.free:  # dynolint: disable=race-await-atomicity -- single writer per slot
                        await self.kv.allocate(slot)
                        slot.free = False
        """,
    })
    assert rule_hits(project, RaceAwaitAtomicityRule()) == []


def test_await_atomicity_health_check_stop_reconstruction(tmp_path):
    """Seeded-bug reconstruction (fixed this PR): HealthCheckManager.stop
    tested `self._task`, awaited it, then nulled it — two concurrent
    stop() calls both pass the None-check and the second await crashes
    on a reaped task. Exactly one violation, at the stale check."""
    project = make_project(tmp_path, {
        "dynamo_tpu/runtime/hc_like.py": """
            import asyncio

            class HealthCheckManager:
                def __init__(self):
                    self._task = None

                async def stop(self):
                    if self._task is not None:
                        self._task.cancel()
                        try:
                            await self._task
                        except asyncio.CancelledError:
                            pass
                        self._task = None
        """,
    })
    hits = rule_hits(project, RaceAwaitAtomicityRule())
    assert len(hits) == 1
    assert hits[0].path == "dynamo_tpu/runtime/hc_like.py"
    assert hits[0].line == 9  # the `if self._task is not None:` check
    assert "self._task" in hits[0].message

    # the shipped fix — claim the task synchronously BEFORE awaiting
    project = make_project(tmp_path / "fixed", {
        "dynamo_tpu/runtime/hc_like.py": """
            import asyncio

            class HealthCheckManager:
                def __init__(self):
                    self._task = None

                async def stop(self):
                    task, self._task = self._task, None
                    if task is not None:
                        task.cancel()
                        try:
                            await task
                        except asyncio.CancelledError:
                            pass
        """,
    })
    assert rule_hits(project, RaceAwaitAtomicityRule()) == []


def test_await_atomicity_delete_prefix_reconstruction(tmp_path):
    """Seeded-bug reconstruction (fixed this PR): the discovery server's
    DELETE_PREFIX sweep scanned `self._kv`, then awaited per-key deletes
    whose watcher notifications suspend — a concurrent op removing one
    of the scanned keys during that await makes the blind
    `del self._kv[k]` raise KeyError and abort the sweep halfway.
    Exactly one violation; the shipped per-key re-check is quiet."""
    torn = """
        class DiscoveryServer:
            def __init__(self):
                self._kv = {}
                self._watches = []

            async def handle(self, control):
                if control["op"] == "delete_prefix":
                    keys = [k for k in list(self._kv) if k.startswith(control["prefix"])]
                    for k in keys:
                        await self._delete_key(k)
                    return {"ok": True, "deleted": len(keys)}

            async def _delete_key(self, k):
                del self._kv[k]
                for w in list(self._watches):
                    await w.notify(k)
    """
    project = make_project(tmp_path, {"dynamo_tpu/runtime/disco_like.py": torn})
    hits = rule_hits(project, RaceAwaitAtomicityRule())
    assert len(hits) == 1
    assert "self._kv" in hits[0].message

    fixed = torn.replace(
        "for k in keys:\n                        await self._delete_key(k)",
        "for k in keys:\n"
        "                        if k not in self._kv:\n"
        "                            continue\n"
        "                        await self._delete_key(k)",
    )
    assert fixed != torn
    project = make_project(
        tmp_path / "fixed", {"dynamo_tpu/runtime/disco_like.py": fixed}
    )
    assert rule_hits(project, RaceAwaitAtomicityRule()) == []


def test_await_atomicity_planner_revision_anchor(tmp_path):
    """The planner VirtualConnector race fixed this PR: lazy-load +
    increment of `self.revision` across the load's await. Both torn
    writes (the lazy-load store and the increment) anchor at the same
    stale check, and the shipped lock makes the region quiet."""
    torn = """
        import json

        class VirtualConnector:
            def __init__(self, client):
                self.client = client
                self.revision = None

            async def _load_revision(self):
                raw = await self.client.get("decision")
                return 0 if raw is None else json.loads(raw).get("revision", 0)

            async def set_replicas(self, prefill, decode):
                if self.revision is None:
                    self.revision = await self._load_revision()
                self.revision += 1
                doc = {"p": prefill, "d": decode, "revision": self.revision}
                await self.client.put("decision", json.dumps(doc).encode())
    """
    project = make_project(tmp_path, {"dynamo_tpu/planner/conn_like.py": torn})
    hits = rule_hits(project, RaceAwaitAtomicityRule())
    assert {v.line for v in hits} == {14}  # the `if self.revision is None:`
    assert all("self.revision" in v.message for v in hits)

    fixed = """
        import asyncio, json

        class VirtualConnector:
            def __init__(self, client):
                self.client = client
                self.revision = None
                self._rev_lock = asyncio.Lock()

            async def _load_revision(self):
                raw = await self.client.get("decision")
                return 0 if raw is None else json.loads(raw).get("revision", 0)

            async def set_replicas(self, prefill, decode):
                async with self._rev_lock:
                    if self.revision is None:
                        self.revision = await self._load_revision()
                    self.revision += 1
                    doc = {"p": prefill, "d": decode, "revision": self.revision}
                    await self.client.put("decision", json.dumps(doc).encode())
    """
    project = make_project(
        tmp_path / "fixed", {"dynamo_tpu/planner/conn_like.py": fixed}
    )
    assert rule_hits(project, RaceAwaitAtomicityRule()) == []


# --------------------------------------------------------------------- #
# race-guarded-state
# --------------------------------------------------------------------- #

_SYNC_LOCK_FIXTURE = """
    GUARDED_STATE = {
        "KvBlockManager.offloaded_blocks": "lock:_lock",
    }
"""


def test_guarded_state_kvbm_stats_reconstruction(tmp_path):
    """Seeded-bug reconstruction (fixed this PR): stats() read the
    offload counters without the lock while the device-exec thread
    stores them — torn counter/tier snapshots. Exactly one violation,
    at the unguarded read."""
    project = make_project(tmp_path, {
        "dynamo_tpu/runtime/sync.py": _SYNC_LOCK_FIXTURE,
        "dynamo_tpu/kvbm/manager_like.py": """
            import threading

            class KvBlockManager:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.offloaded_blocks = 0

                def offload(self, n):
                    with self._lock:
                        self.offloaded_blocks += n

                def stats(self):
                    return {"kvbm_offloaded_blocks": self.offloaded_blocks}
        """,
    })
    hits = rule_hits(project, RaceGuardedStateRule())
    assert len(hits) == 1
    assert hits[0].path == "dynamo_tpu/kvbm/manager_like.py"
    assert hits[0].line == 14
    assert "outside `with self._lock`" in hits[0].message


def test_guarded_state_quiet_when_lock_held_and_init_exempt(tmp_path):
    project = make_project(tmp_path, {
        "dynamo_tpu/runtime/sync.py": _SYNC_LOCK_FIXTURE,
        "dynamo_tpu/kvbm/manager_like.py": """
            import threading

            class KvBlockManager:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.offloaded_blocks = 0

                def offload(self, n):
                    with self._lock:
                        self.offloaded_blocks += n

                def stats(self):
                    with self._lock:
                        return {"kvbm_offloaded_blocks": self.offloaded_blocks}
        """,
    })
    assert rule_hits(project, RaceGuardedStateRule()) == []


def test_guarded_state_confinement_fires_outside_owner(tmp_path):
    """single-task entries: a mutation outside the owner's call closure
    fires; mutations in the owner (or its callees) and reads anywhere
    stay quiet."""
    project = make_project(tmp_path, {
        "dynamo_tpu/runtime/sync.py": """
            GUARDED_STATE = {
                "Engine._inflight": "single-task:_step_loop",
            }
        """,
        "dynamo_tpu/engine/own.py": """
            class Engine:
                def __init__(self):
                    self._inflight = []

                async def _step_loop(self):
                    self._admit()

                def _admit(self):
                    self._inflight.append(1)

                async def cancel_all(self):
                    self._inflight.clear()

                def snapshot(self):
                    return list(self._inflight)
        """,
    })
    hits = rule_hits(project, RaceGuardedStateRule())
    assert len(hits) == 1
    assert hits[0].line == 13  # cancel_all's clear()
    assert "outside its owner task" in hits[0].message


def test_guarded_state_stale_entries_fire_at_registry_lines(tmp_path):
    """Registry honesty: a gone class, a gone owner, and an entry
    matching no access each fire AT THE REGISTRY LINE."""
    project = make_project(tmp_path, {
        "dynamo_tpu/runtime/sync.py": """
            GUARDED_STATE = {
                "Ghost.attr": "lock:_lock",
                "Engine._gone": "single-task:_step_loop",
                "Engine._inflight": "single-task:_vanished",
            }
        """,
        "dynamo_tpu/engine/own.py": """
            class Engine:
                async def _step_loop(self):
                    self._inflight = []
        """,
    })
    hits = rule_hits(project, RaceGuardedStateRule())
    assert len(hits) == 3
    assert all(h.path == "dynamo_tpu/runtime/sync.py" for h in hits)
    by_line = {h.line: h.message for h in hits}
    assert "no longer exists" in by_line[3]       # Ghost.attr
    assert "matches no access" in by_line[4]      # Engine._gone
    assert "'_vanished' no longer exists" in by_line[5]


def test_guarded_state_missing_or_malformed_registry_fires_once(tmp_path):
    project = make_project(tmp_path, {
        "dynamo_tpu/runtime/sync.py": "X = 1\n",
    })
    hits = rule_hits(project, RaceGuardedStateRule())
    assert len(hits) == 1 and "GUARDED_STATE" in hits[0].message

    project = make_project(tmp_path / "malformed", {
        "dynamo_tpu/runtime/sync.py": """
            GUARDED_STATE = {
                "Engine._inflight": "mutex",
            }
        """,
    })
    hits = rule_hits(project, RaceGuardedStateRule())
    assert len(hits) == 1 and "'<kind>:<target>'" in hits[0].message


def test_guarded_state_suppression_with_reason(tmp_path):
    project = make_project(tmp_path, {
        "dynamo_tpu/runtime/sync.py": _SYNC_LOCK_FIXTURE,
        "dynamo_tpu/kvbm/manager_like.py": """
            import threading

            class KvBlockManager:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.offloaded_blocks = 0

                def offload(self, n):
                    with self._lock:
                        self.offloaded_blocks += n

                def peek(self):
                    return self.offloaded_blocks  # dynolint: disable=race-guarded-state -- monotonic int, torn read acceptable for logging
        """,
    })
    assert rule_hits(project, RaceGuardedStateRule()) == []


# the real guard sites the red test strips, one at a time.  `if True:`
# keeps indentation and semantics-except-the-lock intact.
_REAL_GUARD_SITES = [
    (
        "dynamo_tpu/kvbm/manager.py",
        "# a consistent counter+tier snapshot (GUARDED_STATE)\n"
        "        with self._lock:",
        "# a consistent counter+tier snapshot (GUARDED_STATE)\n"
        "        if True:",
        "KvBlockManager.",
    ),
    (
        "dynamo_tpu/kvbm/manager.py",
        "with self._offload_cv:\n"
        "            return (\n"
        "                len(self._staged)",
        "if True:\n"
        "            return (\n"
        "                len(self._staged)",
        "KvbmConnector._staged",
    ),
]


def _copy_package(dst: Path):
    shutil.copytree(
        REPO / "dynamo_tpu", dst / "dynamo_tpu",
        ignore=shutil.ignore_patterns("__pycache__", "*.pyc"),
    )


def test_guarded_state_red_removing_real_guard_fails(tmp_path):
    """Acceptance red test: the real tree is clean; stripping the lock
    from any single registered access site makes race-guarded-state
    fail, naming the attribute, at the stripped site."""
    _copy_package(tmp_path / "clean")
    assert rule_hits(Project.load(tmp_path / "clean"), RaceGuardedStateRule()) == []

    for i, (rel, old, new, attr_prefix) in enumerate(_REAL_GUARD_SITES):
        text = (REPO / rel).read_text()
        assert text.count(old) == 1, (rel, old)
        base = tmp_path / f"site{i}"
        _copy_package(base)
        (base / rel).write_text(text.replace(old, new))
        hits = rule_hits(Project.load(base), RaceGuardedStateRule())
        assert hits, (rel, attr_prefix)
        assert all(h.path == rel for h in hits)
        assert any(attr_prefix in h.message for h in hits), (attr_prefix, hits)


# --------------------------------------------------------------------- #
# race-lock-order
# --------------------------------------------------------------------- #


def test_lock_order_inversion_fires_once(tmp_path):
    project = make_project(tmp_path, {
        "dynamo_tpu/runtime/locks.py": """
            import asyncio

            class Pool:
                def __init__(self):
                    self._a = asyncio.Lock()
                    self._b = asyncio.Lock()

                async def put(self):
                    async with self._a:
                        async with self._b:
                            pass

                async def take(self):
                    async with self._b:
                        async with self._a:
                            pass
        """,
    })
    hits = rule_hits(project, RaceLockOrderRule())
    assert len(hits) == 1
    assert "lock-order inversion" in hits[0].message
    assert "Pool._a" in hits[0].message and "Pool._b" in hits[0].message


def test_lock_order_interprocedural_inversion_fires(tmp_path):
    """Holding A and CALLING a helper that takes B charges A→B; the
    reverse nesting elsewhere completes the deadlock cycle."""
    project = make_project(tmp_path, {
        "dynamo_tpu/runtime/ipc.py": """
            import asyncio

            class S:
                def __init__(self):
                    self._reg = asyncio.Lock()
                    self._io = asyncio.Lock()

                async def register(self):
                    async with self._reg:
                        await self.flush()

                async def flush(self):
                    async with self._io:
                        pass

                async def writeback(self):
                    async with self._io:
                        async with self._reg:
                            pass
        """,
    })
    hits = rule_hits(project, RaceLockOrderRule())
    assert len(hits) == 1
    assert "register() holds it and calls flush()" in hits[0].message


def test_lock_order_mixed_primitive_hazards_fire(tmp_path):
    """A threading lock held across an await, and a sync `with` on an
    asyncio lock (the kvbm device-exec-thread shape), each fire."""
    project = make_project(tmp_path, {
        "dynamo_tpu/runtime/mixed.py": """
            import asyncio, threading

            class M:
                def __init__(self):
                    self._tl = threading.Lock()
                    self._al = asyncio.Lock()

                async def bad_hold(self):
                    with self._tl:
                        await asyncio.sleep(0)

                def device_exec_path(self):
                    with self._al:
                        return 1
        """,
    })
    hits = rule_hits(project, RaceLockOrderRule())
    assert len(hits) == 2
    msgs = " | ".join(h.message for h in hits)
    assert "held across an await" in msgs
    assert "sync `with` on asyncio lock" in msgs


def test_lock_order_quiet_on_consistent_order_and_pure_primitives(tmp_path):
    project = make_project(tmp_path, {
        "dynamo_tpu/runtime/locks_ok.py": """
            import asyncio, threading

            class Pool:
                def __init__(self):
                    self._a = asyncio.Lock()
                    self._b = asyncio.Lock()
                    self._tl = threading.Lock()

                async def put(self):
                    async with self._a:
                        async with self._b:
                            pass

                async def take(self):
                    async with self._a:
                        async with self._b:
                            pass

                def device_side(self):
                    with self._tl:
                        return 1

                async def loop_side(self):
                    with self._tl:
                        n = 2
                    await asyncio.sleep(0)
                    return n
        """,
    })
    assert rule_hits(project, RaceLockOrderRule()) == []


def test_lock_order_suppression_with_reason(tmp_path):
    project = make_project(tmp_path, {
        "dynamo_tpu/runtime/mixed.py": """
            import asyncio, threading

            class M:
                def __init__(self):
                    self._tl = threading.Lock()

                async def bad_hold(self):
                    with self._tl:
                        await asyncio.sleep(0)  # dynolint: disable=race-lock-order -- startup-only path, no second thread exists yet
        """,
    })
    assert rule_hits(project, RaceLockOrderRule()) == []


# --------------------------------------------------------------------- #
# race-iter-mutation
# --------------------------------------------------------------------- #


def test_iter_mutation_step_broadcaster_reconstruction(tmp_path):
    """Seeded-bug reconstruction (fixed this PR): StepBroadcaster.drain
    awaited each follower's writer.drain() while iterating the LIVE
    follower list — `_lose` (connection death) mutates it mid-iteration.
    Exactly one violation; the shipped snapshot is quiet."""
    torn = """
        class StepBroadcaster:
            def __init__(self):
                self._followers = []

            async def drain(self):
                for f in self._followers:
                    if not f.writer.is_closing():
                        await f.writer.drain()

            def _lose(self, f):
                self._followers.remove(f)
    """
    project = make_project(tmp_path, {"dynamo_tpu/parallel/mh_like.py": torn})
    hits = rule_hits(project, RaceIterMutationRule())
    assert len(hits) == 1
    assert hits[0].line == 7
    assert "self._followers" in hits[0].message
    assert "_lose" in hits[0].message  # the mutator is named as evidence

    fixed = torn.replace("for f in self._followers:", "for f in list(self._followers):")
    project = make_project(tmp_path / "fixed", {"dynamo_tpu/parallel/mh_like.py": fixed})
    assert rule_hits(project, RaceIterMutationRule()) == []


def test_iter_mutation_quiet_on_guard_async_for_and_private(tmp_path):
    """A spanning lock, `async for` over a queue, and a container nobody
    else mutates all stay quiet."""
    project = make_project(tmp_path, {
        "dynamo_tpu/runtime/iter_ok.py": """
            class Disco:
                async def notify_guarded(self):
                    async with self._lock:
                        for q in self._subs.values():
                            await q.put(1)

                async def pump(self):
                    async for item in self._queue:
                        await self.handle(item)

                async def sweep_private(self):
                    for t in self._scratch:
                        await t
        """,
    })
    assert rule_hits(project, RaceIterMutationRule()) == []


def test_iter_mutation_fires_and_suppression(tmp_path):
    bad = """
        class Disco:
            async def notify(self):
                for q in self._subs.values():
                    await q.put(1)

            def subscribe(self, q):
                self._subs[id(q)] = q
    """
    project = make_project(tmp_path, {"dynamo_tpu/runtime/iter.py": bad})
    hits = rule_hits(project, RaceIterMutationRule())
    assert len(hits) == 1 and hits[0].line == 4

    waived = bad.replace(
        "for q in self._subs.values():",
        "for q in self._subs.values():  # dynolint: disable=race-iter-mutation -- subscribe only runs before serving starts",
    )
    project = make_project(tmp_path / "w", {"dynamo_tpu/runtime/iter.py": waived})
    assert rule_hits(project, RaceIterMutationRule()) == []


# --------------------------------------------------------------------- #
# real tree: clean gate, visible waivers, generated docs
# --------------------------------------------------------------------- #


def test_real_tree_race_pack_clean():
    assert run(repo_project(), [cls() for cls in RACE_RULES]) == []


def test_real_waivers_are_visible_not_invisible():
    """Every race waiver in the tree must be VISIBLE to the raw rules
    (else the waiver comments are dead weight) and suppressed in the
    gated run — same contract as shard's pipeline forward-edge test."""
    project = repo_project()

    raw = list(RaceAwaitAtomicityRule().check(project))
    assert {(v.path) for v in raw} == {
        "dynamo_tpu/engine/engine.py",      # prefill_pos single-writer
        "dynamo_tpu/llm/discovery.py",      # serial model-watcher task
    }, raw

    raw = list(RaceGuardedStateRule().check(project))
    assert {(v.path) for v in raw} == {
        "dynamo_tpu/runtime/component.py",  # static mode, no watch task
        "dynamo_tpu/deploy/operator_lite.py",  # sanctioned one-shot flag
    }, raw
    assert all("outside its owner task" in v.message for v in raw)


def test_guarded_state_registry_entries_resolve_against_real_tree():
    """Every registered entry names a live class/attr/guard — the
    stale-entry arm of the rule would fire otherwise, but pin the
    registry's minimum coverage here so a mass-deletion also fails."""
    from dynamo_tpu.analysis.race.registry import load_guarded_state

    entries, err = load_guarded_state(repo_project())
    assert err is None
    keys = {e.key for e in entries}
    # the load-bearing minimum: kvbm cross-thread counters, engine step
    # bookkeeping, and the discovery instance table
    assert {"KvBlockManager.offloaded_blocks", "KvbmConnector._staged",
            "JaxEngine._inflight", "Client.instances"} <= keys


def test_sync_docs_are_fresh():
    """docs/concurrency.md's generated guard table matches the registry
    (same contract as the env-docs and fault-docs freshness tests)."""
    from dynamo_tpu.analysis.__main__ import emit_sync_docs

    target = REPO / "docs" / "concurrency.md"
    assert emit_sync_docs(REPO, target) == target.read_text(), (
        "docs/concurrency.md guard table is stale — run "
        "python -m dynamo_tpu.analysis --emit-sync-docs"
    )


# --------------------------------------------------------------------- #
# SARIF output
# --------------------------------------------------------------------- #

# structural subset of the SARIF 2.1.0 schema: the properties the spec
# REQUIRES (version/runs, tool.driver.name, result.message) plus the
# shapes GitHub's code-scanning upload consumes for inline annotations
# (ruleId, artifactLocation.uri, region.startLine >= 1)
_SARIF_SCHEMA = {
    "$schema": "http://json-schema.org/draft-07/schema#",
    "type": "object",
    "required": ["version", "runs"],
    "properties": {
        "version": {"const": "2.1.0"},
        "runs": {
            "type": "array",
            "minItems": 1,
            "items": {
                "type": "object",
                "required": ["tool"],
                "properties": {
                    "tool": {
                        "type": "object",
                        "required": ["driver"],
                        "properties": {
                            "driver": {
                                "type": "object",
                                "required": ["name"],
                                "properties": {
                                    "name": {"type": "string"},
                                    "rules": {
                                        "type": "array",
                                        "items": {
                                            "type": "object",
                                            "required": ["id"],
                                            "properties": {
                                                "id": {"type": "string"},
                                                "shortDescription": {
                                                    "type": "object",
                                                    "required": ["text"],
                                                },
                                            },
                                        },
                                    },
                                },
                            },
                        },
                    },
                    "results": {
                        "type": "array",
                        "items": {
                            "type": "object",
                            "required": ["message"],
                            "properties": {
                                "ruleId": {"type": "string"},
                                "level": {
                                    "enum": ["none", "note", "warning", "error"],
                                },
                                "message": {
                                    "type": "object",
                                    "required": ["text"],
                                },
                                "locations": {
                                    "type": "array",
                                    "items": {
                                        "type": "object",
                                        "properties": {
                                            "physicalLocation": {
                                                "type": "object",
                                                "properties": {
                                                    "artifactLocation": {
                                                        "type": "object",
                                                        "required": ["uri"],
                                                    },
                                                    "region": {
                                                        "type": "object",
                                                        "properties": {
                                                            "startLine": {
                                                                "type": "integer",
                                                                "minimum": 1,
                                                            },
                                                        },
                                                    },
                                                },
                                            },
                                        },
                                    },
                                },
                            },
                        },
                    },
                },
            },
        },
    },
}


def _validate_sarif(doc: dict):
    jsonschema = pytest.importorskip("jsonschema")
    jsonschema.validate(doc, _SARIF_SCHEMA)


def test_sarif_output_validates_and_anchors_findings(tmp_path):
    """--format=sarif on a tree with one known violation: the document
    validates against the SARIF 2.1.0 schema subset, the finding carries
    its ruleId and file/line anchor, and every requested rule appears as
    a reportingDescriptor."""
    for rel, text in {
        "dynamo_tpu/engine/slots.py": textwrap.dedent("""
            class Engine:
                async def admit(self, slot):
                    if slot.free:
                        await self.kv.allocate(slot)
                        slot.free = False
        """),
    }.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(text)

    proc = subprocess.run(
        [sys.executable, "-m", "dynamo_tpu.analysis", "--root", str(tmp_path),
         "--rules", "race-await-atomicity", "--format", "sarif"],
        cwd=REPO, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 1, proc.stderr
    doc = json.loads(proc.stdout)
    _validate_sarif(doc)
    driver = doc["runs"][0]["tool"]["driver"]
    assert driver["name"] == "dynolint"
    assert [r["id"] for r in driver["rules"]] == ["race-await-atomicity"]
    (result,) = doc["runs"][0]["results"]
    assert result["ruleId"] == "race-await-atomicity"
    loc = result["locations"][0]["physicalLocation"]
    assert loc["artifactLocation"]["uri"] == "dynamo_tpu/engine/slots.py"
    assert loc["region"]["startLine"] == 4


def test_sarif_suppressed_findings_never_reach_the_report(tmp_path):
    """Suppression-aware: a waived finding is not an annotation."""
    p = tmp_path / "dynamo_tpu/engine/slots.py"
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(textwrap.dedent("""
        class Engine:
            async def admit(self, slot):
                if slot.free:  # dynolint: disable=race-await-atomicity -- single writer
                    await self.kv.allocate(slot)
                    slot.free = False
    """))
    proc = subprocess.run(
        [sys.executable, "-m", "dynamo_tpu.analysis", "--root", str(tmp_path),
         "--rules", "race-await-atomicity", "--format", "sarif"],
        cwd=REPO, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr
    doc = json.loads(proc.stdout)
    _validate_sarif(doc)
    assert doc["runs"][0]["results"] == []


def test_sarif_real_tree_all_packs_validates():
    """The CI upload artifact: every pack, real tree, valid SARIF with
    an empty result set (the tree is clean)."""
    proc = subprocess.run(
        [sys.executable, "-m", "dynamo_tpu.analysis", "--format", "sarif"],
        cwd=REPO, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stdout[-2000:]
    doc = json.loads(proc.stdout)
    _validate_sarif(doc)
    assert doc["runs"][0]["results"] == []
    from dynamo_tpu.analysis.rules import ALL_RULES

    ids = [r["id"] for r in doc["runs"][0]["tool"]["driver"]["rules"]]
    assert sorted(ids) == sorted(cls.name for cls in ALL_RULES)
    assert len(ids) == len(set(ids))


# --------------------------------------------------------------------- #
# CLI surface
# --------------------------------------------------------------------- #


def _cli(*args, **kw):
    return subprocess.run(
        [sys.executable, "-m", "dynamo_tpu.analysis", *args],
        cwd=REPO, capture_output=True, text=True, timeout=300, **kw,
    )


def test_cli_packs_partition_all_rules():
    """The pack aliases cover every registered rule exactly once — a
    rule landing in two packs (or none) breaks --rules gating."""
    from dynamo_tpu.analysis.rules import ALL_RULES, PACKS

    assert set(PACKS) == {"core", "shard", "flow", "race", "met", "comp"}
    names = [cls.name for pack in PACKS.values() for cls in pack]
    assert sorted(names) == sorted(cls.name for cls in ALL_RULES)
    assert len(names) == len(set(names))
    assert len(set(cls.name for cls in ALL_RULES)) == len(ALL_RULES)


def test_cli_rules_all_is_the_full_rule_set(tmp_path):
    """--rules all == the default run: every registered rule, once."""
    (tmp_path / "dynamo_tpu").mkdir(parents=True)
    (tmp_path / "dynamo_tpu" / "empty.py").write_text("X = 1\n")
    from dynamo_tpu.analysis.rules import ALL_RULES

    for extra in (
        [], ["--rules", "all"], ["--rules", "core,shard,flow,race,met,comp"],
    ):
        proc = _cli("--root", str(tmp_path), "--format", "sarif", *extra)
        assert proc.returncode in (0, 1), proc.stderr
        ids = [
            r["id"] for r in
            json.loads(proc.stdout)["runs"][0]["tool"]["driver"]["rules"]
        ]
        assert sorted(ids) == sorted(cls.name for cls in ALL_RULES), extra
        assert len(ids) == len(set(ids))


def test_cli_unknown_rule_exits_nonzero_with_usable_message():
    proc = _cli("--rules", "race,borken-rule")
    assert proc.returncode == 2
    assert "unknown rule(s): borken-rule" in proc.stderr
    # the message teaches the fix: known rules AND pack aliases listed
    assert "race-await-atomicity" in proc.stderr
    assert "race" in proc.stderr and "all" in proc.stderr

    proc = _cli("--rules", "races")  # near-miss pack alias
    assert proc.returncode == 2 and "unknown rule(s): races" in proc.stderr


def test_cli_list_rules_in_sync_with_packs():
    from dynamo_tpu.analysis.rules import ALL_RULES

    proc = _cli("--list-rules")
    assert proc.returncode == 0
    for alias in ("core", "shard", "flow", "race", "met"):
        assert f"[{alias}]" in proc.stdout
    for cls in ALL_RULES:
        # each rule listed exactly once, with its description
        assert proc.stdout.count(f"{cls.name} ") == 1, cls.name
    race_section = proc.stdout.split("[race]", 1)[1]
    for cls in RACE_RULES:
        assert cls.name in race_section


def test_cli_race_pack_alias_runs_only_race_rules(tmp_path):
    """--rules race selects exactly the four race rules."""
    (tmp_path / "dynamo_tpu").mkdir(parents=True)
    (tmp_path / "dynamo_tpu" / "empty.py").write_text("X = 1\n")
    proc = _cli("--root", str(tmp_path), "--rules", "race", "--format", "sarif")
    assert proc.returncode in (0, 1), proc.stderr
    ids = [
        r["id"] for r in
        json.loads(proc.stdout)["runs"][0]["tool"]["driver"]["rules"]
    ]
    assert sorted(ids) == sorted(cls.name for cls in RACE_RULES)
