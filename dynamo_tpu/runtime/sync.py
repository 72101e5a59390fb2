"""Concurrency registry: the single spelling of who guards what.

The serving plane mutates shared state from four kinds of context — the
asyncio event loop's many tasks, the engine's single-threaded device
executor (``jax-step``), its host-fetch thread, and the KVBM store path
that rides the device executor — and the classic failure is not a crash
but a check-then-act sequence silently torn by an ``await`` or an
unlocked cross-thread read.  ``GUARDED_STATE`` below is the machine-
checked table of every attribute whose guard discipline the
``race-guarded-state`` dynolint rule enforces project-wide, in the same
single-spelling pattern as ``ENV_REGISTRY`` (config.py), ``FRAME_TAGS``
(codec.py) and ``KNOWN_FAULT_POINTS`` (faults.py).

Guard grammar (the value string):

  ``lock:<attr>``
      Every access (read or write) of the attribute inside the owning
      class happens under ``with self.<attr>`` / ``async with
      self.<attr>`` on the named lock.  ``__init__`` is exempt
      (construction precedes sharing).

  ``single-task:<owner>``
      Mutations are confined to the asyncio task whose body is
      ``<owner>``: every mutation site must sit in ``<owner>`` or a
      function (transitively) called from it.  Reads from other tasks
      are allowed — the event loop makes a sync read atomic — which is
      exactly why check-then-act ACROSS an await needs the
      ``race-await-atomicity`` rule instead.

  ``thread:<owner>``
      Same confinement check, but ``<owner>`` runs on a dedicated
      non-event-loop thread (the engine's device executor); readers on
      other threads must take an atomic snapshot (``list(d.items())``)
      rather than iterate live state.

A registry entry whose class, attribute, guard lock, or owner function
no longer exists FIRES — the table cannot drift from the code.  The
table renders into docs/concurrency.md via
``python -m dynamo_tpu.analysis --emit-sync-docs`` (freshness-tested),
so the guard conventions future schedulers must land into are readable
without opening this file.
"""

from __future__ import annotations

#: "Class.attr" -> guard spec (grammar above).  Keep keys as plain string
#: literals: the race rules parse this file's AST and never import it.
GUARDED_STATE = {
    # KVBM tier state: written on the kvbm-tier thread (batched offload
    # stores), read on the event loop (admission probe) — the lock is the
    # only thing standing between them.
    "KvBlockManager.host": "lock:_lock",
    "KvBlockManager.disk": "lock:_lock",
    "KvBlockManager.offloaded_blocks": "lock:_lock",
    "KvBlockManager.onboarded_blocks": "lock:_lock",
    "KvBlockManager.disk_evictions": "lock:_lock",
    "KvBlockManager.dropped_blocks": "lock:_lock",
    "KvBlockManager._load_ms": "lock:_lock",
    # cluster KV fabric: hashes dropped from ALL tiers pending their
    # `evicted` mesh retraction — appended on the kvbm-tier thread's
    # store path, drained wherever announcements fire.
    "KvBlockManager._evicted_pending": "lock:_lock",
    # kvbm offload pipeline (docs/kvbm.md): the event loop stages commits
    # and flushes them into batches, the device-exec thread marks a
    # batch's gather ready, the kvbm-tier thread consumes — three
    # contexts, one condition variable's lock over all of it.
    "KvbmConnector._staged": "lock:_offload_cv",
    "KvbmConnector._queue": "lock:_offload_cv",
    "KvbmConnector._inflight_hashes": "lock:_offload_cv",
    "KvbmConnector._processing": "lock:_offload_cv",
    "KvbmConnector._stopped": "lock:_offload_cv",
    "KvbmConnector.offload_gathers": "lock:_offload_cv",
    "KvbmConnector.offload_blocks_dropped": "lock:_offload_cv",
    "KvbmConnector.offload_failures": "lock:_offload_cv",
    # per-source onboard decision counters (cluster KV fabric): bumped at
    # admission on the event loop, read by stats() from any context.
    "KvbmConnector.onboard_src_local_blocks": "lock:_offload_cv",
    "KvbmConnector.onboard_src_peer_blocks": "lock:_offload_cv",
    "KvbmConnector.onboard_src_recompute_blocks": "lock:_offload_cv",
    # engine decode pipeline: the step-loop task owns the ONE queue of
    # in-flight entries (decode blocks and piped mixed steps, in dispatch
    # order), the list of dispatches the same step fetches itself (split
    # prefill, drained mixed steps) and that drain's hold flag; ROADMAP
    # item 1's scheduler must keep mutations inside the step loop (or
    # take over this entry).
    "JaxEngine._inflight": "single-task:_step_loop",
    "JaxEngine._pending_prefill": "single-task:_step_loop",
    "JaxEngine._mixed_wait_drain": "single-task:_step_loop",
    "JaxEngine._carry_valid": "single-task:_step_loop",
    # per-dispatch-type device occupancy (engine/recorder.py): mutated only
    # inside `timed`, which the engine's wrapper of the same name calls on
    # the jax-step and jax-fetch executor threads, one tag a thread; readers
    # (stats) take a list() snapshot.
    "Recorder.dev_time": "thread:timed",
    # dynosched (engine/scheduler/): the cost model's per-shape EWMA is
    # written on the jax-step thread (the `timed` wrapper observes every
    # dispatch) and read on the event loop (planning, stats, the disagg
    # TTFT estimate) — the lock is the only thing between them. Planner
    # bookkeeping (deadline table, decision records) stays confined to
    # the engine step loop, per the convention this registry was seeded
    # to enforce on ROADMAP item 1's scheduler.
    "CostModel._ewma": "lock:_lock",
    # live role morphing (docs/autoscaling.md "Role morphing"): the
    # serving role and the morph state machine's position are mutated
    # only inside the engines' `morph` coroutine (one morph at a time —
    # morph() refuses re-entry); generate/admission/stats read them from
    # other tasks, which the event loop makes atomic per read.
    "JaxEngine._role": "single-task:morph",
    "JaxEngine._morph_state": "single-task:morph",
    "MockEngine._role": "single-task:morph",
    "MockEngine._morph_state": "single-task:morph",
    "StepPlanner._deadlines": "single-task:_step_loop",
    "StepPlanner._records": "single-task:_step_loop",
    # dynogate tenant-fairness tiebreak bookkeeping: granted tokens per
    # tenant, fed by the planner's own accounting calls (all reached from
    # the engine step loop, like the deadline table above).
    "StepPlanner._tenant_served": "single-task:_step_loop",
    # dynogate (gate/gate.py): every WFQ/virtual-time/debt mutation is
    # confined to the gate's single pump task; `admit` only appends to
    # the inbox asyncio.Queue and awaits its entry's future, so
    # admission decisions cannot tear across requests.
    "AdmissionGate._waiting": "single-task:_pump",
    "AdmissionGate._debt": "single-task:_pump",
    "AdmissionGate._debt_seen": "single-task:_pump",
    # endpoint instance table: the watch task is the only mutator once
    # the client is started (static mode carries a reasoned waiver).
    "Client.instances": "single-task:_watch_loop",
    # SLA planner loop (planner/planner_core.py): the governor's committed
    # target and streak/cooldown counters are owned end-to-end by the
    # planner's own `run` task (observe → adjust → reconcile, serially);
    # the soak and unit tests drive the same methods single-task too.
    "Planner._target": "single-task:run",
    "Planner._below_streak": "single-task:run",
    "Planner._intervals_since_change": "single-task:run",
    # re-role arms (docs/autoscaling.md "Role morphing"): the colocate
    # streak is governor state like the counters above — owned by the
    # planner's run task end to end.
    "Planner._colocate_streak": "single-task:run",
    # connector replica bookkeeping: written only by set_replicas /
    # reconcile, both reached from the planner's run task.
    "LocalProcessConnector._want": "single-task:run",
    "InProcWorkerPool._want": "single-task:run",
    # the in-proc pool's worker list moves with _want: every mutation
    # (spawn/retire/morph/kill) happens in connector methods reached from
    # the planner's run task; other tasks only snapshot-read it.
    "InProcWorkerPool.workers": "single-task:run",
    # deploy/planner reconcilers: one _PollLoop task per reconciler owns
    # the failure-backoff and revision bookkeeping end to end.
    "GraphController._failures": "single-task:reconcile_once",
    "GraphController._retry_at": "single-task:reconcile_once",
    "GraphReconciler._applied_base": "single-task:reconcile_once",
    "GraphReconciler.applied_revision": "single-task:reconcile_once",
    "OperatorLite.applied_revision": "single-task:reconcile_once",
}
