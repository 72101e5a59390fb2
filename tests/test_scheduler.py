"""dynosched tests: cost-model convergence, EDF vs FIFO ordering, ITL-budget
chunk shrinking, starvation guards, fifo bit-for-bit parity on a scripted
mocker trace, disagg staleness/SLA routing, and the chaos arm (an
`engine.step` fault mid-schedule leaves no orphaned deadline state).

The planner-level tests drive StepPlanner with duck-typed fake slots (the
planner only reads admit_seq / sched_deadline / sched_skips / kv_prompt /
prefill_pos, exactly the _Slot surface engine.py hands it); the parity and
chaos tests drive the real MockEngine scheduler and a real tiny JaxEngine.
"""

import asyncio
import random
import time
from dataclasses import dataclass, field
from typing import List, Optional

import pytest

from dynamo_tpu.engine.scheduler import CostModel, SlaConfig, StepPlanner
from dynamo_tpu.llm.disagg import DisaggConfig, DisaggregatedRouter
from dynamo_tpu.llm.mocker.engine import MockEngine, MockEngineArgs, _MockRequest
from dynamo_tpu.llm.protocols import PreprocessedRequest
from dynamo_tpu.llm.tokens import TokenBlockSequence
from dynamo_tpu.runtime import faults
from dynamo_tpu.runtime.engine import Context


# --------------------------------------------------------------------------- #
# fakes
# --------------------------------------------------------------------------- #


@dataclass
class _FakeCfg:
    """The EngineConfig surface StepPlanner reads (duck-typed)."""

    prefill_buckets: tuple = (16, 64, 256)
    prefill_batch_tokens: int = 512
    max_prefill_batch: int = 8
    max_prefill_chunk: int = 256
    decode_block_steps: int = 4
    max_num_seqs: int = 32
    mixed_max_tokens: int = 512
    spec_mode: Optional[str] = None
    spec_draft_len: int = 4


@dataclass
class _FakeSlot:
    request_id: str
    admit_seq: int
    kv_prompt: list
    prefill_pos: int = 0
    sched_deadline: float = 0.0
    sched_skips: int = 0
    priority: int = 0
    arrival_s: float = 0.0


def _slots(n, prompt_len=100, deadlines=None):
    out = []
    for i in range(n):
        out.append(_FakeSlot(
            request_id=f"r{i}", admit_seq=i + 1,
            kv_prompt=list(range(prompt_len)),
            sched_deadline=deadlines[i] if deadlines else float(i),
        ))
    return out


def _planner(policy="sla", ttft_ms=2000.0, itl_ms=0.0, cfg=None):
    return StepPlanner(
        cfg or _FakeCfg(),
        SlaConfig(policy=policy, ttft_target_ms=ttft_ms, itl_target_ms=itl_ms),
    )


# --------------------------------------------------------------------------- #
# cost model
# --------------------------------------------------------------------------- #


def test_cost_model_converges_on_synthetic_timings():
    """EWMA per shape converges to the true mean under noise, and the
    warmup phase washes out a compile-time outlier first sample."""
    cm = CostModel()
    rng = random.Random(0)
    true = {("prefill", 64, 4): 0.020, ("block", 4, 32): 0.008}
    # first observation is a compile outlier 50x the steady state
    cm.observe("prefill", 64, 4, 1.0)
    for _ in range(200):
        for (kind, b, l), t in true.items():
            cm.observe(kind, b, l, t * rng.uniform(0.9, 1.1))
    for (kind, b, l), t in true.items():
        got = cm.predict(kind, b, l)
        assert got == pytest.approx(t, rel=0.15), (kind, got, t)
    assert cm.n_observations() == 401


def test_cost_model_unknown_shape_scales_nearest_and_unknown_kind_is_none():
    cm = CostModel()
    assert cm.predict("prefill", 64, 1) is None  # never observed: no guess
    for _ in range(8):
        cm.observe("prefill", 64, 1, 0.010)
    # unknown shape of a known kind: nearest same-kind shape scaled by
    # token volume (128 tokens vs 64 observed -> 2x)
    assert cm.predict("prefill", 128, 1) == pytest.approx(0.020, rel=0.01)
    assert cm.predict("block", 4, 32) is None  # other kinds stay unknown


def test_cost_model_per_token_rate():
    cm = CostModel()
    for _ in range(8):
        cm.observe("prefill", 100, 1, 0.010)  # 100 us/token
    assert cm.per_token("prefill") == pytest.approx(1e-4, rel=0.01)
    assert cm.per_token("block") is None


# --------------------------------------------------------------------------- #
# SLA config / deadlines
# --------------------------------------------------------------------------- #


def test_sla_config_env_resolution(monkeypatch):
    monkeypatch.setenv("DYN_SCHED_POLICY", "sla")
    monkeypatch.setenv("DYN_SLA_TTFT_MS", "750")
    monkeypatch.setenv("DYN_SLA_ITL_MS", "40")
    sla = SlaConfig.from_env()
    assert (sla.policy, sla.ttft_target_ms, sla.itl_target_ms) == ("sla", 750.0, 40.0)
    # explicit values win over env
    sla = SlaConfig.from_env(policy="fifo", itl_target_ms=0)
    assert sla.policy == "fifo" and sla.itl_target_ms == 0.0
    # unknown policy or garbage floats must not take the path down
    monkeypatch.setenv("DYN_SCHED_POLICY", "frobnicate")
    monkeypatch.setenv("DYN_SLA_TTFT_MS", "not-a-number")
    sla = SlaConfig.from_env()
    assert sla.policy == "fifo" and sla.ttft_target_ms == 2000.0


def test_priority_scales_ttft_deadline():
    sla = SlaConfig(policy="sla", ttft_target_ms=1000.0)
    base = sla.deadline(10.0)
    assert base == pytest.approx(11.0)
    assert sla.deadline(10.0, priority=1) == pytest.approx(10.5)  # +1 halves
    assert sla.deadline(10.0, priority=-1) == pytest.approx(12.0)  # -1 doubles


# --------------------------------------------------------------------------- #
# ordering: EDF vs FIFO, starvation guard
# --------------------------------------------------------------------------- #


def test_edf_ordering_vs_fifo_under_deadline_skew():
    """Admission order and deadline order disagree; fifo follows admission,
    sla follows deadlines."""
    # r0 admitted first but has the LATEST deadline, r2 the earliest
    slots = _slots(3, deadlines=[30.0, 20.0, 10.0])
    fifo = _planner("fifo")
    assert [s.request_id for s in fifo.order(slots)] == ["r0", "r1", "r2"]
    sla = _planner("sla")
    assert [s.request_id for s in sla.order(slots)] == ["r2", "r1", "r0"]
    # order_waiting: same EDF key on the waiting queue, fifo untouched
    assert [s.request_id for s in sla.order_waiting(slots)] == ["r2", "r1", "r0"]
    assert fifo.order_waiting(slots) is slots


def test_edf_starvation_guard_jumps_order():
    """A candidate skipped starve_dispatches times outranks an earlier
    deadline: EDF cannot hold a request back forever."""
    p = _planner("sla")
    slots = _slots(3, deadlines=[10.0, 20.0, 30.0])
    slots[2].sched_skips = p.sla.starve_dispatches
    assert [s.request_id for s in p.order(slots)] == ["r2", "r0", "r1"]


# --------------------------------------------------------------------------- #
# batch-kind starvation (satellite: _dispatch_prefill aging tiebreak)
# --------------------------------------------------------------------------- #


def test_batch_kind_starvation_reconstruction():
    """Reconstructs the seed starvation: under a steady stream of guided
    requests, the legacy rule (first non-plain kind in order wins the
    batch) excludes a lone mm candidate on EVERY dispatch — it never runs.
    The aging tiebreak bounds the wait: after starve_dispatches skips the
    mm candidate wins the batch outright.

    The loop mirrors engine._dispatch_prefill exactly: pick_batch_kind,
    then bump sched_skips on every excluded candidate."""
    p = _planner("fifo")  # the guard is a fairness fix, active under BOTH

    def kind_of(s):
        return s._kind

    mm = _FakeSlot("mm", admit_seq=1, kv_prompt=list(range(64)))
    mm._kind = "mm"
    legacy_wins = 0
    dispatches = 0
    for step in range(p.sla.starve_dispatches + 2):
        # a fresh guided candidate arrives every step and sorts first
        g = _FakeSlot(f"g{step}", admit_seq=step + 2, kv_prompt=list(range(64)))
        g._kind = "guided"
        cands = [g, mm]
        # the legacy rule alone would pick guided forever
        if next((kind_of(s) for s in cands if kind_of(s) != "plain"), "plain") == "mm":
            legacy_wins += 1
        batch_kind = p.pick_batch_kind(cands, kind_of)
        dispatches += 1
        if batch_kind == "mm":
            break
        for s in cands:
            if kind_of(s) not in ("plain", batch_kind):
                s.sched_skips += 1
    else:
        pytest.fail("mm candidate starved past the guard threshold")
    assert legacy_wins == 0, "seed rule would have served mm (test is vacuous)"
    assert dispatches == p.sla.starve_dispatches + 1
    assert p.starvation_overrides == 1


# --------------------------------------------------------------------------- #
# plan_prefill: fifo parity, ITL budget, deferral, deadline override
# --------------------------------------------------------------------------- #


def test_fifo_plan_matches_legacy_formula_bit_for_bit():
    """Fuzz: under fifo the planner must reproduce the seed dispatch
    formula exactly — bucket from the head candidate's chunk, lanes 1
    (lone arrival) or the bucket's cap, chosen = first `lanes` in order."""
    rng = random.Random(42)
    for _ in range(200):
        buckets = sorted(rng.sample([16, 32, 64, 128, 256, 512], rng.randint(1, 4)))
        cfg = _FakeCfg(
            prefill_buckets=tuple(buckets),
            prefill_batch_tokens=rng.choice([128, 512, 1024]),
            max_prefill_batch=rng.randint(1, 8),
            max_prefill_chunk=rng.choice([64, 256]),
        )
        p = _planner("fifo", cfg=cfg)
        cands = []
        for i in range(rng.randint(1, 6)):
            s = _FakeSlot(f"r{i}", admit_seq=i + 1,
                          kv_prompt=list(range(rng.randint(1, 600))))
            s.prefill_pos = rng.randint(0, len(s.kv_prompt) - 1)
            cands.append(s)

        # the seed formula, verbatim (engine.py pre-dynosched)
        first_chunk = min(
            len(cands[0].kv_prompt) - cands[0].prefill_pos, cfg.max_prefill_chunk
        )
        bucket = next((b for b in cfg.prefill_buckets if first_chunk <= b),
                      cfg.prefill_buckets[-1])
        lanes_cap = max(1, min(cfg.prefill_batch_tokens // bucket,
                               cfg.max_prefill_batch))
        lanes = 1 if len(cands) == 1 else lanes_cap

        plan = p.plan_prefill(cands, decode_active=rng.random() < 0.5)
        assert plan is not None, "fifo never defers"
        assert plan.reason == "fifo"
        assert (plan.bucket, plan.lanes) == (bucket, lanes)
        assert plan.chosen == cands[:lanes]


def test_itl_budget_shrinks_prefill_shape():
    """Decode active + a tight ITL budget: the big bucket's predicted time
    busts the budget, the small one fits -> the planner shrinks."""
    cfg = _FakeCfg(prefill_buckets=(16, 256), prefill_batch_tokens=512)
    p = _planner("sla", itl_ms=10.0, cfg=cfg)
    # block of 4 steps over 32 lanes costs 20ms -> budget = 4*10 - 20 = 20ms.
    # With 2 candidates the planner considers (16, lanes 8) and (256,
    # lanes 2) — observe those exact shapes.
    for _ in range(8):
        p.cost.observe("block", cfg.decode_block_steps, cfg.max_num_seqs, 0.020)
        p.cost.observe("prefill", 16, 8, 0.005)     # fits (5ms <= 20ms)
        p.cost.observe("prefill", 256, 2, 0.200)    # busts (200ms > 20ms)
    cands = _slots(2, prompt_len=300, deadlines=[1e9, 1e9])
    now = time.monotonic()
    plan = p.plan_prefill(cands, decode_active=True, now=now)
    assert plan is not None and plan.reason == "itl-shrunk"
    assert plan.bucket == 16
    assert plan.budget_s == pytest.approx(0.020, rel=0.01)
    assert p.itl_shrunk_steps == 1
    # no decode active: same planner goes full throttle (big bucket wins
    # on granted tokens; nothing is shrunk)
    plan2 = p.plan_prefill(cands, decode_active=False, now=now)
    assert plan2.reason == "coverage" and plan2.bucket == 256


def test_itl_budget_exhausted_defers_then_deadline_overrides():
    """Every shape busts the budget: defer while the head has slack; once
    its TTFT deadline goes negative the dispatch goes through anyway
    (SLA attainment outranks decode smoothness)."""
    cfg = _FakeCfg(prefill_buckets=(16, 256))
    p = _planner("sla", itl_ms=10.0, cfg=cfg)
    for _ in range(8):
        p.cost.observe("block", cfg.decode_block_steps, cfg.max_num_seqs, 0.039)
        p.cost.observe("prefill", 16, 1, 0.500)   # busts 1ms budget
        p.cost.observe("prefill", 256, 2, 0.900)
    now = time.monotonic()
    cands = _slots(2, prompt_len=300, deadlines=[now + 60.0, now + 90.0])
    assert p.plan_prefill(cands, decode_active=True, now=now) is None
    assert p.deferred_steps == 1
    # deadline in the past: the smallest shape dispatches regardless
    cands[0].sched_deadline = now - 0.1
    plan = p.plan_prefill(cands, decode_active=True, now=now)
    assert plan is not None and plan.reason == "deadline-override"
    assert plan.bucket == 16
    assert p.deadline_overrides == 1
    assert plan.slack_ms is not None and plan.slack_ms < 0


def test_sla_plan_respects_max_prefill_chunk():
    """The sla shape search must honor the operator's per-chunk latency
    bound: buckets above max_prefill_chunk are out of the candidate
    space, even though they would score highest on granted tokens (the
    engine derives the per-lane chunk from plan.bucket, so a too-big
    bucket IS a too-big chunk)."""
    cfg = _FakeCfg(
        prefill_buckets=(128, 256, 512, 1024),
        prefill_batch_tokens=1024,
        max_prefill_chunk=256,
    )
    p = _planner("sla", cfg=cfg)
    cands = _slots(1, prompt_len=1024, deadlines=[1e9])
    plan = p.plan_prefill(cands, decode_active=False)
    assert plan is not None and plan.bucket <= 256
    # non-bucket-aligned cap rounds up to the covering bucket, exactly
    # like the legacy formula's bucket_for(min(remaining, cap))
    cfg2 = _FakeCfg(
        prefill_buckets=(128, 256, 512, 1024),
        prefill_batch_tokens=1024,
        max_prefill_chunk=300,
    )
    p2 = _planner("sla", cfg=cfg2)
    plan2 = p2.plan_prefill(cands, decode_active=False)
    assert plan2 is not None and plan2.bucket == 512


def test_unknown_cost_means_no_constraint():
    """A cold cost model must never defer: unknown block/prefill cost is
    'no constraint', not 'assume the worst'."""
    p = _planner("sla", itl_ms=5.0)
    cands = _slots(1, prompt_len=100, deadlines=[1e9])
    plan = p.plan_prefill(cands, decode_active=True)
    assert plan is not None and plan.reason == "coverage"


# --------------------------------------------------------------------------- #
# deadline bookkeeping + observability
# --------------------------------------------------------------------------- #


def test_plan_mixed_packs_chunks_beside_decode_rows():
    """plan_mixed grants prefill chunks into the flat-token budget left
    beside the decode rows, in real tokens; the bucket is the smallest of
    the mixed step's token buckets (bucketing.mixed_token_buckets) that
    holds the packed total."""
    p = _planner(policy="fifo", cfg=_FakeCfg(max_num_seqs=4))
    cands = _slots(2, prompt_len=100)
    plan = p.plan_mixed(cands, n_decode=4)
    assert plan is not None and plan.reason == "mixed"
    assert plan.chosen == cands and plan.chunks == [100, 100]
    assert plan.n_decode == 4
    # 2 x 100 chunk tokens + 4 decode tokens = 204 -> 256 of (256, 512)
    assert plan.bucket == 256
    # one chunk more and the pack needs the cap
    assert p.plan_mixed(_slots(3, prompt_len=100), n_decode=4).bucket == 512
    # plan_mixed is pure — grants count only on engine commit
    assert p.granted_tokens == 0 and p.granted_chunks == 0
    p.commit_mixed(plan, list(zip(plan.chosen, plan.chunks)))
    assert p.granted_tokens == 200 and p.granted_chunks == 2


@pytest.mark.parametrize("budget", [519, 512, 257])
def test_plan_mixed_budget_is_real_tokens_and_never_overpacks(budget):
    """mixed_max_tokens bounds the REAL tokens of a pack, whatever it is a
    multiple of: the flat buffer is compact, so the whole budget is
    granted and the largest bucket is the budget itself (while rows were
    packed a q tile apart, a 519-token budget was floored to 512 and a
    one-token row cost 8)."""
    p = _planner(policy="fifo", cfg=_FakeCfg(mixed_max_tokens=budget))
    cands = _slots(3, prompt_len=400)
    plan = p.plan_mixed(cands, n_decode=1)
    assert plan is not None
    assert sum(plan.chunks) + 1 == budget == plan.bucket


def test_plan_mixed_respects_budget_and_declines_when_full():
    p = _planner(policy="fifo")
    # decode rows alone exceed the flat budget -> no fused step
    assert p.plan_mixed(_slots(1), n_decode=600) is None
    # chunks shrink to what fits beside the decode rows
    cands = _slots(3, prompt_len=400)
    plan = p.plan_mixed(cands, n_decode=100)
    assert plan is not None
    assert 100 + sum(plan.chunks) <= 512
    assert all(ch <= 256 for ch in plan.chunks)  # max_prefill_chunk cap


def test_plan_mixed_itl_budget_shrinks_chunks():
    """Under sla with an ITL target, a too-slow predicted mixed step
    halves chunks until the estimate fits or the pack is down to the
    smallest token bucket (never defers outright — the decode lanes ride
    the same dispatch)."""
    p = _planner(policy="sla", itl_ms=10.0, cfg=_FakeCfg(max_num_seqs=4))
    # teach the model: big mixed dispatches are slow, small ones fast
    for _ in range(12):
        p.cost.observe("mixed", 512, 10, 0.050)
        p.cost.observe("mixed", 256, 10, 0.004)
    cands = _slots(2, prompt_len=400)
    plan = p.plan_mixed(cands, n_decode=8)
    assert plan is not None
    assert plan.reason == "mixed-shrunk"
    assert plan.bucket == 256 and 8 + sum(plan.chunks) <= 256
    assert p.itl_shrunk_steps == 0  # pure until commit
    p.commit_mixed(plan, list(zip(plan.chosen, plan.chunks)))
    assert p.itl_shrunk_steps == 1


def test_plan_mixed_spec_rows_reserve_row_budget():
    """n_spec_rows reserves EXTRA one-token verify rows beside the plain
    decode rows: chunks shrink to what fits, MixedPlan reports the count,
    and n_spec_rows=0 is byte-identical to the pre-spec plan shape."""
    p = _planner(policy="fifo")
    cands = _slots(2, prompt_len=300)
    base = p.plan_mixed(cands, n_decode=4)
    spec = p.plan_mixed(cands, n_decode=4, n_spec_rows=12)
    assert base is not None and spec is not None
    assert base.n_spec_rows == 0 and spec.n_spec_rows == 12
    # 12 extra one-token rows eat 12 flat tokens of chunk space
    assert sum(spec.chunks) == sum(base.chunks) - 12
    assert spec.n_decode == base.n_decode == 4
    # budget math: chunk tokens + every one-token row fit the buffer
    assert sum(spec.chunks) + 4 + 12 == 512


def test_plan_mixed_declines_when_spec_rows_fill_budget():
    """Spec verify rows alone filling mixed_max_tokens -> no fused
    step (engine rides the split spec path instead)."""
    p = _planner(policy="fifo")
    # one token a row: 4 decode + 508 spec rows = 512, the whole budget
    assert p.plan_mixed(_slots(1), n_decode=4, n_spec_rows=508) is None
    # one fewer spec row leaves a token for the chunk
    plan = p.plan_mixed(_slots(1), n_decode=4, n_spec_rows=507)
    assert plan is not None and plan.n_spec_rows == 507
    assert plan.chunks == [1]


def test_deadline_lifecycle_and_reset():
    p = _planner("sla")
    slots = _slots(3)
    for s in slots:
        p.on_admit(s)
    assert p.stats()["sched_pending_deadlines"] == 3
    p.on_release(slots[0])
    assert p.stats()["sched_pending_deadlines"] == 2
    p.reset()  # fail-all: no deadline may outlive its slot
    assert p.stats()["sched_pending_deadlines"] == 0


def test_estimate_wait_ms_tracks_queue_depth():
    p = _planner("sla")
    assert p.estimate_wait_ms(1000) is None  # cold model: unknown
    for _ in range(8):
        p.cost.observe("prefill", 100, 1, 0.010)  # 100 us/token
    assert p.estimate_wait_ms(1000) == pytest.approx(100.0, rel=0.05)
    assert p.estimate_wait_ms(0) == 0.0


def test_decision_records_are_bounded_and_reported():
    p = _planner("fifo")
    cands = _slots(2)
    for _ in range(100):
        p.plan_prefill(cands, decode_active=False)
    assert len(p.recent_decisions()) == 64  # bounded history
    st = p.stats()
    assert st["sched_granted_chunks"] == 200
    assert st["sched_policy"] == "fifo"


# --------------------------------------------------------------------------- #
# scripted mocker trace: fifo parity (bit-for-bit) + sla reordering
# --------------------------------------------------------------------------- #


def _seed_admission_and_prefill(eng: MockEngine) -> int:
    """The SEED MockEngine._do_admission_and_prefill, verbatim (pre-
    dynosched): admit in arrival order, chunk in running order, budget =
    max_num_batched_tokens. The parity oracle below diffs per-step
    decisions of the real scheduler under fifo against this."""
    a = eng.args
    budget = a.max_num_batched_tokens
    processed = 0
    still_waiting: List[_MockRequest] = []
    for req in eng._waiting:
        if req.done or req.context.is_stopped():
            eng._finish(req, "cancelled", emit=not req.done)
            continue
        if len(eng._running) >= a.max_num_seqs:
            still_waiting.append(req)
            continue
        hashes = req.seq.block_hashes()
        cached = eng.kv.cached_prefix_blocks(hashes) if a.enable_prefix_caching else 0
        if not eng.kv.can_allocate(hashes, extra_blocks=1):
            still_waiting.append(req)
            continue
        token_blocks = [b.tokens for b in req.seq.blocks]
        eng.kv.acquire(hashes, token_blocks=token_blocks)
        req.held_hashes = list(hashes)
        req.prefill_pos = cached * a.block_size if not req.decode_only else len(req.prompt)
        eng._running.append(req)
    eng._waiting = still_waiting
    for req in eng._running:
        if req.prefill_pos >= len(req.prompt):
            continue
        remaining = len(req.prompt) - req.prefill_pos
        chunk = min(remaining, budget - processed) if a.enable_chunked_prefill else remaining
        if chunk <= 0:
            continue
        req.prefill_pos += chunk
        processed += chunk
    return processed


def _mock_req(rid, prompt, max_tokens, deadline, args):
    r = _MockRequest(
        request_id=rid, prompt=prompt, max_tokens=max_tokens,
        eos_token_ids=[], ignore_eos=True, queue=asyncio.Queue(),
        context=Context(),
    )
    r.seq = TokenBlockSequence(prompt, args.block_size)
    r.sched_deadline = deadline
    return r


def _snapshot(eng):
    """One step's observable scheduling decisions."""
    return (
        [(r.request_id, r.prefill_pos, r.generated) for r in eng._running],
        [r.request_id for r in eng._waiting],
        eng.kv.active_blocks,
    )


def _scripted_trace(policy):
    """Drive the scheduler synchronously (no step loop) over a scripted
    arrival trace that fifo and sla MUST order differently: small-budget
    chunked prefill, late arrivals with tighter deadlines."""
    args = MockEngineArgs(
        num_gpu_blocks=256, block_size=4, max_num_seqs=4,
        max_num_batched_tokens=16,  # forces multi-step chunked prefill
        enable_prefix_caching=False,  # decisions purely scheduling-driven
        sched_policy=policy, ttft_target_ms=1000.0, itl_target_ms=0.0,
    )
    eng = MockEngine(args)
    arrivals = {
        0: [("a", 64, 100.0), ("b", 64, 90.0)],  # earlier arrivals, late ddl
        1: [("c", 32, 1.0)],                     # latecomer, urgent deadline
    }
    trace = []
    first_token_step = {}
    for step in range(40):
        for rid, plen, ddl in arrivals.get(step, []):
            base = 1000 * (ord(rid[0]) - ord("a") + 1)
            eng._waiting.append(_mock_req(
                rid, list(range(base, base + plen)), 4, ddl, args))
        eng._do_admission_and_prefill()
        eng._do_decode()
        for r in eng._running:
            if r.generated and r.request_id not in first_token_step:
                first_token_step[r.request_id] = step
        trace.append(_snapshot(eng))
        if not eng._running and not eng._waiting and step > 2:
            break
    return trace, first_token_step


def test_fifo_parity_bit_for_bit_on_scripted_trace():
    """Under DYN_SCHED_POLICY=fifo the scheduler's per-step decisions are
    byte-identical to the seed implementation replayed on the same trace
    (same arrivals, same budgets, same KV state)."""
    got, _ = _scripted_trace("fifo")

    # replay: identical engine but with the SEED scheduler driving
    args = MockEngineArgs(
        num_gpu_blocks=256, block_size=4, max_num_seqs=4,
        max_num_batched_tokens=16, enable_prefix_caching=False,
        sched_policy="fifo",
    )
    eng = MockEngine(args)
    arrivals = {
        0: [("a", 64, 100.0), ("b", 64, 90.0)],
        1: [("c", 32, 1.0)],
    }
    want = []
    for step in range(40):
        for rid, plen, ddl in arrivals.get(step, []):
            base = 1000 * (ord(rid[0]) - ord("a") + 1)
            eng._waiting.append(_mock_req(
                rid, list(range(base, base + plen)), 4, ddl, args))
        _seed_admission_and_prefill(eng)
        eng._do_decode()
        want.append(_snapshot(eng))
        if not eng._running and not eng._waiting and step > 2:
            break
    assert got == want, "fifo must be bit-for-bit the seed scheduler"


def test_sla_trace_reorders_for_urgent_deadline():
    """Same scripted trace under sla: the urgent latecomer 'c' finishes its
    prefill (first token) no later than the early big arrivals — EDF did
    reorder; fifo serves strictly in arrival order."""
    _, fifo_first = _scripted_trace("fifo")
    _, sla_first = _scripted_trace("sla")
    # fifo: c is last (arrived last, chunk order follows admission)
    assert fifo_first["c"] >= max(fifo_first["a"], fifo_first["b"])
    # sla: c's tight deadline wins the prefill budget
    assert sla_first["c"] <= min(sla_first["a"], sla_first["b"])
    # and strictly earlier than fifo gave it
    assert sla_first["c"] < fifo_first["c"]


def test_mocker_itl_budget_defers_and_deadline_breaks():
    """The mocker's ITL budget: decode active + tight target -> zero
    prefill budget (deferred); an overdue TTFT deadline breaks the zero
    with one block (the deadline override)."""
    args = MockEngineArgs(
        sched_policy="sla", ttft_target_ms=1000.0, itl_target_ms=5.0,
        decode_time_per_step=8e-3,  # decode alone eats the 5ms target
        speedup_ratio=1.0,
    )
    eng = MockEngine(args)
    # one decode-active request, one prefill-pending with future deadline
    dec = _mock_req("dec", list(range(8)), 100, time.monotonic() + 50, args)
    dec.prefill_pos = len(dec.prompt)
    eng._running.append(dec)
    pre = _mock_req("pre", list(range(64)), 4, time.monotonic() + 50, args)
    eng._running.append(pre)
    assert eng._itl_prefill_budget() == 0
    assert eng.sched_deferred_steps == 1
    # now the prefill-pending request is overdue: budget breaks to a block
    pre.sched_deadline = time.monotonic() - 1.0
    assert eng._itl_prefill_budget() == args.block_size
    assert eng.sched_deadline_overrides == 1
    # no decode active: full throttle
    dec.prefill_pos = 0
    assert eng._itl_prefill_budget() == args.max_num_batched_tokens
    # everything fully prefilled: a zeroed budget with NO pending prefill
    # work is not a deferral — the counters must not move (they are the
    # 'deferral runaway' signal --sla-smoke watches)
    dec.prefill_pos = len(dec.prompt)
    pre.prefill_pos = len(pre.prompt)
    before = (eng.sched_deferred_steps, eng.sched_deadline_overrides)
    assert eng._itl_prefill_budget() == 0
    assert (eng.sched_deferred_steps, eng.sched_deadline_overrides) == before


def test_mock_engine_e2e_sla_policy_generates_identically():
    """The sla policy must change WHEN work runs, never WHAT it produces:
    same requests, same token streams as fifo."""
    async def run(policy):
        eng = MockEngine(MockEngineArgs(
            num_gpu_blocks=256, block_size=4, speedup_ratio=1000.0,
            sched_policy=policy, ttft_target_ms=500.0, itl_target_ms=20.0,
        ))

        async def one(rid, priority):
            req = PreprocessedRequest(
                token_ids=list(range(50, 82)),
                stop_conditions={"max_tokens": 5, "ignore_eos": True},
                request_id=rid, priority=priority,
            ).to_dict()
            toks = []
            async for item in eng.generate(req, Context()):
                data = item.get("data")
                if data:
                    toks.extend(data["token_ids"])
            return toks
        out = await asyncio.gather(*[one(f"r{i}", i % 3 - 1) for i in range(8)])
        st = eng.stats()
        await eng.close()
        return out, st

    fifo_out, fifo_stats = asyncio.run(run("fifo"))
    sla_out, sla_stats = asyncio.run(run("sla"))
    assert fifo_out == sla_out
    assert fifo_stats["sched_policy"] == "fifo"
    assert sla_stats["sched_policy"] == "sla"
    # fifo never spends SLA machinery
    assert fifo_stats["sched_deferred_steps"] == 0
    assert fifo_stats["sched_deadline_overrides"] == 0


# --------------------------------------------------------------------------- #
# disagg router: staleness decay + SLA-informed routing (satellite)
# --------------------------------------------------------------------------- #


def test_disagg_backpressure_decays_when_depth_goes_stale():
    """Regression: a depth published just before a prefill worker died
    used to pin 'queue full -> keep local' forever. Stale depth is now
    UNKNOWN: the decision falls back to the threshold rule."""
    r = DisaggregatedRouter(DisaggConfig(
        enabled=True, remote_prefill_threshold_tokens=64,
        max_prefill_queue=8, queue_depth_ttl_s=5.0,
    ))
    t0 = 1000.0
    # no depth ever published: threshold rule applies
    assert r.prefill_remote(200, 0, True, now=t0)
    # fresh over-limit depth: backpressure keeps prefill local
    r.update_queue_depth(100, now=t0)
    assert r.queue_depth_known(now=t0 + 1.0)
    assert not r.prefill_remote(200, 0, True, now=t0 + 1.0)
    # the worker dies; its last report ages out -> unknown -> threshold
    assert not r.queue_depth_known(now=t0 + 5.1)
    assert r.prefill_remote(200, 0, True, now=t0 + 5.1)
    # a fresh healthy report re-enables backpressure semantics
    r.update_queue_depth(2, now=t0 + 6.0)
    assert r.prefill_remote(200, 0, True, now=t0 + 6.5)


def test_disagg_routes_on_estimated_local_ttft():
    """With the scheduler's local-TTFT estimate available, routing asks
    'does the local queue leave room for the TTFT budget', not 'is this
    prompt big'."""
    r = DisaggregatedRouter(DisaggConfig(
        enabled=True, remote_prefill_threshold_tokens=64,
        min_remote_tokens=16, ttft_headroom=0.5,
    ))
    # local queue would eat the budget: offload even a below-threshold prompt
    assert r.prefill_remote(40, 0, True,
                            local_ttft_est_ms=1500.0, ttft_target_ms=2000.0)
    # local queue is empty-ish: the static threshold still decides
    assert not r.prefill_remote(40, 0, True,
                                local_ttft_est_ms=10.0, ttft_target_ms=2000.0)
    assert r.prefill_remote(200, 0, True,
                            local_ttft_est_ms=10.0, ttft_target_ms=2000.0)
    # tiny uncached remainder never goes remote (KV transfer costs more)
    assert not r.prefill_remote(300, 290, True,
                                local_ttft_est_ms=9000.0, ttft_target_ms=2000.0)
    # no estimate (cold model / fifo): the reference rule, unchanged
    assert r.prefill_remote(200, 0, True)
    assert not r.prefill_remote(40, 0, True)


# --------------------------------------------------------------------------- #
# chaos arm: engine.step fault mid-schedule -> no orphaned deadline state
# --------------------------------------------------------------------------- #


def test_mixed_dispatch_streams_byte_identical_to_split_path():
    """PR 7 parity suite extended to the mixed dispatch (ISSUE 8
    acceptance): on the same scripted staggered trace under the fifo
    policy, the unified ragged path and the split prefill+decode path
    must emit byte-identical token streams — sampling draws are
    (seed, position)-keyed, so the dispatch shape must not leak into the
    output. The unified arm must actually take the fused path at least
    once (mixed_steps > 0), or this test proves nothing."""
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.engine import EngineConfig, JaxEngine
    from dynamo_tpu.models import llama

    cfg_model = llama.LlamaConfig.tiny(dtype=jnp.float32)
    params = llama.init_params(cfg_model, jax.random.PRNGKey(0))

    async def drive(mixed: bool):
        cfg = EngineConfig(
            model="tiny", max_num_seqs=4, page_size=16, num_pages=128,
            max_model_len=256, decode_block_steps=4,
            mixed_dispatch=mixed,
        )
        eng = JaxEngine(cfg, model_config=cfg_model, params=params)

        async def one(prompt, osl, seed):
            req = PreprocessedRequest(
                token_ids=prompt,
                stop_conditions={"max_tokens": osl, "ignore_eos": True},
                sampling_options={"temperature": 1.0, "seed": seed},
            ).to_dict()
            toks = []
            async for item in eng.generate(req, Context()):
                assert item.get("event") != "error", item.get("comment")
                if item.get("data"):
                    toks.extend(item["data"]["token_ids"])
            return toks

        rng = random.Random(42)
        prompts = [
            [rng.randrange(5, 500) for _ in range(n)] for n in (40, 60, 33)
        ]
        # staggered: the first request decodes while the others prefill —
        # the unified arm serves those steps with the fused dispatch
        t1 = asyncio.create_task(one(prompts[0], 24, 1))
        await asyncio.sleep(0.4)
        t2 = asyncio.create_task(one(prompts[1], 20, 2))
        await asyncio.sleep(0.2)
        t3 = asyncio.create_task(one(prompts[2], 12, 3))
        streams = await asyncio.gather(t1, t2, t3)
        stats = eng.stats()
        await eng.close()
        return streams, stats

    async def main():
        unified, s_uni = await drive(True)
        split, s_split = await drive(False)
        assert s_uni["mixed_steps"] > 0, \
            "the unified arm never took the fused path — trace too fast?"
        assert s_split["mixed_steps"] == 0
        assert unified == split
        # the fused step fed the cost model under its own shape tag
        assert s_uni["dispatch_mixed_count"] == s_uni["mixed_steps"]

    asyncio.run(main())


def test_engine_step_fault_leaves_no_orphaned_deadline_state():
    """A chaos-injected engine.step fault fails the active batch; the
    scheduler's deadline table must die with it (reset on fail-all) and
    the engine must serve cleanly afterwards with fresh deadlines."""
    import jax.numpy as jnp

    from dynamo_tpu.engine import EngineConfig, JaxEngine
    from dynamo_tpu.models import llama

    cfg_model = llama.LlamaConfig.tiny(dtype=jnp.float32)
    import jax
    params = llama.init_params(cfg_model, jax.random.PRNGKey(0))

    async def main():
        cfg = EngineConfig(
            model="tiny", max_num_seqs=4, page_size=8, num_pages=64,
            max_model_len=128, prefill_buckets=(16, 32), max_prefill_chunk=32,
            sched_policy="sla", ttft_target_ms=5000.0,
        )
        eng = JaxEngine(cfg, model_config=cfg_model, params=params)

        async def one(rid):
            req = PreprocessedRequest(
                token_ids=[5, 9, 17, 33, 101, 7, 250, 3],
                stop_conditions={"max_tokens": 4, "ignore_eos": True},
                request_id=rid,
            ).to_dict()
            items = []
            async for item in eng.generate(req, Context()):
                items.append(item)
            return items

        faults.configure("engine.step:error,times=1")
        try:
            res = await asyncio.gather(*[one(f"f{i}") for i in range(2)])
            # both streams terminated with a typed error chunk, not a hang
            assert all(
                any(it.get("event") == "error" for it in items)
                for items in res
            )
            assert eng.stats()["sched_pending_deadlines"] == 0, \
                "fail-all must clear the deadline table"
        finally:
            faults.reset()

        # recovery: the engine serves again, deadlines tracked AND released
        ok = await asyncio.gather(*[one(f"ok{i}") for i in range(2)])
        for items in ok:
            toks = [t for it in items if it.get("data")
                    for t in it["data"]["token_ids"]]
            assert len(toks) == 4
        assert eng.stats()["sched_pending_deadlines"] == 0
        # the cost model observed real dispatches along the way
        assert eng.stats()["sched_cost_observations"] > 0
        await eng.close()

    asyncio.run(main())
