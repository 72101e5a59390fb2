#!/usr/bin/env python3
"""The benchmark's own arithmetic, checked by hand-made cases. Run by hand:

    JAX_PLATFORMS=cpu python3 benchmark/selftest.py

Not part of tests/. Checks: percentile and due-time arithmetic on made-up
timestamps; that every seed gets the same set of lengths and arrivals; the
per-layer expressions; the trace reduction on fixtures/synthetic_trace
(busy union, idle share, self time, kernel share, gap attribution); and the
dense and mixture-of-experts references against the program's own forwards
(models/llama.py, models/moe.py at capacity_factor 4.0) at the tiny presets
in float32 on the CPU; the judge's limits on made-up logits, the defaults
name for name and a configuration's own (`judge`); the rule that holds such
limits to their readings, by planted files; that a configuration with
limits of its own is a new file alone (a whole `run.py --rehearsal` in a
tree of links, some 90 s); a family whose routing is judged FORCED: the
reference under made-up choices, a wrongly routed token, the client's check
of a reply's `routed_experts` and of its top tokens, the head judged over
those, a free family's re-sent request as it always was, the rule for its
limit, a router wider than the experts held, and such a family
as new files alone; a sizing run's picks, nested past a run's four, and
what it found at 8 a token; and when a closed loop's request is due. (PR 21 found that
a wrong page stays under the tolerance at tiny widths on the CPU: that
sabotage is a chip check.)
"""

from __future__ import annotations

import asyncio
import copy
import json
import os
import shutil
import subprocess
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.dirname(HERE))

import layer_metrics  # noqa: E402
import metrics  # noqa: E402
import reduce_trace  # noqa: E402
from traffic import Generator, Request, load_mix, quantile, warp  # noqa: E402


def close(a, b, tol=1e-9):
    return abs(a - b) <= tol * max(1.0, abs(a), abs(b))


def test_percentile():
    xs = list(range(1, 101))
    assert close(metrics.percentile(xs, 50), 50.5)
    assert close(metrics.percentile(xs, 95), 95.05)
    assert metrics.percentile([], 95) is None
    assert metrics.percentile([7.0], 95) == 7.0


def req(due, send, first, last, end, tokens, frames, ok=True):
    """Frames evenly from first to last, the first carrying one character
    and the others the rest in equal parts."""
    r = Request(rid="x", prompt="p", max_tokens=tokens)
    r.t_due, r.t_send, r.t_first, r.t_last, r.t_end = due, send, first, last, end
    r.tokens, r.frames, r.ok = tokens, frames, ok
    if frames:
        step = (last - first) / max(frames - 1, 1)
        r.frame_at = [first + i * step for i in range(frames)]
        r.frame_chars = [1] + [(tokens - 1) // (frames - 1)] * (frames - 1)
    return r


def test_due_time_arithmetic():
    # window [100, 110). A was due at 101 but the generator sent it at 101.5:
    # its TTFT runs from 101. B was due before the window and ends inside it:
    # its TTFT does not count, and of its tokens only the frame that came
    # inside (9 of 10, at 100.9). C fails: it takes the penalty. D ends after
    # the window: its frames at 109.3 (1 token) and 109.975 (9 of 28; it
    # says 37 characters for 28 tokens) count, the three later ones do not.
    a = req(101.0, 101.5, 102.0, 104.0, 104.1, 21, 4)
    b = req(99.0, 99.0, 99.2, 100.9, 101.0, 10, 2)
    c = req(105.0, 105.0, None, None, 105.1, 0, 0, ok=False)
    d = req(109.0, 109.0, 109.3, 112.0, 112.1, 28, 5)
    d.frame_chars = [1, 9, 9, 9, 9]
    out = metrics.end_to_end([a, b, c, d], 100.0, 110.0, penalty_ms=99e3)
    m, n = out["metrics"], out["counts"]
    assert close(m["out_tok_s"], (21 + 9 + 28 * 10 / 37) / 10.0)
    assert n["output_tokens_completed_in_window"] == 21 + 10  # whole requests
    assert n["requests_due_in_window"] == 3 and n["requests_failed"] == 1
    # TTFTs: a 1000 ms (from due, not from send), d 300 ms, c the penalty
    assert close(m["ttft_p50_ms"], 1000.0, 1e-6)
    assert m["ttft_p95_ms"] > 80e3
    # per-request TPOT: a (104 - 102) / 20 = 100 ms; d 2.7 s / 27 = 100 ms
    assert close(metrics.percentile([100.0, 100.0], 95), 100.0)
    assert m["tpot_p95_ms"] > 80e3 and close(m["tpot_p50_ms"], 100.0, 1e-6)
    assert n["samples_tpot"] == 3 and close(n["generator_late_ms_max"], 500.0, 1e-6)


OPEN_MIX = {  # an open loop, made up: no cell offers one yet
    "loop": "open", "rate_rps": 3.0, "burst": None,
    "prompt_tokens": {"dist": "lognormal", "median": 384, "sigma": 0.8, "min": 32, "max": 1920},
    "output_tokens": {"dist": "lognormal", "median": 96, "sigma": 0.6, "min": 16, "max": 320},
}


def test_same_work_every_seed():
    mix = OPEN_MIX
    a = Generator(mix, 1).block(40.0, "window")
    b = Generator(mix, 2**31 + 11).block(40.0, "window")
    assert len(a) == len(b) == round(mix["rate_rps"] * 40)
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in b)
    assert sorted(r.max_tokens for r in a) == sorted(r.max_tokens for r in b)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    assert all(0 <= r.due < 40.0 for r in a)
    assert len({r.prompt[:16] for r in a}) == len(a)  # distinct first words
    assert Generator(mix, 1).block(40.0, "window")[5].prompt == a[5].prompt
    # a burst of 8x for 2 s in every 10 s: mass 8*2 + 8 = 24 rate-seconds a period
    burst = {"factor": 8, "on_s": 2, "period_s": 10}
    assert close(warp(24.0, 1.0, burst), 10.0) and close(warp(8.0, 1.0, burst), 1.0)
    assert close(warp(20.0, 1.0, burst), 6.0)
    # a closed loop: the same set for every seed, dealt in rounds, so that a
    # window's part of it is the same work too (traffic.py:dealt_rounds)
    cmix = load_mix("decode-closed")
    closed = Generator(cmix, 3).client_streams()
    other = Generator(cmix, 2**31 + 11).client_streams()
    assert len(closed) == 32 and all(len(s) == 64 for s in closed)
    k = cmix["closed_round"]
    for what, dist in ((lambda r: r.max_tokens, cmix["output_tokens"]),
                       (lambda r: len(r.prompt), cmix["prompt_tokens"])):
        every = sorted(what(r) for s in closed for r in s)
        assert every == sorted(what(r) for s in other for r in s)
        assert every == sorted(quantile(dist, (j + 0.5) / 2048) for j in range(2048))
        assert [what(r) for r in closed[0]] != [what(r) for r in other[0]]
        for streams in (closed, other):
            for lo in range(0, 64, k):  # a round: one length of each stratum a client
                rnd_ = sorted(what(r) for s in streams for r in s[lo:lo + k])
                for s in streams:
                    ranks = sorted(rnd_.index(what(r)) // 32 for r in s[lo:lo + k])
                    assert all(abs(a - b) <= 1 for a, b in zip(ranks, range(k))), ranks
    assert [r.prompt for r in Generator(cmix, 3).client_streams()[7]] == \
        [r.prompt for r in closed[7]]
    assert len({r.rid for s in closed for r in s}) == 32 * 64


def test_a_closed_loop_request_is_due_when_the_last_one_ended():
    """What `ttft` is counted from in a closed loop: a client's next request
    is due, and sent, the moment the one before it ended."""
    import client

    streams = Generator(dict(load_mix("decode-closed"), clients=3), 11).client_streams()

    async def fake_send(session, base, model, temperature, req):
        req.t_send = time.monotonic()
        await asyncio.sleep(0.004)
        req.t_end = time.monotonic()
        req.ok = True
        return req

    async def drive():
        load = client.Load(None, "", "m", 0.0)
        load.start_clients(streams)
        await asyncio.sleep(0.3)
        load.stop_offering()
        assert await load.drain(5.0) == 0
        return load.sent

    real, client.send = client.send, fake_send
    try:
        by_client = {}  # request i of the set is client i % clients' (traffic.py)
        for r in asyncio.run(drive()):
            owner = int(r.rid.partition("c")[2].partition("r")[0]) % 3
            by_client.setdefault(owner, []).append(r)
    finally:
        client.send = real
    assert len(by_client) == 3 and all(len(v) >= 4 for v in by_client.values())
    for sent in by_client.values():
        for before, after in zip(sent, sent[1:]):
            assert before.t_end <= after.t_due <= after.t_send < before.t_end + 0.05


def test_layer_expressions():
    s0 = {"mixed_steps": 10, "split_steps": 10, "emit_tokens": 100,
          "dispatch_a_count": 5, "dispatch_b_count": 5, "dispatch_a_s": 1.0,
          "dispatch_b_s": 1.0, "compiled_variants": 38}
    s1 = {"mixed_steps": 40, "split_steps": 20, "emit_tokens": 1100,
          "dispatch_a_count": 55, "dispatch_b_count": 55, "dispatch_a_s": 2.0,
          "dispatch_b_s": 3.0, "compiled_variants": 38}
    s2 = dict(s1, device_memory=[{"peak_bytes_in_use": 15, "bytes_in_use": 8,
                                  "bytes_limit": 16}])
    ctx = {"stats0": s0, "stats1": s1, "stats2": s2, "trace": None, "seconds": 40.0,
           "end_to_end": {"ttft_p50_ms": 387.5, "tpot_p50_ms": 17.5},
           "client": {"tokens": 80, "frames": 10,
                      "output_tokens_completed_in_window": 4000}}
    got = layer_metrics.read_all(
        [{"name": n[:-5]} for n in sorted(os.listdir(os.path.join(HERE, "layer_metrics")))], ctx)
    assert close(got["sched.mixed_step_share"], 75.0)
    assert close(got["sched.tokens_per_dispatch"], 10.0)
    assert close(got["engine.dispatch_host_ms"], 30.0)
    assert got["engine.post_warmup_compiles"] == 0.0
    assert close(got["kv.hbm_resident_share"], 50.0)
    assert close(got["client.completed_tok_s"], 100.0)
    assert got["client.ttft_p50_ms.closed"] == 387.5
    assert got["client.tpot_p50_ms"] == 17.5
    assert close(got["client.tokens_per_frame"], 8.0)
    # no trace: the readers return nothing, and the metric is left out
    assert got["device.idle_share"] is None and got["kernel.pallas_busy_share"] is None
    try:
        layer_metrics.evaluate("__import__('os')", s0, s1, s2)
    except ValueError:
        pass
    else:
        raise AssertionError("an expression may only do arithmetic on counters")


def test_trace_reduction():
    t = reduce_trace.reduce(os.path.join(HERE, "fixtures", "synthetic_trace.xplane.pb"))
    assert close(t["window_s"], 0.016) and close(t["busy_s"], 0.009)
    assert close(t["kernel_s"], 0.002)
    ops = dict(t["device_ops"])
    assert close(ops["while"], 0.002)  # 6 ms less its children's 4
    assert close(ops["fusion"], 0.004)  # fusion.1 and fusion.2 add up
    assert "step 0" not in ops and "step" not in ops  # marker lines are not operations
    assert reduce_trace.op_kind("%reshape.1631 = bf16[1378,64,1024]{2,1,0} reshape(...)") == "reshape"
    gaps = dict(t["idle_gaps"])
    assert close(gaps["engine-step: plan_step"], 0.006)  # innermost cover
    assert close(gaps["engine-step: emit"], 0.001)
    ctx = {"trace": t}
    idle = layer_metrics.read("device.idle_share", ctx)
    assert close(idle, 43.75) and close(layer_metrics.read("kernel.pallas_busy_share", ctx), 200 / 9)
    # the directory holds what the tracing process wrote: it recorded 20 ms,
    # 4 of them before the first or after the last operation, and those are idle
    t = reduce_trace.reduce(os.path.join(HERE, "fixtures"))
    assert close(t["window_s"], 0.020) and close(t["first_to_last_operation_s"], 0.016)
    assert close(layer_metrics.read("device.idle_share", {"trace": t}), 55.0)
    assert close(dict(t["idle_gaps"])["trace edges: before the first or after the last operation"], 0.004)


def test_references_against_the_program():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.models import llama, moe
    from dynamo_tpu.ops.kv_quant import alloc_kv_store
    from references import llama as ref_llama
    from references import moe as ref_moe

    T, PAGE = 48, 16
    for model, ref, cfg in (
        (llama, ref_llama, llama.LlamaConfig.tiny(dtype=jnp.float32)),
        (moe, ref_moe, moe.MoeConfig.tiny_moe(dtype=jnp.float32, capacity_factor=2.0)),
    ):
        # capacity_factor = experts / experts per token (4 / 2 at tiny-moe,
        # 4.0 at Mixtral's 8 / 2): the least at which no token can drop
        params = model.init_params(cfg, jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(1), (T,), 5, cfg.vocab_size)
        with jax.default_matmul_precision("highest"):
            # (a routed family's reference also gives its choices and their
            # deficits: test_a_forced_family_is_judged_by_the_choices_it_is_handed)
            want, margins, *_ = ref.logits(params, cfg, toks, n_last=T)
            pages = T // PAGE
            kv = alloc_kv_store(cfg.num_layers, pages + 2, PAGE, cfg.num_kv_heads,
                                cfg.head_dim, cfg.dtype, "none")
            table = jnp.arange(1, pages + 2, dtype=jnp.int32)
            # the serving path: one prefill chunk of 32, then 16 decode steps
            # through the paged cache
            logits, kv_k, kv_v = model.prefill_forward(
                params, cfg, toks[:32], jnp.arange(32), kv, kv, table,
                jnp.int32(0), last_idx=jnp.int32(31))
            got = [np.asarray(logits).reshape(-1)]
            for t in range(32, T):
                lg, kv_k, kv_v = model.decode_forward(
                    params, cfg, toks[t:t + 1], jnp.array([t]), kv_k, kv_v,
                    table[None, :], jnp.array([t + 1]))
                got.append(np.asarray(lg)[0])
        want = np.asarray(want)[31:]
        err = np.abs(np.stack(got) - want).max() / want.std()
        assert err < 1e-3, f"{model.__name__}: {err:.2e} deviations from the reference"
        if margins is not None:
            assert np.asarray(margins).shape == (T,) and (np.asarray(margins) >= 0).all()
        print(f"  {model.__name__}: serving path within {err:.1e} deviations "
              f"of references/{ref.__name__.split('.')[-1]}.py over {T - 31} positions")


def made_up_run(margins_of_length):
    """Four requests' made-up reference logits, the tokens they put first as
    the served ones, log-probabilities off by bf16's own rounding, and
    routing margins from `margins_of_length(n)`."""
    import numpy as np

    import reference

    rng = np.random.default_rng(0)
    lens = {"0.shortest": 70, "1.median": 140, "2.crosses_page": 700, "3.random": 390}
    cases, rows_of, served_of, margins = {}, {}, {}, {}
    for name, n in lens.items():
        rows = rng.normal(size=(n, 512)).astype(np.float32)
        served = rows.argmax(-1)
        lp = rows.max(-1) - reference.log_normalizer(rows)
        lp = lp + rng.normal(scale=0.012, size=n) * rows.std(-1)  # bf16's own rounding
        cases[name] = {"prompt_ids": [1] * 100}
        rows_of[name], served_of[name] = rows, (served, lp)
        margins[name] = margins_of_length(rng, n)
    return cases, rows_of, served_of, margins


def test_judge_holds_each_request():
    """reference.judge on made-up logits: a sound run agrees; ONE request of
    four served 0.2 deviations off at every position, which the per-position
    tolerance (0.25) and a routed family's pooled means let through, does not:
    the worst request's median (routed) or mean (dense) catches it."""
    import reference

    # some 29% of the margins under the epsilon
    cases, rows_of, served_of, margins = made_up_run(
        lambda rng, n: rng.uniform(0.0, 0.35, size=n))
    lens = {name: len(rows) for name, rows in rows_of.items()}
    for routed, number in ((True, "logprob_gap_request_median_sigmas"),
                           (False, "logprob_gap_mean_sigmas")):
        margins_of = margins if routed else dict.fromkeys(lens)
        sound = reference.judge(cases, rows_of, margins_of, served_of)
        assert sound["agrees"], sound["why_not"]
        ids, lp = served_of["1.median"]
        off = dict(served_of)
        off["1.median"] = (ids, lp - 0.2 * rows_of["1.median"].std(-1))
        broken = reference.judge(cases, rows_of, margins_of, off)
        over = [k for k, (v, lim) in broken["compared"].items() if v > lim]
        assert not broken["agrees"] and over == [number], (routed, broken["compared"])
        assert broken["compared"][number][0] > 0.19


ROUTED_TODAY = {  # name -> limit, in the order `checked` prints them (PR 34)
    "positions_outside": 3, "logprob_gap_pooled_mean_sigmas": 0.035,
    "logprob_gap_pooled_mean_all_sigmas": 0.07,
    "logprob_gap_request_median_sigmas": 0.03, "router_left_out_share": 0.45}
DENSE_TODAY = {"positions_outside": 0, "token_gap_sigmas": 0.3,
               "logprob_gap_sigmas": 0.25, "logprob_gap_mean_sigmas": 0.06}


def test_judge_limits_are_data():
    """Without a `judge` key a family is judged as before PR 36, name for name
    and limit for limit; with one, each number is held to the override, and a
    limit that is no number is refused; the dense family takes none."""
    import reference

    cases, rows_of, served_of, margins = made_up_run(
        lambda rng, n: rng.uniform(0.0, 0.35, size=n))
    plain = reference.judge(cases, rows_of, margins, served_of)
    assert [(k, lim) for k, (_, lim) in plain["compared"].items()] == list(ROUTED_TODAY.items())
    assert plain["router_margin_epsilon"] == 0.1
    dense = reference.judge(cases, rows_of, dict.fromkeys(cases), served_of)
    assert [(k, lim) for k, (_, lim) in dense["compared"].items()] == list(DENSE_TODAY.items())
    for name, (value, _) in plain["compared"].items():
        if not value:
            continue  # a count of 0 passes any limit
        held = reference.judge(cases, rows_of, margins, served_of, {name: value / 2})
        over = [k for k, (v, lim) in held["compared"].items() if v > lim]
        assert not held["agrees"] and over == [name] and held["compared"][name][1] == value / 2
        assert reference.judge(cases, rows_of, margins, served_of, {name: value})["agrees"]
    for bad, margins_of in (({"token_gap_sigmas": 1.0}, margins),
                            ({"logprob_gap_pooled_mean_all_sigmas": None}, margins),
                            ({"positions_outside": 9}, dict.fromkeys(cases))):
        try:
            reference.judge(cases, rows_of, margins_of, served_of, bad)
        except ValueError:
            continue
        raise AssertionError(f"judge took {bad}")


def test_many_experts_are_judged_with_their_flips_in():
    """Made-up normal router logits at 64 experts, 4 a token, 8 routed layers:
    at the default epsilon all but nothing is kept, so no program can pass; at
    an epsilon of 0 every position is judged. And the shares that order
    statistics give (ISSUE 36's table, which the README repeats)."""
    import numpy as np

    import reference

    cases, rows_of, served_of, margins = made_up_run(
        lambda rng, n: reference.router_margins(rng.normal(size=(8, n, 64)), 4))
    default = reference.judge(cases, rows_of, margins, served_of)
    assert default["compared"]["router_left_out_share"][0] > 0.99 and not default["agrees"]
    zero = reference.judge(cases, rows_of, margins, served_of,
                           {"router_margin_epsilon": 0, "router_left_out_share": 0})
    got = {k: v for k, (v, _) in zero["compared"].items()}
    assert zero["agrees"] and got["router_left_out_share"] == 0
    assert got["logprob_gap_pooled_mean_sigmas"] == got["logprob_gap_pooled_mean_all_sigmas"]
    dlp = {name: np.abs(lp - (rows_of[name].max(-1) - reference.log_normalizer(rows_of[name])))
           / rows_of[name].std(-1) for name, (_, lp) in served_of.items()}
    assert close(got["logprob_gap_request_median_sigmas"],
                 max(np.median(d) for d in dlp.values() if len(d) >= 128), 1e-6)
    for geometry, share in (((8, 2, 2, 0.1), 0.321), ((64, 4, 4, 0.1), 0.953),
                            ((64, 4, 8, 0.1), 0.998), ((64, 4, 8, 0.01), 0.458),
                            ((64, 4, 8, 0.0), 0.0)):
        assert abs(reference.order_statistics_share(*geometry) - share) < 0.015, geometry


def pick_checked_before(finished: list, seed: int) -> list:
    """run.py:pick_checked as it stood before a sizing run's `count` (PR 38),
    word for word: what every run is still judged by."""
    import random

    fit = [r for r in finished if len(r.prompt) + r.max_tokens <= 2048]
    by_len = sorted(fit, key=lambda r: (len(r.prompt) + r.max_tokens, r.rid))
    crossing = [r for r in by_len if len(r.prompt) % 64 + r.max_tokens > 64]
    picks = [
        ("shortest", by_len[0]),
        ("median", by_len[len(by_len) // 2]),
        ("crosses_page", (crossing or by_len)[-1]),
        ("random", random.Random(f"{seed}:checked").choice(by_len)),
    ]
    return [{"why": why, "prompt": r.prompt, "max_tokens": r.max_tokens}
            for why, r in picks]


def test_the_picks_are_todays_four_and_nested_beyond():
    """A run's picks are the four of before, to the letter; a sizing run's
    twelve (`--controls-only --requests`) begin with those, the rest are further requests
    that differ from every other pick, ten or more of the twelve have the 128
    served tokens from which the judge holds a request's median, and the first
    M of a seed's picks are its picks at M. A pool without long requests gives
    what it has; a pool too small for the count fails the run."""
    import run

    mix = load_mix("decode-closed")
    for seed in (1811111117, 2**31 + 11):
        pool = [r for stream in Generator(mix, seed).client_streams()
                for r in stream[:run.CONTROLS_ONLY_PER_CLIENT]]
        four = run.pick_checked(pool, seed)
        assert four == pick_checked_before(pool, seed) and len(four) == 4
        twelve = run.pick_checked(pool, seed, 12)
        assert twelve[:4] == four and [p["why"] for p in twelve[4:]] == ["further"] * 8
        assert len({p["prompt"] for p in twelve[3:]}) == 9  # each a request of its own,
        assert not {p["prompt"] for p in twelve[4:]} & {p["prompt"] for p in four}  # and new
        assert sum(p["max_tokens"] >= 128 for p in twelve) >= 10
        for m in range(4, 17):
            assert run.pick_checked(pool, seed, m) == run.pick_checked(pool, seed, 16)[:m]
    short = [r for r in pool if r.max_tokens < 128][:9]
    assert len(run.pick_checked(short, 3, 9)) == 9
    try:
        run.pick_checked(short, 3, 10)
    except run.BenchFailure as e:
        assert "9 finished requests" in str(e)
    else:
        raise AssertionError("ten picks from nine requests")


def told(sound, control, kind="served") -> dict:
    """An entry of `judge_readings`: twelve runs a side, made up."""
    return {"sound": {"lowest": sound[0], "highest": sound[1], "runs": 12, "kind": kind},
            "control_int8": {"lowest": control[0], "highest": control[1], "runs": 12},
            "reason": "made up by selftest.py"}


def planted_judge() -> dict:
    """A `judge` and `judge_readings` that keep the rule, made up."""
    share = {"reason": "made up by selftest.py", "order_statistics_share": 0.0}
    return {
        "judge": {"router_margin_epsilon": 0, "router_left_out_share": 0,
                  "positions_outside": 12, "logprob_gap_pooled_mean_sigmas": 0.05,
                  "logprob_gap_pooled_mean_all_sigmas": 0.05,
                  "logprob_gap_request_median_sigmas": 0.012},
        "judge_readings": {
            "router_margin_epsilon": share, "router_left_out_share": dict(share),
            "positions_outside": told((2, 6), (25, 60)),
            "logprob_gap_pooled_mean_sigmas": told((0.02, 0.03), (0.07, 0.09)),
            "logprob_gap_pooled_mean_all_sigmas": told((0.02, 0.03), (0.07, 0.09)),
            "logprob_gap_request_median_sigmas": told((0.004, 0.006), (0.02, 0.03))}}


def planted_forced(sound=(0.02, 0.05), control=(0.16, 0.19)) -> dict:
    """The same, for a family whose routing is judged forced: the largest
    deficit as a judged number more, with the readings that sound runs and the
    int8 control's gave, made up."""
    planted = planted_judge()
    planted["judge"].update(  # a limit past the geometric mean, under 0.8 of the control's
        router_choice_deficit_max_sigmas=0.75 * control[0])
    planted["judge_readings"].update(
        router_choice_deficit_max_sigmas=told(sound, control, "bf16_control"))
    return {"family": "moe", "judge_routing": "forced", **planted}


def test_files_check_holds_limits_to_their_readings():
    """files_check.py ends with exit code 2 on each planted file, and says
    why: an override without readings; a limit under the sound runs' highest,
    short of the geometric mean of the two readings, above the control's
    lowest, just under it; fewer runs of the control than sound ones; a set in
    which no control reads three times the sound run; a limit that is no
    number; a share stated, or held, under what order statistics leave out at
    the geometry of the file's OWN keys, with or without a `judge`. The sound
    file passes. `judge_routing` and a forced family's limit likewise.
    A router's width is the published count where the experts key is listed
    as reduced. The fixtures pass as they stand, forced and judged over the
    top tokens; the second judged over the first token alone (PR 38's
    readings, kept in the file) and the first judged FREE, by the chip's
    readings at 64 experts and 4 a token (PR 36), are refused."""
    out = os.path.join(os.path.dirname(HERE), "chiprun_out", "selftest_planted")
    shutil.rmtree(out, ignore_errors=True)
    os.makedirs(out)

    def check(path: str):
        p = subprocess.run([sys.executable, os.path.join(HERE, "files_check.py"), path],
                           capture_output=True)
        return p.returncode, p.stderr.decode()

    def plant(name: str, spoil, *says) -> None:
        """Refused, saying each of `says`; or, with none, passed."""
        cfg = {"num_local_experts": 64, "num_experts_per_tok": 4, "num_hidden_layers": 8,
               **planted_judge()}
        spoil(cfg)
        path = os.path.join(out, name + ".json")
        with open(path, "w") as f:
            json.dump(cfg, f)
        rc, err = check(path)
        assert rc == (2 if says else 0) and all(x in err for x in says), (name, rc, err)

    def limit(key, value):
        return lambda cfg: cfg["judge"].update({key: value})

    def each_number(sound, control):
        def spoil(cfg):
            for key, told in cfg["judge_readings"].items():
                if "sound" in told:
                    told["sound"].update(lowest=sound[0], highest=sound[1])
                    told["control_int8"].update(lowest=control[0], highest=control[1])
                    cfg["judge"][key] = 0.2
        return spoil

    def default_epsilon(stated):
        def spoil(cfg):
            cfg["judge"].update(router_margin_epsilon=0.1, router_left_out_share=0.45)
            cfg["judge_readings"]["router_margin_epsilon"]["order_statistics_share"] = stated
            cfg["judge_readings"]["router_left_out_share"]["order_statistics_share"] = stated
        return spoil

    mean = "logprob_gap_pooled_mean_sigmas"
    plant("sound", lambda cfg: None)
    plant("no_readings", lambda cfg: cfg["judge_readings"].pop(mean), "no entry for")
    plant("no_reason", lambda cfg: cfg["judge_readings"][mean].pop("reason"), "`reason`")
    plant("under_the_sound_runs", limit(mean, 0.025), "is not above the highest sound")
    plant("short_of_the_geometric_mean",  # sqrt(0.03 x 0.07) = 0.0458
          limit(mean, 0.04), "the geometric mean of 0.03 and 0.07")
    plant("above_the_control", limit(mean, 0.08), "is over 0.8 of the int8 control's")
    plant("just_under_the_control", limit(mean, 0.0699), "is over 0.8 of the int8 control's")
    plant("near_the_control", limit(mean, 0.056))  # 0.8 x 0.07: the most that passes
    plant("fewer_control_runs",
          lambda cfg: cfg["judge_readings"][mean]["control_int8"].update(runs=6),
          "6 runs of the int8 control for 12 sound ones")
    plant("not_three_times", each_number((0.1, 0.11), (0.3, 0.4)),
          "judge_readings: in no number does the int8 control read 3.0 times")
    plant("three_times", each_number((0.1, 0.11), (0.34, 0.4)))
    plant("not_compared", limit(mean, None), "each to a number")
    plant("a_dense_number", limit("token_gap_sigmas", 1.0), "judge may set")
    # 64 experts, 4 a token, 8 routed layers at the default epsilon: 0.998
    plant("share_by_arithmetic", default_epsilon(0.998), "order statistics leave out 0.99")
    plant("share_understated", default_epsilon(0.3), "order statistics leave out 0.99")
    plant("share_misstated", lambda cfg: cfg["judge_readings"]["router_left_out_share"]
          .update(order_statistics_share=0.3), "order_statistics_share is 0.3 where")
    plant("share_with_no_judge", lambda cfg: (cfg.pop("judge"), cfg.pop("judge_readings")),
          "order statistics leave out 0.99")
    plant("dense_layers_are_not_routed",  # 64 / 4 / 1 at 0.1 reads 0.53
          lambda cfg: (default_epsilon(0.53)(cfg), cfg.update(num_dense_layers=7),
                       cfg["judge"].update(router_left_out_share=0.6)))
    plant("no_experts", lambda cfg: cfg.pop("num_local_experts"), "has `judge` and none of")

    # `judge_routing`: what it may say, what "forced" asks of the file, and the
    # rule for its limit, the same as for every judged number
    def forced(spoil):
        def planted(cfg):
            cfg.update(planted_forced())
            spoil(cfg)
        return planted

    def reading(key, side, **values):
        return forced(lambda cfg: cfg["judge_readings"][key][side].update(**values))

    deficit = "router_choice_deficit_max_sigmas"
    plant("forced", forced(lambda cfg: None))
    plant("routing_unknown", lambda cfg: cfg.update(judge_routing="pinned"),
          "judge_routing is one of")
    plant("free_said", lambda cfg: cfg.update(judge_routing="free"))
    plant("free_with_a_choice_limit", limit(deficit, 0.12), "its routing is judged free")
    plant("forced_with_near_ties_left_out",
          forced(lambda cfg: (default_epsilon(0.998)(cfg),
                              cfg["judge"].update(router_left_out_share=1.0))),
          "keeps every position")
    plant("forced_without_its_limit", forced(lambda cfg: cfg["judge"].pop(deficit)),
          "keeps every position")
    plant("forced_without_readings", forced(lambda cfg: cfg["judge_readings"].pop(deficit)),
          "no entry for " + deficit)
    plant("forced_dense_reference", forced(lambda cfg: cfg.update(family="llama")),
          "references/llama.py:logits takes no `forced`")
    plant("deficit_limit_under_a_sound_run", reading(deficit, "sound", highest=0.13),
          "router_choice_deficit_max_sigmas: limit 0.12 is not above the highest sound")
    plant("deficit_is_no_budget", forced(limit(deficit, 0.14)),
          "router_choice_deficit_max_sigmas: limit 0.14 is over 0.8 of the int8 control's")
    plant("forced_fewer_control_runs", reading(deficit, "control_int8", runs=6),
          "6 runs of the int8 control for 12 sound ones")

    # a router's width is the PUBLISHED count where the file lists its experts
    # key in `reduced` (the key then counts the experts held): 8 held of 16
    # leave out 0.446 at the default epsilon, of 64 0.579, over the share's
    # limit; held alone (8 / 2 / 2, the Mixtral cell's) they would read 0.321
    def held_of(width, **more):
        def spoil(cfg):
            cfg.pop("judge"), cfg.pop("judge_readings")
            cfg.update(num_local_experts=8, num_experts_per_tok=2, num_hidden_layers=2,
                       reduced=["num_local_experts"], published={"num_local_experts": width},
                       **more)
        return spoil

    plant("eight_held_of_sixteen", held_of(16))
    plant("eight_held_of_sixteen_from_the_eighth", held_of(16, first_expert_held=8))
    plant("eight_held_of_sixty_four", held_of(64), "order statistics leave out 0.57",
          "at (64, 2, 2) experts")
    plant("eight_held_of_four", held_of(4), "it counts the experts held")
    plant("held_past_the_width", held_of(16, first_expert_held=9), "it counts the experts held")
    plant("no_published_width", lambda cfg: (held_of(16)(cfg), cfg.pop("published")),
          "it counts the experts held")
    plant("first_held_and_no_share", lambda cfg: cfg.update(first_expert_held=0),
          "and does not list")

    shutil.rmtree(out)
    # the fixtures as they stand keep the rule, forced and with the head judged
    # over the top tokens (PR 43): at 4, at 8 and at 10 experts a token
    fixtures = os.path.join(HERE, "fixtures")
    for name in ("many-experts", "many-experts-k8", "many-experts-k10"):
        rc, err = check(os.path.join(fixtures, name + ".json"))
        assert rc == 0, (name, err)
    # ... where the second, judged over the FIRST token alone on two dozen seeds
    # (PR 38's readings, which stay in the file as the finding they were), did
    # not: the worst request's median 1.54 times apart, less than the two sides'
    # room takes. Laid over the file again, they are refused as they were
    with open(os.path.join(fixtures, "many-experts-k8.json")) as f:
        k8 = json.load(f)
    own = k8["first_token_readings"]["judge_readings"]
    os.makedirs(out)
    with open(os.path.join(out, "many-experts-k8-first-token.json"), "w") as f:
        json.dump(dict(k8, judge=k8["first_token_readings"]["judge"], judge_readings=own), f)
    rc, err = check(os.path.join(out, "many-experts-k8-first-token.json"))
    shutil.rmtree(out)
    assert rc == 2 and "logprob_gap_request_median_sigmas: limit" in err and all(
        x not in err for x in ("logprob_gap_pooled_mean", "router_choice_deficit_max_sigmas",
                               "in no number")), err
    # ... and MORE requests a run made no room either (PR 39's sizing run,
    # fixtures/many-experts-k8.requests-readings.json: the same 24 seeds read
    # at 4, 8, 12 and 16 requests a run, first token alone; at four they are
    # those readings): the largest deficit reads three times at every count, and
    # the pooled mean never the rule's 1.5625 with a served program's 23% over
    # the bf16 control on top
    with open(os.path.join(fixtures, "many-experts-k8.requests-readings.json")) as f:
        by_count = json.load(f)["by_requests_a_run"]
    assert sorted(by_count, key=int) == ["4", "8", "12", "16"]
    for number, entry in by_count["4"].items():
        for side in ("sound", "control_int8") if "sound" in own[number] else ():
            for end in ("lowest", "highest"):
                assert close(entry[side][end], own[number][side][end], 1e-9), (number, side)
    for count, read in by_count.items():
        times = {number: entry["control_int8"]["lowest"] / entry["sound"]["highest"]
                 for number, entry in read.items() if entry["sound"]["highest"]}
        assert times["router_choice_deficit_max_sigmas"] >= 3.0, (count, times)
        assert times["logprob_gap_pooled_mean_sigmas"] < 1.23 / 0.8 ** 2, (count, times)
    # ... and the first fixture judged FREE, with PR 36's thirty seeds' readings
    # laid over it, does not: no limit stands between the controls' counts nor
    # between their medians, and nothing reads three times
    os.makedirs(out)
    with open(os.path.join(HERE, "fixtures", "many-experts.json")) as f:
        free = json.load(f)
    with open(os.path.join(HERE, "fixtures", "many-experts.free-readings.json")) as f:
        free.update({k: v for k, v in json.load(f).items() if k.startswith("judge")})
    del free["judge_routing"]
    with open(os.path.join(out, "many-experts-free.json"), "w") as f:
        json.dump(free, f)
    rc, err = check(os.path.join(out, "many-experts-free.json"))
    shutil.rmtree(out)
    assert rc == 2 and all(x in err for x in (
        "positions_outside: limit", "logprob_gap_request_median_sigmas: limit",
        "is over 0.8 of the int8 control's lowest reading",
        "in no number does the int8 control read 3.0 times")) \
        and "logprob_gap_pooled_mean" not in err, err


def test_a_forced_family_is_judged_by_the_choices_it_is_handed():
    """The first fixture at its rehearsal sizes (64 experts, 4 a token, 5
    layers, float32 on this CPU), judged forced on made-up prompts and tokens.
    Handed its OWN choices the reference computes what it computes free, and
    every deficit is 0. Handed choices in which, at 2% of the input positions,
    one expert of one layer is swapped for another that it did not choose (at
    64 experts: far down the ranking, as a rule), it computes THAT, so a
    program that routed so and computed rightly from there agrees in every
    logit: the number of the choice alone says not correct, the largest
    deficit. And cases of a forced configuration that carry no
    `routed_experts` are not judged."""
    import jax
    import numpy as np

    import reference

    path = os.path.join(HERE, "fixtures", "many-experts.json")
    cfg, weights, ref, limits, forced = reference.load_model(path, rehearsal=True)
    assert forced and "router_choice_deficit_max_sigmas" in limits
    params = weights()
    rng = np.random.default_rng(5)
    cases = {f"{i}.made_up": {
        "prompt_ids": rng.integers(3, cfg.vocab_size, size=p).tolist(),
        "served_ids": rng.integers(3, cfg.vocab_size, size=n).tolist()}
        for i, (p, n) in enumerate(((20, 30), (33, 140), (64, 150), (41, 77)))}
    with jax.default_matmul_precision("highest"):
        rows, margins, routing = reference.forward(ref, cfg, params, cases)
        own = {name: r["chosen"].transpose(1, 0, 2) for name, r in routing.items()}
        rows2, _, routing2 = reference.forward(ref, cfg, params, cases, own)
    layers, k = cfg.num_layers, cfg.num_experts_per_tok
    for name, c in cases.items():
        inputs = len(c["prompt_ids"]) + len(c["served_ids"]) - 1
        assert routing[name]["chosen"].shape == (layers, inputs, k)
        assert routing[name]["deficits"].shape == (layers, inputs)
        assert not routing[name]["deficits"].any() and not routing2[name]["deficits"].any()
        assert (routing2[name]["chosen"] == routing[name]["chosen"]).all()
        assert np.abs(rows2[name] - rows[name]).max() < 1e-4 * rows[name].std()
    served = reference.control_choice(rows, reference.TOP_TOKENS)
    sound = reference.judge(cases, rows, margins, served, limits,
                            {n: r["deficits"] for n, r in routing2.items()})
    assert sound["agrees"] and sound["compared"]["router_choice_deficit_max_sigmas"][0] == 0.0
    swapped, swaps = {}, 0
    for name, rows_ in own.items():
        rows_ = rows_.copy()
        for at in rng.choice(len(rows_), size=max(len(rows_) // 50, 1), replace=False):
            layer = rng.integers(layers)
            left = sorted(set(range(cfg.num_experts)) - set(rows_[at, layer].tolist()))
            rows_[at, layer, rng.integers(k)] = rng.choice(left)
            swaps += 1
        swapped[name] = rows_
    with jax.default_matmul_precision("highest"):
        rows3, margins3, routing3 = reference.forward(ref, cfg, params, cases, swapped)
    wrong = reference.judge(cases, rows3, margins3,
                            reference.control_choice(rows3, reference.TOP_TOKENS), limits,
                            {n: r["deficits"] for n, r in routing3.items()})
    over = [key for key, (v, lim) in wrong["compared"].items() if v > lim]
    assert not wrong["agrees"] and over == ["router_choice_deficit_max_sigmas"], \
        wrong["compared"]
    assert wrong["compared"]["router_choice_deficit_max_sigmas"][0] > 1.0
    print(f"  {swaps} swaps of {sum(len(r) for r in own.values())} input positions: the "
          f"largest deficit {wrong['compared']['router_choice_deficit_max_sigmas'][0]:.2f} "
          f"(limit {limits['router_choice_deficit_max_sigmas']})")
    # limits without it, or deficits for a free family: refused, not guessed
    for bad in ({k_: v for k_, v in limits.items()
                 if k_ != "router_choice_deficit_max_sigmas"},
                dict(limits, router_margin_epsilon=0.1)):
        try:
            reference.judge(cases, rows, margins, served, bad,
                            {n: r["deficits"] for n, r in routing.items()})
        except ValueError:
            continue
        raise AssertionError(f"judge took {bad} under forced routing")
    out = os.path.join(os.path.dirname(HERE), "chiprun_out", "selftest_forced")
    shutil.rmtree(out, ignore_errors=True)
    os.makedirs(out)
    # ... nor cases that carry the choices and no top tokens: never over one token
    for more, says in (({}, "no `routed_experts`"),
                       ({"routed_experts": [[[0] * k] * layers]}, "no `served_top_ids`")):
        with open(os.path.join(out, "cases.json"), "w") as f:
            json.dump({"config_file": path, "rehearsal": True, "controls": [],
                       "cases": {n: dict(c, served_logprobs=[0.0] * len(c["served_ids"]), **more)
                                 for n, c in cases.items()}}, f)
        try:
            reference.run(os.path.join(out, "cases.json"))
        except ValueError as e:
            assert says in str(e), (says, str(e))
        else:
            raise AssertionError(f"a forced configuration was judged with {says}")
    # ... and cases that carry both, as a program's `reference_cases.json` will
    # (here the reference's own choices and its log-probabilities of the made-up
    # tokens and of the tokens it puts first), are judged through `run`: every
    # log-probability number and every deficit 0, over TOP_TOKENS tokens
    def own_logprobs(n, c):
        ids = np.asarray(c["served_ids"])
        return (rows[n][np.arange(len(ids)), ids] - reference.log_normalizer(rows[n])).tolist()

    with open(os.path.join(out, "cases.json"), "w") as f:
        json.dump({"config_file": path, "rehearsal": True, "controls": [], "cases": {
            n: dict(c, served_logprobs=own_logprobs(n, c),
                    served_top_ids=served[n][2][0].tolist(),
                    served_top_logprobs=served[n][2][1].tolist(),
                    routed_experts=own[n].tolist()) for n, c in cases.items()}}, f)
    whole = reference.run(os.path.join(out, "cases.json"))
    # (made-up tokens are not the ones the model puts first: the count of
    # positions outside, which reads the served token's logit, says so alone)
    assert [k for k, (v, _) in whole["compared"].items() if v] == ["positions_outside"], \
        whole["compared"]
    assert all(r["top_tokens"] == reference.TOP_TOKENS for r in whole["cases"].values())
    shutil.rmtree(out)


def test_a_forced_family_is_judged_over_the_top_tokens():
    """reference.judge, forced, on made-up logits: a position's number is the
    mean over the TOP_TOKENS tokens handed over of |their log-probability - the
    reference's| in deviations of the position's logits, and the two pooled
    means and the worst request's median are read from those; the count of
    positions outside stays on the first token. ONE request of four 0.2
    deviations off at every top token fails by the median alone; top tokens
    missing, or of another count than TOP_TOKENS, are refused, not judged over
    one token. A family judged free reads what it read, whatever comes third."""
    import numpy as np

    import reference

    N = reference.TOP_TOKENS
    cases, rows_of, served_of, margins = made_up_run(lambda rng, n: rng.uniform(0.2, 1.0, size=n))
    rng = np.random.default_rng(1)
    limits = {"router_margin_epsilon": 0, "router_left_out_share": 0,
              "router_choice_deficit_max_sigmas": 0.05}
    deficits = {name: np.zeros((2, len(rows) + 99)) for name, rows in rows_of.items()}
    exact = reference.control_choice(rows_of, N)
    tops, by_hand = {}, {}
    for name, (ids, lp, (top_ids, top_lp)) in exact.items():
        assert (top_ids[:, 0] == ids).all() and np.allclose(top_lp[:, 0], lp)
        assert (np.diff(top_lp, axis=-1) <= 0).all() and top_ids.shape == (len(ids), N)
        off = rng.normal(scale=0.012, size=top_lp.shape)  # bf16's own rounding, a token
        tops[name] = (ids, served_of[name][1], (top_ids, top_lp + off * rows_of[name].std(-1)[:, None]))
        by_hand[name] = np.abs(off).mean(-1)
    sound = reference.judge(cases, rows_of, margins, tops, limits, deficits)
    got = {k: v for k, (v, _) in sound["compared"].items()}
    assert sound["agrees"], sound["why_not"]
    assert close(got["logprob_gap_pooled_mean_sigmas"],
                 np.concatenate(list(by_hand.values())).mean(), 1e-5)
    assert got["logprob_gap_pooled_mean_all_sigmas"] == got["logprob_gap_pooled_mean_sigmas"]
    assert close(got["logprob_gap_request_median_sigmas"],
                 max(np.median(d) for d in by_hand.values() if len(d) >= 128), 1e-5)
    one = sound["cases"]["1.median"]
    first = np.abs(served_of["1.median"][1] - exact["1.median"][1]) / rows_of["1.median"].std(-1)
    assert one["top_tokens"] == N and close(one["logprob_diff_sigmas_first_token_mean"],
                                            first.mean(), 1e-5)
    assert close(one["logprob_diff_sigmas_max"], first.max(), 1e-5)  # the first token's
    # the same cases judged FREE read the first token alone, top tokens or none
    free = reference.judge(cases, rows_of, margins, tops)
    assert free["compared"] == reference.judge(cases, rows_of, margins, served_of)["compared"]
    ids, lp, (top_ids, top_lp) = tops["1.median"]
    shifted = dict(tops)
    shifted["1.median"] = (ids, lp - 0.2 * rows_of["1.median"].std(-1),
                           (top_ids, top_lp - 0.2 * rows_of["1.median"].std(-1)[:, None]))
    broken = reference.judge(cases, rows_of, margins, shifted, limits, deficits)
    over = [k for k, (v, lim) in broken["compared"].items() if v > lim]
    assert not broken["agrees"] and over == ["logprob_gap_request_median_sigmas"], broken["compared"]
    assert broken["compared"]["logprob_gap_request_median_sigmas"][0] > 0.19
    # 0.3 off at the first token alone: outside at every position of the
    # request (the count reads the first token), a fifth of it in the mean
    first_only = dict(tops)
    first_only["1.median"] = (ids, lp - 0.3 * rows_of["1.median"].std(-1), (top_ids, top_lp))
    counted = reference.judge(cases, rows_of, margins, first_only, limits, deficits)
    assert counted["compared"]["positions_outside"][0] == len(ids)
    assert counted["compared"]["logprob_gap_request_median_sigmas"][0] < 0.02
    for bad in (served_of, {n: (i, l, (t[0][:, :N - 1], t[1][:, :N - 1]))
                            for n, (i, l, t) in tops.items()}):
        try:
            reference.judge(cases, rows_of, margins, bad, limits, deficits)
        except ValueError as e:
            assert "top" in str(e)
        else:
            raise AssertionError("a forced family was judged without its top tokens")


def test_the_client_holds_a_reply_to_the_wire_contract():
    """client.routed_rows: one row an input position (prompt + served[:-1];
    a row for the last served token is dropped), each [routed layers][experts
    a token] of distinct ids in range. Rows missing altogether (a worker that
    does not know the annotation), too few, a layer short, an expert twice or
    out of range: each fails with its reason, and run.py then fails the run;
    nothing falls back to free routing."""
    import client

    geometry = (64, 4, 5)  # experts, experts a token, routed layers
    row = [[1, 7, 63, 0]] * 5
    rows = [row] * 9
    assert client.routed_rows(list(rows), 9, geometry) == rows
    assert client.routed_rows(rows + [row], 9, geometry) == rows  # the last token's row
    for bad, says in (
            ([], "0 rows"), (rows[:8], "8 rows"), (rows + [row, row], "11 rows"),
            (rows[:4] + [row[:4]] + rows[5:], "row 4 of `routed_experts` holds 4 layers"),
            (rows[:8] + [[[1, 7, 63, 0]] * 4 + [[1, 7, 7, 0]]], "row 8, layer 4"),
            (rows[:8] + [[[1, 7, 64, 0]] + row[1:]], "row 8, layer 0"),
            (rows[:8] + [[[1, 7, -1, 0]] + row[1:]], "row 8, layer 0"),
            (rows[:8] + [[[1, 7, 3]] + row[1:]], "not 4 distinct expert ids under 64"),
            (rows[:8] + [[[1, 7, 3, 2.0]] + row[1:]], "row 8, layer 0"),
            (rows[:8] + [None], "row 8")):
        try:
            client.routed_rows(bad, 9, geometry)
        except ValueError as e:
            assert says in str(e), (says, str(e))
        else:
            raise AssertionError(f"routed_rows took a reply with {says}")


def test_the_client_holds_a_reply_to_the_top_token_contract():
    """client.top_tokens: one entry a served token, each TOP_TOKENS distinct
    ids in range with the served token among them and as many finite
    log-probabilities. Entries missing, one short, a duplicate, the served
    token not among them, an id out of range, a log-probability that is no
    finite number: each fails with its reason (run.py then fails the run)."""
    import client
    from reference import TOP_TOKENS as N

    served = [9, 4, 300]
    entry = [{"ids": [t, *range(100, 100 + N - 1)], "logprobs": [-0.5 - i for i in range(N)]}
             for t in served]
    ids, lps = client.top_tokens(copy.deepcopy(entry), served, 512)
    assert ids == [e["ids"] for e in entry] and lps == [e["logprobs"] for e in entry]

    def spoiled(at, **keys):
        bad = copy.deepcopy(entry)
        bad[at].update(keys)
        return bad

    for bad, says in (
            ([], f"0 entries of `top_logprobs` for 3 served tokens"),
            (entry[:2], "2 entries"),
            (entry[:2] + [None], "entry 2 of `top_logprobs` has the ids None"),
            (spoiled(1, ids=entry[1]["ids"][:-1], logprobs=entry[1]["logprobs"][:-1]),
             f"entry 1 of `top_logprobs` has the ids {entry[1]['ids'][:-1]}: not {N} distinct"),
            (spoiled(1, ids=[4, *[100] * (N - 1)]), f"not {N} distinct token ids under 512"),
            (spoiled(2, ids=list(range(100, 100 + N))), "with the served token 300 among them"),
            (spoiled(0, ids=[9, 512, *range(100, 100 + N - 2)]), "entry 0"),
            (spoiled(0, ids=[9, 1.0, *range(100, 100 + N - 2)]), "entry 0"),
            (spoiled(0, logprobs=[-0.5] * (N - 1)), f"not {N} finite numbers"),
            (spoiled(0, logprobs=[float("nan")] + [-0.5] * (N - 1)), f"not {N} finite numbers"),
            (spoiled(0, logprobs=[float("-inf")] + [-0.5] * (N - 1)), f"not {N} finite numbers")):
        try:
            client.top_tokens(bad, served, 512)
        except ValueError as e:
            assert says in str(e), (says, str(e))
        else:
            raise AssertionError(f"top_tokens took a reply with {says}")


BODY_BEFORE = {  # client.py:resend_greedy's body as PRs 21 to 42 sent it, key for key
    "model": "a-cell", "prompt": "hello, world", "max_tokens": 7, "temperature": 0,
    "stream": False, "nvext": {"ignore_eos": True}, "logprobs": 0}


def test_a_free_family_sends_what_it_always_sent():
    """The re-sent request of a family judged free (both cells): the body is
    the one of before, key for key, and the dictionary that goes over the
    request plane is the frontend's own preprocessing of it, with nothing
    added. A family judged forced differs in two places: the count of top
    tokens under the same option, and the annotation that asks for the
    experts chosen."""
    import client
    from dynamo_tpu.llm.model_card import ModelDeploymentCard
    from dynamo_tpu.llm.preprocessor import OpenAIPreprocessor
    from dynamo_tpu.llm.protocols import CompletionRequest
    from dynamo_tpu.llm.tokenizers import load_tokenizer
    from reference import TOP_TOKENS

    pre = OpenAIPreprocessor(
        ModelDeploymentCard(name="a-cell", tokenizer="byte",
                            kv_cache_block_size=client.PAGE_SIZE, context_length=1024),
        load_tokenizer("byte:512"))
    pick = {"why": "made_up", "prompt": "hello, world", "max_tokens": 7}
    body = client.resend_body("a-cell", pick, False)
    assert body == BODY_BEFORE and list(body) == list(BODY_BEFORE)
    assert json.dumps(body) == json.dumps(BODY_BEFORE)  # byte for byte

    def less_its_id(wire):  # the request's id is drawn anew each time
        assert wire.pop("request_id")
        return wire

    before = less_its_id(pre.preprocess_completion(CompletionRequest(**BODY_BEFORE)).to_dict())
    req, wire = client.resend_wire(pre, "a-cell", pick, False)
    assert less_its_id(wire) == before and "annotations" not in wire
    assert "top_logprobs" not in wire["sampling_options"]
    assert list(req.token_ids) == before["token_ids"]
    _, forced = client.resend_wire(pre, "a-cell", pick, True)
    less_its_id(forced)
    assert forced.pop("annotations") == [client.ROUTED_EXPERTS]
    assert forced["sampling_options"].pop("top_logprobs") == TOP_TOKENS
    assert forced == before


def test_controls_only_needs_no_program_to_serve():
    """`run.py --controls-only` on the first fixture at rehearsal sizes: four
    cases a seed from the mix's generator, the int8 control judged by the
    file's limits against a float32 pass with ITS choices forced (the file
    says `judge_routing: "forced"`), the bf16 control left out on a CPU and
    said so; exit code 4, since the file's limits keep the rule at its own
    geometry (0 or 1 on the chip; 2 where they do not)."""
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--controls-only", "--rehearsal",
         "--config-file", os.path.join(HERE, "fixtures", "many-experts.json"),
         "--traffic", "decode-closed", "--seed", "7", "--seed", "2147483907"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, timeout=600)
    lines = [json.loads(x) for x in p.stdout.decode().strip().splitlines()]
    assert p.returncode == 4 and [x["phase"] for x in lines] == [
        "controls", "controls", "controls_summary"], (p.returncode, p.stderr[-2000:])
    with open(os.path.join(HERE, "fixtures", "many-experts.json")) as f:
        limits = json.load(f)["judge"]
    for one in lines[:2]:
        assert "skipped" in one["bf16"] and one["positions"] > 0
        assert {k: lim for k, (_, lim) in one["int8"]["checked"].items()} == {
            k: v for k, v in limits.items() if k != "router_margin_epsilon"}
    assert lines[2]["seeds"] == [7, 2147483907] and lines[2]["int8"]["agreed"].endswith("of 2")
    assert len(lines[2]["int8"]["router_choice_deficit_max_sigmas"]) == 2
    assert b"checked 7 int8 logprob_gap_pooled_mean_sigmas: " in p.stderr
    assert b"checked 7 int8 router_choice_deficit_max_sigmas: " in p.stderr
    # a sizing run of eight requests a seed (`--requests`): sample_sizes.py
    # reads from its per-request numbers what the run itself judged at eight,
    # and at four what the run above judged: the picks are nested
    import sample_sizes

    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--controls-only", "--rehearsal",
         "--requests", "8", "--config-file", os.path.join(HERE, "fixtures", "many-experts.json"),
         "--traffic", "decode-closed", "--seed", "7", "--seed", "2147483907"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, timeout=600)
    sized = [json.loads(x) for x in p.stdout.decode().strip().splitlines()]
    assert p.returncode == 4 and sized[0]["requests"] == 8, (p.returncode, p.stderr[-2000:])
    result = os.path.join(os.path.dirname(HERE), "chiprun_out", "benchmark",
                          "controls.many-experts.decode-closed.rehearsal",
                          "reference_result.json")
    with open(result) as f:
        sets = json.load(f)["sets"]
    for at_four, at_eight in zip(lines[:2], sized[:2]):
        cases = sets[str(at_eight["seed"])]["int8"]["cases"]
        for count, one in ((4, at_four), (8, at_eight)):
            for number, value in sample_sizes.numbers_at(cases, count).items():
                assert close(value, one["int8"]["checked"][number][0], 1e-6), (count, number)


def tree_of_links(*real: str) -> str:
    """A tree beside the real one in which everything is a link, but for
    BENCHMARK.json (left out) and the directories of benchmark/ named, which
    are directories of links: a later PR's new files go there, and no file
    the benchmark has is touched."""
    root = os.path.dirname(HERE)
    tree = os.path.join(root, "chiprun_out", "selftest_tree")
    shutil.rmtree(tree, ignore_errors=True)
    os.makedirs(os.path.join(tree, "benchmark"))
    for name in os.listdir(root):
        if name not in ("benchmark", "BENCHMARK.json", "chiprun_out", ".jax_cache", ".git"):
            os.symlink(os.path.join(root, name), os.path.join(tree, name))
    for name in os.listdir(HERE):
        if name in real:
            os.makedirs(os.path.join(tree, "benchmark", name))
            for inner in os.listdir(os.path.join(HERE, name)):
                if inner != "__pycache__":
                    os.symlink(os.path.join(HERE, name, inner),
                               os.path.join(tree, "benchmark", name, inner))
        elif name != "__pycache__":
            os.symlink(os.path.join(HERE, name), os.path.join(tree, "benchmark", name))
    return tree


def new_routed_cell(tree: str, name: str, **keys) -> tuple:
    """The routed configuration of BENCHMARK.json again under `name`, with
    `keys` laid over its file, and a cell of it: both written into the tree,
    real files. Returns the file's content and the cell's name."""
    root = os.path.dirname(HERE)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = copy.deepcopy(bench["configs"][0])  # the routed one
    with open(os.path.join(root, entry["file"])) as f:
        cfg = json.load(f)
    assert cfg["family"] == "moe" and "judge" not in cfg
    cfg.update(keys)
    entry.update(name=name, file=f"benchmark/configs/{name}.json", reduced=cfg["reduced"])
    with open(os.path.join(tree, entry["file"]), "w") as f:
        json.dump(cfg, f)
    cell = dict(bench["workloads"][0], name=name + ".decode-closed", config=name)
    bench["configs"].append(entry)
    bench["workloads"].append(cell)
    with open(os.path.join(tree, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return cfg, cell["name"]


def test_limits_of_its_own_are_a_new_file_alone():
    """A later PR's configuration with a `judge` key: one new file and entries
    in BENCHMARK.json, no edit to a file the benchmark has. Built here in a
    tree of links beside the real one (the new file and BENCHMARK.json alone
    are real), and `run.py --rehearsal` there judges it by its overrides."""
    tree = tree_of_links("configs")
    cfg, cell = new_routed_cell(tree, "routed-with-its-own-limits", **planted_judge())
    p = subprocess.run(
        [sys.executable, os.path.join(tree, "benchmark", "run.py"), "--rehearsal",
         "--workload", cell, "--seed", "2147483801"],
        cwd=tree, stdout=subprocess.PIPE, stderr=subprocess.PIPE, timeout=900)
    lines = p.stdout.decode().strip().splitlines()
    line = json.loads(lines[-1]) if lines else {}
    assert p.returncode == 4 and line.get("reference_agrees"), (p.returncode, lines[-3:])
    phases = {x["phase"]: x for x in map(json.loads, lines[:-1])}  # what judging cost
    assert phases["window"]["resend_s"] > 0 and phases["reference"]["reference_s"] > 0
    limits = {k: lim for k, (_, lim) in line["checked"].items()}
    assert limits == {**{k: v for k, v in cfg["judge"].items()
                         if k != "router_margin_epsilon"}, "wrong_length_requests": 0}, limits
    assert line["checked"]["router_left_out_share"][0] == 0
    assert b"checked positions_outside: " in p.stderr and b"(limit 12)" in p.stderr
    shutil.rmtree(tree)


# What a later PR's `references/<family>.py` adds to a copy of references/moe.py
# where a chip holds a SHARE of every layer's experts: the program's dataclass
# with the router's width and the first expert held beside the count held, the
# uncut model's weights cut to the share, and (`held_share_reference`) the scan
# over the experts held alone.
HELD_SHARE = '''

# -- appended by selftest.py: a share of every layer's experts ------------- #
import dataclasses  # noqa: E402

from dynamo_tpu.models import moe as _program  # noqa: E402


@dataclasses.dataclass(frozen=True)
class HeldShare(_program.MoeConfig):
    """`num_experts` counts the experts HELD: [first_expert_held, + num_experts)
    of a router `router_width` wide."""
    router_width: int = 0
    first_expert_held: int = 0


def init_params(cfg, key):
    """The uncut model's weights, of which the share keeps its experts' (the
    router whole)."""
    whole = _program.init_params(dataclasses.replace(cfg, num_experts=cfg.router_width), key)
    lo, hi = cfg.first_expert_held, cfg.first_expert_held + cfg.num_experts
    return dict(whole, layers={k: v[:, lo:hi] if k in ("w_gate", "w_up", "w_down") else v
                               for k, v in whole["layers"].items()})
'''
SCAN_OVER_ALL = '        (w["w_gate"], w["w_up"], w["w_down"], weight.T),\n'
SCAN_OVER_THE_SHARE = (  # the routing weights of the experts held: the others' part is not here
    '        (w["w_gate"], w["w_up"], w["w_down"],\n'
    '         weight[:, getattr(cfg, "first_expert_held", 0):][:, :w["w_gate"].shape[0]].T),\n')


def held_share_reference(path: str) -> None:
    """references/moe.py again at `path`, given the share: it routes over the
    router's full width, computes the part of the experts whose weights it
    holds, and reads margins and deficits over the full width."""
    with open(os.path.join(HERE, "references", "moe.py")) as f:
        source = f.read()
    assert source.count(SCAN_OVER_ALL) == 1, "references/moe.py scans otherwise: cut it anew"
    with open(path, "w") as f:
        f.write(source.replace(SCAN_OVER_ALL, SCAN_OVER_THE_SHARE) + HELD_SHARE)


def test_a_forced_family_is_new_files_alone():
    """A later PR's family whose routing is judged forced, one chip holding a
    SHARE of every layer's experts: a configuration (`judge_routing`, limits of
    its own, its experts key in `reduced`: the count held, the router at its
    published width) and a `references/<family>.py` that takes `forced` and is
    given the share, both new files in a tree of links, and entries in
    BENCHMARK.json; no edit to a file the benchmark has. There the router's
    width is the published count: expert ids past the count held pass the
    client's check, ids at or over the width fail it; the parts that the two
    shares compute add up to the uncut reference's; `run.py --controls-only`
    judges the new reference under its own choices (over the full width) by
    the file's limits; and a whole `run.py --rehearsal` of its cell asks the
    worker for its top tokens, which today's program gives, and for the
    experts it chose, gets none, and FAILS the run with that reason: it is
    never judged free."""
    import importlib.util

    import jax
    import jax.numpy as jnp
    import numpy as np

    import client
    import files_check
    from worker_entry import build_model_config, load_config

    tree = tree_of_links("configs", "references")
    family = os.path.join(tree, "benchmark", "references", "moe_again.py")
    held_share_reference(family)
    root = os.path.dirname(HERE)
    with open(os.path.join(root, "benchmark", "configs", "mixtral-8x7b-d2.json")) as f:
        plain = json.load(f)
    share = {  # the second half of a router twice as wide as the experts held
        "reduced": [*plain["reduced"], "num_local_experts"],
        "published": dict(plain["published"], num_local_experts=2 * plain["num_local_experts"]),
        "first_expert_held": plain["num_local_experts"],
        "dataclass": "benchmark.references.moe_again:HeldShare",
        "dataclass_fields": dict(plain["dataclass_fields"], first_expert_held="first_expert_held",
                                 router_width="published.num_local_experts"),
        "rehearsal": dict(plain["rehearsal"], first_expert_held=4, published=dict(
            plain["published"], num_local_experts=8))}
    cfg, cell = new_routed_cell(
        tree, "routed-and-forced",
        **dict(planted_forced((0.01, 0.02), (0.07, 0.08)), family="moe_again", **share))
    path = os.path.join(tree, "benchmark", "configs", "routed-and-forced.json")
    # the router's width: the published count where the key is in `reduced`,
    # the key's own count where it is not (the cell as it stands)
    small = load_config(path, True)
    assert files_check.routed_geometry("share", cfg) == (16, 2, 2)
    assert files_check.routed_geometry("share", small) == (8, 2, 2)
    assert files_check.routed_geometry("plain", plain) == (8, 2, 2)
    assert files_check.routed_geometry("plain", dict(plain, **plain["rehearsal"])) == (4, 2, 2)
    rows = [[[5, 7], [4, 0]]] * 3  # ids in [held, width) and under held
    assert client.routed_rows(list(rows), 3, files_check.routed_geometry("share", small)) == rows
    for geometry, bad in ((files_check.routed_geometry("share", small), [[[5, 8], [4, 0]]]),
                          ((4, 2, 2), rows[:1])):
        try:
            client.routed_rows(bad, 1, geometry)
        except ValueError as e:
            assert f"distinct expert ids under {geometry[0]}" in str(e), str(e)
        else:
            raise AssertionError(f"an expert id at or over the width {geometry[0]} passed")
    for spoil, says in ((dict(first_expert_held=5), "counts the experts held"),
                        (dict(published={"num_hidden_layers": 32}), "counts the experts held"),
                        (dict(reduced=["num_hidden_layers"]), "and does not list")):
        try:
            files_check.routed_geometry("share", dict(small, **spoil))
        except files_check.BenchmarkFilesError as e:
            assert says in str(e), str(e)
        else:
            raise AssertionError(f"routed_geometry took {spoil}")
    # the parts that the two shares compute add up to the uncut block's
    spec = importlib.util.spec_from_file_location("moe_again_for_selftest", family)
    again = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(again)
    from references import moe as uncut

    second = build_model_config(dict(small, dataclass="dynamo_tpu.models.moe:MoeConfig",
                                     dataclass_fields=plain["dataclass_fields"]))
    second = again.HeldShare(**vars(second), router_width=8, first_expert_held=4)
    first = again.dataclasses.replace(second, first_expert_held=0)
    whole_cfg = again.dataclasses.replace(second, num_experts=8)
    key = jax.random.PRNGKey(3)
    whole = again._program.init_params(whole_cfg, key)
    x = jax.random.normal(jax.random.PRNGKey(4), (24, second.hidden_size), jnp.float32)
    free = jnp.full((24, 2), -1, jnp.int32)

    def block(module, cfg_, params):
        layer = jax.tree.map(lambda v: v[0], params["layers"])
        with jax.default_matmul_precision("highest"):
            out, (margin, chosen, deficit) = module.routed_mlp(x, layer, cfg_, free)
        return np.asarray(out - x), np.asarray(margin), np.asarray(chosen)

    want, margin, chosen = block(uncut, whole_cfg, whole)
    parts = [block(again, c, again.init_params(c, key)) for c in (first, second)]
    assert chosen.max() >= 4 and chosen.min() < 4  # both halves are routed to
    for part in parts:  # each share routes over the full width, as the uncut block does
        assert (part[2] == chosen).all() and np.allclose(part[1], margin)
        assert np.abs(part[0]).max() > 0.1 * np.abs(want).max()
    gap = np.abs(parts[0][0] + parts[1][0] - want).max() / np.abs(want).max()
    assert gap < 1e-3, gap  # (float32's rounding of x + part, less x; a part is a tenth or more)

    run_py = os.path.join(tree, "benchmark", "run.py")
    p = subprocess.run(
        [sys.executable, run_py, "--controls-only", "--rehearsal", "--config-file", path,
         "--traffic", "decode-closed", "--seed", "2147483803"],
        cwd=tree, stdout=subprocess.PIPE, stderr=subprocess.PIPE, timeout=600)
    lines = [json.loads(x) for x in p.stdout.decode().strip().splitlines()]
    assert p.returncode == 4 and [x["phase"] for x in lines] == [
        "controls", "controls_summary"], (p.returncode, p.stderr[-2000:])
    checked = lines[0]["int8"]["checked"]
    assert {k: lim for k, (_, lim) in checked.items()} == {
        k: v for k, v in cfg["judge"].items() if k != "router_margin_epsilon"}
    p = subprocess.run(
        [sys.executable, run_py, "--rehearsal", "--workload", cell, "--seed", "2147483803"],
        cwd=tree, stdout=subprocess.PIPE, stderr=subprocess.PIPE, timeout=900)
    lines = [json.loads(x) for x in p.stdout.decode().strip().splitlines()]
    assert p.returncode == 1 and lines[-1]["phase"] == "failed", (p.returncode, lines[-2:])
    assert "forced routing: 0 rows of `routed_experts`" in lines[-1]["error"], lines[-1]
    shutil.rmtree(tree)


def test_benchmark_files():
    """files_check.py's checks (which every run of run.py makes too), and what
    needs the program: every configuration loads, plain and with its
    rehearsal block laid over, into the dataclass it names."""
    import dataclasses

    import files_check
    from worker_entry import build_model_config, load_config, lookup

    root = os.path.dirname(HERE)
    files_check.check(root)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for c in bench["configs"]:
        for rehearsal in (False, True):
            cfg = load_config(os.path.join(root, c["file"]), rehearsal)
            built = build_model_config(cfg)
            assert dataclasses.is_dataclass(built)
            assert type(built).__name__ == cfg["dataclass"].partition(":")[2]
            for field, key in cfg["dataclass_fields"].items():
                assert getattr(built, field) == lookup(cfg, key), (c["name"], field)
    for w in bench["workloads"]:
        load_mix(w["traffic"], False), load_mix(w["traffic"], True)
    # a per-layer metric is a cell's where the cell reports what it moves: the
    # dense cell reports no `ttft_p95_ms` (PERF.md section 2) and none of its movers
    import run
    cells = {w["name"]: run.load_cell(w["name"]) for w in bench["workloads"]}
    for cell in cells.values():
        reported = {m["name"] for m in cell["end_to_end"]}
        assert "setup_s" in reported and len(reported) >= 2 and cell["per_layer"]
        assert all(m["moves"] in reported for m in cell["per_layer"]), cell["name"]
    dense = cells["mistral-7b-d16.decode-closed"]
    assert "ttft_p95_ms" not in {m["name"] for m in dense["end_to_end"]}
    assert {"engine.post_warmup_compiles", "engine.mixed_family_uncompiled"} <= {
        m["name"] for m in dense["per_layer"]}

    def lists_a_cell_that_lacks_it(b, c):
        next(m for m in b["per_layer"] if m["moves"] == "ttft_p95_ms")["workloads"] = [
            "mistral-7b-d16.decode-closed"]

    # the checks can fail: a width changed, a bound out of range, a metric
    # listed for a cell that does not report what it moves
    for spoil, what in ((lambda b, c: next(iter(c.values())).update(hidden_size=2048), "width"),
                        (lambda b, c: b["end_to_end"][0].update(bound=0.2), "bound"),
                        (lists_a_cell_that_lacks_it, "workloads key")):
        bench2 = json.loads(json.dumps(bench))
        cfgs = {c["name"]: json.load(open(os.path.join(root, c["file"])))
                for c in bench2["configs"]}
        spoil(bench2, cfgs)
        try:
            files_check.check_loaded(bench2, cfgs, root)
        except files_check.BenchmarkFilesError:
            continue
        raise AssertionError(f"files_check passed a spoiled {what}")


def main() -> int:
    for name, fn in sorted(globals().items()):
        if name.startswith("test_") and callable(fn):
            fn()
            print("ok", name)
    return 0


if __name__ == "__main__":
    sys.exit(main())
