#!/usr/bin/env python3
"""The benchmark's own arithmetic, checked by hand-made cases. Run by hand:

    JAX_PLATFORMS=cpu python3 benchmark/selftest.py

Not part of tests/. Checks: percentile and due-time arithmetic on made-up
timestamps; that every seed gets the same set of lengths and arrivals; the
per-layer expressions; the trace reduction on fixtures/synthetic_trace
(busy union, idle share, self time, kernel share, gap attribution); and the
dense and mixture-of-experts references against the program's own forwards
(models/llama.py, models/moe.py at capacity_factor 4.0) at the tiny presets
in float32 on the CPU. (PR 21 found that a wrong page stays under the
tolerance at tiny widths on the CPU: that sabotage is a chip check.)
"""

from __future__ import annotations

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.dirname(HERE))

import layer_metrics  # noqa: E402
import metrics  # noqa: E402
import reduce_trace  # noqa: E402
from traffic import Generator, Request, load_mix, warp  # noqa: E402


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
    closed = Generator(load_mix("decode-closed"), 3).client_streams()
    assert len(closed) == 32 and all(len(s) == 64 for s in closed)


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
           "end_to_end": {"ttft_p50_ms": 387.5},
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
            want, margins = ref.logits(params, cfg, toks, n_last=T)
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


def test_judge_holds_each_request():
    """reference.judge on made-up logits: a sound run agrees; ONE request of
    four served 0.2 deviations off at every position, which the per-position
    tolerance (0.25) and a routed family's pooled means let through, does not:
    the worst request's median (routed) or mean (dense) catches it."""
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
        margins[name] = rng.uniform(0.0, 0.35, size=n)  # some 29% under the epsilon
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


def test_benchmark_files():
    """files_check.py's checks (which every run of run.py makes too), and what
    needs the program: every configuration loads, plain and with its
    rehearsal block laid over, into the dataclass it names."""
    import dataclasses
    import json

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
    # the checks can fail: a width changed, a bound out of range
    for spoil, what in ((lambda b, c: next(iter(c.values())).update(hidden_size=2048), "width"),
                        (lambda b, c: b["end_to_end"][0].update(bound=0.2), "bound")):
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
