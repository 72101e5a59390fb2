"""TTFT breakdown profiler (round-3 verdict #4).

Round 3 measured 84.7 ms first-seq TTFT at isl=128 on llama3-3b — ~5 ms of
which is prefill compute. This tool decomposes the other ~80 ms into the
host-side stages so the fix lands where the time actually goes:

  rtt_noop        dispatch + host-fetch of a 1-element jitted add — the
                  pure dispatch floor of the runtime
  arg_transfer    host->device transfer of the isl-token prompt
  dispatch_only   prefill call returning WITHOUT a fetch: python arg
                  handling + executable-cache lookup + enqueue
  prefill_fetch   full prefill + first-token fetch (= raw TTFT)
  engine_ttft     the same request through JaxEngine.generate (adds
                  admission, scheduling, the step loop, emission)

Usage: python bench_ttft.py [--smoke] [--isl 128] [--model llama3-3b]
Prints a breakdown table on stderr and one JSON line on stdout.
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
from pathlib import Path
from typing import List, Optional

REPO = Path(__file__).resolve().parent
sys.path.insert(0, str(REPO))

from bench import require_tpu  # noqa: E402


def _median_ms(fn, n: int = 7) -> float:
    xs = []
    for _ in range(n):
        t0 = time.perf_counter()
        fn()
        xs.append((time.perf_counter() - t0) * 1000)
    return statistics.median(xs)


def main(argv: Optional[List[str]] = None):
    ap = argparse.ArgumentParser(description="TTFT breakdown profiler")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--model", default=None)
    ap.add_argument("--isl", type=int, default=128)
    ap.add_argument("--reps", type=int, default=7)
    args = ap.parse_args(argv)

    if args.smoke:
        import os

        os.environ["JAX_PLATFORMS"] = "cpu"
    require_tpu(args.smoke)

    model = args.model or ("tiny" if args.smoke else "llama3-3b")

    import jax
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.engine.engine import _resolve_model
    from dynamo_tpu.engine.kv_cache import alloc_kv_arrays
    from dynamo_tpu.models import llama
    from dynamo_tpu.engine.sampling import SamplingParams, sample

    cfg = _resolve_model(model)
    isl = min(args.isl, 64) if args.smoke else args.isl
    PAGE = 64
    pages = (isl + PAGE) // PAGE + 1
    num_pages = pages + 1

    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    kv_k, kv_v = alloc_kv_arrays(
        cfg.num_layers, num_pages, PAGE, cfg.num_kv_heads, cfg.head_dim, cfg.dtype
    )
    pt = jnp.asarray(1 + np.arange(pages, dtype=np.int32))[None, :]
    rng = np.random.RandomState(0)
    toks_host = rng.randint(3, cfg.vocab_size - 1, size=(1, isl)).astype(np.int32)
    pos_host = np.arange(isl, dtype=np.int32)[None, :]
    ctx0 = jnp.zeros((1,), jnp.int32)
    last = jnp.full((1,), isl - 1, jnp.int32)
    samp = SamplingParams.full(1, temperature=0.0)
    key = jax.random.PRNGKey(7)

    # ---- the stages ----
    noop = jax.jit(lambda x: x + 1)
    tiny = jnp.zeros((8,), jnp.int32)
    _ = jax.device_get(noop(tiny))  # compile

    def prefill_fn(p, kk, kv, t, po, tab, cl, li, s, k):
        logits, kk, kv = llama.prefill_forward_batched(
            p, cfg, t, po, kk, kv, tab, cl, li
        )
        return sample(logits, s, k), kk, kv

    prefill = jax.jit(prefill_fn)  # NO donation: repeated timing reuses kv
    first, _, _ = prefill(
        params, kv_k, kv_v, jnp.asarray(toks_host), jnp.asarray(pos_host),
        pt, ctx0, last, samp, key,
    )
    _ = jax.device_get(first)  # compile + warm

    rtt_noop = _median_ms(lambda: jax.device_get(noop(tiny)), args.reps)

    def xfer():
        a = jax.device_put(toks_host)
        jax.device_get(a.ravel()[0])

    arg_transfer = _median_ms(xfer, args.reps)

    dispatch_only = _median_ms(
        lambda: prefill(
            params, kv_k, kv_v, jnp.asarray(toks_host), jnp.asarray(pos_host),
            pt, ctx0, last, samp, key,
        ),
        args.reps,
    )

    def full():
        f, _, _ = prefill(
            params, kv_k, kv_v, jnp.asarray(toks_host), jnp.asarray(pos_host),
            pt, ctx0, last, samp, key,
        )
        jax.device_get(f)

    prefill_fetch = _median_ms(full, args.reps)

    # ---- engine path ----
    import asyncio

    from dynamo_tpu.engine import EngineConfig, JaxEngine
    from dynamo_tpu.runtime.engine import Context

    # reuse the already-resident weights: a second init_params inside the
    # engine would double weight residency and OOM a 16 GiB chip on 3b+
    eng = JaxEngine(EngineConfig(
        model=model, page_size=PAGE, num_pages=max(64, num_pages * 4),
        max_num_seqs=4, max_model_len=isl + 64,
    ), model_config=cfg, params=params)

    async def one_ttft() -> float:
        req = {
            "token_ids": toks_host[0].tolist(),
            "stop_conditions": {"max_tokens": 2, "ignore_eos": True},
        }
        t0 = time.perf_counter()
        async for item in eng.generate(req, Context()):
            if (item.get("data") or {}).get("token_ids"):
                return (time.perf_counter() - t0) * 1000
        return float("nan")

    async def drain():
        # leftover speculative decode blocks of a finished request occupy
        # the device queue; wait them out so each rep measures a CLEAN
        # arrival (the loaded-arrival case is the depth-capped queue delay,
        # reported separately by bench_engine/bench_e2e)
        while eng._inflight or any(s is not None for s in eng.slots):
            await asyncio.sleep(0.005)

    async def engine_rounds():
        await one_ttft()  # compile every engine variant
        await one_ttft()
        out = []
        for _ in range(args.reps):
            await drain()
            out.append(await one_ttft())
        return out

    engine_ttfts = asyncio.run(engine_rounds())
    asyncio.run(eng.close())
    engine_ttft = statistics.median(engine_ttfts)

    rows = {
        "rtt_noop_ms": round(rtt_noop, 2),
        "arg_transfer_ms": round(arg_transfer, 2),
        "dispatch_only_ms": round(dispatch_only, 2),
        "prefill_fetch_ms": round(prefill_fetch, 2),
        "engine_ttft_ms": round(engine_ttft, 2),
        "engine_overhead_ms": round(engine_ttft - prefill_fetch, 2),
        "compute_est_ms": round(prefill_fetch - rtt_noop, 2),
    }
    for k, v in rows.items():
        print(f"# {k:>20}: {v:8.2f}", file=sys.stderr)
    print(json.dumps({
        "metric": f"ttft_breakdown_{model}_isl{isl}",
        "value": rows["prefill_fetch_ms"],
        "unit": "ms",
        "vs_baseline": None,
        **rows,
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
