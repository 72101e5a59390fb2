"""Migration durability bench: checkpoint-assisted resume vs recompute.

Durable decode sessions (ISSUE 15, docs/fault_tolerance.md): with
incremental commit + session checkpointing, a worker death costs the
survivor an onboard of the replicated session prefix plus a recompute of
only the un-checkpointed tail — instead of a full prefill of
prompt + already-emitted tokens.

Two in-proc engines (A = victim, B = survivor) join one discovery plane,
exactly like the kv-fabric bench arm:

  arm `ckpt`      DYN_KV_CHECKPOINT=<N>: deep sessions decode on A, their
                  committed blocks replicate into B's host tier; A is then
                  killed (data plane + mesh down, streams severed) and the
                  migration-shaped retry (prompt + emitted tokens,
                  migration=1) resumes on B — TTFT is the resume cost.
  arm `recompute` DYN_KV_CHECKPOINT=off: same kill, same retry, but B has
                  nothing — full prefill recompute.

Both arms pre-pay compile + inject variants with an untimed warmup
session, then time `--rounds` resumes each; the gate compares MEDIANS.
Greedy streams are byte-checked against the uninterrupted oracle: the
resumed continuation must be exactly the tokens the dead stream would
have produced (count-contiguity is a corollary).

--smoke gates (CI):  median ckpt TTFT <= --max-ratio x median recompute
TTFT, resume_source_checkpoint > 0 on B, and byte-identical
continuations on every round. On the chip: not measured.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import statistics
import sys
import time


def _jsonl(obj):
    print(json.dumps(obj), flush=True)


async def _build_mesh(checkpoint: str, *, page_size: int, host_blocks: int,
                      num_pages: int):
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.engine import EngineConfig, JaxEngine
    from dynamo_tpu.kvbm import KvbmDistributed
    from dynamo_tpu.llm.kv_transfer import KvDataPlaneServer
    from dynamo_tpu.models import llama
    from dynamo_tpu.runtime import DiscoveryServer, DistributedRuntime, RuntimeConfig

    os.environ["DYN_KV_CHECKPOINT"] = checkpoint
    cfg_model = llama.LlamaConfig.tiny(dtype=jnp.float32)
    params = llama.init_params(cfg_model, jax.random.PRNGKey(0))
    server = DiscoveryServer(port=0)
    _, port = await server.start()
    rcfg = RuntimeConfig(discovery_endpoint=f"127.0.0.1:{port}")
    drts, engines, dists, planes = [], [], [], []
    for _ in range(2):
        drt = await DistributedRuntime.create(rcfg)
        eng = JaxEngine(
            EngineConfig(
                model="tiny", max_num_seqs=4, page_size=page_size,
                num_pages=num_pages, max_model_len=4096,
                prefill_buckets=(32, 64, 128), max_prefill_chunk=128,
                kvbm_host_blocks=host_blocks,
            ),
            model_config=cfg_model, params=params,
        )
        dpl = KvDataPlaneServer()
        await dpl.start()
        await dpl.register(drt)
        dist = KvbmDistributed(drt, eng.kvbm, dpl, "ns", "bench",
                               drt.instance_id)
        await dist.start()
        drts.append(drt)
        engines.append(eng)
        dists.append(dist)
        planes.append(dpl)
    return server, drts, engines, dists, planes


async def _teardown(server, drts, engines, dists, planes):
    for eng in engines:
        await eng.close()
    for d in dists:
        await d.close()
    for p in planes:
        await p.close()
    for drt in drts:
        await drt.close()
    await server.stop()


async def _run_stream(engine, prompt, max_tokens, request_id,
                      migration=0, exclude=None):
    """Drive one greedy stream; returns (tokens, ttft_s)."""
    from dynamo_tpu.llm.protocols import PreprocessedRequest
    from dynamo_tpu.runtime.engine import Context

    req = PreprocessedRequest(
        token_ids=list(prompt),
        stop_conditions={"max_tokens": max_tokens, "ignore_eos": True},
        request_id=request_id, migration=migration,
        router={"exclude_instances": exclude} if exclude else {},
    ).to_dict()
    toks, t0, ttft = [], time.perf_counter(), None
    async for item in engine.generate(req, Context()):
        data = item.get("data")
        if data and data.get("token_ids"):
            if ttft is None:
                ttft = time.perf_counter() - t0
            toks.extend(data["token_ids"])
    return toks, ttft if ttft is not None else time.perf_counter() - t0


def _session_prompt(i: int, n: int):
    # distinct per-session prompts: no cross-session prefix reuse blurs
    # the arms (each resume pays its own onboard/recompute)
    return [(7 + i * 131 + j * 3) % 250 + 1 for j in range(n)]


async def _run_arm(name: str, checkpoint: str, args) -> dict:
    server, drts, engines, dists, planes = await _build_mesh(
        checkpoint, page_size=args.page_size,
        host_blocks=args.host_blocks, num_pages=args.num_pages,
    )
    eng_a, eng_b = engines
    dist_b = dists[1]
    plane_b = planes[1]
    n_sessions = args.rounds + 1  # session 0 = untimed warmup
    try:
        # warm B's compile variants with a short plain stream (untimed)
        await _run_stream(eng_b, _session_prompt(99, args.prompt), 8, "warm-b")

        sessions = []
        for i in range(n_sessions):
            prompt = _session_prompt(i, args.prompt)
            toks, _ = await _run_stream(
                eng_a, prompt, args.decode, f"s{i}"
            )
            assert len(toks) == args.decode, (len(toks), args.decode)
            sessions.append((prompt, toks))

        want_blocks = (
            (args.prompt + args.decode) // args.page_size - 1
        ) * n_sessions
        if checkpoint != "off":
            # wait for replication to drain into B's host tier
            deadline = time.monotonic() + 30.0
            while time.monotonic() < deadline:
                if plane_b.checkpoint_blocks_received >= want_blocks:
                    break
                await asyncio.sleep(0.02)

        # kill A: streams sever, its data plane and mesh go dark — the
        # lease lingers exactly like a real SIGKILL corpse
        await eng_a.close()
        await dists[0].close()
        await planes[0].close()
        await drts[0].server.stop()

        ttfts, mismatches = [], 0
        for i, (prompt, toks) in enumerate(sessions):
            cut = args.cut if args.cut > 0 else args.decode // 2
            emitted = toks[:cut]
            retry_prompt = list(prompt) + emitted
            cont, ttft = await _run_stream(
                eng_b, retry_prompt, args.decode - cut, f"s{i}-retry",
                migration=1, exclude=[drts[0].instance_id],
            )
            if cont != toks[cut:]:
                mismatches += 1
            if i > 0:  # session 0 pre-pays inject/prefill variants
                ttfts.append(ttft)
        st = eng_b.stats()
        return {
            "arm": name,
            "ttft_ms_median": round(statistics.median(ttfts) * 1000.0, 2),
            "ttft_ms_all": [round(t * 1000.0, 2) for t in ttfts],
            "mismatched_streams": mismatches,
            "resume_source_checkpoint": st["resume_source_checkpoint"],
            "resume_source_local": st["resume_source_local"],
            "resume_source_peer": st["resume_source_peer"],
            "resume_source_recompute": st["resume_source_recompute"],
            "migrations_resumed": st["migrations_resumed"],
            "migration_replayed_tokens": st["migration_replayed_tokens"],
            "ckpt_blocks_received_by_b": plane_b.checkpoint_blocks_received,
        }
    finally:
        os.environ.pop("DYN_KV_CHECKPOINT", None)
        try:
            await _teardown(server, drts[1:], engines[1:], dists[1:], planes[1:])
        except Exception:  # noqa: BLE001 — teardown of a half-killed mesh
            pass


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--prompt", type=int, default=64)
    ap.add_argument("--decode", type=int, default=448)
    ap.add_argument("--cut", type=int, default=0,
                    help="tokens emitted before the kill (0 = decode/2)")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--num-pages", type=int, default=256)
    ap.add_argument("--host-blocks", type=int, default=256)
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--checkpoint", default="512",
                    help="DYN_KV_CHECKPOINT for the ckpt arm")
    ap.add_argument("--max-ratio", type=float, default=0.5,
                    help="smoke gate: ckpt TTFT <= ratio x recompute TTFT")
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args()

    ck = asyncio.run(_run_arm("ckpt", args.checkpoint, args))
    _jsonl(ck)
    rc = asyncio.run(_run_arm("recompute", "off", args))
    _jsonl(rc)
    ratio = ck["ttft_ms_median"] / max(rc["ttft_ms_median"], 1e-9)
    summary = {
        "summary": "migration-resume",
        "ckpt_ttft_ms": ck["ttft_ms_median"],
        "recompute_ttft_ms": rc["ttft_ms_median"],
        "ratio": round(ratio, 3),
        "gate_max_ratio": args.max_ratio,
    }
    _jsonl(summary)
    if args.smoke:
        ok = (
            ratio <= args.max_ratio
            and ck["resume_source_checkpoint"] > 0
            and ck["mismatched_streams"] == 0
            and rc["mismatched_streams"] == 0
        )
        if not ok:
            _jsonl({"smoke": "FAIL", **summary,
                    "resume_source_checkpoint": ck["resume_source_checkpoint"],
                    "mismatches": [ck["mismatched_streams"],
                                   rc["mismatched_streams"]]})
            sys.exit(1)
        _jsonl({"smoke": "ok"})


if __name__ == "__main__":
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    main()
