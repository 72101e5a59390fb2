#!/usr/bin/env python3
"""The long lane: one request of a shared-prefix mix WITH its prefix (16,384
tokens and its tail, 32 greedy tokens), which `correct` cannot reach
(benchmark/run.py judges requests of at most 2,048 positions). It is served
twice through the engine at the configuration's published widths, cold and
then from the prefix cache, and both serves are compared with the family's
plain reference at the last 32 positions by the judge's own numbers; for a
family with an indexer (a learned selection of the context) the picks of the
served path's own functions at those positions are compared with the
reference's, as sets, layer by layer.

    python3 tools/long_lane.py serve <configuration> [--rehearsal]
        discovery + worker; writes chiprun_out/long_lane/<configuration>/cases.json
    python3 tools/long_lane.py judge <configuration> [--rehearsal]
        the reference (this process holds the chip itself); prints the gaps

Two processes because a chip belongs to one at a time: run `serve`, then
`judge`, in one chip call. `--rehearsal`: the configuration's tiny sizes on
the CPU. PERF.md has the readings. A builder's tool: it uses the benchmark's
client and judge (benchmark/) and is no part of what the driver runs.
"""

import asyncio
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))
sys.path.insert(1, ROOT)

MIX = "sharedprefix-closed"
TOKENS = 32
SEED = 2155003007


def config_file(config):
    return os.path.join(ROOT, "benchmark", "configs", config + ".json")


def out_dir(config, rehearsal):
    d = os.path.join(ROOT, "chiprun_out", "long_lane",
                     config + (".rehearsal" if rehearsal else ""))
    os.makedirs(d, exist_ok=True)
    return d

async def serve_twice(config, discovery_addr, cfg, pick, geometry):
    """(cold and annotated: rows for every input position; again, not
    annotated: from the prefix cache) and the worker's stats around each."""
    import client
    from client import ROUTED_EXPERTS, TOP_LOGPROBS, StatsWatch, resend_wire, routed_rows, top_tokens
    from dynamo_tpu.llm.model_card import ModelDeploymentCard
    from dynamo_tpu.llm.preprocessor import OpenAIPreprocessor
    from dynamo_tpu.llm.tokenizers import load_tokenizer
    from dynamo_tpu.runtime import DistributedRuntime, RuntimeConfig

    vocab = cfg["vocab_size"]
    pre = OpenAIPreprocessor(
        ModelDeploymentCard(name=config, tokenizer="byte", kv_cache_block_size=client.PAGE_SIZE,
                            context_length=cfg["max_position_embeddings"]),
        load_tokenizer(f"byte:{vocab}"))
    rc = RuntimeConfig()
    rc.discovery_endpoint = discovery_addr
    drt = await DistributedRuntime.create(rc)
    watch = StatsWatch(discovery_addr)
    await watch.start()
    served, stats = {}, [await watch.fresh()]
    try:
        ep = drt.namespace("dynamo").component("backend").endpoint("generate")
        cl = await ep.client()
        (instance,) = await cl.wait_for_instances(timeout=30)
        for name, annotated in (("cold", True), ("cached", False)):
            req, wire = resend_wire(pre, config, pick, True)
            if not annotated:
                wire["annotations"] = [a for a in wire["annotations"] if a != ROUTED_EXPERTS]
            out, lps, rows, tops = [], [], [], []
            t0 = time.monotonic()
            first = None
            stream = await cl.direct(wire, instance)
            async for item in stream:
                assert item.get("event") != "error", item
                data = item.get("data") or {}
                if first is None and data.get("token_ids"):
                    first = time.monotonic() - t0
                out.extend(data.get("token_ids") or [])
                lps.extend(data.get("log_probs") or [])
                rows.extend(data.get(ROUTED_EXPERTS) or [])
                tops.extend(data.get(TOP_LOGPROBS) or [])
            assert len(out) == len(lps) == pick["max_tokens"], (len(out), len(lps))
            top_ids, top_lps = top_tokens(tops, out, vocab)
            served[name] = {
                "prompt_ids": list(req.token_ids), "served_ids": out, "served_logprobs": lps,
                "served_top_ids": top_ids, "served_top_logprobs": top_lps,
                "first_token_s": first, "seconds": time.monotonic() - t0}
            if annotated:
                served[name][ROUTED_EXPERTS] = routed_rows(
                    rows, len(req.token_ids) + len(out) - 1, geometry)
            else:
                assert not rows
            stats.append(await watch.fresh())
    finally:
        await watch.close()
        await drt.close()
    return served, stats


def serve(config, rehearsal):
    import run as bench
    import files_check
    from traffic import Generator, load_mix
    from worker_entry import load_config

    cfg = load_config(config_file(config), rehearsal)
    mix = load_mix(MIX, rehearsal)
    gen = Generator(mix, SEED)
    req = next(r for stream in gen.client_streams() for r in stream
               if len(r.prompt) > mix["prefix_sharing"]["prefix_tokens"])
    pick = {"why": "long_lane", "prompt": req.prompt, "max_tokens": 8 if rehearsal else TOKENS}
    d = out_dir(config, rehearsal)
    children = bench.Children(d)
    env = bench.child_env(rehearsal)
    try:
        port = bench.free_port()
        addr = f"127.0.0.1:{port}"
        env["DYN_DISCOVERY_ENDPOINT"] = addr
        os.environ["DYN_DISCOVERY_ENDPOINT"] = addr
        children.start("discovery", [sys.executable, "-m", "dynamo_tpu.runtime.discovery",
                                     "--host", "127.0.0.1", "--port", str(port)], env)
        argv = [sys.executable, os.path.join(ROOT, "benchmark", "worker_entry.py"),
                "--bench-config", config_file(config), "--bench-name", config]
        if rehearsal:
            argv.append("--bench-rehearsal")
        worker = children.start("worker", argv + list(cfg["worker_args"]), env)
        m = children.wait_for_log("worker", worker, r"worker device (\{.*\})", 180)
        print(json.dumps({"phase": "device", **json.loads(m.group(1))}), flush=True)
        children.wait_for_log("worker", worker, r"jax worker up", 1100)
        served, stats = asyncio.run(serve_twice(
            config, addr, cfg, pick, files_check.routed_geometry(config, cfg)))
    finally:
        children.stop_all()
    keys = ("kv_prefix_hit_blocks_total", "kv_skip_ahead_blocks", "req_admitted", "kv_total_blocks",
            "step_prefill_count", "step_mixed_count", "step_block_count", "compiled_variants")
    around = [{k: s.get(k) for k in keys} for s in stats]
    with open(os.path.join(d, "cases.json"), "w") as f:
        json.dump({"rehearsal": rehearsal, "served": served, "stats": around}, f)
    for name, c in served.items():
        print(json.dumps({"phase": "served", "which": name, "prompt_tokens": len(c["prompt_ids"]),
                          "tokens": len(c["served_ids"]), "first_token_s": c["first_token_s"],
                          "seconds": c["seconds"]}), flush=True)
    print(json.dumps({"phase": "stats", "around": around,
                      "same_tokens": served["cold"]["served_ids"] == served["cached"]["served_ids"]}),
          flush=True)


def judge(config, rehearsal):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import reference as harness

    d = out_dir(config, rehearsal)
    with open(os.path.join(d, "cases.json")) as f:
        served = json.load(f)["served"]
    cfg, weights, ref, overrides, forced = harness.load_model(config_file(config), rehearsal)
    assert forced
    params = weights()
    rows_of, margins_of, deficits_of, served_of, cases = {}, {}, {}, {}, {}
    indexed = bool(getattr(cfg, "index_topk", 0))  # a family that selects
    picks_of = {}
    given = np.asarray(served["cold"]["routed_experts"], np.int32).transpose(1, 0, 2)
    fwd = None
    for name, c in served.items():
        prompt, out = c["prompt_ids"], c["served_ids"]
        seq = prompt + out[:-1]
        if seq[: given.shape[1]] != (served["cold"]["prompt_ids"] + served["cold"]["served_ids"][:-1])[: len(seq)]:
            print(json.dumps({"phase": "note", "which": name,
                              "note": "its tokens part from the cold serve's: the cold rows are forced "
                                      "at the positions both share, the rest route freely"}), flush=True)
        T = -(-len(seq) // 64) * 64
        n_last = T - (len(prompt) - 1)
        toks = np.zeros((T,), np.int32)
        toks[: len(seq)] = seq
        forced_rows = np.full((given.shape[0], T, given.shape[2]), -1, np.int32)
        same = 0
        cold_seq = served["cold"]["prompt_ids"] + served["cold"]["served_ids"][:-1]
        while same < min(len(seq), len(cold_seq)) and seq[same] == cold_seq[same]:
            same += 1
        forced_rows[:, :same] = given[:, :same]
        if fwd is None:
            more = {"picks": True} if indexed else {}
            fwd = jax.jit(lambda p, t, f: ref.logits(p, cfg, t, n_last, forced=f, **more))
        t0 = time.monotonic()
        with jax.default_matmul_precision("highest"):
            logits, margins, chosen, deficits, *picks = fwd(
                params, jnp.asarray(toks), jnp.asarray(forced_rows))
        if picks:
            picks_of[name] = (toks, len(prompt), np.asarray(picks[0])[:, : len(out)])
        logits = np.asarray(logits)
        rows_of[name] = np.array(logits[: len(out)])
        margins_of[name] = np.asarray(margins)[: len(out)]
        deficits_of[name] = np.asarray(deficits)[:, : len(seq)]
        served_of[name] = (out, c["served_logprobs"], (c["served_top_ids"], c["served_top_logprobs"]))
        cases[name] = c
        print(json.dumps({"phase": "reference", "which": name, "positions": len(seq), "padded": T,
                          "forced_positions": same, "seconds": time.monotonic() - t0}), flush=True)
    for name in cases:
        one = harness.judge({name: cases[name]}, {name: rows_of[name]}, {name: margins_of[name]},
                            {name: served_of[name]}, overrides, {name: deficits_of[name]})
        print(json.dumps({"phase": "judged", "which": name, "agrees": one["agrees"],
                          "why_not": one["why_not"], "compared": one["compared"],
                          "case": one["cases"][name]}), flush=True)


    del fwd
    for name, (toks, n_prompt, theirs) in picks_of.items():
        mine = served_picks(cfg, params, toks, n_prompt - 1, theirs.shape[1])
        shares = [[len(set(a.tolist()) & set(b.tolist()) - {-1}) / max((b >= 0).sum(), 1)
                   for a, b in zip(mine[fi], theirs[fi])] for fi in range(len(theirs))]
        print(json.dumps({"phase": "picks", "which": name, "positions": theirs.shape[1],
                          "picks_a_position": int((theirs[0, -1] >= 0).sum()),
                          "share_of_the_references_by_full_layer": [
                              {"mean": float(np.mean(x)), "least": float(np.min(x))}
                              for x in shares]}), flush=True)


def served_picks(cfg, params, toks, first, count, chunk=512):
    """The picks of the SERVED path's own functions (models/mla_moe.py:
    prefill_picks, at the matmul precision the worker computes in) at the
    positions `first` .. `first + count` of the sequence `toks`, prefilled
    in chunks through a pool of its own: [full layers, count, index_topk]."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from dynamo_tpu.models import mla_moe
    from dynamo_tpu.ops.state_cache import alloc_state_cache

    page = 64
    chunk = min(chunk, len(toks))
    # whole chunks: the padded tail is inert (causal), as the reference's is
    toks = np.concatenate([toks, np.zeros(-len(toks) % chunk, np.int32)])
    T = len(toks)
    assert T % page == 0, (T, page)
    kv = alloc_state_cache(cfg, T // page + 2, page, 1, chunk, 1)
    table = jnp.arange(1, T // page + 2, dtype=jnp.int32)[None]
    step = jax.jit(lambda p, *a: mla_moe.prefill_picks(p, cfg, *a), donate_argnums=(3, 4))
    kept = []
    for at in range(0, T, chunk):
        _, *kv, picks = step(
            params, jnp.asarray(toks[at: at + chunk])[None], at + jnp.arange(chunk)[None], *kv,
            table, jnp.asarray([at], jnp.int32), jnp.asarray([chunk - 1], jnp.int32))
        if at + chunk > first:
            kept.append((at, np.asarray(picks)))
    rows = np.concatenate([p for _, p in kept], axis=1)
    start = first - kept[0][0]
    return rows[:, start: start + count]


if __name__ == "__main__":
    which, config = sys.argv[1], sys.argv[2]
    rehearsal = "--rehearsal" in sys.argv
    if rehearsal:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    {"serve": serve, "judge": judge}[which](config, rehearsal)
