#!/usr/bin/env python3
"""Compile-only rehearsal: does each configuration's largest step fit one v5e?

    JAX_PLATFORMS=cpu python3 benchmark/compile_rehearsal.py [config ...] [--pages N]

For each configuration under configs/ (or the ones named) it compiles, for a
DESCRIBED `v5e:2x2` chip (nothing attached, nothing run), the model's largest
prefill (one 1,024-token chunk), mixed step (2,048 flat tokens, 64 rows) and
decode step (`--max-num-seqs` lanes; the engine's decode block scans 8 of
them) at the cell's pool size, and prints `memory_analysis()` of each beside
the weights and the pool. The pool is the configuration's `--num-pages`, or,
auto-sized, what `engine.py:_auto_num_pages` would give: 85% of 15.75 GiB
less the weights and a 512 MiB reserve.

These are the model's forwards (`models/llama.py`, `models/moe.py`) with the
serving kernels inside, not the engine's own jitted closures, which exist
only inside a live JaxEngine: sampling and the block's scan are left out.
What the compiler refuses here costs no chip time. A compile that passes is
not a chip run.
"""

from __future__ import annotations

import argparse
import functools
import importlib
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.dirname(HERE))

PAGE = 64
HBM_LIMIT = 16_909_336_064  # bytes_limit of a v5e chip (my chip runs, PR 21)
CHUNK, MIXED_TOKENS, MIXED_ROWS = 1024, 2048, 64


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("configs", nargs="*")
    ap.add_argument("--pages", type=int, default=None, help="override the pool size")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from dynamo_tpu.ops.kv_quant import alloc_kv_store
    from worker_entry import build_model_config, load_config

    jax.config.update("jax_enable_compilation_cache", False)
    # the dispatch gate asks the backend which attention to take: steer it
    # the way a one-chip TPU engine sees it (as tests/test_tpu_compile.py does)
    jax.default_backend = lambda: "tpu"
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def on_chip(tree):
        return jax.tree.map(lambda x: sds(x.shape, x.dtype), tree)

    names = args.configs or sorted(
        f[:-5] for f in os.listdir(os.path.join(HERE, "configs")) if f.endswith(".json"))
    i32 = jnp.int32
    worst = 0
    for name in names:
        cfg_file = load_config(os.path.join(HERE, "configs", f"{name}.json"), False)
        cfg = build_model_config(cfg_file)
        model = importlib.import_module(cfg_file["dataclass"].partition(":")[0])
        wargs = cfg_file["worker_args"]

        def warg(flag, default):
            return int(wargs[wargs.index(flag) + 1]) if flag in wargs else default

        lanes, max_len = warg("--max-num-seqs", 64), warg("--max-model-len", 8192)
        params = on_chip(jax.eval_shape(
            functools.partial(model.init_params, cfg), jax.random.PRNGKey(0)))
        weight_bytes = sum(
            x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
        page_bytes = 2 * cfg.num_layers * PAGE * cfg.num_kv_heads * cfg.head_dim * 2
        pages = args.pages or warg("--num-pages", 0) or (
            int(HBM_LIMIT * 0.85) - weight_bytes - 512 * 2**20) // page_bytes
        kv = on_chip(jax.eval_shape(lambda: alloc_kv_store(
            cfg.num_layers, pages + 1, PAGE, cfg.num_kv_heads, cfg.head_dim,
            cfg.dtype, "none")))
        table = max_len // PAGE + 1
        print(json.dumps({"config": name, "weights_bytes": weight_bytes,
                          "pages": pages, "pool_bytes": pages * page_bytes,
                          "lanes": lanes}), flush=True)

        def decode(params, tokens, positions, kv_k, kv_v, tables, seq_lens):
            return model.decode_forward(
                params, cfg, tokens, positions, kv_k, kv_v, tables, seq_lens)

        def prefill(params, tokens, positions, kv_k, kv_v, tables, ctx, last):
            return model.prefill_forward_batched(
                params, cfg, tokens, positions, kv_k, kv_v, tables, ctx, last)

        def mixed(params, tokens, positions, row_ids, kv_k, kv_v, tables,
                  starts, lens, ctx, last):
            return model.ragged_forward(
                params, cfg, tokens, positions, row_ids, kv_k, kv_v, tables,
                starts, lens, ctx, last)

        N, R = MIXED_TOKENS, MIXED_ROWS
        programs = {
            "decode_step": (decode, (3, 4), (
                params, sds((lanes,), i32), sds((lanes,), i32), kv, kv,
                sds((lanes, table), i32), sds((lanes,), i32))),
            "prefill_chunk": (prefill, (3, 4), (
                params, sds((1, CHUNK), i32), sds((1, CHUNK), i32), kv, kv,
                sds((1, table), i32), sds((1,), i32), sds((1,), i32))),
            "mixed_step": (mixed, (4, 5), (
                params, sds((N,), i32), sds((N,), i32), sds((N,), i32), kv, kv,
                sds((R, table), i32), sds((R,), i32), sds((R,), i32),
                sds((R,), i32), sds((R,), i32))),
        }
        for what, (fn, donate, shapes) in programs.items():
            try:
                compiled = jax.jit(fn, donate_argnums=donate).lower(*shapes).compile()
            except Exception as e:  # noqa: BLE001 — the compiler's refusal is the result
                print(json.dumps({"config": name, "program": what,
                                  "refused": str(e)[-600:]}), flush=True)
                worst = HBM_LIMIT + 1
                continue
            mem = compiled.memory_analysis()
            need = mem.argument_size_in_bytes + mem.temp_size_in_bytes
            worst = max(worst, need)
            print(json.dumps({
                "config": name, "program": what,
                "pallas_kernel_inside": "tpu_custom_call" in compiled.as_text(),
                "argument_bytes": mem.argument_size_in_bytes,
                "temp_bytes": mem.temp_size_in_bytes,
                "output_bytes": mem.output_size_in_bytes,
                "alias_bytes": mem.alias_size_in_bytes,
                "argument_plus_temp_bytes": need,
                "fits_16_9_GB": need < HBM_LIMIT,
            }), flush=True)
    return 0 if worst < HBM_LIMIT else 1


if __name__ == "__main__":
    sys.exit(main())
