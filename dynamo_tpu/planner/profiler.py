"""Pre-deployment SLA profiler: sweep the JAX engine, write planner profiles.

Role of the reference's profiler (benchmarks/profiler/profile_sla.py +
docs/benchmarks/pre_deployment_profiling.md): measure, per chip, (a)
prefill throughput and TTFT across input lengths and (b) decode ITL and
throughput across (kv-cache usage, context length) operating points, then
write npz files in the exact raw_data layout the planner's interpolators
load (selected_prefill_interpolation/raw_data.npz and
selected_decode_interpolation/raw_data.npz, field names per
perf_interpolation.py — "gpu" in names reads "chip"). Every timed region
ends in a fence.
"""

from __future__ import annotations

import os
import time
from typing import Dict, List, Optional, Sequence

import numpy as np


def _fence(x) -> None:
    import jax

    jax.block_until_ready(x)


def profile_prefill(
    cfg, isl_grid: Sequence[int], page: int = 64, num_chips: int = 1
) -> Dict[str, np.ndarray]:
    """Time single-sequence prefill at each ISL; returns the planner's
    prefill raw_data dict (ttft in ms, throughput in tok/s/chip)."""
    import jax
    import jax.numpy as jnp

    from ..engine.kv_cache import alloc_kv_arrays
    from ..models import llama

    isl_grid = sorted(isl_grid)
    max_isl = isl_grid[-1]
    pages_per_seq = (max_isl + page - 1) // page
    num_pages = pages_per_seq + 1
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    kv_k, kv_v = alloc_kv_arrays(
        cfg.num_layers, num_pages, page, cfg.num_kv_heads, cfg.head_dim, cfg.dtype
    )
    page_table = jnp.arange(pages_per_seq, dtype=jnp.int32)

    prefill = jax.jit(
        lambda p, kk, kv, t, pos, li: llama.prefill_forward(
            p, cfg, t, pos, kk, kv, page_table, jnp.asarray(0, jnp.int32), li
        ),
        donate_argnums=(1, 2),
    )

    ttft_ms: List[float] = []
    thpt: List[float] = []
    rng = np.random.RandomState(0)
    for isl in isl_grid:
        toks = jnp.asarray(rng.randint(3, cfg.vocab_size - 1, size=isl), jnp.int32)
        pos = jnp.arange(isl, dtype=jnp.int32)
        li = jnp.asarray(isl - 1, jnp.int32)
        # compile + warmup
        logits, kv_k, kv_v = prefill(params, kv_k, kv_v, toks, pos, li)
        _fence(logits)
        reps = 3
        t0 = time.perf_counter()
        for _ in range(reps):
            logits, kv_k, kv_v = prefill(params, kv_k, kv_v, toks, pos, li)
        _fence(logits)
        dt = (time.perf_counter() - t0) / reps
        ttft_ms.append(dt * 1000.0)
        thpt.append(isl / dt / num_chips)

    return {
        "prefill_isl": np.asarray(isl_grid, np.float64),
        "prefill_ttft": np.asarray(ttft_ms, np.float64),
        "prefill_thpt_per_gpu": np.asarray(thpt, np.float64),
    }


def profile_decode(
    cfg,
    context_grid: Sequence[int],
    kv_usage_grid: Sequence[float],
    max_kv_tokens: int,
    page: int = 64,
    num_chips: int = 1,
    decode_steps: int = 8,
) -> Dict[str, np.ndarray]:
    """Time batched decode at each (kv_usage, context) operating point
    (batch = kv_usage * max_kv_tokens / context); returns the planner's
    decode raw_data dict (itl in ms, throughput in tok/s/chip)."""
    import jax
    import jax.numpy as jnp

    from ..engine.kv_cache import alloc_kv_arrays
    from ..engine.sampling import SamplingParams, sample
    from ..models import llama

    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    num_pages = max_kv_tokens // page + 1
    kv_k, kv_v = alloc_kv_arrays(
        cfg.num_layers, num_pages, page, cfg.num_kv_heads, cfg.head_dim, cfg.dtype
    )

    def _decode(params, kv_k, kv_v, tokens, positions, page_tables, seq_lens, samp, key):
        lg, kv_k, kv_v = llama.decode_forward(
            params, cfg, tokens, positions, kv_k, kv_v, page_tables, seq_lens
        )
        return sample(lg, samp, key), kv_k, kv_v

    decode_step = jax.jit(_decode, donate_argnums=(1, 2))

    xs: List[float] = []
    ys: List[float] = []
    itl: List[float] = []
    thpt: List[float] = []
    for ctx in context_grid:
        pages_per_seq = (ctx + page - 1) // page
        for usage in kv_usage_grid:
            B = max(1, int(usage * max_kv_tokens / ctx))
            if B * pages_per_seq >= num_pages:
                B = (num_pages - 1) // pages_per_seq
                if B < 1:
                    continue
            pt = (
                1 + np.arange(B)[:, None] * pages_per_seq + np.arange(pages_per_seq)
            ) % num_pages
            page_tables = jnp.asarray(pt, jnp.int32)
            tokens = jnp.zeros((B,), jnp.int32)
            positions = jnp.full((B,), ctx - 1, jnp.int32)
            seq_lens = jnp.full((B,), ctx, jnp.int32)
            samp = SamplingParams.full(B, temperature=0.0)
            key = jax.random.PRNGKey(1)
            tokens, kv_k, kv_v = decode_step(
                params, kv_k, kv_v, tokens, positions, page_tables, seq_lens, samp, key
            )
            _fence(tokens)
            t0 = time.perf_counter()
            for i in range(decode_steps):
                key = jax.random.fold_in(key, i)
                tokens, kv_k, kv_v = decode_step(
                    params, kv_k, kv_v, tokens, positions, page_tables, seq_lens,
                    samp, key,
                )
            _fence(tokens)
            dt = (time.perf_counter() - t0) / decode_steps
            xs.append(usage)
            ys.append(float(ctx))
            itl.append(dt * 1000.0)
            thpt.append(B / dt / num_chips)

    return {
        "x_kv_usage": np.asarray(xs, np.float64),
        "y_context_length": np.asarray(ys, np.float64),
        "z_itl": np.asarray(itl, np.float64),
        "z_thpt_per_gpu": np.asarray(thpt, np.float64),
        "max_kv_tokens": np.asarray([max_kv_tokens], np.float64),
    }


def write_profiles(
    output_dir: str,
    prefill_raw: Dict[str, np.ndarray],
    decode_raw: Dict[str, np.ndarray],
) -> None:
    """Write both npz files in the directory layout the interpolators read."""
    for sub, raw in (
        ("selected_prefill_interpolation", prefill_raw),
        ("selected_decode_interpolation", decode_raw),
    ):
        d = os.path.join(output_dir, sub)
        os.makedirs(d, exist_ok=True)
        np.savez(os.path.join(d, "raw_data.npz"), **raw)


def main(argv: Optional[Sequence[str]] = None) -> None:
    import argparse

    ap = argparse.ArgumentParser(description="SLA profiler sweep (JAX engine)")
    ap.add_argument("--model", default="tiny")
    ap.add_argument("--output-dir", required=True)
    ap.add_argument("--isl-grid", type=int, nargs="+", default=[128, 512, 1024, 2048, 4096])
    ap.add_argument("--context-grid", type=int, nargs="+", default=[256, 1024, 4096])
    ap.add_argument(
        "--kv-usage-grid", type=float, nargs="+", default=[0.1, 0.25, 0.5, 0.75, 0.95]
    )
    ap.add_argument("--max-kv-tokens", type=int, default=1 << 16)
    ap.add_argument("--num-chips", type=int, default=1)
    args = ap.parse_args(argv)

    from ..models import llama

    cfgs = {
        "tiny": llama.LlamaConfig.tiny,
        "llama3-3b": llama.LlamaConfig.llama3_2_3b,
        "llama3-8b": llama.LlamaConfig.llama3_8b,
    }
    cfg = cfgs[args.model]()
    prefill_raw = profile_prefill(cfg, args.isl_grid, num_chips=args.num_chips)
    decode_raw = profile_decode(
        cfg, args.context_grid, args.kv_usage_grid, args.max_kv_tokens,
        num_chips=args.num_chips,
    )
    write_profiles(args.output_dir, prefill_raw, decode_raw)
    print(f"profiles written to {args.output_dir}")


if __name__ == "__main__":
    main()
