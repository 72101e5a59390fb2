"""Ask the TPU compiler, before any chip run: do the main-path kernels
compile for a v5e at llama3-3b widths?

Interpret-mode tests (test_pallas_attention.py, test_ragged_attention.py)
check the kernels' arithmetic; they cannot see what Mosaic refuses (SMEM
budgets, unaligned slices, ops it cannot legalize) or what does not fit the
device. Here the installed TPU compiler compiles for a chip that is
DESCRIBED, not attached: nothing runs, so nothing here is a chip result.

The topology is described inside a module-scoped fixture — never at import,
in a skipif or in conftest.py — and every compile happens in this test
process: only one process may hold the TPU library, so under xdist exactly
the worker that is handed this file loads it.
"""

import functools
import math
import os
import re
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from dynamo_tpu.models import llama
from dynamo_tpu.ops import paged_attention as ops
from dynamo_tpu.ops.kv_quant import QuantKV, alloc_kv_store, kv_layer

# llama3-3b attention widths at the worker's default serving shape
H, KH, D = 24, 8, 128
PAGE, B, TABLE, POOL, LAYERS = 64, 64, 128, 1024, 28
HBM_BYTES = 16 * 2**30  # one v5e chip
# the benchmark's cells: Mixtral-8x7B widths, 2 layers, --num-pages 8192
# (at this file's lanes and table), and Mistral-7B widths, 16 layers, the
# auto pool's 1,377 pages and a spare, at the cell's own 32 lanes of at most
# 4,096 positions: 64 pages, the width of the table the decode block carries
# (max_pages_per_seq) and the mixed step's one width
MIXTRAL = {"H": 32, "KH": 8, "layers": 2, "pool": 8193}
CELLS = {
    "mixtral": MIXTRAL,
    "mistral": {"H": 32, "KH": 8, "layers": 16, "pool": 1378, "B": 32, "table": 64},
    # the state-space family's one attention layer: 32 query heads over 2
    # key-value heads (a group of 16, KH*D = 256), beside the auto pool of
    # some 58,000 pages that 9.3 GB of weights and the state store leave
    "nemotron": {"H": 32, "KH": 2, "layers": 1, "pool": 58001, "B": 32, "table": 64},
}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture
def tpu_gate(monkeypatch):
    """Steer the dispatch gate the way a one-chip TPU engine would see it:
    the process here is on the CPU backend, the program is compiled for the
    described chip."""
    monkeypatch.delenv("DYNAMO_TPU_PAGED_ATTN", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _shapes(one_chip):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return sds


def _pool(sds, mode="none", layers=LAYERS, pool=POOL, kh=KH):
    """K (or V) operand as the ops take it: the WHOLE lane-dense pool
    [L, pages, rows, KH*D] (fp array or QuantKV of shapes) and a middle
    layer's index."""
    if mode == "none":
        return kv_layer(sds((layers, pool, PAGE, kh * D), jnp.bfloat16), layers // 2)
    bits = {"int8": 8, "int4": 4}[mode]
    rows = PAGE // 2 if bits == 4 else PAGE
    return kv_layer(
        QuantKV(
            sds((layers, pool, rows, kh * D), jnp.int8),
            sds((layers, pool, kh), jnp.float32), bits, PAGE,
        ),
        layers // 2,
    )


def _op_cases(sds, mode, H=H, KH=KH, layers=LAYERS, pool=POOL, B=B, table=TABLE):
    """(name, fn, args) for the three serving attention ops through the
    dispatch gate, with a `mode` KV pool."""
    k = _pool(sds, mode, layers, pool, KH)
    v = _pool(sds, mode, layers, pool, KH)
    i32 = jnp.int32
    q1 = sds((B, H, D), jnp.bfloat16)
    tables = sds((B, table), i32)
    lens = sds((B,), i32)
    T = 128
    Bp = 8
    N = 512
    R = 128
    return {
        "decode": (ops.paged_attention_decode, (q1, k, v, tables, lens)),
        "prefill_batched": (
            ops.prefill_attention_batched,
            (sds((Bp, T, H, D), jnp.bfloat16), k, v, sds((Bp, T), i32),
             sds((Bp, table), i32), sds((Bp,), i32), sds((Bp,), i32)),
        ),
        "ragged": (
            ops.ragged_attention,
            (sds((N, H, D), jnp.bfloat16), k, v, sds((R, table), i32),
             sds((R,), i32), sds((R,), i32), sds((R,), i32)),
        ),
    }


OPS = ("decode", "prefill_batched", "ragged")


@pytest.mark.parametrize("op", OPS)
def test_fp_kernels_compile_for_v5e(op, one_chip, no_persistent_cache, tpu_gate):
    fn, args = _op_cases(_shapes(one_chip), "none")[op]
    compiled = _compile(fn, *args)
    assert "tpu_custom_call" in compiled.as_text(), (
        f"{op}: the gate did not put the Pallas kernel into the program"
    )


@pytest.mark.parametrize("cell", list(CELLS))
@pytest.mark.parametrize("op", OPS)
def test_whole_pool_kernels_compile_at_the_cell_size(
    op, cell, one_chip, no_persistent_cache, tpu_gate
):
    """A cell's widths, depth, pool and table (Mixtral: 8,192 pages, 4.3 GB
    of K and V; Mistral: 16 layers of 1,377): each kernel takes the whole
    pool operand, and the program holds no temporary the size of even one
    layer of one pool (1.07 GB, 0.18 GB): before PR 26 the wrappers' slice +
    reshape made three such copies."""
    at = CELLS[cell]
    fn, args = _op_cases(_shapes(one_chip), "none", **at)[op]
    compiled = _compile(fn, *args)
    assert "tpu_custom_call" in compiled.as_text()
    layer_bytes = at["pool"] * PAGE * at["KH"] * D * 2
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < layer_bytes // 8, f"{op}: {temp / 2**20:.0f} MiB of temporaries"


def _pool_sized_ops(lowered, layer_numel, tail=()):
    """Operation names of a lowered program whose result holds at least
    one layer of the pool (and, where `tail` is given, ends in those
    dimensions), counted (stablehlo, before any backend)."""
    import collections

    import numpy as np
    from jaxlib.mlir import ir

    found = collections.Counter()

    def visit(op):
        for r in op.results:
            if isinstance(r.type, ir.RankedTensorType) and (
                int(np.prod(r.type.shape)) >= layer_numel
                and tuple(r.type.shape[len(r.type.shape) - len(tail):]) == tail
            ):
                found[op.name] += 1
        return ir.WalkResult.ADVANCE

    lowered.compiler_ir().operation.walk(visit)
    return dict(found)


@pytest.mark.parametrize("program", ("decode", "ragged"))
def test_lowered_programs_touch_the_pool_only_to_update_it(program):
    """Counted in the lowered text, on the CPU: the decode and the mixed
    (ragged) step produce nothing the size of a layer of the pool but the
    in-place KV scatters, one for K and one for V in each layer. A
    per-layer slice or reshape of the pool would show here."""
    cfg = llama.LlamaConfig.tiny(dtype=jnp.float32)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    pages, page = 257, 8
    kv = alloc_kv_store(
        cfg.num_layers, pages, page, cfg.num_kv_heads, cfg.head_dim,
        cfg.dtype, "none",
    )
    assert kv.shape == (cfg.num_layers, pages, page, cfg.num_kv_heads * cfg.head_dim)
    i32 = jnp.int32
    n, r = 32, 4
    if program == "decode":
        def step(params, kv_k, kv_v):
            return llama.decode_forward(
                params, cfg, jnp.zeros((r,), i32), jnp.zeros((r,), i32),
                kv_k, kv_v, jnp.ones((r, 5), i32), jnp.ones((r,), i32),
            )
    else:
        def step(params, kv_k, kv_v):
            return llama.ragged_forward(
                params, cfg, jnp.zeros((n,), i32), jnp.zeros((n,), i32),
                jnp.zeros((n,), i32), kv_k, kv_v, jnp.ones((r, 5), i32),
                jnp.arange(r, dtype=i32) * 8, jnp.ones((r,), i32),
                jnp.zeros((r,), i32), jnp.arange(r, dtype=i32) * 8,
            )
    lowered = jax.jit(step, donate_argnums=(1, 2)).lower(params, kv, kv)
    found = _pool_sized_ops(lowered, kv[0].size)
    assert found == {"stablehlo.scatter": 2 * cfg.num_layers}, found


@pytest.mark.parametrize("mode", ("int8", "int4"))
@pytest.mark.parametrize("op", OPS)
def test_quantized_kv_routes_to_xla_and_compiles(
    op, mode, one_chip, no_persistent_cache, tpu_gate
):
    """The decision for --kv-quant on a TPU: the one gate routes quantized
    pools to the XLA gather+dequant path on every op, and that path
    compiles at a real pool size (the in-kernel dequant does not: next
    test)."""
    fn, args = _op_cases(_shapes(one_chip), mode)[op]
    assert not ops._pallas_eligible(128, quantized=True)
    compiled = _compile(fn, *args)
    assert "tpu_custom_call" not in compiled.as_text()


@pytest.mark.parametrize("mode", ("int8", "int4"))
def test_in_kernel_dequant_is_still_refused(mode, one_chip, no_persistent_cache):
    """Why the gate rule exists. When the TPU compiler starts accepting the
    quantized kernels this fails: then drop the `quantized` clause of
    ops/paged_attention._pallas_eligible."""
    from dynamo_tpu.ops.pallas_ragged_attention import (
        ragged_paged_attention_pallas,
    )

    sds = _shapes(one_chip)
    i32 = jnp.int32
    args = (
        sds((512, H, D), jnp.bfloat16), _pool(sds, mode), _pool(sds, mode),
        sds((128, TABLE), i32), sds((128,), i32), sds((128,), i32),
        sds((128,), i32),
    )
    with pytest.raises(Exception, match="smem|legalize|Mosaic"):
        _compile(ragged_paged_attention_pallas, *args)


def test_full_depth_decode_step_fits_one_chip(
    one_chip, no_persistent_cache, tpu_gate
):
    """The whole llama3-3b decode step — 28 layers, bf16 weights, a
    1,024-page pool — with the decode kernel inside, within 16 GiB."""
    cfg = llama.LlamaConfig.llama3_2_3b()
    sds = _shapes(one_chip)

    def on_chip(tree):
        return jax.tree.map(lambda x: sds(x.shape, x.dtype), tree)

    params = on_chip(jax.eval_shape(
        functools.partial(llama.init_params, cfg), jax.random.PRNGKey(0)
    ))
    kv = on_chip(jax.eval_shape(lambda: alloc_kv_store(
        cfg.num_layers, POOL + 1, PAGE, cfg.num_kv_heads, cfg.head_dim,
        cfg.dtype, "none",
    )))
    i32 = jnp.int32

    def step(params, tokens, positions, kv_k, kv_v, tables, seq_lens):
        return llama.decode_forward(
            params, cfg, tokens, positions, kv_k, kv_v, tables, seq_lens
        )

    compiled = jax.jit(step, donate_argnums=(3, 4)).lower(
        params, sds((B,), i32), sds((B,), i32), kv, kv,
        sds((B, TABLE), i32), sds((B,), i32),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    need = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert need < HBM_BYTES, f"decode step needs {need / 2**30:.2f} GiB"


def test_tp4_decode_step_shards_over_a_four_chip_mesh(
    topo, no_persistent_cache, tpu_gate
):
    """The --tp-size 4 path, compiled over a Mesh of the four described
    chips with the worker's own shardings (depth cut to four layers: the
    sharding rules are per layer). The gate's mesh rule takes XLA
    attention; each chip gets a quarter of the weights and of the pool, and
    the compiler put the tp all-reduces in."""
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec

    from dynamo_tpu.parallel.mesh import (
        LlamaShardings,
        ParallelConfig,
        build_mesh,
    )

    import dataclasses

    cfg = dataclasses.replace(llama.LlamaConfig.llama3_2_3b(), num_layers=4)
    mesh = build_mesh(ParallelConfig(tp_size=4), devices=list(topo.devices))
    sh = LlamaShardings(mesh)
    repl = NamedSharding(mesh, PartitionSpec())
    assert not ops.mesh_allows_kernels(mesh)

    params = jax.eval_shape(
        functools.partial(llama.init_params, cfg), jax.random.PRNGKey(0)
    )
    specs = sh.param_shardings()
    specs["lm_head"] = None  # tied embeddings: no separate head leaf
    params = jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        params, specs,
    )
    kv = jax.ShapeDtypeStruct(
        (cfg.num_layers, POOL + 1, PAGE, cfg.num_kv_heads * cfg.head_dim),
        cfg.dtype, sharding=sh.kv_sharding(),
    )
    i32 = jnp.int32

    def r(shape):
        return jax.ShapeDtypeStruct(shape, i32, sharding=repl)

    def step(params, tokens, positions, kv_k, kv_v, tables, seq_lens):
        with ops.attention_scope(ops.mesh_allows_kernels(mesh)):
            return llama.decode_forward(
                params, cfg, tokens, positions, kv_k, kv_v, tables, seq_lens
            )

    compiled = jax.jit(step, donate_argnums=(3, 4)).lower(
        params, r((B,)), r((B,)), kv, kv, r((B, TABLE)), r((B,)),
    ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" not in text
    assert "all-reduce" in text
    total = sum(
        int(np.prod(x.shape)) * x.dtype.itemsize
        for x in jax.tree.leaves((params, kv, kv))
    )
    per_device = compiled.memory_analysis().argument_size_in_bytes
    assert 0.24 < per_device / total < 0.27, per_device / total


def _cell_models():
    """The benchmark's two cells as (module, config, pool pages): Mixtral
    widths at 2 layers beside --num-pages 8192, Mistral-7B widths at 16
    layers beside the auto-sized pool the worker finds (PERF.md, section
    4)."""
    import dataclasses

    from dynamo_tpu.models import moe

    return {
        "mixtral-8x7b-d2": (moe, dataclasses.replace(
            moe.MoeConfig.mixtral_8x7b(), num_layers=MIXTRAL["layers"],
            capacity_factor=4.0,
        ), MIXTRAL["pool"]),
        "mistral-7b-d16": (llama, llama.LlamaConfig(
            vocab_size=32768, hidden_size=4096, intermediate_size=14336,
            num_layers=16, num_heads=32, num_kv_heads=8, head_dim=128,
            rope_theta=1e6, rms_norm_eps=1e-5, max_position=32768,
        ), 1377),
    }


def _q_tile_operands(lowered_text, kv_heads, lanes_wide):
    """The tile counts of the ragged kernel's q operands in a lowered
    program: tensor<tiles x KH x 16 x G*D x bf16>."""
    return {
        int(m) for m in re.findall(
            rf"tensor<(\d+)x{kv_heads}x16x{lanes_wide}xbf16>", lowered_text)
    }


# the three cells' attention: (H, KH, D, attention layers, pool pages)
CELL_ATTENTION = {
    "mistral-7b-d16": (32, 8, 128, 16, 1378),
    "mixtral-8x7b-d2": (32, 8, 128, 2, 8193),
    "qwen3-next-80b-a3b-ep4-d8": (16, 2, 256, 2, 4096),
    "nemotron-3-super-120b-a12b-ep4-d11": (32, 2, 128, 1, 58001),
}


@pytest.mark.parametrize("cell", list(CELL_ATTENTION))
def test_the_cells_mixed_attention_is_two_kernels_on_a_prompts_tiles(
    cell, one_chip, no_persistent_cache, tpu_gate
):
    """A mixed step's attention call at each cell's widths and pool, 40
    rows, the ONE table width of 65, every token bucket: the paged decode
    kernel over the 40 rows as lanes and the ragged kernel over a grid of
    (M + 15 x max_prefill_batch) / 16 q tiles, both compiled for the
    described v5e (their tables in SMEM side by side with nothing else)."""
    H, KH, D, layers, pool = CELL_ATTENTION[cell]
    sds = _shapes(one_chip)
    i32 = jnp.int32
    kv = kv_layer(sds((layers, pool, PAGE, KH * D), jnp.bfloat16), layers - 1)
    rows, width = 40, 4096 // PAGE + 1
    for tokens, tiles in ((256, 24), (512, 40), (1024, 72), (2048, 136)):
        assert ops.ragged_tiles(tokens, rows, 16, 8) == tiles
        lowered = jax.jit(
            functools.partial(ops.ragged_attention, long_rows=8)
        ).lower(
            sds((tokens, H, D), jnp.bfloat16), kv, kv, sds((rows, width), i32),
            sds((rows,), i32), sds((rows,), i32), sds((rows,), i32),
        )
        assert _q_tile_operands(lowered.as_text(), KH, H // KH * D) == {tiles}
        compiled = lowered.compile()
        assert compiled.as_text().count("tpu_custom_call") == 2


@pytest.mark.parametrize("tokens", (256, 2048))
@pytest.mark.parametrize("cell", ("mixtral-8x7b-d2", "mistral-7b-d16"))
def test_the_cells_mixed_step_family_compiles_with_its_kernels(
    cell, tokens, one_chip, no_persistent_cache, tpu_gate
):
    """The smallest and the largest member of both benchmark cells' lean
    mixed_step family: the cell's widths, depth and pool, 40 rows, the ONE
    table width of 65, a COMPACT token axis of the bucket's length, both
    attention kernels inside (the paged decode kernel for the one-token
    rows, the ragged kernel on the q-tile layout of a prefill batch of 8:
    24 and 136 tiles) and, on the routed family, the grouped expert
    matmul, within one v5e. No temporary the size of an expert matrix
    (0.94 GB): the expert stacks reach the kernel whole, a slice of one
    would be a copy of it; the dense family's largest temporaries are the
    bucket's activations."""
    from dynamo_tpu.engine.bucketing import mixed_row_bucket
    from dynamo_tpu.engine.config import EngineConfig

    mod, cfg, pool = _cell_models()[cell]
    sds = _shapes(one_chip)

    def on_chip(tree):
        return jax.tree.map(lambda x: sds(x.shape, x.dtype), tree)

    params = on_chip(jax.eval_shape(
        functools.partial(mod.init_params, cfg), jax.random.PRNGKey(0)
    ))
    kv = on_chip(jax.eval_shape(lambda: alloc_kv_store(
        cfg.num_layers, pool, PAGE, cfg.num_kv_heads, cfg.head_dim,
        cfg.dtype, "none",
    )))
    i32 = jnp.int32
    engine_config = EngineConfig(model="tiny", max_num_seqs=32)
    rows = mixed_row_bucket(engine_config)
    assert rows == 40  # 32 lanes and a prefill batch of 8, whole sublanes
    width = 4096 // PAGE + 1

    def step(params, kv_k, kv_v, tokens, positions, row_ids, tables,
             row_starts, row_lens, ctx_lens, last_flat):
        return mod.ragged_forward(
            params, cfg, tokens, positions, row_ids, kv_k, kv_v, tables,
            row_starts, row_lens, ctx_lens, last_flat,
            long_rows=engine_config.max_prefill_batch,
        )

    lowered = jax.jit(step, donate_argnums=(1, 2)).lower(
        params, kv, kv, sds((tokens,), i32), sds((tokens,), i32),
        sds((tokens,), i32), sds((rows, width), i32), sds((rows,), i32),
        sds((rows,), i32), sds((rows,), i32), sds((rows,), i32),
    )
    group_lanes = cfg.num_heads // cfg.num_kv_heads * cfg.head_dim
    assert _q_tile_operands(
        lowered.as_text(), cfg.num_kv_heads, group_lanes
    ) == {ops.ragged_tiles(tokens, rows, 16, engine_config.max_prefill_batch)}
    compiled = lowered.compile()
    # per layer: the two attention kernels and, routed, three grouped
    # matmuls
    kernels = 5 if hasattr(cfg, "num_experts") else 2
    assert compiled.as_text().count("tpu_custom_call") >= kernels * cfg.num_layers
    mem = compiled.memory_analysis()
    need = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert need < HBM_BYTES, f"mixed step needs {need / 2**30:.2f} GiB"
    expert_matrix = 8 * cfg.hidden_size * cfg.intermediate_size * 2
    assert mem.temp_size_in_bytes < expert_matrix, (
        f"{mem.temp_size_in_bytes / 2**20:.0f} MiB of temporaries"
    )


@pytest.mark.parametrize("program", ("decode", "mixed_256", "mixed_2048"))
def test_the_state_space_familys_steps_compile_at_the_cell_size(
    program, one_chip, no_persistent_cache, tpu_gate
):
    """models/nemotron_h.py at its cell's configuration (11 layers, 128 of
    512 experts held, 9.3 GB of weights), its state store of 33 slots and
    the auto pool, for the described v5e: the decode step (32 lanes) and
    the smallest and the largest member of the mixed_step family (40 rows,
    a prefill batch of 8). Each routed layer brings two grouped matmuls
    (k/n tiles of 1,024 and 896: 2,688 is 21 x 128), the one attention layer
    one kernel a decode step and two a mixed step. The decode step updates
    the state in the donated store: its temporaries stay under one layer's
    state (138 MB at 33 slots); a mixed step's are those of a group of
    gathered rows whatever its 40 rows hold (no array of 40 rows' states is
    in the program: ops/row_recurrence.py), and the whole fits the chip."""
    from dynamo_tpu.models import nemotron_h

    sds = _shapes(one_chip)
    cfg, params, cache, kv_v = _stateful_cell(
        sds, nemotron_h, "nemotron-3-super-120b-a12b-ep4-d11", 58001)
    i32 = jnp.int32
    if program == "decode":
        def step(params, tokens, positions, kv_k, kv_v, tables, seq_lens):
            return nemotron_h.decode_forward(
                params, cfg, tokens, positions, kv_k, kv_v, tables, seq_lens)

        compiled = jax.jit(step, donate_argnums=(3, 4)).lower(
            params, sds((32,), i32), sds((32,), i32), cache, kv_v,
            sds((32, 64), i32), sds((32,), i32)).compile()
        kernels = 5 * 2 + 1
    else:
        tokens, rows = int(program.split("_")[1]), 40

        def step(params, tokens, positions, row_ids, kv_k, kv_v, tables,
                 row_starts, row_lens, ctx_lens, last_flat):
            return nemotron_h.ragged_forward(
                params, cfg, tokens, positions, row_ids, kv_k, kv_v, tables,
                row_starts, row_lens, ctx_lens, last_flat, long_rows=8)

        compiled = jax.jit(step, donate_argnums=(4, 5)).lower(
            params, sds((tokens,), i32), sds((tokens,), i32),
            sds((tokens,), i32), cache, kv_v, sds((rows, 65), i32),
            sds((rows,), i32), sds((rows,), i32), sds((rows,), i32),
            sds((rows,), i32)).compile()
        kernels = 5 * 2 + 2
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == kernels
    # a mixed step gathers a prefill batch's states, not its 40 rows': the
    # one-token rows are stepped over the lanes in the store
    assert "f32[40,128,64,128]" not in text
    if program == "mixed_256":
        # ... and both of the store's updates a layer (the lanes' slice
        # written back, the gathered rows' scatter) are in place: what a
        # step moves whole is activations and the convolution's tails,
        # under 10 MiB each, where a layer's states are 132
        assert not _hbm_copies(text, 16 * 2**20)
    mem = compiled.memory_analysis()
    need = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert need < HBM_BYTES, f"{program} needs {need / 2**30:.2f} GiB"
    one_layers_state = 33 * 128 * 64 * 128 * 4
    limit = one_layers_state if program == "decode" else 12 * one_layers_state
    assert mem.temp_size_in_bytes < limit, (
        f"{mem.temp_size_in_bytes / 2**20:.0f} MiB of temporaries")


@pytest.mark.parametrize("program", ("block_of_8", "mixed_256", "mixed_2048"))
def test_the_window_familys_steps_compile_at_the_published_widths(
    program, one_chip, no_persistent_cache, tpu_gate
):
    """models/exaone_moe.py at its cell's configuration (8 layers at the
    published widths, 16 of 128 experts held, 12 GB of weights), its rings
    of 33 slots and the auto pool of the two full layers, for the described
    v5e: a decode block's scan of 8 decode steps (32 lanes) and the smallest
    and the largest member of the mixed_step family (40 rows, a prefill batch
    of 8). Each sparse layer brings three grouped matmuls, a
    full layer one kernel a decode step and two a mixed step; the window
    layers are plain XLA. The decode step writes the lanes' slots in the
    donated rings: its temporaries stay under the rings themselves (104 MB);
    a mixed step's window attention is scores of [blocks, heads, 128, 256]
    whatever a lane's context, and the whole fits the chip."""
    from dynamo_tpu.models import exaone_moe

    sds = _shapes(one_chip)
    cfg, params, cache, kv_v = _stateful_cell(
        sds, exaone_moe, "k-exaone-236b-a23b-ep8-d8", 3381)
    assert cache.state.shape == cache.conv.shape == (6, 33, 128, 1024)
    assert cache.pages.shape[0] == kv_v.shape[0] == 2
    i32 = jnp.int32
    if program == "block_of_8":
        def one(params, tokens, positions, kv_k, kv_v, tables, seq_lens):
            return exaone_moe.decode_forward(
                params, cfg, tokens, positions, kv_k, kv_v, tables, seq_lens)

        def block(params, tokens, positions, kv_k, kv_v, tables, seq_lens):
            def body(carry, _):
                tokens, positions, kv_k, kv_v, seq_lens = carry
                logits, kv_k, kv_v = one(
                    params, tokens, positions, kv_k, kv_v, tables, seq_lens)
                return (logits.argmax(-1).astype(i32), positions + 1, kv_k,
                        kv_v, seq_lens + 1), None

            return jax.lax.scan(
                body, (tokens, positions, kv_k, kv_v, seq_lens), None, 8)[0]

        compiled = jax.jit(block, donate_argnums=(3, 4)).lower(
            params, sds((32,), i32), sds((32,), i32), cache, kv_v,
            sds((32, 64), i32), sds((32,), i32)).compile()
        kernels = 7 * 3 + 2
    else:
        tokens, rows = int(program.split("_")[1]), 40

        def step(params, tokens, positions, row_ids, kv_k, kv_v, tables,
                 row_starts, row_lens, ctx_lens, last_flat):
            return exaone_moe.ragged_forward(
                params, cfg, tokens, positions, row_ids, kv_k, kv_v, tables,
                row_starts, row_lens, ctx_lens, last_flat, long_rows=8)

        compiled = jax.jit(step, donate_argnums=(4, 5)).lower(
            params, sds((tokens,), i32), sds((tokens,), i32),
            sds((tokens,), i32), cache, kv_v, sds((rows, 65), i32),
            sds((rows,), i32), sds((rows,), i32), sds((rows,), i32),
            sds((rows,), i32)).compile()
        kernels = 7 * 3 + 2 * 2
    assert compiled.as_text().count("tpu_custom_call") == kernels
    mem = compiled.memory_analysis()
    need = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert need < HBM_BYTES, f"{program} needs {need / 2**30:.2f} GiB"
    rings = 2 * 6 * 33 * 128 * 1024 * 2
    limit = rings if program == "block_of_8" else 1.5 * 2**30
    assert mem.temp_size_in_bytes < limit, (
        f"{mem.temp_size_in_bytes / 2**20:.0f} MiB of temporaries")


@pytest.mark.parametrize(
    "program", ("block_of_8", "mixed_256", "mixed_2048", "prefill_1024"))
def test_the_latent_familys_steps_keep_the_pool_where_it_lies(
    program, one_chip, no_persistent_cache, tpu_gate
):
    """models/mla_moe.py at its cell's configuration (8 layers at the
    published widths, 64 experts, the whole vocabulary, 10.3 GB of weights)
    and its auto pool of 5,300 latent pages of 320 a lane, for the described
    v5e: a decode block's scan of 8 decode steps (32 lanes, every lane
    absorbed), the smallest and the largest member of the mixed_step
    family (40 rows) and a split prefill's chunk of 1,024 tokens. The only
    kernels are the sparse layers' three grouped matmuls; the attention
    walks are XLA loops over blocks of gathered pages, so what is pinned is
    that the pool stays where it lies: the temporaries stay far under the
    pool (3.5 GB; a row of 576 lanes in place of 640 made the compiler copy
    the whole pool into another layout and back, a pool of temporaries a
    step: ops/kv_quant.py), and the whole fits the chip. And what a cold
    start has to compile stays one loop nest a walk and layer (row, tile of
    its tokens, block of its pages: three `while`s, nothing unrolled by row
    or by block): the short rows' absorbed walk in every program over a
    flat axis of rows, the expanded walk only where a row can pass the
    rule's 358 tokens (the mixed step of 256 slots holds none)."""
    from dynamo_tpu.models import mla_moe

    sds = _shapes(one_chip)
    cfg, params, cache, kv_v = _stateful_cell(
        sds, mla_moe, "glm-4.7-flash-d8", 5301)
    assert cache.pages.shape == (8, 5301, PAGE, 640)
    assert cache.state.size == 0 and kv_v.shape == (8, 1, PAGE, 1)
    i32 = jnp.int32
    if program == "block_of_8":
        def block(params, tokens, positions, kv_k, kv_v, tables, seq_lens):
            def body(carry, _):
                tokens, positions, kv_k, kv_v, seq_lens = carry
                logits, kv_k, kv_v = mla_moe.decode_forward(
                    params, cfg, tokens, positions, kv_k, kv_v, tables, seq_lens)
                return (logits.argmax(-1).astype(i32), positions + 1, kv_k,
                        kv_v, seq_lens + 1), None

            return jax.lax.scan(
                body, (tokens, positions, kv_k, kv_v, seq_lens), None, 8)[0]

        compiled = jax.jit(block, donate_argnums=(3, 4)).lower(
            params, sds((32,), i32), sds((32,), i32), cache, kv_v,
            sds((32, 321), i32), sds((32,), i32)).compile()
        nests = {"mla_absorb/": 1}
    elif program == "prefill_1024":
        def chunk(params, tokens, positions, kv_k, kv_v, tables, ctx, last):
            return mla_moe.prefill_forward_batched(
                params, cfg, tokens, positions, kv_k, kv_v, tables, ctx, last)

        compiled = jax.jit(chunk, donate_argnums=(3, 4)).lower(
            params, sds((1, 1024), i32), sds((1, 1024), i32), cache, kv_v,
            sds((1, 321), i32), sds((1,), i32), sds((1,), i32)).compile()
        nests = {"mla_absorb/": 1, "mla_absorb_rows": 3, "mla_expand": 3}
    else:
        tokens, rows = int(program.split("_")[1]), 40
        assert mla_moe.absorbed_row_limit(cfg) == 358
        nests = {"mla_absorb/": 1, "mla_absorb_rows": 3,
                 "mla_expand": 3 if tokens > 358 else 0}

        def step(params, tokens, positions, row_ids, kv_k, kv_v, tables,
                 row_starts, row_lens, ctx_lens, last_flat):
            return mla_moe.ragged_forward(
                params, cfg, tokens, positions, row_ids, kv_k, kv_v, tables,
                row_starts, row_lens, ctx_lens, last_flat, long_rows=8)

        compiled = jax.jit(step, donate_argnums=(4, 5)).lower(
            params, sds((tokens,), i32), sds((tokens,), i32),
            sds((tokens,), i32), cache, kv_v, sds((rows, 321), i32),
            sds((rows,), i32), sds((rows,), i32), sds((rows,), i32),
            sds((rows,), i32)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 7 * 3
    loops = [ln for ln in text.split("\n") if re.search(r"= .* while\(", ln)]
    for scope in ("mla_absorb/", "mla_absorb_rows", "mla_expand"):
        found = sum(scope in ln for ln in loops)
        assert found == 8 * nests.get(scope, 0), (scope, found)
    mem = compiled.memory_analysis()
    need = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert need < HBM_BYTES, f"{program} needs {need / 2**30:.2f} GiB"
    pool = 8 * 5301 * PAGE * 640 * 2
    assert mem.temp_size_in_bytes < pool / 8, (
        f"{mem.temp_size_in_bytes / 2**20:.0f} MiB of temporaries")


@pytest.mark.parametrize("program", ("block_of_8", "mixed_256"))
def test_the_selecting_latent_steps_fit_beside_both_stores(
    program, one_chip, no_persistent_cache, tpu_gate
):
    """models/mla_moe.py at the configuration that SELECTS (GLM-5.2's cut:
    7 layers at the published widths, 16 of 256 experts held, an eighth of
    the vocabulary, 11.0 GB of weights), its latent store and its index-key
    store of 4,600 pages under one set of page ids, for the described v5e:
    a decode block's scan of 8 steps (32 lanes: each scores its context's
    index keys, takes the 2,048 largest and gathers THOSE latent rows) and
    the smallest mixed step (40 rows). The only kernels are the six sparse
    layers' grouped matmuls; both stores stay where they lie (the
    temporaries stay far under the pool: a lane's 2,048 gathered rows, 84
    MB a tile of 32 lanes, and a tile's scores), and the whole fits."""
    from dynamo_tpu.models import mla_moe

    sds = _shapes(one_chip)
    cfg, params, cache, index = _stateful_cell(
        sds, mla_moe, "glm-5.2-ep16-d7", 4601)
    assert cache.pages.shape == (7, 4601, PAGE, 640)
    assert index.shape == (2, 4601, PAGE, 128) and cfg.full_layers == (0, 4)
    i32 = jnp.int32
    if program == "block_of_8":
        def block(params, tokens, positions, kv_k, kv_v, tables, seq_lens):
            def body(carry, _):
                tokens, positions, kv_k, kv_v, seq_lens = carry
                logits, kv_k, kv_v = mla_moe.decode_forward(
                    params, cfg, tokens, positions, kv_k, kv_v, tables, seq_lens)
                return (logits.argmax(-1).astype(i32), positions + 1, kv_k,
                        kv_v, seq_lens + 1), None

            return jax.lax.scan(
                body, (tokens, positions, kv_k, kv_v, seq_lens), None, 8)[0]

        compiled = jax.jit(block, donate_argnums=(3, 4)).lower(
            params, sds((32,), i32), sds((32,), i32), cache, index,
            sds((32, 321), i32), sds((32,), i32)).compile()
    else:
        def step(params, tokens, positions, row_ids, kv_k, kv_v, tables,
                 row_starts, row_lens, ctx_lens, last_flat):
            return mla_moe.ragged_forward(
                params, cfg, tokens, positions, row_ids, kv_k, kv_v, tables,
                row_starts, row_lens, ctx_lens, last_flat, long_rows=8)

        compiled = jax.jit(step, donate_argnums=(4, 5)).lower(
            params, sds((256,), i32), sds((256,), i32), sds((256,), i32),
            cache, index, sds((40, 321), i32), sds((40,), i32),
            sds((40,), i32), sds((40,), i32), sds((40,), i32)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 6 * 3
    mem = compiled.memory_analysis()
    need = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert need < HBM_BYTES, f"{program} needs {need / 2**30:.2f} GiB"
    pool = 4601 * PAGE * (7 * 640 + 2 * 128) * 2
    assert mem.temp_size_in_bytes < pool / 8, (
        f"{mem.temp_size_in_bytes / 2**20:.0f} MiB of temporaries")


def test_the_piped_mixed_steps_carry_programs_compile_at_the_cell_size(
    one_chip, no_persistent_cache
):
    """What ISSUE 37 adds to the lean mixed step, at the Mixtral cell's
    sizes (32 lanes, 40 rows, the 2,048-token bucket, the default penalty
    window): the read of the decode carry that opens `mixed_step` (each
    decode row's token and window gathered by lane) and the write-back
    program behind it. Both are scatters and gathers over a few KiB: no
    temporary beyond their own operands."""
    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.engine import carry_read, carry_write

    sds = _shapes(one_chip)
    i32 = jnp.int32
    lanes, rows, tokens = 32, 40, 2048
    window = EngineConfig(model="tiny").penalty_window
    read = jax.jit(carry_read).lower(
        sds((tokens,), i32), sds((rows, window), i32), sds((rows,), i32),
        sds((rows,), i32), sds((lanes,), i32), sds((lanes, window), i32),
    ).compile()
    write = jax.jit(carry_write).lower(
        sds((lanes,), i32), sds((lanes,), i32), sds((lanes,), i32),
        sds((lanes, window), i32), sds((rows,), i32), sds((rows,), i32),
        sds((rows,), i32),
    ).compile()
    for compiled in (read, write):
        mem = compiled.memory_analysis()
        assert mem.temp_size_in_bytes <= 4 * mem.argument_size_in_bytes
        assert mem.argument_size_in_bytes < 2**20


# the hybrid cell's state store: 6 linear layers, 32 lanes and the scratch
# slot, 32 value heads of a 128 x 128 float32 state
DELTA_STORE = (6, 33, 32, 128, 128)


@pytest.mark.parametrize("heads", (8, 16, 32))
def test_the_delta_step_kernel_compiles_at_the_cell_size(
    heads, one_chip, no_persistent_cache
):
    """ops/pallas_delta_step.py alone for the described v5e, the whole store
    as its operand and a layer in the middle, at each block of heads that
    was timed on the chip (PERF.md, PR 47; 32 is what the shapes give): the
    store is aliased to the result, so the program holds no second one."""
    from dynamo_tpu.ops.pallas_delta_step import delta_step_pallas

    sds = _shapes(one_chip)
    f32 = jnp.float32
    B, nv, dk, dv = 32, *DELTA_STORE[2:]
    compiled = jax.jit(
        functools.partial(delta_step_pallas, heads=heads), donate_argnums=(0,)
    ).lower(
        sds(DELTA_STORE, f32), sds((), jnp.int32), sds((B, nv, dk), f32),
        sds((B, nv, dk), f32), sds((B, nv, dv), f32), sds((B, nv), f32),
        sds((B, nv), f32), sds((B,), jnp.bool_)).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1
    mem = compiled.memory_analysis()
    store = 4 * math.prod(DELTA_STORE)
    assert mem.alias_size_in_bytes >= store
    assert mem.temp_size_in_bytes < 2**20


def _stateful_cell(sds, family, config, pages):
    """(a stateful family's cell configuration, its weights, its cache and
    its V pool as shapes on the described chip): `config` of
    benchmark/configs at 32 lanes, a table of 2,048 positions and 40 rows."""
    from dynamo_tpu.ops.state_cache import alloc_state_cache

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.join(root, "benchmark"))
    from worker_entry import build_model_config, load_config

    cfg = build_model_config(load_config(os.path.join(
        root, "benchmark", "configs", config + ".json"), False))

    def on_chip(tree):
        return jax.tree.map(lambda x: sds(x.shape, x.dtype), tree)

    params = on_chip(jax.eval_shape(
        functools.partial(family.init_params, cfg), jax.random.PRNGKey(0)))
    cache, kv_v = jax.eval_shape(
        lambda: alloc_state_cache(cfg, pages, PAGE, 32, 2048, 40))
    return cfg, params, on_chip(cache), on_chip(kv_v)


def _hybrid_cell(sds):
    """The hybrid cell: 8 layers, 128 of 512 experts held, the state store
    of 33 slots, the recurrence through the kernel."""
    from dynamo_tpu.models import hybrid

    cfg, params, cache, kv_v = _stateful_cell(
        sds, hybrid, "qwen3-next-80b-a3b-ep4-d8", 4096)
    assert hybrid.recurrence_impl(cfg) == "pallas"
    assert cache.state.shape == DELTA_STORE
    return cfg, params, cache, kv_v


def test_the_hybrid_decode_step_compiles_with_the_delta_kernel_inside(
    one_chip, no_persistent_cache, tpu_gate
):
    """models/hybrid.py's decode step at its cell's configuration (8
    layers, 128 of 512 experts held, 32 lanes, the state store of 33 slots)
    for the described v5e: every linear layer's recurrence is the kernel
    (6), beside three grouped matmuls a layer and the two attention
    layers' decode kernel. Nothing of the store's size is produced but the
    kernel's own aliased result (no slice of a layer's slots, no
    `dynamic_update_slice` back: the shape of
    test_lowered_programs_touch_the_pool_only_to_update_it), and the
    compiled step's temporaries are a few activations' (15.8 MiB; 247.6
    while a period's weights were copied every step: PERF.md, PR 48)."""
    from dynamo_tpu.models import hybrid

    sds = _shapes(one_chip)
    cfg, params, cache, kv_v = _hybrid_cell(sds)
    i32 = jnp.int32

    def step(params, tokens, positions, kv_k, kv_v, tables, seq_lens):
        return hybrid.decode_forward(
            params, cfg, tokens, positions, kv_k, kv_v, tables, seq_lens)

    lowered = jax.jit(step, donate_argnums=(3, 4)).lower(
        params, sds((32,), i32), sds((32,), i32), cache, kv_v,
        sds((32, 64), i32), sds((32,), i32))
    # 32 lanes' states of one layer, or more: the kernel's result in the
    # one function the six calls share, and each call's
    one_layers_slots = math.prod(DELTA_STORE[1:])
    found = _pool_sized_ops(
        lowered, one_layers_slots // 33 * 32, tail=DELTA_STORE[2:])
    assert found == {"stablehlo.custom_call": 1, "func.call": 6}, found
    compiled = lowered.compile()
    assert compiled.as_text().count("tpu_custom_call") == 6 + 3 * 8 + 2
    mem = compiled.memory_analysis()
    need = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert need < HBM_BYTES, f"the decode step needs {need / 2**30:.2f} GiB"
    assert mem.temp_size_in_bytes < 32 * 2**20, (
        f"{mem.temp_size_in_bytes / 2**20:.0f} MiB of temporaries")


HLO_ITEMSIZE = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
                "u16": 2, "f32": 4, "s32": 4, "u32": 4}
#: what a mixed step of the hybrid cell still moves whole, by shape, and why
#: it is no weight and no layer's states (PERF.md section 7; the parent of
#: PR 48 had it too, and so has the state-space family's mixed step)
MIXED_STEPS_OWN = {
    "bf16[6,33,3,8192]": "the convolution's tails, relaid once a step for "
                         "the six layers' scatters and once back",
}


def _hbm_copies(hlo_text, at_least):
    """{name: (MiB, shape)} of the ops of every computation of a compiled
    module (a loop's body as the entry; not the inside of a fusion, which
    never reaches memory) that yield an array of `at_least` bytes or more
    in HBM (no `S(1)` in its layout) and only move it: `copy`, `copy-done`,
    `slice-done`, or a fusion named for slices, copies and bitcasts
    alone."""
    fused = set(re.findall(r"\bfusion\([^\n]*calls=%([\w.\-]+)", hlo_text))
    op = re.compile(
        r"^\s*(?:ROOT\s+)?%([\w.\-]+) = (\w+)\[([\d,]*)\](\{[^}]*\})? "
        r"([\w\-]+)\(")
    moves = re.compile(r"(?:(?:dynamic-slice|slice|copy|bitcast)_)+fusion")
    found, inside = {}, None
    for line in hlo_text.split("\n"):
        head = re.match(r"^(?:ENTRY\s+)?%([\w.\-]+) \(.*\{$", line)
        if head:
            inside = head.group(1)
            continue
        m = op.match(line)
        if not m or inside in fused:
            continue
        name, dtype, dims, layout, kind = m.groups()
        if dtype not in HLO_ITEMSIZE or "S(1)" in (layout or ""):
            continue
        size = HLO_ITEMSIZE[dtype] * math.prod(int(d) for d in dims.split(",") if d)
        if size >= at_least and (
                kind in ("copy", "copy-done", "slice-done")
                or kind == "fusion" and moves.fullmatch(name.split(".")[0])):
            found[name] = (size / 2**20, f"{dtype}[{dims}]")
    return found


@pytest.mark.parametrize("program", ("decode", "block_of_8", "mixed_256"))
def test_the_hybrid_steps_copy_no_weight(
    program, one_chip, no_persistent_cache, tpu_gate
):
    """The hybrid cell's decode step, a block of eight of them under one
    `lax.scan` (the shape of engine.py's `decode_block`, greedy in the
    sampler's place) and the smallest mixed step (256 tokens, 40 rows, a
    prefill batch of 8), for the described v5e: no op of any computation
    yields 8 MiB or more in HBM by a copy or a slice alone. A stored stack
    indexed twice (by period, then by layer) was 232 MiB of weights in six
    such ops of a decode step and of a block's loop body, and a layer's
    state store sliced before 40 rows were gathered from it six times 66
    MiB a mixed step beside them (PERF.md, PR 48). Since PR 52 the mixed
    step's store takes two updates a layer, the kernel's aliased result
    and the gathered rows' scatter: both in place, or this finds the copy."""
    from dynamo_tpu.models import hybrid

    sds = _shapes(one_chip)
    cfg, params, cache, kv_v = _hybrid_cell(sds)
    i32 = jnp.int32
    lanes = (sds((32,), i32), sds((32,), i32))
    if program == "mixed_256":
        tokens, rows = 256, 40

        def step(params, tokens, positions, row_ids, kv_k, kv_v, tables,
                 row_starts, row_lens, ctx_lens, last_flat):
            return hybrid.ragged_forward(
                params, cfg, tokens, positions, row_ids, kv_k, kv_v, tables,
                row_starts, row_lens, ctx_lens, last_flat, long_rows=8)

        compiled = jax.jit(step, donate_argnums=(4, 5)).lower(
            params, sds((tokens,), i32), sds((tokens,), i32),
            sds((tokens,), i32), cache, kv_v, sds((rows, 65), i32),
            *(sds((rows,), i32),) * 4).compile()
    else:
        def step(params, tokens, positions, kv_k, kv_v, tables, seq_lens):
            logits, kv_k, kv_v = hybrid.decode_forward(
                params, cfg, tokens, positions, kv_k, kv_v, tables, seq_lens)
            return jnp.argmax(logits, -1).astype(i32), kv_k, kv_v

        def block(params, tokens, positions, kv_k, kv_v, tables, seq_lens):
            def one(carry, _):
                tokens, positions, seq_lens, kv_k, kv_v = carry
                nxt, kv_k, kv_v = step(
                    params, tokens, positions, kv_k, kv_v, tables, seq_lens)
                return (nxt, positions + 1, seq_lens + 1, kv_k, kv_v), nxt

            return jax.lax.scan(
                one, (tokens, positions, seq_lens, kv_k, kv_v), None, length=8)

        compiled = jax.jit(
            step if program == "decode" else block, donate_argnums=(3, 4)
        ).lower(params, *lanes, cache, kv_v, sds((32, 64), i32),
                sds((32,), i32)).compile()
    text = compiled.as_text()
    if program == "block_of_8":  # one step's kernels, in the loop's body
        assert text.count("tpu_custom_call") == 6 + 3 * 8 + 2 and "while(" in text
    copies = _hbm_copies(text, 8 * 2**20)
    if program == "mixed_256":
        copies = {k: v for k, v in copies.items() if v[1] not in MIXED_STEPS_OWN}
        # the one-token rows' recurrence is the decode step's kernel over
        # the lanes (six calls beside the grouped matmuls and the two
        # attention layers' two kernels), and only a prefill batch's states
        # are gathered: no array of 40 rows' states anywhere
        assert text.count("tpu_custom_call") == 6 + 3 * 8 + 2 * 2
        assert "f32[40,32,128,128]" not in text
    assert not copies, copies
