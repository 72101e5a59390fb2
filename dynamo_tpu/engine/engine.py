"""JaxEngine: the TPU-native inference engine.

The role vLLM plays under the reference (SURVEY.md §7 step 4), built the XLA
way: everything on the token hot path is a pre-compiled static-shape program,
and the host loop is designed around the observation that a synchronous
device round-trip costs ~10-100x an async dispatch (dispatches are cheap and
pipelined; host reads are the expensive unit):

  * decode: ONE jitted BLOCK of K steps for the whole slot batch
    [max_num_seqs] — paged attention + on-device sampling, the sampled token
    feeding the next step inside `lax.scan`. KV buffers are donated so XLA
    updates in place. Up to two blocks are kept in flight (the fetch of
    block i overlaps block i+1's compute), so steady-state decode costs ONE
    host read per K*B tokens.
  * prefill: chunked + bucketed + BATCHED — chunks from several waiting
    sequences are packed into one [B_pf, bucket] dispatch (compile variants
    are bounded: B_pf = budget/bucket), with the first token sampled
    on-device inside the same program. Chunks that do not complete a prompt
    need no host read at all.
  * all host reads of an iteration ride a single `jax.device_get` (one RTT).
  * prefix cache: PageAllocator keys pages by the SAME chained block hashes
    the KV router indexes (llm/tokens.py), and emits stored/removed events.
  * preemption: on page exhaustion the newest-admitted sequence is preempted
    — its full blocks are committed (cheap resume via prefix cache), pages
    released, and the request requeued; it resumes decoding from its pending
    token without re-emitting (reference semantics: vLLM preempt/requeue,
    lib/llm/src/mocker/scheduler.rs:240 watermark eviction).
"""

from __future__ import annotations

import asyncio
import concurrent.futures
import logging
import os
import time
from collections import deque
from dataclasses import dataclass, field
from functools import partial
from typing import Any, AsyncIterator, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..llm.mocker.kv_manager import KvEvent
from ..llm.protocols import Annotated, LLMEngineOutput, PreprocessedRequest
from ..llm.tokens import TokenBlockSequence, compute_seq_hashes, salt_hash
from ..models import exaone_moe, hybrid, llama, mla_moe, moe, nemotron_h
from ..models.quant import is_quant
from ..ops.paged_attention import ragged_tiles
from ..ops.state_cache import state_bytes_per_lane
from ..native import native_available
from ..runtime import faults
from ..runtime.engine import Context
from ..runtime.request_plane import StreamSevered
from ..runtime.metrics import (
    NUM_RUNNING_REQS,
    NUM_WAITING_REQS,
    SCHED_EST_DECODE_TOK_S,
    SCHED_EST_PREFILL_TOK_S,
    SCHED_EST_REQ_MS,
    SCHED_EST_TTFT_MS,
)
from .bucketing import (
    bucket_for as _bucket_for,
    mixed_row_bucket,
    mixed_token_buckets,
    next_pow2 as _next_pow2,
    table_rungs,
)
from .config import EngineConfig
from .kv_cache import PageAllocator, alloc_kv_arrays, alloc_state_cache
from .recorder import ESTIMATE_RUNS, SLOW_SPAN_S, Recorder, Work
from .sampling import SamplingParams, penalized, sample, sample_lp, unpack_mask
from .scheduler import SlaConfig, StepPlanner

logger = logging.getLogger(__name__)

SCRATCH_PAGE = 0  # physical page 0 is the dump target for masked lanes
# What the mixed step's ONE table width (max_pages_per_seq + 1 columns for
# every row of the row bucket, int32) may take of a v5e's scalar memory:
# the ragged kernel prefetches the table into SMEM, 1 MiB on a v5e
# (compiled for a described v5e: a 256 x 1025 table is refused), and Mosaic
# pads its rows to 128 columns, so 256 KiB here is at most half of it.
# The benchmark's cell takes 64 x 65 x 4 = 16,640 B. Above it: pow2 rungs.
MIXED_TABLE_SMEM_BYTES = 256 * 1024
# The running entry's successor is queued this share of the entry's
# estimated length before its end (_successor_deadline), or SUCCESSOR_SAFETY
# times the loop's own longest dispatch of the last few, whichever is more.
# A closed loop's arrivals land in an entry's first third (PERF.md section
# 6, PR 57), so a wide margin costs no arrival its place and a late
# successor costs the device its time.
SUCCESSOR_MARGIN = 0.25
SUCCESSOR_SAFETY = 2.0


# The one in-checkout home of JAX's persistent compilation cache. The path
# is part of the cache key's usefulness: a directory that moves (tmp, pid,
# time) never hits, so it is fixed, and every process of a deployment
# started from this checkout shares it.
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def _enable_compile_cache():
    """Persistent XLA compilation cache: the engine compiles one variant per
    (prefill batch x bucket x table-length bucket), cached across process
    restarts so only the first run pays the compiles. Where
    JAX_COMPILATION_CACHE_DIR is set JAX reads it itself and this code sets
    no directory; otherwise the cache lives at COMPILE_CACHE_DIR."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        os.makedirs(COMPILE_CACHE_DIR, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)


def device_info() -> dict:
    """The device this process computes on, as JAX reports it, with the
    installed stack's versions. Logged by the worker before any weight is
    built and published in JaxEngine.stats()."""
    from importlib import metadata

    import jaxlib

    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:  # a CPU-only installation
        libtpu = None
    devs = jax.devices()
    return {
        "platform": devs[0].platform,
        "device_kind": devs[0].device_kind,
        "device_count": len(devs),
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "libtpu": libtpu,
    }


def _bytes_per_device(tree) -> List[int]:
    """Bytes of `tree`'s arrays resident on each local device, in
    jax.local_devices() order (a tp-sharded leaf puts 1/tp of itself on
    each device of its axis, a replicated leaf all of itself on every
    one)."""
    per = {d: 0 for d in jax.local_devices()}
    for x in jax.tree_util.tree_leaves(tree):
        for sh in getattr(x, "addressable_shards", ()):
            per[sh.device] += sh.data.nbytes
    return list(per.values())


def _kv_shard_div(kv_sharding) -> int:
    """How many devices each KV page is SPLIT across (1 when replicated).

    Derived from the sharding spec, not len(jax.devices()): a replicated
    pool puts the full page on every device, so free-memory math must not
    scale with device count (round-4 advisor medium #1)."""
    if kv_sharding is None:
        return 1
    div = 1
    for axes in kv_sharding.spec:
        if not axes:
            continue
        names = axes if isinstance(axes, tuple) else (axes,)
        for a in names:
            div *= int(kv_sharding.mesh.shape[a])
    return max(div, 1)


#: the module that serves a configuration, by the configuration's class
#: (the first entry the class is an instance of: a subclass stands before
#: its base)
MODEL_FAMILIES = (
    (mla_moe.MlaMoeConfig, mla_moe),
    (exaone_moe.ExaoneMoeConfig, exaone_moe),
    (nemotron_h.NemotronHConfig, nemotron_h),
    (hybrid.HybridConfig, hybrid),
    (moe.MoeConfig, moe),
    (llama.LlamaConfig, llama),
)


def kv_stores(model_cfg) -> int:
    """Stores of pages a layer keeps: K and V, or the ONE latent store of a
    family whose `state_spec()` says `value_store` False."""
    spec = getattr(model_cfg, "state_spec", None)
    return 2 if spec is None or spec().value_store else 1


def model_family(model_cfg):
    """The family module (models/<family>.py) whose forwards serve
    `model_cfg`."""
    for klass, module in MODEL_FAMILIES:
        if isinstance(model_cfg, klass):
            return module
    raise ValueError(f"no model family serves a {type(model_cfg).__name__}")


def _weights_quantized(params) -> bool:
    """Whether any weight leaf is an int8 leaf of models/quant.py."""
    return any(
        is_quant(x) for x in jax.tree_util.tree_leaves(params, is_leaf=is_quant)
    )


def _auto_num_pages(params, model_cfg, config: EngineConfig,
                    kv_sharding=None, multihost: bool = False) -> int:
    """Size the KV page pool from free device memory (the role vLLM's
    gpu_memory_utilization plays). Called with the weights already resident,
    so free = bytes_limit * DYN_HBM_UTILIZATION - bytes_in_use. A device
    without memory_stats needs DYN_HBM_BYTES: on an accelerator its absence
    is an error (no HBM size is assumed for an unknown device); the CPU,
    where tests run, keeps a fixed pool.

    All math is PER-DEVICE: free bytes on one device divided by this
    device's share of a page (the page axis may be sharded — see
    _kv_shard_div). `DYN_HBM_RESERVE_MB` (default 512) holds back
    compile/activation workspace the post-weights snapshot can't see. In
    multihost mode the leader's result is broadcast so every process
    allocates identical KV shapes (dispatch replay requires it).
    """
    dev = jax.local_devices()[0]
    util = float(os.environ.get("DYN_HBM_UTILIZATION", "0.85"))
    reserve = int(float(os.environ.get("DYN_HBM_RESERVE_MB", "512")) * 2**20)
    ms = dev.memory_stats() or {}
    limit = ms.get("bytes_limit")
    in_use = ms.get("bytes_in_use")
    if limit is None and os.environ.get("DYN_HBM_BYTES"):
        limit = int(float(os.environ["DYN_HBM_BYTES"]))
    if limit is None and dev.platform != "cpu":
        raise RuntimeError(
            f"{dev.platform} device {dev.device_kind!r} reports no "
            "memory_stats: set DYN_HBM_BYTES to its memory size, or pass "
            "--num-pages"
        )
    if limit is None:
        n = 2048  # CPU tests: the fixed pool
    else:
        if in_use is None:
            # per-device resident weight bytes: sum THIS device's shards,
            # not global nbytes (a TP-sharded leaf holds 1/tp of its bytes
            # here; a replicated leaf holds all of them)
            in_use = 0
            for x in jax.tree_util.tree_leaves(params):
                try:
                    in_use += sum(
                        s.data.nbytes for s in x.addressable_shards
                        if s.device == dev
                    )
                except Exception:  # noqa: BLE001 — non-Array leaves
                    in_use += getattr(x, "nbytes", 0)
        from ..ops.kv_quant import kv_page_bytes, resolve_kv_quant

        # quantized pages shrink the per-page bytes (int8 ~2x, int4 ~4x
        # incl. the f32 per-head scales), so the SAME free-HBM budget
        # yields ~2x/4x the pages — the resident-session density win
        # a family with a recurrent state keeps pages for its attention
        # layers alone, and its state store (one slot a lane and a scratch
        # slot) comes out of what the pool may take
        kv_layers, state_bytes, index_bytes = model_cfg.num_layers, 0, 0
        if hasattr(model_family(model_cfg), "STATE_FAMILY"):
            spec = model_cfg.state_spec()
            kv_layers = spec.attention_layers
            state_bytes = (
                (config.max_num_seqs + 1) * state_bytes_per_lane(model_cfg)
            )
            # a family that selects keeps an index key a token in its
            # index layers, in a second store under the same page ids
            index_bytes = spec.index_layers * kv_page_bytes(
                config.page_size, 1, spec.index_dim, model_cfg.dtype, "none")
        page_bytes = (
            # K and V; a latent layer keeps one row a token and no V store
            kv_stores(model_cfg)
            * kv_layers
            * kv_page_bytes(
                config.page_size, model_cfg.num_kv_heads,
                model_cfg.head_dim, model_cfg.dtype,
                resolve_kv_quant(config.kv_quant),
            )
            + index_bytes
        )
        page_bytes_dev = page_bytes // _kv_shard_div(kv_sharding)
        free = int(limit * util) - int(in_use) - reserve - state_bytes
        n = free // page_bytes_dev
        logger.info(
            "auto-sized KV pool: %d pages (%.2f GiB resident of %.2f GiB free"
            " per device)",
            n, n * page_bytes_dev / 2**30, free / 2**30,
        )
    if multihost:
        # every process must allocate identical KV shapes for dispatch
        # replay; the leader's sizing wins (followers may see different
        # free-memory snapshots — round-4 advisor medium #1). The floor
        # check comes AFTER the rendezvous: a process raising before it
        # would leave the others hung inside the collective.
        from jax.experimental import multihost_utils

        n = int(multihost_utils.broadcast_one_to_all(np.int32(n)))
    floor = config.max_num_seqs + 2  # at least one page per decode slot
    if n < floor:
        raise RuntimeError(
            f"KV pool auto-sizing found room for only {n} pages; reduce "
            "model size, quantize weights (--quantize int8), quantize the "
            "KV cache (DYN_KV_QUANT=int8/int4 — halves/quarters bytes per "
            "page), or lower max_num_seqs"
        )
    return int(n)


def pack_words(arrays):
    """The host side of a hot dispatch's one transfer (JaxEngine.
    _put_words): these arrays laid end to end as ONE int32 buffer, and the
    layout that takes it apart again. An array of 4-byte elements rides as
    its own bits (a view: seeds stay uint32, temperatures and penalties
    float32); a bool mask is widened, a word an element."""
    for a in arrays:
        assert a.dtype == np.bool_ or a.dtype.itemsize == 4, a.dtype
    layout = tuple((a.shape, a.dtype.str) for a in arrays)
    words = [
        a.astype(np.int32).ravel() if a.dtype == np.bool_
        else np.ascontiguousarray(a).view(np.int32).ravel()
        for a in arrays
    ]
    return np.concatenate(words), layout


#: what rides a mixed step's buffer, by the pack's keys (_blank_mixed_pack)
#: in their order; a plain pack adds the carry's maps (CARRY_KEYS), a variant
#: pack the rows' adapter indices where adapters are registered; a stateful
#: family's row lanes ("lanes") ride last
MIXED_KEYS = (
    "toks", "positions", "row_ids", "tables", "row_starts", "row_lens",
    "ctx_lens", "last_flat", "temps", "top_ks", "top_ps", "seeds", "pens",
    "pen_rows",
)
CARRY_KEYS = ("row_lane", "w_lane", "w_pos")
#: what rides a lane patch's buffer, in _dev_patch's order
PATCH_KEYS = (
    "lane_mask", "table_mask", "tokens", "positions", "seq_lens", "tables",
    "temps", "top_ks", "top_ps", "seeds", "pens", "recent",
)


def riders(names, buf, layout) -> dict:
    """The device side, inside a program that takes a dispatch's buffer:
    the arrays `pack_words` laid end to end in `buf`, under `names` in the
    order the host laid them, by static slices, each in the shape and
    dtype it had on the host and to the bit (a bitcast, never a cast; a
    mask is narrowed back)."""
    assert len(names) == len(layout), (names, layout)
    out, off = {}, 0
    for name, (shape, dtype) in zip(names, layout):
        n, dtype = int(np.prod(shape, dtype=np.int64)), np.dtype(dtype)
        x = jax.lax.slice(buf, (off,), (off + n,)).reshape(shape)
        off += n
        if dtype == np.bool_:
            x = x != 0
        elif dtype != np.int32:
            x = jax.lax.bitcast_convert_type(x, dtype)
        out[name] = x
    return out


def rows_sampling(o: dict) -> SamplingParams:
    """The rows' sampling parameters out of a step's riders."""
    pens = o["pens"]
    return SamplingParams(
        o["temps"], o["top_ks"], o["top_ps"], o["seeds"],
        pens[:, 0], pens[:, 1], pens[:, 2],
    )


def carry_read(tokens, pen_rows, row_starts, row_lane, carry_tok, carry_pen):
    """The mixed step's read of the decode pipeline's device carry: a row
    whose `row_lane` names a lane (>= 0; decode rows of a piped pack) takes
    its one token and its penalty window from that lane of the carry, so a
    block or a mixed step still running ahead is read on the device and the
    host never waits for the value. Rows with -1 keep what the host packed
    (prefill rows; every row of a drained pack)."""
    from_carry = row_lane >= 0
    lane = jnp.maximum(row_lane, 0)
    tokens = tokens.at[
        jnp.where(from_carry, row_starts, tokens.shape[0])
    ].set(carry_tok[lane], mode="drop")
    pen_rows = jnp.where(from_carry[:, None], carry_pen[lane], pen_rows)
    return tokens, pen_rows


def carry_write(tok_d, pos_d, sl_d, pen_d, sampled, w_lane, w_pos):
    """A mixed step's samples into the decode carry, behind it on the same
    stream: row r's sample becomes lane `w_lane[r]`'s current token at
    absolute position `w_pos[r]` (a decode row's lane advances one token; a
    lane whose prompt completed in the step takes its first), and the
    penalty ring takes it at that position, as decode_block's scan does.
    Rows with lane -1 write nothing; a lane appears at most once."""
    B, W = pen_d.shape
    lane = jnp.where(w_lane >= 0, w_lane, B)  # out of range: dropped
    tok_d = tok_d.at[lane].set(sampled, mode="drop")
    pos_d = pos_d.at[lane].set(w_pos, mode="drop")
    sl_d = sl_d.at[lane].set(w_pos + 1, mode="drop")
    pen_d = pen_d.at[lane, w_pos % W].set(sampled, mode="drop")
    return tok_d, pos_d, sl_d, pen_d


@dataclass
class _Slot:
    """One decode slot (host bookkeeping)."""

    request_id: str
    queue: asyncio.Queue
    context: Context
    prompt: List[int]
    max_tokens: int
    min_tokens: int
    eos_ids: List[int]
    ignore_eos: bool
    stop_token_ids: List[int]
    seq: TokenBlockSequence
    kv_prompt: List[int] = field(default_factory=list)  # tokens whose KV
    # prefill computes; == prompt for fresh slots, prompt+generated-minus-
    # pending for preempted slots
    pages: List[int] = field(default_factory=list)
    committed_hashes: List[int] = field(default_factory=list)
    prefill_pos: int = 0
    generated: int = 0
    last_token: int = 0
    slot_idx: int = -1
    admit_seq: int = 0  # admission order; preemption victims = newest
    done: bool = False
    resume_token: Optional[int] = None  # preempted: continue with this token
    first_pending: bool = False  # the prompt completed in a piped mixed step
    # still in flight: the lane is decode-active on the device, its first
    # token reaches the host at that entry's fetch
    return_kv: bool = False  # prefill role: ship KV pages with the 1st token
    kv_pull: bool = False  # prefill role: caller can pull via the data plane
    kv_stream: bool = False  # prefill role: caller wants the EARLY-staged
    # streamed handoff (descriptor ships at admission, chunks publish as
    # prefill commits pages — docs/disagg_serving.md)
    kv_stream_tid: Optional[str] = None  # live streamed stage's transfer id
    kv_stream_desc: Optional[dict] = None  # its descriptor (resent at emit)
    kv_holder: Optional[dict] = None  # router holder hint for peer onboard
    preloaded: Optional[tuple] = None  # decode role: (first_tok, k, v, n_tokens)
    pull_desc: Optional[dict] = None  # decode role: pull-path descriptor
    first_token_fut: Optional[asyncio.Future] = None  # decode role, streamed
    # handoff: resolves to the prefill-produced first token (None = abort)
    onboard: Optional[tuple] = None  # KVBM tier hit: (alloc_pages, hashes)
    mm: Optional[List[tuple]] = None  # multimodal splices: (position, emb [n, H])
    guided_fsm: Optional[Any] = None  # llm/guided.TokenFsm (structured output)
    guided_state: int = 0  # current FSM state; advanced per emitted token
    lora_idx: int = 0  # adapter slot in the engine's LoRA stack (0 = base)
    lora_name: str = ""  # adapter pinned in the LoraPool ("" = no pin);
    # the pin releases exactly once (finish/release clears the name)
    want_logprobs: bool = False  # attach sampled-token logprobs to emissions
    sample_seed: int = 0  # per-request sampling seed (SamplingParams.seed)
    presence_penalty: float = 0.0
    frequency_penalty: float = 0.0
    repetition_penalty: float = 1.0
    want_top_logprobs: int = 0  # top-k alternatives per token (max 5)
    # dynosched (engine/scheduler/): SLA bookkeeping. priority scales the
    # TTFT target (each +1 halves it); sched_deadline is the EDF key;
    # sched_skips counts dispatches this candidate was passed over (the
    # starvation guard's aging signal) and resets on every granted chunk.
    priority: int = 0
    arrival_s: float = 0.0
    # the waits ahead of the first token (engine/recorder.py): the first
    # admission and the first token handed to the stream, time.monotonic()
    admit_s: float = 0.0
    first_token_s: float = 0.0
    # (blocks, mixed steps) the recorder had seen fetched at the arrival
    fetched_at_arrival: tuple = (0, 0)
    sched_deadline: float = 0.0
    sched_skips: int = 0
    # dynogate tenant key (docs/overload.md): feeds the StepPlanner's
    # per-tenant fairness tiebreak; "" = the default tenant
    tenant: str = ""
    # migration retry ordinal (llm/migration.py RetryManager): > 0 means
    # this request resumes a stream a dead worker lost — the prompt is
    # the original prompt plus the already-emitted tokens. Admission
    # classifies the resume source (checkpoint/peer/local/recompute)
    # exactly once (a later preemption re-admit must not re-count).
    migration: int = 0
    migration_counted: bool = False
    # the request plane's `routed_experts` annotation (a routed family
    # that records its choices: models/hybrid.py): one row [routed
    # layers][k] of expert ids for each input position, riding the frame
    # that carries the token the position produced. `routed_sent`: input
    # positions whose rows were handed over; `routed_seen`: input positions
    # computed since the last admission (a preempted sequence recomputes
    # from 0 and sends no row twice)
    want_routed: bool = False
    routed_pending: List[Any] = field(default_factory=list)
    routed_sent: int = 0
    routed_seen: int = 0


class StreamedPullHandle:
    """Decode-side handle for an early (streamed) disagg KV pull
    (docs/disagg_serving.md): the pull starts while the PREFILL worker is
    still computing, off its early-shipped descriptor. The disagg handler
    resolves the handle with the prefill's first token once it arrives
    (`set_first_token`), or abandons it (`abort`) when the prefill stream
    fails or the transfer was re-staged under a different id (preempt)."""

    def __init__(self, engine: "JaxEngine", slot: _Slot, transfer_id: str):
        self._engine = engine
        self._slot = slot
        # the handle owns its OWN reference to the future: the pull task
        # detaches slot.first_token_fut before awaiting it, and a
        # set_first_token/abort arriving after that detach (last chunk
        # landed before the handler processed the final event — the
        # exact overlap the feature maximizes) must still resolve it, or
        # the pull task awaits forever with the slot pinned
        self._fut = slot.first_token_fut
        self.transfer_id = transfer_id

    def set_first_token(self, token: int):
        if self._fut is not None and not self._fut.done():
            self._fut.set_result(int(token))

    def abort(self):
        """Abandon the early pull: the slot releases, any in-flight chunk
        injection unwinds, and the handler falls back to the serial /
        local path."""
        eng, slot = self._engine, self._slot
        if self._fut is not None and not self._fut.done():
            self._fut.set_result(None)
        slot.done = True
        if slot.slot_idx >= 0 and eng.slots[slot.slot_idx] is slot:
            eng._release_slot(slot)
        eng._wake.set()

    async def stream(self):
        """Consume the decode stream (same contract as engine.generate)."""
        slot = self._slot
        try:
            while True:
                item = await slot.queue.get()
                if item is None:
                    return
                yield item
        finally:
            slot.done = True
            self._engine._wake.set()


class _ScopedModel:
    """The model family module (models/<family>.py) as one
    engine sees it: every forward it traces runs inside that engine's
    attention scope, so the kernel-or-XLA decision follows the engine's
    own mesh (ops/paged_attention.mesh_allows_kernels) wherever the trace
    happens — dispatch thread, follower replay or a test calling a
    compiled surface directly."""

    def __init__(self, module, allows_kernels: bool):
        self._module = module
        self._allows_kernels = allows_kernels

    def __getattr__(self, name):
        fn = getattr(self._module, name)
        if not callable(fn):
            return fn
        from ..ops.paged_attention import attention_scope

        def scoped(*args, **kwargs):
            with attention_scope(self._allows_kernels):
                return fn(*args, **kwargs)

        return scoped


class JaxEngine:
    """Continuous-batching JAX engine with the MockEngine-compatible
    `generate(request, context)` interface."""

    def __init__(
        self,
        config: EngineConfig,
        model_config: Optional[llama.LlamaConfig] = None,
        params: Optional[dict] = None,
        kv_sharding=None,
        event_sink: Optional[Callable[[KvEvent], None]] = None,
        mesh=None,
        spmd=None,
        multihost: bool = False,
    ):
        """`mesh`+`kv_sharding`: jit programs with explicit out_shardings
        (host-fetched outputs replicated so host 0 can read them on a
        multi-host mesh). `spmd`: a parallel.multihost.StepBroadcaster —
        every device dispatch is mirrored to follower hosts, which replay
        it via `run_follower`. `multihost`: True when jax.distributed is
        active (disagg KV extraction then rides process_allgather)."""
        from ..ops.kv_quant import resolve_kv_quant

        kvq = resolve_kv_quant(config.kv_quant)
        if config.kv_quant != kvq:
            # The KV quant mode (DYN_KV_QUANT) resolves here, into a COPY
            # (the caller's config keeps its auto sentinel), so every later
            # consumer (pool sizing, KVBM block layout, wire descriptors)
            # reads one explicit spelling.
            import dataclasses as _dc

            config = _dc.replace(config, kv_quant=kvq)
        if kvq != "none":
            if config.pp_size > 1 or config.sp_size > 1 or config.tp_size > 1:
                raise ValueError(
                    "kv_quant requires tp_size == pp_size == sp_size == 1 "
                    "(per-page-per-head scale sharding is the multi-chip "
                    "follow-up); set DYN_KV_QUANT=none for parallel layouts"
                )
            if kv_sharding is not None or multihost:
                raise ValueError(
                    "kv_quant is incompatible with a sharded/multi-host KV "
                    "pool; set DYN_KV_QUANT=none"
                )
        self.config = config
        self._mesh = mesh
        self._spmd = spmd
        self._multihost = multihost
        self._kv_sharding = kv_sharding
        _enable_compile_cache()
        self.model_config = model_config or _resolve_model(config.model)
        c = self.model_config
        # family dispatch by the configuration's class: every family module
        # exposes the same init/decode/prefill signatures
        family = model_family(c)
        # a family that keeps a recurrent state per lane beside the pages
        # says so of itself, in the words its refusals are worded in
        # (`STATE_FAMILY`: models/hybrid.py, models/nemotron_h.py,
        # models/exaone_moe.py; docs/hybrid_models.md)
        self.STATE_FAMILY = getattr(family, "STATE_FAMILY", None)
        self._stateful = self.STATE_FAMILY is not None
        # ... and of those, the ones whose lanes do keep something beside
        # the pages (a family of latent layers keeps pages alone, and takes
        # a StateCache for the leaves its chosen experts are recorded in):
        # only these are declined the prefix index
        self._lane_state = (
            self._stateful and c.state_spec().state_layers > 0)
        # a routed family counts the rows its expert matmuls multiply
        self._counts_expert_rows = hasattr(family, "expert_rows")
        # a family with a recurrence steps a mixed step's one-token rows
        # over the lanes in place (its `lanes_step`) and counts them
        self._steps_lanes = hasattr(family, "lanes_step")
        # a latent family's chunks attend absorbed up to so many tokens and
        # expanded past them (the family's own rule, read here to count)
        limit_of = getattr(family, "absorbed_row_limit", None)
        self._absorbed_row_limit = limit_of(c) if limit_of else None
        # ... and where its configuration selects the positions a token
        # attends, at most so many of a context (0: every position)
        self._index_topk = getattr(c, "index_topk", 0)
        if self._stateful:
            self._refuse_what_state_cannot_follow(config, mesh, multihost)

        from ..ops.paged_attention import (
            attention_scope,
            mesh_allows_kernels,
            ragged_tile,
            resolved_attention,
        )

        allows_kernels = mesh_allows_kernels(mesh)
        self._model = _ScopedModel(family, allows_kernels)
        with attention_scope(allows_kernels):
            # one visible decision per engine: which implementation each
            # attention op takes for this model, mesh and KV format
            self.attention_impl = resolved_attention(
                c.head_dim, c.num_kv_heads, kvq != "none"
            )
            if hasattr(family, "attention_impl"):
                # a family with attention ops of its own names what runs
                self.attention_impl = family.attention_impl(c)
            if self._lane_state:
                # and which a decode step's recurrence takes (a family
                # with no kernel of its own for it has none to resolve)
                resolve = getattr(family, "recurrence_impl", None)
                self.attention_impl["recurrence"] = (
                    resolve(c) if resolve else "xla")
            # the ragged kernel's q tile, 1 on the XLA path: what the
            # mixed_attn_* counts of _dispatch_mixed are reckoned in
            self._ragged_tile = ragged_tile(c.dtype, c.head_dim, kvq != "none")
        self.device = device_info()
        logger.info(
            "engine device %s; attention %s (mesh %s, kv_quant %s)",
            self.device, self.attention_impl,
            "none" if mesh is None else dict(mesh.shape), kvq,
        )
        key = jax.random.PRNGKey(config.seed)
        if params is None:
            params = self._model.init_params(c, key)
            if config.quantize == "int8":
                from ..models.quant import quantize_tree

                params = quantize_tree(params, consume=True)
            elif config.quantize:
                raise ValueError(f"unknown quantize mode {config.quantize!r}")
        self.params = params
        if config.num_pages <= 0:
            config.num_pages = _auto_num_pages(
                params, c, config, kv_sharding=kv_sharding, multihost=multihost
            )
        # +1: physical page 0 is scratch. If the layout shards the PAGE axis
        # (dp-attention: pages over ep), round the pool up to a shardable
        # size — the allocator still manages only num_pages, spares idle.
        total_pages = config.num_pages + 1
        if kv_sharding is not None and len(kv_sharding.spec) > 1 and kv_sharding.spec[1]:
            axes = kv_sharding.spec[1]
            names = axes if isinstance(axes, tuple) else (axes,)
            div = int(np.prod([kv_sharding.mesh.shape[a] for a in names]))
            total_pages = -(-total_pages // div) * div
        if self._stateful:
            # the state store beside the pages, in kv_k's place: sized for
            # the most rows and token slots a dispatch packs
            self.kv_k, self.kv_v = alloc_state_cache(
                c, total_pages, config.page_size, config.max_num_seqs,
                max_tokens=max(
                    config.mixed_max_tokens, config.prefill_batch_tokens,
                    config.max_prefill_chunk,
                ),
                row_slots=max(mixed_row_bucket(config),
                              config.max_prefill_batch),
            )
        else:
            self.kv_k, self.kv_v = alloc_kv_arrays(
                c.num_layers,
                total_pages,
                config.page_size,
                c.num_kv_heads,
                c.head_dim,
                dtype=c.dtype,
                sharding=kv_sharding,
                kv_quant=config.kv_quant,
            )
        self.allocator = PageAllocator(
            config.num_pages, config.page_size, event_sink=event_sink
        )
        # where the model and the pool actually live: a tp engine must
        # show about 1/tp of both on every device of its mesh
        self._weight_bytes_per_device = _bytes_per_device(self.params)
        self._kv_bytes_per_device = _bytes_per_device((self.kv_k, self.kv_v))
        # (useful operations, least HBM bytes) of a pipeline entry from the
        # shapes the host holds at dispatch (models/<family>.step_work), at
        # the bytes this engine's weights and pool have: int8 weights are
        # one byte an element (their scales are not counted: a floor)
        from ..ops.kv_quant import kv_page_bytes

        self._quantized = _weights_quantized(self.params)
        self._step_work = partial(
            family.step_work, c,
            weight_bytes=1 if self._quantized
            else jnp.dtype(c.dtype).itemsize,
            kv_bytes=kv_stores(c) * kv_page_bytes(
                config.page_size, c.num_kv_heads, c.head_dim, c.dtype, kvq
            ) / config.page_size,
        )
        self.warmup_seconds = 0.0
        # KVBM host/disk tiers (kvbm/): write-through offload of committed
        # blocks, onboard at admission when the device prefix cache misses
        self.kvbm = None
        if config.kvbm_host_blocks > 0 or config.kvbm_disk_blocks > 0:
            from ..kvbm import KvBlockManager, KvbmConfig, KvbmConnector
            from ..ops.kv_quant import kv_page_bytes

            if config.kv_quant != "none":
                # quantized blocks tier NATIVELY as packed uint8 rows
                # (q bytes + per-page-per-head scales, ops/kv_quant.py):
                # G2/G3 capacity at fixed host/disk bytes and peer-pull
                # payloads shrink by the same 2x/4x as the device pool
                block_shape = (
                    c.num_layers,
                    kv_page_bytes(config.page_size, c.num_kv_heads,
                                  c.head_dim, c.dtype, config.kv_quant),
                )
                np_dtype = np.dtype(np.uint8)
            else:
                block_shape = (c.num_layers, config.page_size, c.num_kv_heads, c.head_dim)
                np_dtype = np.dtype(jnp.zeros((), c.dtype).dtype)
            manager = KvBlockManager(
                KvbmConfig(
                    host_blocks=config.kvbm_host_blocks,
                    disk_blocks=config.kvbm_disk_blocks,
                    disk_path=config.kvbm_disk_path,
                ),
                block_shape,
                np_dtype,
                kv_format=config.kv_quant,
            )
            self.kvbm = KvbmConnector(self, manager)
        # shift page ids by +1 so allocator page 0 -> physical page 1
        B, P = config.max_num_seqs, config.max_pages_per_seq
        self.page_tables = np.zeros((B, P), np.int32)
        self.seq_lens = np.zeros((B,), np.int32)
        self.tokens = np.zeros((B,), np.int32)
        self.temps = np.zeros((B,), np.float32)
        self.top_ks = np.zeros((B,), np.int32)
        self.top_ps = np.ones((B,), np.float32)
        self.seeds = np.zeros((B,), np.uint32)  # per-lane sampling seeds
        self.presence = np.zeros((B,), np.float32)
        self.frequency = np.zeros((B,), np.float32)
        self.repetition = np.ones((B,), np.float32)
        # recent-token ring per lane (penalties window; pad = -1). Host
        # mirror for reset/patch; the device copy rides the decode carry.
        self.recent = np.full((B, config.penalty_window), -1, np.int32)
        self.slots: List[Optional[_Slot]] = [None] * B
        self._free_slots = list(range(B - 1, -1, -1))
        self._waiting: List[_Slot] = []
        self._step_task: Optional[asyncio.Task] = None
        # strong refs to in-flight background pulls: the event loop only
        # keeps weak refs, and a GC'd pull task would strand its slot
        self._bg_tasks: set = set()
        self._wake = asyncio.Event()
        # optional llm.kv_transfer.KvDataPlaneServer (worker attaches it):
        # enables the descriptor/pull disagg path instead of inline payloads
        self.data_plane = None
        # multi-host shard rendezvous (worker wires these after the SPMD
        # followers connect): this host's id and the per-host data-plane
        # addresses [host0, host1, ...]. With these set, disagg KV moves
        # per-shard point-to-point — no process_allgather of the full pages,
        # no leader re-broadcast of KV bytes (reference scaling property:
        # NIXL point-to-point descriptors, block_manager/storage/nixl.rs)
        self.host_id = 0
        self.shard_addrs: Optional[List[str]] = None
        self._closed = False
        self._rng = jax.random.PRNGKey(config.seed + 1)
        self._step_counter = 0
        self.num_requests = 0
        self.num_preemptions = 0
        # decode-side data-plane counters (the serving side counts on the
        # KvDataPlaneServer): how many remote-prefill KV pulls actually
        # landed, and how many pages moved — the disagg tests assert on
        # these instead of grepping logs
        self.kv_pulls_completed = 0
        self.kv_pages_pulled = 0
        # typed mixed-precision rejections (kv_quant): a peer staging a
        # different KV page format is refused BEFORE any byte moves and
        # the request recomputes locally — counted so a misconfigured
        # fleet is visible, never silent (docs/kvbm.md mixed-fleet rules)
        self.kv_format_mismatches = 0
        # streamed disagg handoff (docs/disagg_serving.md): decode-side
        # evidence that KV transfer overlapped prefill — chunks that landed
        # BEFORE the prefill's first-token event, and handoffs where the
        # first token was already client-bound while the tail chunks were
        # still in flight (the serial path is structurally 0 on both)
        self.disagg_streamed_handoffs = 0
        self.disagg_chunks_before_first_token = 0
        self.disagg_first_token_before_last_chunk = 0
        # prefill-side: early-staged streamed transfers, and the ones that
        # died mid-stream and fell back to a fresh serial stage at emit
        self.kv_streamed_stages = 0
        self.kv_streamed_fallbacks = 0
        # blocks reused MID-prefix from concurrent same-prefix requests
        # (_try_skip_ahead; admission-time hits count in the allocator)
        self.prefix_skip_ahead_blocks = 0
        # KVBM tier-chain effectiveness (docs/kvbm.md): G1 = device prefix
        # cache hits at admission; misses = prompt blocks the device cache
        # could not serve (onboarded from G2/G3 or prefilled). Tier-level
        # G2/G3 hit counters live on the tiers themselves.
        self.kvbm_g1_hit_blocks = 0
        self.kvbm_g1_miss_blocks = 0
        # onboard latency histogram (ms buckets) + recompute comparison
        # inputs, on the worker's metrics: whether tier onboarding beats
        # recompute
        self._onboard_hist_bounds = (1.0, 5.0, 20.0, 100.0, 500.0)
        self.kvbm_onboard_hist = [0] * (len(self._onboard_hist_bounds) + 1)
        self.kvbm_onboard_ms_sum = 0.0
        self.kvbm_onboard_count = 0
        self._admit_counter = 0
        # dynosched (engine/scheduler/): the StepPlanner owns prefill
        # ordering and chunk budgeting; policy "fifo" (the default)
        # reproduces the legacy admit-order dispatch bit-for-bit (modulo
        # the batch-kind anti-starvation fairness fix, active under both
        # policies), "sla" spends explicit TTFT/ITL targets
        # (docs/scheduler.md). Its cost
        # model is fed by the _timed dispatch instrumentation below.
        self.scheduler = StepPlanner(
            config,
            SlaConfig.from_env(
                policy=config.sched_policy,
                ttft_target_ms=config.ttft_target_ms,
                itl_target_ms=config.itl_target_ms,
            ),
        )
        # ragged unified mixed dispatch (docs/ragged_attention.md): when
        # the planner has BOTH runnable prefill chunks and active decode
        # lanes, ONE flat ragged buffer + ONE device call replaces the
        # split prefill-batch + decode-block pair. Guided, multi-LoRA and
        # speculative rows fuse too (mask / adapter-index operands on the
        # variant program, spec lanes as 1+d one-token verify rows);
        # pp/sp configs keep the split path outright.
        self._mixed_enabled = (
            config.mixed_dispatch and config.pp_size == 1 and config.sp_size == 1
        )
        # durable decode sessions (docs/fault_tolerance.md "Request
        # migration"): commit newly-FULL generated blocks during the step
        # loop rather than only at _release_slot, so a live session's
        # prefix is continuously visible to the prefix cache, the KVBM
        # offload pipeline, the announcement mesh and (when enabled) the
        # session-checkpoint replicator. The commit logic is the same
        # _commit_generated_blocks call release uses — byte-identical
        # blocks either way, incremental just runs it earlier.
        from ..runtime.config import env_bool

        self._incremental_commit = (
            config.incremental_commit
            if config.incremental_commit is not None
            else env_bool("DYN_KV_INCREMENTAL_COMMIT", True)
        )
        # migration observability (ISSUE 15): what a worker death actually
        # cost. A resumed (migrated) request arrives with req.migration > 0;
        # at admission we classify the session-prefix source — checkpoint
        # (peer-replicated session blocks), peer (plain fabric pull),
        # local (own G1/G2/G3 copies), recompute (full prefill) — and count
        # the tokens that really had to be re-prefilled.
        self.migrations_resumed = 0
        self.migration_replayed_tokens = 0
        self.resume_source_checkpoint = 0
        self.resume_source_peer = 0
        self.resume_source_local = 0
        self.resume_source_recompute = 0
        # live role morphing (docs/autoscaling.md "Role morphing"): the
        # serving role + state machine position, mutated only inside
        # morph() (GUARDED_STATE "JaxEngine._role"/"._morph_state")
        self._role = config.role
        self._morph_state = "serving"
        self._severed_queues: List[asyncio.Queue] = []
        self.morphs_completed = 0
        self.morphs_rolled_back = 0
        self.morph_drained_sessions = 0
        self.morph_last_duration_s = 0.0
        # ONE fixed row bucket: the row axis only sizes scalar operands
        # (tables, sampling state), so a single padded variant is free —
        # compile variants stay (token bucket x table bucket). Under spec
        # every decode lane may pack 1 + spec_draft_len verify rows.
        self._mixed_row_bucket = mixed_row_bucket(config)
        # the lean mixed_step family: token buckets x table widths, closed
        # and small enough to compile together at the first mixed step
        # (_prime_mixed_family). The Pallas ragged kernel's work follows
        # each row's ctx_lens, not the table's width (the table is a
        # scalar-prefetch operand), so there ONE width serves every
        # context — while R_pad x P x 4 B stays within the budget below; the
        # XLA reference gathers P pages a row, so it keeps the pow2 rungs.
        self._mixed_token_buckets = mixed_token_buckets(config)
        # ... and so does an XLA walk that ends at the longest context of
        # the call (ops/latent_attention.py), in the split prefill too
        self._walks_contexts = getattr(family, "ONE_TABLE_WIDTH", False)
        one_width = (
            self.attention_impl["ragged"] == "pallas"
            and self._mixed_row_bucket * (config.max_pages_per_seq + 1) * 4
            <= MIXED_TABLE_SMEM_BYTES
        ) or self._walks_contexts
        self._mixed_table_rungs = (
            (config.max_pages_per_seq,) if one_width
            else table_rungs(config.max_pages_per_seq)
        )
        self._mixed_primed = set()  # of (variant, ctx_pages)
        # fused-vs-split visibility (stats() + jax_worker gauges): is the
        # fused path actually taken in production, and what padding does
        # each path pay per step
        self.mixed_steps = 0
        # of those, the ones that ran as entries of the decode pipeline
        # with no host round trip on either side: dispatched without a
        # drain before them, their successor queued before their fetch
        self.mixed_steps_piped = 0
        self.split_steps = 0
        self.mixed_padded_tokens = 0
        self.mixed_real_tokens = 0
        # where the mixed step's attention is the two Pallas kernels
        # (ops/paged_attention.ragged_attention): q tiles the ragged grid
        # launched, those of them that hold a real q row, and the
        # one-token rows that went to the decode kernel; 0 on the XLA path
        self.mixed_attn_tiles = 0
        self.mixed_attn_tiles_real = 0
        self.mixed_rows_decode_kernel = 0
        self.split_padded_tokens = 0
        self.split_real_tokens = 0
        # per-kind fused coverage (docs/observability.md): which row
        # classes actually ride the fused buffer, and what fraction of
        # fused-ELIGIBLE steps (mixed-shaped traffic) fused — the CI
        # blended smoke gates mixed_coverage_frac >= 0.9
        self.mixed_rows_plain = 0
        self.mixed_rows_guided = 0
        self.mixed_rows_spec = 0
        self.mixed_rows_lora = 0
        # expert rows per dispatch, host arithmetic (moe.expert_rows): what
        # was routed (real tokens x experts per token) against what the
        # expert matmuls multiply; both stay 0 on a dense model
        self.expert_rows_routed = 0
        self.expert_rows_computed = 0
        # a stateful family's own counters (stats() exports them for it
        # alone): first chunks dispatched, each of which starts its lane's
        # state from zero; cached blocks the prefix index was not allowed
        # to hand out; rows of chosen experts sent to annotated requests
        self.state_lanes_reset = 0
        self.state_prefix_hits_declined = 0
        self.routed_rows_emitted = 0
        # ... and a mixed step's rows by the road their recurrence takes
        # (ops/row_recurrence.rows_recurrence, its own rule): one token that
        # goes on from the lane's state, stepped in the store; every other
        # row, gathered out of it and scattered back
        self.state_rows_in_place = 0
        self.state_rows_gathered = 0
        # ... and a latent family's chunks of more than one token by the
        # side of the score W_kvb stood on (models/mla_moe.rows_attention)
        self.mla_rows_absorbed_tokens = 0
        self.mla_rows_expanded_tokens = 0
        self._last_prefill_shape = None  # (padded, real) of the latest dispatch
        self._last_decode_shape = None
        # set by _dispatch_mixed when a pack that needs host-authoritative
        # lanes (_pack_pipes says no) waits for the pipeline to drain: the
        # step loop holds the split prefill one step so the drained
        # pipeline fuses next step instead. A lean pack never sets it: it
        # queues behind what is in flight
        self._mixed_wait_drain = False
        # speculative decoding (engine/spec.py): host mirror of the device
        # history ring + SpecDecodeStats counters (_core.pyi:269-301 role)
        self.hist = (
            np.zeros((config.max_num_seqs, config.spec_hist), np.int32)
            if config.spec_mode else None
        )
        self._hist_dev = None
        self.spec_num_drafts = 0
        self.spec_num_draft_tokens = 0
        self.spec_num_accepted_tokens = 0
        # guided decoding (llm/guided.py): tokenizer for vocab→FSM lift
        # (workers set this to the served model's tokenizer; defaults to
        # ByteTokenizer over the model vocab), lazily-built compiler, and
        # a requests counter for stats()
        self.tokenizer = None
        self._guided = None
        self.guided_requests = 0
        # multi-LoRA (models/lora.py): stacked adapters in HBM + per-lane
        # adapter index mirror (rides lora dispatch variants as an operand).
        # At fleet scale the stack is a FIXED-slot paging tier
        # (models/lora_pool.LoraPool) — adapter weights page HBM<->host on
        # demand, so "names" maps only the RESIDENT roster
        self._lora = None  # {"a": {...}, "b": {...}, "scale", "names"}
        self._lora_pool = None  # models/lora_pool.LoraPool when registered
        self.lora_idx = np.zeros((config.max_num_seqs,), np.int32)
        self.lora_requests = 0
        # the loop's spans and counters (engine/recorder.py): seconds by
        # phase of an iteration, every pipeline entry's kind, interval and
        # work, the waits ahead of a first token, and _timed's table of
        # the host's clock around each device call
        self._rec = Recorder(self._describe_load)
        # emit batching (tokens-per-delta-batch): mean > 1 in steady decode
        # means the serving plane is getting whole blocks, not singletons —
        # the self-diagnosing coalescing signal on hardware e2e rows
        self.emit_batches = 0
        self.emit_tokens = 0
        # decode pipeline: device-resident carry (tokens/positions/seq_lens)
        # + up to two in-flight K-step blocks
        self._carry = None  # (tokens_dev, positions_dev, seq_lens_dev)
        self._carry_valid = False
        # per-lane dirt: admissions/finishes/page-growth touch only their
        # lanes via the patch program — a full invalidation would drain the
        # block pipeline and re-upload everything (the round-2 ITL gap)
        self._dirty_lanes: set = set()  # full lane state from host
        self._dirty_tables: set = set()  # page-table row only (lane carry
        # on device is NEWER than host and must not be overwritten)
        self._tables_dev = None
        self._samp_dev = None
        self._pen_dev = None  # [B, W] recent-token ring (penalties)
        # the pipeline's ONE queue, fetched in dispatch order: decode blocks
        # {"kind": "block"|"spec", "lanes", "toks": dev[K,B], "adv"} and
        # piped mixed steps {"kind": "mixed", "first": dev[R], "done",
        # "progressed", "decode", "spec"}; at most two entries
        self._inflight: deque = deque()
        # the running entry's fetch, asked for at the start of the wait for
        # its successor's moment (_await_successor) and taken up by the
        # step's own fetch
        self._early_fetch: Optional[asyncio.Future] = None
        # the loop's seconds from taking up a step's dispatch to the launch's
        # return of the entry it queued second, the last few: the margin
        self._successor_costs: deque = deque(maxlen=ESTIMATE_RUNS)
        self._sleep = asyncio.sleep  # the wait's timer (a test drives its own)
        # split prefill dispatches and drained mixed steps awaiting their
        # first-token fetch, which the step that dispatched them makes
        self._pending_prefill: List[dict] = []
        # all device dispatches run on this single thread so XLA compiles
        # (which can take tens of seconds) never stall the asyncio event
        # loop; host reads run on a separate fetch thread so a blocking
        # device_get (~1 RTT) never delays the next dispatch
        self._device_exec = concurrent.futures.ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="jax-step"
        )
        self._fetch_exec = concurrent.futures.ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="jax-fetch"
        )
        self._compile()

    # ------------------------------------------------------------------ #
    # a family with a recurrent state beside the pages
    # ------------------------------------------------------------------ #

    @property
    def stateful(self) -> bool:
        """Whether the model keeps a recurrent state per lane beside the
        pages (docs/hybrid_models.md)."""
        return self._stateful

    def _why_refused(self, key: str, why: str) -> str:
        """`why`, or the family's own words for `key` where it has some
        (models/mla_moe.WHY_REFUSED: it keeps no state to lose)."""
        return getattr(
            model_family(self.model_config), "WHY_REFUSED", {}).get(key, why)

    def _refuse_state(self, what: str, why: str, key: str = ""):
        raise ValueError(
            f"{self.STATE_FAMILY} cannot run {what}: "
            f"{self._why_refused(key, why)}")

    def _refuse_what_state_cannot_follow(self, config: EngineConfig, mesh,
                                         multihost: bool):
        """What cannot keep the invariant yet (a lane's state stands at
        exactly the tokens whose keys and values are written) is switched
        off for this family alone, here, at start and by name; the prefix
        index is declined per admission (_try_admit). ROADMAP.md B has
        what each needs."""
        if config.kvbm_host_blocks > 0 or config.kvbm_disk_blocks > 0:
            self._refuse_state(
                "KVBM offload and onboard (and the migration checkpoints "
                "that ride its tiers)",
                "a block's pages come back without the state that stood "
                "at its end", "kvbm",
            )
        if config.spec_mode:
            self._refuse_state(
                f"speculative decoding (--spec {config.spec_mode})",
                "a rejected draft cannot be rolled back out of a state",
                "spec",
            )
        if config.role == "prefill":
            self._refuse_state(
                "the disaggregated hand-off (--role prefill)",
                "the pages would leave without the lane's state", "disagg",
            )
        if config.quantize or (config.kv_quant or "none") != "none":
            self._refuse_state(
                "--quantize / --kv-quant", "its leaves have no int8 form yet",
                "quant")
        if mesh is not None or multihost or max(
                config.tp_size, config.pp_size, config.sp_size,
                config.dp_size) > 1:
            self._refuse_state(
                "over a mesh (tp / pp / sp / dp / multi-host)",
                "the state store has no sharding", "mesh",
            )
        logger.info(
            "%s: %s; KVBM, the disaggregated hand-off, migration checkpoints "
            "and speculation are refused",
            self.STATE_FAMILY,
            "the prefix index hands out no cached pages (counter "
            "state_prefix_hits_declined)" if self._lane_state
            else "the prefix index serves its pages",
        )

    def _routed_behind(self, call, entry: dict):
        """`call`, a stateful family's dispatch that packs rows (a prefill
        batch, a mixed step): right behind it, on the device thread, where
        `entry` says a row's request asked for the experts it chose
        (`want_routed`), they are copied out of the cache before the next
        dispatch takes it (fetched with the entry)."""
        if not entry.get("want_routed"):
            return call

        def routed_behind(*a):
            out = call(*a)
            with self._rec.span("launch", more=True):
                entry["routed"] = jnp.copy(self.kv_k.routed_flat)
            return out

        return routed_behind

    def _note_first_chunks(self, starts) -> None:
        """Rows of a dispatch that begin a sequence (context 0): the
        forward starts each from a zero state, whatever its lane held."""
        if self._stateful:
            self.state_lanes_reset += sum(1 for st in starts if st == 0)

    def _grab_ring(self, call, entry: dict):
        """`call`, a decode block of a stateful family some lane of which
        asked for the experts it chose: the ring they are written to,
        copied out right behind the block (fetched with `entry`)."""
        def with_ring(*a):
            out = call(*a)
            with self._rec.span("launch", more=True):
                entry["routed"] = jnp.copy(self.kv_k.routed_ring)
            return out

        return with_ring

    def _take_routed(self, slot: "_Slot", rows) -> None:
        """Rows [n][routed layers][k] of chosen experts for the next n
        input positions of `slot`, to ride its next frame; positions sent
        before (a preempted sequence's recomputation) are left out."""
        for row in rows:
            if slot.routed_seen >= slot.routed_sent:
                slot.routed_pending.append(row)
                slot.routed_sent += 1
            slot.routed_seen += 1

    # ------------------------------------------------------------------ #
    # compiled programs
    # ------------------------------------------------------------------ #

    def _compile(self):
        c = self.model_config
        cfg = self.config
        K = cfg.decode_block_steps

        # under a (possibly multi-host) mesh, pin host-fetched outputs to
        # fully-replicated shardings so every host can read them locally;
        # the KV cache keeps its tp sharding
        decode_out_sh = prefill_out_sh = None
        if self._mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            repl = NamedSharding(self._mesh, PartitionSpec())
            kvs = self._kv_sharding or repl
            decode_out_sh = (repl, repl, repl, repl, kvs, kvs, repl, repl)
            prefill_out_sh = (repl, kvs, kvs, repl)

        # the RNG key lives ON DEVICE and is threaded through every program
        # (split inside jit, advanced key returned): an eager
        # jax.random.split per dispatch costs a host round-trip per step
        # (the round-1 ITL killer)
        @partial(jax.jit, donate_argnums=(1, 2, 8, 9), out_shardings=decode_out_sh)
        def decode_block(params, kv_k, kv_v, tokens, positions, seq_lens, page_tables, samp, rng, pen):
            """K fused decode steps: sampled tokens feed the next step on
            device — one host read per K*B tokens instead of per token.
            Each step scatters its new K/V rows into the (donated) pool."""
            rng, sub = jax.random.split(rng)
            keys = jax.random.split(sub, K)
            W = pen.shape[1]
            B = tokens.shape[0]

            def step(carry, k):
                tokens, positions, seq_lens, kv_k, kv_v, pen = carry
                if cfg.pp_size > 1:
                    # layers pipelined over pp: each step is a full
                    # microbatch schedule (parallel/pipeline.py)
                    logits, kv_k, kv_v = self._model.decode_forward_pp(
                        params, c, tokens, positions, kv_k, kv_v,
                        page_tables, seq_lens, self._mesh,
                    )
                else:
                    logits, kv_k, kv_v = self._model.decode_forward(
                        params, c, tokens, positions, kv_k, kv_v, page_tables, seq_lens
                    )
                plogits = penalized(logits, samp, pen)
                nxt, lp, tid, tlp = sample_lp(
                    plogits, samp, k, positions=positions, raw=logits
                )
                pen = pen.at[jnp.arange(B), (positions + 1) % W].set(nxt)
                return (
                    (nxt, positions + 1, seq_lens + 1, kv_k, kv_v, pen),
                    (nxt, lp, tid, tlp),
                )

            (tokens, positions, seq_lens, kv_k, kv_v, pen), toks = jax.lax.scan(
                step, (tokens, positions, seq_lens, kv_k, kv_v, pen), keys
            )
            return toks, tokens, positions, seq_lens, kv_k, kv_v, rng, pen

        self._decode_block = decode_block

        self._spec_block_fn = None
        if cfg.spec_mode == "ngram":
            from .spec import hist_write, ngram_draft, verify_accept

            S = cfg.spec_rounds
            d_len = cfg.spec_draft_len
            ng = cfg.spec_ngram
            Hc = cfg.spec_hist
            Tc = d_len + 1

            spec_out_sh = None
            if self._mesh is not None:
                from jax.sharding import NamedSharding, PartitionSpec

                repl = NamedSharding(self._mesh, PartitionSpec())
                kvs = self._kv_sharding or repl
                spec_out_sh = (
                    repl, repl, repl, repl, repl, kvs, kvs, repl, repl,
                )

            @partial(jax.jit, donate_argnums=(1, 2, 8, 9),
                     out_shardings=spec_out_sh)
            def spec_block(params, kv_k, kv_v, tokens, positions, seq_lens,
                           page_tables, samp, rng, hist):
                """S draft-verify rounds (engine/spec.py). Each round: write
                the current token into the history ring, n-gram-draft d
                continuations, verify all 1+d in ONE batched-prefill pass
                (one weight stream instead of 1+d), accept the longest
                matching prefix. Emits 1..1+d tokens per lane per round —
                never fewer than plain decode."""
                B = tokens.shape[0]

                def round_fn(carry, key):
                    tokens, positions, seq_lens, kv_k, kv_v, hist = carry
                    hist = hist_write(hist, positions, tokens)
                    draft = ngram_draft(hist, tokens, positions, ng, d_len)
                    chunk = jnp.concatenate([tokens[:, None], draft], axis=1)
                    cpos = positions[:, None] + jnp.arange(Tc)[None, :]
                    logits, kv_k, kv_v = self._model.prefill_forward_batched(
                        params, c, chunk, cpos, kv_k, kv_v, page_tables,
                        positions,  # context_lens: tokens already in KV
                        jnp.full((B,), d_len, jnp.int32),
                        all_logits=True,
                    )
                    out_toks, n_emit, key = verify_accept(
                        logits.astype(jnp.float32), draft, samp, key
                    )
                    new_tokens = out_toks[jnp.arange(B), n_emit - 1]
                    # ring-append the emitted tokens (pos+1 .. pos+n_emit);
                    # invalid tail indices point out of bounds -> dropped
                    wpos = positions[:, None] + 1 + jnp.arange(Tc)[None, :]
                    slot_i = jnp.where(
                        jnp.arange(Tc)[None, :] < n_emit[:, None],
                        wpos % Hc, Hc,
                    )
                    hist = hist.at[
                        jnp.arange(B)[:, None], slot_i
                    ].set(out_toks, mode="drop")
                    positions = positions + n_emit
                    seq_lens = seq_lens + n_emit
                    return (
                        (new_tokens, positions, seq_lens, kv_k, kv_v, hist),
                        (out_toks, n_emit),
                    )

                rng, sub = jax.random.split(rng)
                keys = jax.random.split(sub, S)
                (tokens, positions, seq_lens, kv_k, kv_v, hist), (toks_s, n_emit_s) = jax.lax.scan(
                    round_fn, (tokens, positions, seq_lens, kv_k, kv_v, hist), keys
                )
                return (
                    toks_s, n_emit_s, tokens, positions, seq_lens,
                    kv_k, kv_v, rng, hist,
                )

            self._spec_block_fn = spec_block

        @partial(jax.jit, donate_argnums=(1, 2, 9), out_shardings=prefill_out_sh)
        def prefill_batch(params, kv_k, kv_v, tokens, positions, page_tables, ctx_lens, last_idx, samp, rng, pen):
            """Batched chunked prefill + on-device first-token sampling."""
            rng, sub = jax.random.split(rng)
            logits, kv_k, kv_v = self._model.prefill_forward_batched(
                params, c, tokens, positions, kv_k, kv_v, page_tables, ctx_lens, last_idx
            )
            plogits = penalized(logits, samp, pen)
            first = sample_lp(
                plogits, samp, sub, positions=ctx_lens + last_idx, raw=logits
            )
            return first, kv_k, kv_v, rng

        self._prefill_batch = prefill_batch

        # a stateful family's cache learns the lane of each row from the
        # step's own buffer (the pack's "lanes": _blank_mixed_pack)
        lanes_key = ("lanes",) if self._stateful else ()

        @partial(jax.jit, donate_argnums=(1, 2, 4), static_argnums=(7,),
                 out_shardings=prefill_out_sh)
        def mixed_step(params, kv_k, kv_v, ops, rng, carry_tok, carry_pen,
                       layout):
            """Unified mixed step: ONE ragged forward over a flat buffer
            packing prefill chunks (row_len > 1) and decode lanes
            (row_len == 1), with each row's last-token logits sampled on
            device — the fused replacement for a prefill_batch dispatch
            followed by a decode dispatch (docs/ragged_attention.md).
            Attention rides ops/pallas_ragged_attention on TPU, the XLA
            ragged reference elsewhere. Decode rows of a piped pack take
            their token and penalty window from the decode carry by lane
            (carry_read), so the step queues behind whatever is in flight.
            The host's operands arrive as ONE buffer (`ops`, JaxEngine.
            _put_words) and are taken apart here by static slices."""
            o = riders(MIXED_KEYS + CARRY_KEYS + lanes_key, ops, layout)
            if lanes_key:
                kv_k = kv_k.replace(lanes=o["lanes"])
            samp = rows_sampling(o)
            row_lens, ctx_lens = o["row_lens"], o["ctx_lens"]
            tokens, pen_rows = carry_read(
                o["toks"], o["pen_rows"], o["row_starts"], o["row_lane"],
                carry_tok, carry_pen,
            )
            rng, sub = jax.random.split(rng)
            logits, kv_k, kv_v = self._model.ragged_forward(
                params, c, tokens, o["positions"], o["row_ids"], kv_k, kv_v,
                o["tables"], o["row_starts"], row_lens, ctx_lens,
                o["last_flat"], long_rows=cfg.max_prefill_batch,
            )
            plogits = penalized(logits, samp, pen_rows)
            # the sampled token's position counter: the row's last real
            # token (= ctx + last_idx for prefill rows, seq_len - 1 for
            # decode rows) — identical to what the split dispatches use,
            # so seeded streams don't depend on the dispatch shape
            first = sample_lp(
                plogits, samp, sub, positions=ctx_lens + row_lens - 1,
                raw=logits,
            )
            return first, kv_k, kv_v, rng

        self._mixed_step = mixed_step

        @partial(jax.jit, donate_argnums=(1, 2, 4), static_argnums=(7,),
                 out_shardings=prefill_out_sh)
        def mixed_step_variant(params, kv_k, kv_v, ops, rng, mask_packed,
                               lora, layout):
            """Mixed step for VARIANT row classes (guided / multi-LoRA /
            speculative): same ragged forward + per-row sampling as
            mixed_step plus a bitpacked per-row FSM admissibility mask
            (all-ones rows are an exact no-op — the invariant the split
            guided variants already rely on) and, when adapters are
            registered, the LoRA stack with per-row adapter indices
            (index 0 = the all-zero base adapter, an exact no-op).
            Speculative verify rows need no extra operand: they are
            ordinary one-token rows whose ctx includes their sibling
            draft rows' KV (written before attention each layer).
            A separate lazy jit so plain blended-free traffic never
            carries the mask/adapter operands. The mask (R x V/8 bytes)
            is an operand of its own beside the step's buffer; the rows'
            adapter indices ride the buffer."""
            idx_key = ("lora_idx",) if lora is not None else ()
            o = riders(MIXED_KEYS + idx_key + lanes_key, ops, layout)
            if lanes_key:
                kv_k = kv_k.replace(lanes=o["lanes"])
            if lora is not None:
                lora = dict(lora, idx=o["lora_idx"])
            samp = rows_sampling(o)
            row_lens, ctx_lens = o["row_lens"], o["ctx_lens"]
            rng, sub = jax.random.split(rng)
            logits, kv_k, kv_v = self._model.ragged_forward(
                params, c, o["toks"], o["positions"], o["row_ids"], kv_k,
                kv_v, o["tables"], o["row_starts"], row_lens, ctx_lens,
                o["last_flat"], lora=lora, long_rows=cfg.max_prefill_batch,
            )
            plogits = penalized(logits, samp, o["pen_rows"])
            mask = unpack_mask(mask_packed, c.vocab_size)
            first = sample_lp(
                plogits, samp, sub, mask=mask,
                positions=ctx_lens + row_lens - 1, raw=logits,
            )
            return first, kv_k, kv_v, rng

        self._mixed_step_variant = mixed_step_variant

        @partial(jax.jit, donate_argnums=(1, 2, 9), out_shardings=prefill_out_sh)
        def prefill_batch_mm(params, kv_k, kv_v, tokens, positions, page_tables,
                             ctx_lens, last_idx, samp, rng, pen, emb, emb_mask):
            """Batched prefill with the multimodal embedding splice: encoder
            rows replace placeholder-token embeddings (E/P/D flow). A
            separate program so text-only dispatches never carry the
            [B, T, H] override operand. jax.jit is lazy — this compiles
            only when a multimodal request actually arrives."""
            rng, sub = jax.random.split(rng)
            logits, kv_k, kv_v = self._model.prefill_forward_batched(
                params, c, tokens, positions, kv_k, kv_v, page_tables,
                ctx_lens, last_idx, emb_override=emb, emb_mask=emb_mask,
            )
            plogits = penalized(logits, samp, pen)
            first = sample_lp(
                plogits, samp, sub, positions=ctx_lens + last_idx, raw=logits
            )
            return first, kv_k, kv_v, rng

        self._prefill_batch_mm = prefill_batch_mm

        # guided-decoding variants (llm/guided.py): same programs with a
        # [B, V] admissibility mask applied inside the sampler. Separate
        # jits so unguided dispatches never carry the mask operand —
        # jax.jit is lazy, these compile only when a guided request
        # actually arrives. The decode variant is a SINGLE step: the mask
        # for step t+1 depends host-side on the token emitted at step t,
        # so guided decode cannot ride the K-step fused block.
        @partial(jax.jit, donate_argnums=(1, 2, 8, 10), out_shardings=decode_out_sh)
        def decode_step_guided(params, kv_k, kv_v, tokens, positions, seq_lens,
                               page_tables, samp, rng, mask_packed, pen):
            rng, sub = jax.random.split(rng)
            if cfg.pp_size > 1:
                logits, kv_k, kv_v = self._model.decode_forward_pp(
                    params, c, tokens, positions, kv_k, kv_v,
                    page_tables, seq_lens, self._mesh,
                )
            else:
                logits, kv_k, kv_v = self._model.decode_forward(
                    params, c, tokens, positions, kv_k, kv_v, page_tables, seq_lens
                )
            plogits = penalized(logits, samp, pen)
            mask = unpack_mask(mask_packed, c.vocab_size)
            nxt, lp, tid, tlp = sample_lp(
                plogits, samp, sub, mask=mask, positions=positions, raw=logits
            )
            pen = pen.at[
                jnp.arange(pen.shape[0]), (positions + 1) % pen.shape[1]
            ].set(nxt)
            return (
                (nxt[None], lp[None], tid[None], tlp[None]),
                nxt, positions + 1, seq_lens + 1, kv_k, kv_v, rng, pen,
            )

        self._decode_step_guided = decode_step_guided

        # guided + LoRA lanes decode-active TOGETHER: the masked single
        # step must still apply the LoRA deltas, or the LoRA lane would
        # silently generate (and write KV!) with the base model while a
        # guided request is in flight
        @partial(jax.jit, donate_argnums=(1, 2, 8, 10), out_shardings=decode_out_sh)
        def decode_step_guided_lora(params, kv_k, kv_v, tokens, positions,
                                    seq_lens, page_tables, samp, rng,
                                    mask_packed, pen, lora):
            rng, sub = jax.random.split(rng)
            logits, kv_k, kv_v = self._model.decode_forward(
                params, c, tokens, positions, kv_k, kv_v, page_tables,
                seq_lens, lora=lora,
            )
            plogits = penalized(logits, samp, pen)
            mask = unpack_mask(mask_packed, c.vocab_size)
            nxt, lp, tid, tlp = sample_lp(
                plogits, samp, sub, mask=mask, positions=positions, raw=logits
            )
            pen = pen.at[
                jnp.arange(pen.shape[0]), (positions + 1) % pen.shape[1]
            ].set(nxt)
            return (
                (nxt[None], lp[None], tid[None], tlp[None]),
                nxt, positions + 1, seq_lens + 1, kv_k, kv_v, rng, pen,
            )

        self._decode_step_guided_lora = decode_step_guided_lora

        @partial(jax.jit, donate_argnums=(1, 2, 9), out_shardings=prefill_out_sh)
        def prefill_batch_guided(params, kv_k, kv_v, tokens, positions,
                                 page_tables, ctx_lens, last_idx, samp, rng,
                                 pen, mask_packed):
            rng, sub = jax.random.split(rng)
            logits, kv_k, kv_v = self._model.prefill_forward_batched(
                params, c, tokens, positions, kv_k, kv_v, page_tables,
                ctx_lens, last_idx
            )
            plogits = penalized(logits, samp, pen)
            mask = unpack_mask(mask_packed, c.vocab_size)
            first = sample_lp(
                plogits, samp, sub, mask=mask,
                positions=ctx_lens + last_idx, raw=logits
            )
            return first, kv_k, kv_v, rng

        self._prefill_batch_guided = prefill_batch_guided

        # multi-LoRA variants (models/lora.py): the adapter stack + per-lane
        # index ride as operands; base-model lanes carry index 0 (the
        # all-zero adapter — an exact no-op), so mixed batches need no
        # masking. Lazy jits: compile only when adapters are registered and
        # a LoRA request arrives. K-step fused blocks work unchanged —
        # adapters are static per lane, unlike guided masks.
        @partial(jax.jit, donate_argnums=(1, 2, 8, 9), out_shardings=decode_out_sh)
        def decode_block_lora(params, kv_k, kv_v, tokens, positions, seq_lens,
                              page_tables, samp, rng, pen, lora):
            rng, sub = jax.random.split(rng)
            keys = jax.random.split(sub, K)
            W = pen.shape[1]
            B = tokens.shape[0]

            def step(carry, key_j):
                tokens, positions, seq_lens, kv_k, kv_v, pen = carry
                logits, kv_k, kv_v = self._model.decode_forward(
                    params, c, tokens, positions, kv_k, kv_v, page_tables,
                    seq_lens, lora=lora,
                )
                plogits = penalized(logits, samp, pen)
                nxt, lp, tid, tlp = sample_lp(
                    plogits, samp, key_j, positions=positions, raw=logits
                )
                pen = pen.at[jnp.arange(B), (positions + 1) % W].set(nxt)
                return (
                    (nxt, positions + 1, seq_lens + 1, kv_k, kv_v, pen),
                    (nxt, lp, tid, tlp),
                )

            (tokens, positions, seq_lens, kv_k, kv_v, pen), toks = jax.lax.scan(
                step, (tokens, positions, seq_lens, kv_k, kv_v, pen), keys
            )
            return toks, tokens, positions, seq_lens, kv_k, kv_v, rng, pen

        self._decode_block_lora = decode_block_lora

        @partial(jax.jit, donate_argnums=(1, 2, 9), out_shardings=prefill_out_sh)
        def prefill_batch_lora(params, kv_k, kv_v, tokens, positions,
                               page_tables, ctx_lens, last_idx, samp, rng,
                               pen, lora):
            rng, sub = jax.random.split(rng)
            logits, kv_k, kv_v = self._model.prefill_forward_batched(
                params, c, tokens, positions, kv_k, kv_v, page_tables,
                ctx_lens, last_idx, lora=lora,
            )
            plogits = penalized(logits, samp, pen)
            first = sample_lp(
                plogits, samp, sub, positions=ctx_lens + last_idx, raw=logits
            )
            return first, kv_k, kv_v, rng

        self._prefill_batch_lora = prefill_batch_lora

        # single-sequence prefill variants for the native parallel layouts
        # (SURVEY.md §2.5): ring attention over sp (long-context), layer
        # pipeline over pp. Both sample the first token on device.
        self._prefill_single = None
        if self._mesh is not None and (cfg.sp_size > 1 or cfg.pp_size > 1):
            mode = "pp" if cfg.pp_size > 1 else "ring"
            from jax.sharding import NamedSharding, PartitionSpec

            repl = NamedSharding(self._mesh, PartitionSpec())
            kvs = self._kv_sharding or repl
            single_out_sh = (repl, kvs, kvs, repl)

            @partial(jax.jit, donate_argnums=(1, 2, 7), out_shardings=single_out_sh)
            def prefill_single(params, kv_k, kv_v, toks, table, ctx_len, real_len, rng, samp, pen):
                rng, sub = jax.random.split(rng)
                if mode == "pp":
                    logits, kv_k, kv_v = self._model.prefill_forward_pp(
                        params, c, toks, kv_k, kv_v, table, ctx_len, real_len,
                        self._mesh,
                    )
                else:
                    logits, kv_k, kv_v = self._model.prefill_forward_ring(
                        params, c, toks, kv_k, kv_v, table, real_len, self._mesh
                    )
                first = sample_lp(
                    penalized(logits[None], samp, pen), samp, sub,
                    positions=(ctx_len + real_len - 1)[None],
                    raw=logits[None],
                )
                return first, kv_k, kv_v, rng

            self._prefill_single = prefill_single

        # per-lane carry patch: admissions/finishes update ONLY their lanes
        # on device instead of invalidating the whole carry (a full reset
        # forces a pipeline drain + re-upload — the round-2 ITL gap under
        # churn). lane_mask patches carry+sampling+table; table_mask extends
        # to lanes whose page table grew mid-decode (their carry values on
        # device are NEWER than host state and must not be overwritten).
        patch_out_sh = write_out_sh = None
        if self._mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            repl = NamedSharding(self._mesh, PartitionSpec())
            patch_out_sh = (repl,) * 12
            write_out_sh = (repl,) * 4

        @partial(jax.jit, static_argnums=(13,), out_shardings=patch_out_sh)
        def patch_lanes(
            tokens, positions, seq_lens, tables, temps, top_ks, top_ps, seeds,
            presence, frequency, repetition, recent, ops, layout,
        ):
            # the host's side arrives as ONE buffer (_dev_patch)
            n = riders(PATCH_KEYS, ops, layout)
            lane_mask, table_mask = n["lane_mask"], n["table_mask"]
            tokens = jnp.where(lane_mask, n["tokens"], tokens)
            positions = jnp.where(lane_mask, n["positions"], positions)
            seq_lens = jnp.where(lane_mask, n["seq_lens"], seq_lens)
            temps = jnp.where(lane_mask, n["temps"], temps)
            top_ks = jnp.where(lane_mask, n["top_ks"], top_ks)
            top_ps = jnp.where(lane_mask, n["top_ps"], top_ps)
            seeds = jnp.where(lane_mask, n["seeds"], seeds)
            # the three penalty columns in and out as the sampler holds
            # them: no stack before the call, no slices of its result
            n_pens = n["pens"]
            presence = jnp.where(lane_mask, n_pens[:, 0], presence)
            frequency = jnp.where(lane_mask, n_pens[:, 1], frequency)
            repetition = jnp.where(lane_mask, n_pens[:, 2], repetition)
            recent = jnp.where(lane_mask[:, None], n["recent"], recent)
            tables = jnp.where(table_mask[:, None], n["tables"], tables)
            return (
                tokens, positions, seq_lens, tables, temps, top_ks, top_ps,
                seeds, presence, frequency, repetition, recent,
            )

        self._patch_lanes = patch_lanes
        # a piped mixed step's samples into the carry (carry_write), by
        # the two maps that rode the step's buffer: a program a layout of
        # the family. A def of this engine's own (jit keeps its cache by
        # the function it wraps): `_surface_cache_sizes` then counts this
        # engine's programs only
        @partial(jax.jit, static_argnums=(6,), out_shardings=write_out_sh)
        def _carry_write(tok_d, pos_d, sl_d, pen_d, sampled, ops, layout):
            o = riders(MIXED_KEYS + CARRY_KEYS + lanes_key, ops, layout)
            return carry_write(
                tok_d, pos_d, sl_d, pen_d, sampled, o["w_lane"], o["w_pos"])

        self._carry_write = _carry_write
        # disagg KV movement (host-staged; llm/disagg.py wire format).
        # ops/kv_quant's accessors cover both store shapes: a plain fp
        # pool, or a QuantKV whose q pages AND per-page scales
        # gather/scatter on the same `[:, page_ids]` slice — scales travel
        # with their pages through every tier/wire hop. Pages cross this
        # boundary as [L, n, rows, KH, D] (a reshape of the gathered
        # pages: the pool itself stays lane-dense), which is what KVBM,
        # the KV data plane and disagg have always carried.
        from ..ops import kv_quant

        @jax.jit
        def extract_pages(kv_k, kv_v, page_ids):
            return (
                kv_quant.extract_pages(kv_k, page_ids, c.num_kv_heads),
                kv_quant.extract_pages(kv_v, page_ids, c.num_kv_heads),
            )

        self._extract_pages = extract_pages

        @partial(jax.jit, donate_argnums=(0, 1))
        def inject_pages(kv_k, kv_v, page_ids, data_k, data_v):
            return (
                kv_quant.inject_pages(kv_k, page_ids, data_k),
                kv_quant.inject_pages(kv_v, page_ids, data_v),
            )

        self._inject_pages = inject_pages

        # per-surface compile telemetry (docs/compilation.md): every
        # staged callable keyed by its COMPILE_SURFACES registry name, so
        # stats() can report XLA cache growth per surface and the replay
        # compile smoke can gate on zero post-warmup recompiles. Keys
        # MUST match engine/compile_registry.py — dynocomp's registry
        # rule anchors the static contract, this map closes it at runtime
        self._compiled_surfaces = {
            "decode_block": self._decode_block,
            "spec_block": self._spec_block_fn,
            "prefill_batch": self._prefill_batch,
            "mixed_step": self._mixed_step,
            "mixed_step_variant": self._mixed_step_variant,
            "prefill_batch_mm": self._prefill_batch_mm,
            "decode_step_guided": self._decode_step_guided,
            "decode_step_guided_lora": self._decode_step_guided_lora,
            "prefill_batch_guided": self._prefill_batch_guided,
            "decode_block_lora": self._decode_block_lora,
            "prefill_batch_lora": self._prefill_batch_lora,
            "prefill_single": self._prefill_single,
            "patch_lanes": self._patch_lanes,
            "carry_write": self._carry_write,
            "extract_pages": self._extract_pages,
            "inject_pages": self._inject_pages,
        }
        # snapshot of per-surface cache sizes taken when warmup finishes;
        # None until then (pre-warmup compiles are expected, not debt)
        self._warmup_compile_baseline = None

    def _surface_cache_sizes(self) -> dict:
        """Per-surface XLA executable counts from jit's compilation cache
        (PjitFunction._cache_size, private but present in the installed
        jax 0.9.0); 0 for a surface this config does not build. A jit
        object without the probe raises: a silent 0 here would report
        post_warmup_compiles == 0 whatever happened."""
        return {
            name: 0 if fn is None else int(fn._cache_size())
            for name, fn in self._compiled_surfaces.items()
        }

    # ------------------------------------------------------------------ #
    # lifecycle / interface (MockEngine-compatible)
    # ------------------------------------------------------------------ #

    def start(self):
        if self._step_task is None:
            self._step_task = asyncio.create_task(self._step_loop())

    async def close(self):
        self._closed = True
        self._wake.set()
        if self._step_task:
            self._step_task.cancel()
        # in-flight KV pulls: their slots are dead with the engine, and a
        # pull left running would keep injecting into reused pages
        for t in list(self._bg_tasks):
            t.cancel()
        if self._early_fetch is not None:
            self._early_fetch.cancel()
        if self.kvbm is not None:
            # flush any staged commits, drain in-flight write-through
            # offloads (staged + queued), stop the tier thread, then
            # persist the G3 index
            self.kvbm.flush_step()
            for _ in range(500):
                if self.kvbm.pending_offloads() == 0:
                    break
                await asyncio.sleep(0.01)
            self.kvbm.shutdown()
            self.kvbm.manager.flush()

    async def _warmup_request(self, req: dict, on_item=None):
        """One warmup request to its end. An error item means a program
        did not compile or a step failed: that is a failed warmup, and the
        worker must not go on to register and serve (on the chip a decode
        block once ran out of HBM in the compiler while warmup carried on
        and retried it for a quarter of an hour)."""
        async for item in self.generate(req, Context()):
            if item.get("event") == "error":
                raise RuntimeError(
                    f"warmup request failed: {item.get('comment')}"
                )
            if on_item is not None:
                on_item()

    async def warmup(self) -> int:
        """Compile every dispatch variant BEFORE serving traffic.

        A first-request compile of a full-depth program takes tens of
        seconds; paying it on-path once the worker is registered starves
        discovery-lease renewal and breaks in-flight streams (the round-4
        e2e ladder failure: worker dropped from the control plane
        mid-compile, 96/96 requests "no instances available"). Driving the real `generate` path pre-registration
        compiles the bounded variant space — per-bucket {1, cap}-lane
        batched prefill, decode reset/patch/block — into the persistent
        XLA cache, so restarts are cheap. Returns the number of warmup
        requests served. vLLM analogue: GPU-worker profile/warmup runs
        before the engine reports ready."""
        import numpy as _np

        t_start = time.monotonic()
        rng = _np.random.RandomState(0xD74A)
        vocab = self.model_config.vocab_size
        K = self.config.decode_block_steps

        async def _drain(isl: int):
            req = PreprocessedRequest(
                token_ids=rng.randint(5, max(vocab - 1, 6), size=isl).tolist(),
                stop_conditions={"max_tokens": K + 2, "ignore_eos": True},
                sampling_options={"temperature": 1.0},
            ).to_dict()
            await self._warmup_request(req)

        n = 0
        buckets = [
            b for b in self.config.prefill_buckets
            if b <= self.config.max_model_len
        ] or [self.config.prefill_buckets[0]]
        prev = 0
        for b in buckets:
            # both ends of this bucket's first-chunk range: the prefill
            # page-table axis (P = next_pow2(pages) + 1) changes rung
            # WITHIN a bucket, so a single isl per bucket leaves page
            # variants to compile on-path (the --compile-smoke replay
            # gate caught exactly that)
            isls = sorted({max(prev + 1, 4), max(b - 8, 4), b})
            for isl in isls:
                # lone arrival: the 1-lane prefill variant (+ decode
                # block/reset on the first pass)
                await _drain(isl)
                n += 1
            cap = max(1, min(
                self.config.prefill_batch_tokens // b,
                self.config.max_prefill_batch,
            ))
            if cap > 1:
                # concurrent arrivals batch into the padded cap-lane
                # variant; admissions mid-decode also exercise _dev_patch.
                # Burst at both page rungs — the P axis is orthogonal to
                # the lane axis
                burst = min(cap, 3)
                for isl in (isls[0], isls[-1]):
                    await asyncio.gather(*[_drain(isl) for _ in range(burst)])
                    n += burst
            prev = b
        long_isl = self.config.max_model_len - K - 4
        if long_isl > buckets[-1]:
            # one long prompt walks the chunked-prefill path: successive
            # chunks carry more context pages, compiling the upper
            # page-table rungs no single-chunk prompt reaches
            await _drain(long_isl)
            n += 1
        if (
            self.config.pp_size == 1 and self.config.sp_size == 1
            and (not self.config.spec_mode or self._mixed_enabled)
        ):
            # compile the guided prefill/decode variants too (a first
            # guided request on-path would otherwise pay the compile) —
            # at both bucket ends, matching the plain coverage. Under
            # spec_mode guided is admittable only via the fused path, so
            # the gate relaxes exactly with _mixed_enabled.
            for isl in sorted({
                max(buckets[0] - 8, 4), max(buckets[-1] - 8, 4)
            }):
                req = PreprocessedRequest(
                    token_ids=rng.randint(
                        5, max(vocab - 1, 6), size=isl
                    ).tolist(),
                    stop_conditions={"max_tokens": 3},
                    sampling_options={"temperature": 1.0},
                    guided={"kind": "regex", "regex": "[ab]*"},
                ).to_dict()
                await self._warmup_request(req)
                n += 1
        if self._mixed_enabled:
            # compile the unified mixed-step variant: a staggered pair puts
            # one request in decode while the other's prefill chunk is
            # runnable, so the fused ragged program (ragged_forward +
            # sampling) compiles before serving traffic instead of on-path
            isl = max(buckets[0] - 8, 4)
            t1 = asyncio.create_task(_drain(isl))
            await asyncio.sleep(0.05)
            t2 = asyncio.create_task(_drain(isl))
            await asyncio.gather(t1, t2)
            n += 2
        if self._lora is not None and self._lora["names"]:
            # compile the LoRA prefill/decode variants with a registered
            # adapter (same on-path-compile hazard as the guided
            # variants), again at both bucket ends
            for isl in sorted({
                max(buckets[0] - 8, 4), max(buckets[-1] - 8, 4)
            }):
                req = PreprocessedRequest(
                    token_ids=rng.randint(
                        5, max(vocab - 1, 6), size=isl
                    ).tolist(),
                    stop_conditions={"max_tokens": K + 2, "ignore_eos": True},
                    sampling_options={"temperature": 1.0},
                    lora_name=next(iter(self._lora["names"])),
                ).to_dict()
                await self._warmup_request(req)
                n += 1
        if self._mixed_enabled and (
            self.config.pp_size == 1 and self.config.sp_size == 1
        ):
            # fused-dispatch variants (lean + mask/adapter operand
            # program): a fused step's page-table axis rides the DECODE
            # rows' context, so blended traffic arriving mid-decode of a
            # long generation lands on table rungs the short staggered
            # pair never reaches. Anchor one long-prompt decode per pow2
            # table rung and admit plain (lean), guided and lora
            # (variant) arrivals beside it — at both chunk-bucket ends —
            # so every (token bucket, table rung) pair steady blended
            # traffic hits is compiled pre-serving
            # (post_warmup_compiles == 0 must hold on blended traffic).
            page = self.config.page_size
            anchor_osl = 8 * K
            anchor_isls = []
            pages = 2
            while pages * page + anchor_osl + 8 <= self.config.max_model_len:
                anchor_isls.append(max(pages * page - 4, 4))
                pages *= 2

            async def _drain_long(isl: int, started: asyncio.Event):
                req = PreprocessedRequest(
                    token_ids=rng.randint(
                        5, max(vocab - 1, 6), size=isl
                    ).tolist(),
                    stop_conditions={"max_tokens": anchor_osl,
                                     "ignore_eos": True},
                    sampling_options={"temperature": 1.0},
                ).to_dict()
                await self._warmup_request(req, on_item=started.set)

            async def _drain_req(r):
                await self._warmup_request(dict(r))

            def _mk_variant_reqs(isl: int) -> list:
                reqs = [PreprocessedRequest(
                    token_ids=rng.randint(
                        5, max(vocab - 1, 6), size=isl
                    ).tolist(),
                    stop_conditions={"max_tokens": 4, "ignore_eos": True},
                    sampling_options={"temperature": 1.0},
                ).to_dict(), PreprocessedRequest(
                    token_ids=rng.randint(
                        5, max(vocab - 1, 6), size=isl
                    ).tolist(),
                    stop_conditions={"max_tokens": 4},
                    sampling_options={"temperature": 1.0},
                    guided={"kind": "regex", "regex": "[ab]*"},
                ).to_dict()]
                if self._lora is not None and self._lora["names"]:
                    reqs.append(PreprocessedRequest(
                        token_ids=rng.randint(
                            5, max(vocab - 1, 6), size=isl
                        ).tolist(),
                        stop_conditions={"max_tokens": 4,
                                         "ignore_eos": True},
                        sampling_options={"temperature": 1.0},
                        lora_name=next(iter(self._lora["names"])),
                    ).to_dict())
                return reqs

            chunk_isls = sorted({
                max(buckets[0] - 8, 4), max(buckets[-1] - 8, 4)
            })
            for a_isl in anchor_isls:
                # sequential arrivals: each fuses ALONE beside the anchor,
                # pinning the token bucket to its own chunk. The anchor is
                # (re)started on demand and each admission gates on the
                # anchor having just emitted (not wall time — post-compile
                # step cadence is far faster than any fixed sleep)
                anchor = None
                for isl in chunk_isls:
                    for vreq in _mk_variant_reqs(isl):
                        if anchor is None or anchor.done():
                            started = asyncio.Event()
                            anchor = asyncio.create_task(
                                _drain_long(a_isl, started)
                            )
                            await started.wait()
                            n += 1
                        await _drain_req(vreq)
                        n += 1
                await anchor
            if self._lora is not None and self._lora["names"]:
                # guided + lora lanes decoding in the SAME split decode
                # block: the combined-kind decode program no single-kind
                # warmup request reaches
                _, g_req, l_req = _mk_variant_reqs(chunk_isls[0])
                g_req["stop_conditions"]["max_tokens"] = K + 2
                l_req["stop_conditions"]["max_tokens"] = K + 2
                await asyncio.gather(_drain_req(g_req), _drain_req(l_req))
                n += 2
        # steady-state contract line: every XLA program compiled from
        # here on counts as a post-warmup recompile
        # (stats()['post_warmup_compiles'], 0 is the contract)
        self._warmup_compile_baseline = self._surface_cache_sizes()
        self.warmup_seconds = time.monotonic() - t_start
        return n

    # ------------------------------------------------------------------ #
    # live role morphing (docs/autoscaling.md "Role morphing")
    # ------------------------------------------------------------------ #

    _ROLES = {
        "prefill": {"prefill"},
        "decode": {"decode"},
        "both": {"prefill", "decode"},
    }

    async def warmup_role(self, role: str) -> int:
        """Trimmed re-warm for the INCOMING role of a morph: drive the
        role's hot compile surfaces (per-bucket short-output prefill for
        a prefill worker; short-prompt decode blocks for a decode worker)
        through the real generate path, then refresh the post-warmup
        compile baseline so morph-time compiles never count as
        steady-state recompile debt (stats()['post_warmup_compiles']).
        Cheap by construction: the full warmup() already populated the
        persistent XLA cache at boot, so these replays hit it — the point
        is paying any residual first-dispatch cost BEFORE the flipped
        worker takes traffic, the same contract warmup() holds at boot."""
        import numpy as _np

        rng = _np.random.RandomState(0xD74B)
        vocab = self.model_config.vocab_size
        K = self.config.decode_block_steps

        async def _drain(isl: int, max_tokens: int):
            req = PreprocessedRequest(
                token_ids=rng.randint(5, max(vocab - 1, 6), size=isl).tolist(),
                stop_conditions={"max_tokens": max_tokens, "ignore_eos": True},
                sampling_options={"temperature": 1.0},
            ).to_dict()
            await self._warmup_request(req)

        buckets = [
            b for b in self.config.prefill_buckets
            if b <= self.config.max_model_len
        ] or [self.config.prefill_buckets[0]]
        n = 0
        if "prefill" in self._ROLES[role]:
            for b in buckets:
                await _drain(max(b - 8, 4), 1)
                n += 1
        if "decode" in self._ROLES[role]:
            for _ in range(2):
                await _drain(max(buckets[0] - 8, 4), K + 2)
                n += 1
        self._warmup_compile_baseline = self._surface_cache_sizes()
        return n

    async def _await_sever_consumed(self, timeout_s: float):
        """Hold the flip until every severed stream's sentinel has been
        picked up by its consumer (the caller is now migrating) — the
        drain budget DYN_MORPH_DRAIN_TIMEOUT_S bounds the wait; expiry
        fails the morph and rolls back."""
        t0 = time.monotonic()
        while any(not q.empty() for q in self._severed_queues):
            if time.monotonic() - t0 > timeout_s:
                raise TimeoutError(
                    f"morph drain exceeded {timeout_s}s budget "
                    f"(severed stream consumer never woke)"
                )
            await asyncio.sleep(0.01)
        self._severed_queues = []

    async def morph(
        self,
        target_role: str,
        *,
        on_flip: Optional[Callable[[], Any]] = None,
    ) -> dict:
        """Re-role this live engine: serving → draining-role → flipped →
        warm → serving. In-flight streams of the outgoing role are
        severed so their sessions resume on peers from durable
        checkpoints (zero lost items, a tail of latency); `on_flip` is
        awaited between the role flip and re-warm so the worker harness
        can atomically move the discovery registration; warmup_role then
        re-warms the incoming role's compile surfaces before the worker
        takes traffic again.

        Failure semantics: any exception mid-morph rolls the engine back
        to its original role (drained sessions already resumed on peers —
        nothing to restore) EXCEPT faults.MorphCrash, which propagates so
        the harness tears the worker down crash-style."""
        from ..runtime.config import env_float

        if target_role not in self._ROLES:
            raise ValueError(f"unknown role {target_role!r}")
        if self._morph_state != "serving":
            raise RuntimeError(f"morph re-entered while {self._morph_state!r}")
        old_role = self._role
        if target_role == old_role:
            return {"from": old_role, "to": target_role,
                    "drained": 0, "duration_s": 0.0}
        t0 = time.monotonic()
        self._morph_state = "draining-role"
        try:
            f = faults.FAULTS
            if f.enabled:
                # dynochaos `worker.morph` (mid-drain): `error` exercises
                # rollback, `crash` the corpse path
                act = await f.on("worker.morph")
                if act == "crash":
                    raise faults.MorphCrash("injected crash mid-drain")
            drained = 0
            # sever when ANY previously-served lane is going away; "both"
            # keeps every lane, so growing into it drains nothing
            if self._ROLES[old_role] - self._ROLES[target_role]:
                drained = self._sever_all(
                    f"worker morphing {old_role}->{target_role}; "
                    "stream re-routed"
                )
                if drained:
                    await self._await_sever_consumed(
                        env_float("DYN_MORPH_DRAIN_TIMEOUT_S", 10.0)
                    )
            self.morph_drained_sessions += drained
            self._morph_state = "flipped"
            if f.enabled:
                # dynochaos `worker.morph` (mid-flip): same actions, after
                # the drain — rollback here proves sessions already moved
                act = await f.on("worker.morph")
                if act == "crash":
                    raise faults.MorphCrash("injected crash mid-flip")
            self._role = target_role
            if on_flip is not None:
                await on_flip()
            self._morph_state = "warm"
            await self.warmup_role(target_role)
        except asyncio.CancelledError:
            raise
        except faults.MorphCrash:
            raise  # harness tears the worker down mid-morph, no rollback
        except Exception:
            self._role = old_role
            self._morph_state = "serving"
            self.morphs_rolled_back += 1
            raise
        self._morph_state = "serving"
        self.morphs_completed += 1
        self.morph_last_duration_s = time.monotonic() - t0
        return {"from": old_role, "to": target_role,
                "drained": drained,
                "duration_s": self.morph_last_duration_s}

    def estimated_role_tok_s(self) -> Dict[str, float]:
        """Marginal per-role throughput from the cost model's observed
        EWMAs (s/token for prefill dispatches and decode blocks) — the
        numbers that price the planner's morph-vs-spawn decision. 0.0
        while the model is cold on a kind (the planner then falls back to
        its static seed costs)."""
        pf = self.scheduler.cost.per_token("prefill")
        dc = self.scheduler.cost.per_token("block")
        return {
            "prefill": 1.0 / pf if pf else 0.0,
            "decode": 1.0 / dc if dc else 0.0,
        }

    def _check_multimodal(self, req: PreprocessedRequest) -> Optional[str]:
        """None when the request is serveable; else the rejection reason.
        Serveable = text-only, OR every part carries encoder embeddings +
        a placeholder position (the encode hop ran; llm/multimodal.py)."""
        if not req.multimodal:
            return None
        H = self.model_config.hidden_size
        for p in req.multimodal:
            if p.get("embedding") is None or p.get("position") is None:
                return (
                    f"model {self.config.model!r} needs encoder embeddings "
                    f"for multimodal parts (type={p.get('type')!r}); "
                    f"deploy an encode worker (dynamo_tpu.encode_worker)"
                )
            # a malformed embedding must fail THIS request at admission —
            # inside the shared prefill dispatch it would _fail_all
            # co-active requests
            try:
                arr = np.asarray(p["embedding"], np.float32)
            except (ValueError, TypeError):
                return "multimodal embedding is not a numeric [n, hidden] array"
            if arr.ndim != 2 or arr.shape[1] != H or arr.shape[0] == 0:
                return (
                    f"multimodal embedding shape {arr.shape} does not match "
                    f"[n>0, hidden={H}] — encode worker configured for a "
                    f"different model?"
                )
            # keep the converted array: real encoders are MBs of nested
            # lists off the wire; _slot_mm must not convert again
            p["embedding"] = arr
        if self.config.pp_size > 1 or self.config.sp_size > 1:
            return "multimodal splice is not supported on pp/sp layouts yet"
        return None

    @staticmethod
    def _slot_mm(req: PreprocessedRequest) -> Optional[List[tuple]]:
        if not req.multimodal:
            return None
        return [
            (int(p["position"]), np.asarray(p["embedding"], np.float32))
            for p in req.multimodal
        ]

    def _guided_compiler(self):
        if self._guided is None:
            from ..llm.guided import GuidedCompiler

            tok = self.tokenizer
            if tok is None:
                from ..llm.tokenizers import ByteTokenizer

                tok = ByteTokenizer(self.model_config.vocab_size)
            self._guided = GuidedCompiler(tok)
        return self._guided

    def register_adapters(self, adapters) -> None:
        """Install LoRA adapters (models/lora.LoraAdapter list) behind the
        fixed-slot paging tier (models/lora_pool.LoraPool): the engine's
        stack reference stays live across onboard/evict, so registration
        is append-only and fleet rosters larger than the device slot count
        page on demand. In-flight LoRA requests keep their indices (their
        slots are pinned)."""
        from ..models import moe
        from ..models.lora_pool import LoraPool
        from ..runtime.config import env_int

        if isinstance(self.model_config, moe.MoeConfig):
            raise ValueError("LoRA serving is not supported on MoE models yet")
        if self._lora_pool is None:
            slots = self.config.lora_pool_slots
            if slots is None:
                slots = env_int("DYN_LORA_POOL_SLOTS", 8)
            self._lora_pool = LoraPool(
                self.model_config, list(adapters), slots=slots,
            )
        else:
            self._lora_pool.register(list(adapters))
        self._lora = self._lora_pool.stack

    def lora_names(self) -> List[str]:
        if self._lora_pool is not None:
            return self._lora_pool.known_names()
        return list(self._lora["names"]) if self._lora else []

    def _check_lora(self, req: PreprocessedRequest) -> Optional[str]:
        if not req.lora_name:
            return None
        cfg = self.config
        if self._lora is None or req.lora_name not in self.lora_names():
            return (
                f"unknown LoRA adapter {req.lora_name!r}; available: "
                f"{sorted(self.lora_names())}"
            )
        if cfg.spec_mode and not self._mixed_enabled:
            # fused spec verify rows carry the adapter index per row; the
            # split spec block has no adapter operand
            return "LoRA is incompatible with speculative decoding (spec_mode)"
        if cfg.pp_size > 1 or cfg.sp_size > 1:
            return "LoRA is not supported on pp/sp layouts yet"
        if req.guided:
            return "guided decoding with a LoRA adapter is not supported yet"
        if req.multimodal:
            return "LoRA with multimodal content parts is not supported yet"
        return None

    def _acquire_lora(self, req: PreprocessedRequest) -> Optional[str]:
        """Resolve + PIN the request's adapter in the paging tier
        (models/lora_pool.py). Hot adapters are a dict lookup; cold ones
        onboard here (bounded, EWMA-priced). A full-and-pinned pool or an
        injected `lora.onboard` fault refuses TYPED — a counted refusal
        the caller can retry/route, never a silent base-model answer.
        Must run LAST in the admission check chain: a later rejection
        would leak the pin."""
        if not req.lora_name or self._lora_pool is None:
            return None
        from ..models.lora_pool import LoraPoolError

        try:
            req._lora_slot = self._lora_pool.acquire(req.lora_name)
        except LoraPoolError as e:
            return str(e)
        return None

    def _release_lora_pin(self, slot: "_Slot") -> None:
        """Idempotent per-stream unpin (clears the name, so double release
        on the finish->release path is a no-op)."""
        if slot.lora_name and self._lora_pool is not None:
            self._lora_pool.release(slot.lora_name)
            slot.lora_name = ""

    def _check_logprobs(self, req: PreprocessedRequest) -> Optional[str]:
        s = req.sampling_options or {}
        if self.config.spec_mode and (
            s.get("presence_penalty") or s.get("frequency_penalty")
            or (s.get("repetition_penalty") or 1.0) != 1.0
        ):
            return (
                "sampling penalties are not supported with speculative "
                "decoding (the verify pass has no penalty hook); run the "
                "worker without --spec"
            )
        if (
            self.config.spec_mode
            and (req.sampling_options or {}).get("logprobs")
        ):
            return (
                "logprobs are not supported with speculative decoding "
                "(the verify pass emits accepted drafts without per-token "
                "logprobs); run the worker without --spec"
            )
        return None

    def _check_guided(self, req: PreprocessedRequest) -> Optional[str]:
        """Validate + pre-compile a guided-decoding spec. Returns an error
        string (rejected request) or None. Like multimodal, silently
        dropping the constraint would be a WRONG answer, not a degraded
        one — unsupported layouts reject up front."""
        if not req.guided:
            return None
        cfg = self.config
        if cfg.spec_mode and not self._mixed_enabled:
            # fused guided rows are single-token and host-authoritative per
            # step, so they coexist with spec lanes on the mixed dispatch;
            # the split-only layout still rejects
            return (
                "guided decoding is incompatible with speculative decoding "
                "(run the worker without --spec)"
            )
        if cfg.pp_size > 1 or cfg.sp_size > 1:
            return "guided decoding is not supported on pp/sp layouts yet"
        if req.multimodal:
            return "guided decoding cannot be combined with multimodal parts"
        return None

    async def _compile_guided_async(self, req: PreprocessedRequest) -> Optional[str]:
        """Static checks + FSM compilation OFF the event loop (DFA subset
        construction + the full-vocab trie walk are pure-Python and can
        take seconds on a cold schema; in-flight streams must not stall)."""
        err = self._check_guided(req)
        if err is not None or not req.guided:
            return err
        try:
            fsm = await asyncio.to_thread(
                self._guided_compiler().compile, req.guided
            )
        except ValueError as e:
            return f"guided spec rejected: {e}"
        # hand the FSM to _new_slot directly: an LRU eviction between the
        # off-loop compile and slot creation must not re-run the compile
        # ON the event loop
        req._compiled_fsm = fsm
        return None

    def _guided_lane_mask(self, fsm, state: int) -> np.ndarray:
        """fsm.allowed trimmed/padded to the MODEL vocab width (the
        tokenizer vocab may differ; out-of-tokenizer logits rows are
        inadmissible)."""
        V = self.model_config.vocab_size
        row = fsm.allowed(state)
        if len(row) == V:
            return row
        if len(row) > V:
            return row[:V]
        out = np.zeros((V,), bool)
        out[: len(row)] = row
        return out

    def _new_slot(self, req: PreprocessedRequest, context: Context, suffix: str = "") -> _Slot:
        """The slot of a request as it arrives. The making (the prompt is
        hashed block by block in it) is the span `engine.ingest`, and ends
        the stage `ingest` of a request that came with a timeline: it runs
        as a task of the event loop whenever the engine's own loop yields."""
        with self._rec.ingest():
            slot = self._build_slot(req, context, suffix)
        self._rec.arrived(slot)
        self.scheduler.assign_deadline(slot)
        return slot

    def _build_slot(self, req: PreprocessedRequest, context: Context, suffix: str) -> _Slot:
        stop = req.stop_conditions or {}
        sampling = req.sampling_options or {}
        slot = _Slot(
            request_id=(req.request_id or f"jax-{self.num_requests}") + suffix,
            queue=asyncio.Queue(),
            context=context,
            prompt=list(req.token_ids),
            max_tokens=int(stop.get("max_tokens") or 128),
            min_tokens=int(stop.get("min_tokens") or 0),
            eos_ids=list(req.eos_token_ids or []),
            ignore_eos=bool(stop.get("ignore_eos")),
            stop_token_ids=list(stop.get("stop_token_ids") or []),
            # the adapter name salts the hash chain (reference lora_id in
            # protocols.rs:110-115): prefix cache / KVBM / router events all
            # key on these hashes, so two adapters sharing a text prefix can
            # never share KV
            seq=TokenBlockSequence(
                req.token_ids, self.config.page_size,
                salt=salt_hash(req.lora_name.encode())
                if req.lora_name else 0,
            ),
        )
        slot.kv_prompt = slot.prompt
        slot.mm = self._slot_mm(req)
        slot.temperature = float(
            sampling.get("temperature", self.config.default_temperature) or 0.0
        )
        slot.top_k = int(sampling.get("top_k") or 0)
        slot.top_p = float(sampling.get("top_p") or 1.0)
        slot.want_logprobs = bool(sampling.get("logprobs"))
        slot.presence_penalty = float(sampling.get("presence_penalty") or 0.0)
        slot.frequency_penalty = float(sampling.get("frequency_penalty") or 0.0)
        slot.repetition_penalty = float(
            sampling.get("repetition_penalty") or 1.0
        )
        # explicit seed => reproducible output independent of co-batched
        # traffic (counter-based draws, sampling.py); else a random one —
        # concurrent identical unseeded requests (n>1) must diverge
        import secrets as _secrets

        seed = sampling.get("seed")
        slot.sample_seed = (
            int(seed) & 0xFFFFFFFF if seed is not None
            else _secrets.randbits(32)
        )
        slot.want_top_logprobs = min(int(sampling.get("top_logprobs") or 0), 5)
        slot.want_routed = self._stateful and (
            "routed_experts" in (req.annotations or []))
        if req.guided:
            slot.guided_fsm = (
                getattr(req, "_compiled_fsm", None)
                or self._guided_compiler().compile(req.guided)
            )
            slot.guided_state = slot.guided_fsm.start_state
            self.guided_requests += 1
        if req.lora_name and self._lora is not None:
            pinned = getattr(req, "_lora_slot", None)
            slot.lora_idx = (
                pinned if pinned is not None
                else self._lora["names"].get(req.lora_name, 0)
            )
            if pinned is not None:
                # the _acquire_lora pin transfers to the slot (released
                # exactly once, at stream finish)
                slot.lora_name = req.lora_name
                req._lora_slot = None
            if slot.lora_idx:
                self.lora_requests += 1
        if len(slot.prompt) + slot.max_tokens > self.config.max_model_len:
            slot.max_tokens = max(self.config.max_model_len - len(slot.prompt), 1)
        slot.priority = int(req.priority or 0)
        slot.tenant = req.tenant or ""
        slot.migration = int(getattr(req, "migration", 0) or 0)
        return slot

    def _morph_guard(self):
        """Refuse NEW streams mid-morph the same way the drain cut the
        in-flight ones: StreamSevered rides the `draining`-coded T_ERR so
        the caller's migration machinery re-routes instead of surfacing a
        terminal error. ("warm" is admitted — re-warm drives generate.)"""
        if self._morph_state in ("draining-role", "flipped"):
            raise StreamSevered(
                f"worker is morphing ({self._morph_state}); stream re-routed"
            )

    async def generate(self, request: Any, context: Context) -> AsyncIterator[dict]:
        self._morph_guard()
        self.start()
        req = (
            request
            if isinstance(request, PreprocessedRequest)
            else PreprocessedRequest.from_dict(request)
        )
        mm_err = self._check_multimodal(req)
        if mm_err is not None:
            # silently dropping image/audio parts would be a wrong answer,
            # not a degraded one (protocol contract in protocols/common.py).
            # Parts that arrived WITH encoder embeddings + positions are
            # spliced at prefill instead (E/P/D flow, _prefill_batch_mm).
            yield Annotated.from_error(mm_err).to_dict()
            return
        g_err = await self._compile_guided_async(req)
        if g_err is not None:
            yield Annotated.from_error(g_err).to_dict()
            return
        l_err = (
            self._check_lora(req) or self._check_logprobs(req)
            or self._acquire_lora(req)
        )
        if l_err is not None:
            yield Annotated.from_error(l_err).to_dict()
            return
        disagg = req.disagg_params or {}
        if self._stateful and any(
                disagg.get(k) for k in ("return_kv", "kv_pull", "kv_stream")):
            yield Annotated.from_error(
                f"{self.STATE_FAMILY} cannot run the disaggregated hand-off: "
                + self._why_refused(
                    "disagg", "the pages would leave without the lane's state")
            ).to_dict()
            return
        slot = self._new_slot(req, context)
        slot.return_kv = bool(disagg.get("return_kv"))
        slot.kv_pull = bool(disagg.get("kv_pull"))
        slot.kv_stream = bool(disagg.get("kv_stream"))
        slot.kv_holder = req.kv_holder
        self.num_requests += 1
        self._waiting.append(slot)
        self._wake.set()
        try:
            while True:
                item = await slot.queue.get()
                if item is None:
                    return
                if isinstance(item, Exception):
                    # _sever_all pushed a StreamSevered sentinel: raise it
                    # out of the handler so the request plane codes the
                    # T_ERR as `draining` and the caller migrates
                    raise item
                yield item
        finally:
            slot.done = True
            self._wake.set()

    async def _decode_entry_slot(self, request: Any, context: Context,
                                 first_token: Optional[int]):
        """Shared prologue of the disagg decode entries (from_kv / resume /
        from_pull): coerce + validate the request, build the "-d" slot,
        and catch the guided FSM up to the prefill worker's already-emitted
        first token. Returns (slot, None) or (None, error_string)."""
        if self._stateful:
            return None, (
                f"{self.STATE_FAMILY} cannot run the disaggregated hand-off: "
                + self._why_refused(
                    "disagg", "injected pages bring no state for the lane")
            )
        self._morph_guard()
        self.start()
        req = (
            request
            if isinstance(request, PreprocessedRequest)
            else PreprocessedRequest.from_dict(request)
        )
        g_err = (
            await self._compile_guided_async(req) or self._check_lora(req)
            or self._check_logprobs(req) or self._acquire_lora(req)
        )
        if g_err is not None:
            return None, g_err
        slot = self._new_slot(req, context, suffix="-d")
        if slot.guided_fsm is not None and first_token is not None:
            slot.guided_state = slot.guided_fsm.advance(
                slot.guided_state, first_token
            )
        return slot, None

    async def _drain_decode_slot(self, slot: _Slot) -> AsyncIterator[dict]:
        """Shared epilogue: enqueue the slot and yield its stream until the
        terminal None, marking it done on consumer teardown."""
        self.num_requests += 1
        self._waiting.append(slot)
        self._wake.set()
        try:
            while True:
                item = await slot.queue.get()
                if item is None:
                    return
                if isinstance(item, Exception):
                    raise item  # morph-drain sentinel, see generate()
                yield item
        finally:
            slot.done = True
            self._wake.set()

    async def generate_decode_from_kv(
        self,
        request: Any,
        context: Context,
        first_token: int,
        kv_k_pages,
        kv_v_pages,
        n_tokens: int,
    ) -> AsyncIterator[dict]:
        """Disagg decode entry: continue decoding from remotely-prefilled KV
        (reference decode-with-kv_transfer_params, handlers.py:258-270).
        The first token was already produced by the prefill worker and is
        NOT re-emitted here."""
        slot, g_err = await self._decode_entry_slot(request, context, first_token)
        if g_err is not None:
            yield Annotated.from_error(g_err).to_dict()
            return
        slot.preloaded = (first_token, kv_k_pages, kv_v_pages, n_tokens)
        async for item in self._drain_decode_slot(slot):
            yield item

    async def generate_decode_resume(
        self, request: Any, context: Context, first_token: int
    ) -> AsyncIterator[dict]:
        """Disagg decode entry WITHOUT a usable KV payload (typed
        kv_format mismatch, docs/kvbm.md mixed-fleet rules): prefill the
        prompt locally and resume decoding from the prefill worker's
        already-emitted first token — the same fallback a failed pull
        takes, entered before any foreign bytes are interpreted."""
        slot, g_err = await self._decode_entry_slot(request, context, first_token)
        if g_err is not None:
            yield Annotated.from_error(g_err).to_dict()
            return
        slot.generated = 1
        slot.last_token = first_token
        slot.seq.append(first_token)
        slot.resume_token = first_token
        slot.prefill_pos = 0
        async for item in self._drain_decode_slot(slot):
            yield item

    async def generate_decode_from_pull(
        self, request: Any, context: Context, first_token: int, desc: dict
    ) -> AsyncIterator[dict]:
        """Disagg decode entry, pull path: the prefill worker staged the KV
        on its data plane; we allocate pages, then stream-inject chunks while
        the decode batch keeps stepping (transfer/compute overlap). Falls
        back to local prefill if the pull dies."""
        slot, g_err = await self._decode_entry_slot(request, context, first_token)
        if g_err is not None:
            yield Annotated.from_error(g_err).to_dict()
            return
        slot.preloaded = (first_token, None, None, int(desc["n_tokens"]))
        slot.pull_desc = desc
        async for item in self._drain_decode_slot(slot):
            yield item

    def begin_streamed_pull(
        self, request: Any, context: Context, desc: dict
    ) -> Optional[StreamedPullHandle]:
        """Disagg decode, streamed handoff (docs/disagg_serving.md): start
        pulling KV chunks off the prefill worker's EARLY descriptor while
        its prefill is still running — the transfer overlaps the peer's
        compute, and the first decode step dispatches as soon as the last
        chunk and the first token both land, instead of paying the whole
        transfer serially after prefill. Returns None for request kinds
        the preload path doesn't carry (guided/multimodal/bad-lora); the
        handler then rides the serial path."""
        if self._stateful:
            return None
        self.start()
        req = (
            request
            if isinstance(request, PreprocessedRequest)
            else PreprocessedRequest.from_dict(request)
        )
        if req.guided is not None or req.multimodal:
            # guided FSM compilation is async and multimodal splices don't
            # ride the preload path: the serial handoff covers these
            return None
        if self._morph_state in ("draining-role", "flipped"):
            # mid-morph: fall to the serial path, whose _decode_entry_slot
            # raises StreamSevered so the caller re-routes
            return None
        if self._check_lora(req) is not None or self._check_logprobs(req) is not None:
            return None
        slot = self._new_slot(req, context, suffix="-d")
        slot.preloaded = (None, None, None, int(desc["n_tokens"]))
        slot.pull_desc = dict(desc)
        slot.first_token_fut = asyncio.get_running_loop().create_future()
        self.num_requests += 1
        self._waiting.append(slot)
        self._wake.set()
        return StreamedPullHandle(self, slot, str(desc.get("transfer_id", "")))

    def clear_kv_blocks(self) -> int:
        """Admin flush (reference clear-kv-blocks route, service_v2.rs:
        319-339): evict every unreferenced prefix-cache page (emitting
        removed events so routers un-index them) and drop the KVBM tiers.
        Active sequences keep their pages."""
        n = self.allocator.clear_cache()
        if self.kvbm is not None:
            n += self.kvbm.clear()
        return n

    def stats(self) -> dict:
        alloc_stats = self.allocator.stats()
        running = sum(1 for s in self.slots if s is not None)
        kv_nbytes = (
            int(self.kv_k.nbytes) + int(self.kv_v.nbytes)
            if hasattr(self.kv_k, "nbytes") else 0
        )
        out = {
            NUM_WAITING_REQS: len(self._waiting),
            NUM_RUNNING_REQS: running,
            "gpu_cache_usage_perc": self.allocator.active_pages / self.allocator.num_pages,
            "request_total_slots": self.config.max_num_seqs,
            # quantized KV density surface (docs/kvbm.md): the format, the
            # resident pool bytes (incl. scales, benchmark/run.py's
            # setup line), and the typed mixed-precision rejections (a
            # fleet-misconfig alert)
            "kv_quant": self.config.kv_quant,
            "kv_pool_bytes": kv_nbytes,
            # bring-up surface (chip_smoke.py reads these off the metrics
            # topic): the device as JAX reports it, the implementation each
            # attention op resolved to, and where the bytes are
            "device": self.device,
            "attention_impl": self.attention_impl,
            # a constant since the "local" decode block went: kept only
            # because benchmark/run.py:445 and chip_smoke.py:470 index it
            "decode_pool_mode": "scatter",
            "native_core": native_available(),
            "device_memory": [
                {
                    k: (d.memory_stats() or {}).get(k)
                    for k in ("bytes_limit", "bytes_in_use",
                              "peak_bytes_in_use")
                }
                for d in jax.local_devices()
            ],
            "weight_bytes_per_device": self._weight_bytes_per_device,
            "kv_bytes_per_device": self._kv_bytes_per_device,
            "warmup_s": round(self.warmup_seconds, 1),
            "kv_format_mismatches": self.kv_format_mismatches,
            **alloc_stats,
        }
        if self.kvbm is not None:
            out.update(self.kvbm.stats())
            # tier-chain effectiveness (docs/kvbm.md): G1 admission hit/miss
            # plus the onboard latency histogram
            out["kvbm_g1_hit_blocks"] = self.kvbm_g1_hit_blocks
            out["kvbm_g1_miss_blocks"] = self.kvbm_g1_miss_blocks
            out["kvbm_onboard_count"] = self.kvbm_onboard_count
            out["kvbm_onboard_ms_sum"] = round(self.kvbm_onboard_ms_sum, 3)
            out["kvbm_onboard_hist"] = {
                **{
                    f"le_{b:g}ms": n
                    for b, n in zip(self._onboard_hist_bounds,
                                    self.kvbm_onboard_hist)
                },
                "inf": self.kvbm_onboard_hist[-1],
            }
        if self.data_plane is not None:
            out["kv_transfers_served"] = self.data_plane.transfers_served
            out["kv_bytes_served"] = self.data_plane.bytes_served
            # session-checkpoint pushes ACCEPTED into this worker's tiers
            # (the replica-holder side of durable decode sessions)
            out["kv_checkpoint_pushes"] = self.data_plane.checkpoint_pushes
            out["kv_checkpoint_blocks_received"] = (
                self.data_plane.checkpoint_blocks_received
            )
        out["kv_pulls_completed"] = self.kv_pulls_completed
        out["kv_pages_pulled"] = self.kv_pages_pulled
        # streamed disagg handoff (docs/disagg_serving.md): decode-side
        # overlap evidence + prefill-side stage accounting. The ratio is
        # the acceptance signal — >0 means first tokens reached clients
        # while KV tail chunks were still in flight
        out["disagg_streamed_handoffs"] = self.disagg_streamed_handoffs
        out["disagg_chunks_before_first_token"] = (
            self.disagg_chunks_before_first_token
        )
        out["disagg_first_token_before_last_chunk"] = (
            self.disagg_first_token_before_last_chunk
        )
        out["disagg_streamed_handoff_ratio"] = round(
            self.disagg_first_token_before_last_chunk
            / self.disagg_streamed_handoffs, 4
        ) if self.disagg_streamed_handoffs else 0.0
        out["kv_streamed_stages"] = self.kv_streamed_stages
        out["kv_streamed_fallbacks"] = self.kv_streamed_fallbacks
        # migration observability (docs/fault_tolerance.md): how many
        # streams resumed here after a worker death, what each resume
        # actually cost (tokens re-prefilled) and where the session
        # prefix came from — the kill-mid-decode CI arm gates on
        # resume_source_checkpoint > 0
        out["migrations_resumed"] = self.migrations_resumed
        out["migration_replayed_tokens"] = self.migration_replayed_tokens
        out["resume_source_checkpoint"] = self.resume_source_checkpoint
        out["resume_source_peer"] = self.resume_source_peer
        out["resume_source_local"] = self.resume_source_local
        out["resume_source_recompute"] = self.resume_source_recompute
        # role-morph telemetry (docs/autoscaling.md "Role morphing"):
        # per-role marginal throughput prices the planner's re-role arm;
        # the role/state gauges make a flip observable
        est_role = self.estimated_role_tok_s()
        out[SCHED_EST_PREFILL_TOK_S] = round(est_role["prefill"], 1)
        out[SCHED_EST_DECODE_TOK_S] = round(est_role["decode"], 1)
        out["engine_role"] = self._role
        out["morph_state"] = self._morph_state
        out["morphs_completed"] = self.morphs_completed
        out["morphs_rolled_back"] = self.morphs_rolled_back
        out["morph_drained_sessions"] = self.morph_drained_sessions
        out["morph_last_duration_s"] = round(self.morph_last_duration_s, 3)
        out["kv_skip_ahead_blocks"] = self.prefix_skip_ahead_blocks
        out["emit_batches"] = self.emit_batches
        out["emit_tokens"] = self.emit_tokens
        # ragged unified dispatch: is the fused path actually taken in
        # production, and what padding does each path pay per step
        # (docs/ragged_attention.md; jax_worker republishes these as
        # prometheus gauges)
        out["mixed_steps"] = self.mixed_steps
        out["mixed_steps_piped"] = self.mixed_steps_piped
        out["split_steps"] = self.split_steps
        # the lean mixed_step family (token buckets x table widths) and
        # how many of its programs the jit cache holds: equal once the
        # first mixed step has returned (_prime_mixed_family)
        out["mixed_family_size"] = (
            len(self._mixed_token_buckets) * len(self._mixed_table_rungs)
        )
        out["mixed_family_compiled"] = int(self._mixed_step._cache_size())
        out["expert_rows_routed"] = self.expert_rows_routed
        out["expert_rows_computed"] = self.expert_rows_computed
        if self._stateful:
            # the state store beside the pages (docs/hybrid_models.md)
            out["state_bytes"] = self.kv_k.state_nbytes
            out["state_lanes_reset"] = self.state_lanes_reset
            out["state_prefix_hits_declined"] = self.state_prefix_hits_declined
            out["routed_rows_emitted"] = self.routed_rows_emitted
        if self._steps_lanes:
            out["state_rows_in_place"] = self.state_rows_in_place
            out["state_rows_gathered"] = self.state_rows_gathered
        if self._absorbed_row_limit:
            out["mla_rows_absorbed_tokens"] = self.mla_rows_absorbed_tokens
            out["mla_rows_expanded_tokens"] = self.mla_rows_expanded_tokens
        # what the mixed steps' dense layers multiplied: real tokens, and
        # the slots of the token buckets they ran in
        out["mixed_real_tokens"] = self.mixed_real_tokens
        out["mixed_padded_tokens"] = self.mixed_padded_tokens
        # ... and what their attention launched (0 on the XLA path)
        out["mixed_attn_tiles"] = self.mixed_attn_tiles
        out["mixed_attn_tiles_real"] = self.mixed_attn_tiles_real
        out["mixed_rows_decode_kernel"] = self.mixed_rows_decode_kernel
        # ... and the split pair that served a mixed-shaped step
        out["split_real_tokens"] = self.split_real_tokens
        out["split_padded_tokens"] = self.split_padded_tokens
        # per-kind fused coverage: which workloads actually ride the fused
        # path (ISSUE 19 CI gate: coverage >= 0.9 on blended traffic)
        out["mixed_rows_plain"] = self.mixed_rows_plain
        out["mixed_rows_guided"] = self.mixed_rows_guided
        out["mixed_rows_spec"] = self.mixed_rows_spec
        out["mixed_rows_lora"] = self.mixed_rows_lora
        denom = self.mixed_steps + self.split_steps
        out["mixed_coverage_frac"] = (
            round(self.mixed_steps / denom, 4) if denom else 1.0
        )
        if self._lora_pool is not None:
            out.update(self._lora_pool.stats())
        # dynosched: policy/targets, per-step decision counters, and the
        # queue/deadline view (published on the worker metrics topic, so
        # disagg decode workers and the planner see prefill-pool pressure)
        out.update(self.scheduler.stats())
        est = self.estimated_prefill_wait_ms()
        out[SCHED_EST_TTFT_MS] = round(est, 1) if est is not None else 0.0
        out[SCHED_EST_REQ_MS] = round(self.estimated_req_ms(), 1)
        # the loop's own spans and counters: phase_*, step_*, req_*,
        # engine_clock_s and _timed's dispatch_* (engine/recorder.py)
        out.update(self._rec.stats())
        # compile telemetry (docs/compilation.md): XLA cache size per
        # staged surface plus the steady-state gate — programs compiled
        # AFTER the warmup baseline snapshot. dynocomp proves warmup
        # reachability statically; post_warmup_compiles proves the same
        # contract at runtime (>0 in steady state = a shape leaked past
        # the bucketing helpers or warmup missed a variant)
        sizes = self._surface_cache_sizes()
        out["compile_surfaces"] = {k: v for k, v in sizes.items() if v}
        out["compiled_variants"] = sum(sizes.values())
        base = self._warmup_compile_baseline
        out["warmup_compiles"] = sum(base.values()) if base else 0
        out["post_warmup_compiles"] = sum(
            max(v - base.get(k, 0), 0) for k, v in sizes.items()
        ) if base is not None else 0
        if self.guided_requests:
            out["guided_requests"] = self.guided_requests
        if self.lora_requests:
            out["lora_requests"] = self.lora_requests
        if self.config.spec_mode:
            out["spec_num_drafts"] = self.spec_num_drafts
            out["spec_num_draft_tokens"] = self.spec_num_draft_tokens
            out["spec_num_accepted_tokens"] = self.spec_num_accepted_tokens
            out["spec_mean_accepted_len"] = (
                1.0 + self.spec_num_accepted_tokens / self.spec_num_drafts
                if self.spec_num_drafts else 0.0
            )
        return out

    def estimated_prefill_wait_ms(self, n_new_tokens: int = 0) -> Optional[float]:
        """Estimated local TTFT contribution of this engine's prefill
        queue for a hypothetical `n_new_tokens`-token arrival: (tokens
        still to prefill across admitted + waiting slots + the new
        prompt) x the cost model's observed per-token prefill rate.
        None until the model has seen a prefill (cold start) — callers
        (DisaggregatedRouter) fall back to the static threshold rule."""
        pending = int(n_new_tokens)
        for s in self.slots:
            if (
                s is not None and not s.done
                and s.preloaded is None and s.onboard is None
            ):
                pending += max(len(s.kv_prompt) - s.prefill_pos, 0)
        for s in self._waiting:
            pending += len(s.prompt)
        return self.scheduler.estimate_wait_ms(pending)

    def estimated_req_ms(self) -> float:
        """Marginal TTFT one more admitted request adds (the dynogate
        optimism-debt unit, docs/overload.md): a typical-length prompt at
        the cost model's observed per-token prefill rate. 0.0 when the
        model is cold or the queue is empty — the gate then corrects from
        the next published sched_est_ttft_ms instead."""
        per_tok = self.scheduler.cost.per_token("prefill")
        if per_tok is None:
            return 0.0
        lens = [
            len(s.kv_prompt) for s in self.slots
            if s is not None and not s.done
        ]
        lens += [len(s.prompt) for s in self._waiting]
        if not lens:
            return 0.0
        return (sum(lens) / len(lens)) * per_tok * 1000.0

    # ------------------------------------------------------------------ #
    # step loop
    # ------------------------------------------------------------------ #

    async def _step_loop(self):
        while not self._closed:
            has_active = any(s is not None for s in self.slots)
            if (
                not self._waiting
                and not has_active
                and not self._inflight
                and not self._pending_prefill
            ):
                self._wake.clear()
                self._rec.entry_kind = "none"
                self._rec.idle()
                with self._rec.span("wait"):
                    await self._wake.wait()
                continue
            try:
                f = faults.FAULTS
                if f.enabled:
                    # dynochaos `engine.step`: a raised FaultError rides the
                    # organic step-failure path below (fail-all -> migration)
                    await f.on("engine.step")
                progressed = await self._step_once()
            except Exception as e:  # noqa: BLE001 — engine loop must not die silently
                logger.exception("engine step failed; failing active requests")
                self._fail_all(f"engine step failed: {type(e).__name__}: {e}")
                with self._rec.span("wait"):
                    await asyncio.sleep(0.1)
                continue
            # yield to the event loop so streams flush between steps: the
            # loop's own share of that time is nothing, the streams' tasks
            # run in it
            with self._rec.span("wait"):
                await asyncio.sleep(0 if progressed else 0.001)

    async def _step_once(self) -> bool:
        """One engine iteration: admit, dispatch ONE entry of the decode
        pipeline (a fused mixed step when both prefill and decode are
        runnable, else prefill batch + decode block), then fetch the oldest
        entry once another is queued behind it, so its host read overlaps
        the newer one's compute. A lean mixed step is an entry like a
        block: it queues behind what is in flight and its successor queues
        behind it, so the host never holds more than one entry behind the
        running one. A pack that needs host-authoritative lanes
        (_pack_pipes) drains the pipeline first and is fetched in the step
        that dispatched it.

        The SECOND entry of the pipeline is queued LATE (_await_successor):
        with one entry running and its program's length known, the step
        asks for that entry's fetch first and waits until the entry is
        about to end, admitting whoever arrives meanwhile, and only then
        dispatches. An entry queued behind the running one cannot start
        before it ends, so nothing is lost by waiting, and the arrivals of
        the wait take that next entry, all of them in ONE mixed step,
        where each stood behind a block queued before it came (and got a
        step of its own after that). With no arrival the block is queued
        at the deadline. Where the pipeline's depth is 1 anyway, or the
        running program has not been timed yet, the step dispatches at
        once, and so does the successor of a mixed step that was
        dispatched with nothing ahead of it (the `chained` block below)."""
        self._admit_waiting()
        progressed = await self._run_injections()
        if await self._await_successor():
            progressed |= await self._run_injections()
        depth, resumed = len(self._inflight), self._rec.clock()
        dispatched = False
        if await self._dispatch_mixed():
            progressed = True
            if len(self._inflight) == 1 and \
                    self._inflight[0]["kind"] == "mixed":
                # nothing ran ahead of it, so it is the running entry:
                # queue its successor now, as a block's second block is —
                # the prompt's next chunk if there is one, else a block
                if not await self._dispatch_mixed():
                    dispatched = await self._dispatch_decode(chained=True)
        else:
            self._last_prefill_shape = self._last_decode_shape = None
            pf = False
            if not self._mixed_wait_drain:
                pf = await self._dispatch_prefill()
            progressed |= pf
            dispatched = await self._dispatch_decode()
            if pf and dispatched and self._last_prefill_shape \
                    and self._last_decode_shape:
                # a mixed-shaped step served by the split pair (mixed off,
                # variant kinds, planner refusal): account its padding
                # beside the fused path's
                self.split_steps += 1
                self.split_padded_tokens += (
                    self._last_prefill_shape[0] + self._last_decode_shape[0]
                )
                self.split_real_tokens += (
                    self._last_prefill_shape[1] + self._last_decode_shape[1]
                )
        # fetch the oldest entry only once the pipeline is full or stalled,
        # so its host read overlaps the newer entry's compute
        fetch_block = len(self._inflight) >= 2 or (
            bool(self._inflight) and not dispatched
        )
        if depth == 1 and len(self._inflight) > 1:
            # what the margin has to cover: the loop's time from here to
            # the launch's return of the entry queued second, whether a
            # wait came before it or not (a margin that outgrew the
            # estimate must be able to shrink again); a compile inside the
            # launch is no reading
            cost = self._inflight[1]["t_launched"] - resumed
            if cost < SLOW_SPAN_S:
                self._successor_costs.append(cost)
        progressed |= dispatched
        progressed |= await self._fetch_and_process(fetch_block)
        if self.kvbm is not None:
            # coalesce this step's block commits into ONE offload gather
            # (kvbm pipeline, docs/kvbm.md) — the only KVBM work the
            # device executor ever sees is that single dispatch
            self.kvbm.flush_step()
        return progressed

    def _successor_deadline(self) -> Optional[float]:
        """When the running entry's successor has to be on its way, on the
        recorder's clock: the moment the running entry began + the engine's
        estimate of its program (the shortest of its last runs) - a margin
        for the dispatch itself (the larger of SUCCESSOR_MARGIN of the
        estimate and SUCCESSOR_SAFETY times the longest such dispatch of
        the last few). None where the successor is queued at once, as it
        always was: the pipeline does not hold exactly one entry, its depth
        is 1 anyway (spec mode, a guided lane, a pack waiting for the
        drain, an invalid carry), the program has not run yet, or the
        moment has passed."""
        if len(self._inflight) != 1 or self._pending_prefill \
                or not self._carry_valid or self._mixed_wait_drain \
                or self.config.spec_mode or self._guided_decoding():
            return None
        running = self._inflight[0]
        estimate = self._rec.estimate(running)
        if estimate is None:
            return None
        margin = max(
            SUCCESSOR_MARGIN * estimate,
            SUCCESSOR_SAFETY * max(self._successor_costs, default=0.0),
        )
        deadline = self._rec.began(running) + estimate - margin
        return deadline if deadline > self._rec.clock() else None

    async def _await_successor(self) -> bool:
        """Hold the second entry of the pipeline back until the running one
        is about to end (_successor_deadline). The running entry's fetch is
        asked for FIRST (it blocks on the fetch thread; _fetch_and_process
        takes it up after the dispatch), so its return is the entry's true
        end: an estimate that was too long ends the wait the moment the
        device falls idle, and the seconds from there to the successor's
        launch are counted (Recorder.fetched: `step_starved_s`,
        `successor_late`). A wake inside the wait admits who arrived
        (pages and the prefix cache, off the deadline's path) and the wait
        goes on: the arrivals of one wait share the entry that is queued
        at its end. KV that waits to be injected ends it at once. Returns
        whether it waited."""
        deadline = self._successor_deadline()
        if deadline is None:
            return False
        rec = self._rec
        running = self._inflight[0]
        self._early_fetch = fetch = asyncio.ensure_future(
            self._fetch(self._fetch_tree([], running)))
        rec.successor_waits += 1
        rec.entry_kind = running["step_kind"]
        admitted = rec.req_admitted
        timer = asyncio.ensure_future(self._sleep(deadline - rec.clock()))
        try:
            while not (fetch.done() or timer.done()):
                self._wake.clear()
                self._admit_waiting()
                if self._injection_pending():
                    break
                wake = asyncio.ensure_future(self._wake.wait())
                with rec.span("wait"):
                    await asyncio.wait({fetch, timer, wake},
                                       return_when=asyncio.FIRST_COMPLETED)
                wake.cancel()
        finally:
            timer.cancel()
        # who arrived in the turn that ended the wait: a waiter nobody
        # admitted would hold the dispatch below at depth 1
        self._admit_waiting()
        if rec.req_admitted > admitted:
            rec.successor_woken += 1
        return True

    # -- admission ------------------------------------------------------- #

    def _admit_waiting(self):
        if not self._waiting:
            return
        still: List[_Slot] = []
        with self._rec.span("admit"):
            # sla policy: admit earliest-TTFT-deadline first (preempted
            # victims keep their original arrival, so they stay at the
            # front exactly as the legacy insert-at-0 intended); fifo:
            # arrival order untouched
            for slot in self.scheduler.order_waiting(self._waiting):
                if slot.done or slot.context.is_stopped():
                    self._emit_finish(slot, "cancelled")
                    continue
                if not self._free_slots or not self._try_admit(slot):
                    still.append(slot)
                else:
                    self._rec.admitted(slot)
            self._waiting = still

    def _try_admit(self, slot: _Slot) -> bool:
        cfg = self.config
        if slot.preloaded is not None:
            # disagg decode role: all prompt pages fresh; KV arrives by
            # injection, not prefill
            n_pages = (len(slot.prompt) + cfg.page_size - 1) // cfg.page_size
            if not self.allocator.can_allocate(n_pages + 1):
                return False
            fresh = self.allocator.alloc_fresh(n_pages)
            if fresh is None:
                return False
            idx = self._free_slots.pop()
            slot.slot_idx = idx
            slot.pages = fresh
            slot.committed_hashes = []
            slot.prefill_pos = len(slot.prompt)
            self.slots[idx] = slot
            self.page_tables[idx, :] = SCRATCH_PAGE
            self.page_tables[idx, : len(fresh)] = [p + 1 for p in fresh]
            self.seq_lens[idx] = 0
            self.temps[idx] = slot.temperature
            self.top_ks[idx] = slot.top_k
            self.top_ps[idx] = slot.top_p
            self.lora_idx[idx] = slot.lora_idx
            self.seeds[idx] = slot.sample_seed
            self.presence[idx] = slot.presence_penalty
            self.frequency[idx] = slot.frequency_penalty
            self.repetition[idx] = slot.repetition_penalty
            self._fill_recent(idx, slot)
            slot.admit_seq = self._admit_counter = self._admit_counter + 1
            return True
        kv_prompt = slot.kv_prompt
        hashes = slot.seq.block_hashes()
        # a stateful family takes no cached pages: nobody kept the state
        # that stood at their end (counted below, once admission is certain)
        declined = 0
        if self._lane_state and cfg.enable_prefix_caching:
            declined = len(self.allocator.cached_prefix(hashes))
        # ... and a request that asked for the experts chosen at EVERY input
        # position (`routed_experts`) computes every position
        cached_pages = (
            self.allocator.acquire_cached(hashes)
            if cfg.enable_prefix_caching and not self._lane_state
            and not slot.want_routed else []
        )
        n_cached = len(cached_pages)
        # KVBM: probe G2/G3 for the hashes the device cache missed; tier hits
        # are injected before prefill (onboard), extending the cached prefix.
        # The probe extends onto PEER tiers too (announcement mesh + the
        # router's holder hint — cluster KV fabric, docs/kvbm.md)
        onboard_hashes: List[int] = []
        hint_inst = None
        prompt_full_blocks = len(kv_prompt) // cfg.page_size
        if self.kvbm is not None and cfg.enable_prefix_caching:
            hint = slot.kv_holder or {}
            hint_inst = hint.get("instance")
            onboard_hashes = self.kvbm.probe(
                hashes[n_cached:prompt_full_blocks],
                hint_instance=hint_inst,
                hint_blocks=max(int(hint.get("blocks", 0)) - n_cached, 0),
            )
        # allocate the prompt's remaining pages now; generation pages grow later
        prompt_pages = (len(kv_prompt) + cfg.page_size - 1) // cfg.page_size
        fresh_prompt = max(prompt_pages - n_cached, 0)
        if not self.allocator.can_allocate(fresh_prompt + 1):
            self.allocator.release(cached_pages, hashes[:n_cached])
            return False
        fresh = self.allocator.alloc_fresh(fresh_prompt)
        if fresh is None:
            self.allocator.release(cached_pages, hashes[:n_cached])
            return False
        # admission is now certain: count G1 hit/miss and settle the
        # onboard budget HERE, not before the allocation checks — a
        # pool-pressured slot retries _try_admit every step, and counting
        # pre-failure would re-count the same request per retry
        if self.kvbm is not None and cfg.enable_prefix_caching:
            self.kvbm_g1_hit_blocks += n_cached
            self.kvbm_g1_miss_blocks += max(prompt_full_blocks - n_cached, 0)
            if onboard_hashes:
                # three-arm onboard budget (docs/kvbm.md cluster KV
                # fabric): local-tier load vs per-peer transfer rate vs
                # recompute — the cheapest source wins per span, and a
                # cold/slow peer never blocks TTFT past the headroom
                # (it loses to a local-prefix trim or full recompute).
                # Cold tiers / cold peers / cold cost model never defer,
                # same rule as the scheduler's CostModel. Under fifo
                # (headroom None) the budget only does source accounting.
                headroom_ms = self.scheduler.onboard_headroom_ms(slot)
                rate = self.scheduler.cost.per_token("prefill")
                onboard_hashes, _ = self.kvbm.budget_onboard(
                    list(onboard_hashes), headroom_ms,
                    rate * 1000.0 * cfg.page_size if rate is not None else None,
                    hint_instance=hint_inst,
                )
        n_onboard = len(onboard_hashes)
        if slot.migration:
            self._count_resume(slot, hashes, n_cached, onboard_hashes)
        if declined:
            if not self.state_prefix_hits_declined:
                logger.info(
                    "%s: %d cached blocks of request %s declined (each such "
                    "admission counts in state_prefix_hits_declined)",
                    self.STATE_FAMILY, declined, slot.request_id,
                )
            self.state_prefix_hits_declined += declined
        slot.routed_seen = 0  # the prompt is (re)computed from position 0
        idx = self._free_slots.pop()
        slot.slot_idx = idx
        slot.pages = cached_pages + fresh
        slot.committed_hashes = hashes[:n_cached]
        slot.prefill_pos = min((n_cached + n_onboard) * cfg.page_size, len(kv_prompt))
        if n_onboard:
            slot.onboard = (fresh[:n_onboard], onboard_hashes)
        # skip-ahead: if the whole prompt is cached, recompute the last token
        # (need its logits) — back off one position
        if slot.prefill_pos >= len(kv_prompt):
            slot.prefill_pos = len(kv_prompt) - 1
        self.slots[idx] = slot
        # host state
        self.page_tables[idx, :] = SCRATCH_PAGE
        phys = [p + 1 for p in slot.pages]  # +1: scratch shift
        self.page_tables[idx, : len(phys)] = phys
        self.seq_lens[idx] = 0
        self.temps[idx] = slot.temperature
        self.top_ks[idx] = slot.top_k
        self.top_ps[idx] = slot.top_p
        self.lora_idx[idx] = slot.lora_idx
        self.seeds[idx] = slot.sample_seed
        self.presence[idx] = slot.presence_penalty
        self.frequency[idx] = slot.frequency_penalty
        self.repetition[idx] = slot.repetition_penalty
        self._fill_recent(idx, slot)
        slot.admit_seq = self._admit_counter = self._admit_counter + 1
        self.scheduler.on_admit(slot)
        if (
            slot.kv_pull and slot.kv_stream and self.data_plane is not None
            and not (self._multihost and self.shard_addrs)
        ):
            # streamed disagg handoff: stage NOW, before any prefill runs —
            # the decode worker pulls chunks while we compute
            # (multi-host shard staging keeps the serial flow)
            self._stage_streamed_kv(slot)
        return True

    # -- device helpers -------------------------------------------------- #

    def _timed(self, fn, tag: str, shape: Optional[tuple] = None):
        """Wrap fn so its wall time accrues to the recorder's table of
        device calls under `tag` (stats: dispatch_<tag>_count, _s) and,
        when `shape`=(bucket, lanes) is given, feeds the scheduler's
        per-shape cost model — the EWMA behind ITL budgeting and the
        disagg router's local-TTFT estimate."""
        def timed(*a):
            t0 = time.perf_counter()
            try:
                return fn(*a)
            finally:
                dt = time.perf_counter() - t0
                self._rec.timed(tag, dt)
                if shape is not None:
                    self.scheduler.cost.observe(tag, shape[0], shape[1], dt)
        return timed

    async def _run_on_device(self, fn, *args, tag: str = None,
                             shape: Optional[tuple] = None):
        if tag is not None:
            fn = self._timed(fn, tag, shape)
        return await asyncio.get_running_loop().run_in_executor(
            self._device_exec, fn, *args
        )

    def _host_read(self, tree):
        """(the tree on the host, the clock when it was there): the fetch
        thread waiting for the device."""
        with self._rec.span("fetch"):
            out = jax.device_get(tree)
        return out, self._rec.clock()

    async def _fetch(self, tree):
        """One host read (single RTT) for an arbitrary pytree of device
        arrays, off the dispatch thread, and when it returned."""
        return await asyncio.get_running_loop().run_in_executor(
            self._fetch_exec, self._timed(self._host_read, "fetch"), tree
        )

    def _describe_load(self) -> str:
        """The engine's load, for the recorder's line on a slow span."""
        return (
            f"{len(self._inflight) + len(self._pending_prefill)} in flight, "
            f"{sum(s is not None for s in self.slots)} running, "
            f"{len(self._waiting)} waiting"
        )

    def _bcast(self, tag: str, arrays: dict):
        """Mirror a device dispatch to follower hosts (SPMD: every host
        must enter the same jitted programs in the same order)."""
        if self._spmd is not None:
            self._spmd.send(tag, arrays)

    def _mark_lane_dirty(self, idx: int):
        """Lane state changed on host (admission/finish/resume): patch just
        that lane before the next block instead of a full carry reset."""
        if self._carry_valid and idx >= 0:
            self._dirty_lanes.add(idx)

    # -- replicated device programs (leader dispatches these after a
    # _bcast; followers replay them verbatim in run_follower) ------------ #

    def _put(self, *arrays):
        """One dispatch's host arrays handed to the runtime in ONE call,
        inside the caller's `put` span, and back on the device in the order
        given; `put_arrays` counts them. The call costs 0.17 ms an array on
        the chip whatever the bytes (PERF.md section 6, PR 51): the cold
        dispatches (the split prefills, a guided or adapter block, the
        carry's reset) pay that, a hot one lays its arrays end to end first
        (_put_words)."""
        self._rec.put_arrays += len(arrays)
        return jax.device_put(arrays)

    def _put_words(self, riding, beside=()):
        """`_put` of a hot dispatch (a mixed step, a lane patch): `riding`
        (4-byte elements, bool masks) as ONE int32 buffer (pack_words),
        `beside` as they are. The program takes the buffer and its layout
        and slices it itself (riders): an array a program returns costs
        another 0.1 ms, so nothing takes the buffer apart on the way.
        Returns (buffer, layout, *beside), on the device."""
        buf, layout = pack_words(riding)
        buf, *beside = self._put(buf, *beside)
        return (buf, layout, *beside)

    @staticmethod
    def _samp_host(temps, top_ks, top_ps, seeds, pens):
        """The rows' sampling parameters on the host as SamplingParams'
        seven fields, for a dispatch's `_put`."""
        return temps, top_ks, top_ps, seeds, pens[:, 0], pens[:, 1], pens[:, 2]

    def _prefill_operands(self, toks, positions, tables, ctx_lens, last_idx,
                          temps, top_ks, top_ps, seeds, pens, pen_rows,
                          *more, lanes=None):
        """A batched prefill program's operands from the host, on the
        device: (toks, positions, tables, ctx_lens, last_idx, samp,
        pen_rows) and, behind them, what a variant takes `more` of (a
        mask, embeddings, adapter indices). A stateful family's cache
        learns the lane of each row from the same call (`lanes`: every row
        the scratch slot's where the caller names none, a program compiled
        ahead of its first call). One `put` span."""
        with self._rec.span("put"):
            if self._stateful:
                more = (*more, self.kv_k.row_lanes(()) if lanes is None
                        else lanes)
            dev = self._put(
                toks, positions, tables, ctx_lens, last_idx,
                *self._samp_host(temps, top_ks, top_ps, seeds, pens),
                pen_rows, *more,
            )
            if self._stateful:
                *dev, rows = dev
                self.kv_k = self.kv_k.replace(lanes=rows)
            return (*dev[:5], SamplingParams(*dev[5:12]), *dev[12:])

    def _dev_prefill(self, *operands, lanes=None):
        toks, positions, tables, ctx_lens, last_idx, samp, pen_rows = \
            self._prefill_operands(*operands, lanes=lanes)
        with self._rec.span("launch"):
            first, self.kv_k, self.kv_v, self._rng = self._prefill_batch(
                self.params, self.kv_k, self.kv_v, toks, positions, tables,
                ctx_lens, last_idx, samp, self._rng, pen_rows,
            )
        return first

    def _mixed_operands(self, p: dict):
        """(operands, carry) of one mixed step from its pack as the "mixed"
        broadcast carries it (_blank_mixed_pack's keys): mixed_step's, with
        the decode carry it reads by lane, for a plain pack; for a pack
        with a mask mixed_step_variant's, and None. The step's one
        transfer from the host to the device (_put_words; the programs
        take the buffer apart by MIXED_KEYS): one `put` span."""
        with self._rec.span("put"):
            variant = "mask" in p
            riding = [p[k] for k in MIXED_KEYS]
            # variant pack: the mask operand is always present (all-ones
            # for maskless packs — an exact no-op), the adapter indices ride
            # iff adapters are registered (idx 0 rows are the base no-op),
            # so exactly ONE variant program exists per deployment
            with_idx = variant and self._lora is not None and "lora_idx" in p
            if with_idx:
                riding.append(p["lora_idx"])
            if not variant:
                # every plain pack carries the carry's three maps, so a
                # shape of mixed_step has one layout and carry_write finds
                # its two in the step's buffer: a drained pack names no lane
                none = np.full_like(p["row_lens"], -1)
                riding += [p.get(k, none) for k in CARRY_KEYS]
            if self._stateful:  # the lane of each row, with its pack
                riding.append(p["lanes"])
            # a mask is R x V/8 bytes: an operand of its own, same call
            ops, layout, *mask = self._put_words(
                riding, [p["mask"]] if variant else [])
            # donated: a priming call runs on a copy
            rng = jnp.copy(self._rng) if "prime" in p else self._rng
            if variant:
                lora = self._lora_operand() if with_idx else None
                return (self.params, self.kv_k, self.kv_v, ops, rng, *mask,
                        lora, layout), None
            # plain pack: the lean program. A drained pack reads no lane
            # (its map is all -1), so any carry of the right shape serves
            if self._carry is not None:
                carry = (*self._carry, self._pen_dev)
            else:  # before the first reset
                B = self.config.max_num_seqs
                lanes = jnp.zeros((B,), jnp.int32)
                carry = (lanes, lanes, lanes, jnp.full(
                    (B, self.config.penalty_window), -1, jnp.int32))
            return (self.params, self.kv_k, self.kv_v, ops, rng, carry[0],
                    carry[3], layout), carry

    def _dev_mixed(self, p: dict):
        """One mixed step from its operands as the "mixed" broadcast carries
        them (_blank_mixed_pack's keys). A pack that carries "row_lane" is
        piped (_dispatch_mixed): its decode rows read the decode carry on
        the device and its samples are written back into it (carry_write),
        so it runs behind whatever is in flight and the next entry behind
        it. A pack that carries "prime" is a priming call
        (_prime_mixed_family): it runs on a copy of the sampling key and
        leaves `_rng` and the carry as they were, so that seeded streams
        do not depend on when the family was compiled."""
        prime, piped = "prime" in p, "row_lane" in p
        args, carry = self._mixed_operands(p)
        with self._rec.span("launch"):
            if carry is None:
                first, self.kv_k, self.kv_v, rng = \
                    self._mixed_step_variant(*args)
            else:
                first, self.kv_k, self.kv_v, rng = self._mixed_step(*args)
            if carry is not None and (piped or prime):
                # priming compiles the write-back beside the family, on
                # a map that names no lane, and drops what it returns; its
                # two maps rode the step's buffer
                ops, layout = args[3], args[7]
                wrote = self._carry_write(*carry, first[0], ops, layout)
                if piped:
                    self._carry, self._pen_dev = wrote[:3], wrote[3]
        if not prime:
            self._rng = rng
        return first

    def _compile_mixed_side_by_side(self, packs: List[dict]):
        """Compile the programs of these packs, or load them from the
        compilation cache, all at once: seconds a member either way (25-30
        s cold, 4-5 s from the cache at 16 layers), nearly all of it the
        compiler's and the runtime's own work, which holds no interpreter
        lock. The executables stay with the jit's lowering, so the
        members' first calls find them and compile nothing."""
        def compile_one(operands):
            args, carry = operands
            program = (
                self._mixed_step_variant if carry is None else self._mixed_step
            )
            program.lower(*args).compile()

        # the operands one after another on this, the device thread (their
        # `put` spans have one writer), the compiles side by side
        operands = [self._mixed_operands(pk) for pk in packs]
        with concurrent.futures.ThreadPoolExecutor(len(packs)) as pool:
            for done in [pool.submit(compile_one, ops) for ops in operands]:
                done.result()

    def _dev_prefill_mm(self, *operands, lanes=None):
        # the last two: the encoder's rows and where they go
        (toks, positions, tables, ctx_lens, last_idx, samp, pen_rows,
         emb, emb_mask) = self._prefill_operands(*operands, lanes=lanes)
        with self._rec.span("launch"):
            first, self.kv_k, self.kv_v, self._rng = self._prefill_batch_mm(
                self.params, self.kv_k, self.kv_v, toks, positions, tables,
                ctx_lens, last_idx, samp, self._rng, pen_rows, emb, emb_mask,
            )
        return first

    def _dev_prefill_guided(self, *operands, lanes=None):
        # the last one: the rows' packed FSM masks
        (toks, positions, tables, ctx_lens, last_idx, samp, pen_rows,
         mask) = self._prefill_operands(*operands, lanes=lanes)
        with self._rec.span("launch"):
            first, self.kv_k, self.kv_v, self._rng = self._prefill_batch_guided(
                self.params, self.kv_k, self.kv_v, toks, positions, tables,
                ctx_lens, last_idx, samp, self._rng, pen_rows, mask,
            )
        return first

    def _lora_operand(self, idx=None):
        """The adapter stack with the rows' indices, already on the device
        (they rode their dispatch's `_put`); without them for a variant
        mixed step, which finds them in its own buffer."""
        lora = {k: self._lora[k] for k in ("a", "b", "scale")}
        return lora if idx is None else dict(lora, idx=idx)

    def _dev_prefill_lora(self, *operands, lanes=None):
        # the last one: the rows' adapter indices
        (toks, positions, tables, ctx_lens, last_idx, samp, pen_rows,
         idx) = self._prefill_operands(*operands, lanes=lanes)
        lora = self._lora_operand(idx)
        with self._rec.span("launch"):
            first, self.kv_k, self.kv_v, self._rng = self._prefill_batch_lora(
                self.params, self.kv_k, self.kv_v, toks, positions, tables,
                ctx_lens, last_idx, samp, self._rng, pen_rows, lora,
            )
        return first

    def _dev_block_lora(self, idx):
        carry = self._carry
        with self._rec.span("put"):
            lora = self._lora_operand(*self._put(idx))
        with self._rec.span("launch"):
            (
                toks, tok_d, pos_d, sl_d,
                self.kv_k, self.kv_v, self._rng, self._pen_dev,
            ) = self._decode_block_lora(
                self.params, self.kv_k, self.kv_v,
                carry[0], carry[1], carry[2],
                self._tables_dev, self._samp_dev, self._rng, self._pen_dev,
                lora,
            )
        self._carry = (tok_d, pos_d, sl_d)
        return toks

    def _dev_reset(self, tokens, positions, seq_lens, page_tables, temps,
                   top_ks, top_ps, seeds, pens, recent, hist=None):
        with self._rec.span("put"):
            dev = self._put(
                tokens, positions, seq_lens, recent, page_tables,
                *self._samp_host(temps, top_ks, top_ps, seeds, pens),
                *(() if hist is None else (hist,)),
            )
            self._carry = dev[:3]
            self._pen_dev, self._tables_dev = dev[3:5]
            self._samp_dev = SamplingParams(*dev[5:12])
            if hist is not None:
                self._hist_dev = dev[12]

    def _dev_patch(self, lane_mask, table_mask, tokens, positions, seq_lens,
                   tables, temps, top_ks, top_ps, seeds, pens, recent,
                   hist=None):
        samp = self._samp_dev
        with self._rec.span("put"):
            # PATCH_KEYS' order; a speculating engine's ring, and the mask
            # that patches it, go beside the buffer in the same call
            spec = hist is not None and self._hist_dev is not None
            ops, layout, *ring = self._put_words([
                lane_mask, table_mask, tokens, positions, seq_lens, tables,
                temps, top_ks, top_ps, seeds, pens, recent,
            ], [lane_mask, hist] if spec else [])
        with self._rec.span("launch"):
            (
                tok_d, pos_d, sl_d, tab_d, t_d, k_d, p_d, s_d,
                pres_d, freq_d, rep_d, rec_d,
            ) = self._patch_lanes(
                self._carry[0], self._carry[1], self._carry[2],
                self._tables_dev,
                samp.temperature, samp.top_k, samp.top_p, samp.seed,
                samp.presence, samp.frequency, samp.repetition, self._pen_dev,
                ops, layout,
            )
        self._carry = (tok_d, pos_d, sl_d)
        self._tables_dev = tab_d
        self._pen_dev = rec_d
        self._samp_dev = SamplingParams(
            temperature=t_d, top_k=k_d, top_p=p_d, seed=s_d,
            presence=pres_d, frequency=freq_d, repetition=rep_d,
        )
        if spec:
            # dirty lanes take the host ring row; others keep the (newer)
            # device rows appended by in-flight spec blocks
            with self._rec.span("launch", more=True):
                lane_mask, hist_d = ring
                self._hist_dev = jnp.where(
                    lane_mask[:, None], hist_d, self._hist_dev,
                )

    def _dev_block(self):
        carry = self._carry
        if self._spec_block_fn is not None:
            with self._rec.span("launch"):
                (
                    toks, n_emit, tok_d, pos_d, sl_d,
                    self.kv_k, self.kv_v, self._rng, self._hist_dev,
                ) = self._spec_block_fn(
                    self.params, self.kv_k, self.kv_v,
                    carry[0], carry[1], carry[2],
                    self._tables_dev, self._samp_dev, self._rng,
                    self._hist_dev,
                )
            self._carry = (tok_d, pos_d, sl_d)
            return (toks, n_emit)
        with self._rec.span("launch"):
            (
                toks,
                tok_d,
                pos_d,
                sl_d,
                self.kv_k,
                self.kv_v,
                self._rng,
                self._pen_dev,
            ) = self._decode_block(
                self.params,
                self.kv_k,
                self.kv_v,
                carry[0],
                carry[1],
                carry[2],
                self._tables_dev,
                self._samp_dev,
                self._rng,
                self._pen_dev,
            )
        self._carry = (tok_d, pos_d, sl_d)
        return toks

    def _dev_block_guided(self, mask, lora_idx=None):
        carry = self._carry
        with self._rec.span("put"):
            mask, *idx = self._put(
                mask, *(() if lora_idx is None else (lora_idx,)))
            args = (
                self.params, self.kv_k, self.kv_v,
                carry[0], carry[1], carry[2],
                self._tables_dev, self._samp_dev, self._rng,
                mask, self._pen_dev,
            )
            lora = self._lora_operand(*idx) if idx else None
        with self._rec.span("launch"):
            if lora is not None:
                out = self._decode_step_guided_lora(*args, lora)
            else:
                out = self._decode_step_guided(*args)
        (
            toks, tok_d, pos_d, sl_d, self.kv_k, self.kv_v, self._rng,
            self._pen_dev,
        ) = out
        self._carry = (tok_d, pos_d, sl_d)
        return toks

    def _dev_inject(self, page_ids, k_np, v_np):
        from ..ops.kv_quant import device_pages

        c = self.model_config
        mode = self.config.kv_quant
        # quantized payloads arrive as packed uint8 [L, n, PB] rows
        # (q bytes + scales, the host/wire layout) and unpack into the
        # QuantKV leaves here; fp payloads are the seed's jnp.asarray
        with self._rec.span("put"):
            ids = jnp.asarray(page_ids)
            k_pages = device_pages(k_np, mode, self.config.page_size,
                                   c.num_kv_heads, c.head_dim)
            v_pages = device_pages(v_np, mode, self.config.page_size,
                                   c.num_kv_heads, c.head_dim)
        with self._rec.span("launch"):
            self.kv_k, self.kv_v = self._inject_pages(
                self.kv_k, self.kv_v, ids, k_pages, v_pages
            )

    def _dev_extract(self, page_ids):
        """Gather pages to host (disagg KV hand-off). On a multi-host mesh
        the KV shards live on several hosts — process_allgather (a
        collective: followers run it too) assembles the full pages. Used
        only by the INLINE-payload fallback; the pull data plane moves
        per-host shards instead (_extract_local_shard)."""
        k, v = self._extract_pages(self.kv_k, self.kv_v, jnp.asarray(page_ids))
        if self._multihost:
            from jax.experimental import multihost_utils

            return (
                multihost_utils.process_allgather(k),
                multihost_utils.process_allgather(v),
            )
        from ..ops.kv_quant import host_pack_pages

        # fp: the seed's np.asarray; quantized: packed uint8 [L, n, PB]
        # rows (q bytes + scales) — the ONE host/wire page layout
        return host_pack_pages(k), host_pack_pages(v)

    def _kv_wire_meta(self):
        """(page_shape, dtype_name) as KV pages travel on the wire: the
        fp [L, ps, KH, D] layout, or the packed uint8 [L, PAGE_BYTES]
        rows of a quantized pool (ops/kv_quant.py host layout). Every
        disagg descriptor/payload carries kv_format beside this so a
        mixed-precision pairing fails typed, never misreads bytes."""
        c = self.model_config
        cfg = self.config
        if cfg.kv_quant != "none":
            from ..ops.kv_quant import kv_page_bytes

            pb = kv_page_bytes(
                cfg.page_size, c.num_kv_heads, c.head_dim, c.dtype,
                cfg.kv_quant,
            )
            return [c.num_layers, pb], "uint8"
        return (
            [c.num_layers, cfg.page_size, c.num_kv_heads, c.head_dim],
            str(jnp.zeros((), c.dtype).dtype),
        )

    def _kv_headwise_shards_ok(self) -> bool:
        """True iff every local KV-pool shard spans the FULL extent on all
        axes except the lane axis (3: kv heads x head_dim, sharded in
        whole-head blocks) — the only layout that
        _local_shard_views/_extract_local_shard (axis-3 concat) and
        _dev_inject_shard (global_shape widened on axis 3 only) can
        reassemble. A pool sharded on layers (pp multihost) or pages
        (dp-attention over a multi-host mesh) would be silently corrupted
        by the per-shard path, so such layouts must use the inline
        allgather transfer instead (advisor r3 finding)."""
        shape = self.kv_k.shape
        for s in self.kv_k.addressable_shards:
            for ax in (0, 1, 2):
                sl = s.index[ax]
                if (sl.start or 0) != 0 or not (
                    sl.stop is None or sl.stop >= shape[ax]
                ):
                    return False
        return True

    def _local_shard_views(self):
        """This host's KV shard pieces, deduped across replicas and sorted
        by the sharded (lane: whole kv heads) axis slice. Single-device
        arrays — safe to index at host-divergent times (no collectives)."""
        def pick(arr):
            seen = {}
            for s in arr.addressable_shards:
                key = tuple(
                    (sl.start or 0, sl.stop) for sl in s.index
                )
                if key not in seen:
                    seen[key] = s
            return [
                s for _, s in sorted(
                    seen.items(), key=lambda kv: kv[0][3][0]
                )
            ]
        return pick(self.kv_k), pick(self.kv_v)

    def local_shard_page_shape(self) -> List[int]:
        """[L, page, KH_local, D] of this host's combined shard."""
        ks, _ = self._local_shard_views()
        L = ks[0].data.shape[0]
        page = ks[0].data.shape[2]
        d = self.model_config.head_dim
        kh_local = sum(s.data.shape[3] for s in ks) // d
        return [L, page, kh_local, d]

    def _extract_local_shard(self, page_ids):
        """Gather the requested page rows of THIS host's shard only: a
        per-device gather on each addressable shard (no collective, no
        cross-host bytes). Returns numpy [L, n, page, KH_local, D]."""
        ids = jnp.asarray(page_ids)
        ks, vs = self._local_shard_views()
        k_parts = [np.asarray(s.data[:, ids]) for s in ks]
        v_parts = [np.asarray(s.data[:, ids]) for s in vs]
        k = k_parts[0] if len(k_parts) == 1 else np.concatenate(k_parts, axis=3)
        v = v_parts[0] if len(v_parts) == 1 else np.concatenate(v_parts, axis=3)
        # the wire carries heads apart, as ever: a view of the gathered rows
        d = self.model_config.head_dim
        return (
            k.reshape(*k.shape[:3], -1, d), v.reshape(*v.shape[:3], -1, d)
        )

    def _dev_inject_shard(self, page_ids, k_local, v_local):
        """SPMD inject where each host supplies ITS OWN shard bytes: build a
        global array from process-local data (metadata-only; no cross-host
        transfer) and enter the same jitted scatter on every host."""
        from jax.sharding import NamedSharding, PartitionSpec

        if self._kv_sharding is not None:
            sharding = self._kv_sharding
        else:
            sharding = NamedSharding(self._mesh, PartitionSpec())
        c = self.model_config
        # wire [L, n, page, KH_local, D] -> this host's lanes of the pool
        k_local = k_local.reshape(*k_local.shape[:3], -1)
        v_local = v_local.reshape(*v_local.shape[:3], -1)
        global_shape = (*k_local.shape[:3], c.num_kv_heads * c.head_dim)
        k_g = jax.make_array_from_process_local_data(sharding, k_local, global_shape)
        v_g = jax.make_array_from_process_local_data(sharding, v_local, global_shape)
        self.kv_k, self.kv_v = self._inject_pages(
            self.kv_k, self.kv_v, jnp.asarray(page_ids), k_g, v_g
        )

    async def run_follower(self, receiver) -> None:
        """Follower-host loop: replay the leader's dispatch sequence.
        No scheduling, no control plane, no host bookkeeping — just the
        same device programs in the same order (reference analogue: vLLM
        node ranks > 0 joining the engine group, main.py:64-296)."""
        while True:
            tag, p = await receiver.recv()
            if tag == "stop":
                return
            if tag == "prefill":
                await self._run_on_device(
                    partial(
                        self._dev_prefill,
                        p["toks"], p["positions"], p["tables"], p["ctx_lens"],
                        p["last_idx"], p["temps"], p["top_ks"], p["top_ps"],
                        p["seeds"], p["pens"], p["pen_rows"],
                    )
                )
            elif tag == "prefill_mm":
                await self._run_on_device(
                    partial(
                        self._dev_prefill_mm,
                        p["toks"], p["positions"], p["tables"], p["ctx_lens"],
                        p["last_idx"], p["temps"], p["top_ks"], p["top_ps"],
                        p["seeds"], p["pens"], p["pen_rows"],
                        p["emb"], p["emb_mask"],
                    )
                )
            elif tag == "reset":
                await self._run_on_device(
                    partial(
                        self._dev_reset,
                        p["tokens"], p["positions"], p["seq_lens"],
                        p["page_tables"], p["temps"], p["top_ks"], p["top_ps"],
                        p["seeds"], p["pens"], p["recent"], p.get("hist"),
                    )
                )
            elif tag == "prefill_single":
                await self._run_on_device(
                    partial(
                        self._dev_prefill_single,
                        p["toks"], p["table"], p["ctx"][0], p["real"][0],
                        p["temps"], p["top_ks"], p["top_ps"], p["seeds"],
                        p["pens"], p["pen_rows"],
                    )
                )
            elif tag == "patch":
                await self._run_on_device(
                    partial(
                        self._dev_patch,
                        p["lane_mask"], p["table_mask"], p["tokens"],
                        p["positions"], p["seq_lens"], p["page_tables"],
                        p["temps"], p["top_ks"], p["top_ps"], p["seeds"],
                        p["pens"], p["recent"], p.get("hist"),
                    )
                )
            elif tag == "prefill_guided":
                await self._run_on_device(
                    partial(
                        self._dev_prefill_guided,
                        p["toks"], p["positions"], p["tables"], p["ctx_lens"],
                        p["last_idx"], p["temps"], p["top_ks"], p["top_ps"],
                        p["seeds"], p["pens"], p["pen_rows"], p["mask"],
                    )
                )
            elif tag == "prefill_lora":
                await self._run_on_device(
                    partial(
                        self._dev_prefill_lora,
                        p["toks"], p["positions"], p["tables"], p["ctx_lens"],
                        p["last_idx"], p["temps"], p["top_ks"], p["top_ps"],
                        p["seeds"], p["pens"], p["pen_rows"], p["idx"],
                    )
                )
            elif tag == "mixed":
                await self._run_on_device(partial(self._dev_mixed, p))
            elif tag == "block":
                await self._run_on_device(self._dev_block)
            elif tag == "block_guided":
                await self._run_on_device(
                    partial(
                        self._dev_block_guided, p["mask"], p.get("lora_idx")
                    )
                )
            elif tag == "block_lora":
                await self._run_on_device(
                    partial(self._dev_block_lora, p["idx"])
                )
            elif tag == "inject":
                await self._run_on_device(
                    partial(self._dev_inject, p["page_ids"], p["k"], p["v"])
                )
            elif tag == "extract":
                await self._run_on_device(partial(self._dev_extract, p["page_ids"]))
            elif tag == "stage_shard":
                # prefill follower: pin OUR shard of these pages under the
                # leader-chosen transfer id; the decode worker's matching
                # host pulls it point-to-point
                tid = p["tid"].tobytes().decode()
                if self.data_plane is not None:
                    self._stage_local_shard(tid, p["page_ids"], lambda ok: None)
                    logger.info(
                        "staged shard %s (%d pages) on follower data plane",
                        tid, len(p["page_ids"]),
                    )
            elif tag == "unstage_shard":
                tid = p["tid"].tobytes().decode()
                if self.data_plane is not None:
                    self.data_plane.unstage_by_id(tid, ok=bool(p["ok"][0]))
            elif tag == "inject_shard":
                # decode follower: pull OUR shard's chunk from our peer
                # prefill host, then enter the same SPMD inject program
                import msgpack as _mp

                from ..llm.kv_transfer import pull_kv_range

                shards = {
                    s["host_id"]: s["addr"]
                    for s in _mp.unpackb(p["addrs"].tobytes(), raw=False)
                }
                tid = p["tid"].tobytes().decode()
                off, n = int(p["off"][0]), int(p["n"][0])
                k_loc, v_loc = await pull_kv_range(
                    shards[self.host_id], tid, off, n,
                    [int(x) for x in p["page_shape"]],
                    str(jnp.zeros((), self.model_config.dtype).dtype),
                )
                logger.info(
                    "follower host %d pulled shard chunk (%d, %d) from %s",
                    self.host_id, off, n, shards[self.host_id],
                )
                await self._run_on_device(
                    partial(self._dev_inject_shard, p["page_ids"], k_loc, v_loc)
                )
            else:
                logger.warning("unknown step tag %r", tag)

    # -- injections (disagg preload / KVBM onboard) ---------------------- #

    def _injection_pending(self) -> bool:
        """Whether a slot's KV waits to be injected (_run_injections)."""
        return any(
            s is not None and (s.preloaded is not None or s.onboard is not None)
            for s in self.slots
        )

    async def _run_injections(self) -> bool:
        did = False
        for slot in list(self.slots):
            if slot is not None and slot.preloaded is not None:
                await self._inject_preloaded(slot)
                did = True
        for slot in list(self.slots):
            if slot is not None and slot.onboard is not None:
                await self._inject_onboard(slot)
                did = True
        return did

    async def _inject_preloaded(self, slot: _Slot):
        """Decode role: write transferred KV pages into our cache and enter
        the decode batch as if we had prefilled locally."""
        first_token, k_np, v_np, n_tokens = slot.preloaded
        slot.preloaded = None
        if slot.pull_desc is not None:
            # pull path: stream chunks in a background task — the decode
            # batch keeps stepping while later pages are still in flight
            desc = slot.pull_desc
            slot.pull_desc = None
            task = asyncio.create_task(self._pull_kv_task(slot, desc, first_token))
            self._bg_tasks.add(task)
            task.add_done_callback(self._bg_tasks.discard)
            return
        page_ids = np.array([p + 1 for p in slot.pages], np.int32)
        self._bcast("inject", {"page_ids": page_ids, "k": np.asarray(k_np), "v": np.asarray(v_np)})
        await self._run_on_device(partial(self._dev_inject, page_ids, k_np, v_np))
        self._activate_transferred(slot, first_token)

    def _activate_transferred(self, slot: _Slot, first_token: int):
        """All prompt KV is in our pages: publish to the prefix cache and
        enter the decode batch (first token was emitted by the prefill
        worker — not re-emitted)."""
        self._commit_blocks(slot)
        slot.prefill_pos = len(slot.prompt)
        slot.generated = 1
        slot.last_token = first_token
        slot.seq.append(first_token)
        self.tokens[slot.slot_idx] = first_token
        self.seq_lens[slot.slot_idx] = len(slot.prompt) + 1
        self._fill_hist(slot.slot_idx, slot)
        self._fill_recent(slot.slot_idx, slot)
        self._mark_lane_dirty(slot.slot_idx)
        self._maybe_finish(slot, first_token)

    async def _pull_kv_task(self, slot: _Slot, desc_dict: dict,
                            first_token: Optional[int]):
        """Stream KV chunks from the staging prefill worker, injecting each
        as it lands. Any failure falls back to computing the prompt KV
        locally, resuming from the already-emitted first token — disagg
        stays strictly an optimization. `first_token=None` = streamed
        handoff: the pull started off the EARLY descriptor while the peer
        was still prefilling; the token arrives later via
        slot.first_token_fut (None result = handler abandoned us)."""
        from ..llm.kv_transfer import KvFormatError, KvTransferDescriptor, pull_kv

        desc = KvTransferDescriptor.from_dict(desc_dict)
        phys = np.array([p + 1 for p in slot.pages], np.int32)
        if desc.kv_format != self.config.kv_quant:
            # mixed-precision pairing: fail TYPED before any byte moves —
            # the except-path below falls back to a local prefill (and
            # counts it), instead of injecting misread pages
            self.kv_format_mismatches += 1
            err: Optional[Exception] = KvFormatError(
                f"peer stages kv_format={desc.kv_format!r}, this worker "
                f"runs {self.config.kv_quant!r}"
            )
        else:
            err = None
        streamed = slot.first_token_fut is not None
        chunks_before_first = 0
        first_before_last_chunk = False

        async def inject(off: int, n: int, k, v):
            nonlocal chunks_before_first, first_before_last_chunk
            if (
                slot.done
                or self._closed
                or slot.slot_idx < 0
                or self.slots[slot.slot_idx] is not slot
            ):
                raise asyncio.CancelledError("slot released mid-pull")
            fut = slot.first_token_fut
            if fut is not None:
                # overlap evidence: final value of first_before_last_chunk
                # = "the first token was already here when the LAST chunk
                # landed" (structurally impossible on the serial path)
                first_before_last_chunk = fut.done()
                if not fut.done():
                    chunks_before_first += 1
            ids = phys[off : off + n]
            if self._spmd is not None:
                self._bcast("inject", {"page_ids": ids, "k": np.asarray(k), "v": np.asarray(v)})
            await self._run_on_device(partial(self._dev_inject, ids, k, v))

        try:
            if err is not None:
                raise err
            if desc.shards is not None:
                await self._pull_kv_shards(slot, desc, phys)
            else:
                await pull_kv(desc, inject)
        except asyncio.CancelledError:
            # slot released mid-pull (inject raises) or engine close()
            # cancelled us: nothing to fall back to — propagate so the
            # task records itself cancelled, not finished
            raise
        except Exception as e:  # noqa: BLE001 — any pull failure -> local fallback
            if streamed:
                first_token = await self._await_first_token(slot)
                if first_token is None:
                    self._abandon_streamed_slot(slot)
                    return
            if slot.done or slot.slot_idx < 0 or self.slots[slot.slot_idx] is not slot:
                return
            logger.warning(
                "kv pull for %s failed (%s); prefilling locally", slot.request_id, e
            )
            slot.generated = 1
            slot.last_token = first_token
            slot.seq.append(first_token)
            slot.resume_token = first_token
            slot.prefill_pos = 0
            self._wake.set()
            return
        if streamed:
            first_token = await self._await_first_token(slot)
            if first_token is None:
                self._abandon_streamed_slot(slot)
                return
            self.disagg_streamed_handoffs += 1
            self.disagg_chunks_before_first_token += chunks_before_first
            if first_before_last_chunk:
                self.disagg_first_token_before_last_chunk += 1
        if slot.done or slot.slot_idx < 0 or self.slots[slot.slot_idx] is not slot:
            return
        logger.info(
            "kv pull complete for %s: %d pages via data plane %s",
            slot.request_id, desc.n_pages, desc.addr,
        )
        self.kv_pulls_completed += 1
        self.kv_pages_pulled += int(desc.n_pages)
        self._activate_transferred(slot, first_token)
        self._wake.set()

    async def _await_first_token(self, slot: _Slot) -> Optional[int]:
        """Streamed handoff: wait for the handler to deliver the prefill's
        first token (None = the handler abandoned the early pull)."""
        fut, slot.first_token_fut = slot.first_token_fut, None
        if fut is None:
            return None
        return await fut

    def _abandon_streamed_slot(self, slot: _Slot):
        """The handler abandoned an early pull (prefill failed or the
        transfer was re-staged): release the slot and unblock any stream
        consumer."""
        if slot.slot_idx >= 0 and self.slots[slot.slot_idx] is slot:
            self._release_slot(slot)
        slot.done = True
        slot.queue.put_nowait(None)
        self._wake.set()

    async def _pull_kv_shards(self, slot: _Slot, desc, phys: np.ndarray):
        """Multi-host shard pull: this (leader) host pulls ITS shard chunk
        by chunk; each chunk's inject is an SPMD dispatch where followers
        supply their OWN shard bytes (pulled from their peer host inside
        the inject_shard replay). No host ever moves another host's bytes;
        nothing is re-broadcast."""
        from ..llm.kv_transfer import pull_kv_range

        if not (self._multihost and self.shard_addrs):
            raise RuntimeError("sharded descriptor but this worker is not multi-host")
        if not self._kv_headwise_shards_ok():
            # raising here lands in _pull_and_activate's fallback: the
            # request prefills locally instead of injecting corrupt KV
            raise RuntimeError(
                "KV pool host-sharded beyond the kv-head axis; shard-wise "
                "inject unsupported for this layout"
            )
        shards = {s["host_id"]: s["addr"] for s in desc.shards}
        if len(shards) != len(self.shard_addrs):
            raise RuntimeError(
                f"shard count mismatch: peer has {len(shards)} hosts, we have "
                f"{len(self.shard_addrs)} — falling back to local prefill"
            )
        my_addr = shards[self.host_id]
        import msgpack as _mp

        addrs_blob = np.frombuffer(
            _mp.packb(desc.shards, use_bin_type=True), np.uint8
        )
        tid_blob = np.frombuffer(desc.transfer_id.encode(), np.uint8)
        off = 0
        while off < desc.n_pages:
            n = min(desc.chunk_pages, desc.n_pages - off)
            if (
                slot.done
                or self._closed
                or slot.slot_idx < 0
                or self.slots[slot.slot_idx] is not slot
            ):
                raise asyncio.CancelledError("slot released mid-pull")
            k_loc, v_loc = await pull_kv_range(
                my_addr, desc.transfer_id, off, n, desc.page_shape, desc.dtype
            )
            ids = phys[off : off + n]
            # bcast + dispatch in ONE synchronous segment: interleaving an
            # await between them could reorder against the step loop's own
            # bcast+dispatch pairs and diverge the SPMD program order
            self._bcast(
                "inject_shard",
                {
                    "tid": tid_blob,
                    "addrs": addrs_blob,
                    "page_ids": ids,
                    "off": np.array([off], np.int64),
                    "n": np.array([n], np.int64),
                    "page_shape": np.array(desc.page_shape, np.int64),
                },
            )
            fut = self._run_on_device(
                partial(self._dev_inject_shard, ids, k_loc, v_loc)
            )
            await fut
            off += n
        logger.info(
            "kv shard pull complete: %d pages from %s (host %d pulled only "
            "its own shard)", desc.n_pages, my_addr, self.host_id,
        )
        # tell the prefill leader the transfer is complete so it releases
        # (its on_done broadcast unpins the prefill followers' stages)
        try:
            from ..llm.kv_transfer import finish_transfer

            await finish_transfer(desc.addr, desc.transfer_id)
        except Exception:  # noqa: BLE001 — TTL reaper is the backstop
            logger.warning("could not signal transfer completion", exc_info=True)

    async def _inject_onboard(self, slot: _Slot):
        """KVBM onboard: scatter G2/G3 blocks into the freshly allocated
        device pages, then register them in the device prefix cache so
        concurrent sequences share them."""
        alloc_pages, hashes = slot.onboard
        slot.onboard = None
        t0 = time.perf_counter()
        try:
            # tier reads (host memcpy / disk memmap) run off the event loop,
            # serialized with offload stores on the same executor; remote
            # (G4/peer) blocks pull over the data plane first, resolved via
            # the announcement mesh with the router's holder hint as
            # fallback (cluster KV fabric)
            hint = slot.kv_holder or {}
            k_np, v_np = await self.kvbm.load_async(
                hashes, self._run_on_device,
                hint_instance=hint.get("instance"),
            )
        except Exception as e:
            from ..llm.kv_transfer import KvFormatError

            if not isinstance(e, (KeyError, faults.FaultError, KvFormatError)):
                raise
            if isinstance(e, KvFormatError):
                # mixed-precision fleet: the peer pull failed TYPED before
                # any bytes were misread — counted, loud, then the same
                # recompute fallback every onboard miss takes
                self.kv_format_mismatches += 1
                logger.warning("KVBM peer kv_format mismatch: %s", e)
            # block evicted between probe and load — or a dynochaos
            # `kvbm.onboard` error: fall back to computing that part of
            # the prompt (pages are already allocated); onboarding is a
            # latency optimization, never a correctness dependency
            logger.warning("KVBM onboard miss: %s; prefilling instead", e)
            n_known = len(slot.committed_hashes)
            slot.prefill_pos = n_known * self.config.page_size
            if slot.migration:
                # replayed-token accounting is OUTCOME-based: the
                # admission plan counted these blocks as reused, but the
                # pull died (dead peer, eviction race) and the span now
                # really re-prefills — an operator reading "what did the
                # death cost" must see it
                self.migration_replayed_tokens += (
                    len(hashes) * self.config.page_size
                )
            return
        # [n, layers, page, heads, dim] -> [layers, n, page, heads, dim]
        k_np = k_np.swapaxes(0, 1)
        v_np = v_np.swapaxes(0, 1)
        phys = np.array([p + 1 for p in alloc_pages], np.int32)  # scratch shift
        self._bcast("inject", {"page_ids": phys, "k": k_np, "v": v_np})
        await self._run_on_device(partial(self._dev_inject, phys, k_np, v_np))
        n_known = len(slot.committed_hashes)
        token_blocks = [
            b.tokens for b in slot.seq.blocks[n_known : n_known + len(hashes)]
        ]
        parent = slot.committed_hashes[-1] if slot.committed_hashes else None
        self.allocator.commit_hashes(alloc_pages, hashes, token_blocks, parent)
        slot.committed_hashes.extend(hashes)
        self._advance_kv_stream(slot)
        # (whole-prompt clamp already applied at admission, _try_admit)
        self._record_onboard_ms((time.perf_counter() - t0) * 1000.0)

    def _record_onboard_ms(self, ms: float):
        """Onboard-latency histogram (tier load + device inject, per
        onboard): the cache-effectiveness signal beside the hit counters."""
        for i, bound in enumerate(self._onboard_hist_bounds):
            if ms <= bound:
                self.kvbm_onboard_hist[i] += 1
                break
        else:
            self.kvbm_onboard_hist[-1] += 1
        self.kvbm_onboard_ms_sum += ms
        self.kvbm_onboard_count += 1

    # -- batched chunked prefill ----------------------------------------- #

    def _try_skip_ahead(self, s: _Slot) -> None:
        """Late-binding prefix reuse: blocks committed SINCE this slot was
        admitted (by a concurrent same-prefix request, possibly via the
        incremental chunk commit) cover part of the remaining prompt —
        swap the cached pages into the table and skip the compute. Only
        whole-page-aligned progress can splice; fresh slots only (resume/
        disagg/onboard slots carry their own page provenance)."""
        cfg = self.config
        if not cfg.enable_prefix_caching or self._lane_state or s.want_routed:
            return  # caching disabled must disable ALL reuse paths (a
            # family with a state of a lane reuses no page, nor does a
            # request that wants every position's experts: _try_admit)
        if s.generated or s.resume_token is not None or s.onboard is not None:
            return
        n_known = len(s.committed_hashes)
        if s.prefill_pos != n_known * cfg.page_size:
            return
        hashes = s.seq.block_hashes()
        prompt_full = len(s.kv_prompt) // cfg.page_size
        if n_known >= prompt_full:
            return
        extra = self.allocator.acquire_cached(hashes[n_known:prompt_full])
        if not extra:
            return
        self.prefix_skip_ahead_blocks += len(extra)
        old = s.pages[n_known : n_known + len(extra)]
        s.pages[n_known : n_known + len(extra)] = extra
        self.allocator.release(old, [])  # fresh, un-hashed -> free list
        s.committed_hashes.extend(hashes[n_known : n_known + len(extra)])
        self._advance_kv_stream(s)
        s.prefill_pos = (n_known + len(extra)) * cfg.page_size
        if s.prefill_pos >= len(s.kv_prompt):
            # whole prompt now cached: recompute the last token for logits
            s.prefill_pos = len(s.kv_prompt) - 1
        phys = [p + 1 for p in s.pages]
        self.page_tables[s.slot_idx, : len(phys)] = phys

    async def _dispatch_prefill(self) -> bool:
        """Pack prefill chunks from several slots into ONE dispatch.

        Shapes are bounded: batch lanes B_pf = prefill_batch_tokens/bucket
        (padded with dummy lanes), table length = pow2 context bucket + a
        scratch tail entry for padded positions — so compile variants stay
        few and cacheable."""
        cfg = self.config
        single = None
        with self._rec.span("pack", more=True):
            cands = []
            for s in self.slots:
                # prefill_pos has a single writer per LIVE slot (this dispatch
                # path); the pull-failure fallback rewrite only reaches slots
                # excluded from cands while their pull is in flight
                if s is None or s.prefill_pos >= len(s.kv_prompt):  # dynolint: disable=race-await-atomicity -- single writer per live slot; pull-path slots are filtered below
                    continue
                if s.preloaded is not None or s.onboard is not None:
                    continue
                if s.done or s.context.is_stopped():
                    self._emit_finish(s, "cancelled")
                    self._release_slot(s)
                    continue
                self._try_skip_ahead(s)
                cands.append(s)
            if not cands:
                return False
            # dynosched: candidate order is the planner's call — fifo is the
            # legacy admit_seq sort bit-for-bit, sla is EDF over TTFT deadlines
            # with a starvation guard (docs/scheduler.md)
            cands = self.scheduler.order(cands)
            # guided / multimodal / LoRA slots ride different dispatch variants
            # (mask vs embedding splice vs adapter stack) and never share a
            # prefill batch with each OTHER; plain slots batch with any single
            # kind (they are exact no-ops under mask=all-true or adapter 0).
            # The excluded kind waits for a later dispatch — the planner's aging
            # tiebreak bounds that wait (a kind skipped starve_dispatches times
            # wins the batch outright, so no kind starves under a steady stream
            # of another kind).
            def _kind(s):
                if s.mm is not None:
                    return "mm"
                if s.guided_fsm is not None:
                    return "guided"
                if s.lora_idx:
                    return "lora"
                return "plain"

            batch_kind = self.scheduler.pick_batch_kind(cands, _kind)
            if batch_kind != "plain":
                excluded = [s for s in cands if _kind(s) not in ("plain", batch_kind)]
                if excluded:
                    for s in excluded:
                        s.sched_skips += 1
                    cands = [s for s in cands if _kind(s) in ("plain", batch_kind)]

            if self._prefill_single is not None:
                s0 = cands[0]
                remaining = len(s0.kv_prompt) - s0.prefill_pos
                # pp: every prompt goes through the pipelined single-seq path
                # (layer-sharded weights make the batched path degenerate);
                # sp: only fresh long prompts ride the ring (history-free).
                # Multimodal slots never ride it (splice unsupported there —
                # _check_multimodal rejects those configs up front).
                use_single = not s0.mm and (
                    cfg.pp_size > 1
                    or (s0.prefill_pos == 0 and remaining >= cfg.ring_prefill_threshold)
                )
                if use_single:
                    single = s0
        if single is not None:
            await self._dispatch_prefill_one(single)
            return True
        self._rec.entry_kind = "prefill"
        with self._rec.span("pack"):
            # two lane variants per bucket — 1 (the lone-arrival TTFT case:
            # padding one request to the full lane budget multiplies its
            # prefill FLOPs by the budget) and the cap (batch case). Exactly
            # two keeps the lazily-compiled shape set small: every new shape
            # costs a multi-second XLA compile ON the serving path the first
            # time it occurs (persistent cache amortizes across restarts).
            # The planner chooses WITHIN that bounded shape space: fifo
            # reproduces the legacy head-candidate formula exactly; sla scores
            # shapes by slots-served/tokens-granted under the ITL budget and
            # may defer the dispatch entirely to protect decode cadence.
            has_decode = bool(self._active_decode_indices())
            plan = self.scheduler.plan_prefill(cands, decode_active=has_decode)
            if plan is None:
                # ITL budget exhausted and no deadline at risk: prefill yields
                # this step; skipped candidates age toward the starvation guard
                for s in cands:
                    s.sched_skips += 1
                return False
            bucket = plan.bucket
            lanes = plan.lanes
            chosen = plan.chosen
            for s in cands[len(chosen):]:
                s.sched_skips += 1
            B_pf = lanes

            # shared context-bounded table: pow2 pages covering the largest
            # (history + chunk), plus one guaranteed-scratch tail entry that
            # padded positions write to
            chunk_of = {}
            max_pages_needed = 1
            for s in chosen:
                chunk = min(len(s.kv_prompt) - s.prefill_pos, bucket)
                chunk_of[s.request_id] = chunk
                pages_needed = (s.prefill_pos + chunk + cfg.page_size - 1) // cfg.page_size
                max_pages_needed = max(max_pages_needed, pages_needed)
            ctx_pages = min(_next_pow2(max_pages_needed), cfg.max_pages_per_seq)
            if self._walks_contexts:
                # the family's attention ends at the longest context of the
                # call, whatever the table's width: one width, one program
                ctx_pages = cfg.max_pages_per_seq
            P = ctx_pages + 1
            pad_pos = P * cfg.page_size - 1

            toks = np.zeros((B_pf, bucket), np.int32)
            positions = np.full((B_pf, bucket), pad_pos, np.int32)
            tables = np.full((B_pf, P), SCRATCH_PAGE, np.int32)
            ctx_lens = np.zeros((B_pf,), np.int32)
            last_idx = np.zeros((B_pf,), np.int32)
            temps = np.zeros((B_pf,), np.float32)
            top_ks = np.zeros((B_pf,), np.int32)
            top_ps = np.ones((B_pf,), np.float32)
            seeds = np.zeros((B_pf,), np.uint32)
            pens = np.zeros((B_pf, 3), np.float32)
            pens[:, 2] = 1.0  # repetition off
            W = self.config.penalty_window
            pen_rows = np.full((B_pf, W), -1, np.int32)
            meta = []
            for lane, s in enumerate(chosen):
                chunk = chunk_of[s.request_id]
                start = s.prefill_pos
                toks[lane, :chunk] = s.kv_prompt[start : start + chunk]
                positions[lane, :chunk] = np.arange(start, start + chunk)
                tables[lane, :ctx_pages] = self.page_tables[s.slot_idx][:ctx_pages]
                ctx_lens[lane] = start
                last_idx[lane] = chunk - 1
                temps[lane] = s.temperature
                top_ks[lane] = s.top_k
                top_ps[lane] = s.top_p
                seeds[lane] = s.sample_seed
                pens[lane] = (s.presence_penalty, s.frequency_penalty,
                              s.repetition_penalty)
                pen_rows[lane] = self.recent[s.slot_idx]
                s.sched_skips = 0  # granted a chunk: starvation clock restarts
                meta.append((s, chunk, lane))
            self._last_prefill_shape = (
                B_pf * bucket, sum(ch for _, ch, _ in meta)
            )
            self._count_expert_rows(*self._last_prefill_shape)

            if any(s.mm for s in chosen):
                # multimodal splice operands: encoder rows land at their
                # absolute prompt positions within this chunk window
                H = self.model_config.hidden_size
                emb = np.zeros((B_pf, bucket, H), np.float32)
                emb_mask = np.zeros((B_pf, bucket), bool)
                for s, chunk, lane in meta:
                    if not s.mm:
                        continue
                    start = s.prefill_pos  # chunk window [start, start+chunk)
                    for pos0, arr in s.mm:
                        lo, hi = max(pos0, start), min(pos0 + len(arr), start + chunk)
                        if lo < hi:
                            emb[lane, lo - start : hi - start] = arr[lo - pos0 : hi - pos0]
                            emb_mask[lane, lo - start : hi - start] = True
                self._bcast(
                    "prefill_mm",
                    {
                        "toks": toks, "positions": positions, "tables": tables,
                        "ctx_lens": ctx_lens, "last_idx": last_idx, "temps": temps,
                        "top_ks": top_ks, "top_ps": top_ps, "seeds": seeds,
                        "pens": pens, "pen_rows": pen_rows,
                        "emb": emb, "emb_mask": emb_mask,
                    },
                )
                call = partial(
                    self._dev_prefill_mm,
                    toks, positions, tables, ctx_lens, last_idx,
                    temps, top_ks, top_ps, seeds, pens, pen_rows,
                    emb, emb_mask,
                )
            elif any(s.guided_fsm is not None for s in chosen):
                # masked first-token sampling: guided lanes constrain the first
                # generated token the same way decode steps are constrained
                V = self.model_config.vocab_size
                mask = np.full((B_pf, (V + 7) // 8), 0xFF, np.uint8)
                for s, chunk, lane in meta:
                    if s.guided_fsm is not None:
                        mask[lane] = np.packbits(self._guided_lane_mask(
                            s.guided_fsm, s.guided_state
                        ))
                self._bcast(
                    "prefill_guided",
                    {
                        "toks": toks, "positions": positions, "tables": tables,
                        "ctx_lens": ctx_lens, "last_idx": last_idx, "temps": temps,
                        "top_ks": top_ks, "top_ps": top_ps, "seeds": seeds,
                        "pens": pens, "pen_rows": pen_rows, "mask": mask,
                    },
                )
                call = partial(
                    self._dev_prefill_guided,
                    toks, positions, tables, ctx_lens, last_idx,
                    temps, top_ks, top_ps, seeds, pens, pen_rows, mask,
                )
            elif any(s.lora_idx for s in chosen):
                lane_idx = np.zeros((B_pf,), np.int32)
                for s, chunk, lane in meta:
                    lane_idx[lane] = s.lora_idx
                self._bcast(
                    "prefill_lora",
                    {
                        "toks": toks, "positions": positions, "tables": tables,
                        "ctx_lens": ctx_lens, "last_idx": last_idx, "temps": temps,
                        "top_ks": top_ks, "top_ps": top_ps, "seeds": seeds,
                        "pens": pens, "pen_rows": pen_rows, "idx": lane_idx,
                    },
                )
                call = partial(
                    self._dev_prefill_lora,
                    toks, positions, tables, ctx_lens, last_idx,
                    temps, top_ks, top_ps, seeds, pens, pen_rows, lane_idx,
                )
            else:
                self._bcast(
                    "prefill",
                    {
                        "toks": toks, "positions": positions, "tables": tables,
                        "ctx_lens": ctx_lens, "last_idx": last_idx, "temps": temps,
                        "top_ks": top_ks, "top_ps": top_ps, "seeds": seeds,
                        "pens": pens, "pen_rows": pen_rows,
                    },
                )
                call = partial(
                    self._dev_prefill,
                    toks, positions, tables, ctx_lens, last_idx, temps,
                    top_ks, top_ps, seeds, pens, pen_rows,
                )
            work = Work(self._index_topk)
            for s, chunk, lane in meta:
                work.chunk(s.prefill_pos, chunk,
                           s.prefill_pos + chunk >= len(s.kv_prompt))
            entry = {}
            if self._stateful:
                self._note_first_chunks(s.prefill_pos for s, _, _ in meta)
                # flat slots of each asking row's chunk, for the fetch
                entry["want_routed"] = [
                    (s, lane * bucket, chunk) for s, chunk, lane in meta
                    if s.want_routed
                ]
                call = self._routed_behind(partial(
                    call, lanes=self.kv_k.row_lanes(
                        [s.slot_idx for s, _, _ in meta])), entry)
            self._rec.dispatched(entry, "prefill", work.of(self._step_work))
        entry["first"] = await self._run_on_device(
            call, tag="prefill", shape=(bucket, B_pf)
        )
        self._rec.launched(entry)
        completions = []
        progressed = []
        for s, chunk, lane in meta:
            s.prefill_pos += chunk
            # commit confirmed at this dispatch's FETCH (execution proof)
            progressed.append((s, s.prefill_pos))
            if s.prefill_pos >= len(s.kv_prompt):
                completions.append((s, lane))
        entry.update(done=completions, progressed=progressed)
        self._pending_prefill.append(entry)
        return True

    async def _dispatch_prefill_one(self, slot: _Slot) -> None:
        """Single-sequence whole-remaining-prompt prefill through the
        parallel path (_prefill_single: ring over sp / pipeline over pp).
        Pads to a pow2 bucket so compile variants stay bounded."""
        cfg = self.config
        self._rec.entry_kind = "prefill"
        with self._rec.span("pack"):
            chunk = len(slot.kv_prompt) - slot.prefill_pos
            unit = max(cfg.sp_size, cfg.pp_size, 1)
            # pow2 bucket for bounded compile variants, then round UP to a unit
            # multiple (a non-pow2 sp/pp size would otherwise fail the ring's
            # divisibility check)
            T_pad = _next_pow2(chunk)
            T_pad = -(-T_pad // unit) * unit
            pages_needed = (slot.prefill_pos + chunk + cfg.page_size - 1) // cfg.page_size
            P = min(_next_pow2(pages_needed), cfg.max_pages_per_seq) + 1
            table = np.full((P,), SCRATCH_PAGE, np.int32)
            table[: min(len(slot.pages), P)] = [p + 1 for p in slot.pages[:P]]
            toks = np.zeros((T_pad,), np.int32)
            toks[:chunk] = slot.kv_prompt[slot.prefill_pos :]
            ctx = np.int32(slot.prefill_pos)
            real = np.int32(chunk)
            temps = np.array([slot.temperature], np.float32)
            top_ks = np.array([slot.top_k], np.int32)
            top_ps = np.array([slot.top_p], np.float32)
            seeds = np.array([slot.sample_seed], np.uint32)
            pens = np.array([[slot.presence_penalty, slot.frequency_penalty,
                              slot.repetition_penalty]], np.float32)
            pen_rows = self.recent[slot.slot_idx : slot.slot_idx + 1]
            self._bcast(
                "prefill_single",
                {
                    "toks": toks, "table": table, "ctx": np.array([ctx]),
                    "real": np.array([real]), "temps": temps,
                    "top_ks": top_ks, "top_ps": top_ps, "seeds": seeds,
                    "pens": pens, "pen_rows": pen_rows,
                },
            )
            work = Work(self._index_topk)
            work.chunk(slot.prefill_pos, chunk, True)
            entry = {"done": [(slot, 0)]}
            self._rec.dispatched(entry, "prefill", work.of(self._step_work))
        entry["first"] = await self._run_on_device(
            partial(self._dev_prefill_single, toks, table, ctx, real, temps,
                    top_ks, top_ps, seeds, pens, pen_rows),
            tag="prefill", shape=(T_pad, 1),
        )
        self._rec.launched(entry)
        self._last_prefill_shape = (T_pad, chunk)
        self._count_expert_rows(T_pad, chunk)
        slot.prefill_pos += chunk
        self._pending_prefill.append(entry)

    def _dev_prefill_single(self, toks, table, ctx, real, temps, top_ks,
                            top_ps, seeds, pens, pen_rows):
        with self._rec.span("put"):
            toks, table, pen_rows, ctx, real, *samp = self._put(
                toks, table, pen_rows,
                np.asarray(ctx, np.int32), np.asarray(real, np.int32),
                *self._samp_host(temps, top_ks, top_ps, seeds, pens),
            )
            samp = SamplingParams(*samp)
        with self._rec.span("launch"):
            first, self.kv_k, self.kv_v, self._rng = self._prefill_single(
                self.params, self.kv_k, self.kv_v, toks, table, ctx, real,
                self._rng, samp, pen_rows,
            )
        return first

    def _fill_recent(self, idx: int, slot: _Slot):
        """Load the lane's penalty window from the tokens so far (prompt +
        generated); ring-indexed by absolute position so device-side
        appends stay consistent across patches."""
        W = self.config.penalty_window
        toks = np.asarray(slot.seq.tokens, np.int32)
        row = self.recent[idx]
        row[:] = -1
        if len(toks):
            ps = np.arange(max(0, len(toks) - W), len(toks))
            row[ps % W] = toks[ps]

    def _fill_hist(self, idx: int, slot: _Slot):
        """Load the lane's history ring (host mirror) for n-gram drafting:
        the last spec_hist tokens of prompt-so-far + the current token.
        Uploaded to device by the reset/patch that follows lane dirtying."""
        if self.hist is None:
            return
        Hc = self.config.spec_hist
        toks = np.asarray(
            list(slot.kv_prompt) + [int(self.tokens[idx])], np.int32
        )
        L1 = len(toks)
        row = self.hist[idx]
        row[:] = 0
        ps = np.arange(max(0, L1 - Hc), L1)
        row[ps % Hc] = toks[ps]

    def _top_entry(self, slot: _Slot, tids, tlps) -> Optional[dict]:
        """Top-k alternatives for one emitted token, sliced to the
        request's ask (None when not requested — zero overhead)."""
        n = slot.want_top_logprobs
        if not n:
            return None
        return {
            "ids": [int(t) for t in tids[:n]],
            "logprobs": [float(v) for v in tlps[:n]],
        }

    @staticmethod
    def _served_among(top: Optional[dict], token: int, lp) -> None:
        """A top entry names the token that was served: where sampling
        took one outside the request's n most likely, the last of them
        gives way to it and its own log-probability."""
        if top and lp is not None and token not in top["ids"]:
            top["ids"][-1], top["logprobs"][-1] = int(token), float(lp)

    def _finish_prefill(self, slot: _Slot, first: int,
                        first_lp: Optional[float] = None,
                        first_top: Optional[dict] = None,
                        piped: bool = False):
        """Prompt KV fully computed and the first token fetched: what
        needs the VALUE (emit, finish reason, the sequence, the host's
        token mirror). What is known at dispatch (the lane is
        decode-active, its length, its table) was done there for a `piped`
        mixed step, whose sample is already in the device carry; any other
        dispatch activates the lane here, from the host's value."""
        self._commit_blocks(slot)
        slot.first_pending = False
        if slot.done or slot.context.is_stopped():
            self._emit_finish(slot, "cancelled")
            self._release_slot(slot)
            return
        if slot.resume_token is not None:
            # preempted resume: continue from the already-emitted pending
            # token; the freshly sampled token is discarded
            first = slot.resume_token
            slot.resume_token = None
            slot.last_token = first
            self.tokens[slot.slot_idx] = first
            self.seq_lens[slot.slot_idx] = len(slot.kv_prompt) + 1
            self._fill_hist(slot.slot_idx, slot)
            self._fill_recent(slot.slot_idx, slot)
            self._mark_lane_dirty(slot.slot_idx)
            return
        if slot.guided_fsm is not None:
            slot.guided_state = slot.guided_fsm.advance(
                slot.guided_state, first
            )
        self._emit_token(slot, first, first_lp, first_top)
        if not slot.done:
            slot.last_token = first
            slot.generated = 1
            slot.seq.append(first)
            self.tokens[slot.slot_idx] = first
            if not piped:
                self.seq_lens[slot.slot_idx] = len(slot.kv_prompt) + 1
                self._fill_hist(slot.slot_idx, slot)
                self._fill_recent(slot.slot_idx, slot)
                self._mark_lane_dirty(slot.slot_idx)
            self._maybe_finish(slot, first)

    async def _emit_prefill_result(self, slot: _Slot, first_token: int,
                                   first_lp: Optional[float] = None,
                                   first_top: Optional[dict] = None):
        from ..llm.disagg import pack_kv_payload

        cfg = self.config
        # the prefill role's first token leaves with the pages, by whichever
        # of the three roads below
        self._rec.first_token(slot)
        n_prompt_pages = (len(slot.prompt) + cfg.page_size - 1) // cfg.page_size
        page_ids = np.array(
            [p + 1 for p in slot.pages[:n_prompt_pages]], np.int32
        )  # +1 scratch shift
        # the computed prompt KV is valid — publish full blocks to our own
        # prefix cache so repeat prefills of shared prefixes are free
        self._commit_blocks(slot)

        if slot.kv_stream_tid is not None and self.data_plane is not None \
                and not slot.done:
            # streamed handoff still alive: publish the final page + the
            # first token under the SAME transfer (the decode worker has
            # been pulling since admission)
            self._finish_streamed_kv(slot, first_token, first_lp, first_top)
            return
        if slot.kv_stream and not slot.done:
            # the early stage died mid-prefill (reaped TTL, severed pull):
            # fall through to a fresh serial stage — the decode worker's
            # failed early pull retries off the final descriptor
            slot.kv_stream_desc = None

        if slot.kv_pull and self.data_plane is not None and not slot.done:
            # fast path: stage the pages on the data plane and return only a
            # descriptor — the decode worker pulls chunks while we keep
            # serving; pages stay pinned until the pull finishes (or TTL)
            self._stage_kv_pull(slot, first_token, page_ids, first_lp,
                                first_top)
            return

        self._bcast("extract", {"page_ids": page_ids})
        k_np, v_np = await self._run_on_device(partial(self._dev_extract, page_ids))
        payload = pack_kv_payload(k_np, v_np, len(slot.prompt), cfg.page_size,
                                  kv_format=cfg.kv_quant)
        if not slot.done:
            out = LLMEngineOutput(
                token_ids=[first_token],
                log_probs=[first_lp]
                if (slot.want_logprobs and first_lp is not None) else None,
                top_logprobs=[first_top] if first_top else None,
                finish_reason="remote_prefill_done",
                kv_transfer_params=payload,
            ).to_dict()
            slot.queue.put_nowait(Annotated(data=out).to_dict())
            slot.queue.put_nowait(None)
            slot.done = True
        self._release_slot(slot)

    def _stage_kv_pull(self, slot: _Slot, first_token: int,
                       page_ids: np.ndarray,
                       first_lp: Optional[float] = None,
                       first_top: Optional[dict] = None):
        """Pin the finished prefill's pages on the data plane and answer with
        a descriptor. The extract callback gathers page CHUNKS lazily as the
        decode worker pulls, so the device gather overlaps the network (and
        on the in-process path never leaves the device). On a multi-host
        mesh each host stages ITS OWN SHARD under one transfer id (the
        stage_shard broadcast) and the descriptor carries the per-host
        rendezvous — the decode worker's hosts pull point-to-point."""
        import jax.numpy as jnp

        c = self.model_config
        cfg = self.config
        wire_shape, dtype_name = self._kv_wire_meta()

        def on_done(ok: bool):
            if not ok:
                logger.warning(
                    "kv pull for %s abandoned; releasing pages", slot.request_id
                )
            self._release_slot(slot)

        shard_path = bool(self._multihost and self.shard_addrs)
        if shard_path and not self._kv_headwise_shards_ok():
            # pool sharded beyond the kv-head axis: the per-shard path would
            # reassemble bytes under wrong layers/pages — use the inline
            # allgather transfer (correct for any sharding, more bytes)
            logger.warning(
                "KV pool is host-sharded beyond the kv-head axis; using the "
                "inline KV transfer path instead of per-shard pulls"
            )
            shard_path = False
        if shard_path:
            import secrets as _secrets

            tid = _secrets.token_hex(8)
            self._bcast(
                "stage_shard",
                {
                    "tid": np.frombuffer(tid.encode(), np.uint8),
                    "page_ids": page_ids,
                },
            )

            def on_done_shard(ok: bool):
                # release leader-side pages AND tell followers to unpin
                self._bcast(
                    "unstage_shard",
                    {
                        "tid": np.frombuffer(tid.encode(), np.uint8),
                        "ok": np.array([1 if ok else 0], np.int8),
                    },
                )
                on_done(ok)

            desc = self._stage_local_shard(tid, page_ids, on_done_shard)
            desc.n_tokens = len(slot.prompt)
            desc.shards = [
                {"host_id": h, "addr": a} for h, a in enumerate(self.shard_addrs)
            ]
        else:
            async def extract(off: int, n: int, device: bool):
                ids = page_ids[off : off + n]
                self._bcast("extract", {"page_ids": ids})
                if device and not self._multihost and cfg.kv_quant == "none":
                    # in-process path: hand over device arrays, no host
                    # staging (quantized pools always serialize to the
                    # packed host rows — the one wire layout)
                    return await self._run_on_device(
                        lambda: self._extract_pages(self.kv_k, self.kv_v, jnp.asarray(ids))
                    )
                return await self._run_on_device(partial(self._dev_extract, ids))

            desc = self.data_plane.stage(
                n_pages=int(len(page_ids)),
                n_tokens=len(slot.prompt),
                page_size=cfg.page_size,
                page_shape=wire_shape,
                dtype=dtype_name,
                kv_format=cfg.kv_quant,
                extract=extract,
                on_done=on_done,
            )
        out = LLMEngineOutput(
            token_ids=[first_token],
            log_probs=[first_lp]
            if (slot.want_logprobs and first_lp is not None) else None,
            top_logprobs=[first_top] if first_top else None,
            finish_reason="remote_prefill_done",
            kv_transfer_params={"pull": desc.to_dict()},
        ).to_dict()
        slot.queue.put_nowait(Annotated(data=out).to_dict())
        slot.queue.put_nowait(None)
        slot.done = True
        # NOT released here: pages stay pinned until on_done (pull or TTL)

    def _stage_streamed_kv(self, slot: _Slot):
        """Early-staged streamed handoff (docs/disagg_serving.md): stage
        the prompt's pages on the data plane AT ADMISSION and ship the
        descriptor immediately — chunks become pullable as prefill commits
        pages, so the decode worker's transfer overlaps our compute
        instead of serializing after it. Chunk granularity matches the
        prefill-chunk commit granularity; the last prompt page is held
        back until emit (its tail token's KV lands with the final chunk),
        which also guarantees the pull can only complete after the first
        token is on the wire. A transfer that dies mid-stream (reap /
        sever / abandoned puller) falls back to a fresh serial stage at
        emit — streamed handoff is strictly an optimization."""
        import jax.numpy as jnp

        c = self.model_config
        cfg = self.config
        n_prompt_pages = (len(slot.prompt) + cfg.page_size - 1) // cfg.page_size
        if n_prompt_pages <= 0:
            return

        async def extract(off: int, n: int, device: bool):
            # slot.pages is read LIVE (not snapshotted): _try_skip_ahead
            # may splice cached pages in mid-prefill — same contents,
            # different physical ids
            ids = np.array([p + 1 for p in slot.pages[off : off + n]], np.int32)
            self._bcast("extract", {"page_ids": ids})
            if device and not self._multihost and cfg.kv_quant == "none":
                return await self._run_on_device(
                    lambda: self._extract_pages(self.kv_k, self.kv_v, jnp.asarray(ids))
                )
            return await self._run_on_device(partial(self._dev_extract, ids))

        def on_done(ok: bool):
            if slot.kv_stream_tid is None:
                return  # engine-initiated abort (release/preempt/emit)
            slot.kv_stream_tid = None
            if slot.done:
                # prefill finished and the pull settled: pages release
                # here, exactly like the serial stage's on_done
                if not ok:
                    logger.warning(
                        "streamed kv pull for %s abandoned; releasing pages",
                        slot.request_id,
                    )
                self._release_slot(slot)
            elif not ok:
                # reaped/severed while prefill still runs: the emit path
                # stages a fresh serial transfer instead
                self.kv_streamed_fallbacks += 1

        wire_shape, wire_dtype = self._kv_wire_meta()
        desc = self.data_plane.stage(
            n_pages=n_prompt_pages,
            n_tokens=len(slot.prompt),
            page_size=cfg.page_size,
            page_shape=wire_shape,
            dtype=wire_dtype,
            kv_format=cfg.kv_quant,
            extract=extract,
            on_done=on_done,
            chunk_pages=max(cfg.max_prefill_chunk // cfg.page_size, 1),
            streamed=True,
            available_pages=min(
                len(slot.committed_hashes), n_prompt_pages - 1
            ),
        )
        slot.kv_stream_tid = desc.transfer_id
        slot.kv_stream_desc = desc.to_dict()
        self.kv_streamed_stages += 1
        # EARLY descriptor event (no token yet): the decode worker starts
        # pulling immediately, while we prefill
        out = LLMEngineOutput(
            kv_transfer_params={"pull": slot.kv_stream_desc}
        ).to_dict()
        slot.queue.put_nowait(Annotated(data=out).to_dict())

    def _advance_kv_stream(self, slot: _Slot):
        """Streamed handoff watermark: every committed prompt page is
        pullable, except the last prompt page which always waits for emit
        (_stage_streamed_kv invariant)."""
        if slot.kv_stream_tid is None or self.data_plane is None:
            return
        n_prompt_pages = (
            len(slot.prompt) + self.config.page_size - 1
        ) // self.config.page_size
        self.data_plane.advance_streamed(
            slot.kv_stream_tid,
            min(len(slot.committed_hashes), n_prompt_pages - 1),
        )

    def _finish_streamed_kv(self, slot: _Slot, first_token: int,
                            first_lp: Optional[float] = None,
                            first_top: Optional[dict] = None):
        """Prefill finished with a live streamed stage: publish the final
        watermark (the last — possibly partial — prompt page is now valid)
        and send the first token with the same descriptor. Pages stay
        pinned until the pull finishes (on_done), like the serial stage."""
        cfg = self.config
        n_prompt_pages = (len(slot.prompt) + cfg.page_size - 1) // cfg.page_size
        out = LLMEngineOutput(
            token_ids=[first_token],
            log_probs=[first_lp]
            if (slot.want_logprobs and first_lp is not None) else None,
            top_logprobs=[first_top] if first_top else None,
            finish_reason="remote_prefill_done",
            kv_transfer_params={"pull": slot.kv_stream_desc},
        ).to_dict()
        slot.queue.put_nowait(Annotated(data=out).to_dict())
        slot.queue.put_nowait(None)
        slot.done = True
        # watermark LAST: the moment it hits n_pages the pull can complete
        # and on_done releases the slot — done/queue state must be settled
        self.data_plane.advance_streamed(slot.kv_stream_tid, n_prompt_pages)
        # NOT released here: pages stay pinned until on_done (pull or TTL)

    def _stage_local_shard(self, tid: str, page_ids: np.ndarray, on_done):
        """Stage THIS host's KV shard of `page_ids` under transfer id `tid`
        on the local data plane (leader and followers run this — leader via
        _stage_kv_pull, followers via the stage_shard replay)."""
        import jax.numpy as jnp

        c = self.model_config
        cfg = self.config

        async def extract(off: int, n: int, device: bool):
            ids = page_ids[off : off + n]
            return await self._run_on_device(
                partial(self._extract_local_shard, ids)
            )

        return self.data_plane.stage(
            n_pages=int(len(page_ids)),
            n_tokens=0,
            page_size=cfg.page_size,
            page_shape=self.local_shard_page_shape(),
            dtype=str(jnp.zeros((), c.dtype).dtype),
            extract=extract,
            on_done=on_done,
            transfer_id=tid,
        )

    def _commit_blocks(self, slot: _Slot, upto_tokens: Optional[int] = None):
        """Bind filled prompt pages to their hashes -> prefix cache + events.

        `upto_tokens`: incremental commit after a confirmed prefill CHUNK
        (the fetch of its dispatch's first-token proves the device ran the
        program, so the pages hold real KV) — concurrent same-prefix
        requests start hitting these blocks before the whole prompt
        finishes, instead of redundantly recomputing a prefix another
        in-flight request already wrote."""
        hashes = slot.seq.block_hashes()
        n_known = len(slot.committed_hashes)
        limit = len(slot.kv_prompt)
        if upto_tokens is not None:
            limit = min(limit, upto_tokens)
        prompt_full_blocks = limit // self.config.page_size
        new_hashes = hashes[n_known:prompt_full_blocks]
        if new_hashes:
            pages = slot.pages[n_known : n_known + len(new_hashes)]
            token_blocks = [
                b.tokens for b in slot.seq.blocks[n_known : n_known + len(new_hashes)]
            ]
            parent = slot.committed_hashes[-1] if slot.committed_hashes else None
            self.allocator.commit_hashes(pages, new_hashes, token_blocks, parent)
            slot.committed_hashes.extend(new_hashes)
            self._advance_kv_stream(slot)
            if self.kvbm is not None:
                self.kvbm.offload_commit(
                    new_hashes, [p + 1 for p in pages], parent=parent
                )

    # -- decode ---------------------------------------------------------- #

    def _active_decode_indices(self) -> List[int]:
        out = []
        for i, slot in enumerate(self.slots):
            if slot is None:
                continue
            if (
                slot.prefill_pos >= len(slot.kv_prompt)
                and (slot.generated > 0 or slot.first_pending)
                and slot.resume_token is None
            ):
                out.append(i)
        return out

    def _grow_pages_for_block(self, active: List[int],
                              steps: Optional[int] = None) -> List[int]:
        """Ensure each active lane's pages cover `steps` decode steps
        (default: one fused block's max advance); preempt the newest
        sequence (or finish with 'length' as last resort) when the pool is
        exhausted. Returns the surviving active set."""
        cfg = self.config
        K = steps or cfg.block_advance
        for i in list(active):
            slot = self.slots[i]
            if slot is None:
                continue
            # clamp to the model-length bound: speculation past it writes to
            # the scratch page (decode_forward routes out-of-range positions
            # there), so no pages are needed beyond max_model_len
            last_pos = min(
                int(self.seq_lens[i]) - 1 + (K - 1), cfg.max_model_len - 1
            )
            needed_pages = last_pos // cfg.page_size + 1
            while len(slot.pages) < needed_pages:
                fresh = self.allocator.alloc_fresh(1)
                if fresh is not None:
                    slot.pages.extend(fresh)
                    self.page_tables[i, len(slot.pages) - 1] = fresh[0] + 1
                    if self._carry_valid:
                        # table-row-only patch: this lane's carry values on
                        # device are newer than host (blocks in flight)
                        self._dirty_tables.add(i)
                    continue
                if not self._preempt_one(exclude_idx=i):
                    # nothing left to preempt: finish with length
                    self._emit_finish(slot, "length")
                    self._release_slot(slot)
                    break
        return self._active_decode_indices()

    def _preempt_one(self, exclude_idx: int) -> bool:
        """Preempt the newest-admitted active sequence: commit its full
        blocks (so resume rides the prefix cache / KVBM), release pages,
        requeue. Reference: mocker scheduler watermark eviction
        (lib/llm/src/mocker/scheduler.rs:240)."""
        victims = [
            s
            for s in self.slots
            if s is not None and s.slot_idx != exclude_idx and s.generated > 0
        ]
        if not victims:
            return False
        victim = max(victims, key=lambda s: s.admit_seq)
        logger.info("preempting %s to reclaim pages", victim.request_id)
        self.num_preemptions += 1
        # resume state: recompute KV for everything except the pending token
        victim.resume_token = victim.last_token
        victim.kv_prompt = list(victim.seq.tokens[:-1])
        victim.prefill_pos = 0
        self._release_slot(victim)
        # what it has in flight is recomputed by the resume: take its rows
        # out of the queued entries, which would otherwise find the same
        # slot in the same lane again if it is re-admitted before their
        # fetch, and emit those tokens twice
        for e in self._inflight:
            if e["kind"] == "mixed":
                e["decode"] = [r for r in e["decode"] if r[2] is not victim]
            else:
                e["lanes"] = [r for r in e["lanes"] if r[1] is not victim]
        self._waiting.insert(0, victim)
        return True

    def _prefill_work_pending(self) -> bool:
        """True when prefill compute could actually be dispatched: a slot
        passing _dispatch_prefill's candidate filter (skip preloaded/
        onboard slots — their KV arrives by injection, not prefill), or an
        admittable waiter. An un-admittable waiter or an in-flight KV pull
        must NOT throttle decode."""
        if self._waiting and self._free_slots:
            return True
        return any(
            s is not None
            and s.prefill_pos < len(s.kv_prompt)
            and s.preloaded is None
            and s.onboard is None
            and not s.done
            for s in self.slots
        )

    def _host_ngram_draft(self, slot, d: int) -> List[int]:
        """Host-side n-gram draft for fused spec verify rows (mirrors the
        device draft in spec.py, but over the authoritative host token
        sequence — drafts only steer ACCEPTANCE rate, never correctness:
        every emitted token is a verified sample from the target model).
        Most-recent n-gram match wins; pads with the last token."""
        seq = slot.seq.tokens
        n = self.config.spec_ngram
        if n <= 0 or len(seq) < n:
            return [int(seq[-1])] * d
        gram = list(seq[len(seq) - n:])
        for start in range(len(seq) - n - 1, -1, -1):
            if list(seq[start:start + n]) == gram:
                follow = [int(t) for t in seq[start + n:start + n + d]]
                return follow + [int(seq[-1])] * (d - len(follow))
        return [int(seq[-1])] * d

    async def _dispatch_mixed(self) -> bool:
        """Unified mixed step (ROADMAP 2, "Ragged Paged Attention"): when
        there are BOTH runnable prefill chunks and active decode lanes,
        pack them into one flat ragged token buffer — prefill chunks as
        T>1 rows, decode lanes as T=1 rows with ctx = seq_len - 1 — and
        run ONE device call per layer stack instead of a prefill dispatch
        followed by a decode dispatch. Every decode lane advances one
        token; completed prompts sample their first token; both ride the
        same fetched [R] result. Guided rows carry a packed FSM mask
        operand, lora rows a per-row adapter index, and spec-eligible
        lanes pack 1+d one-token verify rows — the fused path is the
        default for blended traffic.

        A lean pack is an ordinary entry of the decode pipeline
        (_pack_pipes): the host knows every decode lane's position,
        length, table and sampling parameters at dispatch, so the pack
        queues behind whatever block or mixed step is in flight, its
        decode rows take token and penalty window from the device carry
        by lane, its samples go back into the carry, and it is fetched in
        dispatch order like a block (_inflight, kind "mixed"). A pack with
        a row that needs a HOST value — a guided, spec-verify or LoRA
        row, a preempted resume, the disagg prefill role's completion, or
        any pack while the carry is invalid — waits for the pipeline to
        drain (`_mixed_wait_drain` holds the split prefill meanwhile),
        packs host-authoritative lanes and is fetched in the step that
        dispatched it (_pending_prefill).

        Returns False (split path runs) whenever the fused step is
        inapplicable: mixed disabled, a multimodal candidate starved past
        its SLA (mm stays split-only), a pack that has to wait for the
        drain, the pipeline already holding its two entries, or the
        planner declines.

        Shapes are a closed family, compiled together at its first use
        (_prime_mixed_family): at most four flat-token buckets
        (bucketing.mixed_token_buckets), ONE fixed row bucket
        (self._mixed_row_bucket — the row axis only sizes scalar
        operands), and one table width under the Pallas ragged kernel
        (pow2 rungs on the XLA reference path). The flat buffer is
        compact, rows back to back: a bucket counts real tokens, and the
        q-tile layout the Pallas ragged kernel needs is the attention
        call's own, for q alone and for the rows of more than one token
        (ops/paged_attention.py:ragged_attention)."""
        cfg = self.config
        with self._rec.span("pack", more=True):
            self._mixed_wait_drain = False
            if not self._mixed_enabled:
                return False
            active = self._active_decode_indices()
            if not active:
                return False
            # spec fusion: every spec-eligible decode lane packs 1 + d
            # one-token verify rows (current token + d host n-gram drafts) —
            # the verify step IS a ragged mixed batch. Guided lanes stay
            # single-row (the next mask depends host-side on this token).
            d = cfg.spec_draft_len if cfg.spec_mode else 0
            n_spec_rows = sum(
                d for i in active if self.slots[i].guided_fsm is None
            ) if d else 0
            cands = []
            mm_starved = False
            for s in self.slots:
                if s is None or s.prefill_pos >= len(s.kv_prompt):  # dynolint: disable=race-await-atomicity -- single writer per live slot (same shape as _dispatch_prefill); pull-path slots filtered below
                    continue
                if s.preloaded is not None or s.onboard is not None:
                    continue
                if s.done or s.context.is_stopped():
                    self._emit_finish(s, "cancelled")
                    self._release_slot(s)
                    continue
                if s.mm is not None:
                    # multimodal stays split-only (embedding-splice operand):
                    # exclude ONLY this slot — plain + fused kinds still fuse
                    # this step — and age it toward the starvation guard
                    s.sched_skips += 1
                    if s.sched_skips >= self.scheduler.sla.starve_dispatches:
                        mm_starved = True
                    continue
                self._try_skip_ahead(s)
                cands.append(s)
            if mm_starved:
                # a starved mm candidate must win the next batch outright:
                # yield the whole step to the split path, whose
                # pick_batch_kind starvation override serves it
                return False
            if not cands:
                return False
            cands = self.scheduler.order(cands)
            plan = self.scheduler.plan_mixed(
                cands, n_decode=len(active), n_spec_rows=n_spec_rows,
            )
            if plan is None:
                return False  # nothing fuses (e.g. decode lanes fill the
                # budget) — split path runs at full rate, no hold
            pipes = self._pack_pipes(plan.chosen, active)
            if pipes and self._carry_valid:
                if len(self._inflight) >= 2:
                    # never more than one entry queued behind the running one:
                    # an arrival admitted at the next wake gets the next entry
                    return False
            elif self._inflight or self._pending_prefill:
                # the entries in flight own these lanes' device carry, and this
                # pack needs host-authoritative lanes (or the carry is invalid
                # and is uploaded from them). Signal the step loop to
                # HOLD the split prefill for one step while the pipeline
                # drains (the split dispatch would queue behind the in-flight
                # block on the device stream anyway) — the next step fuses.
                # Only worth it when a fused step is actually plannable,
                # hence AFTER the plan check.
                self._mixed_wait_drain = True
                # the held step grants nothing: every candidate ages, same as
                # a plan_prefill defer (the skipped _dispatch_prefill would
                # otherwise never age them on hold steps)
                for s in cands:
                    s.sched_skips += 1
                return False
            def pack_shape(chunks, lanes):
                """(variant, ctx_pages) of a pack of these prefill chunks and
                decode lanes: the lean program or the variant with the mask /
                adapter operands (a guided or lora row), and the table rung
                that holds the longest context."""
                slots = [s for s, _ in chunks] + [self.slots[i] for i in lanes]
                pages = 1
                for s, ch in chunks:
                    pages = max(pages, -(-(s.prefill_pos + ch) // cfg.page_size))
                for i in lanes:
                    extra = d if self.slots[i].guided_fsm is None else 0
                    pages = max(
                        pages,
                        (int(self.seq_lens[i]) - 1 + extra) // cfg.page_size + 1,
                    )
                return (
                    any(s.guided_fsm is not None or s.lora_idx for s in slots),
                    _bucket_for(pages, self._mixed_table_rungs),
                )

            shape = pack_shape(list(zip(plan.chosen, plan.chunks)), active)
        if shape not in self._mixed_primed:
            # first use: compile the family, then plan again — the await
            # let arrivals and cancellations in, and nothing of this plan
            # is committed yet
            await self._prime_mixed_family(*shape)
            return await self._dispatch_mixed()
        self._rec.entry_kind = "mixed"
        with self._rec.span("pack"):
            # one decode step of page headroom (1 + d under spec: draft rows
            # write KV at speculative positions); growth can preempt —
            # re-filter both the decode set and the chosen prefill slots
            active = self._grow_pages_for_block(active, steps=1 + d)
            if not active:
                return False
            chosen = [
                (s, ch) for s, ch in zip(plan.chosen, plan.chunks)
                if s.slot_idx >= 0 and self.slots[s.slot_idx] is s
            ]
            if not chosen:
                return False
            if pipes:
                # a lane the host already knows will be done before this step
                # runs (what it has generated and what is in flight reaches its
                # max_tokens) is left out: its row would be sampled for nothing
                flying = self._tokens_in_flight()
                active = [
                    i for i in active
                    if self.slots[i].generated + flying.get(id(self.slots[i]), 0)
                    < self.slots[i].max_tokens
                ]
            # the dispatch is committed from here on — account it (plan_mixed
            # itself is pure, so an abandoned plan never skews the sched_*
            # grant counters the split path's plan_prefill also feeds)
            self.scheduler.commit_mixed(plan, chosen)
            # candidates the plan passed over age toward the starvation guard,
            # exactly as on the split path — fused steps must not exempt a
            # steady tight-deadline stream from starve_dispatches promotion
            granted_slots = {id(s) for s, _ in chosen}
            for s in cands:
                if id(s) not in granted_slots:
                    s.sched_skips += 1

            # recompute the decode row count against the SURVIVING active set
            # (page growth can preempt lanes out from under the plan)
            spec_lanes = {
                i for i in active
                if cfg.spec_mode and self.slots[i].guided_fsm is None
            }
            n_rows_decode = len(active) + d * len(spec_lanes)
            total = sum(ch for _, ch in chosen) + n_rows_decode
            # pure-plain and pure-spec packs keep the LEAN program —
            # byte-identical operands to the split path; any guided or lora
            # row takes the variant (all-ones mask rows and adapter index 0
            # are exact no-ops for the rows beside it). The shape is the
            # plan's, primed above: what the filters left may fit a smaller
            # member of the family that nothing has compiled yet
            variant, ctx_pages = shape
            # total <= the largest bucket by construction: it is plan_mixed's
            # budget, mixed_max_tokens
            payload = self._blank_mixed_pack(total, ctx_pages, variant)
            if pipes:
                # row -> lane, for the read of the carry and for the write
                # back into it; the "mixed" broadcast carries both, so that
                # followers replay the same programs
                for key in ("row_lane", "w_lane"):
                    payload[key] = np.full_like(payload["row_lens"], -1)
                payload["w_pos"] = np.zeros_like(payload["row_lens"])
            N_pad = len(payload["toks"])
            toks, positions, row_ids = (
                payload["toks"], payload["positions"], payload["row_ids"])
            tables, row_starts, row_lens = (
                payload["tables"], payload["row_starts"], payload["row_lens"])
            ctx_lens, last_flat = payload["ctx_lens"], payload["last_flat"]
            temps, top_ks, top_ps, seeds = (
                payload["temps"], payload["top_ks"], payload["top_ps"],
                payload["seeds"])
            pens, pen_rows = payload["pens"], payload["pen_rows"]
            mask_packed = payload.get("mask")
            lora_rows = payload.get("lora_idx")
            row_lanes = payload.get("lanes")  # a stateful family's

            off = 0
            row = 0
            work = Work(self._index_topk)  # what the step asks for (llama.step_work)
            meta = []  # prefill rows: (slot, chunk, row)
            decode_rows = []  # (row, lane_idx, slot)
            spec_rows = []  # (first_row, lane_idx, slot, draft) — 1+d rows each
            for s, chunk in chosen:
                start = s.prefill_pos
                row_starts[row] = off
                row_lens[row] = chunk
                ctx_lens[row] = start
                toks[off : off + chunk] = s.kv_prompt[start : start + chunk]
                positions[off : off + chunk] = np.arange(start, start + chunk)
                row_ids[off : off + chunk] = row
                tables[row, :ctx_pages] = self.page_tables[s.slot_idx][:ctx_pages]
                last_flat[row] = off + chunk - 1
                temps[row] = s.temperature
                top_ks[row] = s.top_k
                top_ps[row] = s.top_p
                seeds[row] = s.sample_seed
                pens[row] = (s.presence_penalty, s.frequency_penalty,
                             s.repetition_penalty)
                pen_rows[row] = self.recent[s.slot_idx]
                if s.guided_fsm is not None:
                    mask_packed[row] = np.packbits(self._guided_lane_mask(
                        s.guided_fsm, s.guided_state
                    ))
                    self.mixed_rows_guided += 1
                elif s.lora_idx:
                    self.mixed_rows_lora += 1
                else:
                    self.mixed_rows_plain += 1
                if lora_rows is not None:
                    lora_rows[row] = s.lora_idx
                if row_lanes is not None:
                    row_lanes[row] = s.slot_idx
                s.sched_skips = 0
                meta.append((s, chunk, row))
                work.chunk(start, chunk, start + chunk >= len(s.kv_prompt))
                if pipes and start + chunk >= len(s.kv_prompt):
                    # the prompt completes in this step: its first token goes
                    # into the lane's carry at the prompt's length
                    payload["w_lane"][row] = s.slot_idx
                    payload["w_pos"][row] = len(s.kv_prompt)
                off += chunk
                row += 1
            for i in active:
                s = self.slots[i]
                L = int(self.seq_lens[i])
                spec_lane = i in spec_lanes
                work.decode(L)  # sure of one token (a draft is a guess)
                draft = self._host_ngram_draft(s, d) if (spec_lane and d) else []
                row_toks = [int(self.tokens[i])] + draft
                first_row = row
                for j, tk in enumerate(row_toks):
                    # row j carries one token at position L-1+j with ctx
                    # L-1+j: it attends the lane's committed KV plus rows
                    # 0..j-1 of THIS pack (their KV is written before
                    # attention each layer), so row j's sample is exactly the
                    # plain seeded decode draw at that position — the fused
                    # verify's parity lever
                    row_starts[row] = off
                    row_lens[row] = 1
                    ctx_lens[row] = L - 1 + j
                    toks[off] = tk  # piped: the device's, from the carry
                    positions[off] = L - 1 + j
                    row_ids[off] = row
                    tables[row, :ctx_pages] = self.page_tables[i][:ctx_pages]
                    last_flat[row] = off
                    temps[row] = self.temps[i]
                    top_ks[row] = self.top_ks[i]
                    top_ps[row] = self.top_ps[i]
                    seeds[row] = self.seeds[i]
                    if lora_rows is not None:
                        lora_rows[row] = s.lora_idx
                    if row_lanes is not None:
                        row_lanes[row] = i
                    if not spec_lane:
                        pens[row] = (self.presence[i], self.frequency[i],
                                     self.repetition[i])
                    if pipes:
                        # token and penalty window are the carry's, gathered
                        # by lane on the device (carry_read); the sample goes
                        # back into the lane at position L (carry_write)
                        payload["row_lane"][row] = payload["w_lane"][row] = i
                        payload["w_pos"][row] = L
                    elif not spec_lane:
                        # drained: the device pen ring (decode carry) is not
                        # host-visible; rebuild this lane's window from the
                        # authoritative token sequence (ring-indexed by
                        # absolute position, so the patch after the fetch
                        # stays consistent with it)
                        self._fill_recent(i, s)
                        pen_rows[row] = self.recent[i]
                        if s.guided_fsm is not None:
                            mask_packed[row] = np.packbits(
                                self._guided_lane_mask(
                                    s.guided_fsm, s.guided_state
                                )
                            )
                    # spec rows keep default pens: penalties/logprobs are
                    # rejected under spec_mode at admission
                    off += 1
                    row += 1
                if spec_lane:
                    spec_rows.append((first_row, i, s, draft))
                    self.mixed_rows_spec += len(row_toks)
                else:
                    decode_rows.append((first_row, i, s))
                    if s.guided_fsm is not None:
                        self.mixed_rows_guided += 1
                    elif s.lora_idx:
                        self.mixed_rows_lora += 1
                    else:
                        self.mixed_rows_plain += 1

            completions = []
            progressed = []
            for s, chunk, row_i in meta:
                s.prefill_pos += chunk
                progressed.append((s, s.prefill_pos))
                if s.prefill_pos >= len(s.kv_prompt):
                    completions.append((s, row_i))
                    if pipes:
                        # what the host knows of the new decode lane at
                        # dispatch; the patch below puts it on the device, the
                        # step's write-back adds the token, and the fetch does
                        # what needs the value (_finish_prefill)
                        s.first_pending = True
                        self.seq_lens[s.slot_idx] = len(s.kv_prompt) + 1
                        self._fill_recent(s.slot_idx, s)
                        self._mark_lane_dirty(s.slot_idx)
        after_drain = not self._carry_valid
        if pipes:
            await self._sync_carry(self._active_decode_indices())
        with self._rec.span("pack", more=True):
            # advanced at dispatch, before the device call suspends this
            # task: exact for a plain decode row, and what the next entry
            # packs from. spec lanes are NOT advanced here: acceptance is
            # data-dependent (resolved from the fetched [R] tokens), and
            # their packs drain this same step, so seq_lens stays
            # authoritative for the next dispatch.
            for row_i, i, s in decode_rows:
                self.seq_lens[i] += 1
            self._bcast("mixed", payload)
            entry = {
                "kind": "mixed", "done": completions,
                "progressed": progressed, "decode": decode_rows,
                "spec": spec_rows,
            }
            call = partial(self._dev_mixed, payload)
            if self._stateful:
                self._note_first_chunks(ctx_lens[r] for _, _, r in meta)
                # flat slots of each asking row, for the fetch
                entry["want_routed"] = [
                    (s, int(row_starts[r]), int(row_lens[r]))
                    for s, r in (
                        *((s, r) for s, _, r in meta),
                        *((s, r) for r, _, s in decode_rows),
                    ) if s.want_routed
                ]
                call = self._routed_behind(call, entry)
            self._rec.dispatched(entry, "mixed", work.of(self._step_work),
                                 program=("mixed", N_pad, *shape))
        entry["first"] = await self._run_on_device(
            call, tag="mixed", shape=(N_pad, row),
        )
        self._rec.launched(entry)
        if pipes:
            # an entry of the pipeline: fetched in dispatch order, with
            # its successor queued behind it
            entry["after_drain"] = after_drain
            self._inflight.append(entry)
        else:
            # rides the prefill-pending fetch (drained THIS step, so no
            # decode block can dispatch against the stale device carry in
            # between)
            self._pending_prefill.append(entry)
        real = sum(ch for _, ch, _ in meta) + n_rows_decode
        self.mixed_steps += 1
        self.mixed_padded_tokens += N_pad
        self.mixed_real_tokens += real
        tile = self._ragged_tile
        if tile > 1:
            chunks = [ch for _, ch, _ in meta]
            self.mixed_attn_tiles += ragged_tiles(
                N_pad, len(row_lens), tile, cfg.max_prefill_batch
            )
            self.mixed_attn_tiles_real += sum(
                -(-ch // tile) for ch in chunks if ch > 1
            )
            self.mixed_rows_decode_kernel += n_rows_decode + chunks.count(1)
        if self._steps_lanes:
            going_on = sum(
                1 for _, ch, r in meta if ch == 1 and ctx_lens[r] > 0)
            self.state_rows_in_place += n_rows_decode + going_on
            self.state_rows_gathered += len(meta) - going_on
        if self._absorbed_row_limit:
            chunks = [ch for _, ch, _ in meta if ch > 1]
            short = sum(ch for ch in chunks if ch <= self._absorbed_row_limit)
            self.mla_rows_absorbed_tokens += short
            self.mla_rows_expanded_tokens += sum(chunks) - short
        self._count_expert_rows(N_pad, real)
        self._step_counter += 1
        return True

    def _pack_pipes(self, chosen, lanes) -> bool:
        """Whether a mixed pack of these prefill slots and decode lanes
        joins the decode pipeline, decided from what the pack holds and
        nothing else: every row's token is either the host's (a prompt's)
        or the device carry's (a plain decode lane's). A guided row (the
        FSM's next mask), a spec verify row (the host's n-gram draft), a
        LoRA row (the variant program), a preempted resume (the discarded
        sample) and the disagg prefill role's completion (return_kv) need
        host-authoritative lanes. (An invalid carry is a state of the
        engine, not of the pack: _dispatch_mixed drains for it, and the
        pack then pipes on the carry uploaded from the host.)"""
        if self.config.spec_mode:
            return False
        return not any(
            s.guided_fsm is not None or s.lora_idx or s.return_kv
            or s.resume_token is not None
            for s in [*chosen, *(self.slots[i] for i in lanes)]
        )

    def _tokens_in_flight(self) -> Dict[int, int]:
        """Tokens dispatched and not yet fetched, by id() of their slot."""
        out: Dict[int, int] = {}
        for e in self._inflight:
            if e["kind"] == "mixed":
                slots = [s for _, _, s in e["decode"]]
                slots += [s for s, _ in e["done"]]
                n = 1
            else:
                slots = [s for _, s in e["lanes"]]
                n = e["adv"]
            for s in slots:
                out[id(s)] = out.get(id(s), 0) + n
        return out

    def _blank_mixed_pack(self, tokens: int, pages: int,
                          variant: bool) -> dict:
        """The operands of one mixed step that holds `tokens` real tokens
        and contexts of `pages` pages, with no row packed yet, keyed as
        the "mixed" broadcast carries them: every slot padding (position
        at the scratch tail, owned by the last row), every row empty
        (starting past the buffer), tables of SCRATCH_PAGE, sampling and
        penalties off. `variant` adds the all-ones FSM mask and, where
        adapters are registered, the per-row adapter indices (0 = base).
        The one place that mints a mixed step's shapes: a member of the
        family, whatever it is asked for."""
        cfg = self.config
        N_pad = _bucket_for(tokens, self._mixed_token_buckets)
        R_pad = self._mixed_row_bucket
        P = _bucket_for(pages, self._mixed_table_rungs) + 1
        pens = np.zeros((R_pad, 3), np.float32)
        pens[:, 2] = 1.0  # repetition off
        pack = {
            "toks": np.zeros((N_pad,), np.int32),
            # pads write to the scratch tail
            "positions": np.full((N_pad,), P * cfg.page_size - 1, np.int32),
            "row_ids": np.full((N_pad,), R_pad - 1, np.int32),
            "tables": np.full((R_pad, P), SCRATCH_PAGE, np.int32),
            "row_starts": np.full((R_pad,), N_pad, np.int32),
            "row_lens": np.zeros((R_pad,), np.int32),
            "ctx_lens": np.zeros((R_pad,), np.int32),
            "last_flat": np.zeros((R_pad,), np.int32),
            "temps": np.zeros((R_pad,), np.float32),
            "top_ks": np.zeros((R_pad,), np.int32),
            "top_ps": np.ones((R_pad,), np.float32),
            "seeds": np.zeros((R_pad,), np.uint32),
            "pens": pens,
            "pen_rows": np.full((R_pad, cfg.penalty_window), -1, np.int32),
        }
        if self._stateful:
            # the lane of each row: the scratch slot's until one is packed
            pack["lanes"] = self.kv_k.row_lanes()
        if variant:
            V = self.model_config.vocab_size
            pack["mask"] = np.full((R_pad, (V + 7) // 8), 0xFF, np.uint8)
            if self._lora is not None:
                pack["lora_idx"] = np.zeros((R_pad,), np.int32)
        return pack

    async def _prime_mixed_family(self, variant: bool, ctx_pages: int):
        """Compile the lean (or variant) mixed_step programs of one table
        width together, every token bucket, the first time one is needed:
        a program first met under traffic is seconds of stall inside a
        request's time to first token, and which ones a run meets depends
        on its traffic. Under the Pallas ragged kernel there is one width,
        so this is the whole family and no later pack meets a shape the
        jit cache lacks; the XLA reference keeps its rungs and is primed a
        rung at a time (its programs cost with their width, and all of
        them at once would hold a tp=4 worker for minutes). The members
        are compiled side by side first (_compile_mixed_side_by_side: four
        buckets cost a start what one does). Each member then
        runs once on a pack whose one row is one token at position 0 of
        the scratch page (an all-padding pack would hand the grouped
        expert matmul an empty grid): the donated pool is written only in
        its scratch page. Sent through _bcast like any pack, so followers
        replay it; untimed, so the cost model learns no compile time; on a
        copy of `_rng` (_dev_mixed)."""
        self._mixed_primed.add((variant, ctx_pages))
        packs = []
        for N_pad in self._mixed_token_buckets:
            pack = self._blank_mixed_pack(N_pad, ctx_pages, variant)
            pack["positions"][0] = 0
            pack["row_ids"][0] = 0
            pack["row_starts"][0] = 0
            pack["row_lens"][0] = 1
            pack["prime"] = np.ones((1,), np.int32)
            packs.append(pack)
        t0 = time.monotonic()
        await self._run_on_device(
            partial(self._compile_mixed_side_by_side, packs)
        )
        t1 = time.monotonic()
        for pack in packs:
            self._bcast("mixed", pack)
            # (a stateful family: the one row is the scratch slot's)
            await self._run_on_device(
                partial(self._dev_mixed, pack), tag="mixed_prime",
            )
        logger.info(
            "mixed family primed: %d %s programs of %d pages, compiled in "
            "%.1f s, first runs %.1f s", len(packs),
            "variant" if variant else "lean", ctx_pages, t1 - t0,
            time.monotonic() - t1,
        )

    def _count_expert_rows(self, T: int, real: int, steps: int = 1):
        """Account one dispatch of `steps` forward passes over T token
        slots, `real` of them real, to the expert-row counters (routed
        families only)."""
        if not self._counts_expert_rows:
            return
        routed, computed = self._model.expert_rows(
            self.model_config, T, real, self._quantized,
        )
        self.expert_rows_routed += routed * steps
        self.expert_rows_computed += computed * steps

    async def _sync_carry(self, active: List[int]):
        """Bring the device carry up to date before an entry that reads it
        (a decode block, a piped mixed step). `active`: the lanes that are
        decode-active once that entry has run; every other lane goes
        blank. The DEVICE decode table keeps SCRATCH rows for every lane
        that is not decode-active: inside a fused block, inactive lanes'
        seq_lens still advance (lax.scan carries the whole batch), so
        their KV writes would otherwise land at positions 0..K-1 of
        whatever the host table row points at — including a PREFILLING
        slot's pages (possibly shared prefix-cache pages). A scratch row
        routes all such writes to the reserved scratch page by
        construction.

        An invalid carry (start-up, after a failed step) is uploaded whole
        from the host's arrays, which the caller has made authoritative by
        draining the pipeline. A valid one is patched per lane: just the
        changed lanes — no pipeline drain, no full re-upload. Untouched
        lanes keep their (newer) device carry; table_mask covers lanes
        whose page table grew but whose carry must be preserved."""
        B = self.config.max_num_seqs
        if not self._carry_valid:
            # TAKE the dirt before building the upload: the dispatch below
            # suspends, and a background KV-pull activation landing during
            # that await marks fresh lanes dirty — clearing after the await
            # would erase their mark and leave stale lane state on device.
            # Taken synchronously with the array snapshot, new dirt simply
            # rides the next step's patch.
            with self._rec.span("pack", more=True):
                self._dirty_lanes.clear()
                self._dirty_tables.clear()
                mask = np.zeros((B,), bool)
                for i in active:
                    mask[i] = True
                positions = np.where(mask, self.seq_lens - 1, 0).astype(np.int32)
                seq_lens_step = np.where(mask, self.seq_lens, 0).astype(np.int32)
                tokens = np.where(mask, self.tokens, 0).astype(np.int32)
                tables = np.where(
                    mask[:, None], self.page_tables, SCRATCH_PAGE
                ).astype(np.int32)
                hist = (
                    np.where(mask[:, None], self.hist, 0).astype(np.int32)
                    if self.hist is not None else None
                )
                pens = np.stack(
                    [self.presence, self.frequency, self.repetition], axis=1
                )
                payload = {
                    "tokens": tokens, "positions": positions,
                    "seq_lens": seq_lens_step, "page_tables": tables,
                    "temps": self.temps, "top_ks": self.top_ks,
                    "top_ps": self.top_ps, "seeds": self.seeds,
                    "pens": pens, "recent": self.recent,
                }
                if hist is not None:
                    payload["hist"] = hist
                self._bcast("reset", payload)
            await self._run_on_device(
                partial(
                    self._dev_reset,
                    tokens, positions, seq_lens_step,
                    tables, self.temps.copy(),
                    self.top_ks.copy(), self.top_ps.copy(),
                    self.seeds.copy(), pens, self.recent.copy(), hist,
                ),
                tag="reset",
            )
            self._carry_valid = True
            return
        if not (self._dirty_lanes or self._dirty_tables):
            return
        # TAKE the dirty sets atomically with the host-array snapshot (same
        # reasoning as above: dirt added during the dispatch await must
        # survive into the next patch, not be cleared with this one)
        with self._rec.span("pack", more=True):
            dirty_lanes, dirty_tables = self._dirty_lanes, self._dirty_tables
            self._dirty_lanes, self._dirty_tables = set(), set()
            lane_mask = np.zeros((B,), bool)
            for i in dirty_lanes:
                lane_mask[i] = True
            table_mask = lane_mask.copy()
            for i in dirty_tables:
                table_mask[i] = True
            active_mask = np.zeros((B,), bool)
            for i in active:
                active_mask[i] = True
            n_tokens = np.where(active_mask, self.tokens, 0).astype(np.int32)
            n_positions = np.where(active_mask, self.seq_lens - 1, 0).astype(np.int32)
            n_seq_lens = np.where(active_mask, self.seq_lens, 0).astype(np.int32)
            n_tables = np.where(
                active_mask[:, None], self.page_tables, SCRATCH_PAGE
            ).astype(np.int32)
            hist = self.hist.astype(np.int32) if self.hist is not None else None
            pens = np.stack(
                [self.presence, self.frequency, self.repetition], axis=1
            )
            payload = {
                "lane_mask": lane_mask, "table_mask": table_mask,
                "tokens": n_tokens, "positions": n_positions,
                "seq_lens": n_seq_lens, "page_tables": n_tables,
                "temps": self.temps, "top_ks": self.top_ks,
                "top_ps": self.top_ps, "seeds": self.seeds,
                "pens": pens, "recent": self.recent,
            }
            if hist is not None:
                payload["hist"] = hist
            self._bcast("patch", payload)
        await self._run_on_device(
            partial(
                self._dev_patch, lane_mask, table_mask,
                n_tokens, n_positions, n_seq_lens,
                n_tables, self.temps.copy(),
                self.top_ks.copy(), self.top_ps.copy(),
                self.seeds.copy(), pens, self.recent.copy(), hist,
            ),
            tag="patch",
        )

    async def _dispatch_decode(self, chained: bool = False) -> bool:
        """Queue one K-step decode block from the device carry, behind
        whatever entry (block or piped mixed step) is in flight. `chained`:
        the step loop queues this block right behind a mixed step it has
        just dispatched with nothing ahead of it (_step_once)."""
        cfg = self.config
        # prefill-priority depth cap: with prefill work that does NOT join
        # the pipeline (a pack that needs host-authoritative lanes and is
        # waiting for the drain, the split path, a planner refusal), keep
        # only ONE speculative block in flight — that prefill queues
        # behind every in-flight block on the device stream, so depth-2
        # doubles its queueing delay (TTFT) to buy decode overlap it
        # regains once the queue drains. A lean pack never gets here with
        # work left: _dispatch_mixed queued it as the pipeline's next
        # entry. Spec-decode blocks advance lanes by a DATA-DEPENDENT
        # amount, so host bookkeeping must be corrected from each block's
        # fetch before the next dispatches: depth stays 1 (the verify
        # pass amortizes weight streams instead).
        # guided lanes: the next step's mask depends on the token the
        # PREVIOUS step emitted, so while any guided slot is decode-active
        # the pipeline depth is 1 and every block must be fetched+processed
        # (FSM advanced) before the next dispatch.
        with self._rec.span("pack", more=True):
            depth = 1 if (
                cfg.spec_mode or self._guided_decoding()
                or (not chained and self._prefill_work_pending())
            ) else 2
            if len(self._inflight) >= depth:
                return False
            if not self._carry_valid and self._inflight:
                return False  # drain in-flight blocks before a state reset
            active = self._active_decode_indices()
            if not active:
                return False
            active = self._grow_pages_for_block(active)
            if not active:
                return False
            if not self._carry_valid and self._inflight:
                # growth/preemption invalidated the carry mid-pipeline: drain
                # the in-flight block first (its results update host state),
                # THEN a fresh upload is consistent
                return False

        B = cfg.max_num_seqs
        K = cfg.decode_block_steps
        self._rec.entry_kind = "block"
        await self._sync_carry(active)

        with self._rec.span("pack"):
            guided_lanes = [
                i for i in active if self.slots[i].guided_fsm is not None
            ]
            if guided_lanes:
                # single masked step: guided rows from each lane's FSM
                # state, unguided rows admit everything. Bitpacked: [B, V/8]
                # uint8 host→device instead of a [B, V] bool (the per-step
                # transfer would otherwise dominate guided ITL).
                V = self.model_config.vocab_size
                packed = np.full((B, (V + 7) // 8), 0xFF, np.uint8)
                for i in guided_lanes:
                    s = self.slots[i]
                    packed[i] = np.packbits(
                        self._guided_lane_mask(s.guided_fsm, s.guided_state)
                    )
                lora_idx = (
                    self.lora_idx.copy()
                    if any(self.slots[i].lora_idx for i in active) else None
                )
                payload = {"mask": packed}
                if lora_idx is not None:
                    payload["lora_idx"] = lora_idx
                self._bcast("block_guided", payload)
                call = partial(self._dev_block_guided, packed, lora_idx)
                tag, shape, adv, kind = "block_guided", (1, B), 1, "block"
            elif any(self.slots[i].lora_idx for i in active):
                idx = self.lora_idx.copy()
                self._bcast("block_lora", {"idx": idx})
                call = partial(self._dev_block_lora, idx)
                # decode_block_lora always advances K steps — NOT
                # cfg.block_advance, which under a spec engine is the spec
                # program's worst-case spec_rounds*(1+d) bound
                tag, shape, adv, kind = "block_lora", (K, B), K, "block"
            else:
                self._bcast("block", {})
                # (partial: the compile registry's reachability check
                # follows a method named in a call)
                call = partial(self._dev_block)
                # only this branch runs the spec program under spec_mode;
                # guided/lora blocks above drain through _process_block
                tag, shape, adv = "block", (K, B), cfg.block_advance
                kind = "spec" if cfg.spec_mode else "block"
            entry = {
                "lanes": [(i, self.slots[i]) for i in active],
                "kind": kind, "adv": adv,
            }
            if self._stateful and any(
                    self.slots[i].want_routed for i in active):
                # each asking lane's first input position in this block
                entry["want_routed"] = {
                    i: int(self.seq_lens[i]) - 1 for i in active
                    if self.slots[i].want_routed
                }
                call = self._grab_ring(call, entry)
            # a spec round is one pass that is sure of one token a lane
            self._rec.dispatched(entry, "block", self._block_work(
                active, cfg.spec_rounds if kind == "spec" else adv
            ), program=(tag, adv))
        entry["toks"] = await self._run_on_device(call, tag=tag, shape=shape)
        self._rec.launched(entry)
        with self._rec.span("pack", more=True):
            self._last_decode_shape = (B * adv, len(active) * adv)
            if kind == "spec":
                # a round verifies 1 + d tokens a lane in one batched pass
                per = 1 + cfg.spec_draft_len
                self._count_expert_rows(
                    B * per, len(active) * per, cfg.spec_rounds
                )
                # spec blocks advance lanes by a data-dependent amount:
                # record the pre-dispatch seq_lens so the fetch can correct
                # the worst-case advance below to the device-true values
                entry["seq_before"] = {
                    i: int(self.seq_lens[i]) for i in active
                }
            else:
                self._count_expert_rows(B, len(active), adv)
            self._inflight.append(entry)
            # advance host bookkeeping by the block's max advance for the
            # NEXT block's page growth (exact for plain decode; an upper
            # bound under spec, corrected at fetch)
            for i in active:
                self.seq_lens[i] += adv
            self._step_counter += 1
        return True

    def _guided_decoding(self) -> bool:
        """Whether a guided slot is decode-active: the next step's mask
        then hangs on the token the step before it emitted."""
        return any(
            s is not None and s.guided_fsm is not None
            and s.prefill_pos >= len(s.kv_prompt) and s.generated > 0
            for s in self.slots
        )

    def _block_work(self, active: List[int], steps: int):
        """(useful operations, least bytes) of a block of `steps` forward
        passes over these lanes: a lane counts the passes that its
        max_tokens still asks for after what is in flight (the rest are
        sampled for nothing), each a token deeper into its context, and
        the block the passes that some lane still asks for."""
        flying = self._tokens_in_flight()
        n = np.array([
            min(steps, max(s.max_tokens - s.generated - flying.get(id(s), 0), 0))
            for s in (self.slots[i] for i in active)
        ])
        seq = self.seq_lens[active]
        context = n * seq + n * (n - 1) // 2
        more = {}
        if self._index_topk:
            # pass j of a lane attends, and reads, min(context + j, the most
            # a token selects) positions
            m = np.clip(self._index_topk - seq, 0, n)
            picked = int((m * seq + m * (m - 1) // 2
                          + (n - m) * self._index_topk).sum())
            more = {"attended": picked, "kv_selected": picked}
        return self._step_work(int(n.sum()), int(context.sum()), int(n.max()),
                               **more)

    async def _fetch_and_process(self, fetch_block: bool) -> bool:
        """One RTT: fetch pending prefill first-tokens + the oldest entry of
        the pipeline (a decode block or a piped mixed step) together, then
        run host bookkeeping/emission. Entries leave in the order they
        were dispatched. Where the oldest entry's fetch was asked for ahead
        of the dispatch (_await_successor), that one is taken up first, the
        entry's alone, and what the step dispatched beside the pipeline
        rides a fetch of its own behind it."""
        early, self._early_fetch = self._early_fetch, None
        if early is not None:
            await self._process_fetched(
                [], self._inflight[0], early, waited=True)
            fetch_block = False
        want = self._inflight[0] if (fetch_block and self._inflight) else None
        prefills = self._pending_prefill
        self._pending_prefill = []
        if want is None and not prefills:
            return early is not None
        await self._process_fetched(
            prefills, want, self._fetch(self._fetch_tree(prefills, want)))
        return True

    @staticmethod
    def _fetch_tree(prefills: List[dict], want: Optional[dict]):
        """What one fetch reads of these entries: the prefills' first
        tokens, the pipeline entry's tokens, and a stateful family's chosen
        experts, where a request asked."""
        mixed = want is not None and want["kind"] == "mixed"
        entries = prefills if want is None else [*prefills, want]
        return (
            [p["first"] for p in prefills],
            None if want is None else want["first" if mixed else "toks"],
            [e.pop("routed", None) for e in entries],
        )

    async def _process_fetched(self, prefills: List[dict],
                               want: Optional[dict], fetch,
                               waited: bool = False):
        """Await `fetch` (of _fetch_tree(prefills, want)) and do the host's
        part for what it brought. `waited`: the fetch was asked for before
        the successor was queued (Recorder.fetched counts a late one)."""
        mixed = want is not None and want["kind"] == "mixed"
        if mixed and len(self._inflight) >= 2 and not want["after_drain"]:
            # dispatched without a drain before it, and its successor was
            # queued before this fetch: no host round trip on either side
            self.mixed_steps_piped += 1
        entries = prefills if want is None else [*prefills, want]
        self._rec.entry_kind = (want or prefills[0])["step_kind"]
        (firsts_np, toks_np, routed_np), t_ready = await fetch
        for e, routed in zip(entries, routed_np):
            e["routed"] = routed
        behind = [e["t_launched"] for e in (*self._inflight, *self._pending_prefill)
                  if e is not want]
        self._rec.fetched(entries, t_ready, min(behind, default=None), waited)

        for p, first in zip(prefills, firsts_np):
            await self._process_prefill_result(p, first)
        if want is not None:
            self._inflight.popleft()
            if mixed:
                await self._process_prefill_result(want, toks_np, piped=True)
            # route by the block's dispatch kind, not cfg.spec_mode:
            # guided/lora blocks under a spec engine ride the K-step
            # decode_block programs and must drain through _process_block
            elif want["kind"] == "spec":
                self._process_spec_block(
                    want["lanes"], toks_np[0], toks_np[1],
                    want["seq_before"],
                )
            else:
                self._process_block(
                    want["lanes"], *toks_np,
                    routed=None if want.get("routed") is None
                    else (want["routed"], want["want_routed"]),
                )

    async def _process_prefill_result(self, p: dict, first,
                                      piped: bool = False):
        """Host bookkeeping and emission for one fetched prefill or mixed
        dispatch: chunk commits, first tokens of completed prompts, and a
        mixed step's decode and spec-verify rows. `piped`: a mixed step
        that ran as an entry of the pipeline — lane state went to the
        device at dispatch and the samples are in its carry, so only what
        needs the VALUE happens here, and a lane whose slot ended or was
        re-assigned meanwhile is dropped, as a block's is."""
        with self._rec.span("emit"):
            if p.get("routed") is not None:
                # [routed layers, token slots, k] of the dispatch: each
                # asking row's slots, a row [layers][k] an input position
                for slot, start, n in p["want_routed"]:
                    if slot.slot_idx >= 0 and self.slots[slot.slot_idx] is slot:
                        self._take_routed(slot, np.swapaxes(
                            p["routed"][:, start:start + n], 0, 1).tolist())
            for slot, upto in p.get("progressed", []):
                if slot.slot_idx < 0 or self.slots[slot.slot_idx] is not slot:
                    continue
                if slot.prefill_pos < len(slot.kv_prompt):
                    # mid-prompt: commit the chunk's full pages now so
                    # concurrent same-prefix requests can skip ahead
                    self._commit_blocks(slot, upto_tokens=upto)
        first_toks, first_lps, first_tids, first_tlps = first
        for slot, lane in p["done"]:
            if slot.slot_idx < 0 or self.slots[slot.slot_idx] is not slot:
                continue  # released meanwhile (cancel)
            tok = int(first_toks[lane])
            lp = float(first_lps[lane])
            top = self._top_entry(slot, first_tids[lane], first_tlps[lane])
            if slot.return_kv:
                # awaits the device (the pages' extraction): no span of
                # the loop's is held across it
                await self._emit_prefill_result(slot, tok, lp, top)
            else:
                with self._rec.span("emit", more=True):
                    self._finish_prefill(slot, tok, lp, top, piped)
        with self._rec.span("emit", more=True):
            # mixed-step decode rows: each active lane advanced ONE token
            # inside the fused dispatch — emit it. A piped step has left it
            # in the device carry already; after a drained one the (stale)
            # carry is re-synced for this lane via the patch path
            for row, i, slot_ref in p.get("decode", []):
                slot = self.slots[i]
                if slot is None or slot is not slot_ref:
                    continue  # released/preempted meanwhile
                if slot.done or slot.context.is_stopped():
                    self._emit_finish(slot, "cancelled")
                    self._release_slot(slot)
                    continue
                tok = int(first_toks[row])
                slot.seq.append(tok)
                slot.generated += 1
                slot.last_token = tok
                self.tokens[i] = tok
                if slot.guided_fsm is not None:
                    # fused guided decode: the mixed step is host-
                    # authoritative per step, so the FSM advances here —
                    # the next dispatch packs the updated mask
                    slot.guided_state = slot.guided_fsm.advance(
                        slot.guided_state, tok
                    )
                if self.hist is not None:
                    # keep the spec n-gram ring coherent for lanes that
                    # advanced outside the spec program (guided/plain
                    # rows under spec_mode); patch re-uploads it via
                    # _mark_lane_dirty below
                    self.hist[
                        i, (len(slot.seq.tokens) - 1) % self.config.spec_hist
                    ] = tok
                lp = float(first_lps[row])
                top = self._top_entry(slot, first_tids[row], first_tlps[row])
                self._emit_tokens(
                    slot, [tok],
                    [lp] if slot.want_logprobs else [],
                    [top] if top else [],
                )
                finish = self._finish_reason(slot, tok)
                if finish:
                    self._emit_finish(slot, finish)
                    self._release_slot(slot)
                else:
                    if not piped:
                        self._fill_recent(i, slot)
                        self._mark_lane_dirty(i)
                    self._maybe_commit_incremental(slot)
            # fused spec verify rows: lane i packed rows first_row..
            # first_row+d (current token + draft); row j's sample is the
            # plain seeded draw at position L-1+j, so accepting the
            # longest draft prefix matching the verified samples and
            # emitting n_acc+1 tokens is byte-identical to plain decode
            for first_row, i, slot_ref, draft in p.get("spec", []):
                slot = self.slots[i]
                if slot is None or slot is not slot_ref:
                    continue
                if slot.done or slot.context.is_stopped():
                    self._emit_finish(slot, "cancelled")
                    self._release_slot(slot)
                    continue
                d_n = len(draft)
                out = [int(first_toks[first_row + j]) for j in range(1 + d_n)]
                n_acc = 0
                while n_acc < d_n and out[n_acc] == draft[n_acc]:
                    n_acc += 1
                self.spec_num_drafts += 1
                self.spec_num_draft_tokens += d_n
                self.spec_num_accepted_tokens += n_acc
                L = int(self.seq_lens[i])
                Hc = self.config.spec_hist
                batch: List[int] = []
                finish = None
                for m, tok in enumerate(out[: n_acc + 1]):
                    slot.seq.append(tok)
                    slot.generated += 1
                    slot.last_token = tok
                    if self.hist is not None:
                        self.hist[i, (L + m) % Hc] = tok
                    batch.append(tok)
                    finish = self._finish_reason(slot, tok)
                    if finish:
                        break
                # seq_lens was NOT advanced at dispatch (acceptance is
                # data-dependent); commit the true advance now — rejected
                # rows' KV is garbage past seq_lens and gets overwritten
                # before it is ever attended
                self.seq_lens[i] = L + len(batch)
                self.tokens[i] = batch[-1]
                self._emit_tokens(slot, batch, [], [])
                if finish:
                    self._emit_finish(slot, finish)
                    self._release_slot(slot)
                else:
                    self._fill_recent(i, slot)
                    self._mark_lane_dirty(i)
                    self._maybe_commit_incremental(slot)

    def _process_spec_block(self, lanes: List[tuple], toks: np.ndarray,
                            n_emit: np.ndarray, seq_before: dict):
        """Emit a fetched spec block: toks [S, B, 1+d], n_emit [S, B].
        Per lane, each round contributes its first n_emit tokens; host
        seq_lens/tokens mirrors are corrected to the device-true values
        (dispatch advanced them by the worst-case bound)."""
        with self._rec.span("emit"):
            S, B, T = toks.shape
            Hc = self.config.spec_hist
            for i, slot_ref in lanes:
                slot = self.slots[i]
                if slot is None or slot is not slot_ref:
                    continue
                true_adv = int(n_emit[:, i].sum())
                # device-authoritative mirrors (valid even if the slot finishes
                # below — the lane is re-patched on the next admission anyway)
                self.seq_lens[i] = seq_before[i] + true_adv
                self.tokens[i] = int(toks[S - 1, i, int(n_emit[S - 1, i]) - 1])
                # stats: engine-level acceptance (device view)
                self.spec_num_drafts += S
                self.spec_num_draft_tokens += S * (T - 1)
                self.spec_num_accepted_tokens += true_adv - S
                if slot.done or slot.context.is_stopped():
                    self._emit_finish(slot, "cancelled")
                    self._release_slot(slot)
                    continue
                # the round's current token sits at position seq_before-1 (the
                # device carry was uploaded with positions = seq_lens - 1), so
                # emitted token t of a round lands at (pos + 1 + t) with
                # pos = seq_before - 1 — matching the device ring exactly
                pos = seq_before[i] - 1
                # all accepted rounds flow into one delta batch (same O(1)-per-
                # dispatch contract as _process_block); a stop mid-round
                # truncates host-side before anything reaches the client
                batch: List[int] = []
                finish = None
                for s in range(S):
                    k = int(n_emit[s, i])
                    for t in range(k):
                        tok = int(toks[s, i, t])
                        slot.seq.append(tok)
                        slot.generated += 1
                        slot.last_token = tok
                        if self.hist is not None:
                            self.hist[i, (pos + 1 + t) % Hc] = tok
                        batch.append(tok)
                        finish = self._finish_reason(slot, tok)
                        if finish:
                            break
                    pos += k
                    if finish:
                        break
                self._emit_tokens(slot, batch, [], [])
                if finish:
                    self._emit_finish(slot, finish)
                    self._release_slot(slot)
                else:
                    self._maybe_commit_incremental(slot)

    def _process_block(self, lanes: List[tuple], toks: np.ndarray,
                       lps: np.ndarray, tids: np.ndarray,
                       tlps: np.ndarray, routed: Optional[tuple] = None):
        """Emit a fetched K-step block: per lane, append/emit tokens until a
        stop condition; excess speculated tokens are discarded. Lanes whose
        slot was preempted/released (or re-assigned) meanwhile are skipped —
        their speculated tokens were never emitted, so no client ever sees
        them. `routed`: a stateful family's (ring of chosen experts [ring,
        routed layers, lanes, k], each asking lane's first input position
        in the block): the rows of the inputs whose tokens are emitted ride
        the block's frame."""
        with self._rec.span("emit"):
            K = toks.shape[0]
            for i, slot_ref in lanes:
                slot = self.slots[i]
                if slot is None or slot is not slot_ref:
                    continue
                if slot.done or slot.context.is_stopped():
                    self._emit_finish(slot, "cancelled")
                    self._release_slot(slot)
                    continue
                # the whole K-step block lands in ONE delta batch on the slot
                # queue: downstream (request plane, detokenizer, SSE) then pays
                # O(1) work per dispatch instead of per token. A mid-block
                # stop/eos truncates host-side — tokens past it were speculated
                # by the device and are never client-visible. The batch commits
                # atomically: resume/migration accounting counts it all-or-
                # nothing, exactly like the singleton emissions it replaces.
                batch: List[int] = []
                batch_lps: List[float] = []
                batch_tops: List[Optional[dict]] = []
                finish = None
                for k in range(K):
                    tok = int(toks[k, i])
                    slot.seq.append(tok)
                    slot.generated += 1
                    slot.last_token = tok
                    self.tokens[i] = tok
                    if slot.guided_fsm is not None:
                        slot.guided_state = slot.guided_fsm.advance(
                            slot.guided_state, tok
                        )
                    if self.hist is not None:
                        # spec engine, non-spec block (guided/lora lanes):
                        # keep the n-gram ring coherent host-side
                        self.hist[
                            i, (len(slot.seq.tokens) - 1) % self.config.spec_hist
                        ] = tok
                    batch.append(tok)
                    if slot.want_logprobs:
                        batch_lps.append(float(lps[k, i]))
                        batch_tops.append(
                            self._top_entry(slot, tids[k, i], tlps[k, i])
                        )
                    finish = self._finish_reason(slot, tok)
                    if finish:
                        break
                if routed is not None and i in routed[1]:
                    ring, pos0 = routed[0], routed[1][i]
                    self._take_routed(slot, [
                        ring[(pos0 + k) % ring.shape[0], :, i].tolist()
                        for k in range(len(batch))
                    ])
                self._emit_tokens(slot, batch, batch_lps, batch_tops)
                if finish:
                    self._emit_finish(slot, finish)
                    self._release_slot(slot)
                else:
                    # durable sessions: newly-full generated blocks publish
                    # now (prefix cache + KVBM + mesh + checkpoint), not at
                    # release — a SIGKILL loses only the un-committed tail
                    self._maybe_commit_incremental(slot)

    def _fail_all(self, message: str):
        """A step raised: the batch state is unreliable. Error every live
        request so callers can migrate/retry rather than hang."""
        self._inflight.clear()
        self._pending_prefill = []
        self._early_fetch = None
        self._rec.idle()
        self._carry_valid = False
        self._dirty_lanes.clear()
        self._dirty_tables.clear()
        # no deadline may outlive its slot (chaos contract: an engine.step
        # fault mid-schedule leaves no orphaned scheduler state)
        self.scheduler.reset()
        for slot in list(self.slots):
            if slot is not None:
                if not slot.done:
                    slot.queue.put_nowait(Annotated.from_error(message).to_dict())
                    slot.queue.put_nowait(None)
                    slot.done = True
                self._release_slot(slot)
        for slot in self._waiting:
            if not slot.done:
                slot.queue.put_nowait(Annotated.from_error(message).to_dict())
                slot.queue.put_nowait(None)
                slot.done = True
        self._waiting = []

    def _sever_all(self, message: str) -> int:
        """Role-morph drain: deliberately cut every live stream with a
        StreamSevered sentinel (NOT _fail_all's terminal error chunk).
        The consumer loop raises it, the server codes the T_ERR as
        `draining`, and each caller's migration loop resumes the session
        on a peer from its durable checkpoint — zero lost items, a tail
        of latency. Batch state resets exactly like _fail_all; the
        severed queues are kept so morph() can wait for the sentinels to
        reach their consumers before flipping discovery."""
        self._inflight.clear()
        self._pending_prefill = []
        self._early_fetch = None
        self._rec.idle()
        self._carry_valid = False
        self._dirty_lanes.clear()
        self._dirty_tables.clear()
        self.scheduler.reset()
        severed = 0
        queues: List[asyncio.Queue] = []
        # NO trailing None after the sentinel: the consumer RAISES on it
        # (never reads further), and a leftover None would keep the queue
        # non-empty forever — _await_sever_consumed watches q.empty() to
        # know the migration actually started
        for slot in list(self.slots):
            if slot is not None:
                if not slot.done:
                    slot.queue.put_nowait(StreamSevered(message))
                    slot.done = True
                    severed += 1
                    queues.append(slot.queue)
                self._release_slot(slot)
        for slot in self._waiting:
            if not slot.done:
                slot.queue.put_nowait(StreamSevered(message))
                slot.done = True
                severed += 1
                queues.append(slot.queue)
        self._waiting = []
        self._severed_queues = queues
        return severed

    # -- emission / teardown --------------------------------------------- #

    def _emit_token(self, slot: _Slot, token: int,
                    lp: Optional[float] = None,
                    top: Optional[dict] = None):
        if slot.done:
            return
        self._rec.first_token(slot)
        self._served_among(top, token, lp)
        out = LLMEngineOutput(
            token_ids=[token],
            log_probs=[lp] if (slot.want_logprobs and lp is not None) else None,
            top_logprobs=[top] if top else None,
            routed_experts=self._routed_rows_for_frame(slot),
        ).to_dict()
        slot.queue.put_nowait(Annotated(data=out).to_dict())

    def _routed_rows_for_frame(self, slot: _Slot) -> Optional[list]:
        """The rows of chosen experts that wait for this slot's next frame
        (None for a request that did not ask)."""
        if not slot.routed_pending:
            return None
        rows, slot.routed_pending = slot.routed_pending, []
        self.routed_rows_emitted += len(rows)
        return rows

    def _emit_tokens(self, slot: _Slot, tokens: List[int],
                     lps: List[float], tops: List[Optional[dict]]):
        """Emit a decode block's accepted tokens as ONE delta batch.
        `lps`/`tops` are 1:1 with `tokens` when the request asked for
        logprobs, else empty. The batch is committed atomically to the
        slot queue — the serving plane never sees a partial block."""
        if slot.done or not tokens:
            return
        self._rec.first_token(slot)
        for top, token, lp in zip(tops, tokens, lps):
            self._served_among(top, token, lp)
        out = LLMEngineOutput(
            token_ids=tokens,
            log_probs=lps if (slot.want_logprobs and lps) else None,
            top_logprobs=tops if any(tops) else None,
            routed_experts=self._routed_rows_for_frame(slot),
        ).to_dict()
        slot.queue.put_nowait(Annotated(data=out).to_dict())
        self.emit_batches += 1
        self.emit_tokens += len(tokens)

    def _finish_reason(self, slot: _Slot, token: int) -> Optional[str]:
        """Host-side stop check for one generated token (eos / stop token
        / length) — pure, so block loops can truncate before emitting."""
        if (
            not slot.ignore_eos
            and slot.generated >= slot.min_tokens
            and (token in slot.eos_ids or token in slot.stop_token_ids)
        ):
            return "eos"
        if slot.generated >= slot.max_tokens:
            return "length"
        return None

    def _maybe_finish(self, slot: _Slot, token: int):
        finish = self._finish_reason(slot, token)
        if finish:
            self._emit_finish(slot, finish)
            self._release_slot(slot)

    def _emit_finish(self, slot: _Slot, reason: str):
        if not slot.done:
            out = LLMEngineOutput(token_ids=[], finish_reason=reason).to_dict()
            slot.queue.put_nowait(Annotated(data=out).to_dict())
            slot.queue.put_nowait(None)
            slot.done = True
        # the stream is over: unpin its adapter (idempotent; preempted
        # slots never pass through here, so their pin survives requeue)
        self._release_lora_pin(slot)

    def _release_slot(self, slot: _Slot):
        if slot.done:
            # terminal release (finish / fail / sever) — NOT preemption,
            # which requeues the slot and must keep its adapter pinned
            self._release_lora_pin(slot)
        if slot.kv_stream_tid is not None and self.data_plane is not None:
            # streamed stage still live while its pages are being released
            # (preempt / cancel / engine failure): fail the transfer so
            # the pulling peer aborts instead of reading recycled pages
            tid, slot.kv_stream_tid = slot.kv_stream_tid, None
            self.data_plane.abort_streamed(tid)
        if slot.slot_idx >= 0 and self.slots[slot.slot_idx] is slot:
            self.scheduler.on_release(slot)
            # commit any full generated blocks before release so decode KV is
            # reusable (conversation prefix reuse / cheap preemption resume)
            self._commit_generated_blocks(slot)
            if self.kvbm is not None:
                # flush the stage NOW: release makes these pages evictable,
                # and the offload gather must enter the device queue before
                # any later dispatch that could recycle them (the step-end
                # flush would be too late for a mid-step release — preempt,
                # cancel from the generate() task)
                self.kvbm.flush_step()
            # releasing while blocks are in flight is safe: in-flight writes
            # for this lane land strictly AFTER its last committed position
            # (speculation starts past the fetched tokens), i.e. only on
            # free tail pages — and any reuse of those pages is re-written
            # by a later-dispatched (device-ordered) prefill/inject
            self.allocator.release(slot.pages, slot.committed_hashes)
            idx = slot.slot_idx
            slot.first_pending = False
            self.slots[idx] = None
            self._free_slots.append(idx)
            self.page_tables[idx, :] = SCRATCH_PAGE
            self.seq_lens[idx] = 0
            slot.slot_idx = -1
            slot.pages = []
            self._mark_lane_dirty(idx)

    def _count_resume(self, slot: _Slot, hashes: List[int], n_cached: int,
                      onboard_hashes: List[int]):
        """Classify a migrated request's resume source at admission
        (docs/fault_tolerance.md): `checkpoint` when any reused block is a
        session-checkpoint replica (pushed here or mesh-tagged), `peer`
        when the onboard pulls plain fabric blocks from another worker,
        `local` when the survivor's own G1/tiers cover the prefix, else
        `recompute` (full prefill — the pre-checkpoint cost of a death)."""
        if slot.migration_counted:
            return
        slot.migration_counted = True
        self.migrations_resumed += 1
        ps = self.config.page_size
        reused_blocks = n_cached + len(onboard_hashes)
        self.migration_replayed_tokens += max(
            len(slot.kv_prompt) - reused_blocks * ps, 0
        )
        reused = list(hashes[:n_cached]) + list(onboard_hashes)
        if self.kvbm is not None and reused and self.kvbm.any_checkpoint(reused):
            self.resume_source_checkpoint += 1
        elif self.kvbm is not None and any(
            not self.kvbm.manager.has(h) for h in onboard_hashes
        ):
            self.resume_source_peer += 1
        elif reused_blocks:
            self.resume_source_local += 1
        else:
            self.resume_source_recompute += 1

    def _maybe_commit_incremental(self, slot: _Slot):
        """Step-loop arm of the generated-block commit (durable decode
        sessions): when a decode block just filled a page, publish it NOW
        — same _commit_generated_blocks spelling as release, so the two
        arms commit byte-identical blocks. The length guard keeps the
        per-step cost at two integer compares when nothing new is full."""
        if (
            not self._incremental_commit
            or slot.generated == 0
            or slot.slot_idx < 0
        ):
            return
        written = max(len(slot.seq.tokens) - 1, 0)
        if written // self.config.page_size > len(slot.committed_hashes):
            self._commit_generated_blocks(slot)

    def _commit_generated_blocks(self, slot: _Slot):
        if slot.generated == 0:
            # never produced a token: a prefill-role slot's valid pages
            # are exactly its incrementally-confirmed chunks (already in
            # committed_hashes), and a preloaded/streamed-pull decode
            # slot's injected pages are only ALL valid at activation
            # (generated >= 1). Committing past either point — e.g. on a
            # mid-prefill cancel or an aborted early pull — would publish
            # unwritten/half-injected pages into the prefix cache (and
            # KVBM + the announcement mesh): silent KV poisoning.
            return
        hashes = slot.seq.block_hashes()
        n_known = len(slot.committed_hashes)
        # only commit blocks whose KV is fully WRITTEN: the pending (last
        # sampled) token's KV never is — a block containing it would poison
        # the prefix cache with one missing position
        written = max(len(slot.seq.tokens) - 1, 0)
        full_written = written // self.config.page_size
        max_by_pages = min(full_written, len(slot.pages))
        new_hashes = hashes[n_known:max_by_pages]
        if new_hashes:
            pages = slot.pages[n_known : n_known + len(new_hashes)]
            token_blocks = [
                b.tokens for b in slot.seq.blocks[n_known : n_known + len(new_hashes)]
            ]
            parent = slot.committed_hashes[-1] if slot.committed_hashes else None
            self.allocator.commit_hashes(pages, new_hashes, token_blocks, parent)
            slot.committed_hashes.extend(new_hashes)
            self._advance_kv_stream(slot)
            if self.kvbm is not None:
                self.kvbm.offload_commit(
                    new_hashes, [p + 1 for p in pages], parent=parent
                )


def _resolve_model(name: str) -> llama.LlamaConfig:
    registry = {
        "tiny-hybrid": hybrid.HybridConfig.tiny_hybrid,
        "tiny-nemotron-h": nemotron_h.NemotronHConfig.tiny_nemotron_h,
        "tiny-exaone-moe": exaone_moe.ExaoneMoeConfig.tiny_exaone_moe,
        "tiny-mla-moe": mla_moe.MlaMoeConfig.tiny_mla_moe,
        "tiny-mla-dsa": mla_moe.MlaMoeConfig.tiny_mla_dsa,
        "tiny": llama.LlamaConfig.tiny,
        "llama3-3b": llama.LlamaConfig.llama3_2_3b,
        "llama3-8b": llama.LlamaConfig.llama3_8b,
        "llama3-70b": llama.LlamaConfig.llama3_70b,
        "tiny-moe": moe.MoeConfig.tiny_moe,
        "mixtral-8x7b": moe.MoeConfig.mixtral_8x7b,
        "gptoss-120b": moe.MoeConfig.gptoss_120b,
    }
    if name in registry:
        return registry[name]()
    raise ValueError(f"unknown model {name!r}; known: {sorted(registry)}")
