"""Llama-family model in functional JAX (param pytrees, no framework).

The TPU engine's flagship dense architecture: RMSNorm, rotary embeddings,
GQA attention over a PAGED KV cache, SwiGLU MLP. Equivalent role to the
engine-side model implementations the reference delegates to vLLM/TRT-LLM
(SURVEY.md §2.5: TP must be implemented natively here).

Design notes (TPU-first):
  * all matmuls bf16 on the MXU; accumulation f32 via preferred_element_type
  * static shapes everywhere: prefill takes a fixed [chunk] token block,
    decode takes the full [max_seqs] slot batch with masking
  * KV cache is paged and lane-dense: [layers, pages, page_size,
    kv_heads*head_dim]; the engine passes page tables; attention reads the
    pool where it lies (ops/paged_attention, ops/kv_quant.KVLayer)
  * tensor parallel: heads and MLP hidden sharded over the "tp" mesh axis
    via NamedSharding on params + cache (parallel/sharding.py); XLA inserts
    the all-reduces (scaling-book recipe), no manual collectives needed
"""

from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .quant import embed_rows, head_leaf, qdot
from ..ops.kv_quant import kv_layer, kv_page_size, kv_write
from ..ops.paged_attention import (
    paged_attention_decode,
    prefill_attention,
    prefill_attention_batched,
    ragged_attention,
)
from ..parallel.mesh import PP_AXIS, SP_AXIS


@dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 14336
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: int = 128
    rope_theta: float = 500000.0
    rms_norm_eps: float = 1e-5
    max_position: int = 8192
    dtype: Any = jnp.bfloat16
    tie_embeddings: bool = False

    @classmethod
    def llama3_8b(cls, **overrides):
        return cls(
            vocab_size=128256,
            hidden_size=4096,
            intermediate_size=14336,
            num_layers=32,
            num_heads=32,
            num_kv_heads=8,
            head_dim=128,
            **overrides,
        )

    @classmethod
    def llama3_70b(cls, **overrides):
        return cls(
            vocab_size=128256,
            hidden_size=8192,
            intermediate_size=28672,
            num_layers=80,
            num_heads=64,
            num_kv_heads=8,
            head_dim=128,
            **overrides,
        )

    @classmethod
    def llama3_2_3b(cls, **overrides):
        """Llama-3.2-3B: the single-v5e-chip flagship (≈6.4GB bf16 params)."""
        return cls(
            vocab_size=128256,
            hidden_size=3072,
            intermediate_size=8192,
            num_layers=28,
            num_heads=24,
            num_kv_heads=8,
            head_dim=128,
            tie_embeddings=True,
            **overrides,
        )

    @classmethod
    def tiny(cls, **overrides):
        """CPU-test scale."""
        kw = dict(
            vocab_size=512,
            hidden_size=64,
            intermediate_size=128,
            num_layers=2,
            num_heads=4,
            num_kv_heads=2,
            head_dim=16,
            max_position=512,
        )
        kw.update(overrides)
        return cls(**kw)


def init_params(config: LlamaConfig, key: jax.Array) -> Dict[str, Any]:
    """Random-init parameter pytree (shape-compatible with HF llama weights;
    the loader maps safetensors onto the same tree when weights exist)."""
    c = config
    k_embed, k_layers, k_out = jax.random.split(key, 3)
    scale = 0.02

    def dense(k, shape):
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(c.dtype)

    layers = []
    keys = jax.random.split(k_layers, c.num_layers)
    q_dim = c.num_heads * c.head_dim
    kv_dim = c.num_kv_heads * c.head_dim
    for lk in keys:
        k1, k2, k3, k4, k5, k6, k7 = jax.random.split(lk, 7)
        layers.append(
            {
                "attn_norm": jnp.ones((c.hidden_size,), c.dtype),
                "wq": dense(k1, (c.hidden_size, q_dim)),
                "wk": dense(k2, (c.hidden_size, kv_dim)),
                "wv": dense(k3, (c.hidden_size, kv_dim)),
                "wo": dense(k4, (q_dim, c.hidden_size)),
                "mlp_norm": jnp.ones((c.hidden_size,), c.dtype),
                "w_gate": dense(k5, (c.hidden_size, c.intermediate_size)),
                "w_up": dense(k6, (c.hidden_size, c.intermediate_size)),
                "w_down": dense(k7, (c.intermediate_size, c.hidden_size)),
            }
        )
    params = {
        "embed": dense(k_embed, (c.vocab_size, c.hidden_size)),
        "layers": jax.tree.map(lambda *xs: jnp.stack(xs), *layers),
        "final_norm": jnp.ones((c.hidden_size,), c.dtype),
        "lm_head": None if c.tie_embeddings else dense(k_out, (c.hidden_size, c.vocab_size)),
    }
    return params


def attention_params(c: LlamaConfig) -> int:
    """Elements of one layer's four attention projections."""
    q_dim, kv_dim = c.num_heads * c.head_dim, c.num_kv_heads * c.head_dim
    return 2 * c.hidden_size * q_dim + 2 * c.hidden_size * kv_dim


def step_work(c: LlamaConfig, real_tokens: int, context_tokens: int,
              passes: int, *, sampled: Optional[int] = None,
              kv_tokens: Optional[int] = None,
              weight_bytes: Optional[float] = None,
              kv_bytes: Optional[float] = None,
              layer_params: Optional[int] = None,
              layer_bytes: Optional[float] = None,
              rows: Optional[int] = None):
    """(useful operations, least HBM bytes) of one pipeline entry: host
    arithmetic for the engine's counters (`step_model_flops`,
    `step_min_bytes`), from shapes the host holds at dispatch. Floors of
    the work asked for: padding, recomputation and a second pass over the
    weights do not count, so a program that wastes less reads higher
    against a peak and none reads over it.

    `real_tokens`: real tokens over all `passes` forward passes (a block
    of K steps makes K). `context_tokens`: positions attended, summed over
    the real tokens (a token attends its own). `sampled`: tokens that go
    through the head (all of them unless said: a prompt's chunk samples
    at most once). `kv_tokens`: positions whose K and V are read, summed
    over passes and rows (`context_tokens` unless said: right for
    one-token rows; a chunk of T tokens behind c reads c + T once).
    `weight_bytes`: bytes a weight element (the dtype's unless said),
    `kv_bytes`: bytes of one position's K and V in one layer.

    Operations: 2 x the matmul parameters a real token passes through
    (the head for sampled tokens; the embedding is a lookup) + 4 x layers
    x heads x head size x context. Bytes: per pass the weights once, less
    the embedding table (a tied head reads it as the head), + the K and V
    read + the new tokens' written.

    `layer_params`, `layer_bytes`: a routed family's own count of one
    layer's parameters a token passes through and bytes a pass reads
    (models/moe.step_work); the dense layer's otherwise. `rows`: the (row,
    pass) pairs of the entry, which only a family that keeps a state per
    row reads (models/hybrid.step_work)."""
    itemsize = jnp.dtype(c.dtype).itemsize
    wb = itemsize if weight_bytes is None else weight_bytes
    if kv_bytes is None:
        kv_bytes = 2 * c.num_kv_heads * c.head_dim * itemsize
    sampled = real_tokens if sampled is None else sampled
    kv_tokens = context_tokens if kv_tokens is None else kv_tokens
    if layer_params is None:
        layer_params = attention_params(c) + 3 * c.hidden_size * c.intermediate_size
        layer_bytes = layer_params * wb
    head = c.hidden_size * c.vocab_size
    flops = (
        2 * (c.num_layers * layer_params * real_tokens + head * sampled)
        + 4 * c.num_layers * c.num_heads * c.head_dim * context_tokens
    )
    nbytes = (
        passes * (c.num_layers * layer_bytes + head * wb)
        + c.num_layers * kv_bytes * (kv_tokens + real_tokens)
    )
    return int(flops), int(nbytes)


def rms_norm(x: jax.Array, weight: jax.Array, eps: float) -> jax.Array:
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)).astype(x.dtype) * weight


def rope_cos_sin(positions: jax.Array, head_dim: int, theta: float):
    """positions [...,] -> cos/sin [..., head_dim//2] (f32)."""
    inv_freq = 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )
    angles = positions.astype(jnp.float32)[..., None] * inv_freq
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x [..., heads, head_dim]; cos/sin broadcast over heads."""
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    cos = cos[..., None, :]
    sin = sin[..., None, :]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


@jax.named_scope("mlp")
def _mlp(layer, x, c: LlamaConfig):
    h = rms_norm(x, layer["mlp_norm"], c.rms_norm_eps)
    gate = qdot(h, layer["w_gate"])
    up = qdot(h, layer["w_up"])
    act = (jax.nn.silu(gate) * up).astype(c.dtype)
    return x + qdot(act, layer["w_down"]).astype(c.dtype)


def prefill_forward(
    params: Dict[str, Any],
    config: LlamaConfig,
    tokens: jax.Array,  # [chunk]
    positions: jax.Array,  # [chunk] absolute positions
    kv_k: jax.Array,  # [L, pages, page_size, kv_heads*head_dim] (lane-dense)
    kv_v: jax.Array,
    page_table: jax.Array,  # [max_pages] pages of THIS sequence
    context_len: jax.Array,  # scalar: positions[<context_len] are valid history
    last_idx: Optional[jax.Array] = None,  # index of the last REAL token in the
    # (possibly padded) chunk; defaults to the final position
    mlp_fn=None,  # (layer, x, config) -> x; models/moe.py passes moe_mlp
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Process one prompt chunk of a single sequence; returns
    (logits_last [vocab], kv_k, kv_v) with the chunk's KV written into pages.

    Chunked prefill: the chunk attends causally to itself AND to already-
    written history via the page table (positions < chunk start).
    """
    c = config
    mlp_fn = mlp_fn or _mlp
    with jax.named_scope("embed"):
        x = embed_rows(params["embed"], tokens, c.dtype)  # [T, H]
    cos, sin = rope_cos_sin(positions, c.head_dim, c.rope_theta)
    page_size = kv_page_size(kv_k)
    T = tokens.shape[0]
    # valid context = history + real (unpadded) chunk length; bounds the
    # Pallas prefill kernel's page streaming (pallas_prefill_attention.py)
    real_chunk = (last_idx + 1) if last_idx is not None else T
    total_len = context_len + real_chunk

    def body(x, kv_k, kv_v):
        new_k_chunks = []
        new_v_chunks = []
        for li in range(c.num_layers):
            layer = jax.tree.map(lambda p: p[li], params["layers"])
            with jax.named_scope("qkv"):
                h = rms_norm(x, layer["attn_norm"], c.rms_norm_eps)
                q = qdot(h, layer["wq"]).astype(c.dtype)
                k = qdot(h, layer["wk"]).astype(c.dtype)
                v = qdot(h, layer["wv"]).astype(c.dtype)
                q = q.reshape(-1, c.num_heads, c.head_dim)
                k = k.reshape(-1, c.num_kv_heads, c.head_dim)
                v = v.reshape(-1, c.num_kv_heads, c.head_dim)
                q = apply_rope(q, cos, sin)
                k = apply_rope(k, cos, sin)
            with jax.named_scope("attention"):
                # write chunk KV into the pages for this sequence
                kv_k = _write_chunk(kv_k, li, k, positions, page_table, page_size)
                kv_v = _write_chunk(kv_v, li, v, positions, page_table, page_size)
                attn = prefill_attention(
                    q, k, v, kv_layer(kv_k, li), kv_layer(kv_v, li), positions,
                    page_table, context_len, total_len,
                )
                attn = attn.reshape(-1, c.num_heads * c.head_dim)
            with jax.named_scope("o_proj"):
                x = x + qdot(attn, layer["wo"]).astype(c.dtype)
            x = mlp_fn(layer, x, c)
        return x, kv_k, kv_v

    x, kv_k, kv_v = body(x, kv_k, kv_v)
    with jax.named_scope("head_and_sample"):
        x = rms_norm(x, params["final_norm"], c.rms_norm_eps)
        last = x[-1] if last_idx is None else x[last_idx]
        head = head_leaf(params)
        logits = qdot(last, head)
        return logits, kv_k, kv_v


def prefill_forward_batched(
    params: Dict[str, Any],
    config: LlamaConfig,
    tokens: jax.Array,  # [B, T] one chunk per sequence (padded to bucket)
    positions: jax.Array,  # [B, T] absolute positions (pads -> scratch tail)
    kv_k: jax.Array,  # [L, pages, page_size, kv_heads*head_dim] (lane-dense)
    kv_v: jax.Array,
    page_tables: jax.Array,  # [B, max_pages] per-seq tables (ctx-bounded)
    context_lens: jax.Array,  # [B] history length per seq
    last_idx: jax.Array,  # [B] index of last REAL token per chunk
    mlp_fn=None,
    emb_override: Optional[jax.Array] = None,  # [B, T, H] multimodal rows
    emb_mask: Optional[jax.Array] = None,  # [B, T] True where override applies
    all_logits: bool = False,  # True: return [B, T, vocab] (spec verify)
    lora=None,  # models/lora.py stack + per-lane idx (multi-LoRA serving)
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Batched chunked prefill: one dispatch processes chunks of SEVERAL
    sequences (the round-1 engine serialized one chunk per loop iteration).
    Returns (logits_last [B, vocab], kv_k, kv_v) — or [B, T, vocab] under
    `all_logits` (the speculative-decoding verify pass, engine/spec.py,
    needs every chunk position's logits).

    `emb_override`/`emb_mask`: multimodal E/P/D splice — encoder-produced
    embedding rows replace the placeholder tokens' embeddings at their
    recorded positions (reference trtllm multimodal_epd.md flow)."""
    c = config
    mlp_fn = mlp_fn or _mlp
    B, T = tokens.shape
    with jax.named_scope("embed"):
        x = embed_rows(params["embed"], tokens, c.dtype)  # [B, T, H]
    if emb_override is not None:
        x = jnp.where(emb_mask[..., None], emb_override.astype(c.dtype), x)
    cos, sin = rope_cos_sin(positions, c.head_dim, c.rope_theta)
    page_size = kv_page_size(kv_k)
    total_lens = context_lens + last_idx + 1  # [B] valid context per seq

    # route positions past the table to the scratch page (phys 0):
    # speculative verify chunks (engine/spec.py) may overshoot
    # max_model_len by up to the draft length near the boundary
    P_tab = page_tables.shape[1]
    logical = jnp.minimum(positions // page_size, P_tab - 1)
    phys = jnp.take_along_axis(page_tables, logical, axis=1)  # [B, T]
    phys = jnp.where(positions < P_tab * page_size, phys, 0)
    offs = positions % page_size

    from . import lora as lora_mod

    for li in range(c.num_layers):
        layer = jax.tree.map(lambda p: p[li], params["layers"])
        ll = lora_mod.layer_lora(lora, li)
        with jax.named_scope("qkv"):
            h = rms_norm(x, layer["attn_norm"], c.rms_norm_eps)
            q = lora_mod.proj(h, layer["wq"], qdot, ll, "wq").astype(c.dtype)
            k = lora_mod.proj(h, layer["wk"], qdot, ll, "wk").astype(c.dtype)
            v = lora_mod.proj(h, layer["wv"], qdot, ll, "wv").astype(c.dtype)
            q = q.reshape(B, T, c.num_heads, c.head_dim)
            k = k.reshape(B, T, c.num_kv_heads, c.head_dim)
            v = v.reshape(B, T, c.num_kv_heads, c.head_dim)
            q = apply_rope(q, cos, sin)
            k = apply_rope(k, cos, sin)
        with jax.named_scope("attention"):
            kv_k = kv_write(kv_k, li, phys, offs, k)
            kv_v = kv_write(kv_v, li, phys, offs, v)
            attn = prefill_attention_batched(
                q, kv_layer(kv_k, li), kv_layer(kv_v, li), positions, page_tables,
                total_lens, context_lens
            )
            attn = attn.reshape(B, T, c.num_heads * c.head_dim)
        with jax.named_scope("o_proj"):
            x = x + lora_mod.proj(attn, layer["wo"], qdot, ll, "wo").astype(c.dtype)
        x = mlp_fn(layer, x, c)

    with jax.named_scope("head_and_sample"):
        x = rms_norm(x, params["final_norm"], c.rms_norm_eps)
        head = head_leaf(params)
        if all_logits:
            return qdot(x, head), kv_k, kv_v  # [B, T, vocab]
        last = x[jnp.arange(B), last_idx]  # [B, hidden]
        logits = qdot(last, head)
        return logits, kv_k, kv_v


def ragged_forward(
    params: Dict[str, Any],
    config: LlamaConfig,
    tokens: jax.Array,  # [M] flat packed: prefill chunks + decode singletons
    positions: jax.Array,  # [M] absolute positions (pads -> scratch tail)
    row_ids: jax.Array,  # [M] owning row per flat token
    kv_k: jax.Array,  # [L, pages, page_size, kv_heads*head_dim] (lane-dense)
    kv_v: jax.Array,
    page_tables: jax.Array,  # [R, max_pages] per-row tables (ctx-bounded)
    row_starts: jax.Array,  # [R] flat index of each row's token 0
    row_lens: jax.Array,  # [R] real tokens per row (1 for decode rows)
    ctx_lens: jax.Array,  # [R] history length per row
    last_flat: jax.Array,  # [R] flat index of each row's LAST real token
    mlp_fn=None,
    lora=None,  # models/lora.py stack + PER-ROW idx (fused multi-LoRA)
    long_rows: Optional[int] = None,  # static: rows of > 1 token, at most
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The unified mixed-step forward: ONE pass over a flat ragged token
    buffer that packs prefill chunks (row_len > 1) and decode slots
    (row_len == 1, ctx = seq_len - 1) — the single device dispatch behind
    the engine's `_dispatch_mixed` (vs the split prefill-batch + decode
    dispatches). Returns (logits_last [R, vocab], kv_k, kv_v) with every
    row's chunk KV written into its pages; each row's last-token logits
    feed on-device sampling (the next decode token / the prefill first
    token). Attention rides ops/paged_attention.ragged_attention (Pallas
    ragged kernel on TPU, XLA reference elsewhere).

    The flat axis is COMPACT: rows back to back from slot 0, the bucket's
    padding behind them, so every dense layer multiplies real rows and
    the tail alone, and attention takes that axis as it is: what layout
    its kernels want of q is `ragged_attention`'s own, behind its gate; K
    and V reach them through the pool. `long_rows` is the engine's
    promise of how many rows hold more than one token at most (its
    `max_prefill_batch`; None: any row may), which sizes the ragged
    kernel's grid.

    `lora`: the engine's stacked adapter pair with `idx` a PER-ROW [R]
    adapter index; base rows carry index 0 (the all-zero adapter — an
    exact no-op), so a blended pack needs no masking. The per-row index
    is gathered to per-flat-token through `row_ids` and the delta rides
    lora.proj exactly as in prefill_forward_batched."""
    c = config
    mlp_fn = mlp_fn or _mlp
    with jax.named_scope("embed"):
        x = embed_rows(params["embed"], tokens, c.dtype)  # [M, H]
    cos, sin = rope_cos_sin(positions, c.head_dim, c.rope_theta)
    page_size = kv_page_size(kv_k)

    # per-token physical page: gather the OWNING row's table, route pad
    # positions (and any overshoot) to the scratch page — same trick as
    # prefill_forward_batched, per flat token instead of per [B, T] cell
    P_tab = page_tables.shape[1]
    tab_tok = page_tables[row_ids]  # [M, max_pages]
    logical = jnp.minimum(positions // page_size, P_tab - 1)
    phys = jnp.take_along_axis(tab_tok, logical[:, None], axis=1)[:, 0]
    phys = jnp.where(positions < P_tab * page_size, phys, 0)
    offs = positions % page_size

    from . import lora as lora_mod

    if lora is not None:
        # per-row adapter index -> per-flat-token (lora_delta's 2-D path
        # treats the flat token axis as its batch axis)
        lora = dict(lora, idx=lora["idx"][row_ids])

    for li in range(c.num_layers):
        layer = jax.tree.map(lambda p: p[li], params["layers"])
        ll = lora_mod.layer_lora(lora, li)
        with jax.named_scope("qkv"):
            h = rms_norm(x, layer["attn_norm"], c.rms_norm_eps)
            q = lora_mod.proj(h, layer["wq"], qdot, ll, "wq").astype(c.dtype)
            k = lora_mod.proj(h, layer["wk"], qdot, ll, "wk").astype(c.dtype)
            v = lora_mod.proj(h, layer["wv"], qdot, ll, "wv").astype(c.dtype)
            q = q.reshape(-1, c.num_heads, c.head_dim)
            k = k.reshape(-1, c.num_kv_heads, c.head_dim)
            v = v.reshape(-1, c.num_kv_heads, c.head_dim)
            q = apply_rope(q, cos, sin)
            k = apply_rope(k, cos, sin)
        with jax.named_scope("attention"):
            kv_k = kv_write(kv_k, li, phys, offs, k)
            kv_v = kv_write(kv_v, li, phys, offs, v)
            attn = ragged_attention(
                q, kv_layer(kv_k, li), kv_layer(kv_v, li), page_tables,
                row_starts, row_lens, ctx_lens, long_rows=long_rows,
            )
            attn = attn.reshape(-1, c.num_heads * c.head_dim)
        with jax.named_scope("o_proj"):
            x = x + lora_mod.proj(attn, layer["wo"], qdot, ll, "wo").astype(c.dtype)
        x = mlp_fn(layer, x, c)

    with jax.named_scope("head_and_sample"):
        x = rms_norm(x, params["final_norm"], c.rms_norm_eps)
        last = x[last_flat]  # [R, hidden]
        head = head_leaf(params)
        logits = qdot(last, head)
        return logits, kv_k, kv_v


def prefill_forward_ring(
    params: Dict[str, Any],
    config: LlamaConfig,
    tokens: jax.Array,  # [T] whole prompt (padded to a multiple of sp)
    kv_k: jax.Array,  # [L, pages, page_size, kv_heads*head_dim] (lane-dense)
    kv_v: jax.Array,
    page_table: jax.Array,  # [max_pages] this sequence's table
    real_len: jax.Array,  # scalar i32: tokens beyond this are padding
    mesh,
    axis_name: str = SP_AXIS,
    mlp_fn=None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Sequence-parallel whole-prompt prefill: the token dim is sharded over
    the ``sp`` mesh axis and attention is exact ring attention
    (ops/ring_attention.py — K/V blocks rotate over ICI, O(T/n) attention
    memory per device). This is the engine's long-context path (SURVEY.md
    §2.5 sequence-parallel row: absent upstream, native extension here);
    the reference handles long prompts only by chunking + disagg
    (disagg_router.rs:230 thresholds). History-free by design: prefix-cache
    hits fall back to chunked prefill.

    Returns (logits_of_last_real_token [vocab], kv_k, kv_v)."""
    from ..ops.ring_attention import ring_attention

    c = config
    mlp_fn = mlp_fn or _mlp
    T = tokens.shape[0]
    positions = jnp.arange(T, dtype=jnp.int32)
    with jax.named_scope("embed"):
        x = embed_rows(params["embed"], tokens, c.dtype)  # [T, H]
    cos, sin = rope_cos_sin(positions, c.head_dim, c.rope_theta)
    page_size = kv_page_size(kv_k)

    # pad positions write to the scratch page (phys 0), real ones to the table
    logical = jnp.minimum(positions // page_size, page_table.shape[0] - 1)
    phys = jnp.where(positions < real_len, page_table[logical], 0)
    offs = positions % page_size

    for li in range(c.num_layers):
        layer = jax.tree.map(lambda p: p[li], params["layers"])
        with jax.named_scope("qkv"):
            h = rms_norm(x, layer["attn_norm"], c.rms_norm_eps)
            q = qdot(h, layer["wq"]).astype(c.dtype)
            k = qdot(h, layer["wk"]).astype(c.dtype)
            v = qdot(h, layer["wv"]).astype(c.dtype)
            q = q.reshape(T, c.num_heads, c.head_dim)
            k = k.reshape(T, c.num_kv_heads, c.head_dim)
            v = v.reshape(T, c.num_kv_heads, c.head_dim)
            q = apply_rope(q, cos, sin)
            k = apply_rope(k, cos, sin)
        with jax.named_scope("attention"):
            kv_k = kv_write(kv_k, li, phys, offs, k)
            kv_v = kv_write(kv_v, li, phys, offs, v)
            attn = ring_attention(q, k, v, mesh, axis_name=axis_name, causal=True)
            attn = attn.reshape(T, c.num_heads * c.head_dim)
        with jax.named_scope("o_proj"):
            x = x + qdot(attn, layer["wo"]).astype(c.dtype)
        x = mlp_fn(layer, x, c)

    with jax.named_scope("head_and_sample"):
        x = rms_norm(x, params["final_norm"], c.rms_norm_eps)
        last = x[jnp.maximum(real_len - 1, 0)]
        head = head_leaf(params)
        logits = qdot(last, head)
        return logits, kv_k, kv_v


def _stage_layers_decode(local_params, local_kv, x, aux, valid, c, mlp_fn):
    """One pipeline stage's layers for a decode microbatch. local_kv =
    (kv_k, kv_v) with leading [L/S] layer axis; aux carries the
    microbatch's positions/page-table rows/seq lens; invalid (bubble)
    ticks write to the scratch page."""
    from ..ops.paged_attention import paged_attention_decode

    kv_k_loc, kv_v_loc = local_kv
    positions, tables, seq_lens = aux["positions"], aux["tables"], aux["seq_lens"]
    page_size = kv_page_size(kv_k_loc)
    cos, sin = rope_cos_sin(positions, c.head_dim, c.rope_theta)
    max_positions = tables.shape[1] * page_size
    logical = jnp.minimum(positions // page_size, tables.shape[1] - 1)
    phys = jnp.take_along_axis(tables, logical[:, None], axis=1)[:, 0]
    phys = jnp.where(valid & (positions < max_positions), phys, 0)
    offs = positions % page_size
    n_local = kv_k_loc.shape[0]
    for li in range(n_local):
        layer = jax.tree.map(lambda p: p[li], local_params)
        with jax.named_scope("qkv"):
            h = rms_norm(x, layer["attn_norm"], c.rms_norm_eps)
            q = qdot(h, layer["wq"]).astype(c.dtype)
            k = qdot(h, layer["wk"]).astype(c.dtype)
            v = qdot(h, layer["wv"]).astype(c.dtype)
            q = q.reshape(-1, c.num_heads, c.head_dim)
            k = k.reshape(-1, c.num_kv_heads, c.head_dim)
            v = v.reshape(-1, c.num_kv_heads, c.head_dim)
            q = apply_rope(q, cos, sin)
            k = apply_rope(k, cos, sin)
        with jax.named_scope("attention"):
            kv_k_loc = kv_write(kv_k_loc, li, phys, offs, k)
            kv_v_loc = kv_write(kv_v_loc, li, phys, offs, v)
            attn = paged_attention_decode(
                q, kv_layer(kv_k_loc, li), kv_layer(kv_v_loc, li), tables, seq_lens
            )
            attn = attn.reshape(-1, c.num_heads * c.head_dim)
        with jax.named_scope("o_proj"):
            x = x + qdot(attn, layer["wo"]).astype(c.dtype)
        x = mlp_fn(layer, x, c)
    return x, (kv_k_loc, kv_v_loc)


def decode_forward_pp(
    params: Dict[str, Any],
    config: LlamaConfig,
    tokens: jax.Array,  # [B]
    positions: jax.Array,  # [B]
    kv_k: jax.Array,  # [L, pages, page_size, KH*D] (pp-sharded on L)
    kv_v: jax.Array,
    page_tables: jax.Array,  # [B, max_pages]
    seq_lens: jax.Array,  # [B]
    mesh,
    num_microbatches: int = 0,
    mlp_fn=None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One decode step with the LAYERS pipelined over the ``pp`` mesh axis:
    the batch splits into microbatches that stream through the stages
    (parallel/pipeline.py pipeline_apply_stateful; each stage owns the KV
    pool of its own layers). The reference delegates PP to its engines
    (SURVEY.md §2.5 PP row); here it is a native XLA schedule.
    Returns (logits [B, vocab], kv_k, kv_v)."""
    from ..parallel.pipeline import pipeline_apply_stateful, stack_stages

    c = config
    mlp_fn = mlp_fn or _mlp
    S = mesh.shape[PP_AXIS]
    B = tokens.shape[0]
    M = num_microbatches or min(S, B)
    while B % M:
        M -= 1
    mb = B // M
    L = kv_k.shape[0]

    stage_params = stack_stages(params["layers"], S)
    stage_kv = (
        kv_k.reshape(S, L // S, *kv_k.shape[1:]),
        kv_v.reshape(S, L // S, *kv_v.shape[1:]),
    )
    with jax.named_scope("embed"):
        x = embed_rows(params["embed"], tokens, c.dtype)  # [B, H]
    x_mb = x.reshape(M, mb, -1)
    aux_mb = {
        "positions": positions.reshape(M, mb),
        "tables": page_tables.reshape(M, mb, -1),
        "seq_lens": seq_lens.reshape(M, mb),
    }

    def stage_fn(local_p, local_s, x, aux, valid):
        return _stage_layers_decode(local_p, local_s, x, aux, valid, c, mlp_fn)

    out, (kv_k_s, kv_v_s) = pipeline_apply_stateful(
        stage_params, stage_kv, x_mb, aux_mb, stage_fn, mesh
    )
    kv_k = kv_k_s.reshape(L, *kv_k.shape[1:])
    kv_v = kv_v_s.reshape(L, *kv_v.shape[1:])
    x = out.reshape(B, -1)
    with jax.named_scope("head_and_sample"):
        x = rms_norm(x, params["final_norm"], c.rms_norm_eps)
        head = head_leaf(params)
        logits = qdot(x, head)
        return logits, kv_k, kv_v


def _stage_layers_prefill(local_params, local_kv, x, aux, valid, c, mlp_fn):
    """One pipeline stage's layers for a PREFILL microbatch (a contiguous
    token span of one sequence). Pipeline order = sequence order, so span
    j's KV is fully written at every stage before span j+1 arrives —
    chunked-prefill causality for free."""
    from ..ops.paged_attention import prefill_attention

    kv_k_loc, kv_v_loc = local_kv
    positions = aux["positions"]  # [t] absolute
    table = aux["table"]  # [max_pages]
    context_len = aux["context_len"]  # scalar: history before this span
    total_len = aux["total_len"]  # scalar: history + real tokens in span
    real_mask = aux["real_mask"]  # [t] bool: padding -> scratch writes
    page_size = kv_page_size(kv_k_loc)
    cos, sin = rope_cos_sin(positions, c.head_dim, c.rope_theta)
    logical = jnp.minimum(positions // page_size, table.shape[0] - 1)
    phys = jnp.where(valid & real_mask, table[logical], 0)
    offs = positions % page_size
    n_local = kv_k_loc.shape[0]
    for li in range(n_local):
        layer = jax.tree.map(lambda p: p[li], local_params)
        with jax.named_scope("qkv"):
            h = rms_norm(x, layer["attn_norm"], c.rms_norm_eps)
            q = qdot(h, layer["wq"]).astype(c.dtype)
            k = qdot(h, layer["wk"]).astype(c.dtype)
            v = qdot(h, layer["wv"]).astype(c.dtype)
            q = q.reshape(-1, c.num_heads, c.head_dim)
            k = k.reshape(-1, c.num_kv_heads, c.head_dim)
            v = v.reshape(-1, c.num_kv_heads, c.head_dim)
            q = apply_rope(q, cos, sin)
            k = apply_rope(k, cos, sin)
        with jax.named_scope("attention"):
            kv_k_loc = kv_write(kv_k_loc, li, phys, offs, k)
            kv_v_loc = kv_write(kv_v_loc, li, phys, offs, v)
            attn = prefill_attention(
                q, k, v, kv_layer(kv_k_loc, li), kv_layer(kv_v_loc, li),
                positions, table,
                context_len, total_len,
            )
            attn = attn.reshape(-1, c.num_heads * c.head_dim)
        with jax.named_scope("o_proj"):
            x = x + qdot(attn, layer["wo"]).astype(c.dtype)
        x = mlp_fn(layer, x, c)
    return x, (kv_k_loc, kv_v_loc)


def prefill_forward_pp(
    params: Dict[str, Any],
    config: LlamaConfig,
    tokens: jax.Array,  # [T] remaining prompt, padded to a multiple of M
    kv_k: jax.Array,
    kv_v: jax.Array,
    page_table: jax.Array,  # [max_pages]
    context_len: jax.Array,  # scalar: already-cached history length
    real_len: jax.Array,  # scalar: tokens beyond this are padding
    mesh,
    num_microbatches: int = 0,
    mlp_fn=None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Single-sequence prefill pipelined over ``pp``: the prompt splits into
    sequential token spans that stream through the layer stages. Returns
    (logits_of_last_real_token [vocab], kv_k, kv_v)."""
    from ..parallel.pipeline import pipeline_apply_stateful, stack_stages

    c = config
    mlp_fn = mlp_fn or _mlp
    S = mesh.shape[PP_AXIS]
    T = tokens.shape[0]
    M = num_microbatches or S
    while T % M:
        M -= 1
    t = T // M
    L = kv_k.shape[0]

    stage_params = stack_stages(params["layers"], S)
    stage_kv = (
        kv_k.reshape(S, L // S, *kv_k.shape[1:]),
        kv_v.reshape(S, L // S, *kv_v.shape[1:]),
    )
    positions = context_len + jnp.arange(T, dtype=jnp.int32)
    with jax.named_scope("embed"):
        x = embed_rows(params["embed"], tokens, c.dtype).reshape(M, t, -1)
    span_starts = context_len + jnp.arange(M, dtype=jnp.int32) * t
    span_real = jnp.clip(real_len - jnp.arange(M) * t, 0, t)  # real tokens/span
    aux_mb = {
        "positions": positions.reshape(M, t),
        "table": jnp.broadcast_to(page_table, (M, page_table.shape[0])),
        "context_len": span_starts,
        "total_len": span_starts + span_real,
        "real_mask": (jnp.arange(T).reshape(M, t) < real_len),
    }

    def stage_fn(local_p, local_s, x, aux, valid):
        return _stage_layers_prefill(local_p, local_s, x, aux, valid, c, mlp_fn)

    out, (kv_k_s, kv_v_s) = pipeline_apply_stateful(
        stage_params, stage_kv, x, aux_mb, stage_fn, mesh
    )
    kv_k = kv_k_s.reshape(L, *kv_k.shape[1:])
    kv_v = kv_v_s.reshape(L, *kv_v.shape[1:])
    flat = out.reshape(T, -1)
    x = rms_norm(flat, params["final_norm"], c.rms_norm_eps)
    last = x[jnp.maximum(real_len - 1, 0)]
    head = head_leaf(params)
    logits = qdot(last, head)
    return logits, kv_k, kv_v


def _write_chunk(kv, layer_idx, vals, positions, page_table, page_size):
    """Scatter chunk KV [T, kv_heads, head_dim] into paged cache at absolute
    positions (page_table maps logical page -> physical page). Rides
    ops/kv_quant.kv_write — quantize-on-write under DYN_KV_QUANT, the
    seed's exact scatter otherwise."""
    logical_pages = positions // page_size
    phys_pages = page_table[logical_pages]
    offs = positions % page_size
    return kv_write(kv, layer_idx, phys_pages, offs, vals)


def decode_forward(
    params: Dict[str, Any],
    config: LlamaConfig,
    tokens: jax.Array,  # [B] one new token per slot
    positions: jax.Array,  # [B]
    kv_k: jax.Array,
    kv_v: jax.Array,
    page_tables: jax.Array,  # [B, max_pages]
    seq_lens: jax.Array,  # [B] lengths INCLUDING the new token
    mlp_fn=None,  # (layer, x, config) -> x; models/moe.py passes moe_mlp
    lora=None,  # models/lora.py stack + per-lane idx (multi-LoRA serving)
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One decode step for the whole slot batch; returns
    (logits [B, vocab], kv_k, kv_v)."""
    from . import lora as lora_mod

    c = config
    mlp_fn = mlp_fn or _mlp
    with jax.named_scope("embed"):
        x = embed_rows(params["embed"], tokens, c.dtype)  # [B, H]
    cos, sin = rope_cos_sin(positions, c.head_dim, c.rope_theta)
    page_size = kv_page_size(kv_k)

    for li in range(c.num_layers):
        layer = jax.tree.map(lambda p: p[li], params["layers"])
        ll = lora_mod.layer_lora(lora, li)
        with jax.named_scope("qkv"):
            h = rms_norm(x, layer["attn_norm"], c.rms_norm_eps)
            q = lora_mod.proj(h, layer["wq"], qdot, ll, "wq").astype(c.dtype)
            k = lora_mod.proj(h, layer["wk"], qdot, ll, "wk").astype(c.dtype)
            v = lora_mod.proj(h, layer["wv"], qdot, ll, "wv").astype(c.dtype)
            q = q.reshape(-1, c.num_heads, c.head_dim)
            k = k.reshape(-1, c.num_kv_heads, c.head_dim)
            v = v.reshape(-1, c.num_kv_heads, c.head_dim)
            q = apply_rope(q, cos, sin)
            k = apply_rope(k, cos, sin)
        with jax.named_scope("attention"):
            # write each slot's new KV at its position. Positions past the table
            # (fused-block speculation overshooting max_model_len) route to
            # physical page 0 — the engine's reserved scratch page — instead of
            # XLA's silent clamp-to-last-page, which could corrupt a real
            # (possibly shared/committed) KV page.
            max_positions = page_tables.shape[1] * page_size
            logical = jnp.minimum(positions // page_size, page_tables.shape[1] - 1)
            phys = jnp.take_along_axis(page_tables, logical[:, None], axis=1)[:, 0]
            phys = jnp.where(positions < max_positions, phys, 0)
            offs = positions % page_size
            kv_k = kv_write(kv_k, li, phys, offs, k[:, 0] if k.ndim == 4 else k)
            kv_v = kv_write(kv_v, li, phys, offs, v[:, 0] if v.ndim == 4 else v)
            attn = paged_attention_decode(
                q, kv_layer(kv_k, li), kv_layer(kv_v, li), page_tables, seq_lens
            )
            attn = attn.reshape(-1, c.num_heads * c.head_dim)
        with jax.named_scope("o_proj"):
            x = x + lora_mod.proj(attn, layer["wo"], qdot, ll, "wo").astype(c.dtype)
        x = mlp_fn(layer, x, c)

    with jax.named_scope("head_and_sample"):
        x = rms_norm(x, params["final_norm"], c.rms_norm_eps)
        head = head_leaf(params)
        logits = qdot(x, head)
        return logits, kv_k, kv_v


def param_count(params) -> int:
    return sum(x.size for x in jax.tree.leaves(params) if x is not None)
