"""Mixtral-family sparse-MoE model: llama attention + top-k expert MLP.

The reference serves wide-EP MoE models (DeepSeek-R1 recipe,
recipes/deepseek-r1/sglang-wideep/tep16p-dep16d-disagg.yaml: --ep-size 16)
by delegating to SGLang; here expert parallelism is native (SURVEY.md §2.5
row "Expert parallel (EP / wide-EP)"): experts live on the ``ep`` mesh axis
and tokens are dispatched GShard-style — a capacity-bounded one-hot
dispatch einsum whose [E, C, H] intermediate is sharding-constrained to
P("ep"), so GSPMD lowers the token shuffle to an all-to-all over ICI
instead of gather/scatter (the canonical TPU MoE pattern; see PAPERS.md).

Everything is static-shaped: top-k routing, cumsum slotting, and the expert
FFN batched over the expert dim on the MXU.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..parallel.mesh import EP_AXIS, SP_AXIS
from . import llama
from .llama import LlamaConfig, rms_norm
from .quant import qeinsum


@dataclass(frozen=True)
class MoeConfig(LlamaConfig):
    num_experts: int = 8
    num_experts_per_tok: int = 2
    capacity_factor: float = 1.25

    @classmethod
    def mixtral_8x7b(cls, **overrides):
        return cls(
            vocab_size=32000,
            hidden_size=4096,
            intermediate_size=14336,
            num_layers=32,
            num_heads=32,
            num_kv_heads=8,
            head_dim=128,
            rope_theta=1e6,
            num_experts=8,
            num_experts_per_tok=2,
            **overrides,
        )

    @classmethod
    def gptoss_120b(cls, **overrides):
        """gpt-oss-120b-shaped wide-MoE config (public architecture: 36
        layers, 128 experts top-4, ~5B active params; reference recipe
        recipes/gpt-oss-120b/trtllm/agg). Attention here is GQA (the
        repo's attention stack) at matching head geometry."""
        kw = dict(
            vocab_size=201088,
            hidden_size=2880,
            intermediate_size=2880,
            num_layers=36,
            num_heads=64,
            num_kv_heads=8,
            head_dim=64,
            rope_theta=150e3,
            num_experts=128,
            num_experts_per_tok=4,
        )
        kw.update(overrides)
        return cls(**kw)

    @classmethod
    def tiny_moe(cls, **overrides):
        kw = dict(
            vocab_size=512,
            hidden_size=64,
            intermediate_size=96,
            num_layers=2,
            num_heads=4,
            num_kv_heads=2,
            head_dim=16,
            max_position=512,
            num_experts=4,
            num_experts_per_tok=2,
        )
        kw.update(overrides)
        return cls(**kw)


def init_params(config: MoeConfig, key: jax.Array) -> Dict[str, Any]:
    c = config
    k_embed, k_layers, k_out = jax.random.split(key, 3)
    scale = 0.02

    def dense(k, shape):
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(c.dtype)

    layers = []
    keys = jax.random.split(k_layers, c.num_layers)
    q_dim = c.num_heads * c.head_dim
    kv_dim = c.num_kv_heads * c.head_dim
    E, I = c.num_experts, c.intermediate_size
    for lk in keys:
        k1, k2, k3, k4, k5, k6, k7, k8 = jax.random.split(lk, 8)
        layers.append(
            {
                "attn_norm": jnp.ones((c.hidden_size,), c.dtype),
                "wq": dense(k1, (c.hidden_size, q_dim)),
                "wk": dense(k2, (c.hidden_size, kv_dim)),
                "wv": dense(k3, (c.hidden_size, kv_dim)),
                "wo": dense(k4, (q_dim, c.hidden_size)),
                "mlp_norm": jnp.ones((c.hidden_size,), c.dtype),
                # router kept f32: tiny, and routing decisions are
                # numerically sensitive
                "router": jax.random.normal(k5, (c.hidden_size, E), jnp.float32)
                * scale,
                "w_gate": dense(k6, (E, c.hidden_size, I)),
                "w_up": dense(k7, (E, c.hidden_size, I)),
                "w_down": dense(k8, (E, I, c.hidden_size)),
            }
        )
    params = {
        "embed": dense(k_embed, (c.vocab_size, c.hidden_size)),
        "layers": jax.tree.map(lambda *xs: jnp.stack(xs), *layers),
        "final_norm": jnp.ones((c.hidden_size,), c.dtype),
        "lm_head": None if c.tie_embeddings else dense(k_out, (c.hidden_size, c.vocab_size)),
    }
    return params


def _constrain_ep(x: jax.Array) -> jax.Array:
    """Pin the expert dim (axis 0) to the ``ep`` mesh axis so GSPMD lowers
    dispatch/combine to an all-to-all. No-op when no mesh with an ``ep``
    axis is in context (single-chip, CPU tests)."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or EP_AXIS not in mesh.axis_names:
        return x
    return jax.lax.with_sharding_constraint(
        x, P(EP_AXIS, *([None] * (x.ndim - 1)))
    )


def expert_capacity(num_tokens: int, config: MoeConfig) -> int:
    """Static per-expert token capacity (round up to a multiple of 4 so the
    C dim tiles)."""
    c = math.ceil(
        num_tokens * config.num_experts_per_tok / config.num_experts
        * config.capacity_factor
    )
    return max(4, (c + 3) // 4 * 4)


def moe_mlp(layer: Dict[str, Any], x: jax.Array, c: MoeConfig) -> jax.Array:
    """Sparse MoE block for x [T, H]: top-k routing -> capacity-bounded
    one-hot dispatch -> batched expert SwiGLU -> weighted combine."""
    T, H = x.shape
    E, K = c.num_experts, c.num_experts_per_tok
    C = expert_capacity(T, c)

    h = rms_norm(x, layer["mlp_norm"], c.rms_norm_eps)
    logits = jnp.dot(h.astype(jnp.float32), layer["router"])  # [T, E]
    topv, topi = jax.lax.top_k(logits, K)  # [T, K]
    probs = jax.nn.softmax(topv, axis=-1)  # renormalized over chosen experts

    # combine weight per (token, expert); 0 where not routed
    combine = jnp.zeros((T, E), jnp.float32)
    combine = combine.at[jnp.arange(T)[:, None], topi].add(probs)
    routed = combine > 0.0  # [T, E]

    # slot within expert buffer: tokens claim slots in order; overflow drops
    pos = jnp.cumsum(routed.astype(jnp.int32), axis=0) - 1  # [T, E]
    keep = routed & (pos < C)
    dispatch = (
        jax.nn.one_hot(jnp.where(keep, pos, C), C, dtype=h.dtype)
        * keep[..., None]
    )  # [T, E, C]

    expert_in = _constrain_ep(jnp.einsum("tec,th->ech", dispatch, h))
    # qeinsum: expert stacks may be int8 (models/quant.py) — scale
    # [E, 1, out] applies to the f32 accumulator after the einsum
    gate = qeinsum("ech,ehi->eci", expert_in, layer["w_gate"])
    up = qeinsum("ech,ehi->eci", expert_in, layer["w_up"])
    act = (jax.nn.silu(gate) * up).astype(c.dtype)
    expert_out = _constrain_ep(
        qeinsum("eci,eih->ech", act, layer["w_down"])
    )

    out = jnp.einsum(
        "ech,tec->th", expert_out, dispatch.astype(jnp.float32) * combine[..., None]
    )
    return x + out.astype(c.dtype)


def decode_forward(
    params: Dict[str, Any],
    config: MoeConfig,
    tokens: jax.Array,  # [B]
    positions: jax.Array,  # [B]
    kv_k: jax.Array,
    kv_v: jax.Array,
    page_tables: jax.Array,  # [B, max_pages]
    seq_lens: jax.Array,  # [B]
    lora=None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One decode step for the slot batch; llama attention path with the
    sparse-MoE MLP swapped in. Returns (logits [B, vocab], kv)."""
    return llama.decode_forward(
        params, config, tokens, positions, kv_k, kv_v, page_tables, seq_lens,
        mlp_fn=moe_mlp, lora=lora,
    )


def decode_forward_pp(params, config, tokens, positions, kv_k, kv_v,
                      page_tables, seq_lens, mesh, num_microbatches=0):
    """Pipelined decode step (layers over pp), MoE MLP."""
    return llama.decode_forward_pp(
        params, config, tokens, positions, kv_k, kv_v, page_tables, seq_lens,
        mesh, num_microbatches=num_microbatches, mlp_fn=moe_mlp,
    )


def prefill_forward_pp(params, config, tokens, kv_k, kv_v, page_table,
                       context_len, real_len, mesh, num_microbatches=0):
    """Pipelined single-sequence prefill, MoE MLP."""
    return llama.prefill_forward_pp(
        params, config, tokens, kv_k, kv_v, page_table, context_len, real_len,
        mesh, num_microbatches=num_microbatches, mlp_fn=moe_mlp,
    )


def prefill_forward_ring(params, config, tokens, kv_k, kv_v, page_table,
                         real_len, mesh, axis_name=SP_AXIS):
    """Ring-attention whole-prompt prefill (sequence over sp), MoE MLP."""
    return llama.prefill_forward_ring(
        params, config, tokens, kv_k, kv_v, page_table, real_len, mesh,
        axis_name=axis_name, mlp_fn=moe_mlp,
    )


def prefill_forward(
    params: Dict[str, Any],
    config: MoeConfig,
    tokens: jax.Array,  # [chunk]
    positions: jax.Array,
    kv_k: jax.Array,
    kv_v: jax.Array,
    page_table: jax.Array,  # [max_pages]
    context_len: jax.Array,
    last_idx: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One prompt chunk of a single sequence (chunked prefill), MoE MLP."""
    return llama.prefill_forward(
        params, config, tokens, positions, kv_k, kv_v, page_table, context_len,
        last_idx=last_idx, mlp_fn=moe_mlp,
    )


def ragged_forward(
    params: Dict[str, Any],
    config: MoeConfig,
    tokens: jax.Array,  # [N] flat packed mixed prefill+decode buffer
    positions: jax.Array,  # [N]
    row_ids: jax.Array,  # [N]
    kv_k: jax.Array,
    kv_v: jax.Array,
    page_tables: jax.Array,  # [R, max_pages]
    row_starts: jax.Array,  # [R]
    row_lens: jax.Array,  # [R]
    ctx_lens: jax.Array,  # [R]
    last_flat: jax.Array,  # [R]
    lora=None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Unified mixed-step forward (engine `_dispatch_mixed`), MoE MLP —
    the flat buffer is already [tokens, H], exactly the shape expert
    dispatch wants."""
    return llama.ragged_forward(
        params, config, tokens, positions, row_ids, kv_k, kv_v,
        page_tables, row_starts, row_lens, ctx_lens, last_flat,
        mlp_fn=moe_mlp, lora=lora,
    )


def _moe_mlp_nd(layer, x, c):
    """moe_mlp over [B, T, H] (batched prefill flattens the token dims —
    expert dispatch is position-independent)."""
    if x.ndim == 3:
        B, T, H = x.shape
        return moe_mlp(layer, x.reshape(B * T, H), c).reshape(B, T, H)
    return moe_mlp(layer, x, c)


def prefill_forward_batched(
    params: Dict[str, Any],
    config: MoeConfig,
    tokens: jax.Array,  # [B, T]
    positions: jax.Array,  # [B, T]
    kv_k: jax.Array,
    kv_v: jax.Array,
    page_tables: jax.Array,  # [B, max_pages]
    context_lens: jax.Array,  # [B]
    last_idx: jax.Array,  # [B]
    emb_override: Optional[jax.Array] = None,
    emb_mask: Optional[jax.Array] = None,
    all_logits: bool = False,
    lora=None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Batched chunked prefill (multiple sequences per dispatch), MoE MLP."""
    return llama.prefill_forward_batched(
        params, config, tokens, positions, kv_k, kv_v, page_tables,
        context_lens, last_idx, mlp_fn=_moe_mlp_nd,
        emb_override=emb_override, emb_mask=emb_mask, all_logits=all_logits,
        lora=lora,
    )
