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

import functools
import math
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops.paged_attention import scope_allows_kernels
from ..parallel.mesh import EP_AXIS, SP_AXIS
from . import llama
from .llama import LlamaConfig, rms_norm
from .quant import is_quant, qeinsum


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


class ExpertStack:
    """A stacked expert weight [L, E, k, n] that stays whole under the layer
    loop's `p[li]`: indexing keeps the stack and notes the layer. A slice
    handed to a Pallas call is a copy on the TPU (0.94 GB a matrix at
    Mixtral's widths, three a layer), so the grouped matmul takes the
    stack as [L*E, k, n] and reaches layer li's experts as groups li*E ..
    li*E + E - 1 (as the attention ops take the KV pool, kv_quant.KVLayer);
    `array` is the slice, for the capacity einsum, which XLA fuses."""

    def __init__(self, stack: jax.Array, li=None):
        self.stack, self.li = stack, li

    def __getitem__(self, li) -> "ExpertStack":
        return ExpertStack(self.stack, li)

    @property
    def array(self) -> jax.Array:
        return self.stack[self.li]


def _whole_expert_stacks(params: Dict[str, Any]) -> Dict[str, Any]:
    """`params` with the expert stacks wrapped (see ExpertStack), for the
    forwards whose token count can reach the grouped path. Quantized
    stacks are left as they are: they keep the capacity path."""
    layers = params["layers"]
    if is_quant(layers["w_gate"]):
        return params
    wrapped = {k: ExpertStack(layers[k]) for k in ("w_gate", "w_up", "w_down")}
    return {**params, "layers": {**layers, **wrapped}}


# Above this many tokens the capacity einsum is bound by arithmetic, not
# by the expert weights it streams: it multiplies E*C rows whatever was
# routed, C = T at the dropless capacity_factor E/K, and a v5e's ridge is
# 197 TFLOP/s / 819 GB/s = 240 rows an expert. A decode block (T =
# max_num_seqs) sits under it and keeps the einsum; a mixed step or a
# prefill chunk sits over it and multiplies the routed rows alone.
GROUPED_MIN_TOKENS = 256
# megablox row tile. Every expert with rows in a tile streams its weights
# for it, so a tile's arithmetic should hide behind one expert's stream:
# at Mixtral's widths 256 rows multiply in 0.15 ms beside a stream of 0.14
# ms; 512 do not (0.30 ms). On the chip, one layer's block, ms at 128 / 256
# / 512 rows: 5.66 / 5.76 / 8.42 with 190 real tokens of 1,024, 6.69 / 6.35
# / 9.42 with 290, 13.45 / 10.33 / 13.38 with 1,100 of 2,048 (PERF.md, PR 32).
_GMM_ROWS = 256


def _tile(n: int) -> int:
    """Largest k/n tile of the grouped matmul that divides n: a 1024 x
    1024 bf16 block of expert weights is 2 MB, double-buffered in VMEM
    (2048 does not fit beside it; 512 is a quarter slower on the chip).
    896 and 384 are for a width of 21 x 128 (models/nemotron_h.py's 2,688),
    which no power of two over 128 divides."""
    return next(t for t in (1024, 896, 512, 384, 256, 128) if n % t == 0)


def _grouped_matmul(lhs: jax.Array, w: ExpertStack, group_sizes: jax.Array,
                    rows: int = _GMM_ROWS):
    """lhs [M, k] (rows sorted by group) x rhs [G, k, n] -> [M, n] f32,
    rhs the whole stack of `w` as [L*E, k, n]: the first group_sizes[0]
    rows against rhs[0], the next against rhs[1], and so on; an empty
    group costs nothing. Rows past sum(group_sizes) are left unwritten.
    The Pallas grouped matmul where its tiles fit (a TPU, 128-aligned
    widths), else XLA's ragged_dot. `rows`: the kernel's row tile (a
    family whose decode step takes this road with a handful of rows an
    expert asks for a smaller one: models/hybrid.py)."""
    rhs = w.stack.reshape(-1, *w.stack.shape[2:])
    m, k = lhs.shape
    n = rhs.shape[-1]
    if jax.default_backend() == "tpu" and k % 128 == 0 and n % 128 == 0:
        from jax.experimental.pallas.ops.tpu.megablox import gmm

        # whole row tiles: the rows added lie past every group
        lhs = jnp.pad(lhs, ((0, -m % rows), (0, 0)))
        return gmm(
            lhs, rhs, group_sizes, preferred_element_type=jnp.float32,
            tiling=(rows, _tile(k), _tile(n)),
        )[:m]
    return jax.lax.ragged_dot(
        lhs, rhs, group_sizes, preferred_element_type=jnp.float32
    )


def _experts_grouped(layer, h, topi, probs, valid, c: MoeConfig) -> jax.Array:
    """Dropless expert block over the routed rows: sort the (token,
    expert) assignments by expert, run gate, up and down as grouped
    matmuls over the sorted rows, weigh and sum each token's K rows.
    Tokens that `valid` marks as padding are sent to no expert. -> [T, H]
    f32."""
    T, H = h.shape
    E, K = c.num_experts, c.num_experts_per_tok
    # a plain [E, k, n] weight is a stack of one layer
    w_gate, w_up, w_down = (
        w if isinstance(w, ExpertStack) else ExpertStack(w[None], 0)
        for w in (layer["w_gate"], layer["w_up"], layer["w_down"])
    )
    # groups: every layer's experts, all but this layer's empty
    G = w_gate.stack.shape[0] * E
    group = w_gate.li * E + topi.reshape(T * K)  # assignment a = t * K + k
    if valid is not None:
        # group G does not exist: padding sorts behind every real row and
        # is counted in no group
        group = jnp.where(jnp.repeat(valid, K), group, G)
        probs = jnp.where(valid[:, None], probs, 0.0)
    order = jnp.argsort(group, stable=True)
    group_sizes = jnp.zeros((G,), jnp.int32).at[group].add(1, mode="drop")
    rows = h[order // K]  # [T*K, H]
    gate = _grouped_matmul(rows, w_gate, group_sizes)
    up = _grouped_matmul(rows, w_up, group_sizes)
    act = (jax.nn.silu(gate) * up).astype(c.dtype)
    down = _grouped_matmul(act, w_down, group_sizes)
    # rows no group owns were never written: whatever lies there, drop it
    live = jnp.arange(T * K) < group_sizes.sum()
    down = jnp.where(live[:, None], down, 0.0)
    back = jnp.zeros((T * K,), jnp.int32).at[order].set(jnp.arange(T * K))
    return jnp.einsum("tkh,tk->th", down[back].reshape(T, K, H), probs)


#: megablox row tile of a decode step's routed rows on a chip that holds a
#: SHARE of the experts: a step's real rows times k, of which the share is
#: held, fit one tile, and every expert with a row in it streams its
#: weights once
DECODE_GMM_ROWS = 128
#: an expert's form, by the names of its stacked matrices [L, E, k, n]
EXPERT_FORMS = {
    "gated_silu": ("w_gate", "w_up", "w_down"),  # down(silu(gate(h)) * up(h))
    "relu2": ("w1", "w2"),  # w2(relu(w1(h)) ** 2)
}


def held_rows_tile(T: int) -> int:
    """The grouped matmul's row tile for a forward over T token slots."""
    return DECODE_GMM_ROWS if T < GROUPED_MIN_TOKENS else _GMM_ROWS


def experts_held(stacks, li, h, idx, weight, valid, *, form: str, held: int,
                 first: int, dtype):
    """sum_e w_e expert_e(h) over the chosen experts this chip holds,
    `[first, first + held)` of the router's width: the (token, expert) pairs
    that fall on a held expert sorted by expert, the form's grouped matmuls
    over those rows, each token's rows weighed and summed. No pair is
    dropped, whatever the batch; pairs on experts held elsewhere (and
    padding, where `valid` [T] marks real tokens) reach no expert.
    `stacks`: the WHOLE [L, E, ., .] stacks under the names of
    EXPERT_FORMS[form]; layer `li`'s experts are groups li * E ... of them.
    h [T, width in]; idx, weight [T, K]. -> [T, width out] f32."""
    T, K = idx.shape
    local = idx - first
    here = (local >= 0) & (local < held)
    if valid is not None:
        here &= valid[:, None]
    names = EXPERT_FORMS[form]
    G = stacks[names[0]].shape[0] * held
    group = jnp.where(here, li * held + local, G).reshape(T * K)
    weight = jnp.where(here, weight, 0.0)
    order = jnp.argsort(group, stable=True)
    sizes = jnp.zeros((G,), jnp.int32).at[group].add(1, mode="drop")
    x = h[order // K]
    rows = held_rows_tile(T)

    def mm(lhs, name):
        return _grouped_matmul(lhs, ExpertStack(stacks[name]), sizes, rows=rows)

    if form == "gated_silu":
        act = (jax.nn.silu(mm(x, "w_gate")) * mm(x, "w_up")).astype(dtype)
    else:
        act = jnp.square(jax.nn.relu(mm(x, "w1"))).astype(dtype)
    out = mm(act, names[-1])
    # rows no group owns were never written
    out = jnp.where((jnp.arange(T * K) < sizes.sum())[:, None], out, 0.0)
    back = jnp.zeros((T * K,), jnp.int32).at[order].set(jnp.arange(T * K))
    return jnp.einsum(
        "tkh,tk->th", out[back].reshape(T, K, out.shape[-1]), weight)


def sigmoid_route(h, router, bias, per_token: int, norm_topk_prob: bool,
                  scaling_factor: float):
    """(experts chosen [T, K] under the router's full width, their weights
    [T, K]) of the router that balances by a choice bias and no auxiliary
    loss (models/nemotron_h.py, models/exaone_moe.py): sigmoid scores over
    all the router's outputs in float32; the K largest of score + choice
    bias; the weights are the SCORES at the chosen, over their sum under
    `norm_topk_prob`, times the scaling factor."""
    scores = jax.nn.sigmoid(jnp.dot(
        h.astype(jnp.float32), router, precision=jax.lax.Precision.HIGHEST))
    _, idx = jax.lax.top_k(scores + bias, per_token)
    weight = jnp.take_along_axis(scores, idx, axis=-1)
    if norm_topk_prob:
        weight = weight / weight.sum(-1, keepdims=True)
    return idx, weight * scaling_factor


def experts_touched(held: int, pairs: float) -> float:
    """Of `held` experts, those that `pairs` (token, expert) pairs spread
    evenly over them touch, in expectation."""
    return held * (1.0 - (1.0 - 1.0 / held) ** pairs)


def held_expert_rows(held: int, width: int, per_token: int, T: int, real: int):
    """(routed, computed) expert rows of one layer of `experts_held` over T
    token slots of which `real` are real. Routed: the (token, expert) pairs
    that fall on a held expert, in expectation under a router that spreads
    its choices evenly (real x K x the share held; the choice itself stays
    on the device). Computed: the rows the grouped matmul multiplies, whole
    row tiles and one more for every expert whose rows start inside
    another's tile (an upper bound, as `expert_rows`), with the experts
    touched in expectation too."""
    if not real:
        return 0, 0
    routed = real * per_token * held / width
    tile = held_rows_tile(T)
    tiles = -(-routed // tile) + max(experts_touched(held, routed) - 1.0, 0.0)
    return int(round(routed)), int(round(tiles * tile))


def _on_one_device() -> bool:
    """No mesh in context, and the calling engine's mesh (the attention
    gate's scope) is a single device. Under an `ep` axis the capacity
    path's [E, C, H] buffers are what `_constrain_ep` lowers to an
    all-to-all, and under any mesh a Pallas call has no partitioning rule."""
    mesh = jax.sharding.get_abstract_mesh()
    return (mesh is None or mesh.empty) and scope_allows_kernels()


def _takes_grouped(T: int, quantized: bool) -> bool:
    """moe_mlp's choice between its two expert blocks (see there)."""
    return T >= GROUPED_MIN_TOKENS and _on_one_device() and not quantized


def expert_rows(c: MoeConfig, T: int, real: int, quantized: bool):
    """(routed, computed) expert rows of one layer's moe_mlp over T token
    slots of which `real` are real: host arithmetic for the engine's
    counters, the routing itself stays on the device. Routed: real tokens
    x K. Computed: E x C on the capacity path; on the grouped path an
    upper bound, whole row tiles and one more for every expert whose
    block starts inside another's tile."""
    routed = real * c.num_experts_per_tok
    if not _takes_grouped(T, quantized):
        return routed, c.num_experts * expert_capacity(T, c)
    tiles = -(-routed // _GMM_ROWS) + min(c.num_experts, routed) - 1
    return routed, max(tiles, 0) * _GMM_ROWS


def step_work(c: MoeConfig, real_tokens: int, context_tokens: int,
              passes: int, *, weight_bytes: Optional[float] = None, **kw):
    """(useful operations, least HBM bytes) of one pipeline entry, as
    llama.step_work, with a routed layer's count: a token passes through
    the attention projections, the router and the K experts it is sent to
    (not the capacity the einsum pads to), and a pass reads at most
    min(E, its real rows x K) experts a layer."""
    wb = jnp.dtype(c.dtype).itemsize if weight_bytes is None \
        else weight_bytes
    expert = 3 * c.hidden_size * c.intermediate_size
    router = c.hidden_size * c.num_experts  # kept in f32
    rows = -(-real_tokens // max(passes, 1))  # real rows of one pass
    read = min(c.num_experts, rows * c.num_experts_per_tok)
    return llama.step_work(
        c, real_tokens, context_tokens, passes, weight_bytes=wb,
        layer_params=(
            llama.attention_params(c) + router
            + c.num_experts_per_tok * expert
        ),
        layer_bytes=(
            llama.attention_params(c) * wb + router * 4 + read * expert * wb
        ),
        **kw,
    )


@jax.named_scope("experts")
def moe_mlp(
    layer: Dict[str, Any], x: jax.Array, c: MoeConfig,
    valid: Optional[jax.Array] = None,
) -> jax.Array:
    """Sparse MoE block for x [T, H]: top-k routing, a softmax over the
    chosen logits, the chosen experts' SwiGLU, the weighted sum. Two ways
    through the experts, chosen at trace time from T and the mesh:

    * T < GROUPED_MIN_TOKENS (the decode block), any multi-device mesh, or
      quantized expert stacks: capacity-bounded one-hot dispatch into
      [E, C, H] buffers and matmuls batched over the expert axis. A token
      routed to an expert whose C = `expert_capacity(T)` slots are taken is
      DROPPED for that expert: at the default `capacity_factor` 1.25 a
      decode step can drop tokens, at E / K it cannot.
    * otherwise (mixed steps, prefill chunks): grouped matmuls over the
      rows that were routed (`_experts_grouped`). Dropless by construction;
      `capacity_factor` is not read. `valid` [T] marks the real slots of a
      padded flat buffer (`ragged_forward`); padding reaches no expert.
    """
    T, H = x.shape
    E, K = c.num_experts, c.num_experts_per_tok

    h = rms_norm(x, layer["mlp_norm"], c.rms_norm_eps)
    logits = jnp.dot(h.astype(jnp.float32), layer["router"])  # [T, E]
    topv, topi = jax.lax.top_k(logits, K)  # [T, K]
    probs = jax.nn.softmax(topv, axis=-1)  # renormalized over chosen experts

    if _takes_grouped(T, is_quant(layer["w_gate"])):
        out = _experts_grouped(layer, h, topi, probs, valid, c)
        return x + out.astype(c.dtype)

    w_gate, w_up, w_down = (
        w.array if isinstance(w, ExpertStack) else w
        for w in (layer["w_gate"], layer["w_up"], layer["w_down"])
    )
    C = expert_capacity(T, c)
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
    gate = qeinsum("ech,ehi->eci", expert_in, w_gate)
    up = qeinsum("ech,ehi->eci", expert_in, w_up)
    act = (jax.nn.silu(gate) * up).astype(c.dtype)
    expert_out = _constrain_ep(qeinsum("eci,eih->ech", act, w_down))

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
        _whole_expert_stacks(params), config, tokens, positions, kv_k, kv_v,
        page_table, context_len,
        last_idx=last_idx, mlp_fn=moe_mlp,
    )


def ragged_forward(
    params: Dict[str, Any],
    config: MoeConfig,
    tokens: jax.Array,  # [M] flat packed mixed prefill+decode buffer
    positions: jax.Array,  # [M]
    row_ids: jax.Array,  # [M]
    kv_k: jax.Array,
    kv_v: jax.Array,
    page_tables: jax.Array,  # [R, max_pages]
    row_starts: jax.Array,  # [R]
    row_lens: jax.Array,  # [R]
    ctx_lens: jax.Array,  # [R]
    last_flat: jax.Array,  # [R]
    lora=None,
    long_rows: Optional[int] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Unified mixed-step forward (engine `_dispatch_mixed`), MoE MLP —
    the flat buffer is already [tokens, H], exactly the shape expert
    dispatch wants. The buffer is compact (llama.ragged_forward): the
    step's real tokens lie back to back from slot 0, and the bucket's
    padding behind them is routed to no expert."""
    valid = jnp.arange(tokens.shape[0], dtype=jnp.int32) < row_lens.sum()
    return llama.ragged_forward(
        _whole_expert_stacks(params), config, tokens, positions, row_ids,
        kv_k, kv_v, page_tables, row_starts, row_lens, ctx_lens, last_flat,
        mlp_fn=functools.partial(moe_mlp, valid=valid), lora=lora,
        long_rows=long_rows,
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
        _whole_expert_stacks(params), config, tokens, positions, kv_k, kv_v,
        page_tables, context_lens, last_idx, mlp_fn=_moe_mlp_nd,
        emb_override=emb_override, emb_mask=emb_mask, all_logits=all_logits,
        lora=lora,
    )
