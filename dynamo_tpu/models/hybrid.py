"""Hybrid family: linear-attention layers with a recurrent state, a gated
softmax-attention layer every few, and a routed feed-forward part of which
this chip may hold a share (docs/hybrid_models.md).

Layers come in periods of `full_attention_interval`: all but the last of a
period mix tokens with a gated delta rule (a causal depthwise convolution
and a matrix-valued state per head, kept per LANE in ops/state_cache.py's
store), the last with gated softmax attention over the paged cache every
family shares (QK-norm, rotary on part of the head, a sigmoid output gate).
Every layer ends in a routed feed-forward part: a softmax over the router's
FULL width, the k largest, renormalised; the experts this chip holds,
`[first_expert_held, first_expert_held + num_experts)`, are applied to the
tokens that chose them through models/moe.py's grouped matmul (dropless by
construction; `capacity_factor` plays no part) and the others' part is left
out (it is the other chips'); a shared expert behind a sigmoid gate is
added once.

The forwards keep models/llama.py's signatures. `kv_k` is a
`StateCache`: pages, state store, the lanes of a dispatch's rows, and the
experts each token chose (the request plane's `routed_experts`). The
invariant every forward keeps: after it, a lane's state stands at exactly
the tokens whose keys and values it wrote for that lane. A row whose
context is 0 starts from a zero state whatever the lane held, so admission,
a lane's reuse and a preempted sequence's recomputation need no program of
their own.

Layers are stacked by kind and walked in the periods' order, each leaf
indexed once where it is used; the expert stacks stay whole (a slice handed
to a Pallas call is a copy: moe.ExpertStack) and a layer's experts are
groups `layer * held ...` of one grouped matmul.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops.kv_quant import kv_layer, kv_page_size, kv_write
from ..ops.paged_attention import (
    _pallas_eligible,
    paged_attention_decode,
    prefill_attention_batched,
    ragged_attention,
)
from ..ops.pallas_delta_step import delta_step_pallas, takes as kernel_takes
from ..ops.row_recurrence import flat_conv, rows_recurrence, step_in_store
from ..ops.state_cache import StateCache, StateSpec, state_bytes_per_lane
from . import llama, moe
from .llama import LlamaConfig
from .quant import embed_rows, qdot

f32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
#: what the engine calls a family that keeps a recurrent state per lane
#: beside the pages, in its refusals and its log (engine._stateful)
STATE_FAMILY = "the hybrid family (models/hybrid.py: a recurrent state per lane)"
#: tokens a chunk of the chunked recurrence holds (the step form's
#: sequential depth divides by it; the work inside a chunk grows with it)
CHUNK = 64
#: deviation of a seeded random matrix's elements (`init_params`)
INIT_SCALE = 0.02
#: the chunked recurrence's matmuls: float32 operands in three bf16 passes
#: (errors of 1e-5 of a product; `highest`, six passes, reads the same to
#: the tests' tolerance at twice the time)
CHUNK_PRECISION = jax.lax.Precision.HIGH


@dataclass(frozen=True)
class HybridConfig(LlamaConfig):
    full_attention_interval: int = 4
    partial_rotary_factor: float = 0.25
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    num_experts: int = 128  # the experts HELD on this chip
    router_width: int = 512  # the experts the router scores
    first_expert_held: int = 0
    num_experts_per_tok: int = 10
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    norm_topk_prob: bool = True
    state_dtype: Any = jnp.float32

    def __post_init__(self):
        if self.num_layers % self.full_attention_interval:
            raise ValueError(
                f"{self.num_layers} layers are no whole periods of "
                f"{self.full_attention_interval}"
            )
        if self.linear_num_value_heads % self.linear_num_key_heads:
            raise ValueError("value heads must be a multiple of key heads")
        if self.first_expert_held + self.num_experts > self.router_width:
            raise ValueError(
                f"experts [{self.first_expert_held}, "
                f"{self.first_expert_held + self.num_experts}) lie past the "
                f"router's width {self.router_width}"
            )

    def state_spec(self) -> StateSpec:
        P, Ll, Lf = periods(self)
        return StateSpec(
            state_layers=Ll, attention_layers=Lf, routed_layers=self.num_layers,
            state_shape=(self.linear_num_value_heads, self.linear_key_head_dim,
                         self.linear_value_head_dim),
            conv_shape=(self.linear_conv_kernel_dim - 1, conv_channels(self)),
            state_dtype=self.state_dtype,
            experts_per_token=self.num_experts_per_tok)

    @classmethod
    def tiny_hybrid(cls, **overrides):
        """CPU-test scale: two periods, a router twice as wide as the
        experts held, twice as many value heads as key heads."""
        kw = dict(
            vocab_size=512, hidden_size=64, intermediate_size=96,
            num_layers=8, num_heads=4, num_kv_heads=2, head_dim=16,
            max_position=2048, rope_theta=1e7, rms_norm_eps=1e-6,
            linear_num_key_heads=2, linear_num_value_heads=4,
            linear_key_head_dim=16, linear_value_head_dim=16,
            num_experts=4, router_width=8, first_expert_held=0,
            num_experts_per_tok=2, moe_intermediate_size=32,
            shared_expert_intermediate_size=32,
        )
        kw.update(overrides)
        return cls(**kw)


def periods(c: HybridConfig) -> Tuple[int, int, int]:
    """(periods, linear layers, full-attention layers)."""
    P = c.num_layers // c.full_attention_interval
    return P, c.num_layers - P, P


def conv_channels(c: HybridConfig) -> int:
    """Channels of the linear mixer's convolution: q, k and v side by side."""
    return (
        2 * c.linear_num_key_heads * c.linear_key_head_dim
        + c.linear_num_value_heads * c.linear_value_head_dim
    )


# ---------------------------------------------------------------------- #
# weights
# ---------------------------------------------------------------------- #


@functools.partial(jax.jit, static_argnums=(1, 2))
def dense_leaf(key, shape, dtype):
    """One stacked leaf of seeded normal weights, built in one program (no
    float32 copy of it beside the result)."""
    return (jax.random.normal(key, shape, f32) * INIT_SCALE).astype(dtype)


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def expert_stack_leaf(key, shape, dtype, layers, held, first):
    """[layers, held, *shape]: expert e of layer l from fold_in(fold_in(key,
    l), first + e), its GLOBAL id: a share's experts are the uncut model's,
    whichever share holds them."""
    def one(at):
        ke = jax.random.fold_in(jax.random.fold_in(key, at[0]), first + at[1])
        return (jax.random.normal(ke, shape, f32) * INIT_SCALE).astype(dtype)

    # an expert at a time (`lax.map`, not `vmap`: the generator's bits for
    # a key are the same only where the key is drawn from alone)
    l, e = jnp.meshgrid(jnp.arange(layers), jnp.arange(held), indexing="ij")
    stack = jax.lax.map(one, jnp.stack([l.ravel(), e.ravel()], axis=1))
    return stack.reshape(layers, held, *shape)


def init_params(config: HybridConfig, key: jax.Array) -> Dict[str, Any]:
    """Seeded random weights. Every stacked leaf is built once, in one
    jitted call (no list of layers beside the stack). Matrices are named
    `w*`, `embed`, `lm_head` (the int8 control rounds those); norms,
    `a_log`, `dt_bias` and the float32 router are not."""
    c = config
    P, Ll, Lf = periods(c)
    L, H, D = c.num_layers, c.hidden_size, c.head_dim
    nk, nv = c.linear_num_key_heads, c.linear_num_value_heads
    dk, dv = c.linear_key_head_dim, c.linear_value_head_dim
    Kd, Vd = nk * dk, nv * dv
    E, I, Is = c.num_experts, c.moe_intermediate_size, \
        c.shared_expert_intermediate_size
    # the device's own bit generator (`rbg`): a chip's 3.7 G weights in
    # seconds where threefry's arithmetic takes a minute and a half; the
    # seed is the caller's key all the same
    key = jax.random.wrap_key_data(
        jnp.tile(jax.random.key_data(key).ravel()[:2], 2), impl="rbg")
    names = iter(jax.random.split(key, 40))

    def dense(shape, dtype=None):
        return dense_leaf(next(names), shape, dtype or c.dtype)

    def experts(shape):
        return expert_stack_leaf(next(names), shape, c.dtype, L, E,
                             jnp.int32(c.first_expert_held))

    # decay as the delta-rule layers are initialised: A in (0, 16), a step
    # size of 0.001 to 0.1 behind the softplus, so a state remembers tens
    # of tokens
    ka, kd = next(names), next(names)
    dt = jnp.exp(jax.random.uniform(
        kd, (Ll, nv), f32, jnp.log(0.001), jnp.log(0.1)))
    linear = {
        "norm": dense((Ll, H), f32),
        "w_qkvz": dense((Ll, H, 2 * Kd + 2 * Vd)),
        "w_ba": dense((Ll, H, 2 * nv)),
        "w_conv": dense((Ll, conv_channels(c), c.linear_conv_kernel_dim))
        * (0.5 / INIT_SCALE),
        "a_log": jnp.log(jax.random.uniform(ka, (Ll, nv), f32, 0.1, 16.0)),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "out_norm": 1.0 + dense((Ll, dv), f32),
        "w_out": dense((Ll, Vd, H)),
    }
    full = {
        "norm": dense((Lf, H), f32),
        "wq": dense((Lf, H, c.num_heads * 2 * D)),
        "wk": dense((Lf, H, c.num_kv_heads * D)),
        "wv": dense((Lf, H, c.num_kv_heads * D)),
        "q_norm": dense((Lf, D), f32),
        "k_norm": dense((Lf, D), f32),
        "wo": dense((Lf, c.num_heads * D, H)),
    }
    routed = {
        "norm": dense((L, H), f32),
        # float32: tiny, and a routing decision is sensitive to rounding
        "router": dense((L, H, c.router_width), f32),
        "w_gate": experts((H, I)),
        "w_up": experts((H, I)),
        "w_down": experts((I, H)),
        "ws_gate": dense((L, H, Is)),
        "ws_up": dense((L, H, Is)),
        "ws_down": dense((L, Is, H)),
        "w_shared_gate": dense((L, H, 1)),
    }
    return {
        "embed": dense((c.vocab_size, H)),
        "layers": {"linear": linear, "full": full, "moe": routed},
        "final_norm": dense((H,), f32),
        "lm_head": dense((H, c.vocab_size)),
    }


# ---------------------------------------------------------------------- #
# pieces
# ---------------------------------------------------------------------- #


def norm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    """Zero-centred RMSNorm in float32: x * rsqrt(mean(x^2) + eps) * (1 + w)."""
    x32 = x.astype(f32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps) * (1.0 + w.astype(f32))).astype(
        x.dtype)


def partial_rope(x: jax.Array, positions: jax.Array, c: HybridConfig):
    """Rotary (rotate-half) on the first `partial_rotary_factor` of the
    head, the rest untouched. x [..., heads, D]; positions [...]."""
    rot = int(c.head_dim * c.partial_rotary_factor)
    cos, sin = llama.rope_cos_sin(positions, rot, c.rope_theta)
    return jnp.concatenate(
        [llama.apply_rope(x[..., :rot], cos, sin), x[..., rot:]], axis=-1)


def _qkv_gate(layer, h, positions, c: HybridConfig):
    """The full-attention layer's projections of h [..., H]: q [..., NH, D]
    and k [..., KH, D] normed and rotated, v, and the output gate
    [..., NH * D]."""
    D = c.head_dim
    qg = qdot(h, layer["wq"]).astype(c.dtype)
    qg = qg.reshape(*h.shape[:-1], c.num_heads, 2 * D)
    q, gate = qg[..., :D], qg[..., D:]
    k = qdot(h, layer["wk"]).astype(c.dtype)
    v = qdot(h, layer["wv"]).astype(c.dtype)
    k = k.reshape(*h.shape[:-1], c.num_kv_heads, D)
    v = v.reshape(*h.shape[:-1], c.num_kv_heads, D)
    q = partial_rope(norm(q, layer["q_norm"], c.rms_norm_eps), positions, c)
    k = partial_rope(norm(k, layer["k_norm"], c.rms_norm_eps), positions, c)
    return q, k, v, gate.reshape(*h.shape[:-1], c.num_heads * D)


def _gated_out(layer, attn, gate, c: HybridConfig):
    """o_proj(attn * sigmoid(gate)); attn [..., NH, D]."""
    attn = attn.reshape(gate.shape).astype(f32)
    gated = (attn * jax.nn.sigmoid(gate.astype(f32))).astype(c.dtype)
    return qdot(gated, layer["wo"]).astype(c.dtype)


def _mixer_inputs(layer, h, c: HybridConfig):
    """The linear mixer's projections of h [..., H]: the convolution's
    input [..., C] (q, k, v side by side), the output gate z [..., nv, dv],
    beta [..., nv] and the log decay g [..., nv] (float32)."""
    nv, dv = c.linear_num_value_heads, c.linear_value_head_dim
    C = conv_channels(c)
    qkvz = qdot(h, layer["w_qkvz"]).astype(c.dtype)
    ba = qdot(h, layer["w_ba"]).astype(f32)
    mixed, z = qkvz[..., :C], qkvz[..., C:]
    beta = jax.nn.sigmoid(ba[..., :nv])
    g = -jnp.exp(layer["a_log"].astype(f32)) * jax.nn.softplus(
        ba[..., nv:] + layer["dt_bias"].astype(f32))
    return mixed, z.reshape(*h.shape[:-1], nv, dv), beta, g


def _split_qkv(y, c: HybridConfig):
    """The convolution's output y [..., C] (after SiLU) as q, k [..., nv,
    dk] (L2-normalised, q scaled, each key head repeated for its value
    heads) and v [..., nv, dv], float32."""
    nk, nv = c.linear_num_key_heads, c.linear_num_value_heads
    dk, dv = c.linear_key_head_dim, c.linear_value_head_dim
    Kd = nk * dk
    y = y.astype(f32)
    q = y[..., :Kd].reshape(*y.shape[:-1], nk, dk)
    k = y[..., Kd:2 * Kd].reshape(*y.shape[:-1], nk, dk)
    v = y[..., 2 * Kd:].reshape(*y.shape[:-1], nv, dv)

    def l2(x):
        return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)

    q = jnp.repeat(l2(q) * dk ** -0.5, nv // nk, axis=-2)
    k = jnp.repeat(l2(k), nv // nk, axis=-2)
    return q, k, v


def _mixer_out(layer, o, z, c: HybridConfig):
    """The gated norm over each head (plain weight) and out_proj:
    o, z [..., nv, dv] -> [..., H]."""
    o = o.astype(f32)
    var = jnp.mean(o * o, axis=-1, keepdims=True)
    o = o * jax.lax.rsqrt(var + c.rms_norm_eps) * layer["out_norm"].astype(f32)
    o = (o * jax.nn.silu(z.astype(f32))).astype(c.dtype)
    return qdot(o.reshape(*o.shape[:-2], -1), layer["w_out"]).astype(c.dtype)


def delta_step(S, q, k, v, g, beta):
    """One token of the gated delta rule, every head of every row at once,
    float32 on the vector unit (products and sums, no matmul's rounding):
    S [..., dk, dv]; q, k [..., dk]; v [..., dv]; g, beta [...].
    S <- exp(g) S; d = beta (v - S^T k); S <- S + k d^T; o = S^T q."""
    S = S * jnp.exp(g)[..., None, None]
    d = beta[..., None] * (v - jnp.sum(S * k[..., :, None], axis=-2))
    S = S + k[..., :, None] * d[..., None, :]
    return S, jnp.sum(S * q[..., :, None], axis=-2)


def recurrence_impl(c: HybridConfig) -> str:
    """Which implementation a decode step's recurrence takes: "pallas"
    (ops/pallas_delta_step.py: a lane's state read once and written once, in
    place in the store) under the gate the attention kernels have (a TPU
    backend, an engine of one device, the value head's width whole lane
    registers: paged_attention._pallas_eligible) and where the kernel takes
    the state's shape and dtype; "xla" (`delta_step`) everywhere else. A
    mixed step's one-token rows that go on from a lane's state take the
    same (`lanes_step`); the rows it gathers and the batched prefill keep
    `delta_step` for a row's first token whatever this says. What the
    engine logs at start and publishes as
    stats()["attention_impl"]["recurrence"]."""
    spec = c.state_spec()
    eligible = (kernel_takes(spec.state_shape, spec.state_dtype)
                and _pallas_eligible(c.linear_value_head_dim))
    return "pallas" if eligible else "xla"


def lanes_step(state, ll, q, k, v, g, beta, live, *, impl: str):
    """One token a lane of the gated delta rule over the state store IN
    PLACE, the one function the decode step and a mixed step's one-token
    rows share (ops/row_recurrence.py): state [state layers, lanes + 1, nv,
    dk, dv] at layer `ll`; q, k [B, nv, dk]; v [B, nv, dv]; g, beta [B, nv];
    live [B] (row b IS lane b: a lane that is not keeps its state bit for
    bit). -> (state, o [B, nv, dv]). `impl`: what `recurrence_impl` said:
    the Pallas kernel, or `delta_step` on the layer's first B slots
    (row_recurrence.step_in_store)."""
    if impl == "pallas":
        return delta_step_pallas(state, ll, q, k, v, g, beta, live)
    return step_in_store(delta_step, state, ll, q, k, v, g, beta, live=live)


def delta_chunk(S, q, k, v, g, beta):
    """CHUNK tokens of the same recurrence in closed form (the chunked
    gated delta rule): S [R, nv, dk, dv]; q, k [R, C, nv, dk]; v [R, C, nv,
    dv]; g, beta [R, C, nv]. A token whose beta and g are 0 leaves the
    state as it was. -> (S after the chunk, o [R, C, nv, dv]). Matmuls of
    float32 operands in three bf16 passes (CHUNK_PRECISION): the state is
    what later tokens read, and one pass would round it as bf16 does."""
    def mm(spec, a, b):
        return jnp.einsum(spec, a, b, precision=CHUNK_PRECISION,
                          preferred_element_type=f32)

    q, k, v = (jnp.moveaxis(x, 1, 2) for x in (q, k, v))  # [R, nv, C, d]
    g, beta = jnp.moveaxis(g, 1, 2), jnp.moveaxis(beta, 1, 2)  # [R, nv, C]
    C = q.shape[2]
    G = jnp.cumsum(g, axis=-1)
    lower = jnp.tril(jnp.ones((C, C), bool))
    decay = jnp.exp(jnp.where(lower, G[..., :, None] - G[..., None, :],
                              -jnp.inf))  # [R, nv, C, C], 0 above the diagonal
    kb, vb = k * beta[..., None], v * beta[..., None]
    # (I + tril(kb k^T * decay, -1))^-1 as the finite series of a
    # nilpotent matrix: (I + A)(I + A^2)(I + A^4)... with A = -tril(...)
    A = -mm("rhcd,rhed->rhce", kb, k) * decay * jnp.tril(
        jnp.ones((C, C), f32), -1)
    eye = jnp.eye(C, dtype=f32)
    T = eye + A
    power = 2
    while power < C:
        A = mm("rhce,rhef->rhcf", A, A)
        T = mm("rhce,rhef->rhcf", T, eye + A)
        power *= 2
    value = mm("rhce,rhed->rhcd", T, vb)
    k_cum = mm("rhce,rhed->rhcd", T, kb * jnp.exp(G)[..., None])
    v_new = value - mm("rhck,rhkd->rhcd", k_cum, S)
    o = mm("rhck,rhkd->rhcd", q * jnp.exp(G)[..., None], S) + mm(
        "rhce,rhed->rhcd", mm("rhcd,rhed->rhce", q, k) * decay, v_new)
    last = G[..., -1:]
    S = S * jnp.exp(last)[..., None] + mm(
        "rhck,rhcd->rhkd", k * jnp.exp(last - G)[..., None], v_new)
    return S, jnp.moveaxis(o, 2, 1)


# ---------------------------------------------------------------------- #
# the routed part
# ---------------------------------------------------------------------- #


def route(h, router, c: HybridConfig):
    """(experts chosen [T, K] under the router's full width, their weights
    [T, K]): softmax over all the router's scores in float32, the K
    largest, divided by their sum under `norm_topk_prob`."""
    logits = jnp.dot(h.astype(f32), router, precision=HIGHEST)
    top, idx = jax.lax.top_k(logits, c.num_experts_per_tok)
    if c.norm_topk_prob:
        return idx, jax.nn.softmax(top, axis=-1)
    return idx, jnp.exp(top - jax.nn.logsumexp(logits, -1, keepdims=True))


def routed_block(layer, stacks, li, x, c: HybridConfig, valid=None):
    """y = x + routed(rms(x)) + shared(rms(x)) for x [T, H]; also the
    experts chosen [T, K] (ids under the router's full width, held here or
    not)."""
    h = norm(x, layer["norm"], c.rms_norm_eps)
    with jax.named_scope("experts"):
        idx, weight = route(h, layer["router"], c)
        out = moe.experts_held(
            stacks, li, h, idx, weight, valid, form="gated_silu",
            held=c.num_experts, first=c.first_expert_held, dtype=c.dtype)
    with jax.named_scope("shared_expert"):
        act = (jax.nn.silu(qdot(h, layer["ws_gate"]))
               * qdot(h, layer["ws_up"])).astype(c.dtype)
        shared = qdot(act, layer["ws_down"])
        out = out + jax.nn.sigmoid(qdot(h, layer["w_shared_gate"])) * shared
    return x + out.astype(c.dtype), idx.astype(jnp.int32)


# ---------------------------------------------------------------------- #
# the layer stack
# ---------------------------------------------------------------------- #


def _layer_stack(params, c: HybridConfig, x, cache: StateCache, kv_v,
                 linear_fn, full_fn, valid=None):
    """x [T, H] (any leading shape the two mixers take) through the layers
    in the periods' order: `linear_fn(layer, h, state, conv, ll) -> (out,
    state, conv)` on the normed input of linear layer `ll`, `full_fn(layer,
    h, pages, kv_v, lf) -> (out, pages, kv_v)` of full-attention layer `lf`.
    A layer's leaves are taken from the STORED stacks with one static index
    each (a stack reshaped by period and indexed twice is a period's
    weights copied every step: PERF.md, PR 48).
    -> (x, cache with pages, state and conv as the layers left them, kv_v,
    the experts chosen [L, tokens, K])."""
    n = c.full_attention_interval
    layers = params["layers"]
    stacks = {k: layers["moe"][k] for k in ("w_gate", "w_up", "w_down")}
    small = {k: v for k, v in layers["moe"].items() if k not in stacks}
    lead = x.shape[:-1]
    pages, state, conv = cache.pages, cache.state, cache.conv
    chosen = []
    for li in range(c.num_layers):
        p, j = divmod(li, n)
        if j < n - 1:
            ll = p * (n - 1) + j
            layer = jax.tree.map(lambda a: a[ll], layers["linear"])
            with jax.named_scope("linear_mixer"):
                h = norm(x, layer["norm"], c.rms_norm_eps)
                out, state, conv = linear_fn(layer, h, state, conv, ll)
        else:
            layer = jax.tree.map(lambda a: a[p], layers["full"])
            with jax.named_scope("gated_attention"):
                h = norm(x, layer["norm"], c.rms_norm_eps)
                out, pages, kv_v = full_fn(layer, h, pages, kv_v, p)
        x = x + out
        y, idx = routed_block(
            jax.tree.map(lambda a: a[li], small), stacks, li,
            x.reshape(-1, x.shape[-1]), c, valid)
        x = y.reshape(*lead, -1)
        chosen.append(idx)
    cache = cache.replace(pages=pages, state=state, conv=conv)
    return x, cache, kv_v, jnp.stack(chosen)


def _head(params, c: HybridConfig, x):
    with jax.named_scope("head_and_sample"):
        x = norm(x, params["final_norm"], c.rms_norm_eps)
        return qdot(x, params["lm_head"])


def _note_chosen(routed_flat, chosen):
    """The experts a dispatch chose for its token slots [L, M, K] into the
    cache's leaf (slots past its room, which the engine sizes for its
    largest dispatch, are not kept)."""
    room = routed_flat.shape[1]
    return jax.lax.dynamic_update_slice(routed_flat, chosen[:, :room], (0, 0, 0))


def _refuse(lora, emb_override=None):
    if lora is not None or emb_override is not None:
        raise NotImplementedError(
            "the hybrid family (models/hybrid.py) takes no LoRA adapter and "
            "no multimodal embedding rows"
        )


# ---------------------------------------------------------------------- #
# decode: one token a lane, the step form
# ---------------------------------------------------------------------- #


def decode_forward(
    params: Dict[str, Any],
    config: HybridConfig,
    tokens: jax.Array,  # [B] one new token per lane: row b IS lane b
    positions: jax.Array,  # [B]
    kv_k: StateCache,
    kv_v: jax.Array,
    page_tables: jax.Array,  # [B, max_pages]
    seq_lens: jax.Array,  # [B] lengths INCLUDING the new token
    lora=None,
) -> Tuple[jax.Array, StateCache, jax.Array]:
    """One decode step for the whole slot batch; returns (logits [B,
    vocab], cache, kv_v). A lane whose table row is scratch (not decoding:
    free, or between two chunks of its prompt) keeps its state."""
    _refuse(lora)
    c = config
    B = tokens.shape[0]
    page_size = kv_page_size(kv_k.pages)
    live = page_tables[:, 0] != 0  # the engine's scratch page is 0
    with jax.named_scope("embed"):
        x = embed_rows(params["embed"], tokens, c.dtype)
    P_tab = page_tables.shape[1]
    logical = jnp.minimum(positions // page_size, P_tab - 1)
    phys = jnp.take_along_axis(page_tables, logical[:, None], axis=1)[:, 0]
    phys = jnp.where(positions < P_tab * page_size, phys, 0)
    offs = positions % page_size
    step_lanes = functools.partial(lanes_step, impl=recurrence_impl(c))

    def linear_fn(layer, h, state, conv, ll):
        mixed, z, beta, g = _mixer_inputs(layer, h, c)
        tail = jax.lax.dynamic_index_in_dim(conv, ll, 0, False)[:B]
        window = jnp.concatenate([tail, mixed[:, None]], axis=1)  # [B, 4, C]
        y = jnp.einsum("btc,ct->bc", window.astype(f32),
                       layer["w_conv"].astype(f32))
        q, k, v = _split_qkv(jax.nn.silu(y), c)
        state, o = step_lanes(state, ll, q, k, v, g, beta, live)
        tail = jnp.where(live[:, None, None], window[:, 1:], tail)
        conv = jax.lax.dynamic_update_slice(conv, tail[None], (ll, 0, 0, 0))
        return _mixer_out(layer, o, z, c), state, conv

    def full_fn(layer, h, pages, kv_v, lf):
        q, k, v, gate = _qkv_gate(layer, h, positions, c)
        pages = kv_write(pages, lf, phys, offs, k)
        kv_v = kv_write(kv_v, lf, phys, offs, v)
        attn = paged_attention_decode(
            q, kv_layer(pages, lf), kv_layer(kv_v, lf), page_tables, seq_lens)
        return _gated_out(layer, attn, gate, c), pages, kv_v

    x, cache, kv_v, chosen = _layer_stack(
        params, c, x, kv_k, kv_v, linear_fn, full_fn)
    ring = cache.routed_ring
    ring = ring.at[positions % ring.shape[0], :, jnp.arange(B)].set(
        jnp.moveaxis(chosen, 1, 0))
    return _head(params, c, x), cache.replace(routed_ring=ring), kv_v


# ---------------------------------------------------------------------- #
# rows of many tokens: the chunked form over a flat token axis
# ---------------------------------------------------------------------- #


def _flat_linear_fn(c: HybridConfig, lanes, row_ids, row_starts, row_lens,
                    ctx_lens, long_rows: int):
    """The linear mixer over a flat axis of M token slots that R rows
    share (row r: slots row_starts[r] ... + row_lens[r], lane lanes[r],
    ctx_lens[r] tokens of its sequence before it). A row starts from its
    lane's state, or from zero where its context is 0, and leaves the
    state behind its last token in the lane. The convolution and the
    recurrence's two roads (a row of one token that goes on from its lane's
    state: `lanes_step` over the store, as in a decode step; the other
    rows, of which the caller expects `long_rows` at most: gathered a few
    a group, the step form for the first token and the chunked form for
    what is left) are ops/row_recurrence.py's."""
    fresh = ctx_lens == 0
    step_lanes = functools.partial(lanes_step, impl=recurrence_impl(c))

    def linear_fn(layer, h, state, conv, ll):
        mixed, z, beta, g = _mixer_inputs(layer, h, c)
        # the taps - 1 inputs before a row's first token come from the
        # lane's tail (zero for a sequence's first chunk)
        tails = jnp.where(fresh[:, None, None], 0, conv[ll, lanes])
        y, new_tails = flat_conv(
            mixed, layer["w_conv"].astype(f32), tails, row_ids, row_starts,
            row_lens)
        q, k, v = _split_qkv(jax.nn.silu(y), c)
        # (a zero row's beta and g of 0 leave a state as it was)
        state, o = rows_recurrence(
            state, ll, lanes, ctx_lens, (q, k, v, g, beta),
            (c.linear_num_value_heads, c.linear_value_head_dim),
            step_lanes, delta_step, delta_chunk, CHUNK, row_starts, row_lens,
            long_rows)
        conv = conv.at[ll, lanes].set(new_tails.astype(conv.dtype))
        return _mixer_out(layer, o, z, c), state, conv

    return linear_fn


def ragged_forward(
    params: Dict[str, Any],
    config: HybridConfig,
    tokens: jax.Array,  # [M] flat packed: prefill chunks + decode singletons
    positions: jax.Array,  # [M]
    row_ids: jax.Array,  # [M]
    kv_k: StateCache,  # its `lanes` [>= R]: the lane of each row
    kv_v: jax.Array,
    page_tables: jax.Array,  # [R, max_pages]
    row_starts: jax.Array,  # [R]
    row_lens: jax.Array,  # [R]
    ctx_lens: jax.Array,  # [R]
    last_flat: jax.Array,  # [R]
    lora=None,
    long_rows: Optional[int] = None,
) -> Tuple[jax.Array, StateCache, jax.Array]:
    """The mixed step's forward over a compact flat buffer (see
    models/llama.py:ragged_forward): a row of one token takes the decode
    step's recurrence over its lane, a prompt's chunk the gathered one,
    each from its own lane's state. `long_rows`: the rows of more than one
    token a pack holds at most, for the ragged kernel's grid (None: any row
    may) and for the recurrence's gathered rows (None: the rows that the
    lanes' decode rows leave of R). Returns (logits of each row's last
    token [R, vocab], cache, kv_v)."""
    _refuse(lora)
    c = config
    M, R = tokens.shape[0], row_lens.shape[0]
    lanes = kv_k.lanes[:R]
    with jax.named_scope("embed"):
        x = embed_rows(params["embed"], tokens, c.dtype)
    page_size = kv_page_size(kv_k.pages)
    P_tab = page_tables.shape[1]
    tab_tok = page_tables[row_ids]
    logical = jnp.minimum(positions // page_size, P_tab - 1)
    phys = jnp.take_along_axis(tab_tok, logical[:, None], axis=1)[:, 0]
    phys = jnp.where(positions < P_tab * page_size, phys, 0)
    offs = positions % page_size
    valid = jnp.arange(M, dtype=jnp.int32) < row_lens.sum()

    def full_fn(layer, h, pages, kv_v, lf):
        q, k, v, gate = _qkv_gate(layer, h, positions, c)
        pages = kv_write(pages, lf, phys, offs, k)
        kv_v = kv_write(kv_v, lf, phys, offs, v)
        attn = ragged_attention(
            q, kv_layer(pages, lf), kv_layer(kv_v, lf), page_tables,
            row_starts, row_lens, ctx_lens, long_rows=long_rows)
        return _gated_out(layer, attn, gate, c), pages, kv_v

    x, cache, kv_v, chosen = _layer_stack(
        params, c, x, kv_k, kv_v,
        _flat_linear_fn(
            c, lanes, row_ids, row_starts, row_lens, ctx_lens,
            # a mixed step's rows: a decode row a lane and a prefill batch
            long_rows if long_rows is not None
            else max(R - kv_k.scratch_lane, 1)),
        full_fn, valid)
    flat = _note_chosen(cache.routed_flat, chosen)
    return _head(params, c, x[last_flat]), cache.replace(routed_flat=flat), kv_v


def prefill_forward_batched(
    params: Dict[str, Any],
    config: HybridConfig,
    tokens: jax.Array,  # [B, T] one chunk per sequence (padded to bucket)
    positions: jax.Array,  # [B, T]
    kv_k: StateCache,  # its `lanes` [>= B]: the lane of each row
    kv_v: jax.Array,
    page_tables: jax.Array,  # [B, max_pages]
    context_lens: jax.Array,  # [B]
    last_idx: jax.Array,  # [B] index of the last REAL token per chunk
    emb_override=None,
    emb_mask=None,
    all_logits: bool = False,
    lora=None,
) -> Tuple[jax.Array, StateCache, jax.Array]:
    """Batched chunked prefill. The linear mixers see the chunks as rows of
    one flat axis (row b: slots b * T ..., last_idx[b] + 1 real ones), the
    attention layer as the batch it is. Returns (logits_last [B, vocab],
    cache, kv_v)."""
    _refuse(lora, emb_override)
    if all_logits:
        raise NotImplementedError(
            "the hybrid family cannot verify drafts: a state has no rollback"
        )
    c = config
    B, T = tokens.shape
    lanes = kv_k.lanes[:B]
    with jax.named_scope("embed"):
        x = embed_rows(params["embed"], tokens, c.dtype).reshape(B * T, -1)
    page_size = kv_page_size(kv_k.pages)
    total_lens = context_lens + last_idx + 1
    P_tab = page_tables.shape[1]
    logical = jnp.minimum(positions // page_size, P_tab - 1)
    phys = jnp.take_along_axis(page_tables, logical, axis=1)
    phys = jnp.where(positions < P_tab * page_size, phys, 0)
    offs = positions % page_size
    row_lens = last_idx + 1
    row_starts = jnp.arange(B, dtype=jnp.int32) * T
    row_ids = jnp.repeat(jnp.arange(B, dtype=jnp.int32), T)
    valid = (jnp.arange(T)[None, :] < row_lens[:, None]).reshape(B * T)

    def full_fn(layer, h, pages, kv_v, lf):
        q, k, v, gate = _qkv_gate(
            layer, h.reshape(B, T, -1), positions, c)
        pages = kv_write(pages, lf, phys, offs, k)
        kv_v = kv_write(kv_v, lf, phys, offs, v)
        attn = prefill_attention_batched(
            q, kv_layer(pages, lf), kv_layer(kv_v, lf), positions,
            page_tables, total_lens, context_lens)
        return _gated_out(layer, attn, gate, c).reshape(B * T, -1), pages, kv_v

    x, cache, kv_v, chosen = _layer_stack(
        params, c, x, kv_k, kv_v,
        _flat_linear_fn(c, lanes, row_ids, row_starts, row_lens, context_lens,
                        long_rows=B),
        full_fn, valid)
    flat = _note_chosen(cache.routed_flat, chosen)
    last = x[row_starts + last_idx]
    return _head(params, c, last), cache.replace(routed_flat=flat), kv_v


def prefill_forward(
    params: Dict[str, Any],
    config: HybridConfig,
    tokens: jax.Array,  # [chunk]
    positions: jax.Array,  # [chunk]
    kv_k: StateCache,  # its `lanes[0]`: the sequence's lane
    kv_v: jax.Array,
    page_table: jax.Array,  # [max_pages]
    context_len: jax.Array,
    last_idx: Optional[jax.Array] = None,
) -> Tuple[jax.Array, StateCache, jax.Array]:
    """One prompt chunk of a single sequence: the batch of one."""
    T = tokens.shape[0]
    last = jnp.asarray(T - 1 if last_idx is None else last_idx, jnp.int32)
    logits, cache, kv_v = prefill_forward_batched(
        params, config, tokens[None], positions[None], kv_k, kv_v,
        page_table[None], jnp.asarray(context_len, jnp.int32)[None],
        last[None])
    return logits[0], cache, kv_v


# ---------------------------------------------------------------------- #
# host arithmetic for the engine's counters
# ---------------------------------------------------------------------- #


def held_share(c: HybridConfig) -> float:
    return c.num_experts / c.router_width


def expert_rows(c: HybridConfig, T: int, real: int, quantized: bool = False):
    """(routed, computed) expert rows of one layer over T token slots of
    which `real` are real (moe.held_expert_rows)."""
    return moe.held_expert_rows(
        c.num_experts, c.router_width, c.num_experts_per_tok, T, real)


def step_work(c: HybridConfig, real_tokens: int, context_tokens: int,
              passes: int, *, sampled: Optional[int] = None,
              kv_tokens: Optional[int] = None,
              weight_bytes: Optional[float] = None,
              kv_bytes: Optional[float] = None,
              rows: Optional[int] = None):
    """(useful operations, least HBM bytes, of those the recurrent state's)
    of one pipeline entry, as llama.step_work counts them. A real token
    passes through every mixer, the router, its K chosen experts' share
    held here, the shared expert and, where sampled, the head; a linear
    layer's recurrence adds the decay and three products over a dk x dv
    state for each value head. Bytes: per pass the weights once, with
    min(held, real rows x K x share) experts a layer; the state read and
    written once for each of `rows` (row, pass) pairs (a decode block: one
    a token; a chunk of a prompt: one); the context's pages."""
    P, Ll, Lf = periods(c)
    wb = jnp.dtype(c.dtype).itemsize if weight_bytes is None else weight_bytes
    if kv_bytes is None:
        kv_bytes = 2 * c.num_kv_heads * c.head_dim * jnp.dtype(c.dtype).itemsize
    sampled = real_tokens if sampled is None else sampled
    kv_tokens = context_tokens if kv_tokens is None else kv_tokens
    rows = real_tokens if rows is None else rows
    H, D = c.hidden_size, c.head_dim
    nv, dk, dv = (c.linear_num_value_heads, c.linear_key_head_dim,
                  c.linear_value_head_dim)
    C = conv_channels(c)
    linear = H * (C + nv * dv) + H * 2 * nv + nv * dv * H
    full = (H * c.num_heads * 2 * D + 2 * H * c.num_kv_heads * D
            + c.num_heads * D * H)
    expert = 3 * H * c.moe_intermediate_size
    shared = 3 * H * c.shared_expert_intermediate_size + H
    router = H * c.router_width  # float32
    head = H * c.vocab_size
    share = held_share(c)
    K = c.num_experts_per_tok
    flops = (
        2 * real_tokens * (
            Ll * linear + Lf * full
            + c.num_layers * (router + K * share * expert + shared))
        + real_tokens * Ll * (2 * C * c.linear_conv_kernel_dim
                              + 7 * nv * dk * dv)
        + 4 * Lf * c.num_heads * D * context_tokens
        + 2 * head * sampled
    )
    one_pass = -(-real_tokens // max(passes, 1))
    read = min(c.num_experts, one_pass * K * share)
    state = 2 * rows * state_bytes_per_lane(c)
    nbytes = (
        passes * (
            (Ll * (linear + C * c.linear_conv_kernel_dim) + Lf * full) * wb
            + c.num_layers * (router * 4 + (read * expert + shared) * wb)
            + head * wb)
        + state
        + Lf * kv_bytes * (kv_tokens + real_tokens)
    )
    return int(flops), int(nbytes), int(state)
