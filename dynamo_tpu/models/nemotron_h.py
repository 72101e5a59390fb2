"""Nemotron-H family: ONE sublayer a layer, chosen by a pattern string, each
behind a plain RMSNorm and a residual (docs/hybrid_models.md):

    M  Mamba-2: a fused input projection [z | xBC | dt], a causal depthwise
       convolution of a few taps with a bias, a state `[heads, head dim,
       state size]` per LANE stepped by `h <- exp(dt A) h + dt x (x) B`,
       `y = h C + D x`, a gated norm over groups, an output projection;
    *  softmax attention over the paged cache every family shares: grouped
       queries, no bias, NO rotary (the family embeds no position);
    E  LatentMoE: sigmoid scores over the router's FULL width, the k largest
       of score + a choice bias, weights the scores at the chosen over their
       sum times a scaling factor; the experts (two matrices, relu squared)
       work in a latent space the token is projected down into and back up
       out of; a shared expert on the full width is added once. This chip
       holds experts `[first_expert_held, first_expert_held + num_experts)`
       and leaves the others' part out: it is the other chips'.

The forwards keep models/llama.py's signatures and models/hybrid.py's
contract: `kv_k` is a `StateCache` (the K pages of the `*` layers, the state
store of the `M` layers, the lanes of a dispatch's rows, the experts each
token chose in the `E` layers), and after any forward a lane's state stands
at exactly the tokens whose keys and values it wrote for that lane; a row of
context 0 starts from a zero state. Layers are stacked by KIND and unrolled
in the pattern's order; the expert stacks stay whole (moe.ExpertStack).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops.kv_quant import kv_layer, kv_page_size, kv_write
from ..ops.paged_attention import (
    paged_attention_decode,
    prefill_attention_batched,
    ragged_attention,
)
from ..ops.row_recurrence import flat_conv, rows_recurrence, step_in_store
from ..ops.state_cache import StateCache, StateSpec, state_bytes_per_lane
from . import moe
from .hybrid import (
    CHUNK_PRECISION,
    INIT_SCALE,
    _note_chosen,
    dense_leaf,
    expert_stack_leaf,
)
from .llama import LlamaConfig
from .quant import embed_rows, qdot

f32 = jnp.float32
#: what the engine calls this family in its refusals and its log
STATE_FAMILY = (
    "the Nemotron-H family (models/nemotron_h.py: a state-space state per lane)"
)
KINDS = "ME*"  # state-space mixer, routed part, attention


@dataclass(frozen=True)
class NemotronHConfig(LlamaConfig):
    pattern: str = "MEMEMEM*EME"  # hybrid_override_pattern: a layer a character
    mamba_num_heads: int = 128
    mamba_head_dim: int = 64
    ssm_state_size: int = 128
    n_groups: int = 8  # of B and C, and of the gated norm
    conv_kernel: int = 4
    chunk_size: int = 128  # tokens a chunk of the chunked (SSD) form holds
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    num_experts: int = 128  # the experts HELD on this chip
    router_width: int = 512  # the experts the router scores
    first_expert_held: int = 0
    num_experts_per_tok: int = 22
    moe_intermediate_size: int = 2688
    moe_latent_size: int = 1024
    shared_expert_intermediate_size: int = 5376
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 5.0
    state_dtype: Any = jnp.float32

    def __post_init__(self):
        if len(self.pattern) != self.num_layers or set(self.pattern) - set(KINDS):
            raise ValueError(
                f"pattern {self.pattern!r} is no {self.num_layers} layers of {KINDS!r}")
        if self.mamba_num_heads % self.n_groups:
            raise ValueError("state-space heads must be a multiple of n_groups")
        if self.first_expert_held + self.num_experts > self.router_width:
            raise ValueError(
                f"experts [{self.first_expert_held}, "
                f"{self.first_expert_held + self.num_experts}) lie past the "
                f"router's width {self.router_width}"
            )

    def state_spec(self) -> StateSpec:
        Lm, Le, La = kinds(self)
        return StateSpec(
            state_layers=Lm, attention_layers=La, routed_layers=Le,
            state_shape=(self.mamba_num_heads, self.mamba_head_dim,
                         self.ssm_state_size),
            conv_shape=(self.conv_kernel - 1, conv_channels(self)),
            state_dtype=self.state_dtype,
            experts_per_token=self.num_experts_per_tok)

    @classmethod
    def tiny_nemotron_h(cls, **overrides):
        """CPU-test scale: three state-space layers, three routed ones, two
        that attend; a router twice as wide as the experts held; chunks of
        16 tokens, so that a prompt of a few dozen takes several."""
        kw = dict(
            vocab_size=512, hidden_size=64, intermediate_size=32,
            num_layers=8, pattern="ME*MEM*E", num_heads=4, num_kv_heads=2,
            head_dim=16, max_position=2048, rms_norm_eps=1e-5,
            mamba_num_heads=8, mamba_head_dim=16, ssm_state_size=16,
            n_groups=2, chunk_size=16, num_experts=4, router_width=8,
            first_expert_held=0, num_experts_per_tok=3,
            moe_intermediate_size=32, moe_latent_size=32,
            shared_expert_intermediate_size=48,
        )
        kw.update(overrides)
        return cls(**kw)


def kinds(c: NemotronHConfig) -> Tuple[int, int, int]:
    """(state-space layers, routed layers, attention layers)."""
    return tuple(c.pattern.count(k) for k in KINDS)


def inner_size(c: NemotronHConfig) -> int:
    return c.mamba_num_heads * c.mamba_head_dim


def conv_channels(c: NemotronHConfig) -> int:
    """Channels of the mixer's convolution: x, B and C side by side."""
    return inner_size(c) + 2 * c.n_groups * c.ssm_state_size


# ---------------------------------------------------------------------- #
# weights
# ---------------------------------------------------------------------- #


def init_params(config: NemotronHConfig, key: jax.Array) -> Dict[str, Any]:
    """Seeded random weights, every stacked leaf built once (hybrid.
    dense_leaf, expert_stack_leaf: an expert's weights are keyed by its
    GLOBAL id). Matrices are named `w*`, `embed`, `lm_head` (the int8
    control rounds those); norms, `a_log`, `d_skip`, `dt_bias`,
    `conv_bias`, the float32 router and its choice bias are not."""
    c = config
    Lm, Le, La = kinds(c)
    H, D = c.hidden_size, c.head_dim
    nh, Di, C = c.mamba_num_heads, inner_size(c), conv_channels(c)
    Z, I, Is = (c.moe_latent_size, c.moe_intermediate_size,
                c.shared_expert_intermediate_size)
    # the device's own bit generator, as models/hybrid.py
    key = jax.random.wrap_key_data(
        jnp.tile(jax.random.key_data(key).ravel()[:2], 2), impl="rbg")
    names = iter(jax.random.split(key, 40))

    def dense(shape, dtype=None):
        return dense_leaf(next(names), shape, dtype or c.dtype)

    def experts(shape):
        return expert_stack_leaf(next(names), shape, c.dtype, Le,
                                 c.num_experts, jnp.int32(c.first_expert_held))

    # as Mamba-2 is initialised: A in (1, 16), a step size between
    # time_step_min and time_step_max behind the softplus, D near 1
    ka, kd = next(names), next(names)
    dt = jnp.maximum(jnp.exp(jax.random.uniform(
        kd, (Lm, nh), f32, jnp.log(c.time_step_min), jnp.log(c.time_step_max)
    )), c.time_step_floor)
    mamba = {
        "norm": 1.0 + dense((Lm, H), f32),
        "w_in": dense((Lm, H, Di + C + nh)),  # [z | x B C | dt]
        "w_conv": dense((Lm, C, c.conv_kernel)) * (0.5 / INIT_SCALE),
        "conv_bias": dense((Lm, C), f32) * (0.1 / INIT_SCALE),
        "a_log": jnp.log(jax.random.uniform(ka, (Lm, nh), f32, 1.0, 16.0)),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "d_skip": 1.0 + dense((Lm, nh), f32),
        "out_norm": 1.0 + dense((Lm, Di), f32),
        "w_out": dense((Lm, Di, H)),
    }
    attention = {
        "norm": 1.0 + dense((La, H), f32),
        "wq": dense((La, H, c.num_heads * D)),
        "wk": dense((La, H, c.num_kv_heads * D)),
        "wv": dense((La, H, c.num_kv_heads * D)),
        "wo": dense((La, c.num_heads * D, H)),
    }
    routed = {
        "norm": 1.0 + dense((Le, H), f32),
        # float32: tiny, and a routing decision is sensitive to rounding
        "router": dense((Le, H, c.router_width), f32),
        # small beside the scores' spread, and not zero: it moves the choice
        # at the margin and never the weights
        "router_bias": dense((Le, c.router_width), f32),
        "w_latent_down": dense((Le, H, Z)),
        "w1": experts((Z, I)),
        "w2": experts((I, Z)),
        "w_latent_up": dense((Le, Z, H)),
        "ws1": dense((Le, H, Is)),
        "ws2": dense((Le, Is, H)),
    }
    return {
        "embed": dense((c.vocab_size, H)),
        "layers": {"mamba": mamba, "attention": attention, "experts": routed},
        "final_norm": 1.0 + dense((H,), f32),
        "lm_head": dense((H, c.vocab_size)),
    }


# ---------------------------------------------------------------------- #
# pieces
# ---------------------------------------------------------------------- #


def norm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    """Plain RMSNorm in float32: x * rsqrt(mean(x^2) + eps) * w."""
    x32 = x.astype(f32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps) * w.astype(f32)).astype(x.dtype)


def _mamba_inputs(layer, h, c: NemotronHConfig):
    """The mixer's projection of h [..., H]: the gate z [..., Di], the
    convolution's input [..., C] (x, B, C side by side) and the step size
    dt [..., heads] (float32, behind its bias and the softplus)."""
    Di, C = inner_size(c), conv_channels(c)
    proj = qdot(h, layer["w_in"])  # float32
    dt = jax.nn.softplus(proj[..., Di + C:] + layer["dt_bias"].astype(f32))
    return proj[..., :Di].astype(c.dtype), proj[..., Di:Di + C].astype(c.dtype), dt


def _split_xbc(y, c: NemotronHConfig):
    """The convolution's output y [..., C] (float32, bias added) behind its
    SiLU as x [..., heads, head dim], B and C [..., groups, state size]."""
    nh, hd, G, N = (c.mamba_num_heads, c.mamba_head_dim, c.n_groups,
                    c.ssm_state_size)
    y = jax.nn.silu(y)
    Di = nh * hd
    return (y[..., :Di].reshape(*y.shape[:-1], nh, hd),
            y[..., Di:Di + G * N].reshape(*y.shape[:-1], G, N),
            y[..., Di + G * N:].reshape(*y.shape[:-1], G, N))


def _mamba_out(layer, y, x, z, c: NemotronHConfig):
    """out_proj(grouped_rmsnorm((y + D x) * silu(z))): y, x [..., heads,
    head dim] float32, z [..., Di]; the norm over each of `n_groups` groups
    of Di / n_groups channels, with a weight."""
    Di, G = inner_size(c), c.n_groups
    y = y + layer["d_skip"].astype(f32)[:, None] * x
    g = y.reshape(*z.shape) * jax.nn.silu(z.astype(f32))
    g = g.reshape(*z.shape[:-1], G, Di // G)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + c.rms_norm_eps)
    g = (g.reshape(*z.shape) * layer["out_norm"].astype(f32)).astype(c.dtype)
    return qdot(g, layer["w_out"]).astype(c.dtype)


def ssm_step(S, x, B, C, dt, *, A):
    """One token of the state-space recurrence, every head of every row at
    once, float32 on the vector unit: S [..., heads, head dim, N]; x [...,
    heads, head dim]; B, C [..., groups, N] (a group serves heads / groups
    heads); dt [..., heads]; A [heads] (negative).
    S <- exp(dt A) S + dt x (x) B; y = S C."""
    rep = x.shape[-2] // B.shape[-2]
    B, C = jnp.repeat(B, rep, axis=-2), jnp.repeat(C, rep, axis=-2)
    S = (S * jnp.exp(dt * A)[..., None, None]
         + (dt[..., None] * x)[..., :, None] * B[..., None, :])
    return S, jnp.sum(S * C[..., None, :], axis=-1)


def lanes_step(state, lm, x, B, C, dt, live, *, A):
    """One token a lane of the same recurrence over the state store IN
    PLACE, the one function the decode step and a mixed step's one-token
    rows share (ops/row_recurrence.py): state [state layers, lanes + 1,
    heads, head dim, N]; x, B, C, dt as `ssm_step` takes them, [n, ...] for
    the layer's first n lanes (row b IS lane b); live [n]: a lane that is
    not keeps its state bit for bit. -> (state, y [n, heads, head dim])."""
    return step_in_store(
        functools.partial(ssm_step, A=A), state, lm, x, B, C, dt, live=live)


def ssd_chunk(S, x, B, C, dt, *, A):
    """A chunk of tokens of the same recurrence in closed form (the
    state-space dual form): S [R, heads, head dim, N]; x [R, L, heads, head
    dim]; B, C [R, L, groups, N]; dt [R, L, heads]. A token whose dt is 0
    leaves the state as it was. -> (S after the chunk, y [R, L, heads, head
    dim]). Matmuls of float32 operands in three bf16 passes (hybrid.
    CHUNK_PRECISION): the state is what later tokens read."""
    def mm(spec, a, b):
        return jnp.einsum(spec, a, b, precision=CHUNK_PRECISION,
                          preferred_element_type=f32)

    R, L, nh, hd = x.shape
    G, N = B.shape[-2:]
    rep = nh // G
    cum = jnp.cumsum(dt * A, axis=1)  # [R, L, heads]: log decay up to and with t
    lower = jnp.tril(jnp.ones((L, L), bool))
    decay = jnp.exp(jnp.where(
        lower[None, :, :, None], cum[:, :, None] - cum[:, None, :], -jnp.inf
    )).reshape(R, L, L, G, rep)  # [R, t, s, ...]: 0 where s > t
    xdt = (x * dt[..., None]).reshape(R, L, G, rep, hd)
    S = S.reshape(R, G, rep, hd, N)
    # inside the chunk: y_t += sum_{s <= t} (C_t . B_s) decay(t, s) dt_s x_s
    cb = mm("rtgn,rsgn->rtsg", C, B)
    y = mm("rtsgh,rsghp->rtghp", cb[..., None] * decay, xdt)
    # from the state before the chunk: y_t += decay(t, start) C_t . S
    y = y + mm("rtgn,rghpn->rtghp", C, S) * jnp.exp(cum).reshape(R, L, G, rep, 1)
    last = cum[:, -1:]  # [R, 1, heads]
    S = S * jnp.exp(last).reshape(R, G, rep, 1, 1) + mm(
        "rsghp,rsgn->rghpn",
        xdt * jnp.exp(last - cum).reshape(R, L, G, rep, 1), B)
    return S.reshape(R, nh, hd, N), y.reshape(R, L, nh, hd)


def _qkv(layer, h, c: NemotronHConfig):
    """The attention layer's projections of h [..., H]: q [..., NH, D], k
    and v [..., KH, D]; no bias, no rotary."""
    def heads(w, n):
        return qdot(h, w).astype(c.dtype).reshape(*h.shape[:-1], n, c.head_dim)

    return (heads(layer["wq"], c.num_heads), heads(layer["wk"], c.num_kv_heads),
            heads(layer["wv"], c.num_kv_heads))


def _attn_out(layer, attn, c: NemotronHConfig):
    attn = attn.reshape(*attn.shape[:-2], c.num_heads * c.head_dim)
    return qdot(attn.astype(c.dtype), layer["wo"]).astype(c.dtype)


# ---------------------------------------------------------------------- #
# the routed part
# ---------------------------------------------------------------------- #


def route(h, layer, c: NemotronHConfig):
    """(experts chosen [T, K] under the router's full width, their weights
    [T, K]): moe.sigmoid_route at this family's sizes."""
    return moe.sigmoid_route(
        h, layer["router"], layer["router_bias"], c.num_experts_per_tok,
        c.norm_topk_prob, c.routed_scaling_factor)


def routed_block(layer, stacks, le, x, c: NemotronHConfig, valid=None):
    """y = x + up(routed(down(rms(x)))) + shared(rms(x)) for x [T, H]; also
    the experts chosen [T, K] (ids under the router's full width, held here
    or not). The router and the shared expert read the normed token, the
    experts its latent projection."""
    h = norm(x, layer["norm"], c.rms_norm_eps)
    with jax.named_scope("latent_experts"):
        idx, weight = route(h, layer, c)
        latent = qdot(h, layer["w_latent_down"]).astype(c.dtype)
        out = moe.experts_held(
            stacks, le, latent, idx, weight, valid, form="relu2",
            held=c.num_experts, first=c.first_expert_held, dtype=c.dtype)
        out = qdot(out.astype(c.dtype), layer["w_latent_up"])
    with jax.named_scope("shared_expert"):
        act = jnp.square(jax.nn.relu(qdot(h, layer["ws1"]))).astype(c.dtype)
        out = out + qdot(act, layer["ws2"])
    return x + out.astype(c.dtype), idx.astype(jnp.int32)


# ---------------------------------------------------------------------- #
# the layer stack
# ---------------------------------------------------------------------- #


def _layer_stack(params, c: NemotronHConfig, x, cache: StateCache, kv_v,
                 mamba_fn, attention_fn, valid=None):
    """x [..., H] through the layers in the pattern's order:
    `mamba_fn(layer, h, state, conv, lm) -> (out, state, conv)` on the
    normed input of state-space layer `lm`, `attention_fn(layer, h, pages,
    kv_v, la) -> (out, pages, kv_v)` of attention layer `la`. -> (x, cache
    with pages, state and conv as the layers left them, kv_v, the experts
    chosen [routed layers, tokens, K])."""
    layers = params["layers"]
    stacks = {k: layers["experts"][k] for k in moe.EXPERT_FORMS["relu2"]}
    small = {k: v for k, v in layers["experts"].items() if k not in stacks}
    by_kind = {"M": layers["mamba"], "*": layers["attention"], "E": small}
    lead = x.shape[:-1]
    pages, state, conv = cache.pages, cache.state, cache.conv
    seen = dict.fromkeys(KINDS, 0)
    chosen = []
    for kind in c.pattern:
        i = seen[kind]
        seen[kind] += 1
        layer = jax.tree.map(lambda a: a[i], by_kind[kind])
        if kind == "M":
            with jax.named_scope("mamba_mixer"):
                h = norm(x, layer["norm"], c.rms_norm_eps)
                out, state, conv = mamba_fn(layer, h, state, conv, i)
            x = x + out
        elif kind == "*":
            with jax.named_scope("attention"):
                h = norm(x, layer["norm"], c.rms_norm_eps)
                out, pages, kv_v = attention_fn(layer, h, pages, kv_v, i)
            x = x + out
        else:
            y, idx = routed_block(
                layer, stacks, i, x.reshape(-1, x.shape[-1]), c, valid)
            x = y.reshape(*lead, -1)
            chosen.append(idx)
    cache = cache.replace(pages=pages, state=state, conv=conv)
    return x, cache, kv_v, jnp.stack(chosen)


def _head(params, c: NemotronHConfig, x):
    with jax.named_scope("head_and_sample"):
        x = norm(x, params["final_norm"], c.rms_norm_eps)
        return qdot(x, params["lm_head"])


def _refuse(lora, emb_override=None):
    if lora is not None or emb_override is not None:
        raise NotImplementedError(
            "the Nemotron-H family (models/nemotron_h.py) takes no LoRA "
            "adapter and no multimodal embedding rows"
        )


def _page_slots(page_tables, positions, page_size):
    """(physical page, offset in it) of each position under its row's table
    (the scratch page 0 past the table's reach)."""
    P_tab = page_tables.shape[-1]
    logical = jnp.minimum(positions // page_size, P_tab - 1)
    phys = jnp.take_along_axis(
        page_tables, logical.reshape(*page_tables.shape[:-1], -1), axis=-1)
    phys = phys.reshape(positions.shape)
    return (jnp.where(positions < P_tab * page_size, phys, 0),
            positions % page_size)


# ---------------------------------------------------------------------- #
# decode: one token a lane, the step form
# ---------------------------------------------------------------------- #


def decode_forward(
    params: Dict[str, Any],
    config: NemotronHConfig,
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
    live = page_tables[:, 0] != 0  # the engine's scratch page is 0
    with jax.named_scope("embed"):
        x = embed_rows(params["embed"], tokens, c.dtype)
    phys, offs = _page_slots(page_tables, positions, kv_page_size(kv_k.pages))

    def mamba_fn(layer, h, state, conv, lm):
        z, mixed, dt = _mamba_inputs(layer, h, c)
        tail = jax.lax.dynamic_index_in_dim(conv, lm, 0, False)[:B]
        window = jnp.concatenate([tail, mixed[:, None]], axis=1)  # [B, taps, C]
        y = jnp.einsum("btc,ct->bc", window.astype(f32),
                       layer["w_conv"].astype(f32)) + layer["conv_bias"]
        xs, Bm, Cm = _split_xbc(y, c)
        state, ys = lanes_step(
            state, lm, xs, Bm, Cm, dt, live,
            A=-jnp.exp(layer["a_log"].astype(f32)))
        tail = jnp.where(live[:, None, None], window[:, 1:], tail)
        conv = jax.lax.dynamic_update_slice(conv, tail[None], (lm, 0, 0, 0))
        return _mamba_out(layer, ys, xs, z, c), state, conv

    def attention_fn(layer, h, pages, kv_v, la):
        q, k, v = _qkv(layer, h, c)
        pages = kv_write(pages, la, phys, offs, k)
        kv_v = kv_write(kv_v, la, phys, offs, v)
        attn = paged_attention_decode(
            q, kv_layer(pages, la), kv_layer(kv_v, la), page_tables, seq_lens)
        return _attn_out(layer, attn, c), pages, kv_v

    x, cache, kv_v, chosen = _layer_stack(
        params, c, x, kv_k, kv_v, mamba_fn, attention_fn)
    ring = cache.routed_ring
    ring = ring.at[positions % ring.shape[0], :, jnp.arange(B)].set(
        jnp.moveaxis(chosen, 1, 0))
    return _head(params, c, x), cache.replace(routed_ring=ring), kv_v


# ---------------------------------------------------------------------- #
# rows of many tokens: the chunked form over a flat token axis
# ---------------------------------------------------------------------- #


def _flat_mamba_fn(c: NemotronHConfig, lanes, row_ids, row_starts, row_lens,
                   ctx_lens, long_rows: int):
    """The state-space mixer over a flat axis of M token slots that R rows
    share (row r: slots row_starts[r] ... + row_lens[r], lane lanes[r],
    ctx_lens[r] tokens of its sequence before it). A row starts from its
    lane's state, or from zero where its context is 0, and leaves the
    state behind its last token in the lane (ops/row_recurrence.py: a row
    of one token that goes on from its lane's state takes `lanes_step` over
    the store, as in a decode step; the other rows, of which the caller
    expects `long_rows` at most, are gathered a few a group: the step form
    for the first token, the chunked form, `chunk_size` tokens an
    iteration, for what is left)."""
    fresh = ctx_lens == 0

    def mamba_fn(layer, h, state, conv, lm):
        z, mixed, dt = _mamba_inputs(layer, h, c)
        tails = jnp.where(fresh[:, None, None], 0, conv[lm, lanes])
        y, new_tails = flat_conv(
            mixed, layer["w_conv"].astype(f32), tails, row_ids, row_starts,
            row_lens)
        xs, Bm, Cm = _split_xbc(y + layer["conv_bias"], c)
        A = -jnp.exp(layer["a_log"].astype(f32))
        # (a zero row's dt of 0 leaves a state as it was)
        state, ys = rows_recurrence(
            state, lm, lanes, ctx_lens, (xs, Bm, Cm, dt),
            (c.mamba_num_heads, c.mamba_head_dim),
            functools.partial(lanes_step, A=A),
            functools.partial(ssm_step, A=A), functools.partial(ssd_chunk, A=A),
            c.chunk_size, row_starts, row_lens, long_rows)
        conv = conv.at[lm, lanes].set(new_tails.astype(conv.dtype))
        return _mamba_out(layer, ys, xs, z, c), state, conv

    return mamba_fn


def ragged_forward(
    params: Dict[str, Any],
    config: NemotronHConfig,
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
    models/hybrid.py:ragged_forward, whose contract this keeps). Returns
    (logits of each row's last token [R, vocab], cache, kv_v)."""
    _refuse(lora)
    c = config
    M, R = tokens.shape[0], row_lens.shape[0]
    lanes = kv_k.lanes[:R]
    with jax.named_scope("embed"):
        x = embed_rows(params["embed"], tokens, c.dtype)
    phys, offs = _page_slots(
        page_tables[row_ids], positions, kv_page_size(kv_k.pages))
    valid = jnp.arange(M, dtype=jnp.int32) < row_lens.sum()

    def attention_fn(layer, h, pages, kv_v, la):
        q, k, v = _qkv(layer, h, c)
        pages = kv_write(pages, la, phys, offs, k)
        kv_v = kv_write(kv_v, la, phys, offs, v)
        attn = ragged_attention(
            q, kv_layer(pages, la), kv_layer(kv_v, la), page_tables,
            row_starts, row_lens, ctx_lens, long_rows=long_rows)
        return _attn_out(layer, attn, c), pages, kv_v

    x, cache, kv_v, chosen = _layer_stack(
        params, c, x, kv_k, kv_v,
        _flat_mamba_fn(
            c, lanes, row_ids, row_starts, row_lens, ctx_lens,
            # a mixed step's rows: a decode row a lane and a prefill batch
            long_rows if long_rows is not None
            else max(R - kv_k.scratch_lane, 1)),
        attention_fn, valid)
    flat = _note_chosen(cache.routed_flat, chosen)
    return _head(params, c, x[last_flat]), cache.replace(routed_flat=flat), kv_v


def prefill_forward_batched(
    params: Dict[str, Any],
    config: NemotronHConfig,
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
    """Batched chunked prefill. The state-space mixers see the chunks as
    rows of one flat axis (row b: slots b * T ..., last_idx[b] + 1 real
    ones), the attention layers as the batch it is. Returns (logits_last
    [B, vocab], cache, kv_v)."""
    _refuse(lora, emb_override)
    if all_logits:
        raise NotImplementedError(
            "the Nemotron-H family cannot verify drafts: a state has no rollback"
        )
    c = config
    B, T = tokens.shape
    lanes = kv_k.lanes[:B]
    with jax.named_scope("embed"):
        x = embed_rows(params["embed"], tokens, c.dtype).reshape(B * T, -1)
    phys, offs = _page_slots(page_tables, positions, kv_page_size(kv_k.pages))
    total_lens = context_lens + last_idx + 1
    row_lens = last_idx + 1
    row_starts = jnp.arange(B, dtype=jnp.int32) * T
    row_ids = jnp.repeat(jnp.arange(B, dtype=jnp.int32), T)
    valid = (jnp.arange(T)[None, :] < row_lens[:, None]).reshape(B * T)

    def attention_fn(layer, h, pages, kv_v, la):
        q, k, v = _qkv(layer, h.reshape(B, T, -1), c)
        pages = kv_write(pages, la, phys, offs, k)
        kv_v = kv_write(kv_v, la, phys, offs, v)
        attn = prefill_attention_batched(
            q, kv_layer(pages, la), kv_layer(kv_v, la), positions,
            page_tables, total_lens, context_lens)
        return _attn_out(layer, attn, c).reshape(B * T, -1), pages, kv_v

    x, cache, kv_v, chosen = _layer_stack(
        params, c, x, kv_k, kv_v,
        _flat_mamba_fn(c, lanes, row_ids, row_starts, row_lens, context_lens,
                       long_rows=B),
        attention_fn, valid)
    flat = _note_chosen(cache.routed_flat, chosen)
    last = x[row_starts + last_idx]
    return _head(params, c, last), cache.replace(routed_flat=flat), kv_v


def prefill_forward(
    params: Dict[str, Any],
    config: NemotronHConfig,
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


def expert_rows(c: NemotronHConfig, T: int, real: int, quantized: bool = False):
    """(routed, computed) expert rows of one routed layer over T token slots
    of which `real` are real (moe.held_expert_rows)."""
    return moe.held_expert_rows(
        c.num_experts, c.router_width, c.num_experts_per_tok, T, real)


def step_work(c: NemotronHConfig, real_tokens: int, context_tokens: int,
              passes: int, *, sampled: Optional[int] = None,
              kv_tokens: Optional[int] = None,
              weight_bytes: Optional[float] = None,
              kv_bytes: Optional[float] = None,
              rows: Optional[int] = None):
    """(useful operations, least HBM bytes, of those the recurrent state's,
    of those the held experts') of one pipeline entry, as hybrid.step_work
    counts them. A real token passes through every mixer and attention
    projection, every router, both latent projections, its K chosen
    experts' share held here, every shared expert and, where sampled, the
    head; a state-space layer's recurrence adds the decay and two products
    over a head dim x state size state for each head. Bytes: per pass the
    weights once, with the held experts a pass's real rows touch in
    expectation under an even router; the state read and written once for
    each of `rows` (row, pass) pairs; the context's pages."""
    Lm, Le, La = kinds(c)
    wb = jnp.dtype(c.dtype).itemsize if weight_bytes is None else weight_bytes
    if kv_bytes is None:
        kv_bytes = 2 * c.num_kv_heads * c.head_dim * jnp.dtype(c.dtype).itemsize
    sampled = real_tokens if sampled is None else sampled
    kv_tokens = context_tokens if kv_tokens is None else kv_tokens
    rows = real_tokens if rows is None else rows
    H, D, K = c.hidden_size, c.head_dim, c.num_experts_per_tok
    nh, Di, C = c.mamba_num_heads, inner_size(c), conv_channels(c)
    Z = c.moe_latent_size
    mamba = H * (Di + C + nh) + Di * H
    attention = 2 * H * D * (c.num_heads + c.num_kv_heads)
    expert = 2 * Z * c.moe_intermediate_size
    beside = 2 * H * Z + 2 * H * c.shared_expert_intermediate_size
    router = H * c.router_width  # float32
    head = H * c.vocab_size
    share = c.num_experts / c.router_width
    flops = (
        2 * real_tokens * (
            Lm * mamba + La * attention
            + Le * (router + beside + K * share * expert))
        + real_tokens * Lm * (2 * C * c.conv_kernel
                              + 5 * Di * c.ssm_state_size)
        + 4 * La * c.num_heads * D * context_tokens
        + 2 * head * sampled
    )
    one_pass = -(-real_tokens // max(passes, 1))
    touched = moe.experts_touched(c.num_experts, one_pass * K * share)
    experts = passes * Le * touched * expert * wb
    state = 2 * rows * state_bytes_per_lane(c)
    nbytes = (
        passes * (
            (Lm * (mamba + C * c.conv_kernel) + La * attention) * wb
            + Le * (router * 4 + beside * wb)
            + head * wb)
        + experts + state
        + La * kv_bytes * (kv_tokens + real_tokens)
    )
    return int(flops), int(nbytes), int(state), int(experts)
