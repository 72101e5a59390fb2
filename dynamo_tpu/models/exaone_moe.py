"""EXAONE-MoE family: every layer is `x + attn(rmsnorm(x))` then `x +
ffn(rmsnorm(x))`, with the layer's two kinds read off the configuration
(docs/hybrid_models.md, "A ring beside the pages"):

    attention, by `sliding_window_pattern` repeated over the layers:
      L  window: grouped queries over the last `sliding_window` positions,
         itself among them; an RMSNorm over each head of q and k, then full
         rotary; the K and V of a lane's last W positions live in a RING a
         lane (ops/window_attention.py), whatever the lane's context;
      G  full: the same projections and head norms, NO rotary (the window
         layers carry order), over the paged cache every family shares;
    feed-forward, by `first_k_dense_replace`:
      dense   `W2(silu(W1 h) * W3 h)` at `intermediate_size` (the first
              layers);
      sparse  sigmoid scores over the router's FULL width, the k largest
              of score + a choice bias, weights the scores at the chosen
              over their sum times a scaling factor (moe.sigmoid_route);
              experts of the dense form at `moe_intermediate_size`; a
              shared expert of `num_shared_experts` such widths on every
              token, no gate. This chip holds experts `[first_expert_held,
              first_expert_held + num_experts)` and leaves the others'
              part out: it is the other chips'.

The forwards keep models/llama.py's signatures and models/hybrid.py's
contract: `kv_k` is a `StateCache` whose `pages` are the K pages of the G
layers, whose `state` and `conv` are the K and the V rings of the L layers
`[L layers, lanes + 1, W, KH*D]`, and after any forward a lane's rings stand
at exactly the tokens whose full-layer keys and values are written for that
lane; a row of context 0 finds its ring empty (every slot masked by
position: nothing is cleared). Layers are stacked by KIND and unrolled in
the published order; the expert stacks stay whole (moe.ExpertStack).
"""

from __future__ import annotations

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
from ..ops.state_cache import StateCache, StateSpec
from ..ops.window_attention import (
    decode_window_attention,
    flat_window_attention,
    rings_after,
)
from . import moe
from .hybrid import _note_chosen, dense_leaf, expert_stack_leaf
from .llama import LlamaConfig, apply_rope, rope_cos_sin
from .nemotron_h import _attn_out, _head, _page_slots, norm
from .quant import embed_rows, qdot

f32 = jnp.float32
#: what the engine calls this family in its refusals and its log
STATE_FAMILY = (
    "the EXAONE-MoE family (models/exaone_moe.py: a ring of the window "
    "layers' last keys and values per lane)"
)
EXPERT_FORM = "gated_silu"


@dataclass(frozen=True)
class ExaoneMoeConfig(LlamaConfig):
    rope_theta: float = 1e6
    sliding_window: int = 128
    sliding_window_pattern: str = "LLLG"  # L window, G full; repeated
    first_k_dense_replace: int = 1  # the leading layers whose ffn is dense
    num_experts: int = 16  # the experts HELD on this chip
    router_width: int = 128  # the experts the router scores
    first_expert_held: int = 0
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 2048
    num_shared_experts: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5

    def __post_init__(self):
        if not self.sliding_window_pattern or set(self.sliding_window_pattern) - set("LG"):
            raise ValueError(
                f"sliding_window_pattern {self.sliding_window_pattern!r} is "
                "no string of L (window) and G (full)")
        if not 0 <= self.first_k_dense_replace <= self.num_layers:
            raise ValueError("first_k_dense_replace lies past the layers")
        if self.first_expert_held + self.num_experts > self.router_width:
            raise ValueError(
                f"experts [{self.first_expert_held}, "
                f"{self.first_expert_held + self.num_experts}) lie past the "
                f"router's width {self.router_width}"
            )

    def state_spec(self) -> StateSpec:
        Lw, Lf, _, Le = kinds(self)
        ring = (self.sliding_window, self.num_kv_heads * self.head_dim)
        # `state`: the K ring; `conv`: the V ring ("the last n rows of a
        # lane, carried across chunks" with n = the window)
        return StateSpec(
            state_layers=Lw, attention_layers=Lf, routed_layers=Le,
            state_shape=ring, conv_shape=ring, state_dtype=self.dtype,
            experts_per_token=self.num_experts_per_tok)

    @classmethod
    def tiny_exaone_moe(cls, **overrides):
        """CPU-test scale: two periods of LLLG, a window of 8 under contexts
        of a hundred and more, one dense layer and seven sparse ones, a
        router twice as wide as the experts held."""
        kw = dict(
            vocab_size=512, hidden_size=64, intermediate_size=96,
            num_layers=8, num_heads=4, num_kv_heads=2, head_dim=16,
            max_position=2048, rope_theta=1e4, rms_norm_eps=1e-5,
            sliding_window=8, num_experts=4, router_width=8,
            first_expert_held=0, num_experts_per_tok=3,
            moe_intermediate_size=32,
        )
        kw.update(overrides)
        return cls(**kw)


def is_window(c: ExaoneMoeConfig, li: int) -> bool:
    pattern = c.sliding_window_pattern
    return pattern[li % len(pattern)] == "L"


def kinds(c: ExaoneMoeConfig) -> Tuple[int, int, int, int]:
    """(window layers, full layers, dense layers, sparse layers)."""
    Lw = sum(is_window(c, li) for li in range(c.num_layers))
    return (Lw, c.num_layers - Lw, c.first_k_dense_replace,
            c.num_layers - c.first_k_dense_replace)


def shared_width(c: ExaoneMoeConfig) -> int:
    return c.num_shared_experts * c.moe_intermediate_size


# ---------------------------------------------------------------------- #
# weights
# ---------------------------------------------------------------------- #


def init_params(config: ExaoneMoeConfig, key: jax.Array) -> Dict[str, Any]:
    """Seeded random weights, every stacked leaf built once (hybrid.
    dense_leaf, expert_stack_leaf: an expert's weights are keyed by its
    GLOBAL id). Matrices are named `w*`, `embed`, `lm_head` (the int8
    control rounds those); norms, the float32 router and its choice bias
    are not."""
    c = config
    Lw, Lf, Ld, Le = kinds(c)
    H, D = c.hidden_size, c.head_dim
    I, Im, Is = c.intermediate_size, c.moe_intermediate_size, shared_width(c)
    # the device's own bit generator, as models/hybrid.py
    key = jax.random.wrap_key_data(
        jnp.tile(jax.random.key_data(key).ravel()[:2], 2), impl="rbg")
    names = iter(jax.random.split(key, 40))

    def dense(shape, dtype=None):
        return dense_leaf(next(names), shape, dtype or c.dtype)

    def experts(shape):
        return expert_stack_leaf(next(names), shape, c.dtype, Le,
                                 c.num_experts, jnp.int32(c.first_expert_held))

    def attention(L):
        return {
            "norm": 1.0 + dense((L, H), f32),
            "wq": dense((L, H, c.num_heads * D)),
            "wk": dense((L, H, c.num_kv_heads * D)),
            "wv": dense((L, H, c.num_kv_heads * D)),
            "q_norm": 1.0 + dense((L, D), f32),
            "k_norm": 1.0 + dense((L, D), f32),
            "wo": dense((L, c.num_heads * D, H)),
        }

    mlp = {
        "norm": 1.0 + dense((Ld, H), f32),
        "w_gate": dense((Ld, H, I)),
        "w_up": dense((Ld, H, I)),
        "w_down": dense((Ld, I, H)),
    }
    routed = {
        "norm": 1.0 + dense((Le, H), f32),
        # float32: tiny, and a routing decision is sensitive to rounding
        "router": dense((Le, H, c.router_width), f32),
        # small beside the scores' spread, and not zero: it moves the choice
        # at the margin and never the weights
        "router_bias": dense((Le, c.router_width), f32),
        "w_gate": experts((H, Im)),
        "w_up": experts((H, Im)),
        "w_down": experts((Im, H)),
        "ws_gate": dense((Le, H, Is)),
        "ws_up": dense((Le, H, Is)),
        "ws_down": dense((Le, Is, H)),
    }
    return {
        "embed": dense((c.vocab_size, H)),
        "layers": {"window": attention(Lw), "full": attention(Lf),
                   "dense": mlp, "experts": routed},
        "final_norm": 1.0 + dense((H,), f32),
        "lm_head": dense((H, c.vocab_size)),
    }


# ---------------------------------------------------------------------- #
# pieces
# ---------------------------------------------------------------------- #


def _qkv(layer, h, positions, c: ExaoneMoeConfig, rotary: bool):
    """The attention projections of h [..., H]: q [..., NH, D], k and v
    [..., KH, D]; no bias; q and k normed a head, then rotated where
    `rotary` (the window layers) at `positions` [...]."""
    def heads(w, n):
        return qdot(h, w).astype(c.dtype).reshape(*h.shape[:-1], n, c.head_dim)

    q = norm(heads(layer["wq"], c.num_heads), layer["q_norm"], c.rms_norm_eps)
    k = norm(heads(layer["wk"], c.num_kv_heads), layer["k_norm"], c.rms_norm_eps)
    if rotary:
        cos, sin = rope_cos_sin(positions, c.head_dim, c.rope_theta)
        q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
    return q, k, heads(layer["wv"], c.num_kv_heads)


def _gated_silu(h, w_gate, w_up, w_down, dtype):
    act = (jax.nn.silu(qdot(h, w_gate)) * qdot(h, w_up)).astype(dtype)
    return qdot(act, w_down)


def dense_block(layer, x, c: ExaoneMoeConfig):
    """y = x + W2(silu(W1 h) * W3 h), h = rms(x)."""
    h = norm(x, layer["norm"], c.rms_norm_eps)
    with jax.named_scope("dense_mlp"):
        out = _gated_silu(h, layer["w_gate"], layer["w_up"], layer["w_down"],
                          c.dtype)
    return x + out.astype(c.dtype)


def route(h, layer, c: ExaoneMoeConfig):
    """(experts chosen [T, K] under the router's full width, their weights
    [T, K]): moe.sigmoid_route at this family's sizes."""
    return moe.sigmoid_route(
        h, layer["router"], layer["router_bias"], c.num_experts_per_tok,
        c.norm_topk_prob, c.routed_scaling_factor)


def routed_block(layer, stacks, le, x, c: ExaoneMoeConfig, valid=None):
    """y = x + routed(rms(x)) + shared(rms(x)) for x [T, H]; also the
    experts chosen [T, K] (ids under the router's full width, held here or
    not). The shared expert has no gate."""
    h = norm(x, layer["norm"], c.rms_norm_eps)
    with jax.named_scope("router"):
        idx, weight = route(h, layer, c)
    with jax.named_scope("experts"):
        out = moe.experts_held(
            stacks, le, h, idx, weight, valid, form=EXPERT_FORM,
            held=c.num_experts, first=c.first_expert_held, dtype=c.dtype)
    with jax.named_scope("shared_expert"):
        out = out + _gated_silu(
            h, layer["ws_gate"], layer["ws_up"], layer["ws_down"], c.dtype)
    return x + out.astype(c.dtype), idx.astype(jnp.int32)


# ---------------------------------------------------------------------- #
# the layer stack
# ---------------------------------------------------------------------- #


def _layer_stack(params, c: ExaoneMoeConfig, x, cache: StateCache, kv_v,
                 window_fn, full_fn, valid=None):
    """x [T, H] through the layers in the published order:
    `window_fn(layer, h, ring_k, ring_v, lw) -> (out, ring_k, ring_v)` on
    the normed input of window layer `lw`, `full_fn(layer, h, pages, kv_v,
    lf) -> (out, pages, kv_v)` of full layer `lf`. A layer's leaves are
    taken from the STORED stacks with one static index each. -> (x, cache
    with pages and rings as the layers left them, kv_v, the experts chosen
    [sparse layers, tokens, K])."""
    layers = params["layers"]
    names = moe.EXPERT_FORMS[EXPERT_FORM]
    stacks = {k: layers["experts"][k] for k in names}
    small = {k: v for k, v in layers["experts"].items() if k not in names}
    pages, ring_k, ring_v = cache.pages, cache.state, cache.conv
    lw = lf = 0
    chosen = []
    for li in range(c.num_layers):
        if is_window(c, li):
            layer = jax.tree.map(lambda a: a[lw], layers["window"])
            with jax.named_scope("window_attention"):
                h = norm(x, layer["norm"], c.rms_norm_eps)
                out, ring_k, ring_v = window_fn(layer, h, ring_k, ring_v, lw)
            lw += 1
        else:
            layer = jax.tree.map(lambda a: a[lf], layers["full"])
            with jax.named_scope("attention"):
                h = norm(x, layer["norm"], c.rms_norm_eps)
                out, pages, kv_v = full_fn(layer, h, pages, kv_v, lf)
            lf += 1
        x = x + out
        le = li - c.first_k_dense_replace
        if le < 0:
            x = dense_block(
                jax.tree.map(lambda a: a[li], layers["dense"]), x, c)
        else:
            x, idx = routed_block(
                jax.tree.map(lambda a: a[le], small), stacks, le, x, c, valid)
            chosen.append(idx)
    cache = cache.replace(pages=pages, state=ring_k, conv=ring_v)
    return x, cache, kv_v, jnp.stack(chosen)


def _refuse(lora, emb_override=None):
    if lora is not None or emb_override is not None:
        raise NotImplementedError(
            "the EXAONE-MoE family (models/exaone_moe.py) takes no LoRA "
            "adapter and no multimodal embedding rows"
        )


# ---------------------------------------------------------------------- #
# decode: one token a lane
# ---------------------------------------------------------------------- #


def decode_forward(
    params: Dict[str, Any],
    config: ExaoneMoeConfig,
    tokens: jax.Array,  # [B] one new token per lane: row b IS lane b
    positions: jax.Array,  # [B]
    kv_k: StateCache,
    kv_v: jax.Array,
    page_tables: jax.Array,  # [B, max_pages]
    seq_lens: jax.Array,  # [B] lengths INCLUDING the new token
    lora=None,
) -> Tuple[jax.Array, StateCache, jax.Array]:
    """One decode step for the whole slot batch; returns (logits [B,
    vocab], cache, kv_v). A window layer writes ONE slot of each decoding
    lane's ring, position % W, in place, and reads the lane's W slots,
    whatever its context. A lane whose table row is scratch (not decoding:
    free, or between two chunks of its prompt) keeps its rings: its slot
    goes to the scratch lane."""
    _refuse(lora)
    c = config
    B = tokens.shape[0]
    live = page_tables[:, 0] != 0  # the engine's scratch page is 0
    lane = jnp.where(live, jnp.arange(B), kv_k.scratch_lane)
    slot = positions % c.sliding_window
    with jax.named_scope("embed"):
        x = embed_rows(params["embed"], tokens, c.dtype)
    phys, offs = _page_slots(page_tables, positions, kv_page_size(kv_k.pages))

    def window_fn(layer, h, ring_k, ring_v, lw):
        q, k, v = _qkv(layer, h, positions, c, rotary=True)
        ring_k = ring_k.at[lw, lane, slot].set(k.reshape(B, -1))
        ring_v = ring_v.at[lw, lane, slot].set(v.reshape(B, -1))
        attn = decode_window_attention(
            q, ring_k[lw, :B], ring_v[lw, :B], positions)
        return _attn_out(layer, attn, c), ring_k, ring_v

    def full_fn(layer, h, pages, kv_v, lf):
        q, k, v = _qkv(layer, h, positions, c, rotary=False)
        pages = kv_write(pages, lf, phys, offs, k)
        kv_v = kv_write(kv_v, lf, phys, offs, v)
        attn = paged_attention_decode(
            q, kv_layer(pages, lf), kv_layer(kv_v, lf), page_tables, seq_lens)
        return _attn_out(layer, attn, c), pages, kv_v

    x, cache, kv_v, chosen = _layer_stack(
        params, c, x, kv_k, kv_v, window_fn, full_fn)
    ring = cache.routed_ring
    ring = ring.at[positions % ring.shape[0], :, jnp.arange(B)].set(
        jnp.moveaxis(chosen, 1, 0))
    return _head(params, c, x), cache.replace(routed_ring=ring), kv_v


# ---------------------------------------------------------------------- #
# rows of many tokens, over a flat token axis
# ---------------------------------------------------------------------- #


def _flat_window_fn(c: ExaoneMoeConfig, lanes, positions, row_ids, row_starts,
                    row_lens, ctx_lens, long_rows: int):
    """A window layer over a flat axis of M token slots that R rows share
    (row r: slots row_starts[r] ... + row_lens[r], lane lanes[r],
    ctx_lens[r] tokens of its sequence before it): a row reads its lane's
    rings as they stood before the step and its own keys under the band,
    and leaves its last W positions in the rings."""
    def window_fn(layer, h, ring_k, ring_v, lw):
        q, k, v = _qkv(layer, h, positions, c, rotary=True)
        k2, v2 = (a.reshape(a.shape[0], -1) for a in (k, v))
        # one gather on the stored arrays (as models/nemotron_h.py)
        old_k, old_v = ring_k[lw, lanes], ring_v[lw, lanes]
        attn = flat_window_attention(
            q, k, v, old_k, old_v, row_ids, row_starts, row_lens, ctx_lens,
            long_rows)
        ring_k = ring_k.at[lw, lanes].set(
            rings_after(old_k, k2, row_starts, row_lens, ctx_lens))
        ring_v = ring_v.at[lw, lanes].set(
            rings_after(old_v, v2, row_starts, row_lens, ctx_lens))
        return _attn_out(layer, attn, c), ring_k, ring_v

    return window_fn


def ragged_forward(
    params: Dict[str, Any],
    config: ExaoneMoeConfig,
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

    def full_fn(layer, h, pages, kv_v, lf):
        q, k, v = _qkv(layer, h, positions, c, rotary=False)
        pages = kv_write(pages, lf, phys, offs, k)
        kv_v = kv_write(kv_v, lf, phys, offs, v)
        attn = ragged_attention(
            q, kv_layer(pages, lf), kv_layer(kv_v, lf), page_tables,
            row_starts, row_lens, ctx_lens, long_rows=long_rows)
        return _attn_out(layer, attn, c), pages, kv_v

    x, cache, kv_v, chosen = _layer_stack(
        params, c, x, kv_k, kv_v,
        _flat_window_fn(
            c, lanes, positions, row_ids, row_starts, row_lens, ctx_lens,
            # a mixed step's rows: a decode row a lane and a prefill batch
            long_rows if long_rows is not None
            else max(R - kv_k.scratch_lane, 1)),
        full_fn, valid)
    flat = _note_chosen(cache.routed_flat, chosen)
    return _head(params, c, x[last_flat]), cache.replace(routed_flat=flat), kv_v


def prefill_forward_batched(
    params: Dict[str, Any],
    config: ExaoneMoeConfig,
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
    """Batched chunked prefill. The window layers see the chunks as rows of
    one flat axis (row b: slots b * T ..., last_idx[b] + 1 real ones), the
    full layers as the batch it is. Returns (logits_last [B, vocab], cache,
    kv_v)."""
    _refuse(lora, emb_override)
    if all_logits:
        raise NotImplementedError(
            "the EXAONE-MoE family cannot verify drafts: a rejected draft's "
            "slots of a ring have no rollback"
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

    def full_fn(layer, h, pages, kv_v, lf):
        q, k, v = _qkv(layer, h.reshape(B, T, -1), positions, c, rotary=False)
        pages = kv_write(pages, lf, phys, offs, k)
        kv_v = kv_write(kv_v, lf, phys, offs, v)
        attn = prefill_attention_batched(
            q, kv_layer(pages, lf), kv_layer(kv_v, lf), positions,
            page_tables, total_lens, context_lens)
        return _attn_out(layer, attn, c).reshape(B * T, -1), pages, kv_v

    x, cache, kv_v, chosen = _layer_stack(
        params, c, x, kv_k, kv_v,
        _flat_window_fn(c, lanes, positions.reshape(B * T), row_ids,
                        row_starts, row_lens, context_lens, long_rows=B),
        full_fn, valid)
    flat = _note_chosen(cache.routed_flat, chosen)
    last = x[row_starts + last_idx]
    return _head(params, c, last), cache.replace(routed_flat=flat), kv_v


def prefill_forward(
    params: Dict[str, Any],
    config: ExaoneMoeConfig,
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


def expert_rows(c: ExaoneMoeConfig, T: int, real: int, quantized: bool = False):
    """(routed, computed) expert rows of one sparse layer over T token slots
    of which `real` are real (moe.held_expert_rows)."""
    return moe.held_expert_rows(
        c.num_experts, c.router_width, c.num_experts_per_tok, T, real)


def step_work(c: ExaoneMoeConfig, real_tokens: int, context_tokens: int,
              passes: int, *, sampled: Optional[int] = None,
              kv_tokens: Optional[int] = None,
              weight_bytes: Optional[float] = None,
              kv_bytes: Optional[float] = None,
              rows: Optional[int] = None):
    """(useful operations, least HBM bytes, of those the rings', of those
    the held experts', the K and V bytes the window layers read, the K and
    V bytes they would read at every row's whole context) of one pipeline
    entry, as nemotron_h.step_work counts them. A real token passes through
    every layer's attention projections, the dense layers' feed-forward,
    every sparse layer's router, shared expert and its K chosen experts'
    share held here and, where sampled, the head; a full layer attends a
    token's whole context, a window layer at most W positions of it.
    Bytes: per pass the weights once, with the held experts a pass's real
    rows touch in expectation under an even router; the full layers' pages
    of the context; of a window layer at most W positions for each of
    `rows` (row, pass) pairs (min(kv_tokens, rows x W): exact where every
    row's context lies on one side of W), and the new tokens' written."""
    Lw, Lf, Ld, Le = kinds(c)
    wb = jnp.dtype(c.dtype).itemsize if weight_bytes is None else weight_bytes
    if kv_bytes is None:
        kv_bytes = 2 * c.num_kv_heads * c.head_dim * jnp.dtype(c.dtype).itemsize
    sampled = real_tokens if sampled is None else sampled
    kv_tokens = context_tokens if kv_tokens is None else kv_tokens
    rows = real_tokens if rows is None else rows
    H, D, K, W = (c.hidden_size, c.head_dim, c.num_experts_per_tok,
                  c.sliding_window)
    attention = 2 * H * D * (c.num_heads + c.num_kv_heads)
    mlp = 3 * H * c.intermediate_size
    expert = 3 * H * c.moe_intermediate_size
    shared = 3 * H * shared_width(c)
    router = H * c.router_width  # float32
    head = H * c.vocab_size
    share = c.num_experts / c.router_width
    flops = (
        2 * real_tokens * (
            c.num_layers * attention + Ld * mlp
            + Le * (router + shared + K * share * expert))
        + 4 * c.num_heads * D * (
            Lf * context_tokens + Lw * min(context_tokens, real_tokens * W))
        + 2 * head * sampled
    )
    one_pass = -(-real_tokens // max(passes, 1))
    touched = moe.experts_touched(c.num_experts, one_pass * K * share)
    experts = passes * Le * touched * expert * wb
    window_whole = Lw * kv_bytes * kv_tokens
    window_read = Lw * kv_bytes * min(kv_tokens, rows * W)
    rings = window_read + Lw * kv_bytes * real_tokens
    nbytes = (
        passes * (
            (c.num_layers * attention + Ld * mlp) * wb
            + Le * (router * 4 + shared * wb)
            + head * wb)
        + experts + rings
        + Lf * kv_bytes * (kv_tokens + real_tokens)
    )
    return (int(flops), int(nbytes), int(rings), int(experts),
            int(window_read), int(window_whole))
