"""Latent-attention MoE family (GLM-4.7-Flash's `glm4_moe_lite` as its
`config.json` gives the sizes; docs/latent_cache.md): every layer is `x +
attn(rmsnorm(x))` then `x + ffn(rmsnorm(x))`.

    attention, every layer (MLA), for the normed input h [T, H]:
      c_q = rmsnorm(h W_qa)                      q_lora_rank
      q   = c_q W_qb                             heads x (nope | rope)
      [c | k_r] = h W_kva                        kv_lora_rank | rope
      c = rmsnorm(c); k_r = rope(k_r), ONE key for all heads; q_r = rope(q_r)
      THE CACHE HOLDS [c | k_r]: one row of kv_lora_rank + rope values a
      token and layer, after the norm and the rotation, and nothing else
      (in HBM the row is as wide as the next multiple of 128 lanes, 640 for
      576, the rest zeros: ops/kv_quant.latent_row_width says why).
      expanded  [k_n | v] = c W_kvb, heads x (nope | v_head_dim);
                score = (q_n . k_n + q_r . k_r) / sqrt(nope + rope)
      absorbed  q~ = q_n W_kvb^K; score = (q~ . c + q_r . k_r) / sqrt(...);
                u = sum p c; o = u W_kvb^V            (the same mathematics)
      attn = concat(o) W_o
    feed-forward, by `first_k_dense_replace`: dense gated-silu layers, then
      sparse ones whose router, routed block, shared expert and counters are
      models/exaone_moe.py's own (sigmoid scores, the k largest of score +
      choice bias, weights the scores over their sum times a factor).

Which path a row takes is read off the row's COST, not a switch (three kinds
of row; ops/latent_attention.py has the walks and the rule): a row of ONE
token (a decode lane, a mixed step's decode row, a prompt's one-token chunk)
attends absorbed, in the latent space, where a cached row is read as it
lies, a lane of the lanes' walk; a SHORT row of 2 to `absorbed_row_limit(c)`
tokens (a request's tail behind a cached prefix, a split prompt's last
chunk) attends absorbed too, its tokens folded into the head axis over the
row's own pages, read once; a row of more tokens (a prefill chunk) expands
its context's cached latents through W_kvb and attends at `heads` heads. The
limit is where the two forms cost the same multiply-adds: expanding costs
`rank x heads x (nope + v)` a CACHED position whatever the chunk holds, so
it pays only for a chunk long enough to share it (358 tokens at the
published widths). No flag, environment variable or field chooses.

The forwards keep models/llama.py's signatures. `kv_k` is the latent store
`[L, pages, rows, width]` (ops/kv_quant.latent_row_width: the dataclass's
`num_kv_heads` 1 and `head_dim` = the row's width in HBM say so to every
reader of the pool's shape), or, inside the engine, a `StateCache`
that holds it as `pages` beside the two leaves the chosen experts are
recorded in and NO state of a lane: pages are all there is to a sequence, so
the prefix index serves them as it serves K and V pages. `kv_v` is returned
as it came: there is no V store.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from ..ops.kv_quant import kv_layer, kv_page_size, kv_write, latent_row_width
from ..ops.latent_attention import absorbed_row_limit as row_limit_of_widths
from ..ops.latent_attention import (
    absorbed_attention,
    absorbed_rows_attention,
    expanded_attention,
)
from ..ops.paged_attention import rows_at
from ..ops.state_cache import StateCache, StateSpec
from . import moe
from .exaone_moe import (  # `expert_rows`: the engine's counter, as it is
    EXPERT_FORM,
    dense_block,
    expert_rows,  # noqa: F401
    routed_block,
    shared_width,
)
from .hybrid import _note_chosen, dense_leaf, expert_stack_leaf
from .llama import LlamaConfig, apply_rope, rope_cos_sin
from .nemotron_h import _head, _page_slots, norm
from .quant import embed_rows, qdot

f32 = jnp.float32
#: what the engine calls this family in its refusals and its log
STATE_FAMILY = (
    "the latent-attention family (models/mla_moe.py: one latent store of "
    "kv_lora_rank + rope values a token and layer, no V store)"
)
#: why the engine refuses what it refuses of a family with a StateCache,
#: in this family's words (engine._refuse_what_state_cannot_follow)
WHY_REFUSED = {
    "kvbm": "its tiers are sized for a K and a V block of heads x head "
            "size; the one latent store has no V half to fill them with",
    "spec": "the forwards return no logits of a draft's positions "
            "(all_logits)",
    "disagg": "the hand-off's payload is a K and a V block; the one latent "
              "store has no V half",
    "quant": "a latent row has no int8 form: one scale a page would round "
             "the latent and the rotated key together",
    "mesh": "the latent row has no head axis to shard over",
}
#: its attention walks each row's own context, whatever the table's width:
#: the mixed step keeps ONE table width (engine: _mixed_table_rungs)
ONE_TABLE_WIDTH = True


@dataclass(frozen=True)
class MlaMoeConfig(LlamaConfig):
    rope_theta: float = 1e6
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    first_k_dense_replace: int = 1  # the leading layers whose ffn is dense
    num_experts: int = 64  # the experts HELD on this chip: all of them
    router_width: int = 64  # the experts the router scores
    first_expert_held: int = 0
    num_experts_per_tok: int = 4
    moe_intermediate_size: int = 1536
    num_shared_experts: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.8
    n_group: int = 1
    topk_group: int = 1

    def __post_init__(self):
        # what the CACHE holds a token and layer, in the names every reader
        # of the pool's shape uses: one "head" as wide as the row in HBM
        object.__setattr__(self, "num_kv_heads", 1)
        object.__setattr__(self, "head_dim", latent_row_width(self.latent_dim))
        if self.n_group != 1 or self.topk_group != 1:
            raise ValueError(
                f"n_group {self.n_group} / topk_group {self.topk_group}: the "
                "group-limited choice (the k largest within the best "
                "topk_group of n_group groups of experts) is not written; "
                "this family chooses among all the router's experts "
                "(n_group 1, topk_group 1)")
        if self.qk_rope_head_dim % 2:
            raise ValueError("qk_rope_head_dim is rotated in pairs: even")
        if not 0 <= self.first_k_dense_replace < self.num_layers:
            raise ValueError("first_k_dense_replace leaves no sparse layer")
        if self.first_expert_held + self.num_experts > self.router_width:
            raise ValueError(
                f"experts [{self.first_expert_held}, "
                f"{self.first_expert_held + self.num_experts}) lie past the "
                f"router's width {self.router_width}")

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_dim(self) -> int:
        """Values the cache keeps a token and layer: [c | k_r]."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    def state_spec(self) -> StateSpec:
        """No lane keeps anything: the StateCache is there for the leaves
        the chosen experts are recorded in (ops/state_cache.py)."""
        return StateSpec(
            state_layers=0, attention_layers=self.num_layers,
            routed_layers=self.num_layers - self.first_k_dense_replace,
            state_shape=(1,), conv_shape=(1, 1), state_dtype=self.dtype,
            experts_per_token=self.num_experts_per_tok, value_store=False)

    @classmethod
    def tiny_mla_moe(cls, **overrides):
        """CPU-test scale with the published ratios: a latent wider than a
        head's rope part, `v_head_dim` unlike `qk_nope_head_dim`, one dense
        layer and three sparse ones."""
        kw = dict(
            vocab_size=512, hidden_size=64, intermediate_size=96,
            num_layers=4, num_heads=4, max_position=2048, rope_theta=1e4,
            rms_norm_eps=1e-5, q_lora_rank=24, kv_lora_rank=32,
            qk_nope_head_dim=12, qk_rope_head_dim=8, v_head_dim=16,
            num_experts=8, router_width=8, num_experts_per_tok=2,
            moe_intermediate_size=32,
        )
        kw.update(overrides)
        return cls(**kw)


# ---------------------------------------------------------------------- #
# weights
# ---------------------------------------------------------------------- #


def init_params(config: MlaMoeConfig, key: jax.Array) -> Dict[str, Any]:
    """Seeded random weights, every stacked leaf built once (hybrid.
    dense_leaf, expert_stack_leaf: no second copy of the experts while
    stacking). Matrices are named `w*`, `embed`, `lm_head` (the int8 control
    rounds those); norms, the float32 router and its choice bias are not."""
    c = config
    L, Ld = c.num_layers, c.first_k_dense_replace
    Le = L - Ld
    H, NH = c.hidden_size, c.num_heads
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

    attention = {
        "norm": 1.0 + dense((L, H), f32),
        "wq_a": dense((L, H, c.q_lora_rank)),
        "q_a_norm": 1.0 + dense((L, c.q_lora_rank), f32),
        "wq_b": dense((L, c.q_lora_rank, NH * c.qk_head_dim)),
        "wkv_a": dense((L, H, c.latent_dim)),
        "kv_a_norm": 1.0 + dense((L, c.kv_lora_rank), f32),
        "wkv_b": dense(
            (L, c.kv_lora_rank, NH * (c.qk_nope_head_dim + c.v_head_dim))),
        "wo": dense((L, NH * c.v_head_dim, H)),
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
        "layers": {"attention": attention, "dense": mlp, "experts": routed},
        "final_norm": 1.0 + dense((H,), f32),
        "lm_head": dense((H, c.vocab_size)),
    }


# ---------------------------------------------------------------------- #
# pieces
# ---------------------------------------------------------------------- #


def _queries(layer, h, positions, c: MlaMoeConfig):
    """q [..., heads, nope + rope] of h [..., H] at `positions` [...], its
    rope part rotated."""
    with jax.named_scope("mla_q"):
        cq = norm(qdot(h, layer["wq_a"]).astype(c.dtype), layer["q_a_norm"],
                  c.rms_norm_eps)
        q = qdot(cq, layer["wq_b"]).astype(c.dtype)
        q = q.reshape(*h.shape[:-1], c.num_heads, c.qk_head_dim)
        cos, sin = rope_cos_sin(positions, c.qk_rope_head_dim, c.rope_theta)
        nope = c.qk_nope_head_dim
        return jnp.concatenate(
            [q[..., :nope], apply_rope(q[..., nope:], cos, sin)], axis=-1)


def latent_rows(layer, h, positions, c: MlaMoeConfig):
    """[c | k_r | 0...] [..., head_dim] of h [..., H]: the row the cache
    keeps, the latent normed and the shared key rotated, padded with zeros
    to the row's width in HBM."""
    with jax.named_scope("mla_latent"):
        row = qdot(h, layer["wkv_a"]).astype(c.dtype)
        lat = norm(row[..., :c.kv_lora_rank], layer["kv_a_norm"],
                   c.rms_norm_eps)
        cos, sin = rope_cos_sin(positions, c.qk_rope_head_dim, c.rope_theta)
        k_r = apply_rope(row[..., None, c.kv_lora_rank:], cos, sin)[..., 0, :]
        pad = jnp.zeros((*lat.shape[:-1], c.head_dim - c.latent_dim), c.dtype)
        return jnp.concatenate([lat, k_r, pad], axis=-1)


def _kvb(layer, c: MlaMoeConfig):
    """W_kvb [rank, heads, nope + v]."""
    return layer["wkv_b"].reshape(
        c.kv_lora_rank, c.num_heads, c.qk_nope_head_dim + c.v_head_dim)


def absorbed(layer, q, latent, page_tables, seq_lens, c: MlaMoeConfig):
    """One-token rows q [B, heads, nope + rope] over their pages in the
    latent space -> [B, heads, v_head_dim]."""
    nope, rank = c.qk_nope_head_dim, c.kv_lora_rank
    w = _kvb(layer, c)
    with jax.named_scope("mla_absorb"):
        q_lat = jnp.einsum("bhn,rhn->bhr", q[..., :nope], w[..., :nope],
                           preferred_element_type=f32).astype(c.dtype)
        u = absorbed_attention(
            jnp.concatenate([q_lat, q[..., nope:]], axis=-1), latent,
            page_tables, seq_lens, rank, c.qk_head_dim ** -0.5)
        return jnp.einsum("bhr,rhv->bhv", u, w[..., nope:],
                          preferred_element_type=f32).astype(c.dtype)


def absorbed_row_limit(c: MlaMoeConfig) -> int:
    """The most tokens a row may hold and still attend absorbed, from the
    configuration's widths by ops/latent_attention's rule (the engine's
    counters read it here: mla_rows_absorbed_tokens)."""
    return row_limit_of_widths(
        c.kv_lora_rank, c.num_heads, c.qk_nope_head_dim, c.qk_rope_head_dim,
        c.v_head_dim, c.head_dim)


def rows_attention(layer, q, latent, page_tables, row_starts, row_lens,
                   ctx_lens, c: MlaMoeConfig):
    """q [M, heads, nope + rope] on a flat axis of rows -> [M, heads,
    v_head_dim], each row by what it costs: rows of one token absorbed, as
    lanes (length 0 for every other row: an empty lane reads nothing); rows
    of 2 to `absorbed_row_limit(c)` tokens absorbed, a row at a time; rows
    of more expanded (a buffer too short to hold one has no such walk)."""
    M = q.shape[0]
    one = row_lens == 1
    slots = jnp.where(one, row_starts, M).astype(jnp.int32)
    lanes = absorbed(layer, rows_at(q, slots), latent, page_tables,
                     jnp.where(one, ctx_lens + 1, 0), c)
    limit = absorbed_row_limit(c)
    rows = (q, latent, layer["wkv_b"], page_tables, row_starts, row_lens,
            ctx_lens, c.kv_lora_rank, c.qk_nope_head_dim,
            c.qk_head_dim ** -0.5)
    if M > limit:
        with jax.named_scope("mla_expand"):
            out = expanded_attention(*rows, longer_than=limit)
    else:
        out = jnp.zeros((M, c.num_heads, c.v_head_dim), q.dtype)
    with jax.named_scope("mla_absorb_rows"):
        out = absorbed_rows_attention(*rows, upto=limit, out=out)
    return out.at[slots].set(lanes, mode="drop")


def _o_proj(layer, attn, c: MlaMoeConfig):
    with jax.named_scope("o_proj"):
        attn = attn.reshape(*attn.shape[:-2], c.num_heads * c.v_head_dim)
        return qdot(attn.astype(c.dtype), layer["wo"]).astype(c.dtype)


# ---------------------------------------------------------------------- #
# the layer stack
# ---------------------------------------------------------------------- #


def _pool(kv_k):
    """(the latent pool, the StateCache it came in or None)."""
    if isinstance(kv_k, StateCache):
        return kv_k.pages, kv_k
    return kv_k, None


def _layer_stack(params, c: MlaMoeConfig, x, pages, attn_fn, valid=None):
    """x [T, H] through the layers: `attn_fn(layer, h, pages, li) -> (out,
    pages)` on the normed input of layer `li`. A layer's leaves are taken
    from the STORED stacks with one static index each. -> (x, pages, the
    experts chosen [sparse layers, T, K])."""
    layers = params["layers"]
    names = moe.EXPERT_FORMS[EXPERT_FORM]
    stacks = {k: layers["experts"][k] for k in names}
    small = {k: v for k, v in layers["experts"].items() if k not in names}
    chosen = []
    for li in range(c.num_layers):
        layer = jax.tree.map(lambda a: a[li], layers["attention"])
        with jax.named_scope("attention"):
            h = norm(x, layer["norm"], c.rms_norm_eps)
            out, pages = attn_fn(layer, h, pages, li)
        x = x + out
        le = li - c.first_k_dense_replace
        if le < 0:
            x = dense_block(
                jax.tree.map(lambda a: a[li], layers["dense"]), x, c)
        else:
            x, idx = routed_block(
                jax.tree.map(lambda a: a[le], small), stacks, le, x, c, valid)
            chosen.append(idx)
    return x, pages, jnp.stack(chosen)


def _refuse(lora, emb_override=None):
    if lora is not None or emb_override is not None:
        raise NotImplementedError(
            "the latent-attention family (models/mla_moe.py) takes no LoRA "
            "adapter and no multimodal embedding rows"
        )


# ---------------------------------------------------------------------- #
# the five forwards
# ---------------------------------------------------------------------- #


def decode_forward(
    params: Dict[str, Any],
    config: MlaMoeConfig,
    tokens: jax.Array,  # [B] one new token per lane
    positions: jax.Array,  # [B]
    kv_k,  # the latent store, or the StateCache that holds it
    kv_v: jax.Array,  # returned as it came
    page_tables: jax.Array,  # [B, max_pages]
    seq_lens: jax.Array,  # [B] lengths INCLUDING the new token
    lora=None,
):
    """One decode step for the whole slot batch, every lane absorbed;
    returns (logits [B, vocab], kv_k, kv_v)."""
    _refuse(lora)
    c = config
    B = tokens.shape[0]
    pages, cache = _pool(kv_k)
    with jax.named_scope("embed"):
        x = embed_rows(params["embed"], tokens, c.dtype)
    phys, offs = _page_slots(page_tables, positions, kv_page_size(pages))

    def attn_fn(layer, h, pages, li):
        q = _queries(layer, h, positions, c)
        row = latent_rows(layer, h, positions, c)
        pages = kv_write(pages, li, phys, offs, row[:, None, :])
        attn = absorbed(layer, q, kv_layer(pages, li), page_tables, seq_lens, c)
        return _o_proj(layer, attn, c), pages

    x, pages, chosen = _layer_stack(params, c, x, pages, attn_fn)
    logits = _head(params, c, x)
    if cache is None:
        return logits, pages, kv_v
    ring = cache.routed_ring
    ring = ring.at[positions % ring.shape[0], :, jnp.arange(B)].set(
        jnp.moveaxis(chosen, 1, 0))
    return logits, cache.replace(pages=pages, routed_ring=ring), kv_v


def _flat_rows(params, c: MlaMoeConfig, kv_k, kv_v, x, positions, phys, offs,
               valid, page_tables, row_starts, row_lens, ctx_lens, last):
    """x [M, H] on a flat axis that rows share (a mixed step's buffer, a
    prefill batch's chunks laid end to end): every row's latents are
    written (a slot that is not `valid` writes to the scratch page), then
    each row attends by its length (`rows_attention`). -> (logits
    of the slots `last` [R, vocab], kv_k, kv_v)."""
    pages, cache = _pool(kv_k)
    phys = jnp.where(valid, phys, 0)

    def attn_fn(layer, h, pages, li):
        q = _queries(layer, h, positions, c)
        row = latent_rows(layer, h, positions, c)
        pages = kv_write(pages, li, phys, offs, row[:, None, :])
        attn = rows_attention(
            layer, q, kv_layer(pages, li), page_tables, row_starts, row_lens,
            ctx_lens, c)
        return _o_proj(layer, attn, c), pages

    x, pages, chosen = _layer_stack(params, c, x, pages, attn_fn, valid)
    logits = _head(params, c, x[last])
    if cache is None:
        return logits, pages, kv_v
    flat = _note_chosen(cache.routed_flat, chosen)
    return logits, cache.replace(pages=pages, routed_flat=flat), kv_v


def ragged_forward(
    params: Dict[str, Any],
    config: MlaMoeConfig,
    tokens: jax.Array,  # [M] flat packed: prefill chunks + decode singletons
    positions: jax.Array,  # [M]
    row_ids: jax.Array,  # [M]
    kv_k,
    kv_v: jax.Array,
    page_tables: jax.Array,  # [R, max_pages]
    row_starts: jax.Array,  # [R]
    row_lens: jax.Array,  # [R]
    ctx_lens: jax.Array,  # [R]
    last_flat: jax.Array,  # [R]
    lora=None,
    long_rows: Optional[int] = None,
):
    """The mixed step's forward over a compact flat buffer (models/llama.py:
    ragged_forward's contract): every row's latents are written, then each
    row attends by its length (`rows_attention`). Returns (logits
    of each row's last token [R, vocab], kv_k, kv_v)."""
    _refuse(lora)
    c = config
    M = tokens.shape[0]
    with jax.named_scope("embed"):
        x = embed_rows(params["embed"], tokens, c.dtype)
    phys, offs = _page_slots(
        page_tables[row_ids], positions, kv_page_size(_pool(kv_k)[0]))
    valid = jnp.arange(M, dtype=jnp.int32) < row_lens.sum()
    return _flat_rows(
        params, c, kv_k, kv_v, x, positions, phys, offs, valid, page_tables,
        row_starts, row_lens, ctx_lens, last_flat)


def prefill_forward_batched(
    params: Dict[str, Any],
    config: MlaMoeConfig,
    tokens: jax.Array,  # [B, T] one chunk per sequence (padded to bucket)
    positions: jax.Array,  # [B, T]
    kv_k,
    kv_v: jax.Array,
    page_tables: jax.Array,  # [B, max_pages]
    context_lens: jax.Array,  # [B]
    last_idx: jax.Array,  # [B] index of the last REAL token per chunk
    emb_override=None,
    emb_mask=None,
    all_logits: bool = False,
    lora=None,
):
    """Batched chunked prefill: the chunks as rows of one flat axis (row b:
    slots b * T ..., last_idx[b] + 1 real ones). Returns (logits_last [B,
    vocab], kv_k, kv_v)."""
    _refuse(lora, emb_override)
    if all_logits:
        raise NotImplementedError(
            "the latent-attention family cannot verify drafts: its forwards "
            "return the last position's logits alone")
    c = config
    B, T = tokens.shape
    with jax.named_scope("embed"):
        x = embed_rows(params["embed"], tokens, c.dtype).reshape(B * T, -1)
    row_lens = last_idx + 1
    row_starts = jnp.arange(B, dtype=jnp.int32) * T
    valid = (jnp.arange(T)[None, :] < row_lens[:, None]).reshape(B * T)
    phys, offs = _page_slots(
        page_tables, positions, kv_page_size(_pool(kv_k)[0]))
    return _flat_rows(
        params, c, kv_k, kv_v, x, positions.reshape(B * T),
        phys.reshape(B * T), offs.reshape(B * T), valid, page_tables,
        row_starts, row_lens, context_lens, row_starts + last_idx)


def prefill_forward(
    params: Dict[str, Any],
    config: MlaMoeConfig,
    tokens: jax.Array,  # [chunk]
    positions: jax.Array,  # [chunk]
    kv_k,
    kv_v: jax.Array,
    page_table: jax.Array,  # [max_pages]
    context_len: jax.Array,
    last_idx: Optional[jax.Array] = None,
):
    """One prompt chunk of a single sequence: the batch of one."""
    T = tokens.shape[0]
    last = jnp.asarray(T - 1 if last_idx is None else last_idx, jnp.int32)
    logits, kv_k, kv_v = prefill_forward_batched(
        params, config, tokens[None], positions[None], kv_k, kv_v,
        page_table[None], jnp.asarray(context_len, jnp.int32)[None],
        last[None])
    return logits[0], kv_k, kv_v


# ---------------------------------------------------------------------- #
# host arithmetic for the engine's counters and its log
# ---------------------------------------------------------------------- #


def attention_impl(c: MlaMoeConfig) -> Dict[str, str]:
    """What ran, by the engine's three attention surfaces (stats()
    ["attention_impl"]): every walk is XLA over gathered blocks of pages,
    and a prefill batch's chunks and a mixed step's rows each take the one
    their length gives them (`rows_attention`)."""
    return {
        "decode": "xla-latent-absorbed",
        "prefill": "xla-latent-by-row",
        "ragged": "xla-latent-by-row",
    }


def latent_row_bytes(c: MlaMoeConfig) -> int:
    """Bytes a token's row takes in HBM a layer, its zeros among them:
    what a step reads and what the pool is sized by."""
    return c.head_dim * jnp.dtype(c.dtype).itemsize


def step_work(c: MlaMoeConfig, real_tokens: int, context_tokens: int,
              passes: int, *, sampled: Optional[int] = None,
              kv_tokens: Optional[int] = None,
              weight_bytes: Optional[float] = None,
              kv_bytes: Optional[float] = None,
              rows: Optional[int] = None):
    """(useful operations, least HBM bytes, and by name: of those bytes
    the latent cache's `latent_kv_bytes` and the experts' `expert_bytes`,
    and what the same context would cost as `heads` heads of K and V,
    `latent_kv_expanded_bytes`) of one pipeline entry, counted as
    exaone_moe.step_work counts. A real token passes through every layer's five attention
    projections (W_kvb at its own position: the expanded form's count; the
    absorbed form multiplies the same matrix on the query's side), the dense
    layers' feed-forward, every sparse layer's router, shared expert and K
    chosen experts and, where sampled, the head; it attends its context at
    `heads` x (nope + rope + v) x 2 operations a cached token, the
    expanded form's count again (the absorbed form spends 2 x (rank + rope)
    + 2 x rank a head instead: more operations over the same bytes, so the
    useful count is the smaller one). Bytes: per pass the weights once,
    with the experts a pass's real rows touch in expectation under an even
    router; the context's latent rows read once and the new ones written,
    each at its width in HBM (640 lanes for 512 + 64: the zeros are read
    with the row and are counted); the head."""
    L, Ld = c.num_layers, c.first_k_dense_replace
    Le = L - Ld
    wb = jnp.dtype(c.dtype).itemsize if weight_bytes is None else weight_bytes
    kv_bytes = latent_row_bytes(c) if kv_bytes is None else kv_bytes
    sampled = real_tokens if sampled is None else sampled
    kv_tokens = context_tokens if kv_tokens is None else kv_tokens
    H, NH, K = c.hidden_size, c.num_heads, c.num_experts_per_tok
    attention = (
        H * c.q_lora_rank + c.q_lora_rank * NH * c.qk_head_dim
        + H * c.latent_dim
        + c.kv_lora_rank * NH * (c.qk_nope_head_dim + c.v_head_dim)
        + NH * c.v_head_dim * H)
    mlp = 3 * H * c.intermediate_size
    expert = 3 * H * c.moe_intermediate_size
    shared = 3 * H * shared_width(c)
    router = H * c.router_width  # float32
    head = H * c.vocab_size
    share = c.num_experts / c.router_width
    flops = (
        2 * real_tokens * (
            L * attention + Ld * mlp
            + Le * (router + shared + K * share * expert))
        + 2 * NH * (c.qk_head_dim + c.v_head_dim) * L * context_tokens
        + 2 * head * sampled
    )
    one_pass = -(-real_tokens // max(passes, 1))
    touched = moe.experts_touched(c.num_experts, one_pass * K * share)
    experts = passes * Le * touched * expert * wb
    latent = L * kv_bytes * (kv_tokens + real_tokens)
    expanded = (L * NH * (c.qk_head_dim + c.v_head_dim)
                * jnp.dtype(c.dtype).itemsize * (kv_tokens + real_tokens))
    nbytes = (
        passes * (
            (L * attention + Ld * mlp) * wb
            + Le * (router * 4 + shared * wb)
            + head * wb)
        + experts + latent
    )
    return int(flops), int(nbytes), {
        "latent_kv_bytes": int(latent), "expert_bytes": int(experts),
        "latent_kv_expanded_bytes": int(expanded)}
