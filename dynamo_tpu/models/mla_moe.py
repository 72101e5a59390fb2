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
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops.kv_quant import kv_layer, kv_page_size, kv_write, latent_row_width
from ..ops.latent_attention import absorbed_row_limit as row_limit_of_widths
from ..ops.latent_attention import (
    absorbed_attention,
    absorbed_rows_attention,
    expanded_attention,
    select_rows,
    selected_attention,
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


class LayerTypes(tuple):
    """A per-layer list of a configuration file as the dataclass keeps it:
    hashable (the configuration is a static argument and a cache's key), and
    equal to the LIST it came from, which is what the benchmark's checks
    compare a field with."""

    def __eq__(self, other):
        return tuple.__eq__(self, tuple(other) if isinstance(other, list) else other)

    def __ne__(self, other):
        return not self == other

    __hash__ = tuple.__hash__


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
    #: the rotation pairs NEIGHBOURS (2i, 2i + 1), not halves (i, i + D/2)
    rope_interleave: bool = False
    #: per layer "dense" | "sparse" (None: `first_k_dense_replace` dense
    #: layers, then sparse ones); more entries than layers: the first count
    mlp_layer_types: Optional[Tuple[str, ...]] = None
    #: learned sparse attention (`model_type` `glm_moe_dsa`): a row attends
    #: the `index_topk` positions its layer's indexer scores highest; 0: none,
    #: and no leaf, store or operation of what follows exists
    index_topk: int = 0
    index_n_heads: int = 0
    index_head_dim: int = 0
    indexer_rope_interleave: bool = False
    #: per layer "full" (an indexer of its own) | "shared" (the picks of the
    #: nearest full layer below)
    indexer_types: Optional[Tuple[str, ...]] = None

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
        L = self.num_layers
        for name, kinds in (("mlp_layer_types", ("dense", "sparse")),
                            ("indexer_types", ("full", "shared"))):
            given = getattr(self, name)
            if given is None:
                continue
            given = LayerTypes(tuple(given)[:L])  # the layers that run
            object.__setattr__(self, name, given)
            if len(given) != L or set(given) - set(kinds):
                raise ValueError(
                    f"{name} {given}: one of {kinds} a layer, {L} layers")
        if self.mlp_layer_types is not None:
            leading = next((i for i, k in enumerate(self.mlp_layer_types)
                            if k != "dense"), L)
            if leading != self.first_k_dense_replace:
                raise ValueError(
                    f"mlp_layer_types begins with {leading} dense layers and "
                    f"first_k_dense_replace says {self.first_k_dense_replace}")
            if "sparse" not in self.mlp_layer_types:
                raise ValueError("mlp_layer_types leaves no sparse layer")
        if self.index_topk:
            if self.indexer_types is None or self.indexer_types[0] != "full":
                raise ValueError(
                    "index_topk needs indexer_types, and a first layer that "
                    "is `full`: a `shared` layer borrows the picks of a full "
                    "layer BELOW it")
            if (self.index_n_heads < 1
                    or self.index_head_dim < self.qk_rope_head_dim):
                raise ValueError(
                    "an index head holds the rotated qk_rope_head_dim "
                    "dimensions first, then the others")

    @property
    def ffn_kinds(self) -> Tuple[str, ...]:
        """Per layer "dense" or "sparse"."""
        if self.mlp_layer_types is not None:
            return self.mlp_layer_types
        Ld = self.first_k_dense_replace
        return ("dense",) * Ld + ("sparse",) * (self.num_layers - Ld)

    @property
    def dense_layers(self) -> int:
        return self.ffn_kinds.count("dense")

    @property
    def full_layers(self) -> Tuple[int, ...]:
        """The layers that hold an indexer (none without `index_topk`)."""
        if not self.index_topk:
            return ()
        return tuple(i for i, k in enumerate(self.indexer_types) if k == "full")

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
            routed_layers=self.num_layers - self.dense_layers,
            state_shape=(1,), conv_shape=(1, 1), state_dtype=self.dtype,
            experts_per_token=self.num_experts_per_tok, value_store=False,
            index_layers=len(self.full_layers),
            index_dim=self.index_head_dim if self.index_topk else 0)

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

    @classmethod
    def tiny_mla_dsa(cls, **overrides):
        """`tiny_mla_moe` with the learned selection at the published
        ratios: 16 picks under contexts of tens to hundreds, an indexer in
        layers 0 and 2 whose picks layers 1 and 3 borrow, index heads half
        rotated, neighbours paired, half of the router's experts held."""
        kw = dict(
            rope_interleave=True, index_topk=16, index_n_heads=4,
            index_head_dim=16, indexer_rope_interleave=True,
            indexer_types=("full", "shared", "full", "shared"),
            mlp_layer_types=("dense", "sparse", "sparse", "sparse"),
        )
        kw.update(overrides)
        return cls.tiny_mla_moe(**kw)


# ---------------------------------------------------------------------- #
# weights
# ---------------------------------------------------------------------- #


def init_params(config: MlaMoeConfig, key: jax.Array) -> Dict[str, Any]:
    """Seeded random weights, every stacked leaf built once (hybrid.
    dense_leaf, expert_stack_leaf: no second copy of the experts while
    stacking). Matrices are named `w*`, `embed`, `lm_head` (the int8 control
    rounds those); norms, the float32 router and its choice bias are not."""
    c = config
    L, Ld = c.num_layers, c.dense_layers
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
    params = {
        "embed": dense((c.vocab_size, H)),
        "layers": {"attention": attention, "dense": mlp, "experts": routed},
        "final_norm": 1.0 + dense((H,), f32),
        "lm_head": dense((H, c.vocab_size)),
    }
    if c.index_topk:
        # the full layers' indexers, drawn LAST: a configuration without the
        # selection draws what it always drew
        Lf, J, D = len(c.full_layers), c.index_n_heads, c.index_head_dim
        params["layers"]["indexer"] = {
            "wq": dense((Lf, c.q_lora_rank, J * D)),
            "wk": dense((Lf, H, D)),
            "k_norm": 1.0 + dense((Lf, D), f32),
            "k_norm_bias": dense((Lf, D), f32),
            "w_heads": dense((Lf, H, J)),
        }
    return params


# ---------------------------------------------------------------------- #
# pieces
# ---------------------------------------------------------------------- #


def _rotate(x, positions, c: MlaMoeConfig, interleave: bool):
    """x [..., heads, rope] rotated at `positions` [...]: halves paired (i
    with i + rope / 2), or NEIGHBOURS (2i with 2i + 1) where `interleave`."""
    cos, sin = rope_cos_sin(positions, x.shape[-1], c.rope_theta)
    if not interleave:
        return apply_rope(x, cos, sin)
    pairs = x.astype(f32).reshape(*x.shape[:-1], -1, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    cos, sin = cos[..., None, :], sin[..., None, :]
    out = jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def _query_latent(layer, h, c: MlaMoeConfig):
    """c_q = rmsnorm(h W_qa) [..., q_lora_rank]."""
    with jax.named_scope("mla_q"):
        return norm(qdot(h, layer["wq_a"]).astype(c.dtype), layer["q_a_norm"],
                    c.rms_norm_eps)


def _queries(layer, h, positions, c: MlaMoeConfig):
    """q [..., heads, nope + rope] of h [..., H] at `positions` [...], its
    rope part rotated."""
    return _queries_of(layer, _query_latent(layer, h, c), positions, c)


def _queries_of(layer, cq, positions, c: MlaMoeConfig):
    """... of c_q [..., q_lora_rank], which a full layer's indexer reads too."""
    with jax.named_scope("mla_q"):
        q = qdot(cq, layer["wq_b"]).astype(c.dtype)
        q = q.reshape(*cq.shape[:-1], c.num_heads, c.qk_head_dim)
        nope = c.qk_nope_head_dim
        return jnp.concatenate(
            [q[..., :nope],
             _rotate(q[..., nope:], positions, c, c.rope_interleave)], axis=-1)


def index_inputs(ix, cq, h, positions, c: MlaMoeConfig):
    """A full layer's indexer on h [..., H] and its c_q: (q^I [..., J, D],
    k^I [..., D], w [..., J] float32). `q^I = c_q W_qI`; `k^I = layernorm(h
    W_kI)`, ONE key a token, which the index store keeps; the first
    `qk_rope_head_dim` dimensions of each are rotated; `w = h W_w` times the
    positive scale `J^-0.5 D^-0.5`."""
    J, D, rope = c.index_n_heads, c.index_head_dim, c.qk_rope_head_dim
    with jax.named_scope("dsa_index_inputs"):
        q = qdot(cq, ix["wq"]).astype(c.dtype).reshape(*cq.shape[:-1], J, D)
        k = qdot(h, ix["wk"])
        k = k - k.mean(-1, keepdims=True)
        k = k * jax.lax.rsqrt((k * k).mean(-1, keepdims=True) + c.rms_norm_eps)
        k = (k * ix["k_norm"] + ix["k_norm_bias"]).astype(c.dtype)
        turn = c.indexer_rope_interleave
        q = jnp.concatenate(
            [_rotate(q[..., :rope], positions, c, turn), q[..., rope:]], -1)
        k = jnp.concatenate(
            [_rotate(k[..., None, :rope], positions, c, turn)[..., 0, :],
             k[..., rope:]], -1)
        w = qdot(h, ix["w_heads"]).astype(f32) * (J * D) ** -0.5
        return q, k, w


def latent_rows(layer, h, positions, c: MlaMoeConfig):
    """[c | k_r | 0...] [..., head_dim] of h [..., H]: the row the cache
    keeps, the latent normed and the shared key rotated, padded with zeros
    to the row's width in HBM."""
    with jax.named_scope("mla_latent"):
        row = qdot(h, layer["wkv_a"]).astype(c.dtype)
        lat = norm(row[..., :c.kv_lora_rank], layer["kv_a_norm"],
                   c.rms_norm_eps)
        k_r = _rotate(row[..., None, c.kv_lora_rank:], positions, c,
                      c.rope_interleave)[..., 0, :]
        pad = jnp.zeros((*lat.shape[:-1], c.head_dim - c.latent_dim), c.dtype)
        return jnp.concatenate([lat, k_r, pad], axis=-1)


def _kvb(layer, c: MlaMoeConfig):
    """W_kvb [rank, heads, nope + v]."""
    return layer["wkv_b"].reshape(
        c.kv_lora_rank, c.num_heads, c.qk_nope_head_dim + c.v_head_dim)


def absorbed(layer, q, latent, page_tables, seq_lens, c: MlaMoeConfig):
    """One-token rows q [B, heads, nope + rope] over their pages in the
    latent space -> [B, heads, v_head_dim]."""
    with jax.named_scope("mla_absorb"):
        return _in_latent_space(
            layer, q, c, lambda ql: absorbed_attention(
                ql, latent, page_tables, seq_lens, c.kv_lora_rank,
                c.qk_head_dim ** -0.5))


def _in_latent_space(layer, q, c: MlaMoeConfig, walk):
    """q [B, heads, nope + rope] with W_kvb's key half folded in, through
    `walk((q~ | q_r)) -> u [B, heads, rank]`, and out through its value
    half -> [B, heads, v_head_dim]."""
    nope = c.qk_nope_head_dim
    w = _kvb(layer, c)
    q_lat = jnp.einsum("bhn,rhn->bhr", q[..., :nope], w[..., :nope],
                       preferred_element_type=f32).astype(c.dtype)
    u = walk(jnp.concatenate([q_lat, q[..., nope:]], axis=-1))
    return jnp.einsum("bhr,rhv->bhv", u, w[..., nope:],
                      preferred_element_type=f32).astype(c.dtype)


def selects(c: MlaMoeConfig, page_tables, page_size: int) -> bool:
    """Whether a program over these tables selects at all: a table of
    `index_topk` positions or fewer holds no context the selection would
    thin, and its rows take the walks that stand."""
    return bool(c.index_topk) and (
        page_tables.shape[-1] * page_size > c.index_topk)


def selected(layer, q, latent, tables, picks, serve, c: MlaMoeConfig):
    """One-token lanes q [N, heads, nope + rope] over the `index_topk` rows
    each PICKED (`picks` [N, k], a full layer's own or the layer below's),
    absorbed -> [N, heads, v_head_dim]; zeros where `serve` [N] is not."""
    with jax.named_scope("dsa_selected"):
        return _in_latent_space(
            layer, q, c, lambda ql: selected_attention(
                ql, latent, tables, picks, c.kv_lora_rank,
                c.qk_head_dim ** -0.5, serve))


def absorbed_row_limit(c: MlaMoeConfig) -> int:
    """The most tokens a row may hold and still attend absorbed, from the
    configuration's widths by ops/latent_attention's rule (the engine's
    counters read it here: mla_rows_absorbed_tokens)."""
    return row_limit_of_widths(
        c.kv_lora_rank, c.num_heads, c.qk_nope_head_dim, c.qk_rope_head_dim,
        c.v_head_dim, c.head_dim)


def rows_attention(layer, q, latent, page_tables, row_starts, row_lens,
                   ctx_lens, c: MlaMoeConfig, whole=None):
    """q [M, heads, nope + rope] on a flat axis of rows -> [M, heads,
    v_head_dim], each row by what it costs: rows of one token absorbed, as
    lanes (length 0 for every other row: an empty lane reads nothing); rows
    of 2 to `absorbed_row_limit(c)` tokens absorbed, a row at a time; rows
    of more expanded (a buffer too short to hold one has no such walk).
    `whole` [R] (a program that selects): the rows of several tokens whose
    context the selection leaves whole, which alone are served here; every
    other token is a lane of the selected walk, the one-token rows too."""
    M = q.shape[0]
    if whole is None:
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
            out = expanded_attention(*rows, longer_than=limit, only=whole)
    else:
        out = jnp.zeros((M, c.num_heads, c.v_head_dim), q.dtype)
    with jax.named_scope("mla_absorb_rows"):
        out = absorbed_rows_attention(*rows, upto=limit, out=out, only=whole)
    if whole is not None:
        return out
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
    pages)` on the normed input of layer `li` (`pages`: whatever the
    forward's attention threads from layer to layer: the latent pool, and
    for a configuration that selects the index store and the picks beside
    it). A layer's leaves are taken from the STORED stacks with one static
    index each, a dense or a sparse layer's by its place among its kind.
    -> (x, pages, the experts chosen [sparse layers, T, K])."""
    layers = params["layers"]
    names = moe.EXPERT_FORMS[EXPERT_FORM]
    stacks = {k: layers["experts"][k] for k in names}
    small = {k: v for k, v in layers["experts"].items() if k not in names}
    chosen, ld = [], 0
    for li, kind in enumerate(c.ffn_kinds):
        layer = jax.tree.map(lambda a: a[li], layers["attention"])
        with jax.named_scope("attention"):
            h = norm(x, layer["norm"], c.rms_norm_eps)
            out, pages = attn_fn(layer, h, pages, li)
        x = x + out
        if kind == "dense":
            x = dense_block(
                jax.tree.map(lambda a: a[ld], layers["dense"]), x, c)
            ld += 1
        else:
            le = li - ld
            x, idx = routed_block(
                jax.tree.map(lambda a: a[le], small), stacks, le, x, c, valid)
            chosen.append(idx)
    return x, pages, jnp.stack(chosen)


def _index_key_written(params, c: MlaMoeConfig, li, index, cq, h, positions,
                       phys, offs):
    """The full layer `li`'s indexer on h and its c_q, its key written into
    the index store at the slots the latent rows go to -> (the store, the
    layer's place among the full layers, q^I, w). Keys and queries are
    padded to the store's row (no lane at the store the engine allocates;
    a plain pool in `kv_v`'s place is as wide as the latent row)."""
    fi = c.full_layers.index(li)
    ix = jax.tree.map(lambda a: a[fi], params["layers"]["indexer"])
    q_i, k_i, w = index_inputs(ix, cq, h, positions, c)
    pad = index.shape[3] - k_i.shape[-1]
    k_i = jnp.pad(k_i, ((0, 0), (0, pad)))
    index = kv_write(index, fi, phys, offs, k_i[:, None, :])
    return index, fi, jnp.pad(q_i, ((0, 0), (0, 0), (0, pad))), w


class _Selecting(NamedTuple):
    """What a selecting forward's attention threads through the layers in
    `pages`' place: the latent pool, the index-key store (`kv_v`'s place in
    every program: ops/state_cache.py) and the picks of the nearest full
    layer below."""

    pool: Any
    index: Any
    picks: Any = None


def _attend_selecting(params, c: MlaMoeConfig, layer, h, sel: _Selecting, li,
                      positions, phys, offs, tables, seq_lens, serve,
                      standing=None):
    """A layer of a program that selects, over one-token lanes (a decode
    step's, or a flat buffer's tokens each under its row's table): the
    latent row and, in a full layer, the index key are written; a full layer
    scores and picks, a shared one takes `sel.picks`; the lanes `serve` marks
    attend their picks, and `standing(layer, q, latent) -> [N, heads, v]`
    serves the others. -> (the projected output, `sel` updated)."""
    cq = _query_latent(layer, h, c)
    q = _queries_of(layer, cq, positions, c)
    row = latent_rows(layer, h, positions, c)
    pool = kv_write(sel.pool, li, phys, offs, row[:, None, :])
    index, picks = sel.index, sel.picks
    if li in c.full_layers:
        index, fi, q_i, w = _index_key_written(
            params, c, li, index, cq, h, positions, phys, offs)
        with jax.named_scope("dsa_select"):
            picks = select_rows(q_i, w, kv_layer(index, fi), tables, seq_lens,
                                c.index_topk, serve)
    latent = kv_layer(pool, li)
    attn = selected(layer, q, latent, tables, picks, serve, c)
    if standing is not None:
        attn = jnp.where(serve[:, None, None], attn,
                         standing(layer, q, latent))
    return _o_proj(layer, attn, c), _Selecting(pool, index, picks)


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

    if c.index_topk:
        attn_fn, pages = _selecting(
            params, c, attn_fn, pages, kv_v, page_tables, positions, phys,
            offs, lambda: (page_tables, seq_lens, jnp.ones((B,), bool), None))

    x, pages, chosen = _layer_stack(params, c, x, pages, attn_fn)
    if c.index_topk:
        pages, kv_v = pages.pool, pages.index
    logits = _head(params, c, x)
    if cache is None:
        return logits, pages, kv_v
    ring = cache.routed_ring
    ring = ring.at[positions % ring.shape[0], :, jnp.arange(B)].set(
        jnp.moveaxis(chosen, 1, 0))
    return logits, cache.replace(pages=pages, routed_ring=ring), kv_v


def _selecting(params, c: MlaMoeConfig, attn_fn, pool, index, page_tables,
               positions, phys, offs, lanes: Callable,
               picked: Optional[list] = None):
    """(the attention of a forward whose configuration selects, what it
    threads through the layers). Where the program's tables are wide enough
    to select (`selects`): every layer through `_attend_selecting`, over
    `lanes() -> (each lane's table, the positions it may pick from, the
    lanes that select, the walk of the others or None)`; `picked` collects
    each full layer's picks. Else `attn_fn`, the walks that stand, with a
    full layer's index key written beside its latent row all the same (a
    page holds both, whoever wrote it)."""
    if selects(c, page_tables, kv_page_size(pool)):
        tables, seq_lens, serve, standing = lanes()

        def selecting(layer, h, sel: _Selecting, li):
            out, sel = _attend_selecting(
                params, c, layer, h, sel, li, positions, phys, offs, tables,
                seq_lens, serve, standing)
            if picked is not None and li in c.full_layers:
                picked.append(sel.picks)
            return out, sel

        return selecting, _Selecting(pool, index)

    def with_keys(layer, h, sel: _Selecting, li):
        out, pool = attn_fn(layer, h, sel.pool, li)
        index = sel.index
        if li in c.full_layers:
            index, *_ = _index_key_written(
                params, c, li, index, _query_latent(layer, h, c), h,
                positions, phys, offs)
        return out, _Selecting(pool, index)

    return with_keys, _Selecting(pool, index)


def _flat_rows(params, c: MlaMoeConfig, kv_k, kv_v, x, positions, phys, offs,
               valid, page_tables, row_starts, row_lens, ctx_lens, last,
               row_ids, picked: Optional[list] = None):
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

    if c.index_topk:
        def lanes():
            # a row of several tokens whose context the selection leaves
            # whole takes the walks that stand, by row; every other real
            # token (a decode row, a tail behind a long context) is a lane
            # of the selected walk under its row's table, with a pick of
            # its own
            whole = (row_lens > 1) & (ctx_lens + row_lens <= c.index_topk)
            ids = row_ids
            if ids is None:  # a prefill batch: rows of equal length
                T = x.shape[0] // page_tables.shape[0]
                ids = jnp.arange(x.shape[0], dtype=jnp.int32) // T
            return (page_tables[ids], positions + 1, valid & ~whole[ids],
                    lambda layer, q, latent: rows_attention(
                        layer, q, latent, page_tables, row_starts, row_lens,
                        ctx_lens, c, whole=whole))

        attn_fn, pages = _selecting(
            params, c, attn_fn, pages, kv_v, page_tables, positions, phys,
            offs, lanes, picked)

    x, pages, chosen = _layer_stack(params, c, x, pages, attn_fn, valid)
    if c.index_topk:
        pages, kv_v = pages.pool, pages.index
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
        row_starts, row_lens, ctx_lens, last_flat, row_ids)


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
    return _prefill_batch(params, config, tokens, positions, kv_k, kv_v,
                          page_tables, context_lens, last_idx, all_logits)


def _prefill_batch(params, c: MlaMoeConfig, tokens, positions, kv_k, kv_v,
                   page_tables, context_lens, last_idx, all_logits=False,
                   picked: Optional[list] = None):
    if all_logits:
        raise NotImplementedError(
            "the latent-attention family cannot verify drafts: its forwards "
            "return the last position's logits alone")
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
        row_starts, row_lens, context_lens, row_starts + last_idx, None,
        picked)


def prefill_picks(params, config: MlaMoeConfig, tokens, positions, kv_k, kv_v,
                  page_tables, context_lens, last_idx):
    """`prefill_forward_batched` that also says what it PICKED: (logits, kv_k,
    kv_v, picks [full layers, B * T, index_topk]): the positions each token
    of the chunks attended in each layer that holds an indexer (-1: none;
    all -1 for the tokens of a row that the selection leaves whole, which
    walks by row). For the builders' comparison of the served picks with the
    reference's (tools/long_lane.py; tests/test_mla_dsa_family.py); no
    engine program calls it."""
    if not selects(config, page_tables, kv_page_size(_pool(kv_k)[0])):
        raise ValueError("tables of index_topk positions or fewer pick nothing")
    picked: list = []
    out = _prefill_batch(params, config, tokens, positions, kv_k, kv_v,
                         page_tables, context_lens, last_idx, picked=picked)
    return (*out, jnp.stack(picked))


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
    if not c.index_topk:
        return {
            "decode": "xla-latent-absorbed",
            "prefill": "xla-latent-by-row",
            "ragged": "xla-latent-by-row",
        }
    # a configuration that selects: every one-token row READS its picked
    # rows alone (a gather by position, absorbed), and so does every token
    # of a longer row behind more than `index_topk` positions, a lane each
    # (gather, not a mask over the row's whole context: 2,048 rows a token
    # against the context's 16k, and one mechanism for every kind of row);
    # a row of several tokens that the selection leaves whole walks by row
    by_row = f"xla-latent-selected-top{c.index_topk}-gather+by-row"
    return {
        "decode": f"xla-latent-selected-top{c.index_topk}-gather",
        "prefill": by_row,
        "ragged": by_row,
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
              rows: Optional[int] = None,
              attended: Optional[int] = None,
              kv_selected: Optional[int] = None):
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
    with the row and are counted); the head.

    A configuration that selects (`index_topk`): a token attends `attended`
    positions, at most `index_topk` each, and a row must read `kv_selected`
    latent rows, at most `index_topk` a row (a floor: the tokens of one row
    may pick apart); the least bytes are THOSE, not the context's. In its
    full layers a token also passes through the indexer's three projections
    and scores every position of its context at `index_n_heads x
    index_head_dim` multiply-adds, and a row reads its context's index keys
    whole and writes its own: `index_kv_bytes`. `dsa_context_rows` /
    `dsa_selected_rows`: the positions the rows had behind them and the rows
    of those they had to read."""
    L, Ld = c.num_layers, c.dense_layers
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
    Lf, index_flops, index_kv, indexer = len(c.full_layers), 0, 0, 0
    read = kv_tokens
    if Lf:
        attended = context_tokens if attended is None else attended
        read = kv_tokens if kv_selected is None else kv_selected
        J, D = c.index_n_heads, c.index_head_dim
        indexer = c.q_lora_rank * J * D + H * D + H * J
        index_flops = 2 * Lf * (real_tokens * indexer + J * D * context_tokens)
        index_kv = (Lf * D * jnp.dtype(c.dtype).itemsize
                    * (kv_tokens + real_tokens))
        context_tokens = attended
    flops = (
        2 * real_tokens * (
            L * attention + Ld * mlp
            + Le * (router + shared + K * share * expert))
        + 2 * NH * (c.qk_head_dim + c.v_head_dim) * L * context_tokens
        + 2 * head * sampled + index_flops
    )
    one_pass = -(-real_tokens // max(passes, 1))
    touched = moe.experts_touched(c.num_experts, one_pass * K * share)
    experts = passes * Le * touched * expert * wb
    latent = L * kv_bytes * (read + real_tokens)
    expanded = (L * NH * (c.qk_head_dim + c.v_head_dim)
                * jnp.dtype(c.dtype).itemsize * (read + real_tokens))
    nbytes = (
        passes * (
            (L * attention + Ld * mlp + Lf * indexer) * wb
            + Le * (router * 4 + shared * wb)
            + head * wb)
        + experts + latent + index_kv
    )
    named = {"latent_kv_bytes": int(latent), "expert_bytes": int(experts),
             "latent_kv_expanded_bytes": int(expanded)}
    if Lf:
        named.update(index_kv_bytes=int(index_kv), dsa_context_rows=kv_tokens,
                     dsa_selected_rows=read)
    return int(flops), int(nbytes), named
