"""Plain reference of the latent-attention MoE block with a LEARNED SPARSE
selection of the context (GLM-5.2, `model_type` `glm_moe_dsa`, as its
`config.json` gives the sizes and the per-layer lists). Every layer, pre-norm:

    x = x + attn_l(rmsnorm(x));  x = x + ffn_l(rmsnorm(x))

a final norm and an untied head; plain RMSNorm (`rms_norm_eps`), no bias on
any projection.

  attention, every layer (multi-head latent attention, EXPANDED and no other
     form), for the normed input h [T, hidden]:
       c_q = rmsnorm(h W_qa)              `q_lora_rank`
       q   = c_q W_qb                     heads x (`qk_nope_head_dim` q_n |
                                          `qk_rope_head_dim` q_r)
       [c | k_r] = h W_kva                `kv_lora_rank` | `qk_rope_head_dim`
       c = rmsnorm(c)
       k_r = rope(k_r): ONE rotated key for all heads; q_r = rope(q_r);
         `rope_interleave` true: the rotation pairs NEIGHBOURS (2i, 2i + 1),
         at `rope_theta`, type default (no scaling)
       [k_n | v] = c W_kvb                heads x (`qk_nope_head_dim` |
                                          `v_head_dim`)
       score = (q_n . k_n + q_r . k_r) / sqrt(qk_nope_head_dim +
         qk_rope_head_dim); softmax over the positions s in S_t ALONE (a mask
         over every (t, s): the reference reads everything and masks);
         o = sum p v; attn = concat(o) W_o
  the indexer, in the layers whose `indexer_types` entry is `full`:
       q^I = c_q W_qI                     `index_n_heads` x `index_head_dim`
       k^I = layernorm(h W_kI)            ONE key of `index_head_dim` a
                                          token (weight and bias, eps
                                          `rms_norm_eps`)
       the first `qk_rope_head_dim` dimensions of each q^I_j and of k^I are
         rotated (`indexer_rope_interleave`: neighbours), the others are not
       w = h W_w                          `index_n_heads` weights a token
       I[t, s] = sum_j w[t, j] relu(q^I[t, j] . k^I[s])
                 x index_n_heads^-0.5 x index_head_dim^-0.5   for s <= t
       S_t = the `index_topk` positions of largest I[t, .] among s <= t
         (`jax.lax.top_k` of float32 scores); every position where
         t < index_topk
  in the layers whose entry is `shared`: no indexer and no weights of one;
       S_t is the set of the nearest `full` layer below.
  feed-forward by `mlp_layer_types`: `dense`: `W2(silu(W1 h) * W3 h)` at
     `intermediate_size`; `sparse`: scores `s = sigmoid(h W_r)` over the
     `n_routed_experts` (`scoring_func`); the `num_experts_per_tok` chosen are
     the largest of `s + b` (the choice bias, `topk_method` `noaux_tc`;
     `n_group` 1 and `topk_group` 1: no group limit); their weights are `s` at
     the chosen over their sum (`norm_topk_prob`) times
     `routed_scaling_factor`; expert e: `W2_e(silu(W1_e h) * W3_e h)` at
     `moe_intermediate_size`; one shared expert of the same form on every
     token, no gate; out = routed + shared. Where the weights hold a SHARE of
     the router's experts (`[first_expert_held, first_expert_held + held)`),
     the routed part is the held experts' part of the sum.

A full-sequence causal forward in jax.numpy: float32 activations over the
model's own (bf16) weights; no cache, no page, no chunk, no batching, no
kernel, no absorbed form, no gather of selected rows, nothing from
dynamo_tpu/ops or the serving forwards. Index scores and attention run a
block of query positions at a time (each against every key, under its mask),
so that 2,048 positions fit beside 11 GB of weights. The caller sets the
matmul precision (`highest`, or the TPU's default for the bf16 control).

Departures from the published description, each listed in the
configuration's file under `assumed`: the indexer's form is the public
lightning indexer's (DeepSeek-V3.2's report and inference code), which the
`index_*` keys name and the source does not spell out; k^I is under a
LayerNorm with weight and bias; the rotated half of an index head comes
FIRST; index keys are kept whole (the public code keeps them in fp8 behind a
Hadamard rotation, which is orthogonal and changes no score); `shared` layers
hold no indexer weights; `index_skip_topk_offset`, `index_topk_freq` and
`index_topk_pattern` are read through `indexer_types`, which spells the
layers out; the norms stand before each sublayer; no multi-token-prediction
module (`num_nextn_predict_layers` 1): no weights are made for it.

`picks` (beside `logits`) returns S_t of the last positions, layer by layer,
for the long lane's comparison of sets (tools/long_lane.py).
"""

import math

import jax
import jax.numpy as jnp

f32 = jnp.float32
#: query positions a block of the attention holds at most (the padded
#: lengths of reference.py are multiples of 64, so 64 or more)
QUERY_BLOCK = 256
EXPERT_STACKS = ("w_gate", "w_up", "w_down")


def rms(x, w, eps):
    """Plain weight: x * rsqrt(mean(x^2) + eps) * w."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w.astype(f32)


def rotate(x, theta, neighbours: bool, start=0):
    """Full rotary on x [T, heads, D] at positions start .. start + T - 1:
    dimension 2i with 2i + 1 (`neighbours`), or i with i + D / 2."""
    T, _, D = x.shape
    inv_freq = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=f32) / D)
    angles = (start + jnp.arange(T)).astype(f32)[:, None] * inv_freq  # [T, D / 2]
    cos, sin = jnp.cos(angles)[:, None], jnp.sin(angles)[:, None]
    if neighbours:
        x1, x2 = x[..., 0::2], x[..., 1::2]
        return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1).reshape(x.shape)
    x1, x2 = x[..., : D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def index_inputs(h, c_q, w, cfg):
    """(q^I [T, J, D], k^I [T, D], the heads' weights [T, J]) of a full
    layer's indexer `w`."""
    T = h.shape[0]
    J, D, rope = cfg.index_n_heads, cfg.index_head_dim, cfg.qk_rope_head_dim
    turn = cfg.indexer_rope_interleave
    q = (c_q @ w["wq"].astype(f32)).reshape(T, J, D)
    k = h @ w["wk"].astype(f32)
    k = k - k.mean(-1, keepdims=True)
    k = k * jax.lax.rsqrt((k * k).mean(-1, keepdims=True) + cfg.rms_norm_eps)
    k = k * w["k_norm"].astype(f32) + w["k_norm_bias"].astype(f32)
    q = jnp.concatenate([rotate(q[..., :rope], cfg.rope_theta, turn), q[..., rope:]], -1)
    k = jnp.concatenate(
        [rotate(k[:, None, :rope], cfg.rope_theta, turn)[:, 0], k[:, rope:]], -1)
    return q, k, (h @ w["w_heads"].astype(f32)) * (J * D) ** -0.5


def attention(h, w, cfg, indexer, picks):
    """h [T, hidden] (normed) -> (o_proj(softmax attention over S_t),
    expanded: every position's keys and values at `num_heads` heads; S_t
    [T, min(index_topk, T)], -1: no position): `indexer` (a full layer's
    weights) makes S_t, else `picks` (the layer below's) is taken. A block
    of query positions at a time: its index scores against every key, the
    `index_topk` largest among s <= t (all of them where t < index_topk), the
    mask of those, the softmax under it."""
    T = h.shape[0]
    H, rank = cfg.num_heads, cfg.kv_lora_rank
    nope, rope, vdim = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    k_top = min(cfg.index_topk, T)
    c_q = rms(h @ w["wq_a"].astype(f32), w["q_a_norm"], cfg.rms_norm_eps)
    row = h @ w["wkv_a"].astype(f32)  # [T, rank + rope]
    c = rms(row[:, :rank], w["kv_a_norm"], cfg.rms_norm_eps)
    k_r = rotate(row[:, None, rank:], cfg.rope_theta, cfg.rope_interleave)[:, 0]  # [T, rope]
    # [k_n | v] = c W_kvb, a head's two parts each by its own columns (one
    # product of both, sliced, holds three copies of every position's keys
    # and values: 3.5 GB at 16k positions and 64 heads)
    w_kvb = w["wkv_b"].astype(f32).reshape(rank, H, nope + vdim)
    k_n = jnp.einsum("sr,rhd->shd", c, w_kvb[..., :nope])
    v = jnp.einsum("sr,rhd->shd", c, w_kvb[..., nope:])
    if indexer is not None:
        q_i, k_i, heads = index_inputs(h, c_q, indexer, cfg)
    j = jnp.arange(T)[None, :]

    def block(t0, n):  # the query positions t0 .. t0 + n against every key
        def of(x):
            return jax.lax.dynamic_slice_in_dim(x, t0, n)

        if indexer is not None:
            t = t0 + jnp.arange(n)[:, None]
            s = jnp.einsum("tjd,sd->tjs", of(q_i), k_i)
            s = jnp.einsum("tjs,tj->ts", jax.nn.relu(s), of(heads))
            top, at = jax.lax.top_k(jnp.where(j <= t, s, -jnp.inf), k_top)
            at = jnp.where(top > -jnp.inf, at, -1)
        else:
            at = of(picks)
        seen = jnp.zeros((n, T + 1), bool).at[
            jnp.arange(n)[:, None], jnp.where(at >= 0, at, T)].set(True)[:, :T]
        q = (of(c_q) @ w["wq_b"].astype(f32)).reshape(n, H, nope + rope)
        q_r = rotate(q[..., nope:], cfg.rope_theta, cfg.rope_interleave, t0)
        s = jnp.einsum("thd,shd->hts", q[..., :nope], k_n)
        s = s + jnp.einsum("thd,sd->hts", q_r, k_r)
        p = jax.nn.softmax(
            jnp.where(seen[None], s / jnp.sqrt(f32(nope + rope)), -jnp.inf), -1)
        return jnp.einsum("hts,shd->thd", p, v).reshape(n, H * vdim), at

    n = math.gcd(T, QUERY_BLOCK)
    out, at = jax.lax.map(lambda t0: block(t0, n), jnp.arange(0, T, n))
    return out.reshape(T, H * vdim) @ w["wo"].astype(f32), at.reshape(T, k_top)


def gated_silu(h, w1, w3, w2):
    return (jax.nn.silu(h @ w1.astype(f32)) * (h @ w3.astype(f32))) @ w2.astype(f32)


def sparse_ffn(x, w, cfg, forced, layer):
    """`forced` [T, K]: the expert ids the layer is to use at each token; a
    token whose places are all -1 routes by the reference's own scores. `w`:
    the layer's small leaves and the whole model's expert stacks `[sparse
    layers, experts held, ...]`, of which an expert of `layer` is read at a
    time. Returns routed(x) + shared(x) of the NORMED x and (routing margin,
    the experts used [T, K], their deficit [T]): margin and deficit are read
    on s + b, which the choice is made by; the weights on s."""
    K = cfg.num_experts_per_tok
    rows = jnp.arange(x.shape[0])[:, None]
    scores = jax.nn.sigmoid(x @ w["router"].astype(f32))  # [T, router's width]
    choice = scores + w["router_bias"].astype(f32)
    top, idx = jax.lax.top_k(choice, K + 1)
    spread = choice.std(axis=-1)
    margin = (top[:, K - 1] - top[:, K]) / spread
    chosen = jnp.where(forced >= 0, forced, idx[:, :K])
    deficit = (top[:, K - 1] - choice[rows, chosen].min(axis=-1)) / spread
    weights = scores[rows, chosen]  # the reference's own, at the experts used
    if cfg.norm_topk_prob:
        weights = weights / weights.sum(-1, keepdims=True)
    weights = weights * cfg.routed_scaling_factor
    weight = jnp.zeros_like(scores).at[rows, chosen].add(weights)  # [T, width]
    first, held = cfg.first_expert_held, w["w_gate"].shape[-3]

    def expert(acc, e):
        w1, w3, w2 = (w[k][layer, e] for k in EXPERT_STACKS)
        return acc + weight[:, first + e][:, None] * gated_silu(x, w1, w3, w2), None

    out, _ = jax.lax.scan(expert, jnp.zeros_like(x), jnp.arange(held))
    # one shared expert on every token, no gate
    shared = gated_silu(x, w["ws_gate"], w["ws_up"], w["ws_down"])
    return out + shared, (margin, chosen, deficit)


def layer_kinds(cfg):
    """(per layer "dense" | "sparse", per layer "full" | "shared")."""
    L = cfg.num_layers
    dense = cfg.first_k_dense_replace
    ffn = cfg.mlp_layer_types or ("dense",) * dense + ("sparse",) * (L - dense)
    return tuple(ffn)[:L], tuple(cfg.indexer_types)[:L]


def logits(params, cfg, tokens, n_last: int, forced=None, picks: bool = False):
    """Logits [n_last, vocab]; the routing margins of those positions; and of
    EVERY position the experts used [sparse layers, T, K] and each sparse
    layer's deficit [sparse layers, T]. `forced` [sparse layers, T, K] (int32;
    -1 in every place of a padded position), or None: every token routes by
    the reference's own scores. `picks`: a fifth result, S_t of the last
    `n_last` positions in every full layer [full layers, n_last, index_topk]
    (-1: no position; min(index_topk, T) wide)."""
    T = tokens.shape[0]
    ffn, index = layer_kinds(cfg)
    if forced is None:
        forced = jnp.full((ffn.count("sparse"), T, cfg.num_experts_per_tok), -1, jnp.int32)
    layers = params["layers"]
    x = params["embed"][tokens].astype(f32)
    kept, sets, at = [], [], None
    ld = fi = 0
    for li in range(cfg.num_layers):
        w = jax.tree.map(lambda a: a[li], layers["attention"])
        indexer = None
        if index[li] == "full":
            indexer = jax.tree.map(lambda a: a[fi], layers["indexer"])
            fi += 1
        out, at = attention(rms(x, w["norm"], cfg.rms_norm_eps), w, cfg, indexer, at)
        if indexer is not None:
            sets.append(at[T - n_last:])
        x = x + out
        if ffn[li] == "dense":
            w = jax.tree.map(lambda a: a[ld], layers["dense"])
            ld += 1
            x = x + gated_silu(rms(x, w["norm"], cfg.rms_norm_eps),
                               w["w_gate"], w["w_up"], w["w_down"])
        else:
            le = li - ld
            w = {k: v if k in EXPERT_STACKS else v[le] for k, v in layers["experts"].items()}
            out, routing = sparse_ffn(
                rms(x, w["norm"], cfg.rms_norm_eps), w, cfg, forced[le], le)
            x = x + out
            kept.append(routing)
    margins, chosen, deficits = (jnp.stack(part) for part in zip(*kept))
    # no multi-token-prediction module: the head alone
    x = rms(x[T - n_last:], params["final_norm"], cfg.rms_norm_eps)
    result = (x @ params["lm_head"].astype(f32), margins.min(axis=0)[T - n_last:],
              chosen, deficits)
    return result + (jnp.stack(sets),) if picks else result
