"""Plain reference of the latent-attention MoE block (GLM-4.7-Flash,
`model_type` `glm4_moe_lite`, as its `config.json` gives the sizes). Every
layer, pre-norm:

    x = x + attn_l(rmsnorm(x));  x = x + ffn_l(rmsnorm(x))

a final norm and an untied head; plain RMSNorm (`rms_norm_eps`), no bias on
any projection.

  attention, every layer (multi-head latent attention, in its EXPANDED form
     and no other), for the normed input h [T, hidden]:
       c_q = rmsnorm(h W_qa)              `q_lora_rank`
       q   = c_q W_qb                     heads x (`qk_nope_head_dim` q_n |
                                          `qk_rope_head_dim` q_r)
       [c | k_r] = h W_kva                `kv_lora_rank` | `qk_rope_head_dim`
       c = rmsnorm(c)
       k_r = rope(k_r): ONE rotated key for all heads; q_r = rope(q_r); both
         over all `qk_rope_head_dim` dimensions (rotate-half) at
         `rope_theta`, no scaling (`rope_scaling` null)
       [k_n | v] = c W_kvb                heads x (`qk_nope_head_dim` |
                                          `v_head_dim`)
       score = (q_n . k_n + q_r . k_r) / sqrt(qk_nope_head_dim +
         qk_rope_head_dim); causal softmax; o = sum p v; attn = concat(o) W_o
  feed-forward, layers under `first_k_dense_replace` (dense):
     `W2(silu(W1 h) * W3 h)` at `intermediate_size`.
  feed-forward, the other layers (sparse): scores `s = sigmoid(h W_r)` over
     the `n_routed_experts`; the `num_experts_per_tok` chosen are the largest
     of `s + b` (the choice bias; `n_group` 1 and `topk_group` 1: no group
     limit); their weights are `s` at the chosen over their sum
     (`norm_topk_prob`) times `routed_scaling_factor`; expert e:
     `W2_e(silu(W1_e h) * W3_e h)` at `moe_intermediate_size`; one shared
     expert of the same form at `n_shared_experts` such widths on every
     token, no gate; out = routed + shared.

A full-sequence causal forward in jax.numpy: float32 activations over the
model's own (bf16) weights; no cache, no page, no chunk, no batching, no
kernel, no absorbed form, nothing from dynamo_tpu/ops or the serving
forwards. Attention runs a block of query positions at a time (each against
every key, under its mask), so that 2,048 positions fit beside 10 GB of
weights. The caller sets the matmul precision (`highest`, or the TPU's
default for the bf16 control).

What the catalog's row does not say (the configuration's file lists each
under `assumed`): the norms stand before each sublayer; `c_q` and `c` are
normed with `rms_norm_eps`; the rotation pairs dimension i with i + rope / 2
(rotate-half: a fixed permutation of what an interleaved pairing computes,
under seeded weights); `scoring_func` is sigmoid and the choice bias `b`
exists (`topk_method` `noaux_tc`); no multi-token-prediction module
(`num_nextn_predict_layers` 1): no weights are made for it, and serving
without it computes the same tokens.
"""

import math

import jax
import jax.numpy as jnp

f32 = jnp.float32
#: query positions a block of the attention holds at most (the padded
#: lengths of reference.py are multiples of 64, so 64 or more)
QUERY_BLOCK = 256


def rms(x, w, eps):
    """Plain weight: x * rsqrt(mean(x^2) + eps) * w."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w.astype(f32)


def rotate(x, theta):
    """Full rotary, rotate-half, on x [T, heads, D] at positions 0 .. T - 1."""
    T, _, D = x.shape
    inv_freq = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=f32) / D)
    angles = jnp.arange(T, dtype=f32)[:, None] * inv_freq  # [T, D / 2]
    cos, sin = jnp.cos(angles)[:, None], jnp.sin(angles)[:, None]
    x1, x2 = x[..., : D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(h, w, cfg):
    """h [T, hidden] (normed) -> o_proj(causal softmax attention), expanded:
    every position's keys and values at `num_heads` heads."""
    T = h.shape[0]
    H, rank = cfg.num_heads, cfg.kv_lora_rank
    nope, rope, vdim = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    c_q = rms(h @ w["wq_a"].astype(f32), w["q_a_norm"], cfg.rms_norm_eps)
    q = (c_q @ w["wq_b"].astype(f32)).reshape(T, H, nope + rope)
    row = h @ w["wkv_a"].astype(f32)  # [T, rank + rope]
    c = rms(row[:, :rank], w["kv_a_norm"], cfg.rms_norm_eps)
    k_r = rotate(row[:, None, rank:], cfg.rope_theta)  # [T, 1, rope]
    q_r = rotate(q[..., nope:], cfg.rope_theta)
    kv = (c @ w["wkv_b"].astype(f32)).reshape(T, H, nope + vdim)
    q = jnp.concatenate([q[..., :nope], q_r], -1)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_r, (T, H, rope))], -1)
    v = kv[..., nope:]
    j = jnp.arange(T)[None, :]
    n = math.gcd(T, QUERY_BLOCK)

    def block(t0):  # the query positions t0 .. t0 + n against every key
        t = t0 + jnp.arange(n)[:, None]
        s = jnp.einsum("thd,shd->hts", jax.lax.dynamic_slice_in_dim(q, t0, n), k)
        p = jax.nn.softmax(
            jnp.where((j <= t)[None], s / jnp.sqrt(f32(nope + rope)), -jnp.inf), -1)
        return jnp.einsum("hts,shd->thd", p, v)

    out = jax.lax.map(block, jnp.arange(0, T, n)).reshape(T, H * vdim)
    return out @ w["wo"].astype(f32)


def gated_silu(h, w1, w3, w2):
    return (jax.nn.silu(h @ w1.astype(f32)) * (h @ w3.astype(f32))) @ w2.astype(f32)


EXPERT_STACKS = ("w_gate", "w_up", "w_down")


def sparse_ffn(x, w, cfg, forced, layer):
    """`forced` [T, K]: the expert ids the layer is to use at each token; a
    token whose places are all -1 routes by the reference's own scores. `w`:
    the layer's small leaves and the whole model's expert stacks `[sparse
    layers, experts, ...]`, of which an expert of `layer` is read at a time.
    Returns routed(x) + shared(x) of the NORMED x and (routing margin, the
    experts used [T, K], their deficit [T]): margin and deficit are read on
    s + b, which the choice is made by; the weights on s."""
    K = cfg.num_experts_per_tok
    rows = jnp.arange(x.shape[0])[:, None]
    scores = jax.nn.sigmoid(x @ w["router"].astype(f32))  # [T, experts]
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
    weight = jnp.zeros_like(scores).at[rows, chosen].add(weights)  # [T, experts]
    first, held = cfg.first_expert_held, w["w_gate"].shape[-3]

    def expert(acc, e):
        w1, w3, w2 = (w[k][layer, e] for k in EXPERT_STACKS)
        return acc + weight[:, first + e][:, None] * gated_silu(x, w1, w3, w2), None

    out, _ = jax.lax.scan(expert, jnp.zeros_like(x), jnp.arange(held))
    # one shared expert on every token, no gate
    shared = gated_silu(x, w["ws_gate"], w["ws_up"], w["ws_down"])
    return out + shared, (margin, chosen, deficit)


def logits(params, cfg, tokens, n_last: int, forced=None):
    """Logits [n_last, vocab]; the routing margins of those positions; and of
    EVERY position the experts used [sparse layers, T, K] and each sparse
    layer's deficit [sparse layers, T]. `forced` [sparse layers, T, K] (int32;
    -1 in every place of a padded position), or None: every token routes by
    the reference's own scores."""
    T = tokens.shape[0]
    dense_layers = cfg.first_k_dense_replace
    if forced is None:
        forced = jnp.full((cfg.num_layers - dense_layers, T, cfg.num_experts_per_tok),
                          -1, jnp.int32)
    layers = params["layers"]
    x = params["embed"][tokens].astype(f32)
    kept = []
    for li in range(cfg.num_layers):
        w = jax.tree.map(lambda a: a[li], layers["attention"])
        x = x + attention(rms(x, w["norm"], cfg.rms_norm_eps), w, cfg)
        if li < dense_layers:
            w = jax.tree.map(lambda a: a[li], layers["dense"])
            x = x + gated_silu(rms(x, w["norm"], cfg.rms_norm_eps),
                               w["w_gate"], w["w_up"], w["w_down"])
        else:
            le = li - dense_layers
            w = {k: v if k in EXPERT_STACKS else v[le] for k, v in layers["experts"].items()}
            out, routing = sparse_ffn(
                rms(x, w["norm"], cfg.rms_norm_eps), w, cfg, forced[le], le)
            x = x + out
            kept.append(routing)
    margins, chosen, deficits = (jnp.stack(part) for part in zip(*kept))
    # no multi-token-prediction module: the head alone
    x = rms(x[T - n_last:], params["final_norm"], cfg.rms_norm_eps)
    return (x @ params["lm_head"].astype(f32), margins.min(axis=0)[T - n_last:],
            chosen, deficits)
