"""Plain reference of the EXAONE-MoE block (K-EXAONE-236B-A23B as its
`config.json` gives the sizes and EXAONE 4.0's report, arXiv:2507.11407, the
family's conventions). Every layer, pre-norm:

    x = x + attn_l(rmsnorm(x));  x = x + ffn_l(rmsnorm(x))

a final norm and an untied head; plain RMSNorm (`rms_norm_eps`), no bias on
any projection.

  attention, every layer: `num_attention_heads` query heads over
     `num_key_value_heads` key-value heads of `head_dim`, an RMSNorm with a
     weight of `head_dim` over each head of q and of k, softmax at 1 /
     sqrt(head_dim), causal. `sliding_window_pattern` ("LLLG") repeated over
     the layers says which kind a layer is:
       L  window: position t attends to positions j with t - W < j <= t
          (`sliding_window` W: itself and the W - 1 before it); full rotary
          (rotate-half) at `rope_theta` on q and k;
       G  full: every j <= t; NO positional encoding.
  feed-forward, layers under `first_k_dense_replace` (dense):
     `W2(silu(W1 h) * W3 h)` at `intermediate_size`.
  feed-forward, the other layers (sparse): scores `s = sigmoid(h W_r)` over
     all the router's outputs; the `num_experts_per_tok` chosen are the
     largest of `s + b` (the choice bias; `n_group` 1 and `topk_group` 1: no
     group limit); their weights are `s` at the chosen over their sum
     (`norm_topk_prob`) times `routed_scaling_factor`; expert e:
     `W2_e(silu(W1_e h) * W3_e h)` at `moe_intermediate_size`; one shared
     expert of the same form at `num_shared_experts` such widths on every
     token, no gate; out = routed + shared.

A full-sequence causal forward in jax.numpy: float32 activations over the
model's own (bf16) weights, a window layer as ONE banded mask over the whole
sequence; no ring, chunk, cache, batching or kernel, nothing from
dynamo_tpu/ops or the serving forwards. Attention runs a block of query
positions at a time (each against every key, under its mask), so that 2,048
positions fit beside 12 GB of weights: 64 heads x 256 x 2,048 scores are
134 MB. The caller sets the matmul precision (`highest`, or the TPU's
default for the bf16 control).

Departures from the published description, and what the catalog's row does
not say (the configuration's file lists each under `assumed`):
  * the norms stand BEFORE each sublayer (the row has no key for their
    place; EXAONE 4.0 norms each sublayer's OUTPUT instead: this is the
    other reading; neither moves a byte or an operation);
  * QK norm and rotary on the window layers alone are the family's
    published convention ("QK norm", "SWA-only RoPE"); the row has no key
    for either;
  * the choice bias `b` exists (the row carries `scoring_func` sigmoid,
    `n_group`, `topk_group`, `routed_scaling_factor`: the key set of the
    router that balances without an auxiliary loss, which chooses by s + b
    and weighs by s); seeded small and non-zero;
  * no multi-token-prediction module (`num_nextn_predict_layers` 1,
    `mtp_layer_types` full_attention): no weights are loaded for it, and
    serving without it computes the same tokens;
  * the share: where `cfg.num_experts` < `cfg.router_width` this chip holds
    experts `[first_expert_held, first_expert_held + num_experts)` of every
    sparse layer; the reference routes over the router's FULL width (scores,
    margins, the k chosen, weights over the chosen) and applies the experts
    it holds; the others' part is the other chips' (README.md, "The
    reference's protocol"). Attention, the dense layer, the shared expert
    and the head are what every chip computes alike.
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


def attention(h, w, cfg, window):
    """h [T, hidden] (normed) -> o_proj(softmax attention). `window`: the
    layer attends to the last `window` positions, itself among them, and
    rotates q and k; None: to every earlier position, no rotary."""
    T = h.shape[0]
    H, KH, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = rms((h @ w["wq"].astype(f32)).reshape(T, H, D), w["q_norm"], cfg.rms_norm_eps)
    k = rms((h @ w["wk"].astype(f32)).reshape(T, KH, D), w["k_norm"], cfg.rms_norm_eps)
    v = (h @ w["wv"].astype(f32)).reshape(T, KH, D)
    if window is not None:
        q, k = rotate(q, cfg.rope_theta), rotate(k, cfg.rope_theta)
    k = jnp.repeat(k, H // KH, axis=1)  # a key-value head serves H / KH query heads
    v = jnp.repeat(v, H // KH, axis=1)
    j = jnp.arange(T)[None, :]
    n = math.gcd(T, QUERY_BLOCK)

    def block(t0):  # the query positions t0 .. t0 + n against every key
        t = t0 + jnp.arange(n)[:, None]
        allowed = j <= t
        if window is not None:
            allowed &= j > t - window
        s = jnp.einsum("thd,shd->hts", jax.lax.dynamic_slice_in_dim(q, t0, n), k)
        p = jax.nn.softmax(jnp.where(allowed[None], s / jnp.sqrt(f32(D)), -jnp.inf), -1)
        return jnp.einsum("hts,shd->thd", p, v)

    out = jax.lax.map(block, jnp.arange(0, T, n)).reshape(T, H * D)
    return out @ w["wo"].astype(f32)


def gated_silu(h, w1, w3, w2):
    return (jax.nn.silu(h @ w1.astype(f32)) * (h @ w3.astype(f32))) @ w2.astype(f32)


EXPERT_STACKS = ("w_gate", "w_up", "w_down")


def sparse_ffn(x, w, cfg, forced, layer=None):
    """`forced` [T, K]: the expert ids the layer is to use at each token (ids
    under the router's full width); a token whose places are all -1 routes by
    the reference's own scores. `w`: the layer's leaves, its expert stacks
    `[experts held, ...]`; or, with `layer`, the whole model's stacks `[sparse
    layers, experts held, ...]`, of which an expert of that layer is read at
    a time (a layer's slice of a stack is a copy of 384 MB beside 12 GB of
    weights, three a layer). Returns routed(x) + shared(x) of the NORMED x
    and (routing margin, the experts used [T, K], their deficit [T]): margin
    and deficit are read on s + b, which the choice is made by; the weights
    on s."""
    K = cfg.num_experts_per_tok
    rows = jnp.arange(x.shape[0])[:, None]
    scores = jax.nn.sigmoid(x @ w["router"].astype(f32))  # [T, width]: the FULL width
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
    # the share: the routing weights of the experts held; the others' part
    # is not here
    first, held = cfg.first_expert_held, w["w_gate"].shape[-3]

    def expert(acc, e):
        w1, w3, w2 = (w[k][e] if layer is None else w[k][layer, e] for k in EXPERT_STACKS)
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
    pattern = cfg.sliding_window_pattern
    if forced is None:
        forced = jnp.full((cfg.num_layers - dense_layers, T, cfg.num_experts_per_tok),
                          -1, jnp.int32)
    layers = params["layers"]
    x = params["embed"][tokens].astype(f32)
    seen = {"L": 0, "G": 0}
    kept = []
    for li in range(cfg.num_layers):
        kind = pattern[li % len(pattern)]
        i = seen[kind]
        seen[kind] += 1
        w = jax.tree.map(lambda a: a[i], layers["window" if kind == "L" else "full"])
        x = x + attention(rms(x, w["norm"], cfg.rms_norm_eps), w, cfg,
                          cfg.sliding_window if kind == "L" else None)
        if li < dense_layers:
            w = jax.tree.map(lambda a: a[li], layers["dense"])
            x = x + gated_silu(rms(x, w["norm"], cfg.rms_norm_eps),
                               w["w_gate"], w["w_up"], w["w_down"])
        else:
            le = li - dense_layers
            w = {k: v if k in EXPERT_STACKS else v[le] for k, v in layers["experts"].items()}
            out, routing = sparse_ffn(
                rms(x, w["norm"], cfg.rms_norm_eps), w, cfg, forced[le], layer=le)
            x = x + out
            kept.append(routing)
    margins, chosen, deficits = (jnp.stack(part) for part in zip(*kept))
    # no multi-token-prediction module: the head alone
    x = rms(x[T - n_last:], params["final_norm"], cfg.rms_norm_eps)
    return (x @ params["lm_head"].astype(f32), margins.min(axis=0)[T - n_last:],
            chosen, deficits)
