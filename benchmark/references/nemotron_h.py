"""Plain reference of the Nemotron-H block (NVIDIA-Nemotron-3-Super-120B-A12B
as its `config.json` and the family's reports describe it). Every layer is
`x = x + mixer_l(rmsnorm(x))` with ONE mixer, chosen by the character of
`hybrid_override_pattern` at the layer (`cfg.pattern`); a final norm and an
untied head; plain (not zero-centred) RMSNorm; no bias but the convolution's.

  M  Mamba-2. `[z | xBC | dt] = in_proj(u)`; `xBC = silu(causal depthwise
     convolution of conv_kernel taps, with bias)`; `[x | B | C]` with x as
     [heads, head dim] and B, C as [n_groups, state size], a group of B and C
     serving heads / n_groups heads; `dt = softplus(dt + dt_bias)`, `A =
     -exp(A_log)` a head; the state h [heads, head dim, state size]:
     `h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t`, `y_t = h_t C_t + D x_t`;
     `y = rmsnorm_grouped(y * silu(z)) * weight` over n_groups groups of the
     inner width; `out_proj`.
  *  Softmax attention: `num_attention_heads` query heads over
     `num_key_value_heads` key-value heads of `head_dim`, scores scaled by
     1 / sqrt(head_dim), causal, no bias.
  E  LatentMoE. Scores `s = sigmoid(h W_r)` over all the router's outputs;
     the `num_experts_per_tok` chosen are the largest of `s + b` (the choice
     bias `e_score_correction_bias`; `n_group` 1: no group limit); their
     weights are `s` at the chosen over their sum (`norm_topk_prob`) times
     `routed_scaling_factor`; `l = h W_down` into `moe_latent_size`; expert e:
     `W2_e relu(W1_e l)^2` (`mlp_hidden_act` relu2: two matrices, no gate);
     routed = `W_up(sum_e w_e expert_e(l))`; a shared expert of the same form
     on the full width, `W2_s relu(W1_s h)^2`; out = routed + shared.

A full-sequence causal forward in jax.numpy: float32 activations over the
model's own (bf16) weights, the STEP form of the recurrence in a `lax.scan`
over positions, one softmax over the whole sequence; no cache, chunking,
batching or kernel, nothing from dynamo_tpu/ops or the serving forwards.
The caller sets the matmul precision (`highest`, or the TPU's default for
the bf16 control).

Departures from the published description, and what the catalog's row does
not say (the configuration's file lists each under `assumed`):
  * NO rotary embedding in the attention layers: the family's reports state
    that it embeds no position (the state-space layers carry order); the row
    has `rope_theta` and `partial_rotary_factor` and no key that switches
    them, so this is assumed, not read;
  * the router and the shared expert read the full-width normed token, the
    routed experts its latent projection (this reproduces the parameter
    count that the row's sizes give);
  * no multi-token-prediction module (`num_nextn_predict_layers` 1,
    `mtp_hybrid_override_pattern` "*E"): no weights are loaded for it, and
    serving without it computes the same tokens;
  * the fused `in_proj`'s column order is [z | x | B | C | dt], each part
    contiguous: weights are seeded random, so the order names nothing;
  * the state and the recurrence are float32;
  * the share: where `cfg.num_experts` < `cfg.router_width` this chip holds
    experts `[first_expert_held, first_expert_held + num_experts)` of every
    routed layer; the reference routes over the router's FULL width (scores,
    margins, the k chosen, weights over the chosen) and applies the experts
    it holds; the others' part is the other chips' (README.md, "The
    reference's protocol"). The latent projections, like the mixers, the
    shared expert and the head, are what every chip computes alike: the up
    projection is linear, so the shares' routed parts add up behind it.
"""

import jax
import jax.numpy as jnp

f32 = jnp.float32


def rms(x, w, eps):
    """Plain weight: x * rsqrt(mean(x^2) + eps) * w."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w.astype(f32)


def attention(h, w, cfg, causal):
    """h [T, hidden] (normed) -> o_proj(softmax attention); no rotary (assumed)."""
    T = h.shape[0]
    H, KH, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = (h @ w["wq"].astype(f32)).reshape(T, H, D)
    k = (h @ w["wk"].astype(f32)).reshape(T, KH, D)
    v = (h @ w["wv"].astype(f32)).reshape(T, KH, D)
    k = jnp.repeat(k, H // KH, axis=1)  # a key-value head serves H / KH query heads
    v = jnp.repeat(v, H // KH, axis=1)
    s = jnp.einsum("thd,shd->hts", q, k) / jnp.sqrt(f32(D))
    p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    return jnp.einsum("hts,shd->thd", p, v).reshape(T, H * D) @ w["wo"].astype(f32)


def mamba2(h, w, cfg):
    """h [T, hidden] (normed) -> out_proj(gated norm(state-space recurrence)).
    The state starts at zero and is stepped a position at a time."""
    T = h.shape[0]
    nh, hd, N, G = cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.ssm_state_size, cfg.n_groups
    Di, taps = nh * hd, cfg.conv_kernel
    C = Di + 2 * G * N
    # column order: [z | x B C | dt], the program's own
    proj = h @ w["w_in"].astype(f32)
    z, xbc, dt = proj[:, :Di], proj[:, Di: Di + C], proj[:, Di + C:]
    # causal depthwise convolution with bias: y_t = b + sum_i w[:, i] x_{t-taps+1+i}
    padded = jnp.concatenate([jnp.zeros((taps - 1, C), f32), xbc], 0)
    kernel = w["w_conv"].astype(f32)  # [C, taps]
    y = sum(padded[i: i + T] * kernel[:, i] for i in range(taps)) + w["conv_bias"].astype(f32)
    y = jax.nn.silu(y)
    x = y[:, :Di].reshape(T, nh, hd)
    B = jnp.repeat(y[:, Di: Di + G * N].reshape(T, G, N), nh // G, axis=1)  # [T, nh, N]
    Cm = jnp.repeat(y[:, Di + G * N:].reshape(T, G, N), nh // G, axis=1)
    dt = jax.nn.softplus(dt + w["dt_bias"].astype(f32))  # [T, nh]
    A = -jnp.exp(w["a_log"].astype(f32))  # [nh]

    def step(S, t):  # S [nh, hd, N]
        x_t, B_t, C_t, dt_t = t
        S = S * jnp.exp(dt_t * A)[:, None, None] + jnp.einsum(
            "hp,hn->hpn", dt_t[:, None] * x_t, B_t)
        return S, jnp.einsum("hpn,hn->hp", S, C_t)

    _, o = jax.lax.scan(step, jnp.zeros((nh, hd, N), f32), (x, B, Cm, dt))
    o = o + w["d_skip"].astype(f32)[:, None] * x
    # the gated norm: silu(z) first, then RMSNorm over each of G groups, a weight
    o = (o.reshape(T, Di) * jax.nn.silu(z)).reshape(T, G, Di // G)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + cfg.rms_norm_eps)
    return (o.reshape(T, Di) * w["out_norm"].astype(f32)) @ w["w_out"].astype(f32)


def latent_moe(x, w, cfg, forced):
    """`forced` [T, K]: the expert ids the layer is to use at each token (ids
    under the router's full width); a token whose places are all -1 routes by
    the reference's own scores. Returns x + routed(x) + shared(x) and (routing
    margin, the experts used [T, K], their deficit [T]): margin and deficit
    are read on s + b, which the choice is made by; the weights on s."""
    K = cfg.num_experts_per_tok
    rows = jnp.arange(x.shape[0])[:, None]
    h = rms(x, w["norm"], cfg.rms_norm_eps)
    scores = jax.nn.sigmoid(h @ w["router"].astype(f32))  # [T, width]: the FULL width
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
    first = cfg.first_expert_held
    held = weight[:, first: first + w["w1"].shape[0]]
    latent = h @ w["w_latent_down"].astype(f32)

    def expert(acc, ew):
        w1, w2, wt = ew
        y = jnp.square(jax.nn.relu(latent @ w1.astype(f32)))
        return acc + wt[:, None] * (y @ w2.astype(f32)), None

    out, _ = jax.lax.scan(expert, jnp.zeros_like(latent), (w["w1"], w["w2"], held.T))
    out = out @ w["w_latent_up"].astype(f32)
    shared = jnp.square(jax.nn.relu(h @ w["ws1"].astype(f32))) @ w["ws2"].astype(f32)
    return x + out + shared, (margin, chosen, deficit)


def logits(params, cfg, tokens, n_last: int, forced=None):
    """Logits [n_last, vocab]; the routing margins of those positions; and of
    EVERY position the experts used [routed layers, T, K] and each routed
    layer's deficit [routed layers, T]. `forced` [routed layers, T, K] (int32;
    -1 in every place of a padded position), or None: every token routes by
    the reference's own scores."""
    T = tokens.shape[0]
    causal = jnp.tril(jnp.ones((T, T), bool))
    routed_layers = cfg.pattern.count("E")
    if forced is None:
        forced = jnp.full((routed_layers, T, cfg.num_experts_per_tok), -1, jnp.int32)
    layers = params["layers"]
    x = params["embed"][tokens].astype(f32)
    seen = {"M": 0, "E": 0, "*": 0}
    kept = []
    for kind in cfg.pattern:  # one sublayer a layer
        i = seen[kind]
        seen[kind] += 1
        if kind == "M":
            w = jax.tree.map(lambda a: a[i], layers["mamba"])
            x = x + mamba2(rms(x, w["norm"], cfg.rms_norm_eps), w, cfg)
        elif kind == "*":
            w = jax.tree.map(lambda a: a[i], layers["attention"])
            x = x + attention(rms(x, w["norm"], cfg.rms_norm_eps), w, cfg, causal)
        else:
            w = jax.tree.map(lambda a: a[i], layers["experts"])
            x, routing = latent_moe(x, w, cfg, forced[i])
            kept.append(routing)
    margins, chosen, deficits = (jnp.stack(part) for part in zip(*kept))
    # no multi-token-prediction module: the head alone
    x = rms(x[T - n_last:], params["final_norm"], cfg.rms_norm_eps)
    return (x @ params["lm_head"].astype(f32), margins.min(axis=0)[T - n_last:],
            chosen, deficits)
