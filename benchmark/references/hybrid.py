"""Plain reference of the hybrid block (Qwen3-Next as its `config.json` and
model card describe it): periods of `full_attention_interval` layers, all
but the last of a period a gated delta rule (a causal depthwise convolution
of `linear_conv_kernel_dim` taps, a `dk x dv` state per value head, a gated
norm), the last gated softmax attention (QK-norm, rotary on part of the
head, a sigmoid output gate), every layer followed by a routed feed-forward
part (softmax over the router's scores, the k largest, renormalised) and a
shared expert behind a sigmoid gate; zero-centred RMSNorm.

A full-sequence causal forward in jax.numpy: float32 activations over the
model's own (bf16) weights, the STEP form of the recurrence in a `lax.scan`
over positions, one softmax over the whole sequence; no cache, chunking,
batching or kernel, nothing from dynamo_tpu/ops or the serving forwards.
The caller sets the matmul precision (`highest`, or the TPU's default for
the bf16 control).

Departures from the published description, each noted at its line:
  * no multi-token-prediction module (`described_as`: "MTP 1"; the
    catalog's `config` has no key for it, and no weights are loaded);
  * the fused projections' column order is the program's own ([q | k | v |
    z] and [b | a] side by side; the published checkpoint interleaves them
    by key head): weights are seeded random, so the order names nothing;
  * the share: where `cfg.num_experts` < `cfg.router_width` this chip holds
    experts `[first_expert_held, first_expert_held + num_experts)` of every
    layer; the reference routes over the router's FULL width (scores,
    margins, the k chosen, weights over the chosen) and applies the experts
    it holds; the others' part is the other chips' (README.md, "The
    reference's protocol"). What every chip computes alike (mixers, shared
    expert, head) is added once.
"""

import jax
import jax.numpy as jnp

f32 = jnp.float32


def rms(x, w, eps):
    """Zero-centred weight: x * rsqrt(mean(x^2) + eps) * (1 + w)."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + w.astype(f32))


def gated_attention(h, w, cfg, causal):
    """h [T, hidden] (normed) -> o_proj(softmax attention * sigmoid(gate))."""
    T = h.shape[0]
    H, KH, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    rot = int(D * cfg.partial_rotary_factor)
    inv_freq = 1.0 / (cfg.rope_theta ** (jnp.arange(0, rot, 2, dtype=f32) / rot))
    ang = jnp.arange(T, dtype=f32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]

    def rope(t):  # rotate-half over the first `rot` of the head, the rest as it is
        a, b = t[..., : rot // 2], t[..., rot // 2: rot]
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, t[..., rot:]], -1)

    # q_proj gives each head its query and, behind it, its gate
    qg = (h @ w["wq"].astype(f32)).reshape(T, H, 2 * D)
    q, gate = qg[..., :D], qg[..., D:]
    k = (h @ w["wk"].astype(f32)).reshape(T, KH, D)
    v = (h @ w["wv"].astype(f32)).reshape(T, KH, D)
    q = rope(rms(q, w["q_norm"], cfg.rms_norm_eps))
    k = rope(rms(k, w["k_norm"], cfg.rms_norm_eps))
    k = jnp.repeat(k, H // KH, axis=1)
    v = jnp.repeat(v, H // KH, axis=1)
    s = jnp.einsum("thd,shd->hts", q, k) / jnp.sqrt(f32(D))
    p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    a = jnp.einsum("hts,shd->thd", p, v) * jax.nn.sigmoid(gate)
    return a.reshape(T, H * D) @ w["wo"].astype(f32)  # no biases (assumed)


def gated_delta(h, w, cfg):
    """h [T, hidden] (normed) -> out_proj(gated norm(delta rule)). The state
    starts at zero and is stepped a position at a time."""
    T = h.shape[0]
    nk, nv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
    dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
    Kd, Vd, taps = nk * dk, nv * dv, cfg.linear_conv_kernel_dim
    C = 2 * Kd + Vd
    # column order: the program's own, [q | k | v | z] and [b | a]
    qkvz = h @ w["w_qkvz"].astype(f32)
    ba = h @ w["w_ba"].astype(f32)
    mixed, z = qkvz[:, :C], qkvz[:, C:].reshape(T, nv, dv)
    beta = jax.nn.sigmoid(ba[:, :nv])
    g = -jnp.exp(w["a_log"].astype(f32)) * jax.nn.softplus(ba[:, nv:] + w["dt_bias"].astype(f32))
    # causal depthwise convolution without bias: y_t = sum_i w[:, i] x_{t-taps+1+i}
    padded = jnp.concatenate([jnp.zeros((taps - 1, C), f32), mixed], 0)
    kernel = w["w_conv"].astype(f32)  # [C, taps]
    y = sum(padded[i: i + T] * kernel[:, i] for i in range(taps))
    y = jax.nn.silu(y)
    q = y[:, :Kd].reshape(T, nk, dk)
    k = y[:, Kd: 2 * Kd].reshape(T, nk, dk)
    v = y[:, 2 * Kd:].reshape(T, nv, dv)

    def l2(x):
        return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)

    q = jnp.repeat(l2(q) * dk ** -0.5, nv // nk, axis=1)  # each key head serves nv / nk value heads
    k = jnp.repeat(l2(k), nv // nk, axis=1)

    def step(S, x):  # S [nv, dk, dv]
        q_t, k_t, v_t, g_t, b_t = x
        S = S * jnp.exp(g_t)[:, None, None]
        d = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", S, k_t))
        S = S + jnp.einsum("hk,hv->hkv", k_t, d)
        return S, jnp.einsum("hkv,hk->hv", S, q_t)

    _, o = jax.lax.scan(step, jnp.zeros((nv, dk, dv), f32), (q, k, v, g, beta))
    # the gated norm over each head: a plain weight, no `1 +`
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + cfg.rms_norm_eps)
    o = o * w["out_norm"].astype(f32) * jax.nn.silu(z)
    return o.reshape(T, Vd) @ w["w_out"].astype(f32)


def routed_mlp(x, w, cfg, forced):
    """`forced` [T, K]: the expert ids the layer is to use at each token (ids
    under the router's full width); a token whose places are all -1 routes by
    the reference's own scores. Returns x + routed(x) + shared(x) and (routing
    margin, the experts used [T, K], their deficit [T])."""
    K = cfg.num_experts_per_tok
    rows = jnp.arange(x.shape[0])[:, None]
    h = rms(x, w["norm"], cfg.rms_norm_eps)
    router = h @ w["router"].astype(f32)  # [T, width]: the FULL width
    top, idx = jax.lax.top_k(router, K + 1)
    spread = router.std(axis=-1)
    margin = (top[:, K - 1] - top[:, K]) / spread
    chosen = jnp.where(forced >= 0, forced, idx[:, :K])
    scores = router[rows, chosen]  # the reference's own, at the experts used
    deficit = (top[:, K - 1] - scores.min(axis=-1)) / spread
    probs = jnp.exp(scores - jax.nn.logsumexp(router, -1, keepdims=True))  # softmax over all
    if cfg.norm_topk_prob:
        probs = probs / probs.sum(-1, keepdims=True)
    weight = jnp.zeros_like(router).at[rows, chosen].add(probs)  # [T, width]
    # the share: the routing weights of the experts held; the others' part
    # is not here
    first = cfg.first_expert_held
    held = weight[:, first: first + w["w_gate"].shape[0]]

    def expert(acc, ew):
        w_gate, w_up, w_down, wt = ew
        y = jax.nn.silu(h @ w_gate.astype(f32)) * (h @ w_up.astype(f32))
        return acc + wt[:, None] * (y @ w_down.astype(f32)), None

    out, _ = jax.lax.scan(expert, jnp.zeros_like(x),
                          (w["w_gate"], w["w_up"], w["w_down"], held.T))
    shared = (jax.nn.silu(h @ w["ws_gate"].astype(f32)) * (h @ w["ws_up"].astype(f32))
              ) @ w["ws_down"].astype(f32)
    out = out + jax.nn.sigmoid(h @ w["w_shared_gate"].astype(f32)) * shared
    return x + out, (margin, chosen, deficit)


def logits(params, cfg, tokens, n_last: int, forced=None):
    """Logits [n_last, vocab]; the routing margins of those positions; and of
    EVERY position the experts used [layers, T, K] and each layer's deficit
    [layers, T]. `forced` [layers, T, K] (int32; -1 in every place of a padded
    position), or None: every token routes by the reference's own scores."""
    T = tokens.shape[0]
    n = cfg.full_attention_interval
    P = cfg.num_layers // n
    causal = jnp.tril(jnp.ones((T, T), bool))
    if forced is None:
        forced = jnp.full((cfg.num_layers, T, cfg.num_experts_per_tok), -1, jnp.int32)
    layers = params["layers"]
    linear = jax.tree.map(lambda a: a.reshape(P, n - 1, *a.shape[1:]), layers["linear"])
    routed = jax.tree.map(lambda a: a.reshape(P, n, *a.shape[1:]), layers["moe"])
    forced = forced.reshape(P, n, T, -1)

    def period(x, ws):
        lin, full, moe, f = ws
        kept = []
        for j in range(n):
            if j < n - 1:  # layers with (i + 1) % interval != 0: linear attention
                w = jax.tree.map(lambda a: a[j], lin)
                x = x + gated_delta(rms(x, w["norm"], cfg.rms_norm_eps), w, cfg)
            else:
                x = x + gated_attention(rms(x, full["norm"], cfg.rms_norm_eps), full, cfg, causal)
            x, routing = routed_mlp(x, jax.tree.map(lambda a: a[j], moe), cfg, f[j])
            kept.append(routing)
        return x, tuple(jnp.stack(part) for part in zip(*kept))

    x = params["embed"][tokens].astype(f32)
    x, (margins, chosen, deficits) = jax.lax.scan(period, x, (linear, layers["full"], routed, forced))
    margins, chosen, deficits = (a.reshape(cfg.num_layers, *a.shape[2:])
                                 for a in (margins, chosen, deficits))
    # no multi-token-prediction module: the head alone
    x = rms(x[T - n_last:], params["final_norm"], cfg.rms_norm_eps)
    return (x @ params["lm_head"].astype(f32), margins.min(axis=0)[T - n_last:],
            chosen, deficits)
