"""Plain reference of the Llama/Mistral block (copied from chip_smoke.py,
proven on the chip in PR 21): a full-sequence causal forward in jax.numpy,
float32 activations over the model's own (bf16) weights, one softmax over
the whole sequence, no paging, no kernels, nothing from dynamo_tpu/ops or the
serving forwards. Departures from the published model: none (no sliding
window in Mistral-7B-Instruct-v0.3; rotate-half RoPE as in its HF code)."""

import jax
import jax.numpy as jnp

f32 = jnp.float32


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w.astype(f32)


def attention(x, w, cfg, cos, sin, causal):
    """x [T, hidden] -> x + attention(norm(x)); grouped-query, causal."""
    T = x.shape[0]
    H, KH, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim

    def rope(t):  # [T, heads, D], rotate-half convention
        a, b = t[..., : D // 2], t[..., D // 2:]
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)

    h = rms_norm(x, w["attn_norm"], cfg.rms_norm_eps)
    q = rope((h @ w["wq"].astype(f32)).reshape(T, H, D))
    k = rope((h @ w["wk"].astype(f32)).reshape(T, KH, D))
    v = (h @ w["wv"].astype(f32)).reshape(T, KH, D)
    k = jnp.repeat(k, H // KH, axis=1)
    v = jnp.repeat(v, H // KH, axis=1)
    s = jnp.einsum("thd,shd->hts", q, k) / jnp.sqrt(f32(D))
    p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    a = jnp.einsum("hts,shd->thd", p, v).reshape(T, H * D)
    return x + a @ w["wo"].astype(f32)


def rope_tables(cfg, T):
    D = cfg.head_dim
    inv_freq = 1.0 / (cfg.rope_theta ** (jnp.arange(0, D, 2, dtype=f32) / D))
    ang = jnp.arange(T, dtype=f32)[:, None] * inv_freq[None, :]  # [T, D/2]
    return jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]


def head_logits(params, cfg, x, n_last):
    T = x.shape[0]
    x = rms_norm(x[T - n_last:], params["final_norm"], cfg.rms_norm_eps)
    head = params["lm_head"] if params.get("lm_head") is not None else params["embed"].T
    return x @ head.astype(f32)


def logits(params, cfg, tokens, n_last: int):
    """Logits [n_last, vocab] of the last n_last positions, and None where a
    mixture-of-experts reference returns its routing margins."""
    T = tokens.shape[0]
    cos, sin = rope_tables(cfg, T)
    causal = jnp.tril(jnp.ones((T, T), bool))

    def layer(x, w):
        x = attention(x, w, cfg, cos, sin, causal)
        h = rms_norm(x, w["mlp_norm"], cfg.rms_norm_eps)
        gate = jax.nn.silu(h @ w["w_gate"].astype(f32)) * (h @ w["w_up"].astype(f32))
        return x + gate @ w["w_down"].astype(f32), None

    x = params["embed"][tokens].astype(f32)
    x, _ = jax.lax.scan(layer, x, params["layers"])
    return head_logits(params, cfg, x, n_last), None
