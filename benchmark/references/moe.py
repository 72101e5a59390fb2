"""Plain reference of the Mixtral block: the Llama attention of
references/llama.py with a DROPLESS top-k mixture of SwiGLU experts, as the
published model computes it: router logits in float32, the k largest chosen,
a softmax over the chosen logits alone, every chosen expert applied to every
token that chose it. No capacity, no dispatch buffers, nothing from
dynamo_tpu/ops, models/moe.py:moe_mlp or the serving forwards.

Experts are upcast to float32 one at a time (a scan over the expert axis),
so the bf16 weights and one float32 expert fit a 16 GB chip. Every expert is
applied to every token and the result weighted by the (mostly zero) routing
weight: wasteful, plain, and exact.

Also returns, per position, the smallest margin over the layers between the
k-th and the (k+1)-th router logit: where it is tiny, a bf16 hidden state may
route the token to another expert than float32 does, and the caller leaves
such positions out of the comparison (and counts them)."""

import jax
import jax.numpy as jnp

from references.llama import attention, f32, head_logits, rms_norm, rope_tables


def moe_mlp(x, w, cfg):
    K = cfg.num_experts_per_tok
    h = rms_norm(x, w["mlp_norm"], cfg.rms_norm_eps)
    router = h @ w["router"].astype(f32)  # [T, E]
    top, idx = jax.lax.top_k(router, K + 1)
    # k-th against the best one left out, in deviations of the token's router
    # logits over the experts (about 1.3 logits at the published widths)
    margin = (top[:, K - 1] - top[:, K]) / router.std(axis=-1)
    probs = jax.nn.softmax(top[:, :K], axis=-1)
    weight = jnp.zeros_like(router).at[
        jnp.arange(x.shape[0])[:, None], idx[:, :K]
    ].add(probs)  # [T, E]; 0 where the expert was not chosen

    def expert(acc, ew):
        w_gate, w_up, w_down, wt = ew
        y = jax.nn.silu(h @ w_gate.astype(f32)) * (h @ w_up.astype(f32))
        return acc + wt[:, None] * (y @ w_down.astype(f32)), None

    out, _ = jax.lax.scan(
        expert, jnp.zeros_like(x),
        (w["w_gate"], w["w_up"], w["w_down"], weight.T),
    )
    return x + out, margin


def logits(params, cfg, tokens, n_last: int):
    T = tokens.shape[0]
    cos, sin = rope_tables(cfg, T)
    causal = jnp.tril(jnp.ones((T, T), bool))

    def layer(x, w):
        x = attention(x, w, cfg, cos, sin, causal)
        return moe_mlp(x, w, cfg)

    x = params["embed"][tokens].astype(f32)
    x, margins = jax.lax.scan(layer, x, params["layers"])  # [L, T]
    return head_logits(params, cfg, x, n_last), margins.min(axis=0)[T - n_last:]
