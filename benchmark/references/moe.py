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
such positions out of the comparison (and counts them).

A family of many small experts has such a near-tie at nearly every position,
so it is judged with the served path's own choices FORCED on the reference
(`forced`, README.md "The reference's protocol"): the reference then computes
what the published model computes for those experts, with its own router
scores at them as weights, and says how far each forced set lies from its
own: the deficit. Nothing of the served path's arithmetic comes with the ids."""

import jax
import jax.numpy as jnp

from references.llama import attention, f32, head_logits, rms_norm, rope_tables


def moe_mlp(x, w, cfg):
    """The block routing by its own scores: (x + experts(x), routing margin).
    tests/test_moe_family.py holds the program's grouped experts to it."""
    forced = jnp.full((x.shape[0], cfg.num_experts_per_tok), -1, jnp.int32)
    out, (margin, _, _) = routed_mlp(x, w, cfg, forced)
    return out, margin


def routed_mlp(x, w, cfg, forced):
    """`forced` [T, K]: the expert ids the layer is to use at each token; a
    token whose places are all -1 (`logits`: the free path, and padding)
    routes by the reference's own scores. Returns x + experts(x) and (routing
    margin, the experts used [T, K], their deficit [T])."""
    K = cfg.num_experts_per_tok
    rows = jnp.arange(x.shape[0])[:, None]
    h = rms_norm(x, w["mlp_norm"], cfg.rms_norm_eps)
    router = h @ w["router"].astype(f32)  # [T, E]
    top, idx = jax.lax.top_k(router, K + 1)
    spread = router.std(axis=-1)
    # k-th against the best one left out, in deviations of the token's router
    # logits over the experts (about 1.3 logits at the published widths)
    margin = (top[:, K - 1] - top[:, K]) / spread
    chosen = jnp.where(forced >= 0, forced, idx[:, :K])
    scores = router[rows, chosen]  # the reference's own, at the experts used
    # how far the lowest-scored expert used lies under the reference's own
    # k-th best: 0 where the set used is the reference's own
    deficit = (top[:, K - 1] - scores.min(axis=-1)) / spread
    probs = jax.nn.softmax(scores, axis=-1)
    weight = jnp.zeros_like(router).at[rows, chosen].add(probs)  # [T, E]; 0 where not chosen

    def expert(acc, ew):
        w_gate, w_up, w_down, wt = ew
        y = jax.nn.silu(h @ w_gate.astype(f32)) * (h @ w_up.astype(f32))
        return acc + wt[:, None] * (y @ w_down.astype(f32)), None

    out, _ = jax.lax.scan(
        expert, jnp.zeros_like(x),
        (w["w_gate"], w["w_up"], w["w_down"], weight.T),
    )
    return x + out, (margin, chosen, deficit)


def logits(params, cfg, tokens, n_last: int, forced=None):
    """Logits [n_last, vocab]; the routing margins of those positions; and of
    EVERY position the experts used [layers, T, K] and each layer's deficit
    [layers, T]. `forced` [layers, T, K] (int32; -1 in every place of a padded
    position), or None: every token routes by the reference's own scores."""
    T = tokens.shape[0]
    cos, sin = rope_tables(cfg, T)
    causal = jnp.tril(jnp.ones((T, T), bool))
    if forced is None:
        forced = jnp.full((cfg.num_layers, T, cfg.num_experts_per_tok), -1, jnp.int32)

    def layer(x, w_forced):
        w, layer_forced = w_forced
        x = attention(x, w, cfg, cos, sin, causal)
        return routed_mlp(x, w, cfg, layer_forced)

    x = params["embed"][tokens].astype(f32)
    x, (margins, chosen, deficits) = jax.lax.scan(layer, x, (params["layers"], forced))
    return (head_logits(params, cfg, x, n_last), margins.min(axis=0)[T - n_last:],
            chosen, deficits)
