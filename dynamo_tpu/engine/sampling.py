"""On-device sampling: greedy / temperature / top-k / top-p, fully batched.

TPU-first: sampling runs inside the jitted decode step (no logits transfer
to host). Top-p is computed within a fixed top-K candidate set (K=64) so the
whole thing is static-shaped and cheap even at 128k vocab.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

TOPK_CAP = 64


def unpack_mask(packed: jax.Array, vocab: int) -> jax.Array:
    """[B, ceil(V/8)] uint8 (np.packbits big-endian layout) → [B, V] bool.
    Guided-decoding masks ride host→device bitpacked — 8-32x less
    transfer per step than a bool/f32 mask — and unpack on device with
    two elementwise ops."""
    shifts = jnp.arange(7, -1, -1, dtype=jnp.uint8)
    bits = (packed[:, :, None] >> shifts) & jnp.uint8(1)
    return bits.reshape(packed.shape[0], -1)[:, :vocab].astype(bool)


class SamplingParams(NamedTuple):
    """Per-slot device-resident sampling state.

    `seed`: per-lane sampling seed (uint32). Sampling draws are derived
    from (seed, position) — NOT from a shared RNG stream — so a request
    with an explicit seed reproduces its output exactly, independent of
    what other traffic it was batched with, of lane placement, and of
    preemption/resume. (The engines the reference fronts can't promise
    batch-independent seeded sampling.)"""

    temperature: jax.Array  # [B] f32; <=0 means greedy
    top_k: jax.Array  # [B] i32; 0 = disabled
    top_p: jax.Array  # [B] f32; 1.0 = disabled
    seed: jax.Array = None  # [B] u32; per-lane sampling seed
    # OpenAI penalties, applied over a bounded recent-token window
    # (apply_logit_penalties; all-zero/1.0 is an exact identity)
    presence: jax.Array = None  # [B] f32; 0 = off
    frequency: jax.Array = None  # [B] f32; 0 = off
    repetition: jax.Array = None  # [B] f32; 1.0 = off

    @classmethod
    def full(cls, batch: int, temperature=0.0, top_k=0, top_p=1.0, seed=0,
             presence=0.0, frequency=0.0, repetition=1.0):
        return cls(
            temperature=jnp.full((batch,), temperature, jnp.float32),
            top_k=jnp.full((batch,), top_k, jnp.int32),
            top_p=jnp.full((batch,), top_p, jnp.float32),
            seed=jnp.full((batch,), seed, jnp.uint32),
            presence=jnp.full((batch,), presence, jnp.float32),
            frequency=jnp.full((batch,), frequency, jnp.float32),
            repetition=jnp.full((batch,), repetition, jnp.float32),
        )


def _candidates(logits: jax.Array) -> tuple:
    """Top-TOPK_CAP candidate set per row (sorted desc). approx_max_k is
    the TPU-native tiled reduction (recall ~1.0 at K=64 over 128k vocab)
    — exact top_k lowers to a full sort and dominated the decode step's
    fixed overhead. The max (candidate 0) is always exact."""
    V = logits.shape[-1]
    if V > 4096:
        return jax.lax.approx_max_k(logits, min(TOPK_CAP, V))
    return jax.lax.top_k(logits, min(TOPK_CAP, V))


@jax.named_scope("head_and_sample")  # with the forwards' head
def sample(
    logits: jax.Array,  # [B, V] f32
    params: SamplingParams,
    key: jax.Array,
    mask: jax.Array = None,  # [B, V] bool: admissible tokens (guided decoding)
    positions: jax.Array = None,  # [B] i32: per-lane draw counter (seeded path)
) -> jax.Array:
    """Returns sampled token ids [B]. With `positions` (and params.seed)
    the draw is counter-based per lane — batch-independent seeded
    sampling; without, the legacy shared-key categorical path runs
    (spec verify, profiler, compile-check callers)."""
    if mask is not None:
        # guided decoding: inadmissible tokens are removed BEFORE the
        # candidate extraction so the top-K set is drawn from the legal
        # vocabulary only (llm/guided.py token FSM masks)
        logits = jnp.where(mask, logits, -1e30)
    B, V = logits.shape
    cand_logits, cand_idx = _candidates(logits)
    greedy_tokens = cand_idx[:, 0]
    K = cand_logits.shape[1]

    temp = jnp.maximum(params.temperature, 1e-6)[:, None]
    scaled = cand_logits / temp

    # top-k mask within candidates (top_k<=0 or >K -> disabled)
    k_eff = jnp.where(
        (params.top_k <= 0) | (params.top_k > K), K, params.top_k
    )  # [B]
    rank = jnp.arange(K)[None, :]
    scaled = jnp.where(rank < k_eff[:, None], scaled, -jnp.inf)

    # top-p (nucleus) within candidates: keep the smallest prefix of the
    # sorted probs with cumulative mass >= top_p (candidates are sorted desc)
    probs = jax.nn.softmax(scaled, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep = (cum - probs) < params.top_p[:, None]  # always keeps the first
    scaled = jnp.where(keep, scaled, -jnp.inf)

    if positions is not None and params.seed is not None:
        # counter-based per-lane draw: uniforms from (lane seed, position)
        # via gumbel-max — reproducible under re-batching, lane moves and
        # preemption resume (see SamplingParams.seed)
        def lane_u(s, p):
            k = jax.random.fold_in(
                jax.random.fold_in(jax.random.PRNGKey(7), s), p
            )
            return jax.random.uniform(
                k, (K,), minval=1e-7, maxval=1.0 - 1e-7
            )

        u = jax.vmap(lane_u)(
            params.seed.astype(jnp.uint32), positions.astype(jnp.uint32)
        )  # [B, K]
        gumbel = -jnp.log(-jnp.log(u))
        sampled_pos = jnp.argmax(scaled + gumbel, axis=-1)
    else:
        sampled_pos = jax.random.categorical(key, scaled, axis=-1)  # [B]
    sampled_tokens = jnp.take_along_axis(cand_idx, sampled_pos[:, None], axis=1)[:, 0]

    return jnp.where(params.temperature <= 0.0, greedy_tokens, sampled_tokens)


TOP_LOGPROBS_N = 5  # OpenAI caps top_logprobs alternatives at 5


@jax.named_scope("head_and_sample")  # with the forwards' head
def sample_lp(
    logits: jax.Array,  # [B, V] f32 (possibly penalized — the sampling dist)
    params: SamplingParams,
    key: jax.Array,
    mask: jax.Array = None,
    positions: jax.Array = None,
    raw: jax.Array = None,  # pre-penalty logits for the REPORTED logprobs
) -> tuple:
    """sample() + RAW-model logprobs (log-softmax of the unscaled,
    unmasked logits — the OpenAI `logprobs` surface; under guided masks
    this honestly reports how (un)likely the forced token was).

    Returns (tokens [B] i32, logprobs [B] f32,
             top_ids [B, 5] i32, top_lps [B, 5] f32) — the top-5
    alternatives serve chat `top_logprobs` / legacy completions
    `logprobs=k`; the host slices to the requested k.

    Cost discipline: alternatives come from the RAW logits' candidate
    set (the same approx-top-K reduction sample() uses — no full-vocab
    sort on the step path); the only full-vocab extra is one logsumexp
    pass for normalization."""
    tokens = sample(logits, params, key, mask=mask, positions=positions)
    raw = (raw if raw is not None else logits).astype(jnp.float32)
    logz = jax.scipy.special.logsumexp(raw, axis=-1)
    chosen = jnp.take_along_axis(raw, tokens[:, None], axis=-1)[:, 0]
    k = min(TOP_LOGPROBS_N, raw.shape[-1])
    cand_logits, cand_idx = _candidates(raw)
    top_ids = cand_idx[:, :k]
    top_vals = cand_logits[:, :k]
    return tokens, chosen - logz, top_ids, top_vals - logz[:, None]


@jax.named_scope("head_and_sample")  # with the forwards' head
def penalized(logits: jax.Array, params: SamplingParams,
              recent: jax.Array) -> jax.Array:
    """Apply the params' penalties over the lane's recent-token window
    (no-op when the fields are absent — legacy callers). Runtime-gated
    with lax.cond: when NO lane in the batch carries a penalty (the
    common case), the [B, V] counts scatter is skipped entirely at
    execution time — one program variant, near-zero idle cost."""
    if params.presence is None or recent is None:
        return logits
    active = jnp.any(
        (params.presence != 0.0)
        | (params.frequency != 0.0)
        | (params.repetition != 1.0)
    )
    return jax.lax.cond(
        active,
        lambda l: apply_logit_penalties(
            l, recent, params.presence, params.frequency, params.repetition
        ),
        lambda l: l,
        logits,
    )


def apply_logit_penalties(
    logits: jax.Array,  # [B, V]
    recent_tokens: jax.Array,  # [B, W] window of recent token ids (pad = -1)
    presence_penalty: jax.Array,  # [B]
    frequency_penalty: jax.Array,  # [B]
    repetition_penalty: jax.Array,  # [B] 1.0 = off
) -> jax.Array:
    """OpenAI-style penalties over a recent-token window, batched on device."""
    B, V = logits.shape
    W = recent_tokens.shape[1]
    valid = recent_tokens >= 0
    safe = jnp.where(valid, recent_tokens, 0)
    counts = jnp.zeros((B, V), jnp.float32).at[
        jnp.arange(B)[:, None], safe
    ].add(valid.astype(jnp.float32))
    present = counts > 0
    logits = logits - presence_penalty[:, None] * present
    logits = logits - frequency_penalty[:, None] * counts
    rep = repetition_penalty[:, None]
    logits = jnp.where(
        present & (rep != 1.0),
        jnp.where(logits > 0, logits / rep, logits * rep),
        logits,
    )
    return logits
