"""Pallas TPU kernel: one token of the gated delta rule for every decoding
lane, over the state store IN PLACE.

The hybrid family's linear layers keep, per lane and value head, a dk x dv
float32 state (ops/state_cache.py). A decode step applies

    S <- exp(g) S;  d = beta (v - S^T k);  S <- S + k d^T;  o = S^T q

to each. `S^T k` reduces over the whole tile and `S^T q` over the UPDATED
tile, so XLA cannot fuse them with the update: models/hybrid.delta_step
reads the state three times and writes it once. Here a lane's tile is
copied into VMEM once, both reductions are taken over the tile as it came,

    u = S^T k,  w = S^T q,  a = exp(g),
    d = beta (v - a u),  o = a w + (k . q) d,  S <- a S + k d^T

(the same mathematics, the reductions taken before the update), and the
tile is copied out once: a read and a write of the state, which is what
`hybrid.step_work` counts as the least.

Layouts:
    state:   [state layers, lanes + 1, nv, dk, dv] float32, the WHOLE store
             (+ the layer's index as scalar prefetch); aliased to the
             output, so the program holds no second store and no slice of it
    q, k:    [B, nv, dk] float32     v: [B, nv, dv] float32
    g, beta: [B, nv] float32         live: [B] (row b IS lane b)
    -> (state, o [B, nv, dv] float32)

Design notes:
  * grid = (B, nv / heads a step). The state's block is (layer, lane, head
    block), whole dk x dv tiles, copied in and out by the pipeline that
    `BlockSpec`s give (double-buffered: the next lane's tiles arrive while
    this lane's are multiplied). Slots of other layers, of lanes past B and
    the scratch slot are no block of the grid and are never touched.
  * a lane that is not decoding keeps its state bit for bit: its tiles are
    copied out as they came in (the aliased output's block has to be
    written). Its `o` is computed all the same, as `delta_step`'s caller
    computes it: the two implementations agree on every row.
  * everything is float32 on the vector unit, products and sums, as
    `delta_step` says of itself; nothing rides the MXU.
  * `k` and `q` arrive with dk along the lanes and are needed along the
    sublanes (a column that multiplies every row of the tile): one 128 x
    128 transpose a grid step turns both around, heads as columns.
  * `g` and `beta` are scalars a (lane, head): they ride scalar prefetch
    (SMEM), flat, beside the layer's index and `live`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

f32 = jnp.float32
#: the transposed block of k and q: a full 128 x 128 tile of the vector unit
_T = 128


def heads_per_step(nv: int, dk: int, dv: int) -> int:
    """Value heads one grid step holds: the kernel's one tuned number, from
    the shapes alone. As many whole heads as 2 MiB of state hold (32 at 128
    x 128: a lane's whole layer), at most what one transposed tile seats (q
    and k side by side: 64): all `nv` where they fit, else the largest
    multiple of 8 (the sublanes of a float32 block) that divides `nv`; 0
    where there is none. Read on a v5e at the cell's sizes (32 lanes, 6
    layers: 1.324, 1.271 and 1.267 ms a step at 8, 16 and 32 heads; PERF.md,
    PR 47)."""
    cap = max(1, min((2 << 20) // (dk * dv * 4), _T // 2))
    if nv <= cap:
        return nv
    return next(
        (hb for hb in range(cap - cap % 8, 0, -8) if nv % hb == 0), 0)


def takes(state_shape, state_dtype) -> bool:
    """Whether the kernel takes a lane's state of `state_shape` (nv, dk,
    dv): float32, dk one transposed tile wide, dv whole lane registers, and
    heads that split into blocks."""
    nv, dk, dv = state_shape
    return (jnp.dtype(state_dtype) == f32 and dk == _T and dv % 128 == 0
            and heads_per_step(nv, dk, dv) > 0)


def _kernel(li_ref, live_ref, g_ref, beta_ref, q_ref, k_ref, v_ref, s_ref,
            s_out, o_ref, *, nv: int, hb: int):
    """One (lane, head block): refs are the scalar prefetch (layer [1],
    live [B] i32, g and beta [B * nv] f32), q, k [hb, dk], v [hb, dv], the
    state's block [hb, dk, dv] in and out, o [hb, dv]."""
    del li_ref  # the index maps' alone
    b, hblk = pl.program_id(0), pl.program_id(1)
    dk = q_ref.shape[-1]
    dv = v_ref.shape[-1]
    # q's heads as columns 0 ... hb, k's as columns hb ... 2 hb
    rows = [q_ref[...], k_ref[...]]
    if 2 * hb < _T:
        rows.append(jnp.zeros((_T - 2 * hb, dk), f32))
    cols = jnp.concatenate(rows, axis=0).T  # [dk, 128]
    base = b * nv + hblk * hb
    for h in range(hb):
        qc = cols[:, h:h + 1]  # [dk, 1]
        kc = cols[:, hb + h:hb + h + 1]
        kb = jnp.broadcast_to(kc, (dk, dv))
        S = s_ref[h]  # [dk, dv]
        u = jnp.sum(S * kb, axis=0, keepdims=True)  # [1, dv]
        w = jnp.sum(S * qc, axis=0, keepdims=True)
        kq = jnp.sum(kc * qc, axis=0, keepdims=True)  # [1, 1]
        a = jnp.exp(jnp.full((1, dv), g_ref[base + h], f32))
        d = beta_ref[base + h] * (v_ref[h:h + 1, :] - a * u)
        o_ref[h:h + 1, :] = a * w + kq * d
        s_out[h] = a * S + kb * d

    @pl.when(live_ref[b] == 0)
    def _():
        s_out[...] = s_ref[...]


@functools.partial(jax.jit, static_argnames=("heads", "interpret"))
def delta_step_pallas(
    state: jax.Array,  # [state layers, lanes + 1, nv, dk, dv] float32
    layer: jax.Array,  # i32 scalar: the state layer
    q: jax.Array,  # [B, nv, dk] float32
    k: jax.Array,  # [B, nv, dk]
    v: jax.Array,  # [B, nv, dv]
    g: jax.Array,  # [B, nv] float32 log decay
    beta: jax.Array,  # [B, nv]
    live: jax.Array,  # [B] bool: the lanes that decode
    *,
    heads: int | None = None,
    interpret: bool = False,
):
    """One token of the gated delta rule for lanes 0 ... B of state layer
    `layer`; returns (the store, updated in place, o [B, nv, dv])."""
    Ll, slots, nv, dk, dv = state.shape
    B = q.shape[0]
    if not takes((nv, dk, dv), state.dtype) or B > slots:
        raise ValueError(f"state {state.dtype} {state.shape} for {B} lanes")
    hb = heads or heads_per_step(nv, dk, dv)
    if nv % hb or 2 * hb > _T:
        raise ValueError(
            f"{hb} heads a step of {nv}: a block takes a divisor of the "
            f"heads, at most {_T // 2}")

    def tile(b, h, li, *_):
        return (li[0], b, h, 0, 0)

    def row(b, h, *_):
        return (b, h, 0)

    tiles = pl.BlockSpec((None, None, hb, dk, dv), tile)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B, nv // hb),
        in_specs=[
            pl.BlockSpec((None, hb, dk), row),
            pl.BlockSpec((None, hb, dk), row),
            pl.BlockSpec((None, hb, dv), row),
            tiles,
        ],
        out_specs=[tiles, pl.BlockSpec((None, hb, dv), row)],
    )
    block_bytes = hb * dk * dv * 4
    return pl.pallas_call(
        functools.partial(_kernel, nv=nv, hb=hb),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(state.shape, state.dtype),
            jax.ShapeDtypeStruct((B, nv, dv), f32),
        ],
        # operands count the scalar prefetch: the store is the eighth
        input_output_aliases={7: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            # the state's block in and out, two buffers each, and room for
            # the small operands and the body's temporaries
            vmem_limit_bytes=4 * block_bytes + (8 << 20),
        ),
        cost_estimate=pl.CostEstimate(
            flops=7 * B * nv * dk * dv,
            bytes_accessed=2 * B * nv * dk * dv * 4,
            transcendentals=B * nv * dv,
        ),
        interpret=interpret,
    )(
        jnp.asarray(layer, jnp.int32).reshape(1),
        live.astype(jnp.int32),
        g.astype(f32).reshape(-1),
        beta.astype(f32).reshape(-1),
        q, k, v, state,
    )
