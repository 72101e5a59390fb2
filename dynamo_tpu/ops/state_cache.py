"""The cache of a family that keeps a recurrent state beside its pages
(docs/hybrid_models.md: models/hybrid.py, models/nemotron_h.py), or a ring
of its window layers' last keys and values (models/exaone_moe.py).

Most layers of such a model keep no keys and values: each keeps, for every
sequence, a matrix-valued state of fixed size and the last inputs of a
short convolution. Those belong to the LANE a sequence occupies, not to its
pages. The family's configuration says how many layers of each kind it has
and what one lane keeps in one of them (`StateSpec`). `StateCache` is the K
store of such a family: a registered pytree that holds the K page pool of
the layers that do attend, the state store `[state layers, lanes + 1, ...]`
(the last slot is scratch: padded
rows and lanes that are not decoding read and write there), and what a
dispatch says of its rows. It rides every jitted program in `kv_k`'s place
(as ops/kv_quant.QuantKV does for a quantized pool), is donated with it and
comes back updated, so no program of the engine takes an argument more.

    pages   [attention layers, pages, rows, KH*D]   K pool (V is a plain pool)
    state   [state layers, lanes + 1, *state_shape]   float32
    conv    [state layers, lanes + 1, taps - 1, channels]
    lanes   [row slots] i32: the lane of each row of the NEXT dispatch that
            packs rows (a prefill batch, a mixed step), from the host
            (`row_lanes`); the scratch slot for padding. A decode block's
            row IS its lane and reads nothing here.
    routed_ring [ring, routed layers, lanes, k] i32: the experts a decode
            step chose for each lane, at `position % ring`
    routed_flat [routed layers, token slots, k] i32: the experts the last
            prefill batch or mixed step chose for each of its token slots

A family whose layers attend under a sliding window keeps no recurrence:
what a lane keeps in one of those layers is the K and V of its last W
positions, and the same two leaves hold them (`state` the K ring, `conv` the
V ring, both `[window layers, lanes + 1, W, KH*D]` in the model's dtype:
"the last n rows of a lane, carried across chunks" with n = the window;
ops/window_attention.py).

A family of latent layers (models/mla_moe.py) keeps NO state of a lane
(`state_layers` 0: both leaves are empty) and ONE store of pages
(`value_store` False: `pages` is the latent store, and the V pool that rides
beside the cache is one page of one value): pages are all there is to a
sequence, so the engine serves them from the prefix index as it serves K
and V pages. It takes a StateCache for the two leaves below. Where such a
family SELECTS the rows a token attends (`index_layers` > 0), the layers with
an indexer keep one index key a token in a second store of pages, `[index
layers, pages, rows, index_dim]`, under the SAME page ids: it rides in the V
pool's place, is written wherever a latent row is written, and a page that
the prefix index hands to another sequence brings its index keys with it.

The two `routed_*` leaves are what the request plane's `routed_experts`
annotation is answered from (benchmark/README.md, "The wire contract");
the host copies them out only for a dispatch that holds such a request.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Tuple

import jax
import jax.numpy as jnp
import numpy as np

#: decode positions the ring of chosen experts holds for a lane: over the
#: steps of two decode blocks in flight
ROUTED_RING = 32


@jax.tree_util.register_pytree_node_class
class StateCache:
    FIELDS = ("pages", "state", "conv", "lanes", "routed_ring", "routed_flat")

    def __init__(self, pages, state, conv, lanes, routed_ring, routed_flat):
        self.pages = pages
        self.state = state
        self.conv = conv
        self.lanes = lanes
        self.routed_ring = routed_ring
        self.routed_flat = routed_flat

    def tree_flatten(self):
        return tuple(getattr(self, f) for f in self.FIELDS), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    def replace(self, **leaves) -> "StateCache":
        return StateCache(
            *(leaves.get(f, getattr(self, f)) for f in self.FIELDS)
        )

    def row_lanes(self, lanes=()) -> np.ndarray:
        """`lanes` for a dispatch whose row r belongs to lane `lanes[r]`,
        on the host (rows past the list: the scratch slot): the engine lays
        it into the dispatch's one transfer, and the program that takes the
        transfer apart puts it into the cache (`replace`)."""
        full = np.full(self.lanes.shape, self.scratch_lane, np.int32)
        full[: len(lanes)] = lanes
        return full

    @property
    def scratch_lane(self) -> int:
        return self.state.shape[1] - 1

    @property
    def state_nbytes(self) -> int:
        return int(self.state.nbytes) + int(self.conv.nbytes)

    @property
    def nbytes(self) -> int:
        return sum(int(x.nbytes) for x in jax.tree_util.tree_leaves(self))

    def __repr__(self):  # debugging aid, never in a hot path
        return "StateCache(" + ", ".join(
            f"{f}={getattr(getattr(self, f), 'shape', None)}"
            for f in self.FIELDS
        ) + ")"


@dataclass(frozen=True)
class StateSpec:
    """What a stateful family's configuration says of its caches
    (`<Config>.state_spec()`): three counts of layers, none of which need be
    `num_layers`, and the shapes ONE lane keeps in ONE state layer."""

    state_layers: int  # layers that keep a recurrent state (or a ring)
    attention_layers: int  # layers that keep pages
    routed_layers: int  # layers whose chosen experts are recorded
    state_shape: Tuple[int, ...]  # the matrix state, in `state_dtype`
    conv_shape: Tuple[int, int]  # (taps - 1, channels), in the model's dtype
    state_dtype: Any
    experts_per_token: int
    #: False: the family keeps ONE store a layer and no V store (a latent
    #: row, models/mla_moe.py); `pages` is sized by `head_dim` alone
    value_store: bool = True
    #: layers that keep an index key of `index_dim` values a token beside
    #: their latent row (a learned selection of the context): a SECOND store
    #: `[index_layers, pages, rows, index_dim]` under the latent store's page
    #: ids, which rides in the V pool's place; 0: none
    index_layers: int = 0
    index_dim: int = 0


def state_bytes_per_lane(c) -> int:
    """Bytes ONE lane's state takes over all the state layers of `c` (a
    configuration with `state_spec()`): the matrix state and the
    convolution's tail. What pool sizing takes out of the pages' room, a
    lane at a time."""
    spec = c.state_spec()
    state = math.prod(spec.state_shape) * jnp.dtype(spec.state_dtype).itemsize
    conv = math.prod(spec.conv_shape) * jnp.dtype(c.dtype).itemsize
    return spec.state_layers * (state + conv)


def alloc_state_cache(c, num_pages: int, page_size: int, max_seqs: int,
                      max_tokens: int, row_slots: int = 0):
    """(StateCache, V pool) of a configuration `c` with `state_spec()`: the K
    and V pools of its attention layers, `num_pages` pages each, and the
    zeroed state store of `max_seqs` lanes and one scratch slot.
    `max_tokens`: the most token slots one prefill batch or mixed step
    packs; `row_slots`: the most rows (`max_seqs` where smaller)."""
    from .kv_quant import alloc_kv_store, no_value_store

    spec = c.state_spec()
    pools = [
        alloc_kv_store(spec.attention_layers, num_pages, page_size,
                       c.num_kv_heads, c.head_dim, c.dtype, "none")
        for _ in range(2 if spec.value_store else 1)
    ]
    if not spec.value_store:
        pools.append(
            alloc_kv_store(spec.index_layers, num_pages, page_size, 1,
                           spec.index_dim, c.dtype, "none")
            if spec.index_layers else
            no_value_store(spec.attention_layers, page_size, c.dtype))
    K = spec.experts_per_token
    cache = StateCache(
        pages=pools[0],
        state=jnp.zeros(
            (spec.state_layers, max_seqs + 1, *spec.state_shape),
            spec.state_dtype),
        conv=jnp.zeros(
            (spec.state_layers, max_seqs + 1, *spec.conv_shape), c.dtype),
        lanes=jnp.full((max(row_slots, max_seqs),), max_seqs, jnp.int32),
        routed_ring=jnp.zeros(
            (ROUTED_RING, spec.routed_layers, max_seqs, K), jnp.int32),
        routed_flat=jnp.zeros((spec.routed_layers, max_tokens, K), jnp.int32),
    )
    return cache, pools[1]
