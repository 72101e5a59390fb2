"""The cache of a family that keeps a recurrent state beside its pages
(docs/hybrid_models.md).

Three layers in four of such a model keep no keys and values: each keeps,
for every sequence, a matrix-valued state of fixed size and the last inputs
of a short convolution. Those belong to the LANE a sequence occupies, not
to its pages. `StateCache` is the K store of such a family: a registered
pytree that holds the K page pool of the layers that do attend, the state
store `[linear layers, lanes + 1, ...]` (the last slot is scratch: padded
rows and lanes that are not decoding read and write there), and what a
dispatch says of its rows. It rides every jitted program in `kv_k`'s place
(as ops/kv_quant.QuantKV does for a quantized pool), is donated with it and
comes back updated, so no program of the engine takes an argument more.

    pages   [full layers, pages, rows, KH*D]   K pool (V is a plain pool)
    state   [linear layers, lanes + 1, heads, dk, dv]   float32
    conv    [linear layers, lanes + 1, taps - 1, channels]
    lanes   [row slots] i32: the lane of each row of the NEXT dispatch that
            packs rows (a prefill batch, a mixed step), set by the host
            (`with_lanes`); the scratch slot for padding. A decode block's
            row IS its lane and reads nothing here.
    routed_ring [ring, routed layers, lanes, k] i32: the experts a decode
            step chose for each lane, at `position % ring`
    routed_flat [routed layers, token slots, k] i32: the experts the last
            prefill batch or mixed step chose for each of its token slots

The two `routed_*` leaves are what the request plane's `routed_experts`
annotation is answered from (benchmark/README.md, "The wire contract");
the host copies them out only for a dispatch that holds such a request.
"""

from __future__ import annotations

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

    def with_lanes(self, lanes) -> "StateCache":
        """The cache for a dispatch whose row r belongs to lane `lanes[r]`
        (rows past the list: the scratch slot)."""
        full = np.full(self.lanes.shape, self.scratch_lane, np.int32)
        full[: len(lanes)] = lanes
        return self.replace(lanes=jnp.asarray(full))

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


def state_bytes_per_lane(c) -> int:
    """Bytes ONE lane's state takes over all the linear layers of `c` (a
    models/hybrid.HybridConfig): the matrix state and the convolution's
    tail. What pool sizing takes out of the pages' room, a lane at a time."""
    n_linear = c.num_layers - c.num_layers // c.full_attention_interval
    state = (
        c.linear_num_value_heads * c.linear_key_head_dim
        * c.linear_value_head_dim * jnp.dtype(c.state_dtype).itemsize
    )
    conv = (
        (c.linear_conv_kernel_dim - 1) * conv_channels(c)
        * jnp.dtype(c.dtype).itemsize
    )
    return n_linear * (state + conv)


def conv_channels(c) -> int:
    """Channels of the linear mixer's convolution: q, k and v side by side."""
    return (
        2 * c.linear_num_key_heads * c.linear_key_head_dim
        + c.linear_num_value_heads * c.linear_value_head_dim
    )


def alloc_state_cache(c, num_pages: int, page_size: int, max_seqs: int,
                      max_tokens: int, row_slots: int = 0):
    """(StateCache, V pool) of a models/hybrid.HybridConfig `c`: the K and V
    pools of its full-attention layers, `num_pages` pages each, and the
    zeroed state store of `max_seqs` lanes and one scratch slot.
    `max_tokens`: the most token slots one prefill batch or mixed step
    packs; `row_slots`: the most rows (`max_seqs` where smaller)."""
    from .kv_quant import alloc_kv_store

    n_full = c.num_layers // c.full_attention_interval
    n_linear = c.num_layers - n_full
    pools = [
        alloc_kv_store(n_full, num_pages, page_size, c.num_kv_heads,
                       c.head_dim, c.dtype, "none")
        for _ in range(2)
    ]
    K = c.num_experts_per_tok
    cache = StateCache(
        pages=pools[0],
        state=jnp.zeros(
            (n_linear, max_seqs + 1, c.linear_num_value_heads,
             c.linear_key_head_dim, c.linear_value_head_dim), c.state_dtype),
        conv=jnp.zeros(
            (n_linear, max_seqs + 1, c.linear_conv_kernel_dim - 1,
             conv_channels(c)), c.dtype),
        lanes=jnp.full((max(row_slots, max_seqs),), max_seqs, jnp.int32),
        routed_ring=jnp.zeros(
            (ROUTED_RING, c.num_layers, max_seqs, K), jnp.int32),
        routed_flat=jnp.zeros((c.num_layers, max_tokens, K), jnp.int32),
    )
    return cache, pools[1]
