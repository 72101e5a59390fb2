"""ops/pallas_delta_step.py, interpreted on the CPU, against the step form
it stands in for (models/hybrid.delta_step) and the chunked form
(hybrid.delta_chunk): the arithmetic, and what the kernel must leave alone
in the store it updates in place. What Mosaic makes of it for a v5e is
tests/test_tpu_compile.py's.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.models import hybrid
from dynamo_tpu.ops.pallas_delta_step import (
    delta_step_pallas,
    heads_per_step,
    takes,
)

NV, DK, DV, STEPS = 16, 128, 128, 8
REL = 1e-5


def _inputs(lanes, seed):
    """STEPS tokens for `lanes` lanes: q, k as `_split_qkv` leaves them
    (L2-normalised, q scaled), decays that remember tens of tokens."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (STEPS, lanes, NV, DK))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * DK ** -0.5
    k = jax.random.normal(ks[1], (STEPS, lanes, NV, DK))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (STEPS, lanes, NV, DV))
    g = -jax.random.uniform(ks[3], (STEPS, lanes, NV), minval=0.0, maxval=1.0)
    beta = jax.random.uniform(ks[4], (STEPS, lanes, NV))
    return q, k, v, g, beta


def _close(got, want, rel=REL):
    return float(jnp.abs(got - want).max()) <= rel * float(jnp.abs(want).max())


@pytest.mark.parametrize("layers, layer", ((1, 0), (3, 1)))
@pytest.mark.parametrize("lanes", (1, 4, 32))
@pytest.mark.parametrize("heads", (8, 16))
def test_the_kernel_is_the_step_form_over_the_store_in_place(
        heads, lanes, layers, layer):
    """One step and eight in a row against `delta_step` and one
    `delta_chunk`; a lane that is not live, the scratch slot and every
    other layer's slots come back bit for bit."""
    live = np.arange(lanes) % 3 != 1 if lanes > 1 else np.ones((1,), bool)
    q, k, v, g, beta = _inputs(lanes, seed=heads + lanes + layers)
    store0 = jax.random.normal(
        jax.random.PRNGKey(99), (layers, lanes + 1, NV, DK, DV))
    step = jax.jit(lambda s, *a: delta_step_pallas(
        s, jnp.int32(layer), *a, jnp.asarray(live), heads=heads,
        interpret=True))
    reference = jax.jit(hybrid.delta_step)

    store, S = store0, store0[layer, :lanes]
    for t in range(STEPS):
        store, o = step(store, q[t], k[t], v[t], g[t], beta[t])
        S, want = reference(S, q[t], k[t], v[t], g[t], beta[t])
        # the output of every row, live or not, is the step form's
        assert _close(o, want, REL * (t + 1)), t
        assert _close(store[layer, :lanes][live], S[live], REL * (t + 1)), t
        # a lane that does not decode keeps its state, and so must the
        # reference it is held against
        S = jnp.where(jnp.asarray(live)[:, None, None, None], S,
                      store0[layer, :lanes])
    chunk_S, _ = hybrid.delta_chunk(
        store0[layer, :lanes], *(jnp.moveaxis(x, 0, 1) for x in (q, k, v, g, beta)))
    assert _close(store[layer, :lanes][live], chunk_S[live], 1e-4)

    store, store0 = np.asarray(store), np.asarray(store0)
    assert (store[layer, :lanes][~live] == store0[layer, :lanes][~live]).all()
    assert (store[layer, lanes] == store0[layer, lanes]).all()  # scratch
    others = [ll for ll in range(layers) if ll != layer]
    assert (store[others] == store0[others]).all()


def test_the_block_of_heads_follows_the_shapes():
    """The kernel's one tuned number, and what the gate asks of a state."""
    assert heads_per_step(32, 128, 128) == 32  # the cell's: a lane's layer
    assert heads_per_step(64, 128, 128) == 32
    assert heads_per_step(32, 128, 256) == 16
    assert heads_per_step(4, 128, 128) == 4
    assert takes((32, 128, 128), jnp.float32)
    assert not takes((32, 128, 128), jnp.bfloat16)
    assert not takes((4, 16, 16), jnp.float32)  # the CPU tests' model
    assert not takes((32, 128, 64), jnp.float32)
    assert not takes((36, 128, 128), jnp.float32)  # no block of 8 divides it
