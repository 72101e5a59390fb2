"""Quantized paged KV cache: int8/int4 page storage with per-page-per-head
scales (DYN_KV_QUANT / EngineConfig.kv_quant; docs/kvbm.md "Quantized KV
format", docs/ragged_attention.md "Quantized pages").

The KV cache is the HBM bound on BOTH raw speed and resident-session
count: halving (int8) or quartering (int4) the bytes per page
roughly doubles/quadruples the sessions a chip holds AND shrinks every
byte the KVBM tiers, the peer fabric, and the disagg handoff move. The
production shape is RTP-LLM's (PAPERS.md): pages quantized ON WRITE,
dequantized INSIDE the attention kernel's VMEM window, scales riding the
scalar-prefetch operands beside the page tables.

Pool layout — ONE for every store: lane-dense `[L, pages, rows, KH*D]`,
heads flattened into the minor (lane) axis from allocation on. Where a
page lies in HBM is where the attention kernels read it: the Pallas
wrappers take the WHOLE pool (`memory_space=pl.ANY`) plus the layer index
as a scalar-prefetch operand and DMA `pool[li, page]`; nothing the size
of the pool is ever sliced or reshaped. Readers that want heads apart get
the `[.., KH, D]` view of the PAGES they gathered (`gather_dequant`,
`extract_pages`), never of the pool.

A family of LATENT layers (models/mla_moe.py, docs/latent_cache.md) keeps
ONE store in the same layout and no V store: a token's row is the normed
latent and the rotated shared key side by side, `[L, pages, rows, width]`,
which is `KH` 1 and `D` `width` to every function here (`alloc_kv_store`,
`kv_write`, `gather_dequant`, `extract_pages` and `inject_pages` take it
as they take a K store). `width` is `latent_row_width(rank + rope)`,
the next multiple of 128 lanes: 640 for 512 + 64, of which the last 64 are
zeros, and the counters count 640 (models/mla_moe.step_work). A row of 576
lanes is no saving on a TPU: arrays lie there in tiles of 128 lanes, so a
row-major `[.., 64, 576]` takes 640 lanes a row all the same, and the
compiler's own layout for that shape avoids the padding by making the PAGE
axis the lane axis (`{1,3,2,0}`), from which every program copies the whole
pool into row-major order and back (compiled for a described v5e: two
pool-sized copies and a pool of temporaries a step, PERF.md section 6,
PR 54). In `kv_v`'s place rides `no_value_store`, a store of one page of
one value. A quantized mode is refused: one scale a page would round the
latent and the rotated key together.

Representation — `QuantKV`, a registered pytree replacing the raw
[L, pages, page_size, KH*D] kv_k/kv_v arrays:

    q: int8  [L, pages, ps_eff, KH*D]    quantized values; int4 packs two
                                         tokens per byte ALONG THE
                                         page_size axis (ps_eff = ps//2),
                                         pairing token o with o + ps/2 so
                                         unpack is concat(lo, hi) — no
                                         minor-dim interleave, which the
                                         Pallas VMEM window cannot do
    s: f32   [L, pages, KH]              per-page-per-head scale

(bits, page_size) are STATIC pytree aux data: jit specializes per format,
donation/tree_map/jax.device transfers all work leaf-wise, and
extract/inject gathers ride the same `[:, page_ids]` slice on both leaves.
A plain array does not know its head count; callers that need it pass
`head_dim` (the ops read it off q) or `num_kv_heads` (the engine's config).

Scale discipline (quantize-on-write, `kv_write`):
  * a page's scale is the running max over the amax of every write into
    it; when a write GROWS the scale, the page's existing ints are
    requantized (q' = round(q * old/new)) so dequantization stays exact
    under one scale per page.
  * a write at in-page offset 0 STARTS the page (offset 0 is the earliest
    slot a position can occupy, so any prior content belongs to a dead
    sequence): the stale scale is dropped first, which also zero-scrubs
    the stale ints — page reuse cannot inflate quantization error.
  * fp mode ("none") is one plain in-place scatter of lane-dense rows:
    quant off adds nothing to the seed's write path.

Host/wire boundary (`host_pack_pages`/`host_unpack_pages`): a page
serializes as q-bytes ‖ scale-bytes in one uint8 row `[L, n, PAGE_BYTES]`
— KVBM G2/G3 tiers store these rows natively (block_shape (L, PB) uint8),
and the kv_transfer peer-pull / disagg payloads ship them unchanged, so
tier capacity at fixed host/disk bytes and the fabric's wire bytes shrink
by the same 2x/4x. The format name travels in block descriptors and the
kvbm pull handshake; a mixed-precision fleet fails TYPED
(llm.kv_transfer.KvFormatError), never silently misreads bytes.
"""

from __future__ import annotations

import os
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..models.quant import QMAX, pack_int4, unpack_int4

KV_QUANT_MODES = ("none", "int8", "int4")


def resolve_kv_quant(mode: Optional[str]) -> str:
    """EngineConfig.kv_quant (explicit) else DYN_KV_QUANT else "none"."""
    if mode is None:
        mode = os.environ.get("DYN_KV_QUANT") or "none"
    mode = str(mode).strip().lower() or "none"
    if mode not in KV_QUANT_MODES:
        raise ValueError(
            f"unknown KV quant mode {mode!r} (DYN_KV_QUANT / kv_quant); "
            f"expected one of {KV_QUANT_MODES}"
        )
    return mode


def kv_quant_bits(mode: str) -> int:
    """Bits per stored KV value; 0 = full precision."""
    return {"none": 0, "int8": 8, "int4": 4}[mode]


@jax.tree_util.register_pytree_node_class
class QuantKV:
    """Quantized KV store (see module docstring). Leaves: (q, s); static
    aux: (bits, page_size)."""

    def __init__(self, q, s, bits: int, page_size: int):
        self.q = q
        self.s = s
        self.bits = int(bits)
        self.page_size = int(page_size)

    def tree_flatten(self):
        return (self.q, self.s), (self.bits, self.page_size)

    @classmethod
    def tree_unflatten(cls, aux, children):
        q, s = children
        return cls(q, s, aux[0], aux[1])

    @property
    def mode(self) -> str:
        return {8: "int8", 4: "int4"}[self.bits]

    @property
    def nbytes(self) -> int:
        return int(self.q.nbytes) + int(self.s.nbytes)

    def __repr__(self):  # debugging aid, never in a hot path
        return (
            f"QuantKV(bits={self.bits}, q={getattr(self.q, 'shape', None)}, "
            f"s={getattr(self.s, 'shape', None)})"
        )


def is_quant_kv(x: Any) -> bool:
    return isinstance(x, QuantKV)


def _ps_eff(page_size: int, bits: int) -> int:
    if bits == 4:
        if page_size % 2:
            raise ValueError("int4 KV quant requires an even page_size")
        return page_size // 2
    return page_size


def kv_page_bytes(page_size: int, num_kv_heads: int, head_dim: int,
                  dtype, mode: str) -> int:
    """Bytes ONE K or V page occupies in HBM (and, packed, on the wire):
    quantized = q bytes + 4-byte f32 scale per kv head. Pool sizing
    (engine._auto_num_pages) and the bench's sessions-per-HBM math both
    read this."""
    bits = kv_quant_bits(mode)
    if bits == 0:
        itemsize = jnp.zeros((), dtype).dtype.itemsize
        return page_size * num_kv_heads * head_dim * itemsize
    return _ps_eff(page_size, bits) * num_kv_heads * head_dim + 4 * num_kv_heads


def alloc_kv_store(num_layers: int, num_pages: int, page_size: int,
                   num_kv_heads: int, head_dim: int, dtype, mode: str,
                   sharding=None):
    """One KV store (K or V): a plain fp array for mode "none", else a
    QuantKV with zeroed ints and zeroed scales (scale 0 marks a fresh
    page: kv_write's page-start reset plus requantize-by-ratio scrub it
    before first use)."""
    bits = kv_quant_bits(mode)
    if bits == 0:
        shape = (num_layers, num_pages, page_size, num_kv_heads * head_dim)
        if sharding is None:
            return jnp.zeros(shape, dtype)
        # born sharded: every device fills its own shard. Building the
        # whole pool on one device and moving it (the first four-chip run:
        # 21.78 G asked of a 16 G chip) cannot hold a pool sized to fill
        # the mesh.
        zero_pool = jax.jit(
            lambda: jnp.zeros(shape, dtype), out_shardings=sharding
        )
        return zero_pool()
    if sharding is not None:
        raise ValueError(
            "kv_quant with a sharded KV pool is unsupported (per-head scale "
            "sharding is the multi-chip follow-up); run tp_size=1 or "
            "DYN_KV_QUANT=none"
        )
    q = jnp.zeros(
        (num_layers, num_pages, _ps_eff(page_size, bits),
         num_kv_heads * head_dim),
        jnp.int8,
    )
    s = jnp.zeros((num_layers, num_pages, num_kv_heads), jnp.float32)
    return QuantKV(q, s, bits, page_size)


LATENT_ROW_LANES = 128


def latent_row_width(values: int) -> int:
    """Lanes of the row a latent layer keeps a token for `values` values
    (kv_lora_rank + rope): the next multiple of 128 (the module's text
    says why), the lanes past `values` zeros."""
    return -(-values // LATENT_ROW_LANES) * LATENT_ROW_LANES


def no_value_store(num_layers: int, page_size: int, dtype):
    """What rides in `kv_v`'s place for a family that keeps no V store
    (every program takes and returns a `kv_v`): one page of one value a
    layer."""
    return alloc_kv_store(num_layers, 1, page_size, 1, 1, dtype, "none")


def kv_page_size(store) -> int:
    """Tokens per page of a KV store (QuantKV carries it statically; a
    plain array reads its page axis)."""
    if isinstance(store, QuantKV):
        return store.page_size
    return store.shape[2]


class KVLayer(NamedTuple):
    """One layer of a KV store as the attention ops are handed it: the
    WHOLE pool and the layer's index, not a slice. A slice of a multi-GB
    pool is a copy on the TPU (70% of device time before PR 26); the
    kernels index `pool[li, page]` in their DMAs and the XLA reference
    path folds `li` into its page gather."""

    pool: Any  # [L, pages, rows, KH*D] array, or the whole QuantKV
    li: Any  # python int or i32 scalar


def kv_layer(store, li) -> KVLayer:
    """Per-layer operand for the attention ops (see KVLayer)."""
    return KVLayer(store, li)


def layer_dims(layer: KVLayer, head_dim: int) -> Tuple[int, int]:
    """(page_size, KH) of a per-layer KV operand. The pool is lane-dense,
    so the head count comes from `head_dim` (q's minor dim at every call
    site)."""
    pool = layer.pool
    lanes = (pool.q if isinstance(pool, QuantKV) else pool).shape[3]
    assert lanes % head_dim == 0, (lanes, head_dim)
    return kv_page_size(pool), lanes // head_dim


def kernel_operands(kv_k_layer: KVLayer, kv_v_layer: KVLayer, head_dim: int):
    """Destructure per-layer KV operands for the Pallas wrappers — the ONE
    spelling of what the kernels are handed (pallas_ragged_attention, the
    prefill kernel and both decode kernels): returns (k_pool, v_pool, li,
    KH, rows, page_size, kv_bits, scale_prefetch). k_pool/v_pool are the
    WHOLE lane-dense pools [L, pages, rows, KH*D] to pass in `pl.ANY` as
    they lie (rows = page_size, or page_size//2 int4-packed along the
    sublane axis); li is the [1] i32 layer index for the scalar-prefetch
    operands (the page DMA is `pool[li, page]`); KH is the lane axis over
    `head_dim`; kv_bits selects the in-kernel dequant path (0 = fp);
    scale_prefetch is the list of this layer's f32 [pages, KH] scale
    operands to append to the scalar-prefetch refs (empty for fp)."""
    li = jnp.reshape(jnp.asarray(kv_k_layer.li, jnp.int32), (1,))
    page_size, KH = layer_dims(kv_k_layer, head_dim)
    k_pool, v_pool = kv_k_layer.pool, kv_v_layer.pool
    if isinstance(k_pool, QuantKV):
        return (
            k_pool.q, v_pool.q, li, KH, k_pool.q.shape[2], page_size,
            k_pool.bits,
            [
                k_pool.s[kv_k_layer.li].astype(jnp.float32),
                v_pool.s[kv_v_layer.li].astype(jnp.float32),
            ],
        )
    return k_pool, v_pool, li, KH, page_size, page_size, 0, []


# ---------------------------------------------------------------------- #
# quantize-on-write
# ---------------------------------------------------------------------- #


def _write_one_layer(q, s, phys, offs, vals, bits: int, page_size: int):
    """Core scatter-write of `vals` [T, KH, D] (f-dtype) at (phys[t],
    offs[t]) into one layer's (q [P, ps_eff, KH*D], s [P, KH]). Heads
    come apart on the GATHERED pages only ([T, ps_eff, KH, D]).

    Duplicate pages within one write are handled exactly: scale combines
    via scatter-max, the requantize pass writes identical whole-page
    content per duplicate, and the new values land via a scatter-ADD of
    per-copy deltas (int8 wraparound is linear, so concurrent nibble/row
    deltas into one byte compose exactly)."""
    qmax = QMAX[bits]
    T = phys.shape[0]
    vals32 = vals.astype(jnp.float32)
    amax = jnp.max(jnp.abs(vals32), axis=-1)  # [T, KH]
    # page-start reset: offset 0 is a page's earliest slot, so a write
    # there means any existing content/scale belongs to a dead sequence
    starts = jnp.where(
        (offs == 0)[:, None], jnp.float32(0), jnp.float32(jnp.inf)
    )  # [T, KH] broadcast over heads
    s = s.at[phys].min(jnp.broadcast_to(starts, amax.shape))
    old_s = s[phys]  # [T, KH] (post-reset, pre-grow)
    s = s.at[phys].max(amax / qmax)
    eff_s = s[phys]  # [T, KH] final per-page scales (duplicates agree)
    # requantize the touched pages for grown scales (ratio 0 scrubs
    # freshly-started pages' stale ints to 0)
    # [T, ps_eff, KH, D] (pre-write content, dup-consistent)
    pages_q = q[phys].reshape(T, q.shape[1], *vals.shape[1:])
    nib = unpack_int4(pages_q, axis=1) if bits == 4 else pages_q  # [T, ps, KH, D]
    ratio = jnp.where(eff_s > 0, old_s / jnp.maximum(eff_s, 1e-30), 0.0)
    nib = jnp.clip(
        jnp.round(nib.astype(jnp.float32) * ratio[:, None, :, None]),
        -qmax, qmax,
    ).astype(jnp.int8)
    repacked = pack_int4(nib, axis=1) if bits == 4 else nib

    def lanes(x):  # [T, ps_eff, KH, D] -> the pool's [T, ps_eff, KH*D]
        return x.reshape(T, q.shape[1], -1)

    q = q.at[phys].set(lanes(repacked))  # duplicates write identical content
    # quantize the new values at the final page scale and write each
    # copy's own row; the delta-add merges duplicate pages exactly
    qv = jnp.clip(
        jnp.round(vals32 / jnp.maximum(eff_s, 1e-30)[:, :, None]),
        -qmax, qmax,
    ).astype(jnp.int8)
    written = nib.at[jnp.arange(T), offs].set(qv)
    wpacked = pack_int4(written, axis=1) if bits == 4 else written
    # int8 subtraction/addition wrap (two's complement); the FINAL value
    # per byte is the in-range written one, so wraparound cancels exactly
    q = q.at[phys].add(lanes(wpacked - repacked))
    return q, s


def kv_write(store, li, phys, offs, vals):
    """Write `vals` [..., KH, D] at (li, phys[...], offs[...]) — the ONE
    KV page-write spelling for every model forward (prefill chunk store,
    ragged mixed store, decode, ring, pp). fp mode is one in-place scatter
    of lane-dense rows [..., KH*D] into the donated pool."""
    if not isinstance(store, QuantKV):
        return store.at[li, phys, offs].set(
            vals.reshape(*vals.shape[:-2], -1)
        )
    lead = phys.shape
    T = int(np.prod(lead)) if lead else 1
    phys_f = phys.reshape(T)
    offs_f = offs.reshape(T)
    vals_f = vals.reshape(T, *vals.shape[len(lead):])
    q, s = _write_one_layer(
        store.q[li], store.s[li], phys_f, offs_f, vals_f,
        store.bits, store.page_size,
    )
    return QuantKV(
        store.q.at[li].set(q), store.s.at[li].set(s),
        store.bits, store.page_size,
    )


# ---------------------------------------------------------------------- #
# dequantizing gathers (the XLA reference attention paths / fuzz oracle)
# ---------------------------------------------------------------------- #


def gather_dequant(layer: KVLayer, tables, head_dim: int, dtype=jnp.float32):
    """Gather pages of a per-layer KV operand and return FULL-PRECISION
    context [..., n_pages, page_size, KH, D] in `dtype`: the accessor of
    the XLA reference paths. The layer index rides the gather
    (`pool[li, tables]`), and heads come apart on the gathered pages,
    never on the pool. Plain pools gather as they are (any dtype);
    QuantKV pools unpack + dequantize. `tables` may have any leading
    shape ([max_pages] or [B, max_pages])."""
    pool, li = layer
    if not isinstance(pool, QuantKV):
        pages = pool[li, tables]  # [..., P, ps, KH*D]
        return pages.reshape(*pages.shape[:-1], -1, head_dim)
    q = pool.q[li, tables]  # [..., P, ps_eff, KH*D]
    q = q.reshape(*q.shape[:-1], -1, head_dim)
    if pool.bits == 4:
        q = unpack_int4(q, axis=-3)  # page_size axis
    s = pool.s[li, tables]  # [..., P, KH]
    return (q.astype(jnp.float32) * s[..., None, :, None]).astype(dtype)


def extract_pages(store, page_ids, num_kv_heads: int):
    """Gather whole pages of EVERY layer for the host/wire boundary (KVBM
    offload, KV transfer, disagg): `[L, n, rows, KH, D]` (a QuantKV of q
    in that shape + s `[L, n, KH]`) — the page layout every tier and
    payload has always carried; the bytes of a page are the same bytes in
    the same order as in the lane-dense pool."""
    def heads(a):
        return a.reshape(*a.shape[:3], num_kv_heads, -1)

    if not isinstance(store, QuantKV):
        return heads(store[:, page_ids])
    return QuantKV(
        heads(store.q[:, page_ids]), store.s[:, page_ids],
        store.bits, store.page_size,
    )


def inject_pages(store, page_ids, data):
    """Inverse of extract_pages: scatter `[L, n, rows, KH, D]` pages (or
    rows already lane-dense) into the pool in place."""
    def lanes(d):
        return d.reshape(*d.shape[:3], -1)

    if not isinstance(store, QuantKV):
        return store.at[:, page_ids].set(lanes(data))
    return QuantKV(
        store.q.at[:, page_ids].set(lanes(data.q)),
        store.s.at[:, page_ids].set(data.s),
        store.bits, store.page_size,
    )


# ---------------------------------------------------------------------- #
# host/wire packing (KVBM tiers, peer pulls, disagg payloads)
# ---------------------------------------------------------------------- #


def host_pack_pages(x) -> np.ndarray:
    """Device->host for extracted pages in the `[L, n, ...]` layout:
    fp -> np.asarray (unchanged seed behavior); QuantKV -> one uint8 row
    per (layer, page): q bytes ‖ f32 scale bytes, shape [L, n, PB]."""
    if not isinstance(x, QuantKV):
        return np.asarray(x)
    q = np.asarray(x.q)  # [L, n, ps_eff, KH, D] int8
    s = np.ascontiguousarray(np.asarray(x.s, dtype=np.float32))  # [L, n, KH]
    L, n = q.shape[0], q.shape[1]
    qb = np.ascontiguousarray(q).view(np.uint8).reshape(L, n, -1)
    sb = s.view(np.uint8).reshape(L, n, -1)
    return np.concatenate([qb, sb], axis=-1)


def host_unpack_pages(arr: np.ndarray, mode: str, page_size: int,
                      num_kv_heads: int, head_dim: int
                      ) -> Tuple[np.ndarray, np.ndarray]:
    """Inverse of host_pack_pages for a packed [..., PB] uint8 array:
    returns (q [..., ps_eff, KH, D] int8, s [..., KH] f32)."""
    bits = kv_quant_bits(mode)
    ps_eff = _ps_eff(page_size, bits)
    qbytes = ps_eff * num_kv_heads * head_dim
    lead = arr.shape[:-1]
    if arr.shape[-1] != qbytes + 4 * num_kv_heads:
        raise ValueError(
            f"packed KV page has {arr.shape[-1]} bytes; {mode} layout "
            f"expects {qbytes + 4 * num_kv_heads}"
        )
    q = (
        np.ascontiguousarray(arr[..., :qbytes])
        .view(np.int8)
        .reshape(*lead, ps_eff, num_kv_heads, head_dim)
    )
    s = (
        np.ascontiguousarray(arr[..., qbytes:])
        .view(np.float32)
        .reshape(*lead, num_kv_heads)
    )
    return q, s


def device_pages(arr, mode: str, page_size: int, num_kv_heads: int,
                 head_dim: int):
    """Host payload -> inject operand: fp passthrough (jnp.asarray at the
    call site keeps seed behavior), packed uint8 -> a QuantKV of device
    arrays in the same [L, n, ...] layout extract produced."""
    bits = kv_quant_bits(mode)
    if bits == 0:
        return jnp.asarray(arr)
    q, s = host_unpack_pages(
        np.asarray(arr), mode, page_size, num_kv_heads, head_dim
    )
    return QuantKV(jnp.asarray(q), jnp.asarray(s), bits, page_size)
