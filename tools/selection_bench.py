"""The pieces of a learned selection alone, at the GLM-5.2 cell's shapes, on
the chip (PERF.md section 5, PR 58, has the readings): exact top-k of index
scores three ways, the row gather of the picked latent rows three ways, the
index scoring, and a full layer's selection and attention against the
standing absorbed walk over the whole context.

    python3 tools/selection_bench.py        (through the chip tool; one JSON line a piece)

A builder's tool: it times, it judges nothing.
"""
import json, os, sys, time
import jax, jax.numpy as jnp
import numpy as np
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from dynamo_tpu.ops.latent_attention import absorbed_attention

f32 = jnp.float32
def bench(name, fn, *args, n=20):
    out = fn(*args); jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    ms = (time.perf_counter() - t0) / n * 1e3
    print(json.dumps({"what": name, "ms": round(ms, 4)}), flush=True)
    return out

print(json.dumps({"device": str(jax.devices()[0])}), flush=True)
key = jax.random.PRNGKey(0)
for B, S in ((32, 16448), (32, 20544), (13, 20544), (256, 20544)):
    x = jax.random.normal(key, (B, S), f32)
    bench(f"top_k[{B},{S}]->2048", jax.jit(lambda x: jax.lax.top_k(x, 2048)), x)
    bench(f"sort_key_val[{B},{S}]", jax.jit(lambda x: jax.lax.sort_key_val(
        -x, jnp.broadcast_to(jnp.arange(x.shape[1], dtype=jnp.int32), x.shape))[1][:, :2048]), x)
    bench(f"approx_max_k_r1[{B},{S}]", jax.jit(lambda x: jax.lax.approx_max_k(x, 2048, recall_target=0.95)), x)

def kth_bits(x, k):
    """exact k-th largest of each row by bisection on the ordered bit pattern"""
    u = jax.lax.bitcast_convert_type(x, jnp.uint32)
    u = jnp.where(u >> 31 == 1, ~u, u | jnp.uint32(1 << 31))  # order-preserving
    def body(i, lo):
        bit = jnp.uint32(1) << (31 - i).astype(jnp.uint32)
        cand = lo | bit
        cnt = (u >= cand[:, None]).sum(-1)
        return jnp.where(cnt >= k, cand, lo)
    thr = jax.lax.fori_loop(0, 32, body, jnp.zeros((x.shape[0],), jnp.uint32))
    return u >= thr[:, None]
x = jax.random.normal(key, (32, 20544), f32)
m = bench("kth_bisect_mask[32,20544]", jax.jit(lambda x: kth_bits(x, 2048)), x)
print(json.dumps({"mask_counts": [int(v) for v in np.asarray(m.sum(-1))[:4]]}))
def compact(mask, k):
    pos = jnp.cumsum(mask, -1) - 1
    B, S = mask.shape
    tgt = jnp.where(mask, pos, k)
    return jnp.zeros((B, k + 1), jnp.int32).at[jnp.arange(B)[:, None], tgt].set(
        jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S)), mode="drop")[:, :k]
bench("bisect+scatter_compact[32,20544]", jax.jit(lambda x: compact(kth_bits(x, 2048), 2048)), x)

# the pool and the gathers
L, P, W = 7, 2400, 640
pool = jax.random.normal(key, (L, P, 64, W), jnp.bfloat16)
ipool = jax.random.normal(key, (2, P, 64, 128), jnp.bfloat16)
B, K, T = 32, 2048, 321
rng = np.random.default_rng(0)
tables = jnp.asarray(np.stack([rng.permutation(P - 1)[:T] + 1 for _ in range(B)]).astype(np.int32))
seq = jnp.full((B,), 16500, jnp.int32)
idx = jnp.asarray(np.stack([np.sort(rng.permutation(16500)[:K]) for _ in range(B)]).astype(np.int32))

def gather_rows(pool, li, tables, idx):
    page = jnp.take_along_axis(tables, idx // 64, axis=1)
    return pool[li, page, idx % 64]
bench("gather_rows[32,2048,640] 2-index", jax.jit(gather_rows), pool, 3, tables, idx)
def gather_flat(pool, li, tables, idx):
    page = jnp.take_along_axis(tables, idx // 64, axis=1)
    flat = pool.reshape(L, P * 64, W)
    return flat[li, page * 64 + idx % 64]
bench("gather_rows[32,2048,640] flat", jax.jit(gather_flat), pool, 3, tables, idx)
def gather_take(pool, li, tables, idx):
    page = jnp.take_along_axis(tables, idx // 64, axis=1)
    flat = pool.reshape(L * P * 64, W)
    return jnp.take(flat, (li * P + page) * 64 + idx % 64, axis=0)
bench("gather_rows[32,2048,640] take", jax.jit(gather_take), pool, 3, tables, idx)

H = 64
q = jax.random.normal(key, (B, H, 576), jnp.bfloat16)
def standing(q, pool, li, tables, seq):
    return absorbed_attention(q, (pool, li), tables, seq, 512, 1 / 16)
bench("standing absorbed walk B32 ctx16.5k", jax.jit(standing), q, pool, 3, tables, seq)
def selected(q, pool, li, tables, idx):
    rows = gather_rows(pool, li, tables, idx)
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, W - 576)))
    s = jnp.einsum("bhw,bsw->bhs", qp, rows, preferred_element_type=f32) / 16
    p = jax.nn.softmax(s, -1)
    return jnp.einsum("bhs,bsr->bhr", p.astype(rows.dtype), rows[..., :512], preferred_element_type=f32)
bench("selected gather+attend B32 K2048", jax.jit(selected), q, pool, 3, tables, idx)

qi = jax.random.normal(key, (B, 32, 128), jnp.bfloat16)
wi = jax.random.normal(key, (B, 32), f32)
def index_scores(qi, wi, ipool, fi, tables, seq, pb=16):
    Pp = -(-T // pb) * pb
    tb = jnp.pad(tables, ((0, 0), (0, Pp - T)))
    S = pb * 64
    def block(j, out):
        t = jax.lax.dynamic_slice_in_dim(tb, j * pb, pb, axis=1)
        keys = ipool[fi, t].reshape(B, S, 128)
        s = jnp.einsum("bjd,bsd->bjs", qi, keys, preferred_element_type=f32)
        s = jnp.einsum("bjs,bj->bs", jax.nn.relu(s), wi)
        return jax.lax.dynamic_update_slice_in_dim(out, s, j * S, axis=1)
    out = jax.lax.fori_loop(0, -(-jnp.max(seq) // S), block, jnp.full((B, Pp * 64), -jnp.inf, f32))
    pos = jnp.arange(Pp * 64)[None]
    return jnp.where(pos < seq[:, None], out, -jnp.inf)
sc = bench("index_scores B32 ctx16.5k", jax.jit(index_scores), qi, wi, ipool, 1, tables, seq)
def full_layer(q, qi, wi, pool, ipool, tables, seq):
    sc = index_scores(qi, wi, ipool, 1, tables, seq)
    _, idx = jax.lax.top_k(sc, K)
    return selected(q, pool, 3, tables, idx)
bench("full layer: scores+top_k+gather+attend", jax.jit(full_layer), q, qi, wi, pool, ipool, tables, seq)
