"""Chip micro-benchmark for the LCC kernel's design choices (PR 46): the
rates of the primitives the candidate designs lean on. One process, one
chip; prints one JSON line a measurement."""
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


def timed(name, fn, *args, reps=3, **note):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    s = (time.perf_counter() - t0) / reps
    print(json.dumps(dict(name=name, seconds=s, **{k: (v / s if k.endswith("_per_s") else v)
                                                   for k, v in note.items()})), flush=True)
    return out


key = jax.random.PRNGKey(0)
print(json.dumps({"device": str(jax.devices()[0].device_kind)}), flush=True)

# 1. element gather from a 2 GB bitmap of uint32 words, + bit test
words = jnp.zeros((1 << 29,), jnp.uint32)
n = 1 << 26
idx = jax.random.randint(key, (n,), 0, 1 << 29, jnp.int32)


@jax.jit
def bit_lookup(words, idx):
    w = words[idx]
    return ((w >> (idx & 31).astype(jnp.uint32)) & 1).astype(jnp.int32).sum()


timed("element_gather_2GB", bit_lookup, words, idx, idx_per_s=n)
idx_sorted = jnp.sort(idx)
timed("element_gather_2GB_sorted_idx", bit_lookup, words, idx_sorted, idx_per_s=n)
del words

# 1b. from a small table (64 M int32: the oriented col array)
col = jnp.arange(1 << 26, dtype=jnp.int32)
idx2 = jax.random.randint(key, (n,), 0, 1 << 26, jnp.int32)
timed("element_gather_256MB", jax.jit(lambda c, i: c[i].sum()), col, idx2, idx_per_s=n)


# 1c. 8-step binary search over col with random ranges
@jax.jit
def bsearch(col, lo, want):
    hi = lo + 200

    def body(_, s):
        lo, hi = s
        mid = (lo + hi) // 2
        val = col[mid]
        right = val < want
        return jnp.where(right, mid + 1, lo), jnp.where(right, hi, mid)

    lo, hi = lax.fori_loop(0, 8, body, (lo, hi))
    return (col[lo] == want).sum()


lo0 = jax.random.randint(key, (n,), 0, (1 << 26) - 256, jnp.int32)
timed("bsearch8_256MB", bsearch, col, lo0, lo0 + 77, pairs_per_s=n)
del col

# 2. row gather of bit rows + AND + popcount
for rows, wordsper in ((131072, 4096), (65536, 2048)):
    table = jnp.ones((rows, wordsper), jnp.uint32)
    for nb, w in ((4096, 16), (512, 128)):
        ridx = jax.random.randint(key, (nb, w), 0, rows, jnp.int32)
        b = jnp.ones((nb, wordsper), jnp.uint32)

        @jax.jit
        def rows_and_pop(table, ridx, b):
            g = table[ridx]  # [nb, w, words]
            return lax.population_count(g & b[:, None, :]).astype(jnp.int32).sum(-1)

        timed(f"bitrow_gather_{rows}x{wordsper}_nb{nb}_w{w}", rows_and_pop, table, ridx, b,
              rows_per_s=nb * w, bytes_per_s=nb * w * wordsper * 4)

        @jax.jit
        def rows_and_pop_loop(table, ridx, b):
            def body(j, acc):
                g = table[ridx[:, j]]  # [nb, words]
                c = lax.population_count(g & b).astype(jnp.int32).sum(-1)
                return acc.at[:, j].set(c)
            return lax.fori_loop(0, ridx.shape[1], body, jnp.zeros(ridx.shape, jnp.int32))

        timed(f"bitrow_gather_loop_{rows}x{wordsper}_nb{nb}_w{w}", rows_and_pop_loop,
              table, ridx, b, rows_per_s=nb * w, bytes_per_s=nb * w * wordsper * 4)
    del table

# 3. matmul 8192^3, int8 -> int32 and bf16 -> f32, with mask + row/col sums
B = 8192
for dt, acc in ((jnp.int8, jnp.int32), (jnp.bfloat16, jnp.float32)):
    a = jnp.ones((B, B), dt)

    @jax.jit
    def mm(a, b, m):
        p = lax.dot(a, b, preferred_element_type=acc)
        p = p * m.astype(acc)
        return p.sum(0), p.sum(1)

    timed(f"matmul_{B}_{jnp.dtype(dt).name}", mm, a, a, a, flop_per_s=2 * B ** 3)

# 3b. unpack bits -> int8 block
bits = jnp.ones((B, B // 32), jnp.uint32)


@jax.jit
def unpack(bits):
    sh = jnp.arange(32, dtype=jnp.uint32)
    return ((bits[:, :, None] >> sh) & 1).astype(jnp.int8).reshape(bits.shape[0], -1)


timed("unpack_8192x8192", unpack, bits, bytes_per_s=B * B)

# 4. scatter-add / segment-sum
vals = jnp.ones((n,), jnp.int32)
seg = jax.random.randint(key, (n,), 0, 1 << 22, jnp.int32)
timed("segment_sum_64M_into_4M", jax.jit(lambda v, s: jax.ops.segment_sum(v, s, num_segments=1 << 22)),
      vals, seg, idx_per_s=n)

# 4b. 2-D scatter of ones into a dense int8 block [65536, 65536]
K = 65536
m = 1 << 24
ru = jax.random.randint(key, (m,), 0, K, jnp.int32)
rv = jax.random.randint(jax.random.PRNGKey(1), (m,), 0, K, jnp.int32)
timed("scatter_dense_int8_65536", jax.jit(lambda u, v: jnp.zeros((K, K), jnp.int8).at[u, v].set(1).sum(dtype=jnp.int32)),
      ru, rv, reps=1, idx_per_s=m)

# 5. pair forming + all-pairs equality compare (rows of width 64 vs 64)
nb = 1 << 16
ra = jax.random.randint(key, (nb, 64), 0, 1 << 22, jnp.int32)
rb = jax.random.randint(jax.random.PRNGKey(2), (nb, 64), 0, 1 << 22, jnp.int32)
timed("allpairs_eq_64x64", jax.jit(lambda a, b: (a[:, :, None] == b[:, None, :]).sum(-1, dtype=jnp.int32)),
      ra, rb, compares_per_s=nb * 64 * 64)
