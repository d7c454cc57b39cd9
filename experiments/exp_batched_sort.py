"""Round-2 experiment: can a bucketize + batched-sort discipline beat the
flat-sort merge count (VERDICT #1)?

Measures, on the real chip:
  1. flat lax.sort at 33.5M uint32 (round-1 figure: 51.9 ms)
  2. batched sort at several row lengths (round-1: [4096, 8192] = 25.0 ms)
  3. multi-operand sort cost (the bucketize permutation carrier)
  4. the hypothetical best case: probe_count_bucketized_merge on
     pre-bucketized rows (what we'd get if bucketization were free)
  5. end-to-end merge_count_chunks (round-1 bench: ~48 ms/iter)

Methodology: amortized async dispatches closed by one host readback
(bench.py).
"""
import time

import jax
import jax.numpy as jnp
import numpy as np


def timeit(fn, *args, iters=10):
    out = fn(*args)           # warm/compile
    np.asarray(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    np.asarray(out)
    return (time.perf_counter() - t0) / iters


def main():
    n = 1 << 25               # 33.5M — the merge-count union size for 16M x 16M
    rng = np.random.default_rng(0)
    keys = rng.integers(0, 1 << 31, size=n, dtype=np.uint32)
    x = jax.device_put(jnp.asarray(keys))
    jax.block_until_ready(x)

    sort1 = jax.jit(lambda a: jax.lax.sort((a,), is_stable=False)[0])
    print(f"flat sort {n}: {timeit(sort1, x)*1e3:.1f} ms")

    for rows in (64, 512, 4096, 8192, 16384, 32768):
        cols = n // rows
        xb = x.reshape(rows, cols)
        sortb = jax.jit(lambda a: jax.lax.sort((a,), dimension=1,
                                               is_stable=False)[0])
        print(f"batched sort [{rows}, {cols}]: {timeit(sortb, xb)*1e3:.1f} ms")

    # multi-operand flat sort: 1 key + k carried lanes
    v = jax.device_put(jnp.arange(n, dtype=jnp.uint32))
    sort2 = jax.jit(lambda a, b: jax.lax.sort((a, b), is_stable=False)[1])
    print(f"flat sort kv (2 lanes): {timeit(sort2, x, v)*1e3:.1f} ms")
    sort3 = jax.jit(lambda a, b, c: jax.lax.sort((a, b, c), is_stable=False)[1])
    print(f"flat sort kvv (3 lanes): {timeit(sort3, x, v, v)*1e3:.1f} ms")

    # batched 2-key lexicographic sort (the bucketized probe's inner op)
    for rows in (2048, 4096):
        cols = n // rows
        xb = x.reshape(rows, cols)
        tb = v.reshape(rows, cols)
        sortlex = jax.jit(lambda a, b: jax.lax.sort(
            (a, b), dimension=1, is_stable=False, num_keys=2)[0])
        print(f"batched 2-key sort [{rows}, {cols}]: "
              f"{timeit(sortlex, xb, tb)*1e3:.1f} ms")

    # hypothetical best case: rows pre-bucketized, count via batched sort-merge
    from tpu_radix_join.ops.build_probe import probe_count_bucketized_merge
    nb = 2048
    cap = (1 << 24) // nb * 2          # 2x slack per bucket row
    rk = rng.integers(0, 1 << 31, size=(nb, cap), dtype=np.uint32)
    sk = rng.integers(0, 1 << 31, size=(nb, cap), dtype=np.uint32)
    rb = jax.device_put(jnp.asarray(rk))
    sb = jax.device_put(jnp.asarray(sk))
    pc = jax.jit(probe_count_bucketized_merge)
    print(f"bucketized merge-count [{nb}, {cap}] x2 (pre-bucketized): "
          f"{timeit(pc, rb, sb)*1e3:.1f} ms")

    # end-to-end current champion
    from tpu_radix_join.ops.merge_count import merge_count_chunks
    half = n // 2
    r = x[:half]
    s = x[half:]
    mc = jax.jit(merge_count_chunks)
    print(f"merge_count_chunks 16M x 16M: {timeit(mc, r, s)*1e3:.1f} ms")


if __name__ == "__main__":
    main()
