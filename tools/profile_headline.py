"""Profile the 1B-column Intersect+Count headline into components.

VERDICT r2 item 1(a): split the measured ~2.79 ms/query into
dispatch / gather / popcount-psum / readback, on the real chip, and
measure candidate restructurings before committing to one:

  noop         trivial jitted program over the same inputs — the pure
               dispatch floor
  stream       popcount the WHOLE pool with no gather — the HBM
               streaming ceiling for this shape (reads 1x pool bytes)
  current      compile_serve_count exactly as the serving path runs it
  gather_only  the two leaf gathers + u32 sum, no popcount fold —
               isolates gather cost from combine cost
  nomask       current minus the ownership-mask multiply
  noshard      current but plain jit, no shard_map/psum (1-device only)
  slab         contiguous dynamic-slice per leaf instead of flat gather
               (valid when a row's containers are contiguous in the
               pool — the dense-row common case; host checks idx)
  slab_scan    slab variant folded over slices with lax.scan to bound
               materialized intermediates
  batch16      the batch-16 program (amortized dispatch reference)

Usage: python tools/profile_headline.py [--slices N] [--iters N]
Writes PROFILE_HEADLINE.json and prints a table.
"""

import argparse
import json
import time

import numpy as np


def build_pool(num_slices, num_rows=2, seed=7):
    rng = np.random.default_rng(seed)
    cap = num_rows * 16
    keys = np.tile(np.arange(cap, dtype=np.int32), (num_slices, 1))
    words = rng.integers(0, 2**32, size=(num_slices, cap, 2048),
                         dtype=np.uint32)
    return keys, words


def sustained(fn, iters):
    out = fn()
    np.asarray(out)
    t0 = time.perf_counter()
    acc = None
    for _ in range(iters):
        o = fn()
        acc = o if acc is None else acc + o
    np.asarray(acc)
    return (time.perf_counter() - t0) / iters


def percall(fn, iters):
    import jax

    np.asarray(fn())
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(fn())
    return (time.perf_counter() - t0) / iters


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--slices", type=int, default=960)
    ap.add_argument("--iters", type=int, default=30)
    ap.add_argument("--reps", type=int, default=3)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from pilosa_tpu.parallel.mesh import (
        SLICE_AXIS, ShardedIndex, compile_serve_count,
        compile_serve_count_batch, resolve_row_indices)

    S = args.slices
    keys_host, words_host = build_pool(S)
    mesh = Mesh(np.array(jax.devices()[:1]), (SLICE_AXIS,))
    sh = NamedSharding(mesh, P(SLICE_AXIS))
    words = jax.device_put(words_host, sh)
    mask = jax.device_put(np.ones(S, dtype=np.int32), sh)

    idx0, hit0 = resolve_row_indices(keys_host, 0)
    idx1, hit1 = resolve_row_indices(keys_host, 1)
    d = lambda a: jax.device_put(a, sh)
    idx_t = (d(idx0), d(idx1))
    hit_t = (d(hit0), d(hit1))
    words_t = (words, words)
    tree = ["and", ["leaf", 0], ["leaf", 1]]

    results = {}

    def run(name, fn, iters=None):
        it = iters or args.iters
        best_s = min(sustained(fn, it) for _ in range(args.reps))
        best_p = min(percall(fn, max(2, it // 3)) for _ in range(args.reps))
        results[name] = {"sustained_ms": best_s * 1e3,
                         "percall_ms": best_p * 1e3}
        print(f"{name:14s} sustained {best_s*1e3:8.3f} ms   "
              f"percall {best_p*1e3:8.3f} ms", flush=True)

    # -- dispatch floor
    @jax.jit
    def noop(m):
        return jnp.stack([m.sum(), m.sum()])

    run("noop", lambda: noop(mask))

    # -- HBM streaming ceiling: popcount whole pool, no gather
    @jax.jit
    def stream(w, m):
        pc = lax.population_count(w).sum(axis=(1, 2), dtype=jnp.uint32)
        pc = jnp.where(m != 0, pc, jnp.uint32(0))
        lo = (pc & jnp.uint32(0xFFFF)).astype(jnp.int32).sum()
        hi = (pc >> 16).astype(jnp.int32).sum()
        return jnp.stack([lo, hi])

    run("stream", lambda: stream(words, mask))

    # -- the real serving program
    fn_cur = compile_serve_count(mesh, tree, 2)
    run("current", lambda: fn_cur(words_t, idx_t, hit_t, mask))

    # -- gather only (no popcount fold)
    @jax.jit
    def gather_only(w, i0, h0, i1, h1, m):
        cap = w.shape[1]
        wflat = w.reshape(w.shape[0] * cap, w.shape[2])
        base = (jnp.arange(w.shape[0], dtype=jnp.int32) * cap)[:, None]
        a = wflat[(i0 + base).reshape(-1)] * h0.reshape(-1)[:, None]
        b = wflat[(i1 + base).reshape(-1)] * h1.reshape(-1)[:, None]
        s = (a.sum(dtype=jnp.uint32) + b.sum(dtype=jnp.uint32))
        return jnp.stack([s.astype(jnp.int32), s.astype(jnp.int32)])

    run("gather_only",
        lambda: gather_only(words, idx_t[0], hit_t[0], idx_t[1], hit_t[1],
                            mask))

    # -- current without the shard_map wrapper (1-device)
    @jax.jit
    def noshard(w, i0, h0, i1, h1, m):
        cap = w.shape[1]
        wflat = w.reshape(w.shape[0] * cap, w.shape[2])
        base = (jnp.arange(w.shape[0], dtype=jnp.int32) * cap)[:, None]
        a = wflat[(i0 + base).reshape(-1)] * h0.reshape(-1)[:, None]
        b = wflat[(i1 + base).reshape(-1)] * h1.reshape(-1)[:, None]
        pc = lax.population_count(a & b)
        per = pc.sum(axis=1, dtype=jnp.uint32).reshape(w.shape[0], 16).sum(
            axis=1, dtype=jnp.uint32)
        per = jnp.where(m != 0, per, jnp.uint32(0))
        lo = (per & jnp.uint32(0xFFFF)).astype(jnp.int32).sum()
        hi = (per >> 16).astype(jnp.int32).sum()
        return jnp.stack([lo, hi])

    run("noshard",
        lambda: noshard(words, idx_t[0], hit_t[0], idx_t[1], hit_t[1], mask))

    # -- contiguous-slab variant: rows start at host-known offsets and
    # their 16 containers are contiguous (dense case) -> dynamic_slice
    starts = (np.full(S, 0, dtype=np.int32), np.full(S, 16, dtype=np.int32))
    st_t = tuple(jax.device_put(s, sh) for s in starts)

    @jax.jit
    def slab(w, s0, s1, m):
        def take(start):
            def one(wrow, st):
                return lax.dynamic_slice_in_dim(wrow, st, 16, axis=0)
            return jax.vmap(one)(w, start)          # (S, 16, 2048)

        a = take(s0)
        b = take(s1)
        pc = lax.population_count(a & b).sum(axis=(1, 2), dtype=jnp.uint32)
        pc = jnp.where(m != 0, pc, jnp.uint32(0))
        lo = (pc & jnp.uint32(0xFFFF)).astype(jnp.int32).sum()
        hi = (pc >> 16).astype(jnp.int32).sum()
        return jnp.stack([lo, hi])

    run("slab", lambda: slab(words, st_t[0], st_t[1], mask))

    # -- batch-16 (amortized dispatch reference)
    fnb = compile_serve_count_batch(mesh, tree, 2, 16)
    run("batch16",
        lambda: fnb(words_t, idx_t * 16, hit_t * 16, mask),
        iters=max(4, args.iters // 4))
    results["batch16"]["per_query_ms"] = (
        results["batch16"]["sustained_ms"] / 16)

    with open("PROFILE_HEADLINE.json", "w") as f:
        json.dump({k: {kk: round(vv, 4) for kk, vv in v.items()}
                   for k, v in results.items()}, f, indent=2)
        f.write("\n")
    print(json.dumps(results, indent=2))


if __name__ == "__main__":
    main()
