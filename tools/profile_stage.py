"""Where does cold-start staging time go? (VERDICT r3 item 5)

Breaks the 1 GB holder stage into its parts on the real chip:
  pack_s        — host-side numpy packing (build_sharded_index loop)
  put_whole_s   — one synchronous device_put of the packed pool
  put_chunk_s   — K chunked device_puts + one on-device concatenate
  put_overlap_s — chunked device_puts where chunk i+1 PACKS while
                  chunk i transfers (the pipeline build_sharded_index
                  can adopt)
Writes PROFILE_STAGE.json. Run alone (single-lease chip).
"""

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    import jax
    import jax.numpy as jnp

    out = {"backend": jax.default_backend()}
    num_slices = int(os.environ.get("PROFILE_SLICES", "960"))
    rows = 8
    cap = rows * 16
    rng = np.random.default_rng(7)

    # The holder's per-slice roaring containers, as the staging loop
    # sees them: one (cap, 1024) u64 words view per slice.
    per_slice = [rng.integers(0, 2**64, size=(cap, 1024), dtype=np.uint64)
                 for _ in range(num_slices)]

    # -- pack: the build_sharded_index host loop shape ----------------------
    t0 = time.perf_counter()
    words = np.zeros((num_slices, cap, 2048), dtype=np.uint32)
    for si in range(num_slices):
        for j in range(cap):
            words[si, j] = per_slice[si][j].view(np.uint32)
    out["pack_loop_s"] = time.perf_counter() - t0

    # vectorized pack (whole-slice view copy, no per-container loop)
    t0 = time.perf_counter()
    words2 = np.zeros_like(words)
    for si in range(num_slices):
        words2[si] = per_slice[si].view(np.uint32).reshape(cap, 2048)
    out["pack_slicewise_s"] = time.perf_counter() - t0
    assert np.array_equal(words, words2)
    del words2
    nbytes = words.nbytes
    out["pool_bytes"] = int(nbytes)

    # -- whole-pool device_put ----------------------------------------------
    t0 = time.perf_counter()
    dev = jax.device_put(words)
    dev.block_until_ready()
    out["put_whole_s"] = time.perf_counter() - t0
    out["put_whole_gbps"] = nbytes / 1e9 / out["put_whole_s"]
    del dev

    # -- chunked device_put + device concat ---------------------------------
    for k in (4, 16):
        t0 = time.perf_counter()
        chunks = np.array_split(words, k, axis=0)
        devs = [jax.device_put(c) for c in chunks]
        whole = jnp.concatenate(devs, axis=0)
        whole.block_until_ready()
        dt = time.perf_counter() - t0
        out[f"put_chunk{k}_s"] = dt
        out[f"put_chunk{k}_gbps"] = nbytes / 1e9 / dt
        del devs, whole

    # -- overlapped pack+put pipeline ---------------------------------------
    # Pack chunk i+1 on host while chunk i's transfer is in flight
    # (device_put returns before completion; the final block waits all).
    k = 16
    bounds = np.linspace(0, num_slices, k + 1, dtype=int)
    t0 = time.perf_counter()
    devs = []
    for i in range(k):
        lo, hi = bounds[i], bounds[i + 1]
        chunk = np.zeros((hi - lo, cap, 2048), dtype=np.uint32)
        for si in range(lo, hi):
            chunk[si - lo] = per_slice[si].view(np.uint32).reshape(cap, 2048)
        devs.append(jax.device_put(chunk))
    whole = jnp.concatenate(devs, axis=0)
    whole.block_until_ready()
    dt = time.perf_counter() - t0
    out["put_overlap16_s"] = dt
    out["put_overlap16_gbps"] = nbytes / 1e9 / dt
    del devs, whole

    # -- fold assembly (the shipped path): donated dynamic_update_slice ------
    # Peak HBM = shard + one chunk, vs concat's 2x pool; is the fold's
    # per-chunk dispatch+copy cost acceptable?
    from pilosa_tpu.parallel.mesh import _assemble_shard

    t0 = time.perf_counter()
    devs, offs = [], []
    for i in range(k):
        lo, hi = bounds[i], bounds[i + 1]
        chunk = np.zeros((hi - lo, cap, 2048), dtype=np.uint32)
        for si in range(lo, hi):
            chunk[si - lo] = per_slice[si].view(np.uint32).reshape(cap, 2048)
        devs.append(jax.device_put(chunk))
        offs.append(int(lo))
    whole = _assemble_shard(devs, offs, (num_slices, cap, 2048), None)
    whole.block_until_ready()
    dt = time.perf_counter() - t0
    out["put_fold16_s"] = dt
    out["put_fold16_gbps"] = nbytes / 1e9 / dt
    del devs, whole

    # -- dtype/bit-packing lever: does u64->u32 view matter? ----------------
    # (Transfers are bytes; this checks the put isn't dtype-sensitive.)
    sub = words[: max(1, num_slices // 8)]
    t0 = time.perf_counter()
    d = jax.device_put(sub.view(np.uint64))
    d.block_until_ready()
    out["put_u64_sub_gbps"] = sub.nbytes / 1e9 / (time.perf_counter() - t0)
    del d

    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "PROFILE_STAGE.json"), "w") as f:
        json.dump({k: (round(v, 4) if isinstance(v, float) else v)
                   for k, v in out.items()}, f, indent=2)
        f.write("\n")
    print(json.dumps(out))


if __name__ == "__main__":
    main()
