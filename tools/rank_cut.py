#!/usr/bin/env python3
"""rank_cut.py: where TopN's ranking of R rows is cheaper, on the device
or on the host.

Times the two forms MeshManager.top_n chooses between
(serve.RANK_DEVICE_MIN_ROWS) over a (2, R) row-count limb array that
lies on the device, as a row-count program leaves it:

- host: every limb read back, combine_limbs, rank_pairs (the form below
  the cut). `miss` reads a fresh array back; `hit` a memoised one, whose
  host copy jax keeps, so only combine_limbs and rank_pairs run;
- device: MeshManager._rank_on_device, phase 1 (n rows) and phase 2
  (the n ids phase 1 found), each a launch and k rows read back (what
  an earlier call read back is forgotten first).

Counts are Pareto-distributed, about 30% of the rows nonzero, as in a
src-form TopN over a sparse view. Each form's answer is checked against
the host form's. Prints one JSON object a row count, times in ms, the
median of --reps calls after a warm-up:

    python tools/rank_cut.py [--rows 256,4096,65536,1048576] [--n 5]

Runs on whatever device JAX finds: on the CPU its times are the CPU's.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))


def _median_ms(fn, reps: int) -> float:
    fn()  # warm-up: compiles
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        times.append((time.perf_counter() - t0) * 1e3)
    return statistics.median(times)


def measure(rows: int, n: int, reps: int, seed: int = 7) -> dict:
    import jax
    import jax.numpy as jnp

    from pilosa_tpu.parallel.mesh import default_mesh
    from pilosa_tpu.parallel.serve import MeshManager, rank_pairs

    rng = np.random.default_rng(seed)
    counts = np.where(rng.random(rows) < 0.3,
                      (rng.pareto(1.25, rows) + 1).astype(np.int64), 0)
    limbs_host = np.stack([counts & 0xFFFF, counts >> 16]).astype(np.int32)
    all_rows = np.arange(rows, dtype=np.uint64) * 3
    mgr = MeshManager(None, mesh=default_mesh(1))
    limbs = jax.device_put(limbs_host)
    fresh = jax.jit(lambda a, i: a + i * 0)

    def host_form(arr):
        r, c = mgr._host_counts(all_rows, arr)
        return rank_pairs(r, c, n, (), 1)

    def host_miss():
        arr = fresh(limbs, jnp.int32(0))
        arr.block_until_ready()
        return host_form(arr)

    def device_form(n_, ids):
        mgr._ranked.clear()  # what came back of `limbs`: launch afresh
        return mgr._rank_on_device(all_rows, limbs, n_, ids, 1)

    want = host_form(limbs)
    ids = [p[0] for p in want]
    assert device_form(n, ()) == want
    assert device_form(0, ids) == rank_pairs(all_rows, counts, 0, ids, 1)
    return {
        "rows": rows, "n": n, "device": str(jax.devices()[0].device_kind),
        "host_miss_ms": _median_ms(host_miss, reps),
        "host_hit_ms": _median_ms(lambda: host_form(limbs), reps),
        "device_phase1_ms": _median_ms(lambda: device_form(n, ()), reps),
        "device_phase2_ms": _median_ms(lambda: device_form(0, ids), reps),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--rows", default="256,4096,65536,1048576")
    ap.add_argument("--n", type=int, default=5)
    ap.add_argument("--reps", type=int, default=50)
    args = ap.parse_args(argv)
    for rows in (int(r) for r in args.rows.split(",")):
        print(json.dumps(measure(rows, args.n, args.reps)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
