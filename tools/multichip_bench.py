"""Multichip scaling bench: N-device mesh vs 1-device mesh on the SAME
holder (ISSUE 16 acceptance).

Measures Intersect+Count and BSI-Sum collective QPS on the full local
mesh against a mesh restricted to one device, asserts the device
answers bit-exact against the host fold (Count, TopN row counts, BSI
Sum), drives a read/topn/bsi mix through an Executor on the
multi-device mesh and checks the locality-tier ledger (every
collective records tier="ici", nothing records tier="http" — there is
no ring here to fall back to), then writes its JSON artifact, which
names the backend it ran on.

The ">= 4x single-device QPS" acceptance is ENFORCED only where the
parallel capacity physically exists: a TPU backend, or a CPU host with
at least as many cores as forced devices. On a small CPU box the N
forced host devices time-share the same cores, so the measured speedup
is recorded (with "enforced": false) but does not fail the run.

Standalone for CI (forces an 8-device CPU mesh when JAX_PLATFORMS does
not name an accelerator); bench.py calls main() in its own process,
because a process that holds the chips must not start a child that
needs them:

    python tools/multichip_bench.py --out MULTICHIP.json
"""

import argparse
import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def _force_devices(n: int) -> None:
    """Force an n-device CPU mesh BEFORE jax import, unless the
    environment already provides devices (a real TPU, an outer
    harness that set XLA_FLAGS itself, or a caller that has already
    imported jax and so chosen them)."""
    if "jax" in sys.modules:
        return
    flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" in flags:
        return
    if os.environ.get("JAX_PLATFORMS", "cpu") not in ("", "cpu"):
        return  # accelerator requested: use its real device count
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (
        flags + f" --xla_force_host_platform_device_count={n}").strip()


def _timed_qps(fn, iters: int) -> float:
    fn()  # warm: stage + compile outside the window
    t0 = time.monotonic()
    for _ in range(iters):
        fn()
    return iters / max(time.monotonic() - t0, 1e-9)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default="MULTICHIP.json")
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--slices", type=int, default=16)
    ap.add_argument("--containers", type=int, default=8,
                    help="containers per slice per row (dense pool "
                         "work is ~containers * 8 KiB per row)")
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--bsi-cols", type=int, default=128,
                    help="BSI values per slice")
    ap.add_argument("--min-speedup", type=float, default=4.0)
    args = ap.parse_args(argv)

    _force_devices(args.devices)
    # The scaling sections time the DENSE collective path (full-pool
    # popcount work, sharded on the slice axis); the sparse format
    # pick is covered by the format-agreement tests, not timed here.
    os.environ.setdefault("PILOSA_TPU_SPARSE_DENSITY_THRESHOLD", "0")

    import tempfile

    import numpy as np

    import jax
    from pilosa_tpu import SLICE_WIDTH, jaxrt
    from pilosa_tpu.bsi import FieldSchema
    from pilosa_tpu.core import Holder
    from pilosa_tpu.executor import Executor
    from pilosa_tpu.parallel.mesh import default_mesh
    from pilosa_tpu.parallel.plan import _lower_tree
    from pilosa_tpu.parallel.serve import MeshManager
    from pilosa_tpu.pql import parse_string

    jaxrt.setup_compile_cache()
    n_dev = len(jax.devices())
    if n_dev < 2:
        # Single-device environment (a lone accelerator the forced-CPU
        # path didn't apply to): there is no scaling to measure.
        tail = f"multichip_bench: skipped, {n_dev} device(s)\n"
        with open(args.out, "w") as fp:
            json.dump({"n_devices": n_dev, "rc": 0, "ok": True,
                       "skipped": True, "tail": tail}, fp, indent=2)
            fp.write("\n")
        print(tail, end="")
        return 0
    failures = []

    with tempfile.TemporaryDirectory() as tmp:
        h = Holder(tmp)
        idx = h.create_index_if_not_exists("i")
        f = idx.create_frame_if_not_exists("f")

        # Rows 0/1: --containers containers per slice, each seeded with
        # 128 coprime-strided bits. The strides are coprime to 2^16 so
        # bits never collide within a container, and the two rows
        # overlap partially — the Intersect has real survivors.
        per = 128
        rows_l, cols_l = [], []
        for s in range(args.slices):
            for c in range(args.containers):
                base = s * SLICE_WIDTH + c * (1 << 16)
                for row, stride in ((0, 511), (1, 257)):
                    bits = base + (np.arange(per, dtype=np.uint64)
                                   * stride) % (1 << 16)
                    rows_l.append(np.full(per, row, dtype=np.uint64))
                    cols_l.append(bits)
        f.import_bits(np.concatenate(rows_l), np.concatenate(cols_l))

        # BSI field: deterministic values spread across containers,
        # signs and plane boundaries included via the modular sweep.
        f.create_field_if_not_exists(FieldSchema("val", -4000, 4000))
        oracle_sum, oracle_cnt = 0, 0
        for s in range(args.slices):
            for k in range(args.bsi_cols):
                v = ((s * args.bsi_cols + k) * 37) % 8001 - 4000
                f.set_value("val", s * SLICE_WIDTH + k * 131, v)
                oracle_sum += v
                oracle_cnt += 1

        slices = list(range(args.slices))
        num = args.slices
        host = Executor(h, use_device=False)

        def q(ex, pql):
            return ex.execute("i", parse_string(pql), None, None)

        count_pql = ('Count(Intersect(Bitmap(frame="f", rowID=0), '
                     'Bitmap(frame="f", rowID=1)))')
        tree = parse_string(count_pql).calls[0].children[0]
        leaves = []
        shape = _lower_tree(h, "i", tree, leaves)
        assert shape is not None

        want_count = q(host, count_pql)[0]
        want_top = {int(r): int(c)
                    for r, c in q(host, 'TopN(frame="f")')[0]}
        want_sum = q(host, 'Sum(frame="f", field="val")')[0]
        assert want_sum == {"value": oracle_sum, "count": oracle_cnt}, \
            (want_sum, oracle_sum, oracle_cnt)

        scaling = {}
        for name, mesh_n in (("1dev", 1), (f"{n_dev}dev", None)):
            mgr = MeshManager(h, mesh=default_mesh(mesh_n))
            got = mgr.count("i", shape, leaves, slices, num)
            if got != want_count:
                failures.append(f"count[{name}]: {got} != {want_count}")
            out = mgr.row_counts("i", "f", "standard", slices, num)
            if out is None:
                failures.append(f"row_counts[{name}]: fell back")
            else:
                rids, cnts = out
                got_top = {int(r): int(c) for r, c in zip(rids, cnts)
                           if int(c)}
                if got_top != want_top:
                    failures.append(
                        f"topn[{name}]: {got_top} != {want_top}")
            ex = Executor(h, use_device=True, device_min_work=0)
            ex._mesh_mgr = mgr
            got_sum = q(ex, 'Sum(frame="f", field="val")')[0]
            if got_sum != want_sum:
                failures.append(f"sum[{name}]: {got_sum} != {want_sum}")

            qps_count = _timed_qps(
                lambda: mgr.count("i", shape, leaves, slices, num),
                args.iters)
            def bsi_once(mgr=mgr):
                # Drop the completed-result memo so every iteration
                # executes the full masked-popcount collective instead
                # of replaying the first answer (the memo is the thing
                # a production workload of DISTINCT queries never hits).
                with mgr._mu:
                    mgr._topn_memo.clear()
                return mgr.bsi_plane_counts("i", "f", "bsi.val",
                                            slices, num)

            qps_bsi = _timed_qps(bsi_once, args.iters)
            scaling[name] = {"devices": mesh_n or n_dev,
                             "intersect_count_qps": round(qps_count, 2),
                             "bsi_sum_qps": round(qps_bsi, 2)}
            if mesh_n is None:
                tier_ex = ex  # keep the multi-device executor

        speedup = {
            k: round(scaling[f"{n_dev}dev"][f"{k}_qps"]
                     / max(scaling["1dev"][f"{k}_qps"], 1e-9), 3)
            for k in ("intersect_count", "bsi_sum")}
        efficiency = {k: round(v / n_dev, 3) for k, v in speedup.items()}

        # Tier acceptance: a read/topn/bsi mix on the multi-device mesh
        # must serve entirely from local collectives — `ici` grows,
        # `http` stays flat at zero (there is no ring to leak to).
        for _ in range(3):
            q(tier_ex, count_pql)
            q(tier_ex, 'TopN(frame="f")')
            q(tier_ex, 'Sum(frame="f", field="val")')
        tiers = {}
        for k, v in dict(tier_ex.tier_stats.copy()).items():
            tier = k.partition("|")[2] or "local"
            tiers[tier] = tiers.get(tier, 0) + int(v)
        if tiers.get("http"):
            failures.append(f"http tier leaked: {tiers}")
        if n_dev > 1 and not tiers.get("ici"):
            failures.append(f"no ici-tier queries recorded: {tiers}")

    cores = os.cpu_count() or 1
    enforced = (jax.default_backend() != "cpu") or cores >= n_dev
    accept = {"required": args.min_speedup,
              "measured": speedup["intersect_count"],
              "enforced": enforced,
              "pass": speedup["intersect_count"] >= args.min_speedup}
    if enforced and not accept["pass"]:
        failures.append(
            f"speedup {accept['measured']}x < {args.min_speedup}x "
            f"on {n_dev} devices")

    tail = (f"multichip_bench: {n_dev} devices, "
            f"count speedup {speedup['intersect_count']}x "
            f"(eff {efficiency['intersect_count']}), "
            f"bsi speedup {speedup['bsi_sum']}x, tiers {tiers}"
            + (f", FAIL: {failures}" if failures else ", ok"))
    report = {
        "n_devices": n_dev,
        "rc": 1 if failures else 0,
        "ok": not failures,
        "skipped": False,
        "backend": jax.default_backend(),
        "cores": cores,
        "scaling": scaling,
        "speedup": speedup,
        "efficiency": efficiency,
        "accept_4x": accept,
        "bit_exact": {"count": want_count, "topn_rows": len(want_top),
                      "bsi_sum": want_sum},
        "tiers": tiers,
        "failures": failures,
        "tail": tail + "\n",
    }
    with open(args.out, "w") as fp:
        json.dump(report, fp, indent=2)
        fp.write("\n")
    print(tail)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
