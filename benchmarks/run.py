#!/usr/bin/env python3
"""The benchmark's one command:

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One run = one new process tree. This parent stays off JAX: it makes the
cell's data from --seed, starts the server child as a user does (which alone
holds the chip), warms the cell's own shapes, measures one drained window,
kills the server, compares every answer with the numpy reference, looks for
every acknowledged write on the disk, and prints the result as the last line
of its standard output. With no TPU, or fewer
chips than the cell asks for, it exits 2 and prints no result.

The cell, its configuration, its traffic and its per-layer metrics are data
files found by the names in BENCHMARK.json; see PERF.md.
"""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, HERE)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", default=None,
                    help="the reference in the program's place with one "
                         "guarantee broken (pbench/control.py), or the "
                         "program with its unsafe WAL path on (lost_wal); "
                         "not a result")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at --slices: finds faults, exits 3, "
                         "never a result")
    ap.add_argument("--slices", type=int, default=None)
    ap.add_argument("--server-env", action="append", default=[],
                    metavar="KEY=VALUE",
                    help="extra environment of the server child, for "
                         "experiments (a fault seam, a pin); not a result "
                         "the driver ever asks for")
    args = ap.parse_args()

    if not os.path.isdir(os.path.join(os.path.dirname(HERE), "pilosa_tpu")):
        sys.stderr.write("benchmarks/run.py: no pilosa_tpu package beside "
                         "benchmarks/: there is no system under test\n")
        return 2
    from pbench import harness

    env = dict(kv.split("=", 1) for kv in args.server_env)
    if args.rehearse:
        env.update({"JAX_PLATFORMS": "cpu", "PILOSA_TPU_DEVICE_MIN_WORK": "0",
                    "PILOSA_TPU_CPU_ROUTE_NATIVE": "off"})
    try:
        result = harness.run_cell(
            args.workload, args.seed, args.seconds, bool(args.trace),
            t_start=T_START, require_chip=not args.rehearse,
            slices=args.slices, control=args.control, server_env=env)
    except harness.NoChip as e:
        sys.stderr.write(f"benchmarks/run.py: {e}\n")
        return 2
    if args.rehearse:
        sys.stderr.write("rehearsal (not a result): "
                         + json.dumps(result) + "\n")
        return 3 if result["correct"] else 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
