"""Child process for the two-process jax.distributed test
(test_mesh.py::test_connect_distributed_two_process).

Each of two processes brings 2 local virtual CPU devices; after
connect_distributed the global mesh spans 4 devices across both
processes, and one compile_mesh_count psum must agree everywhere.
"""

import os
import sys


def main():
    pid, nprocs, port = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3]
    mode = sys.argv[4] if len(sys.argv) > 4 else "mesh"
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))  # repo root
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"

    import jax

    jax.config.update("jax_platforms", "cpu")

    from pilosa_tpu import SLICE_WIDTH
    from pilosa_tpu.parallel import (
        build_sharded_index,
        compile_mesh_count,
        connect_distributed,
        default_mesh,
    )
    from pilosa_tpu.roaring import Bitmap

    connect_distributed(f"127.0.0.1:{port}", nprocs, pid,
                        heartbeat_timeout_seconds=10
                        if mode == "spmd-die" else None)
    n_global = len(jax.devices())
    assert n_global == 4, n_global

    if mode == "spmd":
        return spmd_serving(pid)
    if mode == "spmd-die":
        return spmd_death(pid)

    mesh = default_mesh()
    bitmaps = []
    for s in range(4):
        b = Bitmap()
        b.add(0 * SLICE_WIDTH + s)
        b.add(1 * SLICE_WIDTH + s)
        bitmaps.append(b)
    index, row_ids = build_sharded_index(bitmaps, mesh)

    import numpy as np

    fn = compile_mesh_count(mesh, ["and", ["leaf"], ["leaf"]], 2)
    count = int(fn(index, np.int32([0, 1])))
    print(f"RESULT {pid} {count}", flush=True)


def spmd_serving(pid: int):
    """Replicated-data SPMD serving: each process owns an identical
    holder; rank 0 drives counts through parallel.spmd.SpmdServer,
    rank 1 follows broadcast descriptors."""
    import tempfile

    from pilosa_tpu import SLICE_WIDTH
    from pilosa_tpu.core import Holder
    from pilosa_tpu.parallel.plan import _lower_tree
    from pilosa_tpu.parallel.spmd import SpmdServer
    from pilosa_tpu.pql import parse_string

    holder = Holder(tempfile.mkdtemp(prefix=f"spmd{pid}_"))
    holder.open()
    idx = holder.create_index_if_not_exists("i")
    frame = idx.create_frame_if_not_exists("general")
    for s in range(4):
        frame.set_bit(0, s * SLICE_WIDTH + s)
        frame.set_bit(1, s * SLICE_WIDTH + s)
        frame.set_bit(1, s * SLICE_WIDTH + s + 7)

    srv = SpmdServer(holder)
    if pid == 0:
        tree = parse_string(
            "Count(Intersect(Bitmap(rowID=0), Bitmap(rowID=1)))"
        ).calls[0].children[0]
        leaves = []
        shape = _lower_tree(holder, "i", tree, leaves)
        assert shape is not None
        n1 = srv.count("i", shape, leaves, list(range(4)), 4)
        n2 = srv.count("i", shape, leaves, [0, 2], 4)  # masked subset
        srv.stop()
        print(f"RESULT 0 {n1}:{n2}", flush=True)
    else:
        srv.run_worker()
        print("RESULT 1 worker-done", flush=True)
    holder.close()


def _spmd_holder(pid: int):
    import tempfile

    from pilosa_tpu import SLICE_WIDTH
    from pilosa_tpu.core import Holder

    holder = Holder(tempfile.mkdtemp(prefix=f"spmd{pid}_"))
    holder.open()
    idx = holder.create_index_if_not_exists("i")
    frame = idx.create_frame_if_not_exists("general")
    for s in range(4):
        frame.set_bit(0, s * SLICE_WIDTH + s)
        frame.set_bit(1, s * SLICE_WIDTH + s)
        frame.set_bit(1, s * SLICE_WIDTH + s + 7)
    return holder


def spmd_death(pid: int):
    """Rank death mid-stream (VERDICT r4 #6): the worker dies abruptly
    after ONE descriptor; rank 0's next collective must REFUSE LOUDLY
    — an error within the heartbeat window — never hang the pact."""
    import time

    from pilosa_tpu.parallel.plan import _lower_tree
    from pilosa_tpu.parallel.spmd import SpmdServer
    from pilosa_tpu.pql import parse_string

    holder = _spmd_holder(pid)
    srv = SpmdServer(holder)
    if pid == 0:
        tree = parse_string(
            "Count(Intersect(Bitmap(rowID=0), Bitmap(rowID=1)))"
        ).calls[0].children[0]
        leaves = []
        shape = _lower_tree(holder, "i", tree, leaves)
        n1 = srv.count("i", shape, leaves, list(range(4)), 4)
        print(f"RESULT 0 first {n1}", flush=True)
        time.sleep(3)  # let the worker die between descriptors
        try:
            srv.count("i", shape, leaves, list(range(4)), 4)
            print("RESULT 0 unexpected-success", flush=True)
        except BaseException as e:  # noqa: BLE001 — any loud failure is
            #                         the REQUIRED behavior here
            print(f"RESULT 0 refused {type(e).__name__}", flush=True)
    else:
        desc = srv._broadcast(None)
        srv._run(desc)
        print("RESULT 1 dying", flush=True)
        os._exit(17)  # abrupt: no stop descriptor, no cleanup


if __name__ == "__main__":
    main()
