"""Multi-host SPMD cluster: boot N real server processes on one
machine and serve a cluster-wide query over the GLOBAL device mesh.

The production shape this demonstrates (parallel/spmd.py): rank 0
faces clients over HTTP and broadcasts every device request as a
descriptor on the device fabric; all ranks resolve it against their
replicated holders and enter the SAME psum collective; writes, schema
changes, attrs, and bulk imports ride the same totally-ordered stream,
so replicas cannot diverge. On real multi-host TPU pods the same TOML
boots each host with its own spmd-process-id and the collectives ride
ICI/DCN.

Run (CPU simulation, 2 processes x 2 virtual devices):

  python examples/spmd_cluster.py /tmp/spmd-demo

The script spawns both server processes via the real CLI
(`pilosa_tpu.ctl.main server -c rankN.toml`), drives rank 0 over HTTP,
and shows the collective counters rising on BOTH ranks.
"""

import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.request

SLICE_WIDTH = 1 << 20


def free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def post(port, path, body):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=body.encode(), method="POST")
    with urllib.request.urlopen(req, timeout=30) as r:
        return json.loads(r.read() or b"{}")


def get(port, path):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=30) as r:
        return json.loads(r.read())


def main():
    base = sys.argv[1] if len(sys.argv) > 1 else "/tmp/spmd-demo"
    os.makedirs(base, exist_ok=True)
    coord, http0, http1 = free_port(), free_port(), free_port()
    for rank, port in ((0, http0), (1, http1)):
        with open(f"{base}/r{rank}.toml", "w") as f:
            f.write(
                f'data-dir = "{base}/data{rank}"\n'
                f'host = "127.0.0.1:{port}"\n'
                f'use-device = "on"\n'
                f"[cluster]\n"
                f'type = "spmd"\n'
                f'spmd-coordinator = "127.0.0.1:{coord}"\n'
                f"spmd-processes = 2\n"
                f"spmd-process-id = {rank}\n")

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["PILOSA_TPU_DEVICE_MIN_WORK"] = "0"  # demo queries are tiny
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")

    procs = [subprocess.Popen(
        [sys.executable, "-m", "pilosa_tpu.ctl.main", "server",
         "-c", f"{base}/r{r}.toml"], env=env)
        for r in (0, 1)]
    try:
        for proc, port in zip(procs, (http0, http1)):
            for _ in range(120):
                if proc.poll() is not None:
                    raise RuntimeError(
                        f"server on port {port} exited rc={proc.returncode}"
                        " during boot — check its stderr above")
                try:
                    get(port, "/version")
                    break
                except Exception:  # noqa: BLE001 — booting
                    time.sleep(0.5)
            else:
                raise RuntimeError(f"server on port {port} never came up")

        print("-> schema + writes against rank 0")
        post(http0, "/index/demo", "{}")
        post(http0, "/index/demo/frame/events", "{}")
        for col in (5, SLICE_WIDTH + 5, 2 * SLICE_WIDTH + 9):
            for row in (1, 2):
                post(http0, "/index/demo/query",
                     f"SetBit(frame=events, rowID={row}, columnID={col})")

        print("-> cluster-wide Count over the 4-device global mesh")
        out = post(http0, "/index/demo/query",
                   "Count(Intersect(Bitmap(frame=events, rowID=1), "
                   "Bitmap(frame=events, rowID=2)))")
        print("   count =", out["results"][0])

        out = post(http0, "/index/demo/query", "TopN(frame=events, n=5)")
        print("   topn  =", out["results"][0])

        for rank, port in ((0, http0), (1, http1)):
            mesh = get(port, "/debug/vars").get("mesh", {})
            print(f"   rank {rank} collectives: count={mesh.get('count')} "
                  f"topn={mesh.get('topn')} stage={mesh.get('stage')}")

        print("-> rank 1 serves reads from its replica (host path)")
        out = post(http1, "/index/demo/query",
                   "Count(Bitmap(frame=events, rowID=1))")
        print("   rank-1 count =", out["results"][0])
    finally:
        # Rank 0 first (its shutdown broadcasts the STOP descriptor
        # while rank 1's worker is alive); rank 0 also hosts the
        # jax.distributed coordinator, whose exit can block until the
        # other client disconnects — hence the kill fallback.
        for p in procs:
            p.send_signal(signal.SIGTERM)
        for p in procs:
            try:
                p.wait(timeout=20)
            except subprocess.TimeoutExpired:
                p.kill()
    print("done.")


if __name__ == "__main__":
    main()
