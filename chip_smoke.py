#!/usr/bin/env python3
"""chip_smoke.py: the served path, once, on the attached chip.

The quickest proof that this repository still starts on a TPU. It drives
the main path through the entry points a user calls (the HTTP server
started by `python -m pilosa_tpu.ctl.main server`, PQL over HTTP) at
the size of the BASELINE.json headline configuration: 960 slices
(1,006,632,960 columns) of 8 dense rows, 1.0 GB staged on the device,
beside a 4,096-row frame of array and bitmap containers for TopN and a
frame of array containers for the sorted-array kernels. Every answer is
compared with numpy popcounts over the same generated words, and the
program's own counters must show that the device, not a host fallback,
answered. Weights there are none; the data is made from --seed.

    python chip_smoke.py            one chip: server child, HTTP client
    python chip_smoke.py --chips 4  the mesh path only, in one process

One process owns the chip at a time. In the default mode this process
never imports JAX: the device is named by a probe child that exits
before the server child starts, and the server is then the only JAX
process. With --chips 4 everything runs here and nothing is spawned
that needs a device.

Off the chip the script exits 2 and prints no result. --rehearse runs
every phase on the CPU backend (Pallas in interpret mode) at whatever
--slices says, for finding faults without chip time; it never prints
"ok": true and exits 3 when every phase passed.

Lines before the last are smoke readings ("kind": "smoke"), one JSON
object each: not benchmark results. The last line is the contract's.
"""

from __future__ import annotations

import argparse
import itertools
import json
import os
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time
import urllib.error
import urllib.request
from concurrent.futures import ThreadPoolExecutor

HERE = os.path.dirname(os.path.abspath(__file__))

INDEX = "i"
DENSE_ROWS = 8            # BASELINE.json "1B-col Intersect+Count"
MIXED_PER_SLICE = 128     # rows of the mixed frame present in one slice
SPARSE_VALUES = 1966      # ~3% of a container: bench's sparse recipe
PAIRS = tuple(itertools.combinations(range(DENSE_ROWS), 2))  # the 28


class SmokeFailure(Exception):
    """A phase failed. Nothing is caught and carried past one."""


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# -- data: made from the seed, one slice per job ----------------------------

def _frag_path(data_dir: str, frame: str, slice_: int) -> str:
    return os.path.join(data_dir, INDEX, frame, "standard", "fragments",
                        str(slice_))


def _write_fragment(path: str, keys, containers) -> None:
    """Through the repo's own roaring serializer, footer and all, so
    the server's ordinary open path loads and verifies the file."""
    from pilosa_tpu.roaring.bitmap import Bitmap

    bm = Bitmap()
    bm.keys = list(keys)
    bm.containers = list(containers)
    with open(path, "wb") as f:
        bm.write_to(f, footer=True)


def _dense_words(seed: int, slice_: int):
    import numpy as np

    rng = np.random.default_rng([seed, slice_, 0])
    return rng.integers(0, 2**64, size=(DENSE_ROWS * 16, 1024),
                        dtype=np.uint64)


def dense_ref(words) -> dict:
    """One slice's share of every dense-frame answer, from numpy
    popcounts over its (rows * 16, 1024) uint64 words."""
    import numpy as np

    def pc(x) -> int:
        return int(np.bitwise_count(x).sum())

    w = words.reshape(DENSE_ROWS, -1)
    inter = w[0].copy()
    union = w[0].copy()
    diff = [w[0].copy(), w[1].copy()]  # minuend row 0, minuend row 1
    for r in range(1, DENSE_ROWS):
        inter &= w[r]
        union |= w[r]
    for base in (0, 1):
        for r in range(DENSE_ROWS):
            if r != base:
                diff[base] &= ~w[r]
    return {
        "row": [pc(w[r]) for r in range(DENSE_ROWS)],
        "and2": [pc(w[a] & w[b]) for a, b in PAIRS],
        "or2": [pc(w[a] | w[b]) for a, b in PAIRS],
        "diff2": [pc(w[a] & ~w[b]) for a, b in PAIRS],
        "and8": [pc(inter)], "or8": [pc(union)],
        "diff8": [pc(diff[0]), pc(diff[1])],
    }


DENSE_KEYS = ("row", "and2", "or2", "diff2", "and8", "or8", "diff8")


def gen_slice(job):
    """Write slice `s` of the three frames; return its share of every
    reference answer, counted with numpy alone (np.bitwise_count over
    the generated words; nothing of pilosa_tpu.ops or the native
    library has a say in what the right answer is)."""
    import numpy as np

    from pilosa_tpu.roaring.bitmap import Container

    seed, s, data_dir, mixed_rows = job

    def pc(x) -> int:
        return int(np.bitwise_count(x).sum())

    # dense: the shape bench.build_dense_holder makes
    words = _dense_words(seed, s)
    _write_fragment(
        _frag_path(data_dir, "dense", s),
        [r * 16 + b for r in range(DENSE_ROWS) for b in range(16)],
        [Container(bitmap=words[i]) for i in range(len(words))])
    ref = dense_ref(words)

    # mixed: bench.build_mixed_holder's containers (70% arrays of
    # U[1, 4096] values, 30% bitmaps of random density), 4,096 rows; a
    # slice holds MIXED_PER_SLICE of them, because the staged pool is
    # slices x (most containers in any slice) x 8 KB and this frame
    # shares the index's 960 slices.
    rng = np.random.default_rng([seed, s, 1])
    per = min(MIXED_PER_SLICE, mixed_rows)
    rows = np.sort(rng.choice(mixed_rows, size=per, replace=False))
    perm = rng.permutation(65536).astype(np.uint32)
    containers, counts = [], []
    for _ in rows:
        if rng.random() < 0.3:
            bits = rng.integers(0, 2**64, size=1024, dtype=np.uint64)
            bits &= rng.integers(0, 2**64, size=1024, dtype=np.uint64)
            containers.append(Container(bitmap=bits))
            counts.append(pc(bits))
        else:
            n = int(rng.integers(1, 4097))
            start = int(rng.integers(0, 65536 - n))
            containers.append(Container(array=np.sort(perm[start:start + n])))
            counts.append(n)
    _write_fragment(_frag_path(data_dir, "mixed", s),
                    [int(r) * 16 for r in rows], containers)
    ref["mixed"] = ([int(r) for r in rows], counts)

    # sparse: two rows of ~3% array containers in all 16 blocks
    # (bench.build_sparse_holder's shape), windows of one permutation
    # that overlap by half.
    rng = np.random.default_rng([seed, s, 2])
    perm = rng.permutation(65536).astype(np.uint32)
    n = SPARSE_VALUES
    blocks = [[np.sort(perm[b * 4096 + off:b * 4096 + off + n])
               for b in range(16)] for off in (0, n // 2)]
    _write_fragment(
        _frag_path(data_dir, "sparse", s),
        [r * 16 + b for r in (0, 1) for b in range(16)],
        [Container(array=v) for r in (0, 1) for v in blocks[r]])
    ref["sparse_and"] = sum(len(np.intersect1d(a, b, assume_unique=True))
                            for a, b in zip(*blocks))
    ref["sparse_n"] = 16 * n
    return ref


def generate(args, data_dir: str) -> dict:
    """Schema through the Holder, fragments through a pool of workers
    that import numpy and the roaring serializer only."""
    import multiprocessing

    from pilosa_tpu.core import Holder

    h = Holder(data_dir)
    h.open()
    idx = h.create_index_if_not_exists(INDEX)
    for frame in ("dense", "mixed", "sparse"):
        idx.create_frame_if_not_exists(frame) \
            .create_view_if_not_exists("standard")
    h.close()

    jobs = [(args.seed, s, data_dir, args.mixed_rows)
            for s in range(args.slices)]
    workers = max(1, min(len(jobs), (os.cpu_count() or 2) - 1, 12))
    with multiprocessing.get_context("spawn").Pool(workers) as pool:
        parts = pool.map(gen_slice, jobs, chunksize=4)

    ref = {k: [sum(p[k][i] for p in parts) for i in range(len(parts[0][k]))]
           for k in DENSE_KEYS}
    for k in ("sparse_and", "sparse_n"):
        ref[k] = sum(p[k] for p in parts)
    totals: dict = {}
    for p in parts:
        for r, c in zip(*p["mixed"]):
            totals[r] = totals.get(r, 0) + c
    ref["topn"] = sorted(totals.items(), key=lambda rc: (-rc[1], rc[0]))

    # The write phase sets a clear bit of row 0 in slice 0; every
    # dense answer after it is the reference with that slice recounted.
    import numpy as np

    words = _dense_words(args.seed, 0)
    before = dense_ref(words)
    row0 = words[:16].reshape(-1)  # a view: the bit lands in `words`
    word = int(np.flatnonzero(row0 != np.uint64(2**64 - 1))[0])
    bit = next(b for b in range(64) if not (int(row0[word]) >> b) & 1)
    row0[word] |= np.uint64(1 << bit)
    after = dense_ref(words)
    ref["clear_column"] = word * 64 + bit
    ref["written"] = {k: [t - b + a for t, b, a in
                          zip(ref[k], before[k], after[k])]
                      for k in DENSE_KEYS}
    return ref


# -- PQL --------------------------------------------------------------------

def bm(row: int, frame: str = "dense") -> str:
    return f'Bitmap(rowID={row}, frame="{frame}")'


def count(op: str, rows, frame: str = "dense") -> str:
    inner = ", ".join(bm(r, frame) for r in rows)
    return f"Count({op}({inner}))" if op else f"Count({inner})"


def top_pairs(result) -> list:
    return [(int(p["id"]), int(p["count"])) for p in result]


# -- the server child and its HTTP client ------------------------------------

def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Server:
    """`python -m pilosa_tpu.ctl.main server`, use-device = "on", cost
    routing at its default: the one JAX process while it lives."""

    def __init__(self, data_dir: str, log_path: str, env_extra: dict):
        self.port = free_port()
        self.base = f"http://127.0.0.1:{self.port}"
        self.log_path = log_path
        cfg = os.path.join(os.path.dirname(data_dir), "smoke.toml")
        with open(cfg, "w") as f:
            f.write('use-device = "on"\n')
        env = dict(os.environ, **env_extra)
        env["PYTHONPATH"] = HERE + os.pathsep + env.get("PYTHONPATH", "")
        self.log = open(log_path, "ab")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "pilosa_tpu.ctl.main", "server",
             "-c", cfg, "-d", data_dir, "-b", f"127.0.0.1:{self.port}"],
            cwd=HERE, env=env, stdout=self.log, stderr=subprocess.STDOUT)

    def http(self, path: str, body: bytes | None = None, timeout=900):
        req = urllib.request.Request(self.base + path, data=body)
        try:
            with urllib.request.urlopen(req, timeout=timeout) as r:
                return r.read()
        except urllib.error.HTTPError as e:
            raise SmokeFailure(
                f"{path} {body!r}: HTTP {e.code}: {e.read()[:500]!r}")

    def query(self, pql: str):
        out = json.loads(self.http(f"/index/{INDEX}/query", pql.encode()))
        check("results" in out, f"{pql}: {out}")
        return out["results"][0]

    def timed(self, pql: str):
        t0 = time.perf_counter()
        r = self.query(pql)
        return r, (time.perf_counter() - t0) * 1e3

    def vars(self) -> dict:
        return json.loads(self.http("/debug/vars", timeout=60))

    def metrics(self) -> dict:
        """Prometheus text -> {"name{labels}": value}."""
        out = {}
        for line in self.http("/metrics", timeout=60).decode().splitlines():
            if line and not line.startswith("#"):
                name, _, val = line.rpartition(" ")
                try:
                    out[name] = float(val)
                except ValueError:
                    pass
        return out

    def wait_up(self, timeout: float = 180.0) -> float:
        t0 = time.monotonic()
        while time.monotonic() - t0 < timeout:
            check(self.proc.poll() is None,
                  f"server exited with {self.proc.returncode} at start-up")
            try:
                with urllib.request.urlopen(self.base + "/status",
                                            timeout=2):
                    return time.monotonic() - t0
            except OSError:
                time.sleep(0.25)
        raise SmokeFailure(f"server not up after {timeout:.0f} s")

    def stop(self) -> None:
        """SIGTERM and a clean close; a server that outlives it is
        killed, and that is a failure of its own."""
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=90)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
                self.log.close()
                raise SmokeFailure("server ignored SIGTERM for 90 s")
        self.log.close()

    def kill(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait()
        if not self.log.closed:
            self.log.close()

    def log_tail(self, n: int = 4000) -> str:
        try:
            with open(self.log_path, "rb") as f:
                return f.read()[-n:].decode(errors="replace")
        except OSError:
            return ""


def together(srv: Server, pqls) -> list:
    """Send the queries at the same moment, one thread each, so the
    server's batcher sees a herd. Returns results in order."""
    gate = threading.Barrier(len(pqls))

    def one(pql):
        gate.wait()
        return srv.query(pql)

    with ThreadPoolExecutor(len(pqls)) as pool:
        return list(pool.map(one, pqls))


FALLBACK_REASONS = ("unstaged", "oom", "hbm_infeasible", "quarantined",
                    "compile", "error")


class Ledger:
    """What the program's own counters must show for one server: every
    device-eligible Count routed to the mesh AND served there, no
    fallback of any kind, no quarantine."""

    def __init__(self, srv: Server):
        self.srv = srv
        self.sent = 0  # device-eligible Count queries sent

    def assert_device_answered(self, who: str) -> dict:
        m = self.srv.metrics()
        v = self.srv.vars()
        check("mesh" in v,
              f"{who}: /debug/vars has no mesh block (no mesh manager: "
              f"its construction failed or the device path is off)")
        mesh = v["mesh"]
        routed = sum(val for k, val in m.items()
                     if k.startswith("pilosa_query_route_total{")
                     and 'backend="mesh"' in k)
        check(routed == self.sent,
              f"{who}: {self.sent} device-eligible Counts sent, "
              f"{routed:.0f} routed to the mesh; routes: "
              + str({k: val for k, val in m.items()
                     if k.startswith("pilosa_query_route_total")}))
        check(mesh.get("count") == self.sent,
              f"{who}: {self.sent} Counts routed to the mesh, "
              f"{mesh.get('count')} served by it")
        for reason in FALLBACK_REASONS:
            key = f'pilosa_device_fallback_total{{reason="{reason}"}}'
            check(key in m, f"{who}: /metrics lacks {key}")
            check(m[key] == 0, f"{who}: {key} = {m[key]:.0f}")
        check(m.get("pilosa_plan_quarantined_total") == 0,
              f"{who}: plans quarantined: "
              f"{m.get('pilosa_plan_quarantined_total')}")
        for k in ("lone_fused_failed", "h2d_whole_pool_fallback",
                  "fallback_sparse_exec", "fallback_sparse_format",
                  "fallback_sparse_shape", "routed_host"):
            check(not mesh.get(k), f"{who}: mesh.{k} = {mesh.get(k)}")
        return v


def run_served(args, emit, device: dict) -> None:
    rehearsal = args.rehearse
    out_dir = os.path.join(HERE, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    base_env = {}
    if rehearsal:
        # The CPU stands in for the chip: one device, no cost routing
        # to the host (at 8 slices nothing would clear the work
        # threshold, and on a CPU backend large folds route to the C++
        # kernels), a real interpret-mode calibration race.
        base_env = {"JAX_PLATFORMS": "cpu",
                    "PILOSA_TPU_DEVICE_MIN_WORK": "0",
                    "PILOSA_TPU_CPU_ROUTE_NATIVE": "off",
                    "PILOSA_TPU_CALIBRATE": "force"}

    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        data_dir = os.path.join(tmp, "data")
        t0 = time.perf_counter()
        ref = generate(args, data_dir)
        disk = sum(os.path.getsize(os.path.join(d, f))
                   for d, _, fs in os.walk(data_dir) for f in fs)
        emit("generate", seconds=round(time.perf_counter() - t0, 2),
             slices=args.slices, columns=args.slices << 20,
             bytes_on_disk=disk, seed=args.seed)

        srv = None
        try:
            # ---- pass 1: the server as a user starts it -------------------
            srv = Server(data_dir, os.path.join(out_dir, "smoke_server1.log"),
                         base_env)
            emit("open", seconds=round(srv.wait_up(), 2))
            deadline = time.monotonic() + 400
            while "count_calibration" not in (v := srv.vars()):
                check(time.monotonic() < deadline,
                      "no count_calibration record after 400 s")
                check(srv.proc.poll() is None, "server died calibrating")
                time.sleep(0.5)
            cal = v["count_calibration"]
            rt = v.get("jax_runtime") or {}
            check(rt.get("platform") == device["platform"]
                  and rt.get("device_kind") == device["kind"],
                  f"server runs on {rt.get('platform')}/"
                  f"{rt.get('device_kind')}, the probe saw {device}")
            check(cal.get("source") == "measured"
                  and "pallas_ms" in cal and "xla_ms" in cal,
                  f"count calibration did not measure: {cal}")
            emit("calibrate", backend=cal["backend"],
                 pallas_ms=cal["pallas_ms"], xla_ms=cal["xla_ms"],
                 elapsed_ms=cal.get("elapsed_ms"),
                 compile_cache_dir=rt.get("compile_cache_dir"))

            led = Ledger(srv)

            def shape(name, first_pql, first_ref, warm_pql, warm_ref):
                r1, ms1 = srv.timed(first_pql)
                check(r1 == first_ref, f"{first_pql} = {r1}, "
                                       f"reference {first_ref}")
                r2, ms2 = srv.timed(warm_pql)
                check(r2 == warm_ref, f"{warm_pql} = {r2}, "
                                      f"reference {warm_ref}")
                led.sent += 2
                emit("query", shape=name, first_ms=round(ms1, 2),
                     warm_ms=round(ms2, 2))

            def pair(a, b):
                return PAIRS.index((a, b))

            # Count(Bitmap): the first query stages the dense frame.
            t0 = time.perf_counter()
            shape("count_bitmap", count("", [0]), ref["row"][0],
                  count("", [1]), ref["row"][1])
            mesh = srv.vars()["mesh"]
            emit("stage", frame="dense",
                 first_query_seconds=round(time.perf_counter() - t0, 2),
                 stage_seconds=round(mesh["stage_us"] / 1e6, 2),
                 h2d_bytes=mesh["h2d_bytes"], h2d_chunks=mesh["h2d_chunks"],
                 staged_bytes=mesh["staged_bytes"],
                 hbm_budget_bytes=mesh["hbm_budget_bytes"])
            if not rehearsal:
                check(mesh["h2d_bytes"] >= args.slices * DENSE_ROWS * 16
                      * 8192, f"staged only {mesh['h2d_bytes']} bytes")

            for op, key in (("Intersect", "and2"), ("Union", "or2"),
                            ("Difference", "diff2")):
                shape(f"{op.lower()}_2", count(op, [0, 1]),
                      ref[key][pair(0, 1)], count(op, [2, 3]),
                      ref[key][pair(2, 3)])
            rot = [1, 0] + list(range(2, DENSE_ROWS))
            for op, first, warm in (
                    ("Intersect", ref["and8"][0], ref["and8"][0]),
                    ("Union", ref["or8"][0], ref["or8"][0]),
                    ("Difference", ref["diff8"][0], ref["diff8"][1])):
                shape(f"{op.lower()}_8", count(op, range(DENSE_ROWS)), first,
                      count(op, rot), warm)

            # 16 distinct pairs at once: the batcher must coalesce.
            herd = [p for p in PAIRS if p not in ((0, 1), (2, 3))][:16]
            before = srv.vars()["mesh"].get("batched", 0)
            t0 = time.perf_counter()
            got = together(srv, [count("Intersect", p) for p in herd])
            ms = (time.perf_counter() - t0) * 1e3
            led.sent += len(herd)
            want = [ref["and2"][PAIRS.index(p)] for p in herd]
            check(got == want, f"16 concurrent pairs: {got} != {want}")
            batched = srv.vars()["mesh"].get("batched", 0) - before
            check(batched > 0, "16 concurrent Counts and the batcher "
                               "coalesced none (batched = 0)")
            emit("query", shape="intersect_2_x16_concurrent",
                 wall_ms=round(ms, 2), batched=batched)

            # The sorted-array pools, under whichever kernel the
            # sparse calibrator picks.
            sn, sa = ref["sparse_n"], ref["sparse_and"]
            for op, want in (("Intersect", sa), ("Union", 2 * sn - sa),
                             ("Difference", sn - sa)):
                r, ms = srv.timed(count(op, [0, 1], "sparse"))
                check(r == want, f"sparse {op} = {r}, reference {want}")
                led.sent += 1
                emit("query", shape=f"sparse_{op.lower()}_2",
                     first_ms=round(ms, 2))
            if not rehearsal:  # off the chip the race is never run
                sc = srv.vars()["count_calibration"].get("sparse", {})
                check(sc.get("source") == "measured",
                      f"sparse calibration did not measure: {sc}")
                emit("calibrate", kernel="sparse", backend=sc["backend"],
                     pallas_ms=sc["pallas_ms"], xla_ms=sc["xla_ms"])

            # TopN over the mixed frame.
            r, ms1 = srv.timed('TopN(frame="mixed", n=100)')
            check(top_pairs(r) == ref["topn"][:100],
                  f"TopN(n=100) differs from the reference: "
                  f"{top_pairs(r)[:3]} vs {ref['topn'][:3]}")
            r, ms2 = srv.timed('TopN(frame="mixed", n=50)')
            check(top_pairs(r) == ref["topn"][:50], "TopN(n=50) differs")
            emit("query", shape="topn_mixed_n100", first_ms=round(ms1, 2),
                 warm_ms=round(ms2, 2), rows=len(ref["topn"]))
            check(srv.vars()["mesh"].get("topn", 0) >= 1,
                  "TopN was not served by the mesh (mesh.topn = 0)")

            # A write, acknowledged, then read back through the device:
            # an incremental scatter into the staged pool, no restage.
            mesh0 = srv.vars()["mesh"]
            ack = srv.query(f'SetBit(rowID=0, frame="dense", '
                            f'columnID={ref["clear_column"]})')
            check(ack is True, f"SetBit not acknowledged as a change: {ack}")
            r, ms = srv.timed(count("", [0]))
            led.sent += 1
            wr = ref["written"]  # the dense answers from here on
            check(wr["row"][0] == ref["row"][0] + 1, "reference of the write")
            check(r == wr["row"][0],
                  f"after SetBit Count = {r}, reference {wr['row'][0]}")
            mesh1 = srv.vars()["mesh"]
            check(mesh1["incremental"] > mesh0["incremental"]
                  and mesh1["stage"] == mesh0["stage"],
                  f"the write was not an incremental scatter: incremental "
                  f"{mesh0['incremental']}->{mesh1['incremental']}, stage "
                  f"{mesh0['stage']}->{mesh1['stage']}")
            emit("write", read_back_ms=round(ms, 2),
                 incremental=mesh1["incremental"])

            v = led.assert_device_answered("pass 1 (auto)")
            rt = v["jax_runtime"]
            emit("summary", server="auto", count_backend=cal["backend"],
                 counts_on_device=led.sent, compile=rt["compile"],
                 programs_built=v["mesh"]["compile_count"],
                 memory=rt["memory"],
                 mesh={k: v["mesh"].get(k, 0) for k in (
                     "lone_fused", "batched", "coarse", "coarse_uniform",
                     "shared_batch", "sparse_count", "topn", "stage",
                     "incremental", "device_dispatches")})
            srv.stop()

            # ---- pass 2: same directory, every Pallas family -------------
            pin = "pallas_interpret" if rehearsal else "pallas"
            env2 = dict(base_env, PILOSA_TPU_COUNT_BACKEND=pin,
                        PILOSA_TPU_BATCH_SHARED="sync")
            if not rehearsal:
                env2["PILOSA_TPU_SPARSE_BACKEND"] = "pallas"
            srv = Server(data_dir, os.path.join(out_dir, "smoke_server2.log"),
                         env2)
            emit("open", seconds=round(srv.wait_up(), 2), restart=True)
            led = Ledger(srv)

            # The acknowledged bit, after the restart; alone, so the
            # lone fused program serves it.
            r, ms = srv.timed(count("", [0]))
            led.sent += 1
            check(r == wr["row"][0],
                  f"after restart Count = {r}, reference "
                  f"{wr['row'][0]}: the acknowledged SetBit is gone")
            emit("restart", bit_survived=True, first_query_ms=round(ms, 2))

            def herd_check(name, pqls, wants):
                t0 = time.perf_counter()
                got = together(srv, pqls)
                led.sent += len(pqls)
                check(got == wants, f"{name}: {got} != {wants}")
                emit("query", shape=name, server=pin,
                     wall_ms=round((time.perf_counter() - t0) * 1e3, 2))

            disjoint = [(0, 1), (2, 3), (4, 5), (6, 7)]
            herd_check("union_2_x4_disjoint",
                       [count("Union", p) for p in disjoint],
                       [wr["or2"][PAIRS.index(p)] for p in disjoint])
            herd_check("intersect_2_x28_of_8_rows",
                       [count("Intersect", p) for p in PAIRS], wr["and2"])
            herd_check("four_shapes_at_once",
                       [count("Intersect", range(DENSE_ROWS)),
                        count("Union", range(DENSE_ROWS)),
                        count("Difference", range(DENSE_ROWS)),
                        count("Difference", [0, 1])],
                       [wr["and8"][0], wr["or8"][0], wr["diff8"][0],
                        wr["diff2"][pair(0, 1)]])
            r, ms = srv.timed(count("Intersect", [0, 1], "sparse"))
            led.sent += 1
            check(r == sa, f"sparse Intersect = {r}, reference {sa}")
            r, ms = srv.timed('TopN(frame="mixed", n=100)')
            check(top_pairs(r) == ref["topn"][:100], "TopN differs (pass 2)")

            v = led.assert_device_answered(f"pass 2 ({pin})")
            mesh = v["mesh"]
            for k in ("coarse_uniform", "shared_batch", "coarse",
                      "lone_fused", "sparse_count", "batched", "topn"):
                check(mesh.get(k, 0) > 0,
                      f"pass 2: mesh.{k} = {mesh.get(k)}: that kernel "
                      f"family never ran")
            rt = v["jax_runtime"]
            emit("summary", server=pin, counts_on_device=led.sent,
                 compile=rt["compile"],
                 programs_built=mesh["compile_count"], memory=rt["memory"],
                 mesh={k: mesh.get(k, 0) for k in (
                     "lone_fused", "batched", "coarse", "coarse_uniform",
                     "shared_batch", "sparse_count", "topn", "stage",
                     "device_dispatches")})
            srv.stop()
        except BaseException:
            if srv is not None:
                srv.kill()
                sys.stderr.write(f"--- server log tail ({srv.log_path})\n"
                                 f"{srv.log_tail()}\n")
            raise


# -- --chips 4: the mesh path and what it is compared with -------------------

def run_mesh(args, emit, n_chips: int) -> None:
    """The same data on a 4-device default_mesh() in this one process,
    the same Count/TopN queries through an Executor, against the same
    mesh restricted to one device and the numpy reference."""
    import jax

    from pilosa_tpu.core import Holder
    from pilosa_tpu.executor import Executor
    from pilosa_tpu.parallel.mesh import compile_serve_count, default_mesh
    from pilosa_tpu.parallel.plan import _lower_tree, _tree_signature
    from pilosa_tpu.parallel.serve import MeshManager
    from pilosa_tpu.pql import parse_string

    if args.rehearse:
        os.environ["PILOSA_TPU_CPU_ROUTE_NATIVE"] = "off"
    min_work = 0 if args.rehearse else None

    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        data_dir = os.path.join(tmp, "data")
        t0 = time.perf_counter()
        ref = generate(args, data_dir)
        emit("generate", seconds=round(time.perf_counter() - t0, 2),
             slices=args.slices, seed=args.seed)
        h = Holder(data_dir)
        h.open()
        slices = list(range(args.slices))
        queries = (
            [(count("", [0]), ref["row"][0]),
             (count("Intersect", [0, 1]), ref["and2"][0]),
             (count("Union", [2, 3]), ref["or2"][PAIRS.index((2, 3))]),
             (count("Difference", [4, 5]),
              ref["diff2"][PAIRS.index((4, 5))]),
             (count("Intersect", range(DENSE_ROWS)), ref["and8"][0]),
             (count("Union", range(DENSE_ROWS)), ref["or8"][0]),
             (count("Difference", range(DENSE_ROWS)), ref["diff8"][0]),
             (count("Intersect", [0, 1], "sparse"), ref["sparse_and"])])
        answers = {}
        try:
            for name, n in ((f"mesh{n_chips}", None), ("mesh1", 1)):
                mgr = MeshManager(h, mesh=default_mesh(n))
                ex = Executor(h, use_device=True, device_min_work=min_work)
                ex._mesh_mgr = mgr
                got = []
                t0 = time.perf_counter()
                for pql, want in queries:
                    r = ex.execute(INDEX, parse_string(pql), None, None)[0]
                    check(r == want, f"{name}: {pql} = {r}, reference {want}")
                    got.append(r)
                top = ex.execute(
                    INDEX, parse_string('TopN(frame="mixed", n=100)'),
                    None, None)[0]
                top = [(int(r), int(c)) for r, c in top]
                check(top == ref["topn"][:100],
                      f"{name}: TopN differs from the reference")
                answers[name] = (got, top)
                stats = dict(mgr.stats.copy())
                check(stats["count"] == len(queries) and stats["topn"] >= 1,
                      f"{name}: the mesh served {stats['count']} of "
                      f"{len(queries)} Counts, {stats['topn']} TopN")
                for k, v in stats.items():
                    check(not (k.startswith("fallback") and v),
                          f"{name}: mesh.{k} = {v}")
                tiers: dict = {}
                for k, v in dict(ex.tier_stats.copy()).items():
                    route, _, tier = k.partition("|")
                    tiers[tier] = tiers.get(tier, 0) + int(v)
                emit("mesh", name=name, devices=int(mgr.mesh.devices.size),
                     seconds=round(time.perf_counter() - t0, 2), tiers=tiers,
                     h2d_bytes=stats["h2d_bytes"],
                     hbm_budget_bytes=stats["hbm_budget_bytes"])
                if n is not None:
                    check(set(tiers) == {"local"},
                          f"one device, yet tiers {tiers}")
                    continue
                check(tiers.get("ici") and not tiers.get("http"),
                      f"tier ledger of the {n_chips}-device mesh: {tiers}")
                # Where the pool sits: one shard per device, a quarter
                # of the bytes each.
                words = mgr._views[(INDEX, "dense", "standard")] \
                    .sharded.words
                shards = [(str(s.device), s.data.nbytes)
                          for s in words.addressable_shards]
                check(len({d for d, _ in shards}) == n_chips,
                      f"pool shards sit on {shards}")
                check(all(abs(b * n_chips - words.nbytes)
                          <= 0.02 * words.nbytes for _, b in shards),
                      f"uneven shards of {words.nbytes} bytes: {shards}")
                # The program that served the lone Counts reduces over
                # the interconnect.
                tree = parse_string(queries[1][0]).calls[0].children[0]
                leaves: list = []
                lowered = _lower_tree(h, INDEX, tree, leaves)
                with mgr._mu:
                    words_t, idx_all, hit_all, first = \
                        mgr._stage_leaves_host(INDEX, leaves, args.slices)
                    mask = mgr._mask_for(first, slices)
                hlo = compile_serve_count(
                    mgr.mesh, _tree_signature(lowered), len(leaves),
                    host_meta=True).lower(
                    words_t, idx_all, hit_all, mask).compile().as_text()
                check("all-reduce" in hlo,
                      "the compiled count program holds no all-reduce")
                emit("placement", shards=shards,
                     all_reduce=hlo.count("all-reduce("))
            check(answers[f"mesh{n_chips}"] == answers["mesh1"],
                  "the two meshes disagree")
            emit("memory", memory={
                str(d): {k: int(v) for k, v in (d.memory_stats() or {}).items()
                         if k in ("bytes_in_use", "peak_bytes_in_use",
                                  "bytes_limit")}
                for d in jax.local_devices()})
        finally:
            h.close()


# -- entry ------------------------------------------------------------------

_PROBE = """
import json, jax
from importlib import metadata
def ver(n):
    try: return metadata.version(n)
    except metadata.PackageNotFoundError: return None
d = jax.devices()
print(json.dumps({"platform": d[0].platform, "kind": d[0].device_kind,
                  "count": len(d), "jax": jax.__version__,
                  "jaxlib": ver("jaxlib"), "libtpu": ver("libtpu")}))
"""


def probe_device(rehearse: bool) -> dict:
    """Name the device from a child that holds it only while it asks;
    this process stays off JAX (default mode)."""
    env = dict(os.environ)
    if rehearse:
        env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run([sys.executable, "-c", _PROBE], env=env, cwd=HERE,
                       capture_output=True, text=True, timeout=300)
    if p.returncode != 0:
        sys.stderr.write(p.stderr[-2000:])
        raise SmokeFailure(f"JAX did not start (exit {p.returncode})")
    return json.loads(p.stdout.strip().splitlines()[-1])


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=21)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: the mesh path only, in one process")
    ap.add_argument("--slices", type=int, default=960,
                    help="960 = 1,006,632,960 columns, the headline size")
    ap.add_argument("--mixed-rows", type=int, default=4096)
    ap.add_argument("--rehearse", action="store_true",
                    help="run on the CPU backend to find faults; never "
                         "prints a result, exits 3 when all phases pass")
    args = ap.parse_args()

    try:
        from pilosa_tpu.ops import native
    except ImportError:
        sys.stderr.write("chip_smoke: no pilosa_tpu package beside this "
                         "script; it proves nothing alone\n")
        return 2

    if args.chips == 4:
        if args.rehearse:
            os.environ["JAX_PLATFORMS"] = "cpu"
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=4").strip()
        import jax

        from pilosa_tpu import jaxrt

        cache_dir = jaxrt.setup_compile_cache()
        devs = jax.devices()
        rt = jaxrt.snapshot()
        device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
                  "count": len(devs), "jax": rt["jax"],
                  "jaxlib": rt["jaxlib"], "libtpu": rt["libtpu"],
                  "compile_cache_dir": cache_dir}
    else:
        device = probe_device(args.rehearse)

    on_chip = device["platform"] == "tpu"
    if not on_chip and not args.rehearse:
        sys.stderr.write(f"chip_smoke: JAX found no accelerator "
                         f"({device}); nothing to prove here\n")
        return 2
    if device["count"] != args.chips:
        sys.stderr.write(f"chip_smoke: --chips {args.chips} but JAX sees "
                         f"{device['count']} device(s)\n")
        return 2
    kind = "smoke" if on_chip else "rehearsal"

    def emit(phase: str, **fields) -> None:
        print(json.dumps({"kind": kind, "phase": phase,
                          "platform": device["platform"],
                          "device_kind": device["kind"], **fields}),
              flush=True)

    emit("device", **{k: v for k, v in device.items()
                      if k not in ("platform", "kind")},
         has_native=native.has_native(), native_lib=native._lib_name())
    if not native.has_native():
        sys.stderr.write("chip_smoke: no C++ toolchain: the host fold "
                         "runs on numpy (has_native() is False)\n")

    try:
        if args.chips == 4:
            run_mesh(args, emit, args.chips)
        else:
            run_served(args, emit, device)
    except SmokeFailure as e:
        sys.stderr.write(f"chip_smoke: FAILED: {e}\n")
        return 1

    if not on_chip:
        sys.stderr.write("chip_smoke: rehearsal passed on the CPU; that "
                         "is not a chip run\n")
        return 3
    print(json.dumps({"ok": True, "device": {
        "platform": device["platform"], "kind": device["kind"],
        "count": device["count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
