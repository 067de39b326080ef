"""Bootable SPMD multi-host serving (VERDICT r2 item 3): two REAL
server processes started through the CLI with `[cluster] type =
"spmd"`, a client POSTing PQL over HTTP to rank 0, and the collective
provably running on the GLOBAL mesh — the device-serving counters rise
on BOTH ranks' /debug/vars.

Reference analog: server/server.go:107-192 wires the whole node's
transport at startup; executor.go:1103-1163 fans queries across nodes.
Here the fan-out is one broadcast descriptor + one psum over the
4-device (2 per process) mesh, and writes/schema ride the same
descriptor stream (parallel/spmd.py).
"""

import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request

import pytest

SLICE_WIDTH = 1 << 20


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _post(port, path, body):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=body.encode(),
        method="POST")
    try:
        with urllib.request.urlopen(req, timeout=30) as r:
            return json.loads(r.read() or b"{}")
    except urllib.error.HTTPError as e:  # error bodies are JSON too
        return json.loads(e.read() or b"{}")


def _get(port, path):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=30) as r:
        return json.loads(r.read())


def _wait_http(port, deadline):
    while time.time() < deadline:
        try:
            _get(port, "/version")
            return True
        except Exception:  # noqa: BLE001 — still booting
            time.sleep(0.5)
    return False


def test_spmd_server_two_process_boot(tmp_path):
    coord = _free_port()
    http = [_free_port(), _free_port()]
    cfgs = []
    for r in (0, 1):
        cfg = tmp_path / f"r{r}.toml"
        cfg.write_text(
            f'data-dir = "{tmp_path}/data{r}"\n'
            f'host = "127.0.0.1:{http[r]}"\n'
            f'use-device = "on"\n'
            f"[cluster]\n"
            f'type = "spmd"\n'
            f'spmd-coordinator = "127.0.0.1:{coord}"\n'
            f"spmd-processes = 2\n"
            f"spmd-process-id = {r}\n")
        cfgs.append(cfg)

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["PILOSA_TPU_DEVICE_MIN_WORK"] = "0"  # tiny queries stay on mesh
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")

    procs = [
        subprocess.Popen(
            [sys.executable, "-m", "pilosa_tpu.ctl.main", "server",
             "-c", str(cfgs[r])],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env, cwd=str(tmp_path))
        for r in (0, 1)
    ]
    try:
        deadline = time.time() + 120
        if not (_wait_http(http[0], deadline)
                and _wait_http(http[1], deadline)):
            for p in procs:
                p.kill()
            outs = [p.communicate(timeout=10) for p in procs]
            detail = "\n".join(e[-1500:] for _, e in outs)
            if "distributed" in detail or "initialize" in detail \
                    or "gloo" in detail.lower():
                pytest.skip(f"multi-process runtime unavailable:\n{detail}")
            raise AssertionError(f"servers never came up:\n{detail}")

        # schema + writes + queries, all against rank 0
        _post(http[0], "/index/si", "{}")
        _post(http[0], "/index/si/frame/f1", "{}")
        # The first mutation doubles as a RUNTIME probe: a jax whose
        # CPU backend has no multiprocess collectives (no gloo) boots
        # both HTTP servers fine, then every descriptor broadcast
        # errors — that's the runtime missing, not the SPMD plane
        # broken, so skip exactly like the boot-failure guard above.
        probe = _post(http[0], "/index/si/query",
                      f"SetBit(frame=f1, rowID=1, columnID={SLICE_WIDTH + 9})")
        if "results" not in probe:
            for p in procs:
                p.kill()
            outs = [p.communicate(timeout=10) for p in procs]
            detail = "\n".join(e[-1500:] for _, e in outs)
            if ("Multiprocess computations aren't implemented" in detail
                    or "gloo" in detail.lower()):
                pytest.skip(f"multi-process runtime unavailable:\n{detail}")
            raise AssertionError(f"first SetBit failed: {probe}\n{detail}")
        for col in (5, SLICE_WIDTH + 5, 2 * SLICE_WIDTH + 5):
            for row in (0, 1):
                out = _post(http[0], "/index/si/query",
                            f"SetBit(frame=f1, rowID={row}, columnID={col})")
                assert out["results"][0] is True, out

        out = _post(http[0], "/index/si/query",
                    "Count(Intersect(Bitmap(frame=f1, rowID=0), "
                    "Bitmap(frame=f1, rowID=1)))")
        assert out["results"][0] == 3, out

        out = _post(http[0], "/index/si/query", "TopN(frame=f1, n=2)")
        pairs = [(p["id"], p["count"]) for p in out["results"][0]]
        assert pairs == [(1, 4), (0, 3)], out

        # src-intersection TopN rides the RCSRC descriptor: counts are
        # |row ∩ src| over the global mesh (row0∩row0=3, row1∩row0=3)
        out = _post(http[0], "/index/si/query",
                    "TopN(Bitmap(frame=f1, rowID=0), frame=f1, n=2)")
        pairs = [(p["id"], p["count"]) for p in out["results"][0]]
        assert sorted(pairs) == [(0, 3), (1, 3)], out

        # tanimoto form: fused three-vector program + host band math.
        # src=row0 (|src|=3): row0 similarity 100 > 50 qualifies;
        # row1: inter=3, union=4 -> ceil(75) > 50 qualifies too.
        out = _post(http[0], "/index/si/query",
                    "TopN(Bitmap(frame=f1, rowID=0), frame=f1, n=2, "
                    "tanimotoThreshold=50)")
        pairs = [(p["id"], p["count"]) for p in out["results"][0]]
        assert sorted(pairs) == [(0, 3), (1, 3)], out

        # the collective ran on BOTH ranks (the device-serving counters
        # live in the shared MeshManager each rank's executor exposes)
        for r in (0, 1):
            vars_ = _get(http[r], "/debug/vars")
            mesh = vars_.get("mesh") or {}
            assert mesh.get("count", 0) >= 1, (r, mesh)
            assert mesh.get("topn", 0) >= 1, (r, mesh)
            assert mesh.get("stage", 0) >= 1, (r, mesh)

        # write replication: rank 1's own holder answers from the HOST
        # path (its executor has the device path disabled) with the
        # bits that traveled the descriptor stream
        out = _post(http[1], "/index/si/query",
                    "Count(Bitmap(frame=f1, rowID=1))")
        assert out["results"][0] == 4, out

        # attr replication: SetRowAttrs rides the PQL descriptor, so a
        # Bitmap read on rank 1 attaches the attrs
        _post(http[0], "/index/si/query",
              'SetRowAttrs(frame=f1, rowID=1, color="red")')
        out = _post(http[1], "/index/si/query", "Bitmap(frame=f1, rowID=1)")
        assert out["results"][0]["attrs"] == {"color": "red"}, out

        # a mutation sent to a worker rank is rejected, not silently
        # applied to one replica
        out = _post(http[1], "/index/si/query",
                    "SetBit(frame=f1, rowID=5, columnID=1)")
        assert "SPMD rank 0" in out.get("error", ""), out

        # schema mutations on a worker rank are rejected the same way
        # (a worker-local create would diverge the replicas: its
        # broadcaster is a Nop, so the change never reaches the
        # descriptor stream)
        out = _post(http[1], "/index/rogue", "{}")
        assert "SPMD rank 0" in out.get("error", ""), out
        out = _post(http[1], "/index/si/frame/rogue", "{}")
        assert "SPMD rank 0" in out.get("error", ""), out

        # bulk import rides the descriptor stream too: POST protobuf
        # /import to rank 0, then read the bits back from rank 1's
        # host path
        import sys as _sys
        _sys.path.insert(0, repo)
        from pilosa_tpu.wire import pb

        ireq = pb.ImportRequest()
        ireq.index, ireq.frame, ireq.slice = "si", "f1", 0
        ireq.row_ids.extend([30, 30, 30])
        ireq.column_ids.extend([100, 200, 300])
        breq = urllib.request.Request(
            f"http://127.0.0.1:{http[0]}/import",
            data=ireq.SerializeToString(), method="POST",
            headers={"Content-Type": "application/x-protobuf"})
        with urllib.request.urlopen(breq, timeout=30) as r:
            r.read()
        out = _post(http[1], "/index/si/query",
                    "Count(Bitmap(frame=f1, rowID=30))")
        assert out["results"][0] == 3, out
    finally:
        # rank 0 first: its shutdown broadcasts the STOP descriptor
        # while rank 1's worker is still alive to receive it.
        procs[0].send_signal(signal.SIGTERM)
        try:
            procs[0].wait(timeout=30)
        except subprocess.TimeoutExpired:
            procs[0].kill()
        procs[1].send_signal(signal.SIGTERM)
        try:
            procs[1].wait(timeout=30)
        except subprocess.TimeoutExpired:
            procs[1].kill()


class TestDescriptorUnits:
    """Descriptor-execution units, no multi-process runtime needed
    (SpmdServer built without __init__ — these methods touch only the
    holder / apply_query seams)."""

    def _bare(self, holder=None):
        from pilosa_tpu.parallel.spmd import SpmdServer

        s = object.__new__(SpmdServer)
        s.holder = holder
        s.apply_message = None
        s.apply_query = None
        return s

    def test_import_timestamp_epoch_zero_survives(self, tmp_path):
        # 1970-01-01T00:00:00 is a legitimate timestamp and must keep
        # its time-quantum view fan-out (ADVICE r3: 0-as-None dropped it)
        import base64
        from datetime import datetime

        import numpy as np

        from pilosa_tpu.core import Holder
        from pilosa_tpu.parallel.spmd import _OP_IMPORT, _TS_NONE

        holder = Holder(str(tmp_path / "d"))
        holder.open()
        idx = holder.create_index("i")
        idx.create_frame("f", time_quantum="YMD")
        s = self._bare(holder)

        epoch = int(datetime(1970, 1, 1).timestamp() -
                    datetime(1970, 1, 1).timestamp())  # 0 by construction
        desc = {
            "op": _OP_IMPORT, "index": "i", "frame": "f",
            "rows": base64.b64encode(
                np.array([1, 2], dtype=np.uint64).tobytes()).decode(),
            "cols": base64.b64encode(
                np.array([10, 20], dtype=np.uint64).tobytes()).decode(),
            "ts": base64.b64encode(
                np.array([epoch, _TS_NONE], dtype=np.int64).tobytes()
            ).decode(),
        }
        s._execute_import(desc)
        f = holder.frame("i", "f")
        # epoch-0 bit landed in the 1970 time views
        time_views = [v for v in f.views if "1970" in v]
        assert time_views, sorted(f.views)
        # the None-timestamp bit produced no time views of its own —
        # every time view present is a 1970 one from the epoch-0 bit
        assert all("1970" in v for v in f.views
                   if v != "standard"), sorted(f.views)
        holder.close()

    def test_pql_descriptor_allowlist(self):
        from pilosa_tpu.parallel.spmd import _OP_PQL

        s = self._bare()
        calls = []
        s.apply_query = lambda index, q: calls.append((index, q)) or [True]
        # allowed: attr writes
        s._execute_pql({"op": _OP_PQL, "index": "i",
                        "pql": 'SetRowAttrs(frame=f, rowID=1, color="red")'})
        assert calls
        # a read riding the PQL op would deadlock rank 0 (re-enters
        # SpmdServer._mu via executor -> _spmd.count) — must raise
        with pytest.raises(ValueError, match="non-attr-write"):
            s._execute_pql({"op": _OP_PQL, "index": "i",
                            "pql": "Count(Bitmap(frame=f, rowID=1))"})


class TestDescriptorFaults:
    """Fault paths of the descriptor plane (VERDICT r4 #6), single
    process: corruption rejects cleanly, half-valid payloads never
    dispatch, gate disagreement skips collectives without hanging."""

    def test_corrupt_payloads_raise_cleanly(self):
        import numpy as np

        from pilosa_tpu.parallel.spmd import _decode, _encode

        for bad in (
            np.frombuffer(b"\xff" * 32, dtype=np.uint8),
            np.frombuffer(b'{"not": "a descriptor"}', dtype=np.uint8),
            np.frombuffer(b'{"op": "Count"}', dtype=np.uint8),
            np.frombuffer(b"[1, 2, 3]", dtype=np.uint8),
            _encode({"op": 1, "index": "i"})[:10],
        ):
            with pytest.raises((ValueError, KeyError)):
                _decode(bad)

    def test_roundtrip_survives(self):
        from pilosa_tpu.parallel.spmd import _decode, _encode

        d = {"op": 4, "index": "i", "frame": "f", "row": 1, "col": 2,
             "ts": "", "clear": False}
        assert _decode(_encode(d)) == d

    def test_unknown_op_raises_not_hangs(self, tmp_path):
        from pilosa_tpu.core import Holder
        from pilosa_tpu.parallel.spmd import SpmdServer

        h = Holder(str(tmp_path / "d"))
        h.open()
        srv = SpmdServer(h)
        with pytest.raises(ValueError, match="unknown descriptor op"):
            srv._run({"op": 999})

    def test_gate_disagreement_skips_and_recovers(self, tmp_path):
        import numpy as np
        from jax.experimental import multihost_utils as mhu

        from pilosa_tpu import SLICE_WIDTH
        from pilosa_tpu.core import Holder
        from pilosa_tpu.parallel.plan import _lower_tree
        from pilosa_tpu.parallel.spmd import SpmdServer
        from pilosa_tpu.pql import parse_string

        h = Holder(str(tmp_path / "d"))
        h.open()
        f = h.create_index_if_not_exists("i") \
            .create_frame_if_not_exists("g")
        for s in range(2):
            f.set_bit(1, s * SLICE_WIDTH + 3)
        srv = SpmdServer(h)
        tree = parse_string("Count(Bitmap(frame=g, rowID=1))") \
            .calls[0].children[0]
        leaves = []
        shape = _lower_tree(h, "i", tree, leaves)

        real = mhu.process_allgather

        def disagree(x, *a, **kw):
            out = np.atleast_1d(np.asarray(real(x, *a, **kw))).copy()
            return np.concatenate([out, out + 1])

        try:
            mhu.process_allgather = disagree
            assert srv._gate(b"prog") is False
            assert srv.count("i", shape, leaves, [0, 1], 2) is None
        finally:
            mhu.process_allgather = real
        # re-agreement: the collective serves again
        assert srv.count("i", shape, leaves, [0, 1], 2) == 2

    def test_format_disagreement_skips_and_recovers(self, tmp_path):
        """Per-shard format agreement (ISSUE 16): the gate fingerprint
        covers each staged view's sparse/dense per-slice picks, so a
        rank whose PR-14 format choice diverged (sparse where another
        rank went dense) changes the fingerprint — mismatched ranks
        skip the collective together, the executor serves the host
        fold, and re-agreement recovers the device path."""
        from pilosa_tpu import SLICE_WIDTH
        from pilosa_tpu.core import Holder
        from pilosa_tpu.executor import Executor
        from pilosa_tpu.parallel.plan import _lower_tree
        from pilosa_tpu.parallel.spmd import SpmdServer
        from pilosa_tpu.pql import parse_string

        h = Holder(str(tmp_path / "d"))
        h.open()
        f = h.create_index_if_not_exists("i") \
            .create_frame_if_not_exists("g")
        for s in range(2):
            f.set_bit(1, s * SLICE_WIDTH + 3)
        srv = SpmdServer(h)
        ex = Executor(h, use_device=True, device_min_work=0)
        ex.set_spmd(srv)
        q = parse_string("Count(Bitmap(frame=g, rowID=1))")
        tree = q.calls[0].children[0]
        leaves: list = []
        shape = _lower_tree(h, "i", tree, leaves)

        # Baseline: the collective serves and stages the view.
        assert srv.count("i", shape, leaves, [0, 1], 2) == 2
        sv = srv.manager._views[("i", "g", "standard")]

        # The per-shard format vector is part of the fingerprint: a
        # sparse<->dense flip on one shard changes the gated blob, so
        # real ranks with diverged picks would land on different crcs.
        blob0 = srv.manager.staged_format_blob("i", {("g", "standard")})
        sv.slice_formats[0] ^= 1
        blob1 = srv.manager.staged_format_blob("i", {("g", "standard")})
        sv.slice_formats[0] ^= 1
        assert blob0 != blob1

        # Simulate that divergence at the gate (world size 1 can't
        # disagree with itself): capture the fingerprint and force the
        # skip verdict a mismatch produces. The collective must skip
        # CLEANLY — no dispatch, None back to the caller — and the
        # executor seam turns that into a host-path answer.
        real_gate = srv._gate
        seen: list = []

        def veto_gate(blob):
            seen.append(blob)
            return False

        try:
            srv._gate = veto_gate
            assert srv.count("i", shape, leaves, [0, 1], 2) is None
            assert seen  # the count reached the gate, then skipped
            assert ex.execute("i", q)[0] == 2  # host fallback serves
        finally:
            srv._gate = real_gate
        # re-agreement: the device collective serves again, bit-exact
        assert srv.count("i", shape, leaves, [0, 1], 2) == 2
        h.close()


class TestBsiSumDescriptor:
    """BSISUM descriptor differential (ISSUE 16): BSI aggregates served
    through the SPMD descriptor plane — world size 1 on CPU collapses
    broadcast/allgather to identity, so the full broadcast + gate +
    psum machinery runs in-process — must be bit-exact against the host
    roaring fold AND the python oracle over the same holder: negatives,
    multi-slice, plane boundaries, filtered forms."""

    def _setup(self, tmp_path):
        import random

        from pilosa_tpu.bsi import FieldSchema
        from pilosa_tpu.core import Holder
        from pilosa_tpu.executor import Executor
        from pilosa_tpu.parallel.spmd import SpmdServer

        schema = FieldSchema("val", -4000, 4000)
        h = Holder(str(tmp_path / "d"))
        h.open()
        f = h.create_index_if_not_exists("i") \
            .create_frame_if_not_exists("f")
        f.create_field_if_not_exists(schema)
        rng = random.Random(7)
        vals = {}
        # plane boundaries both signs, zero, extremes — then random
        bnd = [0, -4000, 4000, 1, -1, 2047, -2048, 255, -256, 1024]
        for s in range(2):  # multi-slice: partials cross slices
            cols = sorted(rng.sample(range(SLICE_WIDTH), 40))
            for i, c in enumerate(cols):
                v = bnd[i] if i < len(bnd) else rng.randint(-4000, 4000)
                vals[s * SLICE_WIDTH + c] = v
                f.set_value("val", s * SLICE_WIDTH + c, v)
        srv = SpmdServer(h)
        dev = Executor(h, use_device=True, device_min_work=0)
        dev.set_spmd(srv)
        host = Executor(h, use_device=False)
        return h, vals, host, dev, srv

    def test_sum_min_max_vs_host_and_oracle(self, tmp_path):
        from pilosa_tpu.pql import parse_string

        h, vals, host, dev, srv = self._setup(tmp_path)
        try:
            agg0 = srv.manager.stats.copy().get("bsi_aggregate", 0)
            for pql in ('Sum(frame="f", field="val")',
                        'Min(frame="f", field="val")',
                        'Max(frame="f", field="val")'):
                want = host.execute("i", parse_string(pql))[0]
                got = dev.execute("i", parse_string(pql))[0]
                assert got == want, pql
            got = dev.execute(
                "i", parse_string('Sum(frame="f", field="val")'))[0]
            assert got == {"value": sum(vals.values()),
                           "count": len(vals)}
            for name, fn in (("Min", min), ("Max", max)):
                want_v = fn(vals.values())
                got = dev.execute(
                    "i", parse_string(f'{name}(frame="f", '
                                      f'field="val")'))[0]
                assert got == {
                    "value": want_v,
                    "count": sum(1 for v in vals.values()
                                 if v == want_v)}
            # Sum rode the BSISUM descriptor (negatives present → two
            # passes), and the device route served it.
            assert srv.manager.stats.copy() \
                .get("bsi_aggregate", 0) > agg0
            assert dev.route_stats.copy() \
                .get("count_bsi-mesh", 0) >= 3
        finally:
            h.close()

    def test_filtered_sum_rides_rcsrc_descriptor(self, tmp_path):
        from pilosa_tpu.pql import parse_string

        h, vals, host, dev, srv = self._setup(tmp_path)
        try:
            f = h.index("i").frame("f")
            keep = {c for i, c in enumerate(sorted(vals)) if i % 2 == 0}
            for c in keep:
                f.set_bit(7, c)
            pql = ('Sum(Bitmap(frame="f", rowID=7), '
                   'frame="f", field="val")')
            want = {"value": sum(vals[c] for c in keep),
                    "count": len(keep)}
            assert host.execute("i", parse_string(pql))[0] == want
            assert dev.execute("i", parse_string(pql))[0] == want
        finally:
            h.close()

    def test_descriptor_matches_manager_collective(self, tmp_path):
        """srv.bsi_sum must return exactly what the single-host
        MeshManager collective returns for the same view — the SPMD
        plane adds broadcast+gate around the SAME program, never a
        different reduction."""
        h, vals, host, dev, srv = self._setup(tmp_path)
        try:
            view = "bsi.val"
            got = srv.bsi_sum("i", "f", view, [0, 1], 2)
            want = srv.manager.bsi_plane_counts("i", "f", view,
                                                [0, 1], 2)
            assert got is not None and want is not None
            assert got == want
        finally:
            h.close()
