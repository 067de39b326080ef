"""CLI tests (model: /root/reference/cmd/*_test.go config plumbing +
ctl command logic; live-node paths reuse the in-process Server)."""

import io
import json
import os
import socket

import pytest

from pilosa_tpu import SLICE_WIDTH
from pilosa_tpu.config import Config
from pilosa_tpu.ctl.main import (
    build_config,
    cmd_check,
    cmd_inspect,
    cmd_sort,
    main,
    make_parser,
    parse_import_rows,
)


def test_parser_covers_all_subcommands():
    ap = make_parser()
    for cmd in ["server", "import", "export", "backup", "restore",
                "bench", "check", "inspect", "sort", "config"]:
        # every subcommand parses its own --help without crashing
        with pytest.raises(SystemExit) as e:
            ap.parse_args([cmd, "--help"])
        assert e.value.code == 0


def test_config_command(capsys):
    assert main(["config"]) == 0
    out = capsys.readouterr().out
    cfg = Config.from_toml(out, is_text=True)
    assert cfg.host == Config().host


def test_build_config_precedence(tmp_path, monkeypatch):
    toml = tmp_path / "c.toml"
    toml.write_text('host = "from-toml:1"\ndata-dir = "/toml-dir"\n')
    ap = make_parser()
    # TOML only
    args = ap.parse_args(["server", "-c", str(toml)])
    cfg = build_config(args)
    assert cfg.host == "from-toml:1"
    assert cfg.data_dir == "/toml-dir"
    # env overrides toml
    monkeypatch.setenv("PILOSA_TPU_HOST", "from-env:2")
    cfg = build_config(ap.parse_args(["server", "-c", str(toml)]))
    assert cfg.host == "from-env:2"
    # flag overrides env
    cfg = build_config(ap.parse_args(
        ["server", "-c", str(toml), "-b", "from-flag:3", "-d", "/flag-dir"]))
    assert cfg.host == "from-flag:3"
    assert cfg.data_dir == "/flag-dir"


def test_parse_import_rows():
    rows = parse_import_rows(["1,2", "3,4,2017-04-01T12:30", "", " 5 , 6 "])
    assert rows[0] == (1, 2, 0)
    assert rows[1][0:2] == (3, 4) and rows[1][2] > 0
    assert rows[2] == (5, 6, 0)
    with pytest.raises(ValueError, match="bad row"):
        parse_import_rows(["justone"])


def test_sort_orders_by_fragment_then_pos(tmp_path, capsys):
    p = tmp_path / "bits.csv"
    p.write_text(f"5,{SLICE_WIDTH}\n1,7\n0,9\n1,3\n")
    ap = make_parser()
    assert cmd_sort(ap.parse_args(["sort", str(p)])) == 0
    out = capsys.readouterr().out.splitlines()
    # slice 0 first (pos order: row asc then col), then slice 1
    assert out == ["0,9", "1,3", "1,7", f"5,{SLICE_WIDTH}"]


def test_check_and_inspect(tmp_path, capsys):
    from pilosa_tpu.roaring import Bitmap

    b = Bitmap([1, 2, 70000])
    path = tmp_path / "data"
    path.write_bytes(b.to_bytes())
    ap = make_parser()
    assert cmd_check(ap.parse_args(["check", str(path)])) == 0
    assert "ok (3 bits)" in capsys.readouterr().out

    assert cmd_inspect(ap.parse_args(["inspect", str(path)])) == 0
    info = json.loads(capsys.readouterr().out)
    assert [c["key"] for c in info["containers"]] == [0, 1]

    # corrupt the cookie -> check fails
    raw = bytearray(path.read_bytes())
    raw[0] ^= 0xFF
    path.write_bytes(bytes(raw))
    assert cmd_check(ap.parse_args(["check", str(path)])) == 1
    assert "invalid roaring file" in capsys.readouterr().out


class TestLiveNode:
    @pytest.fixture
    def node(self, tmp_path):
        from pilosa_tpu.server import Server

        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        host = f"127.0.0.1:{port}"
        c = Config()
        c.data_dir = str(tmp_path / "data")
        c.host = host
        c.cluster_hosts = [host]
        c.anti_entropy_interval = 3600
        c.polling_interval = 3600
        srv = Server(c)
        srv.open()
        yield host
        srv.close()

    def test_import_export_roundtrip(self, node, tmp_path, capsys):
        csv = tmp_path / "in.csv"
        csv.write_text(f"1,10\n1,20\n2,{SLICE_WIDTH + 5}\n")
        assert main(["import", "--host", node, "-i", "i", "-f", "f",
                     "--create", str(csv)]) == 0
        out_file = tmp_path / "out.csv"
        assert main(["export", "--host", node, "-i", "i", "-f", "f",
                     "-o", str(out_file)]) == 0
        assert out_file.read_text() == f"1,10\n1,20\n2,{SLICE_WIDTH + 5}\n"

    def test_backup_restore_roundtrip(self, node, tmp_path, capsys):
        csv = tmp_path / "in.csv"
        csv.write_text("7,3\n8,9\n")
        main(["import", "--host", node, "-i", "i", "-f", "f", "--create",
              str(csv)])
        tar = tmp_path / "f.tar"
        assert main(["backup", "--host", node, "-i", "i", "-f", "f",
                     "-o", str(tar)]) == 0
        # restore into a second frame on the same node
        from pilosa_tpu.api import InternalClient
        InternalClient(node).create_frame("i", "g")
        assert main(["restore", "--host", node, "-i", "i", "-f", "g",
                     str(tar)]) == 0
        out = tmp_path / "g.csv"
        main(["export", "--host", node, "-i", "i", "-f", "g",
              "-o", str(out)])
        assert out.read_text() == "7,3\n8,9\n"

    def test_bench_set_bit(self, node, capsys):
        assert main(["bench", "--host", node, "--op", "set-bit",
                     "-n", "20"]) == 0
        res = json.loads(capsys.readouterr().out)
        assert res["n"] == 20 and res["ops_per_sec"] > 0

    def test_bench_topn(self, node, capsys):
        assert main(["bench", "--host", node, "--op", "topn",
                     "-n", "5", "--max-row-id", "8",
                     "--max-column-id", "500"]) == 0
        res = json.loads(capsys.readouterr().out)
        assert res["op"] == "topn" and res["ops_per_sec"] > 0

    def test_fleet_panel_live(self, node, capsys):
        from pilosa_tpu.api import InternalClient

        cli = InternalClient(node)
        cli.create_index("i")
        cli.create_frame("i", "f")
        cli.execute_query(None, "i", "SetBit(rowID=1, frame=f, "
                          "columnID=3)", [], remote=False)
        cli.execute_query(None, "i", "Count(Bitmap(rowID=1, "
                          "frame=f))", [], remote=False)
        assert main(["fleet", "--host", node, "-n", "1"]) == 0
        out = capsys.readouterr().out
        assert "pilosa-tpu fleet" in out
        assert "members 1" in out and "healthy 1" in out
        assert node in out and "tiers local:" in out


class TestTopPercentileMerge:
    """`pilosa-tpu top` percentile regression: a scrape whose histogram
    family fans out over several label products (tenant, backend) must
    SUM duplicate `le` buckets, not keep whichever series parsed last —
    the pre-fix parser keyed on (name, labels) but the percentile fold
    overwrote per-le instead of summing."""

    SCRAPE = (
        "# TYPE pilosa_query_phase_us histogram\n"
        'pilosa_query_phase_us_bucket{phase="gather",tenant="a",le="64"} 0\n'
        'pilosa_query_phase_us_bucket{phase="gather",tenant="a",le="256"} 10\n'
        'pilosa_query_phase_us_bucket{phase="gather",tenant="a",le="+Inf"} 10\n'
        'pilosa_query_phase_us_bucket{phase="gather",tenant="b",le="64"} 90\n'
        'pilosa_query_phase_us_bucket{phase="gather",tenant="b",le="256"} 90\n'
        'pilosa_query_phase_us_bucket{phase="gather",tenant="b",le="+Inf"} 90\n'
        'pilosa_query_phase_us_bucket{phase="plan",tenant="a",le="64"} 4\n'
        'pilosa_query_phase_us_bucket{phase="plan",tenant="a",le="+Inf"} 4\n'
    )

    def test_mixed_label_percentiles_sum_per_le(self):
        from pilosa_tpu.ctl.main import _hist_percentiles, _parse_prom

        m = _parse_prom(self.SCRAPE)
        p50, p95, p99, n = _hist_percentiles(
            m, "pilosa_query_phase_us", {"phase": "gather"})
        # 100 observations in all: 90 sit at le=64, 10 more by le=256.
        assert n == 100
        assert p50 == 64.0
        assert p95 == 256.0
        assert p99 == 256.0
        # The phase filter still pins series: plan is its own family.
        assert _hist_percentiles(
            m, "pilosa_query_phase_us", {"phase": "plan"})[3] == 4

    def test_duplicate_cumulative_lines_sum_in_parse(self):
        from pilosa_tpu.ctl.main import _parse_prom

        m = _parse_prom('x_total{t="1"} 2\nx_total{t="1"} 3\n'
                        "a_gauge 5\na_gauge 7\n")
        assert m[("x_total", (("t", "1"),))] == 5.0
        assert m[("a_gauge", ())] == 7.0  # gauges: last wins


class TestRenderFleet:
    DOC = {
        "members": 2, "scraped": 1, "healthy": 1,
        "scrape_interval_s": 5.0, "requests_total": 120,
        "phase_percentiles": {
            "gather": {"p50_us": 64.0, "p95_us": 256.0,
                       "p99_us": 256.0, "count": 100}},
        "nodes": {
            "10.0.0.1:10101": {
                "state": "UP", "requests_total": 120,
                "tiers": {"local": 100, "ici": 15, "http": 5},
                "hints": {"backlog": 2},
                "hbm": {"resident_bytes": 2 << 30,
                        "budget_bytes": 4 << 30,
                        "residency_ratio": 0.5},
                "scrape_age_s": 12.0, "error": None},
            "10.0.0.2:10101": {
                "state": "DOWN", "tiers": None,
                "scrape_age_s": None,
                "error": "ConnectionError: down"},
        },
    }

    def test_panel_rows(self):
        from pilosa_tpu.ctl.main import render_fleet

        out = render_fleet("10.0.0.1:10101", self.DOC)
        assert "members 2   scraped 1   healthy 1" in out
        assert "fleet requests 120" in out
        assert "phase gather" in out and "n=100" in out
        assert "tiers local:100/ici:15/http:5" in out
        assert "hints backlog 2" in out
        assert "2.0GiB/4.0GiB (50%)" in out
        # 12 s old against a 5 s interval: flagged stale.
        assert "STALE 12s" in out
        assert "UNSCRAPED (ConnectionError: down)" in out

    def test_fleet_qps_from_previous_snapshot(self):
        from pilosa_tpu.ctl.main import render_fleet

        prev = dict(self.DOC, requests_total=100)
        out = render_fleet("h", self.DOC, prev=prev, dt=2.0)
        assert "qps 10.0" in out


def test_fleet_subcommand_parses():
    from pilosa_tpu.ctl.main import cmd_fleet

    ap = make_parser()
    for cmd in ("fleet", "top"):
        with pytest.raises(SystemExit) as e:
            ap.parse_args([cmd, "--help"])
        assert e.value.code == 0
    args = ap.parse_args(["fleet", "--host", "h:1", "-n", "3",
                          "--interval", "0.5"])
    assert args.fn is cmd_fleet
    assert args.n == 3 and args.interval == 0.5


def test_server_command_full_binary(tmp_path):
    """Boot the real `server` subcommand as a child process, query it
    over HTTP, and shut it down with SIGTERM (the reference's
    MustRunMain full-binary integration, server/server_test.go)."""
    import signal
    import subprocess
    import sys
    import tempfile
    import time
    import urllib.request

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    host = f"127.0.0.1:{port}"
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    # Log to a file, not a pipe: an undrained pipe can fill and block
    # the server mid-request.
    log = tempfile.NamedTemporaryFile(mode="w+", suffix=".log", delete=False)
    proc = subprocess.Popen(
        [sys.executable, "-m", "pilosa_tpu.ctl.main", "server",
         "-d", str(tmp_path / "data"), "-b", host],
        env=env, stdout=log, stderr=subprocess.STDOUT, text=True)
    try:
        deadline = time.time() + 60
        version = None
        while time.time() < deadline:
            try:
                with urllib.request.urlopen(
                        f"http://{host}/version", timeout=2) as r:
                    version = json.loads(r.read())["version"]
                break
            except OSError:
                if proc.poll() is not None:
                    log.seek(0)
                    raise AssertionError(f"server died: {log.read()}")
                time.sleep(0.2)
        assert version, "server never came up"
        body = b'SetBit(rowID=1, frame=f, columnID=2)'
        for path in ("/index/bin", "/index/bin/frame/f"):
            req = urllib.request.Request(
                f"http://{host}{path}", data=b"{}", method="POST")
            with urllib.request.urlopen(req, timeout=5):
                pass
        req = urllib.request.Request(
            f"http://{host}/index/bin/query", data=body, method="POST")
        with urllib.request.urlopen(req, timeout=5) as r:
            assert json.loads(r.read()) == {"results": [True]}
        proc.send_signal(signal.SIGTERM)
        assert proc.wait(timeout=30) == 0
    finally:
        if proc.poll() is None:
            proc.kill()


def test_embedded_example_runs(tmp_path):
    """examples/embedded.py runs end-to-end on the virtual mesh."""
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
           "PYTHONPATH": repo + os.pathsep + os.environ.get("PYTHONPATH", "")}
    r = subprocess.run(
        [sys.executable, os.path.join(repo, "examples", "embedded.py"),
         str(tmp_path / "demo")],
        capture_output=True, text=True, timeout=300, env=env)
    assert r.returncode == 0, r.stderr
    assert "both ads: 2" in r.stdout
    assert "top ads: [(3, 4), (5, 3)]" in r.stdout


def test_server_kill9_durability(tmp_path):
    """Acked SetBits survive a SIGKILL (no clean shutdown): the WAL's
    unbuffered 13-byte ops are the durability point (reference
    roaring.go:617-628), replayed on reopen."""
    import signal
    import subprocess
    import sys
    import tempfile
    import time
    import urllib.request

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    host = f"127.0.0.1:{port}"
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": repo + os.pathsep + os.environ.get("PYTHONPATH", "")}
    log = tempfile.NamedTemporaryFile(mode="w+", suffix=".log", delete=False)
    data_dir = str(tmp_path / "kdata")
    proc = subprocess.Popen(
        [sys.executable, "-m", "pilosa_tpu.ctl.main", "server",
         "-d", data_dir, "-b", host],
        env=env, stdout=log, stderr=subprocess.STDOUT, text=True)
    try:
        deadline = time.time() + 60
        while time.time() < deadline:
            try:
                urllib.request.urlopen(f"http://{host}/version", timeout=2)
                break
            except OSError:
                assert proc.poll() is None, "server died"
                time.sleep(0.2)
        for path, body in [("/index/k", b"{}"), ("/index/k/frame/f", b"{}")]:
            req = urllib.request.Request(f"http://{host}{path}", data=body,
                                         method="POST")
            with urllib.request.urlopen(req, timeout=5):
                pass
        pql = "".join(f"SetBit(rowID=1, frame=f, columnID={c})"
                      for c in (3, 9, 1_048_580))
        req = urllib.request.Request(f"http://{host}/index/k/query",
                                     data=pql.encode(), method="POST")
        with urllib.request.urlopen(req, timeout=10) as r:
            assert b"true" in r.read()
        proc.send_signal(signal.SIGKILL)  # no flush, no close
        proc.wait(timeout=15)
    finally:
        if proc.poll() is None:
            proc.kill()

    from pilosa_tpu.core import Holder

    holder = Holder(data_dir)
    holder.open()
    try:
        cols = []
        for sl in (0, 1):
            frag = holder.fragment("k", "f", "standard", sl)
            if frag is not None:
                cols += [c for _, c in frag.for_each_bit()]
        assert sorted(cols) == [3, 9, 1_048_580]
    finally:
        holder.close()


class TestServerDryRun:
    """Hidden --dry-run seam (reference cmd/root.go:59-71): resolved
    config prints without executing."""

    def test_dry_run_precedence(self, tmp_path, capsys, monkeypatch):
        from pilosa_tpu.ctl.main import main

        cfg = tmp_path / "c.toml"
        cfg.write_text('data-dir = "/from/toml"\nhost = "toml:1"\n')
        # env beats TOML; flag beats env
        monkeypatch.setenv("PILOSA_TPU_HOST", "env:2")
        rc = main(["server", "-c", str(cfg), "-b", "flag:3", "--dry-run"])
        assert rc == 0
        out = capsys.readouterr().out
        assert 'host = "flag:3"' in out
        assert '/from/toml' in out

    def test_dry_run_env_only(self, capsys, monkeypatch):
        from pilosa_tpu.ctl.main import main

        monkeypatch.setenv("PILOSA_TPU_DATA_DIR", "/env/dir")
        rc = main(["server", "--dry-run"])
        assert rc == 0
        assert '/env/dir' in capsys.readouterr().out
