"""The server child: `python -m pilosa_tpu.ctl.main server` with the
configuration's TOML, started exactly as a user starts it; it alone holds the
chip. The parent talks to it over HTTP and never imports JAX while it lives."""

from __future__ import annotations

import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request
from typing import List, Optional

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH_DIR)


class ServerError(Exception):
    pass


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Server:
    def __init__(self, argv: List[str], log_path: str,
                 env_extra: Optional[dict] = None):
        """`argv` is everything after the interpreter; `{port}` in it is
        replaced by the port chosen."""
        self.port = free_port()
        self.host = "127.0.0.1"
        self.base = f"http://{self.host}:{self.port}"
        self.log_path = log_path
        env = dict(os.environ, **(env_extra or {}))
        # BENCH_RUN is the driver's own; nothing here reads it.
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        self.log = open(log_path, "ab")
        self.proc = subprocess.Popen(
            [sys.executable] + [a.replace("{port}", str(self.port))
                                for a in argv],
            cwd=REPO, env=env, stdout=self.log, stderr=subprocess.STDOUT)

    @classmethod
    def pilosa(cls, toml: str, data_dir: str, log_path: str, *,
               traced: bool = False, env_extra: Optional[dict] = None):
        entry = ([os.path.join(BENCH_DIR, "traced_server.py")] if traced
                 else ["-m", "pilosa_tpu.ctl.main"])
        return cls(entry + ["server", "-c", toml, "-d", data_dir,
                            "-b", "127.0.0.1:{port}"], log_path, env_extra)

    def http(self, path: str, body: Optional[bytes] = None,
             timeout: float = 60.0) -> bytes:
        req = urllib.request.Request(self.base + path, data=body)
        try:
            with urllib.request.urlopen(req, timeout=timeout) as r:
                return r.read()
        except urllib.error.HTTPError as e:
            raise ServerError(f"{path}: HTTP {e.code}: {e.read()[:300]!r}")

    def vars(self) -> dict:
        return json.loads(self.http("/debug/vars"))

    def metrics(self) -> dict:
        """Prometheus text -> {"name{labels}": value}."""
        out = {}
        for line in self.http("/metrics").decode().splitlines():
            if line and not line.startswith("#"):
                name, _, val = line.rpartition(" ")
                try:
                    out[name] = float(val)
                except ValueError:
                    pass
        return out

    def alive(self) -> bool:
        return self.proc.poll() is None

    def wait_up(self, timeout: float = 300.0) -> None:
        t0 = time.monotonic()
        while time.monotonic() - t0 < timeout:
            if not self.alive():
                raise ServerError(
                    f"server exited with {self.proc.returncode} at start-up")
            try:
                with urllib.request.urlopen(self.base + "/status", timeout=2):
                    return
            except OSError:
                time.sleep(0.1)
        raise ServerError(f"server not up after {timeout:.0f} s")

    def signal(self, sig: int) -> None:
        self.proc.send_signal(sig)

    def stop(self, timeout: float = 90.0) -> bool:
        """SIGTERM and a clean close; True if it went by itself."""
        clean = True
        if self.alive():
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=timeout)
            except subprocess.TimeoutExpired:
                clean = False
                self.proc.kill()
                self.proc.wait()
        if not self.log.closed:
            self.log.close()
        return clean

    def kill(self) -> None:
        """SIGKILL, as a crash: no clean close, so what the program held back
        from the disk stays off it."""
        if self.alive():
            self.proc.kill()
        self.proc.wait()
        if not self.log.closed:
            self.log.close()

    def log_tail(self, n: int = 4000) -> str:
        try:
            with open(self.log_path, "rb") as f:
                f.seek(0, os.SEEK_END)
                f.seek(max(0, f.tell() - n))
                return f.read().decode(errors="replace")
        except OSError:
            return ""
