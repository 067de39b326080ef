"""Closed-loop clients over HTTP: one thread and one keep-alive connection
each, the next op sent when the last one's reply is read. The entry driven is
`POST /index/<index>/query`, as any client library does."""

from __future__ import annotations

import http.client
import json
import socket
import threading
import time
from typing import Callable, List, Optional

from .window import READ_KINDS, Done

REQUEST_TIMEOUT_S = 120.0


class Clients:
    """`n` clients against one server. `op_at(stream, i)` gives the bound op
    of index i of a stream, or None past its end; client c of n takes ops
    c, c + n, c + 2n, ... of each stream and remembers where it stands."""

    def __init__(self, host: str, port: int, index: str, n: int,
                 op_at: Callable, profile_one_in: int = 0):
        self.host, self.port, self.n = host, port, n
        self.path = f"/index/{index}/query"
        self.op_at = op_at
        self.profile_one_in = profile_one_in
        self._conns: List[Optional[http.client.HTTPConnection]] = [None] * n
        self._pos: dict = {}

    def post(self, c: int, pql: str, profile: bool):
        """(status, parsed body | error text, t_send, t_done)."""
        path = self.path + ("?profile=true" if profile else "")
        body = pql.encode()
        for attempt in (0, 1):
            conn = self._conns[c]
            if conn is None:
                conn = self._conns[c] = http.client.HTTPConnection(
                    self.host, self.port, timeout=REQUEST_TIMEOUT_S)
                # http.client sends headers and body apart: without this a
                # delayed ACK adds 40 ms to every request.
                conn.connect()
                conn.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY,
                                     1)
            t0 = time.monotonic()
            try:
                conn.request("POST", path, body=body)
                resp = conn.getresponse()
                data = resp.read()
                t1 = time.monotonic()
                try:
                    return resp.status, json.loads(data), t0, t1
                except ValueError:
                    return resp.status, data[:200].decode("replace"), t0, t1
            except (http.client.RemoteDisconnected, ConnectionResetError,
                    BrokenPipeError) as e:
                # A keep-alive connection the server closed while idle:
                # reopen once. Anything else is the op's failure.
                conn.close()
                self._conns[c] = None
                if attempt:
                    return 0, repr(e), t0, time.monotonic()
            except OSError as e:
                conn.close()
                self._conns[c] = None
                return 0, repr(e), t0, time.monotonic()

    def _one(self, c: int, seq: int, op, profile: bool,
             stream: str = "window") -> Done:
        reqs, ok, prof = [], True, None
        for j, pql in enumerate(op.pql):
            # A read's profile rides its one request; an update's rides the
            # SetBit (its wal_commit phase).
            want_prof = profile and j == (0 if op.kind == "update"
                                          else len(op.pql) - 1)
            status, body, t0, t1 = self.post(c, pql, want_prof)
            result = None
            if status == 200 and isinstance(body, dict) \
                    and "results" in body:
                result = body["results"][0]
                if want_prof:
                    prof = body.get("profile")
            else:
                ok = False
                result = body
            reqs.append((pql, t0, t1, status, result))
            if not ok:
                break
        return Done(c, seq, op.kind, reqs[0][1], reqs[-1][2], ok,
                    tuple(reqs), prof, None, stream)

    def run(self, stream: str, *, seconds: Optional[float] = None,
            ops_per_client: Optional[int] = None,
            profiled: bool = False) -> List[Done]:
        """One phase: every client issues until `seconds` have passed since
        the phase began (then finishes the op in flight: the drain), or for
        `ops_per_client` ops. Returns every op, completed."""
        logs: List[List[Done]] = [[] for _ in range(self.n)]
        gate = threading.Barrier(self.n + 1)
        t_stop = [float("inf")]

        def loop(c: int):
            pos = self._pos.get((stream, c), 0)
            gate.wait()
            done = 0
            while time.monotonic() < t_stop[0]:
                if ops_per_client is not None and done >= ops_per_client:
                    break
                seq = c + pos * self.n
                op = self.op_at(stream, seq)
                if op is None:
                    break
                prof = profiled and self.profile_one_in > 0 and (
                    op.kind not in READ_KINDS
                    or pos % self.profile_one_in == c % self.profile_one_in)
                logs[c].append(self._one(c, seq, op, prof, stream))
                pos += 1
                done += 1
            self._pos[(stream, c)] = pos

        threads = [threading.Thread(target=loop, args=(c,), daemon=True,
                                    name=f"client-{c}")
                   for c in range(self.n)]
        for t in threads:
            t.start()
        if seconds is not None:
            t_stop[0] = time.monotonic() + seconds
        gate.wait()
        for t in threads:
            t.join()
        return [d for log in logs for d in log]

    def close(self) -> None:
        for conn in self._conns:
            if conn is not None:
                conn.close()
