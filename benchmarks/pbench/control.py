#!/usr/bin/env python3
"""The control: the plain reference put in the program's place behind the
same HTTP entry, with one guarantee of the configuration broken. A run
against it has to come out `correct: false`; that is what shows the comparison
can fail.

    sound             nothing broken (the harness's own check of itself)
    stale_writes      a SetBit is acknowledged and never applied: "an
                      acknowledged SetBit is in the next Count" (and the
                      next TopN) is broken
    approximate_topn  TopN from every other slice, doubled: an approximate
                      answer where the configuration says exact
    alter_answer      one read in seven answers one too many

(`lost_wal`, the control of the durability look, is not here: it is the
program itself with its unsafe WAL path on; see harness.PROGRAM_CONTROLS.)

It serves from the reference's `live()` tables, whichever reference the
configuration names, holds no chip and imports nothing of the program. A
reference of one frame is asked by key (`live.answer(key)`, `live.set_bit(row,
column)`), the key read from the PQL here. A reference of several frames takes
the PQL whole: its `live()` gives `answer_pql(pql)` (a count, or a ranking as
(row, count) pairs), and `set_bit(row, column, frame)` with the frame the
SetBit names; so does its approximate twin, where it has one.
Started by the harness as a child:  control.py <mode> <reference.pickle> <port>
"""

from __future__ import annotations

import json
import os
import pickle
import re
import signal
import sys
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

MODES = ("sound", "stale_writes", "approximate_topn", "alter_answer")

_ROW = re.compile(r"rowID=(\d+)")
_COL = re.compile(r"columnID=(\d+)")
_N = re.compile(r"\bn=(\d+)")
_FRAME = re.compile(r'frame="([^"]*)"')


class Answers:
    def __init__(self, mode: str, ref, approx=None):
        self.mode, self.ref, self.approx = mode, ref, approx
        self.mu = threading.Lock()
        self.live = ref.live()  # the tables, with the writes it applies
        self.reads = 0

    def _count_key(self, pql: str):
        rows = [int(r) for r in _ROW.findall(pql)]
        inner = pql[len("Count("):]
        if inner.startswith("Bitmap("):
            return ("R", rows[0])
        op = inner[:inner.index("(")]
        if len(rows) == 2:
            a, b = rows
            if op == "Difference":
                return ("D", a, b)
            return ("I" if op == "Intersect" else "U", min(a, b), max(a, b))
        if op == "Difference":
            return ("DA", rows[0])
        return ("IA",) if op == "Intersect" else ("UA",)

    def answer(self, pql: str):
        whole = hasattr(self.live, "answer_pql")  # several frames
        if pql.startswith("SetBit("):
            row = int(_ROW.search(pql).group(1))
            col = int(_COL.search(pql).group(1))
            named = _FRAME.findall(pql)[:1] if whole else ()
            if self.mode != "stale_writes":
                with self.mu:
                    self.live.set_bit(row, col, *named)
            return True
        with self.mu:
            self.reads += 1
            bump = int(self.mode == "alter_answer" and self.reads % 7 == 0)
        topn = pql.startswith("TopN(")
        source = self.approx if topn and self.mode == "approximate_topn" \
            else self.live
        with self.mu:
            if whole:
                got = source.answer_pql(pql)
            elif pql.startswith("Count("):
                got = source.answer(self._count_key(pql))
            elif topn:
                rows = _ROW.findall(pql)
                got = source.answer(("T", int(rows[0]) if rows else None,
                                     int(_N.search(pql).group(1))))
            else:
                raise ValueError(f"the control does not speak {pql[:40]!r}")
        if isinstance(got, int):
            return got + bump
        if bump and got:
            got = [(got[0][0], got[0][1] + 1)] + list(got[1:])
        return [{"id": r, "count": c} for r, c in got]


def main() -> int:
    mode, ref_path, port = sys.argv[1], sys.argv[2], int(sys.argv[3])
    assert mode in MODES, mode
    with open(ref_path, "rb") as f:
        refs = pickle.load(f)
    answers = Answers(mode, refs["ref"], refs.get("approx"))

    class Handler(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        def log_message(self, *a):
            pass

        def _send(self, code: int, obj) -> None:
            body = json.dumps(obj).encode()
            self.send_response(code)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def do_GET(self):
            if self.path.startswith("/debug/vars"):
                self._send(200, {"control": mode})
            elif self.path.startswith("/metrics"):
                self.send_response(200)
                self.send_header("Content-Length", "0")
                self.end_headers()
            else:
                self._send(200, {"status": "control"})

        def do_POST(self):
            n = int(self.headers.get("Content-Length") or 0)
            pql = self.rfile.read(n).decode()
            try:
                self._send(200, {"results": [answers.answer(pql)]})
            except Exception as e:  # noqa: BLE001 - reported to the client
                self._send(400, {"error": repr(e)})

    ThreadingHTTPServer.request_queue_size = 256
    httpd = ThreadingHTTPServer(("127.0.0.1", port), Handler)
    httpd.daemon_threads = True
    signal.signal(signal.SIGTERM, lambda *a: threading.Thread(
        target=httpd.shutdown, daemon=True).start())
    httpd.serve_forever()
    httpd.server_close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
