"""Socket adapter: mounts a Handler on a stdlib threading HTTP server.

The reference serves gorilla/mux over net/http (server.go:146); here the
transport-agnostic Handler.handle() is adapted onto
http.server.ThreadingHTTPServer so every request runs on its own thread
(the executor underneath does its own per-slice fan-out).
"""

from __future__ import annotations

import logging
import threading
import urllib.parse
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional, Tuple

from .handler import FRONT, FrontTimes


class APIServer:
    """Owns the listening socket + serve thread for one Handler."""

    def __init__(self, handler, host: str = "127.0.0.1", port: int = 0,
                 logger=None):
        self.handler = handler
        self.logger = logger
        api = self

        class _Request(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, fmt, *args):
                # The access line, at debug: [log] level = "debug".
                lg = api.logger
                if lg is not None and lg.isEnabledFor(logging.DEBUG):
                    lg.debug("http: " + fmt % args)

            def log_error(self, fmt, *args):  # a malformed request
                if api.logger is not None:
                    api.logger.info("http: " + fmt % args)

            def _dispatch(self):
                # A query's own time here (FrontTimes): opened first,
                # closed after the last write.
                front = token = None
                if self.command == "POST" and \
                        self.path.partition("?")[0].endswith("/query"):
                    front = FrontTimes()
                    token = FRONT.set(front)
                try:
                    parsed = urllib.parse.urlsplit(self.path)
                    params = {k: v[-1] for k, v in
                              urllib.parse.parse_qs(parsed.query).items()}
                    length = int(self.headers.get("Content-Length") or 0)
                    body = self.rfile.read(length) if length else b""
                    resp = api.handler.handle(
                        self.command, parsed.path.rstrip("/") or "/",
                        params, dict(self.headers.items()), body)
                    self.send_response(resp.status)
                    for k, v in resp.headers.items():
                        self.send_header(k, v)
                    self.send_header("Content-Length", str(len(resp.body)))
                    self.end_headers()
                    self.wfile.write(resp.body)
                finally:
                    if front is not None:
                        FRONT.reset(token)
                        took = front.close()
                        note = getattr(api.handler, "note_front", None)
                        if took is not None and note is not None:
                            note(*took)

            do_GET = do_POST = do_DELETE = do_PATCH = _dispatch

        # A herd of concurrent clients opening fresh connections (the
        # reference serves via Go's net/http, whose listener rides the
        # kernel SOMAXCONN backlog) overflows Python's default backlog
        # of 5 and the kernel RSTs the overflow — observed as
        # ConnectionResetError at 50+ simultaneous connects. Raise the
        # accept backlog before bind (class attr: bind happens in
        # __init__).
        srv_cls = type("_PilosaHTTPServer", (ThreadingHTTPServer,),
                       {"request_queue_size": 128})
        self._server = srv_cls((host, port), _Request)
        self._thread: Optional[threading.Thread] = None

    @property
    def address(self) -> Tuple[str, int]:
        return self._server.server_address[:2]

    @property
    def host(self) -> str:
        h, p = self.address
        return f"{h}:{p}"

    def start(self):
        self._thread = threading.Thread(target=self._server.serve_forever,
                                        name="pilosa-http", daemon=True)
        self._thread.start()

    def close(self):
        self._server.shutdown()
        self._server.server_close()
        if self._thread is not None:
            self._thread.join(timeout=5)


def serve(handler, host: str = "127.0.0.1", port: int = 0,
          logger=None) -> APIServer:
    """Start serving `handler`; returns the running APIServer."""
    srv = APIServer(handler, host, port, logger=logger)
    srv.start()
    return srv
