"""Per-query tracing: monotonic-clock spans collected into traces,
retained in bounded rings, and propagated across threads (contextvars)
and across nodes (the X-Pilosa-Trace header, handled by api/).

Design constraints, in order:

1. Zero-ish cost when inactive. Library code calls `span("stage")`
   unconditionally; when no trace is active that is one ContextVar
   read returning a shared no-op singleton. The serving fast path
   (PR 1's fused lone count) must not pay for observability it isn't
   using.
2. Thread-safe by construction, not by locking the hot path. Span
   ids come from itertools.count (atomic in CPython), span lists grow
   by list.append (atomic under the GIL), and the only real lock is
   the Tracer's ring lock, taken once per query at finish().
3. Wall-clock for humans, monotonic for math. Trace start is stamped
   with time.time() for the /debug/queries listing; all durations and
   orderings come from time.monotonic_ns().
"""

from __future__ import annotations

import contextvars
import itertools
import os
import random
import threading
import time
from collections import deque
from contextlib import nullcontext
from typing import Any, Dict, List, Optional

# The active span for this thread/context. Executor pools must carry
# it across submit() boundaries via wrap_ctx().
CURRENT: "contextvars.ContextVar[Optional[Span]]" = contextvars.ContextVar(
    "pilosa_tpu_span", default=None)

DEFAULT_RING = 256
DEFAULT_SLOW_RING = 64
DEFAULT_SLOW_US = 250_000  # 250 ms — generous; tune via config/env.

# Trace ids only need to be unguessable enough not to collide across a
# ring of a few hundred traces; a urandom-seeded Mersenne Twister is
# plenty, and getrandbits is one GIL-atomic C call where uuid4 costs a
# getrandom(2) syscall per trace on the query hot path.
_ID_RAND = random.Random()


def _new_trace_id() -> str:
    return "%016x" % _ID_RAND.getrandbits(64)


class Span:
    """One timed region of a trace. Context manager: entering makes it
    the ambient parent for nested `span()` calls in this context."""

    __slots__ = ("trace", "span_id", "parent_id", "name", "start_ns",
                 "end_ns", "tags", "_token")

    def __init__(self, trace: "Trace", span_id: int,
                 parent_id: Optional[int], name: str,
                 tags: Optional[Dict[str, Any]] = None):
        self.trace = trace
        self.span_id = span_id
        self.parent_id = parent_id
        self.name = name
        self.start_ns = time.monotonic_ns()
        self.end_ns: Optional[int] = None
        # Takes ownership of `tags` — every caller passes a dict built
        # for this span (a **kwargs dict or freshly parsed JSON).
        self.tags: Dict[str, Any] = tags if tags is not None else {}
        self._token = None

    def tag(self, **tags) -> "Span":
        self.tags.update(tags)
        return self

    def finish(self) -> None:
        if self.end_ns is None:
            self.end_ns = time.monotonic_ns()

    @property
    def duration_us(self) -> float:
        end = self.end_ns if self.end_ns is not None else time.monotonic_ns()
        return (end - self.start_ns) / 1e3

    def __enter__(self) -> "Span":
        self._token = CURRENT.set(self)
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.finish()
        if exc_type is not None and "error" not in self.tags:
            self.tags["error"] = exc_type.__name__
        if self._token is not None:
            CURRENT.reset(self._token)
            self._token = None

    def to_dict(self) -> Dict[str, Any]:
        return {
            "id": self.span_id,
            "parent": self.parent_id,
            "name": self.name,
            "start_us": round((self.start_ns - self.trace.start_ns) / 1e3,
                              1),
            "duration_us": round(self.duration_us, 1),
            "tags": self.tags,
        }


class _NoopSpan:
    """Shared do-nothing span returned by `span()` when no trace is
    active. Every method is a constant-time no-op."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return None

    def tag(self, **tags):
        return self

    def finish(self):
        return None


NOOP_SPAN = _NoopSpan()


class Trace:
    """All spans for one query, rooted at `root`. Span creation is
    lock-free (GIL-atomic appends, atomic id counter); the finished
    trace is immutable by convention once the Tracer rings hold it."""

    __slots__ = ("trace_id", "name", "tags", "start_ns", "end_ns",
                 "start_wall", "spans", "root", "_ids")

    def __init__(self, trace_id: str, name: str,
                 tags: Optional[Dict[str, Any]] = None):
        self.trace_id = trace_id
        self.name = name
        self.tags: Dict[str, Any] = dict(tags) if tags else {}
        self.start_ns = time.monotonic_ns()
        self.end_ns: Optional[int] = None
        self.start_wall = time.time()
        self.spans: List[Span] = []
        self._ids = itertools.count(1)
        self.root = self.span(name, parent_id=None)

    def span(self, name: str, parent_id: Optional[int] = None,
             **tags) -> Span:
        if parent_id is None:
            cur = CURRENT.get()
            if cur is not None and cur.trace is self:
                parent_id = cur.span_id
        sp = Span(self, next(self._ids), parent_id, name, tags)
        self.spans.append(sp)
        return sp

    def finish(self) -> None:
        self.root.finish()
        if self.end_ns is None:
            self.end_ns = time.monotonic_ns()

    @property
    def duration_us(self) -> float:
        end = self.end_ns if self.end_ns is not None else time.monotonic_ns()
        return (end - self.start_ns) / 1e3

    def serialize_spans(self) -> List[Dict[str, Any]]:
        """Span dicts with trace-relative times — the wire form carried
        back to the coordinator in X-Pilosa-Trace-Spans."""
        return [sp.to_dict() for sp in self.spans]

    def graft(self, span_dicts: List[Dict[str, Any]], parent_id: int,
              **extra_tags) -> None:
        """Attach spans serialized by a remote node under `parent_id`.

        Remote ids are remapped into this trace's id space; remote
        times are trace-relative on *its* clock, so we anchor them at
        the local parent span's start — the coordinator's fan-out span
        already brackets the remote work, and sub-ms skew inside it is
        acceptable for attribution.
        """
        parent = next((s for s in self.spans if s.span_id == parent_id),
                      self.root)
        base_ns = parent.start_ns
        idmap = {d.get("id"): next(self._ids) for d in span_dicts}
        for d in span_dicts:
            sp = Span(self, idmap[d.get("id")],
                      idmap.get(d.get("parent"), parent_id),
                      d.get("name", "remote"), d.get("tags"))
            sp.start_ns = base_ns + int(d.get("start_us", 0) * 1e3)
            sp.end_ns = sp.start_ns + int(d.get("duration_us", 0) * 1e3)
            sp.tags.update(extra_tags)
            self.spans.append(sp)

    def summary(self) -> Dict[str, Any]:
        return {
            "id": self.trace_id,
            "name": self.name,
            "start": self.start_wall,
            "duration_us": round(self.duration_us, 1),
            "spans": len(self.spans),
            "tags": self.tags,
        }

    def to_dict(self) -> Dict[str, Any]:
        d = self.summary()
        d["spans"] = sorted((sp.to_dict() for sp in self.spans),
                            key=lambda s: (s["start_us"], s["id"]))
        return d


class Tracer:
    """Bounded retention of finished traces: a `recent` ring of the
    last N queries and a `slow` ring of those at/over the slow-query
    threshold (µs). PILOSA_TPU_SLOW_QUERY_US overrides the configured
    threshold at construction."""

    def __init__(self, ring: int = DEFAULT_RING,
                 slow_ring: int = DEFAULT_SLOW_RING,
                 slow_us: Optional[float] = None):
        env = os.environ.get("PILOSA_TPU_SLOW_QUERY_US", "")
        if env:
            slow_us = float(env)
        self.slow_us = float(slow_us if slow_us is not None
                             else DEFAULT_SLOW_US)
        self._mu = threading.Lock()
        self._recent: "deque[Trace]" = deque(maxlen=max(1, int(ring)))
        self._slow: "deque[Trace]" = deque(maxlen=max(1, int(slow_ring)))

    def start(self, name: str, trace_id: Optional[str] = None,
              **tags) -> Trace:
        return Trace(trace_id or _new_trace_id(), name, tags)

    def finish(self, trace: Trace) -> None:
        trace.finish()
        with self._mu:
            self._recent.append(trace)
            if trace.duration_us >= self.slow_us:
                self._slow.append(trace)

    def get(self, trace_id: str) -> Optional[Trace]:
        with self._mu:
            for ring in (self._recent, self._slow):
                for tr in reversed(ring):
                    if tr.trace_id == trace_id:
                        return tr
        return None

    def snapshot(self) -> Dict[str, Any]:
        """JSON shape served at /debug/queries (newest first)."""
        with self._mu:
            recent = [tr.summary() for tr in reversed(self._recent)]
            slow = [tr.summary() for tr in reversed(self._slow)]
        return {
            "slow_threshold_us": self.slow_us,
            "recent": recent,
            "slow": slow,
        }


def current_span() -> Optional[Span]:
    return CURRENT.get()


def span(name: str, **tags):
    """Open a child span of the ambient span, or a shared no-op when
    no trace is active. The inactive case is the fast path: one
    ContextVar read, no allocation."""
    cur = CURRENT.get()
    if cur is None:
        return NOOP_SPAN
    return cur.trace.span(name, parent_id=cur.span_id, **tags)


def wrap_ctx(fn):
    """Bind `fn` to the caller's contextvars context so pool workers
    inherit the active span (and the active query profile / cost
    account). Each call copies its own Context (a Context can't be
    entered concurrently), and when no trace, profile, or cost account
    is active the function is returned untouched."""
    if CURRENT.get() is None:
        from .costs import CURRENT_ACCOUNT
        from .profile import CURRENT_PROFILE
        if CURRENT_PROFILE.get() is None and CURRENT_ACCOUNT.get() is None:
            return fn
    ctx = contextvars.copy_context()

    def run(*args, **kwargs):
        return ctx.run(fn, *args, **kwargs)

    return run


_JAX_PROFILE: Optional[bool] = None


def jax_profile_on() -> bool:
    """The device-trace gate, PILOSA_TPU_JAX_PROFILE: resolved once
    per process; off (the default) never imports jax. profile.phase()
    shares it, so one switch turns on the launch annotations here and
    the `pilosa:<phase>` annotations there."""
    global _JAX_PROFILE
    on = _JAX_PROFILE
    if on is None:
        on = os.environ.get("PILOSA_TPU_JAX_PROFILE", "").strip().lower() \
            in ("1", "on", "true", "yes")
        if on:
            try:
                from jax.profiler import TraceAnnotation  # noqa: F401
            except Exception:
                on = False
        _JAX_PROFILE = on
    return on


def jax_scope(name: str):
    """jax.profiler named scope around kernel dispatch, gated behind
    PILOSA_TPU_JAX_PROFILE so device traces line up with span names."""
    if not jax_profile_on():
        return nullcontext()
    from jax.profiler import TraceAnnotation

    return TraceAnnotation(name)
