"""Measured per-query profiling: the EXPLAIN ANALYZE to trace.py's
distributed flight recorder.

A `QueryProfile` is an accumulator threaded (by contextvar, like the
tracer) through the same seams the tracer instruments — parse, plan,
H2D staging, compile, device dispatch, D2H readback, host fold, remote
fan-out — but where spans record *shape* (who called what, when), the
profile records *cost*: per-phase wall time unioned across threads,
bytes moved per direction, and achieved bytes/s against the device's
peak (a host-bracketed figure; a kernel's roofline share needs a
device trace).

Same cardinal rule as the tracer: near-free when nobody is looking.
`phase("x")` with no active profile (and the device-trace gate off) is
one ContextVar read and one module-global test returning a shared
no-op; byte counters early-return. Device phases are only real
when a profile is active — callers gate their `block_until_ready`
bracketing on `current() is not None`, so the async-dispatch fast path
is byte-identical when profiling is off.

Phase accounting is a per-phase *union of intervals*: each phase keeps
an active-entry depth, and only the outermost enter/exit pair (across
all threads touching the profile) contributes wall time. Nested or
concurrent same-name phases — serve._stage wrapping
mesh.build_sharded_index, or parallel slice workers overlapping —
therefore never double-count.

Two sinks, one call per seam. With `PILOSA_TPU_JAX_PROFILE` on (the
gate `trace.jax_scope` has), every phase of EVERY query, profiled or
not, is also a `jax.profiler.TraceAnnotation("pilosa:<phase>")`, so an
idle gap of a device trace falls under a named host span on the
profiler's own clock.

Phases of one query are disjoint in time, so total - sum(phases) is
the time no seam covers. Where a phase is "the rest" of a region whose
inner steps have phases of their own (`mesh_prepare` around the locks,
the refresh and the launch), it is opened with `residual()`: any phase
entered below it in the same context pauses it, and leaving that phase
resumes it.
"""

from __future__ import annotations

import contextvars
import threading
import time
from typing import Any, Dict, List, Optional

from . import trace as _trace
from .metrics import Histogram

# The canonical phase set, in pipeline order. to_dict() emits phases in
# this order (then any ad-hoc extras) so profiles diff cleanly.
PHASES = ("sched_wait", "parse", "plan", "route_slices", "pool_handoff",
          "mesh_prepare", "mesh_lock_wait", "view_refresh", "stage_h2d",
          "compile", "device_exec", "readback_d2h", "rank", "host_fold",
          "wal_commit", "fanout_remote", "account", "respond")

BYTE_COUNTERS = ("bytes_staged", "bytes_touched_hbm", "bytes_read_back")

# The active profile for this thread/context. trace.wrap_ctx() carries
# it across pool submit() boundaries alongside the active span.
CURRENT_PROFILE: "contextvars.ContextVar[Optional[QueryProfile]]" = \
    contextvars.ContextVar("pilosa_tpu_profile", default=None)

# Injectable clock: every timestamp the profiler takes goes through
# this hook so tests can drive phase accounting with a deterministic
# fake clock instead of asserting against wall-clock sleeps (which
# flake under suite load).
monotonic_ns = time.monotonic_ns


class _NoopPhase:
    """Shared do-nothing phase timer returned when no profile is
    active and the device-trace gate is off — the identity of this
    singleton is itself asserted by tests as proof the fast path pays
    one ContextVar read, one global test and nothing else."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return None

    def start(self):
        return self

    def stop(self):
        return None


NOOP_PHASE = _NoopPhase()

# The residual phase running in this context, if any: the one a phase
# entered here pauses (see residual()). Per context, so per thread of
# a query; never hold one open across a pool submit.
_RESIDUAL: "contextvars.ContextVar[Optional[_Phase]]" = \
    contextvars.ContextVar("pilosa_tpu_residual_phase", default=None)

# jax.profiler.TraceAnnotation, imported when the gate first turns a
# phase into one (tests put a recording stand-in here).
_TraceAnnotation = None


def _annotation(name: str):
    global _TraceAnnotation
    ta = _TraceAnnotation
    if ta is None:
        from jax.profiler import TraceAnnotation as ta
        _TraceAnnotation = ta
    ann = ta("pilosa:" + name)
    ann.__enter__()
    return ann


class _Phase:
    """One enter/exit of a named phase, into the sinks that are on:
    the profile (`prof`; None for a query nobody profiles) and, with
    `annotate`, a TraceAnnotation of the same extent. The annotation
    is made when the phase starts (it times from its construction) and
    records on the thread that stops it, so a phase may be entered on
    one thread and left on another."""

    __slots__ = ("_prof", "_name", "_annotate", "_residual", "_ann",
                 "_outer")

    def __init__(self, prof: "Optional[QueryProfile]", name: str,
                 annotate: bool = False, residual: bool = False):
        self._prof = prof
        self._name = name
        self._annotate = annotate
        self._residual = residual
        self._ann = None
        self._outer: Optional[_Phase] = None

    def _begin(self) -> None:
        if self._prof is not None:
            self._prof._enter(self._name)
        if self._annotate:
            self._ann = _annotation(self._name)

    def _end(self) -> None:
        ann = self._ann
        if ann is not None:
            self._ann = None
            ann.__exit__(None, None, None)
        if self._prof is not None:
            self._prof._exit(self._name)

    # Explicit form for regions with early returns (mirrors Span
    # .finish()). A second stop() of a phase that is not nested in
    # itself is a no-op.
    def start(self):
        outer = self._outer = _RESIDUAL.get()
        if outer is not None:
            outer._end()  # paused until stop()
        if outer is not None or self._residual:
            _RESIDUAL.set(self if self._residual else None)
        self._begin()
        return self

    def stop(self):
        self._end()
        outer = self._outer
        if outer is not None or self._residual:
            _RESIDUAL.set(outer)
        if outer is not None:
            self._outer = None
            outer._begin()
        return None

    __enter__ = start

    def __exit__(self, *exc):
        return self.stop()


class QueryProfile:
    """Measured cost accumulator for one query.

    Thread-safe: staging and slice folds run on pool workers, so every
    mutation takes the profile's lock. That lock is only ever taken
    when a profile IS active — the no-profile fast path never reaches
    here.
    """

    __slots__ = ("_mu", "_phase_ns", "_active", "_bytes", "_slices",
                 "remotes", "start_ns", "end_ns", "backend", "tags",
                 "tenant")

    def __init__(self, backend: Optional[str] = None):
        self._mu = threading.Lock()
        # Bounded tenant label for the exported phase histograms; ""
        # keeps the series tenant-less (remote legs, embedded tests).
        # The handler assigns it through SLORecorder.tenant_label so
        # cardinality is capped at |tenant-weights| + "other".
        self.tenant = ""
        self._phase_ns: Dict[str, int] = {}
        # phase -> [depth, outermost_start_ns]
        self._active: Dict[str, List[int]] = {}
        self._bytes: Dict[str, int] = {}
        self._slices: List[Dict[str, Any]] = []
        self.remotes: List[Dict[str, Any]] = []
        self.start_ns = monotonic_ns()
        self.end_ns: Optional[int] = None
        self.backend = backend or default_backend()
        self.tags: Dict[str, Any] = {}

    # -- phase timers ----------------------------------------------------

    def _enter(self, name: str) -> None:
        now = monotonic_ns()
        with self._mu:
            ent = self._active.get(name)
            if ent is None:
                self._active[name] = [1, now]
            else:
                ent[0] += 1

    def _exit(self, name: str) -> None:
        now = monotonic_ns()
        with self._mu:
            ent = self._active.get(name)
            if ent is None:  # unbalanced exit: ignore rather than raise
                return
            ent[0] -= 1
            if ent[0] <= 0:
                del self._active[name]
                self._phase_ns[name] = (self._phase_ns.get(name, 0)
                                        + now - ent[1])

    def phase(self, name: str) -> _Phase:
        return _Phase(self, name, _trace.jax_profile_on())

    def add_phase_ns(self, name: str, ns: int) -> None:
        """Credit already-measured wall time to a phase (for callers
        that timed a region themselves, e.g. staging stats)."""
        with self._mu:
            self._phase_ns[name] = self._phase_ns.get(name, 0) + int(ns)

    # -- byte counters / breakdowns --------------------------------------

    def add_bytes(self, counter: str, n: int) -> None:
        with self._mu:
            self._bytes[counter] = self._bytes.get(counter, 0) + int(n)

    def add_slice(self, **kv) -> None:
        """One row of the per-slice / per-device breakdown. Bounded:
        a 1B-column index has ~1000 slices and the breakdown is for
        humans, so keep the first 256 rows and count the rest."""
        with self._mu:
            if len(self._slices) < 256:
                self._slices.append(kv)
            else:
                self.tags["slices_truncated"] = \
                    self.tags.get("slices_truncated", 0) + 1

    def tag(self, **kv) -> "QueryProfile":
        with self._mu:
            self.tags.update(kv)
        return self

    def merge_remote(self, host: str, section: Dict[str, Any]) -> None:
        """Attach a remote node's profile section (parsed from the
        X-Pilosa-Profile response header). Remote phases stay in their
        own section — the coordinator's fanout_remote phase already
        brackets the remote wall time, so folding them into the local
        totals would double-count."""
        with self._mu:
            self.remotes.append({"host": host, **section})

    # -- lifecycle / output ----------------------------------------------

    def finish(self) -> None:
        if self.end_ns is None:
            self.end_ns = monotonic_ns()

    @property
    def total_us(self) -> float:
        end = self.end_ns if self.end_ns is not None else monotonic_ns()
        return (end - self.start_ns) / 1e3

    def phase_us(self, name: str) -> float:
        with self._mu:
            return self._phase_ns.get(name, 0) / 1e3

    def roofline(self) -> Dict[str, Any]:
        """Achieved bytes/s against the backend's peak.

        The engine that touched the bytes decides the denominator: a
        device-dispatched query is judged against HBM peak over the
        device_exec phase; a host-folded one against the measured host
        memory bandwidth over the host_fold phase.
        """
        with self._mu:
            dev_ns = self._phase_ns.get("device_exec", 0)
            host_ns = self._phase_ns.get("host_fold", 0)
            touched = self._bytes.get("bytes_touched_hbm", 0)
        if dev_ns > 0:
            engine, ns = "device", dev_ns
        else:
            engine, ns = "host", host_ns
        out: Dict[str, Any] = {"engine": engine,
                               "bytes_touched": touched}
        if ns <= 0 or touched <= 0:
            out["achieved_bytes_per_s"] = 0.0
            out["fraction_of_peak"] = 0.0
            return out
        achieved = touched / (ns / 1e9)
        out["achieved_bytes_per_s"] = round(achieved, 1)
        kind = default_device_kind() if engine == "device" else "host"
        try:
            peak = peak_bytes_per_s(kind)
        except KeyError:
            # An accelerator the table does not list: the query still
            # answers, with no share of a peak nobody recorded.
            out["peak_bytes_per_s"] = None
            out["fraction_of_peak"] = None
            out["peak_unknown_device_kind"] = kind
            return out
        out["peak_bytes_per_s"] = round(peak, 1)
        out["fraction_of_peak"] = round(achieved / peak, 6) if peak else 0.0
        return out

    def to_dict(self) -> Dict[str, Any]:
        with self._mu:
            phase_ns = dict(self._phase_ns)
            # Credit still-open phases up to now so a mid-flight dump
            # (or a caller that forgot an exit) stays roughly honest.
            now = monotonic_ns()
            for name, (_, t0) in self._active.items():
                phase_ns[name] = phase_ns.get(name, 0) + now - t0
            bts = dict(self._bytes)
            slices = list(self._slices)
            remotes = list(self.remotes)
            tags = dict(self.tags)
        ordered = {name: round(phase_ns[name] / 1e3, 1)
                   for name in PHASES if name in phase_ns}
        for name in sorted(phase_ns):
            if name not in ordered:
                ordered[name] = round(phase_ns[name] / 1e3, 1)
        out: Dict[str, Any] = {
            "backend": self.backend,
            "total_us": round(self.total_us, 1),
            "phases_us": ordered,
            "bytes": bts,
            "roofline": self.roofline(),
        }
        if slices:
            out["slices"] = slices
        if remotes:
            out["remotes"] = remotes
        if tags:
            out["tags"] = tags
        return out


# -- contextvar plumbing -------------------------------------------------


def current() -> Optional[QueryProfile]:
    return CURRENT_PROFILE.get()


def activate(prof: QueryProfile):
    """Make `prof` the ambient profile; returns the reset token."""
    return CURRENT_PROFILE.set(prof)


def deactivate(token) -> None:
    CURRENT_PROFILE.reset(token)


def phase(name: str, residual: bool = False):
    """Phase timer into the ambient profile and, with the device-trace
    gate on, into a `pilosa:<name>` TraceAnnotation; the shared no-op
    when neither is looking. That case is the fast path: one
    ContextVar read and one module-global test, no allocation."""
    prof = CURRENT_PROFILE.get()
    on = _trace._JAX_PROFILE
    if on is None:
        on = _trace.jax_profile_on()
    if prof is None and not on:
        return NOOP_PHASE
    return _Phase(prof, name, on, residual)


def residual(name: str):
    """phase() for "the rest" of a region: any phase entered below it
    in this context pauses it until that phase is left, so it is
    credited only with the time no inner phase covers and the phases
    of a query stay disjoint."""
    return phase(name, True)


def add_bytes(counter: str, n: int) -> None:
    prof = CURRENT_PROFILE.get()
    if prof is not None:
        prof.add_bytes(counter, n)


def add_slice(**kv) -> None:
    prof = CURRENT_PROFILE.get()
    if prof is not None:
        prof.add_slice(**kv)


# -- backend + peak resolution -------------------------------------------

_BACKEND: Optional[str] = None


def default_backend() -> str:
    """Cached jax.default_backend(); "cpu" when jax is unavailable or
    uninitialized (config printing, docs builds)."""
    global _BACKEND
    b = _BACKEND
    if b is None:
        try:
            import jax
            b = str(jax.default_backend())
        except Exception:
            b = "cpu"
        _BACKEND = b
    return b


_DEVICE_KIND: Optional[str] = None


def default_device_kind() -> str:
    """Cached jax.devices()[0].device_kind ("TPU v5 lite"), the key of
    config.py's peak table; "cpu" on the CPU backend, whose peak is
    the measured host bandwidth."""
    global _DEVICE_KIND
    k = _DEVICE_KIND
    if k is None:
        if default_backend() == "cpu":
            k = "cpu"
        else:
            import jax
            k = str(jax.devices()[0].device_kind)
        _DEVICE_KIND = k
    return k


def peak_bytes_per_s(device_kind: str) -> float:
    """Peak memory bandwidth of a device kind, or of the host
    (config.py owns the table and raises for an accelerator it does
    not list; lazy import — config imports parallel which imports
    obs)."""
    from .. import config as _config
    return _config.peak_memory_bandwidth(device_kind)


# -- process-wide phase histograms (exported at /metrics) ----------------


class ProfileStats:
    """log₂ histograms per (phase, backend) plus the latest roofline
    measurement per backend. Every profiled query — explicit
    ?profile=true or sampled via [obs] profile-sample-rate — records
    here, so /metrics carries continuous cost attribution."""

    def __init__(self):
        self._mu = threading.Lock()
        self._phase: Dict[tuple, Histogram] = {}
        # backend -> (fraction_of_peak, achieved_bytes_per_s, count)
        self._roofline: Dict[str, tuple] = {}

    def record(self, prof: QueryProfile) -> None:
        d = prof.to_dict()
        backend = d["backend"]
        tenant = getattr(prof, "tenant", "")
        with self._mu:
            for name, us in d["phases_us"].items():
                h = self._phase.get((name, backend, tenant))
                if h is None:
                    h = self._phase[(name, backend, tenant)] = Histogram()
                h.observe(us)
        rf = d["roofline"]
        if rf.get("fraction_of_peak"):
            with self._mu:
                prev = self._roofline.get(backend, (0.0, 0.0, 0))
                self._roofline[backend] = (rf["fraction_of_peak"],
                                           rf["achieved_bytes_per_s"],
                                           prev[2] + 1)

    def snapshot(self):
        with self._mu:
            return dict(self._phase), dict(self._roofline)

    def families(self):
        """MetricFamily bridge for a /metrics collector."""
        from .prom import MetricFamily
        phases, roofs = self.snapshot()
        fams = []
        if phases:
            fam = MetricFamily(
                "pilosa_query_phase_us", "histogram",
                "Measured per-phase query wall time (microseconds).")
            for (name, backend, tenant), h in sorted(phases.items()):
                labels = {"phase": name, "backend": backend}
                if tenant:
                    labels["tenant"] = tenant
                fam.add_histogram(h, labels)
            fams.append(fam)
        if roofs:
            fam = MetricFamily(
                "pilosa_roofline_fraction", "gauge",
                "Most recent measured fraction of peak memory bandwidth.")
            bw = MetricFamily(
                "pilosa_roofline_bytes_per_second", "gauge",
                "Most recent measured achieved bytes/s.")
            for backend, (frac, bps, _n) in sorted(roofs.items()):
                fam.add(frac, {"backend": backend})
                bw.add(bps, {"backend": backend})
            fams.append(fam)
            fams.append(bw)
        return fams


STATS = ProfileStats()
