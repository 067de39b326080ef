"""The served read path's phases between the handler and the kernel:
every seam a mesh-routed Count crosses shows in `?profile=true`,
disjoint in time; the same call feeds `pilosa:<phase>` annotations of a
device trace when PILOSA_TPU_JAX_PROFILE is on, for unprofiled queries
too; nobody looking costs the shared no-op; the counters that reach
where no profiled request goes (`refresh_walks`, `process_cpu_seconds`);
and device programs that carry the name of what they run.
"""

import inspect
import threading

import pytest

from pilosa_tpu import SLICE_WIDTH
from pilosa_tpu.api import Handler
from pilosa_tpu.core import Holder
from pilosa_tpu.executor import Executor
from pilosa_tpu.obs import profile, trace
from pilosa_tpu.parallel import mesh as pmesh
from pilosa_tpu.parallel import new_test_cluster

NEW_PHASES = ("route_slices", "pool_handoff", "mesh_prepare",
              "mesh_lock_wait", "view_refresh", "account", "respond")
SLICES = 16


class _FakeNs:
    def __init__(self):
        self.t = 1_000_000_000

    def __call__(self):
        return self.t

    def advance_us(self, us):
        self.t += int(us * 1000)


class _Recorder:
    """Stand-in for jax.profiler.TraceAnnotation: logs enter and exit."""

    log: list = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        _Recorder.log.append(("in", self.name, threading.get_ident()))
        return self

    def __exit__(self, *exc):
        _Recorder.log.append(("out", self.name, threading.get_ident()))


def mesh_handler(tmp_path, rows=8, slices=SLICES):
    """A one-node handler whose Counts take the mesh route (the
    suite's PILOSA_TPU_DEVICE_MIN_WORK=0 turns cost routing off)."""
    holder = Holder(str(tmp_path / "data"))
    holder.open()
    cluster = new_test_cluster(1)
    host = cluster.nodes[0].host
    ex = Executor(holder, host=host, cluster=cluster, use_device=True,
                  device_min_work=0)
    h = Handler(holder, ex, cluster=cluster, host=host)
    assert h.handle("POST", "/index/i").status == 200
    assert h.handle("POST", "/index/i/frame/f").status == 200
    for row in range(rows):
        q = "".join(
            f"SetBit(rowID={row}, frame=f, columnID={s * SLICE_WIDTH + row})"
            for s in range(slices))
        assert h.handle("POST", "/index/i/query", body=q.encode()).status \
            == 200
    return holder, ex, h


def count(h, row, profiled=False):
    r = h.handle("POST", "/index/i/query",
                 body=f"Count(Bitmap(rowID={row}, frame=f))".encode(),
                 params={"profile": "true"} if profiled else {})
    assert r.status == 200
    return r.json()


@pytest.fixture
def served(tmp_path):
    holder, ex, h = mesh_handler(tmp_path)
    count(h, 0)  # stage and compile
    yield ex, h
    holder.close()


# -- the profile sink -----------------------------------------------------------


def test_mesh_count_shows_every_new_phase_disjoint(served):
    _, h = served
    prof = count(h, 1, profiled=True)["profile"]
    phases = prof["phases_us"]
    assert set(NEW_PHASES) <= set(phases), sorted(phases)
    assert "device_exec" in phases and "host_fold" not in phases
    # Disjoint: the phases of one query never sum past its wall time.
    assert sum(phases.values()) <= 1.02 * prof["total_us"]
    # Pipeline order, as PHASES documents it.
    order = [p for p in profile.PHASES if p in phases]
    assert list(phases)[:len(order)] == order


@pytest.mark.parametrize("name", NEW_PHASES)
def test_phase_is_the_noop_when_nobody_looks(name, monkeypatch):
    monkeypatch.setattr(trace, "_JAX_PROFILE", False)
    assert profile.current() is None
    assert profile.phase(name) is profile.NOOP_PHASE
    assert profile.residual(name) is profile.NOOP_PHASE


def test_gate_resolves_once_from_the_environment(monkeypatch):
    monkeypatch.setattr(trace, "_JAX_PROFILE", None)
    monkeypatch.delenv("PILOSA_TPU_JAX_PROFILE", raising=False)
    assert profile.phase("route_slices") is profile.NOOP_PHASE
    assert trace._JAX_PROFILE is False
    monkeypatch.setenv("PILOSA_TPU_JAX_PROFILE", "1")
    assert profile.phase("route_slices") is profile.NOOP_PHASE  # resolved


def test_a_phase_left_on_another_thread_is_credited_once(monkeypatch):
    clk = _FakeNs()
    monkeypatch.setattr(profile, "monotonic_ns", clk)
    p = profile.QueryProfile()
    tok = profile.activate(p)
    try:
        ph = profile.phase("pool_handoff").start()
        clk.advance_us(300)
        t = threading.Thread(target=ph.stop)
        t.start()
        t.join()
        clk.advance_us(1000)  # the worker's own work: in no hand-off
        ph.stop()             # a second stop is a no-op
    finally:
        profile.deactivate(tok)
    assert p.phase_us("pool_handoff") == 300


def test_a_residual_phase_is_paused_by_the_phases_below_it(monkeypatch):
    clk = _FakeNs()
    monkeypatch.setattr(profile, "monotonic_ns", clk)
    monkeypatch.setattr(trace, "_JAX_PROFILE", True)
    monkeypatch.setattr(profile, "_TraceAnnotation", _Recorder)
    _Recorder.log = []
    p = profile.QueryProfile()
    tok = profile.activate(p)
    try:
        with profile.residual("mesh_prepare"):
            clk.advance_us(100)
            with profile.phase("mesh_lock_wait"):
                clk.advance_us(7)
            clk.advance_us(50)
            with profile.residual("view_refresh"):
                clk.advance_us(20)
                with profile.phase("stage_h2d"):
                    clk.advance_us(4000)
                clk.advance_us(5)
            clk.advance_us(30)
        assert profile._RESIDUAL.get() is None
    finally:
        profile.deactivate(tok)
    got = {n: p.phase_us(n) for n in ("mesh_prepare", "mesh_lock_wait",
                                      "view_refresh", "stage_h2d")}
    assert got == {"mesh_prepare": 180, "mesh_lock_wait": 7,
                   "view_refresh": 25, "stage_h2d": 4000}
    # The annotations mirror the same extents: never two open at once.
    depth = 0
    for kind, _name, _tid in _Recorder.log:
        depth += 1 if kind == "in" else -1
        assert 0 <= depth <= 1
    names = [n for k, n, _ in _Recorder.log if k == "in"]
    assert names == ["pilosa:mesh_prepare", "pilosa:mesh_lock_wait",
                     "pilosa:mesh_prepare", "pilosa:view_refresh",
                     "pilosa:stage_h2d", "pilosa:view_refresh",
                     "pilosa:mesh_prepare"]


# -- the device-trace sink --------------------------------------------------------


@pytest.fixture
def annotated(tmp_path, monkeypatch):
    """One UNPROFILED mesh-routed Count with the gate on and a recording
    stand-in for TraceAnnotation: the log of what it entered."""
    holder, _, h = mesh_handler(tmp_path)
    count(h, 0)
    monkeypatch.setattr(trace, "_JAX_PROFILE", True)
    monkeypatch.setattr(profile, "_TraceAnnotation", _Recorder)
    _Recorder.log = []
    assert "profile" not in count(h, 1)
    log = list(_Recorder.log)
    holder.close()
    return log


@pytest.mark.parametrize("name", NEW_PHASES)
def test_gate_on_an_unprofiled_query_enters_the_annotation(name, annotated):
    entered = [n for kind, n, _ in annotated if kind == "in"]
    left = [n for kind, n, _ in annotated if kind == "out"]
    assert "pilosa:" + name in entered
    assert sorted(entered) == sorted(left)
    if name == "pool_handoff":
        # Entered by the request's thread and left by the worker's,
        # then the other way round.
        legs = [(kind, tid) for kind, n, tid in annotated
                if n == "pilosa:pool_handoff"]
        assert len(legs) == 4
        assert legs[0][1] != legs[1][1] and legs[2][1] != legs[3][1]
        assert legs[0][1] == legs[3][1]


# -- counters where a phase cannot reach ----------------------------------------


def test_the_count_after_a_setbit_walks_the_view_once(served):
    ex, h = served

    def walks():
        s = ex.device_stats
        return s.get("refresh_walks", 0), s.get("refresh_walk_us", 0)

    count(h, 1)
    before = walks()
    r = h.handle("POST", "/index/i/query",
                 body=b"SetBit(rowID=2, frame=f, columnID=77)")
    assert r.status == 200
    assert count(h, 2)["results"] == [SLICES + 1]
    after = walks()
    assert after[0] == before[0] + 1 and after[1] > before[1]
    count(h, 3)
    assert walks() == after


def test_process_cpu_seconds_in_debug_vars_never_decreases(served):
    _, h = served
    seen = []
    for row in (1, 2, 3):
        v = h.handle("GET", "/debug/vars").json()
        assert v["uptime_seconds"] >= 0
        seen.append(v["process_cpu_seconds"])
        count(h, row)
    assert seen == sorted(seen) and seen[0] > 0


# -- device programs that can be told apart -------------------------------------

BUILDER_ARGS = {
    "tree_shape": ["and", ["leaf", 0], ["leaf", 1]], "num_leaves": 2,
    "num_rows": 4, "k": 2, "batch": 2, "leaf_map": ((0, 1), (1, 0)),
    "num_unique": 2, "op": "and", "kind": "ss"}
# The one xla count builder names its program for the form it builds.
FORMS = {"compile_serve_count": [
    ("count_batch", {}), ("count_batch", {"batch": 1}),
    ("count_coarse", {"runs": True}),
    ("count_fused", {"host_meta": True, "batch": 1})]}
PROGRAMS = [
    (n, want, kw)
    for n, f in sorted(inspect.getmembers(pmesh, inspect.isfunction))
    if n.startswith("compile_")
    for want, kw in FORMS.get(n, [(n.replace("compile_serve_", "").replace(
        "compile_", ""), {})])]


def program_name(builder: str, form: dict) -> str:
    fn = getattr(pmesh, builder)
    kwargs = {p: BUILDER_ARGS[p] for p in inspect.signature(fn).parameters
              if p in BUILDER_ARGS}
    return fn(pmesh.default_mesh(), **{**kwargs, **form}).__name__


@pytest.mark.parametrize(
    "builder,want,form", PROGRAMS,
    ids=[b + "".join(f"-{k}={v}" for k, v in kw.items())
         for b, _, kw in PROGRAMS])
def test_a_builders_program_carries_its_name(builder, want, form):
    """`PjitFunction(<name>)` on the host and `jit_<name>` on the device
    are how a trace tells programs apart: each is named for what it
    runs, none `run`."""
    assert program_name(builder, form) == want != "run"


def test_no_two_builders_share_a_program_name():
    made_by = {}
    for builder, _, form in PROGRAMS:
        made_by.setdefault(program_name(builder, form), set()).add(builder)
    assert len(PROGRAMS) >= 19 and len(made_by) >= 18
    assert all(len(b) == 1 for b in made_by.values()), made_by
    assert pmesh._fold_chunk_fn().__name__ == "fold_chunk"
