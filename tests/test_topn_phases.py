"""The served TopN leg's phases, its passes, who holds MeshManager's
lock, and the HTTP front end's own time.

A profiled mesh-served TopN, in every form, shows its seams between the
executor and the device disjoint in time (`mesh_prepare`,
`mesh_lock_wait`, `device_exec`, `readback_d2h`, `rank`, `account`);
nobody looking costs the shared no-op; with the device-trace gate on an
unprofiled TopN enters `pilosa:rank` and the socket adapter's
`pilosa:http_read` / `pilosa:http_write`. `executor.topn_passes` counts
the MeshManager.top_n calls; `mesh.mu_held_us` books every hold of
`_mu` to its holder once; the access line is logged at debug.
"""

import json
import logging
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

from pilosa_tpu import SLICE_WIDTH
from pilosa_tpu.api import Handler
from pilosa_tpu.api.handler import FrontTimes
from pilosa_tpu.api.server import APIServer
from pilosa_tpu.core import Holder
from pilosa_tpu.executor import Executor
from pilosa_tpu.obs import profile, trace
from pilosa_tpu.parallel import new_test_cluster
from pilosa_tpu.parallel import serve

from test_phases import _FakeNs, _Recorder, count, mesh_handler

LEG = ("mesh_prepare", "mesh_lock_wait", "device_exec", "readback_d2h",
       "rank", "account")
FORMS = {
    "ranked": "TopN(frame=f, n=3)",
    "ids": "TopN(frame=f, ids=[1, 2, 5])",
    "src": "TopN(Bitmap(rowID={row}, frame=f), frame=f, n=3)",
    "tanimoto": "TopN(Bitmap(rowID={row}, frame=f), frame=f, "
                "tanimotoThreshold=50)",
    "sparse_src": 'TopN(Bitmap(rowID={row}, frame="language"), '
                  'frame="stargazer", n=4)',
}


def query(h, pql, profiled=False):
    r = h.handle("POST", "/index/i/query", body=pql.encode(),
                 params={"profile": "true"} if profiled else {})
    assert r.status == 200, r.body
    return r.json()


def sparse_handler(tmp_path, slices=3, users=24, langs=5):
    """A one-node handler over a dense `language` frame and a
    `stargazer` frame of a few stars a user, which stages sorted-array:
    the src form ranks it where it lies."""
    holder = Holder(str(tmp_path / "data"))
    holder.open()
    idx = holder.create_index_if_not_exists("i")
    lang = idx.create_frame_if_not_exists("language")
    star = idx.create_frame_if_not_exists("stargazer")
    rng = np.random.default_rng(7)
    for s in range(slices):
        lg = np.where(rng.random(65536) < 0.3, 0,
                      rng.integers(langs, size=65536))
        lang.import_bits(lg.tolist(),
                         (s * SLICE_WIDTH + np.arange(65536)).tolist())
        for u in range(users):
            for c in rng.integers(0, 8 * 65536, size=60):
                star.set_bit(u, s * SLICE_WIDTH + int(c))
    cluster = new_test_cluster(1)
    host = cluster.nodes[0].host
    ex = Executor(holder, host=host, cluster=cluster, use_device=True,
                  device_min_work=0)
    return holder, ex, Handler(holder, ex, cluster=cluster, host=host)


@pytest.fixture(params=sorted(FORMS))
def form(request, tmp_path):
    name = request.param
    if name == "sparse_src":
        holder, ex, h = sparse_handler(tmp_path)
    else:
        holder, ex, h = mesh_handler(tmp_path)
    query(h, FORMS[name].format(row=0))  # stage and compile
    yield name, ex, h
    holder.close()


def test_profiled_mesh_topn_shows_every_leg_phase_disjoint(form):
    name, ex, h = form
    mgr = ex.mesh_manager()
    before = dict(mgr.stats)
    # Another src row, or a write for the forms without one: the limb
    # memo misses, so the program runs.
    if "{row}" not in FORMS[name]:
        query(h, "SetBit(rowID=3, frame=f, columnID=100)")
    out = query(h, FORMS[name].format(row=1), profiled=True)
    assert out["results"][0], out
    prof = out["profile"]
    phases = prof["phases_us"]
    assert set(LEG) <= set(phases), sorted(phases)
    assert "host_fold" not in phases
    # Disjoint: the phases never sum past the wall time, and what none
    # of them covers is a small rest.
    total = prof["total_us"]
    assert sum(phases.values()) <= 1.02 * total
    assert total - sum(phases.values()) < 0.15 * total, phases
    order = [p for p in profile.PHASES if p in phases]
    assert list(phases)[:len(order)] == order
    assert mgr.stats["topn"] > before["topn"]
    if name == "sparse_src":
        assert mgr.stats["sparse_row_counts"] > before["sparse_row_counts"]
        assert mgr.stats["sparse_demote"] == 0


def test_a_memo_hit_reads_back_and_ranks_with_no_launch(tmp_path):
    holder, _, h = mesh_handler(tmp_path)
    query(h, FORMS["ranked"])
    phases = query(h, FORMS["ranked"], profiled=True)["profile"]["phases_us"]
    holder.close()
    assert {"readback_d2h", "rank", "mesh_prepare"} <= set(phases)
    assert "device_exec" not in phases


@pytest.mark.parametrize("name", ["rank", "http_read", "http_write"])
def test_new_seam_is_the_noop_when_nobody_looks(name, monkeypatch):
    monkeypatch.setattr(trace, "_JAX_PROFILE", False)
    assert profile.current() is None
    assert profile.phase(name) is profile.NOOP_PHASE
    front = FrontTimes()
    assert front.read is front.write is profile.NOOP_PHASE


def test_a_pool_worker_does_not_inherit_the_residual_it_would_pause():
    prof = profile.QueryProfile()
    tok = profile.activate(prof)
    try:
        with profile.residual("account"):
            seen = trace.wrap_ctx(profile._RESIDUAL.get)()
            assert profile._RESIDUAL.get() is not None
    finally:
        profile.deactivate(tok)
    assert seen is None


# -- the device-trace sink, through the socket adapter ------------------------------


def settled(cond, timeout=10.0):
    """Wait for what the server does after the client has its reply."""
    end = time.monotonic() + timeout
    while not cond():
        assert time.monotonic() < end
        time.sleep(0.01)


@pytest.fixture
def annotated_over_http(tmp_path, monkeypatch):
    """One UNPROFILED mesh-served TopN sent over a socket with the gate
    on and a recording stand-in for TraceAnnotation: the log of what it
    entered."""
    holder, _, h = mesh_handler(tmp_path)
    srv = APIServer(h)
    srv.start()
    url = f"http://{srv.host}/index/i/query"

    def post(pql):
        req = urllib.request.Request(url, data=pql.encode(), method="POST")
        with urllib.request.urlopen(req, timeout=60) as r:
            return json.loads(r.read())

    try:
        post(FORMS["ranked"])
        post("SetBit(rowID=3, frame=f, columnID=100)")
        monkeypatch.setattr(trace, "_JAX_PROFILE", True)
        monkeypatch.setattr(profile, "_TraceAnnotation", _Recorder)
        _Recorder.log = []
        assert "profile" not in post(FORMS["ranked"])
        # The server closes http_write after its last write, which the
        # client may see first.
        settled(lambda: ("out", "pilosa:http_write")
                in [e[:2] for e in _Recorder.log])
        log = list(_Recorder.log)
    finally:
        srv.close()
        holder.close()
    return log


@pytest.mark.parametrize("name", ["rank", "http_read", "http_write",
                                  "mesh_prepare", "readback_d2h"])
def test_gate_on_an_unprofiled_topn_enters_the_annotation(
        name, annotated_over_http):
    entered = [n for kind, n, _ in annotated_over_http if kind == "in"]
    left = [n for kind, n, _ in annotated_over_http if kind == "out"]
    assert "pilosa:" + name in entered
    assert sorted(entered) == sorted(left)
    if name.startswith("http_"):
        assert entered.count("pilosa:" + name) == 1
    # The front end's two phases bracket everything else the request
    # entered.
    assert entered[0] == "pilosa:http_read"
    assert entered[-1] == "pilosa:http_write"


# -- the passes of a TopN -----------------------------------------------------------


def test_passes_count_the_mesh_top_n_calls(tmp_path):
    holder, ex, h = mesh_handler(tmp_path)
    mgr = ex.mesh_manager()
    calls = []
    real = mgr.top_n

    def top_n(*a, **kw):
        calls.append(1)
        return real(*a, **kw)

    mgr.top_n = top_n
    before = dict(ex.exec_stats)
    for name in ("ranked", "ids", "src", "tanimoto"):
        query(h, FORMS[name].format(row=2))
    vars_ = h.handle("GET", "/debug/vars").json()["executor"]
    prom = h.handle("GET", "/metrics").body.decode()
    holder.close()
    assert vars_["topn_queries"] - before["topn_queries"] == 4
    assert vars_["topn_passes"] - before["topn_passes"] == len(calls) > 0
    assert f"pilosa_topn_passes_total {vars_['topn_passes']}" in prom
    assert f"pilosa_topn_queries_total {vars_['topn_queries']}" in prom


# -- who holds _mu ------------------------------------------------------------------


def test_a_reentrant_hold_is_booked_once_to_the_outermost_holder(
        monkeypatch):
    clk = _FakeNs()
    monkeypatch.setattr(profile, "monotonic_ns", clk)
    lock = threading.RLock()
    book = serve._HoldBook(serve.MU_HOLDERS)
    with serve._held(lock, "topn_args", book):
        clk.advance_us(10)
        with serve._held(lock, "refresh", book):
            clk.advance_us(5)
            with serve._held(lock, "stage", book):
                clk.advance_us(100)
        clk.advance_us(1)
    with serve._held(lock, "memo", book):
        clk.advance_us(2)
    got = book.held_us()
    assert got["topn_args"] == 116 and got["memo"] == 2
    assert got["refresh"] == got["stage"] == 0
    assert got["total"] == 118
    assert set(got) == set(serve.MU_HOLDERS) | {"total"}


def test_threads_holding_in_turn_lose_nothing(monkeypatch):
    clk = _FakeNs()
    monkeypatch.setattr(profile, "monotonic_ns", clk)
    lock = threading.RLock()
    book = serve._HoldBook(serve.MU_HOLDERS)
    holders = serve.MU_HOLDERS[1:]  # all but "refresh", the inner one
    holds = 500

    def hold(holder):
        for _ in range(holds):
            with serve._held(lock, holder, book):
                with serve._held(lock, "refresh", book):  # re-entrant
                    clk.advance_us(1)  # only ever under the lock

    ts = [threading.Thread(target=hold, args=(h,)) for h in holders]
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in ts)
    got = book.held_us()
    assert all(got[h] == holds for h in holders), got
    assert got["refresh"] == 0 and got["total"] == len(holders) * holds


def test_the_routing_peeks_wait_stays_in_plan(tmp_path, monkeypatch):
    """stage_infeasible runs inside the executor's `plan` phase, which is
    not residual: a mesh_lock_wait opened there would count twice."""
    monkeypatch.setenv("PILOSA_TPU_HBM_BUDGET_BYTES", str(1 << 40))
    holder, ex, h = mesh_handler(tmp_path)
    count(h, 0)
    mgr = ex.mesh_manager()
    prof = profile.QueryProfile()
    tok = profile.activate(prof)
    try:
        with profile.phase("plan"):
            assert not mgr.stage_infeasible("i", [("f", "standard", 1, True)],
                                            16)
    finally:
        profile.deactivate(tok)
    holder.close()
    assert set(prof.to_dict()["phases_us"]) == {"plan"}
    assert mgr.lock_held_us()["infeasible"] > 0


def test_debug_vars_and_metrics_carry_the_holders(tmp_path):
    holder, ex, h = mesh_handler(tmp_path)
    query(h, FORMS["ranked"])
    count(h, 1)
    held = h.handle("GET", "/debug/vars").json()["mesh"]["mu_held_us"]
    prom = h.handle("GET", "/metrics").body.decode()
    holder.close()
    assert set(held) == set(serve.MU_HOLDERS) | {"total"}
    assert held["topn_args"] > 0 and held["memo"] > 0
    assert held["total"] == pytest.approx(
        sum(v for k, v in held.items() if k != "total"))
    for name in serve.MU_HOLDERS:
        assert f'pilosa_mesh_lock_held_us_total{{holder="{name}"}}' in prom


# -- the front end ------------------------------------------------------------------


@pytest.fixture
def over_http(tmp_path):
    holder, _, h = mesh_handler(tmp_path)
    srv = APIServer(h, logger=logging.getLogger("pilosa.test.http"))
    srv.start()

    def post(pql):
        req = urllib.request.Request(f"http://{srv.host}/index/i/query",
                                     data=pql.encode(), method="POST")
        with urllib.request.urlopen(req, timeout=60) as r:
            return json.loads(r.read())

    yield h, post, srv
    srv.close()
    holder.close()


def test_the_front_end_counts_each_query_request(over_http):
    h, post, srv = over_http
    for row in (1, 2, 3):
        post(f"Count(Bitmap(rowID={row}, frame=f))")
    post(FORMS["ranked"])
    settled(lambda: h.http_stats["queries"] == 4)
    with urllib.request.urlopen(f"http://{srv.host}/debug/vars",
                                timeout=60) as r:
        got = json.loads(r.read())["http"]
    assert got["queries"] == 4
    assert got["read_us"] > 0 and got["write_us"] > 0


@pytest.mark.parametrize("level,logged", [(logging.INFO, False),
                                          (logging.DEBUG, True)])
def test_the_access_line_is_logged_at_debug(over_http, level, logged,
                                            caplog):
    _, post, _ = over_http
    caplog.set_level(level, logger="pilosa.test.http")
    post("Count(Bitmap(rowID=1, frame=f))")
    lines = [r for r in caplog.records
             if r.name == "pilosa.test.http" and "POST /index/i/query"
             in r.getMessage()]
    assert bool(lines) is logged
    assert all(r.levelno == logging.DEBUG for r in lines)
