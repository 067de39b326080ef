"""The benchmark's own tests (BENCHMARK.json lists this directory under
`paths`): the yardstick checked on the CPU, at sizes a test run can hold.
No JAX and no server at import; the end-to-end cases start children.
"""

import collections
import json
import os
import re
import shutil
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmarks")
HERE = os.path.dirname(os.path.abspath(__file__))
for p in (REPO, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

import pbench.kinds  # noqa: E402
import pbench.refs  # noqa: E402
from pbench import (datagen, durable, harness, layers, reference,  # noqa: E402
                    schedule, window, xplane)

BENCHMARK = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
CELLS = [w["name"] for w in BENCHMARK["workloads"]]
# A TopN configuration and mix that no cell of BENCHMARK.json uses: test data
# for the generator's, the reference's and the control's TopN side, and the
# stand-in for "a later PR's files" in the rehearsal below. Not a deployment.
FIXTURE = os.path.join(HERE, "fixture")
FIXTURE_CELL = "topn-fixture.topn-fixture8"
# And a deployment of two frames with a kind and a reference of its own (Star
# Trace in miniature; test_startrace_fixture.py).
STAR_CELL = "startrace-fixture.startrace-fixture4"
TRAFFIC_DIRS = {**{f[:-5]: os.path.join(BENCH, "traffic")
                   for f in os.listdir(os.path.join(BENCH, "traffic"))},
                "topn-fixture8": os.path.join(FIXTURE, "traffic")}
TRAFFIC = sorted(TRAFFIC_DIRS)


def traffic(name):
    return json.load(open(os.path.join(TRAFFIC_DIRS[name], name + ".json")))


def fixture_config():
    return json.load(open(os.path.join(FIXTURE, "configs",
                                       "topn-fixture.json")))


def rows_for(name):
    if name == "topn-fixture8":
        return int(fixture_config()["frame"]["rows"])
    cell = next(w for w in BENCHMARK["workloads"] if w["traffic"] == name)
    cfg = next(c for c in BENCHMARK["configs"] if c["name"] == cell["config"])
    return int(json.load(open(os.path.join(REPO, cfg["file"])))
               ["frame"]["rows"])


@pytest.fixture
def later_pr(tmp_path, monkeypatch):
    """A tree as a later PR would leave it: the benchmark's files untouched,
    and beside them new configurations, new traffic mixes, a new kind and a
    new reference, a new counter-backed metric and their BENCHMARK.json
    entries. The harness is pointed at it."""
    root = tmp_path / "repo"
    shutil.copytree(BENCH, root / "benchmarks",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    added = []
    for sub in ("configs", "traffic", "pbench/kinds", "pbench/refs"):
        src = os.path.join(FIXTURE, os.path.basename(sub))
        for f in os.listdir(src):
            assert not (root / "benchmarks" / sub / f).exists(), f
            shutil.copy(os.path.join(src, f), root / "benchmarks" / sub / f)
            if sub.startswith("pbench/"):
                added.append(f"pbench.{os.path.basename(sub)}.{f[:-3]}")
    for pkg in (pbench.kinds, pbench.refs):
        monkeypatch.setattr(pkg, "__path__", list(pkg.__path__) + [str(
            root / "benchmarks/pbench" / pkg.__name__.rsplit(".", 1)[1])])
    (root / "benchmarks/layer_metrics/mesh.deduped.json").write_text(
        json.dumps({"name": "mesh.deduped", "layer": "mesh serving",
                    "unit": "ops", "better": "higher",
                    "source": "program_counter", "moves": "ops_per_s",
                    "value": {"vars": "mesh.count"}}))
    b = json.loads(json.dumps(BENCHMARK))
    b["configs"].append({"name": "topn-fixture", "source": "x",
                         "reduced": [], "why": "y",
                         "file": "benchmarks/configs/topn-fixture.json"})
    b["workloads"].append({"name": FIXTURE_CELL, "config": "topn-fixture",
                           "traffic": "topn-fixture8", "chips": 1,
                           "why": "z"})
    b["configs"].append({"name": "startrace-fixture", "source": "x",
                         "reduced": [], "why": "y",
                         "file": "benchmarks/configs/startrace-fixture.json"})
    b["workloads"].append({"name": STAR_CELL, "config": "startrace-fixture",
                           "traffic": "startrace-fixture4", "chips": 1,
                           "why": "z"})
    for m in b["end_to_end"]:
        if m["name"] in ("ops_per_s", "read_p50_ms"):
            assert "workloads" not in m
    b["per_layer"].append({"name": "mesh.deduped", "unit": "ops",
                           "better": "higher", "source": "program_counter",
                           "layer": "mesh serving", "moves": "ops_per_s",
                           "workloads": [FIXTURE_CELL, STAR_CELL]})
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    monkeypatch.setattr(harness, "REPO", str(root))
    monkeypatch.setattr(harness, "BENCH_DIR", str(root / "benchmarks"))
    monkeypatch.setattr(harness, "OUT_DIR", str(tmp_path / "out"))
    monkeypatch.setattr(layers, "HERE", str(root / "benchmarks"))
    yield root
    for module in added:  # the next tree's copies are other files
        sys.modules.pop(module, None)


# -- the schedule ----------------------------------------------------------------


@pytest.mark.parametrize("name", TRAFFIC)
def test_schedule_is_the_same_for_every_seed(name):
    """--seed changes which rows and columns answer, never the composition:
    two templates built apart give identical abstract sequences, and each
    client's share of them is a prefix of one fixed sequence."""
    t, n = traffic(name), rows_for(name)
    a = schedule.Template(t, n).ops(0, 3 * t["block"]["size"])
    b = schedule.Template(t, n).ops(0, 3 * t["block"]["size"])
    assert a == b
    warm = schedule.Template(t, n, "warmup").ops(0, t["block"]["size"])
    assert warm != a[:t["block"]["size"]]
    p1 = schedule.row_permutation(11, n)
    p2 = schedule.row_permutation(3_000_000_019, n)
    assert sorted(p1) == sorted(p2) == list(range(n))
    assert list(p1) != list(p2)
    ops1 = [schedule.bind(op, p1, "f", n, 1) for op in a[:50]]
    ops2 = [schedule.bind(op, p2, "f", n, 1) for op in a[:50]]
    assert [o.kind for o in ops1] == [o.kind for o in ops2]
    assert [o.pql for o in ops1] != [o.pql for o in ops2]


@pytest.mark.parametrize("name", TRAFFIC)
def test_schedule_has_exact_shares_in_every_block(name):
    t, n = traffic(name), rows_for(name)
    size = t["block"]["size"]
    tpl = schedule.Template(t, n)
    want = collections.Counter()
    for spec in t["ops"]:
        arity = {"update": "1", "count": str(spec.get("arity")),
                 "topn": "src" if spec.get("src") else "none"}[spec["kind"]]
        want[(spec["kind"], spec.get("op", ""), arity,
              int(spec.get("n", 0)))] += spec["per_block"]
    for b in range(4):
        ops = tpl.ops(b * size, (b + 1) * size)
        got = collections.Counter((o.kind, o.op, o.arity, o.n) for o in ops)
        assert got == want
        for spec in t["ops"]:
            if spec.get("one_per_stripe"):
                stripe = size // spec["per_block"]
                for s in range(spec["per_block"]):
                    kinds = [o.kind for o in ops[s * stripe:(s + 1) * stripe]]
                    assert kinds.count(spec["kind"]) == 1
    # arity 2 draws two distinct ranks; Zipf puts rank 0 first
    ranks = collections.Counter(r for o in tpl.ops(0, 8 * size)
                                for r in o.ranks)
    if ranks:
        assert ranks.most_common(1)[0][0] == 0
    assert all(len(set(o.ranks)) == len(o.ranks) for o in tpl.ops(0, size))


def test_herd_deals_the_lone_schedule():
    """ycsb-b-herd64 is ycsb-b-lone1's sequence dealt to 64 clients."""
    lone, herd = traffic("ycsb-b-lone1"), traffic("ycsb-b-herd64")
    assert lone["ops"] == herd["ops"] and lone["block"] == herd["block"]
    assert lone["template_seed"] == herd["template_seed"]
    assert schedule.Template(lone, 8).ops(0, 480) == \
        schedule.Template(herd, 8).ops(0, 480)


def test_burst_rounds_send_one_shape_to_every_client():
    herd = traffic("ycsb-b-herd64")
    ops = schedule.Template(herd, 8).bursts(herd["clients"], 1)
    shapes = [s for s in herd["ops"] if s["kind"] != "update"]
    assert len(ops) == len(shapes) * herd["clients"]
    for k, spec in enumerate(shapes):
        rnd = ops[k * herd["clients"]:(k + 1) * herd["clients"]]
        assert {(o.kind, o.op, o.arity) for o in rnd} == \
            {("count", spec["op"], str(spec["arity"]))}
    assert ops == schedule.Template(herd, 8).bursts(herd["clients"], 1)
    assert schedule.Template(traffic("ycsb-b-lone1"), 8).bursts(1, 0) == []


# -- the drained window ----------------------------------------------------------


def _done(client, seq, kind, t0, t1, ok=True):
    return window.Done(client, seq, kind, t0, t1, ok, ())


def test_drained_window_counts_every_op_and_its_whole_span():
    """Four clients in lockstep rounds of 1 s, a 3 s stall in the third
    round, issuing stopped at t = 5: the last round is whole, the rate is
    over first send to last completion, the tail holds the stalled ops."""
    log, t = [], 0.0
    for rnd in range(6):
        dur = 4.0 if rnd == 2 else 1.0
        if t >= 5.0:
            break
        for c in range(4):
            kind = "update" if (rnd, c) == (1, 0) else "count"
            log.append(_done(c, rnd * 4 + c, kind, t, t + dur))
        t += dur
    out = window.reduce_window(log)
    assert out["attempted"] == 12 and out["failed"] == 0
    assert out["span_s"] == pytest.approx(6.0)
    assert out["ops_per_s"] == pytest.approx(12 / 6.0)
    assert out["read_p50_ms"] == pytest.approx(1000.0)
    assert out["read_p95_ms"] == pytest.approx(4000.0)
    assert out["write_visible_ms"] == pytest.approx(1000.0)
    assert out["stripes"] == [4 + 4 + 4, 0] or sum(out["stripes"]) == 12
    assert out["by_kind"] == {"count": 11, "update": 1}


def test_failed_ops_count_in_failed_and_in_no_percentile():
    log = [_done(0, i, "count", i, i + 1.0) for i in range(9)]
    log.append(_done(0, 9, "count", 9, 60.0, ok=False))   # unanswered
    out = window.reduce_window(log, failed_seqs={0})        # and one wrong
    assert out["attempted"] == 10 and out["failed"] == 2
    assert out["read_p95_ms"] == pytest.approx(1000.0)
    assert out["ops_per_s"] == pytest.approx(8 / 60.0)


@pytest.mark.parametrize("p,want", [(0.5, 5), (0.95, 10), (0.1, 1), (1.0, 10)])
def test_percentile_is_nearest_rank(p, want):
    assert window.percentile(list(range(10, 0, -1)), p) == want


# -- the reference ---------------------------------------------------------------


def _brute(words, key):
    """Recount one key over (rows, n) uint64 words, bit by bit."""
    bits = np.unpackbits(words.view(np.uint8), axis=1, bitorder="little")
    cols = bits.T  # one row of R bits per column
    return int(sum(reference.bit_eval(key, c) for c in cols))


@pytest.fixture(scope="module")
def dense2():
    """Two slices' worth of rows, cut to 64 words a row so that a bit-by-bit
    recount stays cheap; the container layout is the real one."""
    n_rows = 8
    words = [datagen.dense_words(7, s, n_rows) for s in (0, 1)]
    base = sum(reference.slice_counts(w.reshape(n_rows, -1), n_rows)
               for w in words)
    return n_rows, words, base


def test_count_reference_follows_writes_like_a_recount(dense2):
    n_rows, words, base = dense2
    rng = np.random.default_rng(5)
    cols = [int(c) for c in rng.choice(2 << 20, size=12, replace=False)]
    kept = {c: reference.column_bits(words[c >> 20], n_rows, c & 0xFFFFF)
            for c in cols}
    ref = reference.CountReference(n_rows, base, kept)
    total = np.array(base)
    live = [w.copy() for w in words]
    for i, c in enumerate(cols):
        row = i % n_rows
        d = ref.delta(row, c)
        assert set(np.unique(d)) <= {-1, 0, 1}
        total = total + d
        local = c & 0xFFFFF
        w = live[c >> 20]
        w[row * 16 + (local >> 16), (local & 0xFFFF) >> 6] |= \
            np.uint64(1 << (local & 63))
    recount = sum(reference.slice_counts(w.reshape(n_rows, -1), n_rows)
                  for w in live)
    assert (total == recount).all()
    # and the tabulated popcounts are what a bit-by-bit count says, on a cut
    small = words[0].reshape(n_rows, -1)[:, :48].copy()
    for key, got in zip(reference.count_keys(n_rows),
                        reference.slice_counts(small, n_rows)):
        if key[0] in ("R", "IA", "UA", "DA") or key[1:] in ((0, 1), (3, 2)):
            assert got == _brute(small, key), key


def test_judge_owes_acknowledged_writes_and_allows_overlapping_ones(dense2):
    n_rows, words, base = dense2
    col = next(c for c in range(100, 200)
               if not reference.column_bits(words[0], n_rows, c)[2])
    ref = reference.CountReference(
        n_rows, base, {col: reference.column_bits(words[0], n_rows, col)})
    i = ref.index[("R", 2)]
    b = int(base[i])
    write = [(2, col, 10.0, 11.0)]
    reads = [(("R", 2), 1.0, 2.0, None),     # before the write was sent
             (("R", 2), 9.0, 10.5, None),    # overlaps it: either
             (("R", 2), 10.5, 12.0, None),   # sent before the ack: either
             (("R", 2), 11.5, 12.0, None),   # sent after the ack: owed
             (("R", 3), 11.5, 12.0, None)]   # another row: untouched
    got = ref.ranges(reads, write)
    assert got[:4] == [(b, b), (b, b + 1), (b, b + 1), (b + 1, b + 1)]
    j = ref.index[("R", 3)]
    assert got[4] == (int(base[j]), int(base[j]))


def test_topn_reference_ranks_by_count_then_row():
    ref = reference.TopNReference({5: 10, 2: 10, 9: 30, 1: 0},
                                  {9: {9: 30, 2: 4, 5: 4, 7: 1}})
    assert ref.answer(("T", None, 2)) == [(9, 30), (2, 10)]
    assert ref.answer(("T", None, 100)) == [(9, 30), (2, 10), (5, 10)]
    assert ref.answer(("T", 9, 3)) == [(9, 30), (2, 4), (5, 4)]
    assert ref.answer(("T", 4, 3)) == []


def test_container_words_set_the_bits_named():
    vals = np.array([0, 63, 64, 65535], dtype=np.uint32)
    w = reference.container_words(vals, None)
    assert int(np.bitwise_count(w).sum()) == 4
    assert int(w[0]) == (1 << 63) | 1 and int(w[1]) == 1
    assert int(w[1023]) == 1 << 63


# -- the trace reduction -----------------------------------------------------------


def test_reduce_on_a_hand_made_trace():
    ms = 1_000_000
    trace = {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [["jit_run", 0, 100 * ms]]},
            {"name": "XLA Ops", "events": [
                ["fusion.1", 0, 10 * ms], ["fusion.2", 5 * ms, 10 * ms],
                ["copy.3", 50 * ms, 5 * ms], ["fusion.1", 90 * ms, 10 * ms]]},
        ]},
        {"name": "/host:CPU", "lines": [{"name": "python", "events": [
            ["np.asarray(jax.Array)", 16 * ms, 33 * ms],
            ["handler", 10 * ms, 85 * ms],
            ["PjitFunction(run)", 56 * ms, 30 * ms]]}]},
    ]}
    out = xplane.reduce(trace, 0.2)
    assert out["busy_s"] == pytest.approx(0.030)
    assert out["idle_share"] == pytest.approx(85.0)
    assert out["device_ops"][0] == ["fusion.1", pytest.approx(0.020)]
    gaps = dict(out["idle_gaps"])
    assert gaps["np.asarray_jax.Array"] == pytest.approx(0.035)
    assert gaps["PjitFunction_run"] == pytest.approx(0.035)
    assert xplane.reduce({"planes": [{"name": "/host:CPU", "lines": []}]},
                         1.0) is None


def test_reduce_cuts_the_ops_to_the_marked_window():
    """The profiler records past the window's end; a device that is never
    idle must not read busier than the window is long."""
    ms = 1_000_000
    ops = [["fusion.1", t * ms, 10 * ms] for t in range(0, 130, 10)]
    trace = {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": ops + [["copy.2", 145 * ms, 5 * ms]]}]},
        {"name": "/host:CPU", "lines": [{"name": "tracer", "events": [
            [xplane.WINDOW_MARK, 15 * ms, 100 * ms]]},
            {"name": "python", "events": [["pilosa:plan", 70 * ms, 60 * ms]]}]},
    ]}
    out = xplane.reduce(trace, 0.0999)
    assert out["window_s"] == pytest.approx(0.100)
    assert out["busy_s"] == pytest.approx(0.100)
    assert out["busy_s"] <= out["window_s"]
    assert out["idle_share"] == pytest.approx(0.0, abs=1e-9)
    assert out["device_ops"] == [["fusion.1", pytest.approx(0.100)]]
    assert out["idle_gaps"] == []
    # An idle stretch at either end of the window is a gap too, and the mark
    # itself is never what a gap is charged to.
    trace["planes"][0]["lines"][0]["events"] = [["fusion.1", 40 * ms, 10 * ms]]
    out = xplane.reduce(trace, 0.1)
    assert out["busy_s"] == pytest.approx(0.010)
    assert dict(out["idle_gaps"]) == {
        "unattributed": pytest.approx(0.025),
        "pilosa:plan": pytest.approx(0.065)}
    # No op inside the window: nothing to report.
    trace["planes"][0]["lines"][0]["events"] = [["fusion.1", 120 * ms, 10 * ms]]
    assert xplane.reduce(trace, 0.1) is None


def test_reduce_on_a_recorded_trace():
    """A cut of a trace recorded on the chip (seg-1b.lone1, TPU v5 lite)."""
    path = os.path.join(HERE, "small_trace.json")
    trace = json.load(open(path))
    events = xplane.device_lines(trace)[0][1]
    span = (max(s + d for _, s, d in events) - min(s for _, s, _ in events))
    out = xplane.reduce(trace, span / 1e9)
    assert 0 < out["busy_s"] <= span / 1e9
    assert out["busy_s"] <= sum(d for _, _, d in events) / 1e9 + 1e-12
    assert 0 <= out["idle_share"] < 100
    assert sum(s for _, s in out["idle_gaps"]) == pytest.approx(
        span / 1e9 - out["busy_s"], rel=1e-6)
    assert out["device_ops"] and out["idle_gaps"]
    assert all(re.fullmatch(r"[A-Za-z0-9_.:\-]+", n)
               for n, _ in out["device_ops"] + out["idle_gaps"])


# -- peaks and per-layer readers ----------------------------------------------------


def test_peaks_raise_on_an_unknown_device_kind():
    assert layers.peak_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(layers.UnknownDevice):
        layers.peak_for("TPU v99")
    with pytest.raises(layers.UnknownDevice):
        layers.peak_for("cpu")


def _ctx(**kw):
    base = dict(vars_before={"mesh": {"count": 10, "device_dispatches": 4},
                             "jax_runtime": {"memory": {
                                 "d0": {"peak_bytes_in_use": 5}}}},
                vars_after={"mesh": {"count": 110, "device_dispatches": 29},
                            "jax_runtime": {"memory": {
                                "d0": {"peak_bytes_in_use": 7},
                                "d1": {"peak_bytes_in_use": 9}}}},
                prom_before={'f_total{reason="oom"}': 1.0},
                prom_after={'f_total{reason="oom"}': 3.0,
                            'f_total{reason="error"}': 1.0},
                log=[], trace=None,
                device_kind="TPU v5 lite", config={})
    base.update(kw)
    return layers.Context(**base)


@pytest.mark.parametrize("expr,want", [
    ({"vars": "mesh.count"}, 100),
    ({"vars": "mesh.count", "at": "setup"}, 10),
    ({"vars": "mesh.nothing"}, None),
    ({"ratio": [{"vars": "mesh.count"}, {"vars": "mesh.device_dispatches"}]},
     4.0),
    ({"ratio": [{"vars": "mesh.count"}, {"vars": "mesh.nothing"}]}, None),
    ({"prom": "f_total", "at": "end"}, 4.0),
    ({"prom": "f_total", "match": 'reason="oom"'}, 2.0),
    ({"max_of": "jax_runtime.memory", "key": "peak_bytes_in_use"}, 9.0),
    ({"trace": "idle_share"}, None),
    ({"times": [{"vars": "mesh.count"}, 2]}, 200.0),
    ({"profile": ["parse"], "of": "reads"}, None),
    ({"window": "read_p95_ms"}, None),
])
def test_layer_readers(expr, want):
    got = layers.evaluate(expr, _ctx())
    assert got == want if want is None else got == pytest.approx(want)


def test_profile_readers_take_medians_over_the_profiled_ops():
    def prof(parse, total):
        return {"total_us": total, "phases_us": {"parse": parse, "plan": 1}}
    log = [window.Done(0, i, "count", 0.0, 0.010, True, (), prof(p, 9000))
           for i, p in enumerate((100, 300, 200))]
    log.append(window.Done(0, 3, "count", 0.0, 0.010, True, (), None))
    ctx = _ctx(log=log)
    assert layers.evaluate({"profile": ["parse", "plan"]}, ctx) == 201
    assert layers.evaluate({"profile_gap": "reads"}, ctx) == \
        pytest.approx(1000.0)
    assert layers.evaluate({"profile": ["wal_commit"], "of": "writes"},
                           ctx) is None


def test_window_reader_gives_the_clients_numbers():
    ctx = _ctx(window={"read_p95_ms": 12.5, "reads": 3})
    assert layers.evaluate({"window": "read_p95_ms"}, ctx) == 12.5
    assert layers.evaluate({"window": "write_visible_ms"}, ctx) is None
    spec = layers.load_metric("tail.read_p95_ms")
    assert layers.evaluate(spec["value"], ctx) == 12.5


@pytest.mark.parametrize("counted,kept", [(1, True), (40, False)])
def test_roofline_goes_loudly_when_the_memo_account_is_off(counted, kept,
                                                           capsys):
    """Which reads the memo answered is the harness's account; how many is
    the program's counter. Where they part, no roofline, and a line that
    says so."""
    class P:
        def op_at(self, stream, seq):
            return schedule.BoundOp("count", ("Count(x)",), ("R", 0), None)

    def done(seq, pql):
        return window.Done(0, seq, "count", seq, seq + 0.5, True,
                           ((pql, seq, seq + 0.5, 200, 1),))
    phases = {"window": [done(0, "Count(a)"), done(1, "Count(a)"),
                         done(2, "Count(b)")]}
    ctx = harness._layer_context(
        {"frame": {"kind": "dense", "rows": 8}, "slices": 2},
        {"clients": 1}, P(),
        reference.CountReference(8, np.zeros(141, np.int64), {}, slices=2),
        phases, None, {},
        ({"host_cache": {"query_hit": 5}},
         {"host_cache": {"query_hit": 5 + counted}}), ({}, {}), "TPU v5 lite")
    assert (ctx.lone_hits == {(1, 0)}) if kept else ctx.lone_hits is None
    assert ctx.bytes_of(("I", 0, 1)) == 2 * 2 * 131072
    assert ("roofline left out" in capsys.readouterr().err) is not kept


def test_bytes_a_count_needs():
    assert layers.read_bytes_needed(("I", 0, 1), 8, 960) == 2 * 960 * 131072
    assert layers.read_bytes_needed(("DA", 3), 8, 960) == 8 * 960 * 131072
    assert layers.read_bytes_needed(("R", 3), 8, 960) == 960 * 131072


# -- BENCHMARK.json and the data-driven layout ---------------------------------------

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def test_benchmark_json_keeps_to_the_contract():
    b = BENCHMARK
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= b["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in b[k]]
    assert all(NAME.match(n) for n in names)
    for k in ("configs", "workloads"):
        assert len({x["name"] for x in b[k]}) == len(b[k])
    metrics = [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    assert len(set(metrics)) == len(metrics)
    assert "setup_s" in [m["name"] for m in b["end_to_end"]]
    cells = {w["name"] for w in b["workloads"]}
    e2e = {m["name"]: m for m in b["end_to_end"]}
    for m in b["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in b["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e and set(m["workloads"]) <= cells
        moved = e2e[m["moves"]].get("workloads", cells)
        assert set(m["workloads"]) <= set(moved)
        spec = layers.load_metric(m["name"])
        assert (spec["unit"], spec["layer"], spec["moves"]) == \
            (m["unit"], m["layer"], m["moves"])
    for w in b["workloads"]:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert any(set(m.get("workloads", [w["name"]])) >= {w["name"]}
                   for m in b["per_layer"])
    for c in b["configs"]:
        assert len(c["source"]) <= 200 and len(c["why"]) <= 200
        cfg = json.load(open(os.path.join(REPO, c["file"])))
        assert set(c["reduced"]) == set(cfg["reduced"])
        assert os.path.exists(os.path.join(
            os.path.dirname(os.path.join(REPO, c["file"])),
            cfg["server_toml"]))
    assert len(json.dumps(b)) < 64 * 1024


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_loads_by_name(cell):
    got = harness.load_cell(cell)
    assert got["traffic"]["clients"] >= 1
    assert {m["name"] for m in got["end_to_end"]} >= {"setup_s", "ops_per_s"}
    assert got["per_layer"]
    plan = harness.Plan(got["config"], dict(got["traffic"], max_ops=480), 3)
    assert plan.op_at("window", 479) is not None or \
        got["config"]["frame"]["kind"] == "dense"
    assert plan.op_at("window", 480) is None


def test_a_new_cell_and_metric_are_files_and_entries_only(later_pr):
    """The rehearsal of a later PR: with no file that exists edited, the
    harness finds the new cell, configuration, mix and metric by name."""
    got = harness.load_cell(FIXTURE_CELL)
    assert got["traffic"]["clients"] == 8
    assert got["config"]["frame"]["rows"] == 4096
    assert [m["name"] for m in got["per_layer"]] == ["mesh.deduped"]
    assert {m["name"] for m in got["end_to_end"]} == \
        {"ops_per_s", "read_p50_ms", "setup_s"}
    vals = layers.read_all(["mesh.deduped"], _ctx())
    assert vals == {"mesh.deduped": {"value": 100, "unit": "ops"}}
    plan = harness.Plan(got["config"], got["traffic"], 5)
    assert max(plan.src_rows()) < 4096
    assert plan.op_at("window", 479) is not None


# -- a whole run, with the timed path sound and broken --------------------------------

CONTROLS = [("seg-1b.lone1", "sound", True),
            ("seg-1b.herd64", "stale_writes", False),
            ("seg-1b.lone1", "alter_answer", False),
            (FIXTURE_CELL, "sound", True),
            (FIXTURE_CELL, "approximate_topn", False),
            (FIXTURE_CELL, "alter_answer", False)]


@pytest.mark.parametrize("cell,mode,want", CONTROLS)
def test_control_comes_out_as_it_should(cell, mode, want, later_pr):
    """The reference in the program's place (pbench/control.py), sound and
    with one guarantee broken, through the whole of a run at 2 slices: only
    the sound one is `correct`."""
    out = harness.run_cell(cell, 4_000_000_007, 1.0, False,
                           require_chip=False, slices=2, control=mode)
    assert out["correct"] is want
    assert out["compared"]["answers_compared"]["value"] > 10
    if not want:
        assert out["compared"]["wrong_answers"]["value"] > 0
        assert out["failed"] > 0
    assert out["metrics"]["ops_per_s"]["value"] > 0


PROGRAM_ENV = {"JAX_PLATFORMS": "cpu", "PILOSA_TPU_DEVICE_MIN_WORK": "0",
               "PILOSA_TPU_CPU_ROUTE_NATIVE": "off"}


@pytest.mark.parametrize("fault,control,want,number", [
    (None, None, True, None),
    ("device.exec:delta=1,after=40,times=3", None, False, "wrong_answers"),
    (None, "lost_wal", False, "lost_writes")])
def test_program_on_the_cpu_sound_and_broken(fault, control, want, number,
                                             tmp_path, monkeypatch):
    """The rest of a run without the look for a chip: the real server on the
    CPU backend at 8 slices. As it is; with the program's own fault seam
    altering three Counts where they are produced (fault.perturb on
    device.exec); and with the program's no-fsync WAL path keeping its
    records in memory (`lost_wal`), so that the SIGKILL after the window
    loses what was acknowledged. `correct` has to follow."""
    monkeypatch.setattr(harness, "OUT_DIR", str(tmp_path))
    env = dict(PROGRAM_ENV)
    if fault:
        env["PILOSA_TPU_FAULT"] = fault
    out = harness.run_cell("seg-1b.lone1", 2_500_000_001, 2.0, False,
                           require_chip=False, slices=8, server_env=env,
                           control=control)
    assert out["device"]["platform"] == "cpu"
    assert out["correct"] is want
    cmp_ = out["compared"]
    assert cmp_["unanswered"]["value"] == 0
    assert cmp_["lost_writes"]["of"] >= 1
    assert list(cmp_)[-1] == "lost_writes" and list(out)[-1] == "compared"
    for name in ("wrong_answers", "lost_writes"):
        # three Counts altered (the whole-query memo may repeat each); or
        # every acknowledged SetBit lost
        assert (cmp_[name]["value"] >= 1) == (name == number)
    if number == "lost_writes":
        assert cmp_[name]["value"] == cmp_[name]["of"]
    # Set-up is everything up to the window less the reference's own pass.
    run = json.loads(open(os.path.join(str(tmp_path), "runs.jsonl"))
                     .readlines()[-1])
    marks = run["marks_s"]
    assert 0 < marks["reference_s"] < marks["generate"]
    assert run["setup_s"] == pytest.approx(
        marks["warm"] - marks["reference_s"], abs=0.5)
    assert out["metrics"]["setup_s"]["value"] == run["setup_s"]


@pytest.mark.parametrize("toml,want", [
    ("benchmarks/configs/seg-1b.toml", "xla"),
    ("benchmarks/configs/topn-1b.toml", "xla"),
    ("tests/benchmarks/fixture/configs/topn-fixture.toml", None)])
def test_a_configuration_names_its_count_backend(toml, want):
    """The cells' servers pin the backend (the boot's `auto` pick is a coin on
    a v5e and decides the herd's state); a pinned server calibrates nothing,
    so the harness reads the device once the first query has staged."""
    assert harness._pinned_backend(os.path.join(REPO, toml)) == want


# -- the look at the disk after the kill ---------------------------------------------


def test_durable_reader_finds_bits_in_containers_ops_and_side_log(tmp_path):
    """The plain reader against files the program's serializer wrote: a bit
    in a bitmap container, one in an array container, one only in the op
    log, one set and cleared again, one in the side log, one nowhere; and a
    torn last record, which was never acknowledged."""
    from pilosa_tpu.roaring.bitmap import Bitmap, Container
    from pilosa_tpu.roaring.serialize import write_op

    W = durable.SLICE_WIDTH
    words = np.zeros(1024, dtype=np.uint64)
    words[:] = 0xFFFF  # 16 bits a word: 16,384 bits, a bitmap container
    bm = Bitmap()
    bm.keys = [3 * 16, 5 * 16 + 1]
    bm.containers = [Container(bitmap=words),
                     Container(array=np.array([7, 9], dtype=np.uint32))]
    path = str(tmp_path / "0")
    for footer in (True, False):
        with open(path, "wb") as f:
            bm.write_to(f, footer=footer)
            write_op(f, 0, 6 * W + 123)
            write_op(f, 0, 6 * W + 124)
            write_op(f, 1, 6 * W + 124)
            f.write(b"\x00" + (6 * W + 125).to_bytes(8, "little"))  # torn
        with open(path + ".wal", "wb") as f:
            write_op(f, 0, 2 * W + 70000)
        want = {3 * W + 64: True, 3 * W + 16: False, 5 * W + 65536 + 9: True,
                5 * W + 65536 + 8: False, 6 * W + 123: True,
                6 * W + 124: False, 6 * W + 125: False, 2 * W + 70000: True,
                1 * W + 5: False}
        assert durable.bits_on_disk(path, want) == want
    acked = [(3, 64), (6, 123), (6, 125), (1, 5), (0, 5 * W + 1)]
    lost = durable.lost_writes(
        lambda s: path if s == 0 else str(tmp_path / "none"), acked)
    assert lost == [(6, 125), (1, 5), (0, 5 * W + 1)]
    assert durable.fnv32a(b"a") == 0xE40C292C
