"""TopN under writes, and generators and references found by name: the
reference against a recount, the subset judge, the writable columns, the name
lookup, the dense path pinned to the parent's values, a fixture mix with
updates through a whole run on the CPU, the plain durable reader on a mixed
fragment, the TopN byte count and memo account. No JAX at import; the
end-to-end cases start children.
"""

import hashlib
import itertools
import json
import os
import sys
import textwrap

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
from pbench import (datagen, durable, harness, layers, names,  # noqa: E402
                    reference, schedule, window)

FIXTURE = os.path.join(HERE, "fixture")
CELL = "topn-w-fixture.topn-w-fixture4"
SEEDS = (3, 11, 2_900_000_001, 3_000_000_019, 4_000_000_007)


def load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def fixture_config(slices=4):
    c = load(FIXTURE, "configs", "topn-w-fixture.json")
    return dict(c, slices=slices, columns=slices << 20)


def fixture_traffic(**kw):
    return dict(load(FIXTURE, "traffic", "topn-w-fixture4.json"), **kw)


@pytest.fixture
def fixture_cell(monkeypatch, tmp_path):
    """The fixture configuration and mix as one cell, handed to the harness
    in place of a look in BENCHMARK.json."""
    bench = load(REPO, "BENCHMARK.json")

    def load_cell(workload):
        assert workload == CELL
        return {"cell": {"name": CELL, "chips": 1},
                "config": fixture_config(), "traffic": fixture_traffic(),
                "config_dir": os.path.join(FIXTURE, "configs"),
                "end_to_end": bench["end_to_end"],
                "per_layer": []}
    monkeypatch.setattr(harness, "load_cell", load_cell)
    monkeypatch.setattr(harness, "OUT_DIR", str(tmp_path / "out"))


# -- the reference under writes ---------------------------------------------------


def in_memory(seed, slices=3, n_candidates=400):
    """The fixture frame's slices as words, and their TopN reference with every
    row tabulated as a src, made without a disk or a worker."""
    frame = fixture_config()["frame"]
    cands = datagen.block0_candidates(seed, slices << 20, n_candidates)
    words, parts = {}, []
    for s in range(slices):
        rows, conts = datagen.mixed_containers(seed, s, frame)
        w = np.stack([reference.container_words(v, b) for v, b in conts])
        words[s] = (list(int(r) for r in rows), w.copy())
        local = [int(c) & 0xFFFFF for c in cands if int(c) >> 20 == s]
        parts.append((s, reference.TopNReference.slice_part(
            frame, rows, w, local, range(int(frame["rows"])))))
    return frame, words, reference.TopNReference.assemble(frame, parts, cands)


def recount(words, n_rows):
    """|row| and |row & src| for every pair, from the words as they stand."""
    totals = np.zeros(n_rows, dtype=np.int64)
    inter = np.zeros((n_rows, n_rows), dtype=np.int64)
    for rows, w in words.values():
        totals[rows] += np.bitwise_count(w).sum(axis=1).astype(np.int64)
        for i, x in enumerate(rows):
            inter[x, rows] += np.bitwise_count(w & w[i]).sum(
                axis=1).astype(np.int64)
    return totals, inter


@pytest.mark.parametrize("seed", SEEDS)
def test_topn_reference_follows_writes_like_a_recount(seed):
    frame, words, ref = in_memory(seed)
    n_rows = int(frame["rows"])
    live, rng, written = ref.live(), np.random.default_rng(seed), 0
    as_generated = [ref.answer(("R", r)) for r in range(n_rows)]
    for c in ref.candidates():
        row = int(rng.integers(n_rows))
        if not ref.can_write(row, c):
            continue
        live.set_bit(row, c)
        rows, w = words[c >> 20]
        low = c & 0xFFFF
        assert not (int(w[rows.index(row), low >> 6]) >> (low & 63)) & 1
        w[rows.index(row), low >> 6] |= np.uint64(1 << (low & 63))
        written += 1
        if written == 60:
            break
    assert written == 60
    totals, inter = recount(words, n_rows)
    for n in (5, 100):
        assert live.answer(("T", None, n)) == reference.rank_top(
            dict(enumerate(totals.tolist())), n)
        for x in range(n_rows):
            assert live.answer(("T", x, n)) == reference.rank_top(
                dict(enumerate(inter[x].tolist())), n), x
    assert [live.answer(("R", r)) for r in range(n_rows)] == totals.tolist()
    # and the reference itself still answers as generated
    assert [ref.answer(("R", r)) for r in range(n_rows)] == as_generated
    assert sum(totals.tolist()) == sum(as_generated) + written


def _pairs(ranking):
    return [{"id": r, "count": c} for r, c in ranking]


def _judge_ref():
    """Rows 0..3 tied so that single bits reorder them; columns 100..119 hold
    row 3 as generated, so a write there moves TopN(src=3) too."""
    totals = {0: 10, 1: 10, 2: 10, 3: 7}
    by_src = {3: {3: 7, 0: 2, 1: 2, 2: 2}}
    kept = {c: frozenset([3]) for c in range(100, 120)}
    return reference.TopNReference(totals, by_src, kept,
                                   {0: frozenset(range(4))}, range(100, 120))


@pytest.mark.parametrize("k", [0, 1, 3])
def test_subset_judge_accepts_exactly_the_reachable_rankings(k):
    """A read overlapping k writes, after one acknowledged write and before
    one not yet sent: right if and only if it is the exact ranking with the
    acknowledged write and some subset of the k applied."""
    ref = _judge_ref()
    writes = [(2, 100, 0.0, 1.0)]                      # acknowledged: owed
    writes += [(r, 101 + r, 4.0, 6.0 + r) for r in range(k)]   # overlap
    writes += [(0, 110, 9.0, 10.0)]                    # sent after the reply
    for key in (("T", None, 4), ("T", 3, 4)):
        base = dict(ref.totals if key[1] is None else ref.by_src[3])
        base[2] += 1
        reachable = set()
        for n in range(k + 1):
            for sub in itertools.combinations(range(k), n):
                counts = dict(base)
                for r in sub:
                    counts[r] += 1
                reachable.add(tuple(reference.rank_top(counts, 4)))
        assert len(reachable) == 2 ** k
        wrong = [reference.rank_top({**base, 0: base[0] + 2}, 4),
                 reference.rank_top({**base, 2: base[2] - 1}, 4),
                 reference.rank_top(base, 4)[:3]]
        if k < 3:  # the write at t = 9 applied: reachable only for k = 3
            wrong.append(reference.rank_top({**base, 0: base[0] + 1}, 4))
        asked = [list(r) for r in sorted(reachable)] + \
            [w for w in wrong if tuple(w) not in reachable]
        reads = [(key, 5.0, 5.5, _pairs(a)) for a in asked]
        verdicts = ref.judge(reads, writes)
        assert verdicts[:len(reachable)] == [None] * len(reachable)
        assert all(v is not None and v[0] == reference.WRONG
                   for v in verdicts[len(reachable):])
        assert len(verdicts) > len(reachable)
    # the read-back of a Count is a range over the same writes
    got = ref.judge([(("R", 0), 5.0, 5.5, 10 + int(k > 0)),
                     (("R", 2), 5.0, 5.5, 11),
                     (("R", 0), 5.0, 5.5, 12)], writes)
    assert got[:2] == [None, None] and got[2][0] == reference.WRONG


def test_a_read_overlapping_more_than_ten_writes_is_not_judged():
    ref = _judge_ref()
    writes = [(r % 3, 100 + r, 4.0, 6.0) for r in range(11)]
    reads = [(("T", None, 3), 5.0, 5.5, _pairs([(0, 10), (1, 10), (2, 10)])),
             (("T", None, 3), 7.0, 7.5, _pairs([(0, 14), (1, 14), (2, 13)])),
             (("T", 3, 3), 5.0, 5.5, "not a ranking")]
    got = ref.judge(reads, writes)
    assert got[0][0] == reference.NOT_JUDGED and got[1] is None
    assert got[2][0] == reference.WRONG
    # ten are still judged: 1,024 subsets, the empty one among them
    assert ref.judge(reads[:1], writes[:10]) == [None]

    log = [window.Done(0, i, "topn", r[1], r[2], True,
                       (("TopN(x)", r[1], r[2], 200, r[3]),))
           for i, r in enumerate(reads[:2])]
    ws = [window.Done(0, 2 + i, "update", w[2], w[3], True,
                      (("SetBit(x)", w[2], w[3], 200, True),))
          for i, w in enumerate(writes)]

    class P:
        def op_at(self, stream, seq):
            if seq < 2:
                return schedule.BoundOp("topn", ("TopN(x)",), ("T", None, 3),
                                        None)
            return schedule.BoundOp("update", ("SetBit(x)",), ("R", 0),
                                    writes[seq - 2][:2])
    out = harness.compare(ref, {"window": log + ws}, P())
    assert (out["not_judged"], out["wrong_answers"]) == (1, 0)
    assert out["answers_compared"] == 2 + 11 and len(out["acked"]) == 11


@pytest.mark.parametrize("seed", SEEDS[:3])
def test_can_write_never_yields_a_container_creating_write(seed, tmp_path):
    """Through the generator on disk and the plan, as a run does it: every
    update's column lies in a (row, slice) that holds a container and is
    clear there, so no SetBit of a run restages the view."""
    config = fixture_config(slices=3)
    plan = harness.Plan(config, fixture_traffic(max_ops=960), seed)
    ref = names.kind(config).generate(config, seed, str(tmp_path), plan)
    plan.assign_columns(ref.candidates(), ref.can_write)
    assert len(plan.columns) == len(plan.updates()) > 40
    assert len(set(plan.columns.values())) == len(plan.columns)
    for (stream, i), c in plan.columns.items():
        row, col = plan.op_at(stream, i).write
        assert col == c and col & 0xFFFFF < 65536
        rows, conts = datagen.mixed_containers(seed, col >> 20,
                                               config["frame"])
        at = list(rows).index(row)  # ValueError: no container for the row
        w = reference.container_words(*conts[at])
        assert not (int(w[(col & 0xFFFF) >> 6]) >> (col & 63)) & 1
    absent = next(r for r in range(32) if r not in ref.present[0])
    assert not ref.can_write(absent, ref.candidates()[0] & 0xFFFF)


@pytest.mark.parametrize("slice_words,frame", [
    (datagen._dense_slice, {"name": "dense", "rows": 2}),
    (datagen._mixed_slice, None)])
def test_the_reference_pass_writes_nothing_and_is_timed_apart(
        slice_words, frame, tmp_path):
    """Generation is two passes: the fragment on disk (set-up), then the same
    words made again from the seed for the reference alone, timed by itself so
    that `setup_s` leaves those seconds out."""
    config = fixture_config(slices=2)
    frame = frame or config["frame"]
    data = str(tmp_path / "data")
    datagen.create_schema(data, "i", frame["name"])
    rows, words = slice_words(7, 1, None, "i", frame)
    assert words.shape == (len(rows), words.shape[1]) and words.dtype == np.uint64
    path = datagen.frag_path(data, "i", frame["name"], 1)
    assert not os.path.exists(path)
    same_rows, nothing = slice_words(7, 1, data, "i", frame)
    assert list(same_rows) == list(rows) and nothing is None
    assert os.path.getsize(path) > 0
    plan = harness.Plan(config, fixture_traffic(max_ops=240), 7)
    ref = names.kind(config).generate(config, 7, str(tmp_path / "run"), plan)
    assert 0.0 < ref.tabulated_s < 60.0


# -- found by name -------------------------------------------------------------------

TOY_KIND = '''
from pbench import names

def generate(config, seed, data_dir, plan, **kw):
    ref = names.reference(config)
    return ref.assemble(config["frame"],
                        [(0, ref.slice_part(config["frame"], [0, 1], None,
                                            (), plan.src_rows()))],
                        range(5, 69))

def stage_query(frame):
    return f'TopN(frame="{frame}", n=1)', ("T", None, 1), "topn"
'''
TOY_REF = '''
class Toy:
    """Row 0 leads until anything is written; every column is writable."""
    def __init__(self, candidates):
        self._c = list(candidates)
    def candidates(self):
        return self._c
    def can_write(self, row, column):
        return True
    def judge(self, reads, writes):
        want = [{"id": 0, "count": 2}]
        return [None if r[3] == want else ("wrong", "reference row 0")
                for r in reads]
    def bytes_needed(self, key):
        return 8_190_000
    def memo_account(self, key):
        return "toy.recounts", "misses"

def slice_part(frame, rows, words, locals_, src_rows=()):
    return {"rows": list(rows)}

def assemble(frame, parts, candidates, weight=1):
    return Toy(candidates)
'''


@pytest.fixture
def toy_names(tmp_path, monkeypatch):
    """What a later PR adds: one file under kinds/, one under refs/."""
    for pkg, name, text in ((pbench.kinds, "toykind", TOY_KIND),
                            (pbench.refs, "toyref", TOY_REF)):
        d = tmp_path / pkg.__name__.rsplit(".", 1)[1]
        d.mkdir()
        (d / f"{name}.py").write_text(textwrap.dedent(text))
        monkeypatch.setattr(pkg, "__path__", list(pkg.__path__) + [str(d)])
    yield
    for m in ("pbench.kinds.toykind", "pbench.refs.toyref"):
        sys.modules.pop(m, None)


def test_a_new_kind_and_reference_are_found_by_name(toy_names, tmp_path):
    """A fixture cell's generation and comparison through a toy kind and a toy
    reference that no file of the harness knows."""
    config = fixture_config()
    config["frame"] = dict(config["frame"], kind="toykind")
    config["correctness"] = {"reference": "toyref"}
    plan = harness.Plan(config, fixture_traffic(max_ops=48), 9)
    kind = names.kind(config)
    ref = kind.generate(config, 9, str(tmp_path), plan)
    plan.assign_columns(ref.candidates(), ref.can_write)
    assert sorted(plan.columns.values()) == list(
        range(5, 5 + len(plan.updates())))
    pql, key, op_kind = kind.stage_query("ranked")
    good, bad = [{"id": 0, "count": 2}], [{"id": 1, "count": 2}]
    stage = window.Done(0, -1, op_kind, 0.0, 0.1, True,
                        ((pql, 0.0, 0.1, 200, good),), None, key)
    first = plan.op_at("window", 0)
    log = [window.Done(0, 0, first.kind, 1.0, 1.1, True,
                       tuple((q, 1.0, 1.1, 200, True if q.startswith("SetBit")
                              else bad) for q in first.pql))]
    out = harness.compare(ref, {"stage": [stage], "window": log}, plan)
    assert out["wrong_answers"] == 1 and out["wrong_seqs"] == {0}
    assert "not_judged" not in out  # only a reference that can decline
    # Its roofline too: the bytes a read needs and the program's counter that
    # the memo account is held to are the reference's to name.
    reads = [_topn_done(i, "TopN(a)", 1.0 + i) for i in range(2)]
    ctx = harness._layer_context(
        config, {"clients": 1}, plan, ref, {"window": reads},
        {"t0": 0.5, "t1": 3.0, "busy_s": 0.001, "window_s": 2.5}, {},
        ({"toy": {"recounts": 3}}, {"toy": {"recounts": 4}}), ({}, {}),
        "TPU v5 lite")
    assert ctx.lone_hits == {(1, 0)}  # the repeat, a memo hit: one recount
    assert layers.evaluate({"trace": "hbm_roofline_share"}, ctx) == \
        pytest.approx(100 * 8_190_000 / 0.001 / 819e9)


@pytest.mark.parametrize("where,key", [("frame", "kind"),
                                       ("correctness", "reference")])
def test_an_unknown_name_is_an_error_that_lists_the_known(where, key):
    config = fixture_config()
    config[where] = dict(config[where], **{key: "no-such-name"})
    find = names.kind if where == "frame" else names.reference
    with pytest.raises(names.UnknownName) as e:
        find(config)
    known = ("dense", "mixed") if where == "frame" else ("counts", "topn")
    assert all(k in str(e.value) for k in known + ("no-such-name",))
    found = find(fixture_config())
    assert found.__name__ == ("pbench.kinds.mixed" if where == "frame"
                              else "pbench.refs.topn")
    assert (found.generate.__func__ is datagen.Kind.generate
            if where == "frame"
            else found.assemble.__func__
            is reference.TopNReference.assemble.__func__)


# -- what a cell generates and binds, pinned --------------------------------------------

PINS = {name: load(FIXTURE, "pins", name + ".2slices.json")
        for name in ("seg-1b", "topn-1b", "topn-ingest-1b")}


def _sha(obj):
    return hashlib.sha256(json.dumps(obj).encode()).hexdigest()


def _tables(ref):
    """The reference's tables in the pins' form: a count reference's `base`
    and kept column bits; a TopN reference's |row|, |row & src|, rows kept
    at each candidate column, rows present in each slice and row bytes."""
    if hasattr(ref, "base"):
        return {"base": [int(x) for x in ref.base],
                "kept_sha256": _sha(sorted((int(c), [int(b) for b in bits])
                                           for c, bits in ref.kept.items()))}

    def pairs(d):
        return sorted([int(k), int(v)] for k, v in d.items())

    def sets(d):
        return _sha(sorted([int(k), sorted(int(r) for r in v)]
                           for k, v in d.items()))
    return {"totals": pairs(ref.totals),
            "by_src": sorted([int(x), pairs(d)]
                             for x, d in ref.by_src.items()),
            "kept_sha256": sets(ref.kept),
            "present_sha256": sets(ref.present),
            "row_bytes_sha256": _sha(pairs(ref.row_bytes))}


@pytest.mark.parametrize("name,seed", [(n, s) for n in sorted(PINS)
                                       for s in sorted(PINS[n]["seeds"])])
def test_dense_path_is_what_it_was_on_the_parent(name, seed, tmp_path):
    """seg-1b, topn-1b and topn-ingest-1b at 2 slices: the fragment files, the
    reference's tables, the candidates, assign_columns' picks, the first 64
    bound ops, every update as bound and the staging query, against values
    taken from the parent commit's code (fixture/pins/): the driver lays this
    benchmark over the parent, and a reading that moves with the harness
    alone would be the benchmark's doing. (The two TopN cells are pinned
    with `max_ops` cut and on seeds whose updated rows hold a container in
    one of the two slices: at 960 slices every row does.)"""
    pins = PINS[name]
    want = pins["seeds"][seed]
    cell = harness.load_cell(pins["cell"])
    config = dict(cell["config"], slices=2, columns=2 << 20)
    traffic = dict(cell["traffic"],
                   max_ops=pins.get("max_ops", cell["traffic"]["max_ops"]))
    plan = harness.Plan(config, traffic, int(seed))
    kind = names.kind(config)
    ref = kind.generate(config, int(seed), str(tmp_path), plan)
    digests = []
    for s in (0, 1):
        with open(datagen.frag_path(str(tmp_path), config["index"],
                                    config["frame"]["name"], s), "rb") as f:
            digests.append(hashlib.sha256(f.read()).hexdigest())
    assert digests == want["fragments_sha256"]
    for table, got in _tables(ref).items():
        assert got == want[table], table
    cands = [int(c) for c in ref.candidates()]
    assert len(cands) == want["n_candidates"]
    assert cands[:16] == want["candidates_head"]
    assert _sha(cands) == want.get("candidates_sha256", _sha(cands))
    plan.assign_columns(ref.candidates(), ref.can_write)
    picks = sorted((s, i, c) for (s, i), c in plan.columns.items())
    assert [list(p) for p in picks[:40]] == want["picks_head"]
    assert _sha(picks) == want["picks_sha256"]

    def bound(o):
        assert o.frame is None  # one frame: a write is (row, column) alone
        return [o.kind, list(o.pql), list(o.key),
                list(o.write) if o.write else None]
    assert [bound(plan.op_at("window", i)) for i in range(64)] == want["ops"]
    if "updates_sha256" in want:
        assert _sha([[stream, i, *bound(plan.op_at(stream, i))[1:]]
                     for stream, i, _ in plan.updates()]) == \
            want["updates_sha256"]
        pql, key, op_kind = kind.stage_query(config["frame"]["name"])
        assert [pql, list(key), op_kind] == want["stage_query"]


# -- a whole run of a mix with updates ------------------------------------------------


@pytest.mark.parametrize("mode,want", [("sound", True),
                                       ("stale_writes", False),
                                       ("approximate_topn", False)])
def test_control_with_updates_comes_out_as_it_should(mode, want,
                                                     fixture_cell):
    """The reference behind the HTTP entry under four clients whose reads
    overlap writes: the subset judge passes the sound one whole, and fails
    the one that acknowledges a SetBit and never applies it."""
    out = harness.run_cell(CELL, 3_100_000_003, 1.5, False,
                           require_chip=False, control=mode)
    cmp_ = out["compared"]
    assert out["correct"] is want
    assert list(cmp_) == ["wrong_answers", "unanswered", "not_judged",
                          "answers_compared"]
    assert cmp_["not_judged"]["value"] == 0
    assert cmp_["answers_compared"]["value"] > 50
    assert (cmp_["wrong_answers"]["value"] > 0) is not want


PROGRAM_ENV = {"JAX_PLATFORMS": "cpu", "PILOSA_TPU_DEVICE_MIN_WORK": "0",
               "PILOSA_TPU_CPU_ROUTE_NATIVE": "off"}


@pytest.mark.parametrize("control,want,number", [
    (None, True, None), ("lost_wal", False, "lost_writes")])
def test_program_on_the_cpu_ranks_under_writes(control, want, number,
                                               fixture_cell):
    """The real server on the CPU backend at 4 slices: TopN from the device
    path while four clients write into array and bitmap containers, every
    answer judged, every acknowledged SetBit looked for after the SIGKILL;
    and with the no-fsync WAL path keeping its records in memory."""
    out = harness.run_cell(CELL, 2_700_000_001, 2.0, False,
                           require_chip=False, server_env=dict(PROGRAM_ENV),
                           control=control)
    cmp_ = out["compared"]
    assert out["device"]["platform"] == "cpu"
    assert out["correct"] is want, cmp_
    assert cmp_["lost_writes"]["of"] >= 2
    for name in ("wrong_answers", "unanswered", "not_judged"):
        assert cmp_[name]["value"] == 0
    assert (cmp_["lost_writes"]["value"] == cmp_["lost_writes"]["of"]) \
        is (number == "lost_writes")
    assert (cmp_["lost_writes"]["value"] == 0) is want


# -- the look at the disk, on a mixed fragment ----------------------------------------


def test_durable_reader_finds_setbits_in_a_mixed_fragment(tmp_path):
    """A generated mixed fragment, opened and written by the program: a bit
    into an array container, one into a bitmap container, both acknowledged
    (so in the op log), against bits of the snapshot's own containers and
    bits that were never set."""
    from pilosa_tpu.core import Holder

    config = fixture_config(slices=1)
    frame = config["frame"]
    data = str(tmp_path / "data")
    datagen.create_schema(data, "i", frame["name"])
    rows, _ = datagen._mixed_slice(5, 0, data, "i", frame)
    _, conts = datagen.mixed_containers(5, 0, frame)
    array = next(i for i, (v, b) in enumerate(conts) if b is None)
    bitmap = next(i for i, (v, b) in enumerate(conts) if v is None)

    def clear_column(i, nth=0):
        w = reference.container_words(*conts[i])
        return [c for c in range(2000)
                if not (int(w[c >> 6]) >> (c & 63)) & 1][nth]
    acked = [(int(rows[array]), clear_column(array)),
             (int(rows[bitmap]), clear_column(bitmap))]
    generated = [(int(rows[array]), int(conts[array][0][0])),
                 (int(rows[bitmap]), next(
                     c for c in range(65536)
                     if (int(conts[bitmap][1][c >> 6]) >> (c & 63)) & 1))]
    never = [(int(rows[array]), clear_column(array) + 65536),
             (int(rows[array]), clear_column(array, 1)),
             (int(rows[bitmap]), clear_column(bitmap, 1))]
    h = Holder(data)
    h.open()
    try:
        frag = h.fragment("i", frame["name"], datagen.VIEW, 0)
        for row, col in acked:
            assert frag.set_bit(row, col) is True
        path = datagen.frag_path(data, "i", frame["name"], 0)
        lost = durable.lost_writes(lambda s: path,
                                   acked + generated + never)
    finally:
        h.close()
    assert lost == never


# -- what a TopN read needs, and which reads the memo answered -----------------------


def test_bytes_a_topn_read_needs():
    """As roaring holds the generated containers, not the dense staging's
    8 KB for each: an array 2 B a value, over 4,096 values a bitmap."""
    assert layers.container_bytes_needed([1, 4096, 4097, 30000]) == \
        2 + 8192 + 8192 + 8192
    parts = [(0, {"rows": np.array([0, 2]), "counts": np.array([10, 5000]),
                  "src": np.array([], np.int64), "inter": np.zeros((0, 2)),
                  "kept": {}}),
             (1, {"rows": np.array([2, 3]), "counts": np.array([100, 7]),
                  "src": np.array([], np.int64), "inter": np.zeros((0, 2)),
                  "kept": {}})]
    ref = reference.TopNReference.assemble({"rows": 4}, parts, ())
    assert ref.row_bytes == {0: 20, 2: 8192 + 200, 3: 14}
    whole = 20 + 8192 + 200 + 14
    assert ref.bytes_needed(("T", None, 100)) == whole
    assert ref.bytes_needed(("T", 2, 100)) == whole + 8392
    assert ref.bytes_needed(("R", 2)) == 8392
    assert ref.bytes_needed(("R", 1)) == 0
    assert ref.memo_account(("R", 2)) == ("host_cache.query_hit", "hits")
    assert ref.memo_account(("T", 2, 5)) == ("mesh.memo_store", "misses")


def _topn_done(seq, pql, t):
    return window.Done(0, seq, "topn", t, t + 0.5, True,
                       ((pql, t, t + 0.5, 200, []),))


@pytest.mark.parametrize("stores,kept", [(2, True), (9, False)])
def test_topn_roofline_rests_on_the_memo_account(stores, kept, capsys):
    """A TopN asks the mesh's limb memo, not the host's query cache: the
    harness's account of which reads repeated a text since the last SetBit is
    held to the program's count of stores. Where they agree the roofline
    divides the bytes of the first sightings inside the traced window by the
    busy time; where they part it is left out, and a line says so."""
    class P:
        def op_at(self, stream, seq):
            key = ("T", None, 100) if seq != 1 else ("T", 3, 100)
            return schedule.BoundOp("topn", ("TopN(x)",), key, None)
    phases = {"window": [_topn_done(0, "TopN(a)", 0.0),
                         _topn_done(1, "TopN(b)", 1.0),
                         _topn_done(2, "TopN(a)", 2.0)]}
    config = {"frame": {"kind": "mixed", "rows": 256, "rows_per_slice": 230},
              "slices": 960}
    ref = reference.TopNReference({}, {}, row_bytes={3: 1000, 4: 50000})
    trace = {"t0": 0.5, "t1": 3.0, "busy_s": 0.01, "window_s": 2.5}
    ctx = harness._layer_context(
        config, {"clients": 1}, P(), ref, phases, trace, {},
        ({"host_cache": {"query_hit": 5}, "mesh": {"memo_store": 1}},
         {"host_cache": {"query_hit": 5}, "mesh": {"memo_store": 1 + stores}}),
        ({}, {}), "TPU v5 lite")
    err = capsys.readouterr().err
    got = layers.evaluate(
        layers.load_metric("topn_reads_roofline")["value"], ctx)
    if kept:
        assert ctx.lone_hits == {(2, 0)} and "left out" not in err
        assert got == pytest.approx(100 * (51000 + 1000) / 0.01 / 819e9)
    else:
        assert ctx.lone_hits is None and got is None
        assert "memo misses (mesh.memo_store)" in err


def test_a_reference_without_the_two_methods_gets_no_roofline():
    class P:
        def op_at(self, stream, seq):
            return schedule.BoundOp("topn", ("TopN(x)",), ("T", None, 5), None)
    ctx = harness._layer_context(
        {}, {"clients": 1}, P(), object(),
        {"window": [_topn_done(0, "TopN(a)", 1.0)]},
        {"t0": 0.5, "t1": 3.0, "busy_s": 0.01, "window_s": 2.5}, {},
        ({}, {}), ({}, {}), "TPU v5 lite")
    assert ctx.lone_hits is None and ctx.bytes_of is None
    assert layers.evaluate({"trace": "hbm_roofline_share"}, ctx) is None
