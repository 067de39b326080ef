"""The rehearsal of a later PR that adds a deployment of two frames (Star
Trace in miniature: fixture/configs/startrace-fixture.json, its kind, its
reference and its mix) as new files only. In a tree where no file of the
benchmark is edited the harness finds the cell, lets the kind bind PQL it has
never sent (a TopN whose src row lies in the other frame, in both directions;
a Count over both frames; a SetBit that names its frame), hands the frame of
every write to the reference, the control and the look at the disk, and
`correct` follows the timed path, sound and broken. No JAX at import; the
end-to-end cases start children.
"""

import collections
import filecmp
import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

from test_benchmarks import (BENCH, PROGRAM_ENV, STAR_CELL,  # noqa: E402,F401
                             later_pr)

from pbench import harness, names, reference, schedule  # noqa: E402

SEEDS = (3, 2_900_000_001, 4_000_000_007)
# The program's server of this fixture gets a CPU mesh of one device, as a
# one-chip cell has. On the tests' mesh of 8 virtual CPU devices (and of 2) the
# program now and then ranks `stargazer` wrongly from a star's container
# patch on, under these four clients (PERF.md, section 7, first: a fault of
# the program, found by this rehearsal; 0 of 77 boots on one device).
ONE_DEVICE = {"XLA_FLAGS": "--xla_force_host_platform_device_count=1"}
SRC_FORMS = {("stargazer", "language"), ("language", "stargazer")}


def star_cell(seed, **traffic):
    cell = harness.load_cell(STAR_CELL)
    plan = harness.Plan(cell["config"], dict(cell["traffic"], **traffic),
                        seed)
    return cell, plan


def test_two_frame_cell_is_found_with_no_file_edited(later_pr):
    cell, plan = star_cell(5)
    config = cell["config"]
    assert [f["name"] for f in names.frames(config)] == \
        ["language", "stargazer"] and "frame" not in config
    assert 4 <= config["slices"] <= 8 and config["index"] == "repository"
    assert 8 <= plan.frames["language"].n_rows <= 16
    assert plan.frames["stargazer"].n_rows >= 2048
    assert plan.default.name == "language"
    kind = names.kind(config)
    assert kind.__name__ == "pbench.kinds.startrace_fixture"
    assert plan.bind is kind.bind and hasattr(kind, "stage_queries")
    assert names.reference(config).__name__ == \
        "pbench.refs.startrace_fixture"
    # The tree is the benchmark's own, every file as it is, and the fixture's
    # beside them.
    for sub in ("", "pbench", "pbench/kinds", "pbench/refs", "configs",
                "traffic", "layer_metrics"):
        theirs = os.path.join(BENCH, sub)
        same = [f for f in os.listdir(theirs)
                if os.path.isfile(os.path.join(theirs, f))]
        assert filecmp.cmpfiles(theirs, later_pr / "benchmarks" / sub, same,
                                shallow=False)[0] == same
    assert os.path.exists(later_pr / "benchmarks/pbench/kinds"
                          / "startrace_fixture.py")


def test_traffic_that_names_frames_draws_each_rank_over_its_own(later_pr):
    """The mix's composition is fixed whatever the seed, exact in every block
    with one star a stripe; a rank lies inside the row count of the frame it
    was drawn over, and the hot `stargazer` rows follow that frame's own
    theta."""
    cell, plan = star_cell(5, max_ops=960)
    t = cell["traffic"]
    assert plan.abstract == star_cell(4_000_000_007, max_ops=960)[1].abstract
    size = t["block"]["size"]
    want = collections.Counter()
    for spec in t["ops"]:
        want[(spec["kind"], tuple(spec["frames"]))] += spec["per_block"]
    ops = plan.abstract["window"]
    for b in range(len(ops) // size):
        block = ops[b * size:(b + 1) * size]
        assert collections.Counter((o.kind, o.frames) for o in block) == want
        assert [sum(o.kind == "update" for o in block[i:i + 12])
                for i in (0, 12)] == [1, 1]
    for o in ops:
        assert len(o.ranks) == {"update": 1, "count": 3}.get(
            o.kind, len(o.frames) - 1)
        for k, rank in enumerate(o.ranks):
            assert 0 <= rank < plan.frames[o.frames[k]].n_rows
        pairs = list(zip(o.frames, o.ranks))
        assert len(set(pairs)) == len(pairs)   # distinct within a frame
    hot = collections.Counter(o.ranks[0] for o in ops if o.kind == "update")
    assert max(o.ranks[0] for o in ops if o.frames[0] == "stargazer"
               and o.ranks) >= 12   # past any language rank
    flat = schedule.Template(dict(t, zipf_theta_by_frame={}), 12, "window",
                             {"language": 12, "stargazer": 2048}).ops(0, 960)
    assert flat != ops and [o.kind for o in flat] == [o.kind for o in ops]
    assert hot and sum(hot.values()) == 80


def test_kind_binds_both_directions_and_the_write_names_its_frame(later_pr):
    from pbench.refs import startrace_fixture

    cell, plan = star_cell(2_900_000_001, max_ops=480)
    ref = names.kind(cell["config"]).generate(
        cell["config"], 2_900_000_001, str(later_pr / "data"), plan)
    assert set(ref.candidates()) == {"stargazer"}
    plan.assign_columns(ref.candidates(), ref.can_write)
    assert len(plan.columns) == len(plan.updates()) > 40
    assert len(set(plan.columns.values())) == len(plan.columns)
    bound = [plan.op_at("window", i) for i in range(480)]
    seen = set()
    for o in bound:
        if o.kind == "update":
            u, col = o.write
            assert o.frame == "stargazer" and o.key == ("R", u)
            assert o.pql == (
                f'SetBit(rowID={u}, frame="stargazer", columnID={col})',
                f'Count(Bitmap(rowID={u}, frame="stargazer"))')
            assert col not in ref.stars[u] and col in ref.language_of
        elif o.kind == "topn" and o.key[2] is not None:
            _, ranked, src, row, n = o.key
            seen.add((src, ranked))
            assert o.pql == (f'TopN(Bitmap(rowID={row}, frame="{src}"), '
                             f'frame="{ranked}", n={n})',)
        elif o.kind == "count":
            assert [f for f, _ in o.key[1:]] == \
                ["stargazer", "stargazer", "language"]
            assert o.pql[0].startswith("Count(Intersect(Bitmap(rowID=")
        assert o.kind == "update" or \
            startrace_fixture.key_of(o.pql[-1]) == o.key
    assert seen == SRC_FORMS
    assert {o.kind for o in bound} == {"update", "topn", "count"}
    stage = names.kind(cell["config"]).stage_queries(cell["config"])
    assert [q[0].split("(")[0] for q in stage] == ["TopN", "Count", "TopN"]
    assert all(startrace_fixture.key_of(q[0]) == q[1] for q in stage)
    assert {f for q in stage for f in ("language", "stargazer")
            if f in q[0]} == {"language", "stargazer"}


def test_frames_in_a_mix_need_a_kind_that_binds_them():
    """`schedule.bind` speaks one frame: a mix that names frames over a kind
    with no `bind` of its own is refused when the plan is made, and an op
    kind the schedule does not know reaches the kind as written."""
    cell = harness.load_cell("topn-1b.lone1")
    named = dict(cell["traffic"], ops=[
        dict(o, frames=["ranked"]) for o in cell["traffic"]["ops"]])
    with pytest.raises(ValueError, match="has to bind them"):
        harness.Plan(cell["config"], named, 3)
    tpl = schedule.Template(
        dict(cell["traffic"], block={"size": 2}, ops=[
            {"kind": "range", "op": "><", "arity": 2, "n": 7,
             "frames": ["a", "b", "c"], "per_block": 2}]),
        8, "window", {"a": 4, "b": 300, "c": 5})
    op = tpl.op(0)
    assert (op.kind, op.op, op.arity, op.n, op.frames) == \
        ("range", "><", "2", 7, ("a", "b", "c"))
    assert len(op.ranks) == 2 and op.ranks[0] < 4
    with pytest.raises(ValueError):
        schedule.bind(op, np.arange(300), "f", 300)  # not the default's


# -- the reference ------------------------------------------------------------------


def recount(language, pairs, key):
    """The answer to `key` from every column's language and the (user,
    column) stars as they stand, counted afresh."""
    if key[0] == "R":
        return sum(1 for u, _ in pairs if u == key[1])
    if key[0] == "I":
        users = [r for f, r in key[1:] if f == "stargazer"]
        (lang,) = [r for f, r in key[1:] if f == "language"]
        return sum(1 for u, c in pairs if u == users[0]
                   and all((v, c) in pairs for v in users[1:])
                   and language[c] == lang)
    _, ranked, src_frame, src, n = key
    if src is None and ranked == "language":
        counts = dict(enumerate(np.bincount(language).tolist()))
    elif src is None:
        counts = collections.Counter(u for u, _ in pairs)
    elif ranked == "language":
        counts = collections.Counter(int(language[c]) for u, c in pairs
                                     if u == src)
    else:
        counts = collections.Counter(u for u, c in pairs
                                     if language[c] == src)
    return reference.rank_top(counts, n)


@pytest.mark.parametrize("seed", SEEDS)
def test_two_frame_reference_under_stars_is_a_brute_force_recount(seed,
                                                                   later_pr):
    """60 stars applied to the reference's live tables and to a plain set of
    (user, column) pairs: every kind of answer the mix asks, both src
    directions among them, equals a recount; the reference itself still
    answers as generated."""
    from pbench.kinds import startrace_fixture as kind

    cell, plan = star_cell(seed, max_ops=480)
    config = cell["config"]
    frames = kind.frames_of(config)
    n_columns = config["slices"] << 20
    language = np.concatenate([
        kind.language_of(seed, s, frames["language"])
        for s in range(config["slices"])])
    stars = kind.stars_of(seed, n_columns, frames["stargazer"])
    assert all(1 <= len(c) <= 50 for c in stars.values())
    assert len(stars) == 2048 and len(language) == n_columns
    cands = kind.star_candidates(seed, n_columns, frames["stargazer"], 300)
    ref = names.reference(config).assemble(config, language, stars,
                                           {"stargazer": cands})
    pairs = {(u, int(c)) for u, cols in stars.items() for c in cols}
    live, rng = ref.live(), np.random.default_rng(seed + 1)
    plan.assign_columns(ref.candidates(), ref.can_write)
    keys = list(dict.fromkeys(plan.op_at("window", i).key
                              for i in range(480)))
    assert {k[0] for k in keys} == {"T", "I", "R"}
    # Stars for the users that the Counts name (on a hot repository an
    # intersection moves), for the last user of a ranking by language (its
    # count moves), and for any user.
    named = [u for k in keys if k[0] == "I" for f, u in k[1:]
             if f == "stargazer"]
    before = [live.answer(k) for k in keys]
    last = {k[3]: a[-1][0] for k, a in zip(keys, before)
            if k[:3] == ("T", "stargazer", "language")}
    written = 0
    for i, c in enumerate(ref.candidates()["stargazer"]):
        u = named[i % len(named)] if i % 2 else last.get(
            ref.language_of[c], int(rng.integers(2048)))
        if not ref.can_write(u, c, "stargazer"):
            continue
        assert (u, c) not in pairs
        live.set_bit(u, c, "stargazer")
        pairs.add((u, c))
        written += 1
        if written == 60:
            break
    assert written == 60
    after = [live.answer(k) for k in keys]
    assert after == [recount(language, pairs, k) for k in keys]
    assert after != before
    moved = {k[:3] if k[0] == "T" else k[0]
             for k, a, b in zip(keys, before, after) if a != b}
    assert {("T", "language", "stargazer"), ("T", "stargazer", "language"),
            "R"} <= moved
    assert [ref.answer(k) for k in keys] == before
    assert not ref.can_write(0, 0, "language")
    # The judge owes an acknowledged star to the next TopN and Count, and is
    # handed the frame it was written to.
    u, c = next((u, c) for c in ref.candidates()["stargazer"][::-1]
                for u in (5,) if ref.can_write(u, c, "stargazer"))
    key = ("T", "language", "stargazer", u, 12)
    base = dict(ref.answer(key))
    lang = ref.language_of[c]
    owed = reference.rank_top({**base, lang: base.get(lang, 0) + 1}, 12)

    def pairs_of(ranking):
        return [{"id": r, "count": n} for r, n in ranking]
    reads = [(key, 2.0, 3.0, pairs_of(ref.answer(key))),
             (key, 2.0, 3.0, pairs_of(owed)),
             (("R", u), 2.0, 3.0, len(stars[u]) + 1)]
    assert [v is None for v in ref.judge(
        reads, [(u, c, 0.0, 1.0, "stargazer")])] == [False, True, True]
    with pytest.raises(ValueError):
        ref.judge(reads, [(u, c, 0.0, 1.0, "language")])


# -- a whole run, with the timed path sound and broken ---------------------------------


@pytest.mark.parametrize("mode,want", [("sound", True),
                                       ("stale_writes", False),
                                       ("alter_answer", False)])
def test_two_frame_control_comes_out_as_it_should(mode, want, later_pr):
    """The fixture's reference in the program's place: the stand-in server
    reads each request's frames and serves from the reference's own tables.
    Sound it is `correct`; a star acknowledged and never applied, or one read
    in seven altered, is not."""
    out = harness.run_cell(STAR_CELL, 3_100_000_003, 1.5, False,
                           require_chip=False, control=mode)
    cmp_ = out["compared"]
    assert out["correct"] is want, cmp_
    assert list(cmp_) == ["wrong_answers", "unanswered", "not_judged",
                          "answers_compared"]
    assert cmp_["unanswered"]["value"] == cmp_["not_judged"]["value"] == 0
    assert cmp_["answers_compared"]["value"] > 50
    assert (cmp_["wrong_answers"]["value"] > 0) is not want
    assert (out["failed"] > 0) is not want
    run = json.loads(open(os.path.join(harness.OUT_DIR, "runs.jsonl"))
                     .readlines()[-1])
    assert run["window"]["by_kind"]["topn"] > 0


@pytest.mark.parametrize("control,want,number", [
    (None, True, None), ("lost_wal", False, "lost_writes")])
def test_two_frame_program_on_the_cpu_sound_and_broken(control, want, number,
                                                       later_pr):
    """The real server on the CPU backend over both frames at 4 slices: both
    directions of the cross-frame src form and the three-leaf Count from the
    device path while four clients star repositories, every answer judged,
    every acknowledged star looked for in `stargazer`'s fragment files after
    the SIGKILL; and with the no-fsync WAL path keeping its records in
    memory, which loses every one of them."""
    out = harness.run_cell(STAR_CELL, 2_700_000_001, 2.0, False,
                           require_chip=False, control=control,
                           server_env=dict(PROGRAM_ENV, **ONE_DEVICE))
    cmp_ = out["compared"]
    assert out["device"]["platform"] == "cpu"
    assert out["correct"] is want, cmp_
    for name in ("wrong_answers", "unanswered", "not_judged"):
        assert cmp_[name]["value"] == 0
    assert cmp_["lost_writes"]["of"] >= 4
    assert (cmp_["lost_writes"]["value"] == cmp_["lost_writes"]["of"]) \
        is (number == "lost_writes")
    assert (cmp_["lost_writes"]["value"] == 0) is want
    run = json.loads(open(os.path.join(harness.OUT_DIR, "runs.jsonl"))
                     .readlines()[-1])
    assert run["window"]["by_kind"]["topn"] > 0
    lost = [e for e in run["examples"] if "not on disk" in e]
    assert all("frame stargazer" in e for e in lost) and bool(lost) is not want
