"""Configuration `topn-ingest-1b` and its cell `topn-ingest-1b.lone1` (PR 34):
found by name with no file of the harness edited; `topn-1b` but for the keys
that make it the insert deployment; a reference that grants only writes that
create a container, each once; its ranking under inserts against a recount;
its three per-layer metrics (data files over the generic readers) on what the
program's /debug/vars say, and left out, never 0, where the program records
nothing (the parent commit); and a rehearsal of the whole cell on the CPU.
"""

import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmarks")
for p in (REPO, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

from pbench import datagen, harness, layers, names, reference  # noqa: E402
from pbench.kinds import mixed_ingest  # noqa: E402
from pbench.refs.topn_ingest import TopNIngestReference  # noqa: E402

BENCHMARK = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
CELL = "topn-ingest-1b.lone1"
NEW = ("refresh.patched_share", "refresh.free_slots_min",
       "ingest_reads_roofline")
# What makes it the insert deployment; everything else is topn-1b's.
DIFFER = {"name", "what", "source", "source_quoted", "server_toml",
          "correctness", "reduced", "assumed", "not_in_this_cell", "frame",
          "requires"}
SEEDS = (3, 2_900_000_001, 4_000_000_007)


def config(name):
    return json.load(open(os.path.join(BENCH, "configs", name + ".json")))


def test_cell_is_found_by_name_and_lists_no_read_p95():
    got = harness.load_cell(CELL)
    assert got["cell"]["chips"] == 1
    assert got["config"]["name"] == "topn-ingest-1b"
    assert {m["name"] for m in got["end_to_end"]} == \
        {"ops_per_s", "read_p50_ms", "write_visible_ms", "setup_s"}
    p95 = next(m for m in BENCHMARK["end_to_end"]
               if m["name"] == "read_p95_ms")
    assert p95["workloads"] == ["seg-1b.lone1", "topn-1b.lone1"]
    listed = {m["name"] for m in got["per_layer"]}
    assert set(NEW) | {"tail.read_p95_ms", "refresh.walk_ms",
                       "refresh.restages_in_window"} <= listed
    assert "topn_reads_roofline" not in listed  # it moves read_p95_ms
    for name in NEW:
        entry = next(m for m in BENCHMARK["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL]
    toml = os.path.join(got["config_dir"], got["config"]["server_toml"])
    assert harness._pinned_backend(toml) == "xla"
    body = [ln for ln in open(toml) if not ln.startswith("#")]
    assert body == [ln for ln in open(os.path.join(
        BENCH, "configs", "topn-1b.toml")) if not ln.startswith("#")]
    assert len(BENCHMARK["configs"]) == 4 and len(BENCHMARK["workloads"]) == 5


def test_configuration_is_topn_1b_but_for_the_insert_keys():
    cfg, base = config("topn-ingest-1b"), config("topn-1b")
    assert set(cfg) == set(base) | {"requires"}
    assert {k for k in cfg if cfg[k] != base.get(k)} == DIFFER
    assert cfg["guarantees"] == base["guarantees"]      # word for word
    assert {k: v for k, v in cfg["frame"].items() if k != "kind"} == \
        {k: v for k, v in base["frame"].items() if k != "kind"}
    assert cfg["staged_bytes"] == 960 * 240 * 8192 == 1_887_436_800
    assert cfg["correctness"]["controls"] == base["correctness"]["controls"]
    assert cfg["correctness"]["reference"] == "topn_ingest"
    assert set(cfg["reduced"]) == {"rows"}
    entry = next(c for c in BENCHMARK["configs"]
                 if c["name"] == "topn-ingest-1b")
    assert entry["source"] == cfg["source"] and len(cfg["source"]) <= 200
    assert "workloadd" in cfg["source"] and "configs[2]" in cfg["source"]
    baseline = json.load(open(os.path.join(REPO, "BASELINE.json")))
    quoted = cfg["source_quoted"]
    assert quoted["BASELINE.json configs[2]"] == baseline["configs"][2]
    d = quoted["YCSB core workload D (workloads/workloadd)"]
    assert "readproportion=0.95" in d and "insertproportion=0.05" in d \
        and "requestdistribution=latest" in d
    assert names.reference(cfg).assemble.__self__ is TopNIngestReference
    assert names.kind(cfg) is mixed_ingest


def test_traffic_shares_are_workload_ds():
    t = harness.load_cell(CELL)["traffic"]
    base = json.load(open(os.path.join(BENCH, "traffic",
                                       "ycsb-b-topn-lone1.json")))
    per = {o["kind"]: o["per_block"] for o in t["ops"]}
    assert per == {"update": 12, "topn": 228} and t["block"]["size"] == 240
    assert per["update"] / 240 == 0.05
    assert t["clients"] == 1 and t["think_ms"] == 0 and t["loop"] == "closed"
    assert "rate" not in t and t["max_ops"] == 48000
    assert t["template_seed"] != base["template_seed"]
    assert (t["warmup"], t["profile_one_in"], t["zipf_theta"]) == \
        (base["warmup"], base["profile_one_in"], 0.99)
    assert t["ops"][1] == {"kind": "topn", "src": False, "n": 100,
                           "per_block": 228}


# -- the kind's data and candidates ------------------------------------------------


def test_data_are_topn_1bs_to_the_byte(tmp_path):
    """The same generator on the same seed writes the same fragment files."""
    frame = config("topn-ingest-1b")["frame"]
    assert mixed_ingest._KIND.slice_words is datagen._mixed_slice
    for kind, frame_ in (("a", frame), ("b", config("topn-1b")["frame"])):
        os.makedirs(tmp_path / kind / "i" / "ranked" / "standard"
                    / "fragments")
        datagen._mixed_slice(7, 2, str(tmp_path / kind), "i", frame_)
    a, b = (open(datagen.frag_path(str(tmp_path / k), "i", "ranked", 2),
                 "rb").read() for k in "ab")
    assert a == b and len(a) > 100_000


def test_candidates_are_the_first_block_then_the_others():
    got = mixed_ingest.ingest_candidates(5, 64 << 20, 500)
    assert len(got) == 1000 and len(set(got.tolist())) == 1000
    assert (got[:500] == datagen.block0_candidates(5, 64 << 20, 500)).all()
    assert ((got[:500] & 0xFFFFF) < 65536).all()
    assert ((got[500:] & 0xFFFFF) >= 65536).all()
    assert set((got >> 20).tolist()) == set(range(64))
    assert (got == mixed_ingest.ingest_candidates(5, 64 << 20, 500)).all()


# -- the reference ------------------------------------------------------------------


def in_memory(seed, slices=8, n_candidates=300):
    """`topn-ingest-1b`'s frame over a few slices as words, and its reference,
    made without a disk or a worker."""
    frame = config("topn-ingest-1b")["frame"]
    cands = mixed_ingest.ingest_candidates(seed, slices << 20, n_candidates)
    words, parts = {}, []
    for s in range(slices):
        rows, conts = datagen.mixed_containers(seed, s, frame)
        w = np.stack([reference.container_words(v, b) for v, b in conts])
        words[s] = ([int(r) for r in rows], w)
        local = [int(c) & 0xFFFFF for c in cands if int(c) >> 20 == s]
        parts.append((s, TopNIngestReference.slice_part(frame, rows, w,
                                                        local, ())))
    return frame, words, TopNIngestReference.assemble(frame, parts, cands)


@pytest.mark.parametrize("seed", SEEDS)
def test_can_write_grants_only_pairs_without_a_container_and_each_once(seed):
    frame, words, ref = in_memory(seed)
    assert isinstance(ref, TopNIngestReference)
    rng = np.random.default_rng(seed)
    granted = set()
    for c in ref.candidates():
        s, block = c >> 20, (c >> 16) & 15
        for row in rng.choice(int(frame["rows"]), size=6, replace=False):
            row = int(row)
            holds = block == 0 and row in words[s][0]
            want = not holds and (row, s, block) not in granted
            assert ref.can_write(row, c) is want
            if want:
                granted.add((row, s, block))
                assert ref.can_write(row, c) is False   # once
                assert row not in ref.kept[c]           # the bit is clear
    first = [g for g in granted if g[2] == 0]
    assert len(first) > 50 and len(granted) - len(first) > 50


@pytest.mark.parametrize("seed", SEEDS)
def test_every_bound_write_of_a_small_plan_creates_a_container(seed):
    """The harness's own binding over the cell's mix, cut to two blocks: each
    update's (row, column) names a container that no row of the data and no
    earlier update holds, in the slice's first block while the row is absent
    from a slice."""
    cell = harness.load_cell(CELL)
    frame, words, ref = in_memory(seed, slices=8, n_candidates=400)
    plan = harness.Plan(dict(cell["config"], slices=8, columns=8 << 20),
                        dict(cell["traffic"], max_ops=480), seed)
    plan.assign_columns(ref.candidates(), ref.can_write)
    ups = plan.updates()
    assert len(ups) == 24 + 36     # two blocks, and the warm-up's rounds
    held = {(r, s, 0) for s, (rows, _) in words.items() for r in rows}
    absent_first = 0
    for stream, i, row in ups:
        op = plan.op_at(stream, i)
        assert op.write[0] == row and op.key == ("R", row)
        c = op.write[1]
        made = (row, c >> 20, (c >> 16) & 15)
        assert made not in held
        held.add(made)
        absent_first += made[2] == 0
    assert absent_first >= 10
    assert ref.bytes_needed(("T", None, 100)) == \
        sum(ref.row_bytes.values()) + 2 * len(ups)
    hot = ups[0][2]
    assert ref.bytes_needed(("R", hot)) == ref.row_bytes.get(hot, 0) \
        + 2 * sum(1 for _, _, r in ups if r == hot)
    assert ref.memo_account(("T", None, 100)) == ("mesh.memo_store", "misses")


@pytest.mark.parametrize("seed", SEEDS)
def test_ranking_under_inserts_is_a_brute_force_recount(seed):
    """8 slices, 40 inserts (into slices a row is absent from and into other
    blocks): the reference's ranking and every |row| equal a numpy count
    over the words with the created containers beside them."""
    frame, words, ref = in_memory(seed)
    n_rows = int(frame["rows"])
    live, rng = ref.live(), np.random.default_rng(seed + 1)
    extra = np.zeros(n_rows, dtype=np.int64)     # bits in created containers
    done = 0
    for c in ref.candidates()[::3]:
        row = int(rng.integers(n_rows))
        if not ref.can_write(row, c):
            continue
        live.set_bit(row, c)
        extra[row] += 1
        done += 1
        if done == 40:
            break
    assert done == 40
    totals = extra.copy()
    for rows, w in words.values():
        totals[rows] += np.bitwise_count(w).sum(axis=1).astype(np.int64)
    brute = sorted(((r, int(n)) for r, n in enumerate(totals) if n),
                   key=lambda rc: (-rc[1], rc[0]))
    assert live.answer(("T", None, 100)) == brute[:100]
    assert live.answer(("T", None, 5)) == brute[:5]
    assert [live.answer(("R", r)) for r in range(n_rows)] == totals.tolist()
    # The judge owes an acknowledged insert to the next TopN and Count.
    row, col = next((r, c) for c in ref.candidates()[1::3]
                    for r in range(n_rows) if ref.can_write(r, c))
    base = ref.answer(("R", row))
    reads = [(("R", row), 2.0, 3.0, base), (("R", row), 2.0, 3.0, base + 1)]
    assert [v is None for v in ref.judge(reads, [(row, col, 0.0, 1.0)])] == \
        [False, True]


# -- the per-layer metrics ------------------------------------------------------------

# /debug/vars as a window began and ended: 450 inserts patched in, none
# restaged for; then one of them restaged for; then the parent's, which has
# neither counter.
VARS = ({"mesh": {"stage": 1, "container_patches": 36, "free_slots_min": 9}},
        {"mesh": {"stage": 1, "container_patches": 486, "free_slots_min": 7}})
ONE_RESTAGE = (VARS[0], {"mesh": {"stage": 2, "container_patches": 485,
                                  "free_slots_min": 10}})
PARENT = ({"mesh": {"stage": 1}}, {"mesh": {"stage": 8}})


def ctx(vars_pair, **kw):
    return layers.Context(vars_before=vars_pair[0], vars_after=vars_pair[1],
                          prom_before={}, prom_after={}, log=[], trace=None,
                          device_kind="TPU v5 lite", config={}, **kw)


@pytest.mark.parametrize("name,pair,want", [
    ("refresh.patched_share", VARS, 100.0),
    ("refresh.patched_share", ONE_RESTAGE, 100.0 * 449 / 450),
    ("refresh.patched_share", PARENT, None),
    ("refresh.free_slots_min", VARS, 7.0),
    ("refresh.free_slots_min", ONE_RESTAGE, 10.0),
    ("refresh.free_slots_min", PARENT, None),
    ("refresh.restages_in_window", PARENT, 7.0),
    ("ingest_reads_roofline", VARS, None),      # no trace: nothing, not 0
])
def test_metric_files_read_the_programs_counters(name, pair, want):
    spec = layers.load_metric(name)
    entry = next(m for m in BENCHMARK["per_layer"] if m["name"] == name)
    assert (spec["unit"], spec["layer"], spec["moves"], spec["source"],
            spec["better"]) == (entry["unit"], entry["layer"], entry["moves"],
                                entry["source"], entry["better"])
    got = layers.evaluate(spec["value"], ctx(pair))
    assert got is None if want is None else got == pytest.approx(want)
    assert layers.read_all([name], ctx(pair)).get(name, {}).get("value") == got


def test_ingest_roofline_is_topn_reads_rooflines_reader_over_this_cells_reads():
    """One device-answered TopN and one read-back Count inside a traced
    second, the memo's hit left out: their bytes over the busy time over the
    chip's peak."""
    from pbench import window

    spec = layers.load_metric("ingest_reads_roofline")
    assert spec["value"] == layers.load_metric("topn_reads_roofline")["value"]
    assert spec["moves"] == "ops_per_s" and spec["unit"] == "%"
    ref = TopNIngestReference({1: 10, 2: 5}, {}, row_bytes={1: 4000, 2: 8192})
    assert ref.can_write(2, 5 << 20)
    log = [window.Done(0, 0, "update", 10.1, 10.2, True, (
               ("SetBit(rowID=2, ...)", 10.1, 10.15, 200, True),
               ("Count(Bitmap(rowID=2))", 10.15, 10.2, 200, 6))),
           window.Done(0, 1, "topn", 10.2, 10.3, True, (
               ("TopN(frame=ranked, n=100)", 10.2, 10.3, 200, []),)),
           window.Done(0, 2, "topn", 10.3, 10.4, True, (
               ("TopN(frame=ranked, n=100)", 10.3, 10.4, 200, []),))]
    keys = {(0, 0): ("R", 2), (0, 1): ("R", 2), (1, 0): ("T", None, 100),
            (2, 0): ("T", None, 100)}
    c = ctx(VARS, keys=keys, lone_hits={(2, 0)}, bytes_of=ref.bytes_needed)
    c.log = log
    c.trace = {"t0": 10.0, "t1": 11.0, "busy_s": 0.001, "idle_share": 99.9}
    need = (8192 + 2) + (4000 + 8192 + 2)
    assert layers.evaluate(spec["value"], c) == pytest.approx(
        100.0 * need / 0.001 / 819e9)


# -- what the configuration requires of the program --------------------------------------


@pytest.mark.parametrize("serve_py,runs", [
    (None, False),                                   # no program at all
    ('stats = {"stage": 0, "incremental": 0}\n', False),   # it restages
    ('stats = {"container_patch_refused_no_slot": 0}\n', False),
    ('stats = {"container_patches": 0}\n', True),
])
def test_a_program_that_does_not_patch_is_refused_before_any_data(
        tmp_path, monkeypatch, serve_py, runs):
    """`generate` ends the run with exit code 1, and writes nothing, where
    `serve.py` names no counter `container_patches`; this tree's names it."""
    assert mixed_ingest.program_patches(REPO)
    assert config("topn-ingest-1b")["requires"]["held_by"].startswith(
        "pbench/kinds/mixed_ingest.py::generate")
    if serve_py is not None:
        d = tmp_path / "repo" / "pilosa_tpu" / "parallel"
        d.mkdir(parents=True)
        (d / "serve.py").write_text(serve_py)
    assert mixed_ingest.program_patches(str(tmp_path / "repo")) is runs
    if runs:
        return
    monkeypatch.setattr(mixed_ingest, "REPO", str(tmp_path / "repo"))
    monkeypatch.setattr(harness, "OUT_DIR", str(tmp_path / "out"))
    with pytest.raises(SystemExit) as e:
        harness.run_cell(CELL, 7, 1.0, False, require_chip=False, slices=64)
    assert e.value.code not in (0, None) and "container_patches" in str(
        e.value.code)
    assert os.listdir(str(tmp_path / "out" / CELL / "data")) == []


# -- the whole cell on the CPU ---------------------------------------------------------


def test_the_cell_runs_on_the_cpu_and_patches_every_insert(tmp_path,
                                                           monkeypatch):
    """64 slices, a 4-s traced window, the real server on the CPU backend:
    `correct`, every insert patched into the staged pool, no view restaged,
    free slots left; a CPU run gives no device metric."""
    monkeypatch.setattr(harness, "OUT_DIR", str(tmp_path))
    env = {"JAX_PLATFORMS": "cpu", "PILOSA_TPU_DEVICE_MIN_WORK": "0",
           "PILOSA_TPU_CPU_ROUTE_NATIVE": "off"}
    out = harness.run_cell(CELL, 3_400_000_019, 4.0, True, require_chip=False,
                           slices=64, server_env=env)
    assert out["device"]["platform"] == "cpu"
    assert out["correct"] is True and out["failed"] == 0
    cmp_ = out["compared"]
    for limit in ("wrong_answers", "unanswered", "not_judged", "lost_writes"):
        assert cmp_[limit]["value"] == 0
    assert cmp_["lost_writes"]["of"] >= 3      # the warm-up's and the window's
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert m["refresh.restages_in_window"] == 0
    assert m["refresh.patched_share"] == 100.0
    assert 1 <= m["refresh.free_slots_min"] <= 10
    assert m["route.device_share"] == 100.0 and m["memo.hit_share"] > 80
    assert m["tail.read_p95_ms"] > 0 and m["refresh.walk_ms"] > 0
    assert "ingest_reads_roofline" not in m and "device.idle_share" not in m
    run = json.loads(open(os.path.join(str(tmp_path), "runs.jsonl"))
                     .readlines()[-1])
    assert run["mesh"]["stage"] == 0 and run["mesh"]["incremental"] >= 1
