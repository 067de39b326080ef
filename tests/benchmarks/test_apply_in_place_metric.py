"""`apply.in_place_share` (a data file under layer_metrics/, PR 35): read
through the generic `vars`, `sum`, `ratio` and `times` readers from what the
program's own /debug/vars says before and after a few SetBits, one of them
beside a pinned reader, and left out, never 0, where the program has no
such counters (the parent commit) or the window scattered nothing.
"""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmarks")
for p in (REPO, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

from pbench import layers  # noqa: E402

NAME = "apply.in_place_share"
CELLS = ["seg-1b.lone1", "seg-1b.herd64", "topn-1b.lone1",
         "seg-2b-x4.herd64", "topn-ingest-1b.lone1"]


def context(before: dict, after: dict) -> layers.Context:
    return layers.Context(vars_before=before, vars_after=after,
                          prom_before={}, prom_after={}, log=[], trace=None,
                          device_kind="TPU v5 lite", config={})


def mesh(in_place, copied, **more):
    return {"mesh": dict(apply_in_place=in_place, apply_copied=copied,
                         **more)}


@pytest.mark.parametrize("before,after,want", [
    (mesh(36, 0), mesh(486, 0), 100.0),          # one client: every refresh
    (mesh(3, 40), mesh(4, 123), 100.0 / 84),     # a herd: readers pinned
    (mesh(0, 5), mesh(0, 60), 0.0),
    (mesh(7, 2), mesh(7, 2), None),              # nothing scattered: no share
    ({"mesh": {"incremental": 3}}, {"mesh": {"incremental": 9}}, None),
], ids=["lone", "herd", "all_copied", "no_writes", "parent"])
def test_share_of_the_windows_scatters_that_ran_in_place(before, after, want):
    got = layers.evaluate(layers.load_metric(NAME)["value"],
                          context(before, after))
    assert got is None if want is None else got == pytest.approx(want)
    assert layers.read_all([NAME], context(before, after)) == (
        {} if want is None else {NAME: {"value": got, "unit": "%"}})


def test_reads_the_programs_own_counters(tmp_path):
    """Three SetBits through the handler, each read back; a reader holds a
    pin across the second: /debug/vars moves by (2, 1), /metrics agrees."""
    from pilosa_tpu.api import Handler
    from pilosa_tpu.core import Holder
    from pilosa_tpu.executor import Executor

    holder = Holder(str(tmp_path / "data"))
    holder.open()
    try:
        ex = Executor(holder, use_device=True, device_min_work=0)
        h = Handler(holder, ex)

        def post(path, body=b""):
            resp = h.handle("POST", path, body=body)
            assert resp.status == 200, resp.body
            return resp.json()

        def scrape():
            return json.loads(h.handle("GET", "/debug/vars").body)

        post("/index/i")
        post("/index/i/frame/f")
        for col in range(4):
            post("/index/i/query",
                 f"SetBit(rowID=1, frame=f, columnID={col})".encode())
        count = b"Count(Bitmap(rowID=1, frame=f))"
        assert post("/index/i/query", count)["results"] == [4]   # staged
        mgr = ex.mesh_manager()
        mgr.deterministic_gate = True  # a measured gate may restage at will
        before = scrape()
        pins: list = []
        for n, col in enumerate((10, 11, 12), start=5):
            if col == 11:
                assert mgr._row_counts_args("i", "f", "standard", [0], 1,
                                            pins=pins) is not None
            post("/index/i/query",
                 f"SetBit(rowID=1, frame=f, columnID={col})".encode())
            assert post("/index/i/query", count)["results"] == [n]
            mgr._release_pins(pins)
        after = scrape()
        text = h.handle("GET", "/metrics").body.decode()
    finally:
        holder.close()
    delta = {k: after["mesh"][k] - before["mesh"][k]
             for k in ("apply_in_place", "apply_copied", "stage")}
    assert delta == {"apply_in_place": 2, "apply_copied": 1, "stage": 0}
    assert layers.read_all([NAME], context(before, after)) == \
        {NAME: {"value": pytest.approx(100.0 * 2 / 3), "unit": "%"}}
    for mode, n in (("in_place", after["mesh"]["apply_in_place"]),
                    ("copied", after["mesh"]["apply_copied"])):
        assert f'pilosa_apply_writes_total{{mode="{mode}"}} {n}' in text


def test_entry_matches_the_file():
    spec = layers.load_metric(NAME)
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    assert next(m for m in bench["per_layer"] if m["name"] == NAME) == {
        "name": NAME, "unit": spec["unit"], "better": spec["better"],
        "source": spec["source"], "layer": spec["layer"],
        "moves": spec["moves"], "workloads": CELLS}
    assert (spec["layer"], spec["moves"], spec["source"]) == \
        ("mesh serving", "write_visible_ms", "program_counter")
    patched = layers.load_metric("refresh.patched_share")["value"]
    assert json.dumps(spec["value"]) == json.dumps(patched).replace(
        "mesh.container_patches", "mesh.apply_in_place").replace(
        "mesh.stage", "mesh.apply_copied")
