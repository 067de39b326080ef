"""`memo.token_pairs_per_put` (a data file under layer_metrics/): read
through the generic `vars` and `ratio` readers from what the program's own
/debug/vars says before and after a few computed Counts, and left out, never
0, where the program has no such counters (the parent commit).
"""

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmarks")
for p in (REPO, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

from pbench import layers  # noqa: E402

NAME = "memo.token_pairs_per_put"
SLICES = 64
CELLS = ["seg-1b.lone1", "seg-1b.herd64", "topn-1b.lone1",
         "seg-2b-x4.herd64"]


def context(before: dict, after: dict) -> layers.Context:
    return layers.Context(vars_before=before, vars_after=after,
                          prom_before={}, prom_after={}, log=[], trace=None,
                          device_kind="TPU v5 lite", config={})


def test_reads_token_pairs_over_tokens_stored_in_the_window(tmp_path):
    from pilosa_tpu.api import Handler
    from pilosa_tpu.core import Holder
    from pilosa_tpu.executor import Executor

    holder = Holder(str(tmp_path / "data"))
    holder.open()
    try:
        h = Handler(holder, Executor(holder, use_device=False))

        def post(path, body=b""):
            resp = h.handle("POST", path, body=body)
            assert resp.status == 200
            return resp

        def scrape():
            return json.loads(h.handle("GET", "/debug/vars").body)

        post("/index/i")
        post("/index/i/frame/f")
        post("/index/i/frame/g")
        for frame in ("f", "g"):
            for s in range(SLICES):
                post("/index/i/query",
                     f"SetBit(rowID=1, frame={frame}, "
                     f"columnID={s * (1 << 20)})".encode())
        post("/index/i/query", b"Count(Bitmap(rowID=1, frame=f))")  # set-up
        before = scrape()
        # Two computed Counts over one view each, one over two views, and
        # a repeat the memo answers (which stores nothing).
        for pql in (b"Count(Bitmap(rowID=2, frame=f))",
                    b"Count(Bitmap(rowID=3, frame=f))",
                    b"Count(Union(Bitmap(rowID=1, frame=f), "
                    b"Bitmap(rowID=1, frame=g)))",
                    b"Count(Bitmap(rowID=3, frame=f))"):
            post("/index/i/query", pql)
        after = scrape()
    finally:
        holder.close()
    delta = {k: after["host_cache"][k] - before["host_cache"][k]
             for k in ("query_put", "query_token_pairs", "query_hit")}
    assert delta == {"query_put": 3, "query_token_pairs": 4, "query_hit": 1}
    spec = layers.load_metric(NAME)
    got = layers.evaluate(spec["value"], context(before, after))
    # One entry a view, whatever the number of slices.
    assert got == 4 / 3
    assert layers.read_all([NAME], context(before, after)) == \
        {NAME: {"value": got, "unit": "pairs"}}

    # A program without the counters: nothing to read, the line leaves it out.
    def parent(vars_):
        hc = {k: v for k, v in vars_["host_cache"].items()
              if k not in ("query_put", "query_token_pairs")}
        return dict(vars_, host_cache=hc)
    assert parent(after) != after
    assert layers.read_all([NAME], context(parent(before),
                                           parent(after))) == {}
    # A window that stored no token: no ratio, not 0.
    assert layers.read_all([NAME], context(after, after)) == {}


def test_entry_matches_the_file():
    spec = layers.load_metric(NAME)
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    entry = next(m for m in bench["per_layer"] if m["name"] == NAME)
    assert entry == {"name": NAME, "unit": spec["unit"],
                     "better": spec["better"], "source": spec["source"],
                     "layer": spec["layer"], "moves": spec["moves"],
                     "workloads": CELLS}
    assert spec["value"] == {"ratio": [
        {"vars": "host_cache.query_token_pairs", "at": "window"},
        {"vars": "host_cache.query_put", "at": "window"}]}
