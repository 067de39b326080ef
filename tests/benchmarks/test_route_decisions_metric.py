"""`route.decisions_per_slice` (a data file under layer_metrics/): read
through the generic `prom` and `ratio` readers from what the program's
own /metrics says before and after a small strict read, and left out,
never 0, where the program has no such counter (the parent commit).
"""

import json
import os
import sys
import types

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmarks")
for p in (REPO, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

from pbench import layers, server  # noqa: E402

NAME = "route.decisions_per_slice"
SLICES = 64


def scrape(handler) -> dict:
    """The harness's own /metrics parser over an in-process handler."""
    text = handler.handle("GET", "/metrics").body
    return server.Server.metrics(types.SimpleNamespace(http=lambda _p: text))


def context(before: dict, after: dict) -> layers.Context:
    return layers.Context(vars_before={}, vars_after={}, prom_before=before,
                          prom_after=after, log=[], trace=None,
                          device_kind="TPU v5 lite", config={})


def test_reads_decisions_over_slices_placed_in_the_window(tmp_path):
    from pilosa_tpu.api import Handler
    from pilosa_tpu.core import Holder
    from pilosa_tpu.executor import Executor
    from pilosa_tpu.parallel import new_test_cluster

    holder = Holder(str(tmp_path / "data"))
    holder.open()
    try:
        cluster = new_test_cluster(1)
        cluster.partition_n = 16
        host = cluster.nodes[0].host
        h = Handler(holder, Executor(holder, host=host, cluster=cluster,
                                     use_device=False),
                    cluster=cluster, host=host)

        def post(path, body=b""):
            resp = h.handle("POST", path, body=body)
            assert resp.status == 200
            return resp

        post("/index/i")
        post("/index/i/frame/f")
        col = (SLICES - 1) * (1 << 20)
        post("/index/i/query",
             f"SetBit(rowID=1, frame=f, columnID={col})".encode())
        post("/index/i/query", b"Count(Bitmap(rowID=1, frame=f))")  # set-up
        before = scrape(h)
        for row in (2, 3, 4):
            post("/index/i/query",
                 f"Count(Bitmap(rowID={row}, frame=f))".encode())
        after = scrape(h)
    finally:
        holder.close()
    spec = layers.load_metric(NAME)
    got = layers.evaluate(spec["value"], context(before, after))
    partitions = len({cluster.partition("i", s) for s in range(SLICES)})
    assert got == partitions / SLICES and got <= 16 / SLICES
    assert layers.read_all([NAME], context(before, after)) == \
        {NAME: {"value": got, "unit": "ratio"}}

    # A program without the counter: nothing to read, the line leaves it out.
    def parent(series):
        return {k: v for k, v in series.items()
                if not k.startswith("pilosa_route_owner_decisions_total")}
    assert parent(after) != after
    assert layers.read_all([NAME], context(parent(before),
                                           parent(after))) == {}


def test_entry_matches_the_file():
    spec = layers.load_metric(NAME)
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    entry = next(m for m in bench["per_layer"] if m["name"] == NAME)
    assert entry == {"name": NAME, "unit": spec["unit"],
                     "better": spec["better"], "source": spec["source"],
                     "layer": spec["layer"], "moves": spec["moves"],
                     "workloads": ["seg-1b.lone1", "seg-1b.herd64",
                                   "topn-1b.lone1"]}
    assert spec["value"] == {"ratio": [
        {"prom": "pilosa_route_owner_decisions_total", "at": "window"},
        {"prom": "pilosa_read_replica_total", "at": "window"}]}
