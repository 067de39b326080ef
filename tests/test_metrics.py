"""Prometheus /metrics exposition tests: text-format correctness
(HELP/TYPE lines, label escaping, cumulative `le` monotonicity,
`_sum`/`_count` consistency), the ExpvarStats structured bridge and
its /debug/vars flat-key compatibility, concurrent scrape-with-writers
safety, the /metrics endpoint end-to-end, build-info/uptime in both
endpoints, and the ?explain=true query surface (which must plan
without executing).
"""

import json
import re
import threading

import pytest

from pilosa_tpu.api import Handler
from pilosa_tpu.core import Holder
from pilosa_tpu.executor import Executor
from pilosa_tpu.obs import Histogram, prom
from pilosa_tpu.parallel import new_test_cluster
from pilosa_tpu.utils.stats import ExpvarStats


# One exposition line: name{labels} value — labels optional.
_SAMPLE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^{}]*\})? "
    r"(NaN|[+-]Inf|-?[0-9].*)$")


def parse_exposition(text):
    """(samples, types, helps): every non-comment line must parse as a
    sample; TYPE/HELP lines index by family name."""
    samples, types, helps = [], {}, {}
    assert text.endswith("\n"), "exposition must end with a newline"
    for line in text.splitlines():
        if line.startswith("# TYPE "):
            _, _, name, mtype = line.split(" ", 3)
            types[name] = mtype
        elif line.startswith("# HELP "):
            _, _, name, help_text = line.split(" ", 3)
            helps[name] = help_text
        elif line:
            assert _SAMPLE.match(line), f"unparseable sample: {line!r}"
            name = re.split(r"[{ ]", line, 1)[0]
            rest = line[len(name):]
            labels = {}
            if rest.startswith("{"):
                body, _, rest = rest[1:].partition("}")
                for pair in re.findall(r'(\w+)="((?:[^"\\]|\\.)*)"', body):
                    labels[pair[0]] = (pair[1].replace('\\"', '"')
                                       .replace("\\n", "\n")
                                       .replace("\\\\", "\\"))
            samples.append((name, labels, rest.strip()))
    return samples, types, helps


class TestTextFormat:
    def test_counter_gauge_families(self):
        reg = prom.Registry()
        reg.counter("reqs_total", "Requests.").labels(code="200").inc(3)
        reg.gauge("temp", "Temp.").set(1.5)
        text = reg.render()
        samples, types, helps = parse_exposition(text)
        assert types == {"reqs_total": "counter", "temp": "gauge"}
        assert helps["reqs_total"] == "Requests."
        assert ("reqs_total", {"code": "200"}, "3") in samples
        assert ("temp", {}, "1.5") in samples

    def test_type_line_precedes_samples(self):
        reg = prom.Registry()
        reg.counter("a_total").inc()
        lines = reg.render().splitlines()
        assert lines.index("# TYPE a_total counter") < lines.index(
            "a_total 1")

    def test_label_escaping_round_trips(self):
        fam = prom.MetricFamily("m", "gauge")
        hostile = 'a"b\\c\nd'
        fam.add(1, {"k": hostile})
        samples, _, _ = parse_exposition(prom.render([fam]))
        assert samples == [("m", {"k": hostile}, "1")]

    def test_help_escaping(self):
        fam = prom.MetricFamily("m", "gauge", "line1\nline2 \\ back")
        fam.add(1)
        text = fam.render()
        assert "# HELP m line1\\nline2 \\\\ back" in text

    def test_name_sanitization(self):
        assert prom.sanitize_name("query.Count") == "query_Count"
        assert prom.sanitize_name("9lives") == "_9lives"
        assert prom.sanitize_name("ok_name:x") == "ok_name:x"
        assert prom.sanitize_label("a.b-c") == "a_b_c"

    def test_empty_families_skipped(self):
        text = prom.render([prom.MetricFamily("empty", "gauge"),
                            prom.MetricFamily("full", "gauge").add(1)])
        assert "empty" not in text
        assert "full 1" in text

    def test_value_formatting(self):
        assert prom.format_value(3.0) == "3"
        assert prom.format_value(float("inf")) == "+Inf"
        assert prom.format_value(float("-inf")) == "-Inf"
        assert prom.format_value(float("nan")) == "NaN"
        assert prom.format_value(0.25) == "0.25"


class TestHistogramExposition:
    def _buckets(self, text, name):
        out = []
        for s, labels, v in parse_exposition(text)[0]:
            if s == name + "_bucket":
                out.append((labels["le"], float(v)))
        return out

    def test_cumulative_le_monotonic(self):
        h = Histogram()
        for v in (0, 1, 2, 3, 100, 1000, 1000):
            h.observe(v)
        fam = prom.MetricFamily("lat", "histogram").add_histogram(h)
        text = prom.render([fam])
        buckets = self._buckets(text, "lat")
        assert buckets[-1][0] == "+Inf"
        counts = [c for _, c in buckets]
        assert counts == sorted(counts), "le buckets must be cumulative"
        assert counts[-1] == 7

    def test_le_bounds_are_powers_of_two(self):
        h = Histogram()
        h.observe(5)  # log2 bucket 3: [4, 8)
        text = prom.render(
            [prom.MetricFamily("lat", "histogram").add_histogram(h)])
        buckets = dict(self._buckets(text, "lat"))
        assert buckets["4"] == 0
        assert buckets["8"] == 1
        assert buckets["+Inf"] == 1

    def test_sum_count_consistency(self):
        h = Histogram()
        vals = [1, 7, 300, 42]
        for v in vals:
            h.observe(v)
        samples, types, _ = parse_exposition(prom.render(
            [prom.MetricFamily("lat", "histogram").add_histogram(h)]))
        assert types["lat"] == "histogram"
        by = {(n, tuple(sorted(l.items()))): float(v)
              for n, l, v in samples}
        assert by[("lat_sum", ())] == sum(vals)
        assert by[("lat_count", ())] == len(vals)
        # +Inf bucket == _count, per the spec.
        assert by[("lat_bucket", (("le", "+Inf"),))] == len(vals)

    def test_labeled_histogram_series(self):
        reg = prom.Registry()
        inst = reg.histogram("lat", "Latency.")
        inst.labels(backend="mesh").observe(4)
        inst.labels(backend="host").observe(1000)
        samples, _, _ = parse_exposition(reg.render())
        backends = {l.get("backend") for n, l, _ in samples
                    if n == "lat_count"}
        assert backends == {"mesh", "host"}


class TestExpvarBridge:
    def test_flat_snapshot_keys_unchanged(self):
        # The /debug/vars contract: tags flatten to "t1,t2,name".
        s = ExpvarStats()
        s.count("reqs", 2)
        s.with_tags("index:i", "frame:f").count("reqs", 3)
        s.gauge("depth", 7)
        s.set("build", "abc")
        snap = s.snapshot()
        assert snap["reqs"] == 2
        assert snap["index:i,frame:f,reqs"] == 3
        assert snap["depth"] == 7
        assert snap["build"] == "abc"

    def test_timing_percentile_keys_preserved(self):
        s = ExpvarStats()
        t = s.with_tags("index:i")
        t.timing("query", 100)
        snap = s.snapshot()
        assert snap["index:i,query.us.count"] == 1
        assert snap["index:i,query.us.sum"] == 100

    def test_structured_view(self):
        s = ExpvarStats()
        s.count("reqs")
        s.with_tags("index:i").gauge("depth", 3)
        values, sets, hists, kinds = s.structured()
        assert values[("reqs", ())] == 1
        assert values[("depth", ("index:i",))] == 3
        assert kinds == {"reqs": "counter", "depth": "gauge"}

    def test_bridge_counter_total_suffix_and_labels(self):
        s = ExpvarStats()
        s.with_tags("index:i").count("query.Count", 4)
        s.gauge("open_files", 9)
        text = prom.render(prom.expvar_families(s))
        samples, types, _ = parse_exposition(text)
        assert types["pilosa_query_Count_total"] == "counter"
        assert types["pilosa_open_files"] == "gauge"
        assert ("pilosa_query_Count_total", {"index": "i"}, "4") in samples

    def test_bridge_histograms_expand(self):
        s = ExpvarStats()
        s.timing("query", 100)
        text = prom.render(prom.expvar_families(s))
        assert "pilosa_query_us_bucket" in text
        samples, types, _ = parse_exposition(text)
        assert types["pilosa_query_us"] == "histogram"

    def test_bridge_string_sets_become_info(self):
        s = ExpvarStats()
        s.set("node_state", "UP")
        samples, _, _ = parse_exposition(
            prom.render(prom.expvar_families(s)))
        assert ("pilosa_node_state_info", {"value": "UP"}, "1") in samples


class TestConcurrency:
    def test_scrape_with_writers(self):
        """Writers hammer every store type while scrapes run; each
        scrape must parse cleanly (no torn lines, no exceptions)."""
        s = ExpvarStats()
        reg = prom.Registry()
        reg.register_collector(lambda: prom.expvar_families(s))
        ctr = reg.counter("ops_total")
        stop = threading.Event()
        errors = []

        def writer(i):
            t = s.with_tags(f"worker:{i}")
            n = 0
            while not stop.is_set():
                t.count("w")
                t.timing("lat", n % 1000)
                ctr.labels(worker=str(i)).inc()
                n += 1

        threads = [threading.Thread(target=writer, args=(i,))
                   for i in range(4)]
        for t in threads:
            t.start()
        try:
            for _ in range(20):
                try:
                    parse_exposition(reg.render())
                except Exception as e:  # noqa: BLE001 — collected below
                    errors.append(e)
        finally:
            stop.set()
            for t in threads:
                t.join()
        assert not errors

    def test_failing_collector_skips_not_fails(self):
        reg = prom.Registry()
        reg.register_collector(lambda: (_ for _ in ()).throw(RuntimeError))
        reg.gauge("ok").set(1)
        assert "ok 1" in reg.render()


@pytest.fixture
def env(tmp_path):
    holder = Holder(str(tmp_path / "data"))
    holder.open()
    cluster = new_test_cluster(1)
    ex = Executor(holder, host=cluster.nodes[0].host, cluster=cluster,
                  use_device=False)
    handler = Handler(holder, ex, cluster=cluster,
                      host=cluster.nodes[0].host)
    yield holder, handler
    holder.close()


def _seed(h):
    assert h.handle("POST", "/index/i").status == 200
    assert h.handle("POST", "/index/i/frame/f").status == 200
    assert h.handle(
        "POST", "/index/i/query",
        body=b"SetBit(rowID=1, frame=f, columnID=5)").status == 200


class TestMetricsEndpoint:
    def test_scrape_parses_and_has_core_families(self, env):
        holder, h = env
        _seed(h)
        for _ in range(2):
            assert h.handle(
                "POST", "/index/i/query",
                body=b"Count(Bitmap(rowID=1, frame=f))").status == 200
        resp = h.handle("GET", "/metrics")
        assert resp.status == 200
        assert resp.headers["Content-Type"].startswith("text/plain")
        text = resp.body.decode()
        samples, types, _ = parse_exposition(text)
        names = {n for n, _, _ in samples}
        # Build info + uptime.
        assert ("pilosa_build_info", {"version": h.version}, "1") in samples
        assert any(n == "pilosa_uptime_seconds" for n in names)
        # Backend-labeled query latency histogram + route counters.
        assert types["pilosa_query_route_duration_microseconds"] \
            == "histogram"
        route_backends = {
            l["backend"] for n, l, _ in samples
            if n == "pilosa_query_route_total"}
        assert route_backends  # at least one engine served
        # Plan/host cache counters.
        assert "pilosa_host_cache_query_hit" in names
        # Sampled fragment gauges.
        assert ("pilosa_fragment_cardinality",
                {"index": "i", "frame": "f"}, "1") in samples
        # Existing ExpvarStats call-sites export for free.
        assert "pilosa_query_Count_total" in names

    def test_route_owner_decisions_counter(self, env):
        """A strict read over 960 slices on one node climbs the owner
        ladder once per partition (16), not once per slice, and says so
        beside pilosa_read_replica_total."""
        holder, h = env
        _seed(h)

        def scrape():
            samples, types, _ = parse_exposition(
                h.handle("GET", "/metrics").body.decode())
            assert types["pilosa_route_owner_decisions_total"] == "counter"
            return {name: sum(float(v) for n, _, v in samples if n == name)
                    for name in ("pilosa_route_owner_decisions_total",
                                 "pilosa_read_replica_total")}

        first = scrape()  # on /metrics before any multi-slice read
        col = 959 * (1 << 20) + 3
        assert h.handle(
            "POST", "/index/i/query",
            body=f"SetBit(rowID=1, frame=f, columnID={col})".encode()
        ).status == 200
        seen = [first]
        for row in (1, 2):
            assert h.handle(
                "POST", "/index/i/query",
                body=f"Count(Bitmap(rowID={row}, frame=f))".encode()
            ).json() == {"results": [2 if row == 1 else 0]}
            seen.append(scrape())
        for before, after in zip(seen, seen[1:]):
            grew = {k: after[k] - before[k] for k in after}
            assert grew["pilosa_read_replica_total"] == 960
            assert 1 <= grew["pilosa_route_owner_decisions_total"] <= 16

    def test_fragment_gauges_cached_by_interval(self, env):
        holder, h = env
        _seed(h)
        h.metrics_sample_interval = 3600.0
        t1 = h.handle("GET", "/metrics").body.decode()
        assert ('pilosa_fragment_cardinality{index="i",frame="f"} 1'
                in t1)
        h.handle("POST", "/index/i/query",
                 body=b"SetBit(rowID=1, frame=f, columnID=6)")
        t2 = h.handle("GET", "/metrics").body.decode()
        # Same cached sample until the interval elapses...
        assert ('pilosa_fragment_cardinality{index="i",frame="f"} 1'
                in t2)
        h.metrics_sample_interval = 0.0
        t3 = h.handle("GET", "/metrics").body.decode()
        # ...and a fresh walk once it has.
        assert ('pilosa_fragment_cardinality{index="i",frame="f"} 2'
                in t3)

    def test_expvar_has_uptime_and_version(self, env):
        holder, h = env
        snap = h.handle("GET", "/debug/vars").json()
        assert snap["version"] == h.version
        assert snap["uptime_seconds"] >= 0


class TestExplain:
    def test_explain_plans_without_executing(self, env):
        holder, h = env
        _seed(h)
        frag = holder.fragment("i", "f", "standard", 0)
        gen_before = frag.generation
        resp = h.handle("POST", "/index/i/query", {"explain": "true"},
                        body=b"Count(Bitmap(rowID=1, frame=f))")
        assert resp.status == 200
        plan = resp.json()
        assert plan["index"] == "i"
        assert "results" not in plan  # planned, not executed
        call = plan["calls"][0]
        assert call["call"] == "Count"
        assert call["route"] in ("memo", "host-fold", "mesh", "roaring")
        cm = call["cost_model"]
        assert cm["lowerable"] is True
        assert cm["leaves"] == 1
        assert cm["work_units"] == 1
        assert cm["min_work"] >= 0  # env may pin routing off (0)
        assert call["staging"]["estimated_h2d_bytes"] > 0
        # Placement mirrors _slices_by_node: every slice owned here.
        nodes = call["placement"]["nodes"]
        assert sum(e["slices"] for e in nodes.values()) == 1
        # No execution happened: fragment untouched, no dispatch.
        assert frag.generation == gen_before
        assert h.executor.route_stats.copy().get("count_mesh", 0) == 0

    def test_explain_memo_peek_does_not_mutate(self, env):
        holder, h = env
        _seed(h)
        q = b"Count(Bitmap(rowID=1, frame=f))"
        h.handle("POST", "/index/i/query", body=q)  # prime the memo
        stats_before = dict(h.executor.host_cache_stats)
        plan = h.handle("POST", "/index/i/query", {"explain": "true"},
                        body=q).json()
        assert plan["calls"][0]["memo_hit"] is True
        assert plan["calls"][0]["route"] == "memo"
        # The peek bumped no hit/miss counters.
        assert dict(h.executor.host_cache_stats) == stats_before

    def test_explain_write_and_parse_errors(self, env):
        holder, h = env
        _seed(h)
        plan = h.handle(
            "POST", "/index/i/query", {"explain": "true"},
            body=b"SetBit(rowID=2, frame=f, columnID=9)").json()
        assert plan["calls"][0]["route"] == "write"
        # The planned write did not execute.
        assert h.handle(
            "POST", "/index/i/query",
            body=b"Count(Bitmap(rowID=2, frame=f))").json() \
            == {"results": [0]}
        bad = h.handle("POST", "/index/i/query", {"explain": "true"},
                       body=b"Nope(")
        assert bad.status == 400
