"""Fleet observability plane tests: the canonical Prometheus text
parser (duplicate-cumulative summing, exemplar tolerance), the exact
fleet merge, FleetAggregator defensiveness (stale tolerance, breaker
skips, ring churn), the 3-node /debug/fleet endpoint with bit-identical
counter sums, exemplars end-to-end (/metrics?exemplars=true ->
/debug/traces/<id>, including cross-node grafted spans), the
query-shape flight recorder (/debug/queryshapes ranking + exact
route/tier agreement with pilosa_query_route_total), SPMD collective
telemetry (dispatch counters, gate-veto reasons, ICI tier bytes), label
cardinality bounds, the metrics-lint rules, and a concurrent
scrape-during-dispatch hammer (never a torn family).
"""

import importlib.util
import json
import os
import re
import socket
import threading

import pytest

from pilosa_tpu import SLICE_WIDTH
from pilosa_tpu.api import Handler, InternalClient
from pilosa_tpu.config import Config
from pilosa_tpu.core import Holder
from pilosa_tpu.executor import Executor
from pilosa_tpu.obs import fleet, flight
from pilosa_tpu.obs.metrics import TIER_BYTES
from pilosa_tpu.parallel import new_test_cluster
from pilosa_tpu.server import Server


@pytest.fixture
def env(tmp_path):
    holder = Holder(str(tmp_path / "data"))
    holder.open()
    cluster = new_test_cluster(1)
    ex = Executor(holder, host=cluster.nodes[0].host, cluster=cluster,
                  use_device=False)
    handler = Handler(holder, ex, cluster=cluster,
                      host=cluster.nodes[0].host)
    yield holder, ex, handler
    holder.close()


def _seed(h):
    assert h.handle("POST", "/index/i").status == 200
    assert h.handle("POST", "/index/i/frame/f").status == 200
    assert h.handle(
        "POST", "/index/i/query",
        body=b"SetBit(rowID=1, frame=f, columnID=5)").status == 200


def _count(h, pql=b"Count(Bitmap(rowID=1, frame=f))"):
    r = h.handle("POST", "/index/i/query", body=pql)
    assert r.status == 200
    return r


# ---------------------------------------------------------------------------
# parse_text / merge / hist_percentiles units


class TestParseText:
    def test_duplicate_cumulative_sums_gauge_last_wins(self):
        text = ('a_total{t="x"} 2\n'
                'a_total{t="x"} 3\n'
                'g{t="x"} 2\n'
                'g{t="x"} 9\n')
        out = fleet.parse_text(text)
        assert out[("a_total", (("t", "x"),))] == 5.0
        assert out[("g", (("t", "x"),))] == 9.0

    def test_exemplar_suffix_tolerated(self):
        text = ('h_bucket{le="8"} 7 # {trace_id="abc"} 5.2 123.000\n'
                "h_count 7\n")
        out = fleet.parse_text(text)
        assert out[("h_bucket", (("le", "8"),))] == 7.0
        assert out[("h_count", ())] == 7.0

    def test_garbage_and_comments_skipped(self):
        text = ("# HELP x y\n# TYPE x counter\n"
                "!!!not a sample\nx_total notanumber\nx_total 4\n")
        assert fleet.parse_text(text) == {("x_total", ()): 4.0}

    def test_label_order_independent(self):
        a = fleet.parse_text('m_total{a="1",b="2"} 3\n')
        b = fleet.parse_text('m_total{b="2",a="1"} 3\n')
        assert a == b


class TestMerge:
    def test_counters_sum_gauges_dropped(self):
        n1 = fleet.parse_text("q_total 3\nuptime_seconds 100\n")
        n2 = fleet.parse_text("q_total 4\nuptime_seconds 7\n")
        merged = fleet.merge([n1, n2])
        assert merged[("q_total", ())] == 7.0
        assert ("uptime_seconds", ()) not in merged

    def test_histogram_buckets_sum_per_le(self):
        n1 = fleet.parse_text('h_bucket{le="1"} 1\nh_bucket{le="2"} 4\n'
                              'h_bucket{le="+Inf"} 4\nh_count 4\n'
                              "h_sum 6\n")
        n2 = fleet.parse_text('h_bucket{le="1"} 2\nh_bucket{le="2"} 2\n'
                              'h_bucket{le="+Inf"} 6\nh_count 6\n'
                              "h_sum 40\n")
        merged = fleet.merge([n1, n2])
        assert merged[("h_bucket", (("le", "1"),))] == 3.0
        assert merged[("h_bucket", (("le", "+Inf"),))] == 10.0
        assert merged[("h_count", ())] == 10.0
        # The merged buckets are still a valid cumulative histogram.
        p50, p95, p99, n = fleet.hist_percentiles(merged, "h", {})
        assert n == 10
        assert p50 <= p95 <= p99

    def test_mixed_label_products_sum_in_percentiles(self):
        # Two tenants' bucket series: percentiles over BOTH must sum
        # duplicate le values, not keep whichever series parsed last.
        text = ('h_bucket{tenant="a",le="1"} 0\n'
                'h_bucket{tenant="a",le="2"} 10\n'
                'h_bucket{tenant="a",le="+Inf"} 10\n'
                'h_bucket{tenant="b",le="1"} 90\n'
                'h_bucket{tenant="b",le="2"} 90\n'
                'h_bucket{tenant="b",le="+Inf"} 90\n')
        m = fleet.parse_text(text)
        p50, p95, p99, n = fleet.hist_percentiles(m, "h", {})
        assert n == 100
        assert p50 == 1.0      # 90 of 100 sit at le=1
        assert p95 == 2.0
        # Pinning the tenant selects one product only.
        assert fleet.hist_percentiles(m, "h", {"tenant": "a"})[3] == 10


class TestAggregator:
    def _mk(self, texts, fail=(), breaker=None, now=None):
        calls = []

        def fetch(host, path, timeout_s):
            calls.append((host, path))
            if host in fail:
                raise ConnectionError("down")
            if path == "/metrics":
                return texts[host]
            return "{}"

        agg = fleet.FleetAggregator(
            members=lambda: {h: "UP" for h in texts},
            fetch=fetch, breaker_state=breaker,
            **({"now": now} if now else {}))
        return agg, calls

    def test_stale_tolerance_keeps_last_good_sample(self):
        clock = [100.0]
        texts = {"n1:1": "pilosa_query_outcome_total 5\n"}
        fail = set()
        agg, _ = self._mk(texts, fail=fail, now=lambda: clock[0])
        doc = agg.snapshot(force=True)
        assert doc["healthy"] == 1 and doc["scraped"] == 1
        assert doc["nodes"]["n1:1"]["scrape_age_s"] == 0.0
        # Node goes dark: old samples survive, aged and annotated.
        fail.add("n1:1")
        clock[0] = 130.0
        doc = agg.snapshot(force=True)
        assert doc["scraped"] == 1 and doc["healthy"] == 0
        row = doc["nodes"]["n1:1"]
        assert row["scrape_age_s"] == 30.0
        assert "ConnectionError" in row["error"]
        assert doc["merged"]["pilosa_query_outcome_total"] == 5.0

    def test_breaker_open_skips_fetch(self):
        texts = {"n1:1": "x_total 1\n", "n2:1": "x_total 2\n"}
        agg, calls = self._mk(
            texts, breaker=lambda h: "open" if h == "n2:1" else "")
        doc = agg.snapshot(force=True)
        assert all(host != "n2:1" for host, _ in calls)
        assert doc["nodes"]["n2:1"]["error"] == "breaker open"
        assert doc["merged"]["x_total"] == 1.0

    def test_member_leaving_ring_forgotten(self):
        texts = {"n1:1": "x_total 1\n", "n2:1": "x_total 2\n"}
        agg, _ = self._mk(texts)
        assert agg.snapshot(force=True)["merged"]["x_total"] == 3.0
        del texts["n2:1"]
        doc = agg.snapshot(force=True)
        assert doc["members"] == 1
        assert doc["merged"]["x_total"] == 1.0

    def test_snapshot_cached_within_interval(self):
        clock = [0.0]
        texts = {"n1:1": "x_total 1\n"}
        agg, calls = self._mk(texts, now=lambda: clock[0])
        agg.snapshot()
        n0 = len(calls)
        agg.snapshot()  # within interval: served from cache
        assert len(calls) == n0
        clock[0] += agg.interval + 1
        agg.snapshot()
        assert len(calls) > n0


# ---------------------------------------------------------------------------
# 3-node cluster: /debug/fleet end-to-end, bit-identical sums


def _free_ports(n):
    socks = [socket.socket() for _ in range(n)]
    for s in socks:
        s.bind(("127.0.0.1", 0))
    ports = [s.getsockname()[1] for s in socks]
    for s in socks:
        s.close()
    return ports


@pytest.fixture
def cluster3(tmp_path):
    ports = _free_ports(3)
    hosts = [f"127.0.0.1:{p}" for p in ports]
    servers = []
    for i, h in enumerate(hosts):
        c = Config()
        c.data_dir = str(tmp_path / f"node{i}")
        c.host = h
        c.cluster_hosts = hosts
        c.replica_n = 1
        c.anti_entropy_interval = 3600
        c.polling_interval = 3600
        s = Server(c)
        s.open()
        servers.append(s)
    yield servers, hosts
    for s in servers:
        s.close()


class TestFleetEndpoint:
    def _traffic(self, hosts):
        cli = InternalClient(hosts[0])
        cli.create_index("i")
        cli.create_frame("i", "f")
        q = "".join(
            f"SetBit(rowID=1, frame=f, columnID={s * SLICE_WIDTH + s})"
            for s in range(8))
        assert cli.execute_query(None, "i", q, [], remote=False)
        for _ in range(3):
            assert cli.execute_query(
                None, "i", "Count(Bitmap(rowID=1, frame=f))", [],
                remote=False) == [8]

    def test_three_node_fleet_merge_bit_identical(self, cluster3):
        servers, hosts = cluster3
        self._traffic(hosts)

        doc = servers[0].handler.handle(
            "GET", "/debug/fleet", params={"force": "true"}).json()
        assert doc["members"] == 3
        assert doc["scraped"] == 3 and doc["healthy"] == 3
        for h in hosts:
            row = doc["nodes"][h]
            assert row["state"] == "UP" and row["error"] is None
            assert row["scrape_age_s"] is not None
            assert set(row) >= {"tiers", "routes", "hints", "hbm",
                                "requests_total"}

        # Bit-identical: per-node /metrics scraped independently, the
        # query-route counters summed by hand (these families are
        # quiescent — scraping itself never moves them), and every one
        # must equal the endpoint's merged value exactly.
        by_key = {}
        for s in servers:
            text = s.handler.handle("GET", "/metrics").body.decode()
            for (name, labels), v in fleet.parse_text(text).items():
                if name == "pilosa_query_route_total":
                    k = fleet.sample_key(name, labels)
                    by_key[k] = by_key.get(k, 0.0) + v
        assert by_key, "no pilosa_query_route_total series scraped"
        for k, v in by_key.items():
            assert doc["merged"][k] == v, k

        # Fan-out Counts crossed the ring over HTTP: the coordinator's
        # client accounted those bytes to the http tier.
        assert doc["merged"].get(
            'pilosa_tier_bytes_total{tier="http"}', 0) > 0

    def test_frozen_scrapes_merge_exactly(self, cluster3):
        # Aggregator over FROZEN per-node expositions vs a by-hand sum
        # of every cumulative sample: the full merged map, bit for bit.
        servers, hosts = cluster3
        self._traffic(hosts)
        texts = {h: s.handler.handle("GET", "/metrics").body.decode()
                 for h, s in zip(hosts, servers)}
        agg = fleet.FleetAggregator(
            members=lambda: {h: "UP" for h in hosts},
            fetch=lambda h, path, t: (texts[h] if path == "/metrics"
                                      else "{}"))
        doc = agg.snapshot(force=True)
        expected = {}
        for text in texts.values():
            for (name, labels), v in fleet.parse_text(text).items():
                if fleet.is_cumulative(name):
                    k = fleet.sample_key(name, labels)
                    expected[k] = expected.get(k, 0.0) + v
        assert doc["merged"] == expected

    def test_fleet_404_without_cluster(self, tmp_path):
        holder = Holder(str(tmp_path / "d"))
        holder.open()
        try:
            ex = Executor(holder, use_device=False)
            h = Handler(holder, ex)
            assert h.handle("GET", "/debug/fleet").status == 404
        finally:
            holder.close()


# ---------------------------------------------------------------------------
# exemplars: /metrics?exemplars=true -> /debug/traces/<id>


_EXEMPLAR_RE = re.compile(r'# \{trace_id="([^"]+)"\} ')


class TestExemplars:
    def test_default_scrape_has_no_exemplars(self, env):
        _, _, h = env
        _seed(h)
        _count(h)
        text = h.handle("GET", "/metrics").body.decode()
        assert "# {" not in text

    def test_exemplar_resolves_to_trace(self, env):
        _, _, h = env
        _seed(h)
        _count(h)
        text = h.handle("GET", "/metrics",
                        params={"exemplars": "true"}).body.decode()
        lines = [ln for ln in text.splitlines()
                 if ln.startswith(
                     "pilosa_query_route_duration_microseconds_bucket")
                 and "# {" in ln]
        assert lines, "no exemplar on the route latency histogram"
        tids = {m.group(1) for ln in lines
                for m in [_EXEMPLAR_RE.search(ln)] if m}
        resolved = 0
        for tid in tids:
            resp = h.handle("GET", f"/debug/traces/{tid}")
            if resp.status == 200:
                tr = resp.json()
                assert {s["name"] for s in tr["spans"]} >= {"query"}
                resolved += 1
        assert resolved, f"none of {tids} resolved at /debug/traces"

    def test_slo_latency_sli_carries_exemplar(self, env):
        _, _, h = env
        _seed(h)
        for _ in range(3):
            _count(h)
        doc = h.handle("GET", "/debug/slo").json()
        exemplars = [row["exemplar"]
                     for w in doc["windows"].values()
                     for row in w["tenants"].values()
                     if "exemplar" in row]
        assert exemplars, "no exemplar in any latency SLI row"
        ex = exemplars[0]
        assert ex["latency_us"] > 0
        assert h.handle(
            "GET", f"/debug/traces/{ex['trace_id']}").status == 200

    def test_cross_node_exemplar_resolves_with_grafted_spans(
            self, cluster3):
        servers, hosts = cluster3
        cli = InternalClient(hosts[0])
        cli.create_index("i")
        cli.create_frame("i", "f")
        n = 8
        q = "".join(
            f"SetBit(rowID=1, frame=f, columnID={s * SLICE_WIDTH + s})"
            for s in range(n))
        assert cli.execute_query(None, "i", q, [], remote=False)
        assert cli.execute_query(
            None, "i", "Count(Bitmap(rowID=1, frame=f))", [],
            remote=False) == [n]
        text = servers[0].handler.handle(
            "GET", "/metrics",
            params={"exemplars": "true"}).body.decode()
        tids = {m.group(1) for m in _EXEMPLAR_RE.finditer(text)}
        assert tids, "no exemplars on the coordinator scrape"
        grafted = []
        for tid in tids:
            resp = servers[0].handler.handle(
                "GET", f"/debug/traces/{tid}")
            if resp.status != 200:
                continue
            spans = resp.json()["spans"]
            if any(str(s["tags"].get("node", "")).startswith("http://")
                   for s in spans):
                grafted = spans
        assert grafted, "no exemplar trace carried grafted remote spans"
        assert "fanout" in {s["name"] for s in grafted}


# ---------------------------------------------------------------------------
# query-shape flight recorder


class TestQueryShapes:
    def test_ring_eviction(self):
        fr = flight.FlightRecorder(ring=2)
        fr.record("a", "mesh", "local", 10.0)
        fr.record("b", "mesh", "local", 10.0)
        fr.record("a", "mesh", "local", 10.0)  # refresh: a is now MRU
        fr.record("c", "mesh", "local", 10.0)  # evicts b (LRU)
        assert len(fr) == 2
        assert fr.stats() == {"shapes": 2, "ring": 2, "evicted": 1}
        sigs = {r["signature"] for r in fr.snapshot()["top"]}
        assert sigs == {"a", "c"}

    def test_bad_sort_rejected(self):
        with pytest.raises(ValueError):
            flight.FlightRecorder().snapshot(sort="nope")

    def test_hot_shape_ranks_first_and_mix_matches_metrics(self, env):
        _, ex, h = env
        _seed(h)
        assert h.handle(
            "POST", "/index/i/query",
            body=b"SetBit(rowID=2, frame=f, columnID=6)").status == 200
        for _ in range(5):
            _count(h)  # the hot shape
        _count(h, b"Count(Intersect(Bitmap(rowID=1, frame=f), "
                  b"Bitmap(rowID=2, frame=f)))")  # a second shape, once

        doc = h.handle("GET", "/debug/queryshapes",
                       params={"sort": "count"}).json()
        assert doc["shapes"] >= 2
        top = doc["top"][0]
        assert top["count"] == 5
        assert top["example"].startswith("Count(")
        assert top["p50_us"] > 0 and top["p99_us"] >= top["p50_us"]

        # The recorder's route/tier marginals must agree EXACTLY with
        # pilosa_query_route_total — both are fed by the same
        # _record_route call, so any drift is a dropped record.
        text = h.handle("GET", "/metrics").body.decode()
        by_backend, by_tier = {}, {}
        for (name, labels), v in fleet.parse_text(text).items():
            if name != "pilosa_query_route_total":
                continue
            d = dict(labels)
            by_backend[d["backend"]] = (
                by_backend.get(d["backend"], 0) + int(v))
            by_tier[d["tier"]] = by_tier.get(d["tier"], 0) + int(v)
        fr_backend, fr_tier = {}, {}
        for row in doc["top"]:
            for r, n in row["routes"].items():
                fr_backend[r] = fr_backend.get(r, 0) + n
            for t, n in row["tiers"].items():
                fr_tier[t] = fr_tier.get(t, 0) + n
        assert fr_backend == by_backend
        assert fr_tier == by_tier

    def test_endpoint_sort_and_limit(self, env):
        _, _, h = env
        _seed(h)
        _count(h)
        for sort in flight.SORTS:
            r = h.handle("GET", "/debug/queryshapes",
                         params={"sort": sort, "limit": "1"})
            assert r.status == 200
            assert len(r.json()["top"]) == 1
        assert h.handle("GET", "/debug/queryshapes",
                        params={"sort": "bogus"}).status == 400

    def test_queryshape_gauges_on_metrics(self, env):
        _, _, h = env
        _seed(h)
        _count(h)
        text = h.handle("GET", "/metrics").body.decode()
        m = fleet.parse_text(text)
        assert m[("pilosa_queryshape_tracked", ())] >= 1
        assert m[("pilosa_queryshape_ring", ())] >= 1
        assert ("pilosa_queryshape_evicted_total", ()) in m


# ---------------------------------------------------------------------------
# SPMD collective telemetry


class TestSpmdTelemetry:
    def test_encode_accounts_ici_tier_bytes(self):
        from pilosa_tpu.parallel import spmd
        desc = {"op": 1, "index": "i", "slices": [0, 1, 2]}
        before = TIER_BYTES.copy().get("ici", 0)
        spmd._encode(desc)
        delta = TIER_BYTES.copy().get("ici", 0) - before
        assert delta == len(json.dumps(desc).encode())

    def test_dispatch_counter_and_histogram(self, tmp_path):
        from pilosa_tpu.parallel import spmd
        holder = Holder(str(tmp_path / "d"))
        holder.open()
        try:
            srv = spmd.SpmdServer(holder)
            before = spmd.SPMD_STATS.copy().get("dispatch:unknown", 0)
            h_before = spmd.op_hist("unknown").total
            with pytest.raises(ValueError):
                srv._run({"op": 999})
            assert spmd.SPMD_STATS.copy()[
                "dispatch:unknown"] == before + 1
            assert spmd.op_hist("unknown").total == h_before + 1
        finally:
            holder.close()

    def test_gate_veto_reasons(self, tmp_path, monkeypatch):
        import numpy as np

        from jax.experimental import multihost_utils
        from pilosa_tpu.parallel import spmd

        holder = Holder(str(tmp_path / "d"))
        holder.open()
        try:
            srv = spmd.SpmdServer(holder)

            def veto_counts():
                c = spmd.SPMD_STATS.copy()
                return (c.get("veto:not_ready", 0),
                        c.get("veto:format_disagreement", 0))

            # No local program: not_ready (single-process allgather).
            nr0, fd0 = veto_counts()
            assert srv._gate(None) is False
            assert veto_counts() == (nr0 + 1, fd0)
            # Agreement: passes, no veto.
            assert srv._gate(b"prog") is True
            assert veto_counts() == (nr0 + 1, fd0)
            # A peer gathered 0 (its program wasn't ready): not_ready.
            monkeypatch.setattr(multihost_utils, "process_allgather",
                                lambda fp: np.array([int(fp), 0]))
            assert srv._gate(b"prog") is False
            assert veto_counts() == (nr0 + 2, fd0)
            # All ranks resolved programs, but they DISAGREE.
            monkeypatch.setattr(multihost_utils, "process_allgather",
                                lambda fp: np.array([int(fp),
                                                     int(fp) + 1]))
            assert srv._gate(b"prog") is False
            assert veto_counts() == (nr0 + 2, fd0 + 1)
        finally:
            holder.close()

    def test_spmd_families_on_metrics(self, env):
        from pilosa_tpu.parallel import spmd
        _, ex, h = env
        ex.mesh_manager()  # device stats exist only once built
        spmd.SPMD_STATS.inc("dispatch:count")
        spmd.SPMD_STATS.inc("veto:not_ready")
        spmd.op_hist("count").observe(42.0)
        m = fleet.parse_text(h.handle("GET", "/metrics").body.decode())
        assert m[("pilosa_spmd_dispatch_total",
                  (("op", "count"),))] >= 1
        assert m[("pilosa_spmd_gate_veto_total",
                  (("reason", "not_ready"),))] >= 1
        assert m[("pilosa_spmd_dispatch_us_count",
                  (("op", "count"),))] >= 1
        # Tier-byte counters are always exported, both tiers.
        for tier in ("ici", "http"):
            assert ("pilosa_tier_bytes_total",
                    (("tier", tier),)) in m

    def test_dispatch_gen_moved_counter_exported(self, tmp_path):
        # The retry-into-coalescing counter rides the device stats
        # block, so it needs a device-backed executor (cpu backend).
        holder = Holder(str(tmp_path / "d"))
        holder.open()
        try:
            ex = Executor(holder, use_device=True)
            assert ex.mesh_manager() is not None
            h = Handler(holder, ex)
            m = fleet.parse_text(
                h.handle("GET", "/metrics").body.decode())
            assert m[("pilosa_dispatch_gen_moved_total", ())] == 0.0
            ex.mesh_manager().stats.inc("dispatch_gen_moved")
            m = fleet.parse_text(
                h.handle("GET", "/metrics").body.decode())
            assert m[("pilosa_dispatch_gen_moved_total", ())] == 1.0
        finally:
            holder.close()


# ---------------------------------------------------------------------------
# cardinality bounds + lint + torn-family hammer


def _load_lint():
    path = os.path.join(os.path.dirname(__file__), os.pardir,
                        "tools", "metrics_lint.py")
    spec = importlib.util.spec_from_file_location("metrics_lint", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestCardinalityAndLint:
    def test_label_values_stay_bounded(self, env):
        _, _, h = env
        # The cost ledger and the phase histograms are one per process
        # and keep the tenants of whatever test file this worker ran
        # before (test_slo.py sends X-Pilosa-Tenant: gold): judged is
        # what this handler's own traffic adds.
        before = {dict(labels).get("tenant") for (_, labels) in
                  fleet.parse_text(h.handle("GET", "/metrics").body.decode())}
        _seed(h)
        for _ in range(3):
            _count(h)
        m = fleet.parse_text(h.handle("GET", "/metrics").body.decode())
        tiers, ops, tenants = set(), set(), set()
        for (_, labels) in m:
            d = dict(labels)
            if "tier" in d:
                tiers.add(d["tier"])
            if "op" in d:
                ops.add(d["op"])
            if "tenant" in d:
                tenants.add(d["tenant"])
        assert tiers <= {"local", "ici", "http"}
        assert ops <= {"count", "stop", "rowcounts", "write", "schema",
                       "pql", "import", "rcsrc", "bsisum", "unknown"}
        # No per-config tenants here: only the defaults plus the cost
        # ledger's reserved fallback row may appear.
        assert tenants - before <= {"default", "other", "system"}

    def test_live_scrape_passes_lint(self, env):
        _, _, h = env
        _seed(h)
        _count(h)
        ml = _load_lint()
        text = h.handle("GET", "/metrics",
                        params={"exemplars": "true"}).body.decode()
        assert ml.lint(text) == []

    def test_lint_rules_catch_violations(self):
        ml = _load_lint()
        bad = ("# TYPE nohelp_total counter\nnohelp_total 1\n"
               "# HELP bad_gauge_total g\n"
               "# TYPE bad_gauge_total gauge\nbad_gauge_total 1\n"
               "# HELP c c\n# TYPE c counter\nc 1\n"
               "# HELP h_ms h\n# TYPE h_ms histogram\n"
               'h_ms_bucket{le="+Inf"} 1\nh_ms_count 1\nh_ms_sum 1\n'
               "# HELP leak l\n# TYPE leak gauge\n"
               'leak{query="Count(...)"} 1\n')
        problems = ml.lint(bad)
        assert any("missing HELP" in p for p in problems)
        assert any("gauge with a counter's _total" in p
                   for p in problems)
        assert any("counter families must end in _total" in p
                   for p in problems)
        assert any("unit suffix" in p for p in problems)
        assert any("'query' not in the bounded" in p for p in problems)

    def test_lint_series_ceiling(self):
        ml = _load_lint()
        lines = ["# HELP big b", "# TYPE big gauge"]
        lines += [f'big{{host="h{i}"}} 1' for i in range(12)]
        assert ml.lint("\n".join(lines) + "\n", max_series=10)
        assert ml.lint("\n".join(lines) + "\n", max_series=20) == []

    def test_scrape_during_dispatch_never_torn(self, env):
        """Hammer the SPMD instrumentation (dispatch counters, per-op
        histograms, tier bytes) from writer threads while scraping
        /metrics: every scrape must parse and every histogram family
        must be internally consistent (+Inf bucket == _count)."""
        from pilosa_tpu.parallel import spmd
        _, _, h = env
        _seed(h)
        stop = threading.Event()

        def _dispatcher():
            while not stop.is_set():
                spmd.SPMD_STATS.inc("dispatch:count")
                spmd.op_hist("count").observe(17.0)
                TIER_BYTES.inc("ici", 64)

        writers = [threading.Thread(target=_dispatcher, daemon=True)
                   for _ in range(4)]
        for t in writers:
            t.start()
        try:
            for _ in range(25):
                text = h.handle("GET", "/metrics").body.decode()
                m = fleet.parse_text(text)
                assert m, "empty scrape under write load"
                inf_by_family: dict = {}
                counts_by_family: dict = {}
                for (name, labels), v in m.items():
                    d = dict(labels)
                    if name.endswith("_bucket") and d.get(
                            "le") == "+Inf":
                        key = (name[: -len("_bucket")], tuple(
                            sorted((k, lv) for k, lv in d.items()
                                   if k != "le")))
                        inf_by_family[key] = v
                    elif name.endswith("_count"):
                        key = (name[: -len("_count")],
                               tuple(sorted(d.items())))
                        counts_by_family[key] = v
                for key, inf in inf_by_family.items():
                    if key in counts_by_family:
                        assert counts_by_family[key] == inf, (
                            f"torn histogram family: {key}")
        finally:
            stop.set()
            for t in writers:
                t.join()
