"""HTTP route table + handlers (parity with /root/reference/handler.go).

Routes (reference handler.go:81-121):

    GET    /                                     WebUI console
    GET    /index                                list indexes (schema)
    GET    /index/{index}                        index info
    POST   /index/{index}                        create index
    DELETE /index/{index}                        delete index
    POST   /index/{index}/attr/diff              column-attr anti-entropy diff
    PATCH  /index/{index}/time-quantum           set index time quantum
    POST   /index/{index}/query                  PQL query (JSON or protobuf)
    POST   /index/{index}/frame/{frame}          create frame
    DELETE /index/{index}/frame/{frame}          delete frame
    POST   /index/{index}/frame/{frame}/attr/diff   row-attr diff
    POST   /index/{index}/frame/{frame}/restore  pull frame data from a host
    PATCH  /index/{index}/frame/{frame}/time-quantum
    GET    /index/{index}/frame/{frame}/views    list view names
    GET    /export                               fragment as CSV
    GET    /fragment/data                        fragment tar (backup)
    POST   /fragment/data                        fragment tar (restore)
    GET    /fragment/blocks                      block checksums
    GET    /fragment/block/data                  block row/col pairs (protobuf)
    GET    /fragment/nodes                       replica nodes for a slice
    POST   /import                               bulk import (protobuf)
    GET    /hosts                                cluster hosts
    GET    /schema                               full schema
    GET    /slices/max                           per-index max slice
    GET    /status                               cluster status
    GET    /version
    GET    /metrics                              Prometheus exposition
    GET    /debug/vars                           stats snapshot
    GET    /debug/queries                        recent/slow query traces
    GET    /debug/traces/{id}                    one query trace (spans)
    POST   /internal/message                     broadcast receive (this
                                                 framework's internal plane —
                                                 replaces the reference's
                                                 separate internal port)
    GET    /internal/status                      NodeStatus exchange
                                                 (gossip-lite pull)

Content negotiation: `Content-Type: application/x-protobuf` request
bodies and `Accept: application/x-protobuf` responses use the wire
messages; everything else is JSON (handler.go:811,873 readQueryRequest /
writeQueryResponse).
"""

from __future__ import annotations

import binascii
import io
import itertools
import json
import os
import re
import threading
import time
from datetime import datetime, timezone
from typing import Callable, Dict, List, NamedTuple, Optional, Tuple

from ..bsi import FieldNotFoundError, FieldValueError
from ..core.attr import diff_blocks
from ..core.row import Row
from ..core.timequantum import parse_time_quantum
from ..errors import (
    DeadlineExceededError,
    FragmentNotFoundError,
    FrameExistsError,
    FrameNotFoundError,
    IndexExistsError,
    IndexNotFoundError,
    PilosaError,
    QueryError,
    WriteBackpressureError,
    WriteConsistencyError,
)
from ..pql import ParseError, parse_string_cached
from ..executor import ExecOptions
from ..sched import AdmissionError
from ..utils.stats import ExpvarStats
from .. import fault
from .. import obs
from ..obs import Tracer
from ..wire import (
    PROTOBUF_CT,
    attrs_to_proto,
    pb,
    result_to_proto,
    unmarshal_message,
)

VERSION = "0.1.0"


def _parse_staleness(raw: str) -> float:
    """X-Pilosa-Staleness / ?staleness= value in seconds: a bare
    number is MILLISECONDS (the loadgen/client convention), anything
    suffixed parses as a Go duration ("500ms", "2s"). Unparseable
    values mean strict (0) — a read must never get LESS freshness
    than it asked for because of a typo'd header."""
    raw = raw.strip()
    if not raw:
        return 0.0
    try:
        return max(0.0, float(raw) / 1e3)
    except ValueError:
        pass
    try:
        from ..config import parse_duration

        return max(0.0, parse_duration(raw))
    except (ValueError, KeyError):
        return 0.0


_WEBUI_PAGE = """<!doctype html>
<html><head><title>pilosa-tpu</title><style>
body{font-family:monospace;margin:0;background:#fff;color:#222}
textarea,input,select{font-family:monospace;box-sizing:border-box}
textarea{width:100%}
pre{background:#f4f4f4;padding:.8em;overflow:auto;margin:.4em 0}
h1{font-size:1.2em;margin:0}
h2{font-size:1em;border-bottom:1px solid #ccc;margin:.8em 0 .4em}
button{font-family:monospace;margin-right:.4em;cursor:pointer}
table{border-collapse:collapse;margin:.4em 0}
td,th{border:1px solid #ccc;padding:.15em .6em;text-align:right}
th{background:#eee}
.hdr{display:flex;align-items:center;gap:1.5em;padding:.7em 1.2em;
     background:#123;color:#fff}
.hdr .dim{color:#9ab}
.nav{display:flex;gap:0}
.nav div{padding:.35em 1.1em;cursor:pointer;border-bottom:2px solid transparent;color:#cde}
.nav div.on{border-color:#6cf;color:#fff;background:#1a3a55}
.page{display:none;padding:1em 1.5em}
.page.on{display:block}
.cols{display:flex;gap:1.5em}.cols>div{flex:1;min-width:0}
.tree span{cursor:pointer;color:#035;text-decoration:underline}
.tree ul{margin:.1em 0 .1em 1.2em;padding:0;list-style:none}
#hist div,#hist2 div{cursor:pointer;color:#035;white-space:nowrap;overflow:hidden;text-overflow:ellipsis}
.err{color:#a00}.dim{color:#777}
.up{color:#070;font-weight:bold}.down{color:#a00;font-weight:bold}
</style></head><body>
<div class="hdr">
  <h1>pilosa-tpu</h1><span class="dim" id="ver"></span>
  <div class="nav">
    <div id="tab-console" class="on" onclick="nav('console')">Console</div>
    <div id="tab-cluster" onclick="nav('cluster')">Cluster Admin</div>
    <div id="tab-stats" onclick="nav('stats')">Stats</div>
    <div id="tab-docs" onclick="nav('docs')">Documentation</div>
  </div>
  <label style="margin-left:auto"><input type="checkbox" id="auto"> auto-refresh</label>
</div>

<div id="page-console" class="page on">
<div class="cols">
<div style="flex:1.5">
<h2>query</h2>
<p>index: <select id="idx" style="min-width:12em"><option value="i">i</option></select>
   <button onclick="run()">run</button>
   <button onclick="refresh()">refresh</button>
   <span class="dim" id="took"></span></p>
<p><textarea id="q" rows="4">Count(Bitmap(rowID=1, frame=general))</textarea></p>
<div id="result"></div>
<h2>history</h2><div id="hist"></div>
<h2>examples</h2><div id="hist2">
<div onclick="setQ(this)">Count(Intersect(Bitmap(rowID=1, frame=general), Bitmap(rowID=2, frame=general)))</div>
<div onclick="setQ(this)">TopN(frame=general, n=10)</div>
<div onclick="setQ(this)">SetBit(rowID=1, frame=general, columnID=7)</div>
<div onclick="setQ(this)">Range(rowID=1, frame=general, start=&quot;2017-01-01T00:00&quot;, end=&quot;2018-01-01T00:00&quot;)</div>
</div>
</div>
<div>
<h2>schema</h2><div id="schema" class="tree"></div>
</div>
</div>
</div>

<div id="page-cluster" class="page">
<h2>nodes</h2><div id="nodes"></div>
<h2>indexes on this cluster</h2><div id="clusteridx"></div>
<h2>raw /status</h2><pre id="status"></pre>
</div>

<div id="page-stats" class="page">
<h2>stats (/debug/vars)</h2><div id="vars"></div>
</div>

<div id="page-docs" class="page">
<h2>PQL quick reference</h2>
<pre>
SetBit(frame=f, rowID=R, columnID=C [, timestamp="2017-04-02T09:00"])
ClearBit(frame=f, rowID=R, columnID=C)
Bitmap(frame=f, rowID=R)            one row (columnID=C reads the inverse view)
Union(a, b, ...)  Intersect(a, b, ...)  Difference(a, b, ...)
Count(&lt;bitmap expr&gt;)                fused on-device popcount
TopN(frame=f, n=N [, threshold=T] [, ids=[..]] [, field=.., filters=[..]]
     [, tanimotoThreshold=P]) [&lt;src bitmap&gt;]
Range(frame=f, rowID=R, start="...", end="...")   time-quantum views
SetRowAttrs(frame=f, rowID=R, k=v, ...)   SetColumnAttrs(columnID=C, k=v, ...)

Integer fields (BSI; declare via POST frame options {"fields":[{"name":..,"min":..,"max":..}]}):
SetValue(frame=f, columnID=C, price=42)   write one column's value
Range(frame=f, price &gt;= 100)              value comparison: &lt; &lt;= &gt; &gt;= == != &gt;&lt; [lo,hi]
Sum(frame=f, field="price")               {value, count}; optional bitmap filter child
Min(frame=f, field="price")  Max(...)     device binary search over bit planes
</pre>
<h2>HTTP API</h2>
<pre>
POST /index/{i}                    create index      POST /index/{i}/query   PQL
POST /index/{i}/frame/{f}          create frame      GET  /schema
GET  /status    GET /hosts         cluster state     GET  /slices/max
POST /import                       protobuf bulk     GET  /export            CSV
GET  /fragment/data                fragment snapshot GET  /debug/vars        stats
GET  /metrics                      Prometheus text   GET  /version
POST /index/{i}/query?explain=true predicted plan (routing, quarantine, no dispatch)
POST /index/{i}/query?profile=true measured profile (phase times, bytes, roofline)
GET  /debug/queries                recent + slow     GET  /debug/traces/{id} spans
GET  /healthz                      liveness (LB)     GET  /readyz            readiness (LB)
GET  /debug/health                 watchdog + heartbeat table
GET  /debug/bundle                 diagnostic dossier (?write=true persists)
GET  /debug/pprof/profile          sampling profiler
GET  /debug/pprof/heap?start=1     alloc tracing (opt-in: PILOSA_TPU_HEAP_TRACE=1)
</pre>
<p class="dim">Full upstream documentation: <a href="https://www.pilosa.com/docs/">pilosa.com/docs</a></p>
</div>

<script>
const $ = id => document.getElementById(id);
function nav(name){
  for (const t of ['console','cluster','stats','docs']) {
    $('tab-'+t).classList.toggle('on', t === name);
    $('page-'+t).classList.toggle('on', t === name);
  }
}
function setQ(el){ $('q').value = el.textContent; }
function esc(s){ const d=document.createElement('div'); d.textContent=s; return d.innerHTML; }

function renderResult(results){
  const out = $('result'); out.innerHTML = '';
  for (const r of results) {
    if (Array.isArray(r) && r.length && r[0] && 'id' in r[0]) {  // TopN pairs
      let h = '<table><tr><th>row</th><th>count</th></tr>';
      for (const p of r) h += `<tr><td>${p.id}</td><td>${p.count}</td></tr>`;
      out.innerHTML += h + '</table>';
    } else if (r && typeof r === 'object' && 'bits' in r) {      // Bitmap row
      out.innerHTML += `<pre>count=${r.bits.length} attrs=${esc(JSON.stringify(r.attrs||{}))}\n` +
        esc(JSON.stringify(r.bits.slice(0, 2048))) +
        (r.bits.length > 2048 ? ' …' : '') + '</pre>';
    } else {
      out.innerHTML += '<pre>' + esc(JSON.stringify(r, null, 2)) + '</pre>';
    }
  }
}

let history = [];
async function run(){
  const q = $('q').value, t0 = performance.now();
  try {
    const r = await fetch('/index/'+$('idx').value+'/query', {method:'POST', body:q});
    const js = await r.json();
    $('took').textContent = (performance.now()-t0).toFixed(1)+' ms';
    if (js.error) { $('result').innerHTML = '<pre class="err">'+esc(js.error)+'</pre>'; }
    else renderResult(js.results || []);
    if (!history.length || history[0] !== q) {
      history.unshift(q); history = history.slice(0, 10);
      $('hist').innerHTML = history.map(h =>
        `<div onclick="setQ(this)">${esc(h)}</div>`).join('');
    }
  } catch (e) { $('result').innerHTML = '<pre class="err">'+esc(String(e))+'</pre>'; }
  refresh();
}

function schemaTree(indexes){
  let h = '<ul>';
  for (const ix of indexes || []) {
    h += `<li><span onclick="$('idx').value='${ix.name}'">${esc(ix.name)}</span><ul>`;
    for (const f of ix.frames || []) {
      const views = (f.views || []).join(', ');
      const m = f.meta || {};
      const extra = [m.timeQuantum ? 'tq='+m.timeQuantum : '',
                     m.inverseEnabled ? 'inverse' : '',
                     m.cacheType || ''].filter(Boolean).join(' ');
      h += `<li><span onclick="pick('${ix.name}','${f.name}')">${esc(f.name)}</span>` +
           ` <span class="dim" style="text-decoration:none;cursor:default">[${esc(views)}] ${esc(extra)}</span></li>`;
    }
    h += '</ul></li>';
  }
  return h + '</ul>';
}
function pick(ix, frame){
  $('idx').value = ix;
  $('q').value = `TopN(frame=${frame}, n=10)`;
}

function fillIndexDropdown(indexes){
  const sel = $('idx'), cur = sel.value;
  sel.innerHTML = '';
  for (const ix of indexes || []) {
    const o = document.createElement('option');
    o.value = o.textContent = ix.name;
    sel.appendChild(o);
  }
  if (!sel.options.length) {
    const o = document.createElement('option');
    o.value = o.textContent = 'i';
    sel.appendChild(o);
  }
  if (cur) sel.value = cur;
  if (!sel.value) sel.selectedIndex = 0;
}

function nodesTable(st){
  let h = '<table><tr><th>host</th><th>state</th><th>indexes</th></tr>';
  for (const n of st.nodes || []) {
    const cls = (n.state || 'UP') === 'UP' ? 'up' : 'down';
    const idxs = (n.indexes || []).map(i =>
      `${esc(i.name)} (maxSlice ${i.maxSlice ?? 0})`).join(', ');
    h += `<tr><td style="text-align:left">${esc(n.host||'')}</td>` +
         `<td class="${cls}">${esc(n.state||'')}</td>` +
         `<td style="text-align:left">${idxs}</td></tr>`;
  }
  return h + '</table>';
}

function clusterIndexTable(st){
  const rows = {};
  for (const n of st.nodes || [])
    for (const i of n.indexes || []) {
      rows[i.name] = rows[i.name] || {max: 0, frames: new Set(), nodes: 0};
      rows[i.name].max = Math.max(rows[i.name].max, i.maxSlice ?? 0);
      for (const f of i.frames || []) rows[i.name].frames.add(f);
      rows[i.name].nodes++;
    }
  let h = '<table><tr><th>index</th><th>maxSlice</th><th>frames</th><th>nodes</th></tr>';
  for (const [name, r] of Object.entries(rows))
    h += `<tr><td style="text-align:left">${esc(name)}</td><td>${r.max}</td>` +
         `<td style="text-align:left">${esc([...r.frames].join(', '))}</td><td>${r.nodes}</td></tr>`;
  return h + '</table>';
}

function varsTables(v){
  // top level is a flat scalar map (ExpvarStats counters) plus nested
  // sections like "mesh" — render scalars as one table, objects as
  // their own tables.
  let h = '', flat = '';
  for (const [k, val] of Object.entries(v)) {
    if (typeof val === 'object' && val !== null) {
      h += `<table><tr><th colspan=2>${esc(k)}</th></tr>`;
      for (const [kk, vv] of Object.entries(val))
        h += `<tr><td style="text-align:left">${esc(kk)}</td><td>${esc(JSON.stringify(vv))}</td></tr>`;
      h += '</table>';
    } else {
      flat += `<tr><td style="text-align:left">${esc(k)}</td><td>${esc(JSON.stringify(val))}</td></tr>`;
    }
  }
  if (flat) h = `<table><tr><th colspan=2>counters</th></tr>${flat}</table>` + h;
  return h || '<pre class="dim">(empty)</pre>';
}

async function refresh(){
  try { $('ver').textContent = 'v' + (await (await fetch('/version')).json()).version; } catch(e){}
  try {
    const sch = await (await fetch('/schema')).json();
    $('schema').innerHTML = schemaTree(sch.indexes);
    fillIndexDropdown(sch.indexes);
  } catch (e) { $('schema').textContent = String(e); }
  try {
    const st = await (await fetch('/status')).json();
    $('status').textContent = JSON.stringify(st, null, 2);
    $('nodes').innerHTML = nodesTable(st);
    $('clusteridx').innerHTML = clusterIndexTable(st);
  } catch (e) { $('status').textContent = String(e); }
  try { $('vars').innerHTML = varsTables(await (await fetch('/debug/vars')).json()); }
  catch (e) { $('vars').textContent = String(e); }
}
setInterval(() => { if ($('auto').checked) refresh(); }, 2000);
refresh();
</script></body></html>"""


class Response(NamedTuple):
    status: int
    headers: Dict[str, str]
    body: bytes

    def json(self):
        return json.loads(self.body.decode() or "null")


def _json_resp(obj, status: int = 200) -> Response:
    return Response(status, {"Content-Type": "application/json"},
                    (json.dumps(obj) + "\n").encode())


def _proto_resp(msg, status: int = 200) -> Response:
    return Response(status, {"Content-Type": PROTOBUF_CT}, msg.SerializeToString())


def _error_status(err: Exception) -> int:
    if isinstance(err, DeadlineExceededError):
        return 504
    if isinstance(err, AdmissionError):
        return 429
    if isinstance(err, (WriteBackpressureError, WriteConsistencyError)):
        return 503
    if isinstance(err, (IndexNotFoundError, FrameNotFoundError,
                        FragmentNotFoundError, FieldNotFoundError)):
        return 404
    if isinstance(err, (IndexExistsError, FrameExistsError)):
        return 409
    # Before the generic ValueError → 400: FieldValueError is a
    # ValueError, but an in-range-typed, out-of-declared-range value is
    # a semantic (422) rejection, not a malformed request.
    if isinstance(err, FieldValueError):
        return 422
    if isinstance(err, (QueryError, ParseError, ValueError, KeyError)):
        return 400
    return 500


class Route(NamedTuple):
    method: str
    pattern: re.Pattern
    fn: Callable


class Handler:
    """Transport-agnostic request handler bound to a Holder + Executor.

    `executor` needs `.execute(index, query, slices, opt) -> list`.
    Tests may swap it for a fake (the HandlerExecutor.ExecuteFn seam,
    reference handler_test.go:822-826).
    """

    def __init__(self, holder, executor, cluster=None, host: str = "",
                 broadcaster=None, broadcast_handler=None,
                 status_handler=None, client_factory=None, stats=None,
                 logger=None, tracer=None):
        self.holder = holder
        self.executor = executor
        self.cluster = cluster
        self.host = host
        # Outbound schema-change notifications (handler.go:366-639).
        self.broadcaster = broadcaster
        # Receives unmarshalled broadcast messages (server.ReceiveMessage).
        self.broadcast_handler = broadcast_handler
        # Provides local_status() for /internal/status and /status.
        self.status_handler = status_handler
        # client_factory(host) -> InternalClient, used by frame restore.
        self.client_factory = client_factory
        self.stats = stats if stats is not None else ExpvarStats()
        # Per-query trace rings behind /debug/queries (+ /debug/traces)
        # — servers pass a config-sized Tracer; a default one keeps
        # handler-only tests and embedded use working.
        self.tracer = tracer if tracer is not None else Tracer()
        self.logger = logger
        self.version = VERSION
        # Default per-query deadline in seconds (config query_deadline;
        # 0 = none). Applies to coordinator-side queries only — remote
        # fan-out legs get their budget from X-Pilosa-Deadline-Us.
        self.default_deadline = 0.0
        # SPMD descriptor plane (server wiring): bulk imports must ride
        # the descriptor stream so every rank's replica gets the bits;
        # None outside spmd mode. spmd_worker marks non-zero ranks,
        # whose mutating bulk routes are rejected.
        self.spmd = None
        self.spmd_worker = False
        # Live migration engine (parallel.Rebalancer, server wiring):
        # POST /cluster/resize triggers it; None = membership changes
        # apply without a coordinated data move (embedded/tests).
        self.resizer = None
        # Guards tracemalloc start/stop from /debug/pprof/heap: the
        # handler is threaded, and crossed ?start/?stop pairs without
        # the lock could stop a trace another request thinks it owns.
        self._tracemalloc_mu = threading.Lock()
        self._tracemalloc_ours = False
        # Prometheus exposition (GET /metrics): one registry, fed by
        # collect-time bridges over the existing stat stores — the hot
        # write paths stay untouched; the scrape pays the bridge cost.
        self._start_time = time.monotonic()
        # Fragment-walk gauges (row-cache sizes, cardinality) refresh
        # at most once per this many seconds ([obs]
        # metrics-sample-interval, server wiring): the walk is cheap
        # but O(fragments), and scrapers poll.
        self.metrics_sample_interval = 10.0
        self._frag_sample: Tuple[float, list] = (0.0, [])
        self._frag_sample_mu = threading.Lock()
        # Continuous profiling cadence ([obs] profile-sample-rate,
        # server wiring): 0 = only on explicit ?profile=true; N = every
        # Nth query is profiled (device bracketing and all), feeding
        # the pilosa_query_phase_us histograms without a response
        # section. The counter is monotonic across all queries.
        self.profile_sample_rate = 0
        self._profile_seq = itertools.count(1)
        # Cost observatory ([obs] cost-debt-threshold, server wiring):
        # a tenant whose attributed device_us share exceeds this gets
        # the observe-only X-Pilosa-Cost-Debt header on its query
        # responses. <= 0 disables the stamp.
        self.cost_debt_threshold = 0.5
        # Adaptive query scheduler (sched.QueryScheduler, server
        # wiring; [sched] config). When set, POST /query goes through
        # admission control — tenant from X-Pilosa-Tenant, shed answers
        # HTTP 429 + Retry-After, queue wait is profiled as sched_wait
        # and counts against the query deadline. None = no scheduling
        # (embedded/test handlers behave exactly as before).
        self.scheduler = None
        # Background integrity scrubber (core/scrub.Scrubber, server
        # wiring; [integrity] config). Feeds the pilosa_scrub_* metric
        # families and the /debug/vars integrity section. None =
        # embedded/test handlers without one.
        self.scrubber = None
        # Hinted-handoff manager (parallel.hints.HintManager, server
        # wiring) + the [cluster] write-consistency level. When hints
        # is set, POST /import coordinates quorum replication to the
        # other replica owners (?remote=true legs apply locally only)
        # and journals misses; None = local-apply-only (embedded/test
        # handlers, single-node).
        self.hints = None
        self.write_consistency = "quorum"
        # Default bounded-staleness read budget in seconds ([cluster]
        # default-read-staleness, server wiring): applied to
        # coordinator queries that carry no X-Pilosa-Staleness header.
        # 0 (the default) = strict owner-only reads everywhere.
        self.default_read_staleness = 0.0
        # Scheduler queue depth for the /internal/epochs digest (the
        # p2c load signal peers spread reads by); server wiring points
        # it at the query scheduler. None = report 0.
        self.queue_depth_fn = None
        # Liveness plane (obs.health, [health] config). /healthz and
        # /readyz read the process-global registry; ready_fn is the
        # server's serving-state half of readiness (open() completed,
        # close() not begun). None = embedded/test handlers count as
        # serving.
        self.ready_fn = None
        # SLO observatory (obs.slo.SLORecorder; [slo] config). Every
        # coordinator query outcome — success, partial, shed 429,
        # deadline 504, backpressure 503, other errors — is recorded
        # here exactly once by _post_query, feeding the rolling SLI
        # windows, pilosa_slo_* families, and GET /debug/slo. The
        # server replaces this default with a config-driven recorder;
        # set to None to disable accounting entirely.
        self.slo = obs.slo.SLORecorder()
        # Federated fleet view (obs.fleet.FleetAggregator) behind
        # GET /debug/fleet. Built lazily on first request — embedded
        # handlers without a cluster pay nothing and answer 404.
        # Interval/deadline from [obs] fleet-scrape-interval (server
        # wiring); peer scrapes ride client_factory transports, the
        # local node short-circuits through handle() directly.
        self.fleet_scrape_interval = 5.0
        self.fleet_scrape_deadline = 2.0
        self._fleet_agg = None
        self._fleet_mu = threading.Lock()
        self._fleet_clients: Dict[str, object] = {}
        self._prom = obs.prom.Registry()
        self._register_collectors()
        self._routes: List[Route] = []
        r = self._add_route
        r("GET", r"/", self._get_webui)
        r("GET", r"/index", self._get_indexes)
        r("GET", r"/index/(?P<index>[^/]+)", self._get_index)
        r("POST", r"/index/(?P<index>[^/]+)", self._post_index)
        r("DELETE", r"/index/(?P<index>[^/]+)", self._delete_index)
        r("POST", r"/index/(?P<index>[^/]+)/attr/diff", self._post_index_attr_diff)
        r("PATCH", r"/index/(?P<index>[^/]+)/time-quantum",
          self._patch_index_time_quantum)
        r("POST", r"/index/(?P<index>[^/]+)/query", self._post_query)
        r("POST", r"/index/(?P<index>[^/]+)/frame/(?P<frame>[^/]+)",
          self._post_frame)
        r("DELETE", r"/index/(?P<index>[^/]+)/frame/(?P<frame>[^/]+)",
          self._delete_frame)
        r("POST", r"/index/(?P<index>[^/]+)/frame/(?P<frame>[^/]+)/attr/diff",
          self._post_frame_attr_diff)
        r("POST", r"/index/(?P<index>[^/]+)/frame/(?P<frame>[^/]+)/restore",
          self._post_frame_restore)
        r("PATCH", r"/index/(?P<index>[^/]+)/frame/(?P<frame>[^/]+)/time-quantum",
          self._patch_frame_time_quantum)
        r("GET", r"/index/(?P<index>[^/]+)/frame/(?P<frame>[^/]+)/views",
          self._get_frame_views)
        r("GET", r"/export", self._get_export)
        r("GET", r"/fragment/data", self._get_fragment_data)
        r("POST", r"/fragment/data", self._post_fragment_data)
        r("GET", r"/fragment/blocks", self._get_fragment_blocks)
        r("GET", r"/fragment/block/data", self._get_fragment_block_data)
        r("GET", r"/fragment/nodes", self._get_fragment_nodes)
        r("POST", r"/import", self._post_import)
        r("GET", r"/hosts", self._get_hosts)
        r("POST", r"/cluster/resize", self._post_cluster_resize)
        r("GET", r"/schema", self._get_schema)
        r("GET", r"/slices/max", self._get_slice_max)
        r("GET", r"/status", self._get_status)
        r("GET", r"/version", self._get_version)
        r("GET", r"/metrics", self._get_metrics)
        r("GET", r"/healthz", self._get_healthz)
        r("GET", r"/readyz", self._get_readyz)
        r("GET", r"/debug/health", self._get_debug_health)
        r("GET", r"/debug/bundle", self._get_debug_bundle)
        r("GET", r"/debug/vars", self._get_expvar)
        r("GET", r"/debug/slo", self._get_debug_slo)
        r("GET", r"/debug/fleet", self._get_debug_fleet)
        r("GET", r"/debug/queryshapes", self._get_debug_queryshapes)
        r("GET", r"/debug/costs", self._get_debug_costs)
        r("GET", r"/debug/queries", self._get_debug_queries)
        r("GET", r"/debug/traces/(?P<tid>[^/]+)", self._get_debug_trace)
        r("GET", r"/debug/pprof/profile", self._get_cpu_profile)
        r("GET", r"/debug/pprof/heap", self._get_heap_profile)
        r("GET", r"/debug/pprof/allocs", self._get_heap_profile)
        r("GET", r"/debug/pprof/(?P<kind>block|mutex)",
          self._get_block_profile)
        r("GET", r"/debug/pprof/trace", self._get_trace)
        r("GET", r"/debug/pprof/goroutine", self._get_thread_dump)
        r("GET", r"/debug/pprof/threadcreate", self._get_threadcreate)
        r("GET", r"/debug/pprof/cmdline", self._get_cmdline)
        r("GET", r"/debug/pprof/?", self._get_pprof)
        r("POST", r"/internal/message", self._post_internal_message)
        r("GET", r"/internal/status", self._get_internal_status)
        r("GET", r"/internal/epochs", self._get_internal_epochs)
        r("POST", r"/internal/epochs/advance",
          self._post_internal_epochs_advance)

    def _add_route(self, method: str, pattern: str, fn: Callable):
        self._routes.append(Route(method, re.compile("^" + pattern + "$"), fn))

    # -- dispatch ------------------------------------------------------------

    def handle(self, method: str, path: str,
               params: Optional[Dict[str, str]] = None,
               headers: Optional[Dict[str, str]] = None,
               body: bytes = b"") -> Response:
        params = params or {}
        headers = {k.lower(): v for k, v in (headers or {}).items()}
        path_matched = False
        for route in self._routes:
            m = route.pattern.match(path)
            if m is None:
                continue
            path_matched = True
            if route.method != method:
                continue
            try:
                return route.fn(m.groupdict(), params, headers, body)
            except PilosaError as e:
                resp = _json_resp({"error": str(e)}, _error_status(e))
                retry = getattr(e, "retry_after_s", None)
                if retry is not None and resp.status == 503:
                    # Transient write sheds (backpressure, below-
                    # consistency) tell clients when to come back.
                    resp.headers["Retry-After"] = str(
                        max(1, int(round(retry))))
                return resp
            except (ValueError, KeyError, TypeError, binascii.Error) as e:
                return _json_resp({"error": str(e) or type(e).__name__}, 400)
            except Exception as e:  # noqa: BLE001 — never drop the connection
                return _json_resp(
                    {"error": f"internal error: {type(e).__name__}: {e}"}, 500)
        if path_matched:
            return _json_resp({"error": "method not allowed"}, 405)
        return _json_resp({"error": "not found"}, 404)

    # -- helpers -------------------------------------------------------------

    def _accepts_proto(self, headers) -> bool:
        return PROTOBUF_CT in headers.get("accept", "")

    def _sends_proto(self, headers) -> bool:
        return PROTOBUF_CT in headers.get("content-type", "")

    def _fragment_args(self, params):
        index = params["index"]
        frame = params["frame"]
        view = params.get("view", "standard")
        slice_ = int(params["slice"])
        return index, frame, view, slice_

    # -- webui / misc --------------------------------------------------------

    def _get_webui(self, pv, params, headers, body) -> Response:
        return Response(200, {"Content-Type": "text/html"},
                        _WEBUI_PAGE.encode())

    def _get_version(self, pv, params, headers, body) -> Response:
        return _json_resp({"version": self.version})

    # -- /metrics ------------------------------------------------------------

    def _get_metrics(self, pv, params, headers, body) -> Response:
        """Prometheus text exposition over every stat store: the
        ExpvarStats bridge, mesh/compile/device-memory telemetry,
        cache + dispatch + breaker counters, backend-labeled query
        latency histograms, build info. All bridged at scrape time.
        ?exemplars=true upgrades the output to OpenMetrics exemplar
        syntax — latency buckets carry sampled trace ids resolvable at
        /debug/traces/<id>; default scrapes stay plain 0.0.4."""
        text = self._prom.render(
            exemplars=params.get("exemplars") == "true")
        return Response(
            200,
            {"Content-Type": "text/plain; version=0.0.4; charset=utf-8"},
            text.encode())

    def _register_collectors(self):
        reg = self._prom
        reg.register_collector(
            lambda: obs.prom.expvar_families(self.stats))
        reg.register_collector(self._collect_runtime)
        reg.register_collector(self._collect_device)
        reg.register_collector(self._collect_caches)
        reg.register_collector(self._collect_cluster)
        reg.register_collector(self._collect_membership)
        reg.register_collector(self._collect_sched)
        reg.register_collector(self._collect_fragments)
        reg.register_collector(self._collect_storage)
        reg.register_collector(self._collect_integrity)
        reg.register_collector(self._collect_hints)
        reg.register_collector(self._collect_slo)
        reg.register_collector(self._collect_spmd)
        reg.register_collector(self._collect_read_path)
        # Liveness plane: pilosa_health_state{subsystem} +
        # pilosa_watchdog_trips_total{subsystem,kind} (process-wide
        # registry, bounded to the registered loops).
        reg.register_collector(obs.health.families)
        # Measured-profile histograms (process-wide: every profiled
        # query records into obs.profile.STATS regardless of handler).
        reg.register_collector(obs.profile.STATS.families)
        # Cost observatory: per-(tenant, shape) cumulative counters
        # (fleet-mergeable) + pilosa_perf_regression gauges.
        reg.register_collector(obs.costs.families)

    def _collect_slo(self) -> list:
        if self.slo is None:
            return []
        return self.slo.families()

    def _collect_spmd(self) -> list:
        """Descriptor-plane + locality-tier telemetry: per-op dispatch
        counts and wall time, rank-gate vetoes by reason, bytes moved
        per tier, and the flight recorder's ring accounting."""
        from ..parallel import spmd as spmd_mod

        prom = obs.prom
        fams: list = []
        tb = obs.metrics.TIER_BYTES.copy()
        tier = prom.MetricFamily(
            "pilosa_tier_bytes_total", "counter",
            "Bytes moved across locality tiers: ici = descriptor-plane "
            "broadcasts over the device fabric, http = node-to-node "
            "request+response bodies.")
        for t in ("ici", "http"):
            tier.add(tb.get(t, 0), {"tier": t})
        fams.append(tier)
        stats = spmd_mod.SPMD_STATS.copy()
        disp = prom.MetricFamily(
            "pilosa_spmd_dispatch_total", "counter",
            "SPMD descriptors executed by this rank, by op.")
        veto = prom.MetricFamily(
            "pilosa_spmd_gate_veto_total", "counter",
            "Collective launches vetoed by the program-agreement gate: "
            "not_ready = a rank had no compiled program, "
            "format_disagreement = ranks resolved different programs "
            "or staged formats.")
        for k, v in sorted(stats.items()):
            kind, _, rest = k.partition(":")
            if kind == "dispatch":
                disp.add(v, {"op": rest})
            elif kind == "veto":
                veto.add(v, {"reason": rest})
        if disp.samples:
            fams.append(disp)
        if veto.samples:
            fams.append(veto)
        hists = spmd_mod.op_hist_snapshot()
        if hists:
            lat = prom.MetricFamily(
                "pilosa_spmd_dispatch_us", "histogram",
                "SPMD descriptor wall time by op (resolve + gate + "
                "collective; log2 buckets, µs).")
            for op, h in sorted(hists.items()):
                lat.add_histogram(h, {"op": op})
            fams.append(lat)
        fr = getattr(self.executor, "flight", None)
        if fr is not None:
            st = fr.stats()
            fams.append(prom.MetricFamily(
                "pilosa_queryshape_tracked", "gauge",
                "Query shapes currently held by the flight recorder "
                "ring.").add(st["shapes"]))
            fams.append(prom.MetricFamily(
                "pilosa_queryshape_ring", "gauge",
                "Flight recorder ring capacity ([obs] "
                "queryshape-ring).").add(st["ring"]))
            fams.append(prom.MetricFamily(
                "pilosa_queryshape_evicted_total", "counter",
                "Query shapes evicted from the flight recorder ring "
                "(LRU).").add(st["evicted"]))
        return fams

    def _collect_read_path(self) -> list:
        """Follower-read + result-cache telemetry (ISSUE 18): which
        replica class served each slice pick, what the epoch-keyed
        result cache did, and how many entries it holds."""
        prom = obs.prom
        fams: list = []
        picks = getattr(self.executor, "read_stats", None)
        if picks is not None:
            snap = picks.copy()
            if snap:
                fam = prom.MetricFamily(
                    "pilosa_read_replica_total", "counter",
                    "Read-path slice placements by replica class "
                    "(owner = the strict ring pick, follower = spread "
                    "to an in-sync replica, fallback_owner = a "
                    "bounded read with no eligible follower) and "
                    "staleness class (strict = X-Pilosa-Staleness "
                    "absent/0, bounded = a positive budget).")
                for k, v in sorted(snap.items()):
                    pick, _, sclass = k.partition("|")
                    fam.add(v, {"replica": pick,
                                "staleness": sclass or "strict"})
                fams.append(fam)
        placed = getattr(self.executor, "placement_stats", None)
        if placed is not None:
            fams.append(prom.MetricFamily(
                "pilosa_route_owner_decisions_total", "counter",
                "Owner-ladder decisions the executor's slice routing "
                "made: one per partition and placement ring of a "
                "strict read, one per slice of a bounded-staleness "
                "spread. Over pilosa_read_replica_total: decisions "
                "per slice placed.").add(
                    placed.get("owner_decisions", 0)))
        rc = getattr(self.executor, "result_cache", None)
        if rc is not None:
            events = rc.stats.copy()
            if events:
                fam = prom.MetricFamily(
                    "pilosa_result_cache_events_total", "counter",
                    "Epoch-keyed result cache events: hit / miss / "
                    "invalidate (an entry keyed to a superseded "
                    "epoch) / evict (LRU) / bypass (strict or "
                    "uncacheable query).")
                for k, v in sorted(events.items()):
                    fam.add(v, {"event": k})
                fams.append(fam)
            fams.append(prom.MetricFamily(
                "pilosa_result_cache_entries", "gauge",
                "Entries currently held by the epoch-keyed result "
                "cache.").add(len(rc)))
        return fams

    def _get_debug_slo(self, pv, params, headers, body):
        """SLO observatory snapshot: per-window SLIs, burn rates, and
        error budgets — the same numbers the pilosa_slo_* families
        export, as one JSON document."""
        if self.slo is None:
            return _json_resp({"error": "slo accounting disabled"}, 404)
        return _json_resp(self.slo.status())

    # -- /debug/fleet + /debug/queryshapes -----------------------------------

    def _fleet(self):
        """Lazily-built FleetAggregator; None without a cluster."""
        if self.cluster is None:
            return None
        with self._fleet_mu:
            if self._fleet_agg is None:
                self._fleet_agg = obs.fleet.FleetAggregator(
                    members=self.cluster.node_states,
                    fetch=self._fleet_fetch,
                    interval=self.fleet_scrape_interval,
                    deadline=self.fleet_scrape_deadline,
                    breaker_state=self._fleet_breaker_state)
            return self._fleet_agg

    def _fleet_breaker_state(self, host: str) -> str:
        breakers = getattr(getattr(self.executor, "client", None),
                           "breakers", None)
        state = getattr(breakers, "state", None)
        if callable(state):
            try:
                return state(host)
            except Exception:  # noqa: BLE001 — unknown peer: no skip
                return ""
        return ""

    def _fleet_fetch(self, host: str, path: str,
                     timeout_s: float) -> str:
        """Fleet scrape transport: the local node answers through its
        own handler (no self-scrape over HTTP — always fresh, never
        breaker-gated); peers go through the internal client, which
        brings retries, deadlines, and breaker accounting."""
        if host == self.host or self.client_factory is None:
            resp = self.handle("GET", path)
            if resp.status != 200:
                raise RuntimeError(
                    f"local {path}: status={resp.status}")
            return resp.body.decode()
        client = self._fleet_clients.get(host)
        if client is None:
            client = self._fleet_clients[host] = self.client_factory(
                host)
        status, data = client._do(
            "GET", path, deadline=time.monotonic() + timeout_s)
        if status != 200:
            raise RuntimeError(f"{host}{path}: status={status}")
        return data.decode()

    def _get_debug_fleet(self, pv, params, headers, body):
        """Federated fleet pane: every ring member's /metrics +
        /debug/vars scraped (bounded concurrency, per-node deadline,
        breaker-aware, stale-tolerant) and the cumulative families
        merged exactly. ?force=true bypasses the snapshot cache."""
        agg = self._fleet()
        if agg is None:
            return _json_resp(
                {"error": "fleet view requires a cluster"}, 404)
        return _json_resp(
            agg.snapshot(force=params.get("force") == "true"))

    def _get_debug_queryshapes(self, pv, params, headers, body):
        """Query-shape flight recorder: per plan-signature traffic,
        latency, route/tier mix, staged bytes, and shadow-check
        outcomes. ?sort=cost|p99|routed_host|count, ?limit=N."""
        fr = getattr(self.executor, "flight", None)
        if fr is None:
            return _json_resp(
                {"error": "flight recorder unavailable"}, 404)
        return _json_resp(fr.snapshot(
            sort=params.get("sort", "cost"),
            limit=int(params.get("limit", "50"))))

    def _get_debug_costs(self, pv, params, headers, body):
        """Cost observatory: top-K (tenant, shape) accounts across
        every metered dimension plus the baseline watch's regression
        bands. ?sort=device_us|hbm|staged|wal|net|queries|regression,
        ?limit=N."""
        ledger = obs.costs.LEDGER
        doc = ledger.snapshot(
            sort=params.get("sort", "device_us"),
            limit=int(params.get("limit", "50")),
            watch=obs.costs.WATCH)
        doc["enabled"] = ledger.enabled
        doc["regression"] = {
            "active": [{"shape": s, "dimension": d}
                       for s, d in obs.costs.WATCH.active()],
            "bands": obs.costs.WATCH.snapshot(
                limit=int(params.get("limit", "50"))),
        }
        doc["debt_threshold"] = self.cost_debt_threshold
        return _json_resp(doc)

    # -- liveness plane (/healthz, /readyz, /debug/health, /debug/bundle) ----

    def _get_healthz(self, pv, params, headers, body):
        """k8s-style liveness: 200 while the watchdog itself is
        beating. A STALLED subsystem does NOT flip this — a node that
        can still diagnose itself must not be restarted out from under
        its own dossier; that is /readyz's job."""
        h = obs.health.HEALTH
        if h.watchdog_alive():
            return _json_resp({"status": "ok",
                               "watchdog": "alive" if h.enabled
                               and h._thread is not None else "off"})
        return _json_resp({"status": "unhealthy",
                           "watchdog": "dead"}, 503)

    def _get_readyz(self, pv, params, headers, body):
        """k8s-style readiness: serving-state ∧ no STALLED critical
        subsystem. A mesh that lost its device plane stays ready — the
        executor host-folds (degraded-mode-capable) — so readiness
        only drops when traffic would actually be harmed. 503 carries
        the reasons so an operator can go straight to the dossier."""
        reasons = []
        if self.ready_fn is not None:
            try:
                if not self.ready_fn():
                    reasons.append("not-serving")
            except Exception:  # noqa: BLE001 — a broken probe reads
                reasons.append("not-serving")  # as not serving
        h = obs.health.HEALTH
        for name in h.stalled_critical():
            reasons.append(f"stalled:{name}")
        if not h.watchdog_alive():
            reasons.append("watchdog-dead")
        if reasons:
            return _json_resp({"status": "unready",
                               "reasons": reasons}, 503)
        return _json_resp({"status": "ok"})

    def _get_debug_health(self, pv, params, headers, body):
        """The full health table: every registered heartbeat's state,
        age, and owning thread; in-flight ops with deadlines; trip
        counters; gossiped peer rollups."""
        return _json_resp(obs.health.HEALTH.snapshot())

    def _get_debug_bundle(self, pv, params, headers, body):
        """The diagnostic dossier, on demand — identical to what a
        watchdog trip writes under <data-dir>/.dossier/ and what
        `pilosa-tpu diagnose` fetches. ?write=true also persists it."""
        h = obs.health.HEALTH
        doc = h.build_bundle(reason="on-demand")
        if params.get("write") == "true":
            try:
                doc["written_to"] = h.write_dossier(doc=doc)
            except OSError as e:
                doc["written_to"] = None
                doc["write_error"] = str(e)
        return Response(200, {"Content-Type": "application/json"},
                        h.encode_bundle(doc) + b"\n")

    def _collect_runtime(self) -> list:
        prom = obs.prom
        info = prom.MetricFamily("pilosa_build_info", "gauge",
                                 "Build metadata; the value is always 1.")
        info.add(1, {"version": self.version})
        up = prom.MetricFamily("pilosa_uptime_seconds", "gauge",
                               "Seconds since this handler started.")
        up.add(time.monotonic() - self._start_time)
        return [info, up]

    def _collect_device(self) -> list:
        """Mesh serving-layer telemetry: raw StatMap gauges, per-entry
        compile counters, dispatch-mode counters, and the per-device
        HBM residency report. Absent stores (device off, fake
        executors) contribute nothing."""
        prom = obs.prom
        fams: list = []
        ex = self.executor
        mesh = getattr(ex, "device_stats", None)
        if mesh is not None:
            stats = dict(mesh.copy())
            fams.extend(prom.statmap_families(stats, "pilosa_mesh_"))
            disp = prom.MetricFamily(
                "pilosa_dispatch_total", "counter",
                "Device dispatches by serving mode.")
            for mode, key in (("fused", "lone_fused"),
                              ("batched", "batched"),
                              ("coarse", "coarse"),
                              ("shared_batch", "shared_batch"),
                              ("fallback", "fallback"),
                              ("routed_host", "routed_host")):
                disp.add(stats.get(key, 0), {"mode": mode})
            fams.append(disp)
            ev = prom.MetricFamily(
                "pilosa_hbm_evictions_total", "counter",
                "Staged views evicted, by trigger: budget = LRU "
                "pressure against [mesh] hbm-budget-bytes, oom = "
                "emergency eviction after device RESOURCE_EXHAUSTED.")
            ev.add(stats.get("evicted_budget", 0), {"reason": "budget"})
            ev.add(stats.get("evicted_oom", 0), {"reason": "oom"})
            fams.append(ev)
            fb = prom.MetricFamily(
                "pilosa_device_fallback_total", "counter",
                "Queries degraded to the host fold, by reason "
                "(unstaged = view missing/unstageable, oom = device "
                "memory exhausted after eviction, hbm_infeasible = one "
                "view overflows the budget, quarantined = plan "
                "signature serving a failure quarantine, compile = the "
                "compiler refused the program, error = any other "
                "exception on the device path).")
            fb.add(stats.get("fallback", 0), {"reason": "unstaged"})
            for reason in ("oom", "hbm_infeasible", "quarantined",
                           "compile", "error"):
                fb.add(stats.get(f"fallback_{reason}", 0),
                       {"reason": reason})
            fams.append(fb)
            refused = prom.MetricFamily(
                "pilosa_container_patch_refused_total", "counter",
                "Containers that writes created and the staged view "
                "was restaged for, not patched, by reason (no_slot = "
                "the slice's capacity is used up, new_row = the row "
                "is not in the view's row table, format = a sparse or "
                "mixed-format view).")
            for reason in ("no_slot", "new_row", "format"):
                refused.add(stats.get(f"container_patch_refused_{reason}",
                                      0), {"reason": reason})
            fams.append(refused)
            applied = prom.MetricFamily(
                "pilosa_apply_writes_total", "counter",
                "Refreshes that scattered writes into a staged pool, "
                "by mode (in_place = no reader held the pool, so its "
                "buffer was donated to the scatter; copied = a reader "
                "was pinned, so the scatter started from a copy).")
            for mode in ("in_place", "copied"):
                applied.add(stats.get(f"apply_{mode}", 0), {"mode": mode})
            fams.append(applied)
            fams.append(prom.MetricFamily(
                "pilosa_plan_quarantined_total", "counter",
                "Plan signatures quarantined off the device path "
                "after repeated failures.")
                .add(stats.get("plan_quarantined", 0)))
            fams.append(prom.MetricFamily(
                "pilosa_dispatch_gen_moved_total", "counter",
                "Launches aborted because another dispatch advanced a "
                "participating view's generation first (retried via "
                "the coalescing path, not a failure).")
                .add(stats.get("dispatch_gen_moved", 0)))
        mgr = getattr(ex, "_mesh_mgr", None)
        cs = getattr(mgr, "compile_stats", None)
        if cs is not None:
            stats = dict(cs.copy())
            counts = prom.MetricFamily(
                "pilosa_compile_total", "counter",
                "Device program compiles by entry point.")
            secs = prom.MetricFamily(
                "pilosa_compile_seconds_total", "counter",
                "Cumulative compile wall time by entry point.")
            for k, v in sorted(stats.items()):
                if k.endswith("_count"):
                    counts.add(v, {"entry": k[:-6]})
                elif k.endswith("_us"):
                    secs.add(v / 1e6, {"entry": k[:-3]})
            fams += [counts, secs]
        if mgr is not None:
            try:
                dm = mgr.device_memory()
            except Exception:  # noqa: BLE001 — telemetry never fails scrape
                dm = None
            if dm is not None:
                res = prom.MetricFamily(
                    "pilosa_hbm_resident_bytes", "gauge",
                    "Staged fragment-pool bytes resident per device.")
                for dev, n in sorted(dm["per_device"].items()):
                    res.add(n, {"device": dev})
                fams.append(res)
                fams.append(prom.MetricFamily(
                    "pilosa_hbm_padded_bytes", "gauge",
                    "Total staged pool bytes including padding slots.")
                    .add(dm["padded_bytes"]))
                fams.append(prom.MetricFamily(
                    "pilosa_hbm_live_bytes", "gauge",
                    "Staged bytes backing live containers only.")
                    .add(dm["live_bytes"]))
                fams.append(prom.MetricFamily(
                    "pilosa_hbm_staged_views", "gauge",
                    "Fragment views currently staged on-device.")
                    .add(dm["views"]))
                fams.append(prom.MetricFamily(
                    "pilosa_hbm_sparse_bytes", "gauge",
                    "Staged pool bytes held as sorted-array (sparse) "
                    "containers.")
                    .add(dm["sparse_bytes"]))
                rr = prom.MetricFamily(
                    "pilosa_hbm_residency_ratio", "gauge",
                    "Live container bytes over padded pool bytes — "
                    "how much of the staged HBM footprint backs real "
                    "data. Unlabeled series is the aggregate; one "
                    "labeled series per device. 1.0 when nothing is "
                    "staged.")
                rr.add(dm["residency_ratio"])
                for dev, r in sorted(
                        dm["residency_per_device"].items()):
                    rr.add(r, {"device": dev})
                fams.append(rr)
            try:
                budget = mgr._hbm_budget_bytes()
            except Exception:  # noqa: BLE001 — telemetry never fails scrape
                budget = 0
            fams.append(prom.MetricFamily(
                "pilosa_hbm_budget_bytes", "gauge",
                "Resolved staged-pool HBM byte budget ([mesh] "
                "hbm-budget-bytes / env / device memory_stats minus "
                "headroom); 0 = unlimited.")
                .add(max(0, budget)))
        return fams

    def _collect_caches(self) -> list:
        """Plan-cache LRU events, host-path cache counters, and the
        backend-labeled query latency histograms + route counters."""
        prom = obs.prom
        fams: list = []
        ex = self.executor
        hc = getattr(ex, "host_cache_stats", None)
        if hc is not None:
            fams.extend(prom.statmap_families(dict(hc),
                                              "pilosa_host_cache_"))
        plans = getattr(getattr(ex, "_mesh_mgr", None), "_fused_plans",
                        None)
        if plans is not None:
            stats = dict(plans.stats)
            ev = prom.MetricFamily(
                "pilosa_plan_cache_total", "counter",
                "Compiled-plan LRU events.")
            for event in ("hit", "miss", "evicted"):
                ev.add(stats.get(event, 0), {"event": event})
            fams.append(ev)
            fams.append(prom.MetricFamily(
                "pilosa_plan_cache_compile_seconds_total", "counter",
                "Wall time spent compiling fused plans.")
                .add(stats.get("compile_us", 0) / 1e6))
        rs = getattr(ex, "route_stats", None)
        if rs is not None:
            routes = prom.MetricFamily(
                "pilosa_query_route_total", "counter",
                "Count queries by serving backend and locality tier "
                "(local = this chip, ici = pod interconnect collective, "
                "http = cross-node ring).")
            ts = getattr(ex, "tier_stats", None)
            tiers = dict(ts.copy()) if ts is not None else {}
            by_route: dict = {}
            for k, v in tiers.items():
                route, _, tier = k.partition("|")
                by_route.setdefault(route, {})[tier or "local"] = v
            for k, v in sorted(dict(rs.copy()).items()):
                if not k.startswith("count_"):
                    continue
                backend = k[len("count_"):]
                # Every _record_route call site threads a real tier, so
                # the tier split is authoritative — no single-chip
                # fallback guessing.
                for tier, tv in sorted(by_route.get(backend,
                                                    {}).items()):
                    routes.add(tv, {"backend": backend, "tier": tier})
            fams.append(routes)
        hists = getattr(ex, "route_latency_hists", None)
        if hists:
            lat = prom.MetricFamily(
                "pilosa_query_route_duration_microseconds", "histogram",
                "Count latency by serving backend (log2 buckets, µs).")
            for route, h in sorted(hists.items()):
                lat.add_histogram(h, {"backend": route})
            fams.append(lat)
        return fams

    def _collect_cluster(self) -> list:
        """Cluster transport counters and per-peer breaker state
        (0=closed, 1=half-open, 2=open — alertable as a number, the
        state string rides along as a label)."""
        prom = obs.prom
        fams: list = []
        cc = getattr(self.executor, "client", None)
        cstats = getattr(cc, "stats", None)
        if cstats is not None and hasattr(cstats, "copy"):
            fams.extend(prom.statmap_families(dict(cstats.copy()),
                                              "pilosa_cluster_"))
        snap = getattr(getattr(cc, "breakers", None), "snapshot", None)
        if callable(snap):
            order = {"closed": 0, "half-open": 1, "half_open": 1,
                     "open": 2}
            f = prom.MetricFamily(
                "pilosa_breaker_state", "gauge",
                "Circuit breaker per peer: 0=closed, 1=half-open, "
                "2=open.")
            for host, state in sorted(snap().items()):
                f.add(order.get(state, -1),
                      {"host": host, "state": state})
            fams.append(f)
        return fams

    def _collect_membership(self) -> list:
        """Elastic-cluster telemetry: per-node membership state (as a
        number so dashboards can alert on it: 0=DOWN, 1=JOINING,
        2=LEAVING, 3=UP), migration gauges from the rebalancer, and
        the handoff-ledger depth. Empty without a cluster."""
        if self.cluster is None:
            return []
        prom = obs.prom
        order = {"DOWN": 0, "JOINING": 1, "LEAVING": 2, "UP": 3}
        f = prom.MetricFamily(
            "pilosa_member_state", "gauge",
            "Membership state per node: 0=DOWN, 1=JOINING, 2=LEAVING, "
            "3=UP/ACTIVE.")
        for host, state in sorted(self.cluster.node_states().items()):
            f.add(order.get(state, -1), {"host": host, "state": state})
        fams = [f]
        rz = self.resizer
        if rz is not None:
            snap = rz.snapshot()
            mig = prom.MetricFamily(
                "pilosa_migrations_in_flight", "gauge",
                "Fragment transfers currently streaming.")
            mig.add(snap["in_flight"])
            byt = prom.MetricFamily(
                "pilosa_migration_bytes_total", "counter",
                "Total fragment bytes shipped by the rebalancer.")
            byt.add(snap["bytes_total"])
            outcome = prom.MetricFamily(
                "pilosa_migrations_total", "counter",
                "Completed fragment transfers by outcome.")
            outcome.add(snap["completed"], {"outcome": "verified"})
            outcome.add(snap["failed"], {"outcome": "failed"})
            outcome.add(snap["checksum_mismatches"],
                        {"outcome": "checksum_retry"})
            hand = prom.MetricFamily(
                "pilosa_handoff_slices", "gauge",
                "Slices cut over to the target ring in the pending "
                "resize (0 when not resizing).")
            hand.add(snap["handoff_slices"])
            fams.extend([mig, byt, outcome, hand])
        return fams

    def _collect_sched(self) -> list:
        """Scheduler telemetry: queue depth by tenant (plus an 'all'
        total), shed/admitted/expired counters, queue-wait and
        cohort-size histograms. Empty when no scheduler is wired."""
        s = self.scheduler
        if s is None:
            return []
        prom = obs.prom
        depth = prom.MetricFamily(
            "pilosa_sched_queue_depth", "gauge",
            "Admitted queries waiting for dispatch, by tenant "
            "('all' = total).")
        for tenant, n in sorted(s.queue_depths().items()):
            depth.add(n, {"tenant": tenant})
        st = s.stats.copy()
        shed = prom.MetricFamily(
            "pilosa_sched_shed_total", "counter",
            "Requests shed at admission (HTTP 429), by reason.")
        shed.add(st.get("shed_deadline", 0), {"reason": "deadline"})
        shed.add(st.get("shed_queue_full", 0), {"reason": "queue_full"})
        adm = prom.MetricFamily(
            "pilosa_sched_admitted_total", "counter",
            "Admitted queries by path (fastpath = idle, no queuing).")
        adm.add(st.get("fastpath", 0), {"path": "fastpath"})
        adm.add(st.get("queued", 0), {"path": "queued"})
        exp = prom.MetricFamily(
            "pilosa_sched_expired_total", "counter",
            "Queries whose deadline expired while queued (HTTP 504).")
        exp.add(st.get("expired_in_queue", 0))
        wait = prom.MetricFamily(
            "pilosa_sched_wait_microseconds", "histogram",
            "Queue wait from admission to dispatch (log2 buckets, µs).")
        wait.add_histogram(s.wait_hist)
        batch = prom.MetricFamily(
            "pilosa_sched_batch_size", "histogram",
            "Released cohort sizes (>1 = coalesced arrivals).")
        batch.add_histogram(s.batch_hist)
        return [depth, shed, adm, exp, wait, batch]

    def _collect_fragments(self) -> list:
        """Sampled fragment gauges, cached for metrics_sample_interval
        seconds: scrapers poll, and even a cheap walk is O(fragments)."""
        now = time.monotonic()
        with self._frag_sample_mu:
            stamp, fams = self._frag_sample
            if fams and now - stamp < self.metrics_sample_interval:
                return fams
        fams = self._sample_fragments()
        with self._frag_sample_mu:
            self._frag_sample = (now, fams)
        return fams

    def _sample_fragments(self) -> list:
        """Per-frame row-cache entries, bitmap cardinality, and
        fragment counts. Lazily-pending fragments are counted but
        never parsed — a scrape must not force a many-GB demand-load —
        so cardinality covers loaded fragments only."""
        prom = obs.prom
        rc = prom.MetricFamily(
            "pilosa_fragment_row_cache_entries", "gauge",
            "Materialized-row LRU entries per frame (sampled).")
        card = prom.MetricFamily(
            "pilosa_fragment_cardinality", "gauge",
            "Bits set per frame, loaded fragments only (sampled).")
        nf = prom.MetricFamily(
            "pilosa_fragments", "gauge",
            "Fragments per frame by load state (sampled).")
        # Copy-on-write dicts throughout core: lock-free iteration is
        # the documented reader protocol.
        for iname, idx in sorted(self.holder.indexes.items()):
            for fname, frame in sorted(idx.frames.items()):
                labels = {"index": iname, "frame": fname}
                rows = bits = loaded = pending = 0
                for view in frame.views.values():
                    for frag in view.fragments.values():
                        with frag._mu:
                            if frag._pending_load:
                                pending += 1
                                continue
                            loaded += 1
                            rows += len(frag._row_cache)
                            bits += frag.storage.count()
                rc.add(rows, labels)
                card.add(bits, labels)
                nf.add(loaded, dict(labels, state="loaded"))
                nf.add(pending, dict(labels, state="pending"))
        return [rc, card, nf]

    def _collect_storage(self) -> list:
        """WAL durability telemetry (process-wide, core/wal.py): fsync
        and backpressure counters, group-commit batch sizes, background
        snapshot wall times."""
        prom = obs.prom
        from ..core.wal import GROUP_SIZE, SNAPSHOT_US, WAL_STATS

        fsync = prom.MetricFamily(
            "pilosa_wal_fsync_total", "counter",
            "WAL group-commit fsyncs across all fragments.")
        fsync.add(WAL_STATS.get("fsync", 0))
        bp = prom.MetricFamily(
            "pilosa_wal_backpressure_total", "counter",
            "Writers gated (state=gated) or shed with 503 (state=shed) "
            "by the [storage] max-wal-ops bound.")
        bp.add(WAL_STATS.get("backpressure", 0), {"state": "gated"})
        bp.add(WAL_STATS.get("backpressure_shed", 0), {"state": "shed"})
        snaps = prom.MetricFamily(
            "pilosa_storage_snapshots_total", "counter",
            "Background fragment snapshots by outcome.")
        snaps.add(WAL_STATS.get("snapshots", 0), {"outcome": "ok"})
        snaps.add(WAL_STATS.get("snapshots_failed", 0),
                  {"outcome": "error"})
        group = prom.MetricFamily(
            "pilosa_wal_group_size", "histogram",
            "Ops coalesced per WAL commit (group-commit batch size).")
        group.add_histogram(GROUP_SIZE)
        swall = prom.MetricFamily(
            "pilosa_storage_snapshot_us", "histogram",
            "Background snapshot wall time (microseconds).")
        swall.add_histogram(SNAPSHOT_US)
        torn = prom.MetricFamily(
            "pilosa_wal_torn_tails_total", "counter",
            "Torn final WAL records truncated at load (crash "
            "mid-append recoveries — expected after power loss; "
            "a climbing rate without crashes means flaky storage).")
        torn.add(WAL_STATS.get("torn_tails", 0))
        return [fsync, bp, snaps, group, swall, torn]

    def _collect_integrity(self) -> list:
        """Data-integrity telemetry: corrupt-load / read-repair
        counters (core/fragment.INTEGRITY_STATS), scrubber progress
        (core/scrub.SCRUB_STATS + last-scrub age), and shadow
        verification checks/mismatches by backend
        (executor.SHADOW_STATS)."""
        prom = obs.prom
        from ..core.fragment import INTEGRITY_STATS
        from ..core.scrub import SCRUB_STATS
        from ..executor import SHADOW_STATS

        corrupt = prom.MetricFamily(
            "pilosa_integrity_corrupt_total", "counter",
            "Fragment loads that failed integrity verification "
            "(footer CRC / container FNV / op-log checksum).")
        corrupt.add(INTEGRITY_STATS.get("corrupt", 0))
        repaired = prom.MetricFamily(
            "pilosa_integrity_repaired_total", "counter",
            "Corrupt fragments restored from a verified replica copy "
            "(outcome=repaired) vs left pending with no donor "
            "(outcome=unrepaired).")
        repaired.add(INTEGRITY_STATS.get("repaired", 0),
                     {"outcome": "repaired"})
        repaired.add(INTEGRITY_STATS.get("unrepaired", 0),
                     {"outcome": "unrepaired"})
        sfrag = prom.MetricFamily(
            "pilosa_scrub_fragments_total", "counter",
            "Fragments verified by the background scrubber.")
        sfrag.add(SCRUB_STATS.get("fragments", 0))
        srep = prom.MetricFamily(
            "pilosa_scrub_repairs_total", "counter",
            "Scrubber-initiated repairs (snapshot rewrite, replica "
            "read-repair, or anti-entropy merge).")
        srep.add(SCRUB_STATS.get("repairs", 0))
        fams = [corrupt, repaired, sfrag, srep]
        if self.scrubber is not None:
            age = prom.MetricFamily(
                "pilosa_scrub_last_age_seconds", "gauge",
                "Seconds since the least-recently-scrubbed fragment "
                "was verified (0 until the first pass).")
            age.add(self.scrubber.oldest_scrub_age())
            fams.append(age)
        shadow_c = prom.MetricFamily(
            "pilosa_shadow_checks_total", "counter",
            "Sampled device results recomputed through the host "
            "roaring fold.")
        shadow_m = prom.MetricFamily(
            "pilosa_shadow_mismatch_total", "counter",
            "Shadow recomputations whose host answer DIFFERED from "
            "the device answer. Any nonzero value is a sev: the "
            "offending plan signature is quarantined.")
        backends = sorted({k.split(":", 1)[1]
                           for k in SHADOW_STATS.copy()
                           if ":" in k}) or ["mesh"]
        for b in backends:
            shadow_c.add(SHADOW_STATS.get(f"checks:{b}", 0),
                         {"backend": b})
            shadow_m.add(SHADOW_STATS.get(f"mismatch:{b}", 0),
                         {"backend": b})
        fams += [shadow_c, shadow_m]
        return fams

    def _collect_hints(self) -> list:
        """Hinted-handoff telemetry (parallel/hints.HINT_STATS +
        per-target backlog): queued/replayed/dropped lifetime counters
        labeled by target, current backlog bytes, and the write-
        consistency outcome counters (executor.CONSISTENCY_STATS). The
        operator invariant: replicas are convergent once
        queued_total == replayed_total (+ dropped handled by
        anti-entropy) with zero backlog bytes."""
        prom = obs.prom
        from ..executor import CONSISTENCY_STATS
        from ..parallel.hints import HINT_STATS

        stats = HINT_STATS.copy()
        targets = sorted({k.split(":", 1)[1] for k in stats
                          if k.startswith(("queued:", "replayed:",
                                           "dropped:"))})
        queued = prom.MetricFamily(
            "pilosa_hints_queued_total", "counter",
            "Missed replica writes durably journaled as hints.")
        replayed = prom.MetricFamily(
            "pilosa_hints_replayed_total", "counter",
            "Hints replayed and acked by their target.")
        dropped = prom.MetricFamily(
            "pilosa_hints_dropped_total", "counter",
            "Hints spilled oldest-first past hint-max-bytes or lost to "
            "a torn log tail (anti-entropy heals these).")
        for t in targets:
            queued.add(stats.get(f"queued:{t}", 0), {"target": t})
            replayed.add(stats.get(f"replayed:{t}", 0), {"target": t})
            dropped.add(stats.get(f"dropped:{t}", 0), {"target": t})
        fams = [queued, replayed, dropped]
        if self.hints is not None:
            hb = prom.MetricFamily(
                "pilosa_hint_bytes", "gauge",
                "Current hint-log backlog bytes per target.")
            for t, nbytes in sorted(
                    self.hints.backlog_bytes_by_target().items()):
                hb.add(nbytes, {"target": t})
            fams.append(hb)
        wc = prom.MetricFamily(
            "pilosa_write_consistency_total", "counter",
            "Replicated-write outcomes by consistency level: ok "
            "(all replicas acked), hinted (level reached, misses "
            "journaled), below_consistency (503 after dispatch), "
            "rejected_unavailable (503 before local apply).")
        for key, n in sorted(CONSISTENCY_STATS.copy().items()):
            level, _, outcome = key.partition(":")
            if outcome:
                wc.add(n, {"level": level, "outcome": outcome})
        fams.append(wc)
        return fams

    def _get_expvar(self, pv, params, headers, body) -> Response:
        # Read together and first: over a window, d(cpu) / d(uptime) is
        # the cores' worth of CPU this process burned. About 1.0 under
        # load says the handler threads share one GIL's worth of
        # Python; well under it says they were blocked (locks, sleeps).
        cpu_s, up_s = time.process_time(), time.monotonic()
        snap = self.stats.snapshot() if hasattr(self.stats, "snapshot") else {}
        snap["uptime_seconds"] = round(up_s - self._start_time, 3)
        snap["process_cpu_seconds"] = round(cpu_s, 3)
        snap["version"] = self.version
        # Mesh serving-layer counters (stage/incremental/count/topn/
        # fallback + cumulative timings) — SURVEY.md §5 observability.
        mesh = getattr(self.executor, "device_stats", None)
        if mesh:
            mesh_snap = dict(mesh)
            # HBM governor state: resolved budget, residency report,
            # and the quarantine roster — the runbook's first stop when
            # pilosa_device_fallback_total moves.
            mgr = getattr(self.executor, "_mesh_mgr", None)
            if mgr is not None:
                try:
                    mesh_snap["hbm"] = {
                        "budget_bytes": max(0, mgr._hbm_budget_bytes()),
                        **mgr.device_memory(),
                    }
                    mesh_snap["quarantined_plans"] = \
                        mgr.quarantined_plans()
                except Exception:  # noqa: BLE001 — debug never 500s
                    pass
            snap = dict(snap, mesh=mesh_snap)
        # Count-backend calibration: the measured Pallas-vs-XLA record
        # behind the "auto" dispatch (None until first resolution). The
        # acceptance trail for "the calibrator picked the faster
        # backend" lives HERE, not in a log line.
        try:
            from ..ops.calibrate import calibration_snapshot
            cal = calibration_snapshot()
            if cal is not None:
                snap = dict(snap, count_calibration=cal)
        except Exception:  # noqa: BLE001 — debug never 500s
            pass
        # The JAX runtime behind the device path: versions, platform,
        # device kind and count, compile-cache directory, compile
        # totals, device memory. Only once the device path has come up
        # (asking earlier would initialise a backend nobody asked for).
        if "mesh" in snap or "count_calibration" in snap:
            from .. import jaxrt

            rt = jaxrt.snapshot()
            if rt is not None:
                snap = dict(snap, jax_runtime=rt)
        hc = getattr(self.executor, "host_cache_stats", None)
        if hc:
            snap = dict(snap, host_cache=dict(hc))
        # Cluster transport health: retry/transport-error/breaker
        # counters plus each peer's current breaker state, via the
        # executor's injected ClusterClient (absent under test fakes).
        cc = getattr(self.executor, "client", None)
        cstats = getattr(cc, "stats", None)
        cluster = {}
        if cstats is not None and hasattr(cstats, "copy"):
            cluster = dict(cstats.copy())
            breakers = getattr(cc, "breakers", None)
            if breakers is not None:
                cluster["breakers"] = breakers.snapshot()
        # Elastic membership: per-node states, the handoff ledger
        # depth, and the rebalancer's live migration snapshot.
        if self.cluster is not None:
            cluster["members"] = self.cluster.node_states()
            cluster["resizing"] = self.cluster.resizing()
            cluster["handoff_slices"] = self.cluster.handoff_count()
        if self.resizer is not None:
            cluster["rebalance"] = self.resizer.snapshot()
        if cluster:
            snap = dict(snap, cluster=cluster)
        # Scheduler state: queue depths, shed/admit counters, wait and
        # cohort-size percentiles (sched.QueryScheduler.snapshot).
        if self.scheduler is not None:
            snap = dict(snap, sched=self.scheduler.snapshot())
        # Per-fragment durability/snapshot state (guarded: test fakes
        # stand in for the holder without storage_state).
        ss = getattr(self.holder, "storage_state", None)
        if ss is not None:
            snap = dict(snap, storage=ss())
        # Data-integrity state: corrupt/repair counters, shadow
        # verification tallies, and the scrubber's pass snapshot.
        from ..core.fragment import INTEGRITY_STATS
        from ..executor import SHADOW_STATS

        integrity = dict(INTEGRITY_STATS.copy())
        shadow = SHADOW_STATS.copy()
        if shadow:
            integrity["shadow"] = dict(shadow)
        if self.scrubber is not None:
            integrity["scrub"] = self.scrubber.snapshot()
        if integrity:
            snap = dict(snap, integrity=integrity)
        # Hinted-handoff queue state: per-target backlog (records,
        # bytes, lifetime counters) — the operator's first stop when
        # pilosa_hint_bytes grows (README runbook).
        if self.hints is not None:
            snap = dict(snap, hints=self.hints.snapshot())
        # Read-path resilience state: what the epoch tracker knows
        # about each peer's write progress, and the result cache's
        # size + hit/miss/invalidation tallies.
        tracker = getattr(self.executor, "epochs", None)
        if tracker is not None:
            try:
                snap = dict(snap, epochs=tracker.snapshot())
            except Exception:  # noqa: BLE001 — debug never 500s
                pass
        rc = getattr(self.executor, "result_cache", None)
        if rc is not None:
            try:
                snap = dict(snap, result_cache=rc.snapshot())
            except Exception:  # noqa: BLE001 — debug never 500s
                pass
        return _json_resp(snap)

    def _get_debug_queries(self, pv, params, headers, body) -> Response:
        """Recent + slow query trace rings (newest first). The slow
        ring uses the tracer's configured threshold; pass
        ?threshold_us=N to re-filter the recent ring ad hoc without
        touching server config."""
        snap = self.tracer.snapshot()
        if "threshold_us" in params:
            thr = float(params["threshold_us"])
            snap["slow"] = [t for t in snap["recent"]
                            if t["duration_us"] >= thr]
            snap["slow_threshold_us"] = thr
        return _json_resp(snap)

    def _get_debug_trace(self, pv, params, headers, body) -> Response:
        """One trace in full: every span with parent links, relative
        start, duration, and tags. 404 once evicted from both rings."""
        tr = self.tracer.get(pv["tid"])
        if tr is None:
            return _json_resp({"error": "trace not found"}, 404)
        return _json_resp(tr.to_dict())

    def _get_cpu_profile(self, pv, params, headers, body) -> Response:
        """Sampling CPU profile across ALL threads — the analog of the
        reference's /debug/pprof/profile (net/http/pprof). Samples
        sys._current_frames() at ~100 Hz for ?seconds=N (default 2,
        max 30) and returns collapsed stacks ("frame;frame;frame N"),
        ready for flamegraph.pl / speedscope. A sampler beats cProfile
        here: cProfile instruments only its own thread, while queries
        run on executor pool threads."""
        from collections import Counter

        seconds = min(float(params.get("seconds", "2") or 2), 30.0)
        stacks: Counter = Counter()
        for _t, _name, parts in self._sample_stacks(seconds):
            stacks[";".join(parts)] += 1
        out = "".join(f"{stack} {n}\n" for stack, n in stacks.most_common())
        return Response(200, {"Content-Type": "text/plain; charset=utf-8"},
                        out.encode())

    # Frames that mean "this thread is waiting on synchronization, not
    # running": the sampling block/mutex profiles classify a sample as
    # waiting when any of its two innermost PYTHON frames matches (a
    # raw C-level Lock.acquire leaves no Python frame of its own, but
    # every composite wait — Condition.wait, Event.wait, queue.get,
    # Thread.join, selectors — runs these stdlib frames).
    _WAIT_FRAMES = frozenset((
        "threading.py:wait", "threading.py:acquire", "threading.py:join",
        "threading.py:_wait_for_tstate_lock", "queue.py:get",
        "queue.py:put", "selectors.py:select", "socket.py:accept",
        "socketserver.py:serve_forever"))
    # The mutex restriction matches only DIRECT lock waits by their
    # innermost Python frame (pure-Python RLock.acquire, Thread.join's
    # tstate lock) — a Condition/Event/queue wait also passes through
    # threading.py:wait, but classifying an idle queue consumer as
    # lock contention would misdiagnose healthy blocking as a lock
    # bottleneck, so composite waits belong to /block only. (A raw
    # C-level Lock.acquire leaves no Python frame at all and is
    # invisible to any Python sampler — documented limitation.)
    _MUTEX_FRAMES = frozenset((
        "threading.py:acquire", "threading.py:_wait_for_tstate_lock"))

    def _sample_stacks(self, seconds: float, interval: float = 0.01):
        """~1/interval Hz samples of every OTHER thread's stack:
        (t_offset_s, thread_name, [frame, ...] outermost-first).
        The shared engine under profile/block/mutex/trace."""
        import sys
        import time as _time

        me = threading.get_ident()
        samples = []
        t0 = _time.monotonic()
        deadline = t0 + seconds
        while _time.monotonic() < deadline:
            names = {t.ident: t.name for t in threading.enumerate()}
            now = _time.monotonic() - t0
            for tid, frame in list(sys._current_frames().items()):
                if tid == me:
                    continue
                parts = []
                f = frame
                while f is not None:
                    code = f.f_code
                    parts.append(f"{code.co_filename.rsplit('/', 1)[-1]}:"
                                 f"{code.co_name}")
                    f = f.f_back
                parts.reverse()
                samples.append((now, names.get(tid, str(tid)), parts))
            _time.sleep(interval)
        return samples

    def _get_block_profile(self, pv, params, headers, body) -> Response:
        """Blocking profile — the reference serves Go's block/mutex
        profiles here (net/http/pprof); the Python-runtime analog is a
        sampling wait profile: stacks whose INNERMOST frame is a
        synchronization wait (lock acquire, queue get, join, poll),
        collapsed + counted over ?seconds=N. /debug/pprof/mutex serves
        the same data restricted to lock acquires."""
        seconds = min(float(params.get("seconds", "2") or 2), 30.0)
        mutex_only = pv.get("kind") == "mutex"
        from collections import Counter

        waits: Counter = Counter()
        total = 0
        for _t, _name, parts in self._sample_stacks(seconds):
            total += 1
            if mutex_only:
                # Direct lock waits only, by INNERMOST frame (see
                # _MUTEX_FRAMES note): composite waits are /block's.
                if parts[-1] not in self._MUTEX_FRAMES:
                    continue
            elif not any(p in self._WAIT_FRAMES for p in parts[-2:]):
                continue
            waits[";".join(parts)] += 1
        out = [f"# sampling {'mutex' if mutex_only else 'block'} "
               f"profile: {seconds}s, {total} thread-samples, "
               f"{sum(waits.values())} in waits\n"]
        out += [f"{stack} {n}\n" for stack, n in waits.most_common()]
        return Response(200, {"Content-Type": "text/plain; charset=utf-8"},
                        "".join(out).encode())

    def _get_trace(self, pv, params, headers, body) -> Response:
        """Execution trace — the reference serves Go's runtime trace;
        the analog here is a wall-clock timeline: per-thread stack
        samples over ?seconds=N as chrome://tracing JSON
        (trace_event format, load in Perfetto), one complete event per
        sample with the innermost frame as the event name."""
        import json as _json

        seconds = min(float(params.get("seconds", "1") or 1), 30.0)
        interval = 0.005
        events = []
        for t, name, parts in self._sample_stacks(seconds, interval):
            events.append({
                "name": parts[-1], "cat": "sample", "ph": "X",
                "ts": int(t * 1e6), "dur": int(interval * 1e6),
                "pid": 1, "tid": name,
                "args": {"stack": ";".join(parts)}})
        return Response(200, {"Content-Type": "application/json"},
                        _json.dumps({"traceEvents": events}).encode())

    def _get_pprof(self, pv, params, headers, body) -> Response:
        """Profile index — the full pprof surface the reference mounts
        at /debug/pprof/ (handler.go:30,99), with Python-runtime
        analogs per profile. The thread dump is appended so a bare
        GET /debug/pprof still answers 'what is every thread doing'."""
        index = (
            "pilosa-tpu /debug/pprof profiles:\n"
            "  profile       sampling CPU profile, all threads "
            "(?seconds=N, collapsed stacks)\n"
            "  heap          tracemalloc top allocation sites + RSS "
            "(?gc=1 collects first)\n"
            "  allocs        alias of heap\n"
            "  block         sampling wait profile (sync waits: locks, "
            "queues, joins; ?seconds=N)\n"
            "  mutex         block, restricted to lock acquires\n"
            "  trace         wall-clock timeline as chrome trace JSON "
            "(?seconds=N; open in Perfetto)\n"
            "  goroutine     per-thread stack dump\n"
            "  threadcreate  live thread table\n"
            "  cmdline       process command line\n\n"
            "other /debug endpoints:\n"
            "  /debug/vars         stats snapshot (counters + query "
            "latency p50/p95/p99; sched = scheduler queue/shed state)\n"
            "  /debug/queries      recent + slow query trace rings "
            "(?threshold_us=N re-filters)\n"
            "  /debug/traces/<id>  one query trace, all spans with "
            "timings and tags\n"
            "  /debug/health       watchdog verdicts: per-subsystem "
            "heartbeats, in-flight ops, peers\n"
            "  /debug/bundle       diagnostic dossier (thread stacks, "
            "health, rings; ?write=true persists)\n"
            "  /healthz /readyz    load-balancer probes (liveness / "
            "readiness; 503 when unready)\n\n"
            "query scheduling (when [sched] enabled):\n"
            "  POST /index/<i>/query reads X-Pilosa-Tenant for fair "
            "queuing; overload answers\n"
            "  429 + Retry-After instead of queuing doomed work; "
            "queue wait counts against the\n"
            "  query deadline (?deadline= / X-Pilosa-Deadline-Us) and "
            "profiles as sched_wait.\n"
            "  /metrics exports pilosa_sched_queue_depth{tenant}, "
            "pilosa_sched_shed_total{reason},\n"
            "  pilosa_sched_wait_microseconds, "
            "pilosa_sched_batch_size.\n\n")
        dump = self._thread_dump_text()
        return Response(200, {"Content-Type": "text/plain; charset=utf-8"},
                        (index + dump).encode())

    @staticmethod
    def _thread_dump_text() -> str:
        import sys
        import traceback

        names = {t.ident: t.name for t in threading.enumerate()}
        out = []
        for tid, frame in sys._current_frames().items():
            out.append(f"--- thread {names.get(tid, '?')} ({tid}) ---")
            out.extend(ln.rstrip()
                       for ln in traceback.format_stack(frame))
        return "\n".join(out) + "\n"

    def _get_thread_dump(self, pv, params, headers, body) -> Response:
        """Per-thread stack dump — the goroutine-profile analog."""
        return Response(200, {"Content-Type": "text/plain; charset=utf-8"},
                        self._thread_dump_text().encode())

    def _get_threadcreate(self, pv, params, headers, body) -> Response:
        """Live thread table (name, ident, daemon, alive)."""
        rows = [f"{t.ident}\t{t.name}\tdaemon={t.daemon}\talive={t.is_alive()}"
                for t in threading.enumerate()]
        return Response(200, {"Content-Type": "text/plain; charset=utf-8"},
                        ("\n".join(rows) + "\n").encode())

    def _get_cmdline(self, pv, params, headers, body) -> Response:
        import sys

        return Response(200, {"Content-Type": "text/plain; charset=utf-8"},
                        "\x00".join(sys.argv).encode())

    def _get_heap_profile(self, pv, params, headers, body) -> Response:
        """Heap profile — tracemalloc top allocation sites plus process
        RSS/VM from /proc (the reference serves Go's runtime heap
        profile here; tracemalloc is the Python runtime's equivalent).
        tracemalloc has real per-allocation overhead, so it is NEVER
        enabled implicitly: a bare GET reports process memory and how
        to opt in; ?start=1 begins tracing, ?stop=1 reports and then
        stops (Go's sampling profiler is always-on and cheap — Python's
        is not, hence the explicit switch). ?gc=1 collects first,
        mirroring Go's ?gc=1.

        ?start additionally requires PILOSA_TPU_HEAP_TRACE=1 in the
        environment (ADVICE r4): the debug mux is unauthenticated, and
        process-wide allocation tracing is an operator decision, not
        something any client on the debug port may switch on. The
        start/stop transitions run under a lock so two crossed
        requests can't stop a trace the other thinks it owns."""
        import gc
        import tracemalloc

        # "?start=0" (or =false/=no, any case) must mean OFF: query
        # params and env values arrive as strings, and a bare
        # truthiness test would read "0" as on. One spelling list for
        # both the query flags and the env gate, so they can't drift.
        falsy = ("", "0", "false", "no")

        def flag(name: str) -> bool:
            return params.get(name, "").lower() not in falsy

        out = []
        with self._tracemalloc_mu:
            if flag("start") and not tracemalloc.is_tracing():
                if os.environ.get("PILOSA_TPU_HEAP_TRACE",
                                  "").lower() in falsy:
                    out.append("# ?start=1 refused: set "
                               "PILOSA_TPU_HEAP_TRACE=1 to allow this "
                               "endpoint to enable tracemalloc\n")
                else:
                    tracemalloc.start()
                    # Only a trace WE started may be stopped by
                    # ?stop=1 — an interpreter-level PYTHONTRACEMALLOC
                    # trace belongs to the operator, not this endpoint.
                    self._tracemalloc_ours = True
            if flag("gc"):
                gc.collect()
            try:
                with open("/proc/self/status") as f:
                    for ln in f:
                        if ln.startswith(("VmRSS", "VmHWM", "VmSize")):
                            out.append("# " + ln.strip() + "\n")
            except OSError:
                pass
            if tracemalloc.is_tracing():
                current, peak = tracemalloc.get_traced_memory()
                out.append(f"# tracemalloc current={current} "
                           f"peak={peak}\n\n")
                snap = tracemalloc.take_snapshot()
                for stat in snap.statistics("lineno")[:64]:
                    out.append(f"{stat.size}\t{stat.count}\t"
                               f"{stat.traceback}\n")
                if flag("stop") and self._tracemalloc_ours:
                    tracemalloc.stop()
                    self._tracemalloc_ours = False
                    out.append("# tracemalloc stopped\n")
            else:
                out.append("# tracemalloc off — ?start=1 to begin "
                           "tracing allocation sites (requires "
                           "PILOSA_TPU_HEAP_TRACE=1 in the server "
                           "env), then re-request (?stop=1 to report "
                           "and stop)\n")
        return Response(200, {"Content-Type": "text/plain; charset=utf-8"},
                        "".join(out).encode())

    def _get_hosts(self, pv, params, headers, body) -> Response:
        nodes = self.cluster.nodes if self.cluster else []
        return _json_resp([n.to_dict() for n in nodes])

    def _post_cluster_resize(self, pv, params, headers, body) -> Response:
        """Admin + control endpoint for elastic membership.

        Actions (JSON body {"action": ..., ...}):
          join     {host}          node enters the ring as JOINING
          leave    {host}          ACTIVE node becomes LEAVING
          cutover  {index, slice}  slice now serves from the target ring
          complete {}              promote JOINING, drop LEAVING
          status   {}              read-only snapshot

        `?remote=true` marks a coordinator's control fan-out: apply
        locally, never re-forward (loop guard), never start a second
        migration. The admin call (no remote flag) lands on ONE node —
        that node forwards the membership change to every peer and
        becomes the migration coordinator.
        """
        if self.cluster is None:
            return _json_resp({"error": "no cluster"}, 501)
        msg = json.loads(body.decode() or "{}")
        action = str(msg.get("action", params.get("action", "")))
        remote = params.get("remote") == "true"
        c = self.cluster
        try:
            if action == "join":
                c.begin_join(str(msg["host"]))
            elif action == "leave":
                c.begin_leave(str(msg["host"]))
            elif action == "cutover":
                c.mark_handed_off(str(msg["index"]), int(msg["slice"]))
            elif action == "complete":
                c.complete_resize()
            elif action != "status":
                return _json_resp(
                    {"error": f"unknown action: {action!r} (want join, "
                     "leave, cutover, complete, or status)"}, 400)
        except KeyError as e:
            return _json_resp({"error": f"missing field: {e}"}, 400)
        except ValueError as e:
            return _json_resp({"error": str(e)}, 400)
        if not remote and action in ("join", "leave"):
            # Coordinator path: replicate the membership change, then
            # kick the migration engine. Forward failures are logged,
            # not fatal — an unreachable peer re-learns membership from
            # the status poll, and data convergence rides anti-entropy.
            if self.client_factory is not None:
                for node in list(c.nodes):
                    if node.host == self.host:
                        continue
                    try:
                        self.client_factory(node.host).cluster_resize(
                            action, **{k: v for k, v in msg.items()
                                       if k != "action"})
                    except Exception as e:  # noqa: BLE001 — best-effort
                        if self.logger is not None:
                            self.logger.warning(
                                f"resize forward to {node.host}: {e}")
            if self.resizer is not None:
                self.resizer.trigger()
        out = {"action": action or "status",
               "node_states": c.node_states(),
               "resizing": c.resizing(),
               "handoff_slices": c.handoff_count()}
        if self.resizer is not None:
            out["rebalance"] = self.resizer.snapshot()
        return _json_resp(out)

    def _get_status(self, pv, params, headers, body) -> Response:
        """Cluster status: this node's status plus last-known peer states."""
        if self.status_handler is None:
            return _json_resp({"error": "status not supported"}, 501)
        status = self.status_handler.cluster_status()
        if self._accepts_proto(headers):
            return _proto_resp(status)
        return _json_resp(_cluster_status_to_dict(status))

    # -- schema --------------------------------------------------------------

    def _get_schema(self, pv, params, headers, body) -> Response:
        return _json_resp({"indexes": self.holder.schema()})

    def _get_indexes(self, pv, params, headers, body) -> Response:
        return self._get_schema(pv, params, headers, body)

    def _get_slice_max(self, pv, params, headers, body) -> Response:
        if params.get("inverse") == "true":
            maxes = self.holder.max_inverse_slices()
        else:
            maxes = self.holder.max_slices()
        if self._accepts_proto(headers):
            msg = pb.MaxSlicesResponse()
            for k, v in maxes.items():
                msg.max_slices[k] = v
            return _proto_resp(msg)
        return _json_resp({"maxSlices": maxes})

    def _get_index(self, pv, params, headers, body) -> Response:
        idx = self.holder.index(pv["index"])
        if idx is None:
            raise IndexNotFoundError()
        return _json_resp({"index": idx.to_dict()})

    def _spmd_guard_schema(self, what: str):
        """Schema mutations on a non-zero SPMD rank would apply to the
        local holder only (workers carry a NopBroadcaster), silently
        diverging the replicated data dirs from the descriptor-ordered
        stream — the same hazard the import/write guards close. Rank 0
        is fine: its SpmdBroadcaster rides the change down the
        descriptor stream to every rank."""
        if self.spmd_worker:
            return _json_resp(
                {"error": f"{what} must be sent to SPMD rank 0"}, 400)
        return None

    def _post_index(self, pv, params, headers, body) -> Response:
        guard = self._spmd_guard_schema("index create")
        if guard is not None:
            return guard
        opts = _decode_options(body, {"columnLabel": "column_label",
                                      "timeQuantum": "time_quantum"})
        idx = self.holder.create_index(pv["index"], **opts)
        if self.broadcaster is not None:
            self.broadcaster.send_sync(pb.CreateIndexMessage(
                index=idx.name, meta=pb.IndexMeta(
                    column_label=idx.column_label,
                    time_quantum=str(idx.time_quantum))))
        return _json_resp({})

    def _delete_index(self, pv, params, headers, body) -> Response:
        guard = self._spmd_guard_schema("index delete")
        if guard is not None:
            return guard
        self.holder.delete_index(pv["index"])
        if hasattr(self.executor, "invalidate_device_index"):
            self.executor.invalidate_device_index(pv["index"])
        if self.broadcaster is not None:
            self.broadcaster.send_sync(
                pb.DeleteIndexMessage(index=pv["index"]))
        return _json_resp({})

    def _patch_index_time_quantum(self, pv, params, headers, body) -> Response:
        guard = self._spmd_guard_schema("index time-quantum patch")
        if guard is not None:
            return guard
        q = json.loads(body.decode() or "{}").get("timeQuantum", "")
        idx = self.holder.index(pv["index"])
        if idx is None:
            raise IndexNotFoundError()
        idx.set_time_quantum(parse_time_quantum(q))
        return _json_resp({})

    def _post_frame(self, pv, params, headers, body) -> Response:
        guard = self._spmd_guard_schema("frame create")
        if guard is not None:
            return guard
        opts = _decode_options(body, {
            "rowLabel": "row_label", "inverseEnabled": "inverse_enabled",
            "cacheType": "cache_type", "cacheSize": "cache_size",
            "timeQuantum": "time_quantum", "fields": "fields"})
        idx = self.holder.index(pv["index"])
        if idx is None:
            raise IndexNotFoundError()
        f = idx.create_frame(pv["frame"], **opts)
        if self.broadcaster is not None:
            self.broadcaster.send_sync(pb.CreateFrameMessage(
                index=idx.name, frame=f.name, meta=pb.FrameMeta(
                    row_label=f.row_label,
                    inverse_enabled=f.inverse_enabled,
                    cache_type=f.cache_type, cache_size=f.cache_size,
                    time_quantum=str(f.time_quantum),
                    fields_json=json.dumps(
                        [s.to_dict()
                         for _, s in sorted(f.fields.items())])
                    if f.fields else "")))
        return _json_resp({})

    def _delete_frame(self, pv, params, headers, body) -> Response:
        guard = self._spmd_guard_schema("frame delete")
        if guard is not None:
            return guard
        idx = self.holder.index(pv["index"])
        if idx is None:
            raise IndexNotFoundError()
        idx.delete_frame(pv["frame"])
        if hasattr(self.executor, "invalidate_device_index"):
            self.executor.invalidate_device_index(pv["index"])
        if self.broadcaster is not None:
            self.broadcaster.send_sync(pb.DeleteFrameMessage(
                index=pv["index"], frame=pv["frame"]))
        return _json_resp({})

    def _patch_frame_time_quantum(self, pv, params, headers, body) -> Response:
        guard = self._spmd_guard_schema("frame time-quantum patch")
        if guard is not None:
            return guard
        q = json.loads(body.decode() or "{}").get("timeQuantum", "")
        f = self.holder.frame(pv["index"], pv["frame"])
        if f is None:
            raise FrameNotFoundError()
        f.set_time_quantum(parse_time_quantum(q))
        return _json_resp({})

    def _get_frame_views(self, pv, params, headers, body) -> Response:
        f = self.holder.frame(pv["index"], pv["frame"])
        if f is None:
            raise FrameNotFoundError()
        return _json_resp({"views": sorted(f.views.keys())})

    # -- query ---------------------------------------------------------------

    def _post_query(self, pv, params, headers, body) -> Response:
        """Outcome-accounting wrapper around the real query path
        (_post_query_inner). Every coordinator-side query outcome —
        success, partial, shed 429, deadline 504, backpressure 503,
        client error, server error — is recorded here EXACTLY ONCE
        into the SLO recorder's pilosa_query_outcome_total family, so
        the availability SLI has a single source of truth instead of
        stitching scheduler stats together with route histograms.
        Remote fan-out legs and ?explain=true are skipped: one logical
        query counts once, at its coordinator, and explain dispatches
        no work worth judging."""
        if self.slo is None:
            return self._post_query_inner(pv, params, headers, body, {})
        info: dict = {}
        t0 = time.monotonic()
        try:
            resp = self._post_query_inner(pv, params, headers, body,
                                          info)
        except PilosaError as e:
            # handle() will turn this into a response via
            # _error_status; record the same mapping now.
            if not (info.get("remote") or info.get("explain")):
                self.slo.record(
                    obs.slo.outcome_for_status(_error_status(e)),
                    tenant=info.get("tenant", "default"))
            raise
        if info.get("remote") or info.get("explain"):
            return resp
        opt = info.get("opt")
        partial = bool(opt is not None and opt.partial
                       and opt.missing_slices)
        latency_us = None
        if resp.status < 400:
            latency_us = (time.monotonic() - t0) * 1e6
        self.slo.record(obs.slo.outcome_for_status(resp.status, partial),
                        tenant=info.get("tenant", "default"),
                        latency_us=latency_us,
                        trace_id=info.get("trace_id"))
        if resp.status < 400:
            debt = self._cost_debt(info.get("tenant", "default"))
            if debt is not None:
                resp.headers["X-Pilosa-Cost-Debt"] = debt
        return resp

    def _cost_debt(self, tenant: str):
        """Observe-only cost-debt stamp: when the tenant's measured
        device_us share (the scheduler's admission estimator consults
        the same number) exceeds [obs] cost-debt-threshold, query
        responses carry X-Pilosa-Cost-Debt: <share>. No throttling —
        the header is the tenant-side signal that its traffic is
        dominating the device."""
        thr = self.cost_debt_threshold
        if thr is None or thr <= 0 or not obs.costs.LEDGER.enabled:
            return None
        label = (self.slo.tenant_label(tenant)
                 if self.slo is not None else tenant)
        share = None
        if self.scheduler is not None:
            share = self.scheduler.tenant_cost_share(label)
        if share is None:
            share = obs.costs.LEDGER.tenant_share(label)
        if share > thr:
            return f"{share:.3f}"
        return None

    def _post_query_inner(self, pv, params, headers, body,
                          info: dict) -> Response:
        index = pv["index"]
        # Read request: protobuf QueryRequest or raw PQL + URL params
        # (reference readQueryRequest, handler.go:811-871).
        if self._sends_proto(headers):
            req = pb.QueryRequest()
            req.ParseFromString(body)
            query, slices = req.query, list(req.slices)
            column_attrs, remote = req.column_attrs, req.remote
        else:
            query = body.decode()
            slices = [int(s) for s in params.get("slices", "").split(",")
                      if s != ""]
            column_attrs = params.get("columnAttrs") == "true"
            remote = False
        tenant = headers.get("x-pilosa-tenant", "") or "default"
        info["remote"] = bool(remote)
        info["tenant"] = tenant
        fault.point("handler.query", host=self.host, index=index,
                    remote=bool(remote))
        opt = self._exec_options(params, headers, remote)
        info["opt"] = opt

        # ?explain=true: return the PLANNED execution — routing with
        # cost-model inputs, breaker-aware placement, cache peeks,
        # staging estimate — without dispatching any device work.
        if params.get("explain") == "true" and not remote:
            info["explain"] = True
            return self._explain_query(index, query, slices, headers, opt)

        # Measured profile (the EXPLAIN ANALYZE counterpart): explicit
        # ?profile=true, a coordinator's X-Pilosa-Profile request
        # header on a remote leg, or the sampled 1-in-N cadence. The
        # profile activates via contextvar exactly like the tracer;
        # with none of the three, profiling code below never allocates.
        # Activated BEFORE admission so a profiled query's queue wait
        # shows up as the sched_wait phase.
        want_profile = params.get("profile") == "true" and not remote
        remote_profile = bool(remote
                              and headers.get("x-pilosa-profile"))
        sampled = (self.profile_sample_rate > 0 and not remote
                   and next(self._profile_seq)
                   % self.profile_sample_rate == 0)
        # Cost-attribution context (obs/costs.py): binds the bounded
        # tenant label for everything this request charges — route
        # taps, WAL bytes, tier bytes, staged-view residency. The
        # sampled path carries the sample rate as its extrapolation
        # weight so ledger device_us stays an unbiased estimate.
        cost_ctx = cost_token = None
        if obs.costs.LEDGER.enabled:
            clabel = (self.slo.tenant_label(tenant)
                      if self.slo is not None else tenant)
            cost_ctx, cost_token = obs.costs.activate(
                clabel, float(self.profile_sample_rate) if sampled
                else 1.0)
        prof = ptoken = None
        if want_profile or remote_profile or sampled:
            prof = obs.profile.QueryProfile()
            if self.slo is not None and not remote:
                # Tenant dimension only on the sampled/profiled path,
                # bounded by the SLO recorder's tenant-label map —
                # pilosa_query_phase_us cardinality stays
                # |tenant-weights| + "other", not one series per
                # arbitrary header value.
                prof.tenant = self.slo.tenant_label(tenant)
            ptoken = obs.profile.activate(prof)
        ticket = None
        trace = None
        try:
            # Admission gate (sched.QueryScheduler, when wired):
            # deadline-aware shedding answers 429 + Retry-After before
            # any work queues; a deadline expiring while queued is an
            # immediate 504; tenants queue fairly by X-Pilosa-Tenant.
            # Remote fan-out legs bypass it — the coordinator already
            # paid admission for the whole query, and gating each leg
            # again would double-queue one logical request.
            if self.scheduler is not None and not remote:
                try:
                    with obs.profile.phase("sched_wait"):
                        ticket = self.scheduler.submit(
                            tenant=tenant, deadline=opt.deadline)
                except AdmissionError as e:
                    self.stats.count("query.shed", 1)
                    return self._shed_response(e, headers)
                except DeadlineExceededError as e:
                    return self._query_error(e, headers)

            # Trace lifecycle: every query records a trace into the
            # bounded rings behind /debug/queries. A remote fan-out leg
            # joins the coordinator's trace id (X-Pilosa-Trace) and
            # ships its spans back in the X-Pilosa-Trace-Spans response
            # header, where InternalClient grafts them under the
            # fan-out span.
            th = headers.get("x-pilosa-trace", "") if remote else ""
            trace = self.tracer.start(
                "query", trace_id=th.partition(":")[0] or None,
                index=index, query=query[:256], remote=bool(remote),
                node=self.host)
            info["trace_id"] = trace.trace_id
            try:
                with trace.root:
                    resp = self._run_query(index, query, slices,
                                           column_attrs, remote, headers,
                                           opt,
                                           profile_section=want_profile)
            finally:
                self.tracer.finish(trace)
        finally:
            if ticket is not None:
                self.scheduler.done(ticket)
            if prof is not None:
                obs.profile.deactivate(ptoken)
                prof.finish()
                obs.profile.STATS.record(prof)
            if cost_ctx is not None:
                obs.costs.deactivate(cost_token)
                if prof is not None:
                    # Execution-engine microseconds from the measured
                    # profile — device_exec plus the host_fold
                    # fallback (a host-routed query burns the same
                    # serving budget), extrapolated by the sampling
                    # weight. The executor stamped the shape during
                    # _record_route.
                    obs.costs.LEDGER.record_device_us(
                        prof.phase_us("device_exec")
                        + prof.phase_us("host_fold"),
                        weight=cost_ctx.weight,
                        tenant=cost_ctx.tenant,
                        shape=cost_ctx.shape)
        if th:
            resp.headers["X-Pilosa-Trace-Spans"] = json.dumps(
                trace.serialize_spans(), separators=(",", ":"))
        if remote_profile:
            # Ship the leg's measured section back; the coordinator's
            # client grafts it under its own profile (merge_remote).
            resp.headers["X-Pilosa-Profile"] = json.dumps(
                prof.to_dict(), separators=(",", ":"))
        return resp

    def _explain_query(self, index, query, slices, headers,
                       opt) -> Response:
        """EXPLAIN surface (executor.explain): parses the PQL, plans
        every call, executes nothing."""
        explain = getattr(self.executor, "explain", None)
        if not callable(explain):
            return _json_resp(
                {"error": "explain unsupported by this executor"}, 400)
        try:
            with obs.span("parse", bytes=len(query)):
                q = parse_string_cached(query)
            plan = explain(index, q, slices or None, opt)
        except (PilosaError, ParseError) as e:
            return self._query_error(e, headers)
        plan["query"] = query[:1024]
        ledger = obs.costs.LEDGER
        if ledger.enabled and getattr(q, "calls", None):
            # Cost block: what the ledger already knows about this
            # tenant × shape — accumulated spend, the tenant's
            # device_us share, and whether the baseline watch has the
            # shape flagged. Planned-cost context, zero dispatch.
            tenant = headers.get("x-pilosa-tenant", "") or "default"
            label = (self.slo.tenant_label(tenant)
                     if self.slo is not None else tenant)
            shape = self.executor._shape_sig(q.calls[0])
            acct = ledger.snapshot(limit=ledger.max_accounts)
            row = next((a for a in acct["accounts"]
                        if a["tenant"] == label and a["shape"] == shape),
                       None)
            plan["cost"] = {
                "tenant": label,
                "shape": shape,
                "tenant_device_us_share":
                    round(ledger.tenant_share(label), 4),
                "account": {k: v for k, v in (row or {}).items()
                            if k not in ("tenant", "shape")},
                "regressed": [
                    d for s, d in obs.costs.WATCH.active() if s == shape],
            }
        return _json_resp(plan)

    def _exec_options(self, params, headers, remote) -> ExecOptions:
        """Per-query ExecOptions from the request: deadline from the
        X-Pilosa-Deadline-Us header (remaining budget in µs, set by an
        upstream coordinator hop) or the ?deadline= param (Go duration,
        e.g. "50ms"), falling back to the configured default for
        coordinator-side queries; ?partial=true opts into graceful
        degradation (missing slices reported, not fatal); read
        staleness from X-Pilosa-Staleness / ?staleness= (bare number =
        milliseconds, or a Go duration like "500ms"), falling back to
        [cluster] default-read-staleness — 0 keeps strict owner-only
        reads. Remote legs never re-apply a staleness spread: the
        coordinator already picked their replica."""
        deadline = None
        hdr = headers.get("x-pilosa-deadline-us", "")
        if hdr:
            deadline = time.monotonic() + int(hdr) / 1e6
        elif params.get("deadline"):
            from ..config import parse_duration

            deadline = time.monotonic() + parse_duration(params["deadline"])
        elif not remote and self.default_deadline > 0:
            deadline = time.monotonic() + self.default_deadline
        staleness = 0.0
        if not remote:
            raw = (headers.get("x-pilosa-staleness", "")
                   or params.get("staleness", ""))
            if raw:
                staleness = _parse_staleness(raw)
            else:
                staleness = self.default_read_staleness
        return ExecOptions(remote=remote, deadline=deadline,
                           partial=params.get("partial") == "true",
                           staleness=staleness)

    def _run_query(self, index, query, slices, column_attrs, remote,
                   headers, opt=None, profile_section=False) -> Response:
        if opt is None:
            opt = ExecOptions(remote=remote)
        try:
            # Parsed-query LRU (pql.parse_string_cached): repeat PQL
            # texts skip the ~100 us parse, which dominates a
            # memo-served Count. The shared Query is immutable by
            # convention (see the cache's docstring).
            with obs.span("parse", bytes=len(query)), \
                    obs.profile.phase("parse"):
                q = parse_string_cached(query)
            t0 = time.monotonic()
            results = self.executor.execute(index, q, slices or None, opt)
            # respond: from the executor's return to the profile's
            # snapshot below — stats tagging and the JSON of the results.
            rph = obs.profile.phase("respond").start()
            # Per-call-name query stats, visible at /debug/vars
            # (observability parity: reference tag-scoped StatsClient,
            # stats.go:33-54). Remote fan-out legs are skipped so a
            # clustered query counts once, at its coordinator. The
            # untagged timing keeps a stable `query.us.p50/p95/p99`
            # key in /debug/vars regardless of index names.
            if not remote:
                dt_us = int((time.monotonic() - t0) * 1e6)
                tagged = self.stats.with_tags(f"index:{index}")
                for call in q.calls:
                    tagged.count(f"query.{call.name}", 1)
                tagged.timing("query", dt_us)
                self.stats.timing("query", dt_us)
        except PilosaError as e:
            return self._query_error(e, headers)
        except ParseError as e:
            return self._query_error(e, headers)

        col_sets = []
        if column_attrs:
            col_sets = self._column_attr_sets(index, results)

        if self._accepts_proto(headers):
            resp = pb.QueryResponse()
            resp.results.extend(result_to_proto(r) for r in results)
            for cid, attrs in col_sets:
                cs = resp.column_attr_sets.add()
                cs.id = cid
                cs.attrs.extend(attrs_to_proto(attrs))
            rph.stop()
            return _proto_resp(resp)

        out = {"results": [_result_to_json(r) for r in results]}
        if column_attrs:
            out["columnAttrs"] = [{"id": cid, "attrs": attrs}
                                  for cid, attrs in col_sets]
        if opt.partial:
            # ?partial=true responses always say whether degradation
            # happened, so clients don't have to infer it from absence.
            out["partial"] = bool(opt.missing_slices)
            out["missing_slices"] = sorted(set(opt.missing_slices))
        rph.stop()
        if profile_section:
            prof = obs.profile.current()
            if prof is not None:
                # Snapshotted BEFORE serialization: total_us is
                # execution wall time, and the phases must sum to
                # >= 90% of it (the acceptance bar) without charging
                # the profile for rendering its own report.
                out["profile"] = prof.to_dict()
        return _json_resp(out)

    def _query_error(self, e, headers) -> Response:
        if isinstance(e, (WriteBackpressureError, WriteConsistencyError)):
            # Write shed (WAL bound exceeded / too few replica acks):
            # 503 + Retry-After, the write-path sibling of
            # _shed_response — transient, so the cluster client's retry
            # classification backs off and retries instead of failing
            # the import. Never a 500: a below-consistency write either
            # rejected pre-apply or journaled its misses as hints.
            retry = max(1, int(round(e.retry_after_s)))
            if self._accepts_proto(headers):
                resp = _proto_resp(pb.QueryResponse(err=str(e)), 503)
            else:
                resp = _json_resp({"error": str(e),
                                   "retry_after_s": retry}, 503)
            resp.headers["Retry-After"] = str(retry)
            return resp
        if isinstance(e, DeadlineExceededError):
            status = 504
        elif isinstance(e, (FieldValueError, FieldNotFoundError)):
            # BSI field errors keep their schema-aware statuses (422 /
            # 404) through the query surface — a SetValue outside the
            # declared range is not a malformed request.
            status = _error_status(e)
        else:
            status = 400
        if self._accepts_proto(headers):
            return _proto_resp(pb.QueryResponse(err=str(e)), status)
        return _json_resp({"error": str(e)}, status)

    def _shed_response(self, e: AdmissionError, headers) -> Response:
        """Admission shed: HTTP 429 with a Retry-After header (whole
        seconds, >= 1 — 'do not retry sooner than this') so well-behaved
        clients back off instead of hammering an overloaded node into
        504 deadline blowouts."""
        retry = max(1, int(round(e.retry_after_s)))
        if self._accepts_proto(headers):
            resp = _proto_resp(pb.QueryResponse(err=str(e)), 429)
        else:
            resp = _json_resp({"error": str(e), "reason": e.reason,
                               "retry_after_s": retry}, 429)
        resp.headers["Retry-After"] = str(retry)
        return resp

    def _column_attr_sets(self, index: str, results) -> List[Tuple[int, dict]]:
        """Attrs for every column appearing in row results
        (handler.go handlePostQuery columnAttrSets)."""
        idx = self.holder.index(index)
        if idx is None:
            return []
        seen = set()
        out = []
        for r in results:
            if not isinstance(r, Row):
                continue
            for col in r.columns():
                col = int(col)
                if col in seen:
                    continue
                seen.add(col)
                attrs = idx.column_attr_store.attrs(col)
                if attrs:
                    out.append((col, attrs))
        out.sort()
        return out

    # -- import / export -----------------------------------------------------

    def _post_import(self, pv, params, headers, body) -> Response:
        """Outcome-accounting wrapper mirroring _post_query's: the
        import write path is where WAL backpressure (503) surfaces, so
        its outcomes land in the same pilosa_query_outcome_total
        family under route="import"."""
        tenant = headers.get("x-pilosa-tenant", "") or "default"
        # Imports meter into the ledger too — the WAL-byte and
        # replication-byte taps below us charge the ambient account,
        # keyed (tenant, "import") since imports have no plan shape.
        cost_token = None
        if obs.costs.LEDGER.enabled:
            clabel = (self.slo.tenant_label(tenant)
                      if self.slo is not None else tenant)
            ctx, cost_token = obs.costs.activate(clabel)
            ctx.shape = "import"
            obs.costs.LEDGER.charge("queries", 1)
        try:
            if self.slo is None:
                return self._post_import_inner(pv, params, headers, body)
            try:
                resp = self._post_import_inner(pv, params, headers, body)
            except PilosaError as e:
                self.slo.record(
                    obs.slo.outcome_for_status(_error_status(e)),
                    tenant=tenant, route="import")
                raise
            # No latency_us: the latency SLI means "query p99 under the
            # declared threshold"; batch imports must not dilute it.
            self.slo.record(obs.slo.outcome_for_status(resp.status),
                            tenant=tenant, route="import")
            return resp
        finally:
            if cost_token is not None:
                obs.costs.deactivate(cost_token)

    def _post_import_inner(self, pv, params, headers, body) -> Response:
        req = pb.ImportRequest()
        req.ParseFromString(body)
        # Validate ownership of the slice (handler.go:931).
        if self.cluster is not None and self.host:
            if not self.cluster.owns_fragment(self.host, req.index, req.slice):
                return _json_resp(
                    {"error": f"host does not own slice {req.slice}"}, 412)
        idx = self.holder.index(req.index)
        if idx is None:
            raise IndexNotFoundError()
        f = idx.frame(req.frame)
        if f is None:
            raise FrameNotFoundError()
        timestamps = None
        if len(req.timestamps):
            timestamps = [
                datetime.fromtimestamp(t, timezone.utc).replace(tzinfo=None)
                if t else None
                for t in req.timestamps]
        if self.spmd_worker:
            return _json_resp(
                {"error": "imports must be sent to SPMD rank 0"}, 400)
        # ?remote=true marks an already-coordinated leg (a replica copy
        # of a quorum import, or a hint replay): apply locally only.
        remote = str(params.get("remote", "")).lower() == "true"
        coord = None
        if (not remote and self.spmd is None and self.hints is not None
                and self.cluster is not None
                and self.client_factory is not None):
            coord = self._import_precheck(req)  # may raise 503 pre-apply
        if self.spmd is not None:
            # Replicate through the descriptor stream (chunked) so every
            # rank's holder receives the bits in query order.
            self.spmd.import_bits(req.index, req.frame,
                                  list(req.row_ids), list(req.column_ids),
                                  timestamps)
        else:
            f.import_bits(list(req.row_ids), list(req.column_ids),
                          timestamps)
        if coord is not None:
            self._import_replicate(req, coord)
        if self._accepts_proto(headers):
            return _proto_resp(pb.ImportResponse())
        return _json_resp({})

    def _import_precheck(self, req):
        """Quorum import, phase 1 (BEFORE local apply): split the other
        replica owners into live vs known-down and reject with 503 when
        the consistency level is unreachable — no acked-but-ambiguous
        state, and no timeout paid to a node the failure detector
        already marked DOWN. Returns (live, down, required, level), or
        None when this host is the slice's only owner."""
        from ..executor import CONSISTENCY_STATS, required_acks
        from ..parallel.cluster import NODE_STATE_DOWN

        owners = self.cluster.fragment_nodes(req.index, req.slice)
        others = [n for n in owners if n.host != self.host]
        if not others:
            return None
        level = self.write_consistency
        required = required_acks(level, len(owners))
        down = [n for n in others if n.state == NODE_STATE_DOWN]
        live = [n for n in others if n.state != NODE_STATE_DOWN]
        if 1 + len(live) < required:
            CONSISTENCY_STATS.inc(f"{level}:rejected_unavailable")
            raise WriteConsistencyError(
                f"import: write-consistency={level} needs {required} of "
                f"{len(owners)} replicas, only {1 + len(live)} reachable",
                level=level, required=required, acked=0)
        return live, down, required, level

    def _import_replicate(self, req, coord) -> None:
        """Quorum import, phase 2 (AFTER local apply): fan the batch
        out to the live replica owners in parallel with ?remote=true,
        journal every miss (down or failed) as an import hint, and
        raise 503 when acks fall below the level — the hints are
        already durable, so an idempotent client retry is safe."""
        from ..executor import CONSISTENCY_STATS

        live, down, required, level = coord
        rows, cols = list(req.row_ids), list(req.column_ids)
        ts = list(req.timestamps) or None

        def send(node):
            self.client_factory(node.host).import_bits(
                req.index, req.frame, req.slice, rows, cols, ts,
                remote=True)

        failures = []
        pool = getattr(self.executor, "_pool", None)
        if pool is not None and len(live) > 1:
            futs = [(n, pool.submit(send, n)) for n in live]
            for n, fut in futs:
                try:
                    fut.result()
                except Exception as e:  # noqa: BLE001 — collected
                    failures.append((n.host, e))
        else:
            for n in live:
                try:
                    send(n)
                except Exception as e:  # noqa: BLE001 — collected
                    failures.append((n.host, e))

        # Post-apply epochs of the imported fragments: fed to the
        # coordinator's tracker immediately (an import is a mutation
        # seam that bypasses the executor write path) and carried on
        # every hint so replay floor-raises the recovered replica.
        epochs = {}
        f = self.holder.frame(req.index, req.frame)
        if f is not None:
            tracker = getattr(self.executor, "epochs", None)
            for vname, view in list(f.views.items()):
                frag = view.fragments.get(req.slice)
                if frag is not None and not frag._pending_load:
                    key = (f"{req.index}/{req.frame}/{vname}"
                           f"/{req.slice}")
                    epochs[key] = frag.epoch
                    if tracker is not None:
                        tracker.observe_local(key, frag.epoch)

        for host in [n.host for n in down] + [h for h, _ in failures]:
            self.hints.enqueue_import(host, req.index, req.frame,
                                      req.slice, rows, cols, ts,
                                      epochs=epochs)
        acked = 1 + len(live) - len(failures)
        if acked >= required:
            CONSISTENCY_STATS.inc(
                f"{level}:hinted" if (down or failures) else f"{level}:ok")
            return
        CONSISTENCY_STATS.inc(f"{level}:below_consistency")
        raise WriteConsistencyError(
            f"import: write-consistency={level}: {acked} of {required} "
            f"required replica acks ({len(failures)} failed mid-import; "
            f"misses journaled as hints)",
            level=level, required=required, acked=acked)

    def _get_export(self, pv, params, headers, body) -> Response:
        index, frame, view, slice_ = self._fragment_args(params)
        frag = self.holder.fragment(index, frame, view, slice_)
        if frag is None:
            raise FragmentNotFoundError()
        buf = io.StringIO()
        for row_id, col_id in frag.for_each_bit():
            buf.write(f"{row_id},{col_id}\n")
        return Response(200, {"Content-Type": "text/csv"},
                        buf.getvalue().encode())

    # -- fragment data plane -------------------------------------------------

    def _get_fragment_nodes(self, pv, params, headers, body) -> Response:
        index = params["index"]
        slice_ = int(params["slice"])
        nodes = (self.cluster.fragment_nodes(index, slice_)
                 if self.cluster else [])
        return _json_resp([n.to_dict() for n in nodes])

    def _get_fragment_data(self, pv, params, headers, body) -> Response:
        index, frame, view, slice_ = self._fragment_args(params)
        frag = self.holder.fragment(index, frame, view, slice_)
        if frag is None:
            raise FragmentNotFoundError()
        buf = io.BytesIO()
        frag.write_to_tar(buf)
        return Response(200, {"Content-Type": "application/octet-stream"},
                        buf.getvalue())

    def _spmd_guard_bulk(self, what: str):
        """Raw-storage mutations (fragment tar restore, frame restore)
        are not descriptor-replicated: applying one to a single rank
        would silently diverge the SPMD replicas, so spmd mode rejects
        them on every rank. Restore into an spmd cluster by restoring
        the data dir on EVERY host before boot, or re-import through
        /import (which replicates)."""
        if self.spmd is not None or self.spmd_worker:
            return _json_resp(
                {"error": f"{what} is not supported under [cluster] "
                          "type=\"spmd\": it would mutate one replica "
                          "only; restore every rank's data dir offline "
                          "or use /import"}, 400)
        return None

    def _post_fragment_data(self, pv, params, headers, body) -> Response:
        guard = self._spmd_guard_bulk("fragment restore")
        if guard is not None:
            return guard
        index, frame, view, slice_ = self._fragment_args(params)
        f = self.holder.frame(index, frame)
        if f is None:
            raise FrameNotFoundError()
        v = f.create_view_if_not_exists(view)
        frag = v.create_fragment_if_not_exists(slice_)
        frag.read_from_tar(io.BytesIO(body))
        return _json_resp({})

    def _get_fragment_blocks(self, pv, params, headers, body) -> Response:
        index, frame, view, slice_ = self._fragment_args(params)
        frag = self.holder.fragment(index, frame, view, slice_)
        if frag is None:
            raise FragmentNotFoundError()
        blocks = [{"id": bid, "checksum": cs.hex()}
                  for bid, cs in frag.blocks()]
        return _json_resp({"blocks": blocks})

    def _get_fragment_block_data(self, pv, params, headers, body) -> Response:
        req = pb.BlockDataRequest()
        if body:
            req.ParseFromString(body)
        else:
            req.index = params["index"]
            req.frame = params["frame"]
            req.view = params.get("view", "standard")
            req.slice = int(params["slice"])
            req.block = int(params["block"])
        frag = self.holder.fragment(req.index, req.frame, req.view, req.slice)
        if frag is None:
            raise FragmentNotFoundError()
        rows, cols = frag.block_data(req.block)
        resp = pb.BlockDataResponse()
        resp.row_ids.extend(int(r) for r in rows)
        resp.column_ids.extend(int(c) for c in cols)
        if self._accepts_proto(headers):
            return _proto_resp(resp)
        return _json_resp({"rowIDs": [int(r) for r in rows],
                           "columnIDs": [int(c) for c in cols]})

    # -- attr diff (anti-entropy) -------------------------------------------

    def _post_index_attr_diff(self, pv, params, headers, body) -> Response:
        idx = self.holder.index(pv["index"])
        if idx is None:
            raise IndexNotFoundError()
        return self._attr_diff(idx.column_attr_store, body)

    def _post_frame_attr_diff(self, pv, params, headers, body) -> Response:
        f = self.holder.frame(pv["index"], pv["frame"])
        if f is None:
            raise FrameNotFoundError()
        return self._attr_diff(f.row_attr_store, body)

    def _attr_diff(self, store, body: bytes) -> Response:
        """The requester sends its block checksums; respond with every
        attr in OUR blocks the requester is missing or disagrees on
        (handler.go attr/diff + attr.go Diff: diff is taken from the
        requester's perspective against this node's store)."""
        req = json.loads(body.decode() or "{}")
        requester = [(int(b["id"]), bytes.fromhex(b["checksum"]))
                     for b in req.get("blocks", [])]
        ids = diff_blocks(requester, store.blocks())
        attrs = {}
        for bid in ids:
            attrs.update({str(k): v
                          for k, v in store.block_data(bid).items()})
        return _json_resp({"attrs": attrs})

    # -- restore -------------------------------------------------------------

    def _post_frame_restore(self, pv, params, headers, body) -> Response:
        """Pull every fragment of a frame from a remote host
        (handler.go:1180 handlePostFrameRestore)."""
        guard = self._spmd_guard_bulk("frame restore")
        if guard is not None:
            return guard
        host = params.get("host")
        if not host:
            return _json_resp({"error": "host required"}, 400)
        if self.client_factory is None:
            return _json_resp({"error": "restore not supported"}, 501)
        index, frame = pv["index"], pv["frame"]
        f = self.holder.frame(index, frame)
        if f is None:
            raise FrameNotFoundError()
        client = self.client_factory(host)
        maxes = client.max_slices()
        inverse_maxes = client.max_slices(inverse=True)
        for view_name in client.frame_views(index, frame):
            v = f.create_view_if_not_exists(view_name)
            # Inverse views are sliced over row-space, standard/time
            # views over column-space — each has its own max.
            from ..core.view import is_inverse_view
            n = (inverse_maxes if is_inverse_view(view_name)
                 else maxes).get(index, 0)
            for slice_ in range(n + 1):
                data = client.fragment_data(index, frame, view_name, slice_)
                if data is None:
                    continue
                frag = v.create_fragment_if_not_exists(slice_)
                frag.read_from_tar(io.BytesIO(data))
        return _json_resp({})

    # -- internal control plane ---------------------------------------------

    def _post_internal_message(self, pv, params, headers, body) -> Response:
        if self.spmd is not None or self.spmd_worker:
            # In spmd mode the descriptor stream is the ONLY schema
            # transport: an HTTP-delivered broadcast would apply to
            # this rank's holder alone (rank 0 included — its
            # receive_message never re-enters the stream), diverging
            # the replicas the fingerprint gate then rejects forever.
            return _json_resp(
                {"error": "internal broadcasts are descriptor-stream "
                          "only under [cluster] type=\"spmd\""}, 400)
        if self.broadcast_handler is None:
            return _json_resp({"error": "broadcast not supported"}, 501)
        msg = unmarshal_message(body)
        self.broadcast_handler.receive_message(msg)
        return _json_resp({})

    def _get_internal_status(self, pv, params, headers, body) -> Response:
        if self.status_handler is None:
            return _json_resp({"error": "status not supported"}, 501)
        status = self.status_handler.local_status()
        return _proto_resp(status)

    def _get_internal_epochs(self, pv, params, headers, body) -> Response:
        """Replication-epoch digest (ISSUE 18): this node's
        (fragment -> epoch) map plus its scheduler queue depth. A JSON
        side-channel on the status poll — the NodeStatus protobuf's
        descriptor is baked, so the digest rides next to it rather
        than inside it. Peers feed the answer to their EpochTracker
        (observe_digest) to judge read-replica staleness in
        writes-behind."""
        depth = 0
        if callable(self.queue_depth_fn):
            try:
                depth = int(self.queue_depth_fn())
            except Exception:  # noqa: BLE001 — telemetry never raises
                depth = 0
        return _json_resp({
            "host": self.host,
            "epochs": self.holder.fragment_epochs(),
            "queue_depth": depth,
        })

    def _post_internal_epochs_advance(self, pv, params, headers,
                                      body) -> Response:
        """Floor-raise local fragment epochs to reconciled values
        (hint-replay and anti-entropy push these after convergence so
        a replica that applied writes out of band reports an epoch
        comparable to its peers'). Raising is the ONLY direction:
        advance_epoch is monotonic, and unknown fragments are skipped
        — a floor push never creates state."""
        try:
            req = json.loads(body or b"{}")
            epochs = req.get("epochs") or {}
        except (ValueError, AttributeError):
            return _json_resp({"error": "bad epoch advance body"}, 400)
        applied = 0
        for key, epoch in epochs.items():
            parts = str(key).split("/")
            if len(parts) != 4:
                continue
            try:
                slice_ = int(parts[3])
                epoch = int(epoch)
            except ValueError:
                continue
            frag = self.holder.fragment(parts[0], parts[1], parts[2],
                                        slice_)
            if frag is None:
                continue
            try:
                before = frag.epoch
                if frag.advance_epoch(epoch) > before:
                    applied += 1
            except Exception:  # noqa: BLE001 — one bad fragment
                continue       # must not fail the whole push
        return _json_resp({"applied": applied})


# ---- JSON encoding of results ----------------------------------------------

def _result_to_json(result):
    if isinstance(result, Row):
        return {"attrs": result.attrs,
                "bits": [int(c) for c in result.columns()]}
    if isinstance(result, list):
        return [{"id": int(k), "count": int(n)} for k, n in result]
    return result  # int, bool, or None


def _decode_options(body: bytes, mapping: Dict[str, str]) -> dict:
    doc = json.loads(body.decode() or "{}")
    if not isinstance(doc, dict):
        raise ValueError("request body must be a JSON object")
    raw = doc.get("options", {})
    if not isinstance(raw, dict):
        raise ValueError("options must be a JSON object")
    out = {}
    for k, v in raw.items():
        if k not in mapping:
            raise ValueError(f"unknown option: {k}")
        out[mapping[k]] = v
    return out


def _cluster_status_to_dict(status) -> dict:
    return {"nodes": [{
        "host": n.host,
        "state": n.state,
        "indexes": [{
            "name": i.name,
            "maxSlice": i.max_slice,
            "frames": [f.name for f in i.frames],
        } for i in n.indexes],
    } for n in status.nodes]}
