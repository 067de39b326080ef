"""Server: the node runtime (parity with /root/reference/server.go).

Wires Config -> Holder + Cluster + Broadcaster + Executor + Handler +
APIServer, applies received broadcast messages (schema + slice
changes), exchanges NodeStatus with peers, and runs the background
daemons:

  - anti-entropy loop    (default 10 min; server.go:182-214)
  - status poll loop     (default 60 s; replaces both the reference's
                          maxSlice polling, server.go:217-252, and its
                          memberlist gossip state sync: each tick pulls
                          /internal/status from every peer, merges
                          schema + remote max slices, and marks
                          unreachable peers DOWN for query failover)
  - cache flush loop     (1 min; holder.go:326-358)
"""

from __future__ import annotations

import json
import logging
import os
import threading
import time
from typing import Dict, Optional

from .api import APIServer, Handler, InternalClient
from .api.client import BREAKER_CLOSED, BREAKER_OPEN, BreakerRegistry
from .config import Config
from .core.fragment import (
    IntegrityContext,
    bitmap_block_checksums,
    bitmap_from_tar,
)
from .core.holder import Holder
from .core.scrub import Scrubber
from .core.syncer import Closing, HolderSyncer
from .core.view import VIEW_INVERSE, VIEW_STANDARD
from .executor import Executor
from .parallel.broadcast import HTTPBroadcaster, NopBroadcaster, StaticNodeSet
from .parallel.cluster import (
    NODE_STATE_DOWN,
    NODE_STATE_UP,
    Cluster,
    Node,
)
from .parallel.hints import HintManager
from .parallel.rebalance import Rebalancer
from .obs import (StatMap, Tracer, costs as obs_costs,
                  health as obs_health, slo as obs_slo)
from .utils.stats import ExpvarStats
from .wire import pb

CACHE_FLUSH_INTERVAL = 60.0


class ClusterClient:
    """Routes executor remote calls to per-node InternalClients (the
    reference passes node hosts into Client per call; here one routing
    object satisfies the executor's client seam). All per-node clients
    share ONE StatMap and ONE BreakerRegistry, so /debug/vars has a
    single `cluster` section and `_slices_by_node` can consult breaker
    state via `breaker_state(host)`."""

    def __init__(self, timeout: float = 30.0, retry_max: int = 2,
                 retry_backoff: float = 0.05, breaker_threshold: int = 5,
                 breaker_cooldown: float = 5.0):
        self.timeout = timeout
        self.retry_max = retry_max
        self.retry_backoff = retry_backoff
        self.stats = StatMap()
        self.breakers = BreakerRegistry(
            breaker_threshold, breaker_cooldown, stats=self.stats)
        self._clients: Dict[str, InternalClient] = {}
        self._lock = threading.Lock()

    def for_host(self, host: str) -> InternalClient:
        with self._lock:
            c = self._clients.get(host)
            if c is None:
                c = self._clients[host] = InternalClient(
                    host, timeout=self.timeout, retry_max=self.retry_max,
                    retry_backoff=self.retry_backoff,
                    breaker=self.breakers.for_host(host), stats=self.stats)
            return c

    def breaker_state(self, host: str) -> str:
        """Executor seam: current breaker state for a node host (raw
        "host:port" form, as Node.host carries it)."""
        return self.breakers.state(host)

    def execute_query(self, node, index, query, slices, remote=True,
                      deadline=None):
        return self.for_host(node.host).execute_query(
            node, index, query, slices, remote=remote, deadline=deadline)


class Server:
    """One node: HTTP API + executor + daemons."""

    def __init__(self, config: Optional[Config] = None, logger=None):
        self.config = config or Config()
        self.logger = logger or logging.getLogger("pilosa_tpu")
        self.closing = Closing()

        self.stats = ExpvarStats()
        # Query trace rings ([obs] config; PILOSA_TPU_SLOW_QUERY_US
        # still wins inside Tracer) — served at /debug/queries.
        self.tracer = Tracer(
            ring=self.config.trace_ring,
            slow_us=self.config.slow_query_threshold * 1e6)
        # Shared IntegrityContext: created empty here (fragments keep a
        # reference), repair_source wired below once the cluster client
        # exists — a corrupt fragment then read-repairs from a replica
        # at load time.
        self.integrity = IntegrityContext()
        self.holder = Holder(self.config.expanded_data_dir(),
                             stats=self.stats,
                             wal=self.config.wal_config(),
                             integrity=self.integrity)
        self.cluster = Cluster(
            nodes=[Node(h) for h in self.config.cluster_hosts],
            replica_n=self.config.replica_n,
            partition_n=self.config.partition_n,
        )
        self.host = self.config.host
        self.client = ClusterClient(
            timeout=self.config.client_timeout,
            retry_max=self.config.retry_max,
            retry_backoff=self.config.retry_backoff,
            breaker_threshold=self.config.breaker_threshold,
            breaker_cooldown=self.config.breaker_cooldown)

        # Transport selection (reference server/server.go:150-187:
        # static | http | gossip; plus the TPU-native "spmd" multi-host
        # data plane).
        self.spmd = None
        self._spmd_rank = 0
        ctype = self.config.cluster_type
        if ctype == "spmd":
            # Multi-host SPMD: join the jax.distributed runtime FIRST
            # (before anything touches a jax backend), then build the
            # descriptor plane over the GLOBAL mesh. The node set is
            # this host alone — replication and fan-out ride the
            # descriptor stream, not HTTP (parallel/spmd.py).
            from .parallel.mesh import connect_distributed
            from .parallel.spmd import SpmdBroadcaster, SpmdServer

            self._spmd_rank = connect_distributed(
                self.config.spmd_coordinator or None,
                (self.config.spmd_num_processes
                 if self.config.spmd_num_processes > 0 else None),
                (self.config.spmd_process_id
                 if self.config.spmd_process_id >= 0 else None))
            self.spmd = SpmdServer(self.holder)
            self.spmd.apply_message = self.receive_message
            # Attr-write replication: descriptor PQL executes through
            # this rank's executor with remote=True (wired below, after
            # the executor exists).
            self.node_set = StaticNodeSet([self.host])
            self.broadcaster = (SpmdBroadcaster(self.spmd)
                                if self._spmd_rank == 0 else NopBroadcaster())
        elif ctype == "gossip":
            from .parallel.gossip import GossipNodeSet
            bind_ip = self.host.partition(":")[0] or "127.0.0.1"
            seeds = []
            if self.config.gossip_seed:
                sh, _, sp = self.config.gossip_seed.partition(":")
                seeds.append((sh or "127.0.0.1",
                              int(sp or self.config.gossip_port)))
            self.node_set = GossipNodeSet(
                local_host=self.host, bind=bind_ip,
                gossip_port=self.config.gossip_port, seeds=seeds,
                broadcast_handler=self, status_handler=self,
                on_change=self._set_live_hosts, logger=self.logger,
                epoch_digest_fn=self._local_epoch_digest,
                on_epoch_digest=self._handle_epoch_digest)
            self.broadcaster = self.node_set
        elif ctype == "http" and len(self.config.cluster_hosts) > 1:
            self.node_set = StaticNodeSet(self.config.cluster_hosts)
            self.broadcaster = HTTPBroadcaster(
                self.node_set, self.host, self.client.for_host,
                logger=self.logger)
        elif ctype in ("http", "static"):
            self.node_set = StaticNodeSet(self.config.cluster_hosts)
            self.broadcaster = NopBroadcaster()
        else:
            raise ValueError(f"unknown cluster type: {ctype!r} "
                             "(want static, http, gossip, or spmd)")
        self.holder.broadcaster = self.broadcaster

        # Staging/backend knobs become env defaults BEFORE the executor
        # (and any staging or backend resolution) exists; an exported
        # env var still wins.
        self.config.apply_mesh_env()
        use_device = self.config.use_device_flag()
        if self.spmd is not None and self._spmd_rank != 0:
            # A worker's executor must NEVER drive mesh collectives by
            # itself (a unilateral shard_map over the global mesh hangs
            # every rank); HTTP queries landing here serve from the
            # host roaring path over the replicated holder.
            use_device = False
        if use_device is not False:
            # Resolve the count backend NOW instead of lazily on the
            # first coarse-eligible count: the /debug/vars
            # count_calibration record exists as soon as the server is
            # up, and a TPU boot absorbs the (bounded, abandonable)
            # measurement before traffic arrives. A pinned
            # PILOSA_TPU_COUNT_BACKEND returns without measuring.
            def _kick():
                try:
                    from .ops.calibrate import resolve_backend
                    resolve_backend()
                except Exception:  # noqa: BLE001 — boot never dies here
                    self.logger.warning(
                        "count-backend calibration failed at boot; the "
                        "first count resolves it again", exc_info=True)
            threading.Thread(target=_kick, daemon=True,
                             name="count-calibrate-boot").start()
        self.executor = Executor(
            self.holder, host=self.host, cluster=self.cluster,
            client=self.client, use_device=use_device,
            prefer_local_reads=self.config.prefer_local_reads,
            ici_hosts=self.config.cluster_ici_hosts,
            mesh_config=self.config.mesh_config())
        if self.spmd is not None:
            def _apply_query(index, query):
                # query arrives pre-parsed: _execute_pql already parsed
                # it for the allowlist check.
                from .executor import ExecOptions

                return self.executor.execute(index, query,
                                             opt=ExecOptions(remote=True))

            self.spmd.apply_query = _apply_query
            if self._spmd_rank == 0:
                self.executor.set_spmd(self.spmd)
            else:
                # Share the manager for /debug/vars visibility of the
                # descriptor-driven collectives this rank participates
                # in (use_device=False + the _device_backend_on gates in
                # the executor keep this rank from driving it alone),
                # and reject mutations: a write applied to this rank's
                # holder outside the descriptor stream would silently
                # diverge the replicas.
                self.executor._mesh_mgr = self.spmd.manager
                self.executor.spmd_reject_writes = True
        # Write-path replication resilience (ISSUE 13): quorum acks +
        # durable hinted handoff. The hint plane only exists on real
        # multi-node HTTP/gossip clusters — SPMD replicates through the
        # descriptor stream, and a single-node ring has no replicas to
        # miss (so single-node tests pay zero threads/dirs for it).
        self.executor.write_consistency = self.config.write_consistency
        self.hints: Optional[HintManager] = None
        if self.spmd is None and (len(self.cluster.nodes) > 1
                                  or ctype == "gossip"):
            self.hints = HintManager(
                os.path.join(self.config.expanded_data_dir(), ".hints"),
                client_factory=self.client.for_host,
                breaker_state=self.client.breaker_state,
                max_bytes=self.config.hint_max_bytes,
                drain_interval=self.config.hint_drain_interval,
                wal_cfg=self.config.wal_config(),
                logger=self.logger, stats=self.stats)
            self.executor.hints = self.hints
            # Failure-detection feedback: an opening breaker marks the
            # node DOWN cluster-wide (the write path then hints instead
            # of paying its timeout per write); a close marks it live
            # and wakes the drainer immediately.
            self.client.breakers.on_change = self._breaker_change
        self.handler = Handler(
            self.holder, self.executor, cluster=self.cluster,
            host=self.host, broadcaster=self.broadcaster,
            broadcast_handler=self, status_handler=self,
            client_factory=self.client.for_host, stats=self.stats,
            logger=self.logger, tracer=self.tracer)
        self.handler.hints = self.hints
        self.handler.write_consistency = self.config.write_consistency
        # Default per-query budget ([cluster] query-deadline; 0 = none).
        self.handler.default_deadline = self.config.query_deadline
        # Sampled-gauge cadence for /metrics ([obs]
        # metrics-sample-interval).
        self.handler.metrics_sample_interval = (
            self.config.metrics_sample_interval)
        # Continuous-profiling cadence ([obs] profile-sample-rate;
        # 0 = only on explicit ?profile=true).
        self.handler.profile_sample_rate = self.config.profile_sample_rate
        # Fleet pane scrape-round TTL ([obs] fleet-scrape-interval) and
        # flight-recorder ring capacity ([obs] queryshape-ring).
        self.handler.fleet_scrape_interval = (
            self.config.fleet_scrape_interval)
        self.executor.flight.ring = max(1, int(
            self.config.queryshape_ring))
        # Read-path resilience (ISSUE 18): bounded-staleness follower
        # reads + the epoch-keyed result cache. default-read-staleness
        # applies to queries without an X-Pilosa-Staleness header
        # (0 = strict everywhere); the cache cap and shadow-verify
        # cadence are operator knobs because the cache trades memory
        # for zipf-head throughput.
        self.handler.default_read_staleness = (
            self.config.default_read_staleness)
        self.executor.result_cache.cap = max(
            1, int(self.config.result_cache_size))
        self.executor.result_cache_verify_1_in = (
            self.config.result_cache_verify_1_in)
        # Adaptive query scheduler ([sched]): deadline-aware admission
        # (429 + Retry-After), adaptive batching window whose cohort
        # releases hint the mesh batch loop (executor.burst_hint), and
        # per-tenant weighted fair queues. Service-time estimates come
        # from the scheduler's own observations, falling back to the
        # executor's measured route latencies.
        self.scheduler = None
        if self.config.sched_enabled:
            from .sched import QueryScheduler

            self.scheduler = QueryScheduler(
                max_window_us=self.config.sched_max_window_us,
                idle_window_us=self.config.sched_idle_window_us,
                queue_depth=self.config.sched_queue_depth,
                default_service_us=self.config.sched_default_service_us,
                tenant_weights=self.config.sched_tenant_weights,
                estimator=self.executor.estimate_service_us,
                on_release=self.executor.burst_hint)
            self.handler.scheduler = self.scheduler
            # Gossiped load signal for follower-read p2c spreading:
            # peers pull this node's queued+inflight depth with the
            # epoch digest.
            self.handler.queue_depth_fn = (
                lambda: (lambda d: d.get("queued", 0)
                         + d.get("inflight", 0))(
                    self.scheduler.queue_depths()))
        # Cost observatory ([obs] cost-*): per-(tenant, shape) resource
        # attribution ledger + self-baselining regression watch. The
        # ledger and watch are process-wide singletons (charges arrive
        # from the executor, WAL, stager, and transports, none of which
        # hold a server reference); the server just applies the knobs
        # and wires the scheduler's admission-time cost estimator.
        obs_costs.LEDGER.enabled = bool(self.config.cost_ledger)
        obs_costs.LEDGER.max_accounts = max(
            1, int(self.config.cost_max_accounts))
        obs_costs.WATCH.enabled = bool(self.config.cost_ledger)
        obs_costs.WATCH.max_bands = max(
            1, int(self.config.cost_watch_bands))
        obs_costs.WATCH.k = float(self.config.cost_regression_k)
        obs_costs.WATCH.min_n = max(
            2, int(self.config.cost_regression_min_n))
        self.handler.cost_debt_threshold = float(
            self.config.cost_debt_threshold)
        if self.scheduler is not None and self.config.cost_ledger:
            self.scheduler.cost_share_fn = obs_costs.LEDGER.tenant_share
        if self.config.cost_ledger:
            # Warm-start the regression bands from whatever the flight
            # recorder already holds (a no-op on a cold process; on an
            # embedded restart it spares the watch its min_n warmup).
            try:
                obs_costs.WATCH.seed_from_flight(
                    self.executor.flight.snapshot(limit=obs_costs
                                                  .WATCH.max_bands))
            except Exception:
                pass
        # SLO observatory ([slo]): replace the handler's default
        # recorder with the config-declared objectives; tenant label
        # cardinality is bounded by the [sched] tenant-weights keys.
        if self.config.slo_enabled:
            self.handler.slo = obs_slo.SLORecorder(
                objectives=self.config.slo_objectives(),
                tenants=self.config.sched_tenant_weights)
        else:
            self.handler.slo = None
        if self.spmd is not None:
            if self._spmd_rank == 0:
                self.handler.spmd = self.spmd
            else:
                self.handler.spmd_worker = True

        # Live slice migration ([rebalance]): the node that takes the
        # /cluster/resize call coordinates; control messages (join/
        # leave/cutover/complete) fan out to peers over the same
        # endpoint with ?remote=true.
        # Data-integrity wiring ([integrity]): read-repair source,
        # device-result shadow sampling, background scrubber.
        self.integrity.repair_source = self._repair_source
        self.executor.shadow_sample = self.config.integrity_shadow_sample
        self.scrubber = Scrubber(
            self.holder, host=self.host, cluster=self.cluster,
            client_factory=self.client.for_host, closing=self.closing,
            logger=self.logger, stats=self.stats,
            interval=self.config.integrity_scrub_interval,
            rate_limit=self.config.integrity_rate_limit,
            enabled=self.config.integrity_enabled,
            op_deadline=self.config.sync_block_deadline)
        self.handler.scrubber = self.scrubber

        self.rebalancer = Rebalancer(
            self.holder, self.cluster, self.host, self.client.for_host,
            closing=self.closing, logger=self.logger, stats=self.stats,
            concurrency=self.config.rebalance_concurrency,
            retry_max=self.config.rebalance_retry_max,
            retry_backoff=self.config.rebalance_retry_backoff,
            broadcast=self._broadcast_resize)
        self.handler.resizer = self.rebalancer

        # Liveness plane ([health]): apply knobs to the process-global
        # registry (STATS/LEDGER idiom — the instrumented loops in
        # core/ and parallel/ never hold a server reference), point
        # dossiers under the data dir, and wire the bundle sections a
        # trip captures. Critical subsystems are the ones whose stall
        # means this node should stop taking traffic (/readyz 503);
        # the rest degrade service without invalidating it.
        hreg = obs_health.HEALTH
        hreg.enabled = bool(self.config.health_enabled)
        hreg.sweep_interval = max(
            0.01, float(self.config.health_sweep_interval))
        hreg.stall_after = max(
            1.0, float(self.config.health_stall_after))
        hreg.dossier_max_bytes = max(
            1024, int(self.config.health_dossier_max))
        hreg.dossier_keep = max(1, int(self.config.health_dossier_keep))
        hreg.dossier_dir = os.path.join(
            self.config.expanded_data_dir(), ".dossier")
        hreg.mark_critical("sched-dispatch", "spmd-dispatch", "wal",
                           "hint-drain", "mesh-count-batch")
        self._ready = False
        self.handler.ready_fn = lambda: self._ready
        hreg.bundle_providers.update({
            "config": lambda: obs_health.redact_config(
                vars(self.config)),
            "slow_queries": self._bundle_endpoint("/debug/queries"),
            "queryshapes": self._bundle_endpoint("/debug/queryshapes"),
            "slo": self._bundle_endpoint("/debug/slo"),
            "costs": self._bundle_endpoint("/debug/costs"),
            "epochs": self._bundle_endpoint("/internal/epochs"),
            "vars": self._bundle_endpoint("/debug/vars"),
        })
        # Gossiped health feeds read placement: a peer that announced
        # itself wedged is not an eligible follower-read target, even
        # before its breaker ever opens.
        self.executor.peer_health_ok = hreg.peer_ready

        self._api: Optional[APIServer] = None
        self._threads: list = []
        # Last NodeStatus seen per peer host (gossip-lite state).
        self._peer_status: Dict[str, pb.NodeStatus] = {}

    # -- lifecycle -----------------------------------------------------------

    def open(self, port: Optional[int] = None):
        """Open holder + listener + daemons (server.go:89-154)."""
        self.holder.open()
        self._apply_config_schema()
        bind_host, _, bind_port = self.host.partition(":")
        if port is None:
            port = int(bind_port or 10101)
        self._api = APIServer(self.handler, bind_host or "127.0.0.1", port,
                              logger=self.logger)
        # Rebind host to the actual listening address (port 0 support).
        h, p = self._api.address
        if port == 0:
            self.host = f"{bind_host or h}:{p}"
            node = self.cluster.node_by_host(self.config.host)
            if node is not None:
                node.host = self.host
            self.executor.host = self.host
            self.handler.host = self.host
            self.scrubber.host = self.host
            if hasattr(self.node_set, "local_host"):
                self.node_set.local_host = self.host
        self._api.start()
        self.node_set.open()
        if self.hints is not None:
            self.hints.start()
        # Watchdog before the daemons it supervises (refcounted: an
        # in-process cluster shares the one sweep thread).
        obs_health.HEALTH.start()

        for name, fn, interval, jitter in [
            ("anti-entropy", self._anti_entropy_tick,
             self.config.anti_entropy_interval,
             self.config.effective_anti_entropy_jitter()),
            ("status-poll", self._status_poll_tick,
             self.config.polling_interval, 0.0),
            ("cache-flush", self._cache_flush_tick, CACHE_FLUSH_INTERVAL,
             0.0),
            ("scrub", self._scrub_tick,
             self.config.integrity_scrub_interval,
             0.1 * self.config.integrity_scrub_interval),
        ]:
            hb = obs_health.HEALTH.register(name,
                                            interval=interval + jitter)
            t = threading.Thread(target=self._loop, name=name,
                                 args=(fn, interval, jitter, hb),
                                 daemon=True)
            t.start()
            self._threads.append(t)

        # Migration service loop: parked until a resize trigger()s it.
        t = threading.Thread(target=self.rebalancer.run, name="rebalance",
                             daemon=True)
        t.start()
        self._threads.append(t)

        if self.spmd is not None and self._spmd_rank != 0:
            # SPMD worker: follow rank 0's descriptor stream (queries,
            # writes, schema) until it broadcasts stop. The HTTP API
            # stays up for status/debug and host-path reads.
            t = threading.Thread(target=self.spmd.run_worker,
                                 name="spmd-worker", daemon=True)
            t.start()
            self._threads.append(t)

        # Background warm: Holder.open defers fragment parsing (O(schema)
        # cold start); this prefetches storage so early queries don't
        # each pay a first-touch parse (SURVEY.md §7 async prefetch).
        t = threading.Thread(
            target=self.holder.warm, name="warm",
            args=(self.closing,), daemon=True)
        t.start()
        self._threads.append(t)
        self._ready = True

    def close(self):
        self._ready = False
        if self.spmd is not None and self._spmd_rank == 0:
            try:
                self.spmd.stop()  # release every worker loop
            except Exception as e:  # noqa: BLE001 — workers may be gone
                self.logger.warning(f"spmd stop: {e}")
        self.closing.close()
        # Drain the scheduler first: queued waiters are released
        # pass-through so no HTTP thread blocks across shutdown.
        if self.scheduler is not None:
            self.scheduler.close()
        # Join the warm thread BEFORE holder.close(): a warm mid-load
        # after close would reopen a WAL fd on a fragment whose flock
        # was just released (leaked fd + unprotected writer).
        for t in self._threads:
            if t.name == "warm":
                t.join(timeout=10)
        if self.hints is not None:
            self.hints.close()
        self.node_set.close()
        if self._api is not None:
            self._api.close()
        # Drop staged device views so the cost ledger's residency
        # meters finalize: an abandoned record would keep accruing
        # hbm_byte_seconds forever against views that no longer exist.
        try:
            self.executor.invalidate_device_index()
        except Exception as e:  # noqa: BLE001 — device layer may be gone
            self.logger.warning(f"view drop at close: {e}")
        self.holder.close()
        # Silence from a closed daemon is shutdown, not a hang: drop
        # the interval-bearing heartbeats this server registered, then
        # release the shared watchdog.
        for name in ("anti-entropy", "status-poll", "cache-flush",
                     "scrub"):
            obs_health.HEALTH.unregister(name)
        obs_health.HEALTH.stop()

    def _set_live_hosts(self, hosts):
        """Gossip membership feed -> cluster liveness
        (reference Cluster.NodeStates, cluster.go:156-169). A live host
        the ring has never seen enters as JOINING — placement ignores
        it until the rebalancer streams its slices over and cuts over."""
        hosts = list(hosts)
        self.cluster.node_set_hosts = hosts
        joined = False
        for h in hosts:
            if h == self.host:
                continue
            if self.cluster.node_by_host(h) is None:
                try:
                    self.cluster.begin_join(h)
                    joined = True
                    self.logger.info(f"gossip: new member {h} JOINING")
                except ValueError:
                    pass
            elif self.cluster.mark_live(h):
                # A known member came back from DOWN: its backlog of
                # missed writes can drain now, not at the next timer.
                self.logger.info(f"gossip: member {h} back UP")
                if self.hints is not None:
                    self.hints.notify(h)
        if joined:
            self.rebalancer.trigger()

    def _loop(self, fn, interval: float, jitter: float = 0.0, hb=None):
        while not self.closing.wait(interval):
            if jitter > 0:
                import random
                if self.closing.wait(random.uniform(0, jitter)):
                    return
            if hb is not None:
                hb.beat()
            try:
                fn()
            except Exception as e:  # noqa: BLE001 — daemons never die
                self.logger.warning(f"daemon error: {e}")

    # -- daemons -------------------------------------------------------------

    def _anti_entropy_tick(self):
        if len(self.cluster.nodes) <= 1:
            return
        syncer = HolderSyncer(self.holder, self.host, self.cluster,
                              self.client.for_host, self.closing,
                              self.logger, stats=self.stats,
                              op_deadline=self.config.sync_block_deadline)
        syncer.sync_holder()
        self.stats.count("anti_entropy")

    def _status_poll_tick(self):
        """Pull NodeStatus from every peer; merge schema/max-slices;
        track liveness. mark_live/mark_unreachable (not raw set_state)
        so a poll success can't stomp a JOINING/LEAVING node back to
        ACTIVE mid-migration. The replication-epoch digest (ISSUE 18)
        rides the same cadence: each reachable peer's
        (fragment -> epoch, queue_depth) feeds the executor's
        EpochTracker, which is what judges follower-read
        eligibility."""
        tracker = self.executor.epochs
        # Refresh local knowledge first: mutation seams that don't
        # pass through the coordinator write path (bulk imports,
        # read-repair, hint replay INTO this node) advance fragment
        # epochs the tracker must see — and invalidate result-cache
        # entries keyed to the old max.
        try:
            tracker.observe_digest(self.host,
                                   self.holder.fragment_epochs())
        except Exception:  # noqa: BLE001 — telemetry never kills polls
            pass
        for node in self.cluster.nodes:
            if node.host == self.host:
                continue
            try:
                status = self.client.for_host(node.host).node_status()
            except Exception:  # noqa: BLE001 — unreachable peer
                node.mark_unreachable()
                # Fail closed: without a live digest the peer is not
                # an eligible follower-read target.
                tracker.forget_host(node.host)
                continue
            was_down = node.state == NODE_STATE_DOWN
            node.mark_live()
            if was_down and self.hints is not None:
                # Recovery observed by the poll: wake the drainer now.
                self.hints.notify(node.host)
            self._peer_status[node.host] = status
            self.handle_remote_status(status)
            try:
                digest = self.client.for_host(node.host).epoch_digest()
                tracker.observe_digest(
                    node.host, digest.get("epochs") or {},
                    int(digest.get("queue_depth") or 0))
                obs_health.HEALTH.observe_peer(node.host,
                                              digest.get("health"))
            except Exception:  # noqa: BLE001 — older peer without the
                pass           # endpoint: digest simply stays absent

    def _local_epoch_digest(self) -> dict:
        """This node's replication-epoch digest — the same document
        GET /internal/epochs serves — for the gossip push-pull
        piggyback."""
        depth = 0
        fn = self.handler.queue_depth_fn
        if fn is not None:
            try:
                depth = int(fn())
            except Exception:  # noqa: BLE001 — load signal only
                depth = 0
        return {"epochs": self.holder.fragment_epochs(),
                "queue_depth": depth,
                "health": obs_health.HEALTH.gossip_summary()}

    def _handle_epoch_digest(self, host: str, digest: dict) -> None:
        """A peer's digest arrived over gossip push-pull: feed the
        follower-read staleness judge and the health plane (a wedged
        drainer on a peer is visible here before its breaker opens)."""
        self.executor.epochs.observe_digest(
            host, digest.get("epochs") or {},
            int(digest.get("queue_depth") or 0))
        obs_health.HEALTH.observe_peer(host, digest.get("health"))

    def _bundle_endpoint(self, path: str):
        """Dossier section provider: answer `path` through the local
        handler (the _fleet_fetch idiom — always fresh, no HTTP)."""
        def fetch():
            resp = self.handler.handle("GET", path)
            if resp.status != 200:
                return {"error": f"status={resp.status}"}
            return json.loads(resp.body.decode())
        return fetch

    def _breaker_change(self, host: str, state: str):
        """Circuit-breaker liveness feedback (BreakerRegistry
        on_change, fired outside the breaker lock): an opening breaker
        collapses the node to DOWN so every writer stops paying its
        timeout; a close (successful probe) marks it live and wakes
        the hint drainer for immediate catch-up."""
        if state == BREAKER_OPEN:
            if self.cluster.mark_unreachable(host):
                self.logger.info(f"breaker open: marked {host} DOWN")
        elif state == BREAKER_CLOSED:
            if self.cluster.mark_live(host):
                self.logger.info(f"breaker closed: {host} back UP")
            if self.hints is not None:
                self.hints.notify(host)

    def _cache_flush_tick(self):
        self.holder.flush_caches()

    def _scrub_tick(self):
        if self.config.integrity_enabled:
            self.scrubber.scrub_pass()

    def _repair_source(self, frag) -> Optional[bytes]:
        """Read-repair source (IntegrityContext.repair_source): stream
        the fragment tar from the first live replica whose payload
        VERIFIES — the tar's own integrity footer must parse, and its
        per-block checksums must match what the replica separately
        reports via /fragment/blocks (a rotted replica must never
        become the repair donor)."""
        for node in self.cluster.fragment_nodes(frag.index, frag.slice):
            if node.host == self.host or node.state != NODE_STATE_UP:
                continue
            client = self.client.for_host(node.host)
            try:
                tar = client.fragment_data(frag.index, frag.frame,
                                           frag.view, frag.slice)
                if not tar:
                    continue
                bm = bitmap_from_tar(tar)
                if bm is None:
                    continue
                want = dict(client.fragment_blocks(
                    frag.index, frag.frame, frag.view, frag.slice))
                if bitmap_block_checksums(bm) != want:
                    self.logger.warning(
                        "read-repair: replica %s serves inconsistent "
                        "checksums for %s/%s/%s/%d — skipping",
                        node.host, frag.index, frag.frame, frag.view,
                        frag.slice)
                    continue
                return tar
            except Exception as e:  # noqa: BLE001 — next replica
                self.logger.warning(
                    "read-repair fetch from %s failed: %s", node.host, e)
        return None

    def _broadcast_resize(self, action: str, **fields):
        """Ship a resize control message (join/leave/cutover/complete)
        to every peer via POST /cluster/resize?remote=true. Best-effort:
        a peer that misses a cutover still converges on `complete`, and
        a peer that misses everything re-learns membership from the
        status poll + anti-entropy."""
        for node in list(self.cluster.nodes):
            if node.host == self.host:
                continue
            try:
                self.client.for_host(node.host).cluster_resize(
                    action, **fields)
            except Exception as e:  # noqa: BLE001 — best-effort fan-out
                self.logger.warning(
                    f"resize broadcast {action} to {node.host}: {e}")

    # -- BroadcastHandler (server.go:255-300) --------------------------------

    def receive_message(self, msg):
        if isinstance(msg, pb.CreateSliceMessage):
            idx = self.holder.index(msg.index)
            if idx is None:
                raise ValueError(f"local index not found: {msg.index}")
            if msg.is_inverse:
                idx.set_remote_max_inverse_slice(msg.slice)
            else:
                idx.set_remote_max_slice(msg.slice)
        elif isinstance(msg, pb.CreateIndexMessage):
            self.holder.create_index_if_not_exists(
                msg.index, column_label=msg.meta.column_label or "columnID",
                time_quantum=msg.meta.time_quantum)
        elif isinstance(msg, pb.DeleteIndexMessage):
            self.holder.delete_index(msg.index)
        elif isinstance(msg, pb.CreateFrameMessage):
            idx = self.holder.index(msg.index)
            if idx is None:
                raise ValueError(f"local index not found: {msg.index}")
            f = idx.create_frame_if_not_exists(
                msg.frame, row_label=msg.meta.row_label or "rowID",
                inverse_enabled=msg.meta.inverse_enabled,
                cache_type=msg.meta.cache_type or "ranked",
                cache_size=msg.meta.cache_size or 50000,
                time_quantum=msg.meta.time_quantum)
            self._merge_fields(f, msg.meta.fields_json)
        elif isinstance(msg, pb.DeleteFrameMessage):
            idx = self.holder.index(msg.index)
            if idx is not None:
                idx.delete_frame(msg.frame)
        else:
            raise ValueError(f"unknown message: {type(msg).__name__}")

    @staticmethod
    def _merge_fields(frame, fields_json: str):
        """Converge a frame's integer-field definitions from a peer's
        broadcast/status meta. Idempotent: an existing identical field
        is a no-op; a CONFLICTING redefinition logs and skips rather
        than poisoning schema sync (the peers disagree — an operator
        problem, not one anti-entropy should escalate)."""
        if not fields_json:
            return
        from .bsi.field import FieldSchema, FieldValueError

        for d in json.loads(fields_json):
            try:
                frame.create_field_if_not_exists(FieldSchema.from_dict(d))
            except FieldValueError as e:
                logging.getLogger("pilosa.server").warning(
                    "field sync skipped for frame %r: %s", frame.name, e)

    def _apply_config_schema(self):
        """Declarative [[schema.indexes]] from the TOML config: create
        the declared indexes/frames/BSI fields at open. Idempotent —
        existing objects are kept and missing fields are added to
        existing frames; definitions were already validated at config
        load (config._parse_schema), so a conflicting redefinition of
        an on-disk field is the only error left, and it raises: a node
        must not serve a schema that contradicts its config."""
        from .bsi.field import FieldSchema

        for ix in self.config.schema_indexes:
            opts = {}
            if ix.get("column-label"):
                opts["column_label"] = ix["column-label"]
            idx = self.holder.create_index_if_not_exists(ix["name"], **opts)
            for fr in ix.get("frames", []):
                fopts = {}
                if fr.get("row-label"):
                    fopts["row_label"] = fr["row-label"]
                f = idx.create_frame_if_not_exists(fr["name"], **fopts)
                for fd in fr.get("fields", []):
                    f.create_field_if_not_exists(FieldSchema.from_dict(fd))

    # -- StatusHandler (server.go:306-387) -----------------------------------

    def local_status(self) -> pb.NodeStatus:
        ns = pb.NodeStatus(host=self.host, state=NODE_STATE_UP)
        for info in self.holder.schema():
            idx = self.holder.index(info["name"])
            ii = ns.indexes.add()
            ii.name = info["name"]
            ii.meta.column_label = idx.column_label
            ii.meta.time_quantum = str(idx.time_quantum)
            ii.max_slice = idx.max_slice()
            ii.max_inverse_slice = idx.max_inverse_slice()
            for fi in info.get("frames", []):
                f = idx.frame(fi["name"])
                fr = ii.frames.add()
                fr.name = fi["name"]
                fr.meta.row_label = f.row_label
                fr.meta.inverse_enabled = f.inverse_enabled
                fr.meta.cache_type = f.cache_type
                fr.meta.cache_size = f.cache_size
                fr.meta.time_quantum = str(f.time_quantum)
                if f.fields:
                    fr.meta.fields_json = json.dumps(
                        [s.to_dict()
                         for _, s in sorted(f.fields.items())])
        return ns

    def cluster_status(self) -> pb.ClusterStatus:
        cs = pb.ClusterStatus()
        cs.nodes.append(self.local_status())
        for node in self.cluster.nodes:
            if node.host == self.host:
                continue
            st = self._peer_status.get(node.host)
            if st is not None:
                peer = cs.nodes.add()
                peer.CopyFrom(st)
                peer.state = node.state
            else:
                cs.nodes.add(host=node.host, state=node.state)
        return cs

    def handle_remote_status(self, status: pb.NodeStatus):
        """Merge a peer's schema into the local holder
        (server.go:357-387: auto-create remote indexes/frames, learn
        remote max slices)."""
        for ii in status.indexes:
            idx = self.holder.create_index_if_not_exists(
                ii.name,
                column_label=ii.meta.column_label or "columnID",
                time_quantum=ii.meta.time_quantum)
            idx.set_remote_max_slice(ii.max_slice)
            idx.set_remote_max_inverse_slice(ii.max_inverse_slice)
            for fr in ii.frames:
                f = idx.create_frame_if_not_exists(
                    fr.name, row_label=fr.meta.row_label or "rowID",
                    inverse_enabled=fr.meta.inverse_enabled,
                    cache_type=fr.meta.cache_type or "ranked",
                    cache_size=fr.meta.cache_size or 50000,
                    time_quantum=fr.meta.time_quantum)
                self._merge_fields(f, fr.meta.fields_json)
