"""SPMD multi-host serving driver.

In a multi-host `jax.distributed` deployment (connect_distributed,
mesh.py), a compiled collective only runs when EVERY process enters it
with the same program and arguments — an HTTP query landing on one
node cannot unilaterally run a psum over the global mesh. This driver
is the TPU-native answer to the reference's multi-node query fan-out
(executor.go:1103-1163, HTTP RPC per node): rank 0 faces clients,
encodes each device request as a fixed-shape descriptor, broadcasts it
over the device fabric (jax.experimental.multihost_utils), and ALL
processes resolve it against their holder and execute the same
collective. Replication model: the host-side data dir is replicated
across hosts — kept in sync by routing every WRITE and SCHEMA change
through the same descriptor stream (one total order for writes,
schema, and queries; the reference's ReplicaN=N write fan-out,
executor.go:767-797, becomes a broadcast on the device fabric); DEVICE
memory is what shards, slices spreading over every host's chips via
the global mesh.

Descriptor ops:
    COUNT      Count over a lowered bitmap-op tree (psum collective)
    ROWCOUNTS  per-row totals for TopN (psum collective)
    BSISUM     per-plane-row popcount partials for BSI Sum/Min/Max —
               the weighted-popcount halves are reduced with the same
               psum collectives as ROWCOUNTS/RCSRC (plane rows and
               their existence/sign rows live in ONE view, so slice
               sharding keeps them co-located per device) and the
               2^k weighting folds on the host
    WRITE      SetBit/ClearBit — every rank applies to ITS holder; the
               staged device image then folds the bits in as an
               incremental scatter at the next query's refresh (a
               per-shard device op, no cross-rank collective)
    SCHEMA     a wire-framed broadcast message (CreateIndex/Frame/...)
               applied through each rank's BroadcastHandler
    PQL        a re-serialized PQL write (SetRowAttrs/SetColumnAttrs —
               the reference's own remote-exec encoding, pql/ast.go
               String()) executed by every rank's executor with
               remote=True, replicating the host-side attr stores
    IMPORT     a chunk of bulk-import bits (base64-packed u64 arrays,
               chunked under the fixed descriptor size); every rank
               runs Frame.import_bits, so bulk loads cannot diverge
               the replicas the way a rank-0-only import would
    STOP       release the worker loops

Control flow per request:
    rank 0: serve(...) -> descriptor -> broadcast_one_to_all -> all
    all:    decode -> resolve against local holder -> agreement gate ->
            identical compiled collective (COUNT/ROWCOUNTS only)
    all:    limbs replicated on every process; rank 0 returns the value
Non-zero ranks sit in run_worker() until rank 0 broadcasts a stop.

Bootable via `[cluster] type = "spmd"` in the server TOML (server.py
wires connect_distributed + SpmdServer + the executor seams; the same
wiring the reference does at startup in server/server.go:107-192).
"""

from __future__ import annotations

import json
import threading
import time
from typing import List, Optional, Sequence

import numpy as np

from .. import fault
from ..obs import Histogram, StatMap
from ..obs import costs, profile
from ..obs.health import HEALTH
from ..obs.metrics import TIER_BYTES
from .broadcast import Broadcaster

# Fixed descriptor size: broadcast payloads must be identical shapes on
# every rank. 64 KB bounds the slice list of a masked query.
_DESC_BYTES = 65536

# IMPORT timestamp "absent" sentinel: outside the valid epoch range so
# a real 1970-01-01T00:00:00 (epoch 0) survives the round-trip.
_TS_NONE = np.iinfo(np.int64).min

_OP_COUNT = 1
_OP_STOP = 2
_OP_ROWCOUNTS = 3
_OP_WRITE = 4
_OP_SCHEMA = 5
_OP_PQL = 6
_OP_IMPORT = 7
_OP_RCSRC = 8  # src / tanimoto row-count collectives (kind field)
_OP_BSISUM = 9  # BSI plane-row count partials (psum collective)

_OP_NAMES = {
    _OP_COUNT: "count",
    _OP_STOP: "stop",
    _OP_ROWCOUNTS: "rowcounts",
    _OP_WRITE: "write",
    _OP_SCHEMA: "schema",
    _OP_PQL: "pql",
    _OP_IMPORT: "import",
    _OP_RCSRC: "rcsrc",
    _OP_BSISUM: "bsisum",
}

# Descriptor-plane telemetry, process-wide (one SpmdServer per process,
# but module scope keeps the /metrics collector free of server plumbing):
#   dispatch:<op>              descriptors executed, by op name
#   veto:not_ready             gate vetoes — this rank had no program
#   veto:format_disagreement   gate vetoes — ranks resolved different
#                              programs / staged formats
SPMD_STATS = StatMap()

# Per-op descriptor wall time (resolve + gate + collective), µs.
_OP_HISTS: dict = {}
_OP_HISTS_MU = threading.Lock()


def op_hist(op: str) -> Histogram:
    h = _OP_HISTS.get(op)
    if h is None:
        with _OP_HISTS_MU:
            h = _OP_HISTS.setdefault(op, Histogram())
    return h


def op_hist_snapshot() -> dict:
    with _OP_HISTS_MU:
        return dict(_OP_HISTS)


def _encode(obj: dict) -> np.ndarray:
    raw = json.dumps(obj).encode()
    TIER_BYTES.inc("ici", len(raw))
    # Per-call ICI attribution mirroring the HTTP client tap.
    costs.LEDGER.charge("net_ici_bytes", len(raw))
    if len(raw) > _DESC_BYTES:
        raise ValueError(f"descriptor too large: {len(raw)} bytes")
    buf = np.zeros(_DESC_BYTES, dtype=np.uint8)
    buf[: len(raw)] = np.frombuffer(raw, dtype=np.uint8)
    return buf


def _decode(buf: np.ndarray) -> dict:
    raw = bytes(np.asarray(buf, dtype=np.uint8))
    desc = json.loads(raw[: raw.index(b"\x00")] if b"\x00" in raw else raw)
    # A corrupt payload that still parses as json must not dispatch as
    # a half-valid descriptor: the op tag is the minimum contract
    # (bool excluded — json true would otherwise dispatch as op 1).
    op = desc.get("op") if isinstance(desc, dict) else None
    if not isinstance(op, int) or isinstance(op, bool):
        raise ValueError("descriptor missing integer op tag")
    return desc


class SpmdBroadcaster(Broadcaster):
    """Broadcaster whose transport is the SPMD descriptor stream: a
    schema message broadcast rides the same total order as writes and
    queries, so a worker can never run a query descriptor against a
    schema it hasn't applied yet. Rank 0 only — workers apply, they
    never originate (their handler's mutating routes shouldn't be used;
    originating from a worker would require a reverse channel)."""

    def __init__(self, spmd: "SpmdServer"):
        self._spmd = spmd

    def send_sync(self, msg) -> None:
        # A broadcast ORIGINATED by descriptor execution (e.g. a write
        # growing a view's maxSlice fires CreateSliceMessage from
        # inside _execute_write) must not re-enter the stream: every
        # rank is executing the same descriptor and derives the same
        # change locally — re-broadcasting would deadlock on _mu.
        if getattr(self._spmd._local, "in_exec", False):
            return
        self._spmd.schema(msg)

    def send_async(self, msg) -> None:
        self.send_sync(msg)


class SpmdServer:
    """One process's half of the SPMD serving pact.

    Every process constructs this over its own (replicated-data) holder;
    rank 0 calls count/top_n/write/schema per client request, other
    ranks call run_worker() once. All processes must create their
    MeshManager over the same GLOBAL mesh (the default after
    connect_distributed). `apply_message` must be set (by server
    wiring) to the node's BroadcastHandler receive_message before
    SCHEMA descriptors flow."""

    def __init__(self, holder, mesh=None):
        import threading

        import jax

        from .serve import MeshManager

        self.rank = jax.process_index()
        self.manager = MeshManager(holder, mesh=mesh)
        # Descriptor-plane invariant: every rank must make the SAME
        # restage-vs-incremental pick for the same descriptor, or a
        # capacity-shrinking restage on one rank diverges pool shapes
        # and the fingerprint gate rejects this view's collectives
        # forever (correct but a silent performance cliff — ADVICE r4).
        # Per-rank measured timings can't satisfy that; switch the
        # manager to the count-based deterministic policy.
        self.manager.deterministic_gate = True
        self.holder = holder
        self.apply_message = None  # set by server wiring (receive_message)
        self.apply_query = None    # set by server wiring: (index, parsed
        #                            pql.Query) -> executor.execute with
        #                            remote=True
        # AOT-compiled programs keyed by (kind, sig, shapes): compilation
        # must happen BEFORE the agreement gate (see _execute_count), and
        # jit only compiles at first call — lower().compile() forces it.
        self._compiled: dict = {}
        # Serializes descriptor broadcast + gate + execute: the HTTP
        # front-end is threaded, and two interleaved
        # broadcast_one_to_all collectives from rank 0 would pair
        # nondeterministically with the workers' sequential loop.
        self._mu = threading.Lock()
        # Per-thread "inside descriptor execution" flag — read by
        # SpmdBroadcaster to swallow re-entrant broadcasts.
        self._local = threading.local()

    def _run(self, desc: dict):
        """Execute one descriptor with the re-entrancy flag set.

        The whole descriptor — collective broadcast included on the
        dispatch side — runs under one in-flight health record: a rank
        that never enters its collective wedges every peer inside
        broadcast_one_to_all, and that blocked thread is exactly what
        the watchdog's "spmd-dispatch" bound must catch.
        """
        op = _OP_NAMES.get(desc.get("op"), "unknown")
        SPMD_STATS.inc(f"dispatch:{op}")
        t0 = time.monotonic()
        self._local.in_exec = True
        try:
            with HEALTH.inflight("spmd-dispatch", op, base=30.0):
                # Deterministic hang seam INSIDE the bracket
                # (watchdog.stall:delay=...,subsystem=spmd-dispatch):
                # the injected delay must be a tracked, judgeable op.
                fault.point("watchdog.stall",
                            subsystem="spmd-dispatch", op=op)
                return self._dispatch(desc)
        finally:
            self._local.in_exec = False
            op_hist(op).observe((time.monotonic() - t0) * 1e6)

    # -- rank 0 --------------------------------------------------------------

    def count(self, index: str, shape, leaves: List[tuple],
              slices: Sequence[int], num_slices: int) -> Optional[int]:
        """Broadcast + execute one Count collective. Rank 0 only."""
        assert self.rank == 0, "count() drives from rank 0; others run_worker()"
        desc = {
            "op": _OP_COUNT,
            "index": index,
            "shape": shape,
            "leaves": [list(leaf) for leaf in leaves],
            "slices": list(map(int, slices)),
            "num_slices": int(num_slices),
        }
        with self._mu:
            self._broadcast(desc)
            return self._run(desc)

    def row_counts(self, index: str, frame: str, view: str,
                   slices: Sequence[int], num_slices: int):
        """Broadcast + execute one per-row-counts collective (the TopN
        device half). Returns (row_ids, counts int64) or None. Rank 0
        only."""
        assert self.rank == 0
        desc = {
            "op": _OP_ROWCOUNTS,
            "index": index,
            "frame": frame,
            "view": view,
            "slices": list(map(int, slices)),
            "num_slices": int(num_slices),
        }
        with self._mu:
            self._broadcast(desc)
            return self._run(desc)

    def top_n(self, index: str, frame: str, view: str,
              slices: Sequence[int], num_slices: int, n: int,
              row_ids: Sequence[int], min_threshold: int,
              src=None, attr_predicate=None, tanimoto_threshold: int = 0):
        """TopN — every argument form — from one descriptor-broadcast
        collective + the SAME host-side ranking the single-host path
        uses (serve.rank_pairs / serve.tanimoto_rank, so the two cannot
        drift). `src` is a lowered (shape, leaves) bitmap-op tree; with
        tanimoto_threshold the fused three-vector program serves the
        band math. Rank 0 only."""
        from .serve import combine_limbs, rank_pairs, tanimoto_rank

        if tanimoto_threshold > 0:
            if src is None:
                return None
            out = self._rcsrc("tan", index, frame, view, src, slices,
                              num_slices)
            if out is None:
                return None
            all_rows, padded, limbs = out
            if limbs is None:
                return []  # staged view has no rows
            r = len(all_rows)
            with profile.phase("rank"):
                full = combine_limbs(limbs, r)
                inter = combine_limbs(limbs, r, start=padded)
                src_count = int(combine_limbs(limbs, 1,
                                              start=2 * padded)[0])
                return tanimoto_rank(all_rows, full, inter, src_count,
                                     0 if row_ids else n,
                                     tanimoto_threshold, row_ids,
                                     attr_predicate)
        if src is not None:
            out = self._rcsrc("rcs", index, frame, view, src, slices,
                              num_slices)
            if out is None:
                return None
            all_rows, _padded, limbs = out
            with profile.phase("rank"):
                counts = (np.zeros(0, dtype=np.int64) if limbs is None
                          else combine_limbs(limbs, len(all_rows)))
        else:
            out = self.row_counts(index, frame, view, slices, num_slices)
            if out is None:
                return None
            all_rows, counts = out
        with profile.phase("rank"):
            return rank_pairs(all_rows, counts, n, row_ids, min_threshold,
                              attr_predicate)

    def _rcsrc(self, kind: str, index: str, frame: str, view: str,
               src, slices: Sequence[int], num_slices: int):
        """Broadcast + execute one src-tree row-count collective
        (kind "rcs" = src intersection counts, "tan" = the fused
        three-vector tanimoto program). Returns (row_ids, padded,
        limbs np.ndarray | None) or None. Rank 0 only."""
        assert self.rank == 0
        src_shape, src_leaves = src
        desc = {
            "op": _OP_RCSRC,
            "kind": kind,
            "index": index,
            "frame": frame,
            "view": view,
            "shape": src_shape,
            "leaves": [list(leaf) for leaf in src_leaves],
            "slices": list(map(int, slices)),
            "num_slices": int(num_slices),
        }
        with self._mu:
            self._broadcast(desc)
            return self._run(desc)

    def bsi_sum(self, index: str, frame: str, view: str,
                slices: Sequence[int], num_slices: int, src=None):
        """Broadcast + execute one BSISUM collective: per-plane-row
        popcount partials psum-reduced over the global mesh — the
        device half of a sharded BSI Sum/Min/Max (executor folds the
        2^k plane weights and the sign split on the host, exactly as
        the single-host path does via bsi_plane_counts). With `src` a
        lowered (shape, leaves) filter tree, counts are restricted to
        the filter — the RCSRC program. Returns {row_id: count} or
        None. Rank 0 only."""
        assert self.rank == 0
        desc = {
            "op": _OP_BSISUM,
            "index": index,
            "frame": frame,
            "view": view,
            "slices": list(map(int, slices)),
            "num_slices": int(num_slices),
        }
        if src is not None:
            src_shape, src_leaves = src
            desc["kind"] = "rcs"
            desc["shape"] = src_shape
            desc["leaves"] = [list(leaf) for leaf in src_leaves]
        with self._mu:
            self._broadcast(desc)
            return self._run(desc)

    def write(self, index: str, frame: str, row_id: int, col_id: int,
              timestamp: Optional[str], clear: bool) -> bool:
        """Broadcast one bit mutation; EVERY rank (this one included)
        applies it to its own holder, keeping the replicated data dirs
        convergent and totally ordered with queries. Returns the local
        changed flag (identical on every rank given identical
        replicas). Rank 0 only."""
        assert self.rank == 0
        desc = {
            "op": _OP_WRITE,
            "index": index,
            "frame": frame,
            "row": int(row_id),
            "col": int(col_id),
            "ts": timestamp,
            "clear": bool(clear),
        }
        with self._mu:
            self._broadcast(desc)
            return self._run(desc)

    def execute_pql(self, index: str, pql: str):
        """Broadcast a re-serialized PQL write; every rank (this one
        included) executes it against its own holder with remote=True.
        Used for attr mutations, whose state lives in host-side stores
        the WRITE bit descriptors don't cover. Rank 0 only."""
        assert self.rank == 0
        desc = {"op": _OP_PQL, "index": index, "pql": pql}
        with self._mu:
            self._broadcast(desc)
            return self._run(desc)

    # Bits per IMPORT chunk: 3 u64 arrays (row, col, ts) base64-encoded
    # must fit _DESC_BYTES with JSON overhead. 24 B/bit raw -> 32 B/bit
    # in base64; 1500 bits ~= 48 KB encoded.
    _IMPORT_CHUNK = 1500

    def import_bits(self, index: str, frame: str, rows, cols,
                    timestamps=None) -> None:
        """Broadcast a bulk import in chunks; every rank applies each
        chunk to its own holder (Frame.import_bits — container
        creation, time-view fan-out, and forced snapshot semantics all
        evaluate identically per rank). Rank 0 only."""
        assert self.rank == 0
        import base64

        rows = np.asarray(rows, dtype=np.uint64)
        cols = np.asarray(cols, dtype=np.uint64)
        from datetime import timezone as _tz

        # Naive datetimes here are UTC by convention (the handler
        # decodes wire timestamps as naive-UTC); t.timestamp() would
        # read them in the HOST timezone and shift every bit's
        # time-quantum view on non-UTC machines. None is encoded as
        # int64 min — 0 is a legitimate epoch timestamp (1970-01-01)
        # and must keep its time-quantum view fan-out.
        ts = (np.zeros(0, dtype=np.int64) if timestamps is None
              else np.asarray(
                  [_TS_NONE if t is None
                   else int(t.replace(tzinfo=_tz.utc).timestamp())
                   for t in timestamps],
                  dtype=np.int64))
        for i in range(0, max(len(rows), 1), self._IMPORT_CHUNK):
            desc = {
                "op": _OP_IMPORT,
                "index": index,
                "frame": frame,
                "rows": base64.b64encode(
                    rows[i:i + self._IMPORT_CHUNK].tobytes()).decode(),
                "cols": base64.b64encode(
                    cols[i:i + self._IMPORT_CHUNK].tobytes()).decode(),
                "ts": base64.b64encode(
                    ts[i:i + self._IMPORT_CHUNK].tobytes()).decode(),
            }
            with self._mu:
                self._broadcast(desc)
                self._run(desc)

    def schema(self, msg) -> None:
        """Broadcast one wire schema message (CreateIndex/CreateFrame/
        Delete.../CreateSlice) through the descriptor stream. Rank 0
        applies locally through the same path as workers (idempotent —
        the handler already applied the originating change to rank 0's
        holder before broadcasting, reference handler.go semantics)."""
        assert self.rank == 0
        from ..wire import marshal_message

        import base64

        desc = {
            "op": _OP_SCHEMA,
            "raw": base64.b64encode(marshal_message(msg)).decode(),
        }
        with self._mu:
            self._broadcast(desc)
            self._run(desc)

    def stop(self):
        """Release every worker loop. Rank 0 only."""
        assert self.rank == 0
        with self._mu:
            self._broadcast({"op": _OP_STOP})

    # -- all ranks -----------------------------------------------------------

    def run_worker(self):
        """Follow rank 0's descriptors until stop. Ranks != 0.

        Errors are contained per descriptor: a raising worker that
        left the loop would wedge every other rank's next collective
        (broadcast_one_to_all blocks until ALL processes enter), so a
        failed execute logs and keeps following."""
        assert self.rank != 0, "rank 0 drives; workers follow"
        from ..obs import get_logger

        log = get_logger("spmd")
        # Event-driven follower: interval=None so blocking in the
        # collective (no descriptor pending) never reads as a stall —
        # the heartbeat exists for stack attribution only.
        hb = HEALTH.register("spmd-worker", interval=None)
        while True:
            # The COLLECTIVE runs outside any catch: a distributed-
            # runtime error (dead coordinator, heartbeat loss — even
            # one raised as ValueError inside jax) must propagate and
            # end this worker loudly, never hot-spin re-entering a
            # failing collective.
            raw = self._broadcast_raw(None)
            try:
                desc = _decode(raw)
            except (ValueError, KeyError) as e:  # corrupt descriptor
                # broadcast_one_to_all hands EVERY rank the same bytes,
                # so a payload that fails to DECODE fails identically
                # everywhere — all ranks log and stay aligned for the
                # next descriptor rather than one rank leaving the loop
                # and wedging every later collective.
                log.warning("spmd worker: undecodable descriptor: %s", e)
                continue
            if desc["op"] == _OP_STOP:
                HEALTH.unregister("spmd-worker")
                return
            try:
                hb.beat()
                self._run(desc)
            except Exception as e:  # noqa: BLE001 — stay in the pact
                log.warning("spmd worker: descriptor failed: %s", e)

    def _dispatch(self, desc: dict):
        op = desc["op"]
        if op == _OP_COUNT:
            return self._execute_count(desc)
        if op == _OP_ROWCOUNTS:
            return self._execute_rowcounts(desc)
        if op == _OP_RCSRC:
            return self._execute_rcsrc(desc)
        if op == _OP_BSISUM:
            return self._execute_bsisum(desc)
        if op == _OP_WRITE:
            return self._execute_write(desc)
        if op == _OP_SCHEMA:
            return self._execute_schema(desc)
        if op == _OP_PQL:
            return self._execute_pql(desc)
        if op == _OP_IMPORT:
            return self._execute_import(desc)
        raise ValueError(f"unknown descriptor op: {op}")

    def _broadcast_raw(self, desc: Optional[dict]) -> np.ndarray:
        """The collective half alone — callers that must distinguish a
        transport failure (propagate, die loudly) from a decode failure
        (symmetric, survivable) run the two halves separately."""
        from jax.experimental import multihost_utils

        payload = _encode(desc) if desc is not None else np.zeros(
            _DESC_BYTES, dtype=np.uint8)
        return multihost_utils.broadcast_one_to_all(payload)

    def _broadcast(self, desc: Optional[dict]) -> dict:
        return _decode(self._broadcast_raw(desc))

    # -- descriptor execution (symmetric on every rank) ----------------------

    def _gate(self, fingerprint_blob: Optional[bytes]) -> bool:
        """Program-agreement gate: allgather a deterministic hash of
        the locally-resolved program; the collective runs only when
        every rank resolved the IDENTICAL program, else all skip
        together (a rank entering a psum alone — or with mismatched
        shapes — hangs the whole mesh)."""
        import zlib

        from jax.experimental import multihost_utils

        fp = (np.int64(0) if fingerprint_blob is None
              else np.int64(zlib.crc32(fingerprint_blob) + 1))
        fps = multihost_utils.process_allgather(fp)  # (num_processes,)
        # Veto accounting distinguishes the two skip causes: this rank
        # (or a peer — every rank that gathered a 0 reports not_ready)
        # had no program vs all ranks resolved programs that DISAGREE.
        # The allgather above always runs regardless — the gate itself
        # is a collective, and vetoing without it would desync ranks.
        if int(fp) == 0 or not np.all(fps != 0):
            SPMD_STATS.inc("veto:not_ready")
            return False
        if not np.all(fps == fps[0]):
            SPMD_STATS.inc("veto:format_disagreement")
            return False
        return True

    def _execute_count(self, desc: dict) -> Optional[int]:
        """Resolve, AGREE on the program, then execute.

        Resolution can fail — or succeed with a DIFFERENT program — on
        one rank alone (replicated data dirs momentarily out of sync: a
        lagging replica stages a different pool capacity), hence the
        fingerprint gate. The fingerprint also covers the PER-SHARD
        sparse/dense format picks of every touched view
        (staged_format_blob): two ranks whose stagers disagreed on a
        shard's layout must skip together rather than enter a
        collective with mismatched programs."""
        import zlib

        from .mesh import combine_count

        leaves = [tuple(leaf) for leaf in desc["leaves"]]
        compiled = blob = None
        try:
            prepared = self.manager._count_args(
                desc["index"], desc["shape"], leaves, desc["slices"],
                desc["num_slices"])
            if prepared is not None:
                # Compile BEFORE the gate (jit compiles at first CALL,
                # so force it with AOT lowering): a per-rank compile
                # failure must read as not-ready so every rank skips —
                # compiling after agreement would let warm-cached peers
                # enter the psum while this rank bails.
                # coarse_t (the single-host whole-row fast path) is
                # deliberately unused here: SPMD ranks agree on the
                # GENERAL program, whose eligibility can't diverge
                # between momentarily out-of-sync replicas.
                sig, words_t, idx_t, hit_t, _coarse_t, mask = prepared
                shapes = tuple(
                    [tuple(w.shape) for w in words_t]
                    + [tuple(i.shape) for i in idx_t]
                    + [tuple(mask.shape)])
                ckey = ("count", sig, shapes)
                compiled = self._compiled.get(ckey)
                if compiled is None:
                    fn = self.manager._count_program(
                        "general", sig, len(idx_t), 1)
                    compiled = fn.lower(words_t, idx_t, hit_t,
                                        mask).compile()
                    self._compiled[ckey] = compiled
                fmt = self.manager.staged_format_blob(
                    desc["index"], {(lf[0], lf[1]) for lf in leaves})
                blob = json.dumps(["count", sig, list(shapes),
                                   int(zlib.crc32(fmt))]).encode()
        except Exception:  # noqa: BLE001 — counted as not-ready below
            compiled = None
        if not self._gate(blob if compiled is not None else None):
            return None  # every rank skips: no divergent collective
        # Past the gate, all ranks run the identical program; a runtime
        # failure here hits every rank symmetrically.
        out = combine_count(
            np.asarray(compiled(words_t, idx_t, hit_t, mask))[:, 0])
        self.manager.stats["count"] += 1
        return out

    def _execute_rowcounts(self, desc: dict):
        """ROWCOUNTS: per-row totals over the global mesh. The
        fingerprint covers the staged shapes AND the dense row table —
        misaligned row_ids across ranks would psum different rows into
        the same position."""
        import zlib

        from .mesh import compile_serve_row_counts

        compiled = blob = None
        try:
            out = self.manager._row_counts_args(
                desc["index"], desc["frame"], desc["view"], desc["slices"],
                desc["num_slices"])
            if out is not None and len(out) == 2:
                # Rowless view everywhere: agree on "empty" (crc of the
                # marker) so every rank returns without a collective.
                blob = b"rowcounts-empty"
                if not self._gate(blob):
                    return None
                return out[1], np.zeros(0, dtype=np.int64)
            if out is not None:
                row_ids, sharded, dev_mask, padded, _epoch = out
                ckey = ("rc", padded, tuple(sharded.words.shape))
                compiled = self._compiled.get(ckey)
                if compiled is None:
                    fn = self.manager._get_or_compile(
                        self.manager._rowcount_fns, padded,
                        lambda: compile_serve_row_counts(
                            self.manager.mesh, padded))
                    compiled = fn.lower(sharded, dev_mask).compile()
                    self._compiled[ckey] = compiled
                fmt = self.manager.staged_format_blob(
                    desc["index"], {(desc["frame"], desc["view"])})
                blob = json.dumps(
                    ["rc", padded, list(sharded.words.shape),
                     int(zlib.crc32(np.ascontiguousarray(row_ids))),
                     int(zlib.crc32(fmt))]
                ).encode()
        except Exception:  # noqa: BLE001 — not-ready below
            compiled = None
        if not self._gate(blob if compiled is not None else None):
            return None
        from .serve import combine_limbs

        limbs = np.asarray(compiled(sharded, dev_mask))
        counts = combine_limbs(limbs, len(row_ids))
        self.manager.stats["topn"] += 1
        return row_ids, counts

    def _execute_rcsrc(self, desc: dict):
        """RCSRC: src-tree row counts ("rcs") or the fused tanimoto
        three-vector program ("tan") over the global mesh. Resolution +
        AOT compile BEFORE the agreement gate (the _execute_count
        pattern); the fingerprint covers the program shape AND the
        dense row table AND the src tree, so ranks with momentarily
        divergent replicas skip together instead of entering a
        mismatched collective."""
        import zlib

        from .mesh import (compile_serve_row_counts_src,
                           compile_serve_row_counts_tanimoto)

        kind = desc["kind"]
        compiler = (compile_serve_row_counts_tanimoto if kind == "tan"
                    else compile_serve_row_counts_src)
        src = (desc["shape"], [tuple(leaf) for leaf in desc["leaves"]])
        compiled = blob = None
        try:
            prepared = self.manager._src_counts_args(
                desc["index"], desc["frame"], desc["view"], src,
                desc["slices"], desc["num_slices"])
            if prepared is not None and prepared[0] == "empty":
                # Rowless view everywhere: agree on "empty", no
                # collective (the _execute_rowcounts pattern).
                blob = b"rcsrc-empty-" + kind.encode()
                if not self._gate(blob):
                    return None
                return prepared[1], 0, None
            if prepared is not None:
                (sv, sharded, words_t, idx_t, hit_t, dev_mask, padded,
                 sig, _epoch) = prepared
                # EVERY argument shape the lowering specializes on must
                # be in the cache key AND the fingerprint — a shape
                # left out (e.g. the gather idx/hit arrays) would let
                # mismatched ranks pass the gate and enter divergent
                # collectives, or an intra-rank cache hit return an
                # executable lowered for stale shapes.
                shapes = (tuple(sharded.keys.shape),
                          tuple(sharded.words.shape),
                          tuple(tuple(w.shape) for w in words_t),
                          tuple(tuple(i.shape) for i in idx_t),
                          tuple(tuple(hh.shape) for hh in hit_t),
                          tuple(dev_mask.shape))
                ckey = (kind, sig, padded, shapes)
                compiled = self._compiled.get(ckey)
                if compiled is None:
                    fn = self.manager._get_or_compile(
                        self.manager._tanimoto_fns if kind == "tan"
                        else self.manager._rowcount_src_fns,
                        (sig, len(idx_t), padded),
                        lambda: compiler(self.manager.mesh,
                                         json.loads(sig),
                                         len(idx_t), padded))
                    compiled = fn.lower(sharded.keys, sharded.words,
                                        words_t, idx_t, hit_t,
                                        dev_mask).compile()
                    self._compiled[ckey] = compiled
                fmt = self.manager.staged_format_blob(
                    desc["index"], {(desc["frame"], desc["view"])})
                blob = json.dumps(
                    [kind, sig, padded, repr(shapes),
                     int(zlib.crc32(np.ascontiguousarray(sv.row_ids))),
                     int(zlib.crc32(fmt))]
                ).encode()
        except Exception:  # noqa: BLE001 — counted as not-ready below
            compiled = None
        if not self._gate(blob if compiled is not None else None):
            return None
        limbs = np.asarray(compiled(sharded.keys, sharded.words, words_t,
                                    idx_t, hit_t, dev_mask))
        self.manager.stats["topn"] += 1
        return sv.row_ids, padded, limbs

    def _execute_bsisum(self, desc: dict):
        """BSISUM: the per-plane-row count partials a sharded BSI
        aggregate needs, as a {row_id: count} dict (the
        MeshManager.bsi_plane_counts contract). The collective halves
        ARE the ROWCOUNTS / RCSRC programs — a BSI view's plane,
        existence and sign rows are ordinary rows of one staged view,
        so the same psum-of-popcounts serves them and the gate
        fingerprints (shapes + row table + per-shard formats) carry
        over unchanged."""
        if "shape" in desc:
            out = self._execute_rcsrc(desc)
            if out is None:
                return None
            row_ids, _padded, limbs = out
            if limbs is None:
                counts = np.zeros(0, dtype=np.int64)
            else:
                from .serve import combine_limbs

                counts = combine_limbs(limbs, len(row_ids))
        else:
            out = self._execute_rowcounts(desc)
            if out is None:
                return None
            row_ids, counts = out
        self.manager.stats.inc("bsi_aggregate")
        return {int(r): int(c) for r, c in zip(row_ids, counts)}

    def _execute_write(self, desc: dict) -> bool:
        """WRITE: apply the bit to THIS rank's holder (host-side; the
        staged device image folds it in as an incremental scatter at
        the next query's refresh). No collective, no gate — each rank
        applies independently and the descriptor order is the total
        order."""
        idx = self.holder.index(desc["index"])
        if idx is None:
            return False
        f = idx.frame(desc["frame"])
        if f is None:
            return False
        if desc["clear"]:
            return bool(f.clear_bit(desc["row"], desc["col"]))
        ts = None
        if desc["ts"]:
            from ..executor import parse_time

            ts = parse_time(desc["ts"])
        return bool(f.set_bit(desc["row"], desc["col"], ts))

    # Calls a PQL descriptor may carry: host-side attr writes only. A
    # read (e.g. Count) riding this op would re-enter SpmdServer._mu
    # via executor -> _spmd.count on rank 0 (non-reentrant lock) and
    # deadlock the whole cluster — enforce, don't assume.
    _PQL_ALLOWED = frozenset({"SetRowAttrs", "SetColumnAttrs"})

    def _execute_pql(self, desc: dict):
        """PQL: run the re-serialized write through this rank's
        executor (remote=True: apply locally, never re-forward or
        re-broadcast — and worker ranks' write-rejection guard admits
        descriptor-applied writes)."""
        if self.apply_query is None:
            raise RuntimeError("SpmdServer.apply_query not wired")
        from ..pql import parse_string

        query = parse_string(desc["pql"])
        bad = [c.name for c in query.calls
               if c.name not in self._PQL_ALLOWED]
        if bad:
            raise ValueError(
                f"PQL descriptor carries non-attr-write calls {bad}; "
                f"only {sorted(self._PQL_ALLOWED)} may ride this op")
        out = self.apply_query(desc["index"], query)
        return out[0] if out else None

    def _execute_import(self, desc: dict) -> None:
        """IMPORT: apply one chunk of bulk bits to THIS rank's holder."""
        import base64
        from datetime import datetime, timezone

        idx = self.holder.index(desc["index"])
        if idx is None:
            return
        f = idx.frame(desc["frame"])
        if f is None:
            return
        rows = np.frombuffer(base64.b64decode(desc["rows"]), dtype=np.uint64)
        cols = np.frombuffer(base64.b64decode(desc["cols"]), dtype=np.uint64)
        ts_raw = np.frombuffer(base64.b64decode(desc["ts"]), dtype=np.int64)
        timestamps = None
        if len(ts_raw):
            timestamps = [
                datetime.fromtimestamp(t, timezone.utc).replace(tzinfo=None)
                if t != _TS_NONE else None for t in ts_raw]
        f.import_bits(rows, cols, timestamps)

    def _execute_schema(self, desc: dict) -> None:
        """SCHEMA: unmarshal the wire message and apply it through the
        node's BroadcastHandler (server.receive_message)."""
        import base64

        from ..wire import unmarshal_message

        if self.apply_message is None:
            raise RuntimeError("SpmdServer.apply_message not wired")
        msg = unmarshal_message(base64.b64decode(desc["raw"]))
        try:
            self.apply_message(msg)
        except ValueError:
            # e.g. CreateSlice for an index this rank hasn't created
            # yet on a fresh boot — the schema descriptor that creates
            # it is earlier in the stream, so this is only reachable
            # when rank 0 itself re-applies its own originating change.
            pass
