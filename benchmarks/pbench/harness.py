"""One run of one cell: data from --seed, the server child, warm-up, the
drained window, the kill, the comparison with the reference and the look at
the disk, the result line.

The parent (this process) stays off JAX while the server child lives: the
child alone holds the chip.
"""

from __future__ import annotations

import json
import math
import os
import pickle
import shutil
import signal
import sys
import threading
import time
import tomllib
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

from . import (datagen, durable, layers, names, reference, schedule,
               xplane)
from .client import Clients
from .server import BENCH_DIR, REPO, Server, ServerError
from .window import Done, reduce_window

OUT_DIR = os.path.join(BENCH_DIR, "out")
TRACE_SECONDS = 5.0
# Controls that are the program itself with a path of its own switched on
# (the others are the reference in the program's place: pbench/control.py).
PROGRAM_CONTROLS = {
    # Acknowledge a SetBit before it is on disk: what deferring the WAL
    # commit would do. The program's no-fsync policy with its buffer kept.
    "lost_wal": {"toml": '\n[storage]\nfsync-policy = "never"\n',
                 "env": {"PILOSA_TPU_WAL_SIM_POWER_LOSS": "1"}},
}


class NoChip(Exception):
    """No accelerator, or not the chips the cell asks for: no result."""


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(workload: str) -> dict:
    """The cell's entry, configuration and traffic, each found by the name
    BENCHMARK.json gives it."""
    bench = load_json(REPO, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json "
                         f"(known: {sorted(cells)})")
    cell = cells[workload]
    cfg_entry = next(c for c in bench["configs"]
                     if c["name"] == cell["config"])
    config = load_json(REPO, cfg_entry["file"])
    traffic = load_json(BENCH_DIR, "traffic", cell["traffic"] + ".json")

    def applies(m: dict) -> bool:
        return "workloads" not in m or workload in m["workloads"]

    return {
        "cell": cell, "config": config, "traffic": traffic,
        "config_dir": os.path.dirname(os.path.join(REPO, cfg_entry["file"])),
        "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
        "per_layer": [m for m in bench["per_layer"] if applies(m)],
    }


# -- the plan: abstract ops of both streams, bound by --seed -----------------


class PlanFrame(NamedTuple):
    name: str
    n_rows: int
    perm: Sequence[int]  # Zipf rank -> row id, from --seed


class Plan:
    """The ops of a run. `frames` holds the configuration's frames by name,
    the first of them the default: the one an op that names no frame draws
    its ranks over. `bind` is the kind's own, or None for `schedule.bind`;
    `config` and `seed` are there for it."""

    def __init__(self, config: dict, traffic: dict, seed: int):
        self.config, self.seed = config, seed
        self.frames = {
            f["name"]: PlanFrame(f["name"], int(f["rows"]),
                                 schedule.row_permutation(seed, int(f["rows"]),
                                                          nth))
            for nth, f in enumerate(names.frames(config))}
        self.default = next(iter(self.frames.values()))
        self.bind = getattr(names.kind(config), "bind", None)
        if self.bind is None and any("frames" in o for o in traffic["ops"]):
            raise ValueError("the traffic names frames: the configuration's "
                             "kind has to bind them (pbench/kinds)")
        rows = {f.name: f.n_rows for f in self.frames.values()}
        warm = traffic["warmup"]
        clients = int(traffic["clients"])
        per_client = math.ceil(warm["ops_per_round"] / clients)
        self.warm_per_client = per_client
        lengths = {"window": int(traffic["max_ops"]),
                   "warmup": per_client * clients * int(warm["max_rounds"])}
        self.abstract = {
            s: schedule.Template(traffic, self.default.n_rows, s,
                                 rows).ops(0, n)
            for s, n in lengths.items()}
        self.abstract["burst"] = schedule.Template(
            traffic, self.default.n_rows, frames=rows).bursts(
                clients, int(warm.get("bursts", 0)))
        self.burst_rounds = len(self.abstract["burst"]) // clients
        self.columns: Dict[tuple, int] = {}
        self._bound: Dict[tuple, schedule.BoundOp] = {}

    def rows(self, op: schedule.AbstractOp) -> List[Tuple[str, int]]:
        """(frame name, row id) of each of the op's ranks."""
        out = []
        for k, rank in enumerate(op.ranks):
            f = self.frames[op.frames[k]] if k < len(op.frames) \
                else self.default
            out.append((f.name, int(f.perm[rank])))
        return out

    def updates(self) -> List[tuple]:
        """(stream, index, row) of every update, warm-up first."""
        return [(s, i, self.rows(op)[0][1])
                for s in ("warmup", "window")
                for i, op in enumerate(self.abstract[s])
                if op.kind == "update"]

    def src_rows(self) -> List[int]:
        return sorted({self.rows(op)[0][1]
                       for ops in self.abstract.values() for op in ops
                       if op.kind == "topn" and op.ranks})

    def assign_columns(self, candidates, can_write) -> None:
        """Each update takes the first unused candidate column that the
        reference lets its row write: the bit is clear, so every SetBit
        changes a bit, and no container has to be made for it. An update
        that names its frame takes from that frame's candidates
        (`candidates[frame]`) and asks `can_write(row, column, frame)`."""
        free: Dict[tuple, List[int]] = {}  # () or (frame,) -> candidates
        for stream, i, row in self.updates():
            named = self.abstract[stream][i].frames[:1]
            if named not in free:
                free[named] = [int(c) for c in (
                    candidates[named[0]] if named else candidates)]
            for j, c in enumerate(free[named]):
                if can_write(row, c, *named):
                    self.columns[(stream, i)] = free[named].pop(j)
                    break
            else:
                raise RuntimeError("ran out of writable candidate columns")

    def op_at(self, stream: str, i: int) -> Optional[schedule.BoundOp]:
        ops = self.abstract[stream]
        if i >= len(ops):
            return None
        got = self._bound.get((stream, i))
        if got is None:
            column = self.columns.get((stream, i))
            got = self._bound[(stream, i)] = (
                self.bind(ops[i], self, column) if self.bind else
                schedule.bind(ops[i], self.default.perm, self.default.name,
                              self.default.n_rows, column))
        return got


# -- the comparison that decides `correct` -----------------------------------


def compare(ref, phases: Dict[str, List[Done]], plan: Plan) -> dict:
    """Every answer of every phase against the reference. Returns the numbers
    compared and the window ops whose answer was wrong. The reference is
    handed reads as (key, t_send, t_done, answer) and writes as (row, column,
    t_send, t_ack), with the frame written as a fifth where the op's kind
    named it (`BoundOp.frame`); `acked` likewise (row, column[, frame])."""
    reads, writes, where, acked = [], [], [], []
    wrong_seqs, examples = set(), []
    wrong = unanswered = not_judged = 0

    def flag(phase: str, seq: int, text: str) -> None:
        if phase == "window":
            wrong_seqs.add(seq)
        examples.append(f"{phase}#{seq}: {text}")
    for phase, log in phases.items():
        for d in log:
            op = plan.op_at(phase, d.seq) if d.seq >= 0 else None
            # A write names its frame last, where its kind named it.
            named = (op.frame,) if op and op.frame is not None else ()
            for j, (pql, t0, t1, status, result) in enumerate(d.requests):
                if status != 200 or (isinstance(result, dict)
                                     and "error" in result):
                    unanswered += 1
                    flag(phase, d.seq,
                         f"{pql[:60]} -> {status} {str(result)[:80]}")
                    if pql.startswith("SetBit("):
                        # Unknown whether it landed: it may be seen by any
                        # later read, and is owed to none.
                        writes.append((*op.write, t0, float("inf"), *named))
                    continue
                if pql.startswith("SetBit("):
                    if result is not True:
                        wrong += 1
                        flag(phase, d.seq,
                             f"{pql[:60]} acknowledged {result!r}")
                    else:
                        acked.append((*op.write, *named))
                    writes.append((*op.write, t0, t1, *named))
                else:
                    reads.append((op.key if op else d.key, t0, t1, result))
                    where.append((phase, d.seq, pql))
    for (_, _, _, got), verdict, (phase, seq, pql) in zip(
            reads, ref.judge(reads, writes) if reads else (), where):
        if verdict is None:
            continue
        if verdict[0] == reference.NOT_JUDGED:
            not_judged += 1
        else:
            wrong += 1
        flag(phase, seq, f"{pql[:70]} -> {str(got)[:60]}, {verdict[1]}")
    out = {"wrong_answers": wrong, "unanswered": unanswered,
           "answers_compared": len(reads) + len(writes),
           "wrong_seqs": wrong_seqs, "examples": examples[:8],
           "acked": acked}
    if getattr(ref, "max_overlap", None) is not None:
        out["not_judged"] = not_judged  # only a reference that can decline
    return out


def lone_memo_hits(phases: Dict[str, List[Done]]) -> set:
    """Which read requests a whole-query memo keyed by the query's text and
    invalidated by any write would have answered, for one client in flight.
    {(seq, request index)} of the window."""
    seen, hits = set(), set()
    for phase in ("stage", "burst", "warmup", "window"):
        for d in sorted(phases.get(phase, ()), key=lambda d: d.t_send):
            for j, (pql, *_r) in enumerate(d.requests):
                if pql.startswith("SetBit("):
                    seen.clear()
                elif pql in seen:
                    if phase == "window":
                        hits.add((d.seq, j))
                else:
                    seen.add(pql)
    return hits


# -- one run -------------------------------------------------------------------


def _wait_calibration(srv: Server, timeout: float = 400.0) -> dict:
    t0 = time.monotonic()
    while True:
        v = srv.vars()
        if "count_calibration" in v and v.get("jax_runtime"):
            return v
        if not srv.alive():
            raise ServerError("server died while calibrating")
        if time.monotonic() - t0 > timeout:
            raise ServerError(f"no count_calibration after {timeout:.0f} s")
        time.sleep(0.25)


def _pinned_backend(toml_path: str) -> Optional[str]:
    """The count backend the server's TOML names, or None where it leaves the
    pick to the boot's calibration ("auto", the default)."""
    with open(toml_path, "rb") as f:
        backend = tomllib.load(f).get("mesh", {}).get("count-backend", "auto")
    return None if backend == "auto" else str(backend)


def _device_of(v: dict, chips: int, require_chip: bool) -> dict:
    """The device as JAX reports it in /debug/vars; not the chips the cell
    asks for: no result."""
    rt = v["jax_runtime"]
    device = {"platform": rt["platform"], "kind": rt["device_kind"],
              "count": int(rt["device_count"])}
    if require_chip and (device["platform"] != "tpu"
                         or device["count"] < chips):
        raise NoChip(f"the cell asks for {chips} TPU chip(s); JAX "
                     f"found {device}")
    if require_chip:
        layers.peak_for(device["kind"])  # unknown kind: an error
    return device


def _compiles(srv: Server) -> int:
    return int(layers.dig(srv.vars(),
                          "jax_runtime.compile.backend_compiles") or 0)


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_start: Optional[float] = None, require_chip: bool = True,
             slices: Optional[int] = None, control: Optional[str] = None,
             server_env: Optional[dict] = None) -> dict:
    """Run the cell once and return the result line as a dict.
    `require_chip=False`, `slices` and `server_env` are the rehearsal's and the
    tests': the driver's command never sets them."""
    t_start = time.monotonic() if t_start is None else t_start
    cell = load_cell(workload)
    config, traffic = dict(cell["config"]), cell["traffic"]
    if slices is not None:
        config["slices"] = slices
        config["columns"] = slices << 20
    chips = int(cell["cell"]["chips"])
    kind = names.kind(config)
    default_frame = names.frames(config)[0]["name"]
    run_dir = os.path.join(OUT_DIR, workload + (f".{control}" if control
                                                else ""))
    shutil.rmtree(run_dir, ignore_errors=True)
    data_dir = os.path.join(run_dir, "data")
    trace_dir = os.path.join(run_dir, "trace")
    os.makedirs(data_dir)
    marks = {"start": 0.0}

    def mark(name: str) -> None:
        marks[name] = time.monotonic() - t_start

    # -- data from the seed ---------------------------------------------------
    program_control = PROGRAM_CONTROLS.get(control)
    stand_in = control if program_control is None else None
    plan = Plan(config, traffic, seed)
    approx = None
    if control == "approximate_topn":
        ref, approx = kind.generate(config, seed, data_dir, plan, approx=True)
    else:
        ref = kind.generate(config, seed, data_dir, plan)
    plan.assign_columns(ref.candidates(), ref.can_write)
    mark("generate")
    # The reference tabulates in a pass of its own (datagen): a run pays those
    # seconds, its set-up does not.
    reference_s = marks["reference_s"] = float(getattr(ref, "tabulated_s", 0.0))

    # -- the server child -------------------------------------------------------
    log_path = os.path.join(run_dir, "server.log")
    pinned = None
    if stand_in:
        ref_path = os.path.join(run_dir, "reference.pickle")
        with open(ref_path, "wb") as f:
            pickle.dump({"ref": ref, "approx": approx}, f)
        srv = Server([os.path.join(BENCH_DIR, "pbench", "control.py"),
                      stand_in, ref_path, "{port}"], log_path)
    else:
        env = dict(server_env or {})
        toml = os.path.join(cell["config_dir"], config["server_toml"])
        if program_control:
            env.update(program_control["env"])
            with open(toml) as f:
                text = f.read() + program_control["toml"]
            toml = os.path.join(run_dir, "control.toml")
            with open(toml, "w") as f:
                f.write(text)
        if trace:
            os.makedirs(trace_dir)
            env["PBENCH_TRACE_DIR"] = trace_dir
        pinned = _pinned_backend(toml)
        srv = Server.pilosa(toml, data_dir, log_path, traced=trace,
                            env_extra=env)
    clients = None
    try:
        srv.wait_up()
        mark("open")
        device = {"platform": "control", "kind": stand_in, "count": 0}
        calibration = None
        if not stand_in and pinned is None:
            v = _wait_calibration(srv)
            device = _device_of(v, chips, require_chip)
            calibration = v["count_calibration"]
        mark("calibrate")

        # -- warm-up: stage, then the cell's own shapes at its concurrency ----
        n_clients = int(traffic["clients"])
        clients = Clients(srv.host, srv.port, config["index"], n_clients,
                          plan.op_at,
                          int(traffic.get("profile_one_in", 0)))
        stage: List[Done] = []
        for stage_pql, stage_key, stage_kind in (
                kind.stage_queries(config) if hasattr(kind, "stage_queries")
                else [kind.stage_query(default_frame)]):
            status, body, t0, t1 = clients.post(0, stage_pql, False)
            result = body["results"][0] if status == 200 \
                and isinstance(body, dict) and "results" in body else body
            stage.append(Done(0, -1, stage_kind, t0, t1, status == 200,
                              ((stage_pql, t0, t1, status, result),),
                              None, stage_key))
        mark("stage")
        vars_staged = srv.vars() if not stand_in else {}
        if not stand_in and pinned is not None:
            # A pinned backend is not calibrated, so the server reports no
            # device until the first query has brought the device path up.
            device = _device_of(vars_staged, chips, require_chip)
            calibration = {"backend": pinned, "source": "pinned"}
        warm_log: List[Done] = []
        warm = traffic["warmup"]
        rounds, compiles = 0, (_compiles(srv) if not stand_in else 0)
        for _ in range(plan.burst_rounds):
            warm_log += clients.run("burst", ops_per_client=1)
        while rounds < int(warm["max_rounds"]):
            warm_log += clients.run("warmup",
                                    ops_per_client=plan.warm_per_client)
            rounds += 1
            now = _compiles(srv) if not stand_in else 0
            moved, compiles = now != compiles, now
            if rounds >= int(warm["min_rounds"]) and not moved:
                break
        if not stand_in:
            # A background compile may still be under way: let it land.
            for _ in range(20):
                time.sleep(0.25)
                now = _compiles(srv)
                if now == compiles:
                    break
                compiles = now
        mark("warm")

        # -- the window -------------------------------------------------------------
        vars_before = srv.vars()
        prom_before = srv.metrics() if not stand_in else {}
        tracer = None
        trace_len = min(TRACE_SECONDS, seconds / 3.0)
        if trace and not stand_in:
            def drive_trace():
                time.sleep(max(0.0, 0.35 * seconds))
                srv.signal(signal.SIGUSR1)
                time.sleep(trace_len)
                srv.signal(signal.SIGUSR2)

            tracer = threading.Thread(target=drive_trace, daemon=True)
            tracer.start()
        setup_s = time.monotonic() - t_start - reference_s
        window_log = clients.run("window", seconds=seconds, profiled=trace)
        mark("window")
        vars_after = srv.vars()
        prom_after = srv.metrics() if not stand_in else {}
        trace_window = None
        if tracer is not None:
            tracer.join()
            wj = os.path.join(trace_dir, "window.json")
            # Stopping a trace took 70.5 s on four chips (PR 32): wait for
            # it while the server lives.
            deadline = time.monotonic() + 600
            while not os.path.exists(wj) and time.monotonic() < deadline \
                    and srv.alive():
                time.sleep(0.2)
            if os.path.exists(wj):
                trace_window = load_json(wj)
        memory_peak = int(layers.max_leaf(
            vars_after, "jax_runtime.memory", "peak_bytes_in_use") or 0)
    except BaseException:
        if clients is not None:
            clients.close()
        srv.stop(timeout=20)
        sys.stderr.write(f"--- server log tail ({log_path})\n"
                         f"{srv.log_tail()}\n")
        shutil.rmtree(data_dir, ignore_errors=True)
        raise
    clients.close()
    # The program is killed, not closed: a clean close would flush whatever
    # an acknowledgement had run ahead of, and hide it from the look below.
    if stand_in:
        srv.stop()
    else:
        srv.kill()
    mark("stop")

    # -- the comparison, once the window has closed and the state is freed ------
    phases = {"stage": stage,
              "burst": [d for d in warm_log if d.stream == "burst"],
              "warmup": [d for d in warm_log if d.stream == "warmup"],
              "window": window_log}
    cmp_ = compare(ref, phases, plan)
    compared = {
        "wrong_answers": {"value": cmp_["wrong_answers"], "limit": 0},
        "unanswered": {"value": cmp_["unanswered"], "limit": 0},
    }
    if "not_judged" in cmp_:
        compared["not_judged"] = {"value": cmp_["not_judged"], "limit": 0}
    compared["answers_compared"] = {"value": cmp_["answers_compared"],
                                    "at_least": 1}
    if not stand_in and any(o["kind"] == "update" for o in traffic["ops"]):
        # "An acknowledged SetBit is durable": every one of them has to be in
        # the dead server's fragment files, by a plain reader of the format.
        by_frame: Dict[str, list] = {}
        for w in cmp_["acked"]:  # (row, column[, frame]): the frame written
            by_frame.setdefault(w[2] if len(w) > 2 else default_frame,
                                []).append(w[:2])
        lost = [(name, r, c) for name, acked in by_frame.items()
                for r, c in durable.lost_writes(
                    lambda s, name=name: datagen.frag_path(
                        data_dir, config["index"], name, s), acked)]
        compared["lost_writes"] = {"value": len(lost), "limit": 0,
                                   "of": len(cmp_["acked"])}
        cmp_["examples"] += [f"SetBit(row {r}, column {c}, frame {name}) "
                             f"acknowledged and not on disk"
                             for name, r, c in lost[:4]]
    shutil.rmtree(data_dir, ignore_errors=True)
    mark("compare")
    win = reduce_window(window_log, cmp_["wrong_seqs"])
    correct = (all(v["value"] <= v["limit"] for v in compared.values()
                   if "limit" in v)
               and cmp_["answers_compared"] >= 1)

    # -- metrics -----------------------------------------------------------------
    metrics: Dict[str, dict] = {}
    reduced = None
    if not trace:
        values = dict(win, setup_s=setup_s)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell["end_to_end"]
                   if values.get(m["name"]) is not None}
    elif not stand_in:
        if trace_window is not None:
            reduced = _reduce_trace(trace_dir, trace_window)
        ctx = _layer_context(config, traffic, plan, ref, phases, reduced, win,
                             (vars_before, vars_after),
                             (prom_before, prom_after), device["kind"])
        metrics = layers.read_all([m["name"] for m in cell["per_layer"]], ctx)
    shutil.rmtree(trace_dir, ignore_errors=True)
    mark("metrics")

    dev = dict(device, memory_peak_bytes=memory_peak)
    result = {"correct": bool(correct), "attempted": win["attempted"],
              "failed": win["failed"], "metrics": metrics, "device": dev}
    if reduced is not None:
        dev["busy_s"], dev["window_s"] = reduced["busy_s"], \
            reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    if control:
        result["control"] = control
    result["compared"] = compared  # last: each number beside its limit

    _record({
        "workload": workload, "seed": seed, "seconds": seconds,
        "trace": int(trace), "control": control, "correct": bool(correct),
        "device": dev, "slices": int(config["slices"]),
        "calibration": calibration, "marks_s": marks,
        "server_env": server_env or None, "setup_s": setup_s,
        "warm_rounds": rounds, "burst_rounds": plan.burst_rounds,
        "window": {k: win.get(k) for k in (
            "attempted", "failed", "span_s", "ops_per_s", "read_p50_ms",
            "read_p90_ms", "read_p95_ms", "write_visible_ms", "reads",
            "updates",
            "by_kind", "stripes")},
        "stage": {k: layers.dig(vars_staged, "mesh." + k)
                  for k in ("stage_us", "h2d_bytes", "h2d_chunks")},
        "compared": {k: v["value"] for k, v in compared.items()},
        "examples": cmp_["examples"],
        "metrics": {k: v["value"] for k, v in metrics.items()},
        "breakdown": result.get("breakdown"),
    }, vars_before, vars_after)
    for line in cmp_["examples"]:
        sys.stderr.write(f"wrong: {line}\n")
    sys.stderr.write("compared: " + json.dumps(compared) + "\n")
    return result


def _reduce_trace(trace_dir: str, trace_window: dict) -> Optional[dict]:
    """The traced window as busy and idle time, top ops and longest gaps.
    Reads the trace with jax's reader, on the CPU: the server has stopped."""
    path = xplane.find_xplane(trace_dir)
    if not path:
        return None
    os.environ["JAX_PLATFORMS"] = "cpu"
    reduced = xplane.reduce(xplane.load(path), trace_window["window_s"])
    if reduced is not None:
        reduced.update(t0=trace_window["t0"], t1=trace_window["t1"])
    return reduced


def _layer_context(config, traffic, plan, ref, phases, reduced, win,
                   vars_pair, prom_pair, device_kind) -> layers.Context:
    window_log = phases["window"]
    read_requests = sum(1 for d in window_log for (pql, *_r) in d.requests
                        if not pql.startswith("SetBit("))
    keys = {(d.seq, j): plan.op_at("window", d.seq).key
            for d in window_log for j in range(len(d.requests))}
    lone_hits = None
    memo_account = getattr(ref, "memo_account", None)
    if int(traffic["clients"]) == 1 and memo_account is not None:
        # The rooflines need to know which reads a whole-query memo answered.
        # How many is the program's own counter, which the reference names for
        # each kind of read (with its sense: the hits or the misses are
        # counted); which ones is the harness's account of a memo keyed by the
        # query's text. Where the two numbers part, the account is wrong and
        # no roofline is reported.
        hits = lone_memo_hits(phases)
        accounts: Dict[tuple, int] = {}
        for d in window_log:
            for j, (pql, *_r) in enumerate(d.requests):
                if pql.startswith("SetBit("):
                    continue
                path, sense = memo_account(keys[(d.seq, j)])
                accounts[(path, sense)] = accounts.get((path, sense), 0) \
                    + (((d.seq, j) in hits) == (sense == "hits"))
        lone_hits = hits
        for (path, sense), account in sorted(accounts.items()):
            count = layers.dig(vars_pair[1], path) \
                - layers.dig(vars_pair[0], path)
            if abs(account - count) > max(2, 0.01 * read_requests):
                lone_hits = None
                sys.stderr.write(
                    f"roofline left out: the program counted {count} memo "
                    f"{sense} ({path}) in the window, the harness's account "
                    f"gives {account}\n")
    return layers.Context(
        vars_before=vars_pair[0], vars_after=vars_pair[1],
        prom_before=prom_pair[0], prom_after=prom_pair[1], log=window_log,
        trace=reduced,
        device_kind=device_kind, config=config, keys=keys,
        lone_hits=lone_hits, window=win,
        bytes_of=getattr(ref, "bytes_needed", None))


MESH_COUNTERS = ("count", "topn", "lone_fused", "batched", "coarse",
                 "coarse_uniform", "shared_batch", "deduped",
                 "device_dispatches", "incremental", "stage", "memo_hit",
                 "fallback", "fallback_error", "fallback_compile",
                 "fallback_oom", "routed_host", "lone_fused_failed")


def _record(record: dict, vars_before: dict, vars_after: dict) -> None:
    """One line per run in out/runs.jsonl (not the result line): the boot's
    pick, the program's counters over the window, ops per 5-s stripe. What a
    builder reads to find where a spread comes from."""
    def delta(path: str):
        a, b = layers.dig(vars_before, path), layers.dig(vars_after, path)
        return None if a is None or b is None else b - a

    record.update(
        mesh={k: delta("mesh." + k) for k in MESH_COUNTERS},
        memo={"query_hit": delta("host_cache.query_hit"),
              "query_miss": delta("host_cache.query_miss")},
        compiles_in_window=delta("jax_runtime.compile.backend_compiles"),
        compiles_at_setup=layers.dig(vars_before, "jax_runtime.compile"),
        at=time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()))
    os.makedirs(OUT_DIR, exist_ok=True)
    with open(os.path.join(OUT_DIR, "runs.jsonl"), "a") as f:
        f.write(json.dumps(record) + "\n")
