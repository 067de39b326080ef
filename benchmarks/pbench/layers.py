"""Per-layer metrics: one small JSON file each under `layer_metrics/`, found
by the name `BENCHMARK.json` gives, read through a few generic readers. A
later PR adds a metric as one more file.

A file's `value` is an expression:

    {"vars": "mesh.count", "at": "window"}   /debug/vars path; "window" = the
                                             difference over the window,
                                             "setup" = as the window began,
                                             "end" = as it ended
    {"prom": "pilosa_query_route_total", "match": "backend=\\"mesh\\"",
     "at": "window"}                         sum of the /metrics series with
                                             that name (and label text)
    {"profile": ["parse", "plan"], "of": "reads"}
                                             median, over the ops that carried
                                             ?profile=true, of the phases' sum
                                             in microseconds ("writes": the
                                             SetBits)
    {"profile_gap": "reads"}                 median of client latency minus
                                             the profile's total, microseconds
    {"profile_rest": "reads"}                median of the profile's total minus
                                             the sum of its phases, microseconds
    {"window": "read_p95_ms"}                a number of the drained window
                                             (window.reduce_window), as the
                                             clients saw it
    {"trace": "idle_share"}                  a named reduction of the device
                                             trace (see TRACE_READERS)
    {"sum": [...]}, {"ratio": [a, b]}, {"times": [a, k]}, {"max_of": "..."}

A reader that finds nothing to read gives None, and so does every expression
built on it: the harness then leaves the metric out of the line. Nothing here
returns 0 for something it could not read.
"""

from __future__ import annotations

import json
import os
import statistics
from typing import Callable, Dict, List, Optional

import numpy as np

from .window import READ_KINDS

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONTAINER_BYTES = 8192
ROW_SPAN = 16  # containers of one row in one slice


class UnknownDevice(Exception):
    pass


def peak_for(device_kind: str, peaks_path: Optional[str] = None) -> dict:
    """The table of peaks, keyed by device_kind. A device that is not in it
    is an error, never a default."""
    with open(peaks_path or os.path.join(HERE, "peaks.json")) as f:
        peaks = json.load(f)["device_kinds"]
    if device_kind not in peaks:
        raise UnknownDevice(
            f"no peaks for device_kind {device_kind!r} in peaks.json "
            f"(known: {sorted(peaks)})")
    return peaks[device_kind]


def read_bytes_needed(key: tuple, n_rows: int, slices: int) -> int:
    """HBM bytes a device-answered Count has to move, whatever kernel serves
    it: every leaf row once, 16 containers of 8 KB in each slice."""
    leaves = {"R": 1, "I": 2, "U": 2, "D": 2}.get(key[0], n_rows)
    return leaves * slices * ROW_SPAN * CONTAINER_BYTES


ARRAY_MAX_VALUES = 4096  # roaring: above it a container is a bitmap


def container_bytes_needed(cardinalities) -> int:
    """HBM bytes the generated containers of those cardinalities hold as
    roaring keeps them, which is what a read of them has to move whatever
    kernel serves it: an array container 2 B a value (u16), a bitmap container
    (over 4,096 values) 8 KB. Not the dense staging's 8 KB for every one."""
    n = np.asarray(cardinalities, dtype=np.int64)
    return int(np.where(n > ARRAY_MAX_VALUES, CONTAINER_BYTES, 2 * n).sum())


def dig(obj, path: str):
    for part in path.split("."):
        if not isinstance(obj, dict) or part not in obj:
            return None
        obj = obj[part]
    return obj


def max_leaf(vars_: dict, path: str, key: str) -> Optional[float]:
    """The largest `key` among the dicts under `path` (one per device)."""
    node = dig(vars_, path)
    vals = [v[key] for v in (node or {}).values()
            if isinstance(v, dict) and key in v]
    return float(max(vals)) if vals else None


def prom_sum(series: dict, name: str, match: str = "") -> Optional[float]:
    hit = [v for k, v in series.items()
           if (k == name or k.startswith(name + "{")) and match in k]
    return sum(hit) if hit else None


class Context:
    """What one traced run gives the readers."""

    def __init__(self, *, vars_before, vars_after, prom_before, prom_after,
                 log, trace, device_kind, config, keys=None, lone_hits=None,
                 window=None, bytes_of=None):
        self.vars_before, self.vars_after = vars_before, vars_after
        self.prom_before, self.prom_after = prom_before, prom_after
        self.log, self.trace = log, trace
        self.device_kind, self.config = device_kind, config
        self.keys = keys or {}      # (seq, request index) -> reference key
        self.lone_hits = lone_hits  # {(seq, request index)} the memo answered
        self.window = window or {}  # reduce_window() of the log
        self.bytes_of = bytes_of    # the reference's bytes_needed(key)


def _at(before, after, at: str):
    if at == "setup":
        return before
    if at == "end":
        return after
    if before is None or after is None:
        return None
    return after - before


def _profiled(ctx: Context, of: str):
    """(client latency in us, profile) of the ops that carried a profile."""
    out = []
    for d in ctx.log:
        if d.profile is None or not d.ok:
            continue
        if of == "reads" and d.kind in READ_KINDS:
            out.append(((d.t_done - d.t_send) * 1e6, d.profile))
        elif of == "writes" and d.kind == "update":
            _, t0, t1, _, _ = d.requests[0]
            out.append(((t1 - t0) * 1e6, d.profile))
    return out


def _hbm_roofline_share(ctx: Context) -> Optional[float]:
    """Bytes the traced window's device-answered reads need (the reference's
    `bytes_needed(key)`, whatever kind of frame it is), over the device's busy
    time, over the chip's peak. Only where one query is in flight at a time
    (`lone_hits` is the harness's account of which reads a whole-query memo
    answered, checked against the program's counters)."""
    tr = ctx.trace
    if tr is None or ctx.lone_hits is None or ctx.bytes_of is None \
            or "t0" not in tr:
        return None
    need = 0
    for d in ctx.log:
        if not d.ok or not (tr["t0"] <= d.t_send and d.t_done <= tr["t1"]):
            continue
        for j, (pql, *_rest) in enumerate(d.requests):
            if not pql.startswith("SetBit(") \
                    and (d.seq, j) not in ctx.lone_hits:
                need += ctx.bytes_of(ctx.keys[(d.seq, j)])
    if not need or tr["busy_s"] <= 0:
        return None
    peak = peak_for(ctx.device_kind)["hbm_bytes_per_s"]
    return 100.0 * need / tr["busy_s"] / peak


TRACE_READERS: Dict[str, Callable[[Context], Optional[float]]] = {
    "idle_share": lambda c: c.trace and c.trace["idle_share"],
    "hbm_roofline_share": _hbm_roofline_share,
}


def evaluate(expr, ctx: Context) -> Optional[float]:
    if isinstance(expr, (int, float)):
        return float(expr)
    if "vars" in expr:
        at = expr.get("at", "window")
        return _at(dig(ctx.vars_before, expr["vars"]),
                   dig(ctx.vars_after, expr["vars"]), at)
    if "max_of" in expr:
        return max_leaf(ctx.vars_after, expr["max_of"], expr["key"])
    if "prom" in expr:
        m = expr.get("match", "")
        return _at(prom_sum(ctx.prom_before, expr["prom"], m),
                   prom_sum(ctx.prom_after, expr["prom"], m),
                   expr.get("at", "window"))
    if "profile" in expr:
        rows = [sum(p["phases_us"].get(ph, 0.0) for ph in expr["profile"])
                for _, p in _profiled(ctx, expr.get("of", "reads"))
                if any(ph in p.get("phases_us", {})
                       for ph in expr["profile"])]
        return statistics.median(rows) if rows else None
    if "profile_gap" in expr:
        rows = [lat - p["total_us"]
                for lat, p in _profiled(ctx, expr["profile_gap"])
                if "total_us" in p]
        return statistics.median(rows) if rows else None
    if "profile_rest" in expr:  # what no phase of the profile covers
        rows = [p["total_us"] - sum(p.get("phases_us", {}).values())
                for _, p in _profiled(ctx, expr["profile_rest"])
                if "total_us" in p]
        return statistics.median(rows) if rows else None
    if "window" in expr:
        v = ctx.window.get(expr["window"])
        return None if v is None else float(v)
    if "trace" in expr:
        v = TRACE_READERS[expr["trace"]](ctx)
        return None if v is None else float(v)
    if "sum" in expr:
        vals = [evaluate(e, ctx) for e in expr["sum"]]
        return None if any(v is None for v in vals) else sum(vals)
    if "ratio" in expr:
        a, b = (evaluate(e, ctx) for e in expr["ratio"])
        return None if a is None or not b else a / b
    if "times" in expr:
        a = evaluate(expr["times"][0], ctx)
        return None if a is None else a * float(expr["times"][1])
    raise ValueError(f"unknown reader in {expr!r}")


def load_metric(name: str) -> dict:
    with open(os.path.join(HERE, "layer_metrics", name + ".json")) as f:
        return json.load(f)


def read_all(names: List[str], ctx: Context) -> Dict[str, dict]:
    out = {}
    for name in names:
        spec = load_metric(name)
        v = evaluate(spec["value"], ctx)
        if v is not None:
            out[name] = {"value": v, "unit": spec["unit"]}
    return out
