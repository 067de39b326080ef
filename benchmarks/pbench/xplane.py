"""From a profiler trace to device busy and idle time, the device ops that
took most time, and the longest idle gaps by what the host was doing.

`load()` reads an `.xplane.pb` with `jax.profiler.ProfileData` into a plain
structure (planes > lines > events of name, start and duration in
nanoseconds); `reduce()` works on that structure alone, so it can be checked
on a small recorded trace kept as JSON.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
WINDOW_MARK = "pbench:window"   # the annotation the traced server holds open
MAX_GAPS = 200          # only the longest gaps are attributed
TOP = 10


def find_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def load(path: str) -> dict:
    """The trace as plain data. Imports jax (for the reader only): call it
    when no server child holds the chip, with JAX_PLATFORMS=cpu."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            events = [[ev.name, int(ev.start_ns), int(ev.duration_ns)]
                      for ev in line.events]
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _clean(name: str) -> str:
    return re.sub(r"[^A-Za-z0-9_.:\-]+", "_", name).strip("_")[:80]


_HLO = re.compile(r"^%?([\w.\-]+) = \(?([a-z0-9]+\[[\d,]*\])")


def _op_name(name: str) -> str:
    """An XLA op event is named by its whole HLO line: keep the op's name
    and the shape of its (first) result."""
    m = _HLO.match(name)
    return _clean(f"{m.group(1)}_{m.group(2)}" if m else name)


def device_lines(trace: dict) -> List[Tuple[str, List[list]]]:
    """(plane name, op events) of every device plane."""
    out = []
    for plane in trace["planes"]:
        if not DEVICE_PLANE.match(plane["name"]):
            continue
        for line in plane["lines"]:
            if line["name"] == OPS_LINE:
                out.append((plane["name"], line["events"]))
    return out


def host_events(trace: dict) -> List[list]:
    out: List[list] = []
    for plane in trace["planes"]:
        if plane["name"].startswith("/host:"):
            for line in plane["lines"]:
                out.extend(line["events"])
    return out


def _attribute(gap: Tuple[int, int], hosts: List[list]) -> str:
    """The host activity a device gap is charged to. Annotations of the
    runtime and of the program (TraceMe: `PjitFunction(run)`,
    `pilosa:count_fused`, ...) come before the Python tracer's frames
    (`$file:line function`), which wrap everything; within a class, of the
    events that overlap at least half the gap, the shortest: the innermost."""
    a, b = gap
    best = {False: None, True: None}   # keyed by "is a Python frame"
    for name, start, dur in hosts:     # longest first
        if 2 * dur < b - a:
            break                      # too short to cover half of it
        lo, hi = max(a, start), min(b, start + dur)
        if 2 * (hi - lo) < b - a:
            continue
        py = name.startswith("$")
        if best[py] is None or dur < best[py][0]:
            best[py] = (dur, name)
    for py in (False, True):
        if best[py] is not None:
            return _clean(best[py][1])
    return "unattributed"


def window_of(trace: dict, devs, window_s: float) -> Tuple[int, int]:
    """The traced window on the trace's own clock: the span of the
    `pbench:window` annotation, which the traced server holds open from the
    moment tracing has started to the moment it is told to stop. The profiler
    goes on recording until `stop_trace` has taken effect, so a device that is
    never idle shows more busy time than the window is long unless the ops
    are cut to it. A trace without the mark (one made by hand) is taken to
    begin at its first device op and to last `window_s`."""
    marks = [(s, d) for name, s, d in host_events(trace)
             if name == WINDOW_MARK and d > 0]
    if marks:
        start, dur = max(marks, key=lambda m: m[1])
        return start, start + dur
    start = min(s for _, events in devs for _, s, _ in events)
    return start, start + int(round(window_s * 1e9))


def _clip(events: List[list], lo: int, hi: int) -> List[list]:
    """The part of each event that lies inside [lo, hi)."""
    out = []
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append([name, a, b - a])
    return out


def reduce(trace: dict, window_s: float) -> Optional[dict]:
    """busy_s (mean over the device planes of the union of their op
    intervals, cut to the traced window), the window's length, the idle share
    of it, the top device ops by time and the longest idle gaps by host
    activity. None where no device op ran in the window."""
    devs = device_lines(trace)
    if not devs or not any(events for _, events in devs):
        return None
    lo, hi = window_of(trace, devs, window_s)
    devs = [(plane, _clip(events, lo, hi)) for plane, events in devs]
    if not any(events for _, events in devs):
        return None
    window_s = (hi - lo) / 1e9
    busy_ns: List[int] = []
    op_ns: Dict[str, int] = {}
    for _, events in devs:
        spans = _union([(s, s + d) for _, s, d in events])
        busy_ns.append(sum(b - a for a, b in spans))
        for name, _, d in events:
            op_ns[name] = op_ns.get(name, 0) + d
    busy_s = sum(busy_ns) / len(busy_ns) / 1e9
    # Gaps on the first device plane (one chip: the only one), the window's
    # two ends among them.
    spans = [(lo, lo)] + _union([(s, s + d) for _, s, d in devs[0][1]]) \
        + [(hi, hi)]
    gaps = [(spans[i][1], spans[i + 1][0]) for i in range(len(spans) - 1)
            if spans[i + 1][0] > spans[i][1]]
    gaps.sort(key=lambda g: g[0] - g[1])
    hosts = sorted((e for e in host_events(trace) if e[0] != WINDOW_MARK),
                   key=lambda e: -e[2])
    gap_ns: Dict[str, int] = {}
    for g in gaps[:MAX_GAPS]:
        name = _attribute(g, hosts)
        gap_ns[name] = gap_ns.get(name, 0) + (g[1] - g[0])
    rest = sum(b - a for a, b in gaps[MAX_GAPS:])
    if rest:
        gap_ns["gaps_shorter_than_the_200_longest"] = rest
    n_dev = len(devs)
    return {
        "busy_s": busy_s,
        "window_s": window_s,
        "idle_share": 100.0 * (1.0 - busy_s / window_s),
        "device_ops": [[_op_name(k), v / n_dev / 1e9] for k, v in sorted(
            op_ns.items(), key=lambda kv: -kv[1])[:TOP]],
        "idle_gaps": [[k, v / 1e9] for k, v in sorted(
            gap_ns.items(), key=lambda kv: -kv[1])[:TOP]],
    }
