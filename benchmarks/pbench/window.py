"""The drained window's arithmetic, on a log of completed ops.

Clients stop *issuing* at the window's length; every op in flight is waited
for. The rate is all ops completed over (last completion - first send), and
the percentiles are over all of them: the tail of all requests, not of those
that happened to end before a cut. Pure Python; no clock is read here.
"""

from __future__ import annotations

import math
from typing import Dict, List, NamedTuple, Optional, Sequence


class Done(NamedTuple):
    """One op as a client saw it. Times are seconds on one monotonic clock."""
    client: int
    seq: int                 # index in the cell's abstract sequence
    kind: str                # "count" | "topn" | "update"
    t_send: float            # first request sent
    t_done: float            # last reply read
    ok: bool                 # every request answered 200
    requests: tuple          # ((pql, t_send, t_done, status, result), ...)
    profile: Optional[dict] = None
    key: Optional[tuple] = None  # only the staging query carries its own
    stream: str = "window"


READ_KINDS = ("count", "topn")


def percentile(values: Sequence[float], p: float) -> float:
    """Nearest rank on the sorted values: the smallest value with at least
    p of the sample at or below it."""
    s = sorted(values)
    if not s:
        raise ValueError("percentile of nothing")
    return s[max(0, math.ceil(p * len(s)) - 1)]


def reduce_window(log: Sequence[Done], failed_seqs=frozenset(),
                  stripe_s: float = 5.0) -> dict:
    """End-to-end numbers of one drained window. `failed_seqs` are ops whose
    answer broke a guarantee: they count in `failed`, like the unanswered,
    and in no rate and no percentile."""
    if not log:
        raise ValueError("the window completed no op")
    good = [d for d in log if d.ok and d.seq not in failed_seqs]
    t0 = min(d.t_send for d in log)
    t1 = max(d.t_done for d in log)
    out: Dict[str, object] = {
        "attempted": len(log),
        "failed": len(log) - len(good),
        "span_s": t1 - t0,
    }
    if not good:
        return out
    out["ops_per_s"] = len(good) / (t1 - t0)
    reads = [(d.t_done - d.t_send) * 1e3 for d in good
             if d.kind in READ_KINDS]
    if reads:
        out["read_p50_ms"] = percentile(reads, 0.50)
        out["read_p90_ms"] = percentile(reads, 0.90)
        out["read_p95_ms"] = percentile(reads, 0.95)
        out["reads"] = len(reads)
    writes = [(d.t_done - d.t_send) * 1e3 for d in good if d.kind == "update"]
    if writes:
        out["write_visible_ms"] = percentile(writes, 0.50)
        out["updates"] = len(writes)
    n = int((t1 - t0) // stripe_s) + 1
    stripes: List[int] = [0] * n
    for d in good:
        stripes[min(n - 1, int((d.t_done - t0) // stripe_s))] += 1
    out["stripes"] = stripes
    kinds: Dict[str, int] = {}
    for d in good:
        kinds[d.kind] = kinds.get(d.kind, 0) + 1
    out["by_kind"] = kinds
    return out
