"""The fixed op schedule: one general generator, driven by a traffic file.

A traffic file carries a `template_seed`. From it the generator builds the
sequence of *abstract* ops (kind, operator, arity, Zipf ranks of the rows, n)
block by block: the same composition in the same order in every run of the
cell, whatever `--seed` says. `--seed` only decides how a rank maps to a row id
(a permutation), which data the rows hold and which columns are written; that
is `bind()`.

Imports numpy only. Nothing of the program.
"""

from __future__ import annotations

import threading
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

STREAMS = {"window": 0, "warmup": 1, "burst": 2}


class AbstractOp(NamedTuple):
    kind: str              # "count" | "update" | "topn"
    op: str                # Intersect | Union | Difference | "" (topn, update)
    arity: str             # "2", "all", "1" (update), "src" / "none" (topn)
    ranks: Tuple[int, ...]  # Zipf ranks, 0 = the hottest row
    n: int                 # TopN's n, else 0


def zipf_cdf(n: int, theta: float) -> np.ndarray:
    """YCSB's zipfian over n items: P(rank k) ~ 1 / (k + 1) ** theta."""
    w = 1.0 / np.arange(1, n + 1, dtype=np.float64) ** theta
    c = np.cumsum(w)
    return c / c[-1]


def _expand(ops: Sequence[dict], size: int) -> Tuple[List[dict], List[dict]]:
    """(striped specs, free specs) of one block, each repeated per_block."""
    striped, free = [], []
    for spec in ops:
        n = int(spec["per_block"])
        (striped if spec.get("one_per_stripe") else free).append((spec, n))
    total = sum(n for _, n in striped) + sum(n for _, n in free)
    if total != size:
        raise ValueError(f"ops per block sum to {total}, block size is {size}")
    return striped, free


class Template:
    """The abstract sequence of one traffic file over a frame of `n_rows`.

    `op(i)` is the i-th op of the stream; client c of C takes ops c, c + C,
    c + 2C, ... Blocks are made on demand and kept."""

    def __init__(self, traffic: dict, n_rows: int, stream: str = "window"):
        self.traffic = traffic
        self.n_rows = int(n_rows)
        self.size = int(traffic["block"]["size"])
        self.seed = int(traffic["template_seed"])
        self.stream = STREAMS[stream]
        self.cdf = zipf_cdf(self.n_rows, float(traffic["zipf_theta"]))
        self._striped, self._free = _expand(traffic["ops"], self.size)
        self._blocks: Dict[int, List[AbstractOp]] = {}
        self._mu = threading.Lock()

    def _ranks(self, rng, k: int) -> Tuple[int, ...]:
        out: List[int] = []
        while len(out) < k:
            r = int(np.searchsorted(self.cdf, rng.random(), side="right"))
            r = min(r, self.n_rows - 1)
            if r not in out:
                out.append(r)
        return tuple(out)

    def _abstract(self, rng, spec: dict) -> AbstractOp:
        kind = spec["kind"]
        if kind == "update":
            return AbstractOp("update", "", "1", self._ranks(rng, 1), 0)
        if kind == "count":
            arity = str(spec["arity"])
            if arity == "all":
                # All rows; a Difference names its minuend by rank.
                k = 1 if spec["op"] == "Difference" else 0
            else:
                k = int(arity)
            return AbstractOp("count", spec["op"], arity,
                              self._ranks(rng, k), 0)
        if kind == "topn":
            k = 1 if spec.get("src") else 0
            return AbstractOp("topn", "", "src" if k else "none",
                              self._ranks(rng, k), int(spec["n"]))
        raise ValueError(f"unknown op kind {kind!r}")

    def _block(self, b: int) -> List[AbstractOp]:
        with self._mu:
            got = self._blocks.get(b)
            if got is not None:
                return got
        rng = np.random.default_rng([self.seed, self.stream, b])
        slots: List[Optional[dict]] = [None] * self.size
        for spec, n in self._striped:
            stripe = self.size // n
            for s in range(n):
                pos = s * stripe + int(rng.integers(stripe))
                while slots[pos] is not None:  # two striped kinds collide
                    pos = s * stripe + (pos + 1 - s * stripe) % stripe
                slots[pos] = spec
        rest = [spec for spec, n in self._free for _ in range(n)]
        order = rng.permutation(len(rest))
        it = iter(order)
        for i in range(self.size):
            if slots[i] is None:
                slots[i] = rest[int(next(it))]
        ops = [self._abstract(rng, spec) for spec in slots]
        with self._mu:
            self._blocks.setdefault(b, ops)
            return self._blocks[b]

    def op(self, i: int) -> AbstractOp:
        return self._block(i // self.size)[i % self.size]

    def bursts(self, clients: int, reps: int) -> List[AbstractOp]:
        """Warm-up rounds in which every client sends the same read shape at
        the same moment, one round per shape and repetition: what shares a
        drain decides which batch program the server compiles, and the mix
        alone meets a given shape in a drain only now and then."""
        rng = np.random.default_rng([self.seed, STREAMS["burst"]])
        shapes = [s for s in self.traffic["ops"] if s["kind"] != "update"]
        return [self._abstract(rng, spec) for _ in range(reps)
                for spec in shapes for _ in range(clients)]

    def ops(self, start: int, stop: int) -> List[AbstractOp]:
        return [self.op(i) for i in range(start, stop)]


# -- binding: --seed decides row ids and written columns ----------------------


def row_permutation(seed: int, n_rows: int) -> np.ndarray:
    return np.random.default_rng([seed, 101]).permutation(n_rows)


def bitmap(row: int, frame: str) -> str:
    return f'Bitmap(rowID={row}, frame="{frame}")'


class BoundOp(NamedTuple):
    kind: str
    pql: Tuple[str, ...]     # one request each; an update has two
    key: tuple               # the reference's key of the (last) read
    write: Optional[Tuple[int, int]]  # (row, column) of an update


def bind(op: AbstractOp, perm: np.ndarray, frame: str, n_rows: int,
         column: Optional[int] = None) -> BoundOp:
    rows = [int(perm[r]) for r in op.ranks]
    if op.kind == "update":
        r = rows[0]
        return BoundOp("update",
                       (f'SetBit(rowID={r}, frame="{frame}", '
                        f'columnID={column})',
                        f"Count({bitmap(r, frame)})"),
                       ("R", r), (r, int(column)))
    if op.kind == "count":
        if op.arity == "all":
            if op.op == "Difference":
                m = rows[0]
                order = [m] + [r for r in range(n_rows) if r != m]
                key = ("DA", m)
            else:
                order = list(range(n_rows))
                key = ("IA",) if op.op == "Intersect" else ("UA",)
        else:
            order = rows
            a, b = rows
            if op.op == "Difference":
                key = ("D", a, b)
            else:
                key = ("I" if op.op == "Intersect" else "U",
                       min(a, b), max(a, b))
        inner = ", ".join(bitmap(r, frame) for r in order)
        return BoundOp("count", (f"Count({op.op}({inner}))",), key, None)
    if op.kind == "topn":
        if rows:
            pql = (f'TopN({bitmap(rows[0], frame)}, frame="{frame}", '
                   f"n={op.n})")
            key = ("T", rows[0], op.n)
        else:
            pql = f'TopN(frame="{frame}", n={op.n})'
            key = ("T", None, op.n)
        return BoundOp("topn", (pql,), key, None)
    raise ValueError(op.kind)
