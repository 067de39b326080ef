"""The fixed op schedule: one general generator, driven by a traffic file.

A traffic file carries a `template_seed`. From it the generator builds the
sequence of *abstract* ops (kind, operator, arity, Zipf ranks of the rows, n)
block by block: the same composition in the same order in every run of the
cell, whatever `--seed` says. `--seed` only decides how a rank maps to a row id
(a permutation), which data the rows hold and which columns are written; that
is `bind()`.

An op of a traffic file may name frames of the configuration, `"frames":
[...]`: its i-th rank is then drawn over the rows of the i-th frame named (that
frame's own row count, and its own Zipf theta where the file's
`zipf_theta_by_frame` gives one), and names past the op's ranks draw nothing.
What the names mean in PQL is for the configuration's kind to say, in its
`bind` (`pbench/kinds/__init__.py`); so is an op kind that this file does not
know, which draws `arity` ranks. A file that names no frame draws every rank
over the configuration's first frame and is bound by `bind()` here.

Imports numpy only. Nothing of the program.
"""

from __future__ import annotations

import threading
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

STREAMS = {"window": 0, "warmup": 1, "burst": 2}


class AbstractOp(NamedTuple):
    kind: str              # "count" | "update" | "topn" | one the kind binds
    op: str                # Intersect | Union | Difference | "" (topn, update)
    arity: str             # "2", "all", "1" (update), "src" / "none" (topn)
    ranks: Tuple[int, ...]  # Zipf ranks, 0 = the hottest row
    n: int                 # TopN's n, else 0
    frames: Tuple[str, ...] = ()  # the frames the traffic file names for it


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
    """The abstract sequence of one traffic file over a frame of `n_rows`,
    and over the `frames` (name -> rows) that its ops name.

    `op(i)` is the i-th op of the stream; client c of C takes ops c, c + C,
    c + 2C, ... Blocks are made on demand and kept."""

    def __init__(self, traffic: dict, n_rows: int, stream: str = "window",
                 frames: Optional[Dict[str, int]] = None):
        self.traffic = traffic
        self.size = int(traffic["block"]["size"])
        self.seed = int(traffic["template_seed"])
        self.stream = STREAMS[stream]
        thetas = traffic.get("zipf_theta_by_frame", {})
        self._zipf = {
            name: (zipf_cdf(int(n), float(thetas.get(
                name, traffic["zipf_theta"]))), int(n))
            for name, n in {None: n_rows, **(frames or {})}.items()}
        self._striped, self._free = _expand(traffic["ops"], self.size)
        self._blocks: Dict[int, List[AbstractOp]] = {}
        self._mu = threading.Lock()

    def _ranks(self, rng, k: int, frames: Sequence[str] = ()
               ) -> Tuple[int, ...]:
        """k ranks, the i-th over the i-th frame named (past the names: the
        default frame), distinct within a frame."""
        out: List[tuple] = []
        while len(out) < k:
            frame = frames[len(out)] if len(out) < len(frames) else None
            cdf, n_rows = self._zipf[frame]
            r = int(np.searchsorted(cdf, rng.random(), side="right"))
            r = min(r, n_rows - 1)
            if (frame, r) not in out:
                out.append((frame, r))
        return tuple(r for _, r in out)

    def _abstract(self, rng, spec: dict) -> AbstractOp:
        kind = spec["kind"]
        if kind == "update":
            op, arity, k, n = "", "1", 1, 0
        elif kind == "count":
            op, arity, n = spec["op"], str(spec["arity"]), 0
            # All rows; a Difference names its minuend by rank.
            k = int(op == "Difference") if arity == "all" else int(arity)
        elif kind == "topn":
            k = 1 if spec.get("src") else 0
            op, arity, n = "", "src" if k else "none", int(spec["n"])
        else:  # a kind of op only the configuration's kind can bind
            k = int(spec.get("arity", 0))
            op, arity, n = str(spec.get("op", "")), str(k), \
                int(spec.get("n", 0))
        frames = tuple(spec.get("frames", ()))
        return AbstractOp(kind, op, arity, self._ranks(rng, k, frames), n,
                          frames)

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


def row_permutation(seed: int, n_rows: int, nth: int = 0) -> np.ndarray:
    """The rank -> row id map of the configuration's nth frame."""
    return np.random.default_rng(
        [seed, 101, nth] if nth else [seed, 101]).permutation(n_rows)


def bitmap(row: int, frame: str) -> str:
    return f'Bitmap(rowID={row}, frame="{frame}")'


class BoundOp(NamedTuple):
    kind: str
    pql: Tuple[str, ...]     # one request each; an update has two
    key: tuple               # the reference's key of the (last) read
    write: Optional[Tuple[int, int]]  # (row, column) of an update
    frame: Optional[str] = None  # the frame written, where the kind names it


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
