"""The one request generator: reads a traffic mix's parameters and the seed.

A mix (``traffic/<name>.json``) names its arrival process and the length
distributions of its requests; each is a module of its own, found by name
(``arrivals/<process>.py``, ``lengths/<dist>.py``), so a new process or
distribution is a new file.  An open process (requests fall due on a
schedule, whether or not the server keeps up) gives the gaps between
arrivals; a closed one (a backlog) says how many requests wait.  A mix
holds one class of requests (``prompt`` and ``output``) or several
(``classes``, each with its ``share`` of every block).

Requests come in blocks of ``block``.  A block's lengths are its classes'
distributions at evenly spaced quantiles.  In an open mix the block's
arrival gaps are one draw of the arrival process, with its bursts and
lulls, and the order of its lengths one shuffle, both made from the
block's index alone: every seed is offered the same requests at the same
times, and the seed draws only their prompt tokens.  (Reordering by the
seed moved the work that falls due in a window by a factor of 1.8 between
seeds.)  A backlog has no clock: there the seed orders each block's
lengths, and every seed still has the same multiset.
"""
from __future__ import annotations

from dataclasses import dataclass
from pathlib import Path

import numpy as np

import spec as specs

BENCH = Path(__file__).resolve().parent
WORK_SEED = 0x5EED  # an open block's draws: one stream per block index


@dataclass
class Spec:
    """One generated request: ``due_s`` after the traffic starts (None in a
    closed loop, where a request is due when it is queued)."""
    uid: int
    due_s: float | None
    prompt: np.ndarray
    max_new: int


def lengths(dist: dict, n: int, root: Path = BENCH) -> np.ndarray:
    """``n`` whole lengths at the quantiles (i + 0.5) / n of ``dist``, whose
    ``dist`` key names its module under ``lengths/``, clipped to its
    ``min`` and ``max``."""
    q = (np.arange(n) + 0.5) / n
    v = np.asarray(specs.lengths(dist["dist"], root).ppf(dist, q))
    return np.clip(np.rint(v), dist["min"], dist["max"]).astype(np.int64)


def classes(mix: dict) -> list[dict]:
    """The mix's request classes, each with ``share``, ``prompt`` and
    ``output``."""
    if "classes" not in mix:
        return [{"share": 1.0, "prompt": mix["prompt"],
                 "output": mix["output"]}]
    shares = [float(c["share"]) for c in mix["classes"]]
    if abs(sum(shares) - 1.0) > 1e-9 or min(shares) <= 0:
        raise ValueError(f"class shares {shares} must be positive, sum 1")
    return mix["classes"]


def class_counts(shares: list[float], block: int) -> list[int]:
    """Requests of each class in a block: ``share * block`` rounded so they
    add up to ``block`` (largest remainders first)."""
    want = np.asarray(shares) * block
    n = np.floor(want).astype(int)
    for i in np.argsort(-(want - n), kind="stable")[:block - n.sum()]:
        n[i] += 1
    return n.tolist()


class Stream:
    """An endless request stream of one mix under one seed."""

    def __init__(self, mix: dict, seed: int, vocab: int,
                 rate_per_s: float | None = None, root: Path = BENCH):
        if seed < 0:
            raise ValueError(f"seed must be >= 0, got {seed}")
        self.arrivals = mix["arrivals"]
        self.process = specs.arrivals(self.arrivals["process"], root)
        self.open = bool(self.process.OPEN)
        if self.open and not (rate_per_s and rate_per_s > 0):
            raise ValueError("an open-loop mix needs the cell's rate_per_s")
        self.rate = rate_per_s
        self.block = int(mix["block"])
        self.vocab = int(vocab)
        self.rng = np.random.default_rng(seed)
        cls = classes(mix)
        counts = class_counts([float(c["share"]) for c in cls], self.block)
        # each class's lengths, at its own quantiles
        self.sizes = [(lengths(c["prompt"], n, root),
                       lengths(c["output"], n, root))
                      for c, n in zip(cls, counts) if n]
        self.n_blocks = 0
        self.uid = 0
        self.clock = 0.0
        self._pending: list[Spec] = []

    def waiting(self, slots: int) -> int:
        """Requests a closed loop keeps waiting beside ``slots`` busy ones."""
        return 0 if self.open else int(self.process.waiting(self.arrivals,
                                                            slots))

    def _next_block(self):
        work = np.random.default_rng([WORK_SEED, self.n_blocks])
        order = work if self.open else self.rng
        pairs = []
        for prompts, outputs in self.sizes:
            pp = order.permutation(len(prompts))
            po = order.permutation(len(outputs))
            pairs += zip(prompts[pp].tolist(), outputs[po].tolist())
        if len(self.sizes) > 1:
            pairs = [pairs[i] for i in order.permutation(len(pairs))]
        gaps = None
        if self.open:
            gaps = np.asarray(self.process.gaps(self.arrivals, self.rate,
                                                work, self.block))
        self.n_blocks += 1
        for j, (plen, max_new) in enumerate(pairs):
            due = None
            if self.open:
                self.clock += float(gaps[j])
                due = self.clock
            prompt = self.rng.integers(0, self.vocab, plen, dtype=np.int32)
            self._pending.append(Spec(self.uid, due, prompt, max_new))
            self.uid += 1

    def peek(self) -> Spec:
        if not self._pending:
            self._next_block()
        return self._pending[0]

    def pop(self) -> Spec:
        spec = self.peek()
        self._pending.pop(0)
        return spec

    def take(self, n: int) -> list[Spec]:
        return [self.pop() for _ in range(n)]
