"""Named host spans: one clock for the profiler's trace and for ``stats()``.

A span opens a ``jax.profiler.TraceAnnotation`` of its name, so a profile
shows it on the host's timeline beside the device's operations, and times
itself with ``time.perf_counter``.  Closed spans add to an in-memory
aggregate, per name: how many closed (``n``), their seconds (``s``), and
their self seconds (``self_s``: a span's time minus what its child spans
cover; the parent is the innermost span open when a span opens).  A span
never waits on the device; it measures the host's time in the interval it
encloses.
"""
from __future__ import annotations

import time

import jax


class Span:
    """One open span: ``start`` is its ``perf_counter`` at opening, and
    after it closes ``s`` holds its seconds."""

    __slots__ = ("name", "start", "s", "_owner", "_child", "_ann")

    def __init__(self, owner: "Spans", name: str):
        self.name = name
        self.s = 0.0
        self._owner = owner

    def __enter__(self) -> "Span":
        self._child = 0.0
        self._owner._open.append(self)
        self._ann = jax.profiler.TraceAnnotation(self.name)
        self._ann.__enter__()
        self.start = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.s = time.perf_counter() - self.start
        self._ann.__exit__(*exc)
        stack = self._owner._open
        stack.pop()
        if stack:
            stack[-1]._child += self.s
        agg = self._owner._agg.setdefault(self.name, [0, 0.0, 0.0])
        agg[0] += 1
        agg[1] += self.s
        agg[2] += self.s - self._child


class Spans:
    """The span aggregate of one owner (an engine)."""

    def __init__(self):
        self._open: list[Span] = []
        self._agg: dict[str, list] = {}

    def span(self, name: str) -> Span:
        return Span(self, name)

    def reset(self) -> None:
        """Forget every closed span; spans still open keep their parents."""
        self._agg = {}

    def total(self, name: str) -> float:
        """Seconds of every closed span named ``name``."""
        return self._agg.get(name, (0, 0.0))[1]

    def snapshot(self) -> dict:
        """``{name: {"n", "s", "self_s"}}``, a fresh copy."""
        return {k: {"n": n, "s": s, "self_s": self_s}
                for k, (n, s, self_s) in self._agg.items()}
