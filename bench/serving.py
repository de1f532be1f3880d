"""Serve a cell's traffic through the engine and time every token.

The engine is the system under test, driven through its public step API:
``adopt`` when a request is due, ``step`` in a loop.  Every token's time
is the host clock after the step that produced it (a step ends on the
host's read of that step's tokens).  Each request is timed from when it
was due, not from when it was adopted, so a late generator shows as
latency; how late adoption ran is recorded beside it.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field

import numpy as np


def build_engine(cfg: dict, seed: int, ref):
    """The cell's engine on a one-device mesh: the ``ModelConfig`` that the
    family's reference module ``ref`` makes of the config file, the
    config's ``engine`` settings (``Engine``'s own keyword arguments; every
    other setting at its default), no fault plan, and the weights of
    ``seed``, laid out as ``ref`` says, in place of its own."""
    import jax

    from repro.launch.engine import Engine
    from repro.launch.mesh import make_mesh
    from repro.models.base import RunOptions
    from repro.runtime import FaultInjector

    import weights

    mesh = make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
    engine = Engine(ref.model_config(cfg), mesh, injector=FaultInjector(""),
                    opts=RunOptions(), **cfg["engine"])
    shapes = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                          engine.params)
    shardings = jax.tree.map(lambda a: a.sharding, engine.params)
    engine.params = None  # the engine's own weights go before ours land
    engine.params = weights.program_params(ref, cfg, seed, shapes, shardings)
    return engine


def warm_slots(engine) -> None:
    """Build in set-up the small programs the engine builds on a slot's
    first use (the row slice a snapshot copies, the row reset at
    admission), for every slot, so that none is built inside the window.
    Through the engine's own API: two waves of short requests, one in
    every slot, each long enough for a snapshot; the first admits onto a
    fresh cache, the second onto the cache as the launches leave it
    (another placement, so other programs).  ``drive`` begins afresh."""
    from repro.launch.serve import Request

    n = engine.max_batch
    max_new = max(engine.snapshot_every, 1) + 1
    engine.begin([])
    for wave in range(2):
        for i in range(n):
            engine.adopt(Request(-1 - wave * n - i, np.zeros(8, np.int32),
                                 max_new=max_new))
        while engine.busy():
            engine.step()


@dataclass
class Record:
    """One request as the client sees it."""
    uid: int
    prompt_len: int
    max_new: int
    due: float
    adopted: float
    req: object
    times: list = field(default_factory=list)
    done: float | None = None


@dataclass
class Served:
    """What a run served: every request, the window, and (when asked) the
    work of each step inside the window."""
    records: list
    w0: float
    w1: float
    window_end: float     # first step boundary at or after w1
    drain_end: float
    steps: list
    stats_w0: dict
    stats_w1: dict
    lateness: list        # adoption time minus due time, open loop

    def tracked(self, open_loop: bool) -> list:
        """Requests the run answers for: due inside the window (open loop),
        or with a token inside it (backlog)."""
        if open_loop:
            return [r for r in self.records if self.w0 <= r.due < self.w1]
        return [r for r in self.records
                if any(self.w0 <= t < self.w1 for t in r.times)]


def _progress(engine, live: dict) -> dict:
    """uid -> (tokens out, prompt tokens prefilled) for live requests."""
    slot_of = {id(s.req): s for s in engine.slots if s.req is not None}
    out = {}
    for uid, rec in live.items():
        n = len(rec.req.out)
        if n:
            pf = rec.prompt_len
        else:
            s = slot_of.get(id(rec.req))
            pf = s.filled if s is not None else 0
        out[uid] = (n, pf)
    return out


def _step_work(before: dict, after: dict, live: dict) -> dict:
    """The useful work of one step: prompt ranges prefilled (by launch) and
    the context length of every token decoded."""
    first, cont, kv = [], [], []
    for uid, (n1, pf1) in after.items():
        n0, pf0 = before.get(uid, (0, 0))
        if pf1 > pf0:
            (first if pf0 == 0 else cont).append((pf0, pf1))
        decoded = n1 - n0 - (1 if n0 == 0 and n1 > 0 else 0)
        if decoded > 0:
            kv.append(live[uid].prompt_len + n1 - 1)
    return {"prefill_first": first, "prefill_cont": cont, "decode_kv": kv}


def drive(engine, stream, *, warmup_s: float, seconds: float,
          drain_max_s: float, on_window_start=None, on_window_end=None,
          log_steps: bool = False, clock=time.perf_counter) -> Served:
    """Serve ``stream`` through ``engine``: warm up for ``warmup_s``, then
    measure for ``seconds``, then drain until every request the window
    answers for is complete or ``drain_max_s`` has passed.  Open-loop
    arrivals go on through the drain, so the last requests due see the same
    load; a backlog stops queueing, since no metric reads its drain."""
    import jax

    from repro.launch.serve import Request

    open_loop = stream.open
    depth = engine.max_batch + stream.waiting(engine.max_batch)
    engine.begin([])
    live: dict = {}
    records: list = []
    lateness: list = []
    steps: list = []
    stats_w0 = stats_w1 = {}
    t0 = clock()
    w0 = w1 = window_end = None
    backlog_owed: list = []
    phase = "warm"

    def adopt(spec, due):
        rec = Record(spec.uid, len(spec.prompt), spec.max_new, due, clock(),
                     Request(spec.uid, spec.prompt, max_new=spec.max_new))
        engine.adopt(rec.req)
        live[spec.uid] = rec
        records.append(rec)
        if open_loop:
            lateness.append(rec.adopted - due)

    while True:
        now = clock()
        if phase == "warm" and now - t0 >= warmup_s:
            if on_window_start is not None:
                on_window_start()
            stats_w0 = engine.stats()
            w0 = clock()
            w1 = w0 + seconds
            phase = "window"
        elif phase == "window" and now >= w1:
            window_end = now
            stats_w1 = engine.stats()
            backlog_owed = [r for r in records
                            if any(w0 <= t < w1 for t in r.times)]
            if on_window_end is not None:
                on_window_end()
            phase = "drain"
        if phase == "drain":
            if open_loop:  # a request due before w1 may be adopted after it
                owed = [r for r in records
                        if r.done is None and w0 <= r.due < w1]
            else:
                owed = [r for r in backlog_owed if r.done is None]
            if not owed or clock() >= w1 + drain_max_s:
                break
        with jax.profiler.TraceAnnotation("bench.generator"):
            if open_loop:
                while t0 + stream.peek().due_s <= clock():
                    spec = stream.pop()
                    adopt(spec, t0 + spec.due_s)
            elif phase != "drain":  # past the window a backlog only drains
                while len(live) < depth:
                    adopt(stream.pop(), clock())
        if not engine.busy():
            with jax.profiler.TraceAnnotation("bench.wait"):
                time.sleep(max(0.0, min(t0 + stream.peek().due_s - clock(),
                                        0.005)))
            continue
        probe = log_steps and phase == "window"
        before = _progress(engine, live) if probe else None
        ts = clock()
        with jax.profiler.TraceAnnotation("bench.step"):
            engine.step()
        t = clock()
        if probe:
            work = _step_work(before, _progress(engine, live), live)
            work.update(t0=ts, t1=t)
            steps.append(work)
        for uid in list(live):
            rec = live[uid]
            n = len(rec.req.out)
            if n > len(rec.times):
                rec.times.extend([t] * (n - len(rec.times)))
            if n >= rec.max_new:
                rec.done = t
                del live[uid]
    return Served(records, w0, w1, window_end, clock(), steps, stats_w0,
                  stats_w1, lateness)


def percentile(values, q: float) -> float:
    """The ``q``-th percentile (0-100) by linear interpolation between
    order statistics (numpy's default)."""
    return float(np.percentile(np.asarray(values, np.float64), q))
