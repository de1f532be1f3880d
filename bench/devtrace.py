"""From a profiler trace to the numbers the per-layer metrics read.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into plain
events: the operations and the programs (XLA modules) each device ran, and
the host spans the benchmark opened around its own calls.  The rest works
on those events alone, so it is checked on a small recorded trace.

A device is busy while any operation runs on it: busy time is the union of
its operation intervals inside the traced window, and the idle share is
the rest.  A kernel's time is the sum of its events' durations.
"""
from __future__ import annotations

import glob
import json
import os
import re
from dataclasses import asdict, dataclass, field

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_SPAN_PREFIX = "bench."


@dataclass
class Event:
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclass
class Trace:
    """Events of one traced window, on the host's clock."""
    begin_ns: float
    end_ns: float
    ops: dict = field(default_factory=dict)      # device -> [Event]
    modules: dict = field(default_factory=dict)  # device -> [Event]
    spans: list = field(default_factory=list)    # host spans [Event]

    @property
    def window_s(self) -> float:
        return (self.end_ns - self.begin_ns) / 1e9

    def to_json(self) -> dict:
        return {"begin_ns": self.begin_ns, "end_ns": self.end_ns,
                "ops": {d: [asdict(e) for e in v] for d, v in self.ops.items()},
                "modules": {d: [asdict(e) for e in v]
                            for d, v in self.modules.items()},
                "spans": [asdict(e) for e in self.spans]}

    @classmethod
    def from_json(cls, data: dict) -> "Trace":
        return cls(data["begin_ns"], data["end_ns"],
                   {d: [Event(**e) for e in v] for d, v in data["ops"].items()},
                   {d: [Event(**e) for e in v]
                    for d, v in data["modules"].items()},
                   [Event(**e) for e in data["spans"]])


def load(trace_dir: str, begin_ns: float | None = None,
         end_ns: float | None = None) -> Trace:
    """Read the one ``.xplane.pb`` under ``trace_dir``.  The window is
    [begin_ns, end_ns] where given, else the span of the host spans."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {trace_dir}, "
                                f"found {paths}")
    data = ProfileData.from_file(paths[0])
    ops, modules, spans = {}, {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name:
            dev = plane.name.split(":", 1)[1]
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[dev] = [Event(e.name, e.start_ns, e.duration_ns)
                                for e in line.events]
                elif line.name == MODULES_LINE:
                    modules[dev] = [Event(e.name, e.start_ns, e.duration_ns)
                                    for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_SPAN_PREFIX):
                        spans.append(Event(e.name, e.start_ns, e.duration_ns))
    spans.sort(key=lambda e: e.start_ns)
    # the window is what the benchmark's spans cover, else what the device
    # did
    bounds = spans or [e for evs in ops.values() for e in evs]
    if begin_ns is None:
        begin_ns = min((e.start_ns for e in bounds), default=0.0)
    if end_ns is None:
        end_ns = max((e.end_ns for e in bounds), default=0.0)
    return Trace(begin_ns, end_ns, ops, modules, spans)


def _clip(events, lo, hi):
    """(start, end) of each event, clipped to [lo, hi]; empty ones dropped."""
    out = []
    for e in events:
        s, t = max(e.start_ns, lo), min(e.end_ns, hi)
        if t > s:
            out.append((s, t))
    return out


def union_ns(intervals) -> float:
    """Total length covered by (start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def busy_s(trace: Trace) -> float:
    """Seconds in which some operation ran, averaged over the devices."""
    if not trace.ops:
        return 0.0
    per = [union_ns(_clip(evs, trace.begin_ns, trace.end_ns))
           for evs in trace.ops.values()]
    return sum(per) / len(per) / 1e9


def idle_gaps(trace: Trace, device: str | None = None) -> list:
    """[(start_ns, end_ns)] of the stretches of the window in which no
    operation ran on ``device`` (default: the first)."""
    if not trace.ops:
        return []
    dev = device or sorted(trace.ops)[0]
    busy = sorted(_clip(trace.ops[dev], trace.begin_ns, trace.end_ns))
    gaps, cursor = [], trace.begin_ns
    for s, e in busy:
        if s > cursor:
            gaps.append((cursor, s))
        cursor = max(cursor, e)
    if trace.end_ns > cursor:
        gaps.append((cursor, trace.end_ns))
    return gaps


def host_label(trace: Trace, t_ns: float) -> str:
    """The innermost benchmark span open on the host at ``t_ns``."""
    best = None
    for e in trace.spans:
        if e.start_ns <= t_ns < e.end_ns and (best is None
                                              or e.dur_ns < best.dur_ns):
            best = e
    return best.name if best is not None else "host (no span)"


_INST = re.compile(r"^%?([A-Za-z_][\w\-]*?)(\.\d+)? = ")
_LAYOUT = re.compile(r"\{[^{}]*\}")
_SHAPE = re.compile(r"\b([a-z]+\d*)\[([\d,]*)\]")


def kernel(ev: Event) -> str:
    """The name an operation was given in the program (its HLO instruction
    name without the numeric suffix): ``hbp_matmul``, ``fa``, ``copy``."""
    m = _INST.match(ev.name)
    return m.group(1) if m else ev.name


def operand_shapes(ev: Event) -> list:
    """[(dtype, shape)] of an operation's operands, read from its HLO text
    (the part after the opening parenthesis of the call)."""
    text = ev.name
    head, sep, args = text.partition(" custom-call(")
    if not sep:
        head, sep, args = text.partition("(")
    return [(d, tuple(int(x) for x in dims.split(",") if x))
            for d, dims in _SHAPE.findall(args.split("), ")[0])]


def short_name(ev: Event, width: int = 100) -> str:
    """An operation's instruction name and result type, cut to ``width``."""
    inst, _, rest = ev.name.partition(" = ")
    out = _LAYOUT.sub("", rest).split(" ", 1)[0] if rest else ""
    return f"{inst.lstrip('%')} {out}"[:width].strip()


def kernel_events(trace: Trace, name: str) -> list:
    """Operations, on every device, that ``kernel`` names ``name``."""
    return [e for evs in trace.ops.values() for e in evs
            if kernel(e) == name]


def module_events(trace: Trace, pattern: str) -> list:
    """Program runs, on every device, whose name holds ``pattern``."""
    return [e for evs in trace.modules.values() for e in evs
            if pattern in e.name]


def breakdown(trace: Trace, top: int = 10) -> dict:
    """The device operations that took most time (by name, summed), and the
    longest idle gaps, each named by what the host was doing in it."""
    total: dict = {}
    for evs in trace.ops.values():
        for s, e, name in ((max(x.start_ns, trace.begin_ns),
                            min(x.end_ns, trace.end_ns), short_name(x))
                           for x in evs):
            if e > s:
                total[name] = total.get(name, 0.0) + (e - s) / 1e9
    ops = sorted(total.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(idle_gaps(trace), key=lambda g: g[0] - g[1])[:top]
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[host_label(trace, (s + e) / 2), (e - s) / 1e9]
                          for s, e in gaps]}


def save(trace: Trace, path: str) -> None:
    with open(path, "w") as f:
        json.dump(trace.to_json(), f)


def read(path: str) -> Trace:
    with open(path) as f:
        return Trace.from_json(json.load(f))
