#!/usr/bin/env python3
"""Run one benchmark cell on the accelerator and print its result line.

    python bench/run.py --workload qwen3-1.7b.chat --seed 7 --seconds 51 --trace 0

One run: check the device (a TPU, as many chips as the cell asks for, its
kind in ``peaks.json``); build the cell's engine with the seed's weights;
compile its launches; serve the cell's traffic through a warm-up, a window
of ``--seconds`` and a drain; read the device's peak memory; free the
engine; check a sample of the served tokens against the plain reference.
``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` runs
the window under the profiler and reports its per-layer metrics and a
breakdown instead.  The last line of standard output is one JSON object;
the last lines of standard error are the numbers compared, each beside
its limit.  Without a TPU the run prints no result and exits non-zero.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import logging  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
REPO = BENCH.parent


def prepare_environment() -> None:
    """Before JAX loads: the compile cache at a fixed place inside the
    checkout, no kernel tile tables from outside it, and no fault plan or
    policy override from the environment."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(REPO / ".jax_cache")
    os.environ["REPRO_AUTOTUNE"] = "off"
    os.environ["REPRO_TUNE_DIR"] = str(REPO / ".jax_cache" / "autotune")
    for key in ("REPRO_FAULTS", "REPRO_IMPL", "REPRO_STRICT_TILES",
                "REPRO_INTERPRET", "REPRO_FAST_BYTES"):
        os.environ.pop(key, None)
    for p in (str(REPO / "src"), str(BENCH)):
        if p not in sys.path:
            sys.path.insert(0, p)


def enable_compile_cache() -> None:
    """JAX's persistent cache, every program in it however fast it
    compiled, so a second run in the checkout compiles nothing."""
    import jax

    from repro.launch.compile_cache import setup_compile_cache

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    setup_compile_cache()


class NoAccelerator(SystemExit):
    pass


def require_accelerator(chips: int):
    """The devices to run on: a TPU with at least ``chips`` chips, or no
    run at all."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoAccelerator(f"no TPU: JAX found {devs[0].platform} devices")
    if len(devs) < chips:
        raise NoAccelerator(f"the cell needs {chips} chips, JAX found "
                            f"{len(devs)}")
    return devs


_PROGRAMS_BUILT: list = []  # (host clock, name, argument types)


class _ProgramLog(logging.Handler):
    """Notes every program JAX lowers (then compiles, or reads from the
    persistent cache): its time, name and argument types, so a run can
    show which programs, if any, were built inside its window.  The
    lowering's debug records stop here; warnings go on to JAX's own
    handlers."""

    def emit(self, record):
        if record.msg.startswith("Compiling %s") and record.args:
            _PROGRAMS_BUILT.append((time.perf_counter(), str(record.args[0]),
                                    str(record.args[1])[:300]))
        if record.levelno >= logging.WARNING:
            logging.getLogger("jax").handle(record)


def _watch_programs() -> None:
    log = logging.getLogger("jax._src.interpreters.pxla")
    if not any(isinstance(h, _ProgramLog) for h in log.handlers):
        log.addHandler(_ProgramLog(logging.DEBUG))
        log.setLevel(logging.DEBUG)
        log.propagate = False


def _profile_options():
    """Device activity and the benchmark's own host spans; no Python
    function tracing, which would slow the host the trace is judging."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return opts


@dataclass
class Run:
    """What a metric reader sees of one run."""
    cell: dict
    cfg: dict
    ref: object           # the family's module, reference/<model_type>.py
    peak: dict
    open_loop: bool
    seconds: float
    setup_s: float
    served: object        # serving.Served
    tracked: list         # the requests the window answers for
    trace: object = None  # devtrace.Trace of the window, --trace 1 only


def run_cell(name: str, seed: int, seconds: float, trace: bool,
             root: Path = BENCH, spec: dict | None = None,
             t_start: float = T_START, controls: tuple = ()) -> dict:
    """One run of cell ``name``; returns its result object.  ``controls``
    (used by ``control.py``, never by a benchmark run) also reads the gap
    of each lower precision in the program's place."""
    import jax

    import check
    import serving
    import spec as specs
    import traffic

    spec = spec if spec is not None else specs.benchmark()
    entry = next(w for w in spec["workloads"] if w["name"] == name)
    cell = specs.workload(name, root)
    cfg, mix = cell["cfg"], cell["mix"]
    reference = specs.reference(cfg["model_type"], root)
    devs = require_accelerator(entry["chips"])
    dev = devs[0]
    peak = specs.peaks(dev.device_kind, root)
    readers = {m["name"]: (m, specs.metric_reader(m["name"], root))
               for m in specs.metrics_for(spec, name, trace)}

    _watch_programs()
    stream = traffic.Stream(mix, seed, cfg["vocab_size"],
                            cell.get("rate_per_s"), root)
    open_loop = stream.open
    engine = serving.build_engine(cfg, seed, reference)
    engine.compile()
    serving.warm_slots(engine)
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    served = serving.drive(
        engine, stream, warmup_s=cell["warmup_s"], seconds=seconds,
        drain_max_s=cell["drain_max_s"],
        on_window_start=(lambda: jax.profiler.start_trace(
            trace_dir, profiler_options=_profile_options())) if trace else None,
        on_window_end=jax.profiler.stop_trace if trace else None,
        log_steps=trace)
    setup_s = served.w0 - t_start
    memory_peak = int((dev.memory_stats() or {}).get("peak_bytes_in_use", 0))
    tracked = served.tracked(open_loop)

    reduced = None
    if trace:
        import devtrace
        reduced = devtrace.load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
    run = Run(cell, cfg, reference, peak, open_loop, seconds, setup_s, served,
              tracked, reduced)
    metrics = {}
    for mname, (m, read) in readers.items():
        value = read(run)
        if value is not None:
            metrics[mname] = {"value": value, "unit": m["unit"]}

    # the reference runs on a chip the program no longer holds
    picked = check.sample(tracked, seed, cell["check"]["sample_tokens"],
                          cell["check"]["max_requests"])
    seqs = [(r.req.prompt, list(r.req.out)) for r in picked]
    built = [(name, types) for t, name, types in _PROGRAMS_BUILT
             if served.w0 <= t <= served.window_end]
    print(f"programs built inside the window: {len(built)}", file=sys.stderr)
    for name, types in built:
        print(f"  built in the window: {name} {types}", file=sys.stderr)
    if served.lateness:
        late = sorted(served.lateness)
        print(f"generator lateness s: median {late[len(late) // 2]} max "
              f"{late[-1]} over {len(late)} arrivals", file=sys.stderr)
    del engine, served, run
    gc.collect()
    # no finished request to check reads as no gap at all: not correct
    got = (check.logit_gaps(reference, cfg, seed, seqs, controls) if seqs
           else {"logit_gap": None, "served_tokens": 0})
    correct, compared = check.judge(got, cell["check"]["limits"])
    failed = sum(1 for r in tracked if r.done is None)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": memory_peak}
    out = {"correct": correct, "attempted": len(tracked),
           "failed": failed, "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = devtrace.busy_s(reduced)
        device["window_s"] = reduced.window_s
        out["breakdown"] = devtrace.breakdown(reduced)
    if controls:  # each control through the program's own comparison
        out["controls"] = {
            c: check.judge({"logit_gap": gap,
                            "served_tokens": got["served_tokens"]},
                           cell["check"]["limits"])
            for c, gap in got.get("control_gap",
                                  dict.fromkeys(controls)).items()}
    out["compared"] = compared
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=int, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    prepare_environment()
    enable_compile_cache()
    try:
        out = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace))
    except NoAccelerator as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for key, c in out["compared"].items():
        print(f"compared {key}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
