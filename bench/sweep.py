#!/usr/bin/env python3
"""Find an open-loop cell's knee on the chip: the highest arrival rate at
which the engine's queue does not grow over a window.

    python bench/sweep.py --workload qwen3-1.7b.chat --seconds 30 --rates 0.4 0.6 0.8 1.0

One process, one engine; for each rate, the cell's traffic at that rate
through the cell's warm-up and a window.  Prints one JSON line per rate:
requests queued at the window's start and end, requests due and finished
in it, tokens per second, the latency tails, and the programs built
inside the window.  A cell's rate is set
once, at about four fifths of the knee, and written into its cell file.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=int, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    run.prepare_environment()
    run.enable_compile_cache()

    import serving
    import spec
    import traffic

    cell = spec.workload(args.workload)
    cfg, mix = cell["cfg"], cell["mix"]
    run.require_accelerator(1)
    run._watch_programs()
    ref = spec.reference(cfg["model_type"])
    engine = serving.build_engine(cfg, args.seed, ref)
    engine.compile()
    serving.warm_slots(engine)
    for rate in args.rates:
        queued = {}
        served = serving.drive(
            engine, traffic.Stream(mix, args.seed, cfg["vocab_size"], rate),
            warmup_s=cell["warmup_s"],
            seconds=args.seconds, drain_max_s=0,
            on_window_start=lambda: queued.update(
                w0=engine.stats()["occupancy"]["queued"]),
            on_window_end=lambda: queued.update(
                w1=engine.stats()["occupancy"]["queued"]),
            log_steps=True)
        view = run.Run(cell, cfg, ref, None, True, args.seconds, 0.0,
                       served, served.tracked(True))
        due = view.tracked
        print(json.dumps({
            "rate_per_s": rate, "queued_w0": queued["w0"],
            "queued_w1": queued["w1"], "due": len(due),
            "finished": sum(r.done is not None and r.done < served.w1
                            for r in due),
            **{m: spec.metric_reader(m)(view)
               for m in ("tok_s", "ttft_p90_ms", "itl_p95_ms",
                         "engine.decode_rows", "engine.prefill_rows")},
            "lateness_max_s": max(served.lateness, default=0.0),
            "built_in_window": [name for t, name, _ in run._PROGRAMS_BUILT
                                if served.w0 <= t <= served.window_end],
            "at": time.strftime("%H:%M:%S")}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
