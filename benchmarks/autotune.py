"""Populate persisted autotune tile tables for every registered op.

For each op the sweep covers the benchmark shapes (the ones
``bench_kernels.py`` reports) plus smaller neighbours, so serving/training
shapes that bucket into the same power-of-two classes replay measured tiles.
Each (op, shape) runs ``repro.kernels.autotune.search``: a power-of-two
candidate ladder around the planner's analytic point, timed compile-excluded
median-of-k, winner persisted under ``REPRO_TUNE_DIR`` keyed by
``(device_kind, op, shape_class, dtype)``.

Usage:
  PYTHONPATH=src python benchmarks/autotune.py              # all ops
  PYTHONPATH=src python benchmarks/autotune.py --ops scan,fft --iters 7
  REPRO_TUNE_DIR=/tmp/tune python benchmarks/autotune.py    # alternate table

Then regenerate ``BENCH_kernels.json`` (``python benchmarks/bench_kernels.py``)
to record the ``pallas_tuned_us`` column next to the fixed/planned arms.
"""
from __future__ import annotations

import argparse
import os
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.kernels import autotune, planner, registry  # noqa: E402


def _sweep() -> dict[str, list[tuple]]:
    """Per-op (args, kwargs) sweep.  The first case of each op is the
    bench_kernels.py shape, so the tuned arm there hits the table."""
    key = jax.random.key

    def n(k, shape, dtype=jnp.float32):
        return jax.random.normal(key(k), shape, dtype)

    def c(k, shape):
        return (jax.random.normal(key(k), shape)
                + 1j * jax.random.normal(key(k + 100), shape)).astype(jnp.complex64)

    return {
        "scan": [((n(0, (8, 8192)),), {}),
                 ((n(1, (8, 4096)),), {})],
        "matmul": [((n(2, (512, 512)), n(3, (512, 512))), {}),
                   ((n(4, (256, 256)), n(5, (256, 256))), {}),
                   # above the modeled Strassen crossover, Strassen requested:
                   # the search covers backend/cutoff/morton variants
                   # alongside the tile ladder (bench_kernels'
                   # matmul_strassen shape)
                   ((n(19, (1024, 1024)), n(20, (1024, 1024))),
                    {"backend": "strassen"})],
        "transpose": [((n(6, (512, 512)),), {}),
                      ((n(7, (256, 256)),), {})],
        "attention": [((n(8, (8, 512, 64)), n(9, (8, 512, 64)),
                        n(10, (8, 512, 64))), {"causal": False, "window": 0}),
                      ((n(11, (4, 256, 64)), n(12, (4, 256, 64)),
                        n(13, (4, 256, 64))), {"causal": True, "window": 0}),
                      # decode regime (bench_kernels' attention_decode arm):
                      # one query row over a mostly-full cache
                      ((n(16, (8, 1, 64)), n(17, (8, 1024, 64)),
                        n(18, (8, 1024, 64))),
                       {"causal": True, "window": 0,
                        "q_offset": 999, "kv_len": 1000})],
        "fft": [((c(14, (4, 1024)),), {}),
                ((c(15, (4, 512)),), {})],
    }


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--ops", default="",
                    help="comma-separated subset (default: all registered)")
    ap.add_argument("--iters", type=int, default=5,
                    help="timing repeats per candidate (median taken)")
    ap.add_argument("--max-candidates", type=int, default=16)
    ap.add_argument("--dir", default=None,
                    help="table directory (else REPRO_TUNE_DIR / default)")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import setup_compile_cache
    setup_compile_cache()

    if args.dir:
        os.environ["REPRO_TUNE_DIR"] = args.dir
        autotune.clear_cache()

    wanted = [o for o in args.ops.split(",") if o] or registry.names()
    sweep = _sweep()
    dp = planner.device_params()
    print(f"# autotune search on {dp.kind} ({dp.platform}), "
          f"fast_bytes={dp.fast_bytes}, table dir {autotune.tune_dir()}")

    entries = {}
    for op in wanted:
        if op not in sweep:
            print(f"# skipping {op!r}: no tuning metadata")
            continue
        for case_args, case_kwargs in sweep[op]:
            entry = autotune.search(op, *case_args, iters=args.iters,
                                    max_candidates=args.max_candidates,
                                    **case_kwargs)
            label = autotune.shape_class(*case_args)
            # analytic_us is None when the analytic candidate itself failed
            # to run (possible on native backends; search skips, not aborts)
            base = entry["analytic_us"] if entry["analytic_us"] is not None \
                else entry["us"]
            gain = base / max(entry["us"], 1e-9)
            print(f"autotune_{op}_{label},{entry['us']:.0f},"
                  f"analytic={base:.0f}us,x{gain:.2f},{entry['plan']}")
            entries[f"{op}|{label}"] = entry
    path = autotune.save_table(dp.kind)
    print(f"# wrote {len(autotune.load_table(dp.kind))} entries to {path}")
    return entries


if __name__ == "__main__":
    main()
