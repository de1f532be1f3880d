"""Model operations of the decode launches in the traced window (the rows
that decoded, from shapes) over the chip's bf16 peak times the decode
program's device time."""
import devtrace
import flops

DECODE_PROGRAM = "decode_rows"


def read(run):
    busy_ns = sum(e.dur_ns for e in devtrace.module_events(run.trace,
                                                           DECODE_PROGRAM))
    total = sum(flops.decode_flops(run.ref, run.cfg, kv)
                for st in run.served.steps for kv in st["decode_kv"])
    if not busy_ns or not total:
        return None
    return 100.0 * total / (run.peak["bf16_flops_per_s"] * busy_ns / 1e9)
