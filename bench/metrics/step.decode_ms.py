"""Median device time of one run of the decode program in the trace."""
import statistics

import devtrace

DECODE_PROGRAM = "decode_rows"


def read(run):
    runs = devtrace.module_events(run.trace, DECODE_PROGRAM)
    if not runs:
        return None
    return statistics.median(e.dur_ns for e in runs) / 1e6
