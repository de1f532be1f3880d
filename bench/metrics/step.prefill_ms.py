"""Mean device time of one run of a prefill program (``prefill_first`` or
``prefill_cont``) in the trace.  A mean, not a median, because the two
programs differ in cost and both count."""
import devtrace

PREFILL_PROGRAMS = ("prefill_first", "prefill_cont")


def read(run):
    runs = [e for name in PREFILL_PROGRAMS
            for e in devtrace.module_events(run.trace, name)]
    if not runs:
        return None
    return sum(e.dur_ns for e in runs) / len(runs) / 1e6
