"""Share of the traced window in which no operation ran on the device."""
import devtrace


def read(run):
    if not run.trace.ops:
        return None
    return 100.0 * (1.0 - devtrace.busy_s(run.trace) / run.trace.window_s)
