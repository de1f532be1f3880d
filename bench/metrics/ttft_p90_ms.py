"""90th percentile of time to first token over the requests due in the
window, from when each was due.  A request that never got its first token
counts with the time it had waited when the drain ended."""
from serving import percentile


def read(run):
    s = run.served
    ttft = [(r.times[0] if r.times else s.drain_end) - r.due
            for r in run.tracked]
    return percentile(ttft, 90) * 1e3 if ttft else None
