"""90th percentile, over the requests the window answers for, of the time
from when the engine queued a request to its first admission into a slot
(the request's ``queued`` and ``admitted`` marks).  A request never
admitted counts with its wait when the drain ended.  None from an engine
that marks no requests."""
from serving import percentile


def read(run):
    waits = []
    for r in run.tracked:
        queued = getattr(r.req, "queued", None)
        if queued is None:
            return None
        admitted = r.req.admitted
        waits.append((admitted if admitted is not None
                      else run.served.drain_end) - queued)
    return percentile(waits, 90) * 1e3 if waits else None
