"""90th percentile, over the requests the window answers for, of the time
from a request's first admission into a slot to its first token (the
request's ``admitted`` and ``first_token`` marks): its prefill iterations.
A request admitted with no token by the drain's end counts with the time
it had then; one never admitted is left out.  None from an engine that
marks no requests."""
from serving import percentile


def read(run):
    spans = []
    for r in run.tracked:
        if not hasattr(r.req, "admitted"):
            return None
        if r.req.admitted is None:
            continue
        first = r.req.first_token
        spans.append((first if first is not None
                      else run.served.drain_end) - r.req.admitted)
    return percentile(spans, 90) * 1e3 if spans else None
