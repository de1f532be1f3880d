"""95th percentile of the gaps between consecutive output tokens, over every
gap of every request that ends inside the window.  Two tokens of one
request that reach the host after the same step are a gap of 0."""
from serving import percentile


def read(run):
    s = run.served
    gaps = [b - a for r in s.records for a, b in zip(r.times, r.times[1:])
            if s.w0 <= b < s.w1]
    return percentile(gaps, 95) * 1e3 if gaps else None
