"""Decode attention's share of its roofline: the least time one call needs
for the live keys and values of the rows that decoded (from shapes and each
row's context length, averaged over the window's decode launches) over the
mean device time of one decode call of the attention kernel (``fa`` with a
single query position)."""
import devtrace
import flops

KERNEL = "fa"


def _query_len(ev) -> int:
    """Query positions of one call: the middle axis of its first 3-D
    operand, (rows x heads, positions, head_dim)."""
    q = [shape for _, shape in devtrace.operand_shapes(ev) if len(shape) == 3]
    return q[0][1] if q else 0


def read(run):
    calls = [ev for ev in devtrace.kernel_events(run.trace, KERNEL)
             if _query_len(ev) == 1]
    steps = [st for st in run.served.steps if st["decode_kv"]]
    if not calls or not steps:
        return None
    least = sum(flops.least_time_s(*flops.attn_decode_call(
        run.ref, run.cfg, st["decode_kv"]), run.peak)
        for st in steps) / len(steps)
    mean_ns = sum(ev.dur_ns for ev in calls) / len(calls)
    return 100.0 * least / (mean_ns / 1e9)
