"""The matmul kernel's share of its roofline over the traced window: for
every ``hbp_matmul`` event, the least time its (m, k) @ (k, n) product needs
on this chip (shapes read from the event's HLO text), summed, over the sum
of the events' device times."""
import devtrace
import flops

KERNEL = "hbp_matmul"


def read(run):
    least = busy = 0.0
    for ev in devtrace.kernel_events(run.trace, KERNEL):
        (_, (m, k)), (dtype, (_, n)) = devtrace.operand_shapes(ev)[:2]
        itemsize = 4 if dtype == "f32" else 2
        least += flops.least_time_s(*flops.matmul(m, k, n, itemsize),
                                    run.peak)
        busy += ev.dur_ns / 1e9
    if not busy:
        return None
    return 100.0 * least / busy
