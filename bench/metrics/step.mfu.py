"""Model operations of every launch in the traced window (useful tokens
only, from shapes) over the chip's bf16 peak times the window."""
import flops


def read(run):
    ref, cfg = run.ref, run.cfg
    total = 0
    for st in run.served.steps:
        for a, b in st["prefill_first"] + st["prefill_cont"]:
            total += flops.prefill_flops(ref, cfg, a, b)
        total += sum(flops.decode_flops(ref, cfg, kv)
                     for kv in st["decode_kv"])
    if not total:
        return None
    return 100.0 * total / (run.peak["bf16_flops_per_s"] * run.trace.window_s)
