"""Rows that carried prompt tokens per prefill launch, over the traced
window.  Every prefill launch runs the whole batch of slots, so the rest of
its rows are padding."""


def read(run):
    s = run.served
    launches = (s.stats_w1["launches"]["prefill"]
                - s.stats_w0["launches"]["prefill"])
    if not launches:
        return None
    rows = sum(len(st["prefill_first"]) + len(st["prefill_cont"])
               for st in s.steps)
    return rows / launches
