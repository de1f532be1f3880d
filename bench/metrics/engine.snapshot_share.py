"""Share of the window the engine spent copying rows to the host: 100 x the
window's seconds in ``engine.snapshot`` spans, from ``Engine.stats()["spans"]``
at the window's two ends, over the window.  None from an engine that
reports no spans."""


def read(run):
    s = run.served
    w0, w1 = s.stats_w0.get("spans"), s.stats_w1.get("spans")
    if w0 is None or w1 is None:
        return None
    snap = (w1.get("engine.snapshot", {}).get("s", 0.0)
            - w0.get("engine.snapshot", {}).get("s", 0.0))
    return 100.0 * snap / (s.window_end - s.w0)
