"""Share of the window in which the engine's host was not blocked on a
launch's outputs: 100 x (1 - the window's seconds in ``engine.wait`` spans,
from ``Engine.stats()["spans"]`` at the window's two ends, over the
window).  The host's account of ``device.idle_share``.  None from an engine
that reports no spans."""


def read(run):
    s = run.served
    w0, w1 = s.stats_w0.get("spans"), s.stats_w1.get("spans")
    if w0 is None or w1 is None:
        return None
    wait = (w1.get("engine.wait", {}).get("s", 0.0)
            - w0.get("engine.wait", {}).get("s", 0.0))
    return 100.0 * (1.0 - wait / (s.window_end - s.w0))
