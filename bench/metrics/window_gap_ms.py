"""Mean device idle per fused window on the fullest device: the traced
window's idle time over its number of windows.  It is the host's work
around each ``run_superstep`` call (staging, the host pull, round metrics,
cost accounting, evaluation dispatch) as the device sees it."""
from harness import trace as T


def read(ctx):
    ns = T.idle_per_window_ns(ctx["trace"])
    return ns / 1e6 if ns is not None else None
