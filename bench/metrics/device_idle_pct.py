"""Share of the traced window in which no operation ran on the fullest
device: 100 x (1 - union of its operation intervals / window)."""
from harness import trace as T


def read(ctx):
    tr = ctx["trace"]
    dev, b = T.fullest(tr), T.bounds(tr)
    if dev is None:
        return None
    return 100.0 * (1.0 - T.busy_ns(tr, dev, *b) / (b[1] - b[0]))
