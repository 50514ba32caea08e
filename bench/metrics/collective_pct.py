"""Share of the traced window that the fullest device spent in XLA
collectives (all-reduce, all-gather, reduce-scatter, collective-permute,
all-to-all)."""
from harness import trace as T


def read(ctx):
    tr = ctx["trace"]
    dev, b = T.fullest(tr), T.bounds(tr)
    if dev is None:
        return None
    ns = T.busy_ns(tr, dev, *b, pattern=T.COLLECTIVE)
    return 100.0 * ns / (b[1] - b[0]) if ns else None
