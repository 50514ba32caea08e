"""The rest of the fullest device's idle time per window (ms): under
``engine.stage``, ``engine.dispatch``, ``engine.global_model`` or the
``engine.window`` span itself, or outside any engine span.  With
``pull_idle_ms``, ``metrics_idle_ms`` and ``eval_idle_ms`` it sums to
``window_gap_ms``."""
from harness import scopes


def read(ctx):
    return scopes.idle_ms(ctx["trace"], "other_idle_ms")
