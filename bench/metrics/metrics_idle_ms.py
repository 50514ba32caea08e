"""Idle time of the fullest device per window while the host was inside
``engine.metrics``, the innermost engine span over the idle instant (ms)."""
from harness import scopes


def read(ctx):
    return scopes.idle_ms(ctx["trace"], "metrics_idle_ms")
