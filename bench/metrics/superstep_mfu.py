"""Useful FLOPs over the traced window, over chips x the chip's bf16 peak.

Useful FLOPs are 3 x the forward FLOPs of every sample that a scheduled
vehicle trained (forward, and a backward of twice the forward), plus the
forward FLOPs of evaluation.  Padded slots and recomputation do not
count."""
from harness import trace as T


def read(ctx):
    b = T.bounds(ctx["trace"])
    recs = ctx["records"]
    if b is None or not recs or not ctx["trace"]["devices"]:
        return None
    fwd = ctx["forward_flops_per_sample"]
    samples = sum(r["n_scheduled"] for r in recs) * ctx["samples_per_vehicle"]
    flops = 3.0 * fwd * samples + fwd * ctx["eval_samples"]
    window_s = (b[1] - b[0]) / 1e9
    return 100.0 * flops / (window_s * ctx["chips"] * ctx["peak_flops"])
