"""Useful FLOPs over the traced window, over chips x the chip's bf16 peak,
for a frozen-base (split LoRA) cell.

Useful FLOPs are 2 x the forward FLOPs of every sequence that a scheduled
vehicle trained (the forward, and the input-gradient backward through the
frozen base, as costly as the forward), plus the forward FLOPs of
evaluation.  The LoRA weight gradients (about 1% of the forward) are left
out, as are padded slots and the recomputed forward of rematerialised
layers.  ``superstep_mfu`` counts 3 x the forward, the weight gradients of
full training, which would overstate a frozen base by half."""
from harness import trace as T


def read(ctx):
    b = T.bounds(ctx["trace"])
    recs = ctx["records"]
    if b is None or not recs or not ctx["trace"]["devices"]:
        return None
    fwd = ctx["forward_flops_per_sample"]
    samples = sum(r["n_scheduled"] for r in recs) * ctx["samples_per_vehicle"]
    flops = 2.0 * fwd * samples + fwd * ctx["eval_samples"]
    window_s = (b[1] - b[0]) / 1e9
    return 100.0 * flops / (window_s * ctx["chips"] * ctx["peak_flops"])
