"""The whole training step's share of the chip's bf16 peak: model FLOPs per
token (bench/counts/granite.py: 6 per matmul parameter with the tied
unembedding, plus causal attention, no recompute) times the tokens one chip
trains on per superstep, over the device time of the compiled superstep in
the trace (mean over supersteps and chips) times the peak of
bench/peaks.json."""
from bench.counts import granite


def read(ctx):
    t, w, cfg = ctx.trace, ctx.window, ctx.spec.config
    if t is None or not ctx.peaks or w.tokens_per_sample is None:
        return None
    step_s = t.module_mean_s("superstep")
    if step_s is None:
        return None
    flops = granite.train_flops_per_token(cfg, ctx.spec.traffic["seq_len"])
    tokens = w.samples_per_superstep * w.tokens_per_sample / w.chips
    return 100.0 * flops * tokens / (step_s * ctx.peaks["bf16_flops_per_s"])
