"""The compiled superstep's share of its roofline: the least time of the
superstep's required work (bench/counts/pca.py: the samples read once, the
iterate-sized buffer read and written per gossip round, against
bench/peaks.json) over the device time of the compiled superstep in the
trace. It times the whole step, every kernel and copy in it, so it also
bounds what a gain in any one kernel can claim."""
from bench.counts import pca


def read(ctx):
    t, w, cfg = ctx.trace, ctx.window, ctx.spec.config
    if t is None or not ctx.peaks:
        return None
    mean_s = t.module_mean_s("superstep")
    if mean_s is None:
        return None
    least = w.rounds_per_superstep * pca.least_seconds(
        batch=int(ctx.spec.traffic["batch"]), dim=int(cfg["dim"]),
        nodes=int(cfg["nodes"]), rounds=int(cfg["averaging"]["rounds"]),
        peaks=ctx.peaks)
    return 100.0 * least / mean_s
