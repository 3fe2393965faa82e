"""Tokens trained on over the window, summed over all nodes and chips, over
the window's whole span (host clock). In a closed loop with mu = 0 this is
the highest stream rate the system takes."""


def read(ctx):
    w = ctx.window
    if w.tokens_per_sample is None or w.span_s <= 0:
        return None
    return w.samples * w.tokens_per_sample / w.span_s
