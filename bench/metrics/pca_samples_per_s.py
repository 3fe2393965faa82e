"""Stream samples learned over the window, over its whole span (host
clock)."""


def read(ctx):
    w = ctx.window
    if w.span_s <= 0:
        return None
    return w.samples / w.span_s
