"""Share of the traced window in which no operation ran on the device,
averaged over the cell's chips (device trace; `trace_reduce.Summary.idle_pct`).
The name under which the LM training cells report it."""


def read(ctx):
    return ctx.trace.idle_pct() if ctx.trace is not None else None
