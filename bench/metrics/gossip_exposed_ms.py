"""Per training step, the device time of collective operations (their done
waits included) during which no other operation runs on that chip, averaged
over the chips (device trace)."""


def read(ctx):
    t, w = ctx.trace, ctx.window
    steps = w.supersteps * w.rounds_per_superstep
    if t is None or steps == 0 or t.collective_s <= 0:
        return None
    return 1e3 * t.collective_exposed_s / steps
