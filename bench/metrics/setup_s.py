"""Set-up: process start to the opening of the window, compile included
(host clock)."""


def read(ctx):
    return ctx.setup_s
