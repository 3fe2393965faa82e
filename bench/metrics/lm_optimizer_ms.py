"""Per training step, the device time of the optimizer update: the self
time of the compiled superstep's operations under the `optimizer` scope, in
the traced window, mean over chips (device trace; each operation's scope is
read from the executable that ran, `bench/spans.py`)."""
from bench import spans


def read(ctx):
    return spans.step_ms(ctx, "optimizer")
