"""Per training step, the device time of the consensus exchange: the self
time of the operations under the `gossip.pack`, `gossip.mix` and
`gossip.unpack` scopes of the compiled superstep in the traced window, mean
over chips (device trace, each operation's scope read from the executable
that ran; `bench/spans.py`)."""
from bench import spans


def read(ctx):
    return spans.step_ms(ctx, "gossip")
