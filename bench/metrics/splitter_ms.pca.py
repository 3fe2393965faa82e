"""Per superstep, the producer's host time in the splitter: the K
`stream.draw` spans (each round's `sample_fn` call and its discard), the
`stream.stack` of the K rounds and the `stream.node_split` into per-node
shares, in the traced window (device trace's host planes;
`bench/spans.py`)."""
from bench import spans


def read(ctx):
    return spans.host_ms(spans.cell_trace(ctx), spans.SPLITTER)
