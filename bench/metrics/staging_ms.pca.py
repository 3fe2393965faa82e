"""Per superstep, the producer's host time staging the batch onto the
device: the `stream.stage` span (`device_put`) in the traced window (device
trace's host planes; `bench/spans.py`)."""
from bench import spans


def read(ctx):
    return spans.host_ms(spans.cell_trace(ctx), ("stream.stage",))
