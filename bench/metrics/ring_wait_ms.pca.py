"""Per superstep, the host time the consumer waited for its staged batch:
the streaming driver's `stream.ring_wait` spans in the traced window (the
device trace's host planes; `bench/spans.py`). The name under which the
streaming PCA cells report it."""
from bench import spans


def read(ctx):
    return spans.host_ms(spans.cell_trace(ctx), ("stream.ring_wait",))
