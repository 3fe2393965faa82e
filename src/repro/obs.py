"""Host spans of the streaming engine.

`span(name, sink, **ids)` times a stretch of host work twice over: as a
`jax.profiler.TraceAnnotation`, so it lands in the profiler's trace on the
same clock as the device's events (and costs about a microsecond when no
profiler session is open), and as seconds added to `sink[key]`, a plain
dict of one superstep that `StreamingDriver` keeps in its history whether
or not a profiler runs. The key is the span's name after its last dot
(`stream.ring_wait` -> `ring_wait`). `ids` become the event's stats in the
trace (`StreamingDriver` passes `step`, the superstep's sequence number,
so the producer's and the consumer's spans of one superstep can be
joined).

Span names and the thread that runs each are listed in docs/DESIGN.md
§Observability.
"""
from __future__ import annotations

import time
from typing import Dict, Optional

import jax


def key(name: str) -> str:
    """The `host_s` key a span adds to: its name after the last dot."""
    return name.rpartition(".")[2]


class span:
    """Context manager: a profiler trace event named `name` carrying `ids`,
    whose duration is also added to `sink[key(name)]` (when a sink is
    given)."""

    __slots__ = ("_name", "_sink", "_ids", "_annotation", "_t0")

    def __init__(self, name: str, sink: Optional[Dict[str, float]] = None,
                 **ids):
        self._name, self._sink, self._ids = name, sink, ids

    def __enter__(self) -> "span":
        self._annotation = jax.profiler.TraceAnnotation(self._name, **self._ids)
        self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        dt = time.perf_counter() - self._t0
        self._annotation.__exit__(*exc)
        if self._sink is not None:
            k = key(self._name)
            self._sink[k] = self._sink.get(k, 0.0) + dt
