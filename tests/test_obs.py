"""Host spans of the streaming driver (repro.obs, docs/DESIGN.md
§Observability):

* under the profiler every `stream.*` span appears, once per superstep (K
  times for `stream.draw`), with the superstep's `step` on both the
  producer's and the consumer's thread, at prefetch depth 2 and 0
* each history record carries `host_s` (the span seconds, kept with the
  profiler off too) and `ring_depth`; the consumer's spans fit in `wall_s`;
  the unread `measured_Rp` / `measured_Re` fields are gone
"""
import collections
import glob
import os

import jax
import jax.numpy as jnp
import pytest
from jax.profiler import ProfileData

from repro import obs
from repro.configs.base import AveragingConfig
from repro.configs.paper_pca import FIG7, PCARunConfig
from repro.core import krasulina
from repro.data.synthetic import make_pca_host_sampler, make_pca_stream
from repro.train.driver import EngineConfig, StreamingDriver

K, STEPS, N = 3, 3, 4
PRODUCER = ("draw", "stack", "node_split", "stage")
CONSUMER = ("ring_wait", "dispatch", "fetch", "observe")


def _driver(depth):
    run_cfg = PCARunConfig(averaging=AveragingConfig(mode="gossip", rounds=2))
    superstep = krasulina.build_krasulina_superstep(
        run_cfg.averaging, N, lambda t: 10.0 / t)
    w0 = jax.random.normal(jax.random.PRNGKey(0), (FIG7.dim,))
    state = krasulina.init_krasulina_state(w0 / jnp.linalg.norm(w0),
                                           run_cfg.averaging, N)
    return StreamingDriver(
        run_cfg, None, state, make_pca_host_sampler(make_pca_stream(FIG7)),
        superstep_fn=superstep, n_nodes=N, batch=20,
        engine=EngineConfig(superstep=K, prefetch_depth=depth, replan_every=0))


def _stream_events(trace_dir):
    """(name, thread, step) of every `stream.*` event of the trace; a
    host plane has one line per thread."""
    path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for thread, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith("stream."):
                    stats = dict(e.stats)
                    out.append((e.name, (plane.name, thread),
                                stats.get("step", stats.get("step_num"))))
    return out


@pytest.mark.parametrize("depth", [2, 0])
def test_stream_spans_in_trace(depth, tmp_path):
    with _driver(depth) as driver:
        with jax.profiler.trace(str(tmp_path)):
            driver.run(STEPS)
    events = _stream_events(str(tmp_path))
    count = collections.Counter((name, step) for name, _, step in events)
    producer = {"stream." + k for k in PRODUCER}
    if depth:
        producer.add("stream.ring_put_wait")
    for step in range(STEPS):
        for name in ("stream.superstep",) + tuple("stream." + k for k in CONSUMER):
            assert count[(name, step)] == 1, (name, step)
        assert count[("stream.draw", step)] == K
        for name in producer - {"stream.draw"}:
            assert count[(name, step)] == 1, (name, step)
    threads = collections.defaultdict(set)
    for name, line, _ in events:
        threads["producer" if name in producer else "consumer"].add(line)
    assert len(threads["consumer"]) == 1
    if depth:
        assert threads["producer"].isdisjoint(threads["consumer"])
    else:
        assert threads["producer"] == threads["consumer"]


@pytest.mark.parametrize("depth", [2, 0])
def test_history_host_seconds(depth):
    with _driver(depth) as driver:
        _, history = driver.run(STEPS)
    want = set(PRODUCER + CONSUMER) | ({"ring_put_wait"} if depth else set())
    for rec in history:
        assert set(rec["host_s"]) == want
        assert all(v >= 0 for v in rec["host_s"].values())
        s = rec["host_s"]
        assert s["ring_wait"] + s["dispatch"] + s["fetch"] <= rec["wall_s"]
        assert 0 <= rec["ring_depth"] <= depth
        assert "measured_Rp" not in rec and "measured_Re" not in rec
        if not depth:  # the consumer deals the batch inside its wait
            assert sum(s[k] for k in PRODUCER) <= s["ring_wait"]


def test_span_adds_to_sink_by_key():
    sink = {}
    with obs.span("stream.draw", sink, step=3):
        pass
    with obs.span("stream.draw", sink, step=3):
        pass
    with obs.span("stream.ring_wait", None):
        pass
    assert set(sink) == {"draw"} and sink["draw"] >= 0
    assert obs.key("stream.node_split") == "node_split"


def test_put_wait_recorded_before_the_consumer_takes_the_item():
    """The producer's put wait enters the item's `host_s` under the ring's
    lock, before the consumer can take it: with a switch interval of a
    microsecond and a consumer that reads at once, no item arrives
    without it."""
    import sys

    from repro.data.pipeline import DevicePrefetcher

    dealt = []

    def produce():
        dealt.append({})
        return len(dealt)

    before = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        with DevicePrefetcher(produce, spans=lambda: (dealt[-1], {"step": len(dealt)}),
                              depth=1) as ring:
            for i in range(1, 301):
                assert next(ring) == i
                assert "ring_put_wait" in ring.host_s, i
    finally:
        sys.setswitchinterval(before)
