"""Host-side streaming input pipeline: glues a token/sample source to the
streaming governor (core.streaming) and the trainer.

The governor decides (B, mu) from the rate model; the pipeline yields
device-ready batches of exactly B samples per round, discarding mu, and tracks
t' (samples arrived) so training curves can be plotted against the paper's
x-axis.

Two streaming-engine extensions (see train.driver for the full picture):

* **Supersteps** — `next_superstep(K)` draws K governed rounds and stacks them
  on a new leading K axis, feeding the K-round `lax.scan` inside the jitted
  train step so dispatch and metric-fetch overhead is paid once per K rounds.
  Given `out`, it stacks into the caller's buffers instead of fresh arrays
  (the driver recycles a few host buffer sets: train.driver).
* **Async prefetch** — `DevicePrefetcher` runs the governed splitter in a
  background thread and stages the *next* superstep onto devices
  (`jax.device_put`) while the current one computes, overlapping host sample
  synthesis + H2D with device work (the compute/stream overlap of Fig. 4).
  Each staged item carries a counter snapshot so consumer-visible accounting
  (`samples_arrived`, `samples_discarded`, `rounds`) stays coherent with the
  batch being trained on, not with how far ahead the producer has run, and
  the host seconds its producer spent on it (`repro.obs` spans).
* **Checkpoint continuity** — the splitter's exact stream position
  (counter quad + PRNG bit-generator state + live plan) is exported by
  `splitter_state()` / restored by `load_splitter_state()` (both from
  `GovernedPlanMixin`). `train.snapshot` threads that snapshot through the
  prefetch ring via the `meta` hook, so a resumed run re-deals the
  staged-but-unconsumed supersteps a crash threw away instead of skipping
  those stream samples (docs/DESIGN.md §Fault-tolerant streaming).
* **Adaptive B** — `update_plan` may move B between the buckets of an adopted
  `core.rates.BucketLadder` mid-stream
  (docs/DESIGN.md §Adaptive batch buckets). The plan is latched once per
  superstep under a lock, so every
  superstep is dealt at a single width even when the swap lands from the
  consumer thread mid-production; supersteps already staged in the prefetch
  ring keep their old width (their samples were drawn — dropping them would
  lose stream samples) and drain through the pre-compiled old-bucket
  superstep, while each staged item's `meta` snapshot tells the consumer
  which plan dealt it.
"""
from __future__ import annotations

import queue
import threading
import time
from typing import Any, Callable, Dict, Iterator, NamedTuple, Optional, Tuple

import numpy as np

from repro import obs
from repro.configs.base import StreamConfig
from repro.core.rates import BucketLadder, Plan, plan as make_plan
from repro.core.streaming import GovernedPlanMixin


class StreamCounters(NamedTuple):
    """Splitter accounting as of a specific round (the paper's t' bookkeeping)."""

    samples_arrived: int
    samples_consumed: int
    samples_discarded: int
    rounds: int


class StreamingPipeline(GovernedPlanMixin):
    def __init__(self, sample_fn: Callable[[np.random.Generator, int], Dict[str, np.ndarray]],
                 stream_cfg: StreamConfig, n_nodes: int, rounds_R: int, *,
                 batch: Optional[int] = None, horizon: Optional[float] = None,
                 ladder: Optional[BucketLadder] = None, seed: int = 0):
        if stream_cfg.streaming_rate > 0:
            self.plan = make_plan(stream_cfg, n_nodes, rounds_R, B=batch,
                                  horizon_samples=horizon)
        else:
            self.plan = Plan(B=batch or n_nodes, mu=max(stream_cfg.forced_mu, 0),
                             R=rounds_R, Re=float("inf"), regime="resourceful")
        self.stream_cfg = stream_cfg
        self.sample_fn = sample_fn
        self.n_nodes = n_nodes
        # adopt_ladder / update_plan / last_superstep_plan: GovernedPlanMixin
        self._init_plan_state(ladder, horizon)
        self._rng = np.random.default_rng(seed)
        self.samples_arrived = 0
        self.samples_consumed = 0
        self.samples_discarded = 0
        self.rounds = 0

    def counters(self) -> StreamCounters:
        return StreamCounters(self.samples_arrived, self.samples_consumed,
                              self.samples_discarded, self.rounds)

    def _round(self, plan: Plan) -> Dict[str, np.ndarray]:
        B, mu = plan.B, plan.mu
        batch = self.sample_fn(self._rng, B + mu)
        batch = {k: v[:B] for k, v in batch.items()}  # splitter discards mu
        self.samples_arrived += B + mu
        self.samples_consumed += B
        self.samples_discarded += mu
        self.rounds += 1
        return batch

    def __iter__(self) -> Iterator[Dict[str, np.ndarray]]:
        return self

    def __next__(self) -> Dict[str, np.ndarray]:
        return self._round(self._latch_plan())

    def next_superstep(self, k: int, sink: Optional[Dict[str, float]] = None,
                       *, out: Optional[Any] = None,
                       **ids) -> Dict[str, np.ndarray]:
        """Draw K governed rounds and stack them: leaves [K, B, ...]. The
        plan is latched once for the whole superstep, so a concurrent
        `update_plan` can never produce ragged round widths within one
        stack. Each round is a `stream.draw` span and the stack a
        `stream.stack` span (`repro.obs`), timed into `sink` and carrying
        `ids`.

        Without `out` every call stacks into fresh arrays. With `out` the
        rounds are stacked into the arrays it gives, which are returned:
        `out` is a dict of arrays keyed like the rounds, or a function
        that takes the superstep's layout, `((key, shape, dtype), ...)`,
        and returns one (so a caller can choose buffers by a shape it
        learns only from the draws)."""
        plan = self._latch_plan()
        rounds = []
        for _ in range(k):
            with obs.span("stream.draw", sink, **ids):
                rounds.append(self._round(plan))
        with obs.span("stream.stack", sink, **ids):
            if callable(out):
                out = out(tuple((key, (k, *v.shape), v.dtype)
                                for key, v in rounds[0].items()))
            if out is None:
                out = {key: np.stack([r[key] for r in rounds]) for key in rounds[0]}
            else:
                for key in rounds[0]:
                    np.stack([r[key] for r in rounds], out=out[key])
        self._last_superstep_plan = plan
        return out


class _Stop:
    pass


class _Raise(NamedTuple):
    exc: BaseException


class _Staged(NamedTuple):
    batch: Any
    counters: Optional[StreamCounters]
    meta: Any
    host_s: Optional[Dict[str, float]]
    lease: Any
    ready: float  # perf_counter when the producer began to offer it


class _Ring(queue.Queue):
    """The bounded FIFO between producer and consumer. As a staged item
    enters (under the ring's lock, before the consumer can take it), its
    `host_s` gains `ring_put_wait`: the seconds its producer waited for
    room, i.e. back-pressure from the consumer."""

    def _put(self, item) -> None:
        if isinstance(item, _Staged) and item.host_s is not None:
            item.host_s["ring_put_wait"] = time.perf_counter() - item.ready
        super()._put(item)


class DevicePrefetcher:
    """Depth-bounded prefetch ring between a host-side producer and the
    training loop: a daemon thread repeatedly calls `produce()` (host sample
    synthesis through the governed splitter) and `stage()` (sharded
    `jax.device_put`) so the next superstep's H2D transfer happens while the
    current superstep computes.

    `counters()` is sampled immediately after each produce; `__next__` returns
    the staged batch after adopting that snapshot into `self.counters`, so the
    consumer sees exactly the accounting a synchronous loop would have seen at
    that round — regardless of how far ahead the producer ring has run. The
    optional `meta` hook rides the same snapshot mechanism (e.g. the
    pipeline's `last_superstep_plan`, so the consumer knows which batch
    bucket a staged superstep was dealt at even while the ring drains items
    produced under a superseded plan).

    The optional `spans` hook, sampled with them, returns the host-seconds
    dict of the superstep just produced and the ids its spans carry
    (`repro.obs`): the wait for room in the ring is a
    `stream.ring_put_wait` span with those ids, and its seconds enter that
    dict as the item enters the ring. `__next__` exposes the dict as
    `host_s`, and `ring_depth` is the number of staged items left behind
    the one taken.

    The optional `lease` hook, sampled with them, returns what the
    consumer hands back once it is done with the batch (the driver's
    host buffer set); `__next__` exposes it as `lease`.
    """

    def __init__(self, produce: Callable[[], Any], *,
                 stage: Optional[Callable[[Any], Any]] = None,
                 counters: Optional[Callable[[], StreamCounters]] = None,
                 meta: Optional[Callable[[], Any]] = None,
                 spans: Optional[Callable[[], Tuple[Optional[Dict[str, float]],
                                                    Dict[str, Any]]]] = None,
                 lease: Optional[Callable[[], Any]] = None,
                 depth: int = 2):
        if depth < 1:
            raise ValueError("prefetch depth must be >= 1")
        self._produce = produce
        self._stage = stage or (lambda x: x)
        self._counters = counters or (lambda: None)
        self._meta = meta or (lambda: None)
        self._spans = spans or (lambda: (None, {}))
        self._lease = lease or (lambda: None)
        self._q: queue.Queue = _Ring(maxsize=depth)
        self._stop = threading.Event()
        self._final: Optional[Any] = None  # latched _Stop/_Raise terminal state
        self._undelivered: Optional[_Raise] = None  # error stranded by close()
        self._close_raised = False  # close() re-raises a pending error ONCE
        self._error_delivered = False  # __next__ already surfaced the error
        self.counters: Optional[StreamCounters] = None
        self.meta: Optional[Any] = None
        self.host_s: Optional[Dict[str, float]] = None
        self.lease: Any = None
        self.ring_depth = 0
        self._thread = threading.Thread(target=self._worker, daemon=True,
                                        name="device-prefetch")
        self._thread.start()

    def _put_stopaware(self, item: Any) -> bool:
        """Bounded-ring put that wakes promptly when close() sets the stop
        event (a plain blocking put could deadlock against close()'s drain).
        Returns False when the item could not be delivered because the ring
        was shut down first — terminal `_Raise` items must then be stashed,
        not dropped, or a pending producer error would vanish."""
        while not self._stop.is_set():
            try:
                self._q.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def _worker(self) -> None:
        try:
            while not self._stop.is_set():
                try:
                    item = self._produce()
                except StopIteration:
                    break
                snap = self._counters()
                meta = self._meta()
                host_s, ids = self._spans()
                lease = self._lease()
                staged = self._stage(item)
                with obs.span("stream.ring_put_wait", **ids):
                    self._put_stopaware(_Staged(staged, snap, meta, host_s,
                                                lease, time.perf_counter()))
        except BaseException as e:  # surface producer failures at the consumer
            if not self._put_stopaware(_Raise(e)):
                self._undelivered = _Raise(e)
            return
        self._put_stopaware(_Stop())

    def __iter__(self) -> "DevicePrefetcher":
        return self

    def __next__(self):
        # once the worker has signalled termination, nothing will ever be
        # enqueued again — keep resolving without touching the queue
        got = self._final if self._final is not None else self._q.get()
        if isinstance(got, _Stop):
            self._final = got
            raise StopIteration
        if isinstance(got, _Raise):
            self._final = got
            self._error_delivered = True
            raise got.exc
        self.ring_depth = self._q.qsize()
        if got.counters is not None:
            self.counters = got.counters
        if got.meta is not None:
            self.meta = got.meta
        self.host_s = got.host_s
        self.lease = got.lease
        return got.batch

    def _drain(self) -> Optional[_Raise]:
        """Empty the ring; return the last pending `_Raise` found, if any."""
        pending = None
        try:
            while True:
                item = self._q.get_nowait()
                if isinstance(item, _Raise):
                    pending = item
        except queue.Empty:
            pass
        return pending

    def close(self) -> None:
        """Shut the ring down. Never deadlocks against a worker blocked on a
        full ring (`_put_stopaware` polls the stop event), and re-raises a
        producer error that was still pending — staged in the ring or
        stranded by the shutdown itself — exactly once; an error already
        delivered through `__next__` is not raised again. Idempotent
        otherwise."""
        self._stop.set()
        # drain so a blocked producer can observe the stop event
        pending = self._drain()
        self._thread.join(timeout=5.0)
        # the worker may have enqueued (or stashed) its error between the
        # first drain and its exit
        pending = self._drain() or pending or self._undelivered
        self._undelivered = None
        if self._final is None:
            # nothing will ever be enqueued again: a post-close __next__
            # must not block on the dead worker
            self._final = pending if pending is not None else _Stop()
        if (pending is not None and not self._error_delivered
                and not self._close_raised):
            self._close_raised = True
            raise pending.exc

    def __enter__(self) -> "DevicePrefetcher":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
