"""Full-loop async checkpoint/restore for the streaming engine
(docs/DESIGN.md §Fault-tolerant streaming).

The stream cannot be replayed — samples not processed within a superstep are
discarded by design (eq. 4's mu) — so a crash without checkpoints loses the
run. `RunSnapshotter` captures the COMPLETE run state at the superstep
boundary (the PR 5 plan latch already makes that a consistency barrier):

* the TrainState (device arrays),
* the splitter's exact stream position — `StreamCounters` quad + PRNG
  bit-generator state + the plan that dealt the last *consumed* superstep
  (`GovernedPlanMixin.splitter_state`, threaded through the prefetch ring's
  `meta` hook so staged-but-unconsumed supersteps are re-dealt on resume,
  not skipped),
* the governor: `RoundTimeEstimator` window, `BucketHysteresis` streak,
  per-signature warm-up counts, the live post-replan `Plan`,
* elastic membership: the active `Membership` and `StragglerPolicy`
  per-node EWMAs / debounce verdicts,
* the publisher's version counter (monotone across restart).

The training thread never blocks on disk: `maybe_snapshot` dispatches a
jitted `a + 0` copy of the state (fresh buffers, async dispatch — the
`serve.publisher.SnapshotPublisher` idiom), gathers the host-side meta
(microseconds of dict building), and hands both to a background writer
thread on the `data.pipeline.DevicePrefetcher` staging pattern. The writer
does the `device_get`, the retried leaf writes, the atomic manifest, and
last-k retention (`train.checkpoint`); a failed save is recorded in
`SnapshotStats` and never propagates into the training thread.

Snapshot cadence is governed twice: a superstep cadence (`every`) and an
EWMA cost governor mirroring the publisher's — the smoothed training-thread
dispatch cost must stay under `overhead_budget` x the wall time since the
last snapshot, so checkpointing can never eat more than the configured
fraction of the loop no matter how small `every` is set.

`restore_driver` rebuilds a `StreamingDriver` mid-stream from the newest
*valid* checkpoint (torn saves are skipped — `train.checkpoint.newest_valid`)
with exact counter/plan/cohort continuity: on the deterministic clock in
exact mode the resumed run is bit-identical to the uninterrupted one.
"""
from __future__ import annotations

import dataclasses
import queue
import threading
import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax

from repro.core.mixing import Membership
from repro.core.rates import Plan
from repro.train import checkpoint


@dataclasses.dataclass
class SnapshotStats:
    saves: int = 0  # durable manifests written by the writer thread
    dispatches: int = 0  # snapshots handed to the writer
    skipped_cadence: int = 0  # not on the `every` superstep grid
    skipped_budget: int = 0  # EWMA cost would exceed the overhead budget
    skipped_busy: int = 0  # writer still on the previous snapshot
    failures: int = 0  # saves that exhausted retries (training unaffected)
    last_error: Optional[str] = None
    cost_ewma_s: Optional[float] = None  # smoothed training-thread dispatch cost
    total_cost_s: float = 0.0  # summed training-thread dispatch cost


def capture_meta(driver) -> dict:
    """Everything host-side a resumed driver needs, as one JSON-serializable
    dict. Captured at the superstep boundary AFTER `_observe` (replan) and
    publication, so the live plan is the post-replan one that deals future
    supersteps, while the splitter snapshot pins the stream position of the
    last consumed superstep."""
    meta: Dict[str, Any] = {
        "supersteps_done": int(driver._supersteps_done),
        "splitter": (driver._last_splitter_state
                     if driver._last_splitter_state is not None
                     else driver.pipeline.splitter_state()),
        "live_plan": driver.pipeline.plan.to_json(),
        "last_round_s": driver._last_round_s,
        "sig_seen": [[int(b), int(m), int(c)]
                     for (b, m), c in sorted(driver._sig_seen.items())],
        "hysteresis": driver._hysteresis.state_dict(),
        "estimator": (driver._estimator.state_dict()
                      if driver._estimator is not None else None),
        "straggler": (driver._straggler.state_dict()
                      if driver._straggler is not None else None),
        "membership": (driver._membership.to_json()
                       if driver._membership is not None else None),
        "publisher": (driver._publisher.state_dict()
                      if driver._publisher is not None else None),
    }
    return meta


def _restore_put(state) -> Callable:
    """A `checkpoint.restore` put that lands each leaf back on the sharding
    the live state's corresponding leaf occupies (restore across the same
    mesh the driver was built under). Committed-ness is mirrored too: an
    explicit-device `device_put` yields a COMMITTED array, and commitment
    feeds the jit compile options — restoring an uncommitted leaf as
    committed would give the resumed process different XLA cache keys than
    the run it is resuming, defeating the persistent compilation cache's
    warm restart."""
    flat = checkpoint._flatten(state)

    def put(key, arr):
        like = flat[key]
        sharding = getattr(like, "sharding", None)
        if sharding is not None and getattr(like, "committed", True):
            return jax.device_put(arr, sharding)
        return jax.device_put(arr)

    return put


def restore_driver(driver, root_or_path: str) -> str:
    """Restore a freshly constructed `StreamingDriver` to the exact point a
    snapshot was taken. `root_or_path` is either a snapshot root (the newest
    valid step directory is selected — torn saves are skipped) or one step
    directory. Returns the path restored from; raises FileNotFoundError when
    no valid checkpoint exists.

    The driver must be constructed with the same config the snapshot was
    taken under (same N, R, buckets, workload); deterministically derived
    objects — cohort ladders, compiled supersteps, ids caches — are NOT in
    the snapshot and are rebuilt lazily, exactly as the uninterrupted run
    built them (with a persistent compilation cache, re-compiles become
    cache hits; see `launch.env.compilation_cache_env`)."""
    if checkpoint.list_steps(root_or_path):
        path = checkpoint.newest_valid(root_or_path)
        if path is None:
            raise FileNotFoundError(
                f"no valid checkpoint under {root_or_path!r} "
                f"(every step directory is torn or corrupt)")
    elif checkpoint.is_valid(root_or_path):
        path = root_or_path
    else:
        raise FileNotFoundError(
            f"no valid checkpoint at {root_or_path!r}")

    meta = checkpoint.load_manifest(path)["meta"]
    driver.state = checkpoint.restore(path, jax.eval_shape(lambda: driver.state),
                                      put=_restore_put(driver.state))

    live_plan = Plan.from_json(meta["live_plan"])
    mem = meta.get("membership")
    if mem is not None:
        membership = Membership.from_json(mem)
        driver._membership = membership
        # cohort ladders re-derive from the full-membership base ladder, so a
        # rejoin after resume restores the same buckets (and re-uses the same
        # compiled signatures) the uninterrupted run would
        driver.ladder = driver._ladder_for(membership.n_active)
    driver.pipeline.ladder = driver.ladder
    driver.pipeline.load_splitter_state(meta["splitter"], plan=live_plan)

    driver._supersteps_done = int(meta["supersteps_done"])
    driver._last_round_s = meta.get("last_round_s")
    driver._sig_seen = {(int(b), int(m)): int(c)
                        for b, m, c in meta.get("sig_seen", [])}
    driver._last_splitter_state = meta["splitter"]
    driver._hysteresis.load_state_dict(meta["hysteresis"])
    if meta.get("estimator") is not None and driver._estimator is not None:
        driver._estimator.load_state_dict(meta["estimator"])
    if meta.get("straggler") is not None and driver._straggler is not None:
        driver._straggler.load_state_dict(meta["straggler"])
    if meta.get("publisher") is not None and driver._publisher is not None:
        driver._publisher.load_state_dict(meta["publisher"])
    return path


class _Flush:
    pass


class RunSnapshotter:
    """Async snapshot writer for `StreamingDriver` (attach via the driver's
    `snapshotter=` argument; `maybe_snapshot` runs at every superstep
    boundary, outside the governor-timed window).

    `every` is the superstep cadence (a snapshot is considered every
    `every`-th superstep); `overhead_budget` caps the smoothed
    training-thread dispatch cost as a fraction of wall time between
    snapshots; `keep_last` is the retention depth (`train.checkpoint.prune`);
    `retries`/`backoff_s` feed the writer's retry-with-backoff around leaf
    writes. `block=True` makes `maybe_snapshot` wait for the durable
    manifest — for deterministic tests, never production."""

    def __init__(self, root: str, *, every: int = 1, keep_last: int = 3,
                 overhead_budget: float = 0.05, retries: int = 3,
                 backoff_s: float = 0.05, block: bool = False,
                 alpha: float = 0.5,
                 clock: Callable[[], float] = time.perf_counter):
        if every < 1:
            raise ValueError(f"snapshot cadence must be >= 1: {every}")
        if keep_last < 1:
            raise ValueError(f"keep_last must be >= 1: {keep_last}")
        if overhead_budget < 0:
            raise ValueError(f"overhead_budget must be >= 0: {overhead_budget}")
        if not 0.0 < alpha <= 1.0:
            raise ValueError(f"alpha must be in (0, 1]: {alpha}")
        self.root = root
        self.every = every
        self.keep_last = keep_last
        self.overhead_budget = overhead_budget
        self.retries = retries
        self.backoff_s = backoff_s
        self.block = block
        self.alpha = alpha
        self.clock = clock
        self.stats = SnapshotStats()
        self._copy = None  # jitted lazily, once per state treedef
        self._last_dispatch_t: Optional[float] = None
        self._in_flight: Optional[threading.Event] = None  # last save's done
        # depth-1 ring: at most one snapshot in flight; a second arriving
        # while the writer is mid-save is skipped (the next cadence hit
        # takes a fresher one anyway) rather than queueing unbounded copies
        self._q: "queue.Queue" = queue.Queue(maxsize=1)
        self._closed = False
        self._thread = threading.Thread(target=self._worker, daemon=True,
                                        name="snapshot-writer")
        self._thread.start()

    # ------------------------------------------------------------- capture

    def _copy_fn(self) -> Callable:
        if self._copy is None:
            # fresh buffers, async dispatch: the checkpointed leaves must not
            # alias the trainer's (potentially donated) buffers, and the
            # device-to-device copy overlaps the next superstep — the
            # training thread pays dispatch cost only (the publisher idiom)
            self._copy = jax.jit(
                lambda t: jax.tree.map(lambda a: a + 0, t))
        return self._copy

    def maybe_snapshot(self, driver) -> Optional[Dict[str, Any]]:
        """Snapshot the driver if the cadence and the cost governor allow.
        Returns {"step", "path"} when a snapshot was dispatched (with
        `block=True`, when it is durable), else None. Never blocks on disk
        and never raises for I/O trouble — a failed save shows up in
        `stats.failures` and the next cadence hit tries again."""
        step = driver._supersteps_done
        if step % self.every != 0:
            self.stats.skipped_cadence += 1
            return None
        if self._last_dispatch_t is not None and self.overhead_budget > 0:
            elapsed = max(self.clock() - self._last_dispatch_t, 1e-12)
            ewma = self.stats.cost_ewma_s
            if ewma is not None and ewma > self.overhead_budget * elapsed:
                self.stats.skipped_budget += 1
                return None
        # depth-1 discipline: at most one snapshot in flight — the queue can
        # be empty while the writer is still mid-save, so busy-ness is the
        # previous save's done event, not queue occupancy
        if (self._q.full() or
                (self._in_flight is not None and not self._in_flight.is_set())):
            self.stats.skipped_busy += 1
            return None
        t0 = self.clock()
        copied = self._copy_fn()(driver.state)
        meta = capture_meta(driver)
        done = threading.Event()
        path = checkpoint.step_dir(self.root, step)
        try:
            self._q.put_nowait((step, copied, meta, done))
        except queue.Full:  # raced with a straggling writer
            self.stats.skipped_busy += 1
            return None
        self._in_flight = done
        cost = self.clock() - t0
        st = self.stats
        st.dispatches += 1
        st.total_cost_s += cost
        st.cost_ewma_s = cost if st.cost_ewma_s is None else (
            self.alpha * cost + (1.0 - self.alpha) * st.cost_ewma_s)
        self._last_dispatch_t = self.clock()
        if self.block:
            done.wait()
        return {"step": step, "path": path}

    # -------------------------------------------------------------- writer

    def _worker(self) -> None:
        while True:
            item = self._q.get()
            if item is None:
                return
            if isinstance(item, tuple) and isinstance(item[0], _Flush):
                item[1].set()
                continue
            step, copied, meta, done = item
            try:
                checkpoint.save(checkpoint.step_dir(self.root, step), copied,
                                step=step, meta=meta, retries=self.retries,
                                backoff_s=self.backoff_s)
                checkpoint.prune(self.root, self.keep_last)
                self.stats.saves += 1
            except Exception as e:  # never kill the training thread
                self.stats.failures += 1
                self.stats.last_error = f"{type(e).__name__}: {e}"
            finally:
                done.set()

    def flush(self) -> None:
        """Wait until every dispatched snapshot is durable (or failed)."""
        if self._closed:
            return
        done = threading.Event()
        self._q.put((_Flush(), done))
        done.wait()

    def close(self) -> None:
        """Flush pending snapshots and stop the writer (idempotent)."""
        if self._closed:
            return
        self._closed = True
        self._q.put(None)
        self._thread.join(timeout=30.0)

    def __enter__(self) -> "RunSnapshotter":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
