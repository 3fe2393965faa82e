"""Superstep streaming engine: the execution loop that keeps the device fed at
the rate the paper's analysis assumes.

The paper's Fig. 3(c) splits a streaming learner into a *splitter* (one node
receives the stream and deals B samples per round, discarding mu) and the
*compute network* (N nodes process their B/N shares, then average). Fig. 4
shows why the split matters: whenever the stream outpaces the effective
processing rate R_e (eq. 4), samples pile up or drop. A naive training loop —
one jitted step per Python iteration with host-side sample synthesis, a
blocking H2D copy, and a blocking metric fetch between steps — throttles R_p
far below hardware and makes that mismatch self-inflicted. This driver removes
it with three stages:

1. **Splitter (host thread)** — `data.pipeline.StreamingPipeline` runs the
   governed splitter of Fig. 3(c): per round it draws B + mu samples, keeps B,
   and stacks K rounds into one superstep batch (leading K axis), in a host
   buffer set recycled once the superstep that read it has been fetched.
2. **Stage (H2D overlap)** — `data.pipeline.DevicePrefetcher` stages the
   *next* superstep onto devices (sharded `jax.device_put`) from a background
   thread while the current superstep computes — the overlap of sample arrival
   with processing in Fig. 4's timeline, so host synthesis and transfer time
   disappear from the critical path.
3. **Compute (device)** — `train.trainer.build_superstep` folds the K rounds
   into a single `lax.scan` inside one jitted call (TrainState donated where
   the backend supports it); dispatch and metric-fetch overhead is paid once
   per K rounds instead of once per round.

The driver is workload-agnostic: any superstep of signature
`superstep(state, batches) -> (state, metrics)` (batch leaves [K, ...],
metric leaves stacked [K]) plugs in via `superstep_fn` — or, bucket-keyed,
via `superstep_builder` (`build(B) -> superstep`; see
`train.trainer.superstep_builder` and
`core.krasulina.krasulina_superstep_builder`) — the nonconvex PCA track
rides the same splitter, prefetch ring, and governor as the LM trainer; when
both are omitted the trainer's builder is constructed here. `run_cfg` only
needs `.stream` and `.averaging` (a full `RunConfig`, or a lightweight carrier
like `configs.paper_pca.PCARunConfig`).

Closing the loop, the driver times every superstep, inverts eq. 4 to get the
*measured* R_p / R_e (`core.rates.measured_processing_rate`), and re-plans
(B, mu) via `core.rates.replan` — so an under-provisioned run discards the mu
its hardware actually requires (Fig. 4's drop rule), not what nominal config
constants predicted. With a multi-bucket `GovernorConfig` ladder the re-plan
adapts **B as well as mu**: B may move between the registered buckets of a
`core.rates.BucketLadder`, each of which the driver compiles (lazily, once)
into its own superstep executable — so a steady-state bucket switch costs one
plan swap and zero retrace — while an online `core.rates.RoundTimeEstimator`
decomposes round times observed at different buckets into a running
(R_p, R_c) estimate that replaces the config's comms constant in the eq. 4
inversion. Bucket switches are debounced (`core.rates.BucketHysteresis`) so
timing jitter cannot thrash the ladder, and the first superstep of every
newly compiled jit signature is excluded from governor input (compile time is
not processing time). See docs/DESIGN.md §Adaptive batch buckets.
"""
from __future__ import annotations

import dataclasses
import inspect
import threading
import time
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import obs
from repro.configs.base import GovernorConfig, RunConfig
from repro.core import dsgd, rates
from repro.core.faults import FaultSchedule
from repro.core.mixing import Membership
from repro.data.pipeline import DevicePrefetcher, StreamCounters, StreamingPipeline
from repro.launch.mesh import data_axes, n_data_nodes
from repro.train.trainer import (TrainState, make_node_batch,
                                 superstep_builder as lm_superstep_builder)


def elastic_superstep(cohort_fn: Callable, n_full: int) -> Callable:
    """Adapt a cohort-sized superstep to the full node axis
    (docs/DESIGN.md §Elastic membership).

    State leaves keep their full [n_full, ...] extent across membership
    changes (no reshape, no reallocation); the wrapper gathers the active
    rows `ids`, runs the cohort superstep on the dense [m, ...] block, and
    scatters the results back — dropped rows pass through untouched (their
    mixing row has degraded to self-weight 1). `ids` is a runtime [m] array,
    not a static argument, so every membership of the same cohort size
    shares one compiled executable: churn that revisits a cohort size never
    retraces."""

    def fn(state, ids, batches):
        def take(p):
            if getattr(p, "ndim", 0) and p.shape[0] == n_full:
                return jnp.take(p, ids, axis=0)
            return p

        def put(p, s):
            if getattr(p, "ndim", 0) and p.shape[0] == n_full:
                return p.at[ids].set(s.astype(p.dtype))
            return s

        sub = jax.tree.map(take, state)
        sub, metrics = cohort_fn(sub, batches)
        return jax.tree.map(put, state, sub), metrics

    return fn


class _Lease(NamedTuple):
    """A host buffer set on loan to one superstep, and whether its stack
    landed in a recycled set."""

    layout: Tuple[Tuple[str, Tuple[int, ...], np.dtype], ...]
    bufs: Dict[str, np.ndarray]
    reused: bool


class _HostBuffers:
    """Free lists of the host buffer sets the splitter stacks supersteps
    into, keyed by a superstep's layout `((key, shape, dtype), ...)`, so
    buckets of the adaptive-B ladder never share a set. The producer takes
    a set and the consumer gives it back, hence the lock. A list grows only
    when every set of its layout is in flight: at most the ring's items,
    the one being dealt and the one being consumed."""

    def __init__(self):
        self._free: Dict[tuple, List[Dict[str, np.ndarray]]] = {}
        self._lock = threading.Lock()
        self.allocated = 0

    def take(self, layout) -> _Lease:
        with self._lock:
            free = self._free.setdefault(layout, [])
            if free:
                return _Lease(layout, free.pop(), True)
            self.allocated += 1
        return _Lease(layout, {key: np.empty(shape, dtype)
                               for key, shape, dtype in layout}, False)

    def give_back(self, lease: _Lease) -> None:
        with self._lock:
            self._free[lease.layout].append(lease.bufs)


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Knobs of the streaming engine (all host-side; no retrace on change)."""

    superstep: int = 8  # K: rounds folded into one device scan
    # staged supersteps in flight; 0 = synchronous. Default backed by the
    # pipeline/prefetch_sweep/* bench rows: depth 1 covers steady-state host
    # synthesis, depth 2 also absorbs scheduling jitter, deeper is staging
    # memory without throughput on this container.
    prefetch_depth: int = 2
    replan_every: int = 1  # supersteps between governor re-plans; 0 = open loop
    # supersteps whose timings the governor ignores on the INITIAL jit
    # signature: the first two calls pay XLA compilation (one per signature —
    # freshly-built then committed state), and treating compile time as
    # processing time would make replan discard thousands of samples for a
    # one-off cost
    warmup_supersteps: int = 2
    # same gate for every LATER-compiled signature (a bucket first visited
    # mid-run pays one batch-shape retrace): its first `warmup_per_bucket`
    # supersteps are excluded from governor timings and the rate estimator
    warmup_per_bucket: int = 1
    # the adaptive-B bucket ladder + online (R_p, R_c) estimator; the default
    # (single-bucket) config pins B and adapts mu only
    governor: GovernorConfig = GovernorConfig()


class StreamingDriver:
    """Owns the three-stage loop: governed splitter -> prefetch ring ->
    K-round device scan, plus the closed-loop (B, mu) governor.

    Call `run()` under the same `mesh_rules` context the initial state was
    built in. `clock` is injectable so tests can fake slow hardware and watch
    the governor raise mu.
    """

    def __init__(self, run_cfg: RunConfig, mesh, state: Any,
                 sample_fn: Callable[[np.random.Generator, int], Dict[str, np.ndarray]],
                 *, superstep_fn: Optional[Callable] = None,
                 superstep_builder: Optional[Callable[[int], Callable]] = None,
                 engine: EngineConfig = EngineConfig(),
                 batch: Optional[int] = None, horizon: Optional[float] = None,
                 n_nodes: Optional[int] = None, seed: int = 0,
                 clock: Callable[[], float] = time.perf_counter,
                 faults: Optional[FaultSchedule] = None,
                 publisher: Optional[Any] = None,
                 snapshotter: Optional[Any] = None,
                 resume_from: Optional[str] = None):
        if engine.superstep < 1:
            raise ValueError("superstep K must be >= 1")
        if mesh is None and n_nodes is None:
            raise ValueError("pass n_nodes when driving without a mesh")
        self.run_cfg = run_cfg
        self.mesh = mesh
        self.state = state
        self.engine = engine
        self.clock = clock
        self.decentralized = run_cfg.averaging.mode != "exact"
        self.n_nodes = n_nodes or n_data_nodes(mesh)
        self._horizon = horizon
        gov = engine.governor
        # elastic membership (docs/DESIGN.md §Elastic membership): NODE
        # faults and/or a non-lockstep straggler policy turn joins/leaves
        # into plan swaps on the governed pipeline. Link-only schedules
        # (loss / bandwidth — docs/DESIGN.md §Scenario harness) stay on the
        # standard path: they reshape the mixing operator and the round
        # times, not the cohort
        self._faults = faults
        if faults is not None and faults.n != self.n_nodes:
            raise ValueError(f"fault schedule covers {faults.n} nodes "
                             f"but the driver has {self.n_nodes}")
        self._elastic = ((faults is not None and faults.has_node_faults)
                         or gov.straggler_policy != "wait")
        if self._elastic:
            if not self.decentralized:
                raise ValueError("elastic membership needs a decentralized "
                                 "node axis (averaging mode gossip)")
            if run_cfg.averaging.mode == "hierarchical":
                raise ValueError("elastic membership is not defined for "
                                 "pod-structured hierarchical averaging")
        self._straggler = (rates.StragglerPolicy(
            self.n_nodes, gov.straggler_policy,
            slow_factor=gov.straggler_slow_factor,
            deadline_s=gov.straggler_deadline_s,
            patience=gov.straggler_patience) if self._elastic else None)
        self.pipeline = StreamingPipeline(
            sample_fn, run_cfg.stream, self.n_nodes, run_cfg.averaging.rounds,
            batch=batch, horizon=horizon, seed=seed)
        self.ladder = self._make_ladder(engine.governor)
        self.pipeline.adopt_ladder(self.ladder)
        # cohort ladders always derive from the FULL-membership base ladder,
        # so a rejoin to a previously seen cohort size restores that cohort's
        # exact buckets (and their compiled supersteps) rather than drifting
        self._base_ladder = self.ladder
        self._cohort_ladders: Dict[int, rates.BucketLadder] = {
            self.n_nodes: self.ladder}
        self._membership: Optional[Membership] = None
        self._ids_cache: Dict[Membership, jax.Array] = {}
        self._last_round_s: Optional[float] = None
        self.membership_events: List[Dict[str, Any]] = []
        if self._elastic:
            self._membership = Membership.full(self.n_nodes)
            self.pipeline.swap_membership(self._membership, self.ladder)
        # superstep source, most to least specific: an explicit bucket-keyed
        # builder, a single superstep_fn (served to every bucket), or the LM
        # trainer's builder
        if superstep_builder is None:
            if superstep_fn is not None:
                superstep_builder = lambda B: superstep_fn
            else:
                superstep_builder = lm_superstep_builder(run_cfg, mesh,
                                                         n_nodes=self.n_nodes)
        self._builder = superstep_builder
        # membership-aware builders take (B, membership); legacy builders
        # (and the superstep_fn adapter above) take B alone and can only
        # serve full-membership supersteps
        try:
            params = inspect.signature(superstep_builder).parameters
            self._builder_elastic = len(params) >= 2
        except (TypeError, ValueError):
            self._builder_elastic = False
        # donation updates the TrainState in place across supersteps where
        # the backend honors it — feature-detected, not a backend list (the
        # pinned jax implements CPU donation; see core.dsgd.donation_supported)
        self._donate = (0,) if dsgd.donation_supported() else ()
        # one compiled superstep per (bucket, cohort size), built lazily on
        # first visit and reused with zero retrace on every revisit — the
        # active ids are a runtime argument, so all same-size memberships
        # share one executable
        self._compiled: Dict[Tuple[int, int], Callable] = {}
        self._sharding = self._batch_sharding()
        self._prefetcher: Optional[DevicePrefetcher] = None
        self._supersteps_done = 0  # across run() calls
        # producer side: the next superstep's sequence number, and the
        # host-seconds dict and span ids of the one being dealt
        self._dealt = 0
        self._dealing: Tuple[Optional[Dict[str, float]], Dict[str, int]] = (None, {})
        # the host buffer sets supersteps are stacked into, and the lease of
        # the one being dealt; a set goes back after its superstep's fetch
        self._buffers = _HostBuffers()
        self._leased: Optional[_Lease] = None
        # governor warm-up gate, per jit signature: supersteps completed at
        # each (bucket, cohort) signature (the first of a fresh signature
        # pays XLA compile time and must not feed replan or the estimator)
        self._sig_seen: Dict[Tuple[int, int], int] = {}
        self._initial_B = self.pipeline.plan.B
        self._initial_sig = (self._initial_B, self.n_nodes)
        self._hysteresis = rates.BucketHysteresis(gov.hysteresis)
        self._estimator = (rates.RoundTimeEstimator(
            self.n_nodes, run_cfg.averaging.rounds, window=gov.window)
            if gov.estimate_rates else None)
        # train-to-serve publication (see docs/DESIGN.md
        # §Train-to-serve publication): snapshots are taken at the
        # superstep boundary, after
        # the timed window — publication cost is engine bookkeeping the
        # publisher's own governor budgets, not stream processing
        self._publisher = publisher
        if publisher is not None:
            from repro.train.trainer import publish_extract
            publisher.configure(extract=publish_extract(
                self.n_nodes if self.decentralized else None))
        self._pub_masks: Dict[Optional[Membership], Optional[jax.Array]] = {}
        self.history: List[Dict[str, Any]] = []
        # fault tolerance (docs/DESIGN.md §Fault-tolerant streaming): the
        # snapshotter runs at the superstep boundary, after publication —
        # same barrier, same async-dispatch discipline, its own cost governor.
        # `_last_splitter_state` is the splitter snapshot that rode the
        # prefetch `meta` with the superstep just consumed: restoring it
        # re-deals the staged-but-unconsumed supersteps a crash threw away.
        self._snapshotter = snapshotter
        self._last_splitter_state: Optional[dict] = None
        self.resumed_from: Optional[str] = None
        if resume_from is not None:
            from repro.train import snapshot as _snapshot
            self.resumed_from = _snapshot.restore_driver(self, resume_from)

    def _make_ladder(self, gov: GovernorConfig) -> rates.BucketLadder:
        """Resolve the governor's B ladder: explicit buckets (clipped to the
        Theorem-4 horizon ceiling, snapped to multiples of N), an auto
        geometric ladder around the planned B, or the pinned single-bucket
        ladder (the pre-adaptive behavior)."""
        N = self.n_nodes
        base_B = self.pipeline.plan.B
        if gov.buckets:
            return rates.BucketLadder.from_buckets(
                gov.buckets, N, horizon_samples=self._horizon)
        if gov.n_buckets == 1:
            # pinned B: keep the planned/user batch EXACTLY (the pre-ladder
            # behavior), including a B that is not a multiple of N in exact
            # mode where no node split happens
            return rates.BucketLadder((base_B,))
        return rates.BucketLadder.build(
            base_B, N, n_buckets=gov.n_buckets, factor=gov.bucket_factor,
            horizon_samples=self._horizon)

    @property
    def host_buffers_allocated(self) -> int:
        """Host buffer sets allocated for the splitter's stack so far, over
        all layouts (docs/DESIGN.md §Observability)."""
        return self._buffers.allocated

    @property
    def compiled_buckets(self) -> Tuple[int, ...]:
        """Buckets whose superstep executable exists (visited at least once)."""
        return tuple(sorted({b for b, _ in self._compiled}))

    @property
    def compiled_signatures(self) -> Tuple[Tuple[int, int], ...]:
        """(bucket, cohort size) pairs with a compiled superstep executable."""
        return tuple(sorted(self._compiled))

    @property
    def membership(self) -> Optional[Membership]:
        """The active cohort future supersteps will be dealt under (None on a
        non-elastic driver)."""
        return self._membership

    def _superstep_for(self, p: rates.Plan) -> Callable:
        mem = p.membership
        partial_cohort = mem is not None and not mem.is_full
        m = mem.n_active if mem is not None else self.n_nodes
        fn = self._compiled.get((p.B, m))
        if fn is None:
            if self._builder_elastic:
                raw = self._builder(p.B, mem if partial_cohort else None)
            elif partial_cohort:
                raise ValueError(
                    "elastic membership needs a membership-aware superstep "
                    "builder `build(B, membership)`; this driver was given a "
                    "single-argument builder (or a bare superstep_fn)")
            else:
                raw = self._builder(p.B)
            if partial_cohort:
                raw = elastic_superstep(raw, self.n_nodes)
            fn = jax.jit(raw, donate_argnums=self._donate,
                         out_shardings=(self._state_shardings(), None))
            self._compiled[(p.B, m)] = fn
        return fn

    def _state_shardings(self):
        """Pin the superstep's output state to the layout the state was
        placed in on a mesh. Left to propagation, XLA may return an
        equivalent but differently spelled layout, and the next superstep
        then recompiles and cannot reuse the donated buffers. None (let XLA
        choose) when the state carries no mesh layout."""
        leaves = jax.tree.leaves(self.state)
        if leaves and all(isinstance(getattr(a, "sharding", None),
                                     NamedSharding) for a in leaves):
            return jax.tree.map(lambda a: a.sharding, self.state)
        return None

    def _ids_for(self, mem: Membership) -> jax.Array:
        ids = self._ids_cache.get(mem)
        if ids is None:
            ids = jnp.asarray(np.asarray(mem.active_ids, np.int32))
            self._ids_cache[mem] = ids
        return ids

    # ---------------------------------------------------------------- stages

    def _host_superstep(self) -> Dict[str, np.ndarray]:
        """Stage 1: K governed splitter rounds, stacked [K, B, ...] (exact)
        or split [K, N, B/N, ...] over the *active cohort* (decentralized
        node axis; the latched plan's membership decides the split).

        Runs on the producer thread (the consumer's when prefetch depth is
        0) and opens `_dealing`, the host-seconds dict of the superstep it
        deals and the `step` its spans carry; `_stage` and the prefetch
        ring add to the same dict. The rounds are stacked into a host
        buffer set leased from `_buffers` (`_leased`), which travels with
        the batch to the consumer."""
        sink, ids = {}, {"step": self._dealt}
        self._dealt += 1
        self._dealing = (sink, ids)
        batch = self.pipeline.next_superstep(self.engine.superstep, sink,
                                             out=self._lease, **ids)
        if self.decentralized:
            p = self.pipeline.last_superstep_plan
            m = self.n_nodes if p.membership is None else p.membership.n_active
            with obs.span("stream.node_split", sink, **ids):
                batch = make_node_batch(batch, m, axis=1)
        return batch

    def _lease(self, layout) -> Dict[str, np.ndarray]:
        self._leased = self._buffers.take(layout)
        return self._leased.bufs

    def _batch_sharding(self) -> Optional[NamedSharding]:
        """Leading-K batches shard their second axis (global batch / node) over
        the data axes; on a single-device mesh a plain `device_put` suffices."""
        if self.mesh is None or self.mesh.devices.size == 1:
            return None
        if self._elastic:
            # churn makes the node extent vary (m <= N need not divide the
            # data axes); plain device_put keeps every cohort shape valid
            return None
        dp = data_axes(self.mesh)
        extent = 1
        for a in dp:
            extent *= self.mesh.shape[a]
        if extent == 1 or (self.decentralized and self.n_nodes % extent != 0):
            return None
        return NamedSharding(self.mesh, P(None, dp))

    def _stage(self, batch: Dict[str, np.ndarray]):
        """Stage 2: H2D — runs on the prefetch thread when depth > 0."""
        sink, ids = self._dealing
        with obs.span("stream.stage", sink, **ids):
            if self._sharding is None:
                return jax.device_put(batch)
            return jax.device_put(batch, self._sharding)

    # ------------------------------------------------------------- main loop

    def run(self, supersteps: int, *,
            log_fn: Optional[Callable[[Dict[str, Any]], None]] = None,
            log_every: int = 1) -> Tuple[TrainState, List[Dict[str, Any]]]:
        """Drive `supersteps` supersteps (K rounds each). Returns the final
        TrainState and the per-superstep history of metrics, throughput, and
        governor decisions. Each record also holds `host_s`, the host
        seconds of each `stream.*` span of that superstep (producer and
        consumer; `repro.obs`), `ring_depth`, the staged supersteps left
        in the prefetch ring when the consumer took this one, and
        `stack_reused`, 1 where its stack landed in a recycled host buffer
        set (docs/DESIGN.md §Observability).

        The prefetch ring persists across calls (it keeps staging between
        runs, bounded at `prefetch_depth`), so a warm-up `run()` leaves the
        ring hot for a subsequent timed one; call `close()` (or use the
        driver as a context manager) when done."""
        if self.engine.prefetch_depth > 0 and self._prefetcher is None:
            # the ring is FIFO: the producer numbers supersteps from the
            # next one the consumer will take, so both sides' spans of a
            # superstep carry the same `step`
            self._dealt = self._supersteps_done
            self._prefetcher = DevicePrefetcher(
                self._host_superstep, stage=self._stage,
                counters=self.pipeline.counters,
                # the meta snapshot carries BOTH the plan that dealt the
                # superstep and the splitter's post-deal stream position, so
                # the consumer-side checkpoint pins exactly what it consumed
                meta=lambda: (self.pipeline.last_superstep_plan,
                              self.pipeline.splitter_state()),
                spans=lambda: self._dealing,
                lease=lambda: self._leased,
                depth=self.engine.prefetch_depth)
        for i in range(supersteps):
            with jax.profiler.StepTraceAnnotation(
                    "stream.superstep", step_num=self._supersteps_done):
                rec = self._superstep()
            if log_fn and (i % log_every == 0 or i == supersteps - 1):
                log_fn(rec)
        return self.state, self.history

    def _superstep(self) -> Dict[str, Any]:
        """One superstep on the consumer side: take the next staged batch,
        run the compiled superstep, fetch its metrics, feed the governor,
        then publish and snapshot. Returns its history record."""
        source = self._prefetcher
        step = self._supersteps_done
        host_s: Dict[str, float] = {}
        # membership changes land OUTSIDE the timed window: the swap (and
        # any rejoin state sync) is engine bookkeeping, not stream
        # processing the governor should bill to R_p
        if self._elastic:
            self._apply_membership(step)
        # the timed window covers batch acquisition too: when the HOST is
        # the bottleneck (prefetch ring empty, slow synthesis), that wait
        # must show up in the round time the governor inverts, or it would
        # keep calling an input-bound run "resourceful"
        t0 = self.clock()
        with obs.span("stream.ring_wait", host_s, step=step):
            if source is not None:
                staged = next(source)
                counters = source.counters
                used_plan, split_state = source.meta or (None, None)
                dealt_s, ring_depth = source.host_s, source.ring_depth
                lease = source.lease
            else:
                self._dealt = step
                staged = self._stage(self._host_superstep())
                counters = self.pipeline.counters()
                used_plan = self.pipeline.last_superstep_plan
                split_state = self.pipeline.splitter_state()
                dealt_s, ring_depth = self._dealing[0], 0
                lease = self._leased
        if split_state is not None:
            self._last_splitter_state = split_state
        with obs.span("stream.dispatch", host_s, step=step):
            # after a bucket or membership switch the ring may still drain
            # supersteps dealt at the old width/cohort: each batch runs
            # through the compiled executable of the (bucket, cohort) that
            # DEALT it (their samples were drawn from the stream — dropping
            # them would lose samples)
            used_plan = used_plan or self.pipeline.plan
            fn = self._superstep_for(used_plan)
            mem = used_plan.membership
            if mem is not None and not mem.is_full:
                self.state, metrics = fn(self.state, self._ids_for(mem),
                                         staged)
            else:
                self.state, metrics = fn(self.state, staged)
        with obs.span("stream.fetch", host_s, step=step):
            metrics = jax.device_get(metrics)  # one fetch per K rounds
        # the executable that read the batch has finished, so its host
        # buffers may take a later stack. Never sooner: until then the H2D
        # copy may still read them, and on the CPU `device_put` may alias
        # them. A superstep with no metrics fetches nothing and proves
        # nothing: its set is dropped, not recycled
        if jax.tree.leaves(metrics):
            self._buffers.give_back(lease)
        wall_s = max(self.clock() - t0, 1e-12)
        # the producer's seconds for this superstep, then the consumer's
        host_s = {**(dealt_s or {}), **host_s}
        with obs.span("stream.observe", host_s, step=step):
            rec = self._observe(metrics, wall_s, counters, used_plan)
        rec["host_s"], rec["ring_depth"] = host_s, ring_depth
        rec["stack_reused"] = int(lease.reused)
        if self._publisher is not None:
            # outside the timed window, at the plan-latch barrier: the
            # publisher's copy dispatch is async and its own governor
            # keeps the cost within the configured overhead budget
            snap = self._publisher.maybe_publish(
                self.state, self._supersteps_done, aux=self._publish_aux())
            rec["published_version"] = snap.version if snap else None
        if self._snapshotter is not None:
            # superstep boundary, after publication: the copy dispatch is
            # async and the writer thread owns all disk I/O — the
            # snapshotter's cost governor bounds what lands here
            ck = self._snapshotter.maybe_snapshot(self)
            rec["checkpoint"] = ck["step"] if ck else None
        return rec

    def _publish_aux(self) -> Optional[jax.Array]:
        """The publisher extract's aux: a [N] float membership mask for
        decentralized runs (consensus mean over *active* nodes), None in
        exact mode. Cached per membership so steady state pays no H2D."""
        if not self.decentralized:
            return None
        mem = self._membership
        mask = self._pub_masks.get(mem)
        if mask is None:
            mask = (jnp.ones((self.n_nodes,), jnp.float32) if mem is None
                    else jnp.asarray(np.asarray(mem.active, np.float32)))
            self._pub_masks[mem] = mask
        return mask

    # ---------------------------------------------------------- membership

    def _ladder_for(self, m: int) -> rates.BucketLadder:
        lad = self._cohort_ladders.get(m)
        if lad is None:
            lad = self._base_ladder.for_cohort(m,
                                               horizon_samples=self._horizon)
            self._cohort_ladders[m] = lad
        return lad

    def _apply_membership(self, step: int) -> None:
        """Resolve the cohort for superstep `step`: the fault layer's alive
        mask intersected with the straggler policy's debounced verdicts. A
        change is a `swap_membership` plan swap on the pipeline (eq. 4
        re-inverted at the cohort, B snapped onto the cohort's ladder) —
        never a restart; supersteps already staged drain under the
        membership that dealt them."""
        desired = (self._faults.alive(step) if self._faults is not None
                   else Membership.full(self.n_nodes))
        if self._straggler is not None:
            if self._faults is not None and self._last_round_s:
                # per-node times are synthesized from MEASURED warm-up round
                # times only: before the first timed superstep there is no
                # base to scale the fault factors by, and feeding a made-up
                # 1.0 s seed would pollute every node's EWMA with the same
                # large constant — ratios to the cohort median then stay
                # ~1 until the seed decays, delaying eviction by ~1/alpha
                # supersteps (the pre-PR-7 behavior)
                self._straggler.observe(
                    self._faults.round_s_per_node(step, self._last_round_s))
            desired = self._straggler.propose(desired)
        prev = self._membership
        if desired == prev:
            return
        ladder = self._ladder_for(desired.n_active)
        new_plan = self.pipeline.swap_membership(desired, ladder)
        self.ladder = ladder
        if prev is not None and self.engine.governor.sync_on_rejoin:
            self._sync_rejoined(prev, desired)
        self._membership = desired
        self.membership_events.append({
            "superstep": step, "from": prev, "to": desired,
            "plan": new_plan})

    def _sync_rejoined(self, prev: Membership, new: Membership) -> None:
        """Overwrite rejoining nodes' state rows with the mean of the nodes
        that stayed active, so a stale iterate re-enters at the cohort's
        consensus point instead of dragging the consensus error back up.
        A rare host-side op (once per rejoin), not part of any superstep."""
        joined = [i for i in new.active_ids if not prev.active[i]]
        donors = [i for i in prev.active_ids if new.active[i]]
        if not joined or not donors:
            return
        j = jnp.asarray(np.asarray(joined, np.int32))
        d = jnp.asarray(np.asarray(donors, np.int32))
        n = self.n_nodes

        def fix(p):
            if not getattr(p, "ndim", 0) or p.shape[0] != n:
                return p
            mean = jnp.mean(jnp.take(p, d, axis=0), axis=0).astype(p.dtype)
            return p.at[j].set(mean)

        self.state = jax.tree.map(fix, self.state)

    def close(self) -> None:
        """Stop the prefetch thread and flush/stop the snapshot writer
        (idempotent)."""
        if self._prefetcher is not None:
            self._prefetcher.close()
            self._prefetcher = None
        if self._snapshotter is not None:
            self._snapshotter.close()

    def __enter__(self) -> "StreamingDriver":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # -------------------------------------------------------------- governor

    def _observe(self, metrics: Dict[str, np.ndarray], wall_s: float,
                 counters: Optional[StreamCounters],
                 used_plan: rates.Plan) -> Dict[str, Any]:
        i = self._supersteps_done
        self._supersteps_done += 1
        K = self.engine.superstep
        round_s = wall_s / K
        self._last_round_s = round_s
        stream = self.run_cfg.stream
        B_used = used_plan.B
        # the cohort that processed THIS superstep (may differ from the
        # current cohort while the ring drains churn-era items)
        m_used = used_plan.n_active or self.n_nodes
        sig = (B_used, m_used)
        # per-jit-signature warm-up gate: a superstep that paid a fresh XLA
        # compile (any (bucket, cohort)'s first visit — not just the global
        # first two supersteps) must not feed the governor or the estimator
        seen = self._sig_seen.get(sig, 0)
        self._sig_seen[sig] = seen + 1
        warm = seen >= (self.engine.warmup_supersteps
                        if sig == self._initial_sig
                        else self.engine.warmup_per_bucket)
        rec: Dict[str, Any] = {
            "superstep": i,
            "round": (i + 1) * K,
            # last round of the scan == what a per-round loop would print
            "metrics": {k: float(np.asarray(v)[-1]) for k, v in metrics.items()},
            "wall_s": wall_s,
            "rounds_per_s": K / wall_s,
            "samples_per_s": K * B_used / wall_s,
            "plan": used_plan,
            "bucket": B_used,
            "n_active": m_used,
            "counters": counters,
        }
        if self._faults is not None and self._faults.has_link_faults:
            # link-model observability (docs/DESIGN.md §Scenario harness):
            # the active bandwidth slowdown and the Bernoulli edge drops
            # realized at this superstep's last consensus round
            rec["bw_factor"] = self._faults.bw_factor(rec["round"])
            rec["link_drops"] = self._faults.link_drops(rec["round"])
        governed = stream.streaming_rate > 0
        if governed and warm and self._estimator is not None:
            if m_used != self.n_nodes:
                self._estimator.observe_cohort(B_used, m_used, round_s)
            else:
                self._estimator.observe(B_used, round_s)
        every = self.engine.replan_every
        if governed and every > 0 and (i + 1) % every == 0 and warm:
            est = self._estimator.estimate() if self._estimator else None
            if est is not None:
                rec["est_Rp"], rec["est_Rc"] = est.Rp, est.Rc
            # the re-plan targets the CURRENT cohort (eq. 4 re-inverted at
            # N = n_active), even while drain-era supersteps are observed
            cur = self.pipeline.plan
            m_cur = cur.n_active or self.n_nodes
            if len(self.ladder) > 1:
                observed = rates.observed_stream(
                    stream, m_used, used_plan.R, B_used, round_s,
                    estimate=est)
                target_B = rates.select_bucket(
                    self.ladder, observed, m_cur, cur.R,
                    horizon_samples=self._horizon)
                rec["target_bucket"] = target_B
                # hysteresis: only `governor.hysteresis` consecutive re-plans
                # agreeing on the same bucket confirm a switch
                decided_B = self._hysteresis.step(cur.B, target_B)
            else:
                decided_B = cur.B
            # the wall-time inversion happens at the OBSERVED bucket (the
            # ring may still drain old-width supersteps); the plan is derived
            # at the hysteresis-confirmed one
            new_plan = rates.replan(stream, m_cur, cur.R, B_used,
                                    round_s, ladder=self.ladder, estimate=est,
                                    decided_B=decided_B,
                                    horizon_samples=self._horizon,
                                    membership=cur.membership)
            if new_plan.B != cur.B:
                self.pipeline.update_plan(new_plan)
                rec["replanned"] = new_plan
                rec["bucket_switch"] = (cur.B, new_plan.B)
            # Re is measured and jitters every superstep; only an actual
            # change of the governor's *decision* (mu / regime) counts
            elif (new_plan.mu, new_plan.regime) != (cur.mu, cur.regime):
                self.pipeline.update_plan(new_plan)
                rec["replanned"] = new_plan
        self.history.append(rec)
        return rec
