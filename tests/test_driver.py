"""Streaming-engine tests (train.driver + the superstep/prefetch machinery):

* superstep(K) == K sequential train steps — bit-identical in exact mode,
  within tolerance for decentralized (gossip) mode
* the superstep computes the consensus diagnostic in its last round only
* the async prefetch ring preserves sample order and keeps the splitter
  counters (samples_arrived, discards) coherent with the consumed batch
* the closed-loop governor raises mu when the measured rate is artificially
  slowed (injected clock), and the rate inversion round-trips eq. 4
* supersteps are stacked into recycled host buffer sets that go back only
  after their superstep's fetch: every superstep trains on its own rows,
  across a bucket switch too, and the sets stay at prefetch depth + 2
"""
import dataclasses
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.configs.base import (AveragingConfig, GovernorConfig, RunConfig,
                                SHAPES, StreamConfig)
from repro.configs.paper_pca import PCARunConfig
from repro.core import rates
from repro.data.lm import MarkovTokenStream
from repro.data.pipeline import DevicePrefetcher, StreamingPipeline
from repro.launch.mesh import make_mesh
from repro.launch.sharding import activation_rules
from repro.models.common import mesh_rules
from repro.train.driver import EngineConfig, StreamingDriver
from repro.train.trainer import (build_superstep, build_train_step, init_state,
                                 make_node_batch, replicate_for_nodes)

SEQ = 16
BATCH = 4


def _run_cfg(mode="exact", rounds=1, stream=StreamConfig()):
    cfg = dataclasses.replace(
        reduced(get_config("granite-8b"), layers=1, d_model=16),
        vocab_size=32, d_ff=32)
    return RunConfig(model=cfg, shape=SHAPES["train_4k"],
                     averaging=AveragingConfig(mode, rounds), stream=stream,
                     optimizer="adam", learning_rate=1e-3,
                     param_dtype="float32", remat=False)


def _sample_fn(vocab=32, seed=0):
    data = MarkovTokenStream(vocab, seed=seed)

    def draw(rng, n):
        toks = data.sample(rng, n, SEQ + 1)
        return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}

    return draw


def _rounds(k, batch=BATCH, seed=0):
    draw = _sample_fn(seed=seed)
    rng = np.random.default_rng(seed)
    return [{kk: jnp.asarray(v) for kk, v in draw(rng, batch).items()}
            for _ in range(k)]


def _stack(batches):
    return {k: jnp.stack([b[k] for b in batches]) for k in batches[0]}


# ---------------------------------------------------------------------------
# Superstep parity
# ---------------------------------------------------------------------------

def test_superstep_exact_mode_bit_identical():
    """K-round superstep == K sequential jitted steps, bitwise (exact mode)."""
    run_cfg = _run_cfg("exact")
    mesh = make_mesh((1, 1), ("data", "model"))
    K = 4
    batches = _rounds(K)
    with mesh_rules(mesh, activation_rules(mesh, run_cfg.shape)):
        state0 = init_state(run_cfg, jax.random.PRNGKey(0))
        step = jax.jit(build_train_step(run_cfg, mesh)[0])
        superstep = jax.jit(build_superstep(run_cfg, mesh)[0])

        seq_state, seq_losses = state0, []
        for b in batches:
            seq_state, m = step(seq_state, b)
            seq_losses.append(np.asarray(m["loss"]))
        sup_state, sup_metrics = superstep(state0, _stack(batches))

    for a, b in zip(jax.tree.leaves(seq_state), jax.tree.leaves(sup_state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # on-device metric accumulation: stacked [K], same values per round
    assert sup_metrics["loss"].shape == (K,)
    np.testing.assert_array_equal(np.stack(seq_losses),
                                  np.asarray(sup_metrics["loss"]))


def test_superstep_decentralized_matches_sequential():
    """Gossip mode (emulated N=4 nodes on one device): same trajectory within
    float tolerance."""
    run_cfg = _run_cfg("gossip", rounds=2)
    mesh = make_mesh((1, 1), ("data", "model"))
    n_nodes, K = 4, 3
    batches = [make_node_batch(b, n_nodes) for b in _rounds(K, batch=4 * BATCH)]
    with mesh_rules(mesh, activation_rules(mesh, run_cfg.shape, node_axis=True)):
        state0 = replicate_for_nodes(
            init_state(run_cfg, jax.random.PRNGKey(0)), n_nodes)
        step = jax.jit(build_train_step(run_cfg, mesh, n_nodes=n_nodes)[0])
        superstep = jax.jit(build_superstep(run_cfg, mesh, n_nodes=n_nodes)[0])

        seq_state = state0
        for b in batches:
            seq_state, m = step(seq_state, b)
        sup_state, ms = superstep(state0, _stack(batches))

    assert float(ms["consensus_err"][-1]) > 0.0  # inexact averaging is live
    for a, b in zip(jax.tree.leaves(seq_state), jax.tree.leaves(sup_state)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


AVERAGING = {
    "gossip": AveragingConfig("gossip", 2),
    "ef_gossip": AveragingConfig("gossip", 2, quantization="int8",
                                 error_feedback="grads"),
}


@pytest.mark.parametrize("mode", sorted(AVERAGING))
def test_superstep_consensus_err_in_last_round_only(mode):
    """The superstep computes the consensus diagnostic in its last round only:
    that round's value is the one K plain steps report on their K-th step,
    and the skipped rounds read NaN (a 0 would read as perfect consensus)."""
    run_cfg = dataclasses.replace(_run_cfg(), averaging=AVERAGING[mode])
    mesh = make_mesh((1, 1), ("data", "model"))
    n_nodes, K = 4, 3
    batches = [make_node_batch(b, n_nodes) for b in _rounds(K, batch=4 * BATCH)]
    with mesh_rules(mesh, activation_rules(mesh, run_cfg.shape, node_axis=True)):
        state0 = replicate_for_nodes(
            init_state(run_cfg, jax.random.PRNGKey(0)), n_nodes)
        step = jax.jit(build_train_step(run_cfg, mesh, n_nodes=n_nodes)[0])
        superstep = jax.jit(build_superstep(run_cfg, mesh, n_nodes=n_nodes)[0])
        seq_state, seq_errs = state0, []
        for b in batches:
            seq_state, m = step(seq_state, b)
            seq_errs.append(float(m["consensus_err"]))
        _, ms = superstep(state0, _stack(batches))

    assert all(np.isfinite(seq_errs)) and seq_errs[-1] > 0.0
    errs = np.asarray(ms["consensus_err"])
    assert errs.shape == (K,)
    assert np.isnan(errs[:-1]).all()
    np.testing.assert_allclose(errs[-1], seq_errs[-1], rtol=1e-5, atol=1e-6)


def test_driver_record_consensus_err_is_the_last_rounds():
    """The driver's history record keeps the superstep's last round, where
    the diagnostic ran: finite and positive under inexact averaging."""
    run_cfg = _run_cfg("gossip", rounds=2)
    mesh = make_mesh((1, 1), ("data", "model"))
    with mesh_rules(mesh, activation_rules(mesh, run_cfg.shape, node_axis=True)):
        state = replicate_for_nodes(init_state(run_cfg, jax.random.PRNGKey(0)), 4)
        driver = StreamingDriver(
            run_cfg, mesh, state, _sample_fn(), batch=4 * BATCH, n_nodes=4,
            engine=EngineConfig(superstep=3, prefetch_depth=0,
                                warmup_supersteps=0))
        _, history = driver.run(2)
    errs = [rec["metrics"]["consensus_err"] for rec in history]
    assert all(np.isfinite(errs)) and all(e > 0.0 for e in errs)


def _walk(jaxpr, in_scope=False, in_cond=False):
    """Yield (eqn, under the `consensus_error` scope, inside a cond branch)
    for every equation of a jaxpr and of the jaxprs nested in it (a nested
    jaxpr's name stack is relative to its equation's)."""
    for eqn in jaxpr.eqns:
        scoped = in_scope or "consensus_error" in str(eqn.source_info.name_stack)
        yield eqn, scoped, in_cond
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _walk(sub, scoped, in_cond or eqn.primitive.name == "cond")


def _superstep_jaxpr(run_cfg, n_nodes=None, K=3):
    mesh = make_mesh((1, 1), ("data", "model"))
    batches = _stack(_rounds(K, batch=4 * BATCH))
    if n_nodes:
        batches = make_node_batch(batches, n_nodes, axis=1)
    rules = activation_rules(mesh, run_cfg.shape, node_axis=bool(n_nodes))
    with mesh_rules(mesh, rules):
        state = init_state(run_cfg, jax.random.PRNGKey(0))
        if n_nodes:
            state = replicate_for_nodes(state, n_nodes)
        superstep = build_superstep(run_cfg, mesh, n_nodes=n_nodes)[0]
        return jax.make_jaxpr(superstep)(state, batches).jaxpr, batches


@pytest.mark.parametrize("mode", sorted(AVERAGING))
def test_superstep_diagnostic_sits_in_a_cond_branch(mode):
    """In the decentralized superstep's scan body, the consensus diagnostic's
    work lies inside a `cond` branch: outside one, the `consensus_error`
    scope holds only the `cond` and its scalar predicate, so no round but the
    last pays for the diagnostic."""
    run_cfg = dataclasses.replace(_run_cfg(), averaging=AVERAGING[mode])
    jaxpr, _ = _superstep_jaxpr(run_cfg, n_nodes=4)
    scan, = [e for e in jaxpr.eqns if e.primitive.name == "scan"]
    eqns = list(_walk(scan.params["jaxpr"].jaxpr))
    outside = [e for e, scoped, in_cond in eqns
               if scoped and not in_cond and e.primitive.name != "cond"]
    assert all(v.aval.size == 1 for e in outside
               for v in (*e.invars, *e.outvars)), outside
    conds = [e for e, scoped, in_cond in eqns
             if scoped and not in_cond and e.primitive.name == "cond"]
    assert len(conds) == 1
    reduces = [e for e, scoped, in_cond in eqns
               if scoped and in_cond and e.primitive.name == "reduce_sum"]
    assert reduces  # the diagnostic itself is in the branch


def test_exact_superstep_jaxpr_unchanged_by_the_round_flag():
    """Exact mode runs no diagnostic: its superstep scans the batches alone,
    with no extra scan input and no `cond`."""
    jaxpr, batches = _superstep_jaxpr(_run_cfg("exact"))
    scan, = [e for e in jaxpr.eqns if e.primitive.name == "scan"]
    p = scan.params
    assert len(scan.invars) - p["num_consts"] - p["num_carry"] == len(
        jax.tree.leaves(batches))
    assert not [e for e, _, _ in _walk(jaxpr) if e.primitive.name == "cond"]


# ---------------------------------------------------------------------------
# Prefetch ring
# ---------------------------------------------------------------------------

def test_prefetch_preserves_order_and_counters():
    """Prefetched stream == synchronous stream, and each consumed batch comes
    with the counter snapshot a synchronous loop would have observed."""
    def mk_pipe():
        return StreamingPipeline(
            lambda rng, n: {"x": rng.normal(size=(n, 2))},
            StreamConfig(forced_mu=3), n_nodes=2, rounds_R=1, batch=8, seed=7)

    sync_pipe, pre_pipe = mk_pipe(), mk_pipe()
    n_steps, K = 6, 2

    sync_batches, sync_counts = [], []
    for _ in range(n_steps):
        sync_batches.append(sync_pipe.next_superstep(K))
        sync_counts.append(sync_pipe.counters())

    staged_log = []
    pf = DevicePrefetcher(lambda: pre_pipe.next_superstep(K),
                         stage=lambda b: (staged_log.append(True), b)[1],
                         counters=pre_pipe.counters, depth=2)
    with pf:
        for want, want_c in zip(sync_batches, sync_counts):
            got = next(pf)
            np.testing.assert_array_equal(got["x"], want["x"])
            assert pf.counters == want_c
    # staging ran on the producer side for every consumed superstep
    assert len(staged_log) >= n_steps
    # coherence: consumer-visible counters lag the producer's read-ahead
    assert pf.counters.samples_arrived <= pre_pipe.samples_arrived


def test_prefetch_finite_source_and_errors():
    it = iter(range(5))
    pf = DevicePrefetcher(lambda: next(it), depth=2)
    assert list(pf) == [0, 1, 2, 3, 4]
    # exhausted ring keeps raising instead of blocking on the dead worker
    with pytest.raises(StopIteration):
        next(pf)
    pf.close()

    def boom():
        raise RuntimeError("producer died")

    pf = DevicePrefetcher(boom, depth=1)
    for _ in range(2):  # the error is latched, not one-shot
        with pytest.raises(RuntimeError, match="producer died"):
            next(pf)
    pf.close()


def test_prefetch_close_while_worker_blocked_on_full_ring():
    """Regression: close() with the worker parked in `_put_stopaware` (ring
    full, consumer gone) must shut down promptly — no deadlock — and a
    producer error that never reached the consumer is re-raised exactly
    once, even if it was stranded by the shutdown itself."""
    import threading
    import time as _time

    produced = threading.Event()

    def produce():
        if produced.is_set():
            raise RuntimeError("late failure")  # fails once the ring is full
        produced.set()
        return 0

    pf = DevicePrefetcher(produce, depth=1)
    # let the worker fill the depth-1 ring and then die trying to enqueue
    # the error behind it; the consumer never drains anything
    assert produced.wait(timeout=5.0)
    deadline = _time.time() + 5.0
    while pf._q.qsize() < 1 and _time.time() < deadline:
        _time.sleep(0.01)
    with pytest.raises(RuntimeError, match="late failure"):
        pf.close()  # must return (not deadlock) AND surface the error
    assert not pf._thread.is_alive()
    pf.close()  # idempotent: the error is re-raised exactly once
    # a post-close consumer must not block on the dead worker either
    with pytest.raises((StopIteration, RuntimeError)):
        next(pf)


def test_prefetch_close_after_error_delivered_does_not_reraise():
    """An error already surfaced through __next__ is not raised again by
    close() (the pre-existing latched-error contract)."""
    def boom():
        raise RuntimeError("seen already")

    pf = DevicePrefetcher(boom, depth=1)
    with pytest.raises(RuntimeError, match="seen already"):
        next(pf)
    pf.close()  # must NOT raise


def test_pipeline_update_plan_keeps_B_fixed():
    pipe = StreamingPipeline(lambda rng, n: {"x": rng.normal(size=(n, 2))},
                             StreamConfig(), 2, 1, batch=8)
    new = dataclasses.replace(pipe.plan, mu=5)
    pipe.update_plan(new)
    assert pipe.plan.mu == 5
    next(pipe)
    assert pipe.samples_arrived == 13 and pipe.samples_discarded == 5
    with pytest.raises(ValueError):
        pipe.update_plan(dataclasses.replace(pipe.plan, B=16))


# ---------------------------------------------------------------------------
# Closed-loop governor
# ---------------------------------------------------------------------------

def test_measured_rate_inverts_effective_rate():
    B, N, R, Rp, Rc = 64, 4, 3, 1e4, 1e5
    round_s = B / (N * Rp) + R / Rc  # eq. 4 timeline
    got = rates.measured_processing_rate(B, N, R, round_s, Rc)
    assert got == pytest.approx(Rp, rel=1e-9)
    assert rates.measured_effective_rate(round_s) == pytest.approx(
        rates.effective_rate(B, N, R, Rp, Rc), rel=1e-9)


def test_replan_raises_mu_when_slow():
    stream = StreamConfig(streaming_rate=1e3, processing_rate=1e6,
                          comms_rate=1e6)
    nominal = rates.plan(stream, N=2, R=1, B=8)
    assert nominal.mu == 0  # config constants claim the system keeps up
    fast = rates.replan(stream, 2, 1, 8, wall_s_per_round=1e-3)
    slow = rates.replan(stream, 2, 1, 8, wall_s_per_round=1.0)
    assert fast.mu == 0
    assert slow.mu > 0 and slow.regime == "under-provisioned"
    assert slow.B == nominal.B  # shape-stable adaptation


def test_replan_distrusts_disproven_comms_model():
    """A round observed FASTER than the modeled comm floor R/R_c proves the
    comms constant wrong; the re-plan must attribute wall time to compute
    (mu = 0 for a run that keeps up), not discard real samples on the model's
    say-so."""
    stream = StreamConfig(streaming_rate=1e3, processing_rate=1e6,
                          comms_rate=1e3)
    R, B, N = 10, 8, 2  # modeled comm floor: R/Rc = 10 ms
    got = rates.replan(stream, N, R, B, wall_s_per_round=2e-3)
    assert got.mu == 0 and got.regime == "resourceful"
    Rp = rates.measured_processing_rate(B, N, R, 2e-3, stream.comms_rate)
    assert Rp == pytest.approx(B / (N * 2e-3))  # sane, not clamp-driven 1e12


def test_replan_honors_forced_mu():
    """A user-pinned mu is an experiment knob; the feedback loop must not
    silently overwrite it."""
    stream = StreamConfig(streaming_rate=1e3, processing_rate=1e6,
                          comms_rate=1e6, forced_mu=7)
    slow = rates.replan(stream, 2, 1, 8, wall_s_per_round=1.0)
    assert slow.mu == 7


class _FakeClock:
    """Monotonic clock that jumps `dt` seconds per reading."""

    def __init__(self, dt):
        self.t, self.dt = 0.0, dt

    def __call__(self):
        self.t += self.dt
        return self.t


@pytest.mark.parametrize("dt,expect_discard", [(1e-4, False), (50.0, True)])
def test_driver_closed_loop_adapts_mu(dt, expect_discard):
    """With an artificially slow clock the governor must re-plan mu > 0; with
    a fast one it must keep mu = 0 (nominal config already keeps up)."""
    stream = StreamConfig(streaming_rate=1e3, processing_rate=1e6,
                          comms_rate=1e6)
    run_cfg = _run_cfg(stream=stream)
    mesh = make_mesh((1, 1), ("data", "model"))
    with mesh_rules(mesh, activation_rules(mesh, run_cfg.shape)):
        state = init_state(run_cfg, jax.random.PRNGKey(0))
        driver = StreamingDriver(
            run_cfg, mesh, state, _sample_fn(), batch=BATCH,
            engine=EngineConfig(superstep=2, prefetch_depth=0, replan_every=1,
                                warmup_supersteps=0),
            clock=_FakeClock(dt))
        assert driver.pipeline.plan.mu == 0
        _, history = driver.run(3)
    assert len(history) == 3
    if expect_discard:
        assert driver.pipeline.plan.mu > 0
        assert driver.pipeline.plan.regime == "under-provisioned"
        assert driver.pipeline.samples_discarded > 0  # later rounds paid mu
    else:
        assert driver.pipeline.plan.mu == 0
        assert driver.pipeline.samples_discarded == 0


def test_driver_governor_skips_compile_warmup():
    """Default warm-up gating: the (slow) compile supersteps must not feed the
    governor, even when their wall time screams under-provisioned."""
    stream = StreamConfig(streaming_rate=1e3, processing_rate=1e6,
                          comms_rate=1e6)
    run_cfg = _run_cfg(stream=stream)
    mesh = make_mesh((1, 1), ("data", "model"))
    with mesh_rules(mesh, activation_rules(mesh, run_cfg.shape)):
        state = init_state(run_cfg, jax.random.PRNGKey(0))
        driver = StreamingDriver(
            run_cfg, mesh, state, _sample_fn(), batch=BATCH,
            engine=EngineConfig(superstep=2, prefetch_depth=0, replan_every=1),
            clock=_FakeClock(50.0))
        _, history = driver.run(2)
        assert all("replanned" not in rec for rec in history)
        assert driver.pipeline.plan.mu == 0
        # warm-up over (also across run() calls): the governor engages
        driver.run(1)
    assert driver.pipeline.plan.mu > 0


def test_driver_runs_with_prefetch_and_counts_rounds():
    run_cfg = _run_cfg()
    mesh = make_mesh((1, 1), ("data", "model"))
    with mesh_rules(mesh, activation_rules(mesh, run_cfg.shape)):
        state = init_state(run_cfg, jax.random.PRNGKey(0))
        with StreamingDriver(
                run_cfg, mesh, state, _sample_fn(), batch=BATCH,
                engine=EngineConfig(superstep=3, prefetch_depth=2,
                                    replan_every=0)) as driver:
            _, history = driver.run(2)
    assert [rec["round"] for rec in history] == [3, 6]
    assert history[-1]["counters"].samples_consumed == 6 * BATCH
    assert all(np.isfinite(rec["metrics"]["loss"]) for rec in history)


# ---------------------------------------------------------------------------
# Train-to-serve publication (PR 7)
# ---------------------------------------------------------------------------

def test_driver_publishes_consensus_snapshots():
    """With a publisher attached the driver publishes at superstep
    boundaries: versions are monotone, each history record carries the
    published version (or None on a governed skip), and the snapshot param
    tree matches the model param structure — including the consensus mean
    over the node axis in decentralized mode."""
    from repro.serve.publisher import SnapshotPublisher

    for mode, n_nodes in (("exact", 1), ("gossip", 1)):
        run_cfg = _run_cfg(mode=mode, rounds=2)
        mesh = make_mesh((1, 1), ("data", "model"))
        decentralized = mode != "exact"
        pub = SnapshotPublisher(overhead_budget=0.0)  # ungoverned: always
        with mesh_rules(mesh, activation_rules(mesh, run_cfg.shape,
                                               node_axis=decentralized)):
            state = init_state(run_cfg, jax.random.PRNGKey(0))
            if decentralized:
                state = replicate_for_nodes(state, n_nodes)
            driver = StreamingDriver(
                run_cfg, mesh, state, _sample_fn(), batch=BATCH,
                n_nodes=n_nodes, publisher=pub,
                engine=EngineConfig(superstep=2, prefetch_depth=0,
                                    replan_every=0, warmup_supersteps=0))
            _, history = driver.run(3)
        assert pub.version == 3
        assert [r["published_version"] for r in history] == [1, 2, 3]
        snap = pub.snapshot()
        ref = jax.eval_shape(lambda: driver.state.params)
        leaves = jax.tree_util.tree_leaves(snap.params)
        ref_leaves = jax.tree_util.tree_leaves(ref)
        if decentralized:
            # node axis averaged away: snapshot leaves drop the leading dim
            assert all(s.shape == r.shape[1:]
                       for s, r in zip(leaves, ref_leaves))
        else:
            assert all(s.shape == r.shape
                       for s, r in zip(leaves, ref_leaves))
        assert snap.superstep == 3


def test_driver_publish_governor_skip_records_none():
    """A budget-starved publisher skips mid-run publishes; the driver records
    published_version=None for those supersteps and the first publish still
    always lands."""
    from repro.serve.publisher import SnapshotPublisher

    run_cfg = _run_cfg()
    mesh = make_mesh((1, 1), ("data", "model"))
    pub = SnapshotPublisher(overhead_budget=1e-12)  # everything over budget
    pub.stats.cost_ewma_s = 10.0  # pretend publishes are very expensive
    with mesh_rules(mesh, activation_rules(mesh, run_cfg.shape)):
        state = init_state(run_cfg, jax.random.PRNGKey(0))
        driver = StreamingDriver(
            run_cfg, mesh, state, _sample_fn(), batch=BATCH, publisher=pub,
            engine=EngineConfig(superstep=2, prefetch_depth=0,
                                replan_every=0, warmup_supersteps=0))
        _, history = driver.run(3)
    versions = [r["published_version"] for r in history]
    assert versions[0] == 1  # unconditional first publish
    assert versions[1:] == [None, None]
    assert pub.stats.skipped_budget == 2


# ---------------------------------------------------------------------------
# Recycled host buffers
# ---------------------------------------------------------------------------

D = 3


class _IndexRows:
    """A sample_fn whose rows name the samples they hold: stream sample i is
    the row i * D + (0, ..., D-1). Logs the first index of every draw."""

    def __init__(self):
        self.next, self.starts = 0, []

    def __call__(self, rng, n):
        start, self.next = self.next, self.next + n
        self.starts.append(start)
        idx = np.arange(start, start + n, dtype=np.int32)
        return {"x": idx[:, None] * D + np.arange(D, dtype=np.int32)}

    def checksums(self, history, k):
        """(lo, hi, sum) of the rows each recorded superstep drew: its K
        draws, each of whose first B rows the splitter kept."""
        out = []
        for s, rec in enumerate(history):
            idx = np.concatenate([np.arange(a, a + rec["bucket"])
                                  for a in self.starts[k * s:k * (s + 1)]])
            x = idx[:, None] * D + np.arange(D)
            out.append((int(x.min()), int(x.max()), int(x.sum())))
        return out


def _checksum_superstep(state, batches):
    """Reports (lo, hi, sum) of the whole superstep's batch in every round."""
    x = batches["x"]
    k = x.shape[0]
    metrics = {"lo": x.min(), "hi": x.max(), "sum": x.sum()}
    return ({"n": state["n"] + 1},
            {name: jnp.full((k,), v) for name, v in metrics.items()})


def _got(history):
    return [tuple(int(rec["metrics"][m]) for m in ("lo", "hi", "sum"))
            for rec in history]


class _SlowClock:
    """Real time, but each reading first sleeps: a consumer that takes its
    time, so the producer fills the ring and runs ahead. Advances `dt`
    more seconds per reading for the governor."""

    def __init__(self, sleep_s=0.03, dt=0.0):
        self.sleep_s, self.dt, self.reads = sleep_s, dt, 0

    def __call__(self):
        time.sleep(self.sleep_s)
        self.reads += 1
        return time.perf_counter() + self.dt * self.reads


def _index_driver(sampler, clock, engine, stream=StreamConfig()):
    """A driver of `_checksum_superstep` whose stage passes the host arrays
    through: the CPU's `device_put` aliases some arrays and copies others,
    and a staged batch that is the host buffer itself shows a set handed
    back too early whichever it does."""
    run_cfg = PCARunConfig(averaging=AveragingConfig("gossip", 1),
                           stream=stream)
    driver = StreamingDriver(
        run_cfg, None, {"n": jnp.zeros((), jnp.int32)}, sampler,
        superstep_fn=_checksum_superstep, n_nodes=2, batch=8,
        engine=engine, clock=clock)
    driver._stage = lambda batch: batch
    return driver


@pytest.mark.parametrize("depth", [2, 0])
def test_recycled_host_buffers_keep_each_supersteps_rows(depth):
    """A slow consumer lets the producer fill the ring: every superstep
    still trains on the rows drawn for it (a set handed back before its
    superstep's fetch would be restacked under the staged batch), after
    warm-up every stack lands in a recycled set, and no more than depth + 2
    sets exist."""
    K, steps = 4, 12
    sampler = _IndexRows()
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        with _index_driver(sampler, _SlowClock(), EngineConfig(
                superstep=K, prefetch_depth=depth, replan_every=0)) as driver:
            _, history = driver.run(steps)
    finally:
        sys.setswitchinterval(interval)
    assert _got(history) == sampler.checksums(history, K)
    sets = driver.host_buffers_allocated
    assert sets == depth + 2 if depth else sets == 1
    assert [rec["stack_reused"] for rec in history] == [0] * sets + [1] * (steps - sets)


def test_bucket_switch_leases_new_buffers_and_drains_on_own_rows():
    """An adaptive-B switch stacks the new width into a set of its own
    layout, while the supersteps already staged at the old width drain
    through the old bucket on the rows drawn for them."""
    K, depth = 4, 2
    sampler = _IndexRows()
    stream = StreamConfig(streaming_rate=1e3, processing_rate=1e6,
                          comms_rate=1e6)
    gov = GovernorConfig(buckets=(8, 16), hysteresis=1, estimate_rates=False)
    with _index_driver(sampler, _SlowClock(dt=1.0), EngineConfig(
            superstep=K, prefetch_depth=depth, replan_every=1,
            warmup_supersteps=0, governor=gov), stream=stream) as driver:
        _, history = driver.run(12)
    switch = next(i for i, rec in enumerate(history) if "bucket_switch" in rec)
    assert history[switch]["bucket_switch"] == (8, 16)
    buckets = [rec["bucket"] for rec in history]
    assert 8 in buckets[switch + 1:]  # staged at the old width, drained
    assert _got(history) == sampler.checksums(history, K)
    wide = [rec["stack_reused"] for rec in history if rec["bucket"] == 16]
    assert wide[0] == 0 and wide[-1] == 1  # a new layout, then recycled
    assert driver.host_buffers_allocated <= 2 * (depth + 2)


def test_pipeline_stacks_into_given_buffers_bit_identically():
    """`next_superstep(k, out=...)` returns the given arrays holding the rows
    a fresh stack holds, bit for bit, whether `out` is a dict or a function
    of the layout; without `out` every call stacks into fresh arrays."""
    def mk():
        return StreamingPipeline(
            lambda rng, n: {"x": rng.normal(size=(n, 3)).astype(np.float32),
                            "y": rng.integers(0, 9, size=n)},
            StreamConfig(forced_mu=2), n_nodes=2, rounds_R=1, batch=8, seed=5)

    K = 3
    fresh, given = mk(), mk()
    a, b = fresh.next_superstep(K), fresh.next_superstep(K)
    assert not any(np.shares_memory(a[key], b[key]) for key in a)
    out = {key: np.empty_like(v) for key, v in a.items()}
    got = given.next_superstep(K, out=out)
    assert all(got[key] is out[key] for key in a)
    assert all(got[key].tobytes() == a[key].tobytes() for key in a)
    layouts = []
    got = given.next_superstep(K, out=lambda lay: layouts.append(lay) or out)
    assert layouts == [tuple((key, v.shape, v.dtype) for key, v in b.items())]
    assert all(got[key] is out[key] for key in b)
    assert all(got[key].tobytes() == b[key].tobytes() for key in b)
    assert given.counters() == fresh.counters()
