"""Full-loop async checkpoint/restore (train/snapshot.py, docs/DESIGN.md
§Fault-tolerant streaming):

* `RunSnapshotter` mechanics: cadence grid, EWMA cost governor, depth-1
  busy skip, writer failures recorded without touching the training
  thread, last-k retention through the writer
* in-process kill-and-resume: a resumed driver is bit-identical to the
  uninterrupted one — exact-mode LM engine WITH the async prefetch ring,
  and the elastic krasulina engine under fault-injected churn (resume from
  a checkpoint taken while the cohort was shrunk; later rejoin retraces
  nothing it already compiled)
* SIGKILL regression: a worker process is killed mid-stream and mid-save
  (torn step directory); the resumed process skips the torn checkpoint via
  `newest_valid` and still reproduces the uninterrupted final state
  bit-for-bit, with the persistent compilation cache making the warm
  restart compile-free
"""
import dataclasses
import os
import signal
import subprocess
import sys
import threading
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.configs.base import (AveragingConfig, GovernorConfig, RunConfig,
                                SHAPES, StreamConfig)
from repro.configs.paper_pca import FIG7, PCARunConfig
from repro.core import krasulina, rates
from repro.core.faults import FaultSchedule
from repro.data.lm import MarkovTokenStream
from repro.data.pipeline import StreamingPipeline
from repro.launch.mesh import make_mesh
from repro.launch.sharding import activation_rules
from repro.models.common import mesh_rules
from repro.data.synthetic import make_pca_host_sampler, make_pca_stream
from repro.train import checkpoint, snapshot
from repro.train.driver import EngineConfig, StreamingDriver
from repro.train.snapshot import RunSnapshotter
from repro.train.trainer import init_state


class _FakeClock:
    def __init__(self, dt):
        self.t, self.dt = 0.0, dt

    def __call__(self):
        self.t += self.dt
        return self.t


def _leaves(state):
    return checkpoint._flatten(state)


def _assert_states_equal(a, b):
    fa, fb = _leaves(a), _leaves(b)
    assert fa.keys() == fb.keys()
    for k in fa:
        np.testing.assert_array_equal(np.asarray(fa[k]), np.asarray(fb[k]),
                                      err_msg=k)


# ---------------------------------------------------------------------------
# RunSnapshotter mechanics (stub driver: no engine needed)
# ---------------------------------------------------------------------------

def _stub_driver(step=0):
    pipe = StreamingPipeline(
        lambda rng, n: {"x": np.zeros((n, 2), np.float32)},
        StreamConfig(), n_nodes=1, rounds_R=1, batch=4)
    d = types.SimpleNamespace(
        state={"w": jnp.arange(4.0)}, pipeline=pipe, _supersteps_done=step,
        _last_splitter_state=None, _last_round_s=None, _sig_seen={},
        _hysteresis=rates.BucketHysteresis(2), _estimator=None,
        _straggler=None, _membership=None, _publisher=None)
    return d


def test_snapshotter_validates_args(tmp_path):
    for kw in ({"every": 0}, {"keep_last": 0}, {"overhead_budget": -0.1},
               {"alpha": 0.0}, {"alpha": 1.5}):
        with pytest.raises(ValueError):
            RunSnapshotter(str(tmp_path), **kw)


def test_snapshotter_cadence_grid(tmp_path):
    d = _stub_driver()
    with RunSnapshotter(str(tmp_path), every=2, overhead_budget=0,
                        block=True) as sn:
        for step in (1, 2, 3, 4):
            d._supersteps_done = step
            sn.maybe_snapshot(d)
    assert sn.stats.dispatches == 2 and sn.stats.saves == 2
    assert sn.stats.skipped_cadence == 2
    assert checkpoint.list_steps(str(tmp_path)) == [2, 4]


def test_snapshotter_budget_governor_skips(tmp_path):
    """With a 1 s/reading fake clock every dispatch 'costs' 1 s; a 0.5
    overhead budget must skip every other cadence hit."""
    d = _stub_driver()
    with RunSnapshotter(str(tmp_path), every=1, overhead_budget=0.5,
                        block=True, clock=_FakeClock(1.0)) as sn:
        for step in (1, 2, 3):
            d._supersteps_done = step
            sn.maybe_snapshot(d)
    assert sn.stats.dispatches == 2
    assert sn.stats.skipped_budget == 1


def test_snapshotter_busy_writer_skips_not_blocks(tmp_path, monkeypatch):
    release, entered = threading.Event(), threading.Event()
    orig = checkpoint.save

    def slow_save(*a, **kw):
        entered.set()
        release.wait(10.0)
        return orig(*a, **kw)

    monkeypatch.setattr(checkpoint, "save", slow_save)
    d = _stub_driver(step=1)
    with RunSnapshotter(str(tmp_path), every=1, overhead_budget=0) as sn:
        assert sn.maybe_snapshot(d) is not None
        assert entered.wait(10.0)
        d._supersteps_done = 2
        t0 = time.perf_counter()
        assert sn.maybe_snapshot(d) is None  # writer busy: skip, don't wait
        assert time.perf_counter() - t0 < 5.0
        assert sn.stats.skipped_busy == 1
        release.set()
        sn.flush()
    assert sn.stats.saves == 1


def test_snapshotter_failure_recorded_never_raised(tmp_path, monkeypatch):
    def boom(*a, **kw):
        raise OSError("disk on fire")

    monkeypatch.setattr(checkpoint, "save", boom)
    d = _stub_driver(step=1)
    with RunSnapshotter(str(tmp_path), every=1, overhead_budget=0,
                        block=True) as sn:
        assert sn.maybe_snapshot(d) is not None  # dispatched fine
    assert sn.stats.failures == 1 and sn.stats.saves == 0
    assert "disk on fire" in sn.stats.last_error


def test_launcher_fails_when_a_snapshot_save_failed(tmp_path, monkeypatch):
    """The writer thread keeps training alive through a failed save, but the
    launcher's run as a whole must not report success."""
    def boom(*a, **kw):
        raise OSError("disk on fire")

    saved = dict(os.environ)  # the launcher wires its env when imported
    try:
        from repro.launch import train
    finally:
        os.environ.clear()
        os.environ.update(saved)
    monkeypatch.setattr(checkpoint, "save", boom)
    with pytest.raises(RuntimeError, match="disk on fire"):
        train.main(["--arch", "granite-8b", "--reduced", "--layers", "1",
                    "--steps", "4", "--batch", "2", "--seq", "16",
                    "--superstep", "2", "--prefetch", "0",
                    "--checkpoint", str(tmp_path), "--checkpoint-every", "1",
                    "--checkpoint-budget", "0"])


def test_snapshotter_retention_keeps_last_k(tmp_path):
    d = _stub_driver()
    with RunSnapshotter(str(tmp_path), every=1, keep_last=2,
                        overhead_budget=0, block=True) as sn:
        for step in (1, 2, 3, 4, 5):
            d._supersteps_done = step
            sn.maybe_snapshot(d)
    assert checkpoint.list_steps(str(tmp_path)) == [4, 5]


def test_restore_driver_requires_a_valid_checkpoint(tmp_path):
    with pytest.raises(FileNotFoundError):
        snapshot.restore_driver(_stub_driver(), str(tmp_path / "nowhere"))
    # a root whose every step directory is torn is as good as empty
    d = _stub_driver(step=3)
    with RunSnapshotter(str(tmp_path), every=1, overhead_budget=0,
                        block=True) as sn:
        sn.maybe_snapshot(d)
    os.remove(os.path.join(checkpoint.step_dir(str(tmp_path), 3),
                           "manifest.json"))
    with pytest.raises(FileNotFoundError, match="torn or corrupt"):
        snapshot.restore_driver(_stub_driver(), str(tmp_path))


# ---------------------------------------------------------------------------
# In-process kill-and-resume: exact-mode LM engine, prefetch ring on
# ---------------------------------------------------------------------------

SEQ, BATCH = 16, 4


def _lm_cfg():
    cfg = dataclasses.replace(
        reduced(get_config("granite-8b"), layers=1, d_model=16),
        vocab_size=32, d_ff=32)
    return RunConfig(model=cfg, shape=SHAPES["train_4k"],
                     averaging=AveragingConfig("exact", 1),
                     stream=StreamConfig(streaming_rate=1e3,
                                         processing_rate=1e6, comms_rate=1e6),
                     optimizer="adam", learning_rate=1e-3,
                     param_dtype="float32", remat=False)


def _lm_sample_fn():
    data = MarkovTokenStream(32, seed=0)

    def draw(rng, n):
        toks = data.sample(rng, n, SEQ + 1)
        return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}

    return draw


def _lm_driver(mesh, run_cfg, clock, **kw):
    state = init_state(run_cfg, jax.random.PRNGKey(0))
    return StreamingDriver(
        run_cfg, mesh, state, _lm_sample_fn(), batch=BATCH,
        engine=EngineConfig(superstep=2, prefetch_depth=2, replan_every=1,
                            warmup_supersteps=0),
        clock=clock, **kw)


def test_resume_bit_identical_exact_mode_with_prefetch(tmp_path):
    """Kill after CUT supersteps, resume from the newest snapshot: params,
    history tail, stream counters, and the online rate-estimator fit are all
    bit-identical to the uninterrupted run. The prefetch ring stays ON —
    the splitter snapshot rides the ring's `meta` hook, so supersteps that
    were staged but never consumed at the cut are re-dealt, not skipped."""
    TOTAL, CUT = 8, 4
    run_cfg = _lm_cfg()
    mesh = make_mesh((1, 1), ("data", "model"))
    with mesh_rules(mesh, activation_rules(mesh, run_cfg.shape)):
        with _lm_driver(mesh, run_cfg, _FakeClock(1e-3)) as ref:
            ref_state, ref_hist = ref.run(TOTAL)
            ref_est = ref._estimator.state_dict()

        with _lm_driver(mesh, run_cfg, _FakeClock(1e-3),
                        snapshotter=RunSnapshotter(
                            str(tmp_path), every=1, overhead_budget=0,
                            block=True)) as victim:
            victim.run(CUT)
        assert checkpoint.list_steps(str(tmp_path))[-1] == CUT

        clk = _FakeClock(1e-3)
        for _ in range(2 * CUT):  # the driver reads the clock 2x/superstep
            clk()
        with _lm_driver(mesh, run_cfg, clk,
                        resume_from=str(tmp_path)) as resumed:
            assert resumed.resumed_from == checkpoint.step_dir(
                str(tmp_path), CUT)
            assert resumed._supersteps_done == CUT
            res_state, res_hist = resumed.run(TOTAL - CUT)
            res_est = resumed._estimator.state_dict()

    _assert_states_equal(ref_state, res_state)
    assert res_est == ref_est
    assert len(res_hist) == TOTAL - CUT
    for r_ref, r_res in zip(ref_hist[CUT:], res_hist):
        assert r_ref["round"] == r_res["round"]
        assert r_ref["counters"] == r_res["counters"]
        np.testing.assert_array_equal(
            np.asarray(r_ref["metrics"]["loss"]),
            np.asarray(r_res["metrics"]["loss"]))


# ---------------------------------------------------------------------------
# In-process resume under churn (elastic krasulina engine)
# ---------------------------------------------------------------------------

def _elastic_driver(faults, *, clock, traces=None, gov=None, n=5, batch=10,
                    **kw):
    run_cfg = PCARunConfig(
        pca=FIG7, averaging=AveragingConfig(mode="gossip", rounds=2))
    builder = krasulina.krasulina_superstep_builder(
        run_cfg.averaging, n, lambda t: 10.0 / t)
    if traces is not None:
        inner = builder

        def builder(B, membership=None):  # noqa: F811
            raw = inner(B, membership)
            m = n if membership is None else membership.n_active

            def counted(s, b):
                traces.append((B, m))
                return raw(s, b)

            return counted

    w0 = jax.random.normal(jax.random.PRNGKey(0), (FIG7.dim,))
    state = krasulina.init_krasulina_state(w0 / jnp.linalg.norm(w0),
                                           run_cfg.averaging, n)
    return StreamingDriver(
        run_cfg, None, state, make_pca_host_sampler(make_pca_stream(FIG7)),
        superstep_builder=builder, n_nodes=n, batch=batch, faults=faults,
        engine=EngineConfig(superstep=2, prefetch_depth=0, replan_every=1,
                            warmup_supersteps=0, warmup_per_bucket=0,
                            governor=gov or GovernorConfig()),
        clock=clock, **kw)


def test_resume_under_churn_bit_identical(tmp_path):
    """Resume from a checkpoint taken while the cohort was SHRUNK (node 4
    dead): the relabeled cohort, its re-derived bucket ladder, and the whole
    trajectory — including the later rejoin — are bit-identical to the
    uninterrupted run."""
    TOTAL, CUT = 8, 3  # cut lands mid-drop-era (supersteps 2-4 run with N=4)
    faults = FaultSchedule.parse("death:4@2-5", 5)

    with _elastic_driver(faults, clock=_FakeClock(1e-3)) as ref:
        ref_state, ref_hist = ref.run(TOTAL)

    with _elastic_driver(faults, clock=_FakeClock(1e-3),
                         snapshotter=RunSnapshotter(
                             str(tmp_path), every=1, overhead_budget=0,
                             block=True)) as victim:
        victim.run(CUT)
        assert victim.membership.n_active == 4  # mid-shrink, as intended

    clk = _FakeClock(1e-3)
    for _ in range(2 * CUT):
        clk()
    with _elastic_driver(faults, clock=clk,
                         resume_from=str(tmp_path)) as resumed:
        # churn continuity restored before the first resumed superstep
        assert resumed.membership.n_active == 4
        assert resumed.membership == ref_hist[CUT - 1]["plan"].membership
        assert resumed.ladder.buckets == resumed._ladder_for(4).buckets
        assert resumed.pipeline.plan.B == 12  # ceil(10/4)*4, the shrunk-era B
        res_state, res_hist = resumed.run(TOTAL - CUT)

    _assert_states_equal(ref_state, res_state)
    assert resumed.membership.is_full  # rejoined at superstep 5
    eras = [(r["bucket"], r["n_active"]) for r in res_hist]
    assert eras == [(r["bucket"], r["n_active"]) for r in ref_hist[CUT:]]
    for r_ref, r_res in zip(ref_hist[CUT:], res_hist):
        assert r_ref["counters"] == r_res["counters"]
        np.testing.assert_array_equal(
            np.asarray(r_ref["metrics"]["consensus_err"]),
            np.asarray(r_res["metrics"]["consensus_err"]))


def test_resume_rejoin_is_zero_retrace_and_straggler_state_survives(tmp_path):
    """Two drop eras: resume lands in the full-cohort gap between them. The
    resumed process compiles each (B, cohort) signature once on first use;
    the SECOND rejoin reuses the already-compiled full-cohort executable —
    zero retrace — and the straggler EWMAs (a 3x-slowed node) come back
    bit-identical."""
    TOTAL, CUT = 10, 5
    spec = "death:4@2-4,slow:1@0-10x3,death:4@6-8"
    gov = GovernorConfig(straggler_policy="drop", straggler_slow_factor=4.0)

    with _elastic_driver(FaultSchedule.parse(spec, 5), gov=gov,
                         clock=_FakeClock(1e-3)) as ref:
        ref_state, ref_hist = ref.run(TOTAL)
        ref_straggler = ref._straggler.state_dict()

    with _elastic_driver(FaultSchedule.parse(spec, 5), gov=gov,
                         clock=_FakeClock(1e-3),
                         snapshotter=RunSnapshotter(
                             str(tmp_path), every=1, overhead_budget=0,
                             block=True)) as victim:
        victim.run(CUT)
        assert victim.membership.is_full  # cut in the between-eras gap

    clk = _FakeClock(1e-3)
    for _ in range(2 * CUT):
        clk()
    traces = []
    with _elastic_driver(FaultSchedule.parse(spec, 5), gov=gov, clock=clk,
                         traces=traces, resume_from=str(tmp_path)) as resumed:
        res_state, res_hist = resumed.run(TOTAL - CUT)
        res_straggler = resumed._straggler.state_dict()

    _assert_states_equal(ref_state, res_state)
    assert res_straggler == ref_straggler
    # supersteps 5, 6-7, 8-9: (10,5) then (12,4) then (10,5) again — the
    # second full-cohort era must NOT have traced a third time
    assert traces == [(10, 5), (12, 4)]
    eras = [(r["bucket"], r["n_active"]) for r in res_hist]
    assert eras == [(10, 5), (12, 4), (12, 4), (10, 5), (10, 5)]


# ---------------------------------------------------------------------------
# SIGKILL regression (subprocess worker)
# ---------------------------------------------------------------------------

WORKER = os.path.join(os.path.dirname(__file__), "snapshot_worker.py")
TOTAL = 8


def _worker_cmd(root, *, out="", resume=False, cache_dir="", snapshots=True):
    cmd = [sys.executable, WORKER, "--root", str(root),
           "--supersteps", str(TOTAL)]
    if out:
        cmd += ["--out", str(out)]
    if resume:
        cmd += ["--resume"]
    if cache_dir:
        cmd += ["--cache-dir", str(cache_dir)]
    if not snapshots:
        cmd += ["--no-snapshots"]
    return cmd


def _env(extra=None):
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(os.path.dirname(__file__)), "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("SNAPSHOT_SLOW_AFTER_STEP", None)
    if extra:
        env.update(extra)
    return env


def _run_to_completion(cmd, env, timeout=300):
    out = subprocess.run(cmd, env=env, capture_output=True, text=True,
                         timeout=timeout)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "DONE" in out.stdout
    return out.stdout


def _kill_when(cmd, env, marker, timeout=300):
    """Start the worker, SIGKILL it as soon as `marker` appears on stdout."""
    proc = subprocess.Popen(cmd, env=env, stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True)
    deadline = time.monotonic() + timeout
    try:
        for line in proc.stdout:
            if time.monotonic() > deadline:
                raise TimeoutError(f"no {marker!r} within {timeout}s")
            if line.startswith(marker):
                os.kill(proc.pid, signal.SIGKILL)
                proc.wait(timeout=30)
                assert proc.returncode == -signal.SIGKILL
                return
        raise AssertionError(f"worker exited before printing {marker!r}")
    finally:
        if proc.poll() is None:
            proc.kill()
        proc.stdout.close()
        proc.wait(timeout=30)


@pytest.fixture(scope="module")
def reference_run(tmp_path_factory):
    """One uninterrupted worker run shared by every SIGKILL scenario."""
    d = tmp_path_factory.mktemp("snapref")
    out = d / "ref.npz"
    _run_to_completion(
        _worker_cmd(d / "unused-root", out=out, snapshots=False), _env())
    return np.load(out)


def _assert_matches_reference(ref, out_path):
    got = np.load(out_path)
    start = int(got["resumed_at"])
    assert 0 < start < TOTAL  # genuinely resumed mid-stream
    for k in ref.files:
        if k.startswith("state::"):
            np.testing.assert_array_equal(ref[k], got[k], err_msg=k)
    np.testing.assert_array_equal(ref["counters"], got["counters"])
    np.testing.assert_array_equal(ref["eras"][start:], got["eras"])
    return start


def test_sigkill_mid_stream_resume_bit_identical(tmp_path, reference_run):
    """SIGKILL the training process right after superstep 3's checkpoint is
    durable (mid-shrink era, node 4 dead); a fresh process resuming from the
    root reproduces the uninterrupted final state bit-for-bit. The warm
    restart hits the persistent compilation cache: zero new entries."""
    root, cache = tmp_path / "ckpt", tmp_path / "cc"
    _kill_when(_worker_cmd(root, cache_dir=cache), _env(), "CKPT 3")
    assert checkpoint.newest_valid(str(root)) is not None

    def superstep_entries():
        # the two (B, cohort) era executables land under the jit names of
        # the full-cohort builder ("superstep") and the membership-aware
        # one ("fn"); everything else in the cache is small op-by-op jits
        return sorted(f for f in os.listdir(cache)
                      if f.startswith(("jit_superstep", "jit_fn")) and
                      f.endswith("-cache"))

    # the killed run persisted both compiled (B, cohort) superstep
    # executables: (10, 5) from the full era and (12, 4) from the shrink
    cold = superstep_entries()
    assert len(cold) == 2

    out = tmp_path / "resumed.npz"
    _run_to_completion(
        _worker_cmd(root, out=out, resume=True, cache_dir=cache), _env())
    start = _assert_matches_reference(reference_run, out)
    assert start >= 3  # resumed at (or after) the checkpoint we killed at
    # warm restart: the resumed process re-traces both signatures but every
    # superstep XLA compile is a cache hit — zero new superstep executables
    # (small op-by-op entries MAY appear for code paths the victim never
    # reached, e.g. the rejoin consensus sync)
    assert superstep_entries() == cold


def test_sigkill_mid_save_leaves_torn_step_and_resumes_from_newest_valid(
        tmp_path, reference_run):
    """SIGKILL while the writer is mid-save for step 3 (after its first leaf
    write, before the manifest): the step directory is torn, `newest_valid`
    falls back to step 2, and the resumed run still matches the
    uninterrupted reference bit-for-bit."""
    root = tmp_path / "ckpt"
    _kill_when(_worker_cmd(root), _env({"SNAPSHOT_SLOW_AFTER_STEP": "3"}),
               "SLOW-SAVE 3")
    torn = checkpoint.step_dir(str(root), 3)
    assert os.path.isdir(torn) and not checkpoint.is_valid(torn)
    assert checkpoint.newest_valid(str(root)) == \
        checkpoint.step_dir(str(root), 2)

    out = tmp_path / "resumed.npz"
    _run_to_completion(_worker_cmd(root, out=out, resume=True), _env())
    start = _assert_matches_reference(reference_run, out)
    assert start == 2  # the torn step 3 was skipped
