"""Subprocess target for the kill-and-resume regression suite
(tests/test_snapshot.py). Runs the elastic krasulina driver — the same
config as tests/test_elastic.py's `_elastic_driver` — on a deterministic
fake clock with per-superstep blocking snapshots, so the parent can SIGKILL
it at a known point and a resumed process must reproduce the uninterrupted
trajectory bit-for-bit.

Usage:
  python tests/snapshot_worker.py --root DIR --supersteps N [--resume]
      [--out FILE.npz] [--faults SPEC] [--cache-dir DIR]

Env knobs (victim-only torture):
  SNAPSHOT_SLOW_AFTER_STEP=K   sleep SNAPSHOT_SLOW_WRITE_S (default 120)
                               after the first leaf write of any save with
                               step >= K, so a SIGKILL lands mid-save and
                               leaves that step directory torn.
"""
from __future__ import annotations

import argparse
import os
import sys


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--root", required=True)
    ap.add_argument("--supersteps", type=int, required=True)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--out", default="")
    ap.add_argument("--faults", default="death:4@2-5")
    ap.add_argument("--no-snapshots", action="store_true",
                    help="uninterrupted reference run: no snapshotter at all")
    ap.add_argument("--cache-dir", default="")
    args = ap.parse_args()

    if args.cache_dir:
        # must land before the jax import below
        from repro.launch import env as _env

        os.environ["JAX_COMPILATION_CACHE_DIR"] = args.cache_dir
        os.environ.update(_env.compilation_cache_env())

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs.base import AveragingConfig, GovernorConfig
    from repro.configs.paper_pca import FIG7, PCARunConfig
    from repro.core import krasulina
    from repro.core.faults import FaultSchedule
    from repro.data.synthetic import make_pca_host_sampler, make_pca_stream
    from repro.train import checkpoint
    from repro.train.driver import EngineConfig, StreamingDriver
    from repro.train.snapshot import RunSnapshotter

    slow_after = os.environ.get("SNAPSHOT_SLOW_AFTER_STEP")
    if slow_after is not None:
        _arm_slow_save(checkpoint, int(slow_after),
                       float(os.environ.get("SNAPSHOT_SLOW_WRITE_S", "120")))

    class FakeClock:
        def __init__(self, dt):
            self.t, self.dt = 0.0, dt

        def __call__(self):
            self.t += self.dt
            return self.t

    n, batch = 5, 10
    run_cfg = PCARunConfig(
        pca=FIG7, averaging=AveragingConfig(mode="gossip", rounds=2))
    builder = krasulina.krasulina_superstep_builder(
        run_cfg.averaging, n, lambda t: 10.0 / t)
    w0 = jax.random.normal(jax.random.PRNGKey(0), (FIG7.dim,))
    state = krasulina.init_krasulina_state(w0 / jnp.linalg.norm(w0),
                                           run_cfg.averaging, n)
    faults = FaultSchedule.parse(args.faults, n) if args.faults else None

    clock = FakeClock(1e-3)
    resume_from = None
    if args.resume:
        # the driver reads the clock exactly twice per superstep: advance the
        # fake clock to where the uninterrupted run's clock stood at the
        # checkpoint, so governed timings replay identically
        path = checkpoint.newest_valid(args.root)
        if path is None:
            print("RESUME-FAILED: no valid checkpoint", flush=True)
            sys.exit(3)
        done = int(checkpoint.load_manifest(path)["meta"]["supersteps_done"])
        for _ in range(2 * done):
            clock()
        resume_from = args.root

    snapshotter = None
    if not args.no_snapshots:
        # block=True: a printed "CKPT k" line means that step is DURABLE, so
        # the parent's kill point is well-defined
        snapshotter = RunSnapshotter(args.root, every=1, keep_last=100,
                                     overhead_budget=0, block=True)

    driver = StreamingDriver(
        run_cfg, None, state, make_pca_host_sampler(make_pca_stream(FIG7)),
        superstep_builder=builder, n_nodes=n, batch=batch, faults=faults,
        engine=EngineConfig(superstep=2, prefetch_depth=0, replan_every=1,
                            warmup_supersteps=0, warmup_per_bucket=0,
                            governor=GovernorConfig()),
        clock=clock, snapshotter=snapshotter, resume_from=resume_from)
    start = driver._supersteps_done
    print(f"START {start}", flush=True)

    def log(rec):
        ck = rec.get("checkpoint")
        if ck is not None:
            print(f"CKPT {ck}", flush=True)

    with driver:
        driver.run(args.supersteps - start, log_fn=log)

    if args.out:
        leaves = checkpoint._flatten(driver.state)
        arrs = {f"state::{k}": np.asarray(v) for k, v in leaves.items()}
        arrs["eras"] = np.array([(r["bucket"], r["n_active"])
                                 for r in driver.history])
        arrs["counters"] = np.array(driver.history[-1]["counters"])
        arrs["resumed_at"] = np.array(start)
        np.savez(args.out, **arrs)
    if args.cache_dir:
        n_cache = len([f for f in os.listdir(args.cache_dir)
                       if f.endswith("-cache")])
        print(f"CACHE-ENTRIES {n_cache}", flush=True)
    print("DONE", flush=True)


def _arm_slow_save(checkpoint, after_step: int, sleep_s: float) -> None:
    """Make every save with step >= `after_step` hang after its first leaf
    write, so a SIGKILL during the hang leaves a torn step directory (leaves
    present, no manifest)."""
    import time

    orig = checkpoint._save_leaf
    hung_steps = set()

    def slow(path, arr, **kw):
        orig(path, arr, **kw)
        step_dir = os.path.basename(os.path.dirname(path))
        if step_dir.startswith("step_"):
            step = int(step_dir[len("step_"):])
            if step >= after_step and step not in hung_steps:
                hung_steps.add(step)
                print(f"SLOW-SAVE {step}", flush=True)
                time.sleep(sleep_s)

    checkpoint._save_leaf = slow


if __name__ == "__main__":
    main()
