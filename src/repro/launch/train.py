"""Production training launcher, wired onto the superstep streaming engine
(`train.driver`): K-round device scans, async device prefetch, and the
closed-loop (B, mu) governor.

`--layers N` cuts depth only: the model keeps its published widths, which is
how one chip holds a granite-8b node (`chip_smoke.py`). `--reduced` swaps in
the family-faithful smoke variant (narrow widths) for CPU runs; the full
configs are exercised via launch.dryrun. `main(argv)` returns what it built,
so a script can drive it in-process.

Example:
  PYTHONPATH=src python -m repro.launch.train --arch granite-8b --reduced \
      --steps 48 --batch 8 --seq 256 --averaging gossip --rounds 4 \
      --superstep 8 --prefetch 2
"""
from __future__ import annotations

import argparse
import dataclasses

# perf hygiene BEFORE the jax import (XLA reads XLA_FLAGS / TF log level at
# import time); `--no-env-tuning` on the command line skips it
from repro.launch import env as _env

_env.apply_from_argv()

import jax
import numpy as np

from repro.configs import SHAPES, get_config, reduced as reduce_cfg
from repro.configs.base import (AveragingConfig, GovernorConfig, PublishConfig,
                                RunConfig, StreamConfig)
from repro.core.faults import FaultSchedule
from repro.data.lm import MarkovTokenStream
from repro.launch import sharding as shlib
from repro.launch.mesh import make_host_mesh, make_production_mesh, n_data_nodes
from repro.models.common import mesh_rules
from repro.train import checkpoint as ckpt
from repro.train.driver import EngineConfig, StreamingDriver
from repro.train.trainer import init_placed_state, superstep_builder


def main(argv=None):
    """Parse `argv` (sys.argv[1:] when None), train, and return
    `(state, history, publisher)`: the final TrainState, the driver's
    per-superstep records, and the SnapshotPublisher (None without
    --publish). Raises when the snapshotter recorded a failed save."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--layers", type=int, default=0,
                    help="cut depth to this many layers, keeping the "
                         "published widths (0 = the config's depth)")
    ap.add_argument("--devices", type=int, default=0,
                    help="build the host mesh over the first N local "
                         "devices (0 = all visible)")
    ap.add_argument("--steps", type=int, default=100,
                    help="total rounds (rounded up to whole supersteps)")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--optimizer", default="adam")
    ap.add_argument("--averaging", default="exact",
                    choices=["exact", "gossip", "hierarchical"])
    ap.add_argument("--rounds", type=int, default=1)
    ap.add_argument("--topology", default="ring")
    ap.add_argument("--streaming-rate", type=float, default=0.0)
    ap.add_argument("--processing-rate", type=float, default=0.0)
    ap.add_argument("--comms-rate", type=float, default=0.0)
    ap.add_argument("--dtype", default="bfloat16",
                    help="parameter dtype; below float32 the optimizer keeps "
                         "float32 master weights")
    ap.add_argument("--checkpoint", default="",
                    help="checkpoint directory; with --checkpoint-every 0 a "
                         "single end-of-run save, otherwise the root for "
                         "step_NNNNNNNN/ async snapshots")
    ap.add_argument("--checkpoint-every", type=int, default=0,
                    help="async snapshot cadence in supersteps (0 = only the "
                         "legacy end-of-run save); requires --checkpoint")
    ap.add_argument("--keep-last", type=int, default=3,
                    help="checkpoints retained under the root (the newest "
                         "VALID one is never pruned)")
    ap.add_argument("--checkpoint-budget", type=float, default=0.05,
                    help="snapshot-governor overhead budget: max fraction of "
                         "train wall time spent dispatching snapshot copies")
    ap.add_argument("--resume", default="",
                    help="resume from this checkpoint root (newest valid "
                         "step) or a specific step_NNNNNNNN directory")
    ap.add_argument("--log-every", type=int, default=1,
                    help="log every this many supersteps")
    ap.add_argument("--superstep", type=int, default=8,
                    help="K: rounds folded into one jitted device scan")
    ap.add_argument("--prefetch", type=int, default=2,
                    help="async prefetch ring depth (0 = synchronous staging)")
    ap.add_argument("--replan-every", type=int, default=1,
                    help="supersteps between closed-loop (B, mu) re-plans; "
                         "0 disables the governor feedback")
    ap.add_argument("--buckets", default="",
                    help="comma-separated B bucket ladder for the adaptive "
                         "governor (e.g. '8,16,32'); empty pins B to --batch")
    ap.add_argument("--n-buckets", type=int, default=1,
                    help="auto geometric ladder size around the planned B "
                         "when --buckets is empty (1 = pinned B)")
    ap.add_argument("--bucket-hysteresis", type=int, default=2,
                    help="consecutive re-plans that must agree on a bucket "
                         "before the governor switches B")
    ap.add_argument("--no-rate-estimator", action="store_true",
                    help="disable the online least-squares (R_p, R_c) "
                         "estimator; fall back to the config comms constant")
    ap.add_argument("--horizon", type=float, default=0.0,
                    help="sample horizon t' for Theorem 4's B <= sqrt(t') "
                         "bucket ceiling (0 = no ceiling)")
    ap.add_argument("--faults", default="",
                    help="fault-injection spec for elastic membership, e.g. "
                         "'death:1@5-12,slow:0@3-9x4' "
                         "(see core/faults.py; needs --averaging gossip)")
    ap.add_argument("--scenario", default="",
                    help="named scenario from core/scenarios.py: replaces "
                         "--topology/--rounds with the scenario's "
                         "time-varying mixing schedule and adds its link "
                         "model (loss/bandwidth) to --faults; the stream "
                         "axis stays the LM token stream (the synthetic "
                         "streams are exercised by "
                         "benchmarks/bench_scenarios.py); needs "
                         "--averaging gossip")
    ap.add_argument("--straggler-policy", default="wait",
                    choices=["wait", "drop", "deadline"],
                    help="straggler handling: wait (lockstep), drop "
                         "(exclude nodes slower than --straggler-factor x "
                         "median), deadline (--straggler-deadline seconds)")
    ap.add_argument("--straggler-factor", type=float, default=2.0)
    ap.add_argument("--straggler-deadline", type=float, default=0.0)
    ap.add_argument("--no-rejoin-sync", action="store_true",
                    help="keep a rejoining node's stale iterate instead of "
                         "syncing it to the cohort mean")
    ap.add_argument("--production-mesh", action="store_true",
                    help="use the 16x16 mesh (requires 256 devices)")
    ap.add_argument("--no-env-tuning", action="store_true",
                    help="skip the launcher perf hygiene (launch/env.py); "
                         "applied at import time, declared here for --help")
    ap.add_argument("--publish", action="store_true",
                    help="publish consensus param snapshots at superstep "
                         "boundaries (serve/publisher.py) for a serving "
                         "replica to adopt")
    ap.add_argument("--publish-budget", type=float, default=0.05,
                    help="publish-governor overhead budget: max fraction of "
                         "train wall time spent on snapshot copies")
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduce_cfg(cfg)
    if args.layers:
        cfg = dataclasses.replace(cfg, num_layers=args.layers)
    mesh = (make_production_mesh() if args.production_mesh
            else make_host_mesh(n_devices=args.devices))
    n_nodes = n_data_nodes(mesh)
    scenario = None
    averaging = AveragingConfig(args.averaging, args.rounds, args.topology)
    if args.scenario:
        if args.averaging != "gossip":
            ap.error("--scenario needs --averaging gossip")
        from repro.core import scenarios as scenario_lib

        scenario = scenario_lib.get_scenario(args.scenario)
        if scenario.n_nodes != n_nodes:
            # scenarios are registered at their canonical size; re-root the
            # schedule on this mesh's node axis (link endpoints must fit)
            scenario = dataclasses.replace(scenario, n_nodes=n_nodes)
        averaging = scenario_lib.averaging_config(scenario)
    run = RunConfig(
        model=cfg, shape=SHAPES["train_4k"],
        averaging=averaging,
        stream=StreamConfig(args.streaming_rate, args.processing_rate,
                            args.comms_rate),
        optimizer=args.optimizer, learning_rate=args.lr, param_dtype=args.dtype)

    decentralized = args.averaging != "exact"
    rules = shlib.activation_rules(mesh, run.shape, node_axis=decentralized)
    buckets = tuple(int(b) for b in args.buckets.split(",") if b.strip())
    governor = GovernorConfig(buckets=buckets, n_buckets=args.n_buckets,
                              hysteresis=args.bucket_hysteresis,
                              estimate_rates=not args.no_rate_estimator,
                              straggler_policy=args.straggler_policy,
                              straggler_slow_factor=args.straggler_factor,
                              straggler_deadline_s=args.straggler_deadline,
                              sync_on_rejoin=not args.no_rejoin_sync)
    # the scenario's link model rides the same fault schedule as any node
    # faults from --faults (link windows index consensus rounds, node
    # windows supersteps — core/faults.py)
    fault_spec = ",".join(
        s for s in (args.faults, scenario.links if scenario else "") if s)
    faults = (FaultSchedule.parse(fault_spec, n_nodes,
                                  seed=scenario.seed if scenario else 0)
              if fault_spec else None)
    builder = (superstep_builder(run, mesh, n_nodes=n_nodes,
                                 mix=scenario_lib.build_mix(scenario))
               if scenario is not None else None)
    engine = EngineConfig(superstep=args.superstep,
                          prefetch_depth=args.prefetch,
                          replan_every=args.replan_every,
                          governor=governor)
    supersteps = -(-args.steps // engine.superstep)

    data = MarkovTokenStream(cfg.vocab_size, seed=0)
    sample_fn = lambda rng, n: _draw(data, rng, n, args.seq)

    publisher = None
    if args.publish:
        from repro.serve.publisher import SnapshotPublisher

        pub_cfg = PublishConfig(enabled=True,
                                overhead_budget=args.publish_budget)
        publisher = SnapshotPublisher(
            overhead_budget=pub_cfg.overhead_budget,
            min_interval_s=pub_cfg.min_interval_s, block=pub_cfg.block)

    snapshotter = None
    if args.checkpoint_every > 0:
        if not args.checkpoint:
            ap.error("--checkpoint-every needs --checkpoint DIR as the root")
        from repro.train.snapshot import RunSnapshotter

        snapshotter = RunSnapshotter(args.checkpoint,
                                     every=args.checkpoint_every,
                                     keep_last=args.keep_last,
                                     overhead_budget=args.checkpoint_budget)

    with mesh_rules(mesh, rules):
        state = init_placed_state(run, mesh, jax.random.PRNGKey(run.seed))
        with StreamingDriver(run, mesh, state, sample_fn, engine=engine,
                             superstep_builder=builder,
                             batch=args.batch, faults=faults,
                             horizon=args.horizon or None,
                             publisher=publisher, snapshotter=snapshotter,
                             resume_from=args.resume or None) as driver:
            plan = driver.pipeline.plan
            if scenario is not None:
                sched = " ".join(f"{t}x{s}"
                                 for t, s in scenario.topology_schedule)
                print(f"scenario: {scenario.name} [{sched}] "
                      f"links='{scenario.links}' rounds={scenario.rounds}")
            if driver.resumed_from:
                print(f"resumed: {driver.resumed_from} "
                      f"(superstep {driver._supersteps_done})")
            print(f"plan: B={plan.B} mu={plan.mu} regime={plan.regime} "
                  f"nodes={n_nodes} K={engine.superstep} "
                  f"prefetch={engine.prefetch_depth} "
                  f"buckets={list(driver.ladder.buckets)}")
            state, history = driver.run(supersteps, log_fn=_log,
                                        log_every=args.log_every)
    if publisher is not None:
        st = publisher.stats
        stale = publisher.staleness(supersteps)
        print(f"publisher: v{publisher.version} publishes={st.publishes} "
              f"skipped(budget={st.skipped_budget} "
              f"interval={st.skipped_interval}) "
              f"cost_ewma={st.cost_ewma_s * 1e3:.2f}ms "
              f"total_cost={st.total_cost_s:.3f}s "
              f"staleness={stale['supersteps']} supersteps "
              f"/ {stale['wall_s']:.2f}s")
    if snapshotter is not None:
        st = snapshotter.stats
        print(f"snapshotter: saves={st.saves} "
              f"skipped(cadence={st.skipped_cadence} "
              f"budget={st.skipped_budget} busy={st.skipped_busy}) "
              f"failures={st.failures} "
              f"cost_ewma={st.cost_ewma_s * 1e3:.2f}ms "
              f"total_cost={st.total_cost_s:.3f}s -> {args.checkpoint}")
        if st.failures:
            raise RuntimeError(f"{st.failures} snapshot save(s) failed, "
                               f"last: {st.last_error}")
    elif args.checkpoint:
        ckpt.save(args.checkpoint, state, step=supersteps * engine.superstep,
                  meta={"arch": args.arch, "reduced": args.reduced,
                        "layers": cfg.num_layers})
        print(f"checkpoint -> {args.checkpoint}")
    return state, history, publisher


def _log(rec):
    m = rec["metrics"]
    c = rec["counters"]
    plan = rec.get("replanned", rec["plan"])
    gov = ""
    if rec["plan"].membership is not None:
        gov += f" N={rec['n_active']}/{rec['plan'].membership.n}"
    if "bucket_switch" in rec:
        gov += f" B:{rec['bucket_switch'][0]}->{rec['bucket_switch'][1]}"
    if "est_Rc" in rec:
        rc = rec["est_Rc"]
        gov += f" est_Rc={'inf' if rc <= 0 else f'{rc:.3g}'}"
    # host seconds of the superstep (docs/DESIGN.md §Observability): a ring
    # wait near the superstep's wall time means the splitter sets the pace
    host = rec["host_s"]
    splitter = sum(host.get(k, 0.0) for k in ("draw", "stack", "node_split"))
    print(f"round {rec['round']:5d} loss {m['loss']:.4f} ce {m['ce']:.4f} "
          f"consensus_err {m['consensus_err']:.2e} "
          f"t'={c.samples_arrived} B={rec['bucket']} mu={plan.mu} "
          f"{plan.regime}{gov} "
          f"({rec['rounds_per_s']:.1f} rounds/s, "
          f"{rec['samples_per_s']:.0f} samples/s, "
          f"ring wait {1e3 * host['ring_wait']:.1f} ms, "
          f"splitter {1e3 * splitter:.1f} ms)", flush=True)


def _draw(data: MarkovTokenStream, rng: np.random.Generator, n: int, seq: int):
    toks = data.sample(rng, n, seq + 1)
    return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}


if __name__ == "__main__":
    main()
