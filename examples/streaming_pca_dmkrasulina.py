"""Paper Section IV-D (Fig. 7): the D(M)-Krasulina family estimating the top
eigenvector of a streaming covariance (d=10, eigengap 0.1) — exact averaging,
gossip consensus through the MixOp engine, and the full streaming engine
(governed splitter -> prefetch ring -> K-round superstep -> closed-loop
governor) driving the PCA workload.

Run:  PYTHONPATH=src python examples/streaming_pca_dmkrasulina.py
"""
import jax
import jax.numpy as jnp

from repro.configs.base import AveragingConfig, GovernorConfig, StreamConfig
from repro.configs.paper_pca import FIG7, PCARunConfig
from repro.core import krasulina, problems
from repro.data.synthetic import make_pca_host_sampler, make_pca_stream
from repro.kernels import ops, ref
from repro.train.driver import EngineConfig, StreamingDriver

stream = make_pca_stream(FIG7)
metric = lambda w: problems.pca_excess_risk(w, stream.cov, stream.lambda1)
w0 = jax.random.normal(jax.random.PRNGKey(0), (FIG7.dim,))
w0 = w0 / jnp.linalg.norm(w0)

print("Fig 7(a): excess risk vs B at t' = 1e5 samples (exact averaging)")
for B in (1, 10, 100, 1000):
    res = krasulina.run_dm_krasulina(
        stream.draw, w0, N=min(10, B), B=B, steps=max(1, 100_000 // B),
        stepsize=lambda t: 10.0 / t, trace_metric=metric)
    print(f"  B={B:5d}  excess risk = {float(res.trace_metric[-1]):.6f}")

print("Fig 7(b): mu discards at (N,B)=(10,100)")
for mu in (0, 10, 100, 1000):
    res = krasulina.run_dm_krasulina(
        stream.draw, w0, N=10, B=100, mu=mu, steps=1000,
        stepsize=lambda t: 10.0 / t, trace_metric=metric, seed=1)
    print(f"  mu={mu:5d}  excess risk = {float(res.trace_metric[-1]):.6f}")

print("gossip-averaged D-Krasulina (ring consensus on the xi's) vs exact:")
for avg in (None,
            AveragingConfig(mode="gossip", rounds=2),
            AveragingConfig(mode="gossip", rounds=8)):
    res = krasulina.run_d_krasulina(
        stream.draw, w0, N=10, B=100, steps=1000,
        stepsize=lambda t: 10.0 / t, averaging=avg, trace_metric=metric, seed=1)
    name = "exact (oracle)" if avg is None else f"gossip R={avg.rounds}"
    print(f"  {name:15s}  excess risk = {float(res.trace_metric[-1]):.6f}")

# the full streaming engine on the PCA workload: the governed splitter deals
# B samples per round, the prefetch ring stages {"z"} batches, the K-round
# superstep scans on device, and the ADAPTIVE governor re-plans (B, mu) from
# measured rates — B moves between pre-compiled buckets (plan swap, zero
# retrace) while the online estimator closes the loop on R_c
# (docs/DESIGN.md §Adaptive batch buckets)
run_cfg = PCARunConfig(
    pca=FIG7, averaging=AveragingConfig(mode="gossip", rounds=4),
    stream=StreamConfig(streaming_rate=1e4, processing_rate=1e6,
                        comms_rate=1e6))
N = 10
builder = krasulina.krasulina_superstep_builder(
    run_cfg.averaging, N, lambda t: 10.0 / t, metric=metric)
state = krasulina.init_krasulina_state(w0, run_cfg.averaging, N)
gov = GovernorConfig(buckets=(50, 100, 200), hysteresis=2)
with StreamingDriver(run_cfg, None, state, make_pca_host_sampler(stream),
                     superstep_builder=builder, n_nodes=N, batch=100,
                     engine=EngineConfig(superstep=8, prefetch_depth=2,
                                         governor=gov)) as drv:
    state, history = drv.run(25)
    print("driver (gossip R=4, K=8) governor decisions:")
    for rec in history:
        decision = ""
        if "bucket_switch" in rec:
            a, b = rec["bucket_switch"]
            decision += f"  SWITCH B:{a}->{b}"
        if "est_Rc" in rec:
            rc = rec["est_Rc"]
            decision += "  est_Rc=inf" if rc <= 0 else f"  est_Rc={rc:.3g}"
        if rec["superstep"] % 8 == 0 or decision:
            p = rec.get("replanned", rec["plan"])
            print(f"  superstep {rec['superstep']:3d}  B={rec['bucket']:4d} "
                  f"mu={p.mu:4d} {p.regime:17s} "
                  f"excess risk={rec['metrics']['metric']:.4f}{decision}")
    print(f"  buckets compiled: {list(drv.compiled_buckets)} "
          f"(ladder {list(drv.ladder.buckets)})")
first, last = history[0], history[-1]
print(f"driver (gossip R=4, K=8): excess risk "
      f"{first['metrics']['metric']:.4f} -> {last['metrics']['metric']:.4f}, "
      f"consensus spread {last['metrics']['consensus_err']:.2e}, "
      f"{last['samples_per_s']:.0f} samples/s, plan mu={drv.pipeline.plan.mu}")

# the fused TPU kernels compute the same answers (interpret mode on CPU):
z = stream.draw(jax.random.PRNGKey(2), 256)
xi_kernel = ops.krasulina_xi(w0, z, force_pallas=True)
xi_ref = problems.krasulina_xi(w0, z)
print(f"Pallas xi kernel max |xi - ref| = "
      f"{float(jnp.max(jnp.abs(xi_kernel - xi_ref))):.2e}")
import repro.core.mixing as mixing
sched = mixing.schedule("ring", N)
zn = stream.draw(jax.random.PRNGKey(3), 40).reshape(N, 4, -1)
wn = jnp.tile(w0[None], (N, 1))
h_kernel = ops.krasulina_xi_gossip(wn, zn, sched, 4, force_pallas=True)
h_ref = ref.krasulina_xi_gossip_ref(wn, zn, sched, 4)
print(f"Pallas xi+gossip kernel max |h - ref| = "
      f"{float(jnp.max(jnp.abs(h_kernel - h_ref))):.2e}")
