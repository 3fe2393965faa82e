"""Smoke check that the system's main path runs on a TPU chip, through the
entry points a user calls, at the published widths of granite-8b.

One process runs every phase (a chip belongs to one process at a time):

* **lm** — `repro.launch.train.main` in-process: granite-8b at published
  widths cut to 2 layers, bf16 params with f32 master weights, exact
  averaging, seq 4096, K=8 supersteps through the prefetch ring, publishing
  the live params. Checks: finite losses, the last superstep's loss below
  the first, at least one published version.
* **serve** — `serve.engine.ContinuousBatchingEngine` on the published
  params: 2 slots, 4 requests of 128 prompt tokens, 16 new tokens each.
  Checks: every request completes, and request 0's tokens equal the static
  `engine.generate` greedy path (prefill + serve_step).
* **pca** — D-Krasulina on `StreamingDriver` at `configs/paper_pca.py:HIGHD`
  (d = 3072), 16 emulated nodes, ring gossip R = 4, B = 256, K = 8, with the
  fused xi+gossip Pallas kernel. Checks: excess risk falls, the compiled
  superstep holds the kernel, and the kernel matches
  `ref.krasulina_xi_gossip_ref` on the same inputs to f32 tolerance.

`--four-chips` runs only the multi-chip path: decentralized granite-8b
training with one node per chip, ring gossip R = 2 over the 4 chips through
the shard_map rule, against the same steps under exact averaging.

Earlier lines report the device, compile seconds (set-up time, not
throughput) and peak device bytes per phase; none of it is a benchmark. A
failed check raises. The last line of stdout is one JSON object:
  {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}

Run:  python chip_smoke.py [--four-chips]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

if __name__ == "__main__":
    # before `import jax`: the compile cache and the launcher env hygiene
    from repro.launch import env as _env

    _env.apply_from_argv()

import jax
import jax.numpy as jnp
import numpy as np

LM_ARCH = "granite-8b"


class CompileClock:
    """Sums XLA backend compile seconds reported by jax.monitoring."""

    def __init__(self):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if "backend_compile" in event:
            self.seconds += duration


def _peak_bytes(devices):
    """The largest `peak_bytes_in_use` over `devices` (None off-TPU)."""
    stats = [d.memory_stats() for d in devices]
    if any(s is None for s in stats):
        return None
    return max(s["peak_bytes_in_use"] for s in stats)


def _report(phase: str, t0: float, compile_s: float, devices, **checks):
    line = {"phase": phase, "wall_s": time.perf_counter() - t0,
            "compile_s": compile_s, "peak_bytes_in_use": _peak_bytes(devices),
            **checks}
    print(json.dumps(line, default=str), flush=True)


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(f"chip_smoke check failed: {what}")


# ---------------------------------------------------------------------------
# Phases (tests call these on CPU at tiny sizes)
# ---------------------------------------------------------------------------


def _train_argv(*, layers, seq, batch, superstep, supersteps, averaging,
                rounds=1, devices=1, reduced=False):
    # lr: at d_model 4096 without warm-up, Adam's 3e-4 default overshoots
    # (the loss drops from ~68 to ~12 within the first superstep, then climbs
    # back), so the end-of-superstep losses this checks do not fall
    argv = ["--arch", LM_ARCH, "--layers", str(layers), "--lr", "3e-5",
            "--dtype", "bfloat16", "--averaging", averaging,
            "--rounds", str(rounds), "--topology", "ring",
            "--seq", str(seq), "--batch", str(batch),
            "--superstep", str(superstep), "--prefetch", "2",
            "--steps", str(supersteps * superstep),
            "--devices", str(devices)]
    return argv + (["--reduced"] if reduced else [])


def _losses(history):
    losses = [rec["metrics"]["loss"] for rec in history]
    _check(all(math.isfinite(x) for x in losses), f"finite losses {losses}")
    _check(losses[-1] < losses[0], f"loss falls {losses}")
    return losses


def lm_phase(*, layers=2, seq=4096, batch=1, superstep=8, supersteps=3,
             reduced=False):
    """Train through `launch.train.main` with publication on; returns the
    model config and the newest published snapshot for the serve phase."""
    from repro.configs import get_config, reduced as reduce_cfg
    from repro.launch import train

    argv = _train_argv(layers=layers, seq=seq, batch=batch,
                       superstep=superstep, supersteps=supersteps,
                       averaging="exact", reduced=reduced) + ["--publish"]
    _, history, publisher = train.main(argv)
    losses = _losses(history)
    snap = publisher.snapshot()
    _check(snap is not None and publisher.version >= 1,
           f"a version was published (v{publisher.version})")
    cfg = get_config(LM_ARCH)
    cfg = dataclasses.replace(reduce_cfg(cfg) if reduced else cfg,
                              num_layers=layers)
    return cfg, snap, {"layers": layers, "losses": losses,
                       "published_version": publisher.version}


def serve_phase(cfg, params, *, slots=2, requests=4, prompt_len=128,
                new_tokens=16, seed=0):
    """Continuous batching on the published params, checked against the
    static prefill + serve_step greedy path."""
    from repro.serve.engine import ContinuousBatchingEngine, generate

    max_len = prompt_len + new_tokens
    eng = ContinuousBatchingEngine(cfg, params, slots=slots, max_len=max_len,
                                   dtype=jnp.bfloat16)
    rng = np.random.default_rng(seed)
    prompts = rng.integers(0, cfg.vocab_size, (requests, prompt_len),
                           dtype=np.int32)
    rids = [eng.submit(p, new_tokens) for p in prompts]
    eng.drain()
    done = [eng.result(r) for r in rids]
    _check(all(r is not None and len(r.tokens) == new_tokens for r in done),
           "every request completes with all its tokens")
    static = generate(params, cfg, {"tokens": jnp.asarray(prompts[:1])},
                      max_len, new_tokens, dtype=jnp.bfloat16)
    static = [int(t) for t in np.asarray(static)[0]]
    _check(done[0].tokens == static,
           f"continuous {done[0].tokens} == static {static}")
    return {"requests": requests, "decode_steps": eng.decode_steps,
            "tokens_req0": done[0].tokens}


def pca_phase(*, dim=None, n_nodes=16, rounds=4, batch=256, superstep=8,
              supersteps=4, fuse_xi=None, interpret=False, seed=0):
    """D-Krasulina on the streaming engine at HIGHD; `fuse_xi=None` lets
    the engine pick (the Pallas kernel on TPU)."""
    from repro.configs.base import AveragingConfig
    from repro.configs.paper_pca import HIGHD, PCARunConfig
    from repro.core import krasulina, problems
    from repro.core.averaging import make_gossip_mix
    from repro.data.synthetic import make_pca_host_sampler, make_pca_stream
    from repro.kernels import ref
    from repro.kernels.krasulina_update import krasulina_xi_gossip_pallas
    from repro.train.driver import EngineConfig, StreamingDriver

    pca = HIGHD if dim is None else dataclasses.replace(HIGHD, dim=dim)
    with jax.default_matmul_precision("highest"):  # set-up: exact covariance
        stream = make_pca_stream(pca)
    metric = lambda w: problems.pca_excess_risk(w, stream.cov, stream.lambda1)
    averaging = AveragingConfig(mode="gossip", rounds=rounds, topology="ring")
    run_cfg = PCARunConfig(pca=pca, averaging=averaging)
    builder = krasulina.krasulina_superstep_builder(
        averaging, n_nodes, lambda t: 5.0 / t, metric=metric, fuse_xi=fuse_xi)
    w0 = jax.random.normal(jax.random.PRNGKey(seed), (pca.dim,))
    w0 = w0 / jnp.linalg.norm(w0)
    risk0 = float(metric(w0))
    state = krasulina.init_krasulina_state(w0, averaging, n_nodes)
    with StreamingDriver(run_cfg, None, state, make_pca_host_sampler(stream),
                         superstep_builder=builder, n_nodes=n_nodes,
                         batch=batch, seed=seed,
                         engine=EngineConfig(superstep=superstep,
                                             prefetch_depth=2)) as drv:
        state, history = drv.run(supersteps)
    risks = [rec["metrics"]["metric"] for rec in history]
    _check(all(math.isfinite(r) for r in risks), f"finite risks {risks}")
    _check(risks[-1] < risks[0] < risk0,
           f"excess risk falls: {risk0} -> {risks}")

    bn = batch // n_nodes
    z = jnp.asarray(make_pca_host_sampler(stream)(
        np.random.default_rng(seed + 1), batch)["z"]).reshape(n_nodes, bn, -1)
    if jax.default_backend() == "tpu":
        batches = {"z": jax.ShapeDtypeStruct((superstep, n_nodes, bn, pca.dim),
                                             jnp.float32)}
        hlo = jax.jit(builder(batch)).lower(state, batches).compile().as_text()
        _check("tpu_custom_call" in hlo, "the superstep runs the Pallas kernel")
    sched = make_gossip_mix(averaging, n_nodes).sched
    got = krasulina_xi_gossip_pallas(
        state.w, z, tuple(s for s, _ in sched), tuple(w for _, w in sched),
        rounds, interpret=interpret)
    with jax.default_matmul_precision("highest"):
        want = ref.krasulina_xi_gossip_ref(state.w, z, sched, rounds)
    err = float(jnp.max(jnp.abs(got - want)))
    scale = float(jnp.max(jnp.abs(want)))
    _check(err <= 1e-4 * scale + 1e-6,
           f"fused kernel vs ref: max err {err} at scale {scale}")
    return {"risk0": risk0, "risks": risks, "kernel_max_err": err,
            "kernel_scale": scale}


def four_chip_phase(*, layers=1, seq=4096, batch_per_node=1, superstep=8,
                    supersteps=3, rounds=2, devices=4, reduced=False):
    """Decentralized LM training with one node per chip: ring gossip through
    the shard_map rule, against exact averaging on the same steps."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs.base import AveragingConfig
    from repro.core.averaging import make_gossip_mix
    from repro.kernels import ops, ref
    from repro.launch import train
    from repro.launch.mesh import make_host_mesh

    mesh = make_host_mesh(n_devices=devices)
    averaging = AveragingConfig(mode="gossip", rounds=rounds, topology="ring")
    mix = make_gossip_mix(averaging, devices, mesh=mesh)
    _check(mix.impl == "shard", f"gossip impl resolves to shard: {mix.impl}")

    x = jax.random.normal(jax.random.PRNGKey(1), (devices, 1 << 16))
    x = jax.device_put(x, NamedSharding(mesh, P("data", None)))
    got = ops.sharded_gossip_mix(x, mix.sched, rounds, mesh, ("data",), "data")
    want = ref.gossip_mix_ref(np.asarray(x), mix.sched, rounds)
    mix_err = float(jnp.max(jnp.abs(np.asarray(got) - np.asarray(want))))
    _check(mix_err <= 1e-6, f"gossip_mix_shard vs ref: max err {mix_err}")

    common = dict(layers=layers, seq=seq, batch=batch_per_node * devices,
                  superstep=superstep, supersteps=supersteps,
                  devices=devices, reduced=reduced)
    _, gossip, _ = train.main(_train_argv(averaging="gossip", rounds=rounds,
                                          **common))
    g_losses = _losses(gossip)
    cerr = [rec["metrics"]["consensus_err"] for rec in gossip]
    _check(all(0.0 < c < 1.0 for c in cerr),
           f"gossip consensus error in (0, 1): {cerr}")
    _, exact, _ = train.main(_train_argv(averaging="exact", **common))
    e_losses = _losses(exact)
    return {"layers": layers, "impl": mix.impl, "mix_max_err": mix_err,
            "gossip_losses": g_losses, "consensus_err": cerr,
            "exact_losses": e_losses}


# ---------------------------------------------------------------------------
# Entry point
# ---------------------------------------------------------------------------


def tpu_devices():
    """The TPU devices JAX sees; a missing chip is an error, never a CPU run."""
    try:
        return jax.devices("tpu")
    except RuntimeError as e:
        raise SystemExit(f"chip_smoke: no TPU chip visible to JAX "
                         f"(default backend {jax.default_backend()}): {e}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the decentralized four-chip path")
    args = ap.parse_args(argv)
    devices = tpu_devices()
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        raise SystemExit(f"chip_smoke: needs {need} chips, sees {len(devices)}")
    dev = devices[0]
    print(json.dumps({"device_kind": dev.device_kind,
                      "visible": len(devices), "using": need}), flush=True)
    clock = CompileClock()

    def run(name, fn, **kw):
        t0, c0 = time.perf_counter(), clock.seconds
        out = fn(**kw)
        checks = out[-1] if isinstance(out, tuple) else out
        _report(name, t0, clock.seconds - c0, devices[:need], **checks)
        return out

    if args.four_chips:
        run("four_chip_lm", four_chip_phase)
    else:
        cfg, snap, _ = run("lm", lm_phase)
        run("serve", serve_phase, cfg=cfg, params=snap.params)
        del snap
        run("pca", pca_phase)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": need}}))


if __name__ == "__main__":
    main()
