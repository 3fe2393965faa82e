import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"
"""Multi-pod dry-run: prove the distribution config is coherent without
hardware.

For every (architecture x input-shape x mesh) this lowers + compiles the real
train/prefill/serve step against ShapeDtypeStruct stand-ins (no allocation),
prints memory_analysis() (fits per chip?) and cost_analysis() (FLOPs/bytes for
the roofline), parses the optimized HLO for collective bytes, and writes a JSON
artifact consumed by repro.roofline.

NOTE the three lines above: jax locks the device count at first init, so the
XLA_FLAGS export precedes every import, including `from repro...`. The 512
devices are fake CPU ones by design, so the CPU platform is pinned: on a TPU
host the dry-run must not take the chip (or see only the 1-4 real ones).
"""
import argparse
import json
import re
import time
from functools import partial

import jax
import jax.numpy as jnp

from repro.configs import SHAPES, get_config
from repro.configs.base import AveragingConfig, RunConfig
from repro.launch import sharding as shlib
from repro.launch.mesh import data_axes, make_production_mesh, n_data_nodes
from repro.models import registry
from repro.models.common import mesh_rules
from repro.serve import engine
from repro.train import trainer

# default gradient-accumulation factor per arch for train shapes (keeps the
# per-chip activation working set inside v5e HBM; see EXPERIMENTS.md §Dry-run)
TRAIN_MICROBATCHES = {
    "llama4-scout-17b-a16e": 16,
    "chameleon-34b": 16,
    "recurrentgemma-9b": 4,
    "starcoder2-15b": 2,
    "seamless-m4t-medium": 4,
}

# archs whose faithful config is full attention: long_500k runs a sliding-window
# variant (docs/DESIGN.md §long_500k applicability)
WINDOWED_FOR_500K = {
    "granite-8b": 8192,
    "phi4-mini-3.8b": 8192,
    "minicpm3-4b": 8192,
    "chameleon-34b": 8192,
    "seamless-m4t-medium": 8192,
}

_DTYPE_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "f64": 8,
                "s8": 1, "u8": 1, "pred": 1, "s64": 8, "u64": 8, "s16": 2, "u16": 2}

_COLLECTIVE_RE = re.compile(
    r"=\s+(\w+)\[([\d,]*)\][^\n]*?\b"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")


_UPCAST_RE = re.compile(r"\(param_[\w.]+: bf16\[([\d,]+)\]\) -> f32\[")


def cost_analysis_dict(compiled) -> dict:
    """`compiled.cost_analysis()`, empty when the backend reports none."""
    return compiled.cost_analysis() or {}


def parse_cpu_upcasts(hlo: str) -> float:
    """Bytes of hoisted bf16->f32 parameter upcasts. The CPU backend has no
    native bf16 GEMM, so it converts whole weight tensors to f32 before the
    layer loop; TPU MXUs consume bf16 directly, so these buffers don't exist on
    the target hardware. Reported so the peak can be TPU-adjusted."""
    total = 0.0
    for m in _UPCAST_RE.finditer(hlo):
        n = 4
        for d in m.group(1).split(","):
            n *= int(d)
        total += n
    return total


_COMP_RE = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \([^)]*\) -> ", re.M)
_WHILE_RE = re.compile(r"while\([^)]*\), condition=%?([\w.\-]+), body=%?([\w.\-]+)")
_TRIP_RE = re.compile(r"compare\([^)]*\), direction=LT")
_CONST_RE = re.compile(r"constant\((\d+)\)")


def _split_computations(hlo: str):
    """-> {comp_name: body_text} from optimized HLO text."""
    comps = {}
    cur, buf = None, []
    for line in hlo.splitlines():
        m = _COMP_RE.match(line)
        if m and line.rstrip().endswith("{"):
            if cur:
                comps[cur] = "\n".join(buf)
            cur, buf = m.group(1), []
        elif cur is not None:
            if line.startswith("}"):
                comps[cur] = "\n".join(buf)
                cur, buf = None, []
            else:
                buf.append(line)
    return comps


def _trip_count(cond_body: str) -> int:
    """Loop trip count from the while condition (induction var < constant)."""
    if _TRIP_RE.search(cond_body):
        consts = [int(c) for c in _CONST_RE.findall(cond_body)]
        if consts:
            return max(consts)
    return 1


def parse_collectives(hlo: str):
    """Sum result-shape bytes per collective kind from optimized HLO,
    multiplying ops inside while loops by their trip counts (XLA cost analysis
    and HLO text report loop bodies once)."""
    comps = _split_computations(hlo)
    mult = {name: 1 for name in comps}
    changed, guard = True, 0
    while changed and guard < 20:
        changed, guard = False, guard + 1
        for name, body in comps.items():
            for wm in _WHILE_RE.finditer(body):
                cond, wbody = wm.group(1), wm.group(2)
                trips = _trip_count(comps.get(cond, ""))
                want = mult.get(name, 1) * trips
                for target in (wbody, cond):
                    if target in mult and mult[target] < want:
                        mult[target] = want
                        changed = True
    # propagate into fusion/call computations
    call_re = re.compile(r"(?:calls=|to_apply=)%?([\w.\-]+)")
    for _ in range(3):
        for name, body in comps.items():
            for cm in call_re.finditer(body):
                callee = cm.group(1)
                if callee in mult and mult[callee] < mult.get(name, 1):
                    mult[callee] = mult[name]

    out = {}
    hbm = 0.0
    shape_re = re.compile(r"=\s+(\w+)\[([\d,]*)\]")
    for name, body in comps.items():
        scale = mult.get(name, 1)
        for m in _COLLECTIVE_RE.finditer(body):
            dtype, dims, kind = m.group(1), m.group(2), m.group(3)
            nbytes = _DTYPE_BYTES.get(dtype, 4)
            for d in dims.split(","):
                if d:
                    nbytes *= int(d)
            out[kind] = out.get(kind, 0) + nbytes * scale
            out[kind + ".count"] = out.get(kind + ".count", 0) + scale
        # HBM traffic estimate: result bytes of every materializing op, x2 for
        # the read side, trip-scaled (fusion internals excluded by only
        # counting each op's result once)
        for m in shape_re.finditer(body):
            dtype = m.group(1)
            if dtype not in _DTYPE_BYTES:
                continue
            nbytes = _DTYPE_BYTES[dtype]
            for d in m.group(2).split(","):
                if d:
                    nbytes *= int(d)
            hbm += 2.0 * nbytes * scale
    out["hbm_bytes_est"] = hbm
    return out


def window_override_for(arch: str, shape_name: str) -> int:
    if shape_name == "long_500k":
        return WINDOWED_FOR_500K.get(arch, 0)
    return 0


def build_lowerable(arch: str, shape_name: str, mesh, averaging: str,
                    rounds: int, topology: str = "ring",
                    microbatches: int = 0, master_weights: bool = True,
                    ring_cache: bool = False, remat: bool = True):
    """Returns (fn, abstract_args) ready for jit(...).lower(*args)."""
    import dataclasses as _dc
    cfg = get_config(arch)
    if ring_cache:
        cfg = _dc.replace(cfg, ring_buffer_cache=True)
    shape = SHAPES[shape_name]
    wo = window_override_for(arch, shape_name)
    key = jax.random.PRNGKey(0)
    decentralized = averaging != "exact"
    mb = microbatches or TRAIN_MICROBATCHES.get(arch, 1)
    run = RunConfig(model=cfg, shape=shape,
                    averaging=AveragingConfig(mode=averaging, rounds=rounds,
                                              topology=topology),
                    optimizer="adam", param_dtype="bfloat16", microbatches=mb,
                    master_weights=master_weights, remat=remat)

    if shape.mode == "train":
        state_shapes = jax.eval_shape(lambda k: trainer.init_state(run, k), key)
        n_nodes = n_data_nodes(mesh)
        if decentralized:
            state_shapes = jax.eval_shape(
                partial(trainer.replicate_for_nodes, n_nodes=n_nodes), state_shapes)
        step, spec_fn = trainer.build_train_step(run, mesh)
        state_specs = spec_fn(state_shapes)
        state_abs = shlib.abstract_with_sharding(state_shapes, state_specs, mesh)
        batch_shapes = registry.input_specs(cfg, shape)
        if decentralized:
            batch_shapes = jax.eval_shape(
                partial(trainer.make_node_batch, n_nodes=n_nodes), batch_shapes)
        bspecs = shlib.batch_specs(batch_shapes, mesh, shape, node_axis=decentralized)
        batch_abs = shlib.abstract_with_sharding(batch_shapes, bspecs, mesh)
        out_shardings = (shlib.named(state_specs, mesh), None)
        fn = jax.jit(step, out_shardings=out_shardings, donate_argnums=0)
        return fn, (state_abs, batch_abs), run

    # inference paths share param setup
    params_shapes = jax.eval_shape(
        lambda k: registry.init_params(k, cfg, jnp.bfloat16, window_override=wo), key)
    # serving keeps weights model-sharded (latency); very large models (llama4)
    # additionally shard over data or they cannot fit next to the KV cache
    per_dev_gib = cfg.param_count() * 2 / mesh.shape["model"] / 2**30
    if per_dev_gib > 6.0:
        pspecs = shlib.zero1_specs(params_shapes, mesh)
    else:
        pspecs = shlib.param_specs(params_shapes, mesh)
    params_abs = shlib.abstract_with_sharding(params_shapes, pspecs, mesh)

    if shape.mode == "prefill":
        serve_shapes = jax.eval_shape(
            lambda: engine.init_serve(cfg, shape.global_batch, shape.seq_len,
                                      jnp.bfloat16, window_override=wo))
        sspec = engine.ServeState(
            shlib.cache_specs(serve_shapes.cache, mesh, shape),
            shlib.batch_specs(serve_shapes.last_tokens, mesh, shape),
            jax.sharding.PartitionSpec())
        serve_abs = shlib.abstract_with_sharding(serve_shapes, sspec, mesh)
        batch_shapes = registry.input_specs(cfg, shape)
        bspecs = shlib.batch_specs(batch_shapes, mesh, shape)
        batch_abs = shlib.abstract_with_sharding(batch_shapes, bspecs, mesh)

        def prefill_step(params, batch, st):
            return engine.prefill(params, cfg, batch, st, window_override=wo)

        fn = jax.jit(prefill_step, donate_argnums=2,
                     out_shardings=engine.ServeState(*jax.tree.map(
                         lambda s: shlib.named(s, mesh), tuple(sspec),
                         is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))))
        return fn, (params_abs, batch_abs, serve_abs), None

    # decode: ONE token against a seq_len cache
    serve_shapes = jax.eval_shape(
        lambda: engine.init_serve(cfg, shape.global_batch, shape.seq_len,
                                  jnp.bfloat16, window_override=wo))
    sspec = engine.ServeState(
        shlib.cache_specs(serve_shapes.cache, mesh, shape),
        shlib.batch_specs(serve_shapes.last_tokens, mesh, shape),
        jax.sharding.PartitionSpec())
    serve_abs = shlib.abstract_with_sharding(serve_shapes, sspec, mesh)

    def step(params, st):
        return engine.serve_step(params, cfg, st, window_override=wo)

    fn = jax.jit(step, donate_argnums=1)
    return fn, (params_abs, serve_abs), None


def run_dryrun(arch: str, shape_name: str, *, multi_pod: bool = False,
               averaging: str = "exact", rounds: int = 1, topology: str = "ring",
               microbatches: int = 0, ring_cache: bool = False,
               remat: bool = True, print_analysis: bool = True) -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    shape = SHAPES[shape_name]
    cfg = get_config(arch)
    rules = shlib.activation_rules(mesh, shape,
                                   node_axis=(averaging != "exact"))
    if shape.mode in ("prefill", "decode"):
        rules.update(shlib.kv_rules(mesh, shape, cfg.num_kv_heads))
    from repro.models.transformer import build_plan
    if cfg.is_encdec:
        layer_trips = cfg.num_layers  # encoder and decoder scans both trip this
    else:
        period, n_rep, tail = build_plan(cfg, window_override_for(arch, shape_name))
        layer_trips = max(n_rep, 1)
    mb_eff = (microbatches or TRAIN_MICROBATCHES.get(arch, 1)) if shape.mode == "train" else 1
    rec = {"arch": arch, "shape": shape_name,
           "trips": {"microbatch": mb_eff, "layer_scan": layer_trips,
                     "scale": mb_eff * layer_trips},
           "microbatches": TRAIN_MICROBATCHES.get(arch, 1) if shape.mode == "train" else 0,
           "mesh": "2x16x16" if multi_pod else "16x16",
           "averaging": averaging, "rounds": rounds,
           "mode": shape.mode,
           "params": cfg.param_count(), "active_params": cfg.active_param_count(),
           "window_override": window_override_for(arch, shape_name),
           "ring_cache": ring_cache}
    def compile_once(master: bool):
        with mesh_rules(mesh, rules):
            fn, args, _ = build_lowerable(arch, shape_name, mesh, averaging,
                                          rounds, topology,
                                          microbatches=microbatches,
                                          master_weights=master,
                                          ring_cache=ring_cache, remat=remat)
            t0 = time.time()
            lowered = fn.lower(*args)
            rec["lower_s"] = round(time.time() - t0, 2)
            t0 = time.time()
            compiled = lowered.compile()
            rec["compile_s"] = round(time.time() - t0, 2)
        return compiled

    rec["master_weights"] = shape.mode == "train"
    compiled = compile_once(rec["master_weights"])
    if shape.mode == "train":
        ma0 = compiled.memory_analysis()
        peak = (ma0.argument_size_in_bytes + ma0.output_size_in_bytes
                + ma0.temp_size_in_bytes - ma0.alias_size_in_bytes) / 2**30
        peak -= parse_cpu_upcasts(compiled.as_text()) / 2**30
        if peak > 15.5:
            # fp32 masters don't fit next to this model: fall back to bf16
            # weight updates and record the tradeoff (EXPERIMENTS.md §Dry-run)
            rec["master_weights"] = False
            compiled = compile_once(False)

    ma = compiled.memory_analysis()
    rec["memory"] = {
        "argument_gib": ma.argument_size_in_bytes / 2**30,
        "output_gib": ma.output_size_in_bytes / 2**30,
        "temp_gib": ma.temp_size_in_bytes / 2**30,
        "alias_gib": ma.alias_size_in_bytes / 2**30,
        # live per-chip working set: args + outputs - aliased + temps
        "peak_gib": (ma.argument_size_in_bytes + ma.output_size_in_bytes
                     + ma.temp_size_in_bytes - ma.alias_size_in_bytes) / 2**30,
    }
    ca = cost_analysis_dict(compiled)
    rec["cost"] = {"flops": ca.get("flops", 0.0),
                   "bytes": ca.get("bytes accessed", 0.0)}
    hlo_text = compiled.as_text()
    rec["collectives"] = parse_collectives(hlo_text)
    upcast_gib = parse_cpu_upcasts(hlo_text) / 2**30
    rec["memory"]["cpu_upcast_gib"] = upcast_gib
    rec["memory"]["peak_tpu_adjusted_gib"] = rec["memory"]["peak_gib"] - upcast_gib
    if print_analysis:
        print(json.dumps(rec, indent=1))
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True, choices=list(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--averaging", default="exact",
                    choices=["exact", "gossip", "hierarchical"])
    ap.add_argument("--rounds", type=int, default=1)
    ap.add_argument("--topology", default="ring")
    ap.add_argument("--microbatches", type=int, default=0)
    ap.add_argument("--ring-cache", action="store_true")
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    rec = run_dryrun(args.arch, args.shape, multi_pod=args.multi_pod,
                     averaging=args.averaging, rounds=args.rounds,
                     topology=args.topology, microbatches=args.microbatches,
                     ring_cache=args.ring_cache, remat=not args.no_remat)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(rec, f, indent=1)


if __name__ == "__main__":
    main()
