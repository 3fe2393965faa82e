"""Gradient-averaging operators for the framework-scale trainer — the paper's
technique as a first-class feature.

The trainer represents the paper's N compute nodes as a leading *node axis* on
the gradient pytree (sharded over the mesh's data axes), so averaging modes are
pure array programs whose collectives are visible in the lowered HLO:

* exact        -- mean over the node axis == AllReduce (DMB, Section IV)
* gossip       -- R rounds of circulant consensus (Section V, eq. 17), executed
                  through `core.mixing.CirculantMixOp`: with quantization off
                  the R-round operator is precomputed once and applied in a
                  single pass (weighted `jnp.roll`s / one circulant matmul /
                  the fused Pallas kernel on TPU)
* hierarchical -- exact within pod, gossip across pods in reduce-scatter form
                  (each intra-pod lane gossips one chunk of the pod mean over
                  DCN, then the pod all-gathers; TPU adaptation)

With `AveragingConfig.packed` (the default) the gossip and hierarchical modes
flatten the gradient pytree into one contiguous [N, D] buffer per dtype
(`core.packing`) so the mixing operator — and the consensus-error diagnostic —
runs ONCE per step instead of once per leaf; a transformer tree with hundreds
of leaves stops paying hundreds of independent roll/compress chains.

Optional message quantization (Section VI) compresses each round's messages;
quantized configs keep the per-round loop (the compressor is nonlinear, so the
operator must not be collapsed). `AveragingConfig.quant_stats` picks the
statistic granularity: "global" pins today's exact per-leaf oracle semantics
(bit-identical, never packed), "segment" reproduces per-leaf scales on the
packed buffer in one pass, "tile" takes the fused quantized kernel.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import AveragingConfig
from repro.core import packing
from repro.core.mixing import (CirculantMixOp, ScheduledMixOp,
                               circulant_mix_op, schedule)
from repro.core.quantize import tile_compress

Tree = Any
# the consensus engine: a static CirculantMixOp or a time-varying
# ScheduledMixOp (scenario harness) — both are called uniformly through
# `_mix_call`, which routes the traced round counter `t` to scheduled ops
MixOp = Any


def make_gossip_mix(cfg: AveragingConfig, n_nodes: int, *,
                    impl: str = "auto", mesh: Any = None) -> CirculantMixOp:
    """Build the consensus engine for a config — once, outside the train step.
    For `mode="hierarchical"` pass the pod count as `n_nodes`.

    `impl="auto"` resolves per layout (`core.mixing.resolve_auto_impl`):
    "roll" whenever the node axis is — or may be — sharded over mesh data
    axes (rolls are the form GSPMD partitions into collective-permute
    chains), the dense-matmul fast path on unsharded CPU/GPU layouts, and
    the fused Pallas kernel on single-device TPU. Pass the mesh the op will
    run under so sharded layouts are detected; without it, multi-device
    hosts conservatively get "roll"."""
    sched = schedule(cfg.topology, n_nodes, cfg.self_weight)
    quantization = cfg.quantization
    if cfg.error_feedback != "off":
        # error feedback compresses ONCE per step outside the operator
        # (`ef_average_and_error`); the consensus rounds themselves are exact
        # and linear, so the composed/fused/shard implementations all apply
        # to compressed gossip — the per-round nonlinear chain is bypassed
        quantization = "none"
    return circulant_mix_op(sched, n_nodes, cfg.rounds,
                            quantization=quantization, impl=impl,
                            mesh=mesh, stats=cfg.quant_stats,
                            block_d=cfg.quant_block_d)


def resolve_packed(cfg: AveragingConfig, mesh: Any = None) -> bool:
    """Resolve the tri-state `AveragingConfig.packed` against the layout the
    step runs under. "auto" (the default) packs everywhere EXCEPT layouts
    whose param leaves are actually sharded over a model axis: the pack
    relayouts every leaf into one [N, D] buffer, which is numerically
    parity-tested under a model split (tests/test_shard_gossip.py) but whose
    all-gather cost on a real mesh is un-profiled (ROADMAP real-TPU debt) —
    model-parallel layouts opt in explicitly with `packed=True`."""
    if cfg.packed == "auto":
        if mesh is None:
            return True
        return int(mesh.shape.get("model", 1)) == 1
    return bool(cfg.packed)


def _packable(mix: MixOp) -> bool:
    """Quantized global-stats configs pin per-leaf statistics (the bit-identity
    oracle), so they keep the per-leaf dispatch; everything else packs."""
    return not (mix.quantization != "none" and mix.stats == "global")


def _mix_call(mix: MixOp, x: jax.Array, *, key: Any = None, t: Any = None,
              **kw) -> jax.Array:
    """Uniform call: scheduled (time-varying) ops take the traced round
    counter `t` to pick the active phase; static ops take the compressor key."""
    if isinstance(mix, ScheduledMixOp):
        return mix(x, t=t, **kw)
    return mix(x, key=key, **kw)


def _apply_mix(mix: MixOp, spec: packing.PackSpec, g: int,
               buf: jax.Array, key: Any = None, t: Any = None) -> jax.Array:
    if mix.quantization != "none" and mix.stats == "segment":
        widths = tuple(spec.leaf_width(i) for i in spec.groups[g])
        return _mix_call(mix, buf, key=key, t=t, seg_widths=widths)
    return _mix_call(mix, buf, key=key, t=t)


def gossip_average(tree: Tree, n_nodes: int, cfg: AveragingConfig,
                   mix: Optional[MixOp] = None, *,
                   key: Any = None, t: Any = None) -> Tree:
    """R rounds of doubly-stochastic consensus over the leading node axis —
    one packed pass per dtype group by default, per-leaf when `cfg.packed`
    is off or the quantized global-stats oracle is selected. `key` (optional)
    is the per-step base key for stochastic compressors — see
    `CirculantMixOp.__call__`. `t` (optional) is the traced round counter a
    time-varying `ScheduledMixOp` uses to select its active phase."""
    if mix is None:
        mix = make_gossip_mix(cfg, n_nodes)
    if not (cfg.packed and _packable(mix)):
        return jax.tree.map(lambda g: _mix_call(mix, g, key=key, t=t), tree)
    bufs, spec = packing.pack_tree(tree)
    outs = tuple(_apply_mix(mix, spec, g, b, key, t)
                 for g, b in enumerate(bufs))
    return packing.unpack_tree(outs, spec)


def exact_average(tree: Tree) -> Tree:
    return jax.tree.map(lambda g: jnp.broadcast_to(
        jnp.mean(g, axis=0, keepdims=True), g.shape), tree)


def _hmix_buffer(g: jax.Array, pods: int, per_pod: int,
                 mix: MixOp, key: Any = None, t: Any = None) -> jax.Array:
    """Reduce-scatter hierarchical consensus on one [N, ...] buffer/leaf."""
    shp = g.shape
    flat = g.reshape(pods, per_pod, -1)  # [P, M, F]
    pod_mean = jnp.mean(flat, axis=1)  # reduce ...
    f = pod_mean.shape[-1]
    chunk = -(-f // per_pod)
    pad = chunk * per_pod - f
    if pad:
        pod_mean = jnp.pad(pod_mean, ((0, 0), (0, pad)))
    scattered = pod_mean.reshape(pods, per_pod, chunk)  # ... scatter
    # cross-pod gossip, one chunk per lane; pad columns sit at the tail of
    # the flattened layout and are masked out of compressor statistics
    mixed = _mix_call(mix, scattered, valid_d=f if pad else None, key=key, t=t)
    gathered = mixed.reshape(pods, 1, chunk * per_pod)[..., :f]  # all-gather
    g = jnp.broadcast_to(gathered, (pods, per_pod, f))
    return g.reshape(shp)


def hierarchical_average(tree: Tree, pods: int, per_pod: int,
                         cfg: AveragingConfig,
                         mix: Optional[MixOp] = None, *,
                         key: Any = None, t: Any = None) -> Tree:
    """Exact averaging within each pod (fast ICI), gossip across pods (slow
    DCN) — in reduce-scatter form.

    Instead of materializing the full pod mean on every node and gossiping
    whole vectors from one lane per pod (broadcast-then-gossip), the pod mean
    is reduce-SCATTERED: lane j of each pod ends up owning chunk j of the pod
    mean, the cross-pod gossip mixes only each lane's own chunk (so each DCN
    link carries 1/per_pod of the vector, in parallel across lanes), and an
    intra-pod all-gather reassembles the mixed mean — halving-or-better the
    serialized cross-pod traffic relative to the broadcast form. The result is
    numerically the same consensus (the mix is applied chunkwise over the pod
    axis). Feature dims are zero-padded up to a multiple of per_pod; the pad
    columns are masked out of quantized compressor statistics, so the padded
    reduce-scatter form matches the unpadded broadcast form (Section VI wire
    format) instead of perturbing it. Quantized segment statistics do not
    survive the chunk-scatter relayout; they degrade to global (masked)
    statistics over the scattered pod means here.
    """
    if mix is None:
        mix = make_gossip_mix(cfg, pods)

    def hmix(g):
        return _hmix_buffer(g, pods, per_pod, mix, key, t)

    if not (cfg.packed and _packable(mix)):
        return jax.tree.map(hmix, tree)
    bufs, spec = packing.pack_tree(tree)
    return packing.unpack_tree(tuple(hmix(b) for b in bufs), spec)


def average_gradients(tree: Tree, cfg: AveragingConfig, *, n_nodes: int,
                      pods: int = 1,
                      mix: Optional[MixOp] = None,
                      key: Any = None, t: Any = None) -> Tree:
    """Dispatch on the paper's averaging mode. `tree` leaves: [n_nodes, ...].

    `mix` is the prebuilt consensus engine (gossip: over `n_nodes`;
    hierarchical: over `pods`); built from `cfg` on the fly when omitted.
    `key` is the optional per-step base key for stochastic compressors; `t`
    the optional traced round counter for time-varying schedules."""
    if cfg.mode == "exact":
        return exact_average(tree)
    if cfg.mode == "gossip":
        return gossip_average(tree, n_nodes, cfg, mix, key=key, t=t)
    if cfg.mode == "hierarchical":
        assert n_nodes % pods == 0
        return hierarchical_average(tree, pods, n_nodes // pods, cfg, mix,
                                    key=key, t=t)
    raise ValueError(f"unknown averaging mode {cfg.mode!r}")


def average_and_error(tree: Tree, cfg: AveragingConfig, *, n_nodes: int,
                      pods: int = 1, mix: Optional[MixOp] = None,
                      key: Any = None, t: Any = None,
                      diag: Any = True) -> Tuple[Tree, jax.Array]:
    """Averaging plus the epsilon-consensus diagnostic with ONE pack: the
    mixed packed buffers feed both the unpack and the fused error reduction,
    so the trainer stops paying a second per-leaf (or re-pack) sweep.

    `diag` says whether to compute the diagnostic (`_diagnostic`): a plain
    step leaves it True; the K-round superstep scan passes a traced flag
    true in its last round only, so its stacked `consensus_err` holds the
    value in the last round and NaN in the others."""
    if cfg.mode == "exact":
        mixed = exact_average(tree)
        return mixed, _diagnostic(consensus_error, mixed, diag)
    if cfg.mode not in ("gossip", "hierarchical"):
        raise ValueError(f"unknown averaging mode {cfg.mode!r}")
    if mix is None:
        mix = make_gossip_mix(cfg, pods if cfg.mode == "hierarchical"
                              else n_nodes)
    if not (cfg.packed and _packable(mix)):
        with jax.named_scope("gossip.mix"):
            mixed = average_gradients(tree, cfg, n_nodes=n_nodes, pods=pods,
                                      mix=mix, key=key, t=t)
        return mixed, _diagnostic(consensus_error, mixed, diag)
    # named scopes mark the consensus's parts in the compiled program's
    # metadata (gossip.pack / gossip.mix / gossip.unpack, consensus_error),
    # so a device trace can be split by part
    with jax.named_scope("gossip.pack"):
        bufs, spec = packing.pack_tree(tree)
    with jax.named_scope("gossip.mix"):
        if cfg.mode == "gossip":
            outs = tuple(_apply_mix(mix, spec, g, b, key, t)
                         for g, b in enumerate(bufs))
        else:
            assert n_nodes % pods == 0
            outs = tuple(_hmix_buffer(b, pods, n_nodes // pods, mix, key, t)
                         for b in bufs)
    err = _diagnostic(partial(_packed_consensus_error, spec=spec), outs, diag)
    with jax.named_scope("gossip.unpack"):
        return packing.unpack_tree(outs, spec), err


def ef_average_and_error(tree: Tree, ef: Tree, cfg: AveragingConfig, *,
                         n_nodes: int, mix: Optional[MixOp] = None,
                         key: Any = None, t: Any = None, diag: Any = True
                         ) -> Tuple[Tree, Tree, jax.Array, jax.Array, jax.Array]:
    """Error-feedback compressed gossip: ONE pack, ONE compression, exact
    linear consensus rounds (docs/DESIGN.md §Decentralized LM track).

    Per step, on the packed [N, D] buffers: v = g + e (residual-corrected
    gradient), q = C(v) with sender-local per-node tile statistics
    (`quantize.tile_compress(per_node=True)` — the granularity the shard_map
    wire uses), mixed = the R-round LINEAR consensus of q, e' = v - q. The
    compressor runs once OUTSIDE the mixing operator, so the rounds keep the
    composed-roll / matmul / shard_map fast paths that per-round quantized
    chains forfeit, and the compression error is carried in the optimizer
    state (`OptState.ef_residual`) instead of accumulating as iterate bias
    under momentum.

    With `cfg.quantization == "none"` the wire is exact: q = v, e' stays
    zero, and the result equals plain packed linear gossip of g + e.

    Returns (mixed, new_ef, consensus_err, ef_norm, ef_rel): `ef_norm` is
    the global L2 norm of the new residual, `ef_rel` its ratio to ||v||.
    `consensus_err` follows `diag` as in `average_and_error`: in a superstep
    scan it holds the value in the last round only, and NaN in the others.
    """
    if mix is None:
        mix = make_gossip_mix(cfg, n_nodes)
    if getattr(mix, "quantization", "none") != "none":
        raise ValueError(
            "error feedback needs a LINEAR consensus operator — build it via "
            "make_gossip_mix, which drops the per-round compressor when "
            "cfg.error_feedback is on")
    with jax.named_scope("gossip.pack"):
        bufs, spec = packing.pack_tree(tree)
        ebufs, espec = packing.pack_tree(ef)
    outs, res = [], []
    v2 = jnp.zeros((), jnp.float32)
    e2 = jnp.zeros((), jnp.float32)
    with jax.named_scope("gossip.mix"):
        for g, (b, e) in enumerate(zip(bufs, ebufs)):
            v = b.astype(jnp.float32) + e.astype(jnp.float32)
            if cfg.quantization == "none" or b.shape[-1] == 0:
                q = v
            else:
                k = jax.random.fold_in(key, g) if key is not None else None
                q = tile_compress(v, cfg.quantization, cfg.quant_block_d,
                                  key=k, per_node=True)
            outs.append(_mix_call(mix, q, key=None, t=t).astype(b.dtype))
            r = v - q
            res.append(r.astype(e.dtype))
            v2 = v2 + jnp.sum(v * v)
            e2 = e2 + jnp.sum(r.astype(jnp.float32) ** 2)
        ef_norm = jnp.sqrt(e2)
        ef_rel = ef_norm / (jnp.sqrt(v2) + 1e-30)
    err = _diagnostic(partial(_packed_consensus_error, spec=spec),
                      tuple(outs), diag)
    with jax.named_scope("gossip.unpack"):
        return (packing.unpack_tree(tuple(outs), spec),
                packing.unpack_tree(tuple(res), espec), err, ef_norm, ef_rel)


def _diagnostic(err_fn: Callable[[Any], jax.Array], operand: Any,
                diag: Any) -> jax.Array:
    """`err_fn(operand)`, the consensus diagnostic, where `diag` asks for it.

    `diag` True, a Python constant (a plain step), computes it with no
    `cond`. A traced flag (the superstep scan's round position) runs it
    under `lax.cond`, built inside the `consensus_error` scope so the
    branch's ops keep that scope in the compiled program; rounds that skip
    it read NaN, not 0, which would read as perfect consensus."""
    if diag is True:
        return err_fn(operand)
    with jax.named_scope("consensus_error"):
        return jax.lax.cond(diag, err_fn,
                            lambda _: jnp.full((), jnp.nan, jnp.float32),
                            operand)


def _packed_consensus_error(bufs: Tuple[jax.Array, ...],
                            spec: packing.PackSpec) -> jax.Array:
    """max_leaf max_n ||v_n - v_bar|| / ||v_bar|| on the packed buffers: the
    squared deviations are computed in one pass over [N, D] and summed per
    leaf segment by `packing.segment_sums` (static contiguous slices — exact,
    scatter-free, sharding-friendly), so a hundred-leaf tree stops paying a
    hundred independent norm chains."""
    with jax.named_scope("consensus_error"):
        errs = []
        for g, buf in enumerate(bufs):
            if buf.shape[-1] == 0:
                continue
            widths = [spec.leaf_width(i) for i in spec.groups[g]]
            b = buf.astype(jnp.float32)
            bar = jnp.mean(b, axis=0, keepdims=True)
            d2 = packing.segment_sums((b - bar) ** 2, widths)  # [N, S]
            num = jnp.max(jnp.sqrt(d2), axis=0)  # [S]
            den = jnp.sqrt(packing.segment_sums(bar[0] ** 2, widths)) + 1e-30
            errs.append(jnp.max(num / den))
        return jnp.max(jnp.stack(errs)) if errs else jnp.zeros(())


def consensus_error(tree: Tree) -> jax.Array:
    """max_n ||v_n - v_bar|| / ||v_bar|| across the pytree — the paper's
    epsilon-accuracy diagnostic for inexact averaging. Computed on the packed
    flat buffer (single fused reduction; `consensus_error_per_leaf` is the
    per-leaf oracle)."""
    with jax.named_scope("consensus_error"):
        bufs, spec = packing.pack_tree(tree)
        return _packed_consensus_error(bufs, spec)


def consensus_error_per_leaf(tree: Tree) -> jax.Array:
    """Per-leaf oracle form of `consensus_error` (one reduction chain per
    leaf) — kept for verification of the packed reduction."""
    def err(g):
        g = g.astype(jnp.float32)
        bar = jnp.mean(g, axis=0, keepdims=True)
        num = jnp.max(jnp.sqrt(jnp.sum((g - bar) ** 2, axis=tuple(range(1, g.ndim)))))
        den = jnp.sqrt(jnp.sum(bar**2)) + 1e-30
        return num / den
    errs = [err(g) for g in jax.tree.leaves(tree)]
    return jnp.max(jnp.stack(errs)) if errs else jnp.zeros(())
