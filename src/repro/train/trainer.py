"""Framework-scale streaming trainer: builds the sharded train_step for a
RunConfig, with the paper's averaging mode as a first-class switch.

Two representations:

* **exact** (paper-faithful DMB, Alg. 1): standard data-parallel pjit. The mean
  loss over the global batch makes XLA emit the AllReduce of gradients — exactly
  the paper's exact-averaging step 7 (B = global batch, N = data-parallel size,
  local mini-batch B/N per node).
* **gossip / hierarchical** (D-SGD, Algs. 3-4 / TPU adaptation): decentralized
  parameters. Every leaf carries a leading node axis sharded over the data mesh
  axes; per-node gradients are computed with vmap and mixed by
  `core.averaging.average_gradients` (R rounds of collective-permute consensus);
  each node applies its own optimizer update. Node disagreement is observable
  via `core.averaging.consensus_error`.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import RunConfig
from repro.core.averaging import (average_and_error, ef_average_and_error,
                                  make_gossip_mix, resolve_packed)
from repro.core.mixing import ScheduledMixOp
from repro.core.quantize import STOCHASTIC
from repro.launch import sharding as shlib
from repro.launch.mesh import data_axes, n_data_nodes
from repro.models import registry
from repro.models.common import mesh_rules
from repro.optim import init_optimizer, make_optimizer

Tree = Any


class TrainState(NamedTuple):
    params: Tree
    opt: Tree


def _dtype(run: RunConfig):
    return jnp.dtype(run.param_dtype)


def init_state(run: RunConfig, key) -> TrainState:
    params = registry.init_params(key, run.model, _dtype(run))
    use_master = run.master_weights and _dtype(run) != jnp.float32
    use_ef = (run.averaging.error_feedback != "off"
              and run.averaging.mode == "gossip")
    return TrainState(params, init_optimizer(run.optimizer, params,
                                             master_weights=use_master,
                                             error_feedback=use_ef))


def replicate_for_nodes(state: TrainState, n_nodes: int) -> TrainState:
    """Attach the decentralized node axis (identical initial copies)."""
    return jax.tree.map(lambda p: jnp.broadcast_to(p[None], (n_nodes, *p.shape)),
                        state)


def init_placed_state(run: RunConfig, mesh, key) -> TrainState:
    """`init_state`, plus `replicate_for_nodes` over the mesh's data nodes in
    decentralized mode, built directly in the trainer's state layout on
    `mesh`: each device materializes only its own shards, so N node replicas
    never pass through one device's memory. Call under `mesh_rules`."""
    decentralized = run.averaging.mode != "exact"
    n_nodes = n_data_nodes(mesh)

    def build(k):
        state = init_state(run, k)
        return replicate_for_nodes(state, n_nodes) if decentralized else state

    shapes = jax.eval_shape(build, key)
    specs = _state_specs(shapes, run=run, mesh=mesh,
                         node_axes=data_axes(mesh) if decentralized else None)
    return jax.jit(build, out_shardings=shlib.named(specs, mesh))(key)


def publish_extract(n_nodes: Optional[int] = None) -> Callable:
    """Extract fn for `serve.publisher.SnapshotPublisher`: map the live
    TrainState to the params a serving replica should load.

    Exact-averaging runs (`n_nodes=None`) publish `state.params` as-is. A
    decentralized run passes `n_nodes` and a [N] float membership mask as the
    publisher's `aux`: leaves carrying the leading node axis are reduced to
    the *consensus iterate* — the mask-weighted mean over active nodes (the
    quantity the paper's eq. 17 averaging drives every node toward), so
    dropped nodes' stale rows never pollute the served weights. Runs inside
    the publisher's jitted copy, billed to the publish governor."""

    def extract(state, mask=None):
        params = state.params if hasattr(state, "params") else state
        if n_nodes is None or mask is None:
            return params
        w = mask / jnp.sum(mask)

        def consensus(p):
            if getattr(p, "ndim", 0) and p.shape[0] == n_nodes:
                return jnp.tensordot(w, p, axes=1).astype(p.dtype)
            return p

        return jax.tree.map(consensus, params)

    return extract


def build_train_step(run: RunConfig, mesh, *,
                     n_nodes: Optional[int] = None,
                     mix: Optional[Any] = None) -> Tuple[Callable, Callable]:
    """Returns (train_step, state_spec_fn).

    train_step(state, batch) -> (state, metrics); call under `mesh_rules`.
    The decentralized step takes `diag=True`, whether to compute the
    consensus diagnostic (`core.averaging.average_and_error`);
    `build_superstep` passes a traced flag true in the scan's last round.

    `n_nodes` overrides the mesh-derived decentralized node count: passing
    N > n_data_nodes(mesh) emulates the paper's N-node network on fewer
    devices (the vmap'd node axis is then partly or fully local), which is how
    the CPU container exercises gossip semantics and the pipeline benchmark
    drives decentralized supersteps on one device.

    `mix` overrides the consensus engine built from `run.averaging` — the
    scenario harness (core/scenarios.py) injects a time-varying
    `core.mixing.ScheduledMixOp` here; the optimizer's step counter is its
    phase clock. Scheduled operators are linear-only, so quantized configs
    reject the override.
    """
    cfg = run.model
    # pin the tri-state packed default against THIS mesh (packed="auto"
    # gates off on model-parallel layouts — core.averaging.resolve_packed)
    run = dataclasses.replace(run, averaging=dataclasses.replace(
        run.averaging, packed=resolve_packed(run.averaging, mesh)))
    update = make_optimizer(run.optimizer, run.learning_rate,
                            weight_decay=run.weight_decay)
    n_nodes = n_nodes or n_data_nodes(mesh)
    pods = mesh.shape.get("pod", 1)
    decentralized = run.averaging.mode != "exact"
    ef_on = run.averaging.error_feedback != "off"
    if ef_on and run.averaging.mode != "gossip":
        raise ValueError("error_feedback requires averaging mode 'gossip' "
                         f"(got {run.averaging.mode!r})")

    # named scopes (`fwd`, `optimizer`; the consensus's own in
    # core.averaging) mark the step's parts in the compiled program's
    # metadata, so a device trace can be split by part; autodiff names the
    # backward pass `transpose(jvp(fwd))`
    def loss(params, batch):
        with jax.named_scope("fwd"):
            return registry.loss_fn(params, cfg, batch, remat=run.remat)

    if not decentralized:
        def grad_of(params, batch):
            return jax.value_and_grad(loss, has_aux=True)(params, batch)

        # the fp32 grad accumulator must be ZeRO-sharded explicitly: left to
        # propagation, XLA keeps the scan carry model-sharded only (8x memory)
        state_shapes = jax.eval_shape(lambda k: init_state(run, k),
                                      jax.random.PRNGKey(0))
        gspec = shlib.zero1_specs(state_shapes.params, mesh)

        def shard_like_zero1(tree):
            return jax.tree.map(
                lambda x, s: jax.lax.with_sharding_constraint(
                    x, jax.NamedSharding(mesh, s)), tree, gspec)

        def train_step(state: TrainState, batch):
            mb = run.microbatches
            if mb > 1:
                # gradient accumulation: process the local mini-batch B/N in
                # `mb` sequential slices (paper Section II-C, compute-limited)
                mbatch = jax.tree.map(
                    lambda a: a.reshape(mb, a.shape[0] // mb, *a.shape[1:]), batch)

                def acc_fn(accu, b):
                    (l, metrics), grads = grad_of(state.params, b)
                    # reduce to ZeRO slices BEFORE the f32 cast: otherwise a
                    # full f32 copy of the gradient tree goes live per microbatch
                    grads = shard_like_zero1(grads)
                    acc_g, acc_l, acc_m = accu
                    acc_g = jax.tree.map(
                        lambda x, g: x + g.astype(jnp.float32) / mb, acc_g, grads)
                    acc_g = shard_like_zero1(acc_g)
                    return (acc_g, acc_l + l / mb,
                            jax.tree.map(lambda x, y: x + y / mb, acc_m, metrics)), None

                zero_g = shard_like_zero1(jax.tree.map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), state.params))
                zero_m = {"ce": jnp.zeros(()), "aux": jnp.zeros(())}
                (grads, l, metrics), _ = jax.lax.scan(
                    acc_fn, (zero_g, jnp.zeros(()), zero_m), mbatch)
            else:
                (l, metrics), grads = grad_of(state.params, batch)
            with jax.named_scope("optimizer"):
                new_params, new_opt = update(grads, state.opt, state.params)
            metrics = dict(metrics, loss=l, consensus_err=jnp.zeros(()))
            return TrainState(new_params, new_opt), metrics
        return train_step, partial(_state_specs, run=run, mesh=mesh, node_axes=None)

    node_axes = data_axes(mesh)
    # the consensus engine: the R-round mixing operator is precomputed HERE,
    # once per build, not once per round inside the jitted step; the mesh
    # lets impl="auto" keep the collective-permute roll lowering on sharded
    # node axes and take the matmul/kernel fast path on single-device runs
    gossip_n = pods if run.averaging.mode == "hierarchical" else n_nodes
    if mix is None:
        mix = make_gossip_mix(run.averaging, gossip_n, mesh=mesh)
    elif isinstance(mix, ScheduledMixOp) and run.averaging.quantization != "none":
        raise ValueError("ScheduledMixOp is linear-only: quantized averaging "
                         "configs keep their static per-round operator")

    def train_step(state: TrainState, batch, diag: Any = True):
        # batch leaves: [n_nodes, B/n_nodes, ...]
        def node_loss_grad(params, node_batch):
            return jax.value_and_grad(loss, has_aux=True)(params, node_batch)

        (l, metrics), grads = jax.vmap(node_loss_grad)(state.params, batch)
        # packed (AveragingConfig.packed, the default): grads are flattened
        # into one [N, D] buffer per dtype, the consensus engine and the
        # error diagnostic both run on that buffer — one pack per step,
        # one mixing pass per buffer instead of one chain per leaf
        #
        # the optimizer's step counter doubles as the round clock: stochastic
        # compressors fold it into their key, and a time-varying
        # ScheduledMixOp reads it as the schedule phase index — runtime data
        # either way, so the K-round scan stays a single trace
        t = jnp.reshape(state.opt.step, (-1,))[0]
        step_key = None
        if run.averaging.quantization in STOCHASTIC:
            # per-STEP base key for the stochastic compressor: fold the
            # optimizer's step counter into the MixOp's static seed so a
            # K-round superstep scan draws fresh per-round noise every round
            # instead of replaying the seed-derived sequence (the MixOp still
            # folds the round index in per consensus round)
            step_key = jax.random.fold_in(jax.random.PRNGKey(mix.seed), t)
        if ef_on:
            # error-feedback compressed gossip: compress once per step on the
            # packed residual-corrected gradients, mix LINEARLY, carry the
            # residual in OptState.ef_residual (core.averaging docstring)
            mixed, new_ef, cerr, ef_norm, ef_rel = ef_average_and_error(
                grads, state.opt.ef_residual, run.averaging,
                n_nodes=n_nodes, mix=mix, key=step_key, t=t, diag=diag)
        else:
            mixed, cerr = average_and_error(grads, run.averaging,
                                            n_nodes=n_nodes, pods=pods,
                                            mix=mix, key=step_key, t=t,
                                            diag=diag)
        with jax.named_scope("optimizer"):
            new_params, new_opt = jax.vmap(update)(mixed, state.opt, state.params)
        metrics = jax.tree.map(jnp.mean, metrics)
        metrics = dict(metrics, loss=jnp.mean(l), consensus_err=cerr)
        if ef_on:
            # the optimizer update rules never touch ef_residual (they return
            # it at its default); re-attach the fresh residual here
            new_opt = new_opt._replace(ef_residual=new_ef)
            metrics = dict(metrics, ef_norm=ef_norm, ef_rel=ef_rel)
        return TrainState(new_params, new_opt), metrics

    return train_step, partial(_state_specs, run=run, mesh=mesh, node_axes=node_axes)


def _state_specs(state_shapes: TrainState, *, run: RunConfig, mesh, node_axes):
    # FSDP: params sharded over model AND data axes (all-gathered per layer at
    # use under the scan); decentralized mode instead uses the node axis.
    pspec = (shlib.param_specs(state_shapes.params, mesh, node_axes=node_axes)
             if node_axes else shlib.zero1_specs(state_shapes.params, mesh))

    def opt_spec(leaf):
        # OptState.step is scalar; moment trees mirror params
        return None

    # opt state: map each leaf by matching structure against params where possible
    opt = state_shapes.opt
    same = lambda t: jax.tree_util.tree_structure(t) == jax.tree_util.tree_structure(
        state_shapes.params)
    # ZeRO-1: fp32 Adam moments additionally sharded over the data axes
    m_spec = shlib.zero1_specs(opt.m, mesh, node_axes=node_axes) if same(
        opt.m) else jax.tree.map(lambda _: jax.sharding.PartitionSpec(), opt.m)
    v_spec = shlib.zero1_specs(opt.v, mesh, node_axes=node_axes) if same(
        opt.v) else jax.tree.map(lambda _: jax.sharding.PartitionSpec(), opt.v)
    master_spec = (shlib.zero1_specs(opt.master, mesh, node_axes=node_axes)
                   if opt.master != () else ())
    ef_spec = (shlib.zero1_specs(opt.ef_residual, mesh, node_axes=node_axes)
               if opt.ef_residual != () else ())
    from repro.optim.optimizers import OptState
    return TrainState(pspec, OptState(jax.sharding.PartitionSpec(), m_spec,
                                      v_spec, master_spec, ef_spec))


def build_superstep(run: RunConfig, mesh, *,
                    n_nodes: Optional[int] = None,
                    mix: Optional[Any] = None) -> Tuple[Callable, Callable]:
    """The K-round device scan: fold K consecutive train steps into ONE jitted
    call via `lax.scan` (paper Fig. 4's amortization of fixed per-round costs).

    Returns (superstep, state_spec_fn) where
    `superstep(state, batches) -> (state, metrics)`: batch leaves carry a
    leading K axis ([K, B, ...] exact / [K, N, B/N, ...] decentralized) and
    metric leaves come back stacked [K] — accumulated on-device, so the host
    pays one dispatch and one metric fetch per K rounds instead of per round.
    K is read from the batch shapes at trace time; each distinct K compiles
    once (jit caches by shape), so pick K once per run.

    Decentralized modes compute the consensus diagnostic in the last round
    only: each round's position comes in as scan data beside its batch, and
    the stacked `consensus_err` holds the value in the last round and NaN in
    the others (the driver's record keeps the last round). Exact mode runs no
    diagnostic and scans its batches alone.
    """
    train_step, spec_fn = build_train_step(run, mesh, n_nodes=n_nodes, mix=mix)

    decentralized = run.averaging.mode != "exact"

    def round_(state: TrainState, xs):
        batch, last = xs
        return train_step(state, batch, diag=last)

    def superstep(state: TrainState, batches):
        if not decentralized:
            return jax.lax.scan(train_step, state, batches)
        K = jax.tree.leaves(batches)[0].shape[0]
        return jax.lax.scan(round_, state, (batches, jnp.arange(K) == K - 1))

    return superstep, spec_fn


def superstep_builder(run: RunConfig, mesh, *,
                      n_nodes: Optional[int] = None,
                      mix: Optional[Any] = None) -> Callable[..., Callable]:
    """Bucket-keyed superstep factory for the adaptive-B governor
    (docs/DESIGN.md §Adaptive batch buckets): `build(B) -> superstep` hands
    `train.driver.StreamingDriver` the function to compile for each
    registered bucket of its `core.rates.BucketLadder`.

    The K-round scan reads K, B, and the node split from its batch shapes at
    trace time, so one closure serves every bucket — the per-bucket identity
    lives in the driver's compiled-superstep registry (one jitted executable
    per bucket, built lazily, reused with zero retrace when the governor
    revisits a bucket). The loss/grad/optimizer graph is built once here, not
    once per bucket.

    `build(B, membership=None)` — a partial `core.mixing.Membership` asks for
    the *cohort* superstep: the same scan rebuilt (and cached) at
    n_nodes = n_active, with the gossip operator recomposed over the active
    cohort (docs/DESIGN.md §Elastic membership). The driver wraps it with the
    full-axis gather/scatter (`train.driver.elastic_superstep`), so this
    builder only ever sees dense node axes.

    The prebuilt `mix` override (scenario harness) only applies at full
    membership — its operator stack is sized for the full node axis; cohort
    supersteps recompose their own operator over the active cohort."""
    n_full = n_nodes or n_data_nodes(mesh)
    cohort_cache: Dict[int, Callable] = {}

    def _for_cohort(m: int) -> Callable:
        fn = cohort_cache.get(m)
        if fn is None:
            fn, _ = build_superstep(run, mesh, n_nodes=m,
                                    mix=mix if m == n_full else None)
            cohort_cache[m] = fn
        return fn

    def build(B: int, membership=None) -> Callable:
        m = n_full if membership is None else membership.n_active
        return _for_cohort(m)

    return build


def make_node_batch(batch: Dict[str, jax.Array], n_nodes: int,
                    axis: int = 0) -> Dict[str, jax.Array]:
    """[B, ...] -> [n_nodes, B/n_nodes, ...] (the splitter of Fig. 3(c)).
    `axis=1` splits superstep batches [K, B, ...] -> [K, n_nodes, B/n_nodes, ...]."""
    def split(a):
        shp = a.shape
        return a.reshape(*shp[:axis], n_nodes, shp[axis] // n_nodes, *shp[axis + 1:])
    return jax.tree.map(split, batch)
