"""Pallas TPU kernels: fused mini-batch Krasulina pseudo-gradient, per node
and — for the decentralized D-Krasulina track — fused with the R-round gossip
consensus that follows it.

The paper's PCA hot spot (Alg. 2 steps 3-5) is, per node and round, a fused
BLAS-2 pass over the local mini-batch: s = Z w, then xi = Z^T s / B - (mean(s^2)
/ ||w||^2) w. A naive implementation streams Z from HBM twice (once for s, once
for Z^T s) or materializes B rank-1 updates. `krasulina_xi_pallas` tiles Z into
VMEM once per block and accumulates both Z^T s and sum(s^2) in a single pass —
arithmetic intensity doubles versus the two-pass form, which matters because
the op is memory-bound (2*B*d flops over B*d*dtype bytes).

`krasulina_xi_gossip_pallas` goes one step further for the gossip-averaged
variant (Alg. 2 step 6 replaced by eq. 17 consensus): the unfused path writes
the per-node xi [N, d] to HBM and then pays (deg+1)*R more passes over it for
the R gossip rounds. Here the xi tile is computed in-register per [N, block_d]
column tile and ALL R rounds of shift/weight/accumulate run on the resident
tile before the single write-back (the `kernels.consensus` trick applied to a
producer-consumer pair). The full-d reductions xi needs (s_n = Z_n w_n,
||w_n||^2) are accumulated by a first grid phase over the same tiles, so the
kernel streams Z twice and the [N, d] consensus state exactly once.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(w_ref, z_ref, o_ref, acc_ref, s2_ref, *, n_tiles: int, batch: int):
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        s2_ref[...] = jnp.zeros_like(s2_ref)

    z = z_ref[...].astype(jnp.float32)  # [tb, d]
    w = w_ref[...].astype(jnp.float32)  # [1, d]
    s = jax.lax.dot_general(z, w, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [tb, 1]
    acc_ref[...] += jax.lax.dot_general(s, z, (((0,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)  # [1, d]
    s2_ref[...] += jnp.sum(s * s, keepdims=True)  # [1, 1]: Mosaic stores vectors

    @pl.when(t == n_tiles - 1)
    def _epilogue():
        wf = w_ref[...].astype(jnp.float32)
        nrm2 = jnp.maximum(jnp.sum(wf * wf), 1e-30)
        mean_s2 = s2_ref[...] / batch  # [1, 1], broadcasts over d
        o_ref[...] = (acc_ref[...] / batch - (mean_s2 / nrm2) * wf).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def krasulina_xi_pallas(w: jax.Array, z: jax.Array, *, block_b: int = 256,
                        interpret: bool) -> jax.Array:
    """w: [d]; z: [B, d] -> xi [d]. Pads B up to a multiple of block_b (zero rows
    contribute nothing to either accumulator, but the mean uses the true B)."""
    B, d = z.shape
    n_tiles = max(1, (B + block_b - 1) // block_b)
    pad = n_tiles * block_b - B
    if pad:
        z = jnp.pad(z, ((0, pad), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_kernel, n_tiles=n_tiles, batch=B),
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((1, d), lambda t: (0, 0)),  # w stays resident
            pl.BlockSpec((block_b, d), lambda t: (t, 0)),  # stream Z tiles
        ],
        out_specs=pl.BlockSpec((1, d), lambda t: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, d), w.dtype),
        scratch_shapes=[
            pltpu.VMEM((1, d), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(w[None], z)
    return out[0]


def _xi_gossip_kernel(w_ref, z_ref, o_ref, s_ref, nrm2_ref, h_ref, *,
                      shifts: Tuple[int, ...], weights: Tuple[float, ...],
                      rounds: int, batch_n: int):
    """Grid (2, n_tiles). Phase 0 accumulates the full-d reductions (s = Z w
    per node, ||w||^2 per node) tile by tile; phase 1 revisits each tile,
    forms the xi column block for all N nodes and runs every gossip round on
    the resident [N, block_d] tile before the one write-back.

    The per-node contractions are multiply-and-reduce on the VPU, one node at
    a time over 2-D [Bn, block_d] slices: Mosaic has no node-batched
    `dot_general`, and a matvec wastes the MXU anyway (the op is
    memory-bound)."""
    p, t = pl.program_id(0), pl.program_id(1)
    n = w_ref.shape[0]

    @pl.when(p == 0)
    def _accumulate():
        @pl.when(t == 0)
        def _init():
            s_ref[...] = jnp.zeros_like(s_ref)
            nrm2_ref[...] = jnp.zeros_like(nrm2_ref)

        w = w_ref[...].astype(jnp.float32)  # [N, bd]
        nrm2_ref[...] += jnp.sum(w * w, axis=1, keepdims=True)  # [N, 1]
        for i in range(n):  # s_n += Z_n[:, tile] w_n[tile], as a [Bn, 1] column
            z = z_ref[i].astype(jnp.float32)  # [Bn, bd]
            s_ref[i] += jnp.sum(z * w[i:i + 1], axis=1, keepdims=True)

    @pl.when(p == 1)
    def _xi_and_gossip():
        w = w_ref[...].astype(jnp.float32)  # [N, bd]
        nrm2 = jnp.maximum(nrm2_ref[...], 1e-30)  # [N, 1], complete after phase 0
        for i in range(n):
            # xi_n tile: (1/Bn) Z_n^T s_n - (mean(s_n^2)/||w_n||^2) w_n
            s = s_ref[i]  # [Bn, 1]
            z = z_ref[i].astype(jnp.float32)  # [Bn, bd]
            zts = jnp.sum(s * z, axis=0, keepdims=True)  # [1, bd]
            coeff = jnp.sum(s * s, keepdims=True) / (batch_n * nrm2[i:i + 1])
            h_ref[i:i + 1, :] = zts / batch_n - coeff * w[i:i + 1]
        h = h_ref[...]  # [N, bd]
        for _ in range(rounds):
            acc = None
            for sh, wt in zip(shifts, weights):
                msg = h if sh == 0 else pltpu.roll(h, sh, 0)
                term = wt * msg
                acc = term if acc is None else acc + term
            h = acc
        o_ref[...] = h.astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("shifts", "weights", "rounds", "block_d",
                                    "interpret"))
def krasulina_xi_gossip_pallas(w: jax.Array, z: jax.Array,
                               shifts: Tuple[int, ...],
                               weights: Tuple[float, ...], rounds: int, *,
                               block_d: int = 512,
                               interpret: bool) -> jax.Array:
    """w: [N, d] per-node iterates; z: [N, Bn, d] per-node mini-batches ->
    [N, d] gossip-mixed pseudo-gradients: R rounds of
    `sum_s w_s * roll(xi, s, axis=0)` applied to xi_n = krasulina_xi(w_n, z_n).

    Pads d up to a multiple of block_d (zero columns contribute nothing to
    s/||w||^2 and stay zero through the rolls). The whole [N, Bn] s-matrix is
    kept in VMEM scratch, so Bn is assumed streaming-small (B/N per the
    splitter), not a full epoch; the node loop is unrolled, so N is too."""
    n, bn, d = z.shape
    assert w.shape == (n, d), (w.shape, z.shape)
    shifts = tuple(int(s) % n for s in shifts)
    block_d = min(block_d, d)
    n_tiles = (d + block_d - 1) // block_d
    pad = n_tiles * block_d - d
    if pad:
        w = jnp.pad(w, ((0, 0), (0, pad)))
        z = jnp.pad(z, ((0, 0), (0, 0), (0, pad)))
    out = pl.pallas_call(
        functools.partial(_xi_gossip_kernel, shifts=shifts, weights=weights,
                          rounds=rounds, batch_n=bn),
        grid=(2, n_tiles),
        in_specs=[
            pl.BlockSpec((n, block_d), lambda p, t: (0, t)),
            pl.BlockSpec((n, bn, block_d), lambda p, t: (0, 0, t)),
        ],
        out_specs=pl.BlockSpec((n, block_d), lambda p, t: (0, t)),
        out_shape=jax.ShapeDtypeStruct((n, n_tiles * block_d), w.dtype),
        scratch_shapes=[
            pltpu.VMEM((n, bn, 1), jnp.float32),  # s = Z w per node
            pltpu.VMEM((n, 1), jnp.float32),  # ||w||^2 per node
            pltpu.VMEM((n, block_d), jnp.float32),  # xi tile before gossip
        ],
        interpret=interpret,
    )(w, z)
    return out[:, :d] if pad else out


# ---------------------------------------------------------------------------
# shard_map partitioning rule (sharded node axis)
# ---------------------------------------------------------------------------


def krasulina_xi_gossip_shard(w: jax.Array, z: jax.Array, sched, rounds: int,
                              mesh, node_axes: Tuple[str, ...],
                              axis: str) -> jax.Array:
    """Fused xi + R-round gossip over a node axis sharded across `node_axes`
    of `mesh` (`axis`: the nontrivial one the ppermute ring runs over).

    The xi pass (Alg. 2 step 4) is node-local — each shard computes its own
    rows' pseudo-gradients without any exchange — and only the consensus
    rounds communicate, as per-round halo ppermutes + fused slice-sum tile
    mixing (`kernels.consensus` shard rules). Matches the strict per-round
    oracle `ref.gossip_mix_ref(vmap(ref.krasulina_xi_ref), ...)` to f32
    round-off (xi itself is shard-invariant bitwise).
    w: [n, d], z: [n, B, d], both sharded on the node axis."""
    from jax.sharding import PartitionSpec as P

    from repro.kernels import ref
    from repro.kernels.consensus import _ext_tile, _slice_round, halo_reach

    n = w.shape[0]
    extent = int(mesh.shape[axis])
    n_local = n // extent
    sched = tuple(sched)
    ru, rd = halo_reach(sched, n)

    def local(w_l, z_l):
        h = jax.vmap(ref.krasulina_xi_ref)(w_l, z_l)  # [n_local, d], no comms
        for _ in range(rounds):
            ext = _ext_tile(h, ru, rd, axis, extent, n_local)
            h = _slice_round(ext, sched, n, ru, n_local)
        return h

    wspec = P(node_axes, None)
    zspec = P(node_axes, None, None)
    return jax.shard_map(local, mesh=mesh, in_specs=(wspec, zspec),
                         out_specs=wspec)(w, z)
