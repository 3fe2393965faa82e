"""Pallas TPU kernels: fused R-round gossip consensus (paper eq. 17),
unquantized and quantized.

The reference device path applies R rounds of weighted circular shifts over the
node axis; each round reads and writes the full [N, d] leaf, so one consensus
step costs (deg+1)*R HBM passes. Since N (the node count) is small, these
kernels tile the [N, block_d] slab into VMEM once and run ALL R rounds of
shift/weight/accumulate in-register before writing back — one HBM read and one
HBM write per buffer regardless of R. The shift schedule and R are static, so
the round loop fully unrolls into VPU adds plus sublane rotations.

Message quantization (Section VI) is fused here too, with **per-tile**
compressor statistics (`gossip_mix_quant_pallas`): each [n, block_d] tile
computes its own scale (mean-|x| for sign, max-|x| for int8) in-register, so
quantized gossip also costs one HBM read+write per buffer instead of
(deg+1)*R passes. This changes the compressor's statistic granularity relative
to the whole-array ("global") form — `core.mixing.CirculantMixOp(stats=...)`
selects between the exact global-stats per-round oracle and this fused tile
form; `benchmarks/bench_consensus.py` carries the accuracy study. Ragged and
padded tails are masked out of every statistic (`valid_d`). The stochastic
int8 compressor stays on the XLA tile path (`core.quantize.tile_compress`) —
threefry keys, not in-kernel PRNG — so its randomness is identical on every
backend.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, o_ref, *, shifts: Tuple[int, ...], weights: Tuple[float, ...],
            rounds: int):
    h = x_ref[...].astype(jnp.float32)  # [n, block_d], resident for all rounds
    for _ in range(rounds):
        acc = None
        for s, w in zip(shifts, weights):
            msg = h if s == 0 else pltpu.roll(h, s, 0)
            term = w * msg
            acc = term if acc is None else acc + term
        h = acc
    o_ref[...] = h.astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("shifts", "weights", "rounds", "block_d",
                                    "interpret"))
def gossip_mix_pallas(x: jax.Array, shifts: Tuple[int, ...],
                      weights: Tuple[float, ...], rounds: int, *,
                      block_d: int = 512, interpret: bool) -> jax.Array:
    """R rounds of `sum_s w_s * roll(x, s, axis=0)` in a single HBM pass.

    x: [n, ...] (any rank; trailing dims are flattened). shifts/weights: the
    one-round circulant schedule. Matches R sequential `roll_mix` applications
    (quantization off) to f32 accuracy.
    """
    n = x.shape[0]
    shifts = tuple(int(s) % n for s in shifts)
    orig_shape = x.shape
    flat = x.reshape(n, -1)
    d = flat.shape[1]
    block_d = min(block_d, d)
    n_tiles = (d + block_d - 1) // block_d
    pad = n_tiles * block_d - d
    if pad:
        flat = jnp.pad(flat, ((0, 0), (0, pad)))
    out = pl.pallas_call(
        functools.partial(_kernel, shifts=shifts, weights=weights,
                          rounds=rounds),
        grid=(n_tiles,),
        in_specs=[pl.BlockSpec((n, block_d), lambda t: (0, t))],
        out_specs=pl.BlockSpec((n, block_d), lambda t: (0, t)),
        out_shape=jax.ShapeDtypeStruct(flat.shape, x.dtype),
        interpret=interpret,
    )(flat)
    if pad:
        out = out[:, :d]
    return out.reshape(orig_shape)


def _quant_kernel(x_ref, o_ref, *, shifts: Tuple[int, ...],
                  weights: Tuple[float, ...], rounds: int, quant: str,
                  block_d: int, valid_d: int):
    """All R quantized-gossip rounds on one resident [n, block_d] tile.

    Compress-once-broadcast per round: the tile scale is invariant under the
    node-axis roll (the roll permutes rows, the stat reduces over them), so
    each round quantizes the resident tile ONCE in-register and accumulates
    rolled copies of the compressed tile — the `stats="tile"` semantics
    `core.quantize.tile_compress` oracles. Columns past `valid_d` (ragged
    tail / caller padding) are zero on input, so they contribute nothing to
    the sum/max statistics; only the mean's element count needs the mask.
    """
    t = pl.program_id(0)
    h = x_ref[...].astype(jnp.float32)  # [n, block_d]
    col = jax.lax.broadcasted_iota(jnp.int32, h.shape, 1) + t * block_d
    nvalid = jnp.maximum(
        jnp.sum((col < valid_d).astype(jnp.float32)), 1.0)
    for _ in range(rounds):
        a = jnp.abs(h)
        if quant == "sign":
            q = jnp.sign(h) * (jnp.sum(a) / nvalid)
        else:  # int8
            scale = jnp.maximum(jnp.max(a), 1e-12) / 127.0
            q = jnp.clip(jnp.round(h / scale), -127, 127) * scale
        acc = None
        for s, w in zip(shifts, weights):
            term = w * (h if s == 0 else pltpu.roll(q, s, 0))
            acc = term if acc is None else acc + term
        h = acc
    o_ref[...] = h.astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("shifts", "weights", "rounds", "quant",
                                    "block_d", "valid_d", "interpret"))
def gossip_mix_quant_pallas(x: jax.Array, shifts: Tuple[int, ...],
                            weights: Tuple[float, ...], rounds: int,
                            quant: str, *, block_d: int = 512,
                            valid_d: int = -1,
                            interpret: bool) -> jax.Array:
    """R rounds of quantized gossip (tile-statistics compressors) in a single
    HBM pass. quant: "sign" | "int8" (deterministic — the stochastic variant
    stays on the XLA path). `valid_d`: flattened columns >= valid_d are pad
    (must be zero) and are masked out of the compressor statistics; -1 means
    all columns are valid."""
    if quant not in ("sign", "int8"):
        raise ValueError(f"fused quantized kernel supports sign/int8, "
                         f"got {quant!r}")
    n = x.shape[0]
    shifts = tuple(int(s) % n for s in shifts)
    orig_shape = x.shape
    flat = x.reshape(n, -1)
    d = flat.shape[1]
    dv = d if valid_d < 0 else valid_d
    block_d = min(block_d, d)
    n_tiles = (d + block_d - 1) // block_d
    pad = n_tiles * block_d - d
    if pad:
        flat = jnp.pad(flat, ((0, 0), (0, pad)))
    out = pl.pallas_call(
        functools.partial(_quant_kernel, shifts=shifts, weights=weights,
                          rounds=rounds, quant=quant, block_d=block_d,
                          valid_d=dv),
        grid=(n_tiles,),
        in_specs=[pl.BlockSpec((n, block_d), lambda t: (0, t))],
        out_specs=pl.BlockSpec((n, block_d), lambda t: (0, t)),
        out_shape=jax.ShapeDtypeStruct(flat.shape, x.dtype),
        interpret=interpret,
    )(flat)
    if pad:
        out = out[:, :d]
    return out.reshape(orig_shape)


# ---------------------------------------------------------------------------
# shard_map partitioning rules (sharded node axis)
#
# GSPMD has no good partition for the circular-shift form: `jnp.roll` on a
# sharded axis lowers to a collective-permute plus a wraparound concat that
# XLA cannot fuse into the weighted sum, so every schedule term pays a full
# pass over the local shard. These rules partition the gossip explicitly:
# each round exchanges only the halo rows the schedule reaches (a
# `lax.ppermute` ring, one hop per n_local rows of reach), builds a
# halo-extended local tile, and applies the round as a weighted sum of
# *contiguous slices* — no wraparound, so XLA fuses the whole round into one
# pass over the tile. Per-round semantics are preserved (bit-identical to
# `ref.gossip_mix_ref`), which is exactly the form the quantized wire path
# requires. The local slice-sum is the kernel's tile mixing restricted to a
# shard; on real TPU the same extended-tile form is the candidate body for a
# per-shard `pallas_call` (ROADMAP: real-TPU validation debt).
# ---------------------------------------------------------------------------


def centered_shift(s: int, n: int) -> int:
    """Canonical shift representative in (-n/2, n/2]."""
    s = s % n
    return s if s <= n // 2 else s - n


def halo_reach(sched, n: int) -> Tuple[int, int]:
    """(rows needed from preceding shards, rows from following shards) for one
    round of `sched` on an [n, ...] buffer: roll by +s pulls rows from s above."""
    up = max((centered_shift(s, n) for s, _ in sched
              if centered_shift(s, n) > 0), default=0)
    down = max((-centered_shift(s, n) for s, _ in sched
                if centered_shift(s, n) < 0), default=0)
    return up, down


def _gather_halo(h, reach: int, axis, extent: int, n_local: int, up: bool):
    """Collect `reach` boundary rows from ring neighbors, one whole-tile hop
    per n_local rows (ceil(reach / n_local) ppermutes)."""
    rows = []
    need, hop = reach, 1
    while need > 0:
        take = min(need, n_local)
        if up:  # rows preceding this shard: tail rows of device i-hop
            rows.insert(0, jax.lax.ppermute(
                h[n_local - take:], axis,
                [(i, (i + hop) % extent) for i in range(extent)]))
        else:   # rows following: head rows of device i+hop
            rows.append(jax.lax.ppermute(
                h[:take], axis,
                [(i, (i - hop) % extent) for i in range(extent)]))
        need -= take
        hop += 1
    return rows


def _ext_tile(h, ru: int, rd: int, axis, extent: int, n_local: int):
    up = _gather_halo(h, ru, axis, extent, n_local, up=True)
    dn = _gather_halo(h, rd, axis, extent, n_local, up=False)
    return jnp.concatenate(up + [h] + dn, axis=0) if (up or dn) else h


def _slice_round(ext, sched, n: int, ru: int, n_local: int, self_term=None):
    """One gossip round as a weighted sum of contiguous row slices of the
    halo-extended tile. `self_term` (optional) substitutes the s==0 source —
    the quantized wire keeps the resident tile uncompressed for itself."""
    acc = None
    for s, w in sched:
        sc = centered_shift(s, n)
        if sc == 0 and self_term is not None:
            t = w * self_term
        else:
            t = w * jax.lax.slice_in_dim(ext, ru - sc, ru - sc + n_local, axis=0)
        acc = t if acc is None else acc + t
    return acc


def shard_compatible(sched, n: int, extent: int) -> bool:
    """True when the halo rules cover this (schedule, split): even row tiles
    and a one-round reach that neighbors can serve without wrapping onto the
    resident shard."""
    if extent <= 1 or n % extent:
        return False
    ru, rd = halo_reach(sched, n)
    return ru + rd <= n - n // extent


def _lane_dense(n_local: int, size: int) -> Tuple[int, ...]:
    """Shape for a shard's [n_local, D] rows in the exchange: [n_local,
    D/128, 128] when it divides, so a shard holding one node's row is not
    padded along its sublanes (a [1, D] bf16 buffer takes twice its size on
    a TPU), else [n_local, D]."""
    d = size // n_local
    return (n_local, d // 128, 128) if d % 128 == 0 else (n_local, d)


def gossip_mix_shard(x: jax.Array, sched, rounds: int, mesh,
                     node_axes: Tuple[str, ...], axis: str) -> jax.Array:
    """R rounds of circulant gossip over a node axis sharded across
    `node_axes` of `mesh` (`axis`: the single nontrivial one the ppermute ring
    runs over). Per-round halo exchange + fused local slice-sum; bit-identical
    to `ref.gossip_mix_ref`."""
    from jax.sharding import PartitionSpec as P

    n = x.shape[0]
    extent = int(mesh.shape[axis])
    n_local = n // extent
    sched = tuple(sched)
    ru, rd = halo_reach(sched, n)

    def local(h):
        shape = h.shape
        h = h.reshape(_lane_dense(n_local, h.size))
        for _ in range(rounds):
            ext = _ext_tile(h, ru, rd, axis, extent, n_local)
            h = _slice_round(ext, sched, n, ru, n_local)
        return h.reshape(shape)

    spec = P(node_axes, *([None] * (x.ndim - 1)))
    return jax.shard_map(local, mesh=mesh, in_specs=spec,
                         out_specs=spec)(x)


def gossip_mix_quant_shard(x: jax.Array, sched, rounds: int, quant: str,
                           mesh, node_axes: Tuple[str, ...], axis: str, *,
                           block_d: int = 512, valid_d: int = -1,
                           key=None) -> jax.Array:
    """Quantized per-round gossip on a sharded node axis with **per-node**
    tile statistics (`core.quantize.tile_compress(per_node=True)`): each node
    scales its outgoing message from its own rows — the statistic a real
    sender can compute locally — so the compressed wire values are invariant
    under the device split and the sharded path matches the unsharded
    `stats="node"` oracle (`ref.gossip_mix_quant_ref(per_node=True)`) — wire
    values bit-identically, the weighted sum to f32 round-off (program
    layouts associate the accumulation differently).
    Stochastic compressors fold the shard index into the key (deterministic,
    but layout-dependent noise — sign/int8 are layout-invariant)."""
    from repro.core.quantize import STOCHASTIC, tile_compress
    from jax.sharding import PartitionSpec as P

    n = x.shape[0]
    extent = int(mesh.shape[axis])
    n_local = n // extent
    sched = tuple(sched)
    ru, rd = halo_reach(sched, n)
    dv = None if valid_d is None or valid_d < 0 else valid_d

    def local(h):
        shape = h.shape
        h = h.reshape(n_local, -1).astype(jnp.float32)
        k0 = key
        if quant in STOCHASTIC and k0 is not None:
            k0 = jax.random.fold_in(k0, jax.lax.axis_index(axis))
        for r in range(rounds):
            k = jax.random.fold_in(k0, r) if k0 is not None else None
            q = tile_compress(h, quant, block_d, valid_d=dv, key=k,
                              per_node=True)
            ext = _ext_tile(q, ru, rd, axis, extent, n_local)
            h = _slice_round(ext, sched, n, ru, n_local, self_term=h)
        return h.reshape(shape).astype(x.dtype)

    spec = P(node_axes, *([None] * (x.ndim - 1)))
    return jax.shard_map(local, mesh=mesh, in_specs=spec,
                         out_specs=spec)(x)
