"""Pallas TPU kernel: blockwise (flash) attention with causal, sliding-window and
chunked-local masking — the serving/backbone hot spot of the framework.

TPU adaptation notes (vs. the CUDA flash-attention algorithm):
* tiles are MXU-aligned (block_q x block_k >= 128x128) and live in VMEM;
* the kv axis is the innermost *sequential* grid dimension, so the online-softmax
  running max / sum / accumulator persist in VMEM scratch across kv steps
  (no atomics / shared-memory reductions, which have no TPU analogue);
* fully-masked (q, kv) block pairs are skipped with `pl.when` — for sliding
  windows this turns O(S^2) into O(S * window) compute.

Softmax statistics are kept as (block_q, 128) tiles (lane-replicated) to stay
vector-register-shaped on TPU.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale, causal, window, chunk, block_q, block_k, n_k):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_first = qi * block_q
    q_last = q_first + block_q - 1
    k_first = ki * block_k
    k_last = k_first + block_k - 1

    # block-level skip predicate (structural sparsity)
    live = True
    if causal:
        live = jnp.logical_and(live, k_first <= q_last)
    if window:
        live = jnp.logical_and(live, k_last > q_first - window)
    if chunk:
        live = jnp.logical_and(live, (k_first // chunk) <= (q_last // chunk))
        live = jnp.logical_and(live, (k_last // chunk) >= (q_first // chunk))

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32)  # [bq, d]
        k = k_ref[0].astype(jnp.float32)  # [bk, d]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        qp = q_first + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        kp = k_first + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = jnp.ones_like(s, dtype=jnp.bool_)
        if causal:
            mask &= kp <= qp
        if window:
            mask &= kp > qp - window
        if chunk:
            mask &= (kp // chunk) == (qp // chunk)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:, :1]  # [bq, 1]
        m_blk = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_blk)
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)  # [bq, 1]
        l_new = l_ref[:, :1] * corr + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(p, v_ref[0].astype(jnp.float32),
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * corr + pv
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ki == n_k - 1)
    def _epilogue():
        l = l_ref[:, :1]
        o_ref[0] = (acc_ref[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "chunk", "block_q", "block_k", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0, chunk: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool) -> jax.Array:
    """q/k/v: [B, H, S, D] (GQA heads pre-broadcast). Returns [B, H, Sq, D]."""
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    scale = 1.0 / math.sqrt(D)
    bq = min(block_q, Sq)
    bk = min(block_k, Sk)
    nq = (Sq + bq - 1) // bq
    nk = (Sk + bk - 1) // bk
    if nq * bq != Sq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, nq * bq - Sq), (0, 0)))
    if nk * bk != Sk:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, nk * bk - Sk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, nk * bk - Sk), (0, 0)))
        if not (causal or window or chunk):
            raise ValueError("unmasked attention requires Sk divisible by block_k")

    qf = q.reshape(B * H, nq * bq, D)
    kf = k.reshape(B * H, nk * bk, D)
    vf = v.reshape(B * H, nk * bk, D)

    out = pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, causal=causal,
                          window=window, chunk=chunk, block_q=bq, block_k=bk,
                          n_k=nk),
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, nq * bq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(B, H, nq * bq, D)[:, :, :Sq]
