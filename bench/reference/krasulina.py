"""Plain reference of D-Krasulina rounds (Alg. 2 with gossip averaging),
written apart from the program: float32 `jax.numpy`, matmuls at
`Precision.HIGHEST`, one node and one round at a time.

Per round t (1-based) and node n, with the node's rows Z_n [b, d]:

    xi_n = Z_n^T (Z_n w_n) / b - (mean((Z_n w_n)^2) / ||w_n||^2) w_n

then R rounds of ring gossip over the nodes (weight 1/3 on self and on each
neighbour), and w_n <- w_n + (c / t) * (mixed xi)_n.

`precision="high"` is the control: the same rounds with the matmuls in
three bfloat16 passes, the step below the float32 the configuration states.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


def _bf16(x):
    """x rounded to bfloat16 by `reduce_precision`: XLA may drop a round trip
    through the type as excess precision."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _split(x):
    hi = _bf16(x)
    return hi, _bf16(x - hi)


def dot(a, b, precision):
    """`highest`: float32 products. `high`: three bfloat16 passes, written
    out (a_hi b_hi + a_hi b_lo + a_lo b_hi, each exact in float32), since a
    backend may run a matrix-vector product at float32 whatever it is asked."""
    if precision == "highest":
        return jnp.dot(a, b, precision=HI)
    (ah, al), (bh, bl) = _split(a), _split(b)
    return (jnp.dot(ah, bh, precision=HI) + jnp.dot(ah, bl, precision=HI)
            + jnp.dot(al, bh, precision=HI))


def xi(w, z, precision):
    zw = dot(z, w, precision)
    grad = dot(z.T, zw, precision) / z.shape[0]
    return grad - (jnp.mean(zw * zw) / jnp.sum(w * w)) * w


def ring_mix(x, rounds: int):
    for _ in range(rounds):
        x = (jnp.roll(x, 1, axis=0) + x + jnp.roll(x, -1, axis=0)) / 3
    return x


def run(w0, batches, *, rounds: int, c: float, precision: str = "highest"):
    """w0: [N, d]; batches: [T, N, b, d] float32, round by round. Returns the
    [N, d] iterates after the T rounds."""
    @jax.jit
    def one_round(w, z, t):
        h = jax.vmap(lambda wn, zn: xi(wn, zn, precision))(w, z)
        return w + (c / t) * ring_mix(h, rounds)

    w = jnp.asarray(w0, jnp.float32)
    for t in range(batches.shape[0]):
        w = one_round(w, jnp.asarray(batches[t], jnp.float32), jnp.float32(t + 1))
    return np.asarray(w)
