"""Plain reference of granite-8b training steps, written apart from the
program: float32 `jax.numpy` at the published widths, with every matmul at
`Precision.HIGHEST`, computed in blocks so that one chip holds it.

One step per node: the causal-LM cross-entropy over the node's rows (mean
over positions), its gradient, optional gossip of the per-node gradients
(R rounds of the ring's circulant weights 1/3 on self and each neighbour),
then Adam (b1 0.9, b2 0.95, eps 1e-8, bias-corrected) on float32 weights.

Weights come from the seed by the initialisation the configuration states
(embedding N(0, 0.02^2), projections N(0, 1/fan_in), norm scales 1, the
layer keys split as the configuration's init splits them), rounded through
the parameter dtype bfloat16 as the served weights are. The tree carries the
same leaf names as the program's so that leaves can be compared one by one.

`numerics="fp8"` is the control, the precision below the configuration's
bfloat16: every matmul operand rounded to float8 (e4m3, per-tensor scale to
its largest magnitude), the product accumulated in float32 and its result
rounded to bfloat16, as an fp8 matmul path would give it.
"""
from __future__ import annotations

from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F8_MAX = 240.0  # largest finite value of e4m3 with IEEE special values
Q_BLOCK = 512  # query rows per attention block
V_BLOCK = 512  # positions per cross-entropy block


def _round(x, exponent_bits, mantissa_bits):
    """x rounded to a narrower float format. `reduce_precision` and not a
    round trip through the type: XLA may drop a convert pair as excess
    precision, and then nothing is rounded."""
    return jax.lax.reduce_precision(x, exponent_bits=exponent_bits,
                                    mantissa_bits=mantissa_bits)


def _fp8(x):
    """x rounded to float8 e4m3 under a per-tensor scale; the gradient passes
    straight through the rounding."""
    scale = F8_MAX / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    q = _round(x * scale, 4, 3) / scale
    return x + jax.lax.stop_gradient(q - x)


def _mm(eq, a, b, numerics):
    if numerics == "fp8":
        out = jnp.einsum(eq, _fp8(a), _fp8(b), precision=HI,
                         preferred_element_type=jnp.float32)
        return _round_bf16(out)
    return jnp.einsum(eq, a, b, precision=HI, preferred_element_type=jnp.float32)


def _round_bf16(x):
    return x + jax.lax.stop_gradient(_round(x, 8, 7) - x)


def _normal(key, shape, std):
    return jax.random.normal(key, shape, jnp.float32) * std


def _fan_in_std(fan_in):
    return 1.0 / jnp.sqrt(jnp.asarray(fan_in, jnp.float32))


def init_params(cfg: dict, key) -> Dict:
    """float32 weights equal to the initial weights rounded through the
    configuration's parameter dtype."""
    d, H, KH, F = cfg["d_model"], cfg["num_heads"], cfg["num_kv_heads"], cfg["d_ff"]
    hd, V, L = d // H, cfg["vocab_size"], cfg["num_layers"]
    keys = jax.random.split(key, 5)

    def block(k):
        ks = jax.random.split(k, 4)
        ka = jax.random.split(ks[1], 4)
        kf = jax.random.split(ks[3], 3)
        return {
            "norm1": {"scale": jnp.ones((d,), jnp.float32)},
            "attn": {"wq": _normal(ka[0], (d, H * hd), _fan_in_std(d)),
                     "wk": _normal(ka[1], (d, KH * hd), _fan_in_std(d)),
                     "wv": _normal(ka[2], (d, KH * hd), _fan_in_std(d)),
                     "wo": _normal(ka[3], (H * hd, d), _fan_in_std(H * hd))},
            "norm2": {"scale": jnp.ones((d,), jnp.float32)},
            "ffn": {"w_gate": _normal(kf[0], (d, F), _fan_in_std(d)),
                    "w_up": _normal(kf[1], (d, F), _fan_in_std(d)),
                    "w_down": _normal(kf[2], (F, d), _fan_in_std(F))},
        }

    params = {
        "embed": _normal(keys[0], (V, d), 0.02),
        "final_norm": {"scale": jnp.ones((d,), jnp.float32)},
        "layers": [jax.vmap(block)(jax.random.split(keys[4], L))],
        "tail": [],
    }
    info = jnp.finfo(jnp.dtype(cfg["training"]["param_dtype"]))
    return jax.tree.map(lambda p: _round(p, info.nexp, info.nmant), params)


def _rmsnorm(x, scale):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + 1e-6) * scale


def _rope(x, theta):
    """x: [S, heads, hd]; rotates the two halves of the head dim."""
    S, hd = x.shape[0], x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def _attention(p, h, cfg, numerics):
    S = h.shape[0]
    H, KH = cfg["num_heads"], cfg["num_kv_heads"]
    hd = cfg["d_model"] // H
    q = _rope(_mm("sd,de->se", h, p["wq"], numerics).reshape(S, H, hd), cfg["rope_theta"])
    k = _rope(_mm("sd,de->se", h, p["wk"], numerics).reshape(S, KH, hd), cfg["rope_theta"])
    v = _mm("sd,de->se", h, p["wv"], numerics).reshape(S, KH, hd)
    k = jnp.repeat(k, H // KH, axis=1)
    v = jnp.repeat(v, H // KH, axis=1)
    qb = min(Q_BLOCK, S)
    nb = S // qb

    @jax.checkpoint
    def one(args):
        i, qi = args
        s = _mm("qhd,khd->hqk", qi, k, numerics) / jnp.sqrt(jnp.float32(hd))
        rows = i * qb + jnp.arange(qb)
        s = jnp.where(jnp.arange(S)[None, None, :] <= rows[None, :, None], s, -jnp.inf)
        return _mm("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v, numerics)

    out = jax.lax.map(one, (jnp.arange(nb), q.reshape(nb, qb, H, hd)))
    return _mm("se,ed->sd", out.reshape(S, H * hd), p["wo"], numerics)


def _ffn(p, h, numerics):
    g = _mm("sd,df->sf", h, p["w_gate"], numerics)
    u = _mm("sd,df->sf", h, p["w_up"], numerics)
    return _mm("sf,fd->sd", jax.nn.silu(g) * u, p["w_down"], numerics)


def sequence_loss(params, tokens, labels, cfg, numerics="f32"):
    """Mean next-token cross-entropy of one sequence ([S] int tokens)."""
    S = tokens.shape[0]
    x = params["embed"][tokens] * jnp.sqrt(jnp.float32(cfg["d_model"]))

    @jax.checkpoint
    def layer(x, p):
        x = x + _attention(p["attn"], _rmsnorm(x, p["norm1"]["scale"]), cfg, numerics)
        x = x + _ffn(p["ffn"], _rmsnorm(x, p["norm2"]["scale"]), numerics)
        return x, None

    x, _ = jax.lax.scan(layer, x, params["layers"][0])
    x = _rmsnorm(x, params["final_norm"]["scale"])
    vb = min(V_BLOCK, S)

    @jax.checkpoint
    def ce(args):
        xb, lb = args
        logits = _mm("sd,vd->sv", xb, params["embed"], numerics)
        gold = jnp.take_along_axis(logits, lb[:, None], axis=-1)[:, 0]
        return jnp.sum(jax.nn.logsumexp(logits, axis=-1) - gold)

    nll = jax.lax.map(ce, (x.reshape(S // vb, vb, -1), labels.reshape(S // vb, vb)))
    return jnp.sum(nll) / S


def node_loss(params, tokens, labels, cfg, numerics="f32"):
    """Mean over the node's rows ([b, S])."""
    per_row = jax.vmap(lambda t, l: sequence_loss(params, t, l, cfg, numerics))
    return jnp.mean(per_row(tokens, labels))


def ring_gossip(tree, rounds: int):
    """R rounds of x_i <- (x_{i-1} + x_i + x_{i+1}) / 3 over the node axis."""
    def mix(x):
        n = x.shape[0]
        for _ in range(rounds):
            if n == 1:
                break
            if n == 2:
                x = (x + jnp.roll(x, 1, axis=0)) / 2
            else:
                x = (jnp.roll(x, 1, axis=0) + x + jnp.roll(x, -1, axis=0)) / 3
        return x
    return jax.tree.map(mix, tree)


def make_step(cfg: dict, train: dict, averaging: dict, numerics="f32"):
    """step(params, m, v, t, tokens, labels) for node-stacked float32 state
    ([N, ...] leaves; tokens [N, b, S]) -> (params, m, v, loss [N]). The
    gradients and the update are two programs, so that the gradient's
    temporaries and Adam's state are never live at once beyond one tree."""
    b1, b2, eps, lr = 0.9, 0.95, 1e-8, float(train["learning_rate"])
    gossip = averaging["mode"] == "gossip"

    @jax.jit
    def grads(params, tokens, labels):
        loss, g = jax.vmap(jax.value_and_grad(
            lambda p, tk, lb: node_loss(p, tk, lb, cfg, numerics)))(params, tokens, labels)
        if gossip:
            g = ring_gossip(g, int(averaging["rounds"]))
        else:
            g = jax.tree.map(lambda a: jnp.broadcast_to(jnp.mean(a, 0, keepdims=True), a.shape), g)
        return loss, g

    @partial(jax.jit, donate_argnums=(0, 1, 2, 3))
    def adam(p, a, b, g, t):
        tf = t.astype(jnp.float32)
        a = b1 * a + (1 - b1) * g
        b = b2 * b + (1 - b2) * g * g
        p = p - lr * ((a / (1 - b1 ** tf)) / (jnp.sqrt(b / (1 - b2 ** tf)) + eps))
        return p, a, b

    def step(params, m, v, t, tokens, labels):
        loss, g = grads(params, tokens, labels)
        out = jax.tree.map(lambda p, a, b, gg: adam(p, a, b, gg, t), params, m, v, g)
        is_triple = lambda x: isinstance(x, tuple)
        pick = lambda i: jax.tree.map(lambda o: o[i], out, is_leaf=is_triple)
        return pick(0), pick(1), pick(2), loss

    return step


def leaf_norms(tree) -> Dict[str, np.ndarray]:
    """{leaf path: [N] norm of each node's copy}."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    norms = [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)),
                              axis=tuple(range(1, x.ndim)))) for _, x in flat]
    norms = jax.device_get(norms)
    return {jax.tree_util.keystr(k): np.asarray(n) for (k, _), n in zip(flat, norms)}


def run(cfg: dict, train: dict, averaging: dict, key, rounds, *, nodes: int,
        numerics: str = "f32", sharding=None) -> dict:
    """Drive `len(rounds)` steps from the seed's weights. `rounds[r]` is
    (tokens, labels), each [N, b, S]. Returns the loss of every step ([R, N]),
    leaf norms of Adam's first moment after the last step, and leaf norms of
    the weights' change over all the steps."""
    place = (lambda t: jax.device_put(t, sharding)) if sharding is not None else (lambda t: t)
    init = jax.jit(lambda k: jax.tree.map(
        lambda p: jnp.broadcast_to(p[None], (nodes, *p.shape)), init_params(cfg, k)),
        out_shardings=sharding)
    params = init(key)
    zeros = jax.jit(lambda p: jax.tree.map(jnp.zeros_like, p), out_shardings=sharding)
    m, v = zeros(params), zeros(params)
    step = make_step(cfg, train, averaging, numerics)
    losses = []
    for r, (tokens, labels) in enumerate(rounds):
        params, m, v, loss = step(params, m, v, jnp.int32(r + 1),
                                  place(jnp.asarray(tokens)), place(jnp.asarray(labels)))
        losses.append(np.asarray(loss))
    del v
    m_norms = leaf_norms(m)
    del m
    p0 = init(key)
    delta = jax.jit(lambda a, b: jax.tree.map(jnp.subtract, a, b),
                    donate_argnums=(0,), out_shardings=sharding)(params, p0)
    return {"loss": np.stack(losses), "m": m_norms, "delta": leaf_norms(delta)}
