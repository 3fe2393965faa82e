"""Tests for the fused consensus engine (core.mixing.MixOp + the Pallas gossip
kernel): the precomputed R-round operator must match the per-round oracle
(`schedule_matrix` + `np.linalg.matrix_power`), the kernel must match the
per-round `roll_mix` loop, and quantized configs must keep exact per-round
semantics (no operator collapsing)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import AveragingConfig
from repro.core import averaging, dsgd, mixing, quantize
from repro.core.quantize import COMPRESSORS
from repro.kernels import ref
from repro.kernels.consensus import gossip_mix_pallas, gossip_mix_quant_pallas
from repro.kernels.ops import gossip_mix


def _x(n, d=24, seed=0, dtype=np.float32):
    return jnp.asarray(np.random.default_rng(seed).normal(size=(n, d)).astype(dtype))


# ---------------------------------------------------------------------------
# Dense engine
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,rounds", [(8, 1), (16, 4), (16, 8), (24, 13)])
def test_dense_mix_op_matches_matrix_power(n, rounds):
    A = mixing.random_regular_expander(n, deg=4, seed=1)
    h = _x(n)
    want = np.linalg.matrix_power(A, rounds) @ np.asarray(h)
    mix = mixing.dense_mix_op(jnp.asarray(A, jnp.float32), rounds)
    np.testing.assert_allclose(np.asarray(mix(h)), want, rtol=1e-5, atol=1e-5)
    # the unfused fallback is the original per-round scan
    unfused = mixing.dense_mix_op(jnp.asarray(A, jnp.float32), rounds, fuse=False)
    assert unfused.A_eff is None
    np.testing.assert_allclose(np.asarray(unfused(h)), want, rtol=1e-5, atol=1e-5)


def test_dense_mix_op_zero_rounds_is_identity():
    h = _x(6)
    mix = mixing.dense_mix_op(jnp.eye(6), 0)
    assert mix(h) is h


def test_consensus_oracle_agrees_with_mix_op():
    n, rounds = 16, 8
    A = jnp.asarray(mixing.random_regular_expander(n, deg=6, seed=0), jnp.float32)
    h = _x(n)
    np.testing.assert_allclose(np.asarray(dsgd.consensus(h, A, rounds)),
                               np.asarray(mixing.dense_mix_op(A, rounds)(h)),
                               rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# Circulant engine
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("topo", ["ring", "circulant2", "torus"])
@pytest.mark.parametrize("n,rounds", [(8, 1), (12, 3), (16, 8), (17, 5)])
def test_compose_schedule_matches_matrix_power(topo, n, rounds):
    sched = mixing.schedule(topo, n)
    fused = mixing.compose_schedule(sched, rounds, n)
    got = mixing.schedule_matrix(fused, n)
    want = np.linalg.matrix_power(mixing.schedule_matrix(sched, n), rounds)
    np.testing.assert_allclose(got, want, atol=1e-12)
    # composition preserves double stochasticity and never exceeds n terms
    assert mixing.is_doubly_stochastic(got)
    assert len(fused) <= n


@pytest.mark.parametrize("impl", ["roll", "matmul", "kernel"])
@pytest.mark.parametrize("topo,rounds", [("ring", 8), ("circulant2", 3),
                                         ("torus", 5)])
def test_circulant_mix_op_matches_oracle(impl, topo, rounds):
    n = 16
    sched = mixing.schedule(topo, n)
    h = _x(n)
    want = np.linalg.matrix_power(mixing.schedule_matrix(sched, n), rounds) @ \
        np.asarray(h)
    op = mixing.circulant_mix_op(sched, n, rounds, impl=impl)
    np.testing.assert_allclose(np.asarray(op(h)), want, rtol=1e-5, atol=1e-5)
    # the unfused escape hatch is the original per-round loop
    loop_op = mixing.circulant_mix_op(sched, n, rounds, fuse=False)
    assert loop_op.fused_sched is None
    np.testing.assert_allclose(np.asarray(loop_op(h)), want,
                               rtol=1e-5, atol=1e-5)


def test_circulant_mix_op_high_rank_leaves():
    """Trainer-style leaves [n, a, b] flatten correctly under every impl."""
    n, rounds = 8, 4
    sched = mixing.schedule("ring", n)
    x = jnp.asarray(np.random.default_rng(3).normal(size=(n, 3, 5)).astype(np.float32))
    outs = [np.asarray(mixing.circulant_mix_op(sched, n, rounds, impl=impl)(x))
            for impl in ("roll", "matmul", "kernel")]
    A_R = np.linalg.matrix_power(mixing.schedule_matrix(sched, n), rounds)
    want = (A_R @ np.asarray(x).reshape(n, -1)).reshape(n, 3, 5)
    for got in outs:
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# Pallas kernel (interpret mode on CPU)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,d", [(8, 64), (16, 512), (16, 700), (5, 33)])
@pytest.mark.parametrize("topo,rounds", [("ring", 1), ("ring", 8),
                                         ("circulant2", 4)])
def test_gossip_kernel_matches_roll_mix(n, d, topo, rounds):
    sched = mixing.schedule(topo, n)
    x = _x(n, d, seed=4)
    got = gossip_mix(x, sched, rounds, force_pallas=True)
    want = x
    for _ in range(rounds):
        want = mixing.roll_mix(want, sched, lambda m: m)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_gossip_kernel_bf16():
    n, d = 16, 256
    sched = mixing.schedule("ring", n)
    x = _x(n, d, seed=5, dtype=np.float32).astype(jnp.bfloat16)
    got = gossip_mix_pallas(x, tuple(s for s, _ in sched),
                            tuple(w for _, w in sched), 4, interpret=True)
    assert got.dtype == jnp.bfloat16
    want = np.asarray(x, np.float32)
    A4 = np.linalg.matrix_power(mixing.schedule_matrix(sched, n), 4)
    np.testing.assert_allclose(np.asarray(got, np.float32), A4 @ want,
                               rtol=5e-2, atol=5e-2)


def test_gossip_kernel_small_block_tiling():
    """Grid tiling over d must be seam-free."""
    n, d = 8, 130
    sched = mixing.schedule("ring", n)
    x = _x(n, d, seed=6)
    got = gossip_mix_pallas(x, tuple(s for s, _ in sched),
                            tuple(w for _, w in sched), 3,
                            block_d=32, interpret=True)
    want = x
    for _ in range(3):
        want = mixing.roll_mix(want, sched, lambda m: m)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# Quantize-fused kernel (interpret mode on CPU) vs the XLA tile oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("quant", ["sign", "int8"])
@pytest.mark.parametrize("n,d,block_d", [(8, 64, 64), (8, 130, 32), (5, 33, 16)])
def test_quant_gossip_kernel_matches_tile_reference(quant, n, d, block_d):
    """The fused quantized kernel's in-register per-tile statistics must match
    `tile_compress` chained per round, including the masked ragged tail."""
    sched = mixing.schedule("ring", n)
    x = _x(n, d, seed=20)
    got = gossip_mix_quant_pallas(x, tuple(s for s, _ in sched),
                                  tuple(w for _, w in sched), 3, quant,
                                  block_d=block_d, interpret=True)
    want = ref.gossip_mix_quant_ref(x, sched, 3, quant, block_d=block_d)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_quant_gossip_kernel_valid_d_masks_pad_columns():
    """Zero pad columns past valid_d must not perturb any tile statistic:
    kernel output on the padded buffer == reference on the unpadded one."""
    n, d, pad = 8, 40, 9
    sched = mixing.schedule("circulant2", n)
    x = _x(n, d, seed=21)
    xp = jnp.pad(x, ((0, 0), (0, pad)))
    got = gossip_mix_quant_pallas(xp, tuple(s for s, _ in sched),
                                  tuple(w for _, w in sched), 2, "sign",
                                  block_d=16, valid_d=d, interpret=True)
    want = ref.gossip_mix_quant_ref(xp, sched, 2, "sign", block_d=16,
                                    valid_d=d)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    # the unmasked kernel would fold the zeros into the mean-|x| scale
    unmasked = gossip_mix_quant_pallas(xp, tuple(s for s, _ in sched),
                                       tuple(w for _, w in sched), 2, "sign",
                                       block_d=16, interpret=True)
    assert not np.allclose(np.asarray(got)[:, :d], np.asarray(unmasked)[:, :d],
                           atol=1e-6)


def test_quant_kernel_rejects_stochastic():
    with pytest.raises(ValueError):
        gossip_mix_quant_pallas(_x(4, 8), (0, 1), (0.5, 0.5), 1, "int8_stoch",
                                interpret=True)


# ---------------------------------------------------------------------------
# Stochastic int8 compressor (threefry-keyed)
# ---------------------------------------------------------------------------

def test_int8_stoch_rounds_to_adjacent_levels():
    x = _x(1, 400, seed=22)[0]
    key = jax.random.PRNGKey(3)
    out = quantize.int8_stoch_compress(x, key=key)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-12) / 127.0
    q = np.asarray(out / scale)
    # every dequantized value is an integer level adjacent to x/scale
    np.testing.assert_allclose(q, np.round(q), atol=1e-4)
    assert np.all(np.abs(q - np.asarray(x / scale)) <= 1.0 + 1e-4)
    # keyed: deterministic under the same key, different under another
    out2 = quantize.int8_stoch_compress(x, key=key)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out2))
    out3 = quantize.int8_stoch_compress(x, key=jax.random.PRNGKey(4))
    assert not np.array_equal(np.asarray(out), np.asarray(out3))


def test_int8_stoch_is_unbiased():
    """E[dequant] = x: averaging over many keys shrinks the rounding error."""
    x = _x(1, 64, seed=23)[0]
    outs = np.stack([np.asarray(quantize.int8_stoch_compress(
        x, key=jax.random.PRNGKey(k))) for k in range(200)])
    scale = float(jnp.max(jnp.abs(x))) / 127.0
    bias = np.abs(outs.mean(0) - np.asarray(x))
    assert np.max(bias) < 0.25 * scale  # ~4 sigma of the mean of 200 draws


def test_int8_stoch_selectable_via_config_and_still_averages():
    n = 8
    v = _x(n, 16, seed=24)
    cfg = AveragingConfig(mode="gossip", rounds=40, topology="ring",
                          quantization="int8_stoch")
    out = averaging.gossip_average({"g": v}, n, cfg)["g"]
    bar = jnp.mean(v, axis=0)
    rel = jnp.linalg.norm(out - bar[None]) / jnp.linalg.norm(bar)
    # stochastic rounding injects unbiased per-round noise, so the residual
    # floor is higher than the deterministic compressor's
    assert rel < 0.05


# ---------------------------------------------------------------------------
# Hierarchical padding: pad columns masked out of compressor statistics
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("quant", ["sign", "int8"])
@pytest.mark.parametrize("per_pod,feat", [(3, 7), (4, 5)])
def test_hierarchical_quantized_padded_matches_unpadded_broadcast(
        quant, per_pod, feat):
    """Regression (pad-perturbation fix): the zero-padded reduce-scatter form
    must equal the unpadded broadcast-then-gossip oracle for quantized
    configs — the pad columns may not leak into the compressor statistics."""
    pods = 4
    n = pods * per_pod
    v = _x(n, feat, seed=25)
    cfg = AveragingConfig(mode="hierarchical", rounds=3, topology="ring",
                          quantization=quant)
    got = np.asarray(averaging.hierarchical_average({"g": v}, pods, per_pod,
                                                    cfg)["g"])
    # oracle: unpadded broadcast form — full pod means gossiped with
    # global-stats compression over [pods, feat]
    pm = jnp.mean(v.reshape(pods, per_pod, feat), axis=1)
    compress = COMPRESSORS[quant]
    sched = mixing.schedule("ring", pods)
    x = pm
    for _ in range(cfg.rounds):
        out = None
        for s, w in sched:
            m = x if s == 0 else compress(jnp.roll(x, s, axis=0))
            term = w * m
            out = term if out is None else out + term
        x = out
    want = np.repeat(np.asarray(x), per_pod, axis=0)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# Quantized configs: per-round semantics, bit-identical to pre-refactor code
# ---------------------------------------------------------------------------

def _legacy_gossip_average(tree, n_nodes, cfg):
    """The pre-refactor implementation, verbatim (per-round roll loop with the
    compressor applied to every non-self message, every round)."""
    sched = mixing.schedule(cfg.topology, n_nodes, cfg.self_weight)
    compress = COMPRESSORS[cfg.quantization]

    def _roll_mix(x):
        out = None
        for shift, w in sched:
            msg = x if shift == 0 else compress(jnp.roll(x, shift, axis=0))
            term = w * msg
            out = term if out is None else out + term
        return out

    def mix(g):
        for _ in range(cfg.rounds):
            g = _roll_mix(g)
        return g

    return jax.tree.map(mix, tree)


@pytest.mark.parametrize("quant", ["sign", "int8"])
@pytest.mark.parametrize("topo", ["ring", "circulant2"])
def test_quantized_gossip_bit_identical_to_legacy(quant, topo):
    n = 8
    cfg = AveragingConfig(mode="gossip", rounds=5, topology=topo,
                          quantization=quant)
    tree = {"g": _x(n, 40, seed=7), "h": _x(n, 9, seed=8)}
    got = averaging.gossip_average(tree, n, cfg)
    want = _legacy_gossip_average(tree, n, cfg)
    for k in tree:
        np.testing.assert_array_equal(np.asarray(got[k]), np.asarray(want[k]))


def test_quantized_mix_op_keeps_per_round_operator():
    """No collapsing under quantization: the fused operator must be absent,
    and the result must differ from applying the (linear) collapsed operator."""
    n, rounds = 8, 5
    sched = mixing.schedule("ring", n)
    op = mixing.circulant_mix_op(sched, n, rounds, quantization="sign")
    assert op.fused_sched is None and op.A_eff is None
    x = _x(n, 16, seed=9)
    collapsed = mixing.circulant_mix_op(sched, n, rounds)(x)
    assert not np.allclose(np.asarray(op(x)), np.asarray(collapsed), atol=1e-4)


def test_unquantized_gossip_average_matches_legacy_loop():
    """Fused (default) unquantized gossip == per-round loop to float accuracy."""
    n = 12
    cfg = AveragingConfig(mode="gossip", rounds=8, topology="torus")
    tree = {"g": _x(n, 30, seed=10)}
    got = averaging.gossip_average(tree, n, cfg)["g"]
    want = _legacy_gossip_average(tree, n, cfg)["g"]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-6)


def test_hierarchical_average_uses_engine():
    n, pods = 8, 2
    v = _x(n, 4, seed=11)
    cfg = AveragingConfig(mode="hierarchical", rounds=50, topology="ring")
    out = np.asarray(averaging.hierarchical_average({"g": v}, pods, n // pods,
                                                    cfg)["g"])
    np.testing.assert_allclose(out, np.tile(np.asarray(v).mean(0), (n, 1)),
                               atol=1e-5)


# ---------------------------------------------------------------------------
# Driver integration: run_dsgd through the fused engine
# ---------------------------------------------------------------------------

def test_run_dsgd_fused_matches_unfused():
    from repro.core import problems
    rng = np.random.default_rng(12)
    n, d, B = 8, 5, 16
    A = jnp.asarray(mixing.random_regular_expander(n, deg=4, seed=2), jnp.float32)
    w_star = jnp.asarray(rng.normal(size=(d + 1,)).astype(np.float32))

    def draw(key, m):
        x = jax.random.normal(key, (m, d))
        y = jnp.sign(x @ w_star[:-1] + w_star[-1])
        return x, y

    grad = lambda w, x, y: problems.logistic_grad(w, x, y)
    kw = dict(B=B, rounds=6, steps=20, stepsize=lambda t: 0.5 / jnp.sqrt(t),
              seed=3)
    w0 = jnp.zeros(d + 1)
    fused = dsgd.run_dsgd(grad, draw, w0, A, **kw)
    unfused = dsgd.run_dsgd(grad, draw, w0, A,
                            mix=mixing.dense_mix_op(A, 6, fuse=False), **kw)
    np.testing.assert_allclose(np.asarray(fused.w), np.asarray(unfused.w),
                               rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# Per-step PRNG key for stochastic compressors (ROADMAP caveat (4) from PR 3)
# ---------------------------------------------------------------------------

def _stoch_mix(stats: str = "global"):
    sched = mixing.schedule("ring", 4)
    return mixing.circulant_mix_op(sched, 4, rounds=3,
                                   quantization="int8_stoch", stats=stats,
                                   seed=11)


@pytest.mark.parametrize("stats", ["global", "segment", "tile"])
def test_mix_op_per_step_key_overrides_static_seed(stats):
    """key=None reproduces the seed-derived noise bit-identically (today's
    static behavior); distinct per-step keys draw fresh per-round noise; and
    passing the seed-derived key explicitly is the identity of the default."""
    mix = _stoch_mix(stats)
    x = jax.random.normal(jax.random.PRNGKey(5), (4, 96))
    kw = {"seg_widths": (32, 64)} if stats == "segment" else {}
    default = np.asarray(mix(x, **kw))
    np.testing.assert_array_equal(default, np.asarray(mix(x, **kw)))
    np.testing.assert_array_equal(
        default, np.asarray(mix(x, key=jax.random.PRNGKey(mix.seed), **kw)))
    stepped = np.asarray(mix(x, key=jax.random.PRNGKey(123), **kw))
    assert not np.array_equal(default, stepped)
    # still a consensus operator: column sums (the network average) preserved
    # in expectation — sanity-check magnitudes stay comparable
    np.testing.assert_allclose(stepped.mean(), default.mean(), atol=0.05)


def test_mix_op_key_ignored_by_deterministic_compressors():
    sched = mixing.schedule("ring", 4)
    mix = mixing.circulant_mix_op(sched, 4, rounds=2, quantization="int8")
    x = jax.random.normal(jax.random.PRNGKey(6), (4, 32))
    np.testing.assert_array_equal(
        np.asarray(mix(x)), np.asarray(mix(x, key=jax.random.PRNGKey(42))))


def test_averaging_threads_per_step_key():
    """`average_gradients(..., key=)` reaches the compressor: two steps with
    different keys mix differently, key=None stays the static sequence (what
    a lax.scan over steps used to replay every step)."""
    cfg = AveragingConfig(mode="gossip", rounds=2, quantization="int8_stoch",
                          quant_stats="segment")
    tree = {"a": jax.random.normal(jax.random.PRNGKey(0), (4, 24)),
            "b": jax.random.normal(jax.random.PRNGKey(1), (4, 8))}
    mix = averaging.make_gossip_mix(cfg, 4)
    k0, k1 = jax.random.PRNGKey(100), jax.random.PRNGKey(101)
    s0 = averaging.average_gradients(tree, cfg, n_nodes=4, mix=mix, key=k0)
    s0b = averaging.average_gradients(tree, cfg, n_nodes=4, mix=mix, key=k0)
    s1 = averaging.average_gradients(tree, cfg, n_nodes=4, mix=mix, key=k1)
    static = averaging.average_gradients(tree, cfg, n_nodes=4, mix=mix)
    np.testing.assert_array_equal(np.asarray(s0["a"]), np.asarray(s0b["a"]))
    assert not np.array_equal(np.asarray(s0["a"]), np.asarray(s1["a"]))
    np.testing.assert_array_equal(
        np.asarray(static["a"]),
        np.asarray(averaging.average_gradients(tree, cfg, n_nodes=4,
                                               mix=mix)["a"]))
