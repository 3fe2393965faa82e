"""A run with the timed path broken underneath comes out not `correct`: once
for each fault the cell can have. The faults are planted in the program
(monkeypatched for the test), never in the benchmark; the cells run at a tiny
size on the CPU. The four-chip cell runs on four virtual devices in a child
process."""
import json
import os
import subprocess
import sys

import jax
import pytest

from bench_tiny import run_tiny

import repro.core.krasulina as krasulina
import repro.train.trainer as trainer


def _unchanged_lm(monkeypatch):
    real = trainer.build_superstep

    def build(*a, **kw):
        fn, spec = real(*a, **kw)
        return (lambda state, b: (state, fn(state, b)[1])), spec

    monkeypatch.setattr(trainer, "build_superstep", build)


def _double_lm(monkeypatch):
    real = trainer.make_optimizer
    monkeypatch.setattr(trainer, "make_optimizer",
                        lambda name, lr, **kw: real(name, 2 * lr, **kw))


def _unchanged_pca(monkeypatch):
    real = krasulina.build_krasulina_superstep

    def build(*a, **kw):
        fn = real(*a, **kw)
        return lambda state, b: (state, fn(state, b)[1])

    monkeypatch.setattr(krasulina, "build_krasulina_superstep", build)


def _double_pca(monkeypatch):
    real = krasulina.build_krasulina_superstep
    monkeypatch.setattr(krasulina, "build_krasulina_superstep",
                        lambda avg, n, step, **kw: real(avg, n, lambda t: 2 * step(t), **kw))


def _half_pca(monkeypatch):
    real = krasulina._gossip_xi
    monkeypatch.setattr(krasulina, "_gossip_xi",
                        lambda w, z, mix, fused, t: real(w, z[:, : z.shape[1] // 2],
                                                         mix, fused, t))


def _no_exchange_pca(monkeypatch):
    monkeypatch.setattr(krasulina, "_gossip_xi",
                        lambda w, z, mix, fused, t: jax.vmap(krasulina.krasulina_xi)(w, z))


FAULTS = [
    ("lm_exact_1chip", "state unchanged", _unchanged_lm),
    ("lm_exact_1chip", "update altered where produced", _double_lm),
    ("pca_gossip_n16", "state unchanged", _unchanged_pca),
    ("pca_gossip_n16", "update altered where produced", _double_pca),
    ("pca_gossip_n16", "half of each node's rows left out", _half_pca),
    ("pca_gossip_n16", "exchange between nodes left out", _no_exchange_pca),
]


@pytest.mark.parametrize("workload,fault,plant", FAULTS,
                         ids=[f"{w}:{f}" for w, f, _ in FAULTS])
def test_fault_is_not_correct(workload, fault, plant, monkeypatch):
    plant(monkeypatch)
    line = run_tiny(workload, seconds=0.5)
    assert line["correct"] is False, (fault, line["checks"])


FOUR = """
import json, sys
sys.path.insert(0, {tests!r})
from bench_tiny import run_tiny
import jax.numpy as jnp
import repro.train.trainer as trainer
fault = sys.argv[1]
if fault == "no_exchange":
    trainer.average_and_error = lambda tree, cfg, **kw: (tree, jnp.zeros(()))
elif fault == "unchanged":
    real = trainer.build_superstep
    def build(*a, **kw):
        fn, spec = real(*a, **kw)
        return (lambda s, b: (s, fn(s, b)[1])), spec
    trainer.build_superstep = build
print("RESULT " + json.dumps(run_tiny("lm_gossip_4chip", seconds=0.5)))
"""


@pytest.mark.parametrize("fault", ["no_exchange", "unchanged"])
def test_four_chip_fault_is_not_correct(fault):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c",
                        FOUR.format(tests=os.path.dirname(os.path.abspath(__file__))),
                        fault], capture_output=True, text=True, env=env, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1][len("RESULT "):])
    assert line["correct"] is False, (fault, line["checks"])
