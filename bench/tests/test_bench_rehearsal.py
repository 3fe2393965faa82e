"""Each cell end to end at a tiny size on the CPU (the four-chip cell on four
virtual devices), the shape of its result line, and the command's refusal to
run without a TPU. The harness's look for a chip is skipped by calling
`run_cell` with the CPU's devices; the command itself never does that."""
import json
import os
import subprocess
import sys

import pytest

from bench_tiny import CONTRACT_KEYS, ROOT, run_tiny


def _shape_ok(res, trace):
    line = json.loads(json.dumps(res))
    keys = CONTRACT_KEYS + (["breakdown"] if trace else []) + ["checks"]
    assert list(line) == keys
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] >= 1
    assert all(set(m) == {"value", "unit"} for m in line["metrics"].values())
    assert all(set(c) == {"value", "limit"} for c in line["checks"].values())
    return line


@pytest.mark.parametrize("workload,rate", [("lm_exact_1chip", "lm_tokens_per_s"),
                                           ("pca_gossip_n16", "pca_samples_per_s")])
def test_one_chip_cell_end_to_end(workload, rate):
    line = _shape_ok(run_tiny(workload), trace=False)
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {rate, "update_lag_p95_ms", "setup_s"}


@pytest.mark.parametrize("workload,idle", [("lm_exact_1chip", "idle_pct.lm"),
                                           ("pca_gossip_n16", "idle_pct.pca")])
def test_one_chip_cell_traced(workload, idle):
    line = _shape_ok(run_tiny(workload, trace=True), trace=True)
    assert line["correct"] is True, line["checks"]
    assert idle in line["metrics"]
    dev = line["device"]
    assert 0 < dev["busy_s"] <= dev["window_s"]
    assert len(line["breakdown"]["device_ops"]) <= 10
    assert len(line["breakdown"]["idle_gaps"]) <= 10


FOUR = """
import json, sys
sys.path.insert(0, {tests!r})
from bench_tiny import run_tiny
print("RESULT " + json.dumps(run_tiny("lm_gossip_4chip")))
"""


def test_four_chip_cell_on_virtual_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c",
                        FOUR.format(tests=os.path.dirname(os.path.abspath(__file__)))],
                       capture_output=True, text=True, env=env, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1][len("RESULT "):])
    line = _shape_ok(line, trace=False)
    assert line["device"]["count"] == 4
    assert line["correct"] is True, line["checks"]
    assert set(line["metrics"]) == {"lm_tokens_per_s", "update_lag_p95_ms", "setup_s"}


@pytest.mark.parametrize("workload", ["lm_exact_1chip", "pca_gossip_n16"])
def test_command_refuses_to_run_without_a_tpu(workload):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(ROOT, "bench", "run.py"),
                        "--workload", workload, "--seed", str(2**33 + 1),
                        "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, env=env, timeout=300, cwd=ROOT)
    assert p.returncode != 0
    assert "no TPU chip" in p.stderr
    assert p.stdout.strip() == ""


def test_command_refuses_outside_a_checkout(tmp_path):
    """A directory with only BENCHMARK.json and bench/ but no program."""
    import shutil

    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".jax_cache", ".traces", "__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run([sys.executable, "bench/run.py", "--workload", "pca_gossip_n16",
                        "--seed", "7", "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, env=env, timeout=300,
                       cwd=tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
