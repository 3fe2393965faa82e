"""chip_smoke.py's phases on the CPU at a tiny size (the chip runs them at
published widths; the four-chip path on 4 virtual devices), and its refusal
to run without a TPU.

The sizes are steered from here through the phase functions' arguments; the
script itself has no option for them."""
import importlib.util
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    # the launchers wire their process environment when imported; keep that
    # from leaking into later tests of this worker
    saved = dict(os.environ)
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    yield mod
    os.environ.clear()
    os.environ.update(saved)


def test_lm_then_serve_phase_tiny(smoke):
    cfg, snap, lm = smoke.lm_phase(layers=1, seq=32, batch=2, superstep=4,
                                   supersteps=3, reduced=True)
    assert cfg.num_layers == 1 and len(lm["losses"]) == 3
    assert lm["published_version"] >= 1
    out = smoke.serve_phase(cfg, snap.params, slots=2, requests=3,
                            prompt_len=8, new_tokens=4)
    assert len(out["tokens_req0"]) == 4 and out["decode_steps"] >= 4


def test_pca_phase_tiny(smoke):
    out = smoke.pca_phase(dim=64, n_nodes=4, batch=32, superstep=4,
                          supersteps=3, fuse_xi=True, interpret=True)
    assert out["risks"][-1] < out["risk0"]
    assert out["kernel_max_err"] <= 1e-4 * out["kernel_scale"] + 1e-6


FOUR_CHIP_WORKER = """
import json, sys
sys.path.insert(0, {root!r})
import chip_smoke
out = chip_smoke.four_chip_phase(layers=1, seq=32, batch_per_node=2,
                                 superstep=4, supersteps=3, reduced=True)
print(json.dumps(out))
"""


def test_four_chip_phase_on_virtual_devices():
    """The --four-chips path on 4 virtual CPU devices (the device count is
    fixed when jax starts, hence the child process, pinned to the CPU)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(ROOT, "src"))
    p = subprocess.run([sys.executable, "-c",
                        FOUR_CHIP_WORKER.format(root=ROOT)],
                       capture_output=True, text=True, env=env, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["impl"] == "shard" and out["mix_max_err"] == 0.0
    assert all(0.0 < c < 1.0 for c in out["consensus_err"])


def test_main_refuses_to_run_without_a_tpu(smoke, capsys):
    with pytest.raises(SystemExit) as e:
        smoke.main([])
    assert e.value.code not in (0, None)
    assert "no TPU chip" in str(e.value.code)
    assert '"ok"' not in capsys.readouterr().out  # prints no result
