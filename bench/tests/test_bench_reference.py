"""The plain references against the program at a small size on the CPU: with
the program in float32 the two agree to float32 rounding, so the reference
computes what the program computes (weights from the seed, the loss, the
gradients, the gossip and the update)."""

import jax

from bench_tiny import tiny

from bench.generator import build_pool
from bench.harness import family, load_cell


def _cell(workload, **config):
    spec = tiny(load_cell(workload))
    for k, v in config.items():
        spec.config[k] = v
    devices = jax.devices()[:spec.chips]
    pool = build_pool(spec.traffic, spec.config, 2**35 + 9, devices[0])
    return family(spec).Cell(spec, devices, 2**35 + 9, pool)


def test_granite_reference_matches_the_float32_program():
    cell = _cell("lm_exact_1chip", training={
        "param_dtype": "float32", "master_weights": False, "optimizer": "adam",
        "learning_rate": 3e-5, "remat": True})
    cell.prime()
    cell.close()
    gaps = cell.gaps(cell.reference())
    assert gaps["loss"] < 1e-5 and gaps["grad"] < 1e-3 and gaps["update"] < 1e-3, gaps


def test_granite_reference_fp8_control_departs_from_it():
    cell = _cell("lm_exact_1chip")
    cell.prime()
    cell.close()
    base = cell.reference()
    sound = cell.gaps(base)
    control = cell.gaps(base, cell.reference(numerics="fp8"))
    assert control["loss"] > 3 * sound["loss"], (sound, control)


def test_krasulina_reference_matches_the_program():
    cell = _cell("pca_gossip_n16")
    cell.prime()
    cell.close()
    base = cell.reference()
    gaps = cell.gaps(base)
    assert gaps["update"] < 1e-5 and gaps["iterate"] < 1e-5, gaps
    control = cell.gaps(base, cell.reference(precision="high"))
    assert control["iterate"] > 3 * gaps["iterate"], (gaps, control)
    faults = {k: cell.gaps(base, cell.reference(**kw)) for k, kw in cell.variants().items()
              if k != "control"}
    assert all(max(g.values()) > 1e-2 for g in faults.values()), faults
