"""Test-only tiny sizes of the benchmark's cells, for rehearsals on the CPU.
Widths here are toy values; the cells themselves run at published widths."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

CONTRACT_KEYS = ["correct", "attempted", "failed", "metrics", "device"]

# Limits for the tiny sizes, read on the CPU: sound runs read at most
# 1.5e-5 (loss), 4.1e-3 (grad), 1.2e-3 (update) for the LM cells and 4.3e-7
# for PCA; every planted fault reads 0.13 or more. The cells' own limits
# (bench/limits/) are set from chip runs at their own sizes.
TINY_LIMITS = {
    "lm": {"loss": 1e-3, "grad": 2e-2, "update": 2e-2},
    "pca": {"update": 1e-5, "iterate": 1e-5},
}


def tiny(spec):
    """Shrink a loaded cell in place to a size a CPU test run holds."""
    if spec.config["harness"] == "lm":
        spec.config.update(d_model=64, num_heads=4, num_kv_heads=2, d_ff=128,
                           vocab_size=256)
        spec.traffic.update(seq_len=32, pool_sequences=32)
    else:
        spec.config.update(dim=64, nodes=4)
        spec.traffic.update(batch=32, pool_samples=1024)
    return spec


def run_tiny(workload, *, seed=2**33 + 5, seconds=1.0, trace=False, root=ROOT,
             bench_dir=None):
    import tempfile
    import time

    import jax

    from bench.harness import BENCH, load_cell, run_cell

    spec = tiny(load_cell(workload, root=root, bench_dir=bench_dir or BENCH))
    with tempfile.TemporaryDirectory() as trace_dir:
        return run_cell(spec, jax.devices()[:spec.chips], seed=seed, seconds=seconds,
                        trace=trace, t0=time.perf_counter(), trace_dir=trace_dir,
                        limits=TINY_LIMITS[spec.config["harness"]])
