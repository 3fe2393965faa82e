"""Chip benchmark of the streaming decentralized learner.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of `BENCHMARK.json` on the chips of the machine it starts on:
builds the cell's inputs and weights from the seed, warms up every shape
(set-up), measures for `--seconds` seconds (or, with `--trace 1`, traces a
short window and reduces it to the per-layer metrics), then checks what the
timed path produced against the plain reference. The last line of standard
output is one JSON object with `correct`, `attempted`, `failed`, `metrics`,
`device` (and with `--trace 1`, `breakdown`), then the compared numbers
beside their limits under `checks`; the same numbers are the last lines of
standard error.

Without a TPU, or with fewer chips than the cell asks for, it exits non-zero
and prints no result. JAX's compilation cache is kept at the fixed path
`bench/.jax_cache` inside the checkout, so only a cell's first run there
compiles."""
import time

T0 = time.perf_counter()

import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
# the TPU runtime logs to a fixed path under /tmp unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def chips(jax, need: int):
    """The TPU devices to use; a missing chip is an error, never a CPU run."""
    try:
        devices = jax.devices("tpu")
    except RuntimeError as e:
        raise SystemExit(f"bench: no TPU chip visible to JAX "
                         f"(default backend {jax.default_backend()}): {e}")
    if len(devices) < need:
        raise SystemExit(f"bench: the cell needs {need} TPU chips, JAX sees "
                         f"{len(devices)}")
    return devices[:need]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench.harness import load_cell, run_cell

    spec = load_cell(args.workload)
    import jax

    jax.config.update("jax_compilation_cache_dir", os.path.join(BENCH, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    devices = chips(jax, spec.chips)
    result = run_cell(spec, devices, seed=args.seed, seconds=args.seconds,
                      trace=bool(args.trace), t0=T0)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
