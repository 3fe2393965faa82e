"""Readings of the control and of the faults that the plain reference can
stand in for, at a cell's own size, on the chips the cell asks for.

    python3 bench/control.py --workload <name> --seeds 11,12,13

For each seed it builds the cell's inputs and the program's state as a run
does (so the rows and the weights are the run's), frees the program, and
then compares, with the cell's own numbers, the float32 reference against
the reference put in the program's place: computed one precision lower (the
control), with the exchange between nodes left out, or with half of each
node's rows left out, where the cell has such a fault. Each variant's numbers
go through the same verdict as a run's, against the cell's limits
(`limits/<cell>.json`), and `correct` is printed beside them: the control and
every fault have to come out not correct. One JSON line per seed. The
benchmark's own runs never run this."""
import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
# the TPU runtime logs to a fixed path under /tmp unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def readings(spec, devices, seed: int, limits: dict) -> dict:
    from bench import compare
    from bench.generator import build_pool
    from bench.harness import family

    pool = build_pool(spec.traffic, spec.config, seed, devices[0], spec.bench_dir)
    cell = family(spec).Cell(spec, devices, seed, pool)
    cell.close()
    base = cell.reference()
    out = {"seed": seed}
    for name, kw in cell.variants().items():
        r = cell.reference(**kw)
        checks = compare.verdict(cell.gaps(base, r), limits)
        out[name] = {"correct": all(c["ok"] for c in checks.values()),
                     "checks": {k: {"value": c["value"], "limit": c["limit"]}
                                for k, c in checks.items()},
                     "worst": cell.explain(base, r)}
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)

    from bench import compare
    from bench.harness import load_cell
    from bench.run import chips

    spec = load_cell(args.workload)
    limits = compare.limits(spec.name, os.path.join(spec.bench_dir, "limits"))
    import jax

    jax.config.update("jax_compilation_cache_dir", os.path.join(BENCH, ".jax_cache"))
    devices = chips(jax, spec.chips)
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(readings(spec, devices, seed, limits)), flush=True)


if __name__ == "__main__":
    main()
