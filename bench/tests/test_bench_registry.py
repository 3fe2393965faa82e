"""A later change adds a cell, a configuration, a traffic mix with a generator
of its own, a limit set and a metric by adding files and entries only: here a
throwaway cell is registered from a temporary directory, with no edit to any
existing file, and runs end to end."""
import json
import os
import shutil

from bench_tiny import ROOT, run_tiny

GENERATOR = """
import numpy as np

from bench.generator import Pool, seed_words


def build(traffic, config, seed, device):
    rng = np.random.default_rng(seed_words(seed))
    d, n = int(config["dim"]), int(traffic["pool_samples"])
    scale = np.linspace(1.0, 0.1, d).astype(np.float32)
    return Pool({"z": rng.standard_normal((n, d)).astype(np.float32) * scale})
"""


def test_new_cell_from_new_files_only(tmp_path):
    bench_dir = tmp_path / "bench"
    shutil.copytree(os.path.join(ROOT, "bench"), bench_dir,
                    ignore=shutil.ignore_patterns(".jax_cache", ".traces", "__pycache__"))
    before = {p: (bench_dir / p).read_bytes() for p in
              (str(q.relative_to(bench_dir)) for q in bench_dir.rglob("*") if q.is_file())}
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)

    # new files: a configuration, a traffic mix and its generator, limits and
    # a metric reader
    cfg = json.loads((bench_dir / "configs" / "pca-highd-3072.json").read_text())
    cfg.update(averaging={"mode": "gossip", "rounds": 2, "topology": "ring"})
    (bench_dir / "configs" / "pca-throwaway.json").write_text(json.dumps(cfg))
    (bench_dir / "generators" / "scaled_gaussian.py").write_text(GENERATOR)
    (bench_dir / "traffic" / "pca_throwaway.json").write_text(json.dumps(
        {"generator": "scaled_gaussian", "batch": 64, "pool_samples": 2048}))
    (bench_dir / "limits" / "pca_throwaway_cell.json").write_text(
        (bench_dir / "limits" / "pca_gossip_n16.json").read_text())
    (bench_dir / "metrics" / "window_supersteps.py").write_text(
        "def read(ctx):\n    return ctx.window.supersteps\n")

    # new entries in BENCHMARK.json
    bench["configs"].append({"name": "pca-throwaway", "source": "https://example.org",
                             "file": "bench/configs/pca-throwaway.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "pca_throwaway_cell", "config": "pca-throwaway",
                               "traffic": "pca_throwaway", "chips": 1, "why": "test"})
    bench["end_to_end"].append({"name": "window_supersteps", "unit": "supersteps",
                                "better": "higher", "bound": 0.25, "source": "host_clock",
                                "workloads": ["pca_throwaway_cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    line = run_tiny("pca_throwaway_cell", root=str(tmp_path), bench_dir=str(bench_dir))
    assert line["correct"] is True, line["checks"]
    assert line["metrics"]["window_supersteps"]["value"] >= 1
    for p, data in before.items():  # no existing file changed
        assert (bench_dir / p).read_bytes() == data, p


def test_every_cell_has_its_files_and_limits():
    from bench import compare
    from bench.harness import load_cell

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for w in bench["workloads"]:
        spec = load_cell(w["name"])
        family = spec.config["harness"]
        assert os.path.exists(os.path.join(ROOT, "bench", "families", f"{family}.py"))
        generator = spec.traffic["generator"]
        assert os.path.exists(os.path.join(ROOT, "bench", "generators", f"{generator}.py"))
        lim = compare.limits(w["name"])
        assert lim and all(0 < v < 1 for v in lim.values()), lim
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert os.path.exists(os.path.join(ROOT, "bench", "metrics", f"{m['name']}.py"))
    for c in bench["configs"]:
        assert os.path.exists(os.path.join(ROOT, c["file"]))
