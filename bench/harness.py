"""One run of one cell: set-up, the measured (or traced) window, the memory
reading, then the comparison with the plain reference that decides
`correct`. Everything a cell is made of is found by name: the workload in
`BENCHMARK.json`, its configuration under `configs/`, its traffic mix under
`traffic/` (whose `generator` key names a module under `generators/`), its
limits under `limits/`, and each metric's reader under `metrics/`. The
configuration's `harness` key names the module under `families/` that builds
the system for it. Modules are loaded from their files, so a later change
adds one by adding a file."""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import re
import shutil
import sys
import time
from typing import Callable, Dict, List, Optional

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


@dataclasses.dataclass
class CellSpec:
    workload: dict
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    bench_dir: str = BENCH

    @property
    def name(self) -> str:
        return self.workload["name"]

    @property
    def chips(self) -> int:
        return int(self.workload["chips"])


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(workload: str, root: str = ROOT, bench_dir: str = BENCH) -> CellSpec:
    """The cell `workload` of `BENCHMARK.json` under `root`, with its files."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"bench: unknown workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    with open(os.path.join(bench_dir, "configs", f"{w['config']}.json")) as f:
        config = json.load(f)
    with open(os.path.join(bench_dir, "traffic", f"{w['traffic']}.json")) as f:
        traffic = json.load(f)
    return CellSpec(w, config, traffic,
                    [m for m in bench["end_to_end"] if _applies(m, workload)],
                    [m for m in bench["per_layer"] if _applies(m, workload)],
                    bench_dir)


class CompileClock:
    """Counts XLA backend compiles and sums their seconds (jax.monitoring)."""

    def __init__(self):
        import jax

        self.seconds, self.count = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if "backend_compile" in event:
            self.seconds += duration
            self.count += 1


def peak_bytes(devices) -> Optional[int]:
    """The largest `peak_bytes_in_use` over `devices` (None where the
    backend keeps no statistics)."""
    stats = [d.memory_stats() for d in devices]
    if any(s is None or "peak_bytes_in_use" not in s for s in stats):
        return None
    return max(int(s["peak_bytes_in_use"]) for s in stats)


@dataclasses.dataclass
class Window:
    """What one measured stretch of supersteps did, by the host clock."""

    span_s: float
    supersteps: int
    samples: int
    attempted: int
    discarded: int
    failed_supersteps: int
    lags_s: np.ndarray  # one entry per sample learned in the window
    tokens_per_sample: Optional[int]
    chips: int
    rounds_per_superstep: int

    samples_per_superstep: int = 0

    @property
    def failed(self) -> int:
        """Discarded by the splitter, plus the samples of a superstep that
        raised."""
        return self.discarded + self.failed_supersteps * self.samples_per_superstep


def drive(cell, seconds: float, *, span: Optional[Callable] = None) -> Window:
    """Call `driver.run(1)` until `seconds` have passed; the window ends with
    the first superstep that completes after that. Rates divide by the whole
    span, and every sample learned in it has its lag."""
    import contextlib

    driver, pool = cell.driver, cell.pool
    span = span or (lambda: contextlib.nullcontext())
    first = len(driver.history)
    before = driver.history[-1]["counters"] if first else None
    done: List[float] = []
    failed_supersteps = 0
    t0 = time.perf_counter()
    while True:
        try:
            with span():
                driver.run(1)
        except Exception as e:  # the window stops at a superstep that raised
            print(f"bench: superstep raised {type(e).__name__}: {e}", file=sys.stderr)
            failed_supersteps += 1
            break
        t = time.perf_counter()
        done.append(t)
        if t - t0 >= seconds:
            break
    t_end = time.perf_counter()
    recs = driver.history[first:]
    start_arrived = before.samples_arrived if before else 0
    start_consumed = before.samples_consumed if before else 0
    start_discarded = before.samples_discarded if before else 0
    last = recs[-1]["counters"] if recs else before
    log = np.asarray(list(pool.log), dtype=np.float64).reshape(-1, 2)
    ends, handed = log[:, 0], log[:, 1]
    starts = np.concatenate([[0.0], ends[:-1]])
    lags = []
    prev = start_arrived
    for rec, t in zip(recs, done):
        hi = rec["counters"].samples_arrived
        sel = (starts >= prev) & (ends <= hi)
        lags.append(np.repeat(t - handed[sel], (ends[sel] - starts[sel]).astype(np.int64)))
        prev = hi
    return Window(
        span_s=t_end - t0, supersteps=len(recs),
        samples=(last.samples_consumed - start_consumed) if recs else 0,
        attempted=((last.samples_arrived - start_arrived) if recs else 0)
        + failed_supersteps * cell.k_rounds * cell.batch,
        discarded=(last.samples_discarded - start_discarded) if recs else 0,
        failed_supersteps=failed_supersteps,
        lags_s=np.concatenate(lags) if lags else np.zeros(0),
        tokens_per_sample=cell.tokens_per_sample, chips=len(cell.devices),
        rounds_per_superstep=cell.k_rounds,
        samples_per_superstep=cell.k_rounds * cell.batch)


def settle(cell, cap: int = 16) -> int:
    """Drive supersteps until the one just finished learned only samples
    handed over after the first superstep (which compiles) returned: the
    prefetch ring then holds nothing that waited through set-up, and the
    window's lags are those of the steady loop. Returns the supersteps run."""
    driver, pool = cell.driver, cell.pool
    t_ready = time.perf_counter()
    for i in range(1, cap + 1):
        prev = driver.history[-1]["counters"].samples_arrived
        driver.run(1)
        hi = driver.history[-1]["counters"].samples_arrived
        handed = [t for end, t in list(pool.log) if prev < end <= hi]
        if handed and min(handed) >= t_ready:
            return i
    return cap


def load_module(bench_dir: str, kind: str, name: str):
    """`<bench_dir>/<kind>/<name>.py` as a module: how a family, a traffic
    generator and a metric reader are found by name."""
    path = os.path.join(bench_dir, kind, f"{name}.py")
    if not os.path.exists(path):
        raise SystemExit(f"bench: no {kind} module {name!r} at {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_" + re.sub(r"\W", "_", name), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def family(spec: CellSpec):
    """The module under `families/` that builds the system for the cell."""
    return load_module(spec.bench_dir, "families", spec.config["harness"])


def read_metrics(spec: CellSpec, metrics: List[dict], ctx) -> Dict[str, dict]:
    out = {}
    for m in metrics:
        value = load_module(spec.bench_dir, "metrics", m["name"]).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


@dataclasses.dataclass
class Context:
    """What a metric reader may read."""

    spec: CellSpec
    window: Window
    setup_s: float
    peaks: dict
    trace: Optional[object] = None


def load_peaks(kind: str, bench_dir: str = BENCH) -> dict:
    with open(os.path.join(bench_dir, "peaks.json")) as f:
        table = json.load(f)
    if kind not in table:
        raise SystemExit(f"bench: no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


def traced(cell, seconds: float, trace_dir: str):
    """A short window under the profiler, with the harness's own spans:
    `driver.superstep` around each `run(1)` and `traffic.draw` (set round
    `sample_fn` by `run_cell`). Returns the window and the reduced trace."""
    import glob

    import jax

    from bench import trace_reduce

    shutil.rmtree(trace_dir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    with jax.profiler.trace(trace_dir, profiler_options=options):
        w = drive(cell, seconds,
                  span=lambda: jax.profiler.TraceAnnotation("driver.superstep"))
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    summary = trace_reduce.reduce_file(files[0]) if files else None
    return w, summary


def run_cell(spec: CellSpec, devices, *, seed: int, seconds: float, trace: bool,
             t0: float, limits: Optional[Dict[str, float]] = None,
             trace_seconds: float = 6.0, trace_dir: Optional[str] = None) -> dict:
    """Set up, measure, check. Returns the result object (the last line)."""
    import jax

    from bench import compare
    from bench.generator import build_pool

    clock = CompileClock()
    kind = devices[0].device_kind
    peaks = load_peaks(kind, spec.bench_dir) if devices[0].platform == "tpu" else {}
    pool = build_pool(spec.traffic, spec.config, seed, devices[0], spec.bench_dir)
    if trace:
        draw = pool.sample_fn

        def sample_fn(rng, n):
            with jax.profiler.TraceAnnotation("traffic.draw"):
                return draw(rng, n)

        pool.sample_fn = sample_fn
    cell = family(spec).Cell(spec, devices, seed, pool)
    cell.prime()
    settled = settle(cell)
    setup_s = time.perf_counter() - t0
    compiles = clock.count
    summary = None
    if trace:
        trace_dir = trace_dir or os.path.join(spec.bench_dir, ".traces", spec.name)
        window, summary = traced(cell, min(seconds, trace_seconds), trace_dir)
    else:
        window = drive(cell, seconds)
    window_compiles = clock.count - compiles
    memory_peak = peak_bytes(devices[:spec.chips])
    cell.close()
    ctx = Context(spec, window, setup_s, peaks, summary)
    metrics = read_metrics(spec, spec.per_layer if trace else spec.end_to_end, ctx)
    print(json.dumps({
        "supersteps": window.supersteps, "samples": window.samples,
        "lag_samples": int(window.lags_s.size), "window_s": window.span_s,
        "compiles_in_window": window_compiles, "compile_s_setup": clock.seconds,
        "settle_supersteps": settled,
    }), flush=True)
    t_ref = time.perf_counter()
    ref = cell.reference()
    gaps = cell.gaps(ref)
    print(json.dumps({"worst": cell.explain(ref)}), flush=True)
    lim = (limits if limits is not None else
           compare.limits(spec.name, os.path.join(spec.bench_dir, "limits")))
    checks = compare.verdict(gaps, lim)
    print(json.dumps({"reference_s": time.perf_counter() - t_ref}), flush=True)
    failed = window.failed
    correct = (all(c["ok"] for c in checks.values()) and window.failed_supersteps == 0
               and window.supersteps > 0)
    dev = devices[0]
    device = {"platform": dev.platform, "kind": kind, "count": spec.chips,
              "memory_peak_bytes": memory_peak}
    if trace and summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
    result = {"correct": bool(correct), "attempted": int(window.attempted),
              "failed": int(failed), "metrics": metrics, "device": device}
    if trace and summary is not None:
        result["breakdown"] = summary.breakdown()
    result["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                        for k, c in checks.items()}
    for k, c in checks.items():
        print(f"check {k}: {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if c['ok'] else 'FAIL'}", file=sys.stderr)
    return result
