"""The attribution of a trace to the program's host spans and device scopes
(bench/spans.py): on hand-built planes, on the committed v5e traces, on a
CPU trace of the program's own scoped gossip superstep, and through the
harness's traced run."""
import glob
import os
import shutil
import time
from types import SimpleNamespace as NS

import pytest

from bench_tiny import ROOT, TINY_LIMITS, tiny

from bench import spans, trace_reduce

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
MATMUL = os.path.join(DATA, "v5e_matmul_3steps.xplane.pb")
SCOPED = os.path.join(DATA, "v5e_scoped_step.xplane.pb.gz")


def ev(name, start, dur, **stats):
    return NS(name=name, start_ns=start, duration_ns=dur, stats=list(stats.items()))


def plane(name, lines):
    return NS(name=name, lines=[NS(name=k, events=v) for k, v in lines])


def fake():
    """Window 0-100 ns (two harness spans). The consumer takes superstep 0
    (wait 0-30, dispatch 30-35, fetch 35-45, observe 45-48) and superstep 1
    (wait 50-80, dispatch 80-85, fetch 85-97, observe 97-98); the producer
    deals superstep 1 (two draws 5-25, stack 25-40, split 40-41, stage
    41-45, put wait 45-50) and superstep 2 (draws 52-60, stack 60-99); of
    superstep 0 only one draw was traced. The chip runs 35-45 and 85-95."""
    c = [ev("driver.superstep", 0, 50), ev("driver.superstep", 50, 50),
         ev("stream.superstep", 0, 50, step_num=0), ev("stream.ring_wait", 0, 30, step=0),
         ev("stream.dispatch", 30, 5, step=0), ev("stream.fetch", 35, 10, step=0),
         ev("stream.observe", 45, 3, step=0),
         ev("stream.superstep", 50, 49, step_num=1), ev("stream.ring_wait", 50, 30, step=1),
         ev("stream.dispatch", 80, 5, step=1), ev("stream.fetch", 85, 12, step=1),
         ev("stream.observe", 97, 1, step=1)]
    p = [ev("stream.draw", 1, 3, step=0),
         ev("stream.draw", 5, 10, step=1), ev("stream.draw", 15, 10, step=1),
         ev("stream.stack", 25, 15, step=1), ev("stream.node_split", 40, 1, step=1),
         ev("stream.stage", 41, 4, step=1), ev("stream.ring_put_wait", 45, 5, step=1),
         ev("stream.draw", 52, 4, step=2), ev("stream.draw", 56, 4, step=2),
         ev("stream.stack", 60, 39, step=2)]
    host = plane("/host:CPU", [("python", c), ("python", p)])
    tpu = plane("/device:TPU:0", [
        ("XLA Ops", [ev("%fusion.1 = f32[4] fusion(%a)", 35, 10),
                     ev("%fusion.2 = f32[4] fusion(%b)", 85, 10)]),
        ("XLA Modules", [ev("jit_superstep(7)", 35, 10), ev("jit_superstep(7)", 85, 10)])])
    return NS(planes=[host, tpu])


def test_host_spans_by_hand():
    t = spans.read(fake())
    assert (t.lo, t.hi, t.windows) == (0, 100, 2)
    assert spans.host_ms(t, ("stream.ring_wait",)) == pytest.approx(30e-6)
    assert spans.host_ms(t, ("stream.fetch",)) == pytest.approx(11e-6)
    # superstep 0 lacks a draw, superstep 2 its stage: only 1 counts
    assert spans.host_seconds(t, spans.SPLITTER) == {1: pytest.approx(36e-9)}
    assert spans.host_ms(t, ("stream.stage",)) == pytest.approx(4e-6)
    assert spans.host_ms(t, ("stream.node_split", "stream.ring_put_wait")) == pytest.approx(6e-6)
    gaps = spans.idle_gaps_program(t)
    # 45-85: the wait for superstep 1 while superstep 2 is stacked; 0-35:
    # the wait for superstep 0 while superstep 1 is drawn; 95-100: a fetch
    assert gaps == [("stream.ring_wait <- stream.stack", pytest.approx(40e-9)),
                    ("stream.ring_wait <- stream.draw", pytest.approx(35e-9)),
                    ("stream.fetch <- stream.stack", pytest.approx(5e-9))]
    # the same gaps, in the same order, as the harness's breakdown
    s = trace_reduce.reduce(fake())
    assert [g for _, g in s.gaps] == [pytest.approx(g) for _, g in gaps]


def test_host_spans_absent_read_nothing():
    t = spans.read(trace_reduce_fake_without_stream())
    assert spans.host_ms(t, ("stream.ring_wait",)) is None
    assert spans.host_ms(None, spans.SPLITTER) is None
    assert [label for label, _ in spans.idle_gaps_program(t)] == ["outside spans"] * 2


def trace_reduce_fake_without_stream():
    host = plane("/host:CPU", [("python", [ev("driver.superstep", 0, 100)])])
    tpu = plane("/device:TPU:0", [("XLA Ops", [ev("fusion.1", 40, 10)])])
    return NS(planes=[host, tpu])


@pytest.mark.parametrize("op_name,want", [
    ("jit(superstep)/while/body/closed_call/vmap(jvp(fwd))/dot_general", "fwd"),
    ("jit(superstep)/while/body/closed_call/vmap(transpose(jvp(fwd)))/add", "bwd"),
    ("jit(superstep)/while/body/vmap(transpose(jvp(fwd)))/while/body/closed_call/"
     "checkpoint/rematted_computation/dot_general", "bwd"),
    ("jvp(fwd)/checkpoint/rematted_computation/dot_general", "bwd"),
    ("checkpoint/rematted_computation", None),  # no scope: a program without them
    ("jit(superstep)/while/body/closed_call/optimizer/vmap()/sqrt", "optimizer"),
    ("jit(superstep)/while/body/closed_call/gossip.mix/shard_map/ppermute", "gossip"),
    ("jit(superstep)/while/body/closed_call/gossip.pack/concatenate", "gossip"),
    ("jit(superstep)/while/body/closed_call/consensus_error/reduce_max", "consensus_error"),
    ("jit(superstep)/while/body/closed_call/and", None),
])
def test_bucket(op_name, want):
    assert spans.bucket(op_name) == want


def _pb(*fields):
    """A serialized protobuf message from (field number, value) pairs: an
    int is a varint; bytes, a str or a list of ints (packed) are
    length-delimited."""
    def varint(v):
        out = b""
        while True:
            out += bytes([(v & 0x7F) | (0x80 if v > 0x7F else 0)])
            v >>= 7
            if not v:
                return out
    out = b""
    for f, v in fields:
        if isinstance(v, int):
            out += varint(f << 3) + varint(v)
            continue
        if isinstance(v, str):
            v = v.encode()
        elif isinstance(v, list):
            v = b"".join(varint(x) for x in v)
        out += varint(f << 3 | 2) + varint(len(v)) + v
    return out


def _inst(name, opcode, op_name=None, calls=None):
    fields = [(1, name), (2, opcode)]
    if op_name:
        fields.append((7, _pb((2, op_name))))
    if calls:
        fields.append((38, calls))
    return _pb(*fields)


# a fusion without metadata of its own (2 of the 3 instructions it fuses
# are the optimizer's), an all-reduce of the diagnostic, a bare copy
MODULE = _pb(
    (1, "jit_superstep"),
    (3, _pb((1, "fused_computation"), (5, 2),
            (2, _inst("param_0", "parameter")),
            (2, _inst("a.1", "add", "jit(superstep)/optimizer/add")),
            (2, _inst("m.1", "multiply", "jit(superstep)/optimizer/mul")),
            (2, _inst("s.1", "subtract", "jit(superstep)/vmap(jvp(fwd))/sub")))),
    (3, _pb((1, "main"), (5, 1),
            (2, _inst("x", "parameter")),
            (2, _inst("fusion.3", "fusion", calls=[2])),
            (2, _inst("all-reduce.7", "all-reduce",
                      "jit(superstep)/consensus_error/reduce_max")),
            (2, _inst("copy.1", "copy")))))


def test_scope_map_by_hand():
    m = spans.scope_map(MODULE)
    assert m["fusion.3"] == "optimizer"
    assert m["all-reduce.7"] == "consensus_error"
    assert m["s.1"] == "fwd" and m["copy.1"] == "other" and m["x"] == "other"
    assert spans.instruction("%all-reduce.7 = f32[4]{0} all-reduce(%fusion.3)") == "all-reduce.7"
    assert spans.instruction("copy.1") == "copy.1"


def test_programs_read_from_the_metadata_plane():
    """The program a trace holds: an event metadata entry of the
    `/host:metadata` plane whose `Hlo Proto` stat wraps the module."""
    stat_meta = _pb((1, 1), (2, _pb((1, 7), (2, "Hlo Proto"))))
    event_meta = _pb((1, 9), (2, _pb((1, 9), (2, "jit_superstep(9)"),
                                     (5, _pb((1, 7), (6, _pb((1, MODULE))))))))
    other = _pb((1, _pb((2, "/device:TPU:0"))))
    raw = other + _pb((1, _pb((2, "/host:metadata"), (4, event_meta), (5, stat_meta))))
    assert spans.programs(raw) == {"jit_superstep(9)": MODULE}


def test_committed_trace_reduces_as_before():
    """The harness's reduction of the committed trace is what it was before
    the program's spans existed; the new reduction leaves it alone."""
    s = trace_reduce.reduce_file(MATMUL)
    assert (s.window_s, s.busy_s, s.collective_s, s.collective_exposed_s,
            s.devices, s.spans) == (0.00737941, pytest.approx(2.9905e-05), 0.0, 0.0, 1, 3)
    assert s.op_s == {"copy-start (tuple) copy-start": pytest.approx(2.6e-08),
                      "copy-done bf16[1024,1024] copy-done": pytest.approx(5e-09),
                      "fusion bf16[1024] fusion": pytest.approx(2.9874e-05)}
    assert s.modules == {"jit__lambda(18407021715937434386)": (2, pytest.approx(2.9918e-05))}
    b = s.breakdown()
    assert [k for k, _ in b["device_ops"]] == [
        "fusion bf16[1024] fusion", "copy-start (tuple) copy-start",
        "copy-done bf16[1024,1024] copy-done"]
    want_gaps = [0.003206259, 0.002184447, 0.001958794, 2e-09, 1e-09, 1e-09, 1e-09]
    assert b["idle_gaps"] == [["host in traffic.draw", pytest.approx(g)] for g in want_gaps]
    t = spans.load(MATMUL)
    assert spans.idle_gaps_program(t) == [("outside spans", pytest.approx(g))
                                          for g in want_gaps]
    assert spans.host_ms(t, ("stream.ring_wait",)) is None


def test_recorded_v5e_scoped_step():
    """A toy superstep with the program's scope names, traced on a v5e chip
    (bench/tests/record_scoped_step.py): the trace holds the program that
    ran, every operation of the module maps to a bucket, each bucket holds
    time, and the buckets sum to the module's time within 1%."""
    t = spans.load(SCOPED)
    assert list(t.programs) == ["jit_superstep(15843446665794253186)"]
    att = spans.attribute(t)
    assert att.executions >= 2 and att.unmapped == 0
    assert all(att.buckets[b] > 0 for b in spans.BUCKETS)
    assert sum(att.buckets.values()) == pytest.approx(att.module_s, rel=0.01)


def test_cpu_scoped_gossip_step(tmp_path):
    """The program's own gossip step (4 nodes on one device, remat on; one
    round, as the CPU's trace leaves out the operations of a scan's body),
    traced on the CPU: the trace holds the executable that ran, whose map
    names every operation (a CPU trace has no module line to find it by, so
    the map is given), each lands in one bucket, and the buckets sum to the
    operations' busy time within 1%."""
    import dataclasses

    import jax
    import numpy as np

    from repro.configs import get_config, reduced
    from repro.configs.base import SHAPES, AveragingConfig, RunConfig
    from repro.launch.mesh import make_mesh
    from repro.launch.sharding import activation_rules
    from repro.models.common import mesh_rules
    from repro.train.trainer import build_train_step, init_state, replicate_for_nodes

    cfg = dataclasses.replace(reduced(get_config("granite-8b"), layers=1, d_model=16),
                              vocab_size=32, d_ff=32)
    run = RunConfig(model=cfg, shape=SHAPES["train_4k"],
                    averaging=AveragingConfig("gossip", 2), optimizer="adam",
                    learning_rate=1e-3, param_dtype="float32", remat=True)
    mesh = make_mesh((1, 1), ("data", "model"))
    nodes, rows, seq = 4, 2, 16
    toks = np.random.default_rng(0).integers(0, 32, (nodes, rows, seq + 1))
    batch = {"tokens": toks[..., :-1], "labels": toks[..., 1:]}
    with mesh_rules(mesh, activation_rules(mesh, run.shape, node_axis=True)):
        state = replicate_for_nodes(init_state(run, jax.random.PRNGKey(0)), nodes)
        step = jax.jit(build_train_step(run, mesh, n_nodes=nodes)[0])
        compiled = step.lower(state, batch).compile()
        state, m = compiled(state, batch)
        jax.block_until_ready(m)
        trace_dir = str(tmp_path / "trace")
        with jax.profiler.trace(trace_dir):
            for _ in range(3):
                with jax.profiler.TraceAnnotation("driver.superstep"):
                    state, m = compiled(state, batch)
                    jax.block_until_ready(m)
    scopes = spans.scope_map(
        compiled.runtime_executable().hlo_modules()[0].as_serialized_hlo_module_proto())
    assert set(scopes.values()) == set(spans.BUCKETS)
    path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    t = spans.load(path)
    held = [spans.scope_map(p) for name, p in t.programs.items()
            if name.startswith("jit_train_step(")]
    assert scopes in held  # the trace holds the program that ran
    att = spans.attribute(t, scopes)
    assert att.unmapped == 0
    assert att.buckets["fwd"] > 0 and att.buckets["bwd"] > 0
    assert sum(att.buckets.values()) == pytest.approx(att.module_s, rel=0.01)


def test_readers_through_a_traced_run(tmp_path):
    """The harness's own traced run on a tiny PCA cell: the host-span
    readers find the trace it wrote and report; a trace of another run is
    passed over."""
    import jax

    from bench.harness import load_cell, run_cell

    bench_dir = str(tmp_path / "bench")
    shutil.copytree(os.path.join(ROOT, "bench"), bench_dir,
                    ignore=shutil.ignore_patterns(".jax_cache", ".traces", "__pycache__"))
    spec = tiny(load_cell("pca_gossip_n16", bench_dir=bench_dir))
    res = run_cell(spec, jax.devices()[:1], seed=2**33 + 7, seconds=1.0, trace=True,
                   t0=time.perf_counter(), limits=TINY_LIMITS["pca"])
    m = res["metrics"]
    for name in ("ring_wait_ms.pca", "splitter_ms.pca", "staging_ms.pca"):
        assert m[name]["unit"] == "ms/superstep" and m[name]["value"] > 0
    window = NS(span_s=1.0, rounds_per_superstep=8)
    stale = NS(spec=spec, trace=NS(spans=1, window_s=123.0), setup_s=1.0, window=window)
    assert spans.cell_trace(stale) is None
