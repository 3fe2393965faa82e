"""Attribute a profiler trace to the program's own host spans and device
scopes.

Host: the streaming driver's `stream.*` spans (`repro.obs`; names and
threads in docs/DESIGN.md §Observability) are events of the `/host:` planes
carrying the superstep's sequence number as the stat `step` (`step_num` on
`stream.superstep`). Per-superstep seconds are taken over the supersteps
whose spans all lie inside the traced window (`trace_reduce`'s: the
harness's `driver.superstep` spans), and, for the producer's spans, whose
full set of spans is there (a superstep whose first draws ran before the
profiler started is left out).

Device: a TPU trace names each operation by its HLO instruction text only,
so the scope of an operation comes from the `op_name` metadata of the
compiled program that ran. A TPU trace carries that program: its
`/host:metadata` plane holds, for every live executable, an event metadata
entry named as the device's module events (`jit_superstep(<program id>)`)
with the serialized `HloProto` as its `Hlo Proto` stat. `programs` reads
them from the file (the `ProfileData` API does not reach event metadata, so
a small protobuf reader does), and `scope_map` maps each instruction to a
bucket of `BUCKETS`, by its own `op_name` or, for a fusion without one, by
the most common bucket among the instructions it fuses (a CPU trace holds
its programs too, but has no module events to find them by: `attribute`
then takes the map of the program that ran). Autodiff names the
backward pass `transpose(jvp(fwd))`, and the forward recomputed under remat
(`rematted_computation` inside it) is counted in `bwd`. Every operation of
the superstep module falls in exactly one bucket; a program without the
scopes reads all `other`, and the device metrics then read nothing.

    python3 bench/spans.py <trace.xplane.pb> [--rounds K]

prints the whole attribution of one trace as JSON: host milliseconds per
span and superstep, device milliseconds per bucket and step of the
superstep's scan, the module's own time, and `idle_gaps_program`, the ten
longest idle gaps of the first chip (those of `trace_reduce`'s breakdown)
labelled by the consumer's span and the producer's span that cover most of
each, as `stream.ring_wait <- stream.stack`.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import gzip
import os
import sys
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import trace_reduce  # noqa: E402

CONSUMER = ("stream.ring_wait", "stream.dispatch", "stream.fetch", "stream.observe")
PRODUCER = ("stream.draw", "stream.stack", "stream.node_split", "stream.stage",
            "stream.ring_put_wait")
SUPERSTEP = "stream.superstep"
SPLITTER = ("stream.draw", "stream.stack", "stream.node_split")
BUCKETS = ("fwd", "bwd", "optimizer", "gossip", "consensus_error", "other")


@dataclasses.dataclass
class Span:
    name: str
    start: float  # ns
    end: float
    step: Optional[int]


@dataclasses.dataclass
class Trace:
    devices: List[trace_reduce.Device]
    lo: float  # the traced window, ns
    hi: float
    windows: int  # harness spans that make the window
    spans: List[Span]
    programs: Dict[str, bytes] = dataclasses.field(default_factory=dict)


# ------------------------------------------------------------------ reading

def read(pd, programs: Optional[Dict[str, bytes]] = None) -> Trace:
    """The devices, the traced window and the `stream.*` host spans of a
    profile (`jax.profiler.ProfileData`, or anything shaped like it), with
    the programs the file holds (`programs`)."""
    devices, harness = trace_reduce.read_planes(pd)
    win = np.asarray(harness[trace_reduce.SPAN_WINDOW], np.float64).reshape(-1, 2)
    if len(win):
        lo, hi = float(win[:, 0].min()), float(win[:, 1].max())
    else:
        ends = [t for d in devices for _, s, e in d.ops for t in (s, e)]
        lo, hi = (min(ends), max(ends)) if ends else (0.0, 0.0)
    spans = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if not e.name.startswith("stream."):
                    continue
                stats = dict(e.stats)
                step = stats.get("step", stats.get("step_num"))
                spans.append(Span(e.name, float(e.start_ns),
                                  float(e.start_ns + e.duration_ns),
                                  None if step is None else int(step)))
    return Trace(devices, lo, hi, len(win), spans, programs or {})


_CACHE: Dict[Tuple[str, float], Trace] = {}


def load(path: str) -> Trace:
    """`read` of a trace file (`.xplane.pb`, or gzipped as `.xplane.pb.gz`),
    kept for the process (every reader of one run reads the same file)."""
    from jax.profiler import ProfileData

    key = (path, os.path.getmtime(path))
    if key not in _CACHE:
        with (gzip.open if path.endswith(".gz") else open)(path, "rb") as f:
            raw = f.read()
        _CACHE.clear()
        _CACHE[key] = read(ProfileData.from_serialized_xspace(raw), programs(raw))
    return _CACHE[key]


def cell_trace(ctx) -> Optional[Trace]:
    """The trace the harness wrote for this traced run, under
    `<bench>/.traces/<cell>/` (emptied before each traced window), or None.
    A file whose window differs from the one this run's reduced trace
    (`ctx.trace`) read is another run's."""
    if ctx.trace is None:
        return None
    pattern = os.path.join(ctx.spec.bench_dir, ".traces", ctx.spec.name,
                           "**", "*.xplane.pb")
    files = sorted(glob.glob(pattern, recursive=True), key=os.path.getmtime)
    if not files:
        return None
    t = load(files[-1])
    if (t.windows, (t.hi - t.lo) * 1e-9) != (ctx.trace.spans, ctx.trace.window_s):
        return None
    return t


# --------------------------------------------------------------------- host

def _in_window(t: Trace, s: Span) -> bool:
    return s.start >= t.lo and s.end <= t.hi


def host_seconds(t: Trace, names: Sequence[str]) -> Dict[int, float]:
    """Seconds of the spans `names` per superstep (`step`), over the
    supersteps whose spans on that side (the producer's, or the consumer's
    with `stream.superstep`) all lie in the window; on the producer's side,
    only supersteps dealt whole in the trace: every draw there, and the
    `stream.stage` that ends the deal."""
    producer = any(n in PRODUCER for n in names)
    side = PRODUCER if producer else CONSUMER + (SUPERSTEP,)
    by_step: Dict[int, List[Span]] = collections.defaultdict(list)
    for s in t.spans:
        if s.step is not None and s.name in side:
            by_step[s.step].append(s)
    draws = {k: sum(s.name == "stream.draw" for s in v) for k, v in by_step.items()}
    full = max(draws.values(), default=0)
    out = {}
    for step, spans in by_step.items():
        mine = [s for s in spans if s.name in names]
        if not mine or not all(_in_window(t, s) for s in spans):
            continue
        if producer and (draws[step] != full
                         or not any(s.name == "stream.stage" for s in spans)):
            continue
        out[step] = sum((s.end - s.start) * 1e-9 for s in mine)
    return out


def host_ms(t: Optional[Trace], names: Sequence[str]) -> Optional[float]:
    """Mean milliseconds per superstep of the spans `names`; None where the
    trace has none of them."""
    if t is None:
        return None
    per = host_seconds(t, names)
    return 1e3 * float(np.mean(list(per.values()))) if per else None


def _cover(spans: List[Span], s: float, e: float) -> Dict[str, float]:
    out: Dict[str, float] = collections.defaultdict(float)
    for sp in spans:
        lo, hi = max(sp.start, s), min(sp.end, e)
        if hi > lo:
            out[sp.name] += hi - lo
    return out


def idle_gaps_program(t: Trace, n: int = 10) -> List[Tuple[str, float]]:
    """The `n` longest idle gaps of the first device in the window (the gaps
    of `trace_reduce`'s breakdown), each labelled by the consumer span and
    the producer span that cover most of it: `consumer <- producer`, one of
    them where the other covers nothing, `outside spans` where neither
    covers anything."""
    if not t.devices:
        return []
    d = t.devices[0]
    iv = np.asarray([(s, e) for _, s, e in d.ops], np.float64).reshape(-1, 2)
    u = trace_reduce._union(trace_reduce._clip(iv, t.lo, t.hi))
    if not len(u):
        return []
    edges = np.concatenate([[t.lo], u.ravel(), [t.hi]]).reshape(-1, 2)
    gaps = sorted(((s, e) for s, e in edges if e > s), key=lambda g: g[0] - g[1])[:n]
    consumer = [s for s in t.spans if s.name in CONSUMER]
    producer = [s for s in t.spans if s.name in PRODUCER]
    out = []
    for s, e in gaps:
        labels = []
        for spans in (consumer, producer):
            cover = _cover(spans, s, e)
            if cover:
                labels.append(max(cover, key=cover.get))
        out.append((" <- ".join(labels) or "outside spans", (e - s) * 1e-9))
    return out


# ------------------------------------------------------------------- device

def bucket(op_name: str) -> Optional[str]:
    """The bucket a scope path falls in; None where it names none."""
    if "consensus_error" in op_name:
        return "consensus_error"
    if "gossip." in op_name:
        return "gossip"
    if "optimizer" in op_name:
        return "optimizer"
    if "fwd" not in op_name:
        return None
    if "transpose(" in op_name or "rematted_computation" in op_name:
        return "bwd"
    return "fwd"


def _varint(b: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        x = b[i]
        i += 1
        out |= (x & 0x7F) << shift
        shift += 7
        if not x & 0x80:
            return out, i


def _fields(b: bytes):
    """(field number, value) of a serialized protobuf message: an int for a
    varint, bytes for a length-delimited field; fixed-width ones skipped."""
    i = 0
    while i < len(b):
        key, i = _varint(b, i)
        kind = key & 7
        if kind == 0:
            v, i = _varint(b, i)
        elif kind == 2:
            n, i = _varint(b, i)
            v, i = b[i:i + n], i + n
        else:
            i += 8 if kind == 1 else 4
            continue
        yield key >> 3, v


def _get(b: bytes, field: int, default=None):
    return next((v for f, v in _fields(b) if f == field), default)


def programs(raw: bytes) -> Dict[str, bytes]:
    """The serialized `HloModuleProto` of each executable in a trace's
    `/host:metadata` plane, keyed by the name the device's module events
    carry. Fields read (tensorflow/tsl xplane.proto, xla hlo.proto): XSpace
    planes 1; XPlane name 2, event_metadata 4, stat_metadata 5;
    XEventMetadata name 2, stats 5; XStat metadata_id 1, bytes_value 6;
    XStatMetadata id 1, name 2; HloProto hlo_module 1."""
    out = {}
    for f, plane in _fields(raw):
        if f != 1 or _get(plane, 2) != b"/host:metadata":
            continue
        entries = [(f2, _get(e, 2, b"")) for f2, e in _fields(plane) if f2 in (4, 5)]
        hlo = {_get(m, 1) for f2, m in entries if f2 == 5 and _get(m, 2) == b"Hlo Proto"}
        for f2, event in entries:
            if f2 != 4:
                continue
            for f3, stat in _fields(event):
                if f3 == 5 and _get(stat, 1) in hlo:
                    out[_get(event, 2, b"").decode()] = _get(_get(stat, 6, b""), 1, b"")
    return out


def scope_map(module: bytes) -> Dict[str, str]:
    """Instruction name -> bucket, for every instruction of a serialized
    `HloModuleProto` (names are unique within a module). Fields read:
    computations 3; HloComputationProto instructions 2, id 5;
    HloInstructionProto name 1, opcode 2, metadata 7, called_computation_ids
    38 (packed); OpMetadata op_name 2."""
    own: Dict[str, Optional[str]] = {}
    calls: Dict[str, List[int]] = {}
    members: Dict[int, List[str]] = {}
    for f, comp in _fields(module):
        if f != 3:
            continue
        names = members.setdefault(_get(comp, 5, 0), [])
        for g, inst in _fields(comp):
            if g != 2:
                continue
            name = _get(inst, 1, b"").decode()
            op = _get(_get(inst, 7, b""), 2)
            own[name] = bucket(op.decode()) if op else None
            if _get(inst, 2) == b"fusion":
                packed, i, ids = _get(inst, 38, b""), 0, []
                while i < len(packed):
                    v, i = _varint(packed, i)
                    ids.append(v)
                calls[name] = ids
            names.append(name)

    def fused(c: int, seen: frozenset) -> Optional[str]:
        votes = collections.Counter()
        for inst in members.get(c, ()):
            b = own[inst]
            if b is None:
                b = next((x for x in (fused(k, seen | {c}) for k in calls.get(inst, ())
                                      if k not in seen) if x), None)
            if b is not None:
                votes[b] += 1
        return votes.most_common(1)[0][0] if votes else None

    out = {}
    for name, b in own.items():
        if b is None:
            b = next((x for x in (fused(c, frozenset()) for c in calls.get(name, ())) if x),
                     None)
        out[name] = b or "other"
    return out


def instruction(event_name: str) -> str:
    """The HLO instruction an operation event names: a TPU event carries the
    instruction's text (`%fusion.3 = ...`), a CPU event its name."""
    m = trace_reduce.HLO_TEXT.match(event_name)
    return m.group("name") if m else event_name


@dataclasses.dataclass
class Attribution:
    """Device seconds of the superstep module, mean over devices."""

    executions: int  # module executions in the window, per device
    module_s: float  # their summed duration
    buckets: Dict[str, float]  # seconds per bucket (self time of each op)
    unmapped: int  # operations whose instruction the map lacks


def _module_ops(t: Trace, d: trace_reduce.Device, module: str):
    """(executions, their seconds, [(instruction, self ns, module event
    name)]) of the operations run inside a `module` execution in the window.
    On a device with no module line (the CPU stand-in) every operation of
    the window counts, and the module's time is the union of their
    intervals."""
    mods = sorted((s, e, name) for name, s, e in d.modules
                  if module in name and s >= t.lo and e <= t.hi)
    starts = [s for s, _, _ in mods]
    self_ns, _ = trace_reduce._self_times(d.ops)
    ops, spans = [], []
    for (name, s, e), own in zip(d.ops, self_ns):
        if d.modules:
            i = bisect.bisect_right(starts, s) - 1
            if i < 0 or e > mods[i][1]:
                continue
            ops.append((instruction(name), own, mods[i][2]))
        elif t.lo <= s and e <= t.hi:
            ops.append((instruction(name), own, None))
            spans.append((s, e))
    if d.modules:
        return len(mods), sum(e - s for s, e, _ in mods) * 1e-9, ops
    iv = np.asarray(spans, np.float64).reshape(-1, 2)
    return int(bool(ops)), trace_reduce._measure(trace_reduce._union(iv)) * 1e-9, ops


def attribute(t: Trace, scopes: Optional[Dict[str, str]] = None,
              module: str = "superstep") -> Optional[Attribution]:
    """Self time of every operation run inside a `module` execution in the
    window, by bucket, each operation's bucket read from the program the
    trace holds for that execution, or from `scopes` where given (the CPU
    stand-in has no module events to find the program by); None where no
    device ran the module."""
    maps: Dict[str, Dict[str, str]] = {}
    per_dev = []
    for d in t.devices:
        count, module_s, ops = _module_ops(t, d, module)
        if not count:
            continue
        sums = dict.fromkeys(BUCKETS, 0.0)
        unmapped = 0
        for inst, own, name in ops:
            if scopes is None and name not in maps:
                maps[name] = scope_map(t.programs[name]) if name in t.programs else {}
            m = maps[name] if scopes is None else scopes
            unmapped += inst not in m
            sums[m.get(inst, "other")] += own * 1e-9
        per_dev.append((count, module_s, sums, unmapped))
    if not per_dev:
        return None
    n = len(per_dev)
    return Attribution(
        executions=int(round(sum(c for c, _, _, _ in per_dev) / n)),
        module_s=sum(m for _, m, _, _ in per_dev) / n,
        buckets={b: sum(s[b] for _, _, s, _ in per_dev) / n for b in BUCKETS},
        unmapped=max(u for _, _, _, u in per_dev))


def cell_attribution(ctx) -> Optional[Attribution]:
    """The superstep's device time by bucket in this traced run; None where
    the trace is not found or no operation carries a scope of the program
    (a program without scopes reads all `other`)."""
    t = cell_trace(ctx)
    att = attribute(t) if t is not None else None
    if att is None or not any(att.buckets[b] for b in BUCKETS[:-1]):
        return None
    return att


def step_ms(ctx, *names: str) -> Optional[float]:
    """Milliseconds per training step (a round of the superstep's scan) of
    the buckets `names`, mean over chips; None where the program's trace
    shows none of them."""
    att = cell_attribution(ctx)
    if att is None or att.executions == 0:
        return None
    s = sum(att.buckets[b] for b in names)
    if s <= 0:
        return None
    return 1e3 * s / (att.executions * ctx.window.rounds_per_superstep)


# ---------------------------------------------------------------------- CLI

def summary(t: Trace, rounds: int = 1, scopes: Optional[Dict[str, str]] = None) -> dict:
    """The whole attribution of one trace, as `main` prints it."""
    steps = {s.step for s in t.spans if s.name == SUPERSTEP and _in_window(t, s)}
    out = {"window_s": (t.hi - t.lo) * 1e-9, "supersteps": len(steps),
           "host_ms": {n: host_ms(t, (n,)) for n in CONSUMER + PRODUCER},
           "splitter_ms": host_ms(t, SPLITTER),
           "idle_gaps_program": idle_gaps_program(t)}
    att = attribute(t, scopes)
    if att is not None and att.executions:
        per = att.executions * rounds
        out["device_ms_per_step"] = {b: 1e3 * v / per for b, v in att.buckets.items()}
        out["module_ms_per_step"] = 1e3 * att.module_s / per
        out["buckets_over_module"] = sum(att.buckets.values()) / att.module_s
        out["unmapped_ops"] = att.unmapped
    return out


def main(argv=None) -> None:
    import argparse
    import json

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("trace")
    ap.add_argument("--rounds", type=int, default=8, help="rounds per superstep")
    args = ap.parse_args(argv)
    print(json.dumps(summary(load(args.trace), args.rounds), indent=1))


if __name__ == "__main__":
    main()
