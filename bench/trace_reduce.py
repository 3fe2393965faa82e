"""Reduce a profiler trace (`.xplane.pb`) to the numbers the per-layer
metrics read: device busy and idle time, time per device operation, the time
collectives run with nothing else on their chip, compiled-program (module)
times, and idle gaps labelled by what the host was doing.

The traced window runs from the start of the first `driver.superstep` host
span to the end of the last one (the harness puts one round each
`StreamingDriver.run(1)`); device events are clipped to it. A TPU's
operations are the events of the `XLA Ops` line of each `/device:TPU:<n>`
plane (they nest: a loop's event holds its body's, so per-op times are self
times), its compiled programs those of `XLA Modules`, and collectives in
flight those of `Async XLA Ops`. A collective is exposed where it runs (in
flight or waited on) while no other operation runs on its chip. On a backend without
device planes (the CPU, in tests) the host threads that run XLA programs
stand in for the device, so the reduction can be rehearsed; such numbers are
never written under a device metric's name.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Tuple

import numpy as np

TPU_PLANE = re.compile(r"^/device:TPU:\d+$")
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|collective-broadcast|ragged-all-to-all|send|recv)")
SPAN_WINDOW = "driver.superstep"
HOST_LABELS = ("traffic.draw", "driver.superstep")
STAGING = ("DevicePutWithSharding", "H2D Dispatch", "TransferToDevice")
HLO_TEXT = re.compile(r"^%?(?P<name>\S+) = (?P<rest>.*)$")


def short_name(text: str) -> str:
    """`%fusion.552 = bf16[49152,4096]{1,0:T(8,128)} fusion(...)` ->
    `fusion.552 bf16[49152,4096] fusion`: the instruction, its result shape
    without layout, and its opcode (a TPU trace names ops by their HLO text)."""
    m = HLO_TEXT.match(text)
    if not m:
        return text[:120]
    rest = m.group("rest")
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                break
        shape, tail = "(tuple)", rest[i + 1:]
    else:
        shape, _, tail = rest.partition(" ")
    opcode = tail.strip().split("(")[0]
    return f"{m.group('name')} {re.sub(r'{[^}]*}', '', shape)} {opcode}"[:120]


def _self_times(ops):
    """Per event, its duration less its direct children's (events of one line
    nest: a loop's span holds its body's ops). Returns (self_ns, is_leaf)."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    child = [0.0] * len(ops)
    leaf = [True] * len(ops)
    stack = []
    for i in order:
        _, s, e = ops[i]
        while stack and ops[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            child[stack[-1]] += e - s
            leaf[stack[-1]] = False
        stack.append(i)
    return [e - s - c for (_, s, e), c in zip(ops, child)], leaf


def _union(iv: np.ndarray) -> np.ndarray:
    """Merge [n, 2] intervals into disjoint sorted ones."""
    if len(iv) == 0:
        return iv.reshape(0, 2)
    iv = iv[np.argsort(iv[:, 0])]
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out, dtype=np.float64)


def _measure(iv: np.ndarray) -> float:
    return float(np.sum(iv[:, 1] - iv[:, 0])) if len(iv) else 0.0


def _subtract(a: np.ndarray, b: np.ndarray) -> float:
    """Measure of union(a) minus union(b)."""
    a, b = _union(a), _union(b)
    total, j = 0.0, 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                total += b[k][0] - cur
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            total += e - cur
    return total


def _clip(iv: np.ndarray, lo: float, hi: float) -> np.ndarray:
    if len(iv) == 0:
        return iv.reshape(0, 2)
    c = np.stack([np.maximum(iv[:, 0], lo), np.minimum(iv[:, 1], hi)], axis=1)
    return c[c[:, 1] > c[:, 0]]


@dataclasses.dataclass
class Device:
    name: str
    ops: List[Tuple[str, float, float]]  # (name, start_ns, end_ns)
    modules: List[Tuple[str, float, float]]
    async_ops: List[Tuple[str, float, float]] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float  # mean over devices
    collective_s: float  # mean over devices
    collective_exposed_s: float  # mean over devices
    op_s: Dict[str, float]  # mean over devices, per op name
    modules: Dict[str, Tuple[int, float]]  # name -> (count, total s), mean over devices
    gaps: List[Tuple[str, float]]  # longest idle gaps, labelled by host activity
    devices: int
    spans: int

    def idle_pct(self) -> Optional[float]:
        """Share of the window in which no operation ran on the device, mean
        over the devices."""
        if self.window_s <= 0:
            return None
        return 100.0 * (1.0 - self.busy_s / self.window_s)

    def module_mean_s(self, contains: str) -> Optional[float]:
        hits = [(n, t) for k, (n, t) in self.modules.items() if contains in k]
        count = sum(n for n, _ in hits)
        return sum(t for _, t in hits) / count if count else None

    def breakdown(self) -> dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in self.gaps[:10]]}


def _events(line):
    for e in line.events:
        yield e.name, float(e.start_ns), float(e.start_ns + e.duration_ns)


def read_planes(pd) -> Tuple[List[Device], Dict[str, List[Tuple[float, float]]]]:
    devices: List[Device] = []
    spans: Dict[str, List[Tuple[float, float]]] = {k: [] for k in HOST_LABELS + ("staging",)}
    cpu_ops: List[Tuple[str, float, float]] = []
    for plane in pd.planes:
        if TPU_PLANE.match(plane.name):
            ops, mods, asyncs = [], [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.extend(_events(line))
                elif line.name == "XLA Modules":
                    mods.extend(_events(line))
                elif line.name == "Async XLA Ops":
                    asyncs.extend(_events(line))
            devices.append(Device(plane.name, ops, mods, asyncs))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    key = "staging" if any(t in e.name for t in STAGING) else e.name
                    if key in spans:
                        spans[key].append((float(e.start_ns),
                                           float(e.start_ns + e.duration_ns)))
                    elif line.name.startswith("tf_XLAPjRtCpuClient") and any(
                            k == "hlo_op" for k, _ in e.stats):
                        cpu_ops.append((e.name, float(e.start_ns),
                                        float(e.start_ns + e.duration_ns)))
    if not devices and cpu_ops:
        devices.append(Device("cpu", cpu_ops, []))
    return devices, spans


def reduce(pd) -> Summary:
    devices, spans = read_planes(pd)
    win = np.asarray(spans[SPAN_WINDOW], dtype=np.float64).reshape(-1, 2)
    if len(win):
        lo, hi = float(win[:, 0].min()), float(win[:, 1].max())
    else:
        ends = [t for d in devices for _, s, e in d.ops for t in (s, e)]
        lo, hi = (min(ends), max(ends)) if ends else (0.0, 0.0)
    window_ns = max(hi - lo, 0.0)
    busy, coll, exposed = [], [], []
    op_s: Dict[str, float] = {}
    modules: Dict[str, Tuple[int, float]] = {}
    gaps: List[Tuple[str, float]] = []
    host = {k: _union(_clip(np.asarray(v, np.float64).reshape(-1, 2), lo, hi))
            for k, v in spans.items()}
    n = max(len(devices), 1)
    for i, d in enumerate(devices):
        all_iv = np.asarray([(s, e) for _, s, e in d.ops], np.float64).reshape(-1, 2)
        u = _union(_clip(all_iv, lo, hi))
        busy.append(_measure(u))
        self_ns, leaf = _self_times(d.ops)
        leaf = np.asarray(leaf, bool)
        names = [short_name(name) for name, _, _ in d.ops]
        is_c = np.asarray([bool(COLLECTIVE.match(nm)) for nm in names], bool)
        if len(all_iv):
            c_iv = _clip(all_iv[leaf & is_c], lo, hi)
            o_iv = _clip(all_iv[leaf & ~is_c], lo, hi)
        else:
            c_iv = o_iv = all_iv
        in_flight = np.asarray([(s, e) for name, s, e in d.async_ops
                                if COLLECTIVE.match(short_name(name))],
                               np.float64).reshape(-1, 2)
        c_iv = np.concatenate([c_iv, _clip(in_flight, lo, hi)])
        coll.append(_measure(_union(c_iv)))
        exposed.append(_subtract(c_iv, o_iv))
        for name, (_, s, e), own in zip(names, d.ops, self_ns):
            if s >= lo and e <= hi and own > 0:
                op_s[name] = op_s.get(name, 0.0) + own * 1e-9 / n
        for name, s, e in d.modules:
            if s >= lo and e <= hi:
                c, t = modules.get(name, (0, 0.0))
                modules[name] = (c + 1, t + (e - s) * 1e-9)
        if i == 0 and len(u):
            edges = np.concatenate([[lo], u.ravel(), [hi]]).reshape(-1, 2)
            for s, e in edges:
                if e > s:
                    gaps.append((_label(host, s, e), (e - s) * 1e-9))
    gaps.sort(key=lambda g: -g[1])
    modules = {k: (int(round(c / n)), t / n) for k, (c, t) in modules.items()}
    mean = lambda xs: float(np.mean(xs)) * 1e-9 if xs else 0.0
    return Summary(window_s=window_ns * 1e-9, busy_s=mean(busy), collective_s=mean(coll),
                   collective_exposed_s=mean(exposed), op_s=op_s, modules=modules,
                   gaps=gaps, devices=len(devices), spans=len(win))


def _label(host: Dict[str, np.ndarray], s: float, e: float) -> str:
    """What the host was doing during the gap [s, e]: drawing traffic, or
    staging onto the device, where that covers a quarter of it, else the
    harness span that covers most of it, else `outside spans`."""
    cover = {name: (_measure(_clip(host[name], s, e)) if len(host[name]) else 0.0)
             for name in host}
    if cover["traffic.draw"] >= 0.25 * (e - s):
        return "host in traffic.draw"
    if cover["staging"] >= 0.25 * (e - s):
        return "host staging (device_put)"
    cover.pop("staging")
    name = max(cover, key=cover.get)
    return f"host in {name}" if cover[name] > 0 else "host outside spans"


def reduce_file(path: str) -> Summary:
    from jax.profiler import ProfileData

    return reduce(ProfileData.from_file(path))
