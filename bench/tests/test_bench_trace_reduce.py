"""The trace reduction on hand-built planes (exact arithmetic) and on a small
trace recorded on a TPU v5e chip (bench/tests/data/)."""
import glob
import os
from types import SimpleNamespace as NS

import pytest

from bench_tiny import ROOT  # noqa: F401  (puts the checkout on sys.path)

from bench import trace_reduce

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def ev(name, start, dur, **stats):
    return NS(name=name, start_ns=start, duration_ns=dur, stats=list(stats.items()))


def plane(name, lines):
    return NS(name=name, lines=[NS(name=k, events=v) for k, v in lines.items()])


def fake():
    """Window 0-100 ns (two superstep spans). Chip 0 runs a loop (10-40)
    holding a fusion (10-25) and a collective's done-wait (25-40), a fusion
    at 60-70 while a collective is in flight 50-70, and a fusion from 95 on;
    chip 1 runs one fusion, 20-30."""
    host = plane("/host:CPU", {
        "python": [ev("driver.superstep", 0, 45), ev("driver.superstep", 45, 55)],
        "device-prefetch": [ev("traffic.draw", 72, 20)]})
    tpu0 = plane("/device:TPU:0", {
        "XLA Ops": [ev("%while.4 = (s32[]) while(%t)", 10, 30),
                    ev("%fusion.1 = bf16[8,4]{1,0:T(8,128)} fusion(%a), kind=kOutput", 10, 15),
                    ev("%collective-permute-done.3 = f32[4] collective-permute-done(%s)", 25, 15),
                    ev("fusion.3", 60, 10), ev("fusion.9", 95, 20)],
        "Async XLA Ops": [ev("%all-reduce-start.2 = f32[4] all-reduce-start(%x)", 50, 20)],
        "XLA Modules": [ev("jit_superstep(1)", 10, 60)]})
    tpu1 = plane("/device:TPU:1", {"XLA Ops": [ev("fusion.3", 20, 10)],
                                   "XLA Modules": [ev("jit_superstep(1)", 20, 10)]})
    return NS(planes=[host, tpu0, tpu1])


def test_reduce_by_hand():
    s = trace_reduce.reduce(fake())
    assert s.devices == 2 and s.spans == 2
    assert s.window_s == pytest.approx(100e-9)
    # chip 0: 10-40, 60-70, 95-100 (clipped) = 45; chip 1: 10
    assert s.busy_s == pytest.approx((45 + 10) / 2 * 1e-9)
    # chip 0: the done-wait 25-40 and the all-reduce in flight 50-70
    assert s.collective_s == pytest.approx(35 / 2 * 1e-9)
    # exposed: 25-40 and 50-60 (60-70 overlaps fusion.3)
    assert s.collective_exposed_s == pytest.approx(25 / 2 * 1e-9)
    # self times: the loop holds nothing of its own
    assert "while.4 (tuple) while" not in s.op_s
    assert s.op_s["fusion.1 bf16[8,4] fusion"] == pytest.approx(15 / 2 * 1e-9)
    assert s.op_s["fusion.3"] == pytest.approx((10 + 10) / 2 * 1e-9)
    assert s.module_mean_s("superstep") == pytest.approx(35e-9)
    # chip 0's gaps: 0-10 and 40-60 (superstep), 70-95 (draw covers 20 of 25)
    assert s.gaps[0] == ("host in traffic.draw", pytest.approx(25e-9))
    assert s.gaps[1] == ("host in driver.superstep", pytest.approx(20e-9))
    assert s.gaps[2] == ("host in driver.superstep", pytest.approx(10e-9))
    b = s.breakdown()
    assert b["device_ops"][0][0] == "fusion.3" and len(b["idle_gaps"]) == 3


def test_short_name():
    text = ("%fusion.552 = bf16[49152,4096]{1,0:T(8,128)(2,1)} fusion(bf16[4096,49152]"
            "{1,0:T(8,128)(2,1)} %get-tuple-element.4703), kind=kOutput")
    assert trace_reduce.short_name(text) == "fusion.552 bf16[49152,4096] fusion"
    text = "%copy-start = (bf16[4]{0}, u32[]{:S(2)}) copy-start(bf16[4]{0} %x.1)"
    assert trace_reduce.short_name(text) == "copy-start (tuple) copy-start"


def test_subtract_and_union():
    import numpy as np

    a = np.array([[0, 10], [5, 15], [20, 30]], float)
    b = np.array([[2, 4], [12, 25]], float)
    assert trace_reduce._measure(trace_reduce._union(a)) == 25
    assert trace_reduce._subtract(a, b) == pytest.approx(25 - 2 - 3 - 5)


def test_recorded_v5e_trace():
    files = sorted(glob.glob(os.path.join(DATA, "*.xplane.pb")))
    assert files, "no recorded trace under bench/tests/data"
    s = trace_reduce.reduce_file(files[0])
    assert s.devices == 1 and s.spans == 3
    assert 0 < s.busy_s < s.window_s
    assert s.collective_s == 0
    assert s.gaps and s.gaps[0][0].startswith("host")
