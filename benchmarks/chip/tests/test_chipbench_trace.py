"""The trace reduction on a trace recorded on a TPU v5e, against a plain
per-nanosecond timeline, and on nested operations worked by hand."""
import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

import trace_reduce as TR  # noqa: E402

FIXTURE = os.path.join(HERE, "tests", "fixtures", "trace_soccer.json")


@pytest.fixture(scope="module")
def recorded():
    with open(FIXTURE) as f:
        return json.load(f)


def _timeline(trace):
    """Busy nanoseconds of the device, and the kernel's, by brute force."""
    host = [e for p in trace["planes"] if not p["name"].startswith(
        TR.DEVICE_PREFIX) for ln in p["lines"] for e in ln["events"]]
    w0, dur = next((e[1], e[2]) for e in host if e[0] == TR.WINDOW)
    busy = np.zeros(dur, bool)
    kernel_ns = 0
    for p in trace["planes"]:
        if not p["name"].startswith(TR.DEVICE_PREFIX):
            continue
        for ln in p["lines"]:
            for name, s, d in ln["events"]:
                a, b = max(s - w0, 0), min(s + d - w0, dur)
                if b > a:
                    busy[a:b] = True
                    if "cep_block_step" in name:
                        kernel_ns += b - a
    return busy, kernel_ns


def test_recorded_trace_busy_and_kernel(recorded):
    r = TR.reduce(recorded, "cep_block_step")
    busy, kernel_ns = _timeline(recorded)
    assert r["window_s"] == pytest.approx(busy.size * 1e-9)
    assert r["busy_s"] == pytest.approx(busy.sum() * 1e-9)
    assert r["kernel_s"] == pytest.approx(kernel_ns * 1e-9)
    assert r["kernel_calls"] >= 1
    idle = sum(v for _, v in r["breakdown"]["idle_gaps"])
    assert idle == pytest.approx((~busy).sum() * 1e-9)
    # Self times partition the busy time of operations that do not
    # overlap but by nesting.
    ops = sum(v for _, v in r["breakdown"]["device_ops"])
    assert ops <= r["busy_s"] * (1 + 1e-9)


def test_nested_operations_by_hand():
    # A while (0..100 ns) holds a kernel (10..40) and a fusion (50..60);
    # a copy runs 150..170.  The host pushes 0..120, then sleeps.
    trace = {"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
            ["while.3", 0, 100], ["cep_block_step.1", 10, 30],
            ["fusion.7", 50, 10], ["copy.2", 150, 20]]}]},
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
            ["bench.window", 0, 200], ["bench.push", 0, 120],
            ["rt.refresh", 100, 20]]}]}]}
    r = TR.reduce(trace, "cep_block_step")
    assert r["busy_s"] == pytest.approx(120e-9)
    assert r["kernel_s"] == pytest.approx(30e-9)
    assert r["kernel_calls"] == 1
    ops = dict(r["breakdown"]["device_ops"])
    assert ops == pytest.approx({"while": 60e-9, "cep_block_step": 30e-9,
                                 "copy": 20e-9, "fusion": 10e-9})
    # Gaps: 100..150 (middle 125: no span covers it) and 170..200.
    idle = dict(r["breakdown"]["idle_gaps"])
    assert idle == pytest.approx({"none": 80e-9})
    trace["planes"][1]["lines"][0]["events"].append(["bench.gen", 120, 80])
    idle = dict(TR.reduce(trace, "cep_block_step")["breakdown"]["idle_gaps"])
    assert idle == pytest.approx({"bench.gen": 80e-9})


def test_no_window_or_no_device_work_is_an_error():
    with pytest.raises(ValueError):
        TR.reduce({"planes": []}, "k")
    with pytest.raises(ValueError):
        TR.reduce({"planes": [{"name": "/host:CPU", "lines": [
            {"name": "python3", "events": [["bench.window", 0, 10]]}]}]},
            "k")
