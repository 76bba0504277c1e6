"""From a profiler trace to the numbers the per-layer metrics read.

``load`` turns the ``.xplane.pb`` that ``jax.profiler`` writes into a small
neutral form, ``{"planes": [{"name", "lines": [{"name", "events":
[[name, start_ns, duration_ns], ...]}]}]}``, keeping the device planes'
operations (``XLA Ops``, named by their HLO instruction, ``while.12``) and
the host spans that the benchmark annotates (names that start with
``bench.`` or ``rt.``).  ``reduce`` works on that form only, so ``tests/``
check it on a small recorded fixture.

Device busy time is the union of the intervals in which an operation ran,
clipped to the ``bench.window`` span, per device, averaged over the
devices used.  Kernel time is the sum of the durations of the operations
whose name holds the kernel's name.  Operations nest (a ``while`` holds
its body's operations), so the breakdown ranks operation families by
self time.  Each idle gap between operations is put down to the innermost
host span that covers its middle.
"""
from __future__ import annotations

import bisect
import glob
import os
import re

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PREFIXES = ("bench.", "rt.")
WINDOW = "bench.window"


def load(trace_dir: str) -> dict:
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    prof = ProfileData.from_file(paths[-1])
    planes = []
    for plane in prof.planes:
        dev = plane.name.startswith(DEVICE_PREFIX)
        lines = []
        for line in plane.lines:
            if dev and line.name != OPS_LINE:
                continue
            evs = [[_short(e.name) if dev else e.name, int(e.start_ns),
                    int(e.duration_ns)] for e in line.events
                   if dev or e.name.startswith(HOST_PREFIXES)]
            if evs:
                lines.append({"name": line.name, "events": evs})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def _short(hlo: str) -> str:
    """``%while.12 = (s32[] ...) while(...)`` -> ``while.12``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def _merge(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _op_family(name: str) -> str:
    """Group numbered instances of one operation (``fusion.12``)."""
    return re.sub(r"[.\d]+$", "", name) or name


def _self_times(ivs, acc: dict) -> None:
    """Add each operation family's self time (its time less that of the
    operations nested in it) to ``acc``, in seconds."""
    stack = []                         # [end, family, duration, children]

    def close(top):
        acc[top[1]] = acc.get(top[1], 0.0) + (top[2] - top[3]) * 1e-9

    for a, b, name in sorted(ivs, key=lambda iv: (iv[0], -iv[1])):
        while stack and a >= stack[-1][0]:
            close(stack.pop())
        if stack:
            stack[-1][3] += b - a
        stack.append([b, _op_family(name), b - a, 0])
    while stack:
        close(stack.pop())


def reduce(trace: dict, kernel: str) -> dict:
    """busy_s, window_s, kernel_s, kernel_calls and the breakdown."""
    host = [ev for p in trace["planes"] if not p["name"].startswith(
        DEVICE_PREFIX) for ln in p["lines"] for ev in ln["events"]]
    win = [ev for ev in host if ev[0] == WINDOW]
    if not win:
        raise ValueError(f"trace has no {WINDOW!r} span")
    w0 = win[0][1]
    w1 = w0 + win[0][2]
    devices = [p for p in trace["planes"]
               if p["name"].startswith(DEVICE_PREFIX)]
    busy, kernel_s, calls, op_time = [], 0.0, 0, {}
    gaps = []
    for p in devices:
        ivs = []
        for ln in p["lines"]:
            for name, s, d in ln["events"]:
                a, b = max(s, w0), min(s + d, w1)
                if b <= a:
                    continue
                ivs.append((a, b, name))
                if kernel in name:
                    kernel_s += (b - a) * 1e-9
                    calls += 1
        if not ivs:
            continue
        _self_times(ivs, op_time)
        ivs = [(a, b) for a, b, _ in ivs]
        merged = _merge(ivs)
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        gaps += [(edges[k], edges[k + 1])
                 for k in range(0, len(edges), 2)
                 if edges[k + 1] > edges[k]]
    if not busy:
        raise ValueError("no device operation ran inside the window")
    n_dev = len(busy)
    idle = {}
    spans = sorted((ev for ev in host if ev[0] != WINDOW),
                   key=lambda ev: ev[1])
    starts = [ev[1] for ev in spans]
    for s, e in gaps:
        mid = (s + e) / 2
        what = "none"
        # Spans nest, so the innermost cover is the latest-starting one
        # that still covers the middle; its siblings end before it.
        k = bisect.bisect_right(starts, mid) - 1
        for ev in spans[max(0, k - 63):k + 1][::-1]:
            if ev[1] <= mid <= ev[1] + ev[2]:
                what = ev[0]
                break
        idle[what] = idle.get(what, 0.0) + (e - s) * 1e-9 / n_dev
    top = lambda d: [[k, v] for k, v in sorted(  # noqa: E731
        d.items(), key=lambda kv: -kv[1])[:10]]
    return dict(busy_s=sum(busy) / n_dev, window_s=(w1 - w0) * 1e-9,
                kernel_s=kernel_s / n_dev, kernel_calls=calls // n_dev,
                devices=n_dev,
                breakdown={"device_ops": top({k: v / n_dev for k, v in
                                              op_time.items()}),
                           "idle_gaps": top(idle)})

