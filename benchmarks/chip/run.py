#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip it is started on.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``; its configuration is
``configs/<config>.json`` and its traffic ``workloads/<cell>.json`` beside
this file, and each per-layer metric is read by ``metrics/<metric>.py``.
One run: generate the stream from the seed, build the model and the
served runtime, warm every shape the window uses (set-up), then push for
``--seconds`` (closed loop: back to back; open loop: on a fixed wall-clock
schedule), then check what the window produced against the plain
reference (``reference.py``).  The last line of standard output is one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device``, with ``--trace 1`` a ``breakdown``, and last ``checks``, each
number compared with its limit.

A configuration of tenant lanes (``served.runtime`` ``"lanes"``; schema
in ``system.py``) runs the same way on the cell's chips: L lanes, each a
tenant with its own stream, model and rate, pushed lane-stacked.  Its
workload's ``push_events`` is per lane, so a push carries L times as many
events; ``attempted``, ``failed``, ``events_per_s`` and the events the
per-layer readers divide by count every lane's, an open loop's
``rate_events_per_s`` is the total over lanes, and the check runs the
reference on each lane alone.  Lanes emit counts only, so no ``emit_*``
metric exists for them.

Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result.  ``--rehearse`` runs the cell at toy sizes
on any backend, for the tests only.  ``--fault`` plants a fault or the
control under the timed path (``faults.py``), for the limit readings.
"""
from __future__ import annotations

import argparse
import contextlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time

T_START = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)


def fail(msg: str) -> int:
    print(f"run.py: {msg}", file=sys.stderr)
    return 2


def load_cell(name: str):
    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next((w for w in bench["workloads"] if w["name"] == name), None)
    if cell is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    with open(os.path.join(HERE, "configs", cell["config"] + ".json")) as f:
        config = json.load(f)
    with open(os.path.join(HERE, "workloads", name + ".json")) as f:
        traffic_mix = json.load(f)
    return bench, cell, config, traffic_mix


def lane_cell(bench: dict, cell: dict, config: dict, fault) -> None:
    """Refuse, before any run, a cell that a lane configuration cannot
    serve as asked (``system.lane_configs`` refuses the rest)."""
    import system as S
    lanes = S.lane_configs(config, cell["chips"])
    if lanes is None:
        if fault == "lane_swap":
            raise ValueError("lane_swap needs a lane configuration")
        return
    emit = [m["name"] for m in reported(bench, cell, False)
            if m["name"].startswith("emit_")]
    if emit:
        raise ValueError(f"{emit} listed for a lane cell, whose runtime "
                         f"emits counts only")


def reported(bench: dict, cell: dict, trace: bool) -> list[dict]:
    """The metrics this cell reports in a run of this kind."""
    def in_cell(m):
        return cell["name"] in m.get("workloads", [cell["name"]])
    e2e = [m for m in bench["end_to_end"] if in_cell(m)]
    if not trace:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if in_cell(m) and m["moves"] in names]


def compile_cache(jax) -> None:
    """JAX's persistent cache at a fixed path inside the checkout, keeping
    every program (JAX's default floor keeps only those that took a
    second or more to compile)."""
    jax.config.update("jax_compilation_cache_dir",
                      os.path.join(HERE, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def pin_malloc() -> None:
    """Fix glibc malloc's mmap and trim thresholds at the ceiling its own
    dynamic policy reaches in a long-running process (32 MiB, 64 MiB).

    Left dynamic, each threshold rises to the largest large buffer the
    process has freed so far, so set-up's allocations decided whether the
    match decode's ~50 MB of fresh host buffers per chunk page-fault on
    every chunk: one seed on one chip ran at 11.9k or 16.3k events/s."""
    import ctypes
    import ctypes.util
    try:
        libc = ctypes.CDLL(ctypes.util.find_library("c"))
        mallopt = libc.mallopt
    except (OSError, AttributeError, TypeError):
        return                              # not glibc: nothing to pin
    mallopt.argtypes = [ctypes.c_int, ctypes.c_int]
    mallopt.restype = ctypes.c_int
    M_TRIM_THRESHOLD, M_MMAP_THRESHOLD = -1, -3
    mallopt(M_MMAP_THRESHOLD, 32 << 20)
    mallopt(M_TRIM_THRESHOLD, 64 << 20)


def derive_seeds(seed: int) -> dict:
    import numpy as np
    s = np.random.default_rng(seed).integers(0, 2**31 - 1, size=4)
    return dict(zip(("warm", "stream", "carry", "admission"),
                    (int(x) for x in s)))


class CompileClock:
    """Counts XLA compiles and persistent-cache hits as they happen."""

    def __init__(self):
        from jax import monitoring
        self.compiles, self.hits = 0, 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1


class Record:
    """What the window produced, for the per-layer readers."""

    def __init__(self):
        self.events = 0
        self.push_spans: list = []
        self.gen_lags: list = []
        self.latencies: list = []
        self.chunks: list = []
        self.trace = None
        self.roofline = None


def _annotate(tracing: bool):
    if not tracing:
        return lambda name: contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation


def window(system, mix: dict, lo: int, seconds: float, ann,
           max_pushes: int | None) -> tuple[Record, int, float]:
    """The measured window.  Returns the record, the next stream index
    and the window's wall seconds.

    Closed loop: pushes back to back.  Open loop: the generator creates
    event ``j`` of the window at ``t0 + (j + 1) / rate`` and sends a push
    when its last event exists; a match's latency runs from the creation
    of its completing event to the end of the push that emitted it.
    ``lo`` indexes each lane's stream; a push carries ``push_events``
    events of every lane."""
    import system as S
    rt, push = system.runtime, mix["push_events_scaled"]
    width = push * (system.lanes or 1)
    rec = Record()
    rate = mix.get("rate_events_per_s")
    timed = rate and system.emits
    lo0 = lo
    k = 0
    with ann("bench.window"):
        t0 = time.perf_counter()
        while max_pushes is None or k < max_pushes:
            if rate:
                due = t0 + (k + 1) * width / rate
                if due - t0 > seconds:
                    break
            elif time.perf_counter() - t0 >= seconds:
                break
            with ann("bench.gen"):
                b = S.batch(system, lo, lo + push)
            if rate:
                while True:
                    left = due - time.perf_counter()
                    if left <= 0:
                        break
                    time.sleep(left - 1e-3 if left > 2e-3 else 0)
            if timed:
                before = [set(m) for m in rt.matches]
            t1 = time.perf_counter()
            with ann("bench.push"):
                rt.push(b)
            t2 = time.perf_counter()
            rec.push_spans.append((t1, t2))
            if rate:
                rec.gen_lags.append(t1 - due)
            if timed:
                for m, old in zip(rt.matches, before):
                    rec.latencies += [t2 - (t0 + (e - lo0 + 1) / rate)
                                      for _, _, e in m - old]
            lo += push
            k += 1
        t_end = time.perf_counter()
    rec.events = k * width
    return rec, lo, t_end - t0


def percentile(xs, q):
    import numpy as np
    return float(np.percentile(np.asarray(xs), q))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="toy sizes on any backend (tests only)")
    ap.add_argument("--fault", default=None,
                    choices=("stuck", "half", "altered", "duplicate",
                             "lane_swap", "shed_all", "shed_none",
                             "refresh_skipped", "stale_tables"))
    args = ap.parse_args(argv)
    pin_malloc()

    try:
        bench, cell, config, mix = load_cell(args.workload)
        lane_cell(bench, cell, config, args.fault)
    except (OSError, KeyError, ValueError) as e:
        return fail(f"cannot load the cell: {e}")
    if not os.path.isdir(os.path.join(CHECKOUT, "src", "repro")):
        return fail(f"no program (src/repro) in {CHECKOUT}")
    sys.path.insert(0, os.path.join(CHECKOUT, "src"))
    import jax

    devices = jax.devices()
    t_jax = time.perf_counter() - T_START
    if not args.rehearse:
        if devices[0].platform != "tpu":
            return fail(f"JAX found no TPU (platform "
                        f"{devices[0].platform!r})")
        if len(devices) < cell["chips"]:
            return fail(f"the cell needs {cell['chips']} chips, JAX sees "
                        f"{len(devices)}")
        compile_cache(jax)

    clock = CompileClock()
    os.makedirs(os.path.join(HERE, "out"), exist_ok=True)
    out_dir = tempfile.mkdtemp(prefix=args.workload + ".",
                               dir=os.path.join(HERE, "out"))
    try:
        return _run(args, bench, cell, config, mix, devices, clock, out_dir,
                    t_jax)
    finally:
        shutil.rmtree(out_dir, ignore_errors=True)


def _run(args, bench, cell, config, mix, devices, clock, out_dir,
         t_jax) -> int:
    import jax
    import numpy as np
    import reference as REF
    import system as S
    import work as WK
    seeds = derive_seeds(args.seed)
    sz = S.sizes(config, args.rehearse)
    per_push = mix["push_events"] // config["served"]["chunk"]
    mix = dict(mix, push_events_scaled=per_push * sz["chunk"])
    push = mix["push_events_scaled"]
    warm_n = mix["warm_pushes"] * push
    max_pushes = mix["rehearsal_pushes"] if args.rehearse else None
    system = S.build(config, seeds, os.path.join(out_dir, "persist"),
                     cell["chips"], args.rehearse)
    rt = system.runtime
    L = system.lanes or 1
    S.watch_refresh(rt)
    t_built = time.perf_counter() - T_START
    for lo in range(0, warm_n, push):
        rt.push(S.batch(system, lo, lo + push))
    n_warm_chunks = len(rt.telemetry.chunks)
    if args.fault:
        import faults
        faults.plant(rt, args.fault)
    ann = _annotate(bool(args.trace))
    trace_dir = os.path.join(out_dir, "trace")
    if args.trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0       # host spans only, no per-call
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    c0, h0 = clock.compiles, clock.hits
    setup_s = time.perf_counter() - T_START
    rec, lo, wall = window(system, mix, warm_n, args.seconds, ann,
                           max_pushes)
    c1, h1 = clock.compiles, clock.hits
    if args.trace:
        jax.profiler.stop_trace()
    rec.chunks = rt.telemetry.chunks[n_warm_chunks:]
    used = devices[:cell["chips"]]
    stats = [d.memory_stats() or {} for d in used]
    mem_peak = max(int(s.get("peak_bytes_in_use", 0)) for s in stats)
    quarters = np.array_split(
        np.array([b - a for a, b in rec.push_spans]), 4)
    print(json.dumps({"compiles_in_window": c1 - c0,
                      "cache_hits_in_window": h1 - h0,
                      "pushes": len(rec.push_spans), "events": rec.events,
                      "window_s": wall, "setup_s": setup_s,
                      "setup_marks_s": {"jax_up": t_jax, "built": t_built,
                                        "warm": setup_s},
                      "model_builds_s": system.models_s,
                      "lanes": system.lanes, "devices": system.devices,
                      "max_rate_sim": (system.max_rates if system.lanes
                                       else system.max_rates[0]),
                      "push_ms_median_by_quarter": [
                          float(np.median(x)) * 1e3 if x.size else None
                          for x in quarters]}), flush=True)

    progs = S.final_state(system)
    # The chunk's latency is the worst lane's.
    l_e_max = max((float(c.l_e_max) for c in rec.chunks), default=0.0)
    for prog in progs:
        prog["l_e_max"] = l_e_max
    fires = int(sum(c.shed_calls for c in rec.chunks))
    bins = int(rt.model.ut_tables.shape[-2])
    system.runtime = rt = None
    device = {"platform": used[0].platform, "kind": used[0].device_kind,
              "count": len(used), "memory_peak_bytes": mem_peak}
    line = {}
    metrics = {}
    if args.trace:
        import trace_reduce as TR
        try:
            rec.trace = TR.reduce(TR.load(trace_dir), "cep_block_step")
        except (FileNotFoundError, ValueError) as e:
            if not args.rehearse:
                raise
            print(f"run.py: trace not reduced: {e}", file=sys.stderr)
        if rec.trace is not None:
            device["busy_s"] = rec.trace["busy_s"]
            device["window_s"] = rec.trace["window_s"]
            line["breakdown"] = rec.trace["breakdown"]
            if rec.trace["kernel_s"] > 0:
                w = WK.for_config(system.cfg, bins)
                # Every chip's kernel time against one chip's peak.
                rec.roofline = WK.roofline(
                    w, rec.events, system.cfg.block_events,
                    rec.trace["kernel_s"] * rec.trace["devices"],
                    WK.peaks(used[0].device_kind))
                print(json.dumps({"roofline": rec.roofline,
                                  "kernel_calls":
                                      rec.trace["kernel_calls"]}),
                      flush=True)
    for m in reported(bench, cell, bool(args.trace)):
        v = None
        if m["name"] == "setup_s":
            v = setup_s
        elif m["name"] == "events_per_s":
            v = rec.events / wall
        elif m["name"] == "emit_p50_ms":
            v = percentile(rec.latencies, 50) * 1e3 if rec.latencies else None
        elif args.trace:
            v = _reader(m["name"])(rec)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    print(json.dumps({"matches_in_window": len(rec.latencies),
                      "fires_in_window": fires}), flush=True)

    # The check, outside the window, on the host: each lane's offered
    # events and admissions against its own stream.
    t_ref = time.perf_counter()
    n_offered = lo
    a = config["served"].get("admission")
    adms, raws = [], []
    for cl in system.classifiers:
        adm = None
        if a is not None:
            pushes = [(push, float(np.float32((b + push - 1) / cl.rate)))
                      for b in range(0, n_offered, push)]
            adm = REF.admitted(pushes, a["rate_x_max"] * max(
                system.max_rates), a["burst"])
        adms.append(adm)
        raw = cl.stream.raw(0, n_offered)
        raws.append((raw.type_id, raw.attr, raw.group))
    checks, info, per_lane = REF.compare_lanes(
        [dict(c, limits=sz["limits"]) for c in system.configs], raws,
        n_offered, progs, adms)
    if system.lanes:
        print(json.dumps({"lanes": per_lane}), flush=True)
    print(json.dumps({"reference": info,
                      "shed_calls": sum(p["shed_calls"] for p in progs),
                      "pms_shed": sum(p["pms_shed"] for p in progs),
                      "reference_s": time.perf_counter() - t_ref}),
          flush=True)
    correct = all(v <= lim for v, lim in checks.values())
    processed = sum(p["events_processed"] for p in progs)
    failed = max(0, rec.events - (processed - warm_n * L))
    line = dict({"correct": correct, "attempted": rec.events,
                 "failed": failed, "metrics": metrics,
                 "device": device}, **line)
    line["checks"] = {k: {"value": v, "limit": lim}
                      for k, (v, lim) in checks.items()}
    for k, (v, lim) in checks.items():
        print(f"check {k} {v} limit {lim}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


def _reader(name: str):
    path = os.path.join(HERE, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


if __name__ == "__main__":
    sys.exit(main())
