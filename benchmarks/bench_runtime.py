"""Throughput benchmark for the repro.runtime streaming layer.

Three measurements, written to BENCH_runtime.json (the repo's perf
trajectory — CI uploads it per PR):

  multitenant  (headline)  events/sec of L tenant lanes through the
      vmapped chunked runtime vs the SAME L streams run back-to-back
      through monolithic ``run_engine`` scans.  The vmapped runtime
      collapses L scans into one lane-batched scan, so it must win.
  chunk_sweep   single-lane chunked throughput across chunk sizes vs the
      monolithic scan — the price of host-side control between chunks.
  refresh       multi-tenant throughput with per-lane online model
      refresh on vs off — the cost of staying adapted.

Usage:  PYTHONPATH=src python benchmarks/bench_runtime.py [--quick]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import compile_cache
from repro.cep import engine as eng
from repro.cep import patterns as pat
from repro.cep import runner
from repro.data import streams
from repro import runtime as RT

COST = dict(c_base=3e-4, c_match=6e-5, c_shed_base=1.5e-4, c_shed_pm=1.5e-6,
            c_ebl=6e-5)
REPEATS = 3  # best-of-N walls (2-core CI boxes are noisy)


def build_workload(num_lanes: int, n_per_lane: int, max_pms: int,
                   gather_stats: bool, shedder: str = eng.SHED_PSPICE,
                   drift: bool = False):
    """L drifting stock streams against one Q1 pattern set."""
    specs = [pat.make_q1(window_size=400, num_symbols=4)]
    cp = pat.compile_patterns(specs)
    cfg = runner.default_config(cp, max_pms=max_pms, latency_bound=1.0,
                                gather_stats=gather_stats, shedder=shedder,
                                **COST)
    model = eng.make_model(cp, cfg)
    # Rate ~20% above what the cost model sustains at a mid-size PM pool.
    rate = 1.2 / (cfg.c_base + cfg.c_match * 0.5 * max_pms)
    evs = []
    for lane in range(num_lanes):
        gen = streams.gen_stock_drift if drift else streams.gen_stock
        raw = gen(n_per_lane, num_symbols=50, pattern_symbols=4,
                  p_class=0.05, seed=100 + lane)
        evs.append(streams.classify(specs, raw, rate=rate * (1 + 0.1 * lane),
                                    seed=lane,
                                    rate_end=1.5 * rate if drift else None))
    return specs, cfg, model, evs


def _block(tree):
    jax.block_until_ready(jax.tree.leaves(tree)[0])


def bench_multitenant(num_lanes: int, n_per_lane: int, chunk_size: int,
                      max_pms: int) -> dict:
    specs, cfg, model, evs = build_workload(num_lanes, n_per_lane, max_pms,
                                            gather_stats=False)
    evL = RT.stack(evs)
    mL = RT.broadcast_model(model, num_lanes)
    total = num_lanes * n_per_lane

    # -- baseline: back-to-back monolithic scans, one per tenant ----------
    def run_sequential():
        # Carry init outside the timed region, mirroring the runtime path
        # (MultiTenantRuntime builds its lane carries before its t0).
        carries = [eng.init_carry(cfg, seed=lane)
                   for lane in range(num_lanes)]
        t0 = time.perf_counter()
        for lane in range(num_lanes):
            c, _ = eng.run_engine(cfg, model, evs[lane], carries[lane])
            _block(c)
        return time.perf_counter() - t0

    # -- lane-batched chunked runtime --------------------------------------
    def run_runtime():
        mt = RT.MultiTenantRuntime(
            cfg, mL, num_lanes=num_lanes,
            rt=RT.RuntimeConfig(chunk_size=chunk_size))
        t0 = time.perf_counter()
        mt.push(evL, flush=True)
        return time.perf_counter() - t0, mt

    run_sequential()                    # compile
    run_runtime()                       # compile the lane chunk shapes
    wall_seq = min(run_sequential() for _ in range(REPEATS))
    wall_rt, mt = min((run_runtime() for _ in range(REPEATS)),
                      key=lambda t: t[0])
    agg = mt.telemetry.aggregate()
    return {
        "num_lanes": num_lanes, "events_per_lane": n_per_lane,
        "chunk_size": chunk_size, "total_events": total,
        "events_per_s_sequential": total / wall_seq,
        "events_per_s_multitenant": total / wall_rt,
        "speedup": wall_seq / wall_rt,
        "wall_s_sequential": wall_seq, "wall_s_multitenant": wall_rt,
        "l_e_p99_max": agg["l_e_p99_max"],
        "pms_shed": agg["pms_shed"],
    }


def bench_chunk_sweep(n: int, chunk_sizes, max_pms: int) -> list[dict]:
    _, cfg, model, evs = build_workload(1, n, max_pms, gather_stats=False)
    ev = evs[0]

    def run_mono():
        t0 = time.perf_counter()
        c, _ = eng.run_engine(cfg, model, ev, eng.init_carry(cfg))
        _block(c)
        return time.perf_counter() - t0

    run_mono()
    wall_mono = min(run_mono() for _ in range(REPEATS))
    rows = [{"chunk_size": 0, "variant": "monolithic",
             "events_per_s": n / wall_mono, "wall_s": wall_mono}]
    for cs in chunk_sizes:
        def run():
            srt = RT.StreamRuntime(cfg, model,
                                   rt=RT.RuntimeConfig(chunk_size=cs))
            t0 = time.perf_counter()
            srt.push(ev, flush=True)
            return time.perf_counter() - t0
        run()
        wall = min(run() for _ in range(REPEATS))
        rows.append({"chunk_size": cs, "variant": "chunked",
                     "events_per_s": n / wall, "wall_s": wall,
                     "overhead_vs_monolithic_pct":
                         100.0 * (wall / wall_mono - 1.0)})
    return rows


def bench_refresh(num_lanes: int, n_per_lane: int, chunk_size: int,
                  max_pms: int, every: int) -> dict:
    specs, cfg, model, evs = build_workload(num_lanes, n_per_lane, max_pms,
                                            gather_stats=True, drift=True)
    rcfg = RT.RefreshConfig(every_chunks=every, min_observations=128.0)
    evL = RT.stack(evs)
    # Widen utility tables up front for BOTH runs so refresh-on and
    # refresh-off share one compiled chunk executable (no retrace noise).
    mL = RT.prepare_model(specs, RT.broadcast_model(model, num_lanes), rcfg)
    total = num_lanes * n_per_lane

    def run(refresh):
        mt = RT.MultiTenantRuntime(
            cfg, mL, num_lanes=num_lanes, specs=specs,
            rt=RT.RuntimeConfig(chunk_size=chunk_size, refresh=refresh))
        t0 = time.perf_counter()
        mt.push(evL, flush=True)
        return time.perf_counter() - t0, mt

    run(None)                           # compile the chunk executable
    run(rcfg)                           # compile the refresh path's jits
    wall_off = min(run(None)[0] for _ in range(REPEATS))
    wall_on, mt = min((run(rcfg) for _ in range(REPEATS)),
                      key=lambda t: t[0])
    return {
        "refresh_every_chunks": every,
        "events_per_s_no_refresh": total / wall_off,
        "events_per_s_refresh": total / wall_on,
        "refresh_overhead_pct": 100.0 * (wall_on / wall_off - 1.0),
        "refreshes_per_lane":
            [s.refresh_count for s in mt.refresh_state],
    }


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="CI-sized run")
    ap.add_argument("--out", default="BENCH_runtime.json")
    args = ap.parse_args(argv)
    compile_cache.enable()

    # max_pms=64 on both tiers: multi-tenant consolidation is the
    # many-SMALL-tenants regime — lane-batching amortizes per-op overhead
    # of small PM stores; at much larger stores the sequential scans are
    # already amortized and lane-batching stops paying.
    if args.quick:
        L, n, chunk, max_pms = 4, 4096, 512, 64
        sweep_n, sweep = 8192, (256, 1024)
    else:
        L, n, chunk, max_pms = 8, 16384, 1024, 64
        sweep_n, sweep = 32768, (256, 1024, 4096)

    out = {"quick": bool(args.quick), "num_devices": len(jax.devices()),
           "backend": jax.default_backend()}
    print("name,events_per_s,derived")
    t0 = time.time()
    head = bench_multitenant(L, n, chunk, max_pms)
    out["multitenant"] = head
    print(f"multitenant:L={L},{head['events_per_s_multitenant']:.0f},"
          f"speedup_vs_sequential={head['speedup']:.2f}x")
    out["chunk_sweep"] = bench_chunk_sweep(sweep_n, sweep, max_pms)
    for r in out["chunk_sweep"]:
        tag = r["variant"] if r["chunk_size"] == 0 \
            else f"chunk={r['chunk_size']}"
        print(f"chunk_sweep:{tag},{r['events_per_s']:.0f},"
              f"wall_s={r['wall_s']:.3f}")
    out["refresh"] = bench_refresh(L, n, chunk, max_pms, every=4)
    print(f"refresh:every=4,{out['refresh']['events_per_s_refresh']:.0f},"
          f"overhead={out['refresh']['refresh_overhead_pct']:.1f}%")
    print(f"# total wall: {time.time() - t0:.1f}s", file=sys.stderr)

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    print(f"# wrote {args.out}", file=sys.stderr)
    if head["speedup"] <= 1.0:
        print("# WARNING: multi-tenant runtime did not beat sequential "
              "scans", file=sys.stderr)


if __name__ == "__main__":
    main()
