"""Engine hot-path throughput benchmark (DESIGN.md §8/§10).

Measures the event-block megakernel (``backend="pallas_block"``,
kernels/block_step.py — the PM store resident across ``block_events``
fused events) against the per-event xla scan and against the PRE-PR-3
configuration (stable-argsort allocator, sort-based Algorithm 2, no
census) on identical streams.  Written to BENCH_engine.json (committed
at the repo root as the perf trajectory; CI re-runs --quick per PR and
gates on regression):

  single_lane   (headline)  events/sec on the paper config (Q1,
      ws=3000, MAX_PMS=128 — configs/pspice_paper.py) under 120%
      overload with the pSPICE shedder: block kernel vs per-event xla
      vs pre-PR legacy.
  single_lane_large   the same at the engine-default 2048-slot store —
      the memory-traffic-bound regime the block kernel targets.
      Target: ≥2x over the per-event path.
  lanes   L=8 tenant lanes through one lane-batched scan (the vmapped
      block kernel runs W=128: per-lane stores are small, so bigger
      blocks amortize the per-block machinery).
  block_sweep   single-lane large-store events/s per W ∈ {8, 32, 128}
      — the block-size tuning artifact CI uploads per PR.
  overload_sweep   single-lane large-store events/s at overload
      1.0/1.2/1.4/1.6× — the tentpole's flat-throughput story (Alg-2
      fires resolve in-kernel).  retention_1p4 (= ev/s at 1.4× ÷
      unloaded) is gated ≥0.70 per PR, plus a machine-normalized
      absolute floor at 1.4×.
  chunk_sweep   single-lane chunked runtime (auto-grouped chunk groups,
      donated carry+events, fused device-side telemetry) vs the
      monolithic scan.  Target: chunk=256 overhead ≤5%.
  roofline   analytic arithmetic-intensity estimate for the fused vs
      unfused step (launch/roofline.py engine_block_intensity).

Regression gate (--check BASELINE.json): events/sec must not regress
more than 20% (35% on the noisier large-store cell) against the
checked-in baseline on the single-lane cells, and the chunk=256
overhead must stay within the 5% budget plus a 5-point quick-mode
noise allowance.  CI boxes differ from the box that wrote the
baseline, so throughput comparisons are machine-normalized by the
legacy engine's throughput measured in the SAME run:
    pass  ⇔  new_now ≥ 0.8 · new_base · (legacy_now / legacy_base)
(the legacy path never changes, so it is the machine-speed probe).

Usage:  PYTHONPATH=src python benchmarks/bench_engine.py
            [--quick] [--check BENCH_engine.json] [--out PATH]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import jax
import jax.numpy as jnp

from repro import compile_cache
from repro.cep import engine as eng
from repro.cep import patterns as pat
from repro.cep import runner
from repro.configs import pspice_paper as pp
from repro.data import streams
from repro.launch import roofline
from repro import runtime as RT

REPEATS = 3  # best-of-N walls (2-core CI boxes are noisy)
LANES_W = 128  # block size for the lane cell (small stores: amortize)


def _legacy(cfg: eng.EngineConfig) -> eng.EngineConfig:
    """The pre-PR-3 engine: per-event argsort spawn allocator, sort-based
    Algorithm 2, no pattern-census specialization, per-event scan."""
    return dataclasses.replace(cfg, backend=eng.BACKEND_XLA,
                               spawn_alloc="argsort", shed_plan="sort",
                               kinds="mixed", spawn_modes="mixed")


def _blocked(cfg: eng.EngineConfig, w: int | None = None):
    return dataclasses.replace(
        cfg, backend=eng.BACKEND_PALLAS_BLOCK,
        block_events=w if w is not None else cfg.block_events)


def _paper_workload(n: int, max_pms: int, seed: int = 7,
                    rate_mult: float = pp.RATE_MULTIPLIER):
    specs = [pat.make_q1(window_size=3000, num_symbols=10)]
    cp = pat.compile_patterns(specs)
    cfg = runner.default_config(cp, max_pms=max_pms,
                                latency_bound=pp.LATENCY_BOUND,
                                shedder=eng.SHED_PSPICE, **pp.COST)
    model = eng.make_model(cp, cfg)
    # rate_mult × what the cost model sustains at a half-full store
    # (the default is the paper's ~120% overload).
    rate = rate_mult / (cfg.c_base + cfg.c_match * 0.5 * max_pms)
    raw = streams.gen_stock(n, num_symbols=500, pattern_symbols=10,
                            hot_fraction=0.9, p_class=0.03, seed=seed)
    ev = streams.classify(specs, raw, rate=rate, seed=0)
    return cfg, model, ev


_OVERLOAD_LB = 0.05  # bound tight enough that queue growth crosses it
                     # within a bench-sized cell (pp.LATENCY_BOUND=1.0
                     # needs the paper's ~60k-event stream to fire)


def _overload_workload(n: int, max_pms: int, seed: int = 7):
    """Calibrated true-overload workload for the overload sweep.

    ``_paper_workload``'s hand-derived rate assumes half-full-store
    service cost, but the store settles at ~10 live PMs on bench-sized
    streams, so actual service is ~100× faster than that estimate and
    the queue never builds — Algorithm 2 never fires.  This instead
    follows ``runner.run_experiment``: a warm unloaded run fits the
    latency model, ``max_rate = 1/f(steady_n_pm)`` is what the engine
    sustains, and arrivals at ``max_rate × ratio`` are a TRUE overload
    ratio.  Returns ``(cfg, model, classify)`` where ``classify(mult)``
    yields the stream arriving at ``mult ×`` the sustainable rate.
    """
    specs = [pat.make_q1(window_size=3000, num_symbols=10)]
    cp = pat.compile_patterns(specs)
    cfg = runner.default_config(cp, max_pms=max_pms,
                                latency_bound=_OVERLOAD_LB,
                                shedder=eng.SHED_PSPICE, **pp.COST)
    raw_warm = streams.gen_stock(2000, num_symbols=500, pattern_symbols=10,
                                 hot_fraction=0.9, p_class=0.2,
                                 seed=seed + 1)
    warm = streams.classify(specs, raw_warm, rate=1.0, seed=seed)
    built = runner.build_model(specs, cfg, warm)
    model = eng.make_model(cp, cfg, ut_tables=built.ut_stacked,
                           ut_bins=built.ut_bins, f_model=built.f_model,
                           g_model=built.g_model)
    raw = streams.gen_stock(n, num_symbols=500, pattern_symbols=10,
                            hot_fraction=0.9, p_class=0.2, seed=seed)

    def classify(mult: float):
        return streams.classify(specs, raw, rate=built.max_rate * mult,
                                seed=0)

    return cfg, model, classify


def _refuse_degraded() -> None:
    """Refuse to record baselines from a silently-degraded build.

    BENCH_engine.json is the regression gate's ground truth, so before
    any timing runs, the benchmarked (non-legacy) configurations are
    traced and their jaxprs run through the hot-path contract rules —
    a build whose spawn allocator regressed to argsort, whose shed plan
    sorts, or whose block kernel lost its store aliases must never
    refresh the baseline.  Jaxpr-only artifacts (compile=False) keep
    this to a few hundred ms; the legacy cell is the gate's machine-
    speed probe and is deliberately NOT checked (its sort is the point).
    """
    from repro import analysis as A
    from repro.analysis import pallas_rules as APR

    cfg, model, ev = _paper_workload(64, pp.MAX_PMS)
    ctr = A.get_contract("cep.run_engine")
    jaxpr_rules = [r for r in A.RULES
                   if r.name in ("no-sort", "no-callback", "control-flow")]
    bad = []
    for label, cell in (("xla", cfg), ("pallas_block", _blocked(cfg))):
        art = A.trace_artifact(eng.run_engine, cell, model, ev,
                               eng.init_carry(cell), name=f"bench[{label}]",
                               n_events=64, compile=False)
        fs = A.run_rules(art, ctr, rules=jaxpr_rules)
        fs += APR.check_pallas_calls(art, ctr)
        bad += [f for f in fs if not f.ok]
    if bad:
        for f in bad:
            print(f"CONTRACT VIOLATION {f.cell}: {f.rule}: {f.evidence}",
                  file=sys.stderr)
        print("refusing to record baselines from a degraded build "
              "(see repro.analysis / DESIGN.md §11)", file=sys.stderr)
        sys.exit(2)


def _time_engine(cfg, model, ev, n, reps) -> float:
    def run():
        t0 = time.perf_counter()
        c, _ = eng.run_engine(cfg, model, ev, eng.init_carry(cfg))
        jax.block_until_ready(c.sim_time)
        return time.perf_counter() - t0
    run()                                # compile
    return n / min(run() for _ in range(reps))


def bench_single_lane(n: int, max_pms: int, reps: int) -> dict:
    cfg, model, ev = _paper_workload(n, max_pms)
    new = _time_engine(_blocked(cfg), model, ev, n, reps)
    xla = _time_engine(cfg, model, ev, n, reps)
    legacy = _time_engine(_legacy(cfg), model, ev, n, reps)
    return {
        "n_events": n, "max_pms": max_pms,
        "block_events": cfg.block_events,
        "events_per_s_new": new, "events_per_s_xla": xla,
        "events_per_s_legacy": legacy,
        "speedup_vs_xla": new / xla,
        "speedup_vs_pre_pr": new / legacy,
    }


def bench_block_sweep(n: int, max_pms: int, reps: int,
                      ws=(8, 32, 128)) -> list[dict]:
    """Single-lane large-store events/s per block size W."""
    cfg, model, ev = _paper_workload(n, max_pms)
    return [{"block_events": w, "max_pms": max_pms,
             "events_per_s": _time_engine(_blocked(cfg, w), model, ev, n,
                                          reps)}
            for w in ws]


def bench_overload_sweep(n: int, max_pms: int, reps: int,
                         ratios=(1.0, 1.2, 1.4, 1.6)) -> dict:
    """The tentpole's flat-throughput story: fused pallas_block ev/s as a
    function of overload ratio.  Algorithm-2 fires are handled inside the
    kernel, so ev/s must stay ~flat across the sweep instead of decaying
    toward per-event throughput at 1.4× (the PR-5 bail/replay behavior).
    The 1.4× cell also times the legacy per-event engine as the gate's
    machine-speed probe; retention_1p4 (ev/s at 1.4× ÷ unloaded) is the
    machine-independent headline the CI gate floors at 0.70.  The
    workload is the calibrated one (``_overload_workload``): the ratio
    axis is relative to the fitted sustainable rate, so ratios > 1.0
    actually fire the shed (shed_calls is recorded per row as proof)."""
    cfg, model, classify = _overload_workload(n, max_pms)
    rows = []
    for mult in ratios:
        ev = classify(mult)
        cfg_b = _blocked(cfg)
        carry, _ = eng.run_engine(cfg_b, model, ev, eng.init_carry(cfg_b))
        row = {"overload": mult, "max_pms": max_pms, "n_events": n,
               "shed_calls": float(carry.shed_calls),
               "events_per_s_new": _time_engine(cfg_b, model, ev, n, reps)}
        if mult == 1.4:
            row["events_per_s_legacy"] = _time_engine(_legacy(cfg), model,
                                                      ev, n, reps)
        rows.append(row)
    by = {r["overload"]: r["events_per_s_new"] for r in rows}
    return {"rows": rows,
            "retention_1p4": by[1.4] / by[1.0] if 1.0 in by and 1.4 in by
            else None}


def bench_lanes(num_lanes: int, n_per_lane: int, max_pms: int,
                reps: int) -> dict:
    specs = [pat.make_q1(window_size=400, num_symbols=4)]
    cp = pat.compile_patterns(specs)
    cfg = runner.default_config(cp, max_pms=max_pms, latency_bound=1.0,
                                shedder=eng.SHED_PSPICE, **pp.COST)
    model = eng.make_model(cp, cfg)
    rate = 1.2 / (cfg.c_base + cfg.c_match * 0.5 * max_pms)
    evs = []
    for lane in range(num_lanes):
        raw = streams.gen_stock(n_per_lane, num_symbols=50,
                                pattern_symbols=4, p_class=0.05,
                                seed=100 + lane)
        evs.append(streams.classify(specs, raw,
                                    rate=rate * (1 + 0.1 * lane),
                                    seed=lane))
    evL = RT.stack(evs)
    mL = RT.broadcast_model(model, num_lanes)
    total = num_lanes * n_per_lane

    def run(c):
        carry = RT.init_lane_carries(c, num_lanes)
        t0 = time.perf_counter()
        out, _ = RT.run_chunk_lanes(c, mL, evL, carry, jnp.int32(0))
        jax.block_until_ready(out.sim_time)
        return time.perf_counter() - t0

    def best(c):
        run(c)
        return total / min(run(c) for _ in range(reps))

    new = best(_blocked(cfg, LANES_W))
    xla = best(cfg)
    legacy = best(_legacy(cfg))
    return {
        "num_lanes": num_lanes, "events_per_lane": n_per_lane,
        "max_pms": max_pms, "total_events": total,
        "block_events": LANES_W,
        "events_per_s_new": new, "events_per_s_xla": xla,
        "events_per_s_legacy": legacy,
        "speedup_vs_xla": new / xla,
        "speedup_vs_pre_pr": new / legacy,
    }


def bench_chunk_sweep(n: int, chunk_sizes, max_pms: int,
                      reps: int) -> list[dict]:
    """Chunked-runtime overhead vs the monolithic scan, on the block
    backend (the default auto-grouping policy sizes chunk groups —
    runtime.chunker.suggested_group_chunks)."""
    specs = [pat.make_q1(window_size=400, num_symbols=4)]
    cp = pat.compile_patterns(specs)
    cfg = runner.default_config(cp, max_pms=max_pms, latency_bound=1.0,
                                shedder=eng.SHED_PSPICE, **pp.COST)
    cfg = _blocked(cfg)
    model = eng.make_model(cp, cfg)
    rate = 1.2 / (cfg.c_base + cfg.c_match * 0.5 * max_pms)
    raw = streams.gen_stock(n, num_symbols=50, pattern_symbols=4,
                            p_class=0.05, seed=100)
    ev = streams.classify(specs, raw, rate=rate, seed=0)

    def run_mono():
        t0 = time.perf_counter()
        c, _ = eng.run_engine(cfg, model, ev, eng.init_carry(cfg))
        jax.block_until_ready(c.sim_time)
        return time.perf_counter() - t0

    run_mono()
    wall_mono = min(run_mono() for _ in range(reps))
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
        wall = min(run() for _ in range(reps))
        rows.append({"chunk_size": cs, "variant": "chunked",
                     "events_per_s": n / wall, "wall_s": wall,
                     "overhead_vs_monolithic_pct":
                         100.0 * (wall / wall_mono - 1.0)})
    return rows


def _gate_cell(out: dict, base: dict, cell: str, norm: float,
               factor: float = 0.8) -> bool:
    b, c = base[cell], out[cell]
    floor = factor * b["events_per_s_new"] * norm
    ok = c["events_per_s_new"] >= floor
    print(f"# gate[{cell}]: new={c['events_per_s_new']:.0f} ev/s, "
          f"baseline={b['events_per_s_new']:.0f}, machine-norm={norm:.2f}, "
          f"floor={floor:.0f} → {'PASS' if ok else 'FAIL'}",
          file=sys.stderr)
    return ok


def _gate_overload(out: dict, base: dict) -> bool:
    """The overload gate, two halves: (1) intra-run retention — ev/s at
    1.4× overload must hold ≥70% of the unloaded rate (machine-free: a
    ratio of walls from the SAME run, so it catches the fused shed path
    reverting to bail/replay no matter the box); (2) when the baseline
    has an overload_sweep, the machine-normalized ev/s floor at 1.4×
    (the 2048-slot store's 0.65 factor — same variance class as
    single_lane_large)."""
    sw = out.get("overload_sweep")
    if not sw or sw.get("retention_1p4") is None:
        return True
    ret = sw["retention_1p4"]
    ok = ret >= 0.70
    print(f"# gate[overload@1.4x]: retention={ret:.2f} (floor 0.70) → "
          f"{'PASS' if ok else 'FAIL'}", file=sys.stderr)
    bsw = base.get("overload_sweep")
    if bsw:
        now = {r["overload"]: r for r in sw["rows"]}
        was = {r["overload"]: r for r in bsw["rows"]}
        if 1.4 in now and 1.4 in was and "events_per_s_legacy" in was[1.4]:
            norm = (now[1.4]["events_per_s_legacy"] /
                    was[1.4]["events_per_s_legacy"])
            floor = 0.65 * was[1.4]["events_per_s_new"] * norm
            ok14 = now[1.4]["events_per_s_new"] >= floor
            print(f"# gate[overload@1.4x abs]: "
                  f"new={now[1.4]['events_per_s_new']:.0f} ev/s, "
                  f"baseline={was[1.4]['events_per_s_new']:.0f}, "
                  f"machine-norm={norm:.2f}, floor={floor:.0f} → "
                  f"{'PASS' if ok14 else 'FAIL'}", file=sys.stderr)
            ok &= ok14
    return ok


def check_regression(out: dict, baseline_path: str) -> bool:
    """Machine-normalized ±20% events/sec gate vs the checked-in baseline
    on BOTH single-lane cells (paper config and the 2048-slot store this
    PR's kernel targets), the 1.4×-overload cell (retention + absolute),
    plus the chunk=256 overhead ceiling.  Returns True when passing."""
    with open(baseline_path) as f:
        base = json.load(f)
    norm = (out["single_lane"]["events_per_s_legacy"] /
            base["single_lane"]["events_per_s_legacy"])
    ok = _gate_cell(out, base, "single_lane", norm)
    if "single_lane_large" in base:
        norm_l = (out["single_lane_large"]["events_per_s_legacy"] /
                  base["single_lane_large"]["events_per_s_legacy"])
        # The 2048-slot block cell has higher run-to-run variance than
        # the legacy probe tracks (quick-mode spread of 0.68-1.03x the
        # baseline observed on a loaded 2-core box); a 35% floor still
        # catches the regression class the cell exists for (the ~4x
        # fused-kernel win reverting toward the ~5k ev/s per-event
        # path, which lands at ~0.23x).
        ok &= _gate_cell(out, base, "single_lane_large", norm_l,
                         factor=0.65)
    cell256 = [r for r in out["chunk_sweep"] if r["chunk_size"] == 256]
    if cell256:
        # Budget is ≤5% (DESIGN.md §8; the committed full-run sweep sits
        # at ~0%); the CI ceiling adds a 5-point allowance for quick-mode
        # noise on shared 2-core boxes.
        ov = cell256[0]["overhead_vs_monolithic_pct"]
        ok256 = ov <= 10.0
        print(f"# gate[chunk=256]: overhead={ov:.1f}% (budget 5% + 5 "
              f"noise allowance) → {'PASS' if ok256 else 'FAIL'}",
              file=sys.stderr)
        ok &= ok256
    ok &= _gate_overload(out, base)
    if not ok:
        print("# events/s regressed past a cell's floor (20% paper cell "
              "/ 35% large cell / 0.70 overload retention) or chunk "
              "overhead blew the ceiling, vs checked-in baseline",
              file=sys.stderr)
    return ok


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="CI-sized run")
    ap.add_argument("--check", default=None, metavar="BASELINE",
                    help="fail if events/s regresses >20% vs this JSON")
    ap.add_argument("--out", default="BENCH_engine.json")
    args = ap.parse_args(argv)
    compile_cache.enable()

    # Quick mode shrinks ONLY the event counts/repeats — identical
    # configurations, so per-event rates stay comparable with the
    # committed full-run baseline (the --check gate relies on this).
    if args.quick:
        # n_large stays big enough that fixed per-run costs don't eat
        # into the 20% gate margin at the slow 2048-slot per-event rate,
        # and the chunk sweep keeps the full-run stream length: at 8k
        # events its walls are ~50 ms and the overhead gate becomes
        # noise (±20% observed) — the full 32k costs CI ~1 s.
        n, n_large, reps = 8000, 8000, 2
        L, n_lane = 4, 4096
        sweep_n, sweep = 32768, (256, 1024)
    else:
        n, n_large, reps = 30000, 15000, REPEATS
        L, n_lane = 8, 8192
        sweep_n, sweep = 32768, (256, 1024, 4096)

    _refuse_degraded()
    out = {"quick": bool(args.quick), "num_devices": len(jax.devices()),
           "backend": jax.default_backend()}
    print("name,events_per_s_new,derived")
    t0 = time.time()
    head = bench_single_lane(n, pp.MAX_PMS, reps)
    out["single_lane"] = head
    print(f"single_lane:max_pms={pp.MAX_PMS},"
          f"{head['events_per_s_new']:.0f},"
          f"speedup_vs_xla={head['speedup_vs_xla']:.2f}x,"
          f"vs_pre_pr={head['speedup_vs_pre_pr']:.2f}x")
    large = bench_single_lane(n_large, 2048, reps)
    out["single_lane_large"] = large
    print(f"single_lane:max_pms=2048,{large['events_per_s_new']:.0f},"
          f"speedup_vs_xla={large['speedup_vs_xla']:.2f}x,"
          f"vs_pre_pr={large['speedup_vs_pre_pr']:.2f}x")
    out["block_sweep"] = bench_block_sweep(n_large, 2048, reps)
    for r in out["block_sweep"]:
        print(f"block_sweep:W={r['block_events']},"
              f"{r['events_per_s']:.0f},")
    out["overload_sweep"] = bench_overload_sweep(n_large, 2048, reps)
    for r in out["overload_sweep"]["rows"]:
        print(f"overload_sweep:x{r['overload']},"
              f"{r['events_per_s_new']:.0f},"
              f"shed_calls={r['shed_calls']:.0f}")
    print(f"overload_sweep:retention_1p4,"
          f"{out['overload_sweep']['retention_1p4']:.3f},")
    lanes = bench_lanes(L, n_lane, 64, reps)
    out["lanes"] = lanes
    print(f"lanes:L={L},{lanes['events_per_s_new']:.0f},"
          f"speedup_vs_xla={lanes['speedup_vs_xla']:.2f}x,"
          f"vs_pre_pr={lanes['speedup_vs_pre_pr']:.2f}x")
    # Sweep overheads are ratios of ~0.2 s walls: always take best-of-3,
    # quick mode included — min-of-2 leaves ±5-point overhead noise.
    out["chunk_sweep"] = bench_chunk_sweep(sweep_n, sweep, 64,
                                           max(reps, REPEATS))
    for r in out["chunk_sweep"]:
        tag = r["variant"] if r["chunk_size"] == 0 \
            else f"chunk={r['chunk_size']}"
        extra = "" if r["chunk_size"] == 0 else \
            f"overhead={r['overhead_vs_monolithic_pct']:.1f}%"
        print(f"chunk_sweep:{tag},{r['events_per_s']:.0f},{extra}")
    # Memory-traffic story of the fused step (analytic, DESIGN.md §10).
    cfg_large = _blocked(_paper_workload(64, 2048)[0])
    out["roofline"] = roofline.engine_block_intensity(cfg_large)
    print(f"roofline:intensity,"
          f"{out['roofline']['intensity_fused']:.2f},"
          f"unfused={out['roofline']['intensity_unfused']:.2f},"
          f"traffic_ratio={out['roofline']['traffic_ratio']:.1f}x")
    print(f"# total wall: {time.time() - t0:.1f}s", file=sys.stderr)

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    print(f"# wrote {args.out}", file=sys.stderr)

    if large["speedup_vs_xla"] < 2.0:
        print("# WARNING: large-store block speedup below the 2x target",
              file=sys.stderr)
    if args.check and not check_regression(out, args.check):
        sys.exit(1)


if __name__ == "__main__":
    main()
