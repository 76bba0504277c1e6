"""Chaos benchmark: the fault matrix the resilience layer must survive.

Runs the streaming runtime (ingest front-end + degradation ladder + carry
guard, DESIGN.md §12) under every fault kind ``repro.runtime.faults``
defines — alone, all at once, and off — and writes BENCH_robustness.json.
Every cell is SEEDED and deterministic, so CI gates on exact outcomes:

  * zero unhandled exceptions in any cell;
  * zero NaN/Inf escaping into the final carry or deployed model;
  * a clean guard sweep after the run (violations were caught + restored);
  * every ladder/guard decision mirrored in telemetry (the event log
    agrees with the ladder's and guard's own counters);
  * bounded FN degradation: each fault cell keeps at least
    ``1 - FN_BOUND`` of the clean cell's complex-event completions;
  * ``process_kill``: the one fault the in-process matrix cannot apply —
    losing the process itself.  The chaos harness (repro.runtime.
    supervisor) SIGKILLs a persist-enabled subprocess at a seeded
    mid-chunk point, relaunches it, and the recovered run must end
    bitwise-identical (carry sha, match sets, counters) to an
    uninterrupted one.  The full kill-site × backend × shedder grid
    lives in benchmarks/bench_recovery.py; this cell keeps the fault
    matrix COMPLETE over ``faults.FAULT_KINDS``.
  * ``disabled_bitwise_<backend>``: with injection and resilience off,
    the chunked runtime stays bitwise-identical to one monolithic
    ``run_engine`` scan on all three backends.

Usage:  PYTHONPATH=src python benchmarks/bench_faults.py [--quick]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
import traceback

import jax
import numpy as np

from repro import compile_cache
from repro.cep import engine as eng
from repro.cep import patterns as pat
from repro.cep import runner
from repro.data import streams
from repro import runtime as RT
from repro.runtime import supervisor as SV

# In-process faults: everything except process_kill, which needs the
# subprocess harness below.
INPROC_FAULTS = RT.STREAM_FAULTS + RT.STATE_FAULTS

COST = dict(c_base=3e-4, c_match=6e-5, c_shed_base=1.5e-4, c_shed_pm=1.5e-6,
            c_ebl=6e-5)
# Max fraction of the clean cell's delivered completions a fault cell
# may lose.  This is a LIVENESS bound, not a quality target (the paper's
# FN claims are measured by repro.eval): quarantine refuses whole pushes
# and the worst cell (stall: repeated 256-event pile-ups) legitimately
# sheds most of the stream — the gate asserts the runtime keeps
# delivering matches under every fault instead of wedging at zero.
FN_BOUND = 0.98


def build_workload(n: int, backend: str = eng.BACKEND_XLA,
                   max_pms: int = 48):
    specs = [pat.make_q1(window_size=400, num_symbols=4)]
    cp = pat.compile_patterns(specs)
    cfg = runner.default_config(cp, max_pms=max_pms, latency_bound=0.005,
                                gather_stats=True, shedder=eng.SHED_PSPICE,
                                backend=backend, **COST)
    model = eng.make_model(cp, cfg)
    # At ~sustainable rate: the CLEAN cell stays mostly under the ladder
    # bound, so escalation in fault cells is attributable to the faults.
    rate = 1.0 / (cfg.c_base + cfg.c_match * 0.3 * cfg.max_pms)
    raw = streams.gen_stock(n, num_symbols=50, pattern_symbols=4,
                            p_class=0.05, seed=101)
    ev = streams.classify(specs, raw, rate=rate, seed=7)
    return specs, cfg, model, ev


def resilience_rt(chunk: int) -> RT.RuntimeConfig:
    return RT.RuntimeConfig(
        chunk_size=chunk,
        refresh=RT.RefreshConfig(every_chunks=4, min_observations=64.0),
        ingest=RT.IngestConfig(max_queue_events=1 << 15,
                               high_watermark=1 << 13,
                               low_watermark=1 << 11, seed=5),
        # deescalate_streak also paces quarantine recovery (one rung per
        # streak of refused pushes) — keep it short so a stalled stream
        # is readmitted within the run instead of starving the cell.
        ladder=RT.LadderConfig(escalate_streak=2, deescalate_streak=2,
                               latency_bound=0.01),
        guard=RT.GuardConfig(check_every_chunks=1,
                             checkpoint_every_chunks=4))


def _floats_finite(tree) -> bool:
    for leaf in jax.tree.leaves(tree):
        a = np.asarray(leaf)
        if np.issubdtype(a.dtype, np.floating) and not np.isfinite(a).all():
            return False
    return True


def run_cell(name: str, kinds: tuple[str, ...], specs, cfg, model, ev,
             chunk: int, push: int, p_fault: float = 0.35,
             seed: int = 3) -> dict:
    row: dict = {"cell": name, "kinds": list(kinds)}
    try:
        inj = RT.FaultInjector(RT.FaultConfig(
            kinds=kinds, seed=seed, p_fault=p_fault)) if kinds else None
        srt = RT.StreamRuntime(cfg, model, resilience_rt(chunk),
                               specs=specs)
        n = RT.num_events(ev)
        t0 = time.perf_counter()
        for s in range(0, n, push):
            batch = RT.slice_events(ev, s, min(s + push, n))
            if inj is not None:
                # State faults strike between pushes, stream faults
                # rewrite the batch before it is offered.
                srt.carry = inj.corrupt_carry(srt.carry)
                srt.model = inj.corrupt_model(srt.model)
                batch = inj.corrupt_events(batch)
            srt.push(batch)
        srt.flush()
        srt.guard_now()                      # end-of-run sweep (+restore)
        row["wall_s"] = time.perf_counter() - t0

        agg = srt.telemetry.aggregate()
        row.update(
            events_processed=srt.events_processed,
            completions=float(np.asarray(srt.carry.complex_count).sum()),
            completions_observed=agg.get("completions", 0.0),
            faults_applied=len(inj.log) if inj else 0,
            admission_shed=srt.ingest.total_shed,
            admission_rejected=srt.ingest.total_rejected,
            quarantine_dropped=srt.quarantine_dropped,
            max_rung=agg.get("max_rung", 0),
            ladder_transitions=len(srt.ladder.transitions),
            guard_checks=srt.guard.checks_run,
            guard_violations=srt.guard.violations,
            guard_restores=srt.guard.restores,
            refresh_skipped_nonfinite=srt.refresh_state.skipped_nonfinite,
        )
        row["ok_no_exception"] = True
        # No NaN/Inf may survive into the carry or the deployed model.
        row["ok_state_finite"] = (_floats_finite(srt.carry)
                                  and _floats_finite(srt.model))
        # After the final sweep's restore, a re-check must be clean.
        row["ok_guard_clean"] = srt.guard.check(srt.carry, srt.model) == []
        # Every runtime decision must be mirrored in telemetry.
        row["ok_mirrored"] = (
            len(srt.ladder.transitions)
            == len(srt.telemetry.events_of("ladder"))
            == agg.get("ladder_transitions", -1)
            and srt.guard.violations
            == len(srt.telemetry.events_of("guard_violation"))
            and srt.guard.restores
            == len(srt.telemetry.events_of("guard_restore")))
    except Exception:
        row["ok_no_exception"] = False
        row["traceback"] = traceback.format_exc()
    return row


def run_process_kill_cell(n: int, chunk: int, push: int,
                          seed: int = 3) -> dict:
    """The process-death cell: seeded SIGKILL mid-chunk via the chaos
    harness, restart, recovery must be bitwise vs an uninterrupted run."""
    row: dict = {"cell": "process_kill", "kinds": list(RT.PROCESS_FAULTS)}
    try:
        spec = {"backend": eng.BACKEND_XLA, "shedder": eng.SHED_PSPICE,
                "n": n, "push": push, "chunk": chunk, "max_pms": 32,
                "rate_mult": 3.0, "refresh_every": 4, "snapshot_every": 4,
                "min_observations": 64.0}
        inj = RT.FaultInjector(RT.FaultConfig(kinds=RT.PROCESS_FAULTS,
                                              seed=seed))
        ks = inj.plan_kill("chunk", lo=2, hi=8)
        row["kill_spec"] = ks.spec()
        t0 = time.perf_counter()
        with tempfile.TemporaryDirectory() as d:
            ref = SV.Supervisor(d).reference(spec)
        with tempfile.TemporaryDirectory() as d:
            res = SV.Supervisor(d).run(spec, kill=ks.spec())
        row["wall_s"] = time.perf_counter() - t0
        rep = res["report"]
        row.update(faults_applied=len(inj.log),
                   completions=rep["counters"].get("completions", 0.0),
                   replayed_records=rep["recovery"]["replayed_records"],
                   guard_restores=rep["counters"].get("guard_restores", 0),
                   max_rung=rep["counters"].get("max_rung", 0),
                   events_processed=rep["events_processed"])
        row["ok_no_exception"] = True
        row["ok_killed"] = res["killed"]
        row["ok_recovered"] = res["recovered"]
        row["ok_bitwise"] = (
            rep["carry_sha"] == ref["carry_sha"]
            and rep["matches"] == ref["matches"]
            and rep["counters"] == ref["counters"])
    except Exception:
        row["ok_no_exception"] = False
        row["traceback"] = traceback.format_exc()
    return row


def run_bitwise_cell(backend: str, n: int, chunk: int) -> dict:
    """Resilience OFF + no injection: the chunked runtime must equal one
    monolithic scan bit for bit on this backend."""
    row: dict = {"cell": f"disabled_bitwise_{backend}", "backend": backend,
                 "n": n}
    try:
        _, cfg, model, ev = build_workload(n, backend=backend)
        t0 = time.perf_counter()
        c_mono, _ = eng.run_engine(cfg, model, ev, eng.init_carry(cfg))
        srt = RT.StreamRuntime(cfg, model,
                               rt=RT.RuntimeConfig(chunk_size=chunk))
        srt.push(ev, flush=True)
        row["wall_s"] = time.perf_counter() - t0
        row["ok_no_exception"] = True
        row["ok_bitwise"] = all(
            np.array_equal(np.asarray(a), np.asarray(b))
            for a, b in zip(jax.tree.leaves(c_mono),
                            jax.tree.leaves(srt.carry)))
    except Exception:
        row["ok_no_exception"] = False
        row["traceback"] = traceback.format_exc()
    return row


def _gates(row: dict) -> list[str]:
    return [k for k, v in row.items() if k.startswith("ok_") and not v]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="CI-sized run")
    ap.add_argument("--out", default="BENCH_robustness.json")
    args = ap.parse_args(argv)

    compile_cache.enable()
    if args.quick:
        n, chunk, push, bw_n = 4096, 256, 512, 768
    else:
        n, chunk, push, bw_n = 8192, 256, 512, 1536

    t_all = time.time()
    print("cell,completions,faults,restores,max_rung,gates")
    # The process cell first: its children need the device, and this
    # process must not hold it yet (it initializes JAX below).
    kill_row = run_process_kill_cell(n=1536, chunk=128, push=512)

    specs, cfg, model, ev = build_workload(n)
    out = {"quick": bool(args.quick), "backend": jax.default_backend(),
           "n_events": n, "chunk_size": chunk, "fn_bound": FN_BOUND,
           "cells": []}
    cells = [("clean", ())]
    cells += [(k, (k,)) for k in INPROC_FAULTS]
    cells += [("all_faults", INPROC_FAULTS)]
    clean_completions = None
    for name, kinds in cells:
        row = run_cell(name, kinds, specs, cfg, model, ev, chunk, push)
        # Bounded FN degradation vs the clean cell (fault cells only).
        # The FN bound compares completions OBSERVED (telemetry's
        # per-chunk deltas: matches already delivered downstream), not
        # the final carry counter — a guard restore rewinds the carry,
        # but delivered matches are not un-delivered by it.
        if name == "clean":
            clean_completions = row.get("completions_observed", 0.0)
            row["ok_clean_nonempty"] = clean_completions > 0
        elif row["ok_no_exception"] and clean_completions:
            lost = 1.0 - row["completions_observed"] / clean_completions
            row["fn_vs_clean"] = lost
            row["ok_fn_bounded"] = lost <= FN_BOUND
        bad = _gates(row)
        out["cells"].append(row)
        print(f"{name},{row.get('completions', 'ERR')},"
              f"{row.get('faults_applied', 0)},"
              f"{row.get('guard_restores', 0)},"
              f"{row.get('max_rung', 0)},"
              f"{'FAIL:' + '+'.join(bad) if bad else 'pass'}")

    row = kill_row
    bad = _gates(row)
    out["cells"].append(row)
    print(f"process_kill,{row.get('completions', 'ERR')},"
          f"{row.get('faults_applied', 0)},"
          f"{row.get('guard_restores', 0)},"
          f"{row.get('max_rung', 0)},"
          f"{'FAIL:' + '+'.join(bad) if bad else 'pass'}")

    for backend in (eng.BACKEND_XLA, eng.BACKEND_PALLAS,
                    eng.BACKEND_PALLAS_BLOCK):
        row = run_bitwise_cell(backend, bw_n, chunk)
        bad = _gates(row)
        out["cells"].append(row)
        print(f"{row['cell']},-,-,-,-,"
              f"{'FAIL:' + '+'.join(bad) if bad else 'pass'}")

    failures = {r["cell"]: _gates(r) for r in out["cells"] if _gates(r)}
    out["failures"] = failures
    out["wall_s_total"] = time.time() - t_all
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    print(f"# wrote {args.out} ({out['wall_s_total']:.1f}s)",
          file=sys.stderr)
    if failures:
        print(f"# CHAOS GATE FAILURES: {failures}", file=sys.stderr)
        for r in out["cells"]:
            if r.get("traceback"):
                print(r["traceback"], file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
