"""Crash-kill recovery benchmark: the process-level chaos matrix
(DESIGN.md §13).

Every cell of (backend × shedder) runs the seeded supervisor workload
twice: once uninterrupted (the reference), once under the chaos harness — a subprocess SIGKILLed at a seeded kill site (mid-chunk,
mid-refresh, or mid-snapshot-write, cycled across the grid), then
relaunched to recover from the newest valid snapshot + WAL tail and
finish the stream.  The gates are absolute:

- ``ok_killed``      the armed SIGKILL actually fired (rc == -9);
- ``ok_recovered``   the relaunched child finished the stream;
- ``ok_bitwise``     carry sha256, decoded match sets, semantic
                     telemetry counters and the event count all equal
                     the uninterrupted run — divergence == 0;
- ``ok_torn_rejected`` (snapshot-kill cells) the mid-write kill left a
                     torn file that recovery CRC-rejected in favor of
                     the previous generation.

A snapshot-cadence sweep (SIGKILL at a fixed chunk, recover in a fresh
process) reports recovery wall time vs WAL replay length as the cadence
coarsens — the knob's cost curve.

Every run happens in a child process (``Supervisor``); this process
never initializes a JAX backend, so on a TPU host the children get the
device.  The platform recorded is the one the children report.

Writes BENCH_recovery.json (always, also on failure) and exits 1 on any
gate failure; CI runs ``--quick`` and gates merges on it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
import traceback

from repro import compile_cache
from repro.cep import engine as eng
from repro import runtime as RT
from repro.runtime import supervisor as SV

BACKENDS = (eng.BACKEND_XLA, eng.BACKEND_PALLAS, eng.BACKEND_PALLAS_BLOCK)
SHEDDERS = (eng.SHED_NONE, eng.SHED_PSPICE, eng.SHED_PMBL, eng.SHED_EBL)

# Seeded kill-point draw ranges per site.  The snapshot site must strike
# the SECOND write so a previous generation exists for the torn-file
# fallback the cell asserts on.
KILL_RANGES = {"chunk": (2, 10), "refresh": (1, 2), "snapshot": (2, 2)}


def make_spec(backend: str, shedder: str, n: int, push: int,
              chunk: int) -> dict:
    return {"backend": backend, "shedder": shedder, "n": n, "push": push,
            "chunk": chunk, "max_pms": 32, "block_events": 16,
            "rate_mult": 3.0, "refresh_every": 4, "snapshot_every": 4,
            "min_observations": 64.0}


def plan_cell_kill(site: str, seed: int) -> RT.KillSwitch:
    """Seeded kill draw via the fault injector — the chaos matrix uses
    the same randomness discipline as the in-process fault matrix."""
    inj = RT.FaultInjector(RT.FaultConfig(kinds=RT.PROCESS_FAULTS,
                                          seed=seed))
    lo, hi = KILL_RANGES[site]
    return inj.plan_kill(site, lo=lo, hi=hi)


def run_cell(backend: str, shedder: str, site: str, spec: dict,
             ref: dict, seed: int) -> dict:
    row: dict = {"cell": f"{backend}/{shedder}", "backend": backend,
                 "shedder": shedder, "kill_site": site}
    try:
        ks = plan_cell_kill(site, seed)
        row["kill_spec"] = ks.spec()
        with tempfile.TemporaryDirectory() as d:
            t0 = time.perf_counter()
            res = SV.Supervisor(d).run(spec, kill=ks.spec())
            row["wall_s"] = time.perf_counter() - t0
        rep = res["report"]
        rec = rep["recovery"]
        row.update(
            attempts=[a["returncode"] for a in res["attempts"]],
            snapshot_chunk=rec["snapshot_chunk"],
            replayed_records=rec["replayed_records"],
            rejected_snapshots=len(rec["rejected_snapshots"]),
            recovery_wall_s=rec["recovery_wall_s"],
            events_processed=rep["events_processed"],
            n_matches=sum(len(m) for m in rep["matches"]),
        )
        row["ok_killed"] = res["killed"]
        row["ok_recovered"] = res["recovered"]
        row["ok_bitwise"] = (
            rep["carry_sha"] == ref["carry_sha"]
            and rep["matches"] == ref["matches"]
            and rep["counters"] == ref["counters"]
            and rep["events_processed"] == ref["events_processed"])
        if site == "snapshot":
            row["ok_torn_rejected"] = row["rejected_snapshots"] >= 1
    except Exception:
        row["ok_no_exception"] = False
        row["traceback"] = traceback.format_exc()
    return row


def cadence_sweep(spec: dict, everies: tuple[int, ...],
                  crash_chunk: int = 6) -> list[dict]:
    """Crash per snapshot cadence: SIGKILL the child at chunk
    ``crash_chunk``, recover in a fresh child, finish, and compare
    against the uninterrupted run.  Coarser cadences replay more WAL
    records; the rows quantify that recovery-time cost."""
    with tempfile.TemporaryDirectory() as d:
        ref = SV.Supervisor(d).reference(spec)
    rows = []
    for every in everies:
        s = dict(spec, snapshot_every=every)
        row: dict = {"cell": f"cadence_{every}", "snapshot_every": every}
        try:
            with tempfile.TemporaryDirectory() as d:
                res = SV.Supervisor(d).run(s, kill=f"chunk:{crash_chunk}")
            rep, rec = res["report"], res["report"]["recovery"]
            row.update(replayed_records=rec["replayed_records"],
                       recovery_wall_s=rec["recovery_wall_s"],
                       snapshot_chunk=rec["snapshot_chunk"])
            row["ok_recovered"] = res["recovered"]
            row["ok_bitwise"] = (rep["carry_sha"] == ref["carry_sha"]
                                 and rep["counters"] == ref["counters"])
        except Exception:
            row["ok_no_exception"] = False
            row["traceback"] = traceback.format_exc()
        rows.append(row)
    return rows


def _gates(row: dict) -> list[str]:
    return [k for k, v in row.items() if k.startswith("ok_") and not v]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="CI-sized run")
    ap.add_argument("--out", default="BENCH_recovery.json")
    args = ap.parse_args(argv)
    compile_cache.enable()

    n, push, chunk = (1536, 256, 128) if args.quick else (3072, 256, 128)

    out = {"quick": bool(args.quick), "backend": None,
           "n_events": n, "chunk_size": chunk, "cells": [],
           "cadence_sweep": []}
    t_all = time.time()

    print("cell,kill,replayed,rejected,recovery_s,gates")
    sites = list(RT.KILL_SITES)
    i = 0
    for backend in BACKENDS:
        for shedder in SHEDDERS:
            site = sites[i % len(sites)]
            spec = make_spec(backend, shedder, n, push, chunk)
            try:
                with tempfile.TemporaryDirectory() as d:
                    ref = SV.Supervisor(d).reference(spec)
                out["backend"] = ref["platform"]
            except Exception:
                out["cells"].append({
                    "cell": f"{backend}/{shedder}", "kill_site": site,
                    "ok_no_exception": False,
                    "traceback": traceback.format_exc()})
                i += 1
                continue
            row = run_cell(backend, shedder, site, spec, ref, seed=100 + i)
            bad = _gates(row)
            out["cells"].append(row)
            print(f"{row['cell']},{row.get('kill_spec', '?')},"
                  f"{row.get('replayed_records', '-')},"
                  f"{row.get('rejected_snapshots', '-')},"
                  f"{row.get('recovery_wall_s', -1):.3f},"
                  f"{'FAIL:' + '+'.join(bad) if bad else 'pass'}")
            i += 1

    spec = make_spec(eng.BACKEND_XLA, eng.SHED_PSPICE, n, push, chunk)
    for row in cadence_sweep(spec, everies=(2, 4, 8)):
        bad = _gates(row)
        out["cadence_sweep"].append(row)
        print(f"{row['cell']},-,{row.get('replayed_records', '-')},-,"
              f"{row.get('recovery_wall_s', -1):.3f},"
              f"{'FAIL:' + '+'.join(bad) if bad else 'pass'}")

    failures = {r["cell"]: _gates(r)
                for r in out["cells"] + out["cadence_sweep"] if _gates(r)}
    out["failures"] = failures
    out["wall_s_total"] = time.time() - t_all
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    print(f"# wrote {args.out} ({out['wall_s_total']:.1f}s)",
          file=sys.stderr)
    if failures:
        print(f"# RECOVERY GATE FAILURES: {failures}", file=sys.stderr)
        for r in out["cells"] + out["cadence_sweep"]:
            if r.get("traceback"):
                print(r["traceback"], file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
