"""Process-level chaos harness: SIGKILL + restart + bitwise recovery
(DESIGN.md §13).

The in-process fault matrix (bench_faults) proves the runtime survives
corrupted STATE; this module proves it survives losing the PROCESS.  A
child worker runs a persist-enabled :class:`MatchRuntime` over a seeded
workload with a kill switch armed at one of the instrumented sites
(``faults.KILL_SITES``: mid-chunk, mid-refresh, mid-snapshot-write).
The supervisor launches it, watches it die with SIGKILL, relaunches it
WITHOUT the switch, and the restarted child recovers from the newest
valid snapshot + WAL tail and finishes the stream.  The final report —
carry sha256, telemetry counters, decoded match sets — must be bitwise
identical to an uninterrupted run, which bench_recovery checks across
every backend × shedder cell.

The child is this module run as ``__main__`` (``python -m
repro.runtime.supervisor --child``): kill specs travel in the
``PSPICE_KILL`` environment variable so the harness exercises the same
entry path an external process manager would use.
"""
from __future__ import annotations

import argparse
import dataclasses
import hashlib
import json
import os
import signal
import subprocess
import sys

import jax
import numpy as np

from repro import compile_cache
from repro.cep import engine as eng
from repro.cep import patterns as pat
from repro.cep import runner
from repro.data import streams
from repro.runtime import chunker, faults as FT, persist as PS
from repro.runtime import service as RT

# Simulated-cost scale matching benchmarks/bench_faults.py: chunk wall
# times land in the ladder's measurable range on small chaos workloads.
COST = dict(c_base=3e-4, c_match=6e-5, c_shed_base=1.5e-4,
            c_shed_pm=1.5e-6, c_ebl=6e-5)


class MatchRuntime(RT.StreamRuntime):
    """StreamRuntime that accumulates decoded match identities.

    Matches emitted BEFORE a snapshot are not re-emitted by WAL replay
    (replay starts at the snapshot), so the accumulator rides inside the
    snapshot via the ``_persist_extra`` hook — exactly the pattern an
    exactly-once downstream sink needs.  Requires ``cfg.emit_matches``
    and forces ``group_chunks=1`` (match decode is per chunk).
    """

    def __init__(self, cfg, model, rt, **kw):
        if not cfg.emit_matches:
            raise ValueError("MatchRuntime needs cfg.emit_matches")
        rt = dataclasses.replace(rt, group_chunks=1)
        super().__init__(cfg, model, rt, **kw)
        self.matches: list[set[tuple]] = [set() for _ in
                                          range(cfg.num_patterns)]

    def _run(self, chunk, start):
        carry, outs = super()._run(chunk, start)
        tm = self.telemetry
        with tm.span("rt.device_wait"):
            outs = jax.block_until_ready(outs)
        with tm.span("rt.decode.fetch"):
            cols = eng.match_columns(outs)
        with tm.span("rt.decode.sets"):
            # Set-union is idempotent, so a chunk that ran but died before
            # its snapshot re-absorbs the same identities on replay.
            for p, s in enumerate(eng.decode_matches(*cols, start)):
                self.matches[p] |= s
                tm.count("matches_decoded", len(s))
        return carry, outs

    def _run_group(self, start, piece, n_chunks):  # group_chunks == 1
        raise AssertionError("MatchRuntime must run chunk-at-a-time")

    def _persist_extra(self) -> dict:
        return {"matches": [sorted([list(map(int, m)) for m in s])
                            for s in self.matches]}

    def _persist_restore_extra(self, extra: dict) -> None:
        if "matches" in extra:
            self.matches = [{tuple(m) for m in s}
                            for s in extra["matches"]]


def build_workload(spec: dict):
    """Seeded (specs, cfg, model, events) — every knob from the spec
    dict, so the parent, the killed child and the restarted child build
    the IDENTICAL workload from the JSON spec alone."""
    specs = [pat.make_q1(window_size=400, num_symbols=4)]
    cp = pat.compile_patterns(specs)
    cfg = runner.default_config(
        cp, max_pms=spec["max_pms"], latency_bound=0.005,
        gather_stats=True, emit_matches=True, shedder=spec["shedder"],
        backend=spec["backend"], block_events=spec.get("block_events", 16),
        **COST)
    model = eng.make_model(cp, cfg)
    rate = spec.get("rate_mult", 3.0) / (cfg.c_base
                                         + cfg.c_match * 0.3 * cfg.max_pms)
    raw = streams.gen_stock(spec["n"], num_symbols=50, pattern_symbols=4,
                            p_class=0.05, seed=101)
    ev = streams.classify(specs, raw, rate=rate, seed=7)
    return specs, cfg, model, ev


def runtime_config(spec: dict, persist_dir: str | None) -> RT.RuntimeConfig:
    return RT.RuntimeConfig(
        chunk_size=spec["chunk"],
        refresh=RT.RF.RefreshConfig(
            every_chunks=spec.get("refresh_every", 4),
            min_observations=spec.get("min_observations", 64.0)),
        ingest=RT.IG.IngestConfig(max_queue_events=1 << 15,
                                  high_watermark=1 << 13,
                                  low_watermark=1 << 11, seed=5),
        ladder=RT.LadderConfig(escalate_streak=2, deescalate_streak=2,
                               latency_bound=0.01),
        guard=RT.GD.GuardConfig(check_every_chunks=1,
                                checkpoint_every_chunks=4),
        persist=None if persist_dir is None else PS.PersistConfig(
            dir=persist_dir,
            snapshot_every_chunks=spec.get("snapshot_every", 4)))


def carry_sha(srt: RT.StreamRuntime) -> str:
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(srt.carry):
        h.update(np.asarray(leaf).tobytes())
    return h.hexdigest()


# Wall-clock aggregate fields: real time, not recovered state — excluded
# from every divergence comparison.
WALL_FIELDS = ("wall_s", "refresh_wall_s")


def semantic_counters(srt: RT.StreamRuntime) -> dict:
    return {k: v for k, v in srt.telemetry.aggregate().items()
            if k not in WALL_FIELDS}


def run_service(spec: dict, persist_dir: str | None = None,
                telemetry_dump: str | None = None) -> dict:
    """One worker lifetime: recover (or cold-start), push the remaining
    stream, flush, report.  A cold start and a post-crash restart are THE
    SAME code path — recovery with an empty directory is a no-op."""
    specs, cfg, model, ev = build_workload(spec)
    srt = MatchRuntime(cfg, model, runtime_config(spec, persist_dir),
                       specs=specs)
    recovery = None
    if persist_dir is not None:
        recovery = srt.recover_from_disk()
        if recovery["replayed_records"] or recovery["snapshot_chunk"] \
                is not None:
            # Satellite hook: a REAL recovery dumps the restored
            # telemetry for post-mortem before new chunks dilute it.
            dump = telemetry_dump or os.path.join(
                persist_dir, "telemetry_recovered.json")
            with open(dump, "w") as f:
                json.dump(srt.telemetry.to_json(), f)
    # Resume the push loop after the last durable record: record ids are
    # global and one push == one record, so the WAL length IS the cursor.
    push = spec["push"]
    start_push = 0 if persist_dir is None \
        else srt.persist.wal.next_record_id
    n = chunker.num_events(ev)
    for s in range(start_push * push, n, push):
        srt.push(chunker.slice_events(ev, s, min(s + push, n)))
    srt.flush()
    dev = jax.devices()[0]
    return {
        "platform": dev.platform, "device_kind": dev.device_kind,
        "carry_sha": carry_sha(srt),
        "counters": semantic_counters(srt),
        "matches": [sorted([list(map(int, m)) for m in s])
                    for s in srt.matches],
        "events_processed": int(srt.events_processed),
        "recovery": recovery,
    }


def child_main(argv: list[str]) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--spec", required=True, help="workload spec JSON")
    ap.add_argument("--dir", default=None,
                    help="persistence directory (omit: no persistence)")
    ap.add_argument("--out", required=True, help="final report JSON path")
    args = ap.parse_args(argv)
    compile_cache.enable()
    FT.install_kill_from_env()
    report = run_service(json.loads(args.spec), persist_dir=args.dir)
    PS.atomic_write(args.out,
                    json.dumps(report, sort_keys=True).encode())
    return 0


class Supervisor:
    """Launch the child worker, expect the armed SIGKILL, relaunch until
    the report file appears.

    Every run of the workload — the uninterrupted reference included
    (:meth:`reference`) — happens in a child, so the supervising process
    never initializes a JAX backend: on a TPU host the device belongs to
    one process at a time, and a parent holding it would block the
    children.  Each report names the platform its child ran on."""

    def __init__(self, workdir: str):
        self.workdir = workdir
        self.attempts: list[dict] = []

    def _launch(self, spec: dict, out: str, kill: str | None,
                persist: bool = True):
        env = dict(os.environ)
        env["PYTHONPATH"] = os.pathsep.join(
            [os.path.dirname(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))))]
            + env.get("PYTHONPATH", "").split(os.pathsep))
        env.pop(FT.KILL_ENV, None)
        if kill is not None:
            env[FT.KILL_ENV] = kill
        cmd = [sys.executable, "-m", "repro.runtime.supervisor", "--child",
               "--spec", json.dumps(spec), "--out", out]
        if persist:
            cmd += ["--dir", os.path.join(self.workdir, "persist")]
        return subprocess.run(cmd, env=env, capture_output=True, text=True)

    def reference(self, spec: dict) -> dict:
        """The uninterrupted run (no persistence, no kill), in a child."""
        out = os.path.join(self.workdir, "reference.json")
        proc = self._launch(spec, out, kill=None, persist=False)
        if proc.returncode != 0:
            raise RuntimeError(f"reference child failed rc={proc.returncode}"
                               f":\n{proc.stderr[-2000:]}")
        with open(out, "rb") as f:
            return json.loads(f.read())

    def run(self, spec: dict, kill: str | None,
            max_restarts: int = 2) -> dict:
        """Returns {report, attempts, killed, recovered}; raises when the
        child fails for any reason other than the armed kill."""
        out = os.path.join(self.workdir, "report.json")
        killed = False
        for attempt in range(max_restarts + 1):
            want_kill = kill if attempt == 0 else None
            proc = self._launch(spec, out, want_kill)
            self.attempts.append({"attempt": attempt, "kill": want_kill,
                                  "returncode": proc.returncode})
            if proc.returncode == 0:
                with open(out, "rb") as f:
                    report = json.loads(f.read())
                return {"report": report, "attempts": self.attempts,
                        "killed": killed,
                        "recovered": killed and attempt > 0}
            if want_kill is not None \
                    and proc.returncode == -signal.SIGKILL:
                killed = True     # the armed crash — restart and recover
                continue
            raise RuntimeError(
                f"child attempt {attempt} failed rc={proc.returncode} "
                f"(kill={want_kill!r}):\n{proc.stderr[-2000:]}")
        raise RuntimeError(f"child did not finish in {max_restarts + 1} "
                           "attempts")


def main(argv: list[str] | None = None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if argv and argv[0] == "--child":
        return child_main(argv[1:])
    raise SystemExit("repro.runtime.supervisor is the chaos-harness child "
                     "entry point; drive it via benchmarks/"
                     "bench_recovery.py or Supervisor.run")


if __name__ == "__main__":
    sys.exit(main())
