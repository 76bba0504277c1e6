"""Durable persistence tests (DESIGN.md §13): snapshot codec, store
rotation + torn-file fallback, write-ahead log, bitwise crash recovery
through the streaming runtime, guard-control rewind, telemetry JSON,
and one REAL SIGKILL through the chaos-harness supervisor.

The load-bearing property: snapshot + WAL-tail replay lands the runtime
bitwise-identical — carry, counters, match sets — to a run that never
died, on every backend/shedder combination sampled here (the full grid
is benchmarks/bench_recovery.py).
"""
import dataclasses
import json
import os
import struct
import zlib

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # declared in requirements-dev.txt; deterministic
    from _hyp_fallback import given, settings, st  # fallback sweeps

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.runtime as RT
from repro.cep import engine as eng
from repro.cep import patterns as pat
from repro.cep import runner
from repro.data import streams
from repro.runtime import persist as PS
from repro.runtime import supervisor as SV

COST = dict(c_base=3e-4, c_match=6e-5, c_shed_base=1.5e-4, c_shed_pm=1.5e-6,
            c_ebl=6e-5)


def _assert_tree_equal(a, b, what=""):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=what)


def _spec(kind: str) -> pat.PatternSpec:
    return {"q1": lambda: pat.make_q1(window_size=400, num_symbols=4),
            "q2": lambda: pat.make_q2(window_size=300),
            "q3": lambda: pat.make_q3(any_n=3, window_size=200),
            "q4": lambda: pat.make_q4(any_n=3, window_size=120, slide=40),
            }[kind]()


def _randomize(tree, seed: int):
    """Same-shape pytree with seeded random bytes in every leaf — the
    codec must round-trip arbitrary states, not just freshly-inited
    ones."""
    rng = np.random.default_rng(seed)

    def rand(leaf):
        a = np.asarray(leaf)
        if a.dtype == bool:
            return rng.random(a.shape) < 0.5
        if np.issubdtype(a.dtype, np.integer):
            info = np.iinfo(a.dtype)
            return rng.integers(info.min, info.max, a.shape,
                                dtype=a.dtype, endpoint=True)
        return rng.standard_normal(a.shape).astype(a.dtype)

    return jax.tree.map(rand, tree)


# ---------------------------------------------------------------------------
# Snapshot codec: property round-trip + actionable failures
# ---------------------------------------------------------------------------

class TestSnapshotCodec:
    @given(st.integers(9, 61), st.sampled_from(["q1", "q2", "q3", "q4"]),
           st.integers(0, 5))
    @settings(max_examples=8, deadline=None)
    def test_roundtrip_bitwise(self, max_pms, kind, seed):
        """encode → container → parse → decode is bitwise for carry AND
        model pytrees, across odd/even max_pms, all pattern kinds (both
        spawn modes), with every leaf randomized.  Pure codec — no
        engine compile."""
        cp = pat.compile_patterns([_spec(kind)])
        cfg = runner.default_config(cp, max_pms=max_pms, **COST)
        carry = _randomize(eng.init_carry(cfg, seed=0), seed)
        model = _randomize(eng.make_model(cp, cfg), seed + 1)
        ctl = {"wal_next_record": 7, "nested": {"a": [1, 2.5, None]}}
        data = PS.build_snapshot_bytes(3, ctl, {"carry": carry,
                                                "model": model,
                                                "skipped": None})
        header, sections = PS.parse_snapshot_bytes(data)
        assert header["chunk_index"] == 3
        assert header["control"] == ctl
        assert set(sections) == {"carry", "model"}
        _assert_tree_equal(carry,
                           PS.decode_tree(*sections["carry"], carry,
                                          what="carry"), "carry")
        _assert_tree_equal(model,
                           PS.decode_tree(*sections["model"], model,
                                          what="model"), "model")

    @pytest.fixture(scope="class")
    def small(self):
        cp = pat.compile_patterns([_spec("q1")])
        cfg = runner.default_config(cp, max_pms=16, **COST)
        carry = eng.init_carry(cfg, seed=0)
        data = PS.build_snapshot_bytes(0, {"wal_next_record": 0},
                                       {"carry": carry})
        return cp, carry, data

    def test_torn_file_is_corrupt(self, small):
        _, _, data = small
        with pytest.raises(PS.CorruptSnapshotError, match="CRC"):
            PS.parse_snapshot_bytes(data[: len(data) // 2]
                                    + data[: len(data) - len(data) // 2])
        with pytest.raises(PS.CorruptSnapshotError, match="torn"):
            PS.parse_snapshot_bytes(data[:10])

    def test_wrong_magic(self, small):
        _, _, data = small
        with pytest.raises(PS.CorruptSnapshotError, match="magic"):
            PS.parse_snapshot_bytes(b"NOTSNAP!" + data[8:])

    def test_wrong_version_actionable(self, small):
        """A future-version file must fail on VERSION (with both numbers
        in the message), not on CRC — re-sign the tampered body."""
        _, _, data = small
        body = bytearray(data[len(PS.SNAP_MAGIC):-4])
        struct.pack_into("<I", body, 0, PS.SNAP_VERSION + 1)
        tampered = (PS.SNAP_MAGIC + bytes(body)
                    + struct.pack("<I", zlib.crc32(bytes(body))))
        with pytest.raises(PS.CorruptSnapshotError,
                           match=f"version {PS.SNAP_VERSION + 1}"):
            PS.parse_snapshot_bytes(tampered)

    def test_wrong_manifest_actionable(self, small):
        cp, carry, data = small
        _, sections = PS.parse_snapshot_bytes(data)
        other = eng.init_carry(
            runner.default_config(cp, max_pms=32, **COST), seed=0)
        with pytest.raises(PS.ManifestMismatchError, match="different "
                           "config"):
            PS.decode_tree(*sections["carry"], other, what="carry")

    def test_manifest_paths_are_named(self, small):
        _, carry, _ = small
        paths = [e["path"] for e in eng.pytree_manifest(carry)]
        assert ".pms.active" in paths and ".lat_ptr" in paths


# ---------------------------------------------------------------------------
# Store rotation / torn fallback + WAL reopen / truncation
# ---------------------------------------------------------------------------

class TestStoreAndWal:
    def test_rotation_and_torn_fallback(self, tmp_path):
        cp = pat.compile_patterns([_spec("q1")])
        cfg = runner.default_config(cp, max_pms=16, **COST)
        carry = eng.init_carry(cfg, seed=0)
        store = PS.SnapshotStore(str(tmp_path), keep_generations=2)
        for chunk in (1, 2, 3):
            p = store.save(chunk, {"wal_next_record": chunk},
                           {"carry": carry})
        assert len(store.paths()) == 2  # generation 1 pruned
        header, _, meta = store.load_latest()
        assert header["chunk_index"] == 3 and meta["rejected"] == []
        # Tear the newest generation: load falls back to the previous
        # one and records the rejection.
        data = open(p, "rb").read()
        with open(p, "wb") as f:
            f.write(data[: len(data) // 2])
        header, sections, meta = store.load_latest()
        assert header["chunk_index"] == 2
        assert len(meta["rejected"]) == 1
        assert "CRC" in meta["rejected"][0]["error"]
        _assert_tree_equal(carry, PS.decode_tree(*sections["carry"], carry))

    def test_wal_append_reopen_replay(self, tmp_path):
        ev = eng.EventBatch(*[np.arange(4, dtype=np.float32) + i
                              for i in range(len(eng.EventBatch._fields))])
        ev2 = jax.tree.map(lambda x: x * 3, ev)
        wal = PS.WriteAheadLog(str(tmp_path), fsync_every=2)
        assert (wal.append(ev), wal.append(ev2)) == (0, 1)
        wal.close()
        # Reopen resumes ids; a fresh append lands in a NEW segment.
        wal = PS.WriteAheadLog(str(tmp_path))
        assert wal.next_record_id == 2
        assert wal.append(ev) == 2
        wal.close()
        assert len(wal.segments()) == 2
        recs = PS.WriteAheadLog(str(tmp_path)).records_since(1)
        assert [r[0] for r in recs] == [1, 2]
        _assert_tree_equal(ev2, recs[0][1], "record 1")
        _assert_tree_equal(ev, recs[1][1], "record 2")

    def test_truncated_segment_actionable(self, tmp_path):
        ev = eng.EventBatch(*[np.zeros(3, np.float32)
                              for _ in eng.EventBatch._fields])
        wal = PS.WriteAheadLog(str(tmp_path))
        wal.append(ev)
        wal.append(ev)
        seg = wal.segments()[-1][1]
        wal.close()
        data = open(seg, "rb").read()
        with open(seg, "wb") as f:
            f.write(data[:-5])
        with pytest.raises(PS.CorruptSegmentError, match="torn record"):
            PS.WriteAheadLog(str(tmp_path))

    def test_config_validation(self, tmp_path):
        with pytest.raises(ValueError, match="snapshot_every_chunks"):
            PS.PersistConfig(dir=str(tmp_path), snapshot_every_chunks=0)
        with pytest.raises(ValueError, match="keep_generations"):
            PS.PersistConfig(dir=str(tmp_path), keep_generations=0)
        with pytest.raises(ValueError, match="dir"):
            PS.PersistConfig(dir="")


# ---------------------------------------------------------------------------
# Recovery through the streaming runtime: bitwise resume
# ---------------------------------------------------------------------------

N_EVENTS = 1536
PUSH = 256

# Wall-clock aggregate fields are real time, not recovered state.
WALL = SV.WALL_FIELDS


@pytest.fixture(scope="module")
def workload():
    specs = [pat.make_q1(window_size=400, num_symbols=4)]
    cp = pat.compile_patterns(specs)

    def build(backend, shedder, max_pms=32):
        cfg = runner.default_config(cp, max_pms=max_pms,
                                    latency_bound=0.005, gather_stats=True,
                                    shedder=shedder, backend=backend,
                                    block_events=16, **COST)
        model = eng.make_model(cp, cfg)
        rate = 3.0 / (cfg.c_base + cfg.c_match * 0.3 * cfg.max_pms)
        raw = streams.gen_stock(N_EVENTS, num_symbols=50,
                                pattern_symbols=4, p_class=0.05, seed=101)
        ev = streams.classify(specs, raw, rate=rate, seed=7)
        return specs, cfg, model, ev

    return build


def _resilient_rt(persist_dir=None, snapshot_every=4):
    return RT.RuntimeConfig(
        chunk_size=128,
        refresh=RT.RefreshConfig(every_chunks=4, min_observations=64.0),
        ingest=RT.IngestConfig(max_queue_events=1 << 15,
                               high_watermark=1 << 13,
                               low_watermark=1 << 11, seed=5),
        ladder=RT.LadderConfig(escalate_streak=2, deescalate_streak=2,
                               latency_bound=0.01),
        guard=RT.GuardConfig(check_every_chunks=1,
                             checkpoint_every_chunks=4),
        persist=None if persist_dir is None else PS.PersistConfig(
            dir=str(persist_dir), snapshot_every_chunks=snapshot_every))


def _push_all(srt, ev, lo=0):
    n = RT.num_events(ev)
    for s in range(lo * PUSH, n, PUSH):
        srt.push(RT.slice_events(ev, s, min(s + PUSH, n)))
    srt.flush()


def _semantic(srt):
    return {k: v for k, v in srt.telemetry.aggregate().items()
            if k not in WALL}


class TestRuntimeRecovery:
    @pytest.mark.parametrize("backend,shedder", [
        (eng.BACKEND_XLA, eng.SHED_PSPICE),
        (eng.BACKEND_PALLAS_BLOCK, eng.SHED_PMBL),
    ])
    def test_crash_resume_bitwise(self, workload, tmp_path, backend,
                                  shedder):
        """Abandon a persist-enabled runtime mid-stream (disk state is
        exactly what SIGKILL leaves), recover in a FRESH runtime, finish
        the stream: carry, counters and event totals must equal the
        uninterrupted run bit for bit — full resilience stack on."""
        specs, cfg, model, ev = workload(backend, shedder)
        clean = RT.StreamRuntime(cfg, model, _resilient_rt(), specs=specs)
        _push_all(clean, ev)

        a = RT.StreamRuntime(cfg, model, _resilient_rt(tmp_path),
                             specs=specs)
        for s in range(0, 3 * PUSH, PUSH):
            a.push(RT.slice_events(ev, s, s + PUSH))
        a.persist.wal.close()
        del a

        b = RT.StreamRuntime(cfg, model, _resilient_rt(tmp_path),
                             specs=specs)
        rep = b.recover_from_disk()
        assert rep["snapshot_chunk"] is not None
        assert b.persist.wal.next_record_id == 3
        _push_all(b, ev, lo=3)
        _assert_tree_equal(clean.carry, b.carry, "recovered carry")
        assert _semantic(clean) == _semantic(b)
        assert clean.events_processed == b.events_processed

    def test_recover_empty_dir_is_noop(self, workload, tmp_path):
        specs, cfg, model, ev = workload(eng.BACKEND_XLA, eng.SHED_PSPICE)
        srt = RT.StreamRuntime(cfg, model, _resilient_rt(tmp_path),
                               specs=specs)
        rep = srt.recover_from_disk()
        assert rep["snapshot_chunk"] is None
        assert rep["replayed_records"] == 0

    def test_snapshot_requires_persist(self, workload):
        specs, cfg, model, _ = workload(eng.BACKEND_XLA, eng.SHED_PSPICE)
        srt = RT.StreamRuntime(cfg, model, _resilient_rt(), specs=specs)
        with pytest.raises(ValueError, match="persist"):
            srt.snapshot_now()
        with pytest.raises(ValueError, match="persist"):
            srt.recover_from_disk()

    def test_multitenant_roundtrip(self, workload, tmp_path):
        """Lane-stacked runtime: snapshot + recovery must preserve every
        lane's carry and per-lane queue state bitwise."""
        specs, cfg, model, ev = workload(eng.BACKEND_XLA, eng.SHED_PSPICE)
        L = 2
        evL = RT.stack([ev, ev])
        mL = RT.broadcast_model(model, L)
        rt_kw = dict(chunk_size=128,
                     guard=RT.GuardConfig(check_every_chunks=1,
                                          checkpoint_every_chunks=2))
        clean = RT.MultiTenantRuntime(
            cfg, mL, num_lanes=L, rt=RT.RuntimeConfig(**rt_kw),
            specs=specs)
        clean.push(evL, flush=True)

        mt = RT.MultiTenantRuntime(
            cfg, RT.broadcast_model(model, L), num_lanes=L,
            rt=RT.RuntimeConfig(persist=PS.PersistConfig(
                dir=str(tmp_path), snapshot_every_chunks=2), **rt_kw),
            specs=specs)
        half = (RT.num_events(evL, axis=1) // 2 // 128) * 128
        mt.push(RT.slice_events(evL, 0, half, axis=1))
        mt.persist.wal.close()
        del mt

        mt2 = RT.MultiTenantRuntime(
            cfg, RT.broadcast_model(model, L), num_lanes=L,
            rt=RT.RuntimeConfig(persist=PS.PersistConfig(
                dir=str(tmp_path), snapshot_every_chunks=2), **rt_kw),
            specs=specs)
        rep = mt2.recover_from_disk()
        assert rep["replayed_records"] >= 0
        mt2.push(RT.slice_events(evL, half, RT.num_events(evL, axis=1),
                                 axis=1), flush=True)
        _assert_tree_equal(clean.carry, mt2.carry, "lane carries")
        assert _semantic(clean) == _semantic(mt2)


# ---------------------------------------------------------------------------
# Guard control rewind (satellite regression)
# ---------------------------------------------------------------------------

class TestGuardControlRewind:
    def test_restore_rewinds_ladder_rung_and_admission(self, workload):
        """Checkpoint while ESCALATED, de-escalate, poison the carry:
        the guard restore must resume at the checkpointed rung with the
        matching standing input-shed fraction — not at the pre-fault
        rung.  (Before control-state checkpointing, restores rewound
        the arrays but left the controllers at post-fault values.)"""
        specs, cfg, model, ev = workload(eng.BACKEND_XLA, eng.SHED_PSPICE)
        srt = RT.StreamRuntime(cfg, model, _resilient_rt(), specs=specs)
        srt.push(RT.slice_events(ev, 0, 2 * PUSH))

        # Drive the ladder to INPUT_SHED via its own observe path.
        for _ in range(4):
            srt._apply_ladder(srt.ladder.observe(True, srt._chunk_i))
        assert srt.ladder.rung == RT.RUNG_INPUT_SHED
        assert srt.ingest.forced_drop > 0
        srt.guard.save(srt.carry, srt.model, srt._chunk_i,
                       control=srt._control_state(scope="guard"))
        n_transitions = len(srt.ladder.transitions)

        # De-escalate back to normal, then poison the carry.
        for _ in range(4):
            srt._apply_ladder(srt.ladder.observe(False, srt._chunk_i))
        assert srt.ladder.rung == RT.RUNG_NORMAL
        assert srt.ingest.forced_drop == 0.0
        srt.carry = srt.carry._replace(
            sim_time=jnp.full_like(srt.carry.sim_time, jnp.nan))
        viols = srt.guard_now()
        assert viols and srt.guard.restores == 1

        # Rung, streaks and standing admission effects all rewound ...
        assert srt.ladder.rung == RT.RUNG_INPUT_SHED
        assert srt.ingest.forced_drop \
            == srt.rt.ladder.input_shed_frac
        # ... but the transitions LOG is history, not state: the
        # de-escalations stay recorded (ladder/telemetry mirror).
        assert len(srt.ladder.transitions) > n_transitions
        assert len(srt.ladder.transitions) \
            == len(srt.telemetry.events_of("ladder"))

    def test_quarantine_counter_rides_checkpoint(self, workload,
                                                 tmp_path):
        specs, cfg, model, ev = workload(eng.BACKEND_XLA, eng.SHED_PSPICE)
        srt = RT.StreamRuntime(cfg, model, _resilient_rt(tmp_path),
                               specs=specs)
        srt.push(RT.slice_events(ev, 0, PUSH))
        srt.quarantine_dropped = 17
        srt.snapshot_now()
        b = RT.StreamRuntime(cfg, model, _resilient_rt(tmp_path),
                             specs=specs)
        b.recover_from_disk()
        assert b.quarantine_dropped == 17


# ---------------------------------------------------------------------------
# Telemetry JSON round-trip (satellite)
# ---------------------------------------------------------------------------

class TestTelemetryJson:
    def test_roundtrip(self, workload):
        specs, cfg, model, ev = workload(eng.BACKEND_XLA, eng.SHED_PSPICE)
        srt = RT.StreamRuntime(cfg, model, _resilient_rt(), specs=specs)
        srt.push(RT.slice_events(ev, 0, 2 * PUSH), flush=True)
        d = srt.telemetry.to_json()
        json.dumps(d)  # must be pure JSON
        back = RT.TelemetryLog.from_json(d)
        assert [dataclasses.asdict(r) for r in back.chunks] \
            == [dataclasses.asdict(r) for r in srt.telemetry.chunks]
        assert [dataclasses.asdict(r) for r in back.events] \
            == [dataclasses.asdict(r) for r in srt.telemetry.events]
        # The aggregate is recomputed, never trusted from the file.
        assert back.aggregate() == srt.telemetry.aggregate()

    def test_aggregate_not_trusted(self, workload):
        specs, cfg, model, ev = workload(eng.BACKEND_XLA, eng.SHED_PSPICE)
        srt = RT.StreamRuntime(cfg, model, _resilient_rt(), specs=specs)
        srt.push(RT.slice_events(ev, 0, PUSH), flush=True)
        d = srt.telemetry.to_json()
        d["aggregate"]["n_events"] = -999
        assert RT.TelemetryLog.from_json(d).aggregate()["n_events"] \
            == srt.telemetry.aggregate()["n_events"]


# ---------------------------------------------------------------------------
# The real thing: SIGKILL a subprocess, restart, bitwise recovery
# ---------------------------------------------------------------------------

class TestSupervisorSigkill:
    def test_sigkill_mid_chunk_recovers_bitwise(self, tmp_path):
        spec = {"backend": eng.BACKEND_XLA, "shedder": eng.SHED_PSPICE,
                "n": 1024, "push": 256, "chunk": 128, "max_pms": 32,
                "rate_mult": 3.0, "refresh_every": 4, "snapshot_every": 3,
                "min_observations": 64.0}
        sup = SV.Supervisor(str(tmp_path))
        ref = sup.reference(spec)      # the uninterrupted run, in a child
        res = sup.run(spec, kill="chunk:3")
        assert res["killed"] and res["recovered"]
        assert res["attempts"][0]["returncode"] == -9
        rep = res["report"]
        assert rep["carry_sha"] == ref["carry_sha"]
        assert rep["matches"] == ref["matches"]
        assert rep["counters"] == ref["counters"]
        assert rep["events_processed"] == ref["events_processed"]
        assert rep["platform"] == ref["platform"]
        # Satellite: a real recovery dumps the restored telemetry.
        dump = os.path.join(str(tmp_path), "persist",
                            "telemetry_recovered.json")
        assert os.path.exists(dump)
        with open(dump) as f:
            assert "chunks" in json.load(f)

    def test_kill_switch_env_spec(self, monkeypatch):
        from repro.runtime import faults as FT
        prev = FT.active_kill_switch()
        monkeypatch.setenv(RT.KILL_ENV, "refresh:2")
        try:
            ks = RT.install_kill_from_env()
            assert ks is FT.active_kill_switch()
            assert ks is not None and ks.spec() == "refresh:2"
            assert not ks.pending("chunk")
            assert not ks.pending("refresh")
            assert ks.pending("refresh")
        finally:
            FT.install_kill_switch(prev)

    def test_plan_kill_is_seeded(self):
        draws = []
        for _ in range(2):
            inj = RT.FaultInjector(RT.FaultConfig(
                kinds=RT.PROCESS_FAULTS, seed=11))
            draws.append(inj.plan_kill("chunk", lo=2, hi=9).spec())
        assert draws[0] == draws[1]
        with pytest.raises(ValueError, match="process_kill"):
            RT.FaultInjector(RT.FaultConfig(
                kinds=("burst",), seed=1)).plan_kill("chunk")
