"""Host spans and counters inside ``StreamRuntime.push`` (DESIGN.md §7):
each span lands in a profiler trace under ``rt.push``, its self time is
charged to a chunk so that a push's self times partition the push, the
counters equal the snapshot bytes on disk and the growth of the match
sets, and none of it enters a snapshot or changes a result."""
import dataclasses
import glob
import os
import sys
import time

import jax
import pytest

import repro.runtime as RT
from repro.cep import engine as eng
from repro.runtime import persist as PS
from repro.runtime import supervisor as SV
from repro.runtime import telemetry as TM

CHIPBENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks", "chip")
sys.path.insert(0, CHIPBENCH)

import trace_reduce as TR  # noqa: E402

# A toy served stream: 4 pushes of 2 chunks, so refresh, the guard
# checkpoint and a snapshot each fall due twice.
SPEC = {"backend": eng.BACKEND_XLA, "shedder": eng.SHED_PSPICE, "n": 1024,
        "push": 256, "chunk": 128, "max_pms": 32, "rate_mult": 1.0,
        "refresh_every": 4, "snapshot_every": 4, "min_observations": 4.0}

CHUNK_SPANS = ("rt.dispatch", "rt.device_wait", "rt.stats",
               "rt.decode.fetch", "rt.decode.sets", "rt.refresh.obs",
               "rt.refresh.fit", "rt.refresh.deploy", "rt.guard.check",
               "rt.guard.checkpoint")
PUSH_SPANS = ("rt.push", "rt.admit", "rt.wal.append", "rt.wal.fsync",
              "rt.slice", "rt.snapshot.control", "rt.snapshot.encode",
              "rt.snapshot.write")


def _served(persist_dir):
    specs, cfg, model, ev = SV.build_workload(SPEC)
    rtc = SV.runtime_config(SPEC, str(persist_dir))
    # Keep every snapshot generation, so the files on disk are all of
    # them.
    rtc = dataclasses.replace(rtc, persist=dataclasses.replace(
        rtc.persist, keep_generations=100))
    return SV.MatchRuntime(cfg, model, rtc, specs=specs), ev


def _run(persist_dir, trace_dir=None):
    """Push the stream; returns the runtime and, per push, its chunks and
    the growth of the decoded match sets."""
    srt, ev = _served(persist_dir)
    push, n = SPEC["push"], SPEC["n"]
    if trace_dir is not None:
        jax.profiler.start_trace(str(trace_dir))
    pushes = []
    for lo in range(0, n, push):
        before = sum(len(m) for m in srt.matches)
        stats = srt.push(RT.slice_events(ev, lo, lo + push))
        pushes.append((stats, sum(len(m) for m in srt.matches) - before))
    if trace_dir is not None:
        jax.profiler.stop_trace()
    return srt, pushes


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    d = tmp_path_factory.mktemp("spans")
    srt, pushes = _run(d / "persist", d / "trace")
    host = [ev for p in TR.load(str(d / "trace"))["planes"]
            if not p["name"].startswith(TR.DEVICE_PREFIX)
            for ln in p["lines"] for ev in ln["events"]]
    return srt, pushes, host, d / "persist"


def test_every_span_in_the_trace_under_a_push(traced):
    srt, pushes, host, _ = traced
    roots = sorted((s, s + d) for name, s, d in host
                   if name == TM.PUSH_SPAN)
    assert len(roots) == len(pushes)
    names = {name for name, _, _ in host}
    assert set(CHUNK_SPANS + PUSH_SPANS) <= names
    for name, s, d in host:
        if name != TM.PUSH_SPAN:
            assert any(a <= s and s + d <= b for a, b in roots), name


def test_every_span_charged_to_chunks(traced):
    srt, *_ = traced
    charged = {k for c in srt.telemetry.chunks for k in c.spans}
    assert charged == set(CHUNK_SPANS + PUSH_SPANS)
    assert all(v >= 0 for c in srt.telemetry.chunks
               for v in c.spans.values())


def test_self_times_partition_each_push(traced):
    _, pushes, host, _ = traced
    roots = sorted((s, d) for name, s, d in host if name == TM.PUSH_SPAN)
    for (stats, _), (_, dur_ns) in zip(pushes, roots):
        assert stats, "every toy push runs its chunks"
        total = sum(v for c in stats for v in c.spans.values())
        assert total == pytest.approx(dur_ns * 1e-9, rel=0.01)


def test_push_level_spans_on_the_pushs_last_chunk(traced):
    _, pushes, _, _ = traced
    for stats, _ in pushes:
        first, last = stats[0], stats[-1]
        assert "rt.push" in last.spans and "rt.wal.append" in last.spans
        assert not set(PUSH_SPANS) & set(first.spans)
        assert "rt.dispatch" in first.spans


def test_chunk_spans_inside_the_chunk_wall(traced):
    srt, *_ = traced
    inner = ("rt.dispatch", "rt.device_wait", "rt.stats",
             "rt.decode.fetch", "rt.decode.sets")
    for c in srt.telemetry.chunks:
        assert sum(c.spans.get(k, 0.0) for k in inner) <= c.wall_s
        refresh = sum(v for k, v in c.spans.items()
                      if k.startswith("rt.refresh."))
        assert refresh <= c.refresh_wall_s + 1e-6


def _total(srt, name):
    return sum(c.counts.get(name, 0) for c in srt.telemetry.chunks)


def test_time_outside_a_chunk_waits_for_the_push(traced):
    # The guard checks every chunk, after the chunk is appended: the check
    # that follows a push's first chunk lands on its last chunk.
    _, pushes, _, _ = traced
    for stats, _ in pushes:
        assert len(stats) == 2
        assert "rt.guard.check" not in stats[0].spans
        assert stats[1].spans["rt.guard.check"] > 0


def test_snapshot_bytes_are_the_files_on_disk(traced):
    srt, _, _, d = traced
    snaps = glob.glob(os.path.join(d, "snap-*.ckpt"))
    assert len(snaps) == 2
    assert _total(srt, "snapshot_bytes") \
        == sum(os.path.getsize(p) for p in snaps)
    for p in snaps:
        with open(p, "rb") as f:
            data = f.read()
        hlen = int.from_bytes(data[len(PS.SNAP_MAGIC) + 4:
                                   len(PS.SNAP_MAGIC) + 8], "little")
        assert 0 < hlen < len(data)


def test_matches_decoded_is_the_growth_of_the_match_sets(traced):
    srt, pushes, _, _ = traced
    assert sum(g for _, g in pushes) > 0
    for stats, grown in pushes:
        assert sum(c.counts.get("matches_decoded", 0)
                   for c in stats) == grown


def test_snapshot_bytes_do_not_hold_spans(tmp_path):
    srt, _ = _run(tmp_path)
    path = srt.snapshot_now()
    with open(path, "rb") as f:
        plain = f.read()
    for c in srt.telemetry.chunks:
        c.spans = {"rt.made_up": 1.0}
        c.counts = {"made_up": 7}
    assert srt.snapshot_now() == path
    with open(path, "rb") as f:
        assert f.read() == plain
    row = srt.telemetry.chunks[0].to_row()
    assert "spans" not in row and "counts" not in row


def _semantic_snapshot(path):
    """A snapshot's state with the chunk rows' wall readings left out."""
    with open(path, "rb") as f:
        header, sections = PS.parse_snapshot_bytes(f.read())
    for row in header["control"]["telemetry"]["chunks"]:
        for k in SV.WALL_FIELDS:
            row.pop(k)
    header["control"]["telemetry"].pop("aggregate")
    return header, {k: bytes(v[1]) for k, v in sections.items()}


def test_a_trace_changes_no_state(traced, tmp_path):
    srt, *_ = traced
    plain, _ = _run(tmp_path / "persist")
    assert SV.carry_sha(plain) == SV.carry_sha(srt)
    assert plain.matches == srt.matches
    assert SV.semantic_counters(plain) == SV.semantic_counters(srt)
    a = PS.SnapshotStore(str(tmp_path / "persist"), plain.telemetry).paths()
    b = PS.SnapshotStore(str(traced[3]), srt.telemetry).paths()
    assert _semantic_snapshot(a[1]) == _semantic_snapshot(b[1])


ROW = dict(chunk_index=0, start=0, n_events=8, n_lanes=1, wall_s=0.5,
           l_e_p50=0.0, l_e_p99=0.0, l_e_max=0.0, n_pm_end=0.0,
           shed_events=0, dropped_events=0, pms_shed=0.0, shed_calls=0.0,
           overflow=0.0, ebl_dropped=0.0, completions=0.0)


def test_retired_row_key_is_ignored():
    # A row written before the per-chunk events_per_s was removed.
    log = TM.TelemetryLog.from_json(
        {"chunks": [dict(ROW, events_per_s=16.0)]})
    assert log.chunks[0].wall_s == 0.5
    assert "events_per_s" not in log.chunks[0].to_row()


def test_grouped_dispatch_splits_spans_evenly():
    specs, cfg, model, ev = SV.build_workload(SPEC)
    srt = RT.StreamRuntime(cfg, model, RT.RuntimeConfig(
        chunk_size=128, group_chunks=4,
        refresh=RT.RefreshConfig(every_chunks=4, min_observations=4.0),
        guard=RT.GuardConfig(check_every_chunks=4)), specs=specs)
    stats = srt.push(RT.slice_events(ev, 0, 512))
    assert len(stats) == 4
    for k in ("rt.dispatch", "rt.device_wait", "rt.stats",
              "rt.refresh.fit", "rt.guard.check", "rt.push"):
        vals = [c.spans[k] for c in stats]
        assert vals == pytest.approx([vals[0]] * 4), k


def test_a_push_without_chunks_carries_its_time_forward():
    specs, cfg, model, ev = SV.build_workload(SPEC)
    rtc = RT.RuntimeConfig(chunk_size=128, group_chunks=1)
    srt = RT.StreamRuntime(cfg, model, rtc, specs=specs)
    assert srt.push(RT.slice_events(ev, 0, 100)) == []
    stats = srt.push(RT.slice_events(ev, 100, 256))
    assert len(stats) == 2
    # Both pushes' roots and slicing land on the second push's last chunk.
    assert stats[-1].spans["rt.slice"] > 0
    assert stats[-1].spans["rt.push"] > 0
    assert "rt.push" not in stats[0].spans


def test_span_overhead_without_a_trace():
    log = TM.TelemetryLog()
    n = 2000
    t0 = time.perf_counter()
    for _ in range(n):
        with log.span("rt.stats"):
            pass
    assert (time.perf_counter() - t0) / n < 50e-6
    # Held until a push appends a chunk, then charged to it.
    log.append(TM.ChunkStats(**ROW))
    assert "rt.stats" not in log.chunks[0].spans
    with log.span(TM.PUSH_SPAN):
        log.append(TM.ChunkStats(**ROW))
    assert log.chunks[1].spans["rt.stats"] > 0


@pytest.mark.parametrize("group_chunks", [1, 4])
def test_lane_refresh_spans_once_for_all_lanes(group_chunks):
    """One due refresh over L tenant lanes opens each ``rt.refresh.*``
    span once, not once a lane, and counts every lane as refit or
    skipped (``lanes_refit``, ``lanes_skipped``) on the chunk it ends."""
    specs, cfg, model, ev = SV.build_workload(SPEC)
    L, n = 4, 256
    evL = RT.stack([RT.slice_events(ev, lane * n, (lane + 1) * n)
                    for lane in range(L)])
    # The last lane opens no window: no PM, no observation to fit.
    evL = evL._replace(ev_open=evL.ev_open.at[L - 1].set(False))
    mt = RT.MultiTenantRuntime(
        cfg, RT.broadcast_model(model, L), num_lanes=L, specs=specs,
        rt=RT.RuntimeConfig(chunk_size=64, group_chunks=group_chunks,
                            refresh=RT.RefreshConfig(
                                every_chunks=4, min_observations=4.0)))
    opened, span = [], mt.telemetry.span

    def recording(name, **meta):
        opened.append(name)
        return span(name, **meta)
    mt.telemetry.span = recording
    stats = mt.push(evL)
    assert len(stats) == 4 and stats[-1].refreshed
    assert sorted(k for k in opened if k.startswith("rt.refresh.")) == [
        "rt.refresh.deploy", "rt.refresh.fit", "rt.refresh.obs"]
    assert stats[-1].counts == {"lanes_refit": L - 1, "lanes_skipped": 1}
    assert all(not c.counts for c in stats[:-1])
    assert [st.skipped_obs for st in mt.refresh_state] == [0] * (L - 1) + [1]
