"""Per-chunk runtime telemetry (DESIGN.md §7, §8).

Between chunks the host owns control — but the chunk-size overhead budget
(<10% vs the monolithic scan, BENCH_engine.json) leaves no room for the
old per-chunk pattern of four chunk-sized device→host copies plus numpy
percentiles plus half a dozen scalar reads.  All per-chunk reductions now
run ON DEVICE in one fused jit (``device_chunk_stats``) and cross to the
host as a single ~12-float vector per chunk; that transfer doubles as the
synchronization point the wall-clock measurement needs.  The log
aggregates chunk walls, p50/p99 event latency and shed/overflow counters.

``TelemetryLog.span`` names the host's work inside ``push`` (admission,
WAL, slicing, dispatch, device wait, stats, match decode, refresh, guard,
snapshot): each span is a ``jax.profiler.TraceAnnotation``, so a running
profiler trace shows it beside the device's operations, and its self time
is charged to a chunk's in-memory ``spans``; ``TelemetryLog.count`` does
the same for counters (DESIGN.md §7).
"""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.cep.engine import Carry, StepOut

# Carry accumulator scalars differenced per chunk.
_COUNTERS = ("pms_shed", "shed_calls", "overflow", "ebl_dropped")

# The device_chunk_stats vector layout — the SINGLE place that names the
# slots.  _chunk_stats_device stacks in this order; summarize_chunk and
# counters_from_vec read by name through _VEC.
_VEC_FIELDS = ("l_e_p50", "l_e_p99", "l_e_max", "n_pm_end", "shed_events",
               "dropped_events") + _COUNTERS + ("complex_count",)
_VEC = {name: i for i, name in enumerate(_VEC_FIELDS)}


def counter_snapshot(carry: Carry) -> dict[str, float]:
    """Host copies of the carry's scalar counters (+ total completions).
    Used once per stream for the first chunk's baseline; steady-state
    chunks reuse the counter tail of the previous ``device_chunk_stats``
    vector instead."""
    snap = {k: float(np.asarray(getattr(carry, k)).sum()) for k in _COUNTERS}
    snap["complex_count"] = float(np.asarray(carry.complex_count).sum())
    return snap


@jax.jit
def _chunk_stats_device(outs: StepOut, counters: tuple) -> jax.Array:
    l_e = outs.l_e.reshape(-1)
    if l_e.shape[0] == 0:
        # Zero-length chunk (an empty push/drain): there are no events to
        # reduce over — jnp.quantile/max on an empty axis would produce
        # NaN / raise.  The latency/count slots are zero; the cumulative
        # counter tail still reads the carry so the next chunk's baseline
        # stays correct.  Static shape ⇒ this branch resolves at trace.
        z = jnp.float32(0.0)
        pieces = [z, z, z, z, z, z]
    else:
        qs = jnp.quantile(l_e, jnp.array([0.5, 0.99], l_e.dtype))
        pieces = [qs[0], qs[1], l_e.max(),          # l_e_p50 / p99 / max
                  outs.n_pm[..., -1].sum(),         # n_pm_end
                  outs.shed.sum(), outs.dropped.sum()]
    pieces += [c.sum() for c in counters]       # _COUNTERS + complex_count
    assert len(pieces) == len(_VEC_FIELDS)
    return jnp.stack([jnp.asarray(p).astype(jnp.float32) for p in pieces])


def device_chunk_stats(outs: StepOut, carry: Carry) -> jax.Array:
    """Every per-chunk reduction fused into ONE device computation: l_e
    p50/p99/max, end-of-chunk PM count, shed/dropped event counts, and the
    carry's cumulative counters.  Returns a (11,) f32 vector — the single
    device→host transfer each chunk costs."""
    counters = tuple(getattr(carry, k) for k in _COUNTERS)
    counters += (carry.complex_count,)
    return _chunk_stats_device(outs, counters)


def counters_from_vec(vec: np.ndarray) -> dict[str, float]:
    """The cumulative-counter tail of a ``device_chunk_stats`` vector, in
    ``counter_snapshot``'s format (the next chunk's 'before')."""
    return {k: float(vec[_VEC[k]]) for k in _COUNTERS + ("complex_count",)}


@dataclasses.dataclass
class ChunkStats:
    chunk_index: int
    start: int                  # global index of the chunk's first event
    n_events: int               # events processed (all lanes)
    n_lanes: int
    wall_s: float
    l_e_p50: float
    l_e_p99: float
    l_e_max: float
    n_pm_end: float             # active PMs after the chunk (all lanes)
    shed_events: int            # events at which a shed triggered
    dropped_events: int         # E-BL input drops
    pms_shed: float             # counter deltas over the chunk
    shed_calls: float
    overflow: float
    ebl_dropped: float
    completions: float
    refreshed: bool = False     # model refresh ran after this chunk
    refresh_wall_s: float = 0.0  # host time spent in/gating the refresh
    rung: int = 0               # degradation-ladder rung after this chunk

    def __post_init__(self):
        # Host seconds of self time by span name, and counters, charged to
        # this chunk by TelemetryLog.span / count.  Wall readings held in
        # memory only: not dataclass fields, so never in to_row(), the
        # snapshot, or a recovery comparison.
        self.spans: dict[str, float] = {}
        self.counts: dict[str, int] = {}

    def to_row(self) -> dict:
        return dataclasses.asdict(self)


# Row keys of snapshots written by earlier builds, no longer fields.
_RETIRED_ROW_KEYS = ("events_per_s",)


@dataclasses.dataclass
class RuntimeEvent:
    """A discrete runtime occurrence (ladder transition, guard violation,
    guard restore, admission backpressure) — the mirror CI's chaos gate
    checks runtime decisions against (DESIGN.md §12)."""
    kind: str            # "ladder" | "guard_violation" | "guard_restore" |
                         # "admission"
    chunk_index: int
    detail: dict = dataclasses.field(default_factory=dict)

    def to_row(self) -> dict:
        return dataclasses.asdict(self)


def summarize_chunk(chunk_index: int, start: int, n_events: int,
                    n_lanes: int, vec: np.ndarray,
                    before: dict[str, float], wall_s: float,
                    refreshed: bool = False,
                    refresh_wall_s: float = 0.0) -> ChunkStats:
    """Stats for one chunk from its ``device_chunk_stats`` vector (the
    chunk's one device→host transfer) + the previous chunk's cumulative
    counters."""
    after = counters_from_vec(vec)
    d = {k: after[k] - before[k] for k in before}
    v = lambda k: float(vec[_VEC[k]])  # noqa: E731
    return ChunkStats(
        chunk_index=chunk_index, start=start, n_events=n_events,
        n_lanes=n_lanes, wall_s=wall_s,
        l_e_p50=v("l_e_p50"), l_e_p99=v("l_e_p99"), l_e_max=v("l_e_max"),
        n_pm_end=v("n_pm_end"),
        shed_events=int(v("shed_events")),
        dropped_events=int(v("dropped_events")),
        pms_shed=d["pms_shed"], shed_calls=d["shed_calls"],
        overflow=d["overflow"], ebl_dropped=d["ebl_dropped"],
        completions=d["complex_count"], refreshed=refreshed,
        refresh_wall_s=refresh_wall_s,
    )


# The root span: one per ``StreamRuntime.push``.  What closes inside a
# chunk's work (``begin_chunk`` .. ``extend``) is that chunk's; what closes
# outside it waits for the root to close and goes to the group the push
# appended last (or, for a push that appends none, to the next one's).
PUSH_SPAN = "rt.push"


def _add(acc: dict, name: str, v) -> None:
    acc[name] = acc.get(name, 0) + v


class _Span:
    """One open span: a profiler annotation and a self-time clock."""
    __slots__ = ("log", "name", "ann", "t0", "child_ns")

    def __init__(self, log: "TelemetryLog", name: str, meta: dict):
        self.log, self.name = log, name
        self.ann = jax.profiler.TraceAnnotation(name, **meta)

    def __enter__(self) -> "_Span":
        self.ann.__enter__()
        self.child_ns = 0
        self.log._stack.append(self)
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        dur = time.perf_counter_ns() - self.t0
        stack = self.log._stack
        stack.pop()
        if stack:
            stack[-1].child_ns += dur
        self.log._charge(self.name, (dur - self.child_ns) * 1e-9, "spans")
        if not stack:
            self.log._close_root()
        self.ann.__exit__(*exc)


class TelemetryLog:
    """Append-only chunk log with run-level aggregation, and the host
    spans and counters charged to its chunks."""

    def __init__(self):
        self.chunks: list[ChunkStats] = []
        self.events: list[RuntimeEvent] = []
        self._stack: list[_Span] = []
        # Totals not yet charged, {"spans": ..., "counts": ...}: those of
        # the chunk's work under way, and those outside it in this push.
        self._chunk_held = {"spans": {}, "counts": {}}
        self._held = {"spans": {}, "counts": {}}
        self._last: list[ChunkStats] = []   # the group appended last
        self._running = False               # a chunk's work is under way

    # -- spans and counters ------------------------------------------------
    def span(self, name: str, **meta) -> _Span:
        """``with log.span("rt.decode.fetch"):`` -- a profiler annotation
        of that name (``meta`` rides as its metadata) whose self time (its
        duration less its children's) is charged under ``name``."""
        return _Span(self, name, meta)

    def count(self, name: str, n: int) -> None:
        self._charge(name, int(n), "counts")

    def begin_chunk(self) -> None:
        """A chunk's (or chunk group's) work starts: what closes from now
        until ``extend`` is that chunk's."""
        self._running = True

    def _charge(self, name: str, v, kind: str) -> None:
        held = self._chunk_held if self._running else self._held
        _add(held[kind], name, v)

    def _close_root(self) -> None:
        """The root span closed: the time held outside the chunks goes to
        the group appended last, if this root appended one."""
        if self._last:
            self._release(self._held, self._last)
            self._last = []

    def _release(self, held: dict, group: list[ChunkStats]) -> None:
        for kind in ("spans", "counts"):
            self._put(group, held[kind], kind)
            held[kind] = {}

    @staticmethod
    def _put(group: list[ChunkStats], d: dict, kind: str) -> None:
        """Spans split evenly over a group (as its wall is); counters go
        whole to its last chunk."""
        if kind == "counts":
            for k, v in d.items():
                _add(group[-1].counts, k, v)
            return
        for k, v in d.items():
            for c in group:
                _add(c.spans, k, v / len(group))

    def append(self, stats: ChunkStats) -> None:
        self.extend([stats])

    def extend(self, group: list[ChunkStats]) -> None:
        """Append the chunks of one dispatch, charging them what their
        work left held."""
        self.chunks.extend(group)
        self._release(self._chunk_held, group)
        self._last = list(group)
        self._running = False

    def record_event(self, kind: str, chunk_index: int,
                     detail: dict | None = None) -> RuntimeEvent:
        ev = RuntimeEvent(kind, chunk_index, detail or {})
        self.events.append(ev)
        return ev

    def events_of(self, kind: str) -> list[RuntimeEvent]:
        return [e for e in self.events if e.kind == kind]

    def rows(self) -> list[dict]:
        return [c.to_row() for c in self.chunks]

    def event_rows(self) -> list[dict]:
        return [e.to_row() for e in self.events]

    def to_json(self) -> dict:
        """JSON-able forensic dump: chunk rows + runtime events + the
        aggregate.  Rides inside every durable snapshot
        (repro.runtime.persist) and in the supervisor's dump-on-recovery
        hook, so post-crash telemetry survives the process."""
        return {"chunks": self.rows(), "events": self.event_rows(),
                "aggregate": self.aggregate()}

    @classmethod
    def from_json(cls, d: dict) -> "TelemetryLog":
        """Rebuild a log from ``to_json`` output (the aggregate is
        recomputed from the rows, never trusted)."""
        log = cls()
        log.load_json(d)
        return log

    def load_json(self, d: dict) -> None:
        """Replace the rows with ``to_json`` output, in place (spans and
        counters still held stay held, for the next chunk appended)."""
        self.chunks = [ChunkStats(**{k: v for k, v in row.items()
                                     if k not in _RETIRED_ROW_KEYS})
                       for row in d.get("chunks", [])]
        self.events = [RuntimeEvent(**row) for row in d.get("events", [])]
        self._last = []

    def aggregate(self) -> dict:
        if not self.chunks:
            return {"n_chunks": 0, "n_events": 0}
        return {
            "n_chunks": len(self.chunks),
            "n_events": sum(c.n_events for c in self.chunks),
            # chunk walls and refresh: not the push's whole host time
            # (WAL, snapshots, guard and admission are in the spans)
            "wall_s": sum(c.wall_s + c.refresh_wall_s for c in self.chunks),
            "refresh_wall_s": sum(c.refresh_wall_s for c in self.chunks),
            "l_e_p50_max": max(c.l_e_p50 for c in self.chunks),
            "l_e_p99_max": max(c.l_e_p99 for c in self.chunks),
            "l_e_max": max(c.l_e_max for c in self.chunks),
            "pms_shed": sum(c.pms_shed for c in self.chunks),
            "shed_calls": sum(c.shed_calls for c in self.chunks),
            "overflow": sum(c.overflow for c in self.chunks),
            "ebl_dropped": sum(c.ebl_dropped for c in self.chunks),
            "completions": sum(c.completions for c in self.chunks),
            "refreshes": sum(1 for c in self.chunks if c.refreshed),
            "max_rung": max(c.rung for c in self.chunks),
            "ladder_transitions": len(self.events_of("ladder")),
            "guard_violations": len(self.events_of("guard_violation")),
            "guard_restores": len(self.events_of("guard_restore")),
        }
