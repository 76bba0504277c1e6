"""Vectorized CEP operator with pSPICE load shedding (paper §III).

The operator keeps a fixed-capacity dense PM store per pattern and advances
EVERY active PM against each incoming event in one vectorized step; the whole
stream is one ``lax.scan`` (or, for unbounded streams, consecutive
``run_engine_chunk`` scans driven by ``repro.runtime`` — DESIGN.md §7).
Latency is tracked with a deterministic
simulated-time model calibrated against the real (wall-clock) cost of the
jitted engine — see DESIGN.md §3 "Wall-clock latency → simulated-time model".

Per event step (order matters, mirrors the paper's operator):
  1. expire PMs whose window closed,
  2. overload check (Alg. 1) → optional shed (Alg. 2 / PM-BL) via lax.cond,
  3. E-BL input-drop decision (black-box baseline only),
  4. advance PMs (SEQ table lookup / ANY distinct count), detect completions,
  5. spawn PMs (window-open events / slide-window ring),
  6. gather <q, s, s', t> observations (model-building phase),
  7. advance simulated time, record latency telemetry.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import contracts as ctr
from repro.analysis.tracing import count_traces
from repro.cep import patterns as pat
from repro.core import overload as ovl
from repro.core import shedder as shd
from repro.kernels import block_step as kblock
from repro.kernels import ops as kops
from repro.kernels import tiling as ktile

Array = jax.Array

SHED_NONE, SHED_PSPICE, SHED_PMBL, SHED_EBL = "none", "pspice", "pmbl", "ebl"

BACKEND_XLA, BACKEND_PALLAS = "xla", "pallas"
BACKEND_PALLAS_BLOCK = "pallas_block"
# Backends whose shed path routes through repro.kernels (DESIGN.md §8/§10).
_KERNEL_BACKENDS = (BACKEND_PALLAS, BACKEND_PALLAS_BLOCK)


# ---------------------------------------------------------------------------
# Configuration
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Static (hashable) engine configuration — one jit cache entry each."""
    num_patterns: int
    max_states: int          # M (padded)
    max_classes: int         # C (padded), classes 0..C
    max_pms: int = 2048      # N PM slots per pattern
    max_any_ids: int = 8     # distinctness-set capacity for ANY patterns
    ring_size: int = 8       # open-window ring for SPAWN_IN_WINDOWS
    latency_bound: float = 1.0
    safety_buffer: float = 0.0
    # Simulated-time cost model (seconds). The paper's operator load comes
    # from matching events against PMs (c_match · n_pm, scaled per pattern by
    # proc_cost) plus per-event window/bookkeeping cost c_base; shedding costs
    # c_shed_base + c_shed_pm · n_pm — the O(N) histogram-threshold plan
    # (utility lookup + a constant number of bucket passes per PM; the old
    # sort plan was O(N log N), which the linear g model under-predicted at
    # large stores).  E-BL pays c_ebl per dropped event.
    c_base: float = 2e-6
    c_match: float = 1e-7
    c_shed_base: float = 5e-6
    c_shed_pm: float = 2e-9   # per-PM shed cost, recalibrated to the O(N) plan
    c_ebl: float = 5e-7
    # Hot-path dispatch (DESIGN.md §8).  backend: "xla" runs the jnp
    # reference ops; "pallas" routes advance / utility lookup / shed
    # through repro.kernels.ops (compiled on TPU, interpret elsewhere) —
    # bitwise-equivalent (tests/test_backend.py); "pallas_block" replaces
    # the per-event scan with one fused kernel launch per
    # ``block_events`` events (kernels/block_step.py, DESIGN.md §10) —
    # the PM store stays resident across the block and the scan runs
    # over blocks.  Algorithm-2 fires are handled IN-KERNEL by default
    # (``block_shed="fused"``: the threshold select runs against the
    # store-resident utility column, PRNG keys are precomputed host-side
    # and threaded in); ``block_shed="replay"`` pins the legacy
    # block-split protocol — bail at the fire, replay that event through
    # the host ``_step``, re-enter — which stays as the oracle, and is
    # forced whenever ``shed_plan="sort"`` (the fused path implements
    # the threshold plan only).  All bitwise-equivalent
    # (tests/test_block_backend.py, eval/oracle.py).
    # spawn_alloc / shed_plan keep the legacy O(N log N) paths selectable
    # as oracles and as the baseline benchmarks/bench_engine.py measures
    # against.
    backend: str = BACKEND_XLA          # "xla" | "pallas" | "pallas_block"
    block_events: int = 32              # W — events fused per block launch
    block_shed: str = "fused"           # "fused" (in-kernel Alg. 2) | "replay"
    spawn_alloc: str = "cumsum"         # "cumsum" (O(N)) | "argsort" (legacy)
    shed_plan: str = "threshold"        # "threshold" (O(N)) | "sort" (legacy)
    # Static pattern census (DESIGN.md §8): when every pattern shares one
    # kind / spawn mode, the step skips the other family's per-event ops
    # (the O(A·N) idset machinery for SEQ-only sets, the O(K·N)
    # window-spawn exists-check for AT_OPEN-only sets) — bitwise-identical
    # to "mixed", which always computes both and selects.
    # ``runner.default_config`` fills these in from the compiled patterns.
    kinds: str = "mixed"                # "seq" | "any" | "mixed"
    spawn_modes: str = "mixed"          # "at_open" | "in_windows" | "mixed"
    # Match emission (repro.eval, DESIGN.md §9): when on, every step also
    # emits the identity of each completed match — (open_idx, bind) of the
    # completing PM, -1 where no completion — so a run's MATCH SET (not
    # just its completion counts) can be extracted and diffed against the
    # NumPy oracle / a no-shed ground truth.  Off (the default) the fields
    # are zero-width (P, 0) arrays: same pytree structure, no hot-path
    # cost, no retrace of existing configs.
    emit_matches: bool = False
    gather_stats: bool = False
    shedder: str = SHED_NONE
    # E-BL drop-fraction controller: model-based feedforward (drop enough to
    # match the arrival rate) + backlog-proportional term, with decay when
    # not overloaded.
    ebl_backlog_gain: float = 0.5
    ebl_decay: float = 0.997
    # When the drop budget exceeds what low-utility types can supply, the
    # remainder spreads uniformly across all types (He et al.'s weighted
    # sampling degrades toward uniform under pressure): effective priority
    # = floor + (1-floor)·raw.
    ebl_floor: float = 0.25

    def __post_init__(self):
        # Config-time validation: EngineConfigs are built both by
        # runner.default_config and by bare dataclasses.replace all over
        # the benchmarks/tests — a bad knob must fail HERE, not as a
        # ZeroDivisionError or silent xla fallback deep inside a trace.
        if self.backend not in (BACKEND_XLA, BACKEND_PALLAS,
                                BACKEND_PALLAS_BLOCK):
            raise ValueError(
                f"unknown engine backend {self.backend!r}; expected one "
                f"of ('{BACKEND_XLA}', '{BACKEND_PALLAS}', "
                f"'{BACKEND_PALLAS_BLOCK}')")
        if self.block_events < 1:
            raise ValueError(
                f"block_events must be >= 1: {self.block_events}")
        for name in ("num_patterns", "max_states", "max_classes",
                     "max_pms", "max_any_ids", "ring_size"):
            v = getattr(self, name)
            if v < 1:
                raise ValueError(
                    f"{name} must be >= 1 (it sizes a store/table axis): "
                    f"{v}")
        if not self.latency_bound > 0:
            raise ValueError(
                "latency_bound must be > 0 seconds — the overload "
                "detector (Alg. 1) compares realized event latency l_e "
                f"against it: {self.latency_bound}")
        if self.safety_buffer < 0:
            raise ValueError(
                "safety_buffer must be >= 0 seconds (it tightens the "
                f"latency bound, never loosens it): {self.safety_buffer}")
        for name in ("c_base", "c_match", "c_shed_base", "c_shed_pm",
                     "c_ebl"):
            v = getattr(self, name)
            if v < 0:
                raise ValueError(
                    f"cost constant {name} must be >= 0 seconds (simulated-"
                    f"time costs are non-negative): {v}")
        for name in ("ebl_floor", "ebl_decay"):
            v = getattr(self, name)
            if not 0.0 <= v <= 1.0:
                raise ValueError(
                    f"{name} must be in [0, 1] (it scales/decays the E-BL "
                    f"drop fraction): {v}")
        if self.ebl_backlog_gain < 0:
            raise ValueError(
                "ebl_backlog_gain must be >= 0 (backlog-proportional term "
                f"of the E-BL drop controller): {self.ebl_backlog_gain}")
        if self.shedder not in (SHED_NONE, SHED_PSPICE, SHED_PMBL,
                                SHED_EBL):
            raise ValueError(
                f"unknown shedder {self.shedder!r}; expected one of "
                f"('{SHED_NONE}', '{SHED_PSPICE}', '{SHED_PMBL}', "
                f"'{SHED_EBL}')")
        if self.spawn_alloc not in ("cumsum", "argsort"):
            raise ValueError(f"unknown spawn_alloc {self.spawn_alloc!r}; "
                             "expected 'cumsum' or 'argsort'")
        if self.shed_plan not in ("threshold", "sort"):
            raise ValueError(f"unknown shed_plan {self.shed_plan!r}; "
                             "expected 'threshold' or 'sort'")
        if self.block_shed not in ("fused", "replay"):
            raise ValueError(f"unknown block_shed {self.block_shed!r}; "
                             "expected 'fused' or 'replay'")
        if self.kinds not in ("seq", "any", "mixed"):
            raise ValueError(f"unknown kinds census {self.kinds!r}; "
                             "expected 'seq', 'any' or 'mixed'")
        if self.spawn_modes not in ("at_open", "in_windows", "mixed"):
            raise ValueError(
                f"unknown spawn_modes census {self.spawn_modes!r}; "
                "expected 'at_open', 'in_windows' or 'mixed'")

    @property
    def flat_pms(self) -> int:
        return self.num_patterns * self.max_pms


class EngineModel(NamedTuple):
    """Learned / compiled array-valued inputs (a pytree; not static)."""
    trans: Array          # (P, M, C+1) int32
    kind: Array           # (P,) int32
    spawn_mode: Array     # (P,) int32
    window_size: Array    # (P,) int32
    slide: Array          # (P,) int32
    final_state: Array    # (P,) int32
    proc_cost: Array      # (P,) float32 — relative match cost multiplier
    uses_binding: Array   # (P,) bool
    spawn_counts: Array   # (P,) bool — ANY spawn consumes the first match
    # pSPICE utility tables (stacked across patterns) + latency regressions.
    ut_tables: Array      # (P, B, M) float32
    ut_bins: Array        # (P,) int32
    f_model: ovl.LatencyModel
    g_model: ovl.LatencyModel
    # E-BL per-event raw drop priority (1 - normalized type utility).
    ebl_raw_mean: Array   # scalar float32


class EventBatch(NamedTuple):
    """Per-event classified inputs (precomputed by the data layer)."""
    ev_class: Array    # (n, P) int32 — class per pattern (0 = irrelevant)
    ev_bind: Array     # (n, P) int32 — binding value per pattern (-1 = none)
    ev_open: Array     # (n, P) bool  — window-open flag per pattern
    ev_id: Array       # (n,)  int32  — distinctness id (ANY patterns)
    ev_rand: Array     # (n,)  float32 — u(0,1) for E-BL sampling
    ebl_raw: Array     # (n,)  float32 — E-BL raw drop priority per event
    arrival: Array     # (n,)  float32 — arrival time (seconds)


class PMStore(NamedTuple):
    active: Array     # (P, N) bool
    state: Array      # (P, N) int32
    open_idx: Array   # (P, N) int32 — event index at window open
    bind: Array       # (P, N) int32
    idset: Array      # (P, N, A) int32 — matched distinct ids (ANY), -1 empty


class Carry(NamedTuple):
    pms: PMStore
    ring: Array          # (P, K) int32 window-open indices (-1 = empty)
    ring_ptr: Array      # (P,) int32
    sim_time: Array      # scalar f32
    key: Array           # PRNG key
    ebl_frac: Array      # scalar f32 — E-BL current drop fraction
    ema_gap: Array       # scalar f32 — EMA of inter-arrival gap (1/rate)
    prev_arrival: Array  # scalar f32
    # accumulators
    complex_count: Array  # (P,) f32
    pms_created: Array    # (P,) f32
    pms_shed: Array       # scalar f32
    shed_calls: Array     # scalar f32
    overflow: Array       # scalar f32 — spawns lost to a full store
    ebl_dropped: Array    # scalar f32
    obs_counts: Array     # (P, M, M) f32 transition counts
    obs_rewards: Array    # (P, M, M) f32 summed transition times
    lat_samples_n: Array  # (S,) f32  (n_pm, l_p) samples for fitting f
    lat_samples_l: Array  # (S,) f32
    lat_ptr: Array        # scalar int32


class StepOut(NamedTuple):
    l_e: Array       # realized event latency (s)
    n_pm: Array      # total active PMs after the step
    shed: Array      # bool — shed triggered at this event
    dropped: Array   # bool — event dropped by E-BL
    # Match identities (cfg.emit_matches; zero-width (P, 0) otherwise):
    # slot j of pattern p completed at this event iff match_open[p, j] >= 0,
    # in which case (match_open, match_bind)[p, j] are the completing PM's
    # window-open event index and binding value.
    match_open: Array   # (P, N | 0) int32 — open_idx of completed PM, -1
    match_bind: Array   # (P, N | 0) int32 — bind of completed PM, -1


# ---------------------------------------------------------------------------
# Engine construction
# ---------------------------------------------------------------------------

def make_model(cp: pat.CompiledPatterns, cfg: EngineConfig,
               ut_tables: Array | None = None, ut_bins: Array | None = None,
               f_model: ovl.LatencyModel | None = None,
               g_model: ovl.LatencyModel | None = None,
               ebl_raw_mean: float = 0.5) -> EngineModel:
    P, M = cp.num_patterns, cp.max_states
    # The census fields gate which per-event op families the step compiles
    # — an inconsistent census would silently produce wrong matches.
    kind, sm = np.asarray(cp.kind), np.asarray(cp.spawn_mode)
    if (cfg.kinds == "seq" and (kind != pat.KIND_SEQ).any()) or \
       (cfg.kinds == "any" and (kind != pat.KIND_ANY).any()):
        raise ValueError(f"cfg.kinds={cfg.kinds!r} but patterns have "
                         f"kinds {sorted(set(kind.tolist()))}")
    if (cfg.spawn_modes == "at_open" and
            (sm != pat.SPAWN_AT_OPEN).any()) or \
       (cfg.spawn_modes == "in_windows" and
            (sm != pat.SPAWN_IN_WINDOWS).any()):
        raise ValueError(f"cfg.spawn_modes={cfg.spawn_modes!r} but patterns "
                         f"have spawn modes {sorted(set(sm.tolist()))}")
    num_bins = 1 if ut_tables is None else ut_tables.shape[1]
    if ut_tables is None:
        ut_tables = jnp.ones((P, num_bins, M), jnp.float32)
    if ut_bins is None:
        ut_bins = jnp.ones((P,), jnp.int32)
    ident = ovl.LatencyModel(a=jnp.float32(cfg.c_match),
                             b=jnp.float32(cfg.c_base),
                             kind=jnp.int32(ovl.LINEAR))
    g_ident = ovl.LatencyModel(a=jnp.float32(cfg.c_shed_pm),
                               b=jnp.float32(cfg.c_shed_base),
                               kind=jnp.int32(ovl.LINEAR))
    return EngineModel(
        trans=jnp.asarray(cp.trans), kind=jnp.asarray(cp.kind),
        spawn_mode=jnp.asarray(cp.spawn_mode),
        window_size=jnp.asarray(cp.window_size),
        slide=jnp.asarray(cp.slide),
        final_state=jnp.asarray(cp.final_state),
        proc_cost=jnp.asarray(cp.proc_cost),
        uses_binding=jnp.asarray(cp.uses_binding),
        spawn_counts=jnp.asarray(cp.spawn_counts),
        ut_tables=jnp.asarray(ut_tables), ut_bins=jnp.asarray(ut_bins),
        f_model=f_model if f_model is not None else ident,
        g_model=g_model if g_model is not None else g_ident,
        ebl_raw_mean=jnp.float32(ebl_raw_mean),
    )


def init_carry(cfg: EngineConfig, seed: int = 0,
               lat_capacity: int = 4096) -> Carry:
    P, N, M, A, K = (cfg.num_patterns, cfg.max_pms, cfg.max_states,
                     cfg.max_any_ids, cfg.ring_size)
    pms = PMStore(
        active=jnp.zeros((P, N), bool),
        state=jnp.zeros((P, N), jnp.int32),
        open_idx=jnp.zeros((P, N), jnp.int32),
        bind=jnp.full((P, N), -1, jnp.int32),
        idset=jnp.full((P, N, A), -1, jnp.int32),
    )
    # Each scalar gets its OWN buffer: run_engine_chunk donates the carry,
    # and donating one buffer aliased across several leaves is an error.
    z = lambda: jnp.zeros((), jnp.float32)  # noqa: E731
    return Carry(
        pms=pms,
        ring=jnp.full((P, K), -1, jnp.int32),
        ring_ptr=jnp.zeros((P,), jnp.int32),
        sim_time=z(), key=jax.random.PRNGKey(seed), ebl_frac=z(),
        ema_gap=jnp.float32(1e-3), prev_arrival=z(),
        complex_count=jnp.zeros((P,), jnp.float32),
        pms_created=jnp.zeros((P,), jnp.float32),
        pms_shed=z(), shed_calls=z(), overflow=z(), ebl_dropped=z(),
        obs_counts=jnp.zeros((P, M, M), jnp.float32),
        obs_rewards=jnp.zeros((P, M, M), jnp.float32),
        lat_samples_n=jnp.zeros((lat_capacity,), jnp.float32),
        lat_samples_l=jnp.zeros((lat_capacity,), jnp.float32),
        lat_ptr=jnp.int32(0),
    )


# ---------------------------------------------------------------------------
# One event step
# ---------------------------------------------------------------------------

def _advance(cfg: EngineConfig, model: EngineModel, pms: PMStore,
             ev_class: Array, ev_bind: Array, ev_id: Array):
    """Advance all active PMs against one event.  Returns (pms, old_state,
    new_state, completed_per_pattern)."""
    P, N = cfg.num_patterns, cfg.max_pms
    c = ev_class[:, None]                      # (P,1)
    b = ev_bind[:, None]
    bind_ok = jnp.where(model.uses_binding[:, None], pms.bind == b, True)
    c_eff = jnp.where(bind_ok, c, 0)

    # SEQ: dense table lookup trans[p, state, c_eff] — ONE flat (P·N,)
    # gather (the old double take_along_axis materialized a (P, N, C+1)
    # intermediate every event).  Class 0 self-loops, so a failed binding
    # (c_eff = 0) keeps the state — which is also exactly what the Pallas
    # kernel's in-kernel binding check does.
    final = model.final_state[:, None]
    if cfg.kinds != "any":
        if cfg.backend == BACKEND_PALLAS:
            seq_next = kops.advance_seq_multi(
                pms.state, pms.bind, pms.active, model.trans, ev_class,
                ev_bind, model.final_state, model.uses_binding,
                interpret=kops.default_interpret())
        else:
            M, C1 = model.trans.shape[1], model.trans.shape[2]
            pidx = jnp.arange(P, dtype=jnp.int32)[:, None]
            flat_idx = (pidx * M + pms.state) * C1 + c_eff.astype(jnp.int32)
            seq_next = jnp.take(model.trans.reshape(-1), flat_idx)

    # ANY: distinct-count advance + idset insert at the next free position:
    # a PM at state j holds (j-1) ids if the spawn event didn't count (Q3)
    # or j ids if it did (Q4) — insertion slot is state-1 (+1 when
    # spawn_counts).  SEQ-only pattern sets skip all of it (the inserts
    # are dead: do_insert requires ~is_seq).
    if cfg.kinds != "seq":
        in_set = (pms.idset == ev_id).any(axis=-1)            # (P, N)
        any_match = (c_eff == 1) & ~in_set & (pms.state < final)
        any_next = pms.state + any_match.astype(jnp.int32)
        A = cfg.max_any_ids
        sc = model.spawn_counts.astype(jnp.int32)[:, None]
        slot = jnp.clip(pms.state - 1 + sc, 0, A - 1)
        is_seq = (model.kind == pat.KIND_SEQ)[:, None]
        do_insert = (~is_seq) & pms.active & any_match
        onehot = jax.nn.one_hot(slot, A, dtype=bool) & do_insert[..., None]
        idset = jnp.where(onehot, ev_id, pms.idset)

    if cfg.kinds == "seq":
        new_state = jnp.where(pms.active, seq_next, pms.state)
        idset = pms.idset
    elif cfg.kinds == "any":
        new_state = jnp.where(pms.active, any_next, pms.state)
    else:
        new_state = jnp.where(pms.active,
                              jnp.where(is_seq, seq_next, any_next),
                              pms.state)

    completed = pms.active & (new_state == final) & (pms.state != final)
    active = pms.active & ~completed
    pms2 = PMStore(active=active, state=new_state, open_idx=pms.open_idx,
                   bind=pms.bind, idset=idset)
    return pms2, pms.state, new_state, completed


def _spawn(cfg: EngineConfig, model: EngineModel, pms: PMStore, ring: Array,
           i: Array, ev_open: Array, ev_class: Array, ev_bind: Array,
           ev_id: Array):
    """Spawn new PMs.  Returns (pms, spawned_per_pattern, overflow_count).

    SPAWN_AT_OPEN: the window-open event itself spawns one PM at state 1.
    SPAWN_IN_WINDOWS: a class-1 event spawns a PM (state 1, bound to its
    binding value) in every ring window that lacks one.
    """
    P, N, K = cfg.num_patterns, cfg.max_pms, cfg.ring_size
    at_open = model.spawn_mode == pat.SPAWN_AT_OPEN

    # Candidate spawns: K slots per pattern. Candidate 0 doubles as the
    # AT_OPEN candidate.  The O(K·N) ring exists-check only runs when a
    # SPAWN_IN_WINDOWS pattern can exist (census: cfg.spawn_modes).
    if cfg.spawn_modes != "at_open":
        ring_valid = ring >= 0
        in_window = (i - ring) < model.window_size[:, None]
        exists = ((pms.active[:, None, :]) &
                  (pms.open_idx[:, None, :] == ring[:, :, None]) &
                  (pms.bind[:, None, :] == ev_bind[:, None, None])).any(-1)
        win_spawn = (ring_valid & in_window & ~exists &
                     (ev_class == 1)[:, None] & (~at_open)[:, None])
    open_spawn = (at_open & ev_open)[:, None] & (jnp.arange(K) == 0)
    if cfg.spawn_modes == "at_open":
        cand = open_spawn                                    # (P, K)
        cand_open_idx = jnp.broadcast_to(i, (P, K)).astype(jnp.int32)
    elif cfg.spawn_modes == "in_windows":
        cand = win_spawn
        cand_open_idx = ring
    else:
        cand = win_spawn | open_spawn
        cand_open_idx = jnp.where(at_open[:, None], i, ring)  # (P, K)

    # Allocate free slots: candidate r takes the (r+1)-th lowest-index
    # inactive slot (stable inactive-first order).
    n_free = (~pms.active).sum(axis=1)                          # (P,)
    rank = jnp.cumsum(cand, axis=1) - 1                        # (P, K)
    can_alloc = cand & (rank < n_free[:, None])
    overflow = (cand & ~can_alloc).sum()
    if cfg.spawn_alloc == "argsort":
        # Legacy allocator (the oracle the O(N) scheme is property-tested
        # against, and bench_engine.py's baseline): full per-event sort.
        free_order = jnp.argsort(pms.active, axis=1, stable=True)  # (P, N)
        slots = jnp.take_along_axis(free_order, jnp.clip(rank, 0, N - 1),
                                    axis=1)
    else:
        # O(N) free-list compaction: every inactive slot scatters its own
        # index at its rank among the free slots (masked-cumsum rank), so
        # `free_slots[p, r]` is precisely what the stable argsort put
        # there for r < n_free — bitwise-identical slot choices
        # (tests/test_backend.py).  Ranks ≥ n_free stay at the sentinel N;
        # they are only read where ~can_alloc masks the update to a
        # dropped OOB scatter, exactly like the legacy path's junk slots.
        free_rank = jnp.cumsum(~pms.active, axis=1) - 1        # (P, N)
        rowbase = jnp.arange(P, dtype=jnp.int32)[:, None] * N
        tgt = jnp.where(~pms.active, rowbase + free_rank, cfg.flat_pms)
        cols = jnp.broadcast_to(jnp.arange(N, dtype=jnp.int32), (P, N))
        free_slots = jnp.full((cfg.flat_pms,), N, jnp.int32).at[
            tgt.reshape(-1)].set(cols.reshape(-1),
                                 mode="drop").reshape(P, N)
        slots = jnp.take_along_axis(free_slots, jnp.clip(rank, 0, N - 1),
                                    axis=1)

    rows = jnp.arange(P)[:, None] * jnp.ones((1, K), jnp.int32)
    flatidx = (rows * N + slots).reshape(-1)
    sel = can_alloc.reshape(-1)

    upd = jnp.where(sel, flatidx, cfg.flat_pms)  # drop-mode OOB when not sel
    active = pms.active.reshape(-1).at[upd].set(True, mode="drop")
    state = pms.state.reshape(-1).at[upd].set(1, mode="drop")
    open_i = pms.open_idx.reshape(-1).at[upd].set(
        cand_open_idx.reshape(-1), mode="drop")
    bind = pms.bind.reshape(-1).at[upd].set(
        jnp.broadcast_to(ev_bind[:, None], (P, K)).reshape(-1), mode="drop")
    # Fresh idset row: the spawning event's id occupies slot 0 for patterns
    # where the spawn consumes the first distinct match (Q4).
    A = cfg.max_any_ids
    row0 = jnp.where(model.spawn_counts[:, None],
                     jnp.full((P, 1), ev_id, jnp.int32), -1)       # (P, 1)
    fresh = jnp.concatenate(
        [row0, jnp.full((P, A - 1), -1, jnp.int32)], axis=1)  # (P, A)
    fresh_pk = jnp.broadcast_to(fresh[:, None, :], (P, K, A)).reshape(-1, A)
    idset = pms.idset.reshape(cfg.flat_pms, A).at[upd].set(
        fresh_pk, mode="drop")

    spawned = can_alloc.sum(axis=1).astype(jnp.float32)
    pms2 = PMStore(active=active.reshape(P, N), state=state.reshape(P, N),
                   open_idx=open_i.reshape(P, N), bind=bind.reshape(P, N),
                   idset=idset.reshape(P, N, cfg.max_any_ids))
    return pms2, spawned, overflow.astype(jnp.float32)


def _shed_now(cfg: EngineConfig, model: EngineModel, c: Carry, i: Array,
              rho: Array) -> tuple[Carry, Array]:
    """Run the load shedder (Alg. 2 / PM-BL) and pay its simulated cost."""
    P, N = cfg.num_patterns, cfg.max_pms
    pms = c.pms
    n_before = pms.active.sum()
    r_w = model.window_size[:, None] - (i - pms.open_idx)
    flat_active = pms.active.reshape(-1)
    key, sub = jax.random.split(c.key)
    if cfg.shedder == SHED_PSPICE:
        if cfg.backend in _KERNEL_BACKENDS:
            # Kernel path: fused per-pattern utility lookup + the same
            # histogram-threshold plan with the Pallas bucket counter.
            interp = kops.default_interpret()
            u = kops.pm_utilities_multi(
                pms.state, r_w, pms.active, model.ut_tables, model.ut_bins,
                interpret=interp).reshape(-1)
            if cfg.shed_plan == "sort":
                new_flat = shd.drop_lowest_utility(
                    flat_active, jnp.where(flat_active, u, jnp.inf), rho)
            else:
                new_flat = kops.shed_lowest_threshold(flat_active, u, rho,
                                                      interpret=interp)
        else:
            pattern_id = jnp.repeat(jnp.arange(P, dtype=jnp.int32), N)
            new_flat = shd.shed(
                "pspice", key=sub, active=flat_active, rho=rho,
                stacked_tables=model.ut_tables, bin_sizes=model.ut_bins,
                pattern_id=pattern_id, state=pms.state.reshape(-1),
                r_w=r_w.reshape(-1), plan=cfg.shed_plan)
    else:  # PM-BL — O(N) select over uniform scores on either backend
        new_flat = shd.shed("pmbl", key=sub, active=flat_active, rho=rho,
                            plan=cfg.shed_plan)
    active = new_flat.reshape(P, N)
    dropped = (n_before - active.sum()).astype(jnp.float32)
    shed_cost = cfg.c_shed_base + cfg.c_shed_pm * n_before.astype(jnp.float32)
    c = c._replace(
        pms=pms._replace(active=active), key=key,
        sim_time=c.sim_time + shed_cost,
        pms_shed=c.pms_shed + dropped,
        shed_calls=c.shed_calls + 1.0)
    return c, dropped


def _pre_shed(cfg: EngineConfig, model: EngineModel, carry: Carry,
              i: Array, ev_open: Array,
              arrival: Array) -> tuple[Carry, Array, Array]:
    """Steps 1-2 up to the overload decision: expire windows, ring
    bookkeeping, queueing latency.  Returns (carry, l_q, n_pm)."""
    c = carry
    pms = c.pms

    # -- 1. expire closed windows ------------------------------------------
    expired = pms.active & ((i - pms.open_idx) >= model.window_size[:, None])
    pms = pms._replace(active=pms.active & ~expired)

    # -- ring update (window-open bookkeeping for SPAWN_IN_WINDOWS) ---------
    if cfg.spawn_modes == "at_open":
        ring, ring_ptr = c.ring, c.ring_ptr   # no in-window spawner exists
    else:
        in_win_mode = model.spawn_mode == pat.SPAWN_IN_WINDOWS
        opens = ev_open & in_win_mode
        ring = jnp.where(
            opens[:, None] &
            (jnp.arange(cfg.ring_size) == c.ring_ptr[:, None]), i, c.ring)
        ring_ptr = jnp.where(opens, (c.ring_ptr + 1) % cfg.ring_size,
                             c.ring_ptr)

    # -- 2. queueing latency & overload check (Alg. 1) ----------------------
    sim_time = jnp.maximum(c.sim_time, arrival)
    l_q = sim_time - arrival
    n_pm = pms.active.sum().astype(jnp.float32)
    c = c._replace(pms=pms, ring=ring, ring_ptr=ring_ptr, sim_time=sim_time)
    return c, l_q, n_pm


def _step(cfg: EngineConfig, model: EngineModel, carry: Carry,
          ev: tuple) -> tuple[Carry, StepOut]:
    (i, ev_class, ev_bind, ev_open, ev_id, ev_rand, ebl_raw, arrival) = ev
    c, l_q, n_pm = _pre_shed(cfg, model, carry, i, ev_open, arrival)

    did_shed = jnp.bool_(False)
    if cfg.shedder in (SHED_PSPICE, SHED_PMBL):
        dec = ovl.detect_overload(model.f_model, model.g_model, l_q,
                                  n_pm.astype(jnp.int32), cfg.latency_bound,
                                  cfg.safety_buffer)
        c = jax.lax.cond(
            dec.shed & (dec.rho > 0),
            lambda cc: _shed_now(cfg, model, cc, i, dec.rho)[0],
            lambda cc: cc, c)
        did_shed = dec.shed & (dec.rho > 0)
    return _post_shed(cfg, model, c, ev, l_q, n_pm, did_shed)


def _post_shed(cfg: EngineConfig, model: EngineModel, c: Carry,
               ev: tuple, l_q: Array, n_pm: Array,
               did_shed: Array) -> tuple[Carry, StepOut]:
    """Steps 3-7: E-BL drop, advance/spawn, observations, simulated time."""
    (i, ev_class, ev_bind, ev_open, ev_id, ev_rand, ebl_raw, arrival) = ev

    # -- 3. E-BL input drop --------------------------------------------------
    ev_dropped = jnp.bool_(False)
    gap = jnp.maximum(arrival - c.prev_arrival, 1e-9)
    ema_gap = 0.99 * c.ema_gap + 0.01 * gap
    c = c._replace(ema_gap=ema_gap, prev_arrival=arrival)
    if cfg.shedder == SHED_EBL:
        dec = ovl.detect_overload(model.f_model, model.g_model, l_q,
                                  n_pm.astype(jnp.int32), cfg.latency_bound,
                                  cfg.safety_buffer)
        # Feedforward: drop fraction d s.t. d·c_ebl + (1-d)·l_p == 1/rate,
        # plus backlog-proportional pressure to drain existing queueing.
        l_p_est = ovl.predict_latency(model.f_model, n_pm)
        d_ff = (l_p_est - ema_gap) / jnp.maximum(l_p_est - cfg.c_ebl, 1e-9)
        d_bk = cfg.ebl_backlog_gain * l_q / cfg.latency_bound
        d_need = jnp.clip(d_ff + d_bk, 0.0, 1.0)
        ebl_frac = jnp.where(dec.shed,
                             jnp.maximum(c.ebl_frac * cfg.ebl_decay, d_need),
                             c.ebl_frac * cfg.ebl_decay)
        raw_eff = cfg.ebl_floor + (1.0 - cfg.ebl_floor) * ebl_raw
        mean_eff = cfg.ebl_floor + (1.0 - cfg.ebl_floor) * model.ebl_raw_mean
        p_drop = jnp.clip(raw_eff * ebl_frac /
                          jnp.maximum(mean_eff, 1e-9), 0.0, 1.0)
        ev_dropped = ev_rand < p_drop
        c = c._replace(ebl_frac=ebl_frac,
                       ebl_dropped=c.ebl_dropped + ev_dropped)
        did_shed = dec.shed

    pms = c.pms
    live_class = jnp.where(ev_dropped, jnp.zeros_like(ev_class), ev_class)
    live_open = jnp.where(ev_dropped, jnp.zeros_like(ev_open), ev_open)

    # -- 4. advance + completions -------------------------------------------
    pms2, s_old, s_new, completed = _advance(cfg, model, pms, live_class,
                                             ev_bind, ev_id)
    n_completed = completed.sum(axis=1).astype(jnp.float32)
    if cfg.emit_matches:
        # Identity of each completed match: advance never moves PM payloads,
        # so the completing slot's open_idx / bind are still in place.
        m_open = jnp.where(completed, pms.open_idx, -1)
        m_bind = jnp.where(completed, pms.bind,
                           jnp.full_like(pms.bind, -1))
    else:
        m_open = jnp.zeros((cfg.num_patterns, 0), jnp.int32)
        m_bind = jnp.zeros((cfg.num_patterns, 0), jnp.int32)

    # -- 5. spawn -------------------------------------------------------------
    pms3, spawned, oflow = _spawn(cfg, model, pms2, c.ring, i, live_open,
                                  live_class, ev_bind, ev_id)

    # -- 6. observations (model-building phase only) -------------------------
    # Exact per-event transition counts (integer-valued f32, so the
    # scatter-add order cannot matter); rewards add count × the pattern's
    # per-PM time, elementwise — the same bits on every backend.
    obs_counts, obs_rewards = c.obs_counts, c.obs_rewards
    if cfg.gather_stats:
        P, N, M = cfg.num_patterns, cfg.max_pms, cfg.max_states
        w = pms.active.astype(jnp.float32)                    # observed PMs
        pidx = jnp.arange(P, dtype=jnp.int32)[:, None] * jnp.ones(
            (1, N), jnp.int32)
        flat = (pidx * M + s_old) * M + s_new
        delta = jnp.zeros((P * M * M,), jnp.float32).at[
            flat.reshape(-1)].add(w.reshape(-1)).reshape(P, M, M)
        obs_counts = obs_counts + delta
        obs_rewards = obs_rewards + delta * (
            cfg.c_match * model.proc_cost)[:, None, None]

    # -- 7. simulated processing time & latency ------------------------------
    # The per-pattern sum runs in pattern order (a reduce may reassociate,
    # and the block kernel and the oracle add in this order).
    n_active_p = pms.active.sum(axis=1).astype(jnp.float32)  # matched-against
    terms = cfg.c_match * model.proc_cost * n_active_p
    t_sum = terms[0]
    for p in range(1, cfg.num_patterns):
        t_sum = t_sum + terms[p]
    t_proc = cfg.c_base + t_sum
    t_proc = jnp.where(ev_dropped, cfg.c_ebl, t_proc)
    sim_time = c.sim_time + t_proc
    l_e = sim_time - arrival

    # latency samples for fitting f (n_pm -> l_p): store (n, t_proc).
    S = c.lat_samples_n.shape[0]
    ptr = c.lat_ptr % S
    lat_n = c.lat_samples_n.at[ptr].set(n_pm)
    lat_l = c.lat_samples_l.at[ptr].set(t_proc)

    c = Carry(
        pms=pms3, ring=c.ring, ring_ptr=c.ring_ptr, sim_time=sim_time,
        key=c.key, ebl_frac=c.ebl_frac, ema_gap=c.ema_gap,
        prev_arrival=c.prev_arrival,
        complex_count=c.complex_count + n_completed,
        pms_created=c.pms_created + spawned,
        pms_shed=c.pms_shed, shed_calls=c.shed_calls,
        overflow=c.overflow + oflow, ebl_dropped=c.ebl_dropped,
        obs_counts=obs_counts, obs_rewards=obs_rewards,
        lat_samples_n=lat_n, lat_samples_l=lat_l, lat_ptr=c.lat_ptr + 1,
    )
    out = StepOut(l_e=l_e, n_pm=pms3.active.sum().astype(jnp.float32),
                  shed=did_shed, dropped=ev_dropped,
                  match_open=m_open, match_bind=m_bind)
    return c, out


# ---------------------------------------------------------------------------
# Public entry points
# ---------------------------------------------------------------------------

def _scan_events(cfg: EngineConfig, model: EngineModel, events: EventBatch,
                 carry: Carry, start: Array) -> tuple[Carry, StepOut]:
    """The one scan both entry points share: event indices are GLOBAL
    (``start + arange``), so scanning a stream in consecutive chunks
    replays the exact op sequence of one monolithic scan — window expiry,
    ring bookkeeping and spawn open-indices all key off the global index."""
    n = events.ev_class.shape[0]
    idx = jnp.int32(start) + jnp.arange(n, dtype=jnp.int32)
    xs = (idx, events.ev_class, events.ev_bind,
          events.ev_open, events.ev_id, events.ev_rand, events.ebl_raw,
          events.arrival)
    step = functools.partial(_step, cfg, model)
    return jax.lax.scan(step, carry, xs)


# ---------------------------------------------------------------------------
# Event-block execution (backend="pallas_block", DESIGN.md §10)
# ---------------------------------------------------------------------------

def _pad_event_blocks(events: EventBatch, n: int, w: int,
                      axis: int = 0) -> tuple[EventBatch, int]:
    """Pad the event axis to a multiple of ``w`` (masked in-kernel) and
    reshape it into (nb, w) blocks; returns (blocked events, nb)."""
    pad = ktile.tile_pad(w, n)
    nb = max(1, (n + pad) // w)
    pad = nb * w - n

    def f(x):
        if pad:
            widths = [(0, 0)] * x.ndim
            widths[axis] = (0, pad)
            x = jnp.pad(x, widths)
        return x.reshape(x.shape[:axis] + (nb, w) + x.shape[axis + 1:])

    return jax.tree.map(f, events), nb


@count_traces("cep._run_block")
def _run_block(cfg: EngineConfig, model: EngineModel, carry: Carry,
               blk: tuple, i0: Array, n_valid: Array) -> tuple[Carry, dict]:
    """One event block through the fused kernel (DESIGN.md §10).

    Default (``block_shed="fused"``): exactly ONE launch per block for
    every shedder — Algorithm-2 fires are handled inside the kernel, so
    overload is the fast path, not an escape hatch.

    Legacy oracle (``block_shed="replay"``, or any ``shed_plan="sort"``
    config): the kernel commits events until the Algorithm-1 check
    fires; the fired event is then replayed through the ordinary
    ``_step`` — which re-derives the identical overload decision from
    the committed carry and runs the host-level Algorithm-2 shed — and
    the kernel re-enters at the next event.  Fire-at-the-tail re-entry
    is safe by construction: with ``fire_idx + 1 == n_valid`` the while
    cond is immediately false, so no zero-width relaunch happens (under
    vmap the batched while keeps finished lanes on identity relaunches;
    non-fired lanes carry the ``fire_idx = W`` sentinel, whose replay
    reads are clamped and discarded by the per-lane ``fired`` select).
    """
    W = cfg.block_events
    interp = kops.default_interpret()
    ev_blk = EventBatch(*blk)

    if cfg.shedder not in (SHED_PSPICE, SHED_PMBL) or kblock.fused_shed(cfg):
        carry, rows, _, _ = kblock.block_step(
            cfg, model, carry, ev_blk, i0, 0, n_valid, interpret=interp)
        return carry, rows

    rows0 = dict(
        l_e=jnp.zeros((W,), jnp.float32), n_pm=jnp.zeros((W,), jnp.float32),
        shed=jnp.zeros((W,), bool), dropped=jnp.zeros((W,), bool),
        match_open=jnp.zeros(
            (W, cfg.num_patterns, cfg.max_pms if cfg.emit_matches else 0),
            jnp.int32),
        match_bind=jnp.zeros(
            (W, cfg.num_patterns, cfg.max_pms if cfg.emit_matches else 0),
            jnp.int32))
    jidx = jnp.arange(W, dtype=jnp.int32)

    def cond(st):
        return st[0] < n_valid

    def body(st):
        s, c, rows = st
        c2, krows, fired, fire_idx = kblock.block_step(
            cfg, model, c, ev_blk, i0, s, n_valid, interpret=interp)
        stop = jnp.where(fired, fire_idx, n_valid)
        mask = (jidx >= s) & (jidx < stop)
        rows = {k: jnp.where(mask.reshape((W,) + (1,) * (v.ndim - 1)),
                             krows[k], v) for k, v in rows.items()}

        def on_fire(args):
            c3, rows3 = args
            j = fire_idx
            ev = tuple(jax.lax.dynamic_index_in_dim(x, j, keepdims=False)
                       for x in (jidx,) + blk)
            ev = (i0 + ev[0],) + ev[1:]
            c3, row = _step(cfg, model, c3, ev)
            row_d = dict(l_e=row.l_e, n_pm=row.n_pm, shed=row.shed,
                         dropped=row.dropped, match_open=row.match_open,
                         match_bind=row.match_bind)
            rows3 = {k: v.at[j].set(row_d[k]) for k, v in rows3.items()}
            return c3, rows3

        c2, rows = jax.lax.cond(fired, on_fire, lambda a: a, (c2, rows))
        return (jnp.where(fired, fire_idx + 1, n_valid), c2, rows)

    _, carry, rows = jax.lax.while_loop(
        cond, body, (jnp.int32(0), carry, rows0))
    return carry, rows


def _scan_event_blocks(cfg: EngineConfig, model: EngineModel,
                       events: EventBatch, carry: Carry,
                       start: Array) -> tuple[Carry, StepOut]:
    """``_scan_events`` with the per-event step fused into one kernel
    launch per ``cfg.block_events`` events: the outer scan runs over
    event BLOCKS, and each block's W events execute inside
    ``kernels.block_step`` with the PM store resident.  Event indices
    stay global, so monolithic, chunked and blocked execution all replay
    the identical op sequence (bit-for-bit with backend="xla")."""
    n = events.ev_class.shape[0]
    W = cfg.block_events
    blocks, nb = _pad_event_blocks(events, n, W)
    offs = jnp.arange(nb, dtype=jnp.int32) * W

    def body(c, xs):
        blk, off = xs
        n_valid = jnp.clip(jnp.int32(n) - off, 0, W)
        return _run_block(cfg, model, c, tuple(blk), jnp.int32(start) + off,
                          n_valid)

    carry, rows = jax.lax.scan(body, carry, (blocks, offs))
    outs = StepOut(**{k: v.reshape((nb * W,) + v.shape[2:])[:n]
                      for k, v in rows.items()})
    return carry, outs


def _scan_event_blocks_lanes(cfg: EngineConfig, model: EngineModel,
                             events: EventBatch, carry: Carry,
                             start: Array) -> tuple[Carry, StepOut]:
    """Lane-batched ``_scan_event_blocks``: the fused kernel vmaps over
    the lane axis (lanes are independent operators — per-lane results
    are bitwise those of the single-lane block scan, which equals the
    per-event engine).  Fire handling composes with vmap in both shed
    modes: fused (default) needs nothing special — each lane's kernel
    resolves its own Algorithm-2 fires in the single launch; on the
    legacy replay path the batched while loop runs until every lane
    committed its block (finished lanes relaunch as identity) and the
    replayed ``_step`` commits only on lanes whose own check fired."""
    L, n = events.ev_class.shape[0], events.ev_class.shape[1]
    W = cfg.block_events
    blocks, nb = _pad_event_blocks(events, n, W, axis=1)
    blocks = jax.tree.map(lambda x: jnp.moveaxis(x, 1, 0), blocks)
    offs = jnp.arange(nb, dtype=jnp.int32) * W

    def body(c, xs):
        blk, off = xs
        n_valid = jnp.clip(jnp.int32(n) - off, 0, W)
        i0 = jnp.int32(start) + off
        run = lambda m, cc, b: _run_block(   # noqa: E731
            cfg, m, cc, tuple(b), i0, n_valid)
        return jax.vmap(run)(model, c, blk)

    carry, rows = jax.lax.scan(body, carry, (blocks, offs))
    outs = StepOut(**{
        k: jnp.moveaxis(v, 0, 1).reshape((L, nb * W) + v.shape[3:])[:, :n]
        for k, v in rows.items()})
    return carry, outs


def _scan_events_backend(cfg: EngineConfig, model: EngineModel,
                         events: EventBatch, carry: Carry,
                         start: Array) -> tuple[Carry, StepOut]:
    """Backend dispatch for every event-scan entry point (run_engine,
    run_engine_chunk, the runtime's group runners)."""
    if cfg.backend == BACKEND_PALLAS_BLOCK:
        return _scan_event_blocks(cfg, model, events, carry, start)
    return _scan_events(cfg, model, events, carry, start)


def _scan_events_lanes_backend(cfg: EngineConfig, model: EngineModel,
                               events: EventBatch, carry: Carry,
                               start: Array) -> tuple[Carry, StepOut]:
    """Lane-batched backend dispatch (runtime lanes + sharded lanes)."""
    if cfg.backend == BACKEND_PALLAS_BLOCK:
        return _scan_event_blocks_lanes(cfg, model, events, carry, start)
    return _scan_events_lanes(cfg, model, events, carry, start)


@count_traces("cep._step_lanes")
def _step_lanes(cfg: EngineConfig, model: EngineModel, carry: Carry,
                ev: tuple) -> tuple[Carry, StepOut]:
    """Lane-batched event step for the multi-tenant runtime (DESIGN.md §7).

    ``model``/``carry`` leaves have a leading (L,) lane axis; ``ev``
    leaves are lane-stacked except the shared global index ``i`` (lanes
    advance in lockstep).  Naively vmapping ``_step`` would turn the
    per-lane shed ``lax.cond`` into a select that executes the O(N log N)
    shed path on EVERY event for EVERY lane; instead the overload
    decisions are computed batched (elementwise, cheap) and the expensive
    shed runs under a SCALAR ``any(lane sheds)`` gate.  Per-lane results
    stay bitwise identical to the sequential engine: lanes that shed get
    exactly ``_shed_now``'s output, the rest keep their carry bits.
    """
    (i, ev_class, ev_bind, ev_open, ev_id, ev_rand, ebl_raw, arrival) = ev
    c, l_q, n_pm = jax.vmap(
        functools.partial(_pre_shed, cfg),
        in_axes=(0, 0, None, 0, 0))(model, carry, i, ev_open, arrival)
    L = l_q.shape[0]
    did_shed = jnp.zeros((L,), bool)
    if cfg.shedder in (SHED_PSPICE, SHED_PMBL):
        # Elementwise over the lane axis — no vmap needed.
        dec = ovl.detect_overload(model.f_model, model.g_model, l_q,
                                  n_pm.astype(jnp.int32), cfg.latency_bound,
                                  cfg.safety_buffer)
        want = dec.shed & (dec.rho > 0)

        def shed_lanes(cc: Carry) -> Carry:
            shed_c = jax.vmap(
                lambda m, c1, r: _shed_now(cfg, m, c1, i, r)[0])(
                    model, cc, dec.rho)
            sel = lambda a, b: jnp.where(                    # noqa: E731
                want.reshape((L,) + (1,) * (a.ndim - 1)), a, b)
            return jax.tree.map(sel, shed_c, cc)

        c = jax.lax.cond(jnp.any(want), shed_lanes, lambda cc: cc, c)
        did_shed = want
    return jax.vmap(
        functools.partial(_post_shed, cfg),
        in_axes=(0, 0, (None, 0, 0, 0, 0, 0, 0, 0), 0, 0, 0))(
            model, c, ev, l_q, n_pm, did_shed)


def _scan_events_lanes(cfg: EngineConfig, model: EngineModel,
                       events: EventBatch, carry: Carry,
                       start: Array) -> tuple[Carry, StepOut]:
    """Lane-batched ``_scan_events``: events are lane-stacked (L, n, ...);
    the scan runs over the event axis with ``_step_lanes`` as its body.
    Returned StepOut leaves are lane-stacked (L, n)."""
    n = events.ev_class.shape[1]
    idx = jnp.int32(start) + jnp.arange(n, dtype=jnp.int32)
    ev_t = jax.tree.map(lambda x: jnp.moveaxis(x, 0, 1), events)
    xs = (idx, ev_t.ev_class, ev_t.ev_bind, ev_t.ev_open, ev_t.ev_id,
          ev_t.ev_rand, ev_t.ebl_raw, ev_t.arrival)
    step = functools.partial(_step_lanes, cfg, model)
    carry, outs = jax.lax.scan(step, carry, xs)
    return carry, jax.tree.map(lambda x: jnp.moveaxis(x, 0, 1), outs)


@ctr.contract("cep.run_engine",
              max_while=12, max_cond=24, max_compiles=1,
              max_temp_bytes=ctr.hot_path_temp_budget,
              max_gather_bytes=ctr.hot_path_gather_budget)
@functools.partial(jax.jit, static_argnames=("cfg",))
def run_engine(cfg: EngineConfig, model: EngineModel, events: EventBatch,
               carry: Carry) -> tuple[Carry, StepOut]:
    """Run the operator over a whole event stream (one lax.scan)."""
    return _scan_events_backend(cfg, model, events, carry, jnp.int32(0))


def wrap_event_index(start) -> Array:
    """An unbounded Python event index as a wrap-safe int32 scalar.

    The engine's window arithmetic is int32 differences (``i - open_idx``,
    ``i - ring``), which stay correct across two's-complement wraparound
    as long as windows are << 2^31 — but ``jnp.int32(start)`` raises
    OverflowError once a streamed index reaches 2^31.  Mapping the index
    into int32 modular space keeps the runtime's unbounded-stream claim
    honest past 2.1B events.
    """
    wrapped = int(start) & 0xFFFFFFFF
    return jnp.asarray(np.uint32(wrapped).astype(np.int32))


@ctr.contract("cep.run_engine_chunk",
              donate=("carry", "events"),
              max_while=12, max_cond=24, max_compiles=2,
              max_temp_bytes=ctr.hot_path_temp_budget,
              max_gather_bytes=ctr.hot_path_gather_budget)
@functools.partial(jax.jit, static_argnames=("cfg",),
                   donate_argnames=("carry", "events"))
def run_engine_chunk(cfg: EngineConfig, model: EngineModel,
                     events: EventBatch, carry: Carry,
                     start: Array) -> tuple[Carry, StepOut]:
    """One micro-batch of the chunked runtime (repro.runtime, DESIGN.md §7).

    Identical semantics to ``run_engine`` restricted to events
    ``[start, start + chunk)``; the carry is DONATED so the steady-state
    loop reuses its buffers (constant memory over an unbounded stream),
    and so are the chunk's event buffers — each chunk slice is consumed
    exactly once, and donating it lets XLA write the per-event StepOut
    columns into the arriving chunk's storage instead of fresh
    allocations.  ``start`` is a traced scalar, so every same-length
    chunk hits one compiled executable — zero retraces while streaming.
    """
    return _scan_events_backend(cfg, model, events, carry, start)


def merge_carries(stacked: Carry, axis: int = 0) -> Carry:
    """Fold an L-lane-stacked carry (every leaf has a leading lane axis)
    into one flat carry over L·P patterns — the lane-merge used by the
    runtime's telemetry and by model refresh over multi-tenant state.

    Pattern-dim state (PM store, rings, per-pattern counters, obs
    matrices) concatenates along the pattern axis; scalar counters sum;
    clocks take the slowest lane (``max``, mirroring the sharded engine's
    pmax semantics in repro.dist); the latency ring keeps per-slot global
    PM counts (sum) against the slowest lane's per-event time (max).
    """
    def _flat(x):  # (L, P, ...) -> (L·P, ...)
        x = jnp.moveaxis(x, axis, 0)
        return x.reshape((-1,) + x.shape[2:])

    pms = PMStore(*[_flat(x) for x in stacked.pms])
    if jax.tree.leaves(stacked)[0].shape[axis] == 0:
        # Zero-lane merge: the flattened pattern state is (0, ...) and
        # every folded scalar takes its reduction identity (max over no
        # lanes = the zero clock) instead of tripping the empty-axis
        # reduction error.
        zero = lambda x: jnp.zeros(                      # noqa: E731
            x.shape[:axis] + x.shape[axis + 1:], x.dtype)
        mx = sm = first = zero
    else:
        mx = lambda x: x.max(axis=axis)          # noqa: E731
        sm = lambda x: x.sum(axis=axis)          # noqa: E731
        first = lambda x: jnp.take(x, 0, axis=axis)  # noqa: E731
    return Carry(
        pms=pms, ring=_flat(stacked.ring), ring_ptr=_flat(stacked.ring_ptr),
        sim_time=mx(stacked.sim_time), key=first(stacked.key),
        ebl_frac=mx(stacked.ebl_frac), ema_gap=mx(stacked.ema_gap),
        prev_arrival=mx(stacked.prev_arrival),
        complex_count=_flat(stacked.complex_count),
        pms_created=_flat(stacked.pms_created),
        pms_shed=sm(stacked.pms_shed), shed_calls=sm(stacked.shed_calls),
        overflow=sm(stacked.overflow), ebl_dropped=sm(stacked.ebl_dropped),
        obs_counts=_flat(stacked.obs_counts),
        obs_rewards=_flat(stacked.obs_rewards),
        lat_samples_n=sm(stacked.lat_samples_n),
        lat_samples_l=mx(stacked.lat_samples_l),
        lat_ptr=mx(stacked.lat_ptr),
    )


# ---------------------------------------------------------------------------
# Durable-state manifest (repro.runtime.persist)
# ---------------------------------------------------------------------------

def pytree_manifest(tree) -> list[dict]:
    """Leaf schema of a pytree in ``jax.tree_util`` flatten order:
    ``[{"path", "dtype", "shape"}, ...]``.

    This is the validation half of the durable snapshot codec
    (``repro.runtime.persist``): a snapshot records the manifest it was
    written with, and a restore only proceeds when it matches the live
    tree's manifest — a mismatch means the snapshot belongs to a
    different config (shapes) or code version (structure) and must be
    surfaced, never coerced.
    """
    out = []
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        arr = np.asarray(leaf)
        out.append({"path": jax.tree_util.keystr(path),
                    "dtype": arr.dtype.str, "shape": list(arr.shape)})
    return out


def carry_manifest(cfg: EngineConfig, seed: int = 0,
                   lat_capacity: int = 4096) -> list[dict]:
    """The manifest any durable snapshot of this config's carry must
    match (``init_carry`` shapes are a pure function of the config)."""
    return pytree_manifest(init_carry(cfg, seed=seed,
                                      lat_capacity=lat_capacity))


# ---------------------------------------------------------------------------
# Results summary
# ---------------------------------------------------------------------------

def match_sets(outs: StepOut, start: int = 0) -> list[set[tuple]]:
    """Decode emitted matches into per-pattern sets of match identities.

    A match is the tuple ``(open_idx, bind, end_idx)`` — the completing
    PM's window-open event index, its binding value, and the global index
    of the completing event.  One PM exists per such identity (spawn
    dedupes on (open_idx, bind)), so the match multiset IS a set; this is
    the equality ``repro.eval`` uses for differential and metamorphic
    testing (DESIGN.md §9).  Requires ``cfg.emit_matches``; ``start`` is
    the global index of the first event (chunked runs pass their chunk
    start and union the per-chunk sets).
    """
    m_open = np.asarray(outs.match_open)         # (n, P, N)
    m_bind = np.asarray(outs.match_bind)
    if m_open.ndim != 3 or m_open.shape[-1] == 0:
        raise ValueError("run had cfg.emit_matches off — no match identity "
                         "was emitted (match fields are zero-width)")
    n, P, _ = m_open.shape
    out: list[set[tuple]] = [set() for _ in range(P)]
    ev, p, slot = np.nonzero(m_open >= 0)
    for e, q, s in zip(ev.tolist(), p.tolist(), slot.tolist()):
        out[q].add((int(m_open[e, q, s]), int(m_bind[e, q, s]),
                    start + e))
    return out


@dataclasses.dataclass
class RunResult:
    complex_count: np.ndarray   # (P,)
    pms_created: np.ndarray     # (P,)
    pms_shed: float
    shed_calls: float
    overflow: float
    ebl_dropped: float
    l_e: np.ndarray             # (n,)
    n_pm: np.ndarray            # (n,)
    carry: Carry
    # Per-pattern match-identity sets (cfg.emit_matches runs; else None).
    matches: list | None = None

    @property
    def match_probability(self) -> np.ndarray:
        return self.complex_count / np.maximum(self.pms_created, 1.0)

    def false_negatives(self, ground_truth: "RunResult",
                        weights: np.ndarray | None = None) -> float:
        """Weighted FN fraction vs a no-shed run on the same stream (§II-B)."""
        gt = np.maximum(ground_truth.complex_count, 1e-9)
        fn = np.maximum(gt - self.complex_count, 0.0)
        w = np.ones_like(gt) if weights is None else np.asarray(weights)
        return float((w * fn).sum() / (w * gt).sum())


def summarize(carry: Carry, outs: StepOut) -> RunResult:
    emitted = np.asarray(outs.match_open).ndim == 3 and \
        outs.match_open.shape[-1] > 0
    return RunResult(
        complex_count=np.asarray(carry.complex_count),
        pms_created=np.asarray(carry.pms_created),
        pms_shed=float(carry.pms_shed),
        shed_calls=float(carry.shed_calls),
        overflow=float(carry.overflow),
        ebl_dropped=float(carry.ebl_dropped),
        l_e=np.asarray(outs.l_e),
        n_pm=np.asarray(outs.n_pm),
        carry=carry,
        matches=match_sets(outs) if emitted else None,
    )
