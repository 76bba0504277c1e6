"""Online model refresh between chunks (paper §III-C/§III-D; DESIGN.md §7).

The monolithic runner builds the Markov/utility model once from a warm-up
run.  A continuously running operator must keep adapting: stream statistics
drift, so the transition matrices — and with them the completion
probabilities, remaining-time tables and the latency regression ``f`` —
go stale.  Chunk boundaries give the host a natural cadence: the engine's
carry already accumulates ``obs_counts`` / ``obs_rewards`` (when
``gather_stats`` is on) and the ``(n_pm, t_proc)`` latency ring, so a
refresh is a pure re-estimation from the carry, no extra stream pass.

Refreshes are gated twice: a minimum observation count (don't fit noise)
and an optional drift threshold on the transition-matrix MSE between the
deployed and freshly-estimated chains (``markov.needs_retraining``, §III-D)
so stable streams skip the rebuild cost.
"""
from __future__ import annotations

import dataclasses
from typing import Sequence

import jax.numpy as jnp
import numpy as np

from repro.cep import engine as eng
from repro.cep import patterns as pat
from repro.core import markov, overload as ovl, utility as util
from repro.runtime import telemetry as TM


@dataclasses.dataclass(frozen=True)
class RefreshConfig:
    every_chunks: int = 4          # cadence; <= 0 disables refresh
    min_observations: float = 256.0  # total transition obs before first fit
    drift_threshold: float = 0.0   # max per-pattern T-MSE gate; 0 = always
    bin_size: int = 64
    use_remaining_time: bool = True
    refit_latency: bool = True     # refit f from the carry's latency ring
    decay: float = 1.0             # obs decay applied after each refresh
                                   # (<1 = exponential forgetting, so the
                                   # model tracks drift instead of the
                                   # all-time average)


@dataclasses.dataclass
class RefreshState:
    """What the refresher remembers between invocations."""
    last_T: np.ndarray | None = None   # (P, M, M) deployed transition chains
    refresh_count: int = 0
    skipped_drift: int = 0
    skipped_obs: int = 0
    skipped_nonfinite: int = 0   # NaN-safe gate fired (DESIGN.md §12)

    def to_control(self) -> dict:
        """JSON control form for the durable snapshot codec.  float32 →
        Python float → float32 is exact (repr round-trip), so the drift
        gate computes the same MSE after recovery."""
        lt = None
        if self.last_T is not None:
            lt = {"dtype": self.last_T.dtype.str,
                  "shape": list(self.last_T.shape),
                  "data": self.last_T.reshape(-1).tolist()}
        return {"last_T": lt, "refresh_count": self.refresh_count,
                "skipped_drift": self.skipped_drift,
                "skipped_obs": self.skipped_obs,
                "skipped_nonfinite": self.skipped_nonfinite}

    @classmethod
    def from_control(cls, d: dict) -> "RefreshState":
        lt = d["last_T"]
        arr = None if lt is None else np.asarray(
            lt["data"], dtype=np.dtype(lt["dtype"])).reshape(lt["shape"])
        return cls(last_T=arr,
                   refresh_count=int(d["refresh_count"]),
                   skipped_drift=int(d["skipped_drift"]),
                   skipped_obs=int(d["skipped_obs"]),
                   skipped_nonfinite=int(d["skipped_nonfinite"]))


def table_width(specs: Sequence[pat.PatternSpec], bin_size: int) -> int:
    """Bins a refreshed utility table will occupy: max ceil(ws/bs)."""
    return max(1, max(-(-s.window_size // bin_size) for s in specs))


def prepare_model(specs: Sequence[pat.PatternSpec], model: eng.EngineModel,
                  rcfg: RefreshConfig) -> eng.EngineModel:
    """Pre-widen ``ut_tables`` to the width refresh will produce.

    A refresh must never change the model pytree's shapes — that would
    retrace the chunk executable mid-stream (seconds of compile hidden in
    a steady-state loop).  Widening up front (edge-replicated bins, a
    no-op for lookups) keeps every post-refresh chunk on the original
    executable.  Works on single and lane-stacked models (the bin axis is
    always second-to-last).
    """
    width = table_width(specs, rcfg.bin_size)
    cur = model.ut_tables.shape[-2]
    if cur >= width:
        return model
    pad = [(0, 0)] * model.ut_tables.ndim
    pad[-2] = (0, width - cur)
    return model._replace(ut_tables=jnp.pad(model.ut_tables, pad,
                                            mode="edge"))


def estimate_chains(specs: Sequence[pat.PatternSpec], cfg: eng.EngineConfig,
                    obs_counts, obs_rewards):
    """Per-pattern (T, R) from the carry's accumulated observations."""
    Ts, Rs = [], []
    for p, spec in enumerate(specs):
        m = spec.num_states
        stats = markov.TransitionStats(
            counts=jnp.asarray(obs_counts[p, :m, :m]),
            reward_sum=jnp.asarray(obs_rewards[p, :m, :m]))
        Ts.append(markov.estimate_transition_matrix(stats))
        Rs.append(markov.estimate_reward_matrix(
            stats, default_reward=cfg.c_match * float(spec.proc_cost)))
    return Ts, Rs


def _stack_T(Ts, max_states: int) -> np.ndarray:
    out = np.zeros((len(Ts), max_states, max_states), np.float32)
    for p, T in enumerate(Ts):
        m = T.shape[0]
        out[p, :m, :m] = np.asarray(T)
    return out


def refit_latency_model(carry: eng.Carry) -> ovl.LatencyModel:
    """Refit f: n_pm -> l_p from the carry's rolling latency ring.

    ``lat_ptr`` increments once per event and, on a multi-billion-event
    stream, wraps negative (int32); by then the ring has long been full,
    so a wrapped pointer means every slot is valid — without the guard
    the mask would go all-zero and the fit would degenerate.
    """
    S = carry.lat_samples_n.shape[0]
    n_valid = jnp.where(carry.lat_ptr < 0, S,
                        jnp.minimum(carry.lat_ptr, S))
    valid = jnp.arange(S) < n_valid
    return ovl.fit_latency_model(carry.lat_samples_n, carry.lat_samples_l,
                                 valid)


def refresh_model(specs: Sequence[pat.PatternSpec], cfg: eng.EngineConfig,
                  model: eng.EngineModel, carry: eng.Carry,
                  rcfg: RefreshConfig, state: RefreshState,
                  telemetry: TM.TelemetryLog,
                  ) -> tuple[eng.EngineModel, eng.Carry, bool]:
    """Re-estimate the utility tables (+ latency model) from the carry.

    Returns ``(model, carry, refreshed)``; the carry comes back with its
    observation accumulators decayed by ``rcfg.decay`` when a refresh ran.
    Mutates ``state`` (refresh/skip counters, deployed chains).  Its three
    phases are spans of ``telemetry``: ``rt.refresh.obs`` (observations
    to the host, gates), ``rt.refresh.fit`` (chains, drift test, tables,
    latency refit) and ``rt.refresh.deploy``.
    """
    with telemetry.span("rt.refresh.obs"):
        ready = _gate(carry, rcfg, state)
    if not ready:
        return model, carry, False
    with telemetry.span("rt.refresh.fit"):
        fit = _fit(specs, cfg, model, carry, rcfg, state)
    if fit is None:
        return model, carry, False
    fresh, ut_stacked, ut_bins, f_model = fit
    with telemetry.span("rt.refresh.deploy"):
        model = model._replace(ut_tables=ut_stacked, ut_bins=ut_bins,
                               f_model=f_model)
        if rcfg.decay < 1.0:
            carry = carry._replace(obs_counts=carry.obs_counts * rcfg.decay,
                                   obs_rewards=carry.obs_rewards * rcfg.decay)
    state.last_T = fresh
    state.refresh_count += 1
    return model, carry, True


def _gate(carry: eng.Carry, rcfg: RefreshConfig,
          state: RefreshState) -> bool:
    """The carry's observations read on the host: False where they are
    non-finite or too few, and the refresh is skipped."""
    # NaN-safe gate (DESIGN.md §12): a poisoned accumulator must SKIP the
    # refresh, not deploy corrupt tables.  Note `nan < threshold` is False
    # — the min-observation gate alone would wave NaNs straight through.
    obs_c = np.asarray(carry.obs_counts)
    obs_r = np.asarray(carry.obs_rewards)
    if not (np.isfinite(obs_c).all() and np.isfinite(obs_r).all()):
        state.skipped_nonfinite += 1
        return False
    if float(obs_c.sum()) < rcfg.min_observations:
        state.skipped_obs += 1
        return False
    return True


def _fit(specs, cfg, model, carry, rcfg, state):
    """(chains, tables, bins, latency model) fitted from the observations,
    or None where the drift test or a non-finite product skips the
    refresh."""
    Ts, Rs = estimate_chains(specs, cfg, carry.obs_counts, carry.obs_rewards)
    fresh = _stack_T(Ts, cfg.max_states)
    if rcfg.drift_threshold > 0 and state.last_T is not None:
        mse = float(max(
            markov.transition_matrix_mse(jnp.asarray(state.last_T[p]),
                                         jnp.asarray(fresh[p]))
            for p in range(len(specs))))
        if mse <= rcfg.drift_threshold:
            state.skipped_drift += 1
            return None

    tables = [util.build_utility_table(
        T, R, window_size=spec.window_size, bin_size=rcfg.bin_size,
        weight=spec.weight, use_remaining_time=rcfg.use_remaining_time)
        for spec, T, R in zip(specs, Ts, Rs)]
    ut_stacked, ut_bins = util.stack_tables(tables,
                                            max_states=cfg.max_states)
    # stack_tables may widen the bin axis vs the deployed model; keep the
    # deployed width so the EngineModel pytree structure (and the compiled
    # chunk executable) never changes mid-stream.
    B = model.ut_tables.shape[1]
    if ut_stacked.shape[1] < B:
        ut_stacked = jnp.pad(
            ut_stacked, ((0, 0), (0, B - ut_stacked.shape[1]), (0, 0)))
    elif ut_stacked.shape[1] > B:
        ut_stacked = ut_stacked[:, :B]
    # Same NaN discipline for the freshly built tables and the latency
    # refit: a non-finite product (e.g. an Inf-polluted latency ring that
    # degenerates the regression) keeps the deployed model.
    if not np.isfinite(np.asarray(ut_stacked)).all():
        state.skipped_nonfinite += 1
        return None
    f_model = model.f_model
    if rcfg.refit_latency:
        cand = refit_latency_model(carry)
        if bool(np.isfinite(np.asarray(cand.a)).all()
                and np.isfinite(np.asarray(cand.b)).all()):
            f_model = cand
        else:
            state.skipped_nonfinite += 1
    return fresh, ut_stacked, ut_bins, f_model
